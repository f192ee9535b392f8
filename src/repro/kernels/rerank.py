"""Pallas TPU kernels: tiled exact-distance matrix + pooled gather-rerank.

Paper hot spot: Stage B computes exact distances between each query and its
oversampled candidate set ("computes exact distances", §6), and the build
path computes full-precision distances during robust-prune.  This is a dense
(Q, D) × (N, D) problem — ideal MXU work.

``rerank_distances_pallas`` computes squared-L2 via the expanded form

    dist = |q|^2 - 2 q·x + |x|^2

with the cross term as a (TILE_Q × D) @ (D × TILE_N) matmul and the norms
reduced in-kernel, or negative inner product for ``metric="ip"``.

``gather_rerank_pallas`` is the on-device replacement for the executor's
old host rerank of a per-query candidate pool (NumPy ``vectors[pids]``
gather + einsum): each query row carries P candidate ids into the point
matrix, and the kernel scores exactly those candidates at full precision
with an in-kernel top-k, never materializing the (Q, P, D) gathered tensor
on the host.  The gather itself is reformulated as a one-hot selection —
but applied to the SCORE tile, not the vector tile: per N-tile the kernel
computes the dense (TILE_Q, TILE_N) distance tile it needs anyway (MXU
matmul), builds the (TILE_Q, P, TILE_N) one-hot of ``pool_ids == global
row id``, and contracts it against the score tile into a (TILE_Q, P)
VMEM scratch accumulator.  Selecting scores instead of vectors cuts the
one-hot contraction from O(P·N·D) to O(P·N) FLOPs and shrinks the scratch
from (TILE_Q·P, D) to (TILE_Q, P) — at D=4096, P=256 that is 32 MB (over
budget) down to 8 KB.  Each pool id lives in exactly one N tile, so the
sum over tiles recovers its score exactly.  On the last N step the
accumulated pool scores (sentinel ids < 0 forced to the MASKED sentinel)
run the shared k-step top-k extraction, emitting the same ascending
(MASKED, -1)-sentinel rows as the masked kernels.

VMEM per grid step (TILE_Q=128, TILE_N=128, D≤4096, f32), rerank kernel:
  q tile 128×4096×4 ≈ 2 MB, x tile 128×4096×4 ≈ 2 MB, out 64 KB  → ~4.1 MB.
gather-rerank kernel (TILE_Q=8, TILE_N=128, P≤1024, D≤4096):
  q tile 128 KB, x tile 2 MB, pids 8×1024×4 = 32 KB, scratch 8×1024×4 =
  32 KB, one-hot intermediate 8×1024×128×4 ≈ 4 MB, outputs 2×8×k×4 —
  ~6.2 MB, comfortably under the 16 MB budget.
D is padded to a multiple of 128 by the wrapper so the contraction is
MXU-aligned; zero-padding the feature dim changes neither L2 nor IP.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.masked_topk import MASKED, _topk_merge


def _rerank_kernel(q_ref, x_ref, out_ref, *, metric: str):
    q = q_ref[...]  # (TILE_Q, D)
    x = x_ref[...]  # (TILE_N, D)
    cross = jax.lax.dot_general(
        q, x, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (TILE_Q, TILE_N)
    if metric == "l2":
        q2 = jnp.sum(q * q, axis=-1, keepdims=True)  # (TILE_Q, 1)
        x2 = jnp.sum(x * x, axis=-1)[None, :]  # (1, TILE_N)
        out_ref[...] = q2 - 2.0 * cross + x2
    else:  # ip
        out_ref[...] = -cross


@functools.partial(
    jax.jit, static_argnames=("metric", "tile_q", "tile_n", "interpret")
)
def rerank_distances_pallas(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    *,
    metric: str = "l2",
    tile_q: int = 128,
    tile_n: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Exact distance matrix (Q, N).  Q, N, D must be tile-aligned
    (the ops.py wrapper pads)."""
    q, d = queries.shape
    n, d2 = points.shape
    assert d == d2, (d, d2)
    assert q % tile_q == 0 and n % tile_n == 0, (q, n)
    grid = (q // tile_q, n // tile_n)
    return pl.pallas_call(
        functools.partial(_rerank_kernel, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q, n), jnp.float32),
        interpret=interpret,
    )(queries.astype(jnp.float32), points.astype(jnp.float32))


def _gather_rerank_kernel(
    q_ref, x_ref, pid_ref, od_ref, oi_ref, acc_ref, *, metric, k, tile_n, n_tiles
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        od_ref[...] = jnp.full(od_ref.shape, MASKED, jnp.float32)
        oi_ref[...] = jnp.full(oi_ref.shape, -1, jnp.int32)

    q = q_ref[...]  # (TILE_Q, D)
    x = x_ref[...]  # (TILE_N, D)
    pids = pid_ref[...]  # (TILE_Q, P) int32; < 0 = sentinel slot
    cross = jax.lax.dot_general(
        q, x, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (TILE_Q, TILE_N)
    if metric == "l2":
        q2 = jnp.sum(q * q, axis=-1, keepdims=True)
        x2 = jnp.sum(x * x, axis=-1)[None, :]
        d = q2 - 2.0 * cross + x2
    else:  # ip
        d = -cross
    tq, tn = d.shape
    ids_tile = j * tile_n + jax.lax.broadcasted_iota(jnp.int32, (tn,), 0)
    # one-hot of "pool slot (q, p) lives in this tile's column c" — applied
    # to the score tile, not the vectors (see module docstring)
    onehot = (pids[:, :, None] == ids_tile[None, None, :]).astype(jnp.float32)
    # (TILE_Q, P, TILE_N) × (TILE_Q, TILE_N) -> (TILE_Q, P), batched over q
    contrib = jax.lax.dot_general(
        onehot, d, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    acc_ref[...] += contrib

    @pl.when(j == n_tiles - 1)
    def _finish():
        pool_d = jnp.where(pids < 0, MASKED, acc_ref[...])
        od, oi = _topk_merge(pool_d, pids, k)
        od_ref[...] = od
        oi_ref[...] = oi


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "tile_q", "tile_n", "interpret")
)
def gather_rerank_pallas(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    pool_ids: jnp.ndarray,
    k: int,
    *,
    metric: str = "l2",
    tile_q: int = 8,
    tile_n: int = 128,
    interpret: bool = False,
):
    """Pooled gather-rerank.  queries (Q, D) f32, points (N, D) f32,
    pool_ids (Q, P) int32 (slots < 0 are sentinels and stay (MASKED, -1);
    live ids must be in [0, N)).  Q, N, D must be tile-aligned and P a
    multiple of 128 — the ops.py wrapper pads (pid padding is -1, so padded
    slots never win).  Returns (dists (Q, k) f32 with MASKED sentinels, ids
    (Q, k) int32 with -1 sentinels), each row ascending; ``k`` may exceed
    P."""
    q, d = queries.shape
    n, d2 = points.shape
    assert d == d2, (d, d2)
    q2, p = pool_ids.shape
    assert q2 == q, (pool_ids.shape, q)
    assert q % tile_q == 0 and n % tile_n == 0, (q, n, tile_q, tile_n)
    grid = (q // tile_q, n // tile_n)
    return pl.pallas_call(
        functools.partial(
            _gather_rerank_kernel,
            metric=metric, k=k, tile_n=tile_n, n_tiles=grid[1],
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((tile_q, p), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, k), jnp.float32),
            jax.ShapeDtypeStruct((q, k), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((tile_q, p), jnp.float32)],
        interpret=interpret,
    )(
        queries.astype(jnp.float32),
        points.astype(jnp.float32),
        pool_ids.astype(jnp.int32),
    )
