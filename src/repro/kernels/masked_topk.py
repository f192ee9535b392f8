"""Pallas TPU kernels: mask-aware distance scan with in-kernel top-k.

Filtered probes (attribute predicates, paper §6 + PR 2) previously faked
predicate awareness on the executor: the "mask" plan widened the beam pool
by 1/selectivity and filtered in NumPy afterwards, and the pre-filter exact
scan was a host-side gather.  Both burn compute that a predicate-aware
kernel avoids — the executor-side distance-compute bottleneck SHINE
(arXiv:2507.17647) identifies as the scaling limiter.  These kernels fuse
the per-row predicate/tombstone bitmask into the distance computation
itself: masked-out rows are forced to a ``+inf`` sentinel inside the tile,
and a per-tile top-k reduction keeps only ``k`` survivors per grid step, so
a filtered Stage A is ONE kernel call over (queries × shard rows) with no
pool widening and no post-hoc filtering.

Two scoring flavors share the reduction:

- ``masked_exact_topk_pallas`` — f32 points, squared-L2 / negative-IP via
  the expanded-form matmul (same tiling as the rerank kernel);
- ``masked_pq_topk_pallas``    — PQ-ADC scores via the one-hot matmul
  reformulation of the LUT gather (same trick as ``pq_scan``), with the
  mask fused into the accumulation.

Each flavor also has a **multi-mask** variant (``*_multi_pallas``) whose
mask input is a per-query plane ``(Q, N)`` instead of a shared row
``(1, N)``: tile ``(i, j)`` of the plane rides into grid step ``(i, j)``
alongside the query and point tiles, so a coalesced batch whose queries
carry HETEROGENEOUS predicates is still ONE kernel call — each query's
rows are forced to +inf under its own bitmask before the shared top-k
reduction.  The kernel bodies are identical (``jnp.where(m > 0.5, ...)``
broadcasts a ``(1, TILE_N)`` row and applies a ``(TILE_Q, TILE_N)`` plane
elementwise); only the mask BlockSpec differs.

``unified_masked_topk_pallas`` fuses BOTH scoring flavors into one
dispatch: a fragment whose queries split between exact-flavor and
PQ-ADC-flavor plans (mixed selectivities on a PQ shard) used to cost two
kernel calls per shard — one per flavor.  The unified kernel takes the
exact inputs (queries × points) AND the ADC inputs (LUTs × codes) plus a
**selector plane** ``(Q, N)`` that encodes the per-query mask and flavor
in one f32 value per cell: 0 = masked out, 1 = score full-precision,
2 = score ADC.  Each grid step computes both score tiles and selects per
row before the shared top-k reduction, so the whole mixed-flavor fragment
is ONE dispatch.  (Compute per tile doubles, but at shard scale the
dispatch/transfer overhead dominates the filtered path; the
``table2.filtered_mixed_flavor`` bench row gates one dispatch per shard.)

The exact flavor also scores in reduced precision when asked: the same
kernel body runs on **bf16** inputs (MXU bf16 rate, f32 accumulation via
``preferred_element_type``; norms are upcast before squaring so only the
VALUES are low-precision), and a dedicated **int8** kernel scores
symmetric per-tensor int8 points/queries with int32 accumulation and a
``(1, 2)`` f32 scale input ``[q_scale, x_scale]`` folded in after the
matmul.  Quantized scores carry value error — the ops/executor layers
restore recall by feeding the surviving pool through the full-precision
``gather_rerank`` guard (kernels/rerank.py).

Accumulation pattern: grid ``(Q_tiles, N_tiles)`` with the N axis
innermost; the output BlockSpecs pin ``(i, 0)`` so the same ``(TILE_Q, k)``
distance/id accumulator blocks stay resident in VMEM across the whole N
sweep (the standard Pallas revisiting-reduction idiom — TPU grids execute
sequentially, last axis fastest).  Each step merges the incoming tile's
masked distances into the running top-k with a k-step argmin-extraction
loop built from iota / where / min only — no per-lane gathers, so it
lowers to pure VPU work; the candidate matmul is MXU work.

The unified kernel computes both flavors into ONE shared ``(TILE_Q,
TILE_N)`` score buffer (VMEM scratch) selected per row, instead of two
resident score planes: exact scores land first (ADC rows zeroed), then the
ADC contribution accumulates per subquantizer chunk — the one-hot LUT
selection is built ``(TILE_N, K)`` per subquantizer, never the full
``(TILE_N, m, K)`` tensor.  At m=16, K=256, TILE_N=128 that shrinks the
largest transient from 2 MB to 128 KB and drops one resident plane.

VMEM per grid step — resident blocks (the BlockSpec-walked budget;
see :func:`unified_block_shapes` / :func:`unified_vmem_bytes`, asserted by
tests/test_kernels.py), worst case D=4096, TILE_Q=8, TILE_N=128, m=16,
K=256, k=128:

  flavor    blocks (f32 unless noted)                              resident
  exact     q 8×4096 (128 KB) + x 128×4096 (2 MB) + mask 0.5 KB
            + out 2×8×k                                            ~2.1 MB
  exact/bf16  same blocks at 2 bytes for q and x                   ~1.1 MB
  exact/int8  same blocks at 1 byte for q and x + (1,2) scale      ~0.6 MB
  pq-adc    lut 8×16×256 (128 KB) + codes 128×16 int32 (8 KB)
            + mask + out                                           ~0.2 MB
  unified   q + x + lut + codes + selector 8×128 (4 KB)
            + out + score scratch 8×128 (4 KB)                     ~2.3 MB

Double-buffered inputs (×2) plus the largest transient (the (TILE_N, K)
one-hot chunk, 128 KB) keep the unified worst case at ~4.8 MB — D=4096
fits the 16 MB/core budget with TILE_Q=8 un-halved.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# sentinel for masked-out / empty slots: large f32 that real (squared-L2 or
# negative-IP) scores never reach; converted to +inf by the ops.py wrapper.
# Plain Python floats — jnp scalars would be captured as kernel constants.
MASKED = 3.0e38
MASKED_THRESHOLD = 1.0e38  # scores >= this are "no candidate"


def _topk_merge(cat_d: jnp.ndarray, cat_i: jnp.ndarray, k: int):
    """(TQ, W) masked scores + ids -> ascending (TQ, k) top-k of each row.

    k-step selection: each step one-hot-extracts the row argmin (iota ==
    argmin — no gather), records it into output column ``s`` via an iota
    mask, and overwrites the extracted slot with the sentinel.  Slots whose
    score is the sentinel report id -1.
    """
    tq, w = cat_d.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (tq, w), 1)
    out_col = jax.lax.broadcasted_iota(jnp.int32, (tq, k), 1)

    def body(s, carry):
        cd, od, oi = carry
        pick = jnp.argmin(cd, axis=1)  # (TQ,)
        val = jnp.min(cd, axis=1)  # (TQ,)
        sel = col == pick[:, None]  # one-hot (TQ, W)
        pid = jnp.sum(jnp.where(sel, cat_i, 0), axis=1)  # picked id per row
        pid = jnp.where(val < MASKED_THRESHOLD, pid, -1)
        od = jnp.where(out_col == s, val[:, None], od)
        oi = jnp.where(out_col == s, pid[:, None], oi)
        cd = jnp.where(sel, MASKED, cd)
        return cd, od, oi

    od = jnp.full((tq, k), MASKED, jnp.float32)
    oi = jnp.full((tq, k), -1, jnp.int32)
    _, od, oi = jax.lax.fori_loop(0, k, body, (cat_d, od, oi))
    return od, oi


def _merge_tile(d, j, tile_n, od_ref, oi_ref, k):
    """Shared epilogue: mask'd tile scores ``d`` + running accumulators ->
    updated accumulators."""
    tq, tn = d.shape
    ids = j * tile_n + jax.lax.broadcasted_iota(jnp.int32, (tq, tn), 1)
    cat_d = jnp.concatenate([od_ref[...], d], axis=1)
    cat_i = jnp.concatenate([oi_ref[...], ids], axis=1)
    od, oi = _topk_merge(cat_d, cat_i, k)
    od_ref[...] = od
    oi_ref[...] = oi


def _masked_exact_kernel(q_ref, x_ref, m_ref, od_ref, oi_ref, *, metric, k, tile_n):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        od_ref[...] = jnp.full(od_ref.shape, MASKED, jnp.float32)
        oi_ref[...] = jnp.full(oi_ref.shape, -1, jnp.int32)

    q = q_ref[...]  # (TILE_Q, D) f32 or bf16
    x = x_ref[...]  # (TILE_N, D) f32 or bf16
    m = m_ref[...]  # (1, TILE_N) f32, 1.0 = live
    cross = jax.lax.dot_general(
        q, x, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        # f32 at full precision (Mosaic's default is one bf16 pass, a
        # percent-level distance error); bf16 products are exact anyway
        precision=jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None,
    )  # (TILE_Q, TILE_N); bf16 inputs run the MXU at bf16 rate, f32 accum
    if metric == "l2":
        # norms upcast first: only the VALUES are reduced precision
        qf = q.astype(jnp.float32)
        xf = x.astype(jnp.float32)
        q2 = jnp.sum(qf * qf, axis=-1, keepdims=True)
        x2 = jnp.sum(xf * xf, axis=-1)[None, :]
        d = q2 - 2.0 * cross + x2
    else:  # ip
        d = -cross
    d = jnp.where(m > 0.5, d, MASKED)  # mask fused before the reduction
    _merge_tile(d, j, tile_n, od_ref, oi_ref, k)


def _masked_exact_q_kernel(
    q_ref, x_ref, s_ref, m_ref, od_ref, oi_ref, *, metric, k, tile_n
):
    """int8 scoring variant: int8 × int8 matmul with int32 accumulation,
    symmetric per-tensor scales ``s_ref = [[q_scale, x_scale]]`` folded in
    after the contraction."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        od_ref[...] = jnp.full(od_ref.shape, MASKED, jnp.float32)
        oi_ref[...] = jnp.full(oi_ref.shape, -1, jnp.int32)

    q = q_ref[...]  # (TILE_Q, D) int8
    x = x_ref[...]  # (TILE_N, D) int8
    s = s_ref[...]  # (1, 2) f32
    m = m_ref[...]  # (1, TILE_N) f32
    sq, sx = s[0, 0], s[0, 1]
    cross_i = jax.lax.dot_general(
        q, x, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    cross = cross_i.astype(jnp.float32) * (sq * sx)
    if metric == "l2":
        qf = q.astype(jnp.float32) * sq
        xf = x.astype(jnp.float32) * sx
        q2 = jnp.sum(qf * qf, axis=-1, keepdims=True)
        x2 = jnp.sum(xf * xf, axis=-1)[None, :]
        d = q2 - 2.0 * cross + x2
    else:  # ip
        d = -cross
    d = jnp.where(m > 0.5, d, MASKED)
    _merge_tile(d, j, tile_n, od_ref, oi_ref, k)


def _masked_pq_kernel(lut_ref, codes_ref, m_ref, od_ref, oi_ref, *, K, k, tile_n):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        od_ref[...] = jnp.full(od_ref.shape, MASKED, jnp.float32)
        oi_ref[...] = jnp.full(oi_ref.shape, -1, jnp.int32)

    lut = lut_ref[...]  # (TILE_Q, m, K)
    codes = codes_ref[...]  # (TILE_N, m)
    m_mask = m_ref[...]  # (1, TILE_N)
    tile_q, m_sub, _ = lut.shape
    tn = codes.shape[0]
    # ADC gather as a one-hot matmul (MXU-rate; see pq_scan.py)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (tn, m_sub, K), 2)
    onehot = (codes[:, :, None] == iota_k).astype(jnp.float32)
    d = jax.lax.dot_general(
        lut.reshape(tile_q, m_sub * K),
        onehot.reshape(tn, m_sub * K),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (TILE_Q, TILE_N)
    d = jnp.where(m_mask > 0.5, d, MASKED)
    _merge_tile(d, j, tile_n, od_ref, oi_ref, k)


def _exact_call_dtype(points: jnp.ndarray) -> jnp.dtype:
    """Scoring dtype the exact kernels run at, decided by the point matrix:
    int8 and bf16 stay put (reduced-precision scoring), anything else is
    coerced to f32."""
    if points.dtype in (jnp.int8, jnp.bfloat16):
        return points.dtype
    return jnp.dtype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "tile_q", "tile_n", "interpret")
)
def masked_exact_topk_pallas(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    mask: jnp.ndarray,
    k: int,
    *,
    metric: str = "l2",
    tile_q: int = 8,
    tile_n: int = 128,
    interpret: bool = False,
    scales: jnp.ndarray | None = None,
):
    """Masked exact top-k.  queries (Q, D), points (N, D), mask (1, N) f32
    (1.0 = row may win).  Q, N, D must be tile-aligned — the ops.py wrapper
    pads (padded rows carry mask 0, so they never win).  The scoring dtype
    follows ``points``: f32 (default), bf16, or int8 — int8 requires
    ``scales`` (1, 2) f32 ``[[q_scale, x_scale]]`` and int8 queries.
    Returns (dists (Q, k) f32 with MASKED sentinels, ids (Q, k) int32 with
    -1 sentinels), each row ascending."""
    q, d = queries.shape
    n, d2 = points.shape
    assert d == d2, (d, d2)
    assert q % tile_q == 0 and n % tile_n == 0, (q, n, tile_q, tile_n)
    assert mask.shape == (1, n), (mask.shape, n)
    grid = (q // tile_q, n // tile_n)
    out_specs = [
        pl.BlockSpec((tile_q, k), lambda i, j: (i, 0)),
        pl.BlockSpec((tile_q, k), lambda i, j: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((q, k), jnp.float32),
        jax.ShapeDtypeStruct((q, k), jnp.int32),
    ]
    dt = _exact_call_dtype(points)
    if dt == jnp.int8:
        assert scales is not None, "int8 scoring requires scales (1, 2) f32"
        assert queries.dtype == jnp.int8, queries.dtype
        return pl.pallas_call(
            functools.partial(
                _masked_exact_q_kernel, metric=metric, k=k, tile_n=tile_n
            ),
            grid=grid,
            in_specs=[
                pl.BlockSpec((tile_q, d), lambda i, j: (i, 0)),
                pl.BlockSpec((tile_n, d), lambda i, j: (j, 0)),
                pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
                pl.BlockSpec((1, tile_n), lambda i, j: (0, j)),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
        )(queries, points, scales.astype(jnp.float32), mask.astype(jnp.float32))
    return pl.pallas_call(
        functools.partial(_masked_exact_kernel, metric=metric, k=k, tile_n=tile_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, tile_n), lambda i, j: (0, j)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(queries.astype(dt), points.astype(dt), mask.astype(jnp.float32))


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "tile_q", "tile_n", "interpret")
)
def masked_exact_topk_multi_pallas(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    masks: jnp.ndarray,
    k: int,
    *,
    metric: str = "l2",
    tile_q: int = 8,
    tile_n: int = 128,
    interpret: bool = False,
    scales: jnp.ndarray | None = None,
):
    """Per-query-mask exact top-k.  queries (Q, D), points (N, D),
    masks (Q, N) f32 (row q is query q's bitmask; 1.0 = row may win).  Same
    alignment, scoring-dtype dispatch, and (MASKED, -1) sentinel contract as
    :func:`masked_exact_topk_pallas`; the kernel bodies are shared — only
    the mask BlockSpec changes from a broadcast row to a (i, j) plane
    tile."""
    q, d = queries.shape
    n, d2 = points.shape
    assert d == d2, (d, d2)
    assert q % tile_q == 0 and n % tile_n == 0, (q, n, tile_q, tile_n)
    assert masks.shape == (q, n), (masks.shape, q, n)
    grid = (q // tile_q, n // tile_n)
    out_specs = [
        pl.BlockSpec((tile_q, k), lambda i, j: (i, 0)),
        pl.BlockSpec((tile_q, k), lambda i, j: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((q, k), jnp.float32),
        jax.ShapeDtypeStruct((q, k), jnp.int32),
    ]
    dt = _exact_call_dtype(points)
    if dt == jnp.int8:
        assert scales is not None, "int8 scoring requires scales (1, 2) f32"
        assert queries.dtype == jnp.int8, queries.dtype
        return pl.pallas_call(
            functools.partial(
                _masked_exact_q_kernel, metric=metric, k=k, tile_n=tile_n
            ),
            grid=grid,
            in_specs=[
                pl.BlockSpec((tile_q, d), lambda i, j: (i, 0)),
                pl.BlockSpec((tile_n, d), lambda i, j: (j, 0)),
                pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
                pl.BlockSpec((tile_q, tile_n), lambda i, j: (i, j)),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
        )(queries, points, scales.astype(jnp.float32), masks.astype(jnp.float32))
    return pl.pallas_call(
        functools.partial(_masked_exact_kernel, metric=metric, k=k, tile_n=tile_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((tile_q, tile_n), lambda i, j: (i, j)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(queries.astype(dt), points.astype(dt), masks.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("k", "tile_q", "tile_n", "interpret"))
def masked_pq_topk_pallas(
    luts: jnp.ndarray,
    codes: jnp.ndarray,
    mask: jnp.ndarray,
    k: int,
    *,
    tile_q: int = 8,
    tile_n: int = 128,
    interpret: bool = False,
):
    """Masked PQ-ADC top-k.  luts (Q, m, K) f32, codes (N, m) int32, mask
    (1, N) f32.  Same alignment/sentinel contract as
    :func:`masked_exact_topk_pallas`."""
    q, m, kcode = luts.shape
    n, m2 = codes.shape
    assert m == m2, (m, m2)
    assert q % tile_q == 0 and n % tile_n == 0, (q, n, tile_q, tile_n)
    assert mask.shape == (1, n), (mask.shape, n)
    grid = (q // tile_q, n // tile_n)
    return pl.pallas_call(
        functools.partial(_masked_pq_kernel, K=kcode, k=k, tile_n=tile_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, m, kcode), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((tile_n, m), lambda i, j: (j, 0)),
            pl.BlockSpec((1, tile_n), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((tile_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, k), jnp.float32),
            jax.ShapeDtypeStruct((q, k), jnp.int32),
        ],
        interpret=interpret,
    )(luts.astype(jnp.float32), codes.astype(jnp.int32), mask.astype(jnp.float32))


def _unified_kernel(
    q_ref, x_ref, lut_ref, codes_ref, s_ref, od_ref, oi_ref, score_ref,
    *, metric, K, k, tile_n
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        od_ref[...] = jnp.full(od_ref.shape, MASKED, jnp.float32)
        oi_ref[...] = jnp.full(oi_ref.shape, -1, jnp.int32)

    s = s_ref[...]  # (TILE_Q, TILE_N) selector: 0 masked / 1 exact / 2 adc
    is_adc = s > 1.5
    q = q_ref[...]  # (TILE_Q, D)
    x = x_ref[...]  # (TILE_N, D)
    cross = jax.lax.dot_general(
        q, x, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    if metric == "l2":
        q2 = jnp.sum(q * q, axis=-1, keepdims=True)
        x2 = jnp.sum(x * x, axis=-1)[None, :]
        d_exact = q2 - 2.0 * cross + x2
    else:  # ip
        d_exact = -cross
    # One shared score buffer: exact scores land first, ADC cells zeroed so
    # the per-subquantizer contributions below accumulate from a clean slate.
    score_ref[...] = jnp.where(is_adc, 0.0, d_exact)
    lut = lut_ref[...]  # (TILE_Q, m, K)
    codes = codes_ref[...]  # (TILE_N, m)
    m_sub = lut.shape[1]
    tn = codes.shape[0]
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (tn, K), 1)
    for c in range(m_sub):
        # (TILE_N, K) one-hot for ONE subquantizer — never the full
        # (TILE_N, m, K) tensor
        onehot_c = (codes[:, c][:, None] == iota_k).astype(jnp.float32)
        part = jax.lax.dot_general(
            lut[:, c, :], onehot_c,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )  # (TILE_Q, TILE_N)
        score_ref[...] += jnp.where(is_adc, part, 0.0)
    d = jnp.where(s > 0.5, score_ref[...], MASKED)
    _merge_tile(d, j, tile_n, od_ref, oi_ref, k)


def unified_block_shapes(tile_q: int, tile_n: int, d: int, m: int, K: int, k: int):
    """Resident VMEM blocks of one unified-kernel grid step, keyed by input
    name, as ``(shape, dtype)``.  This is the budget table the module
    docstring quotes; tests walk the BlockSpecs of
    :func:`unified_masked_topk_pallas` and assert they match."""
    return {
        "queries": ((tile_q, d), jnp.float32),
        "points": ((tile_n, d), jnp.float32),
        "luts": ((tile_q, m, K), jnp.float32),
        "codes": ((tile_n, m), jnp.int32),
        "selector": ((tile_q, tile_n), jnp.float32),
        "out_dists": ((tile_q, k), jnp.float32),
        "out_ids": ((tile_q, k), jnp.int32),
        "score_scratch": ((tile_q, tile_n), jnp.float32),
    }


def unified_vmem_bytes(
    tile_q: int, tile_n: int, d: int, m: int, K: int, k: int
) -> int:
    """Worst-case VMEM estimate for one unified grid step: double-buffered
    resident blocks (×2) plus the largest transient — the per-subquantizer
    (TILE_N, K) one-hot chunk."""
    import numpy as _np

    resident = sum(
        int(_np.prod(shape)) * _np.dtype(dt).itemsize
        for shape, dt in unified_block_shapes(tile_q, tile_n, d, m, K, k).values()
    )
    transient = tile_n * K * 4  # one-hot chunk, f32
    return 2 * resident + transient


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "tile_q", "tile_n", "interpret")
)
def unified_masked_topk_pallas(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    luts: jnp.ndarray,
    codes: jnp.ndarray,
    selector: jnp.ndarray,
    k: int,
    *,
    metric: str = "l2",
    tile_q: int = 8,
    tile_n: int = 128,
    interpret: bool = False,
):
    """Single-dispatch mixed-flavor masked top-k.  queries (Q, D) f32,
    points (N, D) f32, luts (Q, m, K) f32, codes (N, m) int32, selector
    (Q, N) f32 with 0 = masked out, 1 = exact flavor, 2 = ADC flavor.
    Same alignment and (MASKED, -1) sentinel contract as the other flavors;
    the selector plane is tiled (i, j) like the multi-mask plane."""
    q, d = queries.shape
    n, d2 = points.shape
    assert d == d2, (d, d2)
    q2, m, kcode = luts.shape
    n2, m2 = codes.shape
    assert q2 == q and n2 == n and m == m2, (luts.shape, codes.shape)
    assert q % tile_q == 0 and n % tile_n == 0, (q, n, tile_q, tile_n)
    assert selector.shape == (q, n), (selector.shape, q, n)
    grid = (q // tile_q, n // tile_n)
    # BlockSpecs are built FROM the budget table so the docstring's VMEM
    # numbers and the actual kernel layout cannot drift (tested).
    shapes = unified_block_shapes(tile_q, tile_n, d, m, kcode, k)
    return pl.pallas_call(
        functools.partial(
            _unified_kernel, metric=metric, K=kcode, k=k, tile_n=tile_n
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(shapes["queries"][0], lambda i, j: (i, 0)),
            pl.BlockSpec(shapes["points"][0], lambda i, j: (j, 0)),
            pl.BlockSpec(shapes["luts"][0], lambda i, j: (i, 0, 0)),
            pl.BlockSpec(shapes["codes"][0], lambda i, j: (j, 0)),
            pl.BlockSpec(shapes["selector"][0], lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec(shapes["out_dists"][0], lambda i, j: (i, 0)),
            pl.BlockSpec(shapes["out_ids"][0], lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, k), jnp.float32),
            jax.ShapeDtypeStruct((q, k), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM(*shapes["score_scratch"])],
        interpret=interpret,
    )(
        queries.astype(jnp.float32),
        points.astype(jnp.float32),
        luts.astype(jnp.float32),
        codes.astype(jnp.int32),
        selector.astype(jnp.float32),
    )


@functools.partial(jax.jit, static_argnames=("k", "tile_q", "tile_n", "interpret"))
def masked_pq_topk_multi_pallas(
    luts: jnp.ndarray,
    codes: jnp.ndarray,
    masks: jnp.ndarray,
    k: int,
    *,
    tile_q: int = 8,
    tile_n: int = 128,
    interpret: bool = False,
):
    """Per-query-mask PQ-ADC top-k.  luts (Q, m, K) f32, codes (N, m) int32,
    masks (Q, N) f32.  Same alignment/sentinel contract as
    :func:`masked_pq_topk_pallas`, mask plane tiled (i, j)."""
    q, m, kcode = luts.shape
    n, m2 = codes.shape
    assert m == m2, (m, m2)
    assert q % tile_q == 0 and n % tile_n == 0, (q, n, tile_q, tile_n)
    assert masks.shape == (q, n), (masks.shape, q, n)
    grid = (q // tile_q, n // tile_n)
    return pl.pallas_call(
        functools.partial(_masked_pq_kernel, K=kcode, k=k, tile_n=tile_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, m, kcode), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((tile_n, m), lambda i, j: (j, 0)),
            pl.BlockSpec((tile_q, tile_n), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((tile_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, k), jnp.float32),
            jax.ShapeDtypeStruct((q, k), jnp.int32),
        ],
        interpret=interpret,
    )(luts.astype(jnp.float32), codes.astype(jnp.int32), masks.astype(jnp.float32))
