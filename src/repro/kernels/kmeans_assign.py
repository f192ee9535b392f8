"""Pallas TPU kernel: nearest-centroid assignment with a K-tiled running min.

Paper hot spot: Stage-0 centroid training (coordinator k-means over the 1 %
sample) and Stage-1 shard-ownership confirmation ("assigns each vector to its
nearest centroid", §5) are Lloyd-iteration assignment scans: every vector
against every centroid.

Grid layout: ``(N tiles, K tiles)``.  The output blocks depend only on the
N-tile index, so for a fixed N tile the kernel is re-entered once per K tile
and keeps a **running (min, argmin)** in the output refs — the canonical
Pallas cross-step reduction idiom.  Centroid tiles therefore never need to
fit all of K in VMEM at once.

The outputs are lane-dense ``(1, N)`` rows written in ``(1, TILE_N)``
blocks: a 1-D ``(N,)`` output is laid out by XLA in 1024-element tiles and
by Mosaic in 256-element ones, and the TPU compiler refuses the mismatch.

VMEM per step (TILE_N=256, TILE_K=128, D≤1024 f32): x 1 MB, c 0.5 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kmeans_assign_kernel(x_ref, c_ref, dist_ref, idx_ref, *, tile_k: int):
    k_step = pl.program_id(1)
    x = x_ref[...]  # (TILE_N, D)
    c = c_ref[...]  # (TILE_K, D)
    cross = jax.lax.dot_general(
        x, c, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (TILE_N, TILE_K)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)
    c2 = jnp.sum(c * c, axis=-1)[None, :]
    d = x2 - 2.0 * cross + c2  # (TILE_N, TILE_K)
    local_min = jnp.min(d, axis=1)[None, :]  # (1, TILE_N)
    local_arg = jnp.argmin(d, axis=1).astype(jnp.int32)[None, :] + k_step * tile_k

    @pl.when(k_step == 0)
    def _init():
        dist_ref[...] = local_min
        idx_ref[...] = local_arg

    @pl.when(k_step != 0)
    def _update():
        prev = dist_ref[...]
        take_new = local_min < prev
        dist_ref[...] = jnp.where(take_new, local_min, prev)
        idx_ref[...] = jnp.where(take_new, local_arg, idx_ref[...])


@functools.partial(jax.jit, static_argnames=("tile_n", "tile_k", "interpret"))
def kmeans_assign_pallas(
    points: jnp.ndarray,
    centroids: jnp.ndarray,
    *,
    tile_n: int = 256,
    tile_k: int = 128,
    interpret: bool = False,
):
    """Returns (assignments (N,) int32, sq_distances (N,) f32).

    N % tile_n == 0 and K % tile_k == 0 required (ops.py pads; padded
    centroids are +inf-normed so they never win the argmin)."""
    n, d = points.shape
    k, d2 = centroids.shape
    assert d == d2, (d, d2)
    assert n % tile_n == 0 and k % tile_k == 0, (n, k)
    grid = (n // tile_n, k // tile_k)
    dist, idx = pl.pallas_call(
        functools.partial(_kmeans_assign_kernel, tile_k=tile_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_k, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile_n), lambda i, j: (0, i)),
            pl.BlockSpec((1, tile_n), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ],
        interpret=interpret,
    )(points.astype(jnp.float32), centroids.astype(jnp.float32))
    return idx[0], dist[0]
