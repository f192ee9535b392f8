"""Pallas TPU kernel: PQ asymmetric-distance (ADC) scan.

Paper hot spot: Stage-A beam search and refresh inserts score candidates with
PQ-approximate distances ("~1,000 PQ-approximate distance computations per
insert", §7.2; "PQ-approximate distances for candidate scoring", §6).  On
CPU the paper uses AVX2 LUT gathers; the TPU has no efficient per-lane
gather, so we *reformulate the gather as a one-hot matmul* that the MXU
executes at full rate — the hardware-adaptation called out in DESIGN.md §2:

    scores[q, n] = sum_j LUT[q, j, codes[n, j]]
                 = LUT_flat[q, :] @ onehot(codes)[n, :]      (length m*K)

VMEM budget per grid step (defaults TILE_Q=8, TILE_N=128, m=48, K=256):
  LUT tile   8 × 12288 × 4 B  ≈ 0.39 MB
  onehot   128 × 12288 × 4 B  ≈ 6.3 MB
  codes    128 × 48 × 4 B     ≈ 0.02 MB
  out        8 × 128 × 4 B    ≈ 4 KB          → ≈ 6.7 MB < 16 MB VMEM.

The MXU sees a (TILE_Q × mK) @ (mK × TILE_N) matmul; mK is a multiple of 256
so the contraction dim is 128-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pq_scan_kernel(lut_ref, codes_ref, out_ref, *, K: int):
    # lut_ref:   (TILE_Q, m, K) f32
    # codes_ref: (TILE_N, m)    int32
    # out_ref:   (TILE_Q, TILE_N) f32
    lut = lut_ref[...]
    codes = codes_ref[...]
    tile_q, m, k = lut.shape
    tile_n = codes.shape[0]
    # one-hot over the K axis: (TILE_N, m, K)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (tile_n, m, K), 2)
    onehot = (codes[:, :, None] == iota_k).astype(jnp.float32)
    # flatten to a single MXU matmul: (TILE_Q, m*K) @ (m*K, TILE_N)
    lut_flat = lut.reshape(tile_q, m * K)
    onehot_flat = onehot.reshape(tile_n, m * K)
    out_ref[...] = jax.lax.dot_general(
        lut_flat,
        onehot_flat,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(jax.jit, static_argnames=("tile_q", "tile_n", "interpret"))
def pq_scan_pallas(
    luts: jnp.ndarray,
    codes: jnp.ndarray,
    *,
    tile_q: int = 8,
    tile_n: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """ADC scores via the one-hot-matmul kernel.

    luts:  (Q, m, K) f32;  codes: (N, m) int32.  Q % tile_q == 0 and
    N % tile_n == 0 are required — the ops.py wrapper pads.
    Returns (Q, N) f32.
    """
    q, m, k = luts.shape
    n, m2 = codes.shape
    assert m == m2, (m, m2)
    assert q % tile_q == 0 and n % tile_n == 0, (q, n, tile_q, tile_n)
    grid = (q // tile_q, n // tile_n)
    return pl.pallas_call(
        functools.partial(_pq_scan_kernel, K=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, m, k), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((tile_n, m), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q, n), jnp.float32),
        interpret=interpret,
    )(luts.astype(jnp.float32), codes.astype(jnp.int32))
