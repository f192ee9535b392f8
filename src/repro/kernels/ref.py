"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: each kernel's test sweeps shapes and
dtypes and asserts ``assert_allclose`` against the functions here.  They are
also the CPU fallback used when Pallas interpret mode is not wanted (e.g.
inside heavily-iterated host-side build loops).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def l2_distances(queries: jnp.ndarray, points: jnp.ndarray) -> jnp.ndarray:
    """Squared L2 distance matrix.

    queries: (Q, D) f32;  points: (N, D) f32  ->  (Q, N) f32.
    Uses the expanded form |q|^2 - 2 q.x + |x|^2 (same math as the kernel so
    numerical behaviour matches to float tolerance).  The cross term runs at
    ``HIGHEST`` precision: TPU's default f32 matmul is a single bf16 pass,
    whose error survives the cancellation in the expanded form as a
    percent-level distance error.
    """
    q2 = jnp.sum(queries * queries, axis=-1, keepdims=True)  # (Q, 1)
    x2 = jnp.sum(points * points, axis=-1)[None, :]  # (1, N)
    cross = _matmul_t(queries, points)  # (Q, N)
    return q2 - 2.0 * cross + x2


def ip_distances(queries: jnp.ndarray, points: jnp.ndarray) -> jnp.ndarray:
    """Negative inner product ("distance": smaller is closer)."""
    return -_matmul_t(queries, points)


def _matmul_t(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``a @ b.T`` at full f32 precision on every backend."""
    return jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)


def pq_adc_scores(luts: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """Asymmetric-distance-computation scores.

    luts:  (Q, m, K) f32 — per-query lookup tables (distance of the query's
           j-th subvector to each of the K codewords of subquantizer j).
    codes: (N, m) integer — PQ codes of the database points.
    Returns (Q, N) f32: ``scores[q, n] = sum_j luts[q, j, codes[n, j]]``.
    """
    codes = codes.astype(jnp.int32)
    # gather per subquantizer: (Q, m, N)
    gathered = jnp.take_along_axis(
        luts, codes.T[None, :, :].astype(jnp.int32), axis=2
    )  # luts (Q,m,K) indexed with (1,m,N) -> (Q,m,N)
    return jnp.sum(gathered, axis=1)


def build_pq_luts(
    queries: jnp.ndarray, codebook: jnp.ndarray, metric: str = "l2"
) -> jnp.ndarray:
    """LUT construction for ADC.

    queries:  (Q, D) f32;  codebook: (m, K, D/m) f32.
    Returns (Q, m, K) f32 of sub-distances.
    """
    m, K, dsub = codebook.shape
    q_sub = queries.reshape(queries.shape[0], m, dsub)  # (Q, m, dsub)
    if metric == "l2":
        diff = q_sub[:, :, None, :] - codebook[None, :, :, :]  # (Q, m, K, dsub)
        return jnp.sum(diff * diff, axis=-1)
    if metric == "ip":
        return -jnp.einsum(
            "qmd,mkd->qmk", q_sub, codebook, precision=jax.lax.Precision.HIGHEST
        )
    raise ValueError(f"unknown metric {metric}")


def _masked_topk(scores: jnp.ndarray, mask: jnp.ndarray, k: int):
    """Shared masked top-k epilogue: scores (Q, N), mask (N,) shared across
    queries or (Q, N) per query, truthy.

    Masked-out rows are forced to +inf before the reduction.  Returns
    (dists (Q, k) f32, ids (Q, k) int32) per row ascending; slots beyond
    the number of passing rows hold (+inf, -1) — the masked-op contract
    ops.py documents."""
    n = scores.shape[1]
    mask = jnp.asarray(mask).astype(bool)
    if mask.ndim == 1:
        mask = mask[None, :]
    scores = jnp.where(mask, scores, jnp.inf)
    k_avail = min(k, n)
    neg, idx = jax.lax.top_k(-scores, k_avail)
    d = -neg
    idx = jnp.where(jnp.isinf(d), -1, idx).astype(jnp.int32)
    if k_avail < k:
        pad = ((0, 0), (0, k - k_avail))
        d = jnp.pad(d, pad, constant_values=jnp.inf)
        idx = jnp.pad(idx, pad, constant_values=-1)
    return d.astype(jnp.float32), idx


def masked_exact_topk(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    mask: jnp.ndarray,
    k: int,
    metric: str = "l2",
):
    """Mask-aware exact top-k: queries (Q, D), points (N, D), mask (N,)."""
    fn = l2_distances if metric == "l2" else ip_distances
    return _masked_topk(fn(queries, points), mask, k)


def masked_pq_topk(luts: jnp.ndarray, codes: jnp.ndarray, mask: jnp.ndarray, k: int):
    """Mask-aware PQ-ADC top-k: luts (Q, m, K), codes (N, m), mask (N,)."""
    return _masked_topk(pq_adc_scores(luts, codes), mask, k)


def masked_exact_topk_multi(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    masks: jnp.ndarray,
    k: int,
    metric: str = "l2",
):
    """Per-query-mask exact top-k: queries (Q, D), points (N, D), masks
    (Q, N) — row q masks query q independently (heterogeneous predicates
    in one call)."""
    fn = l2_distances if metric == "l2" else ip_distances
    return _masked_topk(fn(queries, points), masks, k)


def masked_pq_topk_multi(
    luts: jnp.ndarray, codes: jnp.ndarray, masks: jnp.ndarray, k: int
):
    """Per-query-mask PQ-ADC top-k: luts (Q, m, K), codes (N, m), masks
    (Q, N)."""
    return _masked_topk(pq_adc_scores(luts, codes), masks, k)


def unified_masked_topk(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    luts: jnp.ndarray,
    codes: jnp.ndarray,
    masks: jnp.ndarray,
    flavor: jnp.ndarray,
    k: int,
    metric: str = "l2",
):
    """Single-dispatch mixed-flavor masked top-k: queries (Q, D), points
    (N, D), luts (Q, m, K), codes (N, m), masks (N,) or (Q, N), flavor (Q,)
    truthy (True = score row q with PQ-ADC, False = full-precision).  Each
    query's scores come from ITS flavor; the masked top-k epilogue is
    shared, so a fragment mixing both flavors is one call.

    Like the Pallas kernel, both score planes are computed and selected
    per row: at these shapes the two dense computes beat any
    subset-gather/scatter assembly (eager-mode gathers cost more than the
    matmul they save — measured), and the shared top-k epilogue runs
    once instead of once per flavor."""
    fn = l2_distances if metric == "l2" else ip_distances
    d_exact = fn(queries, points)
    d_adc = pq_adc_scores(luts, codes)
    sel = jnp.asarray(flavor).astype(bool).reshape(-1, 1)
    return _masked_topk(jnp.where(sel, d_adc, d_exact), masks, k)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def gather_rerank(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    pool_ids: jnp.ndarray,
    k: int,
    metric: str = "l2",
):
    """Exact full-precision rerank of a per-query candidate pool.

    queries (Q, D) f32, points (N, D) f32, pool_ids (Q, P) integer — row q
    holds query q's candidate ids into ``points``; slots < 0 are sentinels
    ("no candidate") and stay (+inf, -1).  Returns (dists (Q, k) f32, ids
    (Q, k) int32) ascending per row, (+inf, -1) beyond the live pool — the
    same sentinel contract as the masked ops.  ``k`` may exceed P.

    This is the semantic ground truth for the old executor host rerank
    (``np.clip`` gather + einsum / squared-difference sum): same direct-form
    L2 so distances agree to float tolerance and ids bit-match on
    non-degenerate pools."""
    pids = jnp.asarray(pool_ids).astype(jnp.int32)
    q = jnp.asarray(queries).astype(jnp.float32)
    x = jnp.asarray(points).astype(jnp.float32)
    safe = jnp.clip(pids, 0, x.shape[0] - 1)
    vecs = x[safe]  # (Q, P, D)
    if metric == "ip":
        d = -jnp.einsum("qpd,qd->qp", vecs, q, precision=jax.lax.Precision.HIGHEST)
    else:
        diff = vecs - q[:, None, :]
        d = jnp.sum(diff * diff, axis=-1)
    d = jnp.where(pids < 0, jnp.inf, d)
    p = d.shape[1]
    k_avail = min(k, p)
    neg, slot = jax.lax.top_k(-d, k_avail)
    out_d = -neg
    out_i = jnp.take_along_axis(pids, slot, axis=1)
    out_i = jnp.where(jnp.isinf(out_d), -1, out_i).astype(jnp.int32)
    if k_avail < k:
        pad = ((0, 0), (0, k - k_avail))
        out_d = jnp.pad(out_d, pad, constant_values=jnp.inf)
        out_i = jnp.pad(out_i, pad, constant_values=-1)
    return out_d.astype(jnp.float32), out_i


# -- quantized scoring --------------------------------------------------------
#
# bf16/int8 are *storage + matmul-rate* levers: values are quantized, the
# accumulation stays f32 (bf16) / int32 (int8).  The oracles emulate exactly
# that — dequantize the stored values and score in f32 — so they predict the
# recall of the quantized kernels bit-for-bit at the value level, and on
# hardware without native reduced-precision matmul units they double as the
# production CPU path (quantization there buys memory footprint, not FLOPs).

SCORE_DTYPES = ("f32", "bf16", "int8")


def quantize_points(points: jnp.ndarray, dtype: str):
    """Quantize a point matrix for reduced-precision scoring.

    Returns (stored, scale): ``bf16`` stores bfloat16 values (scale 1.0);
    ``int8`` stores symmetric per-tensor int8 with ``scale = max|x| / 127``;
    ``f32`` passes through.  Dequantization is ``stored.astype(f32) *
    scale`` in every case."""
    x = jnp.asarray(points)
    if dtype == "f32":
        return x.astype(jnp.float32), 1.0
    if dtype == "bf16":
        return x.astype(jnp.bfloat16), 1.0
    if dtype == "int8":
        scale = float(jnp.max(jnp.abs(x.astype(jnp.float32)))) / 127.0
        scale = scale or 1.0
        q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
        return q.astype(jnp.int8), scale
    raise ValueError(f"unknown score dtype {dtype!r}")


def dequantize_points(stored: jnp.ndarray, scale: float) -> jnp.ndarray:
    return stored.astype(jnp.float32) * jnp.float32(scale)


def masked_exact_topk_quant(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    mask: jnp.ndarray,
    k: int,
    *,
    metric: str = "l2",
    dtype: str = "bf16",
    x_scale: float = 1.0,
):
    """Quantized-scoring oracle for the masked exact scan: ``points`` is the
    STORED (quantized) matrix from :func:`quantize_points`; queries are
    quantized per call with their own scale.  The scores carry quantization
    error — callers restore recall by feeding the surviving pool through the
    full-precision :func:`gather_rerank` guard.  ``mask`` may be (N,) or a
    (Q, N) plane."""
    xq = dequantize_points(points, x_scale)
    qs, q_scale = quantize_points(queries, dtype)
    qq = dequantize_points(qs, q_scale)
    fn = l2_distances if metric == "l2" else ip_distances
    return _masked_topk(fn(qq, xq), mask, k)


def kmeans_assign(points: jnp.ndarray, centroids: jnp.ndarray):
    """Nearest-centroid assignment.

    points: (N, D) f32;  centroids: (K, D) f32.
    Returns (assignments (N,) int32, sq_distances (N,) f32).
    """
    d = l2_distances(points, centroids)  # (N, K)
    idx = jnp.argmin(d, axis=1).astype(jnp.int32)
    return idx, jnp.min(d, axis=1)
