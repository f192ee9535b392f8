"""Public jit'd wrappers for the Pallas kernels.

Each op handles tile padding, dtype coercion, and backend dispatch:

- ``backend="auto"``   → real Pallas on TPU; pure-jnp oracle on CPU (fast —
  interpret mode executes the kernel body per grid step in Python and is for
  *validation*, not production CPU work).
- ``backend="pallas"`` → Pallas always (``interpret=True`` off-TPU).  This is
  what the kernel correctness tests use.
- ``backend="ref"``    → the ref.py oracle.

Padding rules preserve semantics: feature dims pad with zeros (no effect on
L2/IP), point/centroid tiles pad with +inf sentinels that can never win a
min/top-k, query tiles pad with zeros and are sliced off the output.

Masked-op contract (``masked_exact_topk`` / ``masked_pq_topk`` and their
``*_multi`` per-query-mask variants):

- ``mask`` is a per-row bitmask over the N points/codes (bool or 0/1
  numeric, length N): truthy = the row may appear in results; falsy rows —
  predicate misses, tombstones — are forced to ``+inf`` *inside* the
  kernel, before the top-k reduction, so they can never displace a passing
  row.  No pool widening, no post-hoc filtering.
- the ``*_multi`` ops take a mask PLANE ``(Q, N)`` instead: row ``q`` is
  query ``q``'s own bitmask, so a coalesced batch carrying heterogeneous
  predicates is still ONE kernel call.  ``Q == 1`` degenerates to the
  single-mask kernel (same tile schedule, no plane materialization).
- the ``*_dedup`` variants take the plane FACTORED as ``(unique_masks
  (m, N), row_index (Q,))`` — when a mostly-homogeneous batch has only m
  distinct predicates, only the m unique rows cross host→device; the
  dense ``(Q, N)`` plane is broadcast on-device (a jnp gather inside the
  same jit) before the kernel sees it.  Results are bit-identical to the
  dense ``*_multi`` call on the expanded plane.
- ``unified_masked_topk`` scores a MIXED-flavor batch in one dispatch: it
  takes both the exact inputs (points) and the ADC inputs (luts, codes)
  plus a per-query ``flavor`` vector (truthy = ADC); the kernel folds mask
  and flavor into one selector plane (0 = masked, 1 = exact, 2 = ADC) and
  each query's rows are scored by its own flavor before the shared top-k
  reduction.  Same sentinel contract.
- Outputs are ``(dists (Q, k) f32, ids (Q, k) int32)``, each row ascending.
  When fewer than ``k`` rows pass, trailing slots hold ``(+inf, -1)`` —
  callers must treat non-finite distance or negative id as "no candidate".
  ``k`` may exceed N; the extra slots are sentinels too.
- Backend dispatch matches every other op: ``auto`` → Pallas on TPU / ref
  on CPU; ``pallas`` forces the kernel (``interpret=True`` off-TPU — the
  parity tests); ``ref`` forces the jnp oracle.  Point/code rows pad to the
  N tile with mask 0 (never win), query rows pad with zeros and are sliced
  off, feature dims pad with zeros.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import autotune, ref
from repro.kernels.kmeans_assign import kmeans_assign_pallas
from repro.kernels.masked_topk import (
    MASKED_THRESHOLD,
    masked_exact_topk_multi_pallas,
    masked_exact_topk_pallas,
    masked_pq_topk_multi_pallas,
    masked_pq_topk_pallas,
    unified_masked_topk_pallas,
)
from repro.kernels.pq_scan import pq_scan_pallas
from repro.kernels.rerank import gather_rerank_pallas, rerank_distances_pallas

_BIG = jnp.float32(3.4e38)  # ~f32 max; safe "never wins" sentinel


@functools.cache
def _on_tpu() -> bool:
    """Whether the default device is a TPU, decided once per process.  A
    failure to enumerate devices propagates: a broken accelerator must not
    silently turn every op into the CPU oracle or interpret mode."""
    return jax.devices()[0].platform == "tpu"


def _resolve(backend: str) -> str:
    if backend == "auto":
        return "pallas" if _on_tpu() else "ref"
    return backend


def _tiles(
    tile_q: Optional[int], tile_n: Optional[int], n_rows: int, d: int, flavor: str
) -> Tuple[int, int]:
    """Resolve a wrapper's tile choice: explicit values win; ``None`` asks
    the autotuner for this (rows, D, flavor) bucket — measured winner from
    the committed sweep fixture, or the old (8, 128) constants on a miss."""
    if tile_q is not None and tile_n is not None:
        return int(tile_q), int(tile_n)
    auto_q, auto_n = autotune.get_tiles(n_rows, d, flavor)
    return (
        int(tile_q) if tile_q is not None else auto_q,
        int(tile_n) if tile_n is not None else auto_n,
    )


def _pad_to(x: jnp.ndarray, axis: int, multiple: int, value) -> Tuple[jnp.ndarray, int]:
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=value), size


# -- exact distances ---------------------------------------------------------

def exact_distances(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    *,
    metric: str = "l2",
    backend: str = "auto",
    tile_q: int = 128,
    tile_n: int = 128,
) -> jnp.ndarray:
    """(Q, D) × (N, D) → (Q, N) distance matrix (squared L2 or -IP)."""
    backend = _resolve(backend)
    if backend == "ref":
        fn = ref.l2_distances if metric == "l2" else ref.ip_distances
        return fn(queries, points)
    interpret = not _on_tpu()
    q_pad, q0 = _pad_to(queries.astype(jnp.float32), 0, tile_q, 0.0)
    x_pad, n0 = _pad_to(points.astype(jnp.float32), 0, tile_n, 0.0)
    q_pad, _ = _pad_to(q_pad, 1, 128, 0.0)
    x_pad, _ = _pad_to(x_pad, 1, 128, 0.0)
    out = rerank_distances_pallas(
        q_pad, x_pad, metric=metric, tile_q=tile_q, tile_n=tile_n, interpret=interpret
    )
    return out[:q0, :n0]


STAGE_B_MIN_ROWS = 256  # smallest candidate-row bucket of bucketed_exact_distances


def _stage_b_buckets(q: int, n: int) -> Tuple[int, int]:
    """(queries, rows) bucket of a (q, n) Stage-B call: queries up to a
    multiple of the traversal's query batch, rows up to the next power of
    two (at least ``STAGE_B_MIN_ROWS``), so a bucket wastes at most half its
    rows."""
    from repro.core.vamana import QUERY_BATCH  # lazy: vamana -> pq -> ops

    qb = -(-max(q, 1) // QUERY_BATCH) * QUERY_BATCH
    nb = max(STAGE_B_MIN_ROWS, 1 << (max(n, 1) - 1).bit_length())
    return qb, nb


@functools.partial(jax.jit, static_argnames=("metric",))
def _bucket_distances(queries: jnp.ndarray, points: jnp.ndarray, metric: str) -> jnp.ndarray:
    fn = ref.l2_distances if metric == "l2" else ref.ip_distances
    return fn(queries, points)


def bucketed_exact_distances(
    queries: np.ndarray, points: np.ndarray, *, metric: str = "l2"
) -> np.ndarray:
    """(Q, D) × (N, D) host arrays → (Q, N) host distance matrix (squared L2
    or -IP): the math of ``exact_distances(backend="ref")``, run as ONE jitted
    program per (queries, rows) bucket instead of eager jnp per exact shape.

    Both arrays are zero-padded on the host to their bucket
    (:func:`_stage_b_buckets`) before they reach the device, and the padded
    matrix is sliced back on the host, so a call whose bucket is warm
    compiles nothing — no eager pad or slice runs outside the jit.  Padded
    rows and queries never leave this function."""
    q = np.asarray(queries, np.float32)
    x = np.asarray(points, np.float32)
    q0, n0 = q.shape[0], x.shape[0]
    qb, nb = _stage_b_buckets(q0, n0)
    q_pad = np.pad(q, ((0, qb - q0), (0, 0)))
    x_pad = np.pad(x, ((0, nb - n0), (0, 0)))
    out = _bucket_distances(jnp.asarray(q_pad), jnp.asarray(x_pad), metric)
    return np.asarray(out)[:q0, :n0]


def exact_topk(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    k: int,
    *,
    metric: str = "l2",
    backend: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k nearest: returns (distances (Q, k), indices (Q, k))."""
    d = exact_distances(queries, points, metric=metric, backend=backend)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx


# -- mask-aware top-k --------------------------------------------------------

def _finalize_masked(out_d, out_i, q0: int):
    """Slice off query padding and normalize sentinels to (+inf, -1)."""
    d = out_d[:q0]
    i = out_i[:q0]
    empty = d >= MASKED_THRESHOLD
    return jnp.where(empty, jnp.inf, d), jnp.where(empty, -1, i)


def _mask_row(mask: jnp.ndarray, tile_n: int) -> jnp.ndarray:
    """(N,) truthy mask -> (1, N_padded) f32; padded rows get 0 (never win)."""
    m = mask.astype(jnp.float32).reshape(1, -1)
    m, _ = _pad_to(m, 1, tile_n, 0.0)
    return m


def _quant_inputs(queries: jnp.ndarray, points: jnp.ndarray, dtype: str, x_scale):
    """Normalize a quantized-scoring call: ``points`` may arrive pre-stored
    (int8/bf16 from a cached device copy, with its ``x_scale``) or f32 to be
    quantized here; queries are always quantized per call.  Returns
    (stored_q, stored_x, q_scale, x_scale)."""
    want = {"bf16": jnp.bfloat16, "int8": jnp.int8}[dtype]
    x = jnp.asarray(points)
    if x.dtype != want:
        x, x_scale = ref.quantize_points(x, dtype)
    qs, q_scale = ref.quantize_points(jnp.asarray(queries), dtype)
    return qs, x, float(q_scale), float(x_scale)


def masked_exact_topk(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    mask: jnp.ndarray,
    k: int,
    *,
    metric: str = "l2",
    backend: str = "auto",
    tile_q: Optional[int] = None,
    tile_n: Optional[int] = None,
    dtype: str = "f32",
    x_scale: float = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Masked exact top-k: (Q, D) × (N, D) under a (N,) row bitmask →
    (dists (Q, k), ids (Q, k)) per the masked-op contract above.

    ``dtype`` picks the scoring precision (``f32``/``bf16``/``int8``): for
    quantized dtypes ``points`` may be the pre-quantized stored matrix (pass
    its ``x_scale``) or f32 to quantize on the fly; queries quantize per
    call.  Quantized scores carry value error — callers MUST route the
    surviving pool through the full-precision :func:`gather_rerank` guard
    (the planner/executor do)."""
    backend = _resolve(backend)
    k = int(k)
    flavor = "exact" if dtype == "f32" else f"exact_{dtype}"
    tile_q, tile_n = _tiles(
        tile_q, tile_n, points.shape[0], points.shape[1], flavor
    )
    if dtype != "f32":
        qs, xs, q_scale, x_scale = _quant_inputs(queries, points, dtype, x_scale)
        if backend == "ref":
            return ref.masked_exact_topk_quant(
                queries, xs, mask, k, metric=metric, dtype=dtype, x_scale=x_scale
            )
        interpret = not _on_tpu()
        q_pad, q0 = _pad_to(qs, 0, tile_q, 0)
        x_pad, _n0 = _pad_to(xs, 0, tile_n, 0)
        q_pad, _ = _pad_to(q_pad, 1, 128, 0)
        x_pad, _ = _pad_to(x_pad, 1, 128, 0)
        m = _mask_row(jnp.asarray(mask), tile_n)
        scales = jnp.asarray([[q_scale, x_scale]], dtype=jnp.float32)
        out_d, out_i = masked_exact_topk_pallas(
            q_pad, x_pad, m, k, metric=metric, tile_q=tile_q, tile_n=tile_n,
            interpret=interpret, scales=scales if dtype == "int8" else None,
        )
        return _finalize_masked(out_d, out_i, q0)
    if backend == "ref":
        return ref.masked_exact_topk(queries, points, mask, k, metric=metric)
    interpret = not _on_tpu()
    q_pad, q0 = _pad_to(queries.astype(jnp.float32), 0, tile_q, 0.0)
    x_pad, _n0 = _pad_to(points.astype(jnp.float32), 0, tile_n, 0.0)
    q_pad, _ = _pad_to(q_pad, 1, 128, 0.0)
    x_pad, _ = _pad_to(x_pad, 1, 128, 0.0)
    m = _mask_row(jnp.asarray(mask), tile_n)
    out_d, out_i = masked_exact_topk_pallas(
        q_pad, x_pad, m, k, metric=metric, tile_q=tile_q, tile_n=tile_n,
        interpret=interpret,
    )
    return _finalize_masked(out_d, out_i, q0)


def masked_pq_topk(
    luts: jnp.ndarray,
    codes: jnp.ndarray,
    mask: jnp.ndarray,
    k: int,
    *,
    backend: str = "auto",
    tile_q: Optional[int] = None,
    tile_n: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Masked PQ-ADC top-k: per-query LUTs (Q, m, K) × codes (N, m) under a
    (N,) row bitmask → (scores (Q, k), ids (Q, k)) per the masked-op
    contract above."""
    backend = _resolve(backend)
    k = int(k)
    tile_q, tile_n = _tiles(tile_q, tile_n, codes.shape[0], codes.shape[1], "pq")
    if backend == "ref":
        return ref.masked_pq_topk(luts, codes, mask, k)
    interpret = not _on_tpu()
    luts_p, q0 = _pad_to(luts.astype(jnp.float32), 0, tile_q, 0.0)
    codes_p, _n0 = _pad_to(codes.astype(jnp.int32), 0, tile_n, 0)
    m = _mask_row(jnp.asarray(mask), tile_n)
    out_d, out_i = masked_pq_topk_pallas(
        luts_p, codes_p, m, k, tile_q=tile_q, tile_n=tile_n, interpret=interpret
    )
    return _finalize_masked(out_d, out_i, q0)


def _mask_plane(masks: jnp.ndarray, tile_q: int, tile_n: int) -> jnp.ndarray:
    """(Q, N) truthy plane -> (Q_pad, N_pad) f32; padded rows/cols get 0
    (padded queries see every row masked, padded rows never win)."""
    m = masks.astype(jnp.float32)
    m, _ = _pad_to(m, 0, tile_q, 0.0)
    m, _ = _pad_to(m, 1, tile_n, 0.0)
    return m


def masked_exact_topk_multi(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    masks: jnp.ndarray,
    k: int,
    *,
    metric: str = "l2",
    backend: str = "auto",
    tile_q: Optional[int] = None,
    tile_n: Optional[int] = None,
    dtype: str = "f32",
    x_scale: float = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-query-mask exact top-k: (Q, D) × (N, D) under a (Q, N) mask
    PLANE (row q masks query q) → (dists (Q, k), ids (Q, k)) per the
    masked-op contract above.  One kernel call for a whole heterogeneous-
    predicate batch; Q == 1 dispatches to the single-mask kernel.  Scoring
    precision dispatch matches :func:`masked_exact_topk` (``dtype`` +
    ``x_scale``; quantized pools need the :func:`gather_rerank` guard)."""
    masks = jnp.asarray(masks)
    q = queries.shape[0]
    assert masks.shape == (q, points.shape[0]), (masks.shape, queries.shape, points.shape)
    if q == 1:
        return masked_exact_topk(
            queries, points, masks[0], k,
            metric=metric, backend=backend, tile_q=tile_q, tile_n=tile_n,
            dtype=dtype, x_scale=x_scale,
        )
    backend = _resolve(backend)
    k = int(k)
    flavor = "exact" if dtype == "f32" else f"exact_{dtype}"
    tile_q, tile_n = _tiles(
        tile_q, tile_n, points.shape[0], points.shape[1], flavor
    )
    if dtype != "f32":
        qs, xs, q_scale, x_scale = _quant_inputs(queries, points, dtype, x_scale)
        if backend == "ref":
            return ref.masked_exact_topk_quant(
                queries, xs, masks, k, metric=metric, dtype=dtype, x_scale=x_scale
            )
        interpret = not _on_tpu()
        q_pad, q0 = _pad_to(qs, 0, tile_q, 0)
        x_pad, _n0 = _pad_to(xs, 0, tile_n, 0)
        q_pad, _ = _pad_to(q_pad, 1, 128, 0)
        x_pad, _ = _pad_to(x_pad, 1, 128, 0)
        m = _mask_plane(masks, tile_q, tile_n)
        scales = jnp.asarray([[q_scale, x_scale]], dtype=jnp.float32)
        out_d, out_i = masked_exact_topk_multi_pallas(
            q_pad, x_pad, m, k, metric=metric, tile_q=tile_q, tile_n=tile_n,
            interpret=interpret, scales=scales if dtype == "int8" else None,
        )
        return _finalize_masked(out_d, out_i, q0)
    if backend == "ref":
        return ref.masked_exact_topk_multi(queries, points, masks, k, metric=metric)
    interpret = not _on_tpu()
    q_pad, q0 = _pad_to(queries.astype(jnp.float32), 0, tile_q, 0.0)
    x_pad, _n0 = _pad_to(points.astype(jnp.float32), 0, tile_n, 0.0)
    q_pad, _ = _pad_to(q_pad, 1, 128, 0.0)
    x_pad, _ = _pad_to(x_pad, 1, 128, 0.0)
    m = _mask_plane(masks, tile_q, tile_n)
    out_d, out_i = masked_exact_topk_multi_pallas(
        q_pad, x_pad, m, k, metric=metric, tile_q=tile_q, tile_n=tile_n,
        interpret=interpret,
    )
    return _finalize_masked(out_d, out_i, q0)


def masked_pq_topk_multi(
    luts: jnp.ndarray,
    codes: jnp.ndarray,
    masks: jnp.ndarray,
    k: int,
    *,
    backend: str = "auto",
    tile_q: Optional[int] = None,
    tile_n: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-query-mask PQ-ADC top-k: per-query LUTs (Q, m, K) × codes (N, m)
    under a (Q, N) mask plane → (scores (Q, k), ids (Q, k)) per the
    masked-op contract above.  Q == 1 dispatches to the single-mask kernel."""
    masks = jnp.asarray(masks)
    q = luts.shape[0]
    assert masks.shape == (q, codes.shape[0]), (masks.shape, luts.shape, codes.shape)
    if q == 1:
        return masked_pq_topk(
            luts, codes, masks[0], k, backend=backend, tile_q=tile_q, tile_n=tile_n
        )
    backend = _resolve(backend)
    k = int(k)
    tile_q, tile_n = _tiles(tile_q, tile_n, codes.shape[0], codes.shape[1], "pq")
    if backend == "ref":
        return ref.masked_pq_topk_multi(luts, codes, masks, k)
    interpret = not _on_tpu()
    luts_p, q0 = _pad_to(luts.astype(jnp.float32), 0, tile_q, 0.0)
    codes_p, _n0 = _pad_to(codes.astype(jnp.int32), 0, tile_n, 0)
    m = _mask_plane(masks, tile_q, tile_n)
    out_d, out_i = masked_pq_topk_multi_pallas(
        luts_p, codes_p, m, k, tile_q=tile_q, tile_n=tile_n, interpret=interpret
    )
    return _finalize_masked(out_d, out_i, q0)


def unified_masked_topk(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    luts: jnp.ndarray,
    codes: jnp.ndarray,
    masks: jnp.ndarray,
    flavor: jnp.ndarray,
    k: int,
    *,
    metric: str = "l2",
    backend: str = "auto",
    tile_q: Optional[int] = None,
    tile_n: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-dispatch mixed-flavor masked top-k: (Q, D) × (N, D) exact AND
    (Q, m, K) × (N, m) PQ-ADC under a (Q, N) mask plane, with a per-query
    ``flavor`` vector (truthy = that query's rows score via ADC).  One
    kernel call answers a fragment whose queries split between the exact
    and PQ plans — the two-dispatch-per-shard path collapses to one."""
    masks = jnp.asarray(masks)
    q = queries.shape[0]
    assert masks.shape == (q, points.shape[0]), (masks.shape, queries.shape, points.shape)
    assert luts.shape[0] == q and codes.shape[0] == points.shape[0], (
        luts.shape, codes.shape,
    )
    backend = _resolve(backend)
    k = int(k)
    tile_q, tile_n = _tiles(
        tile_q, tile_n, points.shape[0], points.shape[1], "unified"
    )
    if backend == "ref":
        return ref.unified_masked_topk(
            queries, points, luts, codes, masks, flavor, k, metric=metric
        )
    interpret = not _on_tpu()
    q_pad, q0 = _pad_to(queries.astype(jnp.float32), 0, tile_q, 0.0)
    x_pad, _n0 = _pad_to(points.astype(jnp.float32), 0, tile_n, 0.0)
    q_pad, _ = _pad_to(q_pad, 1, 128, 0.0)
    x_pad, _ = _pad_to(x_pad, 1, 128, 0.0)
    luts_p, _ = _pad_to(luts.astype(jnp.float32), 0, tile_q, 0.0)
    codes_p, _ = _pad_to(codes.astype(jnp.int32), 0, tile_n, 0)
    # selector plane: 0 = masked out, 1 = exact flavor, 2 = ADC flavor —
    # padded query rows / point cols get 0, so they never win
    sel = masks.astype(jnp.float32) * (
        1.0 + jnp.asarray(flavor).astype(jnp.float32).reshape(-1, 1)
    )
    sel = _mask_plane(sel, tile_q, tile_n)
    out_d, out_i = unified_masked_topk_pallas(
        q_pad, x_pad, luts_p, codes_p, sel, k,
        metric=metric, tile_q=tile_q, tile_n=tile_n, interpret=interpret,
    )
    return _finalize_masked(out_d, out_i, q0)


# -- dedup-then-broadcast mask planes ----------------------------------------
#
# A coalesced fragment's (Q, N) mask plane is often highly redundant: most
# production batches carry only a few distinct predicates, so Q rows hold m
# << Q unique bitmasks.  The *_dedup entry points accept the factored form
# (unique_masks (m, N), row_index (Q,)) and broadcast it to the dense plane
# ON DEVICE (jnp.take inside the same jit'd region), so host→device traffic
# shrinks from Q·N to m·N + Q while the kernel and its results stay
# bit-identical to the dense *_multi call.


def expand_mask_plane(unique_masks: jnp.ndarray, row_index: jnp.ndarray) -> jnp.ndarray:
    """(m, N) unique rows + (Q,) row index -> dense (Q, N) plane (device)."""
    return jnp.take(jnp.asarray(unique_masks), jnp.asarray(row_index), axis=0)


def masked_exact_topk_dedup(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    unique_masks: jnp.ndarray,
    row_index: jnp.ndarray,
    k: int,
    *,
    metric: str = "l2",
    backend: str = "auto",
    tile_q: Optional[int] = None,
    tile_n: Optional[int] = None,
    dtype: str = "f32",
    x_scale: float = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dedup'd-plane exact top-k: semantics of ``masked_exact_topk_multi``
    on ``unique_masks[row_index]``, shipping only the unique rows."""
    plane = expand_mask_plane(unique_masks, row_index)
    return masked_exact_topk_multi(
        queries, points, plane, k,
        metric=metric, backend=backend, tile_q=tile_q, tile_n=tile_n,
        dtype=dtype, x_scale=x_scale,
    )


def masked_pq_topk_dedup(
    luts: jnp.ndarray,
    codes: jnp.ndarray,
    unique_masks: jnp.ndarray,
    row_index: jnp.ndarray,
    k: int,
    *,
    backend: str = "auto",
    tile_q: Optional[int] = None,
    tile_n: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dedup'd-plane PQ-ADC top-k: semantics of ``masked_pq_topk_multi`` on
    ``unique_masks[row_index]``, shipping only the unique rows."""
    plane = expand_mask_plane(unique_masks, row_index)
    return masked_pq_topk_multi(
        luts, codes, plane, k, backend=backend, tile_q=tile_q, tile_n=tile_n
    )


def unified_masked_topk_dedup(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    luts: jnp.ndarray,
    codes: jnp.ndarray,
    unique_masks: jnp.ndarray,
    row_index: jnp.ndarray,
    flavor: jnp.ndarray,
    k: int,
    *,
    metric: str = "l2",
    backend: str = "auto",
    tile_q: Optional[int] = None,
    tile_n: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dedup'd-plane mixed-flavor top-k: ``unified_masked_topk`` on
    ``unique_masks[row_index]``, shipping only the unique rows."""
    plane = expand_mask_plane(unique_masks, row_index)
    return unified_masked_topk(
        queries, points, luts, codes, plane, flavor, k,
        metric=metric, backend=backend, tile_q=tile_q, tile_n=tile_n,
    )


# -- pooled gather-rerank -----------------------------------------------------

def gather_rerank(
    queries: jnp.ndarray,
    points: jnp.ndarray,
    pool_ids: jnp.ndarray,
    k: int,
    *,
    metric: str = "l2",
    backend: str = "auto",
    tile_q: Optional[int] = None,
    tile_n: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-precision rerank of per-query candidate pools: (Q, D) queries ×
    (N, D) points under (Q, P) ``pool_ids`` (row q = query q's candidate ids;
    slots < 0 or >= N are sentinels) → (dists (Q, k), ids (Q, k)), ascending,
    (+inf, -1) beyond the live pool.  ``k`` may exceed P.

    This is the device replacement for the executor/graph host rerank
    (NumPy ``vectors[pool]`` gather + einsum): the kernel scores candidates
    inside the tiled scan and never materializes the (Q, P, D) gather.  It
    is also the mandatory recall guard behind the quantized (bf16/int8)
    scan flavors — their pools are re-scored here at f32 before results
    leave the executor."""
    backend = _resolve(backend)
    k = int(k)
    pids = jnp.asarray(pool_ids).astype(jnp.int32)
    n0 = points.shape[0]
    # out-of-range ids (stale pools, clipped host fills) become sentinels
    pids = jnp.where((pids < 0) | (pids >= n0), -1, pids)
    if backend == "ref":
        return ref.gather_rerank(queries, points, pids, k, metric=metric)
    tile_q, tile_n = _tiles(
        tile_q, tile_n, points.shape[0], points.shape[1], "gather_rerank"
    )
    interpret = not _on_tpu()
    q_pad, q0 = _pad_to(queries.astype(jnp.float32), 0, tile_q, 0.0)
    x_pad, _n0 = _pad_to(points.astype(jnp.float32), 0, tile_n, 0.0)
    q_pad, _ = _pad_to(q_pad, 1, 128, 0.0)
    x_pad, _ = _pad_to(x_pad, 1, 128, 0.0)
    pids_pad, _ = _pad_to(pids, 0, tile_q, -1)  # padded queries: empty pools
    pids_pad, _ = _pad_to(pids_pad, 1, 128, -1)  # pool slots pad with sentinel
    out_d, out_i = gather_rerank_pallas(
        q_pad, x_pad, pids_pad, k, metric=metric, tile_q=tile_q, tile_n=tile_n,
        interpret=interpret,
    )
    return _finalize_masked(out_d, out_i, q0)


# -- PQ ADC scan ---------------------------------------------------------------

def pq_scan(
    luts: jnp.ndarray,
    codes: jnp.ndarray,
    *,
    backend: str = "auto",
    tile_q: Optional[int] = None,
    tile_n: Optional[int] = None,
) -> jnp.ndarray:
    """ADC scores (Q, N) from per-query LUTs (Q, m, K) and codes (N, m)."""
    backend = _resolve(backend)
    if backend == "ref":
        return ref.pq_adc_scores(luts, codes)
    tile_q, tile_n = _tiles(tile_q, tile_n, codes.shape[0], codes.shape[1], "pq")
    interpret = not _on_tpu()
    luts_p, q0 = _pad_to(luts.astype(jnp.float32), 0, tile_q, 0.0)
    codes_p, n0 = _pad_to(codes.astype(jnp.int32), 0, tile_n, 0)
    out = pq_scan_pallas(
        luts_p, codes_p, tile_q=tile_q, tile_n=tile_n, interpret=interpret
    )
    return out[:q0, :n0]


def pq_scan_topk(
    luts: jnp.ndarray,
    codes: jnp.ndarray,
    k: int,
    *,
    backend: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scores = pq_scan(luts, codes, backend=backend)
    neg, idx = jax.lax.top_k(-scores, k)
    return -neg, idx


# -- k-means assignment -----------------------------------------------------------

def kmeans_assign(
    points: jnp.ndarray,
    centroids: jnp.ndarray,
    *,
    backend: str = "auto",
    tile_n: int = 256,
    tile_k: int = 128,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (assignments (N,) int32, squared distances (N,) f32)."""
    backend = _resolve(backend)
    if backend == "ref":
        return ref.kmeans_assign(points, centroids)
    interpret = not _on_tpu()
    x_pad, n0 = _pad_to(points.astype(jnp.float32), 0, tile_n, 0.0)
    # pad centroid *rows* with a huge coordinate so padded centroids lose
    c = centroids.astype(jnp.float32)
    k = c.shape[0]
    rem = (-k) % tile_k
    if rem:
        filler = jnp.full((rem, c.shape[1]), 1e18, dtype=jnp.float32)
        c = jnp.concatenate([c, filler], axis=0)
    x_pad, _ = _pad_to(x_pad, 1, 128, 0.0)
    c, _ = _pad_to(c, 1, 128, 0.0)
    idx, dist = kmeans_assign_pallas(
        x_pad, c, tile_n=tile_n, tile_k=tile_k, interpret=interpret
    )
    return idx[:n0], dist[:n0]
