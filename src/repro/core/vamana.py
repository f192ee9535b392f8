"""Vamana / DiskANN graph index (paper §2.2, §5–§7) — JAX-accelerated.

TPU adaptation (DESIGN.md §2): the graph lives as a **dense padded adjacency**
``int32 (N, R)`` (−1 padding) instead of SSD-resident varint lists; beam
search is a fully-jittable ``lax.while_loop`` over a fixed-size candidate
pool, so the probe path can run *on device* inside a shard_map'd serving
step.  Graph construction keeps DiskANN's batch-parallel structure: batched
beam searches + batched robust-prune (both jit'd), with only the variable-
degree reverse-edge scatter on host.

Entry points:
- :func:`build_vamana`      — full build (random init + 2 refinement passes)
- :meth:`VamanaGraph.search`        — batched beam search (full precision)
- :meth:`VamanaGraph.search_pq`     — beam search with PQ ADC distances and
  exact rerank of the pool (the paper's Stage-A probe)
- :meth:`VamanaGraph.search_masked` — predicate-aware beam search: masked
  nodes are traversed for connectivity but never admitted to the result set
  (the filtered-DiskANN move behind the ``MaskedBeam`` plan op)
- :meth:`VamanaGraph.insert_batch`  — greedy insert (§7.2 refresh)
- :meth:`VamanaGraph.tombstone`     — lazy deletes (§7.3)
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pq import PQCodebook, build_luts, encode


@dataclass
class VamanaParams:
    R: int = 64  # max degree
    L: int = 100  # beam width / pool size
    alpha: float = 1.2  # RNG pruning slack
    metric: str = "l2"  # l2 | ip

    def to_props(self) -> dict:
        return {"R": str(self.R), "L": str(self.L), "alpha": str(self.alpha), "metric": self.metric}


# queries per traversal call of the search methods (each batch also costs
# one gather_rerank call on the PQ paths)
QUERY_BATCH = 64
# a call's query slots come in multiples of two f32 sublane tiles
SLOT_STEP = 16
SLOT_BUCKETS = tuple(range(SLOT_STEP, QUERY_BATCH + 1, SLOT_STEP))


def query_slots(n: int) -> int:
    """Query slots of one traversal call carrying ``n`` queries: the smallest
    multiple of ``SLOT_STEP`` that holds them, capped at ``QUERY_BATCH``.  A
    padded slot does a real row's work at every step, so a call runs only
    the slots its queries need, from the few ``SLOT_BUCKETS`` shapes."""
    return min(QUERY_BATCH, -(-max(int(n), 1) // SLOT_STEP) * SLOT_STEP)


def stream_slots(n: int) -> int:
    """Query slots the traversal calls of an ``n``-query stream run: chunks of
    ``QUERY_BATCH``, the last of them bucketed by :func:`query_slots`."""
    full, rest = divmod(int(n), QUERY_BATCH)
    return full * QUERY_BATCH + (query_slots(rest) if rest else 0)


def _pad_rows(a: np.ndarray, rows: int) -> np.ndarray:
    pad = rows - a.shape[0]
    return np.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) if pad else a


def _in_chunks(queries: np.ndarray, k: int, traverse):
    """Run ``traverse`` over ``queries`` in chunks of ``QUERY_BATCH`` rows
    and stack its (dists (rows, k), ids (rows, k)) answers."""
    out_d = np.empty((queries.shape[0], k), np.float32)
    out_i = np.empty((queries.shape[0], k), np.int64)
    for s in range(0, queries.shape[0], QUERY_BATCH):
        e = s + QUERY_BATCH
        out_d[s:e], out_i[s:e] = traverse(queries[s:e])
    return out_d, out_i


# shapes whose buckets have compiled: process-wide, as JAX's own cache of
# compiled programs is.  Executor threads first search their shards at once:
# one lock a shape, so shards of one shape warm it once and shards of other
# shapes warm theirs alongside
_warm_lock = threading.Lock()
_shape_locks: dict = {}
_warm_shapes: set = set()


def _warm_slot_buckets(shape: tuple, run) -> None:
    """The first time the process traverses a graph of ``shape`` (every
    static argument and array shape of the call's programs), ``run(slots)``
    traverses zero queries at each of ``SLOT_BUCKETS``: each bucket's
    programs compile here, and no later call of that shape compiles."""
    if shape in _warm_shapes:
        return
    with _warm_lock:
        lock = _shape_locks.setdefault(shape, threading.Lock())
    with lock:
        if shape in _warm_shapes:
            return
        for slots in SLOT_BUCKETS:
            run(slots)
        _warm_shapes.add(shape)


# ---------------------------------------------------------------------------
# jit'd primitives.  All take padded fixed shapes; `n_valid` bounds real ids.
# ---------------------------------------------------------------------------


def _pair_dist(q: jnp.ndarray, v: jnp.ndarray, metric: str) -> jnp.ndarray:
    """q: (..., D), v: (..., D) -> (...)"""
    if metric == "ip":
        return -jnp.sum(q * v, axis=-1)
    diff = q - v
    return jnp.sum(diff * diff, axis=-1)


def _adc_dist(luts: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """PQ asymmetric distances: luts (B, m, K) f32, codes (B, R, m) int ->
    (B, R) ``sum_j luts[b, j, codes[b, r, j]]``.

    The TPU has no efficient per-lane gather (``take_along_axis`` over the
    LUTs reads a whole tile per element), so each code selects its LUT entry
    by a compare with ``iota(K)`` and one reduction over (m, K) sums them:
    XLA fuses it without materialising the (B, R, m, K) one-hot.  The
    selected f32 entries are exact; only the order of the sum over m differs
    from the gather's."""
    hit = codes[..., None] == jnp.arange(luts.shape[-1], dtype=codes.dtype)
    return jnp.sum(jnp.where(hit, luts[:, None], 0.0), axis=(-1, -2))


def _dedupe_sorted_by_id(ids, dists, expanded):
    """Mark duplicate ids invalid.  Inputs already sorted by id asc with
    expanded entries first within a run (so the surviving copy keeps its
    expansion status)."""
    dup = jnp.concatenate(
        [jnp.zeros_like(ids[:, :1], dtype=bool), ids[:, 1:] == ids[:, :-1]], axis=1
    )
    dists = jnp.where(dup, jnp.inf, dists)
    expanded = jnp.where(dup, True, expanded)  # never expand a dup
    return ids, dists, expanded


@functools.partial(jax.jit, static_argnames=("L", "max_iters", "metric", "use_pq"))
def _beam_search(
    vectors: jnp.ndarray,  # (cap, D) f32   (or PQ codes (cap, m) int32 if use_pq)
    adjacency: jnp.ndarray,  # (cap, R) int32, -1 pad
    n_valid: jnp.ndarray,  # () int32
    entry: jnp.ndarray,  # () int32
    queries: jnp.ndarray,  # (B, D) f32     (or LUTs (B, m, K) f32 if use_pq)
    L: int,
    max_iters: int,
    metric: str,
    use_pq: bool,
):
    """Batched greedy beam search.

    Returns (pool_ids (B,L), pool_dists (B,L), visited_ids (B,max_iters),
    visited_dists (B,max_iters)).  Invalid slots: id == cap, dist == +inf.
    """
    cap = vectors.shape[0]
    B = queries.shape[0]
    INF = jnp.float32(jnp.inf)

    def dist_to(ids: jnp.ndarray) -> jnp.ndarray:  # ids (B, K) -> (B, K)
        safe = jnp.clip(ids, 0, cap - 1)
        if use_pq:
            d = _adc_dist(queries, vectors[safe])  # code rows (B, K, m)
        else:
            v = vectors[safe]  # (B, K, D)
            d = _pair_dist(queries[:, None, :], v, metric)
        return jnp.where(ids < n_valid, d, INF)

    # multi-entry seeding: the medoid plus three strided nodes.  Costs three
    # extra expansions but makes search robust to weakly-connected regions
    # (single-pass builds on clustered data can leave islands the medoid
    # alone never reaches).
    n_seeds = min(4, L)
    strides = jnp.arange(n_seeds, dtype=jnp.int32)
    seeds = jnp.where(
        strides == 0, entry, (strides * (n_valid // jnp.int32(n_seeds))) % jnp.maximum(n_valid, 1)
    )
    pool_ids = jnp.full((B, L), cap, jnp.int32).at[:, :n_seeds].set(
        jnp.broadcast_to(seeds, (B, n_seeds))
    )
    d0 = dist_to(pool_ids[:, :n_seeds])
    pool_dists = jnp.full((B, L), INF).at[:, :n_seeds].set(d0)
    pool_exp = jnp.ones((B, L), bool).at[:, :n_seeds].set(False)
    visited_ids = jnp.full((B, max_iters), cap, jnp.int32)
    visited_dists = jnp.full((B, max_iters), INF)

    def has_frontier(state):
        _, dists, exp, *_ = state
        return jnp.any(~exp & jnp.isfinite(dists))

    def cond(state):
        return has_frontier(state) & (state[-1] < max_iters)

    def body(state):
        ids, dists, exp, vis_ids, vis_dists, it = state
        frontier = jnp.where(~exp & jnp.isfinite(dists), dists, INF)
        best = jnp.argmin(frontier, axis=1)  # (B,)
        row = jnp.arange(B)
        best_id = ids[row, best]
        best_dist = dists[row, best]
        active = jnp.isfinite(frontier[row, best])  # row still has frontier
        exp = exp.at[row, best].set(True)
        vis_ids = vis_ids.at[row, it].set(jnp.where(active, best_id, cap))
        vis_dists = vis_dists.at[row, it].set(jnp.where(active, best_dist, INF))
        nbrs = adjacency[jnp.clip(best_id, 0, cap - 1)]  # (B, R)
        nbrs = jnp.where((nbrs >= 0) & active[:, None], nbrs, cap)
        nd = dist_to(nbrs)
        # merge pool + neighbors
        cat_ids = jnp.concatenate([ids, nbrs], axis=1)
        cat_dists = jnp.concatenate([dists, nd], axis=1)
        cat_exp = jnp.concatenate([exp, jnp.zeros_like(nbrs, bool)], axis=1)
        # sort by (id asc, expanded first) to dedupe: key = id*2 + (1-expanded)
        key = cat_ids * 2 + (1 - cat_exp.astype(jnp.int32))
        order = jnp.argsort(key, axis=1)
        cat_ids = jnp.take_along_axis(cat_ids, order, axis=1)
        cat_dists = jnp.take_along_axis(cat_dists, order, axis=1)
        cat_exp = jnp.take_along_axis(cat_exp, order, axis=1)
        cat_ids, cat_dists, cat_exp = _dedupe_sorted_by_id(cat_ids, cat_dists, cat_exp)
        # keep top-L by distance
        order = jnp.argsort(cat_dists, axis=1)[:, :L]
        ids = jnp.take_along_axis(cat_ids, order, axis=1)
        dists = jnp.take_along_axis(cat_dists, order, axis=1)
        exp = jnp.take_along_axis(cat_exp, order, axis=1)
        return ids, dists, exp, vis_ids, vis_dists, it + 1

    state = (pool_ids, pool_dists, pool_exp, visited_ids, visited_dists, jnp.int32(0))
    ids, dists, _exp, vis_ids, vis_dists, _ = jax.lax.while_loop(cond, body, state)
    return ids, dists, vis_ids, vis_dists


@functools.partial(
    jax.jit, static_argnames=("L", "k_res", "max_iters", "metric", "use_pq")
)
def _masked_beam_search(
    vectors: jnp.ndarray,  # (cap, D) f32   (or PQ codes (cap, m) int32 if use_pq)
    adjacency: jnp.ndarray,  # (cap, R) int32, -1 pad
    n_valid: jnp.ndarray,  # () int32
    entry: jnp.ndarray,  # () int32
    queries: jnp.ndarray,  # (B, D) f32     (or LUTs (B, m, K) f32 if use_pq)
    mask_unique: jnp.ndarray,  # (B, cap) bool — True = admissible; unused rows all-false
    mask_idx: jnp.ndarray,  # (B,) int32 — query row -> mask row
    L: int,
    k_res: int,
    max_iters: int,
    metric: str,
    use_pq: bool,
):
    """Predicate-aware batched beam search (the filtered-DiskANN traversal).

    The frontier expands exactly like :func:`_beam_search` — masked nodes
    keep their connectivity role, their distances steer the pool — and every
    (id, dist) the traversal evaluates is buffered; after the loop ONE
    mask-gated admit pass (neutralize inadmissible, dedupe by id, top-k_res
    by distance) builds the admitted result set.  Hoisting the admit out of
    the loop matters: an in-loop accumulator costs two extra argsorts per
    iteration, which is what let the unmasked postfilter beam win the
    paired bench timing.  The admitted SET is identical either way — an
    in-loop accumulator would only ever see these same candidates.  The
    mask ships dedup'd: ``mask_unique`` holds the
    distinct admissibility rows, ``mask_idx`` maps each query to its row
    (the PR 5 dedup-then-broadcast shape — the (B, cap) plane is expanded by
    gather on device, never materialized on host).  Callers pad the distinct
    rows to the call's B slots, so the number of distinct masks never names
    a new program.

    Returns (res_ids (B, k_res), res_dists (B, k_res), vis_ids
    (B, max_iters)).  Result rows ascend by distance; slots the traversal
    could not fill hold (id == cap, dist == +inf).
    """
    cap = vectors.shape[0]
    B = queries.shape[0]
    INF = jnp.float32(jnp.inf)

    def dist_to(ids: jnp.ndarray) -> jnp.ndarray:  # ids (B, K) -> (B, K)
        safe = jnp.clip(ids, 0, cap - 1)
        if use_pq:
            d = _adc_dist(queries, vectors[safe])  # code rows (B, K, m)
        else:
            v = vectors[safe]  # (B, K, D)
            d = _pair_dist(queries[:, None, :], v, metric)
        return jnp.where(ids < n_valid, d, INF)

    R = adjacency.shape[1]

    n_seeds = min(4, L)
    strides = jnp.arange(n_seeds, dtype=jnp.int32)
    seeds = jnp.where(
        strides == 0, entry, (strides * (n_valid // jnp.int32(n_seeds))) % jnp.maximum(n_valid, 1)
    )
    pool_ids = jnp.full((B, L), cap, jnp.int32).at[:, :n_seeds].set(
        jnp.broadcast_to(seeds, (B, n_seeds))
    )
    d0 = dist_to(pool_ids[:, :n_seeds])
    pool_dists = jnp.full((B, L), INF).at[:, :n_seeds].set(d0)
    pool_exp = jnp.ones((B, L), bool).at[:, :n_seeds].set(False)
    visited_ids = jnp.full((B, max_iters), cap, jnp.int32)
    # every (id, dist) the traversal evaluates, buffered for the single
    # post-loop admit pass: one (B, R) slab per iteration
    cand_ids = jnp.full((B, max_iters, R), cap, jnp.int32)
    cand_dists = jnp.full((B, max_iters, R), INF)

    def cond(state):
        _, dists, exp, _, _, _, it = state
        return jnp.any(~exp & jnp.isfinite(dists)) & (it < max_iters)

    def body(state):
        ids, dists, exp, vis_ids, c_ids, c_dists, it = state
        frontier = jnp.where(~exp & jnp.isfinite(dists), dists, INF)
        best = jnp.argmin(frontier, axis=1)  # (B,)
        row = jnp.arange(B)
        best_id = ids[row, best]
        active = jnp.isfinite(frontier[row, best])
        exp = exp.at[row, best].set(True)
        vis_ids = vis_ids.at[row, it].set(jnp.where(active, best_id, cap))
        nbrs = adjacency[jnp.clip(best_id, 0, cap - 1)]  # (B, R)
        nbrs = jnp.where((nbrs >= 0) & active[:, None], nbrs, cap)
        nd = dist_to(nbrs)
        c_ids = c_ids.at[:, it, :].set(nbrs)
        c_dists = c_dists.at[:, it, :].set(nd)
        cat_ids = jnp.concatenate([ids, nbrs], axis=1)
        cat_dists = jnp.concatenate([dists, nd], axis=1)
        cat_exp = jnp.concatenate([exp, jnp.zeros_like(nbrs, bool)], axis=1)
        key = cat_ids * 2 + (1 - cat_exp.astype(jnp.int32))
        order = jnp.argsort(key, axis=1)
        cat_ids = jnp.take_along_axis(cat_ids, order, axis=1)
        cat_dists = jnp.take_along_axis(cat_dists, order, axis=1)
        cat_exp = jnp.take_along_axis(cat_exp, order, axis=1)
        cat_ids, cat_dists, cat_exp = _dedupe_sorted_by_id(cat_ids, cat_dists, cat_exp)
        order = jnp.argsort(cat_dists, axis=1)[:, :L]
        ids = jnp.take_along_axis(cat_ids, order, axis=1)
        dists = jnp.take_along_axis(cat_dists, order, axis=1)
        exp = jnp.take_along_axis(cat_exp, order, axis=1)
        return ids, dists, exp, vis_ids, c_ids, c_dists, it + 1

    state = (
        pool_ids,
        pool_dists,
        pool_exp,
        visited_ids,
        cand_ids,
        cand_dists,
        jnp.int32(0),
    )
    _, _, _, vis_ids, cand_ids, cand_dists, _ = jax.lax.while_loop(cond, body, state)

    # the ONE admit pass: seeds ∪ every buffered neighbor offer, gated by the
    # query's mask row, deduped by id (same id ⇒ same distance, so either
    # copy may survive), top-k_res by distance.  Inadmissible candidates are
    # neutralized to (cap, +inf) so they can never displace an admitted node.
    all_ids = jnp.concatenate(
        [
            jnp.broadcast_to(seeds, (B, n_seeds)),
            cand_ids.reshape(B, max_iters * R),
        ],
        axis=1,
    )
    all_d = jnp.concatenate([d0, cand_dists.reshape(B, max_iters * R)], axis=1)
    if all_ids.shape[1] < k_res:  # static: keep the output width at k_res
        pad = k_res - all_ids.shape[1]
        all_ids = jnp.pad(all_ids, ((0, 0), (0, pad)), constant_values=cap)
        all_d = jnp.pad(all_d, ((0, 0), (0, pad)), constant_values=jnp.inf)
    safe = jnp.clip(all_ids, 0, cap - 1)
    ok = mask_unique[mask_idx[:, None], safe] & (all_ids < n_valid)
    all_ids = jnp.where(ok, all_ids, cap)
    all_d = jnp.where(ok, all_d, INF)
    order = jnp.argsort(all_ids, axis=1)
    s_ids = jnp.take_along_axis(all_ids, order, axis=1)
    s_d = jnp.take_along_axis(all_d, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros_like(s_ids[:, :1], bool), s_ids[:, 1:] == s_ids[:, :-1]],
        axis=1,
    )
    s_d = jnp.where(dup, INF, s_d)
    order = jnp.argsort(s_d, axis=1)[:, :k_res]
    res_ids = jnp.take_along_axis(s_ids, order, axis=1)
    res_dists = jnp.take_along_axis(s_d, order, axis=1)
    return res_ids, res_dists, vis_ids


@functools.partial(jax.jit, static_argnames=("R", "alpha", "metric"))
def _robust_prune(
    vectors: jnp.ndarray,  # (cap, D)
    p_vecs: jnp.ndarray,  # (B, D) the points being pruned
    cand_ids: jnp.ndarray,  # (B, C) candidate ids (cap = invalid)
    n_valid: jnp.ndarray,
    R: int,
    alpha: float,
    metric: str,
):
    """Vectorized α-RNG robust prune.  Returns (B, R) int32, -1 padded."""
    cap, D = vectors.shape
    B, C = cand_ids.shape
    safe = jnp.clip(cand_ids, 0, cap - 1)
    cand_vecs = vectors[safe]  # (B, C, D)
    valid = cand_ids < n_valid
    d_p = jnp.where(valid, _pair_dist(p_vecs[:, None, :], cand_vecs, metric), jnp.inf)
    # dedupe identical ids: sort by id, invalidate repeats
    order = jnp.argsort(cand_ids, axis=1)
    s_ids = jnp.take_along_axis(cand_ids, order, axis=1)
    s_dp = jnp.take_along_axis(d_p, order, axis=1)
    s_vecs = jnp.take_along_axis(cand_vecs, order[:, :, None], axis=1)
    dup = jnp.concatenate(
        [jnp.zeros_like(s_ids[:, :1], bool), s_ids[:, 1:] == s_ids[:, :-1]], axis=1
    )
    s_dp = jnp.where(dup, jnp.inf, s_dp)
    alive = jnp.isfinite(s_dp)

    result = jnp.full((B, R), -1, jnp.int32)

    def body(step, carry):
        alive, result = carry
        masked = jnp.where(alive, s_dp, jnp.inf)
        pick = jnp.argmin(masked, axis=1)  # (B,)
        row = jnp.arange(B)
        ok = jnp.isfinite(masked[row, pick])
        pick_id = s_ids[row, pick]
        result = result.at[:, step].set(jnp.where(ok, pick_id, -1))
        pvec = s_vecs[row, pick]  # (B, D)
        d_star = _pair_dist(pvec[:, None, :], s_vecs, metric)  # (B, C)
        # l2: d_star is 0 at the pick, so the pick removes itself.  ip: the
        # scores are negative, so alpha > 1 prunes more rather than less, and
        # the pick removes itself only where alpha * -|pick|^2 <= its own
        # score (ROADMAP queue 2.8)
        kill = alpha * d_star <= s_dp
        alive = alive & ~kill & ok[:, None]
        return alive, result

    _, result = jax.lax.fori_loop(0, R, body, (alive, result))
    return result


# ---------------------------------------------------------------------------
# Graph object (host-resident arrays; device work via the jit'd primitives)
# ---------------------------------------------------------------------------


def _round_capacity(n: int) -> int:
    cap = 1024
    while cap < n:
        cap *= 2
    return cap


@dataclass
class VamanaGraph:
    vectors: np.ndarray  # (cap, D) f32; rows >= n are padding
    adjacency: np.ndarray  # (cap, R) int32, -1 pad
    n: int
    medoid: int
    params: VamanaParams
    tombstones: np.ndarray = field(default=None)  # (cap,) bool
    pq: Optional[PQCodebook] = None
    pq_codes: Optional[np.ndarray] = None  # (cap, m) uint8

    def __post_init__(self):
        if self.tombstones is None:
            self.tombstones = np.zeros(self.vectors.shape[0], dtype=bool)

    # -- stats ---------------------------------------------------------------
    @property
    def dim(self) -> int:
        return self.vectors.shape[1]

    @property
    def num_live(self) -> int:
        return int(self.n - self.tombstones[: self.n].sum())

    @property
    def tombstone_ratio(self) -> float:
        return float(self.tombstones[: self.n].sum() / max(self.n, 1))

    def degrees(self) -> np.ndarray:
        return (self.adjacency[: self.n] >= 0).sum(axis=1)

    # -- search ---------------------------------------------------------------
    def search(
        self,
        queries: np.ndarray,
        k: int,
        L: Optional[int] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Full-precision beam search.  Returns (dists (Q,k), ids (Q,k));
        tombstoned nodes traversed but filtered (paper §7.3)."""
        L = max(L or self.params.L, k)
        queries = np.ascontiguousarray(queries, dtype=np.float32)
        max_iters = int(1.3 * L) + 8
        vectors_j = jnp.asarray(self.vectors)
        adj_j = jnp.asarray(self.adjacency)

        def traverse(q):
            qb = _pad_rows(q, query_slots(q.shape[0]))
            ids, dists, _, _ = _beam_search(
                vectors_j,
                adj_j,
                jnp.int32(self.n),
                jnp.int32(self.medoid),
                jnp.asarray(qb),
                L,
                max_iters,
                self.params.metric,
                False,
            )
            return self._filter_topk(np.asarray(ids), np.asarray(dists), k, q.shape[0])

        _warm_slot_buckets(
            ("search", self.vectors.shape, self.adjacency.shape, L, self.params.metric),
            lambda slots: traverse(np.zeros((slots, self.dim), np.float32)),
        )
        return _in_chunks(queries, k, traverse)

    def _filter_topk(self, ids_np, dists_np, k: int, rows: int):
        """Lazy-tombstone filter of a traversal's pools, then each of the
        first ``rows`` rows' ``k`` nearest: (dists, ids)."""
        ids_np, dists_np = ids_np[:rows], dists_np[:rows]
        ts = self.tombstones[np.clip(ids_np, 0, self.vectors.shape[0] - 1)]
        dists_np = np.where(ts | (ids_np >= self.n), np.inf, dists_np)
        order = np.argsort(dists_np, axis=1)[:, :k]
        return (
            np.take_along_axis(dists_np, order, axis=1),
            np.take_along_axis(ids_np, order, axis=1),
        )

    def search_pq(
        self,
        queries: np.ndarray,
        k: int,
        L: Optional[int] = None,
        rerank: bool = True,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Stage-A probe: PQ-approximate traversal + full-precision rerank of
        the candidate pool (paper §6)."""
        if self.pq is None or self.pq_codes is None:
            raise ValueError("graph has no PQ data; call attach_pq()")
        L = max(L or self.params.L, k)
        queries = np.ascontiguousarray(queries, dtype=np.float32)
        max_iters = int(1.3 * L) + 8
        codes_j = jnp.asarray(self.pq_codes.astype(np.int32))
        adj_j = jnp.asarray(self.adjacency)

        def traverse(q):
            rows = q.shape[0]
            qb = _pad_rows(q, query_slots(rows))
            luts = build_luts(self.pq, qb)  # (B, m, K)
            ids, dists, vis_ids, _vis_d = _beam_search(
                codes_j,
                adj_j,
                jnp.int32(self.n),
                jnp.int32(self.medoid),
                luts,
                L,
                max_iters,
                self.params.metric,
                True,
            )
            ids_np = np.asarray(ids)
            if not rerank:
                return self._filter_topk(ids_np, np.asarray(dists), k, rows)
            # DiskANN-style rerank: every *visited* node's full vector is
            # already paged in during traversal, so the exact rerank runs
            # over pool ∪ visited, not just the final PQ-ranked pool —
            # this is what keeps recall high when PQ noise exceeds the
            # within-cluster distance gaps.  Duplicates, out-of-range ids
            # and tombstones all fold to the pid=-1 sentinel; the
            # gather-rerank kernel (kernels/rerank.py) scores the rest
            # on-device — no (B, C, D) host gather.
            from repro.kernels import device_cache, ops

            cand = np.concatenate([ids_np, np.asarray(vis_ids)], axis=1)
            sort_idx = np.argsort(cand, axis=1, kind="stable")
            sorted_ids = np.take_along_axis(cand, sort_idx, axis=1)
            dup = np.concatenate(
                [
                    np.zeros((cand.shape[0], 1), bool),
                    sorted_ids[:, 1:] == sorted_ids[:, :-1],
                ],
                axis=1,
            )
            safe = np.clip(sorted_ids, 0, self.vectors.shape[0] - 1)
            bad = dup | (sorted_ids >= self.n) | self.tombstones[safe]
            pids = np.where(bad, -1, sorted_ids).astype(np.int32)
            rd, ri = ops.gather_rerank(
                jnp.asarray(qb),
                device_cache.device_vectors(self),
                jnp.asarray(pids),
                k,
                metric=self.params.metric,
                backend="auto",
            )
            return np.asarray(rd)[:rows], np.asarray(ri, np.int64)[:rows]

        # the rerank's points are the graph's first n rows
        _warm_slot_buckets(
            ("search_pq", self.n, self.vectors.shape, self.pq_codes.shape,
             self.pq.codebook.shape, self.adjacency.shape, L, k, rerank, self.params.metric),
            lambda slots: traverse(np.zeros((slots, self.dim), np.float32)),
        )
        return _in_chunks(queries, k, traverse)

    def search_masked(
        self,
        queries: np.ndarray,
        k: int,
        unique_masks: np.ndarray,
        mask_idx: Optional[np.ndarray] = None,
        L: Optional[int] = None,
        batch: int = QUERY_BATCH,
        use_pq: bool = False,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Predicate-aware beam search (the filtered-DiskANN traversal).

        The traversal expands *through* masked nodes — connectivity is never
        lost to the predicate — but only mask-passing nodes are admitted to
        the returned top-``k``.  ``unique_masks`` is ``(m, n)`` bool over
        graph ids (True = admissible; the caller folds tombstones in —
        admissibility means *predicate AND NOT tombstoned*); ``mask_idx``
        maps each query to its mask row (default: all queries share row 0).
        A traversal call uploads only its own chunk's distinct rows, padded
        with all-false rows to the call's ``query_slots``: one program per
        slot bucket, however many distinct predicates a call carries.
        With ``use_pq`` the traversal runs on ADC distances and the admitted
        pool ∪ admissible visited nodes get a full-precision host rerank.

        Unlike :meth:`search`, ``L`` is NOT floored at ``k``: the admitted
        result set is built from every neighbor the traversal evaluates
        (not from the final pool), so a wide admit target ``k`` rides a
        beam of ordinary depth.  Flooring the depth at the planner-widened
        ``k`` would make the masked traversal as expensive as the
        1/frac-deepened postfilter pool it exists to beat.

        Returns (dists (Q, k), ids (Q, k)), each row ascending; slots the
        traversal could not fill hold ``(+inf, -1)`` — the masked-op
        sentinel contract, so callers detect under-delivery and fall back to
        the exact masked scan.
        """
        k = int(k)
        L = int(L) if L is not None else self.params.L
        queries = np.ascontiguousarray(queries, dtype=np.float32)
        Q = queries.shape[0]
        cap = self.vectors.shape[0]
        unique_masks = np.asarray(unique_masks, dtype=bool)
        if unique_masks.ndim == 1:
            unique_masks = unique_masks[None, :]
        width = min(unique_masks.shape[1], cap)
        idx_np = (
            np.zeros(Q, np.int32)
            if mask_idx is None
            else np.asarray(mask_idx, np.int32)
        )
        out_d = np.full((Q, k), np.inf, np.float32)
        out_i = np.full((Q, k), -1, np.int64)
        max_iters = int(1.3 * L) + 8
        if use_pq:
            if self.pq is None or self.pq_codes is None:
                raise ValueError("graph has no PQ data; call attach_pq()")
            codes_j = jnp.asarray(self.pq_codes.astype(np.int32))
        else:
            vecs_j = jnp.asarray(self.vectors)
        adj_j = jnp.asarray(self.adjacency)
        batch = min(int(batch), QUERY_BATCH)  # a call runs at most QUERY_BATCH slots
        for s in range(0, Q, batch):
            q = queries[s : s + batch]
            slots = query_slots(q.shape[0])
            qb = _pad_rows(q, slots)
            # the chunk's own mask rows, padded with all-false rows to its
            # slots: one program per slot bucket, whatever the number of
            # distinct masks; padded query slots point at row 0
            rows, ib = np.unique(idx_np[s : s + batch], return_inverse=True)
            ib = _pad_rows(ib.astype(np.int32), slots)
            plane = np.zeros((slots, cap), dtype=bool)
            plane[: len(rows), :width] = unique_masks[rows, :width]
            masks_j = jnp.asarray(plane)
            if use_pq:
                luts = build_luts(self.pq, qb)
                res_i, _res_d, vis_i = _masked_beam_search(
                    codes_j,
                    adj_j,
                    jnp.int32(self.n),
                    jnp.int32(self.medoid),
                    luts,
                    masks_j,
                    jnp.asarray(ib),
                    L,
                    k,
                    max_iters,
                    self.params.metric,
                    True,
                )
                # full-precision rerank over admitted pool ∪ admissible
                # visited nodes (their vectors are already paged in during
                # traversal, same as search_pq's rerank): inadmissible rows
                # fold to pid=-1 and the gather-rerank kernel scores the
                # rest on-device
                from repro.kernels import device_cache, ops

                cand = np.concatenate([np.asarray(res_i), np.asarray(vis_i)], axis=1)
                sort_idx = np.argsort(cand, axis=1, kind="stable")
                s_ids = np.take_along_axis(cand, sort_idx, axis=1)
                safe = np.clip(s_ids, 0, cap - 1)
                adm = plane[ib[:, None], safe] & (s_ids < self.n)
                dup = np.concatenate(
                    [
                        np.zeros((cand.shape[0], 1), bool),
                        s_ids[:, 1:] == s_ids[:, :-1],
                    ],
                    axis=1,
                )
                adm &= ~dup
                pids = np.where(adm, s_ids, -1).astype(np.int32)
                rd, ri = ops.gather_rerank(
                    jnp.asarray(qb),
                    device_cache.device_vectors(self),
                    jnp.asarray(pids),
                    k,
                    metric=self.params.metric,
                    backend="auto",
                )
                dists_np = np.asarray(rd)
                ids_np = np.asarray(ri, np.int64)
            else:
                res_i, res_d, _vis = _masked_beam_search(
                    vecs_j,
                    adj_j,
                    jnp.int32(self.n),
                    jnp.int32(self.medoid),
                    jnp.asarray(qb),
                    masks_j,
                    jnp.asarray(ib),
                    L,
                    k,
                    max_iters,
                    self.params.metric,
                    False,
                )
                dists_np = np.asarray(res_d)
                ids_np = np.asarray(res_i).astype(np.int64)
            ids_np = np.where(np.isfinite(dists_np), ids_np, -1)
            out_d[s : s + q.shape[0]] = dists_np[: q.shape[0]]
            out_i[s : s + q.shape[0]] = ids_np[: q.shape[0]]
        return out_d, out_i

    # -- mutation -----------------------------------------------------------------
    def _ensure_capacity(self, extra: int) -> None:
        need = self.n + extra
        cap = self.vectors.shape[0]
        if need <= cap:
            return
        new_cap = _round_capacity(need)
        self.vectors = np.concatenate(
            [self.vectors, np.zeros((new_cap - cap, self.dim), np.float32)]
        )
        self.adjacency = np.concatenate(
            [self.adjacency, np.full((new_cap - cap, self.params.R), -1, np.int32)]
        )
        self.tombstones = np.concatenate([self.tombstones, np.zeros(new_cap - cap, bool)])
        if self.pq_codes is not None:
            self.pq_codes = np.concatenate(
                [self.pq_codes, np.zeros((new_cap - cap, self.pq.m), np.uint8)]
            )

    def insert_batch(self, new_vectors: np.ndarray, batch: int = 64) -> np.ndarray:
        """Greedy insert (paper §7.2): beam search from medoid → robust prune
        → bidirectional edges → re-prune over-degree neighbors.
        Returns the assigned ids."""
        new_vectors = np.ascontiguousarray(new_vectors, dtype=np.float32)
        count = new_vectors.shape[0]
        self._ensure_capacity(count)
        ids = np.arange(self.n, self.n + count, dtype=np.int64)
        self.vectors[self.n : self.n + count] = new_vectors
        if self.pq is not None:
            self.pq_codes[self.n : self.n + count] = encode(self.pq, new_vectors)
        # keep n at pre-insert value during search so new points are invisible
        p = self.params
        max_iters = int(1.3 * p.L) + 8
        for s in range(0, count, batch):
            stop = min(s + batch, count)
            q = new_vectors[s:stop]
            pad = batch - q.shape[0]
            qb = np.pad(q, ((0, pad), (0, 0))) if pad else q
            pool_ids, pool_dists, vis_ids, vis_dists = _beam_search(
                jnp.asarray(self.vectors),
                jnp.asarray(self.adjacency),
                jnp.int32(self.n),
                jnp.int32(self.medoid),
                jnp.asarray(qb),
                p.L,
                max_iters,
                p.metric,
                False,
            )
            cand = jnp.concatenate([pool_ids, vis_ids], axis=1)
            nbrs = _robust_prune(
                jnp.asarray(self.vectors),
                jnp.asarray(qb),
                cand,
                jnp.int32(self.n),
                p.R,
                p.alpha,
                p.metric,
            )
            nbrs_np = np.asarray(nbrs)[: stop - s]
            batch_ids = ids[s:stop]
            self.adjacency[batch_ids] = nbrs_np
            self._add_reverse_edges(batch_ids, nbrs_np)
        self.n += count
        return ids

    def _add_reverse_edges(self, src_ids: np.ndarray, nbrs: np.ndarray) -> None:
        """Host-side scatter of reverse edges with robust-prune on overflow."""
        overflow: dict[int, list[int]] = {}
        for sid, row in zip(src_ids, nbrs):
            for nbr in row:
                if nbr < 0:
                    continue
                adj = self.adjacency[nbr]
                slot = np.flatnonzero(adj < 0)
                if sid in adj:
                    continue
                if len(slot):
                    adj[slot[0]] = sid
                else:
                    overflow.setdefault(int(nbr), []).append(int(sid))
        if overflow:
            self._reprune_nodes(overflow)

    def _reprune_nodes(self, overflow: dict) -> None:
        """Batch robust-prune nodes whose degree exceeded R.

        Shapes are bucketed (C to a multiple of 32, node count to the next
        power of two) so `_robust_prune` compiles only a handful of times
        over an entire build instead of once per batch.
        """
        p = self.params
        nodes = np.array(sorted(overflow.keys()), dtype=np.int64)
        max_extra = max(len(v) for v in overflow.values())
        C = p.R + max(32, 32 * int(np.ceil(max_extra / 32)))
        cap = self.vectors.shape[0]
        n_pad = 1 << int(np.ceil(np.log2(max(len(nodes), 1))))
        cand = np.full((n_pad, C), cap, dtype=np.int32)
        max_id = int(nodes.max())
        for i, node in enumerate(nodes):
            cur = self.adjacency[node]
            cur = cur[cur >= 0]
            extras = np.array(overflow[int(node)], dtype=np.int32)
            allc = np.concatenate([cur.astype(np.int32), extras])[:C]
            cand[i, : len(allc)] = allc
            if len(allc):
                max_id = max(max_id, int(allc.max()))
        p_vecs = np.zeros((n_pad, self.dim), np.float32)
        p_vecs[: len(nodes)] = self.vectors[nodes]
        # validity bound must cover mid-insert ids (>= self.n): their vectors
        # are already written, and excluding them silently drops every
        # reverse edge into a dense region (zero-reachability inserts)
        pruned = _robust_prune(
            jnp.asarray(self.vectors),
            jnp.asarray(p_vecs),
            jnp.asarray(cand),
            jnp.int32(max(self.n, max_id + 1)),
            p.R,
            p.alpha,
            p.metric,
        )
        self.adjacency[nodes] = np.asarray(pruned)[: len(nodes)]

    def tombstone(self, ids: np.ndarray) -> None:
        self.tombstones[np.asarray(ids, dtype=np.int64)] = True

    def attach_pq(self, pq: PQCodebook, codes: Optional[np.ndarray] = None) -> None:
        self.pq = pq
        if codes is None:
            codes = encode(pq, self.vectors[: self.n])
        full = np.zeros((self.vectors.shape[0], pq.m), np.uint8)
        full[: self.n] = codes[: self.n]
        self.pq_codes = full


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------


def _medoid(vectors: np.ndarray) -> int:
    mean = vectors.mean(axis=0, keepdims=True)
    d = np.sum((vectors - mean) ** 2, axis=1)
    return int(np.argmin(d))


def build_vamana(
    vectors: np.ndarray,
    params: VamanaParams = VamanaParams(),
    *,
    seed: int = 0,
    passes: int = 2,
    batch: int = 64,
    with_pq: bool = False,
    pq_m: Optional[int] = None,
    pq_nbits: int = 8,
) -> VamanaGraph:
    """Batch-parallel Vamana build.

    1. random R-regular init;
    2. ``passes`` refinement sweeps (first at α=1.0, last at α=params.alpha,
       per the DiskANN two-pass schedule): for every point, beam-search the
       current graph, robust-prune the visited set into its new neighbor
       list, then scatter reverse edges.
    """
    vectors = np.ascontiguousarray(vectors, dtype=np.float32)
    n, d = vectors.shape
    if n == 0:
        raise ValueError("empty build")
    rng = np.random.default_rng(seed)
    cap = _round_capacity(n)
    padded = np.zeros((cap, d), np.float32)
    padded[:n] = vectors
    adjacency = np.full((cap, params.R), -1, np.int32)
    if n > 1:
        for i in range(n):  # random init, self-loop free
            deg = min(params.R, n - 1)
            choices = rng.choice(n - 1, size=deg, replace=False)
            choices = choices + (choices >= i)
            adjacency[i, :deg] = choices
    graph = VamanaGraph(
        vectors=padded,
        adjacency=adjacency,
        n=n,
        medoid=_medoid(vectors),
        params=params,
    )
    max_iters = int(1.3 * params.L) + 8
    order = rng.permutation(n)
    for p_idx in range(passes):
        alpha = 1.0 if p_idx < passes - 1 else params.alpha
        for s in range(0, n, batch):
            sel = order[s : s + batch]
            q = vectors[sel]
            pad = batch - q.shape[0]
            qb = np.pad(q, ((0, pad), (0, 0))) if pad else q
            pool_ids, _pd, vis_ids, _vd = _beam_search(
                jnp.asarray(graph.vectors),
                jnp.asarray(graph.adjacency),
                jnp.int32(n),
                jnp.int32(graph.medoid),
                jnp.asarray(qb),
                params.L,
                max_iters,
                params.metric,
                False,
            )
            cand = np.concatenate([np.asarray(pool_ids), np.asarray(vis_ids)], axis=1)
            # a point must not select itself
            cand = np.where(cand == np.pad(sel, (0, pad))[:, None], cap, cand)
            nbrs = _robust_prune(
                jnp.asarray(graph.vectors),
                jnp.asarray(qb),
                jnp.asarray(cand),
                jnp.int32(n),
                params.R,
                alpha,
                params.metric,
            )
            nbrs_np = np.asarray(nbrs)[: len(sel)]
            graph.adjacency[sel] = nbrs_np
            graph._add_reverse_edges(sel, nbrs_np)
    if with_pq:
        m = pq_m if pq_m is not None else max(1, d // 16)
        from repro.core.pq import train_pq

        pq = train_pq(vectors, m=m, nbits=pq_nbits)
        graph.attach_pq(pq)
    return graph


def brute_force_topk(
    vectors: np.ndarray, queries: np.ndarray, k: int, metric: str = "l2"
) -> Tuple[np.ndarray, np.ndarray]:
    """Exact ground truth for recall measurements."""
    from repro.kernels import ops

    d, i = ops.exact_topk(
        jnp.asarray(queries, jnp.float32), jnp.asarray(vectors, jnp.float32), k, metric=metric, backend="ref"
    )
    return np.asarray(d), np.asarray(i)


def recall_at_k(result_ids: np.ndarray, truth_ids: np.ndarray) -> float:
    hits = 0
    for r, t in zip(result_ids, truth_ids):
        hits += len(set(int(x) for x in r) & set(int(x) for x in t))
    return hits / truth_ids.size
