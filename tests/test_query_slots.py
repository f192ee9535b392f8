"""Query slots of a traversal call (``vamana.query_slots``): a call pads its
queries to the smallest multiple of 16 that holds them, capped at 64, and
every real row's answer is bit for bit what the old fixed 64-slot padding
gave; after a graph shape's first traversal no query count compiles."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pq import build_luts
from repro.core.vamana import (
    QUERY_BATCH,
    SLOT_BUCKETS,
    VamanaParams,
    _beam_search,
    _masked_beam_search,
    build_vamana,
    query_slots,
    stream_slots,
)
from repro.kernels import device_cache, ops
from repro.serving import metrics
from repro.serving.metrics import MetricsRegistry
from conftest import clustered_vectors

K, L = 10, 32
COUNTS = [1, 15, 16, 17, 33, 48, 49, 64, 65, 130]


@pytest.mark.parametrize(
    "n, slots",
    [(0, 16), (1, 16), (15, 16), (16, 16), (17, 32), (32, 32), (33, 48), (48, 48),
     (49, 64), (64, 64), (65, 64), (130, 64)],
)
def test_query_slots_rule(n, slots):
    assert query_slots(n) == slots
    assert SLOT_BUCKETS == (16, 32, 48, 64) and QUERY_BATCH == 64


@pytest.mark.parametrize(
    "n, slots", [(0, 0), (1, 16), (64, 64), (65, 80), (113, 128), (130, 144), (192, 192)]
)
def test_stream_slots_bucket_only_the_last_chunk(n, slots):
    assert stream_slots(n) == slots


@pytest.fixture(scope="module")
def graph():
    rng = np.random.default_rng(5)
    X, _ = clustered_vectors(rng, n_clusters=12, per_cluster=100, dim=32)
    g = build_vamana(
        X, VamanaParams(R=16, L=L), passes=1, batch=128, with_pq=True, pq_m=8
    )
    g.tombstones[::97] = True  # the filters below must see some
    Q = X[rng.choice(len(X), max(COUNTS))] + 0.05 * rng.normal(
        size=(max(COUNTS), X.shape[1])
    ).astype(np.float32)
    masks = np.stack([rng.random(g.n) < 0.4, rng.random(g.n) < 0.8])
    return g, Q, masks


# -- the fixed 64-slot padding, written out --------------------------------


def _padded_chunks(Q):
    """Chunks of 64 queries, each zero-padded to 64 slots."""
    for s in range(0, len(Q), 64):
        q = Q[s : s + 64]
        yield s, len(q), np.pad(q, ((0, 64 - len(q)), (0, 0)))


def _rerank(g, qb, cand, admissible):
    """Exact rerank of each row's candidates: repeats and inadmissible ids
    become the -1 sentinel, the gather-rerank kernel scores the rest."""
    s_ids = np.sort(cand, axis=1, kind="stable")
    dup = np.concatenate([np.zeros((len(s_ids), 1), bool), s_ids[:, 1:] == s_ids[:, :-1]], 1)
    ok = admissible(s_ids) & ~dup
    pids = np.where(ok, s_ids, -1).astype(np.int32)
    rd, ri = ops.gather_rerank(
        jnp.asarray(qb), device_cache.device_vectors(g), jnp.asarray(pids), K,
        metric="l2", backend="auto",
    )
    return np.asarray(rd), np.asarray(ri, np.int64)


def _live(g, ids):
    return (ids < g.n) & ~g.tombstones[np.clip(ids, 0, g.vectors.shape[0] - 1)]


def _old_search(g, Q, use_pq):
    out_d, out_i = np.empty((len(Q), K), np.float32), np.empty((len(Q), K), np.int64)
    points = jnp.asarray(g.pq_codes.astype(np.int32) if use_pq else g.vectors)
    for s, n, qb in _padded_chunks(Q):
        x = build_luts(g.pq, qb) if use_pq else jnp.asarray(qb)
        ids, d, vis, _ = _beam_search(
            points, jnp.asarray(g.adjacency), jnp.int32(g.n), jnp.int32(g.medoid), x,
            L, int(1.3 * L) + 8, "l2", use_pq,
        )
        ids, d = np.asarray(ids), np.asarray(d)
        if use_pq:
            rd, ri = _rerank(g, qb, np.concatenate([ids, np.asarray(vis)], 1),
                             lambda i: _live(g, i))
        else:
            d = np.where(_live(g, ids), d, np.inf)
            order = np.argsort(d, axis=1)[:, :K]
            rd, ri = np.take_along_axis(d, order, 1), np.take_along_axis(ids, order, 1)
        out_d[s : s + n], out_i[s : s + n] = rd[:n], ri[:n]
    return out_d, out_i


def _old_search_masked(g, Q, masks, use_pq, depth=L):
    """64-slot padding, and the whole (m, cap) plane of distinct masks in
    every call."""
    idx = np.arange(len(Q), dtype=np.int32) % len(masks)
    out_d, out_i = np.empty((len(Q), K), np.float32), np.empty((len(Q), K), np.int64)
    points = jnp.asarray(g.pq_codes.astype(np.int32) if use_pq else g.vectors)
    mask_pad = np.zeros((len(masks), g.vectors.shape[0]), bool)
    mask_pad[:, : g.n] = masks
    for s, n, qb in _padded_chunks(Q):
        ib = np.pad(idx[s : s + 64], (0, 64 - n))
        x = build_luts(g.pq, qb) if use_pq else jnp.asarray(qb)
        res_i, res_d, vis = _masked_beam_search(
            points, jnp.asarray(g.adjacency), jnp.int32(g.n), jnp.int32(g.medoid), x,
            jnp.asarray(mask_pad), jnp.asarray(ib), depth, K, int(1.3 * depth) + 8, "l2", use_pq,
        )
        if use_pq:
            cand = np.concatenate([np.asarray(res_i), np.asarray(vis)], 1)
            d, i = _rerank(g, qb, cand, lambda s_ids: mask_pad[
                ib[:, None], np.clip(s_ids, 0, g.vectors.shape[0] - 1)] & (s_ids < g.n))
        else:
            d, i = np.asarray(res_d), np.asarray(res_i).astype(np.int64)
        out_d[s : s + n], out_i[s : s + n] = d[:n], np.where(np.isfinite(d), i, -1)[:n]
    return out_d, out_i


METHODS = {
    "search": (lambda g, Q, m: g.search(Q, K, L=L), lambda g, Q, m: _old_search(g, Q, False)),
    "search_pq": (lambda g, Q, m: g.search_pq(Q, K, L=L),
                  lambda g, Q, m: _old_search(g, Q, True)),
    "search_masked": (
        lambda g, Q, m: g.search_masked(Q, K, m, np.arange(len(Q)) % len(m), L=L),
        lambda g, Q, m: _old_search_masked(g, Q, m, False)),
    "search_masked_pq": (
        lambda g, Q, m: g.search_masked(Q, K, m, np.arange(len(Q)) % len(m), L=L, use_pq=True),
        lambda g, Q, m: _old_search_masked(g, Q, m, True)),
}


@pytest.mark.parametrize("count", COUNTS)
@pytest.mark.parametrize("method", list(METHODS))
def test_real_rows_match_the_64_slot_padding_bit_for_bit(graph, method, count):
    g, Q, masks = graph
    new, old = METHODS[method]
    d, i = new(g, Q[:count], masks)
    d_old, i_old = old(g, Q[:count], masks)
    assert d.shape == (count, K) and np.isfinite(d[:, 0]).all()
    np.testing.assert_array_equal(i, i_old)
    np.testing.assert_array_equal(d, d_old)


@pytest.mark.parametrize("method", ["search", "search_pq"])
def test_no_query_count_compiles_after_a_shapes_first_traversal(graph, method):
    g, Q, _ = graph
    depth = L + 2  # a shape no other test of the module traverses
    run = {"search": g.search, "search_pq": g.search_pq}[method]
    reg = MetricsRegistry()
    metrics.drain()
    metrics.set_tracing(reg)
    try:
        with metrics.span("first"):
            run(Q[:5], K, L=depth)
        for n in range(1, QUERY_BATCH + 1):
            with metrics.span("later"):
                run(Q[:n], K, L=depth)
    finally:
        metrics.set_tracing(None)
        metrics.drain()
    assert reg.counter_value("compiles", "first") > 0
    assert reg.counter_value("compiles", "later") == 0


@pytest.mark.parametrize("use_pq", [False, True], ids=["exact", "pq"])
def test_distinct_masks_share_one_program_per_slot_bucket(graph, use_pq):
    """``search_masked`` pads a call's distinct mask rows to its slots: calls
    of one slot bucket carrying 1, 3, 7 and 16 distinct masks run one
    program, and answer as the unpadded (m, cap) plane does."""
    g, Q, _ = graph
    depth = L + 6  # a shape no other test of the module traverses
    rng = np.random.default_rng(11)
    planes = {m: rng.random((m, g.n)) < rng.uniform(0.2, 0.9, (m, 1)) for m in (1, 3, 7, 16)}
    before = _masked_beam_search._cache_size()
    got = {
        m: g.search_masked(Q[:16], K, masks, np.arange(16) % m, L=depth, use_pq=use_pq)
        for m, masks in planes.items()
    }
    assert _masked_beam_search._cache_size() == before + 1
    for m, (d, i) in got.items():
        d_old, i_old = _old_search_masked(g, Q[:16], planes[m], use_pq, depth=depth)
        np.testing.assert_array_equal(i, i_old)
        np.testing.assert_array_equal(d, d_old)


@pytest.mark.parametrize(
    "count, calls",
    [(1, [16]), (17, [32]), (33, [48]), (49, [64]), (64, [64]), (65, [64, 16]),
     (130, [64, 64, 16])],
)
def test_each_call_runs_the_slots_its_queries_need(graph, monkeypatch, count, calls):
    from repro.core import vamana

    g, Q, _ = graph
    g.search_pq(Q[:1], K, L=L)  # the shape's warm-up, before the spy
    seen = []
    traverse = vamana._beam_search

    def spy(*args, **kw):
        seen.append(args[4].shape[0])
        return traverse(*args, **kw)

    monkeypatch.setattr(vamana, "_beam_search", spy)
    g.search_pq(Q[:count], K, L=L)
    assert seen == calls


def test_threads_first_traversing_a_shape_warm_it_once(graph, monkeypatch):
    """Executor threads search their shards at once: one of them warms the
    shape's buckets, the others wait for it and then run only their own call."""
    import sys
    import threading

    from repro.core import vamana

    g, Q, _ = graph
    depth = L + 4  # a shape no other test of the module traverses
    calls = []
    traverse = vamana._beam_search

    def spy(*args, **kw):
        calls.append(args[4].shape[0])
        return traverse(*args, **kw)

    monkeypatch.setattr(vamana, "_beam_search", spy)
    workers = 8
    results = [None] * workers

    def search(i):
        results[i] = g.search_pq(Q[i : i + 1], K, L=depth)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=search, args=(i,)) for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert sorted(calls) == sorted(list(SLOT_BUCKETS) + [16] * workers)
    for i, (d, ids) in enumerate(results):
        d_one, ids_one = g.search_pq(Q[i : i + 1], K, L=depth)
        np.testing.assert_array_equal(ids, ids_one)
