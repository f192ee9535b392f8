"""Program spans and the compile counter (``repro.serving.metrics``): how
spans nest across the scheduler's attempt threads, self time, compile
attribution, the off path, ``ProbeReport``'s stage times, the micro-batcher's
queue wait, and the clock shared with the JAX profiler's trace."""

from __future__ import annotations

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import metrics
from repro.serving.metrics import MetricsRegistry


@pytest.fixture()
def registry():
    """Tracing on for one test, with an empty log; off and empty after."""
    reg = MetricsRegistry()
    metrics.drain()
    metrics.set_tracing(reg)
    try:
        yield reg
    finally:
        metrics.set_tracing(None)
        metrics.drain()


def _by_id(spans):
    return {s["span_id"]: s for s in spans}


def _probe(cluster, n=6, seed=1):
    c, _t, X, _centers, _rep = cluster
    rng = np.random.default_rng(seed)
    q = X[rng.choice(len(X), n, replace=False)] + 0.01
    return c.coordinator.probe_batch("emb", q, 5, strategy="diskann")


def test_spans_nest_and_share_the_roots_trace_id(registry):
    with metrics.span("outer", trace_id=7, probes=3):
        with metrics.span("middle"):
            with metrics.span("inner", k=10):
                pass
        with metrics.span("sibling"):
            pass
    with metrics.span("second_root"):
        pass
    spans = {s["name"]: s for s in metrics.drain()}
    assert spans["outer"]["parent_id"] is None
    assert spans["middle"]["parent_id"] == spans["outer"]["span_id"]
    assert spans["inner"]["parent_id"] == spans["middle"]["span_id"]
    assert spans["sibling"]["parent_id"] == spans["outer"]["span_id"]
    assert {spans[n]["trace_id"] for n in ("outer", "middle", "inner", "sibling")} == {7}
    assert spans["second_root"]["trace_id"] is None
    assert spans["outer"]["attrs"] == {"probes": 3}
    assert spans["inner"]["attrs"] == {"k": 10}
    o, i = spans["outer"], spans["inner"]
    assert o["start_ns"] <= i["start_ns"] <= i["end_ns"] <= o["end_ns"]
    assert metrics.drain() == []


def test_executor_spans_name_their_wave_across_attempt_threads(registry, built_cluster):
    with metrics.span("serving.batch", trace_id=41):
        _probe(built_cluster)
    spans = metrics.drain()
    by_id = _by_id(spans)
    tasks = [s for s in spans if s["name"] == "executor.task"]
    assert len(tasks) >= 2
    for t in tasks:
        wave = by_id[t["parent_id"]]
        assert wave["name"] == "scheduler.wave"
        assert t["thread"] != wave["thread"]  # an attempt thread
        assert wave["start_ns"] <= t["start_ns"] <= t["end_ns"] <= wave["end_ns"]
        assert t["attrs"]["executor"].startswith("ex-")
        assert t["attrs"]["kind"] in ("BatchProbe", "Rerank")
    kinds = {t["attrs"]["kind"] for t in tasks}
    assert kinds == {"BatchProbe", "Rerank"}
    waves = [s for s in spans if s["name"] == "scheduler.wave"]
    assert len(waves) == 2
    assert all(w["attrs"]["tasks"] >= 1 for w in waves)
    assert {by_id[w["parent_id"]]["name"] for w in waves} == {
        "coordinator.stage_a", "coordinator.stage_b"}
    stages = [s for s in spans if s["name"].startswith("coordinator.stage_")]
    probe_batch = next(s for s in spans if s["name"] == "coordinator.probe_batch")
    assert {by_id[s["parent_id"]]["name"] for s in stages} == {"coordinator.probe_batch"}
    merge = next(s for s in spans if s["name"] == "coordinator.merge")
    assert by_id[merge["parent_id"]]["name"] == "coordinator.stage_b"
    assert by_id[probe_batch["parent_id"]]["name"] == "serving.batch"
    # every span of the batch carries the root's trace id, on every thread
    assert {s["trace_id"] for s in spans} == {41}
    # Stage A and Stage B work is named inside each task
    names = {by_id[s["parent_id"]]["name"] + ">" + s["name"]
             for s in spans if s["parent_id"] in by_id}
    assert {"executor.task>executor.load_shard", "executor.task>traversal.search",
            "executor.task>executor.candidates", "executor.task>executor.rerank.read",
            "executor.task>executor.rerank.score",
            "executor.task>executor.rerank.emit"} <= names


def _span(sid, parent, start, end):
    return {"span_id": sid, "parent_id": parent, "start_ns": start, "end_ns": end}


@pytest.mark.parametrize(
    "children, expected",
    [
        ([], 100),
        ([(10, 20)], 90),
        ([(10, 30), (20, 40)], 70),  # overlapping children count once
        ([(10, 30), (50, 60)], 70),
        ([(-5, 10), (95, 120)], 85),  # clipped to the parent
        ([(10, 20), (12, 18)], 90),  # one inside another
    ],
)
def test_self_time_is_duration_minus_union_of_children(children, expected):
    parent = _span(1, None, 0, 100)
    kids = [_span(10 + i, 1, 0 + a, 0 + b) for i, (a, b) in enumerate(children)]
    grandchild = _span(99, 10, 0, 100)  # not a direct child: ignored
    unrelated = _span(98, 5, 0, 100)
    assert metrics.self_ns(parent, [parent, *kids, grandchild, unrelated]) == expected


def test_a_compile_inside_a_span_is_counted_on_it(registry):
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    with metrics.span("outer"):
        with metrics.span("compiling"):
            f(jnp.ones((7, 3), jnp.float32)).block_until_ready()
        f(jnp.ones((7, 3), jnp.float32)).block_until_ready()  # cached: no compile
    spans = {s["name"]: s for s in metrics.drain()}
    assert spans["compiling"]["compiles"] >= 1
    assert spans["compiling"]["compile_s"] > 0.0
    assert spans["outer"]["compiles"] == 0  # the innermost open span takes it
    assert registry.counter_value("compiles", "compiling") == spans["compiling"]["compiles"]
    assert registry.counter_value("compile_s", "compiling") == pytest.approx(
        spans["compiling"]["compile_s"])
    assert registry.counter_value("compiles", "outer") == 0


def test_tracing_off_logs_nothing_and_registers_nothing(built_cluster, monkeypatch):
    from jax._src import monitoring

    assert metrics._registry is None  # off by default
    entered = []

    def annotation(name):
        entered.append(name)
        raise AssertionError("TraceAnnotation entered with tracing off")

    monkeypatch.setattr(metrics, "_annotation", annotation)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    metrics.drain()
    assert metrics.span("x") is metrics.span("y", a=1)  # one shared null context
    with metrics.timed("t") as t:
        pass
    assert t.seconds >= 0.0
    rep = _probe(built_cluster, seed=2)
    jax.jit(lambda x: x - 2.0)(jnp.ones((5, 11))).block_until_ready()
    assert metrics.drain() == []
    assert entered == []
    assert metrics._on_compile not in monitoring._event_duration_secs_listeners
    assert rep.stage_a_seconds > 0.0 and rep.stage_b_seconds > 0.0
    # switching on and off again leaves no listener behind
    metrics.set_tracing(MetricsRegistry())
    try:
        assert metrics._on_compile in monitoring._event_duration_secs_listeners
    finally:
        metrics.set_tracing(None)
    assert metrics._on_compile not in monitoring._event_duration_secs_listeners


def test_traversal_spans_carry_queries_and_slots_and_count_the_padding(registry, built_cluster):
    from repro.core.vamana import query_slots

    _probe(built_cluster, n=21, seed=4)
    spans = [s for s in metrics.drain() if s["name"].startswith("traversal.search")]
    assert spans
    for s in spans:
        queries, slots = s["attrs"]["queries"], s["attrs"]["slots"]
        assert 1 <= queries <= 21
        assert slots == query_slots(queries)
    padded = sum(s["attrs"]["slots"] - s["attrs"]["queries"] for s in spans)
    assert padded > 0
    assert registry.counter_value("traversal.padded_slots") == padded


@pytest.fixture(scope="module")
def filtered_cluster(tmp_path_factory):
    """One 1,200-row shard with a uniform int ``price`` column."""
    from repro.lakehouse.table import LakehouseTable
    from repro.runtime.cluster import make_local_cluster
    from repro.runtime.coordinator import IndexConfig

    rng = np.random.default_rng(19)
    c = make_local_cluster(str(tmp_path_factory.mktemp("filtered")), num_executors=1)
    t = LakehouseTable(c.catalog, "emb")
    t.create(dim=16)
    X = rng.normal(size=(1200, 16)).astype(np.float32)
    price = rng.integers(0, 100, len(X)).astype(np.int64)
    t.append_vectors(X, num_files=2, rows_per_group=300, attributes={"price": price})
    c.coordinator.create_index("emb", IndexConfig(
        name="idx", num_shards=1, R=16, L=32, pq_m=4, build_passes=1))
    return c, X


def test_masked_traversal_spans_and_counters(registry, filtered_cluster, monkeypatch):
    """Per-probe bands on a shard the planner treats as too large to scan:
    each probe's mask is built under ``executor.predicate_mask``, the
    MaskedBeam rows share one ``traversal.search_masked`` span carrying
    ``queries``, ``slots`` and ``masks``, and the ``masked_beam.*`` counters
    agree with the ``ProbeReport``."""
    from repro.core.vamana import query_slots
    from repro.runtime import planner

    monkeypatch.setattr(planner, "EXACT_SCAN_MAX_ROWS", 512)
    c, X = filtered_cluster
    rng = np.random.default_rng(6)
    n = 21
    los = rng.choice(80, n, replace=False)
    rep = c.coordinator.probe_batch(
        "emb", X[rng.choice(len(X), n, replace=False)] + 0.01, 5, strategy="diskann",
        filter=[f"price >= {lo} AND price < {lo + 20}" for lo in los])
    spans = metrics.drain()
    by_id = _by_id(spans)
    masks = [s for s in spans if s["name"] == "executor.predicate_mask"]
    assert len(masks) == n
    assert {by_id[s["parent_id"]]["name"] for s in masks} == {"executor.task"}
    (trav,) = [s for s in spans if s["name"] == "traversal.search_masked"]
    assert by_id[trav["parent_id"]]["name"] == "executor.task"
    assert trav["attrs"] == {"queries": n, "slots": query_slots(n), "masks": n}
    assert rep.masked_beam_rows == n
    assert registry.counter_value("masked_beam.rows") == rep.masked_beam_rows
    assert registry.counter_value("masked_beam.fallbacks") == rep.masked_beam_fallbacks
    assert registry.counter_value("traversal.padded_slots") == query_slots(n) - n


def test_padded_slots_are_not_counted_with_tracing_off(built_cluster):
    reg = MetricsRegistry()
    metrics.set_tracing(reg)
    metrics.set_tracing(None)
    metrics.count("traversal.padded_slots", 5)
    _probe(built_cluster, n=3, seed=5)
    assert reg.snapshot() == {}
    assert metrics.drain() == []


def test_probe_report_stage_times_are_the_spans_durations(registry, built_cluster):
    rep = _probe(built_cluster, seed=3)
    spans = {s["name"]: s for s in metrics.drain()}

    def seconds(name):
        return (spans[name]["end_ns"] - spans[name]["start_ns"]) / 1e9

    assert rep.stage_a_seconds == seconds("coordinator.stage_a")
    assert rep.stage_b_seconds == seconds("coordinator.stage_b")
    assert rep.stage_c_seconds == seconds("coordinator.stage_c")
    a, b, c = (spans[f"coordinator.stage_{x}"] for x in "abc")
    assert a["end_ns"] <= b["start_ns"] and b["end_ns"] <= c["start_ns"]


def test_micro_batcher_fills_queue_wait_and_numbers_its_batches(registry, built_cluster):
    from repro.serving.serve_loop import ProbeMicroBatcher

    c, _t, X, _centers, _rep = built_cluster
    with ProbeMicroBatcher(c.coordinator, "emb", strategy="diskann", max_batch=4,
                           max_wait_s=0.005) as mb:
        futures = [mb.submit(X[i] + 0.01, k=5) for i in range(10)]
        for f in futures:
            assert len(f.result(timeout=120)) == 5
    wait = mb.metrics.histogram("serving.queue_wait_ms", "default")
    assert wait.count == 10
    assert 0.0 <= wait.percentile(0) <= wait.percentile(100)
    latency = mb.metrics.histogram("latency_ms", "default")
    assert wait.total <= latency.total  # the wait is a part of each probe's latency
    spans = metrics.drain()
    batches = [s for s in spans if s["name"] == "serving.batch"]
    assert sum(b["attrs"]["probes"] for b in batches) == 10
    assert sorted(b["trace_id"] for b in batches) == list(range(1, len(batches) + 1))
    assert all(b["attrs"]["k"] == 5 and b["attrs"]["queue_wait_ms"] >= 0.0 for b in batches)
    by_id = _by_id(spans)
    for s in spans:
        if s["name"] == "executor.task":
            root = s
            while root["parent_id"] is not None:
                root = by_id[root["parent_id"]]
            assert root["name"] == "serving.batch"
            assert s["trace_id"] == root["trace_id"]


def test_logged_spans_start_with_their_profiler_annotations(registry, built_cluster, tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with metrics.span("serving.batch", trace_id=1):
            _probe(built_cluster, seed=4)
    finally:
        jax.profiler.stop_trace()
    spans = metrics.drain()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True))[-1]
    data = ProfileData.from_file(path)
    env = next(p for p in data.planes if p.name == "Task Environment")
    t0 = dict(env.stats)["profile_start_time"]
    names = {s["name"] for s in spans}
    traced = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        traced.setdefault(ev.name, []).append(t0 + ev.start_ns)
    assert len(names) >= 10
    for name in names:
        logged = sorted(s["start_ns"] for s in spans if s["name"] == name)
        found = sorted(traced.get(name, []))
        assert len(found) == len(logged), name
        for a, b in zip(logged, found):
            assert abs(a - b) < 1_000_000, (name, a - b)


def test_span_log_is_bounded(registry, monkeypatch):
    from collections import deque

    monkeypatch.setattr(metrics, "_log", deque(maxlen=3))
    for _ in range(10):
        with metrics.span("s"):
            pass
    assert len(metrics.drain()) == 3
    assert registry.counter_value("spans_dropped") == 7
