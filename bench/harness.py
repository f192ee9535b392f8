"""Run one cell: build the deployment from the seed, offer its traffic as an
open loop through ``ProbeMicroBatcher.submit``, and hold the answers to the
plain reference.

One run is one process:

1. set-up: generate the table from ``--seed``, ingest it through
   ``LakehouseTable.append_vectors``, build the index with
   ``Coordinator.create_index``, start the micro-batcher and warm up the
   cell's batch shapes (``traffic["warmup_batches"]``);
2. the window: probes are submitted at their due times (``datagen``), each
   timed from its due time to the resolution of its Future; the window closes
   ``seconds`` after it opens, and every probe due in it is waited for up to
   ``WAIT_AFTER_CLOSE_S`` past the close;
3. the reference (``reference.compare``) checks every answer of the window,
   after the program's state is freed.

With ``trace`` the window runs under the JAX profiler with the benchmark's
layer spans installed (``layers.annotate``), and the result carries the
per-layer metrics instead of the end-to-end ones.
"""

from __future__ import annotations

import contextlib
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

import cell as cell_mod
import datagen
import layers
import reference
import trace_reduce

TABLE = "bench_docs"
INDEX = "bench_idx"
WAIT_AFTER_CLOSE_S = 60.0
START_DELAY_S = 0.05  # the window opens this long after the schedule is armed
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
PERSIST_MIN_COMPILE_S = 0.5


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Backend compilations, tallied by the phase the run is in (one listener
    per process; nothing is counted while ``phase`` is None)."""

    _instance: Optional["CompileCounter"] = None

    def __init__(self) -> None:
        self.phase: Optional[str] = None
        self.count: Dict[str, int] = {}
        self.seconds: Dict[str, float] = {}
        self._lock = threading.Lock()

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            import jax

            cls._instance = cls()
            jax.monitoring.register_event_duration_secs_listener(cls._instance._on)
        return cls._instance

    def _on(self, event: str, secs: float, **_) -> None:
        phase = self.phase
        if event == BACKEND_COMPILE_EVENT and phase is not None:
            with self._lock:
                self.count[phase] = self.count.get(phase, 0) + 1
                self.seconds[phase] = self.seconds.get(phase, 0.0) + secs


def persist_compiles(on: bool) -> None:
    """Which compiled programs go to the persistent cache.

    During set-up (``on``) only programs that took ``PERSIST_MIN_COMPILE_S``
    or more to compile are written: the index build's and the traversal's
    programs, whose shapes every seed shares.  The small eager programs of
    the Stage-B rerank, whose shapes follow the candidates a seed's batches
    bring, are never written, so a run compiles the same kind of work
    whichever seeds ran before it in the checkout.  Inside the window
    (``on`` false) nothing is written, and the cache stops growing after the
    first run of a cell."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      PERSIST_MIN_COMPILE_S if on else 1e9)


@dataclass
class Window:
    latency_ms: np.ndarray  # per probe, inf where no answer
    answers: List[Optional[list]]  # per probe: ProbeHit list, or None
    lateness_ms: np.ndarray  # submit time minus due time
    seconds: float
    errors: List[str] = field(default_factory=list)


@dataclass
class RunRecord:
    """What the per-layer readers see (``bench/metrics/*.py``)."""

    cell: cell_mod.Cell
    reports: List[object]
    batches: int
    queries: int
    trace: Optional[trace_reduce.Reduced]
    kernel_calls: List[Dict]
    peaks: Optional[dict]
    work: Dict[str, object]

    def module_seconds(self, *names: str) -> float:
        if self.trace is None:
            return 0.0
        return sum(self.trace.module_s.get(n, 0.0) for n in names)


def build(cell: cell_mod.Cell, seed: int, root: str):
    """Ingest the seed's table and build the index; returns the cluster and
    the rows as written."""
    from repro.lakehouse.table import LakehouseTable
    from repro.runtime.cluster import make_local_cluster
    from repro.runtime.coordinator import IndexConfig

    cfg = cell.config
    t = time.perf_counter()
    rows = datagen.make_table(cfg, seed)
    log(f"datagen_s={time.perf_counter() - t} rows={len(rows.vectors)} dim={rows.vectors.shape[1]}")
    cluster = make_local_cluster(root, num_executors=int(cfg["executors"]))
    table = LakehouseTable(cluster.catalog, TABLE)
    table.create(dim=rows.vectors.shape[1])
    t = time.perf_counter()
    table.append_vectors(
        rows.vectors,
        num_files=int(cfg["files"]),
        rows_per_group=int(cfg["rows_per_group"]),
        attributes=rows.attributes,
    )
    log(f"ingest_s={time.perf_counter() - t}")
    ix = cfg["index"]
    t = time.perf_counter()
    rep = cluster.coordinator.create_index(TABLE, IndexConfig(
        name=INDEX, R=int(ix["R"]), L=int(ix["L"]), alpha=float(ix["alpha"]),
        pq_m=int(ix["pq_m"]), num_shards=int(ix["num_shards"]),
        build_passes=int(ix["build_passes"]), oversample=int(ix["oversample"]),
        partition_mode=ix["partition_mode"],
    ))
    log(f"build s={time.perf_counter() - t} stage0_s={rep.stage0_seconds} stage1_s={rep.stage1_seconds} "
        f"stage2_s={rep.stage2_seconds} shard_rows="
        f"{sorted(r.vector_count for r in rep.shard_results)} puffin_bytes={rep.total_bytes}")
    return cluster, rows


def submit(mb, traffic: dict, probe: datagen.Probe):
    return mb.submit(probe.query, k=probe.k, filter=datagen.predicate_sql(traffic, probe))


def warm_up(mb, cell: cell_mod.Cell, seed: int) -> None:
    """Drive the served path at each batch size the traffic lists."""
    sizes = [int(s) for s in cell.traffic["warmup_batches"]]
    probes = iter(datagen.warmup_probes(cell.config, cell.traffic, seed, sum(sizes)))
    compiles = CompileCounter.get()
    for size in sizes:
        t = time.perf_counter()
        n0, s0 = compiles.count.get("setup", 0), compiles.seconds.get("setup", 0.0)
        futures = [submit(mb, cell.traffic, next(probes)) for _ in range(size)]
        for f in futures:
            f.result(timeout=900)
        log(f"warmup batch={size} s={time.perf_counter() - t} "
            f"compiles={compiles.count.get('setup', 0) - n0} "
            f"compile_s={compiles.seconds.get('setup', 0.0) - s0}")


def drive(mb, traffic: dict, probes: List[datagen.Probe], seconds: float,
          window_span=None) -> Window:
    """Offer ``probes`` at their due times; wait for every answer."""
    n = len(probes)
    done = np.full(n, np.nan)
    submitted = np.full(n, np.nan)
    futures: List[Optional[object]] = [None] * n
    errors: List[str] = []

    def finished(i: int):
        def cb(_f) -> None:
            done[i] = time.perf_counter()
        return cb

    t0 = time.perf_counter() + START_DELAY_S
    span = window_span() if window_span is not None else None
    while time.perf_counter() < t0:
        pass
    if span is not None:
        span.__enter__()
    for i, p in enumerate(probes):
        due = t0 + p.due_s
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        submitted[i] = time.perf_counter()
        try:
            f = submit(mb, traffic, p)
        except Exception as exc:  # refused at the door: a failed probe
            errors.append(f"probe {i}: {type(exc).__name__}: {exc}")
            continue
        futures[i] = f
        f.add_done_callback(finished(i))
    close = t0 + seconds
    if close > time.perf_counter():
        time.sleep(close - time.perf_counter())
    if span is not None:
        span.__exit__(None, None, None)
    deadline = close + WAIT_AFTER_CLOSE_S
    answers: List[Optional[list]] = [None] * n
    for i, f in enumerate(futures):
        if f is None:
            continue
        try:
            answers[i] = f.result(timeout=max(0.0, deadline - time.perf_counter()))
        except Exception as exc:  # failed, or never came
            errors.append(f"probe {i}: {type(exc).__name__}: {exc}")
    due = t0 + np.array([p.due_s for p in probes])
    lat = (done - due) * 1e3
    lat[[a is None for a in answers]] = np.inf
    return Window(lat, answers, (submitted - due) * 1e3, seconds, errors)


def percentile(lat_ms: np.ndarray, q: float) -> float:
    """The ``q``-th percentile over every probe of the window (a probe with
    no answer counts as infinitely late)."""
    v = float(np.percentile(np.where(np.isfinite(lat_ms), lat_ms, 1e300), q))
    return v if v < 1e299 else math.inf


def device_info(chips: int) -> dict:
    import jax

    devices = jax.devices()[:chips]
    d0 = devices[0]
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def dispatches(cluster):
    """Masked-kernel and rerank-kernel dispatches, summed over executors."""
    return (sum(e.masked_kernel_dispatches for e in cluster.executors),
            sum(e.rerank_kernel_dispatches for e in cluster.executors))


def dir_bytes(root: str) -> int:
    total = 0
    for dirpath, _dirs, files in os.walk(root):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(dirpath, f))
            except OSError:
                pass
    return total


@contextlib.contextmanager
def deployment(cell: cell_mod.Cell, seed: int, root: str, capture: layers.Capture):
    """Set-up: the built index behind a started, warmed-up micro-batcher.
    Yields ``(cluster, rows, batcher)``."""
    from repro.serving.serve_loop import ProbeMicroBatcher

    cluster, rows = build(cell, seed, root)
    capture.wrap_probe_batch(cluster.coordinator)
    serving = cell.config["serving"]
    with ProbeMicroBatcher(
        cluster.coordinator, TABLE, max_batch=int(serving["max_batch"]),
        max_wait_s=float(serving["max_wait_s"]), use_pq=bool(serving["use_pq"]),
    ) as mb:
        warm_up(mb, cell, seed)
        yield cluster, rows, mb


def run(cell: cell_mod.Cell, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    """One run of ``cell``; returns the result object (the last line)."""
    import jax

    cfg, traffic = cell.config, cell.traffic
    compiles = CompileCounter.get()
    compiles.phase = "setup"
    persist_compiles(True)
    capture = layers.Capture()
    probes = datagen.make_probes(cfg, traffic, float(cell.params["rate_per_s"]), seconds, seed)
    log(f"cell={cell.name} seed={seed} seconds={seconds} trace={int(trace)} "
        f"rate_per_s={cell.params['rate_per_s']} probes={len(probes)}")
    tmp = tempfile.mkdtemp(prefix="bench_")
    tdir = os.path.join(tmp, "trace")
    try:
        with contextlib.ExitStack() as stack:
            if trace:
                stack.enter_context(layers.annotate(capture))
            cluster, rows, mb = stack.enter_context(
                deployment(cell, seed, os.path.join(tmp, "cluster"), capture))
            setup_s = time.perf_counter() - t_start
            log(f"setup_s={setup_s} compiles_in_setup={compiles.count.get('setup', 0)} "
                f"compile_s_in_setup={compiles.seconds.get('setup', 0.0)}")
            persist_compiles(False)
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(tdir, profiler_options=opts)
            before = (mb.stats.batches, mb.stats.queries, dispatches(cluster))
            capture.recording = True
            compiles.phase = "window"
            win = drive(mb, traffic, probes, seconds,
                        (lambda: jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN))
                        if trace else None)
            capture.recording = False
            compiles.phase = None
            batches = mb.stats.batches - before[0]
            queries = mb.stats.queries - before[1]
            if trace:
                jax.profiler.stop_trace()
            masked, rerank = (a - b for a, b in zip(dispatches(cluster), before[2]))
            failures = cluster.coordinator.scheduler.stats.failures_seen
            written = dir_bytes(tmp)
        del cluster, mb
        device = device_info(cell.chips)
        reduced = None
        if trace:
            t = time.perf_counter()
            reduced = trace_reduce.reduce_trace(trace_reduce.find_xplane(tdir), layers.SPAN_DEPTH)
            log(f"trace_reduce_s={time.perf_counter() - t}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    answered = int(sum(a is not None for a in win.answers))
    log(f"window probes={len(probes)} answered={answered} batches={batches} "
        f"queries={queries} compiles_in_window={compiles.count.get('window', 0)} "
        f"compile_s_in_window={compiles.seconds.get('window', 0.0)} scheduler_failures={failures} "
        f"generator_late_ms p50={np.percentile(win.lateness_ms, 50)} "
        f"max={np.max(win.lateness_ms)} bytes_written={written}")
    # also in traced runs, where the result line carries per-layer metrics:
    # traced against untraced latency is the tracing's cost
    log(f"latency p50_ms={percentile(win.latency_ms, 50)} p95_ms={percentile(win.latency_ms, 95)}")
    for e in win.errors[:5]:
        log(f"error {e}")
    op_kinds: Dict[str, int] = {}
    for r in capture.reports:
        for row in (r.plan.ops if r.plan is not None else []):
            for op in row.values():
                op_kinds[type(op).__name__] = op_kinds.get(type(op).__name__, 0) + 1
    log(f"planned_ops={op_kinds} masked_kernel_dispatches={masked} "
        f"rerank_kernel_dispatches={rerank} masked_beam_rows="
        f"{sum(r.masked_beam_rows for r in capture.reports)}")
    if capture.kernel_calls:
        calls: Dict[str, int] = {}
        for c in capture.kernel_calls:
            calls[c["op"]] = calls.get(c["op"], 0) + 1
        log(f"kernel_calls={calls}")

    t = time.perf_counter()
    locate = reference.row_locator(len(rows.vectors), int(cfg["files"]), int(cfg["rows_per_group"]))
    answers = [
        None if a is None else [
            (locate(h.file_path, h.row_group, h.row_offset), float(h.distance)) for h in a
        ]
        for a in win.answers
    ]
    flt = traffic.get("filter")
    attr = rows.attributes[flt["column"]] if flt else None
    limits = dict(cell.params["limits"])
    limits["recall_at_10"] = float(cfg["guarantees"]["recall_at_10_min"])
    checks = reference.compare(rows.vectors, attr, probes, answers, limits)
    correct = reference.passed(checks)
    log(f"reference_s={time.perf_counter() - t}")

    failed = len(probes) - answered
    metrics: Dict[str, Dict] = {}
    if not trace:
        values = {
            "probe_p50_ms": percentile(win.latency_ms, 50),
            "probe_p95_ms": percentile(win.latency_ms, 95),
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            v = values[m["name"]]
            if math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        record = RunRecord(
            cell=cell, reports=list(capture.reports), batches=batches, queries=queries,
            trace=reduced, kernel_calls=list(capture.kernel_calls),
            peaks=cell_mod.peaks(device["kind"]), work=cell_mod.work_counters(),
        )
        for m in cell.per_layer:
            v = cell_mod.reader(m["name"])(record)
            if v is None:
                log(f"per-layer metric {m['name']}: nothing to read in this run")
                continue
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    result = {"correct": correct, "attempted": len(probes), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": [list(x) for x in reduced.top_ops],
                               "idle_gaps": [list(x) for x in reduced.idle_gaps]}
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]} for k, c in checks.items()}
    for name, c in checks.items():
        print(f"check {name} {c['value']} {c['op']} {c['limit']}", file=sys.stderr, flush=True)
    return result
