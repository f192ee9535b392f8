"""Smoke run of the snapshot-bound probe path on a TPU.

    python chip_smoke.py              # one chip: the served coordinator/executor path
    python chip_smoke.py --chips 4    # four chips: the sharded device probe only

One chip: a lakehouse table of clustered 768-d f32 vectors with an int
``price`` attribute, a 4-shard Puffin-backed Vamana index built through
``Coordinator.create_index``, and a few batches of probes through the entry
points a user calls (``ProbeMicroBatcher.submit``, ``Coordinator.probe_batch``):
unfiltered at k=10 and k=100, filtered at ~1% and ~30% selectivity, the PQ
traversal, an append served by the fresh-tail scan, and ``refresh_index``.
Every answer is checked against a NumPy brute-force oracle: recall@10, the
predicate on every filtered hit, and every returned distance against a
float64 recomputation.

Four chips: four Vamana shards built with ``build_vamana`` and packed into a
``DeviceAnnIndex`` with one shard per chip, probed by the ``shard_map``
Stage-A/C probe of ``serving/device_index.py`` and compared with the merged
per-shard host search and the brute-force oracle.

Lines before the last are smoke readings, not benchmark results.  The last
line is one JSON object: ``{"ok": true, "device": {...}}``.  The script exits
non-zero, without that line, when the default JAX device is not a TPU or any
check fails.  Data comes from ``--seed``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

DIM = 768
ONE_CHIP_ROWS_PER_SHARD = 65536
FOUR_CHIP_ROWS_PER_SHARD = 32768
N_QUERIES = 64
N_CLUSTERS = 256
LOCAL_DIM = 16  # intrinsic dimension of each cluster's spread
PRICE_RANGE = 10_000
FILTERS = {"filtered_1pct": "price < 100", "filtered_30pct": "price < 3000"}
APPEND_ROWS = 8192  # > the 4,096-row exact-scan cap: two tail row groups
MIN_RECALL = 0.90
DIST_RTOL = 1e-3
TABLE = "smoke_docs"
INDEX = "smoke_idx"


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def cache_entries(cache_dir: str) -> int:
    """Files in the persistent compilation cache (each write adds some)."""
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


# -- data and oracle -----------------------------------------------------------


class Corpus:
    """Clustered vectors with low intrinsic dimension (as real embeddings
    have): ``center + z @ basis + noise`` with ``z`` in ``LOCAL_DIM`` dims.

    Centers spread about as far as the points around them, so clusters
    touch, as in real embeddings.  Far-apart clusters would leave PQ's 256
    codes per subspace naming little but the cluster, and the PQ traversal
    could not rank a cluster's members."""

    def __init__(self, seed: int, dim: int) -> None:
        self.rng = np.random.default_rng(seed)
        self.centers = self.rng.standard_normal((N_CLUSTERS, dim), dtype=np.float32)
        self.basis = (self.rng.standard_normal((LOCAL_DIM, dim)) / 4).astype(np.float32)

    def vectors(self, n: int) -> np.ndarray:
        c = self.rng.integers(0, N_CLUSTERS, n)
        z = self.rng.standard_normal((n, LOCAL_DIM), dtype=np.float32)
        x = self.centers[c] + z @ self.basis
        x += 0.1 * self.rng.standard_normal(x.shape, dtype=np.float32)
        return x

    def prices(self, n: int) -> np.ndarray:
        return self.rng.integers(0, PRICE_RANGE, n).astype(np.int64)


def brute_force(X: np.ndarray, Q: np.ndarray, k: int, passing=None) -> np.ndarray:
    """Exact top-k row indices per query (NumPy, independent of the system)."""
    d = (Q * Q).sum(1)[:, None] - 2.0 * (Q @ X.T) + (X * X).sum(1)[None, :]
    if passing is not None:
        d[:, ~passing] = np.inf
    idx = np.argpartition(d, k, axis=1)[:, :k]
    order = np.argsort(np.take_along_axis(d, idx, axis=1), axis=1)
    return np.take_along_axis(idx, order, axis=1)


class Snapshot:
    """The table's rows as the oracle sees them, keyed by row location."""

    def __init__(self, table) -> None:
        self.X, locs = table.scan_vectors()
        self.price = np.asarray(table.scan_attributes(["price"])["price"], np.int64)
        self.row = {(l.file_path, l.row_group_id, l.row_offset): i for i, l in enumerate(locs)}

    def rows(self, hits) -> np.ndarray:
        return np.array([self.row[(h.file_path, h.row_group, h.row_offset)] for h in hits])


def check_answers(name, snap, Q, hits, k, passing=None, predicate=None) -> dict:
    """Recall@10 against the oracle, the predicate on every hit, and every
    returned distance against a float64 recomputation."""
    truth = brute_force(snap.X, Q, k, passing)
    recalls, worst_rel = [], 0.0
    for qi, row_hits in enumerate(hits):
        if len(row_hits) != k:
            raise AssertionError(f"{name}: query {qi} got {len(row_hits)} hits, want {k}")
        rows = snap.rows(row_hits)
        if predicate is not None and not predicate(snap.price[rows]).all():
            raise AssertionError(f"{name}: query {qi} returned a row failing its filter")
        got = np.array([h.distance for h in row_hits], np.float64)
        want = ((snap.X[rows].astype(np.float64) - Q[qi].astype(np.float64)) ** 2).sum(1)
        worst_rel = max(worst_rel, float(np.max(np.abs(got - want) / np.maximum(want, 1.0))))
        top = min(10, k)
        recalls.append(len(set(rows[:top]) & set(truth[qi, :top])) / top)
    recall = float(np.mean(recalls))
    if recall < MIN_RECALL:
        raise AssertionError(f"{name}: recall@10 {recall:.4f} < {MIN_RECALL}")
    if worst_rel > DIST_RTOL:
        raise AssertionError(f"{name}: distance off by {worst_rel:.2e} relative (> {DIST_RTOL})")
    return {"recall@10": recall, "max_dist_rel_err": worst_rel}


# -- one chip: the served coordinator/executor path ------------------------------


def run_one_chip(seed: int, rows_per_shard: int, dim: int = DIM) -> None:
    from repro.kernels import ops
    from repro.lakehouse.table import LakehouseTable
    from repro.runtime.cluster import make_local_cluster
    from repro.runtime.coordinator import IndexConfig
    from repro.serving.serve_loop import ProbeMicroBatcher

    n_rows = 4 * rows_per_shard
    log(f"rows={n_rows} dim={dim} shards=4 rows_per_shard~{rows_per_shard} queries={N_QUERIES}")
    log(
        "backends: build kmeans=ref stage_a_kernels="
        f"{ops._resolve('auto')} stage_b_rerank=ref (ref = jnp programs XLA runs on the device)"
    )
    corpus = Corpus(seed, dim)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        cluster = make_local_cluster(root, num_executors=4)
        coord = cluster.coordinator
        table = LakehouseTable(cluster.catalog, TABLE)
        table.create(dim=dim)
        t = time.perf_counter()
        table.append_vectors(
            corpus.vectors(n_rows), num_files=16,
            attributes={"price": corpus.prices(n_rows)},
        )
        log(f"ingest_s={time.perf_counter() - t}")

        cfg = IndexConfig(
            name=INDEX, R=64, L=100, alpha=1.2, pq_m=48, num_shards=4, build_passes=1,
        )
        rep = coord.create_index(TABLE, cfg)
        log(
            f"build stage0_s={rep.stage0_seconds} stage1_s={rep.stage1_seconds} "
            f"stage2_s={rep.stage2_seconds} shard_rows="
            f"{sorted(r.vector_count for r in rep.shard_results)} "
            f"puffin_bytes={rep.total_bytes} passes={cfg.build_passes} "
            f"batch={cfg.build_batch}"
        )
        snap = Snapshot(table)

        def timed(name, fn, check):
            t0 = time.perf_counter()
            fn()
            cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            out = fn()
            warm = time.perf_counter() - t0
            info = check(out)
            log(f"phase={name} first_call_s={cold} warm_s={warm} " + " ".join(
                f"{key}={val}" for key, val in info.items()
            ))

        Q = corpus.vectors(N_QUERIES)

        def served():
            with ProbeMicroBatcher(coord, TABLE, max_batch=N_QUERIES, use_pq=False) as mb:
                futures = [mb.submit(q, k=10) for q in Q]
                return [f.result(timeout=900) for f in futures]

        timed("served_k10", served, lambda hits: check_answers("served_k10", snap, Q, hits, 10))

        def batch(k, **kw):
            return lambda: coord.probe_batch(TABLE, Q, k, **kw)

        def report_check(name, k, passing=None, predicate=None):
            def check(rep):
                info = check_answers(name, snap, Q, rep.hits, k, passing, predicate)
                info.update(
                    plan=rep.filter_plan or "-", dispatches=rep.kernel_dispatches,
                    mbeam_rows=rep.masked_beam_rows, mbeam_fallbacks=rep.masked_beam_fallbacks,
                    tail_rows=rep.tail_rows,
                )
                return info
            return check

        timed("batch_k100", batch(100, use_pq=False), report_check("batch_k100", 100))
        for name, where in FILTERS.items():
            bound = int(where.split("<")[1])
            passing = snap.price < bound
            log(f"{name}: filter '{where}' passes {passing.mean()} of rows")
            timed(
                name, batch(10, use_pq=False, filter=where),
                report_check(name, 10, passing, lambda p, b=bound: p < b),
            )
        timed("pq_k10", batch(10, use_pq=True), report_check("pq_k10", 10))

        table.append_vectors(
            corpus.vectors(APPEND_ROWS), num_files=2, file_prefix="fresh",
            attributes={"price": corpus.prices(APPEND_ROWS)},
        )
        snap = Snapshot(table)
        tail_check = report_check("fresh_tail", 10)

        def tail_served(rep):
            if rep.tail_rows != APPEND_ROWS:
                raise AssertionError(f"fresh_tail: tail served {rep.tail_rows} rows")
            return tail_check(rep)

        timed("fresh_tail", batch(10, use_pq=False), tail_served)
        rr = coord.refresh_index(TABLE, INDEX)
        log(f"refresh_index inserted={rr.inserted} seconds={rr.seconds}")
        timed("refreshed", batch(10, use_pq=False), report_check("refreshed", 10))

        failures = coord.scheduler.stats.failures_seen
        masked = sum(ex.masked_kernel_dispatches for ex in cluster.executors)
        rerank = sum(ex.rerank_kernel_dispatches for ex in cluster.executors)
        log(f"failures_seen={failures} masked_kernel_dispatches={masked} "
            f"rerank_kernel_dispatches={rerank}")
        if failures:
            raise AssertionError(f"scheduler saw {failures} task failures")
        if masked == 0 or rerank == 0:
            raise AssertionError("a kernel family never dispatched")


# -- four chips: the sharded device probe -------------------------------------------


def run_four_chips(seed: int, rows_per_shard: int, dim: int = DIM, L: int = 100) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.vamana import VamanaParams, build_vamana
    from repro.launch.mesh import make_debug_mesh
    from repro.serving.device_index import DeviceAnnIndex, make_probe_fn

    devices = jax.devices()
    if len(devices) < 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found {len(devices)}")
    corpus = Corpus(seed, dim)
    shards = [corpus.vectors(rows_per_shard) for _ in range(4)]
    log(f"rows={4 * rows_per_shard} dim={dim} shards=4 (one per chip) queries={N_QUERIES}")
    params = VamanaParams(R=64, L=L, alpha=1.2)

    def build(i):
        with jax.default_device(devices[i]):
            return build_vamana(shards[i], params, passes=1, batch=128, seed=i)

    t = time.perf_counter()
    with ThreadPoolExecutor(max_workers=4) as pool:
        graphs = list(pool.map(build, range(4)))
    log(f"build_s={time.perf_counter() - t} (4 shards, one thread and chip each)")

    mesh = make_debug_mesh(data=4)
    payloads = [np.arange(i * rows_per_shard, (i + 1) * rows_per_shard) for i in range(4)]
    idx = DeviceAnnIndex.from_graphs(
        graphs, payloads=payloads, sharding=NamedSharding(mesh, P("data"))
    )
    placed = {s.device for s in idx.vectors.addressable_shards}
    if len(placed) != 4 or idx.vectors.sharding != idx.shardings(mesh).vectors:
        raise AssertionError(f"shards not one per device: {placed}")
    for s in idx.vectors.addressable_shards:
        if s.data.shape[0] != 1:
            raise AssertionError(f"device {s.device} holds {s.data.shape[0]} shards")
    log(f"placement: one shard per device on {sorted(d.id for d in placed)}")

    Q = corpus.vectors(N_QUERIES)
    probe = jax.jit(make_probe_fn(mesh, k=10, L=L))
    with mesh:
        t = time.perf_counter()
        jax.block_until_ready(probe(idx, jnp.asarray(Q)))
        cold = time.perf_counter() - t
        t = time.perf_counter()
        d_dev, ids_dev = jax.block_until_ready(probe(idx, jnp.asarray(Q)))
        warm = time.perf_counter() - t
    d_dev, ids_dev = np.asarray(d_dev), np.asarray(ids_dev)

    host_d, host_i = [], []
    for i, g in enumerate(graphs):
        with jax.default_device(devices[i]):
            d, ids = g.search(Q, 10, L=L)
        host_d.append(d)
        host_i.append(ids + i * rows_per_shard)
    host_d, host_i = np.concatenate(host_d, 1), np.concatenate(host_i, 1)
    order = np.argsort(host_d, axis=1, kind="stable")[:, :10]
    host_i = np.take_along_axis(host_i, order, axis=1)
    host_d = np.take_along_axis(host_d, order, axis=1)
    agree = np.mean([len(set(a) & set(b)) / 10 for a, b in zip(ids_dev, host_i)])
    if agree < 0.99 or not np.allclose(np.sort(d_dev, 1), host_d, rtol=DIST_RTOL):
        raise AssertionError(f"device probe disagrees with the host merge ({agree:.4f})")
    X = np.concatenate(shards)
    truth = brute_force(X, Q, 10)
    recall = float(np.mean([len(set(a) & set(b)) / 10 for a, b in zip(ids_dev, truth)]))
    log(f"probe first_call_s={cold} warm_s={warm} host_merge_agreement={agree} "
        f"recall@10={recall}")
    if recall < MIN_RECALL:
        raise AssertionError(f"recall@10 {recall:.4f} < {MIN_RECALL}")


# -- entry point ----------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (default device platform is "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    entries_before = cache_entries(cache_dir)
    cache_events = {"hits": 0, "misses": 0}
    lock = threading.Lock()

    def on_event(event, **_):
        key = {"/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "misses"}.get(event)
        if key:
            with lock:
                cache_events[key] += 1

    jax.monitoring.register_event_listener(on_event)
    d0 = devices[0]
    log(f"platform={d0.platform} device_kind={d0.device_kind} device_count={len(devices)}")
    t = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args.seed, FOUR_CHIP_ROWS_PER_SHARD)
    else:
        run_one_chip(args.seed, ONE_CHIP_ROWS_PER_SHARD)
    log(f"total_s={time.perf_counter() - t} "
        f"peak_bytes_in_use={d0.memory_stats()['peak_bytes_in_use']}")
    log(f"compile_cache dir={cache_dir} entries_before={entries_before} "
        f"entries_after={cache_entries(cache_dir)} hits={cache_events['hits']} "
        f"misses={cache_events['misses']}")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
