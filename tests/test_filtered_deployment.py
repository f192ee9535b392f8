"""Filtered probes on the served path: VectorDBBench's int-filter case on the
Cohere 768-d deployment (``bench/configs/cohere-768d-int-filter.json``).

Each probe carries its own range ``price >= lo AND price < lo + width`` over
a column uniform on [0, 10000); every hit must pass its predicate, and
recall is taken against the exact top-10 of the passing rows.  This module
builds the deployment's corpus (drawn by ``bench/datagen.py``) through the
normal path (``LakehouseTable.append_vectors`` -> ``Coordinator.create_index``
with the deployment's file partitioning -> ``ProbeMicroBatcher.submit``) at
the deployment's 16-d PQ subspaces, and holds every answer to a float64
NumPy brute force over the passing rows, written here.  The scale is cut for
the CPU: 2 files of 4,352 rows, each a shard just above
``planner.EXACT_SCAN_MAX_ROWS``, so a 10% band plans the predicate-aware
traversal (``MaskedBeam``) as the benchmark's 8,192-row shards and the
1M-row deployment do; R=24, L=64.  The width is cut too, to D=384 (``pq_m``
24): building two such shards at D=768 takes ~75 s of CPU, over the minute a
test file may take, and at R=16, L=32, which would build in time at D=768,
recall falls to 0.8.  ``tests/test_wide_embedding.py`` holds the served path
at full width.  A 1% band at this size passes ~44 rows a shard, under the
small-set threshold, and resolves to the exact masked scan: the case
documents where the cut stops exercising the traversal.

The distance tolerance is 1.5e-6 of ``|q|^2 + |x|^2``, the benchmark cell's
``dist_err`` limit: float32 rounding of the expanded form stays well under it.
"""

import json
import os
import sys

import numpy as np
import pytest

from repro.lakehouse.table import LakehouseTable
from repro.runtime import planner
from repro.runtime.cluster import make_local_cluster
from repro.runtime.coordinator import IndexConfig
from repro.serving.serve_loop import ProbeMicroBatcher

# the benchmark's cell loader, data generator and row locator import nothing
# of the system
BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench")
sys.path.insert(0, BENCH)
import cell as cell_mod  # noqa: E402
import datagen  # noqa: E402
import reference  # noqa: E402

with open(os.path.join(BENCH, "configs", "cohere-768d-int-filter.json")) as _f:
    CONFIG = json.load(_f)
D = 384  # half the published 768: see the module's docstring
PQ_M = D // 16  # the deployment's 16-d subspaces
FILES, ROWS_PER_FILE, ROWS_PER_GROUP = 2, 4352, 2176
SEED = 768
K, PROBES = 10, 24
PRICE = CONFIG["attributes"]["price"]
TOL = 1.5e-6  # the cell's dist_err limit
RECALL_MIN = 0.9


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    """A 2-shard PQ-guided index over 8,704 rows of 384-d with the
    deployment's ``price`` column; yields the cluster, the rows as written
    and their prices."""
    assert ROWS_PER_FILE > planner.EXACT_SCAN_MAX_ROWS
    cfg = dict(CONFIG, rows=FILES * ROWS_PER_FILE, corpus=dict(CONFIG["corpus"], dim=D))
    table_rows = datagen.make_table(cfg, SEED)
    cluster = make_local_cluster(str(tmp_path_factory.mktemp("filtered")), num_executors=2)
    table = LakehouseTable(cluster.catalog, "docs")
    table.create(dim=D)
    table.append_vectors(
        table_rows.vectors, num_files=FILES, rows_per_group=ROWS_PER_GROUP,
        attributes=table_rows.attributes,
    )
    ix = CONFIG["index"]
    cluster.coordinator.create_index("docs", IndexConfig(
        name="ix", R=24, L=64, alpha=ix["alpha"], pq_m=PQ_M, num_shards=FILES,
        build_passes=ix["build_passes"], oversample=ix["oversample"],
        partition_mode=ix["partition_mode"],
    ))
    return cluster, table_rows.vectors, table_rows.attributes["price"]


def _served(cluster, probes):
    """Answers of ``probes`` through the micro-batcher, and the
    ``ProbeReport`` of every ``probe_batch`` call that served them."""
    reports = []
    inner = cluster.coordinator.probe_batch

    def probe_batch(*args, **kwargs):
        rep = inner(*args, **kwargs)
        reports.append(rep)
        return rep

    cluster.coordinator.probe_batch = probe_batch
    try:
        with ProbeMicroBatcher(cluster.coordinator, "docs", max_batch=64, use_pq=True) as mb:
            futures = [mb.submit(p.query, k=p.k, filter=f"price >= {p.lo} AND price < {p.hi}")
                       for p in probes]
            answers = [f.result(timeout=600) for f in futures]
    finally:
        del cluster.coordinator.probe_batch
    return answers, reports


def _probes(width, seed):
    corpus = datagen.Corpus(SEED, dict(CONFIG["corpus"], dim=D))
    queries = corpus.vectors(PROBES, datagen.QUERIES + seed)
    los = np.random.default_rng(seed).integers(PRICE["low"], PRICE["high"] - width + 1, PROBES)
    return [datagen.Probe(0.0, q, K, int(lo), int(lo) + width) for q, lo in zip(queries, los)]


_locate = reference.row_locator(FILES * ROWS_PER_FILE, FILES, ROWS_PER_GROUP)


def _check(rows, price, probes, answers):
    """Every hit a distinct passing row in distance order, its distance
    exact to float32 rounding; returns the mean recall@10 against the
    float64 brute force over the passing rows."""
    x64 = rows.astype(np.float64)
    x2 = (x64 * x64).sum(1)
    recalls = []
    for p, hits in zip(probes, answers):
        passing = (price >= p.lo) & (price < p.hi)
        q = p.query.astype(np.float64)
        exact = ((x64 - q) ** 2).sum(1)
        ids = np.array([_locate(h.file_path, h.row_group, h.row_offset) for h in hits])
        assert len(hits) == min(K, int(passing.sum()))
        assert (ids >= 0).all() and len(set(ids.tolist())) == len(ids)
        assert passing[ids].all()
        dists = np.array([h.distance for h in hits])
        assert (np.diff(dists) >= 0).all()
        gap = np.abs(dists - exact[ids]) / ((q * q).sum() + x2[ids])
        assert gap.max() <= TOL, gap.max()
        truth = np.flatnonzero(passing)[np.argsort(exact[passing], kind="stable")[:K]]
        recalls.append(len(set(ids.tolist()) & set(truth.tolist())) / len(truth))
    return float(np.mean(recalls))


def test_ten_percent_bands_run_the_masked_traversal(deployment):
    cluster, rows, price = deployment
    probes = _probes(1000, seed=11)
    answers, reports = _served(cluster, probes)
    ops = [op for r in reports for row in r.plan.ops for op in row.values()]
    assert len(ops) == FILES * PROBES
    assert all(isinstance(op, planner.MaskedBeam) for op in ops), ops
    # the band's estimate is its own 10%, not the product of its two bounds
    assert all(op.width == 4 * op.k == 160 for op in ops)
    assert sum(r.masked_beam_rows for r in reports) == FILES * PROBES
    recall = _check(rows, price, probes, answers)
    assert recall >= RECALL_MIN, recall


def test_one_percent_bands_take_the_small_set_exact_scan(deployment):
    """At this cut a 1% band passes ~44 rows a shard, under the small-set
    threshold max(4 * k_eff, 64) = 160: the exact masked scan answers."""
    cluster, rows, price = deployment
    probes = _probes(100, seed=12)
    answers, reports = _served(cluster, probes)
    # the coordinator's band plans MaskedBeam; each shard's measured match
    # count resolves every row to the one masked exact-scan kernel call
    assert all("mbeam" in r.filter_plan for r in reports)
    assert sum(r.masked_beam_rows for r in reports) == 0
    assert all(r.kernel_dispatches == FILES for r in reports)
    recall = _check(rows, price, probes, answers)
    assert recall == 1.0


def test_the_cells_bands_pass_ten_percent_and_warm_every_slot_bucket():
    """The benchmark cell ``cohere-768d.range-10pct`` sends each probe a band
    of its own that passes 10% of the deployment's prices, and its warm-up
    batches reach every query-slot bucket, so no traversal compiles in the
    measured window."""
    from repro.core.vamana import SLOT_BUCKETS, query_slots

    c = cell_mod.load_cell("cohere-768d.range-10pct")
    assert c.config_name == CONFIG["name"] == "cohere-768d-int-filter"
    cfg = dict(c.config, corpus=dict(c.config["corpus"], dim=8))
    seed = 2**31 + 987654321
    price = datagen.make_table(cfg, seed).attributes["price"]
    probes = datagen.make_probes(cfg, c.traffic, 40.0, 5.0, seed)
    shares = [((price >= p.lo) & (price < p.hi)).mean() for p in probes]
    assert 0.095 < np.mean(shares) < 0.105 and min(shares) > 0.08
    assert len({p.lo for p in probes}) > 0.9 * len(probes)
    assert {query_slots(n) for n in c.traffic["warmup_batches"]} == set(SLOT_BUCKETS)
