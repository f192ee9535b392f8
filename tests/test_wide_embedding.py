"""The served probe path at the OpenAI embedding width: D=1536, ``pq_m=96``.

VectorDBBench's ``Performance1536D500K`` case serves ``text-embedding-ada-002``
vectors (1536-d, cosine).  The deployment stores them unit-normalised and
ranks by squared L2, which orders as cosine does.  This module builds a small
table of the benchmark deployment's corpus (``bench/configs/openai-1536d.json``,
drawn by ``bench/datagen.py``) through the normal path
(``LakehouseTable.append_vectors`` -> ``Coordinator.create_index`` with the
deployment's centroid partitioning -> ``ProbeMicroBatcher.submit``) at the
published width and the deployment's 16-d PQ subspaces, and holds every
answer to a float64 NumPy brute force written here.  Only the scale is cut
for the CPU: 4 files of 512 rows, R=24, L=48.

The distance tolerance is 1.5e-6 of ``|q|^2 + |x|^2``: float32 rounding of
the expanded form ``|q|^2 - 2 q.x + |x|^2`` over 1536 terms stays well under
it, and a bf16 scorer put in Stage B's place exceeds it.
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.lakehouse.table import LakehouseTable
from repro.runtime.cluster import make_local_cluster
from repro.runtime.coordinator import IndexConfig
from repro.serving.serve_loop import ProbeMicroBatcher

# the benchmark's data generator and row locator import nothing of the system
BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench")
sys.path.insert(0, BENCH)
import datagen  # noqa: E402
import reference  # noqa: E402

with open(os.path.join(BENCH, "configs", "openai-1536d.json")) as _f:
    CONFIG = json.load(_f)
D, PQ_M = CONFIG["dim"], CONFIG["index"]["pq_m"]
FILES, ROWS_PER_FILE, ROWS_PER_GROUP = 4, 512, 256
SEED = 1536
K, PROBES = 10, 24
# float32 expanded-form rounding at 1536 terms: the benchmark cell's dist_err
TOL = 1.5e-6
RECALL_MIN = 0.9


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    """A 4-shard PQ-guided index over 2,048 rows of 1536-d; yields the
    cluster, the rows as written and two disjoint sets of probe queries."""
    corpus = datagen.Corpus(SEED, CONFIG["corpus"])
    rows = corpus.vectors(FILES * ROWS_PER_FILE, datagen.ROWS)
    queries = corpus.vectors(2 * PROBES, datagen.QUERIES)
    cluster = make_local_cluster(str(tmp_path_factory.mktemp("wide")), num_executors=4)
    table = LakehouseTable(cluster.catalog, "docs")
    table.create(dim=D)
    table.append_vectors(rows, num_files=FILES, rows_per_group=ROWS_PER_GROUP)
    ix = CONFIG["index"]
    cluster.coordinator.create_index("docs", IndexConfig(
        name="ix", R=24, L=48, alpha=ix["alpha"], pq_m=PQ_M, num_shards=ix["num_shards"],
        build_passes=ix["build_passes"], oversample=ix["oversample"],
        partition_mode=ix["partition_mode"],
    ))
    return cluster, rows, queries[:PROBES], queries[PROBES:]


def _served(cluster, queries):
    with ProbeMicroBatcher(cluster.coordinator, "docs", max_batch=64, use_pq=True) as mb:
        futures = [mb.submit(q, k=K) for q in queries]
        return [f.result(timeout=600) for f in futures]


_locate = reference.row_locator(FILES * ROWS_PER_FILE, FILES, ROWS_PER_GROUP)


def _row(hit) -> int:
    """The table row a hit names (-1 for none)."""
    return _locate(hit.file_path, hit.row_group, hit.row_offset)


def _brute_force(rows, q):
    x, q = rows.astype(np.float64), q.astype(np.float64)
    return ((x - q) ** 2).sum(1)


def _largest_gap(rows, queries, answers) -> float:
    """Largest |returned - float64 distance| over |q|^2 + |x|^2."""
    gap = 0.0
    x2 = (rows.astype(np.float64) ** 2).sum(1)
    for q, hits in zip(queries, answers):
        exact = _brute_force(rows, q)
        q2 = float((q.astype(np.float64) ** 2).sum())
        for h in hits:
            r = _row(h)
            gap = max(gap, abs(h.distance - exact[r]) / (q2 + x2[r]))
    return gap


@pytest.fixture(scope="module")
def answers(deployment):
    cluster, _rows, queries, _ = deployment
    return _served(cluster, queries)


def test_hits_are_distinct_rows_in_distance_order(deployment, answers):
    _, rows, queries, _ = deployment
    assert len(answers) == len(queries) >= 16
    for hits in answers:
        assert len(hits) == K
        ids = [_row(h) for h in hits]
        assert all(0 <= r < len(rows) for r in ids)
        assert len(set(ids)) == K
        dists = [h.distance for h in hits]
        assert dists == sorted(dists)


def test_recall_at_10_against_float64_brute_force(deployment, answers):
    _, rows, queries, _ = deployment
    recall = [
        len(set(np.argsort(_brute_force(rows, q), kind="stable")[:K]) & {_row(h) for h in hits}) / K
        for q, hits in zip(queries, answers)
    ]
    assert np.mean(recall) >= RECALL_MIN, recall


def test_distances_exact_to_float32_rounding(deployment, answers):
    _, rows, queries, _ = deployment
    gap = _largest_gap(rows, queries, answers)
    assert gap <= TOL, gap


@functools.partial(jax.jit, static_argnames=("metric",))
def _bf16_distances(queries, points, metric):
    q, x = queries.astype(jnp.bfloat16), points.astype(jnp.bfloat16)
    cross = jnp.dot(q, x.T, preferred_element_type=jnp.float32)
    qf, xf = q.astype(jnp.float32), x.astype(jnp.float32)
    return (qf * qf).sum(1)[:, None] - 2.0 * cross + (xf * xf).sum(1)[None, :]


def test_bf16_scorer_fails_the_tolerance(deployment, monkeypatch):
    """Stage B scoring in bf16, one precision below the configuration's,
    is caught by the distance check: the tolerance is tight enough."""
    cluster, rows, _, queries = deployment
    monkeypatch.setattr(ops, "_bucket_distances", _bf16_distances)
    answers = _served(cluster, queries)
    assert all(len(hits) == K for hits in answers)
    assert _largest_gap(rows, queries, answers) > TOL
