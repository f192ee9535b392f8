"""Stage B's bucketed exact scorer (``ops.bucketed_exact_distances``) and the
executor's row rerank that runs through it.

The scorer pads queries and candidate rows on the host to a shape bucket and
runs one jitted distance program per bucket, so these tests check three
things: the distances against float64 NumPy, that no padded row or query
leaves the scorer or the executor, and, with the program's compile counter
on, that a warm bucket compiles nothing and a cold one compiles exactly once
(an eager pad or slice outside the jit would add compiles)."""

import numpy as np
import pytest

from repro.kernels import ops
from repro.lakehouse.objectstore import ObjectStore
from repro.lakehouse.vparquet import write_vector_file
from repro.runtime import fragments as F
from repro.runtime.executor import Executor
from repro.serving import metrics

TOL = 1.5e-6  # of |q|^2 + |x|^2, the benchmark's dist_err limit


def _f64_distances(q, x, metric):
    q, x = q.astype(np.float64), x.astype(np.float64)
    if metric == "ip":
        return -(q @ x.T)
    return ((q[:, None, :] - x[None, :, :]) ** 2).sum(-1)


def _assert_close(got, q, x, metric):
    want = _f64_distances(q, x, metric)
    scale = (q.astype(np.float64) ** 2).sum(1)[:, None] + (x.astype(np.float64) ** 2).sum(1)[None, :]
    assert got.shape == want.shape
    assert np.all(np.abs(got - want) <= TOL * scale), np.max(np.abs(got - want) / scale)


def _clustered(rng, n, d):
    """Rows near one centre, so the expanded form's cancellation matters."""
    centre = rng.normal(size=d)
    return (centre + 0.05 * rng.normal(size=(n, d))).astype(np.float32)


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("n", [1, 255, 256, 257, 1500])
@pytest.mark.parametrize("q", [1, 7, 33, 64])
def test_bucketed_distances_match_float64(q, n, metric):
    rng = np.random.default_rng(q * 10_000 + n)
    x = _clustered(rng, n + q, 96)
    got = ops.bucketed_exact_distances(x[:q], x[q:], metric=metric)
    assert isinstance(got, np.ndarray)
    _assert_close(got, x[:q], x[q:], metric)


@pytest.mark.parametrize(
    "q,n,expected",
    [(1, 1, (64, 256)), (64, 256, (64, 256)), (65, 257, (128, 512)),
     (40, 1500, (64, 2048)), (0, 0, (64, 256))],
)
def test_bucket_rule(q, n, expected):
    assert ops._stage_b_buckets(q, n) == expected


@pytest.fixture()
def registry():
    reg = metrics.MetricsRegistry()
    metrics.set_tracing(reg)
    yield reg
    metrics.set_tracing(None)
    metrics.drain()


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_a_warm_bucket_compiles_nothing(registry, metric):
    # D=40 is used by no other test, so the (64, 1024) bucket starts cold
    rng = np.random.default_rng(3)
    x = rng.normal(size=(1100, 40)).astype(np.float32)

    def compiles(q, n):
        before = registry.counter_value("compiles", "score")
        with metrics.span("score"):
            d = ops.bucketed_exact_distances(x[:q], x[100:100 + n], metric=metric)
        assert d.shape == (q, n)
        return registry.counter_value("compiles", "score") - before

    assert compiles(20, 600) == 1  # cold bucket: the jitted distance alone
    assert compiles(64, 1000) == 0  # same bucket, other shape
    assert compiles(1, 513) == 0


def _stage_b_task(tmp_path, n_rows, n_queries, owners, metric, seed):
    """Two files of ``n_rows`` rows in all, row groups of 64, a Stage-B task
    over every row, and the rows' vectors in task order."""
    rng = np.random.default_rng(seed)
    store = ObjectStore(str(tmp_path / "s3"))
    x = _clustered(rng, n_rows, 24)
    split = n_rows // 2 + 1
    masks, vecs = {}, []
    for fi, part in enumerate((x[:split], x[split:])):
        key = f"t/data/data-{fi:05d}.vpq"
        write_vector_file(store, key, part, rows_per_group=64)
        masks[key] = {rg: list(range(len(part[rg * 64:(rg + 1) * 64])))
                      for rg in range(-(-len(part) // 64))}
        vecs.append(part)
    task = F.RerankTaskInfo(task_id="rr", masks=masks, metric=metric,
                            queries=_clustered(rng, n_queries, 24))
    if owners == "row":
        task.row_owners = {
            fp: {rg: {off: {int(qi) for qi in np.flatnonzero(rng.random(n_queries) < 0.5)}
                      for off in offs}
                 for rg, offs in groups.items()}
            for fp, groups in masks.items()
        }
    elif owners == "file":
        task.file_owners = {fp: {fi % n_queries} for fi, fp in enumerate(masks)}
    return Executor("ex-0", store, str(tmp_path / "cache")), task, np.concatenate(vecs)


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize(
    "n_rows,n_queries,owners",
    [(257, 5, "row"), (513, 33, "row"), (257, 3, "file"), (257, 2, None)],
)
def test_executor_rerank_emits_only_real_owned_rows(tmp_path, n_rows, n_queries, owners, metric):
    """Row counts just past a bucket boundary: each query gets exactly the
    rows it owns, no padded row, at the old eager path's distances."""
    import jax.numpy as jnp

    ex, task, x = _stage_b_task(tmp_path, n_rows, n_queries, owners, metric, seed=n_rows)
    result = ex._rerank(task)
    flat = [(fp, rg, off) for fp, groups in task.masks.items()
            for rg, offs in groups.items() for off in offs]
    assert len(flat) == n_rows
    eager = np.asarray(ops.exact_distances(
        jnp.asarray(task.queries), jnp.asarray(x), metric=metric, backend="ref"))
    q = task.queries.astype(np.float64)
    assert len(result.rows) == n_queries
    for qi, rows in enumerate(result.rows):
        if owners == "row":
            owned = {loc for loc in flat if qi in task.row_owners[loc[0]][loc[1]][loc[2]]}
        elif owners == "file":
            owned = {loc for loc in flat if qi in task.file_owners[loc[0]]}
        else:
            owned = set(flat)
        locs = [(r.file_path, r.row_group, r.row_offset) for r in rows]
        assert len(locs) == len(set(locs)) and set(locs) == owned
        for r in rows:
            ci = flat.index((r.file_path, r.row_group, r.row_offset))
            scale = (q[qi] ** 2).sum() + (x[ci].astype(np.float64) ** 2).sum()
            assert abs(r.distance - eager[qi, ci]) <= TOL * scale
