"""Per-kernel validation: shape/dtype sweeps, Pallas(interpret) vs ref oracle.

Every test here carries the ``kernels`` marker: ``pytest -m "kernels and not
slow"`` is the CI tier-1 kernel-parity gate (scripts/ci.sh) asserting that
the Pallas path (``interpret=True`` off-TPU) agrees with the ref.py oracle
for every op in ops.py — including the masked ops' all-masked / one-row /
non-tile-aligned edge cases.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

pytestmark = pytest.mark.kernels


def _np(*shape, seed=0, scale=1.0):
    return (np.random.default_rng(seed).normal(size=shape) * scale).astype(np.float32)


# ---------------------------------------------------------------------------
# exact distances (rerank kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q,n,d", [(1, 1, 1), (7, 33, 5), (37, 301, 100), (128, 256, 768), (3, 500, 17)])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_rerank_matches_ref(q, n, d, metric):
    Q, X = _np(q, d, seed=1), _np(n, d, seed=2)
    got = ops.exact_distances(jnp.asarray(Q), jnp.asarray(X), metric=metric, backend="pallas")
    want = ops.exact_distances(jnp.asarray(Q), jnp.asarray(X), metric=metric, backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-3)


def test_rerank_topk_order():
    Q, X = _np(4, 16, seed=3), _np(100, 16, seed=4)
    d, i = ops.exact_topk(jnp.asarray(Q), jnp.asarray(X), 5, backend="pallas")
    full = np.asarray(ops.exact_distances(jnp.asarray(Q), jnp.asarray(X), backend="ref"))
    for qi in range(4):
        np.testing.assert_array_equal(
            np.sort(np.asarray(i)[qi]), np.sort(np.argsort(full[qi])[:5])
        )


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_rerank_dtypes(dtype):
    Q = _np(8, 32, seed=5).astype(dtype)
    X = _np(64, 32, seed=6).astype(dtype)
    got = ops.exact_distances(jnp.asarray(Q), jnp.asarray(X), backend="pallas")
    want = ref.l2_distances(jnp.asarray(Q, jnp.float32), jnp.asarray(X, jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-3, atol=5e-2)


# ---------------------------------------------------------------------------
# PQ ADC scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q,n,m,K", [(1, 1, 1, 2), (5, 77, 8, 16), (16, 300, 48, 256), (2, 130, 4, 64)])
def test_pq_scan_matches_ref(q, n, m, K):
    rng = np.random.default_rng(7)
    luts = rng.normal(size=(q, m, K)).astype(np.float32)
    codes = rng.integers(0, K, size=(n, m)).astype(np.int32)
    got = ops.pq_scan(jnp.asarray(luts), jnp.asarray(codes), backend="pallas", tile_q=4, tile_n=32)
    want = ops.pq_scan(jnp.asarray(luts), jnp.asarray(codes), backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


def test_pq_scan_topk():
    rng = np.random.default_rng(8)
    luts = rng.normal(size=(3, 8, 32)).astype(np.float32)
    codes = rng.integers(0, 32, size=(50, 8)).astype(np.int32)
    d, i = ops.pq_scan_topk(jnp.asarray(luts), jnp.asarray(codes), 7, backend="pallas")
    full = np.asarray(ref.pq_adc_scores(jnp.asarray(luts), jnp.asarray(codes)))
    for qi in range(3):
        np.testing.assert_array_equal(np.sort(np.asarray(i)[qi]), np.sort(np.argsort(full[qi])[:7]))


# ---------------------------------------------------------------------------
# k-means assignment
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k,d", [(1, 1, 1), (100, 10, 8), (555, 100, 48), (1000, 257, 16)])
def test_kmeans_assign_matches_ref(n, k, d):
    X = _np(n, d, seed=9)
    C = _np(k, d, seed=10)
    ip, dp = ops.kmeans_assign(jnp.asarray(X), jnp.asarray(C), backend="pallas", tile_n=128, tile_k=32)
    ir, dr = ops.kmeans_assign(jnp.asarray(X), jnp.asarray(C), backend="ref")
    np.testing.assert_array_equal(np.asarray(ip), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dr), rtol=2e-4, atol=2e-3)


# ---------------------------------------------------------------------------
# masked top-k (mask-aware filtered-probe kernels)
# ---------------------------------------------------------------------------


def _assert_masked_contract(dists, ids, full_d, mask, k):
    """Masked-op contract: rows ascending, only passing rows appear, each
    returned distance equals the oracle's distance for that id, and exactly
    min(k, passing) slots are populated (the rest are (+inf, -1))."""
    q = dists.shape[0]
    n_pass = int(np.asarray(mask).sum())
    for qi in range(q):
        d_row, i_row = np.asarray(dists[qi]), np.asarray(ids[qi])
        valid = i_row >= 0
        assert valid.sum() == min(k, n_pass)
        assert np.isfinite(d_row[valid]).all() and np.isinf(d_row[~valid]).all()
        assert (i_row[~valid] == -1).all()
        assert np.all(np.diff(d_row[valid]) >= -1e-4)  # ascending
        if valid.any():
            assert np.asarray(mask)[i_row[valid]].all()  # never a masked row
            np.testing.assert_allclose(
                d_row[valid], full_d[qi, i_row[valid]], rtol=2e-4, atol=2e-3
            )


# shapes deliberately non-tile-aligned (tile_q=8, tile_n=128 defaults),
# plus the one-row and k>N edges
@pytest.mark.parametrize("q,n,k", [(1, 1, 1), (3, 37, 5), (7, 130, 10), (5, 300, 320)])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_masked_exact_topk_matches_ref(q, n, k, metric):
    rng = np.random.default_rng(q * 13 + n)
    Q, X = _np(q, 16, seed=q), _np(n, 16, seed=n)
    mask = rng.random(n) < 0.4
    full = np.asarray(
        ops.exact_distances(jnp.asarray(Q), jnp.asarray(X), metric=metric, backend="ref")
    )
    for backend in ("pallas", "ref"):
        d, i = ops.masked_exact_topk(
            jnp.asarray(Q), jnp.asarray(X), jnp.asarray(mask), k,
            metric=metric, backend=backend,
        )
        _assert_masked_contract(np.asarray(d), np.asarray(i), full, mask, k)
    dp, ipal = ops.masked_exact_topk(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(mask), k, metric=metric, backend="pallas"
    )
    dr, _ = ops.masked_exact_topk(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(mask), k, metric=metric, backend="ref"
    )
    dp, dr = np.asarray(dp), np.asarray(dr)
    np.testing.assert_allclose(
        np.where(np.isinf(dp), 0.0, dp), np.where(np.isinf(dr), 0.0, dr),
        rtol=2e-4, atol=2e-3,
    )
    assert (np.isinf(dp) == np.isinf(dr)).all()


@pytest.mark.parametrize("backend", ["pallas", "ref"])
def test_masked_exact_topk_all_masked(backend):
    Q, X = _np(2, 8, seed=1), _np(40, 8, seed=2)
    d, i = ops.masked_exact_topk(
        jnp.asarray(Q), jnp.asarray(X), jnp.zeros(40, bool), 5, backend=backend
    )
    assert np.isinf(np.asarray(d)).all() and (np.asarray(i) == -1).all()


@pytest.mark.parametrize("backend", ["pallas", "ref"])
def test_masked_exact_topk_single_passing_row(backend):
    """One passing row, k > 1: exactly one populated slot, and it is that row."""
    Q, X = _np(3, 8, seed=3), _np(50, 8, seed=4)
    mask = np.zeros(50, bool)
    mask[17] = True
    d, i = ops.masked_exact_topk(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(mask), 4, backend=backend
    )
    i = np.asarray(i)
    assert (i[:, 0] == 17).all() and (i[:, 1:] == -1).all()
    assert np.isinf(np.asarray(d)[:, 1:]).all()


@pytest.mark.parametrize("q,n,m,K,k", [(1, 1, 1, 2, 1), (5, 77, 8, 16, 9), (3, 300, 4, 64, 12)])
def test_masked_pq_topk_matches_ref(q, n, m, K, k):
    rng = np.random.default_rng(q * 31 + n)
    luts = rng.normal(size=(q, m, K)).astype(np.float32)
    codes = rng.integers(0, K, size=(n, m)).astype(np.int32)
    mask = rng.random(n) < 0.5
    full = np.asarray(ref.pq_adc_scores(jnp.asarray(luts), jnp.asarray(codes)))
    for backend in ("pallas", "ref"):
        d, i = ops.masked_pq_topk(
            jnp.asarray(luts), jnp.asarray(codes), jnp.asarray(mask), k, backend=backend
        )
        _assert_masked_contract(np.asarray(d), np.asarray(i), full, mask, k)
    dp, _ = ops.masked_pq_topk(
        jnp.asarray(luts), jnp.asarray(codes), jnp.asarray(mask), k, backend="pallas"
    )
    dr, _ = ops.masked_pq_topk(
        jnp.asarray(luts), jnp.asarray(codes), jnp.asarray(mask), k, backend="ref"
    )
    dp, dr = np.asarray(dp), np.asarray(dr)
    np.testing.assert_allclose(
        np.where(np.isinf(dp), 0.0, dp), np.where(np.isinf(dr), 0.0, dr),
        rtol=1e-4, atol=1e-4,
    )
    assert (np.isinf(dp) == np.isinf(dr)).all()


@pytest.mark.parametrize("backend", ["pallas", "ref"])
def test_masked_pq_topk_all_masked(backend):
    rng = np.random.default_rng(5)
    luts = rng.normal(size=(2, 4, 16)).astype(np.float32)
    codes = rng.integers(0, 16, size=(60, 4)).astype(np.int32)
    d, i = ops.masked_pq_topk(
        jnp.asarray(luts), jnp.asarray(codes), jnp.zeros(60, bool), 6, backend=backend
    )
    assert np.isinf(np.asarray(d)).all() and (np.asarray(i) == -1).all()


# ---------------------------------------------------------------------------
# multi-mask top-k (per-query (Q, N) mask planes — heterogeneous filters)
# ---------------------------------------------------------------------------


def _assert_masked_contract_multi(dists, ids, full_d, masks, k):
    """Per-query plane contract: each row obeys the single-mask contract
    under ITS OWN mask row."""
    for qi in range(dists.shape[0]):
        _assert_masked_contract(
            dists[qi : qi + 1], ids[qi : qi + 1], full_d[qi : qi + 1], masks[qi], k
        )


# non-tile-aligned Q and N (tile_q=8, tile_n=128 defaults), single-row, and
# k > passing-rows edges
@pytest.mark.parametrize("q,n,k", [(2, 1, 1), (3, 37, 5), (9, 130, 10), (5, 300, 320)])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_masked_exact_topk_multi_matches_ref(q, n, k, metric):
    rng = np.random.default_rng(q * 17 + n)
    Q, X = _np(q, 16, seed=q), _np(n, 16, seed=n)
    masks = rng.random((q, n)) < 0.4
    if q > 1:
        masks[1] = False  # one all-masked QUERY among live ones
    full = np.asarray(
        ops.exact_distances(jnp.asarray(Q), jnp.asarray(X), metric=metric, backend="ref")
    )
    outs = {}
    for backend in ("pallas", "ref"):
        d, i = ops.masked_exact_topk_multi(
            jnp.asarray(Q), jnp.asarray(X), jnp.asarray(masks), k,
            metric=metric, backend=backend,
        )
        d, i = np.asarray(d), np.asarray(i)
        _assert_masked_contract_multi(d, i, full, masks, k)
        if q > 1:
            assert np.isinf(d[1]).all() and (i[1] == -1).all()
        outs[backend] = (d, i)
    dp, dr = outs["pallas"][0], outs["ref"][0]
    np.testing.assert_allclose(
        np.where(np.isinf(dp), 0.0, dp), np.where(np.isinf(dr), 0.0, dr),
        rtol=2e-4, atol=2e-3,
    )
    assert (np.isinf(dp) == np.isinf(dr)).all()


@pytest.mark.parametrize("q,n,m,K,k", [(2, 1, 1, 2, 1), (5, 77, 8, 16, 9), (3, 300, 4, 64, 12)])
def test_masked_pq_topk_multi_matches_ref(q, n, m, K, k):
    rng = np.random.default_rng(q * 29 + n)
    luts = rng.normal(size=(q, m, K)).astype(np.float32)
    codes = rng.integers(0, K, size=(n, m)).astype(np.int32)
    masks = rng.random((q, n)) < 0.5
    full = np.asarray(ref.pq_adc_scores(jnp.asarray(luts), jnp.asarray(codes)))
    outs = {}
    for backend in ("pallas", "ref"):
        d, i = ops.masked_pq_topk_multi(
            jnp.asarray(luts), jnp.asarray(codes), jnp.asarray(masks), k, backend=backend
        )
        d, i = np.asarray(d), np.asarray(i)
        _assert_masked_contract_multi(d, i, full, masks, k)
        outs[backend] = (d, i)
    dp, dr = outs["pallas"][0], outs["ref"][0]
    np.testing.assert_allclose(
        np.where(np.isinf(dp), 0.0, dp), np.where(np.isinf(dr), 0.0, dr),
        rtol=1e-4, atol=1e-4,
    )
    assert (np.isinf(dp) == np.isinf(dr)).all()


@pytest.mark.parametrize("backend", ["pallas", "ref"])
def test_masked_multi_all_queries_masked(backend):
    Q, X = _np(3, 8, seed=1), _np(40, 8, seed=2)
    d, i = ops.masked_exact_topk_multi(
        jnp.asarray(Q), jnp.asarray(X), jnp.zeros((3, 40), bool), 5, backend=backend
    )
    assert np.isinf(np.asarray(d)).all() and (np.asarray(i) == -1).all()
    rng = np.random.default_rng(3)
    luts = rng.normal(size=(2, 4, 16)).astype(np.float32)
    codes = rng.integers(0, 16, size=(60, 4)).astype(np.int32)
    d, i = ops.masked_pq_topk_multi(
        jnp.asarray(luts), jnp.asarray(codes), jnp.zeros((2, 60), bool), 6, backend=backend
    )
    assert np.isinf(np.asarray(d)).all() and (np.asarray(i) == -1).all()


@pytest.mark.parametrize("backend", ["pallas", "ref"])
def test_masked_multi_q1_degenerates_to_single_mask(backend):
    """Q == 1 planes dispatch to the single-mask kernels and must return
    exactly what the single-mask op returns."""
    rng = np.random.default_rng(11)
    Q, X = _np(1, 16, seed=5), _np(90, 16, seed=6)
    mask = rng.random(90) < 0.3
    dm, im = ops.masked_exact_topk_multi(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(mask[None, :]), 7, backend=backend
    )
    ds, is_ = ops.masked_exact_topk(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(mask), 7, backend=backend
    )
    np.testing.assert_array_equal(np.asarray(im), np.asarray(is_))
    np.testing.assert_array_equal(np.asarray(dm), np.asarray(ds))


@pytest.mark.parametrize("backend", ["pallas", "ref"])
def test_masked_multi_rows_match_per_query_single_calls(backend):
    """The plane call is semantically Q independent single-mask calls: each
    row must equal the single-mask op run with that query's own bitmask."""
    rng = np.random.default_rng(13)
    Q, X = _np(6, 16, seed=7), _np(150, 16, seed=8)
    masks = rng.random((6, 150)) < 0.35
    dm, im = ops.masked_exact_topk_multi(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(masks), 8, backend=backend
    )
    for qi in range(6):
        ds, is_ = ops.masked_exact_topk(
            jnp.asarray(Q[qi : qi + 1]), jnp.asarray(X), jnp.asarray(masks[qi]), 8,
            backend=backend,
        )
        np.testing.assert_array_equal(np.asarray(im)[qi], np.asarray(is_)[0])
        np.testing.assert_allclose(
            np.asarray(dm)[qi], np.asarray(ds)[0], rtol=2e-4, atol=2e-3
        )


# ---------------------------------------------------------------------------
# unified exact/PQ kernel (mixed-flavor single dispatch) + dedup'd planes
# ---------------------------------------------------------------------------


def _unified_inputs(q, n, d, m, K, seed):
    rng = np.random.default_rng(seed)
    Q = _np(q, d, seed=seed)
    X = _np(n, d, seed=seed + 1)
    luts = rng.normal(size=(q, m, K)).astype(np.float32)
    codes = rng.integers(0, K, size=(n, m)).astype(np.int32)
    masks = rng.random((q, n)) < 0.4
    flavor = (np.arange(q) % 2).astype(bool)
    return Q, X, luts, codes, masks, flavor


# non-tile-aligned Q/N, single-row, and k > passing edges, both metrics
@pytest.mark.parametrize("q,n,k", [(2, 1, 1), (5, 77, 9), (9, 130, 10), (4, 300, 320)])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_unified_masked_topk_matches_ref(q, n, k, metric):
    Q, X, luts, codes, masks, flavor = _unified_inputs(q, n, 16, 4, 16, seed=q * 7 + n)
    dp, ip_ = ops.unified_masked_topk(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(luts), jnp.asarray(codes),
        jnp.asarray(masks), jnp.asarray(flavor), k, metric=metric, backend="pallas",
    )
    dr, ir = ops.unified_masked_topk(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(luts), jnp.asarray(codes),
        jnp.asarray(masks), jnp.asarray(flavor), k, metric=metric, backend="ref",
    )
    np.testing.assert_array_equal(np.asarray(ip_), np.asarray(ir))
    dp, dr = np.asarray(dp), np.asarray(dr)
    np.testing.assert_allclose(
        np.where(np.isinf(dp), 0.0, dp), np.where(np.isinf(dr), 0.0, dr),
        rtol=2e-4, atol=2e-3,
    )
    assert (np.isinf(dp) == np.isinf(dr)).all()


@pytest.mark.parametrize("backend", ["pallas", "ref"])
def test_unified_rows_match_per_flavor_split_ops(backend):
    """The acceptance contract of the fused dispatch: every exact-flavor
    row equals the dedicated exact multi-op's row, every ADC-flavor row
    equals the dedicated PQ multi-op's row — the unified kernel is the two
    split dispatches, bit-for-bit, in one call."""
    Q, X, luts, codes, masks, flavor = _unified_inputs(7, 210, 16, 4, 16, seed=3)
    k = 12
    du, iu = ops.unified_masked_topk(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(luts), jnp.asarray(codes),
        jnp.asarray(masks), jnp.asarray(flavor), k, backend=backend,
    )
    de, ie = ops.masked_exact_topk_multi(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(masks), k, backend=backend
    )
    da, ia = ops.masked_pq_topk_multi(
        jnp.asarray(luts), jnp.asarray(codes), jnp.asarray(masks), k, backend=backend
    )
    du, iu = np.asarray(du), np.asarray(iu)
    for qi in range(7):
        want_i = np.asarray(ia if flavor[qi] else ie)[qi]
        want_d = np.asarray(da if flavor[qi] else de)[qi]
        np.testing.assert_array_equal(iu[qi], want_i)
        np.testing.assert_allclose(
            np.where(np.isinf(du[qi]), 0.0, du[qi]),
            np.where(np.isinf(want_d), 0.0, want_d),
            rtol=2e-4, atol=2e-3,
        )


@pytest.mark.parametrize("backend", ["pallas", "ref"])
def test_unified_all_masked_and_all_one_flavor(backend):
    """Degenerate flavors: an all-masked plane yields pure sentinels; an
    all-exact (or all-ADC) flavor vector reproduces the single-flavor op."""
    Q, X, luts, codes, masks, _ = _unified_inputs(4, 90, 8, 4, 16, seed=9)
    d, i = ops.unified_masked_topk(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(luts), jnp.asarray(codes),
        jnp.zeros((4, 90), bool), jnp.zeros(4, bool), 5, backend=backend,
    )
    assert np.isinf(np.asarray(d)).all() and (np.asarray(i) == -1).all()
    for flav, split in (
        (np.zeros(4, bool), lambda: ops.masked_exact_topk_multi(
            jnp.asarray(Q), jnp.asarray(X), jnp.asarray(masks), 5, backend=backend)),
        (np.ones(4, bool), lambda: ops.masked_pq_topk_multi(
            jnp.asarray(luts), jnp.asarray(codes), jnp.asarray(masks), 5,
            backend=backend)),
    ):
        du, iu = ops.unified_masked_topk(
            jnp.asarray(Q), jnp.asarray(X), jnp.asarray(luts), jnp.asarray(codes),
            jnp.asarray(masks), jnp.asarray(flav), 5, backend=backend,
        )
        ds, is_ = split()
        np.testing.assert_array_equal(np.asarray(iu), np.asarray(is_))


@pytest.mark.parametrize("backend", ["pallas", "ref"])
def test_dedup_plane_matches_dense_plane(backend):
    """Dedup-then-broadcast contract: the (m unique rows, row index)
    factored plane returns exactly what the dense (Q, N) plane returns,
    for the exact, PQ, and unified ops alike."""
    rng = np.random.default_rng(21)
    Q, X = _np(9, 16, seed=31), _np(140, 16, seed=32)
    luts = rng.normal(size=(9, 4, 16)).astype(np.float32)
    codes = rng.integers(0, 16, size=(140, 4)).astype(np.int32)
    unique = rng.random((3, 140)) < 0.4
    idx = rng.integers(0, 3, size=9)
    dense = unique[idx]
    flavor = (np.arange(9) % 2).astype(bool)
    pairs = [
        (
            ops.masked_exact_topk_dedup(
                jnp.asarray(Q), jnp.asarray(X), jnp.asarray(unique),
                jnp.asarray(idx), 8, backend=backend,
            ),
            ops.masked_exact_topk_multi(
                jnp.asarray(Q), jnp.asarray(X), jnp.asarray(dense), 8,
                backend=backend,
            ),
        ),
        (
            ops.masked_pq_topk_dedup(
                jnp.asarray(luts), jnp.asarray(codes), jnp.asarray(unique),
                jnp.asarray(idx), 8, backend=backend,
            ),
            ops.masked_pq_topk_multi(
                jnp.asarray(luts), jnp.asarray(codes), jnp.asarray(dense), 8,
                backend=backend,
            ),
        ),
        (
            ops.unified_masked_topk_dedup(
                jnp.asarray(Q), jnp.asarray(X), jnp.asarray(luts),
                jnp.asarray(codes), jnp.asarray(unique), jnp.asarray(idx),
                jnp.asarray(flavor), 8, backend=backend,
            ),
            ops.unified_masked_topk(
                jnp.asarray(Q), jnp.asarray(X), jnp.asarray(luts),
                jnp.asarray(codes), jnp.asarray(dense), jnp.asarray(flavor), 8,
                backend=backend,
            ),
        ),
    ]
    for (dd, di), (dm, im) in pairs:
        np.testing.assert_array_equal(np.asarray(di), np.asarray(im))
        np.testing.assert_array_equal(np.asarray(dd), np.asarray(dm))


# ---------------------------------------------------------------------------
# property-based sweeps
# ---------------------------------------------------------------------------

@pytest.mark.slow  # every drawn shape pays a fresh Pallas-interpret compile
@settings(max_examples=20, deadline=None)
@given(
    q=st.integers(1, 24),
    n=st.integers(1, 200),
    d=st.integers(1, 64),
)
def test_property_rerank(q, n, d):
    Q, X = _np(q, d, seed=q * 7 + n), _np(n, d, seed=d)
    got = np.asarray(
        ops.exact_distances(jnp.asarray(Q), jnp.asarray(X), backend="pallas")
    )
    want = np.asarray(ops.exact_distances(jnp.asarray(Q), jnp.asarray(X), backend="ref"))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-3)
    # metric properties: non-negative, d(x,x)=0
    self_d = np.asarray(
        ops.exact_distances(jnp.asarray(X[:5]), jnp.asarray(X[:5]), backend="pallas")
    )
    assert np.all(self_d > -1e-2)
    np.testing.assert_allclose(np.diag(self_d), 0.0, atol=1e-2)


@pytest.mark.slow  # every drawn shape pays a fresh Pallas-interpret compile
@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(1, 150),
    m=st.integers(1, 16),
    nbits=st.integers(1, 8),
)
def test_property_pq_scan(n, m, nbits):
    K = 1 << nbits
    rng = np.random.default_rng(n * 31 + m)
    luts = rng.normal(size=(3, m, K)).astype(np.float32)
    codes = rng.integers(0, K, size=(n, m)).astype(np.int32)
    got = np.asarray(ops.pq_scan(jnp.asarray(luts), jnp.asarray(codes), backend="pallas", tile_q=4, tile_n=32))
    want = np.asarray(ref.pq_adc_scores(jnp.asarray(luts), jnp.asarray(codes)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# gather-rerank (device candidate-pool rerank — the host-rerank replacement)
# ---------------------------------------------------------------------------


def _host_rerank(Q, X, pids, k, metric="l2"):
    """The removed NumPy rerank, verbatim in shape: clip-gather the pool
    vectors, score, push sentinels to +inf, argsort top-k.  Kept here only
    as the bit-parity oracle for the kernel that replaced it."""
    n = X.shape[0]
    safe = np.clip(pids, 0, n - 1)
    vecs = X[safe]  # (Q, P, D)
    if metric == "ip":
        d = -np.einsum("qpd,qd->qp", vecs, Q)
    else:
        d = np.sum((vecs - Q[:, None, :]) ** 2, axis=-1)
    d = np.where((pids < 0) | (pids >= n), np.inf, d)
    order = np.argsort(d, axis=1, kind="stable")[:, :k]
    out_d = np.take_along_axis(d, order, axis=1)
    out_i = np.take_along_axis(pids, order, axis=1)
    out_i = np.where(np.isfinite(out_d), out_i, -1)
    return out_d.astype(np.float32), out_i.astype(np.int64)


# Q / N / P deliberately non-tile-aligned (tile_q=8, tile_n=128 defaults)
@pytest.mark.parametrize("q,n,p,d", [(1, 1, 1, 1), (3, 90, 7, 16), (9, 300, 33, 24), (5, 130, 130, 100)])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_gather_rerank_matches_ref(q, n, p, d, metric):
    rng = np.random.default_rng(q * 11 + n)
    Q, X = _np(q, d, seed=q), _np(n, d, seed=n + 1)
    pids = rng.choice(n, size=(q, p), replace=p <= n).astype(np.int32) if p <= n \
        else rng.integers(0, n, size=(q, p)).astype(np.int32)
    k = min(5, p)
    outs = {}
    for backend in ("pallas", "ref"):
        dd, ii = ops.gather_rerank(
            jnp.asarray(Q), jnp.asarray(X), jnp.asarray(pids), k,
            metric=metric, backend=backend,
        )
        outs[backend] = (np.asarray(dd), np.asarray(ii))
    np.testing.assert_array_equal(outs["pallas"][1], outs["ref"][1])
    dp, dr = outs["pallas"][0], outs["ref"][0]
    np.testing.assert_allclose(
        np.where(np.isinf(dp), 0.0, dp), np.where(np.isinf(dr), 0.0, dr),
        rtol=2e-4, atol=2e-3,
    )
    assert (np.isinf(dp) == np.isinf(dr)).all()


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_gather_rerank_bit_parity_with_host_rerank(metric):
    """The kernel answers exactly what the NumPy gather+einsum it replaced
    answered (distinct pool ids — the unstable-argsort duplicate tie order
    was never part of the old contract)."""
    rng = np.random.default_rng(42)
    Q, X = _np(6, 32, seed=1), _np(200, 32, seed=2)
    pids = np.stack([rng.choice(200, size=24, replace=False) for _ in range(6)]).astype(np.int32)
    pids[2, 5:] = -1  # one mostly-empty pool
    want_d, want_i = _host_rerank(Q, X, pids, 10, metric=metric)
    for backend in ("pallas", "ref"):
        got_d, got_i = ops.gather_rerank(
            jnp.asarray(Q), jnp.asarray(X), jnp.asarray(pids), 10,
            metric=metric, backend=backend,
        )
        np.testing.assert_array_equal(np.asarray(got_i, np.int64), want_i)
        np.testing.assert_allclose(
            np.where(np.isinf(np.asarray(got_d)), 0.0, np.asarray(got_d)),
            np.where(np.isinf(want_d), 0.0, want_d),
            rtol=2e-4, atol=2e-3,
        )


@pytest.mark.parametrize("backend", ["pallas", "ref"])
def test_gather_rerank_sentinels_and_out_of_range(backend):
    """pid < 0 and pid >= N slots never score: they surface as (+inf, -1),
    and an all-sentinel pool row is all (+inf, -1)."""
    Q, X = _np(4, 16, seed=3), _np(50, 16, seed=4)
    pids = np.full((4, 8), -1, np.int32)
    pids[0, :3] = [5, 7, 50]  # 50 is out of range -> sentinel
    pids[1, 0] = 999
    d, i = ops.gather_rerank(jnp.asarray(Q), jnp.asarray(X), jnp.asarray(pids), 8, backend=backend)
    d, i = np.asarray(d), np.asarray(i)
    assert set(i[0][i[0] >= 0]) == {5, 7}
    assert (i[1] == -1).all() and np.isinf(d[1]).all()
    assert (i[2:] == -1).all() and np.isinf(d[2:]).all()
    assert np.isfinite(d[0][:2]).all() and np.isinf(d[0][2:]).all()


@pytest.mark.parametrize("backend", ["pallas", "ref"])
def test_gather_rerank_k_exceeds_pool(backend):
    """k > P: the extra slots are (+inf, -1) and the live prefix is the
    whole pool, ascending."""
    Q, X = _np(2, 8, seed=5), _np(60, 8, seed=6)
    pids = np.array([[3, 9, 41], [0, 59, 17]], np.int32)
    d, i = ops.gather_rerank(jnp.asarray(Q), jnp.asarray(X), jnp.asarray(pids), 10, backend=backend)
    d, i = np.asarray(d), np.asarray(i)
    assert d.shape == (2, 10)
    for qi in range(2):
        assert set(i[qi][:3]) == set(pids[qi].tolist())
        assert (i[qi][3:] == -1).all() and np.isinf(d[qi][3:]).all()
        assert np.all(np.diff(d[qi][:3]) >= -1e-5)


@pytest.mark.parametrize("backend", ["pallas", "ref"])
def test_gather_rerank_duplicate_pids(backend):
    """Duplicate pool ids are allowed: the top-k multiset matches the
    brute-force multiset (tie ORDER among equal ids is unspecified, exactly
    as it was for the unstable host argsort)."""
    rng = np.random.default_rng(9)
    Q, X = _np(3, 16, seed=7), _np(40, 16, seed=8)
    pids = rng.integers(0, 40, size=(3, 12)).astype(np.int32)
    pids[:, 6:] = pids[:, :6]  # force duplicates
    k = 5
    d, i = ops.gather_rerank(jnp.asarray(Q), jnp.asarray(X), jnp.asarray(pids), k, backend=backend)
    d, i = np.asarray(d), np.asarray(i)
    want_d, want_i = _host_rerank(Q, X, pids, k)
    for qi in range(3):
        np.testing.assert_allclose(d[qi], want_d[qi], rtol=2e-4, atol=2e-3)
        assert sorted(i[qi].tolist()) == sorted(want_i[qi].tolist())


# ---------------------------------------------------------------------------
# quantized scan flavors (bf16 / int8) + full-precision guard
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_quantized_exact_matches_quant_oracle(dtype, metric):
    """Pallas quantized scan vs the ref quantized oracle: identical id sets
    (both score the SAME quantized values) and close scores."""
    rng = np.random.default_rng(17)
    Q, X = _np(5, 48, seed=11), _np(300, 48, seed=12)
    mask = rng.random(300) < 0.5
    k = 10
    dp, ip_ = ops.masked_exact_topk(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(mask), k,
        metric=metric, backend="pallas", dtype=dtype,
    )
    dr, ir = ops.masked_exact_topk(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(mask), k,
        metric=metric, backend="ref", dtype=dtype,
    )
    ip_, ir = np.asarray(ip_), np.asarray(ir)
    dp, dr = np.asarray(dp), np.asarray(dr)
    # quantized ties can swap adjacent ids; compare as sets + score values
    for qi in range(5):
        assert set(ip_[qi].tolist()) == set(ir[qi].tolist())
    np.testing.assert_allclose(
        np.where(np.isinf(dp), 0.0, dp), np.where(np.isinf(dr), 0.0, dr),
        rtol=5e-3, atol=5e-2,
    )


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_quantized_prestored_points_match_fresh_quantization(dtype):
    """Passing the cached pre-quantized stored matrix (+ its x_scale) must
    answer exactly like quantize-on-the-fly from f32."""
    rng = np.random.default_rng(19)
    Q, X = _np(4, 32, seed=13), _np(200, 32, seed=14)
    mask = rng.random(200) < 0.6
    stored, x_scale = ref.quantize_points(jnp.asarray(X), dtype)
    for backend in ("pallas", "ref"):
        d1, i1 = ops.masked_exact_topk(
            jnp.asarray(Q), jnp.asarray(X), jnp.asarray(mask), 8,
            backend=backend, dtype=dtype,
        )
        d2, i2 = ops.masked_exact_topk(
            jnp.asarray(Q), stored, jnp.asarray(mask), 8,
            backend=backend, dtype=dtype, x_scale=x_scale,
        )
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_quantized_scan_plus_guard_restores_f32_recall(dtype):
    """The planner's two-stage contract: quantized scan at the oversampled
    quant_guard_pool, then full-precision gather_rerank — top-k recall vs
    the f32 scan must be >= 0.95, and the emitted distances are exact f32
    distances (never quantized scores)."""
    from repro.runtime import planner

    rng = np.random.default_rng(23)
    Q, X = _np(8, 64, seed=15), _np(500, 64, seed=16)
    mask = rng.random(500) < 0.7
    k = 10
    pool = min(planner.quant_guard_pool(k), 500)
    _qd, pids = ops.masked_exact_topk(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(mask), pool,
        backend="auto", dtype=dtype,
    )
    gd, gi = ops.gather_rerank(jnp.asarray(Q), jnp.asarray(X), pids, k, backend="auto")
    fd, fi = ops.masked_exact_topk(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(mask), k, backend="auto"
    )
    gd, gi = np.asarray(gd), np.asarray(gi)
    fd, fi = np.asarray(fd), np.asarray(fi)
    hits = sum(
        len(set(gi[qi][gi[qi] >= 0]) & set(fi[qi][fi[qi] >= 0])) for qi in range(8)
    )
    total = int((fi >= 0).sum())
    assert hits / total >= 0.95
    # guarded distances are full-precision: every returned id's distance
    # equals the f32 oracle distance for that id
    full = np.asarray(ops.exact_distances(jnp.asarray(Q), jnp.asarray(X), backend="ref"))
    for qi in range(8):
        live = gi[qi] >= 0
        np.testing.assert_allclose(gd[qi][live], full[qi, gi[qi][live]], rtol=2e-4, atol=2e-3)


def test_quantize_roundtrip_error_bounds():
    """int8 symmetric quantization error is bounded by scale/2 per value;
    bf16 by ~2^-8 relative."""
    X = _np(100, 32, seed=21, scale=3.0)
    for dtype, tol in (("int8", None), ("bf16", 0.01)):
        stored, scale = ref.quantize_points(jnp.asarray(X), dtype)
        back = np.asarray(ref.dequantize_points(stored, scale))
        if dtype == "int8":
            assert np.abs(back - X).max() <= float(scale) * 0.5 + 1e-6
        else:
            assert np.abs(back - X).max() <= tol * np.abs(X).max() + 1e-6


# ---------------------------------------------------------------------------
# unified-kernel VMEM budget (BlockSpec walk)
# ---------------------------------------------------------------------------


def test_unified_block_shapes_walk():
    """Independently recompute every resident block of one unified grid
    step and assert the budget table (which the kernel builds its
    BlockSpecs from) matches — the docstring numbers cannot drift."""
    from repro.kernels import masked_topk as mt

    tq, tn, d, m, K, k = 8, 128, 1024, 16, 256, 128
    shapes = mt.unified_block_shapes(tq, tn, d, m, K, k)
    assert shapes["queries"] == ((tq, d), jnp.float32)
    assert shapes["points"] == ((tn, d), jnp.float32)
    assert shapes["luts"] == ((tq, m, K), jnp.float32)
    assert shapes["codes"] == ((tn, m), jnp.int32)
    assert shapes["selector"] == ((tq, tn), jnp.float32)
    assert shapes["out_dists"] == ((tq, k), jnp.float32)
    assert shapes["out_ids"] == ((tq, k), jnp.int32)
    assert shapes["score_scratch"] == ((tq, tn), jnp.float32)
    resident = sum(
        int(np.prod(s)) * np.dtype(dt).itemsize for s, dt in shapes.values()
    )
    assert mt.unified_vmem_bytes(tq, tn, d, m, K, k) == 2 * resident + tn * K * 4


def test_unified_vmem_fits_16mb_at_d4096():
    """Acceptance: the restructured unified kernel's worst-case estimate at
    D=4096 (m=16, K=256, k=128) fits a 16 MB VMEM budget WITHOUT halving
    tile_q — the old dual-buffer layout did not."""
    from repro.kernels import masked_topk as mt

    budget = 16 * 1024 * 1024
    assert mt.unified_vmem_bytes(8, 128, 4096, 16, 256, 128) < budget
    # and the shared-buffer design keeps even D=8192 under budget
    assert mt.unified_vmem_bytes(8, 128, 8192, 16, 256, 128) < budget


# ---------------------------------------------------------------------------
# autotuner (measured tile selection)
# ---------------------------------------------------------------------------


def test_autotune_defaults_on_cache_miss(tmp_path):
    from repro.kernels import autotune

    autotune.clear_cache()
    assert autotune.get_tiles(4096, 128, "exact", cache_path=tmp_path / "nope.json") \
        == autotune.DEFAULT_TILES
    autotune.clear_cache()


def test_autotune_reads_fixture_and_rejects_unknown_tiles(tmp_path):
    import json

    import jax

    from repro.kernels import autotune

    path = tmp_path / "cache.json"
    path.write_text(json.dumps({
        "meta": {"backend": jax.devices()[0].platform},
        "tiles": {
            autotune.cache_key(4096, 128, "exact"): [16, 256],
            autotune.cache_key(4096, 128, "pq"): [13, 77],  # never swept
        },
    }))
    autotune.clear_cache()
    assert autotune.get_tiles(4096, 128, "exact", cache_path=path) == (16, 256)
    # bucketing: 3000 rows round up to the same 4096 bucket
    assert autotune.get_tiles(3000, 128, "exact", cache_path=path) == (16, 256)
    # invalid tiles are discarded -> defaults
    assert autotune.get_tiles(4096, 128, "pq", cache_path=path) == autotune.DEFAULT_TILES
    autotune.clear_cache()


@pytest.mark.parametrize("meta", [{"backend": "other"}, {}, None])
def test_autotune_ignores_fixture_from_another_platform(tmp_path, meta):
    """Tiles timed on one platform never steer another: a fixture whose
    ``meta.backend`` is not the running platform (or is not recorded) gives
    the defaults, as the committed CPU-swept fixture does on a TPU."""
    import json

    from repro.kernels import autotune

    fixture = {"tiles": {autotune.cache_key(4096, 128, "exact"): [16, 256]}}
    if meta is not None:
        fixture["meta"] = meta
    path = tmp_path / "cache.json"
    path.write_text(json.dumps(fixture))
    autotune.clear_cache()
    assert autotune.get_tiles(4096, 128, "exact", cache_path=path) == autotune.DEFAULT_TILES
    autotune.clear_cache()


def test_autotune_candidates_include_defaults():
    """Structural never-regress: the default tiling is always a candidate,
    and a challenger must beat it by the hysteresis margin."""
    from repro.kernels import autotune

    assert autotune.DEFAULT_TILES in autotune.CANDIDATES
    assert 0.0 < autotune.HYSTERESIS < 0.5


def test_autotune_tiles_give_identical_results():
    """Whatever tiles the autotuner picks, the kernel answers identically —
    tiling is a performance knob, never a semantics knob."""
    rng = np.random.default_rng(29)
    Q, X = _np(9, 40, seed=25), _np(300, 40, seed=26)
    mask = rng.random(300) < 0.5
    from repro.kernels import autotune

    base = None
    for tq, tn in autotune.CANDIDATES:
        d, i = ops.masked_exact_topk(
            jnp.asarray(Q), jnp.asarray(X), jnp.asarray(mask), 7,
            backend="pallas", tile_q=tq, tile_n=tn,
        )
        d, i = np.asarray(d), np.asarray(i)
        if base is None:
            base = (d, i)
        else:
            np.testing.assert_array_equal(i, base[1])
            np.testing.assert_allclose(
                np.where(np.isinf(d), 0.0, d),
                np.where(np.isinf(base[0]), 0.0, base[0]),
                rtol=2e-4, atol=2e-3,
            )


# ---------------------------------------------------------------------------
# device-copy caching (identity-keyed)
# ---------------------------------------------------------------------------


class _FakeGraph:
    def __init__(self, vectors, n):
        self.vectors = vectors
        self.n = n


def test_device_vectors_cached_by_identity():
    from repro.kernels import device_cache

    g = _FakeGraph(_np(50, 8, seed=31), 40)
    a = device_cache.device_vectors(g)
    b = device_cache.device_vectors(g)
    assert a is b  # cache hit: same device buffer
    np.testing.assert_allclose(np.asarray(a), g.vectors[:40])


def test_device_vectors_staleness_same_length_swap():
    """Regression (the old cache keyed by n alone): swapping in a DIFFERENT
    array of the SAME length must invalidate the cached device copy."""
    from repro.kernels import device_cache

    g = _FakeGraph(_np(50, 8, seed=33), 50)
    a = device_cache.device_vectors(g)
    g.vectors = _np(50, 8, seed=34)  # same shape, new contents
    b = device_cache.device_vectors(g)
    assert a is not b
    np.testing.assert_allclose(np.asarray(b), g.vectors[:50])


def test_device_vectors_revalidates_on_n_change():
    from repro.kernels import device_cache

    vecs = _np(50, 8, seed=35)
    g = _FakeGraph(vecs, 30)
    a = device_cache.device_vectors(g)
    assert np.asarray(a).shape == (30, 8)
    g.n = 45  # same array grew its live prefix (insert_batch)
    b = device_cache.device_vectors(g)
    assert np.asarray(b).shape == (45, 8)
    np.testing.assert_allclose(np.asarray(b), vecs[:45])


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_device_vectors_quant_cached_per_dtype(dtype):
    from repro.kernels import device_cache

    g = _FakeGraph(_np(60, 16, seed=37), 60)
    s1, sc1 = device_cache.device_vectors_quant(g, dtype)
    s2, sc2 = device_cache.device_vectors_quant(g, dtype)
    assert s1 is s2 and sc1 == sc2
    f32 = device_cache.device_vectors(g)
    assert np.asarray(f32).dtype == np.float32  # separate attr per flavor
