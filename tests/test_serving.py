"""Serving: device-resident ANN probe, kNN-LM retrieval decode."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.vamana import VamanaParams, brute_force_topk, build_vamana, recall_at_k
from repro.launch.mesh import make_debug_mesh
from repro.models.model import build_model
from repro.serving.device_index import DeviceAnnIndex, make_probe_fn
from repro.serving.serve_loop import ServeConfig, make_serve_fns
from conftest import clustered_vectors


@pytest.fixture(scope="module")
def device_index():
    rng = np.random.default_rng(0)
    X, _ = clustered_vectors(rng, n_clusters=8, per_cluster=125, dim=16)
    # two shards (single-device mesh still exercises shard_map semantics)
    half = len(X) // 2
    g1 = build_vamana(X[:half], VamanaParams(R=12, L=24), passes=1, batch=128)
    g2 = build_vamana(X[half:], VamanaParams(R=12, L=24), passes=1, batch=128)
    payloads = [np.arange(half), np.arange(half, len(X))]
    idx = DeviceAnnIndex.from_graphs([g1, g2], payloads=payloads)
    return X, idx


def test_device_probe_matches_host_search(device_index):
    X, idx = device_index
    mesh = make_debug_mesh(1, 1)
    # one device: both shards probed on it (leading dim = 2 shards over
    # data axis of size 1 -> sequential but same math)
    probe = make_probe_fn(mesh, k=10, L=24)
    rng = np.random.default_rng(1)
    Q = X[rng.choice(len(X), 8)] + 0.05 * rng.normal(size=(8, 16)).astype(np.float32)
    with mesh:
        d, ids = jax.jit(probe)(idx, jnp.asarray(Q))
    _, truth = brute_force_topk(X, Q, 10)
    rec = recall_at_k(np.asarray(ids), truth)
    assert rec >= 0.85, rec
    # distances sorted ascending
    d = np.asarray(d)
    assert (np.diff(d, axis=1) >= -1e-4).all()


def test_abstract_index_lowering(device_index):
    """The dry-run path: probe lowers+compiles from ShapeDtypeStructs."""
    mesh = make_debug_mesh(1, 1)
    probe = make_probe_fn(mesh, k=8, L=16)
    idx = DeviceAnnIndex.abstract(n_shards=1, cap=2048, dim=16, R=12)
    q = jax.ShapeDtypeStruct((4, 16), jnp.float32)
    with mesh:
        compiled = jax.jit(probe).lower(idx, q).compile()
    assert compiled is not None


def test_from_graphs_places_shards_on_their_sharding(device_index):
    """With a sharding, every index array lands straight on the mesh's
    devices (not whole on the default one), and the probe answers the same."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    X, idx = device_index
    mesh = make_debug_mesh(1, 1)
    rng = np.random.default_rng(0)
    half = len(X) // 2
    graphs = [
        build_vamana(part, VamanaParams(R=12, L=24), passes=1, batch=128)
        for part in (X[:half], X[half:])
    ]
    payloads = [np.arange(half), np.arange(half, len(X))]
    sharded = DeviceAnnIndex.from_graphs(
        graphs, payloads=payloads, sharding=NamedSharding(mesh, P("data"))
    )
    for leaf, spec in zip(
        jax.tree.leaves(sharded), jax.tree.leaves(sharded.shardings(mesh))
    ):
        assert leaf.sharding == spec
    probe = jax.jit(make_probe_fn(mesh, k=10, L=24))
    Q = X[rng.choice(len(X), 8)]
    with mesh:
        d_a, i_a = probe(idx, jnp.asarray(Q))
        d_b, i_b = probe(sharded, jnp.asarray(Q))
    np.testing.assert_array_equal(np.asarray(i_a), np.asarray(i_b))
    np.testing.assert_allclose(np.asarray(d_a), np.asarray(d_b))


def test_knn_lm_decode_runs_and_mixes():
    cfg = dataclasses.replace(reduced(get_config("qwen2.5-3b")), num_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_debug_mesh(1, 1)
    rng = np.random.default_rng(2)
    # corpus in lm_head space with token payloads
    d = cfg.d_model
    corpus = rng.normal(size=(500, d)).astype(np.float32)
    tokens = rng.integers(0, cfg.vocab_size, size=500)
    g = build_vamana(corpus, VamanaParams(R=8, L=16), passes=1, batch=128)
    idx = DeviceAnnIndex.from_graphs([g], payloads=[tokens])
    probe = make_probe_fn(mesh, k=4, L=16)
    prefill, decode, sample, sh = make_serve_fns(
        model, mesh, cfg=ServeConfig(knn_lambda=0.5), retrieval=probe,
        index_template=idx, batch_hint=2, max_len_hint=16,
    )
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, 8)))
    cache = model.init_cache(2, 16)
    with mesh:
        _, cache = prefill(params, ids, cache)
        logits, cache = decode(params, ids[:, -1:], cache, jnp.int32(8), idx)
    assert bool(jnp.isfinite(logits).all())
    # λ=0 vs λ=0.5 must differ (retrieval actually contributes)
    prefill0, decode0, _, _ = make_serve_fns(
        model, mesh, cfg=ServeConfig(knn_lambda=0.0), retrieval=probe,
        index_template=idx, batch_hint=2, max_len_hint=16,
    )
    cache0 = model.init_cache(2, 16)
    with mesh:
        _, cache0 = prefill0(params, ids, cache0)
        logits0, _ = decode0(params, ids[:, -1:], cache0, jnp.int32(8), idx)
    assert float(jnp.abs(logits - logits0).max()) > 1e-4


def test_greedy_generation_loop():
    cfg = dataclasses.replace(reduced(get_config("chatglm3-6b")), num_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    mesh = make_debug_mesh(1, 1)
    prefill, decode, sample, _ = make_serve_fns(model, mesh, batch_hint=1, max_len_hint=24)
    rng = np.random.default_rng(3)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(1, 8)))
    cache = model.init_cache(1, 24)
    with mesh:
        logits, cache = prefill(params, prompt, cache)
        tok = sample(logits, jax.random.PRNGKey(0))
        outs = [int(tok[0, 0])]
        for t in range(8, 16):
            logits, cache = decode(params, tok, cache, jnp.int32(t))
            tok = sample(logits, jax.random.PRNGKey(t))
            outs.append(int(tok[0, 0]))
    assert len(outs) == 9
    assert all(0 <= t < cfg.vocab_size for t in outs)
