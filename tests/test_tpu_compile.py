"""Compile the main-path programs for a described TPU v5e, no chip needed.

The TPU compiler runs here against a ``v5e:2x2`` topology description at the
paper's width (D=768, ``pq_m=48``), a 65,536-row shard, a 64-query batch and
k=100; the beam search also at its smaller query-slot buckets (16, 32, 48).
The programs whose shapes grow with the width (the gather-rerank kernel, the
Stage-B bucket distances, the k-means assignment, the beam search with and
without PQ, the robust prune) also compile at the OpenAI embedding
width (D=1536, ``pq_m=96``); both widths keep 16-d PQ subspaces.  The compiler
refuses what interpret mode accepts: unaligned or mislaid blocks, too much
VMEM, an unpartitionable sharded program.  Nothing runs, so these tests say
nothing about results or speed.

The topology is described only inside the module fixture: loading the TPU
library while a module is imported would give xdist workers different tests.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.vamana import _beam_search, _masked_beam_search, _robust_prune
from repro.kernels import ops
from repro.kernels.kmeans_assign import kmeans_assign_pallas
from repro.kernels.masked_topk import (
    masked_exact_topk_multi_pallas,
    masked_exact_topk_pallas,
    masked_pq_topk_pallas,
    unified_masked_topk_pallas,
)
from repro.kernels.pq_scan import pq_scan_pallas
from repro.kernels.rerank import gather_rerank_pallas, rerank_distances_pallas
from repro.serving.device_index import DeviceAnnIndex, make_probe_fn

D, N, Q, K = 768, 65536, 64, 100
R, L = 64, 100
MAX_ITERS = int(1.3 * L) + 8
PQ_M, PQ_K = 48, 256
PQ_DSUB = 16  # pq_m = D / 16 in both deployments
# D of the benchmark's deployments: cohere-768d, openai-1536d
WIDTHS = pytest.mark.parametrize("dim", [768, 1536], ids=["D768", "D1536"])


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # entries compiled for a described chip cannot be read back without one
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tiles", [(8, 128), (16, 256), (32, 128)])
@pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
def test_masked_exact_topk_compiles(one_chip, dtype, tiles):
    dt = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}[dtype]
    args = [
        _sds((Q, D), dt, one_chip),
        _sds((N, D), dt, one_chip),
        _sds((1, N), jnp.float32, one_chip),
    ]
    kw = dict(k=K, tile_q=tiles[0], tile_n=tiles[1], interpret=False)
    if dtype == "int8":
        kw["scales"] = _sds((1, 2), jnp.float32, one_chip)
    assert _compiled_kernel(masked_exact_topk_pallas.lower(*args, **kw).compile())


def test_masked_exact_topk_multi_compiles(one_chip):
    compiled = masked_exact_topk_multi_pallas.lower(
        _sds((Q, D), jnp.float32, one_chip),
        _sds((N, D), jnp.float32, one_chip),
        _sds((Q, N), jnp.float32, one_chip),
        k=K, interpret=False,
    ).compile()
    assert _compiled_kernel(compiled)


@pytest.mark.parametrize("m", [8, PQ_M])
def test_masked_pq_topk_compiles(one_chip, m):
    compiled = masked_pq_topk_pallas.lower(
        _sds((Q, m, PQ_K), jnp.float32, one_chip),
        _sds((N, m), jnp.int32, one_chip),
        _sds((1, N), jnp.float32, one_chip),
        k=K, interpret=False,
    ).compile()
    assert _compiled_kernel(compiled)


def test_unified_masked_topk_compiles(one_chip):
    compiled = unified_masked_topk_pallas.lower(
        _sds((Q, D), jnp.float32, one_chip),
        _sds((N, D), jnp.float32, one_chip),
        _sds((Q, PQ_M, PQ_K), jnp.float32, one_chip),
        _sds((N, PQ_M), jnp.int32, one_chip),
        _sds((Q, N), jnp.float32, one_chip),
        k=K, interpret=False,
    ).compile()
    assert _compiled_kernel(compiled)


@WIDTHS
@pytest.mark.parametrize("pool", [256, 512])
def test_gather_rerank_compiles(one_chip, pool, dim):
    compiled = gather_rerank_pallas.lower(
        _sds((Q, dim), jnp.float32, one_chip),
        _sds((N, dim), jnp.float32, one_chip),
        _sds((Q, pool), jnp.int32, one_chip),
        k=K, interpret=False,
    ).compile()
    assert _compiled_kernel(compiled)


def test_rerank_distances_and_pq_scan_compile(one_chip):
    dists = rerank_distances_pallas.lower(
        _sds((128, D), jnp.float32, one_chip),  # ops.exact_distances pads Q to 128
        _sds((N, D), jnp.float32, one_chip),
        interpret=False,
    ).compile()
    adc = pq_scan_pallas.lower(
        _sds((Q, PQ_M, PQ_K), jnp.float32, one_chip),
        _sds((N, PQ_M), jnp.int32, one_chip),
        interpret=False,
    ).compile()
    assert _compiled_kernel(dists) and _compiled_kernel(adc)


@WIDTHS
@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("rows", [256, 2048])
def test_stage_b_bucket_distances_compile(one_chip, rows, metric, dim):
    compiled = ops._bucket_distances.lower(
        _sds((Q, dim), jnp.float32, one_chip),
        _sds((rows, dim), jnp.float32, one_chip),
        metric,
    ).compile()
    assert compiled.memory_analysis() is not None


@WIDTHS
def test_kmeans_assign_compiles(one_chip, dim):
    compiled = kmeans_assign_pallas.lower(
        _sds((N, dim), jnp.float32, one_chip),
        _sds((128, dim), jnp.float32, one_chip),
        tile_n=256, tile_k=128, interpret=False,
    ).compile()
    assert _compiled_kernel(compiled)


@WIDTHS
@pytest.mark.parametrize("use_pq", [False, True])
def test_beam_search_compiles(one_chip, use_pq, dim):
    pq_m = dim // PQ_DSUB
    points = (N, pq_m) if use_pq else (N, dim)
    queries = (Q, pq_m, PQ_K) if use_pq else (Q, dim)
    compiled = _beam_search.lower(
        _sds(points, jnp.int32 if use_pq else jnp.float32, one_chip),
        _sds((N, R), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip),
        _sds(queries, jnp.float32, one_chip),
        L, MAX_ITERS, "l2", use_pq,
    ).compile()
    assert compiled.memory_analysis() is not None


@WIDTHS
@pytest.mark.parametrize("use_pq", [False, True])
@pytest.mark.parametrize("slots", [16, 32, 48])
def test_beam_search_compiles_at_fewer_slots(one_chip, slots, use_pq, dim):
    """A traversal call runs only the query slots its queries need
    (``vamana.query_slots``): the buckets below the full 64."""
    pq_m = dim // PQ_DSUB
    points = (N, pq_m) if use_pq else (N, dim)
    queries = (slots, pq_m, PQ_K) if use_pq else (slots, dim)
    compiled = _beam_search.lower(
        _sds(points, jnp.int32 if use_pq else jnp.float32, one_chip),
        _sds((N, R), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip),
        _sds(queries, jnp.float32, one_chip),
        L, MAX_ITERS, "l2", use_pq,
    ).compile()
    assert compiled.memory_analysis() is not None


@WIDTHS
@pytest.mark.parametrize("slots", [16, 64])
def test_pq_beam_search_has_no_lut_gather(one_chip, slots, dim):
    """A step's PQ distances select each LUT entry by a one-hot compare: no
    gather reads the (slots, m, 256) f32 LUTs (the TPU's per-lane gather
    reads a whole tile per element), and the one-hot is fused, never
    materialised as a (slots, R, m, 256) f32 buffer."""
    pq_m = dim // PQ_DSUB
    lowered = _beam_search.lower(
        _sds((N, pq_m), jnp.int32, one_chip),
        _sds((N, R), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip),
        _sds((slots, pq_m, PQ_K), jnp.float32, one_chip),
        L, MAX_ITERS, "l2", True,
    )
    lut = f"tensor<{slots}x{pq_m}x{PQ_K}xf32>"
    lut_gathers = [
        line for line in lowered.as_text().splitlines() if "gather" in line and lut in line
    ]
    assert not lut_gathers
    temp = lowered.compile().memory_analysis().temp_size_in_bytes
    assert temp < slots * R * pq_m * PQ_K * 4


def test_masked_beam_search_compiles(one_chip):
    compiled = _masked_beam_search.lower(
        _sds((N, D), jnp.float32, one_chip),
        _sds((N, R), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip),
        _sds((Q, D), jnp.float32, one_chip),
        _sds((2, N), jnp.bool_, one_chip),
        _sds((Q,), jnp.int32, one_chip),
        L, 4 * K, MAX_ITERS, "l2", False,
    ).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("slots", [16, 64])
def test_masked_pq_beam_search_compiles_at_the_filtered_cells_shapes(one_chip, slots):
    """The ``cohere-768d.range-10pct`` traversal: an 8,192-row shard's PQ
    codes (m=48), one mask row a slot (``search_masked`` pads a call's
    distinct rows to its slots), the planner's MaskedBeam width 160 as the
    admit target, L=100."""
    cap = 8192
    compiled = _masked_beam_search.lower(
        _sds((cap, PQ_M), jnp.int32, one_chip),
        _sds((cap, R), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip),
        _sds((slots, PQ_M, PQ_K), jnp.float32, one_chip),
        _sds((slots, cap), jnp.bool_, one_chip),
        _sds((slots,), jnp.int32, one_chip),
        L, 160, MAX_ITERS, "l2", True,
    ).compile()
    assert compiled.memory_analysis() is not None


@WIDTHS
def test_robust_prune_compiles(one_chip, dim):
    batch = 128
    compiled = _robust_prune.lower(
        _sds((N, dim), jnp.float32, one_chip),
        _sds((batch, dim), jnp.float32, one_chip),
        _sds((batch, L + MAX_ITERS), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip),
        R, 1.2, "l2",
    ).compile()
    assert compiled.memory_analysis() is not None


def test_probe_fn_compiles_on_four_chips(topo):
    """The shard_map Stage-A/C probe, one 65,536-row shard per chip of a
    2x2 host: it partitions, and the merge is an all-gather."""
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"))
    sharded = NamedSharding(mesh, P("data"))
    abstract = DeviceAnnIndex.abstract(n_shards=4, cap=N, dim=D, R=R, dtype=jnp.float32)
    idx = jax.tree.map(lambda s: _sds(s.shape, s.dtype, sharded), abstract)
    queries = _sds((Q, D), jnp.float32, NamedSharding(mesh, P()))
    compiled = jax.jit(make_probe_fn(mesh, k=K, L=L)).lower(idx, queries).compile()
    assert "all-gather" in compiled.as_text()
    assert compiled.memory_analysis() is not None
