"""Device-resident sharded ANN index — the probe path on the TPU mesh.

This is the TPU-native rendering of the paper's Stage-A probe (DESIGN.md §2):
each ``data``-axis slice owns one Vamana shard as dense arrays in HBM
(vectors, padded adjacency, medoid); a probe is a ``shard_map`` over the
``data`` axis running the jittable beam search per shard, followed by an
``all_gather`` + global ``top_k`` merge (Stage C).  The executor/SSD path in
:mod:`repro.runtime` and this device path share the same graph semantics —
blobs decoded from a Puffin file can be uploaded straight into a
:class:`DeviceAnnIndex`.

For decode-time retrieval (kNN-LM), :func:`make_probe_fn` returns a function
that can be fused into ``serve_step`` under the same mesh.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, Sharding

from repro.core.vamana import _beam_search


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["vectors", "adjacency", "medoids", "counts", "payload"],
    meta_fields=[],
)
@dataclass
class DeviceAnnIndex:
    """Sharded index arrays.  Leading dim = shard (maps onto 'data' axis)."""

    vectors: jnp.ndarray  # (n_shards, cap, D) f32|bf16
    adjacency: jnp.ndarray  # (n_shards, cap, R) int32
    medoids: jnp.ndarray  # (n_shards,) int32
    counts: jnp.ndarray  # (n_shards,) int32 valid nodes per shard
    payload: Optional[jnp.ndarray] = None  # (n_shards, cap) int32 e.g. token ids

    @property
    def n_shards(self) -> int:
        return self.vectors.shape[0]

    def shardings(self, mesh: Mesh, shard_axes: Tuple[str, ...] = ("data",)):
        spec = P(shard_axes if len(shard_axes) > 1 else shard_axes[0])
        s = NamedSharding(mesh, spec)
        return DeviceAnnIndex(
            vectors=s, adjacency=s, medoids=s, counts=s,
            payload=s if self.payload is not None else None,
        )

    @staticmethod
    def from_graphs(
        graphs, payloads=None, dtype=jnp.float32, sharding: Optional[Sharding] = None
    ) -> "DeviceAnnIndex":
        """Pack host VamanaGraphs (equal capacity) into device arrays.

        With ``sharding`` (e.g. ``NamedSharding(mesh, P("data"))``) every
        array goes from the host straight onto its shard's devices, so a
        sharded index never lands whole on the default device."""
        cap = max(g.vectors.shape[0] for g in graphs)
        R = max(g.adjacency.shape[1] for g in graphs)
        D = graphs[0].dim
        n = len(graphs)
        vecs = np.zeros((n, cap, D), np.float32)
        adj = np.full((n, cap, R), -1, np.int32)
        meds = np.zeros(n, np.int32)
        counts = np.zeros(n, np.int32)
        pl = None
        if payloads is not None:
            pl = np.zeros((n, cap), np.int32)
        for i, g in enumerate(graphs):
            c = g.vectors.shape[0]
            vecs[i, :c] = g.vectors
            adj[i, :c, : g.adjacency.shape[1]] = g.adjacency
            meds[i] = g.medoid
            counts[i] = g.n
            if payloads is not None:
                pl[i, : len(payloads[i])] = payloads[i]
        return DeviceAnnIndex(
            vectors=jax.device_put(vecs.astype(dtype), sharding),
            adjacency=jax.device_put(adj, sharding),
            medoids=jax.device_put(meds, sharding),
            counts=jax.device_put(counts, sharding),
            payload=jax.device_put(pl, sharding) if pl is not None else None,
        )

    @staticmethod
    def abstract(n_shards: int, cap: int, dim: int, R: int, dtype=jnp.bfloat16, with_payload: bool = True):
        """ShapeDtypeStructs for dry-run lowering (no allocation)."""
        return DeviceAnnIndex(
            vectors=jax.ShapeDtypeStruct((n_shards, cap, dim), dtype),
            adjacency=jax.ShapeDtypeStruct((n_shards, cap, R), jnp.int32),
            medoids=jax.ShapeDtypeStruct((n_shards,), jnp.int32),
            counts=jax.ShapeDtypeStruct((n_shards,), jnp.int32),
            payload=jax.ShapeDtypeStruct((n_shards, cap), jnp.int32) if with_payload else None,
        )


def make_probe_fn(
    mesh: Mesh,
    *,
    k: int,
    L: int = 32,
    metric: str = "l2",
    oversample: int = 2,
    shard_axes: Tuple[str, ...] = ("data",),
):
    """Build the shard_map'd Stage-A+C probe.

    ``shard_axes`` controls shard ownership: ("data",) gives one shard per
    data slice (replicated across model — fine for small indexes);
    ("data", "model") flattens both axes so a billion-vector index holds one
    ~4M-vector shard per chip (6 GB of bf16 vectors + 1 GB adjacency at
    768 d, R=64 — the paper's §9 configuration on a v5e-256 pod).

    Returned fn: (index, queries (B, D) replicated) ->
        (dists (B, k), payload_or_ids (B, k)) globally merged.
    """
    max_iters = int(1.3 * L) + 8
    k_local = min(k * oversample, L)
    has_pod = "pod" in mesh.axis_names

    def local_probe(vectors, adjacency, medoid, count, payload, queries):
        # shapes inside shard_map: (S_local, cap, D), (S_local, cap, R),
        # (S_local,), (S_local,), (S_local, cap).  S_local > 1 when there are
        # more shards than data slices (tests; small deployments) — vmap the
        # beam search over the local shard dim.
        cap = vectors.shape[1]

        def one_shard(vecs, adj, cnt, med, pl_tab):
            ids, dists, _, _ = _beam_search(
                vecs.astype(jnp.float32), adj, cnt, med,
                queries.astype(jnp.float32), L, max_iters, metric, False,
            )
            neg, idx = jax.lax.top_k(-dists, k_local)
            lids = jnp.take_along_axis(ids, idx, axis=1)
            pl = jnp.where(lids < cap, pl_tab[jnp.clip(lids, 0, cap - 1)], -1)
            return -neg, pl

        d_s, p_s = jax.vmap(one_shard)(vectors, adjacency, count, medoid, payload)
        # (S_local, B, k_local) -> (B, S_local*k_local)
        local_d = d_s.transpose(1, 0, 2).reshape(queries.shape[0], -1)
        pl = p_s.transpose(1, 0, 2).reshape(queries.shape[0], -1)
        # Stage C merge: gather candidates over every shard axis, global top-k
        all_d, all_p = local_d, pl
        gather_axes = shard_axes + (("pod",) if has_pod else ())
        for ax in gather_axes:
            all_d = jax.lax.all_gather(all_d, ax, axis=1, tiled=True)
            all_p = jax.lax.all_gather(all_p, ax, axis=1, tiled=True)
        negg, gi = jax.lax.top_k(-all_d, k)
        return -negg, jnp.take_along_axis(all_p, gi, axis=1)

    pspec_sharded = P(shard_axes if len(shard_axes) > 1 else shard_axes[0])
    pspec_none = P()
    in_specs = (
        pspec_sharded,  # vectors
        pspec_sharded,  # adjacency
        pspec_sharded,  # medoids
        pspec_sharded,  # counts
        pspec_sharded,  # payload
        pspec_none,  # queries replicated
    )
    out_specs = (pspec_none, pspec_none)

    def probe(index: DeviceAnnIndex, queries: jnp.ndarray):
        payload = index.payload if index.payload is not None else index.adjacency[:, :, 0]
        return jax.shard_map(
            local_probe,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )(index.vectors, index.adjacency, index.medoids, index.counts, payload, queries)

    return probe
