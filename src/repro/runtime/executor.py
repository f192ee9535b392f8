"""Executor process model (paper §3.1): stateless worker + SSD/L1 caches.

An executor owns:
- an **SSD cache** directory keyed by ``(object_path, credential_fingerprint,
  byte_range, etag)`` — raw blob bytes survive across tasks and are safe to
  lose (the object store is the source of truth);
- an **L1 cache** of deserialized Vamana graphs (bounded LRU);
- task handlers for the five fragment kinds: partition scan, shard build,
  shard probe, exact rerank, shard refresh.

Failure-injection hooks (``kill()``, ``fail_next()``, ``delay_next()``)
drive the fault-tolerance and straggler tests.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core.blobs import ShardLocationMap, decode_shard_blob, encode_shard_blob
from repro.runtime import planner
from repro.runtime.predicates import row_group_mask
from repro.core.vamana import QUERY_BATCH, VamanaGraph, VamanaParams, build_vamana, stream_slots
from repro.core.pq import PQCodebook, encode as pq_encode
from repro.iceberg.puffin import _decompress  # codec shared with Puffin blobs
from repro.kernels import device_cache, ops
from repro.lakehouse.objectstore import ObjectStore
from repro.lakehouse.vparquet import VParquetReader
from repro.runtime import fragments as F
from repro.serving.metrics import count, span

import jax.numpy as jnp


class ExecutorDead(RuntimeError):
    """Raised when a task lands on a dead executor (heartbeat timeout)."""


class InjectedFailure(RuntimeError):
    """Deterministic task failure for tests."""


def _scan_files_with_locations(
    store: ObjectStore, files: List[str]
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, List[str]]:
    """Read the vector column of ``files`` with per-row locations.

    Returns (vectors, file_idx, row_group, row_offset, file_paths)."""
    vecs: List[np.ndarray] = []
    fidx: List[np.ndarray] = []
    rgrp: List[np.ndarray] = []
    roff: List[np.ndarray] = []
    for i, path in enumerate(files):
        r = VParquetReader.from_store(store, path)
        for rg_id, rg in enumerate(r.row_groups):
            arr = r.read_column("vec", [rg_id])
            n = arr.shape[0]
            vecs.append(arr)
            fidx.append(np.full(n, i, np.uint32))
            rgrp.append(np.full(n, rg_id, np.uint32))
            roff.append(np.arange(n, dtype=np.uint32))
    if not vecs:
        return (
            np.empty((0, 0), np.float32),
            np.empty(0, np.uint32),
            np.empty(0, np.uint32),
            np.empty(0, np.uint32),
            list(files),
        )
    return (
        np.concatenate(vecs),
        np.concatenate(fidx),
        np.concatenate(rgrp),
        np.concatenate(roff),
        list(files),
    )


def _locmap_membership(
    locmap: ShardLocationMap, n: int, live: Optional[np.ndarray] = None
) -> List[Tuple[str, int]]:
    """Distinct (file_path, row_group) pairs a shard's (live) rows occupy —
    the zone-map membership used for coordinator-side shard pruning."""
    fidx = np.asarray(locmap.file_idx[:n], np.int64)
    rgrp = np.asarray(locmap.row_group[:n], np.int64)
    if live is not None:
        fidx, rgrp = fidx[live[:n]], rgrp[live[:n]]
    return sorted({(locmap.file_paths[int(f)], int(g)) for f, g in zip(fidx, rgrp)})


def _owner_shards(
    vectors: np.ndarray, centroids: np.ndarray, shard_of_partition: np.ndarray
) -> np.ndarray:
    part, _ = ops.kmeans_assign(
        jnp.asarray(vectors), jnp.asarray(centroids), backend="ref"
    )
    return shard_of_partition[np.asarray(part)]


class Executor:
    def __init__(
        self,
        executor_id: str,
        store: ObjectStore,
        cache_dir: str,
        *,
        l1_capacity: int = 4,
        credential_fingerprint: str = "default-cred",
    ) -> None:
        self.executor_id = executor_id
        self.store = store
        self.cache_dir = cache_dir
        os.makedirs(cache_dir, exist_ok=True)
        self.cred = credential_fingerprint
        self._l1: "OrderedDict[str, Tuple[VamanaGraph, ShardLocationMap]]" = OrderedDict()
        self._l1_capacity = l1_capacity
        # filtered search: (shard key, predicate) -> per-vector-id bool mask
        self._mask_cache: "OrderedDict[tuple, np.ndarray]" = OrderedDict()
        self._mask_cache_capacity = 64
        self._lock = threading.Lock()
        # failure injection
        self.dead = False
        self._fail_budget = 0
        self._delay_next = 0.0
        self._kill_mid_task = 0
        self._kill_hold_s = 0.0
        # metrics
        self.tasks_done = 0
        self.cache_hits = 0
        self.cache_misses = 0
        # masked top-k kernel calls issued (single- and multi-mask flavors).
        # The executor-wide total is lock-guarded; the per-TASK counts in
        # BatchProbeResult come from a thread-local tally (each task
        # attempt runs on its own scheduler thread), so concurrent probes
        # on one executor cannot misattribute each other's dispatches.
        self.masked_kernel_dispatches = 0
        # gather-rerank kernel calls (ADC-pool reranks, quantized-scan
        # guards, PQ-traversal pool reranks).  Deliberately a SEPARATE
        # counter: rerank stages have never counted toward
        # masked_kernel_dispatches, and the dispatch-
        # count invariants the fragment tests assert must keep meaning
        # "masked scan dispatches".
        self.rerank_kernel_dispatches = 0
        self._dispatch_tls = threading.local()

    # -- health -----------------------------------------------------------
    def heartbeat(self) -> bool:
        return not self.dead

    def kill(self) -> None:
        self.dead = True

    def revive(self) -> None:
        self.dead = False
        self._kill_mid_task = 0  # disarm any unspent chaos budget

    def fail_next(self, count: int = 1) -> None:
        self._fail_budget = count

    def delay_next(self, seconds: float) -> None:
        self._delay_next = seconds

    def kill_next(self, count: int = 1, *, hold_s: float = 0.0) -> None:
        """Chaos hook: die while HOLDING the next ``count`` accepted tasks.

        Unlike ``kill()`` (dead before the next task is even accepted) the
        executor passes the gate, goes heartbeat-dead mid-task — holding the
        fragment for ``hold_s`` so the scheduler's lease monitor can observe
        the death — and then loses the result (``ExecutorDead``).  This is
        the mid-wave failure the lease re-dispatch path exists for."""
        self._kill_mid_task = count
        self._kill_hold_s = hold_s

    def _gate(self) -> None:
        if self.dead:
            raise ExecutorDead(self.executor_id)
        if self._fail_budget > 0:
            self._fail_budget -= 1
            raise InjectedFailure(f"injected failure on {self.executor_id}")
        if self._delay_next > 0:
            d, self._delay_next = self._delay_next, 0.0
            time.sleep(d)

    # -- SSD cache ------------------------------------------------------------
    def _cache_path(self, object_path: str, offset: int, length: int) -> str:
        etag = ""
        try:
            etag = self.store.stat(object_path).etag
        except Exception:
            pass
        key = hashlib.sha1(
            f"{object_path}|{self.cred}|{offset}|{length}|{etag}".encode()
        ).hexdigest()
        return os.path.join(self.cache_dir, key + ".blob")

    def has_cached(self, cache_key: Optional[str]) -> bool:
        if not cache_key:
            return False
        with self._lock:
            if any(k.startswith(cache_key) for k in self._l1):
                return True
        # any SSD entry tagged with this logical key
        marker = os.path.join(self.cache_dir, hashlib.sha1(cache_key.encode()).hexdigest() + ".key")
        return os.path.exists(marker)

    def _mark_cached(self, cache_key: Optional[str]) -> None:
        if not cache_key:
            return
        marker = os.path.join(self.cache_dir, hashlib.sha1(cache_key.encode()).hexdigest() + ".key")
        with open(marker, "wb") as f:
            f.write(b"1")

    def fetch_range_cached(self, object_path: str, offset: int, length: int) -> Tuple[bytes, bool]:
        """Range-read through the SSD cache.  Returns (bytes, cache_hit)."""
        cpath = self._cache_path(object_path, offset, length)
        if os.path.exists(cpath):
            with open(cpath, "rb") as f:
                self.cache_hits += 1
                return f.read(), True
        data = self.store.get_range(object_path, offset, length)
        tmp = cpath + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, cpath)
        self.cache_misses += 1
        return data, False

    def _load_shard(
        self, puffin_path: str, offset: int, length: int, codec: Optional[str], cache_key: Optional[str]
    ) -> Tuple[VamanaGraph, ShardLocationMap, bool]:
        with span("executor.load_shard"):
            l1_key = f"{cache_key or puffin_path}@{offset}"
            with self._lock:
                if l1_key in self._l1:
                    self._l1.move_to_end(l1_key)
                    self.cache_hits += 1
                    g, lm = self._l1[l1_key]
                    return g, lm, True
            raw, hit = self.fetch_range_cached(puffin_path, offset, length)
            payload = _decompress(codec, raw)
            graph, locmap = decode_shard_blob(payload, lazy_vectors=True)
            if not np.any(graph.vectors[: graph.n]):
                # lean blob (paper §4.3 retention policy): full-precision vectors
                # omitted — re-fetch them from Parquet through the location map
                # (the "extra round trip" trade-off), then L1-cache as usual.
                graph.vectors[: graph.n] = self._fetch_vectors(locmap, graph.n)
            with self._lock:
                self._l1[l1_key] = (graph, locmap)
                while len(self._l1) > self._l1_capacity:
                    self._l1.popitem(last=False)
            self._mark_cached(cache_key)
            return graph, locmap, hit

    def _fetch_vectors(self, locmap: ShardLocationMap, n: int) -> np.ndarray:
        """Read each indexed vector's row from its source Parquet row group."""
        readers: dict = {}
        out = None
        for vid in range(n):
            fpath = locmap.file_paths[int(locmap.file_idx[vid])]
            if fpath not in readers:
                readers[fpath] = VParquetReader.from_store(self.store, fpath)
            row = readers[fpath].read_rows(
                "vec", int(locmap.row_group[vid]), [int(locmap.row_offset[vid])]
            )[0]
            if out is None:
                out = np.empty((n, row.shape[0]), np.float32)
            out[vid] = row
        return out if out is not None else np.empty((0, 0), np.float32)

    # -- filtered search ----------------------------------------------------
    def _count_dispatch(self) -> None:
        """Record one masked-kernel call: executor-wide total (locked) +
        the current task's thread-local tally (see __init__)."""
        with self._lock:
            self.masked_kernel_dispatches += 1
        self._dispatch_tls.count = getattr(self._dispatch_tls, "count", 0) + 1

    def _count_rerank(self, calls: int = 1) -> None:
        """Record gather-rerank kernel calls (see the counter's note in
        __init__ — separate from masked-scan dispatch accounting)."""
        with self._lock:
            self.rerank_kernel_dispatches += calls

    def _count_graph_reranks(self, queries: np.ndarray) -> None:
        """A PQ traversal (``search_pq`` / ``search_masked(use_pq=True)``)
        reranks its pool with one ``gather_rerank`` call per query batch."""
        self._count_rerank(-(-len(queries) // QUERY_BATCH))

    def _task_dispatches(self) -> int:
        return getattr(self._dispatch_tls, "count", 0)

    def _count_mbeam(self, rows: int, fallbacks: int) -> None:
        """Tally MaskedBeam accounting for the current task: how many rows
        the predicate-aware traversal answered, and how many of those
        under-delivered and were re-answered by the fused exact-masked
        fallback (thread-local, reset per task like the dispatch count;
        with tracing on, also the counters ``masked_beam.rows`` and
        ``masked_beam.fallbacks``)."""
        t = self._dispatch_tls
        t.mbeam_rows = getattr(t, "mbeam_rows", 0) + rows
        t.mbeam_fallbacks = getattr(t, "mbeam_fallbacks", 0) + fallbacks
        count("masked_beam.rows", rows)
        count("masked_beam.fallbacks", fallbacks)

    def _task_mbeam(self) -> Tuple[int, int]:
        t = self._dispatch_tls
        return getattr(t, "mbeam_rows", 0), getattr(t, "mbeam_fallbacks", 0)

    def _reset_task_tallies(self) -> None:
        self._dispatch_tls.count = 0
        self._dispatch_tls.mbeam_rows = 0
        self._dispatch_tls.mbeam_fallbacks = 0

    def _resolve_op(self, task, op, live_mask: np.ndarray, has_pq: bool):
        """Refine a planner op with the measured match count.  ALL
        selectivity thresholds and flavor classification live in
        runtime/planner.py — the executor only interprets the resolved op."""
        if op is None:
            op = planner.default_filtered_op(task.k, task.oversample, task.use_pq)
        return planner.resolve(
            op,
            match_count=int(live_mask.sum()),
            k=task.k,
            oversample=task.oversample,
            has_pq=task.use_pq and has_pq,
        )

    @staticmethod
    def _dedup_rows(
        masks: List[np.ndarray], keys: List[object]
    ) -> Tuple[List[np.ndarray], np.ndarray]:
        """Dedup-then-broadcast mask-plane builder: per-query mask rows
        keyed by their predicate collapse to the unique rows plus a (Q,)
        row index.  The ops-layer ``*_dedup`` kernels broadcast the plane
        on-device, so host->device traffic for a mostly-homogeneous batch
        is m unique rows, not Q."""
        pos: Dict[object, int] = {}
        unique: List[np.ndarray] = []
        idx = np.empty(len(masks), np.int64)
        for j, (m, key) in enumerate(zip(masks, keys)):
            p = pos.get(key)
            if p is None:
                p = len(unique)
                pos[key] = p
                unique.append(m)
            idx[j] = p
        return unique, idx

    def _predicate_mask(self, locmap: ShardLocationMap, n: int, pred, shard_key: str) -> np.ndarray:
        """Executor-side row bitmask: does vector id's source row satisfy
        ``pred``?  Each (file, row_group) referenced by the location map is
        evaluated once with attribute-column projection; the per-id gather is
        cached per (shard, row-count, predicate) so repeated filtered probes
        reuse it.  ``n`` rides in the key as the shard's version: a refresh
        appends rows (the location map is append-only), so a mask computed
        against the pre-refresh row set can never be served for the
        refreshed shard — and ``_refresh_shard`` also drops this shard's
        entries outright."""
        key = (shard_key, n, pred)
        with self._lock:
            if key in self._mask_cache:
                self._mask_cache.move_to_end(key)
                return self._mask_cache[key]
        mask = np.zeros(n, bool)
        fidx = np.asarray(locmap.file_idx[:n], np.int64)
        rgrp = np.asarray(locmap.row_group[:n], np.int64)
        roff = np.asarray(locmap.row_offset[:n], np.int64)
        readers: Dict[str, VParquetReader] = {}
        groups = int(rgrp.max(initial=0)) + 1
        for pair in np.unique(fidx * groups + rgrp):  # each (file, row group) once
            fi, rg = divmod(int(pair), groups)
            fpath = locmap.file_paths[fi]
            if fpath not in readers:
                readers[fpath] = VParquetReader.from_store(self.store, fpath)
            rg_mask = row_group_mask(pred, readers[fpath], rg)
            sel = np.flatnonzero((fidx == fi) & (rgrp == rg))
            mask[sel] = rg_mask[roff[sel]]
        with self._lock:
            self._mask_cache[key] = mask
            while len(self._mask_cache) > self._mask_cache_capacity:
                self._mask_cache.popitem(last=False)
        return mask

    def _exact_masked(
        self,
        graph,
        queries: np.ndarray,
        live_mask: np.ndarray,
        k_eff: int,
        dtype: str = "f32",
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Kernel-backed pre-filter exact scan: one ``masked_exact_topk``
        call ranks only the rows passing the mask (masked-out rows are
        forced to +inf inside the kernel tile — no host-side gather).
        Exact by construction — the high-selectivity plan and the fallback
        when beam search can't surface enough passing candidates.  Output
        is always (Q, k_eff); slots beyond the passing-row count hold
        (+inf, -1) per the masked-op contract.

        ``dtype`` != f32 runs the plan's two-stage quantized form: the
        reduced-precision scan ranks a quant_guard_pool-sized pool from the
        cached quantized device copy, and the full-precision gather-rerank
        guard re-scores that pool down to ``k_eff`` — quantization never
        reaches the emitted distances."""
        self._count_dispatch()
        q = jnp.asarray(np.ascontiguousarray(queries, np.float32))
        if dtype != "f32":
            stored, x_scale = device_cache.device_vectors_quant(graph, dtype)
            pool = min(planner.quant_guard_pool(k_eff), graph.n)
            _qd, pids = ops.masked_exact_topk(
                q, stored, jnp.asarray(live_mask), int(pool),
                metric=graph.params.metric, backend="auto",
                dtype=dtype, x_scale=x_scale,
            )
            return self._rerank_pool(
                graph, queries, np.asarray(pids, np.int64), int(k_eff)
            )
        d, ids = ops.masked_exact_topk(
            q,
            device_cache.device_vectors(graph),
            jnp.asarray(live_mask),
            int(k_eff),
            metric=graph.params.metric,
            backend="auto",
        )
        return np.asarray(d), np.asarray(ids, np.int64)

    def _masked_pq_stage(
        self, graph, queries: np.ndarray, live_mask: np.ndarray, pool: int, k_out: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """PQScan interpretation on PQ shards: ONE masked ADC kernel call
        scores every passing code row (mask fused into the pq_scan
        accumulation) at the planner-resolved ``pool``, then the pooled
        survivors get the same full-precision rerank the unfiltered PQ path
        applies to its beam pool.  Every passing row is scored, so the pool
        can never under-deliver below min(pool, match_count)."""
        from repro.core.pq import build_luts

        q = np.ascontiguousarray(queries, np.float32)
        luts = build_luts(graph.pq, q)  # (Q, m, K)
        codes = self._device_codes(graph)
        self._count_dispatch()
        _pq_d, pids = ops.masked_pq_topk(
            jnp.asarray(luts),
            codes,
            jnp.asarray(live_mask),
            int(pool),
            backend="auto",
        )
        return self._rerank_pool(graph, q, np.asarray(pids, np.int64), k_out)

    def _device_codes(self, graph):
        """Codes are immutable between refreshes; cache the int32 device
        copy on the graph object (identity-keyed — see
        kernels/device_cache.py) instead of re-widening O(N·m) bytes per
        probe."""
        return device_cache.device_codes(graph)

    def _device_vectors(self, graph):
        """Cached f32 device copy of the shard's vectors (identity-keyed,
        like ``_device_codes``) — every kernel dispatch that used to ship
        ``jnp.asarray(graph.vectors[:graph.n])`` per call reuses this."""
        return device_cache.device_vectors(graph)

    def _rerank_pool(
        self, graph, q: np.ndarray, pids: np.ndarray, k_out: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Exact full-precision rerank of a candidate pool (Q, pool) — ADC
        survivors and quantized-scan guard pools alike: ONE gather-rerank
        kernel call scores each row's pool ids against the cached device
        vectors (kernels/rerank.py — the (Q, P, D) host gather and einsum
        this used to do in NumPy never materializes).  Sentinel slots
        (pid < 0) stay (+inf, -1); rows are independent, so a row's math
        does not depend on which other rows share the call."""
        self._count_rerank()
        d, ids = ops.gather_rerank(
            jnp.asarray(np.ascontiguousarray(q, np.float32)),
            self._device_vectors(graph),
            jnp.asarray(np.ascontiguousarray(pids, np.int64).astype(np.int32)),
            int(k_out),
            metric=graph.params.metric,
            backend="auto",
        )
        return np.asarray(d), np.asarray(ids, np.int64)

    def _exact_masked_plane(
        self,
        graph,
        queries: np.ndarray,
        unique_masks,
        row_index,
        k_out: int,
        dtype: str = "f32",
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Heterogeneous-predicate ExactScan: ONE kernel call answers every
        query of a coalesced fragment under its own bitmask row, shipped as
        the dedup'd (m, N) unique rows + (Q,) index — the per-predicate-
        group kernel loop collapses to a single dispatch per shard.
        Quantized ``dtype`` runs the same two-stage scan+guard form as
        ``_exact_masked``."""
        self._count_dispatch()
        q = jnp.asarray(np.ascontiguousarray(queries, np.float32))
        if dtype != "f32":
            stored, x_scale = device_cache.device_vectors_quant(graph, dtype)
            pool = min(planner.quant_guard_pool(k_out), graph.n)
            _qd, pids = ops.masked_exact_topk_dedup(
                q, stored,
                jnp.asarray(np.stack(unique_masks)),
                jnp.asarray(row_index),
                int(pool),
                metric=graph.params.metric, backend="auto",
                dtype=dtype, x_scale=x_scale,
            )
            return self._rerank_pool(
                graph, queries, np.asarray(pids, np.int64), int(k_out)
            )
        d, ids = ops.masked_exact_topk_dedup(
            q,
            self._device_vectors(graph),
            jnp.asarray(np.stack(unique_masks)),
            jnp.asarray(row_index),
            int(k_out),
            metric=graph.params.metric,
            backend="auto",
        )
        return np.asarray(d), np.asarray(ids, np.int64)

    def _masked_pq_plane(
        self,
        graph,
        queries: np.ndarray,
        unique_masks,
        row_index,
        pool: int,
        k_out: int,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Heterogeneous-predicate PQScan: ONE multi-mask ADC kernel call
        (dedup'd plane) scores every query's passing codes at the shared
        ``pool`` size, then the shared exact rerank.  One pool suffices for
        bit-for-bit parity with a one-query probe: planner.resolve pins
        the PQScan pool to the same constant for every PQ-flavor query of a
        fragment (see its docstring)."""
        from repro.core.pq import build_luts

        q = np.ascontiguousarray(queries, np.float32)
        luts = build_luts(graph.pq, q)  # (Q, m, K)
        codes = self._device_codes(graph)
        self._count_dispatch()
        _pq_d, pids = ops.masked_pq_topk_dedup(
            jnp.asarray(luts),
            codes,
            jnp.asarray(np.stack(unique_masks)),
            jnp.asarray(row_index),
            int(pool),
            backend="auto",
        )
        return self._rerank_pool(graph, q, np.asarray(pids, np.int64), k_out)

    def _unified_masked_stage(
        self,
        graph,
        queries: np.ndarray,
        unique_masks,
        row_index,
        flavor: np.ndarray,
        pq_pool: int,
        k_out: int,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Mixed-flavor fragment: ONE ``unified_masked_topk`` call scores
        exact-flavor rows full-precision and PQ-flavor rows via ADC in the
        same dispatch (per-query flavor selector fused into the mask
        plane).  The call returns max(k_out, pq_pool) columns: exact rows
        keep their first k_out (identical to a dedicated exact dispatch —
        the top-k extraction is prefix-stable), PQ rows feed their
        ``pq_pool`` ADC survivors through the shared full-precision
        rerank (identical to a dedicated ADC dispatch).  Collapses the
        two-dispatch-per-shard mixed fragment to one."""
        from repro.core.pq import build_luts

        q = np.ascontiguousarray(queries, np.float32)
        luts = build_luts(graph.pq, q)  # (Q, m, K)
        codes = self._device_codes(graph)
        kk = int(max(k_out, pq_pool))
        self._count_dispatch()
        d, ids = ops.unified_masked_topk_dedup(
            jnp.asarray(q),
            self._device_vectors(graph),
            jnp.asarray(luts),
            codes,
            jnp.asarray(np.stack(unique_masks)),
            jnp.asarray(row_index),
            jnp.asarray(flavor),
            kk,
            metric=graph.params.metric,
            backend="auto",
        )
        d = np.asarray(d)
        ids = np.asarray(ids, np.int64)
        out_d = np.empty((q.shape[0], k_out), np.float32)
        out_i = np.empty((q.shape[0], k_out), np.int64)
        ex = ~flavor
        out_d[ex] = d[ex, :k_out]
        out_i[ex] = ids[ex, :k_out]
        if flavor.any():
            rd, ri = self._rerank_pool(
                graph, q[flavor], ids[flavor][:, : int(pq_pool)], k_out
            )
            out_d[flavor] = rd
            out_i[flavor] = ri
        return out_d, out_i

    # -- dispatch ------------------------------------------------------------
    def handle(self, task) -> object:
        """Run one fragment, as one ``executor.task`` span."""
        with span("executor.task", kind=type(task).__name__.removesuffix("TaskInfo"),
                  shard=getattr(task, "shard_id", None), executor=self.executor_id):
            return self._handle(task)

    def _handle(self, task) -> object:
        self._gate()
        if self._kill_mid_task > 0:
            self._kill_mid_task -= 1
            self.dead = True  # heartbeat goes dark while the task is held
            if self._kill_hold_s > 0:
                time.sleep(self._kill_hold_s)
            raise ExecutorDead(self.executor_id)
        if isinstance(task, F.ScanPartitionTaskInfo):
            result = self._scan_partition(task)
        elif isinstance(task, F.IndexBuildTaskInfo):
            result = self._build_shard(task)
        elif isinstance(task, F.BatchProbeTaskInfo):
            result = self._probe_shard_batch(task)
        elif isinstance(task, F.TailScanTaskInfo):
            result = self._tail_scan(task)
        elif isinstance(task, F.RerankTaskInfo):
            result = self._rerank(task)
        elif isinstance(task, F.RefreshTaskInfo):
            result = self._refresh_shard(task)
        else:
            raise TypeError(f"unknown task type {type(task)}")
        self.tasks_done += 1
        return result

    # -- handlers --------------------------------------------------------------
    def _scan_partition(self, task: F.ScanPartitionTaskInfo) -> F.ScanPartitionResult:
        vectors, fidx, rgrp, roff, paths = _scan_files_with_locations(
            self.store, task.assigned_files
        )
        out = F.ScanPartitionResult(executor_id=self.executor_id)
        if vectors.shape[0] == 0:
            return out
        owners = _owner_shards(vectors, task.partition_centroids, task.shard_of_partition)
        for shard in range(task.num_shards):
            sel = np.flatnonzero(owners == shard)
            if len(sel) == 0:
                continue
            out.per_shard[shard] = (
                vectors[sel],
                fidx[sel],
                rgrp[sel],
                roff[sel],
                paths,
            )
        return out

    def _build_shard(self, task: F.IndexBuildTaskInfo) -> F.IndexBuildResult:
        if task.exchanged is not None:
            vectors, fidx, rgrp, roff, paths = task.exchanged
        else:
            vectors, fidx, rgrp, roff, paths = _scan_files_with_locations(
                self.store, task.assigned_files
            )
            if task.partition_mode == "centroid" and task.partition_centroids is not None:
                owners = _owner_shards(
                    vectors, task.partition_centroids, task.shard_of_partition
                )
                sel = np.flatnonzero(owners == task.shard_id)
                vectors, fidx, rgrp, roff = vectors[sel], fidx[sel], rgrp[sel], roff[sel]
        if vectors.shape[0] == 0:
            raise ValueError(f"shard {task.shard_id}: no vectors to index")
        params = VamanaParams(R=task.R, L=task.L, alpha=task.alpha, metric=task.metric)
        graph = build_vamana(
            vectors, params, passes=task.build_passes, batch=task.build_batch,
            seed=task.shard_id,
        )
        if task.pq_m:
            pq = PQCodebook(task.pq_codebook, task.metric)
            graph.attach_pq(pq, pq_encode(pq, vectors))
        # per-partition counts for the routing table
        counts = None
        if task.partition_centroids is not None:
            part, _ = ops.kmeans_assign(
                jnp.asarray(vectors), jnp.asarray(task.partition_centroids), backend="ref"
            )
            counts = np.bincount(
                np.asarray(part), minlength=task.partition_centroids.shape[0]
            )
        locmap = ShardLocationMap(paths, fidx, rgrp, roff)
        blob = encode_shard_blob(graph, locmap, include_vectors=task.include_vectors)
        self.store.put(task.output_path, blob)
        return F.IndexBuildResult(
            shard_id=task.shard_id,
            output_path=task.output_path,
            vector_count=graph.n,
            byte_size=len(blob),
            executor_id=self.executor_id,
            partition_counts=counts,
            rg_membership=_locmap_membership(locmap, graph.n),
        )

    def _shard_search(
        self,
        task,
        graph,
        queries: Optional[np.ndarray] = None,
        width: Optional[int] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Shared Stage-A search: batched beam search (PQ ADC when the shard
        carries codes) over however many queries the fragment brought.
        ``width`` is a planner Beam op's requested candidate count; absent,
        the task's own k * oversample applies (same value on every
        coordinator-built plan — the parameter keeps replayed plans
        honest)."""
        q = task.queries if queries is None else queries
        k_eff = min(width or task.k * task.oversample, graph.num_live)
        L = max(task.L, k_eff)
        slots = stream_slots(len(q))
        count("traversal.padded_slots", slots - len(q))
        if task.use_pq and graph.pq is not None:
            self._count_graph_reranks(q)
            with span("traversal.search_pq", queries=len(q), slots=slots):
                return graph.search_pq(q, k_eff, L=L)
        with span("traversal.search", queries=len(q), slots=slots):
            return graph.search(q, k_eff, L=L)

    def _row_candidates(
        self, graph, locmap, dists_row, ids_row, shard_id: int
    ) -> List[F.ProbeCandidate]:
        cands: List[F.ProbeCandidate] = []
        for d, vid in zip(dists_row, ids_row):
            if not np.isfinite(d) or vid < 0 or vid >= graph.n:
                continue
            fpath, rg, ro = locmap.lookup(int(vid))
            cands.append(
                F.ProbeCandidate(
                    file_path=fpath,
                    row_group=rg,
                    row_offset=ro,
                    approx_distance=float(d),
                    vec_id=int(vid),
                    shard_id=shard_id,
                )
            )
        return cands

    def _tail_scan(self, task: F.TailScanTaskInfo) -> F.BatchProbeResult:
        """Fresh-tail tier Stage A: score one appended-but-unindexed row
        group for every query routed to it with ONE masked exact kernel
        dispatch.  Tail rows have no graph and no PQ codes, so every plan
        op is an ExactScan; predicates become per-query bitmask rows
        (dedup'd plane when the batch mixes them), and the kernel's
        (+inf, -1) sentinel contract covers zero-match predicates and
        k > live-rows exactly as shard scans do — sentinel slots are
        dropped before candidates leave the executor."""
        result = F.BatchProbeResult(
            shard_id=task.tail_id, executor_id=self.executor_id
        )
        self._reset_task_tallies()
        qidx = np.asarray(task.query_index, np.int64)
        reader = VParquetReader.from_store(self.store, task.file_path)
        vectors = np.ascontiguousarray(
            reader.read_column("vec", [task.row_group]), np.float32
        )
        n = vectors.shape[0]
        if n == 0:
            for qi in qidx:
                result.candidates[int(qi)] = []
            return result
        q = np.ascontiguousarray(task.queries, np.float32)
        k_eff = min(max(1, task.k * task.oversample), n)
        all_rows = np.ones(n, bool)
        masks: List[np.ndarray] = []
        keys: List[object] = []
        for bi in range(q.shape[0]):
            pred = task.filters[bi] if task.filters else None
            if pred is None:
                masks.append(all_rows)
                keys.append(None)
            else:
                masks.append(row_group_mask(pred, reader, task.row_group))
                keys.append(pred)
        unique, row_index = self._dedup_rows(masks, keys)
        self._count_dispatch()
        if len(unique) == 1:
            d, ids = ops.masked_exact_topk(
                jnp.asarray(q),
                jnp.asarray(vectors),
                jnp.asarray(unique[0]),
                int(k_eff),
                metric=task.metric,
                backend="auto",
            )
        else:
            d, ids = ops.masked_exact_topk_dedup(
                jnp.asarray(q),
                jnp.asarray(vectors),
                jnp.asarray(np.stack(unique)),
                jnp.asarray(row_index),
                int(k_eff),
                metric=task.metric,
                backend="auto",
            )
        d = np.asarray(d)
        ids = np.asarray(ids, np.int64)
        for bi, qi in enumerate(qidx):
            result.candidates[int(qi)] = [
                F.ProbeCandidate(
                    file_path=task.file_path,
                    row_group=task.row_group,
                    row_offset=int(vid),
                    approx_distance=float(dist),
                    vec_id=int(vid),
                    shard_id=task.tail_id,
                )
                for dist, vid in zip(d[bi], ids[bi])
                if np.isfinite(dist) and vid >= 0
            ]
        result.kernel_dispatches = self._task_dispatches()
        return result

    def _probe_shard_batch(self, task: F.BatchProbeTaskInfo) -> F.BatchProbeResult:
        """Coalesced Stage A: one shard load, then interpret each query's
        planner op and answer every kernel-planned query of the fragment
        with ONE masked-kernel call per shard — regardless of how many
        distinct predicates the batch carries, and regardless of whether
        their resolved flavors mix exact and PQ-ADC scoring (the unified
        kernel fuses both into the same dispatch).  Each query gets its own
        row of a dedup'd mask plane assembled from the per-predicate
        ``_mask_cache`` bitmasks (tombstones AND-ed in); unfiltered queries
        ride a shared beam pass, or a size-capped all-ones kernel row on
        small shards, per their planner op."""
        graph, locmap, hit = self._load_shard(
            task.puffin_path, task.blob_offset, task.blob_length, task.blob_codec, task.cache_key
        )
        result = F.BatchProbeResult(
            shard_id=task.shard_id, executor_id=self.executor_id, cache_hit=hit
        )
        self._reset_task_tallies()
        qidx = np.asarray(task.query_index, np.int64)
        if not task.filters:
            # fully-unfiltered fragments keep the batched beam search: its
            # hits must stay byte-identical to sequential probe() calls
            dists, ids = self._shard_search(task, graph)
            with span("executor.candidates"):
                for bi, qi in enumerate(qidx):
                    result.candidates[int(qi)] = self._row_candidates(
                        graph, locmap, dists[bi], ids[bi], task.shard_id
                    )
            return result
        self._probe_mask_plane(task, graph, locmap, result, qidx)
        result.kernel_dispatches = self._task_dispatches()
        result.masked_beam_rows, result.masked_beam_fallbacks = self._task_mbeam()
        return result

    def _probe_mask_plane(
        self, task, graph, locmap, result, qidx: np.ndarray
    ) -> None:
        """Mask-plane Stage A: resolve every query's planner op against its
        measured match count (planner.resolve — the executor itself holds
        no thresholds), then answer ALL kernel-planned queries with one
        masked-kernel call: a single flavor dispatches the dedup'd-plane
        exact or ADC kernel; a fragment mixing both flavors dispatches the
        unified kernel ONCE with a per-query flavor selector.  Beam-planned
        rows (unfiltered queries on large shards) share one batched beam
        pass, and PostfilterBeam rows share over-fetched beam passes
        grouped by pool with a single fused masked-kernel fallback —
        heterogeneous predicates never multiply kernel dispatches."""
        shard_key = f"{task.cache_key or task.puffin_path}@{task.blob_offset}"
        n = graph.n
        tomb_live = ~graph.tombstones[:n]
        k_out = max(1, min(task.k * task.oversample, n))
        exact_rows: List[int] = []
        exact_masks: List[np.ndarray] = []
        exact_keys: List[object] = []
        exact_dtypes: List[str] = []  # per-row planner scan dtype
        pq_rows: List[int] = []
        pq_masks: List[np.ndarray] = []
        pq_keys: List[object] = []
        beam_rows: Dict[int, List[int]] = {}  # planner Beam width -> rows
        post_rows: Dict[int, List[int]] = {}
        post_masks: Dict[int, np.ndarray] = {}
        post_ks: Dict[int, int] = {}
        mbeam_rows: Dict[int, List[int]] = {}  # planner MaskedBeam width -> rows
        mbeam_masks: Dict[int, np.ndarray] = {}
        mbeam_ks: Dict[int, int] = {}
        pq_pool = 0
        for bi in range(len(qidx)):
            pred = task.filters[bi]
            op = task.plan_ops[bi] if task.plan_ops else None
            if pred is None:
                if isinstance(op, planner.ExactScan):
                    # unfiltered query in a mixed fragment on a small
                    # shard: all-ones row (only tombstones masked) rides
                    # the fragment's kernel call
                    exact_rows.append(bi)
                    exact_masks.append(tomb_live)
                    exact_keys.append(None)
                    exact_dtypes.append(getattr(op, "dtype", "f32"))
                else:
                    w = op.width if isinstance(op, planner.Beam) else 0
                    beam_rows.setdefault(int(w), []).append(bi)
                continue
            with span("executor.predicate_mask"):
                live = self._predicate_mask(locmap, n, pred, shard_key) & tomb_live
            final = self._resolve_op(task, op, live, graph.pq is not None)
            if isinstance(final, planner.Skip):
                result.candidates[int(qidx[bi])] = []
            elif isinstance(final, planner.PQScan):
                pq_rows.append(bi)
                pq_masks.append(live)
                pq_keys.append(pred)
                pq_pool = final.pool  # pinned: identical for every PQ row
            elif isinstance(final, planner.ExactScan):
                exact_rows.append(bi)
                exact_masks.append(live)
                exact_keys.append(pred)
                exact_dtypes.append(getattr(final, "dtype", "f32"))
            elif isinstance(final, planner.MaskedBeam):
                mbeam_rows.setdefault(int(final.width), []).append(bi)
                mbeam_masks[bi] = live
                mbeam_ks[bi] = final.k  # planner-resolved k_eff
            else:  # PostfilterBeam
                post_rows.setdefault(int(final.pool), []).append(bi)
                post_masks[bi] = live
                post_ks[bi] = final.k  # planner-resolved k_eff

        def _emit(rows, dists, ids):
            for j, bi in enumerate(rows):
                result.candidates[int(qidx[bi])] = self._row_candidates(
                    graph, locmap, dists[j], ids[j], task.shard_id
                )

        # Reduced-precision exact rows never join the unified fusion: the
        # unified kernel scores exact rows full-precision only.  Group the
        # quantized rows per dtype (each gets its own scan+guard dispatch)
        # and keep the f32 subset for the fusion/plane logic below.
        quant_groups: Dict[str, List[int]] = {}
        for pos, dt in enumerate(exact_dtypes):
            if dt != "f32":
                quant_groups.setdefault(dt, []).append(pos)
        if quant_groups:
            for dt, poss in sorted(quant_groups.items()):
                rows = [exact_rows[p] for p in poss]
                masks = [exact_masks[p] for p in poss]
                keys = [exact_keys[p] for p in poss]
                unique, idx = self._dedup_rows(masks, keys)
                if len(unique) == 1:
                    dists, ids = self._exact_masked(
                        graph, task.queries[rows], unique[0], k_out, dtype=dt
                    )
                else:
                    dists, ids = self._exact_masked_plane(
                        graph, task.queries[rows], unique, idx, k_out, dtype=dt
                    )
                _emit(rows, dists, ids)
            keep = [p for p, dt in enumerate(exact_dtypes) if dt == "f32"]
            exact_rows = [exact_rows[p] for p in keep]
            exact_masks = [exact_masks[p] for p in keep]
            exact_keys = [exact_keys[p] for p in keep]

        if exact_rows and pq_rows:
            # mixed flavors: ONE unified dispatch for the whole fragment
            rows = exact_rows + pq_rows
            unique, idx = self._dedup_rows(
                exact_masks + pq_masks, exact_keys + pq_keys
            )
            flavor = np.zeros(len(rows), bool)
            flavor[len(exact_rows):] = True
            dists, ids = self._unified_masked_stage(
                graph, task.queries[rows], unique, idx, flavor, pq_pool, k_out
            )
            _emit(rows, dists, ids)
        else:
            # Homogeneous-predicate short-circuit inside each flavor: one
            # unique mask row ships the single-mask kernel; otherwise the
            # dedup'd plane (m unique rows + row index, broadcast
            # on-device) — either way ONE dispatch per flavor.
            if exact_rows:
                unique, idx = self._dedup_rows(exact_masks, exact_keys)
                if len(unique) == 1:
                    dists, ids = self._exact_masked(
                        graph, task.queries[exact_rows], unique[0], k_out
                    )
                else:
                    dists, ids = self._exact_masked_plane(
                        graph, task.queries[exact_rows], unique, idx, k_out
                    )
                _emit(exact_rows, dists, ids)
            if pq_rows:
                unique, idx = self._dedup_rows(pq_masks, pq_keys)
                if len(unique) == 1:
                    dists, ids = self._masked_pq_stage(
                        graph, task.queries[pq_rows], unique[0], pq_pool, k_out
                    )
                else:
                    dists, ids = self._masked_pq_plane(
                        graph, task.queries[pq_rows], unique, idx, pq_pool, k_out
                    )
                _emit(pq_rows, dists, ids)
        for w, rows in sorted(beam_rows.items()):
            dists, ids = self._shard_search(
                task, graph, task.queries[rows], width=w or None
            )
            _emit(rows, dists, ids)
        short_rows: List[int] = []
        if post_rows:
            short_rows += self._postfilter_pooled(
                task, graph, locmap, result, qidx, post_rows, post_masks, post_ks
            )
        if mbeam_rows:
            short_rows += self._masked_beam_pooled(
                task, graph, locmap, result, qidx, mbeam_rows, mbeam_masks, mbeam_ks
            )
        if short_rows:
            self._fused_exact_fallback(
                task,
                graph,
                locmap,
                result,
                qidx,
                sorted(short_rows),
                {**post_masks, **mbeam_masks},
            )

    def _masked_beam_pooled(
        self,
        task,
        graph,
        locmap,
        result,
        qidx: np.ndarray,
        rows_by_width: Dict[int, List[int]],
        masks_by_row: Dict[int, np.ndarray],
        ks_by_row: Dict[int, int],
    ) -> List[int]:
        """MaskedBeam rows of a fragment: one predicate-aware traversal per
        distinct planner width (usually a single pass — resolution keeps
        the width shared unless match counts cap it), each row's mask
        riding the dedup'd plane, each row sliced to ITS planner-resolved
        k.  Returns the under-delivered rows so they join the fragment's
        ONE fused masked-kernel fallback alongside any short postfilter
        rows.  Per-query results are identical to interpreting each row
        alone: traversal rows are independent and the fallback math is
        per-row.

        The traversal is ``VamanaGraph.search_masked``: masked nodes expand
        for connectivity, only mask-passing nodes are admitted.  The
        planner-widened ``width`` sizes only the ADMIT target — the beam
        depth stays at ``max(task.L, k)``, because admitted candidates come
        from every neighbor the traversal evaluates, not just the final
        pool.  This is the structural edge over PostfilterBeam, whose pool
        must deepen by 1/frac to surface enough passing rows.  A traversal
        is a beam pass, not a masked-kernel dispatch: it does not count
        toward ``kernel_dispatches`` (its fused fallback does)."""
        short_rows: List[int] = []
        total = 0
        use_pq = task.use_pq and graph.pq is not None
        for width, rows in sorted(rows_by_width.items()):
            unique, idx = self._dedup_rows(
                [masks_by_row[bi] for bi in rows],
                [task.filters[bi] for bi in rows],
            )
            queries = task.queries[rows]
            k = max(ks_by_row[bi] for bi in rows)
            if use_pq:
                self._count_graph_reranks(queries)
            slots = stream_slots(len(rows))
            count("traversal.padded_slots", slots - len(rows))
            with span("traversal.search_masked", queries=len(rows), slots=slots,
                      masks=len(unique)):
                dists, ids = graph.search_masked(
                    queries,
                    max(1, min(int(width), graph.num_live)),
                    np.stack(unique),
                    idx,
                    L=max(task.L, min(int(k), graph.num_live)),
                    use_pq=use_pq,
                )
            total += len(rows)
            for j, bi in enumerate(rows):
                kj = ks_by_row[bi]
                dj, ij = dists[j, :kj], ids[j, :kj]
                if np.isinf(dj).any():
                    short_rows.append(bi)
                else:
                    result.candidates[int(qidx[bi])] = self._row_candidates(
                        graph, locmap, dj, ij, task.shard_id
                    )
        self._count_mbeam(total, len(short_rows))
        return short_rows

    def _fused_exact_fallback(
        self,
        task,
        graph,
        locmap,
        result,
        qidx: np.ndarray,
        short_rows: List[int],
        masks_by_row: Dict[int, np.ndarray],
    ) -> None:
        """ONE fused masked-kernel call answers every beam row the fragment
        under-delivered — postfilter and masked-beam rows alike — instead
        of per-predicate (or per-path) fallback dispatches."""
        k_out = max(1, min(task.k * task.oversample, graph.n))
        unique, idx = self._dedup_rows(
            [masks_by_row[bi] for bi in short_rows],
            [task.filters[bi] for bi in short_rows],
        )
        if len(unique) == 1:
            d, i = self._exact_masked(
                graph, task.queries[short_rows], unique[0], k_out
            )
        else:
            d, i = self._exact_masked_plane(
                graph, task.queries[short_rows], unique, idx, k_out
            )
        for j, bi in enumerate(short_rows):
            result.candidates[int(qidx[bi])] = self._row_candidates(
                graph, locmap, d[j], i[j], task.shard_id
            )

    def _postfilter_pooled(
        self,
        task,
        graph,
        locmap,
        result,
        qidx: np.ndarray,
        rows_by_pool: Dict[int, List[int]],
        masks_by_row: Dict[int, np.ndarray],
        ks_by_row: Dict[int, int],
    ) -> List[int]:
        """PostfilterBeam rows of a fragment: one ordinary beam pass per
        distinct planner pool (NOT per distinct predicate — usually a
        single pass), over-fetched to that pool; each row drops the
        candidates failing ITS mask (failures sorted to the (+inf, -1)
        tail) and is sliced to ITS planner-resolved k.  Returns the
        under-delivered rows so they join the fragment's ONE fused
        masked-kernel fallback call (shared with short masked-beam rows)
        instead of per-predicate fallbacks.  Per-query results are
        identical to interpreting each row alone: beam rows are independent
        and the fallback math is per-row."""
        short_rows: List[int] = []
        for pool, rows in sorted(rows_by_pool.items()):
            queries = task.queries[rows]
            plane = np.stack([masks_by_row[bi] for bi in rows])
            p = min(int(pool), graph.num_live)
            L = max(task.L, p)
            if task.use_pq and graph.pq is not None:
                dists, ids = graph.search_pq(queries, p, L=L)
            else:
                dists, ids = graph.search(queries, p, L=L)
            safe = np.clip(ids, 0, graph.n - 1)
            passing = (
                np.take_along_axis(plane, safe, axis=1)
                & (ids >= 0)
                & np.isfinite(dists)
            )
            dists = np.where(passing, dists, np.inf)
            ids = np.where(passing, ids, -1)
            order = np.argsort(dists, axis=1)
            dists = np.take_along_axis(dists, order, axis=1)
            ids = np.take_along_axis(ids, order, axis=1)
            for j, bi in enumerate(rows):
                kj = ks_by_row[bi]
                dj, ij = dists[j, :kj], ids[j, :kj]
                if np.isinf(dj).any():
                    short_rows.append(bi)
                else:
                    result.candidates[int(qidx[bi])] = self._row_candidates(
                        graph, locmap, dj, ij, task.shard_id
                    )
        return short_rows

    def _rerank(self, task: F.RerankTaskInfo) -> F.RerankResult:
        """Stage B on this executor's files: read the candidate rows
        (``executor.rerank.read``), score them (``executor.rerank.score``)
        and emit each query's owned rows (``executor.rerank.emit``)."""
        rows_flat: List[Tuple[str, int, int]] = []
        # per flat row: None => every query owns it, else the owning set
        owners_flat: List[Optional[set]] = []
        vec_parts: List[np.ndarray] = []
        with span("executor.rerank.read"):
            for fpath, groups in task.masks.items():
                reader = VParquetReader.from_store(self.store, fpath)
                f_own = task.file_owners.get(fpath) if task.file_owners else None
                r_own = task.row_owners.get(fpath) if task.row_owners else None
                for rg_id, offsets in groups.items():
                    arr = reader.read_rows("vec", rg_id, offsets)
                    vec_parts.append(arr)
                    rg_own = r_own.get(rg_id) if r_own is not None else None
                    for off in offsets:
                        rows_flat.append((fpath, rg_id, off))
                        if rg_own is not None:
                            owners_flat.append(rg_own.get(off, set()))
                        else:
                            owners_flat.append(f_own)
        result = F.RerankResult(executor_id=self.executor_id)
        q = np.ascontiguousarray(task.queries, np.float32)
        if not rows_flat:
            result.rows = [[] for _ in range(q.shape[0])]
            return result
        # the union of every query's rows is read and scored ONCE — one jitted
        # program per shape bucket, so a warm bucket compiles nothing; the
        # (Q, N) matrix comes back unpadded and ownership filters it afterwards
        with span("executor.rerank.score"):
            d = ops.bucketed_exact_distances(
                q, np.concatenate(vec_parts), metric=task.metric
            )
        with span("executor.rerank.emit"):
            for qi in range(q.shape[0]):
                result.rows.append(
                    [
                        F.RerankRow(fp, rg, ro, float(d[qi, ci]))
                        for ci, (fp, rg, ro) in enumerate(rows_flat)
                        if owners_flat[ci] is None or qi in owners_flat[ci]
                    ]
                )
        return result

    def _refresh_shard(self, task: F.RefreshTaskInfo) -> F.RefreshResult:
        graph, locmap, _hit = self._load_shard(
            task.puffin_path, task.blob_offset, task.blob_length, task.blob_codec, task.cache_key
        )
        # deletions first: tombstone every vector whose source file was removed
        tombstoned = 0
        if task.removed_files:
            removed = set(task.removed_files)
            path_arr = np.array(
                [locmap.file_paths[int(i)] for i in locmap.file_idx[: graph.n]]
            )
            doomed = np.flatnonzero(np.isin(path_arr, list(removed)))
            fresh = doomed[~graph.tombstones[doomed]]
            graph.tombstone(fresh)
            tombstoned = int(len(fresh))
        # insertions: scan added files, filter to this shard's ownership
        inserted = 0
        if task.added_files:
            vectors, fidx, rgrp, roff, paths = _scan_files_with_locations(
                self.store, task.added_files
            )
            if vectors.shape[0]:
                owners = _owner_shards(
                    vectors, task.partition_centroids, task.shard_of_partition
                )
                sel = np.flatnonzero(owners == task.shard_id)
                if len(sel):
                    graph.insert_batch(vectors[sel])
                    inserted = int(len(sel))
                    # extend the location map
                    base = len(locmap.file_paths)
                    locmap.file_paths.extend(paths)
                    locmap.file_idx = np.concatenate(
                        [locmap.file_idx, fidx[sel] + base]
                    )
                    locmap.row_group = np.concatenate([locmap.row_group, rgrp[sel]])
                    locmap.row_offset = np.concatenate([locmap.row_offset, roff[sel]])
        blob = encode_shard_blob(graph, locmap, include_vectors=task.include_vectors)
        self.store.put(task.output_path, blob)
        # The refresh mutated the graph/locmap objects IN PLACE — the very
        # objects the L1 cache serves under the pre-refresh key.  Evict that
        # entry (a later probe of the old snapshot must re-decode the
        # pristine old blob) and drop every cached predicate mask for this
        # shard: the row set changed, so (shard, predicate) bitmasks
        # computed before the refresh are stale.
        l1_key = f"{task.cache_key or task.puffin_path}@{task.blob_offset}"
        with self._lock:
            self._l1.pop(l1_key, None)
            for key in [kk for kk in self._mask_cache if kk[0] == l1_key]:
                del self._mask_cache[key]
        return F.RefreshResult(
            shard_id=task.shard_id,
            output_path=task.output_path,
            executor_id=self.executor_id,
            inserted=inserted,
            tombstoned=tombstoned,
            vector_count=graph.n,
            byte_size=len(blob),
            tombstone_ratio=graph.tombstone_ratio,
            rg_membership=_locmap_membership(
                locmap, graph.n, live=~graph.tombstones[: graph.n]
            ),
        )
