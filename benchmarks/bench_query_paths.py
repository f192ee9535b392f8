"""Paper Table 2: query cost by strategy (no index / centroid / DiskANN),
plus the batched multi-query pipeline (sequential probes vs probe_batch)
and the filtered-search path (attribute predicate vs brute-force oracle).

Measurable scale: ~32k vectors, 32 files, 4 executors.  Reports files
scanned, bytes read from the object store, cold/warm latency, and recall —
the same columns as the paper's table; the derived field carries the
probe-vs-scan reduction ratios.  The ``table2.batched`` row compares warm
per-query sequential probes against one ``probe_batch`` over the same
queries: the batch shares ≤ one shard fragment per shard and one rerank
wave, so its throughput must come out strictly higher.

The ``table2.overload`` row drives the multi-tenant serving tier at ~2x
measured capacity with two tenants (one abusive): admission control must
make the abuser absorb the rejections while the well-behaved tenant keeps
a >= 0.9 deadline hit-rate and the bounded queue holds.

``--tiny`` shrinks everything to a seconds-scale smoke run (used by
scripts/ci.sh to catch query-path regressions).

Every row is also written to ``--json`` (default ``BENCH_query_paths.json``)
as ``{"rows": {name: {"throughput_qps": ..., "recall": ..., ...}}}`` —
the machine-readable record scripts/check_bench.py gates CI on (absolute
floors plus >20% throughput / any-recall regression vs the committed
baseline in benchmarks/baselines/).
"""

import argparse
import json
import time

import numpy as np

from benchmarks.common import clustered, emit, make_cluster
from repro.core.vamana import brute_force_topk
from repro.lakehouse.table import LakehouseTable
from repro.runtime.coordinator import IndexConfig


def _best_of(fn, repeats: int = 3):
    """Best-of-N wall time for a warm code path.  Single-shot timings of
    ~10 ms sections swing well past the CI gate's 20% budget from scheduler
    and allocator noise alone; the minimum over a few repeats is the stable
    statistic (the true cost plus the least interference)."""
    best, out = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def main(tiny: bool = False, json_path: str = "BENCH_query_paths.json") -> None:
    rows: dict = {}  # row name -> machine-readable fields for check_bench

    rng = np.random.default_rng(0)
    if tiny:
        n_vec, n_files, n_exec, D, n_clusters = 2_048, 8, 2, 32, 16
        cfg = IndexConfig(name="idx", R=16, L=32, pq_m=8, pq_nbits=8,
                          partitions_per_shard=2, build_passes=1, build_batch=128)
        n_q, rows_per_group, n_probe = 8, 128, 3
    else:
        n_vec, n_files, n_exec, D, n_clusters = 32_000, 32, 4, 96, 64
        # paper-style search params: PQ traversal needs L ≳ 100 (DiskANN
        # ships L_search 100+; at L=48 PQ-guided beams misroute on
        # well-separated clusters — measured in EXPERIMENTS §1)
        cfg = IndexConfig(name="idx", R=24, L=128, pq_m=24, pq_nbits=8,
                          partitions_per_shard=4, build_passes=2, build_batch=256)
        n_q, rows_per_group, n_probe = 12, 512, 6
    c = make_cluster(n_exec)
    t = LakehouseTable(c.catalog, "bench")
    t.create(dim=D)
    X = clustered(rng, n_vec, D, n_clusters=n_clusters)
    # cluster-correlated file layout: the paper's §10 states recall (and
    # centroid pruning) depend on the data-partition correlation; writing
    # shuffled files makes every file centroid ≈ the global mean and
    # centroid pruning degenerates to random file choice (measured:
    # recall 0.27 at n_probe=6 — a §10 validation).  Real ingest pipelines
    # cluster by time/key, which the sorted layout models.
    from repro.core.kmeans import assign, train_kmeans
    cents, _ = train_kmeans(X[:8192], n_clusters, iters=8, seed=0)
    labels = assign(X, cents)
    order = np.argsort(labels, kind="stable")
    X = X[order]
    # attribute columns ride along: category follows the cluster layout
    # (zone maps get tight per-row-group tags), price is uncorrelated
    category = np.asarray([f"cat{int(l)}" for l in labels[order]])
    price = rng.integers(0, 100, size=len(X)).astype(np.int64)
    t.append_vectors(
        X,
        num_files=n_files,
        rows_per_group=rows_per_group,
        attributes={"category": category, "price": price},
    )
    c.coordinator.create_index("bench", cfg)
    Q = X[rng.choice(len(X), n_q)] + 0.05 * rng.normal(size=(n_q, D)).astype(np.float32)
    _, truth = brute_force_topk(X, Q, 10)
    vecs_all, locs_all = t.scan_vectors()
    truth_locs = [
        {(locs_all[i].file_path, locs_all[i].row_group_id, locs_all[i].row_offset) for i in row}
        for row in truth
    ]

    def recall(hits_lists):
        scores = [
            len({(h.file_path, h.row_group, h.row_offset) for h in hits} & tl) / len(tl)
            for hits, tl in zip(hits_lists, truth_locs)
        ]
        return float(np.mean(scores))

    results = {}
    for strat, kw in (
        ("scan", {}),
        ("centroid", {"n_probe": n_probe}),
        ("diskann", {}),
        ("diskann_fp", {"use_pq": False}),
    ):
        probe_strat = "diskann" if strat.startswith("diskann") else strat
        # cold: fresh executor caches
        for ex in c.executors:
            ex._l1.clear()
        t0 = time.perf_counter()
        c.coordinator.probe("bench", Q[:1], 10, strategy=probe_strat, **kw)
        cold_s = time.perf_counter() - t0
        # warm, PER QUERY (the paper's Table 2 counts files/bytes per query)
        def _warm_loop():
            hits, files, bytes_ = [], [], []
            for qi in range(len(Q)):
                pr = c.coordinator.probe("bench", Q[qi], 10, strategy=probe_strat, **kw)
                hits.append(pr.hits[0])
                files.append(pr.files_scanned)
                bytes_.append(pr.bytes_read)
            return hits, files, bytes_

        loop_s, (hits, files, bytes_) = _best_of(_warm_loop)
        warm_s = loop_s / len(Q)
        r = recall(hits)
        results[strat] = (float(np.mean(files)), float(np.mean(bytes_)))
        emit(
            f"table2.{strat}",
            warm_s * 1e6,
            f"files_per_query_{np.mean(files):.1f}_bytes_per_query_{np.mean(bytes_):.0f}"
            f"_cold_ms_{cold_s*1e3:.0f}_warm_ms_{warm_s*1e3:.0f}_recall_{r:.3f}",
        )
        rows[f"table2.{strat}"] = {
            "throughput_qps": 1.0 / warm_s,
            "recall": r,
            "files_per_query": float(np.mean(files)),
            "bytes_per_query": float(np.mean(bytes_)),
        }
    emit(
        "table2.read_reduction",
        0.0,
        f"centroid_{results['scan'][1]/max(results['centroid'][1],1):.1f}x"
        f"_diskann_{results['scan'][1]/max(results['diskann'][1],1):.1f}x"
        f"_paper_25x_200x",
    )

    # ---- batched multi-query pipeline -----------------------------------
    # warm both paths (jit + caches already hot from the loop above), then
    # time B sequential probes against ONE probe_batch over the same block
    c.coordinator.probe_batch("bench", Q, 10, strategy="diskann")
    seq_s, seq_hits = _best_of(
        lambda: [
            c.coordinator.probe("bench", Q[qi], 10, strategy="diskann").hits[0]
            for qi in range(len(Q))
        ]
    )
    batch_s, pr_b = _best_of(
        lambda: c.coordinator.probe_batch("bench", Q, 10, strategy="diskann")
    )
    seq_qps = len(Q) / seq_s
    batch_qps = len(Q) / batch_s
    # parity check rides along: the batch must return the sequential hits
    same = all(
        [(h.file_path, h.row_group, h.row_offset) for h in a]
        == [(h.file_path, h.row_group, h.row_offset) for h in b]
        for a, b in zip(seq_hits, pr_b.hits)
    )
    emit(
        "table2.batched",
        batch_s / len(Q) * 1e6,
        f"B_{len(Q)}_seq_qps_{seq_qps:.1f}_batch_qps_{batch_qps:.1f}"
        f"_speedup_{batch_qps/seq_qps:.2f}x_fragments_{pr_b.probe_fragments}"
        f"_recall_{recall(pr_b.hits):.3f}_parity_{'ok' if same else 'BROKEN'}",
    )
    rows["table2.batched"] = {
        "throughput_qps": batch_qps,
        "seq_qps": seq_qps,
        "speedup": batch_qps / seq_qps,
        "recall": recall(pr_b.hits),
        "parity_ok": bool(same),
        "probe_fragments": pr_b.probe_fragments,
    }

    # ---- filtered probe vs brute-force post-filter oracle ----------------
    # High-selectivity predicate on the cluster-correlated attribute: the
    # zone map must prune shards (fewer fragments than the unfiltered
    # batch), and recall against the scan+post-filter oracle must stay
    # ≥ 0.95 — both gated by scripts/check_bench.py on the emitted JSON.
    target = f"cat{int(labels[order][len(X) // 2])}"
    flt = f"category = '{target}' AND price < 90"
    # warm both paths (first call pays one-time jit tracing of the masked
    # kernels; the row measures steady-state throughput, like the batched
    # row), then INTERLEAVE the oracle/filtered timing rounds so the
    # speedup-vs-oracle ratio check_bench gates on sees the same load in
    # numerator and denominator (wall clock alone swings >2x with ambient
    # load at this scale — measured live tripping the old baseline gate)
    c.coordinator.probe("bench", Q[:1], 10, strategy="scan", filter=flt)
    c.coordinator.probe_batch("bench", Q, 10, strategy="diskann", filter=flt)
    oracle_s = filt_s = float("inf")
    oracle = pr_f = None
    for _ in range(3):
        t0 = time.perf_counter()
        oracle = c.coordinator.probe("bench", Q, 10, strategy="scan", filter=flt)
        oracle_s = min(oracle_s, time.perf_counter() - t0)
        t0 = time.perf_counter()
        pr_f = c.coordinator.probe_batch("bench", Q, 10, strategy="diskann", filter=flt)
        filt_s = min(filt_s, time.perf_counter() - t0)
    truth_f = [
        {(h.file_path, h.row_group, h.row_offset) for h in hits} for hits in oracle.hits
    ]
    scores = [
        len({(h.file_path, h.row_group, h.row_offset) for h in hits} & tf) / max(len(tf), 1)
        for hits, tf in zip(pr_f.hits, truth_f)
    ]
    recall_f = float(np.mean(scores))
    emit(
        "table2.filtered",
        filt_s / len(Q) * 1e6,
        f"B_{len(Q)}_plan_{pr_f.filter_plan.replace(',', '+')}_sel_{pr_f.est_selectivity:.3f}"
        f"_pruned_{pr_f.shards_pruned}_fragments_{pr_f.probe_fragments}"
        f"_vs_unfiltered_{pr_b.probe_fragments}_oracle_ms_{oracle_s*1e3:.0f}"
        f"_filtered_ms_{filt_s*1e3:.0f}_recall_vs_oracle_{recall_f:.3f}",
    )
    rows["table2.filtered"] = {
        "throughput_qps": len(Q) / filt_s,
        "recall": recall_f,
        "filter_plan": pr_f.filter_plan,
        "est_selectivity": pr_f.est_selectivity,
        "shards_pruned": pr_f.shards_pruned,
        "probe_fragments": pr_f.probe_fragments,
        "unfiltered_fragments": pr_b.probe_fragments,
        "oracle_qps": len(Q) / oracle_s,
        "speedup_vs_oracle": oracle_s / filt_s,
    }

    # ---- heterogeneous-filter batch: per-query mask planes ----------------
    # Every query carries a DISTINCT predicate (8+ of them).  One-query
    # probe() calls pay one masked-kernel pass per (query, shard); the
    # batch answers the whole coalesced fragment with one multi-mask call
    # per shard.  Both are measured in the same window (load cancels in the
    # ratio) and must return identical hits; check_bench gates: fewer
    # kernel dispatches than the one-query calls, speedup > 1, recall vs
    # oracle >= 0.95.
    hetero_filters = [
        f"price < {5 + (63 * i) // max(len(Q) - 1, 1)}" for i in range(len(Q))
    ]  # est selectivities ~0.05..0.68 — all mask-/prefilter-planned
    assert len(set(hetero_filters)) >= 8
    # warm both (masks cached, jit traced), then INTERLEAVE the sequential/
    # batch timing rounds: a load spike hits the same rounds of both, so
    # the speedup ratio check_bench hard-gates on stays clean — two
    # back-to-back best-of windows would let one unlucky window fail the
    # gate with no real regression (same reasoning as bench_kernels'
    # round-robin timing).
    def _hetero_probe():
        return c.coordinator.probe_batch(
            "bench", Q, 10, strategy="diskann", filter=hetero_filters
        )

    def _hetero_seq():
        return [
            c.coordinator.probe("bench", q, 10, strategy="diskann", filter=f)
            for q, f in zip(Q, hetero_filters)
        ]

    _hetero_probe()
    _hetero_seq()
    seq_s = het_s = float("inf")
    singles = pr_h = None
    for _ in range(3):
        t0 = time.perf_counter()
        singles = _hetero_seq()
        seq_s = min(seq_s, time.perf_counter() - t0)
        t0 = time.perf_counter()
        pr_h = _hetero_probe()
        het_s = min(het_s, time.perf_counter() - t0)
    oracle_h = c.coordinator.probe_batch(
        "bench", Q, 10, strategy="scan", filter=hetero_filters
    )
    truth_h = [
        {(h.file_path, h.row_group, h.row_offset) for h in hits} for hits in oracle_h.hits
    ]
    recall_h = float(np.mean([
        len({(h.file_path, h.row_group, h.row_offset) for h in hits} & th) / max(len(th), 1)
        for hits, th in zip(pr_h.hits, truth_h)
    ]))
    parity_h = all(
        [(h.file_path, h.row_group, h.row_offset) for h in a]
        == [(h.file_path, h.row_group, h.row_offset) for h in s.hits[0]]
        for a, s in zip(pr_h.hits, singles)
    )
    seq_dispatches = sum(s.kernel_dispatches for s in singles)
    emit(
        "table2.filtered_hetero",
        het_s / len(Q) * 1e6,
        f"B_{len(Q)}_distinct_{len(set(hetero_filters))}"
        f"_dispatches_{pr_h.kernel_dispatches}_vs_seq_{seq_dispatches}"
        f"_speedup_{seq_s/het_s:.2f}x_recall_vs_oracle_{recall_h:.3f}"
        f"_parity_{'ok' if parity_h else 'BROKEN'}",
    )
    rows["table2.filtered_hetero"] = {
        "throughput_qps": len(Q) / het_s,
        "seq_qps": len(Q) / seq_s,
        "speedup_vs_seq": seq_s / het_s,
        "recall": recall_h,
        "kernel_dispatches": pr_h.kernel_dispatches,
        "seq_dispatches": seq_dispatches,
        "distinct_filters": len(set(hetero_filters)),
        "probe_fragments": pr_h.probe_fragments,
        "parity_ok": bool(parity_h),
    }

    # ---- mixed-flavor fragment: unified exact/PQ kernel -------------------
    # Alternating tight (prefilter-band -> exact flavor) and wide (mask-band
    # -> PQ-ADC flavor) predicates put BOTH scoring flavors in every
    # coalesced fragment.  The unified kernel answers such a fragment in
    # exactly ONE dispatch per shard; check_bench gates that count and the
    # recall vs the scan oracle.
    from repro.runtime import planner

    mixed_filters = [
        f"price < {1 + i // 2}" if i % 2 == 0 else f"price < {55 + 3 * (i // 2)}"
        for i in range(len(Q))
    ]
    assert len(set(mixed_filters)) >= 8

    def _mixed_probe():
        return c.coordinator.probe_batch(
            "bench", Q, 10, strategy="diskann", filter=mixed_filters
        )

    _mixed_probe()  # warm masks + jit
    mixed_s = float("inf")
    pr_u = None
    for _ in range(3):
        t0 = time.perf_counter()
        pr_u = _mixed_probe()
        mixed_s = min(mixed_s, time.perf_counter() - t0)
    # the plan must genuinely mix flavors (else the row gates nothing)
    flavors = {
        type(pr_u.plan.op_for(qi, sid)).__name__
        for qi in range(len(Q))
        for sid in pr_u.plan.ops[qi]
    }
    assert {"ExactScan", "PQScan"} <= flavors, flavors
    oracle_m = c.coordinator.probe_batch(
        "bench", Q, 10, strategy="scan", filter=mixed_filters
    )
    truth_m = [
        {(h.file_path, h.row_group, h.row_offset) for h in hits}
        for hits in oracle_m.hits
    ]
    recall_m = float(np.mean([
        len({(h.file_path, h.row_group, h.row_offset) for h in hits} & tm)
        / max(len(tm), 1)
        for hits, tm in zip(pr_u.hits, truth_m)
    ]))
    emit(
        "table2.filtered_mixed_flavor",
        mixed_s / len(Q) * 1e6,
        f"B_{len(Q)}_distinct_{len(set(mixed_filters))}"
        f"_dispatches_{pr_u.kernel_dispatches}"
        f"_fragments_{pr_u.probe_fragments}_recall_vs_oracle_{recall_m:.3f}",
    )
    rows["table2.filtered_mixed_flavor"] = {
        "throughput_qps": len(Q) / mixed_s,
        "recall": recall_m,
        "kernel_dispatches": pr_u.kernel_dispatches,
        "probe_fragments": pr_u.probe_fragments,
        "distinct_filters": len(set(mixed_filters)),
    }

    # ---- low-selectivity predicate on a BIG shard: MaskedBeam vs postfilter
    # A shard above planner.EXACT_SCAN_MAX_ROWS cannot answer a filtered
    # query with a masked linear scan (the O(N·D) hole the cap exists for),
    # so below MASK_MAX_FRAC the planner routes it to MaskedBeam: a
    # predicate-aware traversal that expands through masked nodes but never
    # admits them.  The baseline is the over-fetched PostfilterBeam, whose
    # capped pool starves at low selectivity and dumps most rows into the
    # exact-masked fallback — replayed over the SAME queries via a
    # hand-authored plan, both paths timed interleaved in the same window
    # so ambient load cancels in the ratio.  check_bench gates the speedup,
    # recall vs the scan oracle, bounded dispatches (traversal rows cost no
    # masked-kernel dispatch; at most ONE fused fallback per fragment), and
    # guards the row against going vacuous: the shard must really be above
    # the cap, every row must really take the traversal, and not every
    # traversal row may fall back.
    n_big = 5_000 if tiny else 8_192
    D_big = 32
    assert n_big > planner.EXACT_SCAN_MAX_ROWS
    t_big = LakehouseTable(c.catalog, "bench_big")
    t_big.create(dim=D_big)
    Xb = clustered(rng, n_big, D_big, n_clusters=10)
    price_b = rng.integers(0, 100, size=n_big).astype(np.int64)
    t_big.append_vectors(
        Xb, num_files=4, rows_per_group=256, attributes={"price": price_b}
    )
    c.coordinator.create_index(
        "bench_big",
        IndexConfig(name="idx_big", num_shards=1, R=16 if tiny else 24,
                    L=32 if tiny else 64, partitions_per_shard=4,
                    build_passes=1, build_batch=256),
    )
    Qb = Xb[rng.choice(n_big, n_q)] + 0.05 * rng.normal(
        size=(n_q, D_big)
    ).astype(np.float32)
    flt_big = "price < 15"  # ~0.15: far below any sane over-fetch factor
    oracle_bb = c.coordinator.probe_batch(
        "bench_big", Qb, 10, strategy="scan", filter=flt_big
    )
    pr_mb = c.coordinator.probe_batch(
        "bench_big", Qb, 10, strategy="diskann", filter=flt_big
    )  # warm + capture the MaskedBeam plan
    assert "mbeam" in pr_mb.filter_plan, pr_mb.filter_plan
    post_plan = planner.ProbePlan(
        k=pr_mb.plan.k,
        oversample=pr_mb.plan.oversample,
        use_pq=pr_mb.plan.use_pq,
        ops=[
            {
                sid: (
                    planner.PostfilterBeam(
                        pool=planner.postfilter_pool(
                            10, pr_mb.plan.oversample, op.est_frac
                        ),
                        k=op.k,
                        est_frac=op.est_frac,
                    )
                    if isinstance(op, planner.MaskedBeam)
                    else op
                )
                for sid, op in row.items()
            }
            for row in pr_mb.plan.ops
        ],
        est_selectivity=pr_mb.plan.est_selectivity,
        pruned_shards=pr_mb.plan.pruned_shards,
    )
    c.coordinator.probe_batch(
        "bench_big", Qb, 10, strategy="diskann", filter=flt_big,
        replay_plan=post_plan,
    )  # warm the postfilter path (its pooled beam + fallback jit)
    mb_s = post_s = float("inf")
    pr_post = None
    for _ in range(3):
        t0 = time.perf_counter()
        pr_post = c.coordinator.probe_batch(
            "bench_big", Qb, 10, strategy="diskann", filter=flt_big,
            replay_plan=post_plan,
        )
        post_s = min(post_s, time.perf_counter() - t0)
        t0 = time.perf_counter()
        pr_mb = c.coordinator.probe_batch(
            "bench_big", Qb, 10, strategy="diskann", filter=flt_big
        )
        mb_s = min(mb_s, time.perf_counter() - t0)
    truth_bb = [
        {(h.file_path, h.row_group, h.row_offset) for h in hits}
        for hits in oracle_bb.hits
    ]
    recall_bb = float(np.mean([
        len({(h.file_path, h.row_group, h.row_offset) for h in hits} & tb)
        / max(len(tb), 1)
        for hits, tb in zip(pr_mb.hits, truth_bb)
    ]))
    emit(
        "table2.filtered_lowsel_bigshard",
        mb_s / len(Qb) * 1e6,
        f"B_{len(Qb)}_rows_{n_big}_sel_{pr_mb.est_selectivity:.3f}"
        f"_mbeam_rows_{pr_mb.masked_beam_rows}"
        f"_fallbacks_{pr_mb.masked_beam_fallbacks}"
        f"_dispatches_{pr_mb.kernel_dispatches}"
        f"_speedup_vs_postfilter_{post_s/mb_s:.2f}x"
        f"_recall_vs_oracle_{recall_bb:.3f}",
    )
    rows["table2.filtered_lowsel_bigshard"] = {
        "throughput_qps": len(Qb) / mb_s,
        "postfilter_qps": len(Qb) / post_s,
        "speedup_vs_postfilter": post_s / mb_s,
        "recall": recall_bb,
        "est_selectivity": pr_mb.est_selectivity,
        "shard_rows": n_big,
        "exact_scan_cap": planner.EXACT_SCAN_MAX_ROWS,
        "batch_queries": len(Qb),
        "masked_beam_rows": pr_mb.masked_beam_rows,
        "masked_beam_fallbacks": pr_mb.masked_beam_fallbacks,
        "postfilter_dispatches": pr_post.kernel_dispatches,
        "kernel_dispatches": pr_mb.kernel_dispatches,
        "probe_fragments": pr_mb.probe_fragments,
        "plan_mbeam": "mbeam" in pr_mb.filter_plan,
    }

    # ---- freshness: append → probe with NO refresh (fresh-tail tier) ------
    # Sustained write load: append a tail (~1/16 of the corpus), then probe
    # immediately against the now-stale index binding.  The scan oracle
    # reads the snapshot's own file list, so it is fresh by construction;
    # the tail tier must hold recall vs it >= 0.95 with unindexed_rows == 0
    # (the silent stale-read window this tier closes), carrying exactly one
    # plan op per unindexed row group.  ``recall_without_tail`` records the
    # pre-fix silent-drop recall for the staleness axis; latency is the
    # stale-probe p50 (tail scan riding the same wave as the graph shards).
    n_tail = max(len(X) // 16, rows_per_group)
    Xt = clustered(rng, n_tail, D, n_clusters=8)
    t.append_vectors(
        Xt,
        num_files=1,
        rows_per_group=rows_per_group,
        file_prefix="tail",
        attributes={
            "category": np.asarray(["tail"] * n_tail),
            "price": rng.integers(0, 100, size=n_tail).astype(np.int64),
        },
    )
    # half the queries target old (indexed) rows, half the fresh tail
    half = len(Q) // 2
    Qf = np.concatenate([
        Q[:half],
        Xt[rng.choice(n_tail, len(Q) - half)]
        + 0.05 * rng.normal(size=(len(Q) - half, D)).astype(np.float32),
    ])
    c.coordinator.probe_batch("bench", Qf, 10, strategy="diskann")  # warm
    oracle_fs = stale_s = float("inf")
    oracle_fr = pr_t = None
    for _ in range(3):
        t0 = time.perf_counter()
        oracle_fr = c.coordinator.probe_batch("bench", Qf, 10, strategy="scan")
        oracle_fs = min(oracle_fs, time.perf_counter() - t0)
        t0 = time.perf_counter()
        pr_t = c.coordinator.probe_batch("bench", Qf, 10, strategy="diskann")
        stale_s = min(stale_s, time.perf_counter() - t0)
    truth_t = [
        {(h.file_path, h.row_group, h.row_offset) for h in hits}
        for hits in oracle_fr.hits
    ]
    def _recall_vs_fresh(rep):
        return float(np.mean([
            len({(h.file_path, h.row_group, h.row_offset) for h in hits} & tt)
            / max(len(tt), 1)
            for hits, tt in zip(rep.hits, truth_t)
        ]))
    recall_t = _recall_vs_fresh(pr_t)
    # the pre-fix behavior, for the staleness axis: tail tier off
    pr_drop = c.coordinator.probe_batch(
        "bench", Qf, 10, strategy="diskann", include_tail=False
    )
    recall_drop = _recall_vs_fresh(pr_drop)
    tail_rgs = -(n_tail // -rows_per_group)  # ceil: row groups in the tail
    tail_plan_ops = (
        len([sid for sid in pr_t.plan.ops[0] if sid < 0]) if pr_t.plan else 0
    )
    emit(
        "table2.freshness",
        stale_s / len(Qf) * 1e6,
        f"B_{len(Qf)}_tail_rows_{pr_t.tail_rows}_rgs_{tail_rgs}"
        f"_recall_vs_oracle_{recall_t:.3f}_without_tail_{recall_drop:.3f}"
        f"_unindexed_{pr_t.unindexed_rows}_stale_{pr_t.stale}"
        f"_p50_ms_{stale_s/len(Qf)*1e3:.1f}",
    )
    rows["table2.freshness"] = {
        "throughput_qps": len(Qf) / stale_s,
        "recall": recall_t,
        "recall_without_tail": recall_drop,
        "tail_rows": pr_t.tail_rows,
        "tail_row_groups": tail_rgs,
        "tail_plan_ops": tail_plan_ops,
        "unindexed_rows": pr_t.unindexed_rows,
        "stale": bool(pr_t.stale),
        "oracle_qps": len(Qf) / oracle_fs,
    }

    # ---- overload: two tenants at ~2x capacity, one abusive ------------
    # The serving tier's admission-control contract: with offered load about
    # twice what the cluster can serve, an ABUSIVE tenant (flooding far past
    # its token-bucket rate) must absorb the rejections while the
    # well-behaved tenant keeps a >= 0.9 deadline hit-rate and the bounded
    # submission queue never grows past its cap.
    import queue as queue_mod
    import threading

    from repro.serving.admission import AdmissionRejected, TenantPolicy
    from repro.serving.serve_loop import ProbeMicroBatcher

    batch_s, _ = _best_of(
        lambda: c.coordinator.probe_batch("bench", Q, 10, strategy="diskann")
    )
    capacity_qps = n_q / batch_s  # warm micro-batch service rate
    well_qps = 0.25 * capacity_qps
    abusive_qps = 1.75 * capacity_qps  # offered, mostly refused at the door
    duration_s = 2.0
    max_queue = 64
    deadline_ms = max(1000.0, 20.0 * batch_s * 1e3)
    counts = {
        "well_attempts": 0, "well_full": 0,
        "abusive_attempts": 0, "abusive_admitted": 0, "abusive_rejected": 0,
    }
    well_futs: list = []
    peak_q = [0]
    with ProbeMicroBatcher(
        c.coordinator,
        "bench",
        strategy="diskann",
        max_batch=max(8, n_q),
        max_wait_s=0.002,
        max_queue=max_queue,
        tenant_policies={
            # the abuser's budget: ~25% of capacity, everything past it
            # bounces off its own bucket instead of the shared queue
            "abusive": TenantPolicy(rate_qps=0.25 * capacity_qps, burst=8.0),
        },
    ) as mb:
        stop_at = time.perf_counter() + duration_s

        def flood():
            # absolute schedule: sleep-to-next-tick, so the OFFERED rate
            # holds even when sleep() overshoots at millisecond intervals
            next_t = time.perf_counter()
            while time.perf_counter() < stop_at:
                counts["abusive_attempts"] += 1
                try:
                    mb.submit(
                        Q[counts["abusive_attempts"] % n_q], 10,
                        tenant="abusive", deadline_ms=deadline_ms,
                    )
                    counts["abusive_admitted"] += 1
                except (AdmissionRejected, queue_mod.Full):
                    counts["abusive_rejected"] += 1
                next_t += 1.0 / abusive_qps
                delay = next_t - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)

        flooder = threading.Thread(target=flood)
        flooder.start()
        next_t = time.perf_counter()
        while time.perf_counter() < stop_at:
            counts["well_attempts"] += 1
            try:
                well_futs.append(mb.submit(
                    Q[counts["well_attempts"] % n_q], 10,
                    tenant="well", deadline_ms=deadline_ms,
                ))
            except (AdmissionRejected, queue_mod.Full):
                counts["well_full"] += 1
            peak_q[0] = max(peak_q[0], mb._queue.qsize())
            next_t += 1.0 / well_qps
            delay = next_t - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        flooder.join()
        well_served = 0
        for f in well_futs:
            try:
                f.result(timeout=30)
                well_served += 1
            except Exception:
                pass
        served_total = mb.stats.queries
        deadline_misses = mb.stats.deadline_misses
        degraded_batches = mb.stats.degraded_batches
    offered_qps = (counts["well_attempts"] + counts["abusive_attempts"]) / duration_s
    well_hit_rate = well_served / max(1, counts["well_attempts"])
    well_rejected = counts["well_full"] + (len(well_futs) - well_served)
    queue_bounded = peak_q[0] <= max_queue
    emit(
        "table2.overload",
        duration_s / max(1, served_total) * 1e6,
        f"capacity_{capacity_qps:.0f}qps_offered_{offered_qps:.0f}qps"
        f"_x{offered_qps / capacity_qps:.1f}_well_hit_{well_hit_rate:.2f}"
        f"_abusive_rej_{counts['abusive_rejected']}"
        f"_deadline_misses_{deadline_misses}_peak_queue_{peak_q[0]}",
    )
    rows["table2.overload"] = {
        "throughput_qps": served_total / duration_s,
        "capacity_qps": capacity_qps,
        "offered_qps": offered_qps,
        "overload_factor": offered_qps / capacity_qps,
        "well_hit_rate": well_hit_rate,
        "well_attempts": counts["well_attempts"],
        "well_served": well_served,
        "well_rejected": well_rejected,
        "abusive_attempts": counts["abusive_attempts"],
        "abusive_admitted": counts["abusive_admitted"],
        "abusive_rejected": counts["abusive_rejected"],
        "deadline_misses": deadline_misses,
        "degraded_batches": degraded_batches,
        "queue_bounded": queue_bounded,
    }

    # ---- zipfian: the serving-tier cache hierarchy under skewed traffic --
    # Real streams are Zipfian: a Zipf(s≈1.1) stream over a fixed query
    # pool, two tenants, warm (both cache layers on) vs cold (caches off)
    # interleaved per event in the SAME timing window.  Repeats within a
    # tenant hit the semantic result cache at the batcher's door; the first
    # cross-tenant repeat misses the (per-tenant) semantic layer and hits
    # the shared shard-probe cache instead.  The row also proves the two
    # correctness claims the gate enforces: bit-parity with the cache-off
    # path on non-repeating AND fully-cached traffic, and a refresh commit
    # invalidating both layers with zero stale answers afterwards.
    from repro.serving.cache import SemanticResultCache, ShardProbeCache

    pool_n = 16
    pool = (
        X[rng.choice(len(X), pool_n)]
        + 0.05 * rng.normal(size=(pool_n, D)).astype(np.float32)
    ).astype(np.float32)
    oracle_zr = c.coordinator.probe_batch("bench", pool, 10, strategy="scan")
    truth_z = [
        {(h.file_path, h.row_group, h.row_offset) for h in hits}
        for hits in oracle_zr.hits
    ]
    zipf_s = 1.1
    zr = np.arange(1, pool_n + 1, dtype=np.float64)
    pz = zr ** -zipf_s
    pz /= pz.sum()
    stream_len = 96 if tiny else 128
    stream = rng.choice(pool_n, size=stream_len, p=pz)
    tenant_stream = np.where(rng.random(stream_len) < 0.5, "tenant_a", "tenant_b")

    def _locs_z(rep):
        return [
            [(h.file_path, h.row_group, h.row_offset) for h in hs]
            for hs in rep.hits
        ]

    shard_cache = ShardProbeCache(max_bytes=8 << 20)
    sem_cache = SemanticResultCache(max_bytes=4 << 20, distance_threshold=1e-4)
    warm_lat: list = []
    cold_lat: list = []
    warm_answers: list = []
    mb_warm = ProbeMicroBatcher(
        c.coordinator, "bench", strategy="diskann", max_wait_s=0.0005,
        semantic_cache=sem_cache,
    ).start()
    mb_cold = ProbeMicroBatcher(
        c.coordinator, "bench", strategy="diskann", max_wait_s=0.0005
    ).start()
    try:
        for pi, ten in zip(stream, tenant_stream):
            q = pool[pi]
            # cold leg first, caches off — same interleaved window
            c.coordinator.probe_cache = None
            t0 = time.perf_counter()
            mb_cold.submit(q, 10, tenant=str(ten)).result(timeout=60)
            cold_lat.append(time.perf_counter() - t0)
            # warm leg, both layers on
            c.coordinator.probe_cache = shard_cache
            t0 = time.perf_counter()
            wh = mb_warm.submit(q, 10, tenant=str(ten)).result(timeout=60)
            warm_lat.append(time.perf_counter() - t0)
            warm_answers.append((int(pi), wh))
        sem_hits = mb_warm.stats.semantic_hits
        sem_misses = mb_warm.stats.semantic_misses
        shard_hits = shard_cache.stats.hits
        shard_lookups = shard_cache.stats.hits + shard_cache.stats.misses
        recall_z = float(np.mean([
            len({(h.file_path, h.row_group, h.row_offset) for h in hs}
                & truth_z[pi]) / max(len(truth_z[pi]), 1)
            for pi, hs in warm_answers
        ]))
        # bit-parity proof: a FRESH shard cache on non-repeating traffic
        # (first pass populates, zero hits) and on a full repeat (every
        # fragment a hit) both match the cache-off path exactly
        parity_cache = ShardProbeCache(max_bytes=8 << 20)
        c.coordinator.probe_cache = None
        off_rep = c.coordinator.probe_batch("bench", pool, 10, strategy="diskann")
        c.coordinator.probe_cache = parity_cache
        on_first = c.coordinator.probe_batch("bench", pool, 10, strategy="diskann")
        on_replay = c.coordinator.probe_batch("bench", pool, 10, strategy="diskann")
        parity_ok = bool(
            _locs_z(off_rep) == _locs_z(on_first) == _locs_z(on_replay)
        )
        replay_cache_hits = int(on_replay.shard_cache_hits)
        # refresh: the snapshot commit is the invalidation token for BOTH
        # layers; afterwards, caches-on must equal caches-off exactly
        n_zt = rows_per_group
        t.append_vectors(
            clustered(rng, n_zt, D, n_clusters=4),
            num_files=1,
            rows_per_group=rows_per_group,
            attributes={
                "category": np.asarray(["zfresh"] * n_zt),
                "price": rng.integers(0, 100, size=n_zt).astype(np.int64),
            },
        )
        c.coordinator.probe_cache = shard_cache
        c.coordinator.refresh_index("bench", "idx")
        invalidations = int(
            shard_cache.stats.invalidations + sem_cache.stats.invalidations
        )
        post_on = c.coordinator.probe_batch("bench", pool, 10, strategy="diskann")
        c.coordinator.probe_cache = None
        post_off = c.coordinator.probe_batch("bench", pool, 10, strategy="diskann")
        stale_hits = sum(
            1 for a, b in zip(_locs_z(post_on), _locs_z(post_off)) if a != b
        )
        # the semantic layer must re-probe too (entries evicted at commit)
        wh_post = mb_warm.submit(
            pool[0], 10, tenant="tenant_a"
        ).result(timeout=60)
        stale_hits += int(mb_warm.stats.semantic_hits > sem_hits)
        stale_hits += int(
            [(h.file_path, h.row_group, h.row_offset) for h in wh_post]
            != _locs_z(post_off)[0]
        )
    finally:
        mb_warm.stop()
        mb_cold.stop()
        c.coordinator.probe_cache = None
    warm_p50, warm_p99 = np.percentile(np.array(warm_lat) * 1e3, [50, 99])
    cold_p50, cold_p99 = np.percentile(np.array(cold_lat) * 1e3, [50, 99])
    emit(
        "table2.zipfian",
        float(np.sum(warm_lat)) / stream_len * 1e6,
        f"pool_{pool_n}_stream_{stream_len}_sem_hits_{sem_hits}"
        f"_shard_hits_{shard_hits}_warm_p50_ms_{warm_p50:.2f}"
        f"_cold_p50_ms_{cold_p50:.2f}_recall_{recall_z:.3f}"
        f"_inval_{invalidations}_stale_{stale_hits}_parity_{parity_ok}",
    )
    rows["table2.zipfian"] = {
        "throughput_qps": stream_len / float(np.sum(warm_lat)),
        "recall": recall_z,
        "zipf_s": zipf_s,
        "pool_size": pool_n,
        "stream_len": stream_len,
        "semantic_hits": int(sem_hits),
        "semantic_misses": int(sem_misses),
        "semantic_hit_rate": sem_hits / stream_len,
        "shard_hits": int(shard_hits),
        "shard_lookups": int(shard_lookups),
        "shard_hit_rate": shard_hits / max(1, shard_lookups),
        "warm_p50_ms": float(warm_p50),
        "warm_p99_ms": float(warm_p99),
        "cold_p50_ms": float(cold_p50),
        "cold_p99_ms": float(cold_p99),
        "parity_ok": parity_ok,
        "replay_cache_hits": replay_cache_hits,
        "invalidations": invalidations,
        "stale_hits": int(stale_hits),
    }

    if json_path:
        doc = {
            "meta": {"bench": "bench_query_paths", "tiny": tiny, "n_vec": n_vec,
                     "n_queries": n_q, "dim": D},
            "rows": rows,
        }
        with open(json_path, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f"wrote {json_path}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="seconds-scale smoke run (CI)")
    ap.add_argument("--json", dest="json_path", default="BENCH_query_paths.json",
                    help="machine-readable output for scripts/check_bench.py "
                         "('' disables)")
    main(**vars(ap.parse_args()))
