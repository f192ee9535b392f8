#!/usr/bin/env python
"""Benchmark-regression gate for CI (scripts/ci.sh --bench).

Reads the machine-readable records the benchmark runs write — any number
of them, each paired with its own committed baseline (currently
``BENCH_query_paths.json`` from ``benchmarks/bench_query_paths.py`` and
``BENCH_kernels.json`` from ``benchmarks/bench_kernels.py``) — and fails
with a readable report when a run regresses, replacing the ad-hoc asserts
that used to live inside the bench scripts:

Absolute gates (hold regardless of any baseline):
  - ``table2.batched``: per-query parity with sequential probes
    (``parity_ok``) and throughput strictly above the sequential path
    (``speedup > 1``);
  - ``table2.filtered``: recall vs the brute-force post-filter oracle
    >= 0.95, and zone-map pruning still reducing dispatched shard
    fragments (fewer fragments than the unfiltered batch, or whole shards
    pruned) on the high-selectivity predicate (``speedup_vs_oracle`` is
    recorded but not gated — at tiny CI scale the one-wave oracle
    legitimately outruns the two-wave distributed pipeline; the masked
    kernels' own perf gates live in the kernels file);
  - ``table2.filtered_hetero`` (8+ distinct predicates in one batch):
    recall vs oracle >= 0.95, hits identical to one-query ``probe()``
    calls (``parity_ok``), FEWER masked-kernel dispatches than those calls
    (``kernel_dispatches < seq_dispatches`` — the whole point of the
    (Q, N) mask-plane kernels), and throughput strictly above them
    (``speedup_vs_seq > 1``; both are timed in the same window, so
    ambient load cancels in the ratio).
  - ``table2.filtered_mixed_flavor`` (batch mixing exact- and PQ-flavor
    plans with heterogeneous predicates): recall vs oracle >= 0.95 and
    EXACTLY one kernel dispatch per shard (``kernel_dispatches ==
    probe_fragments`` — the unified exact/PQ kernel's contract).
  - ``table2.filtered_lowsel_bigshard`` (low-selectivity predicate on a
    shard above the planner's masked-scan cap): the MaskedBeam traversal
    must beat the replayed over-fetched postfilter plan in its same-window
    paired timing (``speedup_vs_postfilter > 1``), hold recall vs the scan
    oracle >= 0.95, and stay within its dispatch budget
    (``kernel_dispatches <= probe_fragments`` — traversal rows cost no
    masked-kernel dispatch, at most ONE fused fallback per fragment);
    vacuous-run guards: the shard really above ``exact_scan_cap``, every
    batch row really traversed (``masked_beam_rows == batch_queries``
    with ``plan_mbeam``), and not every traversal row allowed to fall
    back to the exact scan.
  - ``table2.freshness`` (probe immediately after an append, NO index
    refresh): an unindexed tail must actually be present (``tail_rows >
    0`` and ``stale``), recall vs the fresh scan oracle >= 0.95, ZERO
    silently-dropped rows (``unindexed_rows == 0`` — the stale-read
    window the fresh-tail tier closes), and exactly one plan op per
    unindexed row group (``tail_plan_ops == tail_row_groups``).
  - ``table2.overload`` (two tenants at ~2x serving capacity, one abusive):
    offered load actually over capacity (``overload_factor >= 1.5``), the
    well-behaved tenant's deadline hit-rate >= 0.9, the ABUSIVE tenant
    absorbing the rejections (``abusive_rejected > well_rejected``), and
    the submission queue staying bounded (``queue_bounded``) — the
    serving tier's admission-control contract.  Never wall-clock gated:
    the row's qps rides the scheduler like every other table2 row.
  - ``table2.zipfian`` (Zipf-distributed repeat traffic through the
    two-layer cache hierarchy): vacuous-run guards first (the stream must
    be longer than the query pool so repeats actually occur, and the
    full-repeat parity pass must take >0 shard-cache hits — otherwise
    ``parity_ok`` compares the uncached path with itself), then both hit
    rates > 0 (``semantic_hit_rate``, ``shard_hit_rate``), warm p50
    strictly below cold p50 (same interleaved window, so load cancels),
    recall vs the scan oracle >= 0.95, bit parity with the cache-off path
    (``parity_ok``), >0 ``invalidations`` after the mid-bench refresh,
    and ZERO ``stale_hits`` after the snapshot commit.  Never wall-clock
    gated against the baseline — warm-vs-cold is its own paired timing.

  - ``kernel.gather_rerank``: the device pool rerank must beat the removed
    NumPy host rerank it replaced (``speedup_vs_host > 1``; both sides are
    timed in the same interleaved window, so load cancels);
  - ``kernel.unified_masked_topk``: fused-dispatch hits identical to the
    split-flavor exact+ADC dispatches (``parity_ok``);
  - quantized scan rows (``kernel.masked_exact_topk_bf16`` / ``_int8``):
    recall AFTER the full-precision gather-rerank guard >= 0.95
    (``recall_post_guard``), and speed vs the f32 scan gated by backend —
    ``speedup_vs_f32 > 1`` when ``quantized_native`` (TPU), else the 0.5x
    plumbing floor (CPU scoring dequantizes to f32, so quantization buys
    bandwidth/footprint there, not FLOPs).

Baseline gates (vs the committed baseline, benchmarks/baselines/):
  - a THROUGHPUT-GATED row's ``throughput_qps`` dropping more than
    ``--max-regress`` (default 20%) below the baseline, after normalizing
    by the machine factor — the MEDIAN of cur/base throughput ratios
    across ALL rows of the same bench file — or, when the file carries
    ``anchor.*`` rows (fixed pure-numpy work no repo change can affect),
    across the anchors alone, so even a uniform regression of every gated
    row is caught.  The baseline was recorded on
    one machine and CI runs on another, so a uniform speed difference must
    divide out; a real regression changes one path's ratio and sticks out
    from the median.  Throughput-gated rows: every ``kernel.*`` row
    (single-process compute, no beam search or scheduler in the loop;
    kernel rows use the wider ``KERNEL_MAX_REGRESS`` budget — see its
    comment).  NO table2 row is wall-clock gated: every one rides the
    coordinator/scheduler (5 ms poll quantization per wave) and swings
    >2x with ambient load even best-of-N (measured live) — gating those
    on wall clock makes CI cry wolf; batched and hetero are instead gated
    on their speedup ratios (numerator and denominator timed in the same
    window, so load cancels).  All rows still feed the
    machine factor and the recall gate.
  - baseline drift, BOTH directions: a row present in the baseline but
    missing from the current run (a silently dropped row would otherwise
    un-gate itself), and a row the bench now emits that is missing from
    the committed baseline (a stale baseline would otherwise exempt the
    new row from every baseline-relative gate — regenerate the baseline
    alongside the change that added the row).
  - ANY row's ``recall`` dropping below the baseline at all (recall is
    deterministic under the bench's fixed seeds, so any drop is a real
    behavior change, not timing noise).

A missing, empty, or row-less input file is an ERROR (exit 2), not a
pass: a bench run that crashed before writing its record must fail the
gate loudly instead of green-lighting stale or absent data.

Baseline update procedure: see the header of scripts/ci.sh.

Exit status: 0 = clean, 1 = regression(s) (each printed on its own
``BENCH-REGRESSION:`` line), 2 = bad invocation / unreadable input.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

DEFAULT_MAX_REGRESS = 0.20
# kernel.* rows time bare eager matmuls whose wall clock floats ±20% on a
# shared runner even after interleaved best-of-16 and the machine-factor
# normalization (measured across repeated runs) — a 20% budget flakes, so
# they get a wider one.  A genuine kernel regression (an accidentally
# quadratic mask path, a lost fusion) costs 2x+, far past 35%.
KERNEL_MAX_REGRESS = 0.35
RECALL_EPS = 1e-9  # float-representation slack only: ANY real drop fails
FILTERED_MIN_RECALL = 0.95
# quantized scan flavors (bf16/int8): recall AFTER the mandatory
# full-precision gather-rerank guard must stay >= this floor — the guard
# exists precisely so reduced-precision scanning never costs recall
QUANT_MIN_RECALL = 0.95
# speed: on a native backend (TPU) a quantized scan must beat the f32 scan
# outright; on CPU the honest scoring path dequantizes to f32 (quantization
# buys memory footprint, not CPU FLOPs — measured ~0.6-0.7x), so the gate
# only catches a pathological slowdown of the quantized plumbing
QUANT_NON_NATIVE_SPEED_FLOOR = 0.5
QUANT_ROWS = ("kernel.masked_exact_topk_bf16", "kernel.masked_exact_topk_int8")
# Wall-clock baseline gating is reserved for the kernels file: its rows
# are single-process compute timed in interleaved rounds against a
# pure-numpy anchor.  NO table2 row is wall-clock gated — every one of
# them rides the coordinator/scheduler (5 ms poll quantization per wave)
# and swings >2x with ambient load (measured live, including
# table2.filtered, which PR 3 briefly wall-clock-gated) — they gate on
# load-cancelling SAME-WINDOW ratios instead: batched speedup vs
# sequential, filtered speedup vs the brute-force oracle, hetero speedup
# vs one-query probes + its dispatch count, plus recall.
THROUGHPUT_GATED = ()
THROUGHPUT_GATED_PREFIXES = ("kernel.",)
DEFAULT_BASELINE_DIR = "benchmarks/baselines"


def _throughput_gated(name: str) -> bool:
    return name in THROUGHPUT_GATED or name.startswith(THROUGHPUT_GATED_PREFIXES)


def _regress_budget(name: str, max_regress: float) -> float:
    if name.startswith(THROUGHPUT_GATED_PREFIXES):
        return max(max_regress, KERNEL_MAX_REGRESS)
    return max_regress


def check(
    current: dict,
    baseline: Optional[dict],
    max_regress: float = DEFAULT_MAX_REGRESS,
) -> List[str]:
    """Pure gate logic for ONE (current, baseline) document pair: returns a
    list of human-readable failures (empty = clean).  Split from main() so
    the unit tests can doctor JSON documents and assert specific injected
    regressions are caught."""
    failures: List[str] = []
    rows = current.get("rows", {})
    base_rows = (baseline or {}).get("rows", {})

    batched = rows.get("table2.batched")
    if batched is not None:
        if not batched.get("parity_ok", True):
            failures.append(
                "table2.batched: batched hits diverge from sequential probes"
            )
        if batched.get("speedup", 0.0) <= 1.0:
            failures.append(
                f"table2.batched: batched throughput "
                f"{batched.get('throughput_qps', 0.0):.1f} qps is not above the "
                f"sequential path {batched.get('seq_qps', 0.0):.1f} qps"
            )
    filtered = rows.get("table2.filtered")
    if filtered is not None:
        if filtered.get("recall", 0.0) < FILTERED_MIN_RECALL:
            failures.append(
                f"table2.filtered: recall vs oracle {filtered.get('recall', 0.0):.3f} "
                f"< {FILTERED_MIN_RECALL}"
            )
        if (
            filtered.get("probe_fragments", 0)
            >= filtered.get("unfiltered_fragments", 0)
            and filtered.get("shards_pruned", 0) == 0
        ):
            failures.append(
                "table2.filtered: zone-map pruning dispatched no fewer shard "
                f"fragments ({filtered.get('probe_fragments')} vs unfiltered "
                f"{filtered.get('unfiltered_fragments')}) on a high-selectivity "
                "predicate"
            )
        # (speedup_vs_oracle is informational, NOT gated: at the tiny CI
        # scale the one-wave brute-force oracle legitimately beats the
        # two-wave distributed pipeline on wall clock — the masked
        # kernels' own perf is gated in BENCH_kernels.json instead)
    hetero = rows.get("table2.filtered_hetero")
    if hetero is not None:
        if hetero.get("recall", 0.0) < FILTERED_MIN_RECALL:
            failures.append(
                f"table2.filtered_hetero: recall vs oracle "
                f"{hetero.get('recall', 0.0):.3f} < {FILTERED_MIN_RECALL}"
            )
        if not hetero.get("parity_ok", True):
            failures.append(
                "table2.filtered_hetero: mask-plane hits diverge from the "
                "one-query probes"
            )
        if hetero.get("kernel_dispatches", 0) >= hetero.get("seq_dispatches", 0):
            failures.append(
                "table2.filtered_hetero: mask-plane path issued no fewer kernel "
                f"dispatches ({hetero.get('kernel_dispatches')}) than the "
                f"one-query probes ({hetero.get('seq_dispatches')}) "
                f"on {hetero.get('distinct_filters', '?')} distinct predicates"
            )
        if hetero.get("speedup_vs_seq", 0.0) <= 1.0:
            failures.append(
                f"table2.filtered_hetero: mask-plane throughput "
                f"{hetero.get('throughput_qps', 0.0):.1f} qps is not above the "
                f"one-query probes {hetero.get('seq_qps', 0.0):.1f} qps"
            )
    mixed = rows.get("table2.filtered_mixed_flavor")
    if mixed is not None:
        if mixed.get("recall", 0.0) < FILTERED_MIN_RECALL:
            failures.append(
                f"table2.filtered_mixed_flavor: recall vs oracle "
                f"{mixed.get('recall', 0.0):.3f} < {FILTERED_MIN_RECALL}"
            )
        if mixed.get("kernel_dispatches", -1) != mixed.get("probe_fragments", 0):
            failures.append(
                "table2.filtered_mixed_flavor: mixed-flavor fragments did not "
                f"complete in exactly one kernel dispatch per shard "
                f"({mixed.get('kernel_dispatches')} dispatches for "
                f"{mixed.get('probe_fragments')} fragments)"
            )
    bigshard = rows.get("table2.filtered_lowsel_bigshard")
    if bigshard is not None:
        # vacuous-run guards first: the row gates nothing unless the shard
        # is really above the masked-scan cap AND every batch row really
        # took the MaskedBeam traversal
        if bigshard.get("shard_rows", 0) <= bigshard.get("exact_scan_cap", 0):
            failures.append(
                f"table2.filtered_lowsel_bigshard: shard has "
                f"{bigshard.get('shard_rows', 0)} rows, not above the "
                f"masked-scan cap {bigshard.get('exact_scan_cap', 0)} — the "
                "MaskedBeam band was never exercised"
            )
        if not bigshard.get("plan_mbeam", False) or (
            bigshard.get("masked_beam_rows", 0)
            < bigshard.get("batch_queries", -1)
        ):
            failures.append(
                f"table2.filtered_lowsel_bigshard: only "
                f"{bigshard.get('masked_beam_rows', 0)} of "
                f"{bigshard.get('batch_queries', 0)} batch rows took the "
                f"MaskedBeam traversal (plan_mbeam="
                f"{bigshard.get('plan_mbeam', False)}) — the row is not "
                "measuring the predicate-aware path"
            )
        if bigshard.get("masked_beam_fallbacks", 0) >= max(
            bigshard.get("masked_beam_rows", 0), 1
        ):
            failures.append(
                f"table2.filtered_lowsel_bigshard: every traversal row "
                f"({bigshard.get('masked_beam_fallbacks', 0)}) under-delivered "
                "into the exact fallback — the timing just compares the "
                "fallback path with itself"
            )
        if bigshard.get("recall", 0.0) < FILTERED_MIN_RECALL:
            failures.append(
                f"table2.filtered_lowsel_bigshard: recall vs oracle "
                f"{bigshard.get('recall', 0.0):.3f} < {FILTERED_MIN_RECALL}"
            )
        if bigshard.get("speedup_vs_postfilter", 0.0) <= 1.0:
            failures.append(
                f"table2.filtered_lowsel_bigshard: MaskedBeam throughput "
                f"{bigshard.get('throughput_qps', 0.0):.1f} qps is not above "
                f"the replayed postfilter path "
                f"{bigshard.get('postfilter_qps', 0.0):.1f} qps (same-window "
                "paired timing)"
            )
        if bigshard.get("kernel_dispatches", 0) > bigshard.get(
            "probe_fragments", 0
        ):
            failures.append(
                f"table2.filtered_lowsel_bigshard: "
                f"{bigshard.get('kernel_dispatches', 0)} masked-kernel "
                f"dispatches for {bigshard.get('probe_fragments', 0)} "
                "fragments — traversal rows must cost no dispatch beyond "
                "ONE fused fallback per fragment"
            )
    fresh = rows.get("table2.freshness")
    if fresh is not None:
        if fresh.get("tail_rows", 0) <= 0 or not fresh.get("stale", False):
            failures.append(
                "table2.freshness: the bench probed with no unindexed tail "
                f"present (tail_rows={fresh.get('tail_rows', 0)}, "
                f"stale={fresh.get('stale', False)}) — the staleness gate "
                "exercised nothing"
            )
        if fresh.get("recall", 0.0) < FILTERED_MIN_RECALL:
            failures.append(
                f"table2.freshness: recall vs the fresh scan oracle "
                f"{fresh.get('recall', 0.0):.3f} < {FILTERED_MIN_RECALL} with "
                "an unindexed tail present — appended rows are not searchable"
            )
        if fresh.get("unindexed_rows", -1) != 0:
            failures.append(
                f"table2.freshness: probe silently dropped "
                f"{fresh.get('unindexed_rows')} appended-but-unindexed rows "
                "(the pre-tail-tier stale-read window is back)"
            )
        if fresh.get("tail_plan_ops", -1) != fresh.get("tail_row_groups", 0):
            failures.append(
                f"table2.freshness: plan carried "
                f"{fresh.get('tail_plan_ops')} tail ops for "
                f"{fresh.get('tail_row_groups')} unindexed row groups — the "
                "one-ExactScan-per-tail-row-group contract broke"
            )

    overload = rows.get("table2.overload")
    if overload is not None:
        if overload.get("overload_factor", 0.0) < 1.5:
            failures.append(
                f"table2.overload: offered load was only "
                f"{overload.get('overload_factor', 0.0):.2f}x capacity — the "
                "bench did not actually overload the serving tier"
            )
        if overload.get("well_hit_rate", 0.0) < 0.9:
            failures.append(
                f"table2.overload: well-behaved tenant deadline hit-rate "
                f"{overload.get('well_hit_rate', 0.0):.2f} < 0.9 under an "
                "abusive co-tenant — admission control is not isolating tenants"
            )
        if overload.get("abusive_rejected", 0) <= overload.get("well_rejected", 0):
            failures.append(
                f"table2.overload: the abusive tenant absorbed "
                f"{overload.get('abusive_rejected', 0)} rejections vs the "
                f"well-behaved tenant's {overload.get('well_rejected', 0)} — "
                "the wrong tenant is paying for the overload"
            )
        if not overload.get("queue_bounded", False):
            failures.append(
                "table2.overload: the submission queue exceeded its bound "
                "under overload — backpressure is not holding"
            )

    zipf = rows.get("table2.zipfian")
    if zipf is not None:
        # vacuous-run guards: the row gates nothing unless the stream
        # actually repeated queries and the replay pass actually hit
        if zipf.get("stream_len", 0) <= zipf.get("pool_size", 0):
            failures.append(
                f"table2.zipfian: stream of {zipf.get('stream_len', 0)} over a "
                f"pool of {zipf.get('pool_size', 0)} never repeats a query — "
                "the cache hierarchy was never exercised"
            )
        if zipf.get("replay_cache_hits", 0) <= 0:
            failures.append(
                "table2.zipfian: the full-repeat parity pass took zero shard-"
                "cache hits — parity_ok compares the uncached path with itself"
            )
        if zipf.get("semantic_hit_rate", 0.0) <= 0.0:
            failures.append(
                f"table2.zipfian: semantic hit rate "
                f"{zipf.get('semantic_hit_rate', 0.0):.3f} is not > 0 under "
                "Zipfian repeats — the result cache never answered"
            )
        if zipf.get("shard_hit_rate", 0.0) <= 0.0:
            failures.append(
                f"table2.zipfian: shard-probe hit rate "
                f"{zipf.get('shard_hit_rate', 0.0):.3f} is not > 0 — Stage-A "
                "fragments were always recomputed"
            )
        if zipf.get("warm_p50_ms", float("inf")) >= zipf.get("cold_p50_ms", 0.0):
            failures.append(
                f"table2.zipfian: warm p50 {zipf.get('warm_p50_ms', 0.0):.2f} ms "
                f"is not below cold p50 {zipf.get('cold_p50_ms', 0.0):.2f} ms in "
                "the same interleaved window — the caches bought nothing"
            )
        if zipf.get("recall", 0.0) < FILTERED_MIN_RECALL:
            failures.append(
                f"table2.zipfian: recall vs oracle {zipf.get('recall', 0.0):.3f} "
                f"< {FILTERED_MIN_RECALL} — cached answers are degrading results"
            )
        if not zipf.get("parity_ok", False):
            failures.append(
                "table2.zipfian: cached probes diverged from the cache-off "
                "path — the cache changed results, not just latency"
            )
        if zipf.get("invalidations", 0) <= 0:
            failures.append(
                "table2.zipfian: the post-refresh probe saw zero cache "
                "invalidations — the snapshot commit is not reaching the caches"
            )
        if zipf.get("stale_hits", -1) != 0:
            failures.append(
                f"table2.zipfian: {zipf.get('stale_hits', -1)} stale answers "
                "served after the refresh commit — snapshot invalidation broke"
            )

    gather = rows.get("kernel.gather_rerank")
    if gather is not None:
        if gather.get("speedup_vs_host", 0.0) <= 1.0:
            failures.append(
                f"kernel.gather_rerank: device pool rerank "
                f"(speedup_vs_host {gather.get('speedup_vs_host', 0.0):.2f}x) is "
                "not faster than the removed NumPy host rerank it replaced "
                "(same-window paired timing)"
            )
    unified_row = rows.get("kernel.unified_masked_topk")
    if unified_row is not None and not unified_row.get("parity_ok", True):
        failures.append(
            "kernel.unified_masked_topk: fused-dispatch hits diverge from the "
            "split-flavor exact+ADC dispatches — the unified kernel changed "
            "results, not just dispatch count"
        )
    for name in QUANT_ROWS:
        qrow = rows.get(name)
        if qrow is None:
            continue
        if qrow.get("recall_post_guard", 0.0) < QUANT_MIN_RECALL:
            failures.append(
                f"{name}: post-guard recall "
                f"{qrow.get('recall_post_guard', 0.0):.3f} < {QUANT_MIN_RECALL} "
                "— the full-precision gather-rerank guard is not restoring "
                "the quantized scan's recall"
            )
        speed = qrow.get("speedup_vs_f32", 0.0)
        if qrow.get("quantized_native", False):
            if speed <= 1.0:
                failures.append(
                    f"{name}: native quantized scan (speedup_vs_f32 "
                    f"{speed:.2f}x) is not faster than the f32 scan"
                )
        elif speed < QUANT_NON_NATIVE_SPEED_FLOOR:
            failures.append(
                f"{name}: non-native quantized scan (speedup_vs_f32 "
                f"{speed:.2f}x) fell below the "
                f"{QUANT_NON_NATIVE_SPEED_FLOOR}x plumbing floor"
            )

    # baseline drift, both directions: a baseline row no bench emits anymore
    # silently keeps gating thin air, and a bench row missing from the
    # baseline silently exempts itself from every baseline-relative gate
    for name in sorted(base_rows):
        if name not in rows:
            failures.append(
                f"{name}: present in the baseline but missing from the current "
                "run — its gates would silently vanish"
            )
    if base_rows:
        for name in sorted(rows):
            if name not in base_rows:
                failures.append(
                    f"{name}: emitted by the bench but missing from the "
                    "committed baseline — regenerate the baseline alongside "
                    "the change that added this row"
                )
    # machine factor: median throughput ratio over rows present in both.
    # When the document carries ``anchor.*`` rows (fixed pure-numpy work no
    # repo change can touch — bench_kernels writes one), the factor comes
    # from the anchors ALONE: otherwise a uniform real regression across
    # every gated row would read as "slower machine" and pass (the
    # query-paths file needs no anchor — its ungated beam rows already
    # anchor the median).
    all_ratios = {
        name: rows[name]["throughput_qps"] / base_rows[name]["throughput_qps"]
        for name in rows
        if name in base_rows
        and rows[name].get("throughput_qps") is not None
        and base_rows[name].get("throughput_qps")
    }
    anchor_ratios = [r for n, r in all_ratios.items() if n.startswith("anchor.")]
    ratios = sorted(anchor_ratios if anchor_ratios else all_ratios.values())
    factor = 1.0
    if ratios:
        mid = len(ratios) // 2
        factor = (
            ratios[mid] if len(ratios) % 2 else (ratios[mid - 1] + ratios[mid]) / 2.0
        )
    for name in sorted(rows):
        cur, base = rows[name], base_rows.get(name)
        if base is None:
            continue
        cur_qps, base_qps = cur.get("throughput_qps"), base.get("throughput_qps")
        if _throughput_gated(name) and cur_qps is not None and base_qps:
            budget = _regress_budget(name, max_regress)
            floor = (1.0 - budget) * base_qps * factor
            if cur_qps < floor:
                failures.append(
                    f"{name}: throughput {cur_qps:.1f} qps regressed "
                    f">{budget:.0%} below baseline {base_qps:.1f} qps "
                    f"(machine factor {factor:.2f} applied)"
                )
        cur_rec, base_rec = cur.get("recall"), base.get("recall")
        if cur_rec is not None and base_rec is not None:
            if cur_rec < base_rec - RECALL_EPS:
                failures.append(
                    f"{name}: recall {cur_rec:.4f} dropped below baseline "
                    f"{base_rec:.4f}"
                )
    return failures


def _load(path: str) -> dict:
    if os.path.exists(path) and os.path.getsize(path) == 0:
        raise ValueError("file is empty — the bench run crashed before writing it?")
    with open(path) as f:
        return json.load(f)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "current", nargs="+",
        help="JSON record(s) written by the benchmark run(s)",
    )
    ap.add_argument(
        "--baseline",
        action="append",
        default=None,
        help="committed baseline for the current file at the same position "
        "(repeatable; '' skips that file's baseline gates).  Default: "
        f"{DEFAULT_BASELINE_DIR}/<basename of the current file>",
    )
    ap.add_argument(
        "--max-regress", type=float, default=DEFAULT_MAX_REGRESS,
        help="tolerated fractional throughput drop vs baseline (default 0.20)",
    )
    args = ap.parse_args(argv)
    baselines = args.baseline
    if baselines is None:
        baselines = [
            os.path.join(DEFAULT_BASELINE_DIR, os.path.basename(p))
            for p in args.current
        ]
    if len(baselines) != len(args.current):
        print(
            f"check_bench: {len(args.current)} bench file(s) but "
            f"{len(baselines)} --baseline flag(s) — pass one per file "
            "('' to skip a file's baseline gates)",
            file=sys.stderr,
        )
        return 2
    failures: List[str] = []
    total_rows = 0
    base_notes: List[str] = []
    for cur_path, base_path in zip(args.current, baselines):
        try:
            current = _load(cur_path)
        except (OSError, ValueError) as e:
            print(f"check_bench: cannot read {cur_path}: {e}", file=sys.stderr)
            return 2
        if not current.get("rows"):
            # a crashed bench that still wrote an empty shell (or a stale
            # truncated file) must not green-light itself
            print(
                f"check_bench: {cur_path} contains no benchmark rows — the "
                "bench run did not complete",
                file=sys.stderr,
            )
            return 2
        baseline = None
        if base_path:
            try:
                baseline = _load(base_path)
            except (OSError, ValueError) as e:
                print(f"check_bench: cannot read baseline {base_path}: {e}",
                      file=sys.stderr)
                return 2
        failures.extend(check(current, baseline, max_regress=args.max_regress))
        total_rows += len(current.get("rows", {}))
        base_notes.append(base_path if baseline is not None else "(none)")
    base_note = ", ".join(base_notes)
    if failures:
        for f_msg in failures:
            print(f"BENCH-REGRESSION: {f_msg}")
        print(f"check_bench: {len(failures)} regression(s) across {total_rows} rows "
              f"(baseline: {base_note})")
        return 1
    print(f"check_bench: OK — {total_rows} rows within gates (baseline: {base_note})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
