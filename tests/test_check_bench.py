"""scripts/check_bench.py: the CI benchmark-regression gate.

The acceptance contract: the gate must demonstrably FAIL on an injected
regression (doctored JSON) and pass on a clean run — both through the pure
``check()`` function and the CLI entry point's exit codes.
"""

import copy
import importlib.util
import json
import pathlib

import pytest

_SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "scripts" / "check_bench.py"
_spec = importlib.util.spec_from_file_location("check_bench", _SCRIPT)
check_bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_bench)


def _clean_doc():
    return {
        "meta": {"bench": "bench_query_paths", "tiny": True},
        "rows": {
            "table2.scan": {"throughput_qps": 25.0, "recall": 1.0},
            "table2.diskann": {"throughput_qps": 5.2, "recall": 0.96},
            "table2.batched": {
                "throughput_qps": 130.0,
                "seq_qps": 19.0,
                "speedup": 6.8,
                "recall": 0.96,
                "parity_ok": True,
                "probe_fragments": 2,
            },
            "table2.filtered": {
                "throughput_qps": 60.0,
                "recall": 1.0,
                "shards_pruned": 1,
                "probe_fragments": 1,
                "unfiltered_fragments": 2,
                "oracle_qps": 40.0,
                "speedup_vs_oracle": 1.5,
            },
            "table2.filtered_hetero": {
                "throughput_qps": 45.0,
                "seq_qps": 20.0,
                "speedup_vs_seq": 2.25,
                "recall": 1.0,
                "kernel_dispatches": 2,
                "seq_dispatches": 16,
                "distinct_filters": 8,
                "parity_ok": True,
            },
            "table2.filtered_mixed_flavor": {
                "throughput_qps": 40.0,
                "recall": 1.0,
                "kernel_dispatches": 2,
                "probe_fragments": 2,
                "distinct_filters": 8,
            },
            "table2.filtered_lowsel_bigshard": {
                "throughput_qps": 30.0,
                "postfilter_qps": 12.0,
                "speedup_vs_postfilter": 2.5,
                "recall": 1.0,
                "est_selectivity": 0.15,
                "shard_rows": 5000,
                "exact_scan_cap": 4096,
                "batch_queries": 8,
                "masked_beam_rows": 8,
                "masked_beam_fallbacks": 1,
                "postfilter_dispatches": 1,
                "kernel_dispatches": 1,
                "probe_fragments": 1,
                "plan_mbeam": True,
            },
            "table2.freshness": {
                "throughput_qps": 70.0,
                "recall": 0.98,
                "recall_without_tail": 0.48,
                "tail_rows": 128,
                "tail_row_groups": 1,
                "tail_plan_ops": 1,
                "unindexed_rows": 0,
                "stale": True,
                "oracle_qps": 350.0,
            },
            "table2.overload": {
                "throughput_qps": 50.0,
                "capacity_qps": 70.0,
                "offered_qps": 140.0,
                "overload_factor": 2.0,
                "well_hit_rate": 1.0,
                "well_attempts": 40,
                "well_served": 40,
                "well_rejected": 0,
                "abusive_attempts": 230,
                "abusive_admitted": 40,
                "abusive_rejected": 190,
                "deadline_misses": 0,
                "degraded_batches": 0,
                "queue_bounded": True,
            },
            "table2.zipfian": {
                "throughput_qps": 20000.0,
                "recall": 0.98,
                "zipf_s": 1.1,
                "pool_size": 16,
                "stream_len": 96,
                "semantic_hits": 72,
                "semantic_misses": 24,
                "semantic_hit_rate": 0.75,
                "shard_hits": 18,
                "shard_lookups": 40,
                "shard_hit_rate": 0.45,
                "warm_p50_ms": 0.1,
                "warm_p99_ms": 5.0,
                "cold_p50_ms": 120.0,
                "cold_p99_ms": 200.0,
                "parity_ok": True,
                "replay_cache_hits": 12,
                "invalidations": 54,
                "stale_hits": 0,
            },
        },
    }


def _kernels_doc():
    return {
        "meta": {"bench": "bench_kernels"},
        "rows": {
            "kernel.rerank": {"throughput_qps": 22.0},
            "kernel.masked_exact_topk": {"throughput_qps": 45.0},
            "kernel.masked_exact_topk_multi": {"throughput_qps": 65.0},
            "kernel.masked_pq_topk_multi": {"throughput_qps": 5.0},
            "kernel.unified_masked_topk": {"throughput_qps": 12.0, "parity_ok": True},
            "kernel.gather_rerank": {
                "throughput_qps": 900.0,
                "host_qps": 420.0,
                "speedup_vs_host": 2.1,
            },
            "host.gather_rerank": {"throughput_qps": 420.0},
            "kernel.masked_exact_topk_bf16": {
                "throughput_qps": 40.0,
                "speedup_vs_f32": 0.7,
                "recall_raw": 0.97,
                "recall_post_guard": 1.0,
                "quantized_native": False,
            },
            "kernel.masked_exact_topk_int8": {
                "throughput_qps": 38.0,
                "speedup_vs_f32": 0.65,
                "recall_raw": 0.90,
                "recall_post_guard": 1.0,
                "quantized_native": False,
            },
            "anchor.numpy_matmul": {"throughput_qps": 300.0},
        },
    }


def test_clean_run_passes():
    doc = _clean_doc()
    assert check_bench.check(doc, copy.deepcopy(doc)) == []
    assert check_bench.check(doc, None) == []  # no baseline: absolute gates only


def test_throughput_regression_fails():
    """Wall-clock baseline gating lives on the kernel rows: a single
    kernel row dropping past its budget fails."""
    base = _kernels_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["kernel.rerank"]["throughput_qps"] *= 0.5  # −50% > 35% budget
    failures = check_bench.check(cur, base)
    assert len(failures) == 1 and "kernel.rerank" in failures[0]
    assert "throughput" in failures[0]


def test_throughput_within_budget_passes():
    base = _kernels_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["kernel.rerank"]["throughput_qps"] *= 0.75  # −25% < 35%
    assert check_bench.check(cur, base) == []


def test_table2_rows_are_not_wall_clock_gated():
    """Every table2 row rides the scheduler, so its wall clock never
    gates against the baseline — only its same-window ratios and recall
    do.  A filtered-row throughput drop (and a sub-1 oracle ratio, normal
    at tiny scale) passes; its recall dropping fails."""
    base = _clean_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["table2.filtered"]["throughput_qps"] *= 0.4
    cur["rows"]["table2.filtered"]["speedup_vs_oracle"] = 0.8
    assert check_bench.check(cur, base) == []
    cur["rows"]["table2.filtered"]["recall"] = 0.96
    failures = check_bench.check(cur, base)
    assert any("table2.filtered" in f and "recall" in f for f in failures)


def test_ungated_row_throughput_is_informational_but_recall_is_not():
    """Beam-search-driven rows (the table rows and the batched row) are too
    timing-noisy to gate on wall clock — but their recall is deterministic
    and stays gated, and batched keeps its speedup gate."""
    base = _clean_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["table2.diskann"]["throughput_qps"] = 5.2 * 0.3  # huge, ignored
    cur["rows"]["table2.batched"]["throughput_qps"] = 130.0 * 0.3  # ignored too
    assert check_bench.check(cur, base) == []
    cur["rows"]["table2.diskann"]["recall"] = 0.90
    failures = check_bench.check(cur, base)
    assert any("table2.diskann" in f and "recall" in f for f in failures)


def test_baseline_row_missing_from_current_fails():
    """A row silently dropped from the bench output must fail the gate —
    otherwise deleting/renaming a row un-gates it."""
    base = _clean_doc()
    cur = copy.deepcopy(base)
    del cur["rows"]["table2.filtered"]
    failures = check_bench.check(cur, base)
    assert any("table2.filtered" in f and "missing" in f for f in failures)


def test_uniform_machine_slowdown_passes():
    """Every row slower by the same factor = a slower machine, not a
    regression: the median-ratio normalization must absorb it."""
    base = _kernels_doc()
    cur = copy.deepcopy(base)
    for row in cur["rows"].values():
        if "throughput_qps" in row:
            row["throughput_qps"] *= 0.4  # 2.5x slower across the board
    assert check_bench.check(cur, base) == []


def test_single_row_regression_sticks_out_of_machine_factor():
    """One row regressing on an otherwise-identical machine is caught even
    though the anchor-pinned factor stays ~1."""
    base = _kernels_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["kernel.masked_pq_topk_multi"]["throughput_qps"] *= 0.5
    failures = check_bench.check(cur, base)
    assert any(
        "kernel.masked_pq_topk_multi" in f and "machine factor" in f
        for f in failures
    )


def test_any_recall_drop_fails():
    base = _clean_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["table2.filtered"]["recall"] = 0.999  # tiny, still a drop
    failures = check_bench.check(cur, base)
    assert any("table2.filtered" in f and "recall" in f for f in failures)


def test_absolute_gates_without_baseline():
    cur = _clean_doc()
    cur["rows"]["table2.filtered"]["recall"] = 0.80  # below the 0.95 floor
    cur["rows"]["table2.batched"]["speedup"] = 0.9
    cur["rows"]["table2.batched"]["parity_ok"] = False
    failures = check_bench.check(cur, None)
    assert any("recall vs oracle" in f for f in failures)
    assert any("not above the sequential" in f for f in failures)
    assert any("diverge" in f for f in failures)


def test_zone_prune_gate():
    cur = _clean_doc()
    cur["rows"]["table2.filtered"]["shards_pruned"] = 0
    cur["rows"]["table2.filtered"]["probe_fragments"] = 2  # == unfiltered
    failures = check_bench.check(cur, None)
    assert any("zone-map pruning" in f for f in failures)


def test_current_row_missing_from_baseline_fails():
    """Drift check, forward direction: a row the bench emits that the
    committed baseline lacks means the baseline is stale — the new row
    would otherwise silently exempt itself from every baseline-relative
    gate.  (Absolute gates still run on it; but the drift failure is what
    forces the baseline regeneration alongside the change.)"""
    base = _clean_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["table2.new_path"] = {"throughput_qps": 100.0, "recall": 1.0}
    failures = check_bench.check(cur, base)
    assert any(
        "table2.new_path" in f and "missing from the committed baseline" in f
        for f in failures
    )
    # without a baseline there is nothing to drift from: absolute-only runs
    # (check_bench <file> --baseline '') must not fail on row presence
    assert check_bench.check(cur, None) == []


def test_zipfian_vacuous_stream_fails():
    """Guard: a stream no longer than the pool never repeats a query, so
    every hit-rate and parity number is vacuous."""
    cur = _clean_doc()
    cur["rows"]["table2.zipfian"]["stream_len"] = 16  # == pool_size
    failures = check_bench.check(cur, None)
    assert any("table2.zipfian" in f and "never repeats" in f for f in failures)


def test_zipfian_vacuous_parity_pass_fails():
    """Guard: parity_ok proves nothing if the replay pass took zero
    shard-cache hits — it compared the uncached path with itself."""
    cur = _clean_doc()
    cur["rows"]["table2.zipfian"]["replay_cache_hits"] = 0
    failures = check_bench.check(cur, None)
    assert any(
        "table2.zipfian" in f and "uncached path with itself" in f
        for f in failures
    )


def test_zipfian_zero_semantic_hit_rate_fails():
    cur = _clean_doc()
    cur["rows"]["table2.zipfian"]["semantic_hit_rate"] = 0.0
    failures = check_bench.check(cur, None)
    assert any(
        "table2.zipfian" in f and "result cache never answered" in f
        for f in failures
    )


def test_zipfian_zero_shard_hit_rate_fails():
    cur = _clean_doc()
    cur["rows"]["table2.zipfian"]["shard_hit_rate"] = 0.0
    failures = check_bench.check(cur, None)
    assert any(
        "table2.zipfian" in f and "always recomputed" in f for f in failures
    )


def test_zipfian_warm_not_faster_than_cold_fails():
    cur = _clean_doc()
    cur["rows"]["table2.zipfian"]["warm_p50_ms"] = 150.0  # >= cold 120.0
    failures = check_bench.check(cur, None)
    assert any(
        "table2.zipfian" in f and "caches bought nothing" in f for f in failures
    )


def test_zipfian_recall_floor_fails():
    cur = _clean_doc()
    cur["rows"]["table2.zipfian"]["recall"] = 0.90
    failures = check_bench.check(cur, None)
    assert any("table2.zipfian" in f and "recall" in f for f in failures)


def test_zipfian_parity_break_fails():
    cur = _clean_doc()
    cur["rows"]["table2.zipfian"]["parity_ok"] = False
    failures = check_bench.check(cur, None)
    assert any(
        "table2.zipfian" in f and "changed results" in f for f in failures
    )


def test_zipfian_zero_invalidations_fails():
    cur = _clean_doc()
    cur["rows"]["table2.zipfian"]["invalidations"] = 0
    failures = check_bench.check(cur, None)
    assert any(
        "table2.zipfian" in f and "not reaching the caches" in f
        for f in failures
    )


def test_zipfian_stale_hits_fail():
    """Any stale answer after the refresh commit fails — and so does a
    bench that forgot to record the field at all (default -1)."""
    cur = _clean_doc()
    cur["rows"]["table2.zipfian"]["stale_hits"] = 2
    failures = check_bench.check(cur, None)
    assert any("table2.zipfian" in f and "stale" in f for f in failures)
    del cur["rows"]["table2.zipfian"]["stale_hits"]
    failures = check_bench.check(cur, None)
    assert any("table2.zipfian" in f and "stale" in f for f in failures)


def test_zipfian_never_wall_clock_gated():
    """The zipfian row rides the scheduler like every table2 row: its
    absolute qps dropping vs the baseline must not gate."""
    base = _clean_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["table2.zipfian"]["throughput_qps"] *= 0.2
    assert check_bench.check(cur, base) == []


@pytest.mark.parametrize(
    "doctor,expected_exit",
    [
        (lambda rows: None, 0),  # untouched => clean
        (lambda rows: rows["table2.filtered"].__setitem__("recall", 0.5), 1),
        (lambda rows: rows["table2.batched"].__setitem__("recall", 0.5), 1),
    ],
)
def test_cli_exit_codes(tmp_path, capsys, doctor, expected_exit):
    base = _clean_doc()
    cur = copy.deepcopy(base)
    doctor(cur["rows"])
    cur_p, base_p = tmp_path / "cur.json", tmp_path / "base.json"
    cur_p.write_text(json.dumps(cur))
    base_p.write_text(json.dumps(base))
    rc = check_bench.main([str(cur_p), "--baseline", str(base_p)])
    out = capsys.readouterr().out
    assert rc == expected_exit
    if expected_exit:
        assert "BENCH-REGRESSION:" in out
    else:
        assert "OK" in out


def test_cli_unreadable_input(tmp_path):
    missing = tmp_path / "nope.json"
    assert check_bench.main([str(missing), "--baseline", ""]) == 2
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert check_bench.main([str(bad), "--baseline", ""]) == 2


def test_cli_empty_or_rowless_input_is_an_error(tmp_path, capsys):
    """A bench that crashed before writing its record must FAIL the gate,
    not pass vacuously: an empty file and a row-less document are both
    invocation errors (exit 2), never exit 0."""
    empty = tmp_path / "empty.json"
    empty.write_text("")
    assert check_bench.main([str(empty), "--baseline", ""]) == 2
    assert "empty" in capsys.readouterr().err
    rowless = tmp_path / "rowless.json"
    rowless.write_text(json.dumps({"meta": {"bench": "x"}, "rows": {}}))
    assert check_bench.main([str(rowless), "--baseline", ""]) == 2
    assert "no benchmark rows" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# heterogeneous-filter row gates
# ---------------------------------------------------------------------------


def test_hetero_absolute_gates():
    """The mask-plane acceptance gates: worse-or-equal dispatch count than
    the one-query probes, speedup <= 1, parity breakage, and recall below
    the floor each fail without any baseline."""
    cur = _clean_doc()
    h = cur["rows"]["table2.filtered_hetero"]
    h["kernel_dispatches"] = 16  # == one-query probes: coalescing win lost
    h["speedup_vs_seq"] = 0.8
    h["parity_ok"] = False
    h["recall"] = 0.90
    failures = check_bench.check(cur, None)
    assert any("no fewer kernel dispatches" in f for f in failures)
    assert any("not above the one-query probes" in f for f in failures)
    assert any("diverge from the one-query probes" in f for f in failures)
    assert any("table2.filtered_hetero" in f and "recall vs oracle" in f for f in failures)


def test_hetero_clean_row_passes():
    doc = _clean_doc()
    assert check_bench.check(doc, copy.deepcopy(doc)) == []


def test_mixed_flavor_absolute_gates():
    """The unified-kernel acceptance gates: more than one dispatch per
    shard and recall below the floor each fail without any baseline."""
    cur = _clean_doc()
    m = cur["rows"]["table2.filtered_mixed_flavor"]
    m["kernel_dispatches"] = 4  # two dispatches per shard again
    m["recall"] = 0.90
    failures = check_bench.check(cur, None)
    assert any("exactly one kernel dispatch per shard" in f for f in failures)
    assert any(
        "table2.filtered_mixed_flavor" in f and "recall vs oracle" in f
        for f in failures
    )


def test_mixed_flavor_one_dispatch_gate_is_exact():
    """kernel_dispatches must EQUAL probe_fragments: even fewer dispatches
    than fragments (a shard silently skipped) fails the gate."""
    cur = _clean_doc()
    cur["rows"]["table2.filtered_mixed_flavor"]["kernel_dispatches"] = 1
    failures = check_bench.check(cur, None)
    assert any("exactly one kernel dispatch per shard" in f for f in failures)


def test_hetero_gates_on_speedup_ratio_not_wall_clock():
    """filtered_hetero spans two scheduler waves, so its wall clock is as
    load-sensitive as the batched row's: a throughput drop alone must NOT
    fail (it is not baseline-throughput-gated), but the same-window
    speedup_vs_seq ratio falling to 1 must."""
    base = _clean_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["table2.filtered_hetero"]["throughput_qps"] *= 0.4
    cur["rows"]["table2.filtered_hetero"]["seq_qps"] *= 0.4  # same window
    assert check_bench.check(cur, base) == []
    cur["rows"]["table2.filtered_hetero"]["speedup_vs_seq"] = 0.97
    failures = check_bench.check(cur, base)
    assert any(
        "table2.filtered_hetero" in f and "not above the one-query probes" in f
        for f in failures
    )


# ---------------------------------------------------------------------------
# low-selectivity big-shard row gates (the MaskedBeam traversal)
# ---------------------------------------------------------------------------


def test_bigshard_absolute_gates():
    """The MaskedBeam acceptance gates: losing the paired timing to the
    replayed postfilter plan, recall below the floor, and dispatches beyond
    one fused fallback per fragment each fail without any baseline."""
    cur = _clean_doc()
    b = cur["rows"]["table2.filtered_lowsel_bigshard"]
    b["speedup_vs_postfilter"] = 0.8
    b["recall"] = 0.90
    b["kernel_dispatches"] = 3  # > probe_fragments: traversal leaked dispatches
    failures = check_bench.check(cur, None)
    assert any("not above" in f and "postfilter" in f for f in failures)
    assert any(
        "table2.filtered_lowsel_bigshard" in f and "recall vs oracle" in f
        for f in failures
    )
    assert any("ONE fused fallback per fragment" in f for f in failures)


def test_bigshard_gate_requires_a_big_shard():
    """A run whose shard shrank below the masked-scan cap (or whose rows
    never took the traversal) gates nothing — it must fail rather than
    pass vacuously."""
    cur = _clean_doc()
    cur["rows"]["table2.filtered_lowsel_bigshard"]["shard_rows"] = 2000
    failures = check_bench.check(cur, None)
    assert any("not above the masked-scan cap" in f for f in failures)
    cur = _clean_doc()
    cur["rows"]["table2.filtered_lowsel_bigshard"]["masked_beam_rows"] = 2
    failures = check_bench.check(cur, None)
    assert any("took the MaskedBeam traversal" in f for f in failures)
    cur = _clean_doc()
    cur["rows"]["table2.filtered_lowsel_bigshard"]["plan_mbeam"] = False
    failures = check_bench.check(cur, None)
    assert any("took the MaskedBeam traversal" in f for f in failures)


def test_bigshard_gate_rejects_all_fallback_runs():
    """If EVERY traversal row under-delivered into the exact fallback, the
    paired timing compares the fallback with itself — fail loudly."""
    cur = _clean_doc()
    cur["rows"]["table2.filtered_lowsel_bigshard"]["masked_beam_fallbacks"] = 8
    failures = check_bench.check(cur, None)
    assert any("fallback path with itself" in f for f in failures)


def test_bigshard_is_not_wall_clock_gated_but_recall_is():
    """Like every table2 row: wall clock is informational, recall and the
    same-window ratio gate."""
    base = _clean_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["table2.filtered_lowsel_bigshard"]["throughput_qps"] *= 0.3
    cur["rows"]["table2.filtered_lowsel_bigshard"]["postfilter_qps"] *= 0.3
    assert check_bench.check(cur, base) == []
    cur["rows"]["table2.filtered_lowsel_bigshard"]["recall"] = 0.97
    failures = check_bench.check(cur, base)
    assert any(
        "table2.filtered_lowsel_bigshard" in f and "recall" in f
        for f in failures
    )


def test_bigshard_cli_doctored_json(tmp_path):
    base = _clean_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["table2.filtered_lowsel_bigshard"]["speedup_vs_postfilter"] = 0.5
    cur_p, base_p = tmp_path / "cur.json", tmp_path / "base.json"
    cur_p.write_text(json.dumps(cur))
    base_p.write_text(json.dumps(base))
    assert check_bench.main([str(cur_p), "--baseline", str(base_p)]) == 1


# ---------------------------------------------------------------------------
# freshness row gates (the fresh-tail tier's stale-read window)
# ---------------------------------------------------------------------------


def test_freshness_absolute_gates():
    """The stale-read acceptance gates: recall below the floor with a tail
    present, silently-dropped unindexed rows, and a plan that does not
    carry one op per tail row group each fail without any baseline."""
    cur = _clean_doc()
    f = cur["rows"]["table2.freshness"]
    f["recall"] = 0.48  # the pre-fix silent-drop recall
    f["unindexed_rows"] = 128
    f["tail_plan_ops"] = 0
    failures = check_bench.check(cur, None)
    assert any(
        "table2.freshness" in x and "recall vs the fresh scan oracle" in x
        for x in failures
    )
    assert any("silently dropped" in x for x in failures)
    assert any("one-ExactScan-per-tail-row-group" in x for x in failures)


def test_freshness_gate_requires_a_tail():
    """A freshness row measured with no unindexed tail present gates
    nothing — the run must fail rather than pass vacuously."""
    cur = _clean_doc()
    cur["rows"]["table2.freshness"]["tail_rows"] = 0
    failures = check_bench.check(cur, None)
    assert any("exercised nothing" in x for x in failures)
    cur = _clean_doc()
    cur["rows"]["table2.freshness"]["stale"] = False
    failures = check_bench.check(cur, None)
    assert any("exercised nothing" in x for x in failures)


def test_freshness_recall_drop_vs_baseline_fails():
    base = _clean_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["table2.freshness"]["recall"] = 0.97  # above floor, below base
    failures = check_bench.check(cur, base)
    assert any("table2.freshness" in x and "recall" in x for x in failures)


def test_freshness_cli_doctored_json(tmp_path):
    base = _clean_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["table2.freshness"]["unindexed_rows"] = 64
    cur_p, base_p = tmp_path / "cur.json", tmp_path / "base.json"
    cur_p.write_text(json.dumps(cur))
    base_p.write_text(json.dumps(base))
    assert check_bench.main([str(cur_p), "--baseline", str(base_p)]) == 1


# ---------------------------------------------------------------------------
# kernel-bench file (multi-file gating)
# ---------------------------------------------------------------------------


def test_kernel_rows_are_throughput_gated():
    """Every kernel.* row is throughput-gated (prefix rule), with the same
    median-ratio machine-factor normalization — including the multi-mask
    rows."""
    base = _kernels_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["kernel.masked_exact_topk_multi"]["throughput_qps"] *= 0.5
    failures = check_bench.check(cur, base)
    assert len(failures) == 1
    assert "kernel.masked_exact_topk_multi" in failures[0]
    assert "machine factor" in failures[0]
    # a uniform slowdown (slower CI runner) is absorbed by the factor —
    # the anchor row slows down with everything else
    uniform = copy.deepcopy(base)
    for row in uniform["rows"].values():
        row["throughput_qps"] *= 0.3
    assert check_bench.check(uniform, base) == []


def test_anchor_row_pins_the_machine_factor():
    """A uniform regression of EVERY kernel row would read as a slower
    machine under an all-rows median — the pure-numpy anchor row (which no
    repo change can slow down) pins the factor, so it is caught."""
    base = _kernels_doc()
    cur = copy.deepcopy(base)
    for name, row in cur["rows"].items():
        if name.startswith("kernel."):
            row["throughput_qps"] *= 0.3  # anchor stays at baseline speed
    failures = check_bench.check(cur, base)
    gated = [n for n in base["rows"] if n.startswith("kernel.")]
    assert len(failures) == len(gated)
    assert all("machine factor 1.00" in f for f in failures)


def test_kernel_rows_use_wider_noise_budget():
    """Eager-matmul timing floats ±20% on shared runners even after the
    interleaved best-of measurement, so kernel rows gate at
    KERNEL_MAX_REGRESS (35%) instead of the default 20%: a −30% drop
    passes, a −50% drop fails (see test_throughput_regression_fails)."""
    base = _kernels_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["kernel.rerank"]["throughput_qps"] *= 0.70
    assert check_bench.check(cur, base) == []


def test_cli_multiple_bench_files(tmp_path):
    """One invocation gates several bench records, each against its own
    baseline; a regression in ANY file fails the run."""
    qp_base, qp_cur = _clean_doc(), _clean_doc()
    k_base, k_cur = _kernels_doc(), _kernels_doc()
    k_cur["rows"]["kernel.masked_pq_topk_multi"]["throughput_qps"] *= 0.4
    paths = {}
    for name, doc in [
        ("qp_cur", qp_cur), ("qp_base", qp_base), ("k_cur", k_cur), ("k_base", k_base)
    ]:
        p = tmp_path / f"{name}.json"
        p.write_text(json.dumps(doc))
        paths[name] = str(p)
    rc = check_bench.main([
        paths["qp_cur"], paths["k_cur"],
        "--baseline", paths["qp_base"], "--baseline", paths["k_base"],
    ])
    assert rc == 1
    # clean kernels file: whole invocation passes
    pathlib.Path(paths["k_cur"]).write_text(json.dumps(_kernels_doc()))
    rc = check_bench.main([
        paths["qp_cur"], paths["k_cur"],
        "--baseline", paths["qp_base"], "--baseline", paths["k_base"],
    ])
    assert rc == 0


def test_cli_mismatched_baseline_count(tmp_path):
    p = tmp_path / "cur.json"
    p.write_text(json.dumps(_clean_doc()))
    rc = check_bench.main([str(p), str(p), "--baseline", ""])
    assert rc == 2


# ---------------------------------------------------------------------------
# overload row (multi-tenant serving tier)
# ---------------------------------------------------------------------------


def test_overload_absolute_gates():
    """The serving-tier acceptance gates: a well-behaved tenant starved
    below a 0.9 deadline hit-rate, rejections landing on the wrong tenant,
    and an unbounded queue each fail without any baseline."""
    cur = _clean_doc()
    o = cur["rows"]["table2.overload"]
    o["well_hit_rate"] = 0.6
    o["well_rejected"] = 200
    o["abusive_rejected"] = 5
    o["queue_bounded"] = False
    failures = check_bench.check(cur, None)
    assert any(
        "table2.overload" in x and "hit-rate" in x for x in failures
    )
    assert any("wrong tenant is paying" in x for x in failures)
    assert any("backpressure is not holding" in x for x in failures)


def test_overload_gate_requires_actual_overload():
    """An overload row measured UNDER capacity gates nothing — the run
    must fail rather than pass vacuously."""
    cur = _clean_doc()
    cur["rows"]["table2.overload"]["overload_factor"] = 1.1
    failures = check_bench.check(cur, None)
    assert any("did not actually overload" in x for x in failures)


def test_overload_clean_row_passes_and_is_not_wall_clock_gated():
    """A clean overload row passes, and its throughput is informational:
    the row rides the scheduler, so wall clock never gates it even
    against a much faster baseline."""
    base = _clean_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["table2.overload"]["throughput_qps"] = 5.0  # 10x slower
    assert check_bench.check(cur, base) == []


def test_overload_cli_doctored_json(tmp_path):
    base = _clean_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["table2.overload"]["well_hit_rate"] = 0.2
    cur_p, base_p = tmp_path / "cur.json", tmp_path / "base.json"
    cur_p.write_text(json.dumps(cur))
    base_p.write_text(json.dumps(base))
    assert check_bench.main([str(cur_p), "--baseline", str(base_p)]) == 1


# ---------------------------------------------------------------------------
# gather-rerank / quantized-scan / unified-parity gates (the kernel hot path)
# ---------------------------------------------------------------------------


def test_gather_rerank_speedup_gate():
    """The device pool rerank replaced the executor's NumPy host rerank; if
    its same-window paired timing ever loses to that comparator, the
    replacement regressed and the gate must fail — with or without a
    baseline."""
    cur = _kernels_doc()
    cur["rows"]["kernel.gather_rerank"]["speedup_vs_host"] = 0.9
    failures = check_bench.check(cur, None)
    assert any(
        "kernel.gather_rerank" in f and "host rerank" in f for f in failures
    )


def test_host_comparator_row_is_not_throughput_gated():
    """host.gather_rerank exists only as the same-window comparator for the
    paired ratio; its absolute wall clock dropping must not gate (the ratio
    is what gates)."""
    base = _kernels_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["host.gather_rerank"]["throughput_qps"] *= 0.2
    assert check_bench.check(cur, base) == []


def test_unified_kernel_parity_gate():
    """The single-dispatch unified kernel must return bit-identical hits to
    the split exact+ADC dispatches — a dispatch-count win that changes
    results is a correctness bug, not an optimization."""
    cur = _kernels_doc()
    cur["rows"]["kernel.unified_masked_topk"]["parity_ok"] = False
    failures = check_bench.check(cur, None)
    assert any(
        "kernel.unified_masked_topk" in f and "changed results" in f
        for f in failures
    )


def test_quantized_post_guard_recall_gate():
    """Reduced-precision scanning is only admissible because the
    full-precision gather-rerank guard restores recall: post-guard recall
    below the floor fails for each quantized flavor independently — and so
    does a bench that forgot to record the field (default 0.0)."""
    cur = _kernels_doc()
    cur["rows"]["kernel.masked_exact_topk_bf16"]["recall_post_guard"] = 0.90
    del cur["rows"]["kernel.masked_exact_topk_int8"]["recall_post_guard"]
    failures = check_bench.check(cur, None)
    assert any(
        "kernel.masked_exact_topk_bf16" in f and "guard" in f for f in failures
    )
    assert any(
        "kernel.masked_exact_topk_int8" in f and "guard" in f for f in failures
    )


def test_quantized_raw_recall_is_informational():
    """recall_raw (before the guard) is expected to dip — that is the whole
    reason the guard exists — so it must never gate on its own."""
    cur = _kernels_doc()
    cur["rows"]["kernel.masked_exact_topk_int8"]["recall_raw"] = 0.50
    assert check_bench.check(cur, None) == []


def test_quantized_speed_gate_is_backend_conditional():
    """On a native backend (TPU) a quantized scan that fails to beat f32 is
    a regression; on CPU the honest path dequantizes to f32, so only the
    0.5x plumbing floor gates.  The same 0.9x ratio must fail natively and
    pass non-natively."""
    for name in check_bench.QUANT_ROWS:
        native = _kernels_doc()
        native["rows"][name]["quantized_native"] = True
        native["rows"][name]["speedup_vs_f32"] = 0.9
        failures = check_bench.check(native, None)
        assert any(name in f and "native quantized scan" in f for f in failures)
        native["rows"][name]["speedup_vs_f32"] = 1.3
        assert check_bench.check(native, None) == []
        nonnative = _kernels_doc()
        nonnative["rows"][name]["speedup_vs_f32"] = 0.9  # above 0.5 floor
        assert check_bench.check(nonnative, None) == []
        nonnative["rows"][name]["speedup_vs_f32"] = 0.3  # below it
        failures = check_bench.check(nonnative, None)
        assert any(name in f and "plumbing floor" in f for f in failures)


def test_new_kernel_rows_are_throughput_gated():
    """The gather/quantized rows are kernel.* rows like any other: a
    wall-clock drop past the kernel budget fails against the baseline even
    when every same-window ratio stays healthy."""
    base = _kernels_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["kernel.gather_rerank"]["throughput_qps"] *= 0.5
    failures = check_bench.check(cur, base)
    assert any(
        "kernel.gather_rerank" in f and "machine factor" in f for f in failures
    )


def test_gather_rerank_cli_doctored_json(tmp_path):
    base = _kernels_doc()
    cur = copy.deepcopy(base)
    cur["rows"]["kernel.gather_rerank"]["speedup_vs_host"] = 0.8
    cur_p, base_p = tmp_path / "cur.json", tmp_path / "base.json"
    cur_p.write_text(json.dumps(cur))
    base_p.write_text(json.dumps(base))
    assert check_bench.main([str(cur_p), "--baseline", str(base_p)]) == 1
