"""``trace_reduce`` on a small trace recorded on one TPU v5e chip
(``data/small.xplane.pb``, made by ``record_trace.py``): a matmul, a Pallas
``gather_rerank`` call under an ``executor.task`` span, then a 50 ms host
sleep under a ``coordinator.probe_batch`` span, inside ``bench.window``."""

import os

import pytest

import layers
import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_trace(TRACE, layers.SPAN_DEPTH)


def test_window_is_the_bench_window_span(reduced):
    assert 0.05 < reduced.window_s < 0.07
    assert reduced.devices == 1


def test_busy_is_the_union_of_device_ops(reduced):
    assert 0 < reduced.busy_s < 0.001
    assert reduced.idle_share > 0.98
    # busy can never exceed the sum of the programs that ran
    assert reduced.busy_s <= sum(reduced.module_s.values()) * 1.5 + 1e-4


def test_programs_are_found_by_stable_name(reduced):
    assert reduced.module_calls["jit_gather_rerank_pallas"] == 1
    assert 0 < reduced.module_s["jit_gather_rerank_pallas"] < 0.001
    assert reduced.top_ops[0][0] in reduced.module_s
    assert all("(" not in name for name in reduced.module_s)


def test_longest_gap_is_named_by_the_host_span_around_it(reduced):
    name, seconds = reduced.idle_gaps[0]
    assert name == "coordinator.probe_batch"
    assert 0.045 < seconds < 0.06
    assert len(reduced.idle_gaps) <= 10


def test_stable_name_drops_the_compile_suffix():
    assert trace_reduce.stable_name("jit__beam_search(1234567)") == "jit__beam_search"
    assert trace_reduce.stable_name("jit_gather_rerank_pallas") == "jit_gather_rerank_pallas"


def test_union_merges_overlaps():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace(TRACE, {"no.such.span": 0})
