"""``trace_reduce`` names idle gaps by the program's own spans.

``data/program_spans.xplane.pb`` was recorded on one TPU v5e chip by
``record_program_trace.py``: one 9-probe ``probe_batch`` call of a tiny
deployment, with the program's tracing on and none of the benchmark's span
wrappers installed, so every host span in it is the program's.  Stage B's
eager rerank compiles at the new batch size while the device idles."""

import os

import pytest

import layers
import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data", "program_spans.xplane.pb")

# the accepted depths, with the program's deeper spans added between them
PROGRAM_DEPTH = dict(layers.SPAN_DEPTH, **{
    "serving.batch": 1,
    "coordinator.stage_a": 2.5,
    "coordinator.stage_b": 2.5,
    "coordinator.stage_c": 2.5,
    "coordinator.merge": 2.7,
    "executor.load_shard": 4.5,
    "executor.candidates": 4.5,
    "executor.rerank": 4.5,
})
PROGRAM_SPANS = {"serving.batch", "coordinator.probe_batch", "coordinator.stage_a",
                 "coordinator.stage_b", "coordinator.stage_c", "coordinator.merge",
                 "scheduler.wave", "executor.task", "executor.load_shard",
                 "executor.candidates", "executor.rerank.read", "executor.rerank.score",
                 "executor.rerank.emit", "traversal.search_pq"}


@pytest.fixture(scope="module")
def accepted():
    return trace_reduce.reduce_trace(TRACE, layers.SPAN_DEPTH)


@pytest.fixture(scope="module")
def deeper():
    return trace_reduce.reduce_trace(TRACE, PROGRAM_DEPTH)


def test_the_accepted_depths_name_gaps_by_program_spans(accepted):
    assert accepted.devices == 1
    assert accepted.idle_gaps
    names = [name for name, _s in accepted.idle_gaps]
    assert set(names) <= PROGRAM_SPANS | {"host:none"}
    assert names[0] == "executor.task"


def test_deeper_program_spans_name_the_compile_gap(deeper, accepted):
    name, seconds = deeper.idle_gaps[0]
    assert name == "executor.rerank.score"
    assert seconds == accepted.idle_gaps[0][1]
    assert {n for n, _s in deeper.idle_gaps} <= PROGRAM_SPANS | {"host:none"}


def test_program_spans_leave_the_device_numbers_alone(deeper, accepted):
    assert deeper.busy_s == accepted.busy_s
    assert deeper.window_s == accepted.window_s
    assert deeper.module_s == accepted.module_s
