"""A whole run at a tiny size, with the timed path broken underneath: each
fault the served path can have must turn ``correct`` false.

The harness's look for a chip lives in ``run_cell.main``; these tests call
``harness.run`` directly, on the CPU.  A step that returns its state
unchanged has no counterpart here (a probe carries no state from step to
step), and the exchange between chips is, on one chip, the merge of the
shards' Stage-A candidates."""

import time

import pytest

import harness
from conftest import tiny_cell

SEED = 2**31 + 4242


def half_batch(monkeypatch):
    """Half of each batch left out: only the first half is probed, and the
    rest get the first half's answers."""
    from repro.runtime.coordinator import Coordinator

    orig = Coordinator.probe_batch

    def probe_batch(self, table, queries, k, **kw):
        b = len(queries)
        if b < 2:
            return orig(self, table, queries, k, **kw)
        keep = b - b // 2
        flt = kw.get("filter")
        if isinstance(flt, list):
            kw["filter"] = flt[:keep]
        rep = orig(self, table, queries[:keep], k, **kw)
        rep.hits = rep.hits + rep.hits[: b - keep]
        return rep

    monkeypatch.setattr(Coordinator, "probe_batch", probe_batch)


def altered_answer(monkeypatch):
    """A distance altered where Stage B produces it."""
    from repro.runtime.executor import Executor

    orig = Executor._rerank

    def _rerank(self, task):
        res = orig(self, task)
        for rows in res.rows:
            if rows:
                rows[0].distance *= 1.01
        return res

    monkeypatch.setattr(Executor, "_rerank", _rerank)


def shard_dropped(monkeypatch):
    """One shard's Stage-A candidates left out of the merge."""
    from repro.runtime.scheduler import Scheduler

    orig = Scheduler.run_coalesced_wave

    def run_coalesced_wave(self, tasks):
        results = orig(self, tasks)
        first = min(r.shard_id for r in results)
        for r in results:
            if r.shard_id == first:
                r.candidates = {qi: [] for qi in r.candidates}
        return results

    monkeypatch.setattr(Scheduler, "run_coalesced_wave", run_coalesced_wave)


CELL = "cohere-768d.knn-steady"


@pytest.mark.parametrize("traffic", [None, "range-1pct"])
@pytest.mark.parametrize("fault", [half_batch, altered_answer, shard_dropped])
def test_fault_turns_correct_false(fault, traffic, monkeypatch):
    fault(monkeypatch)
    res = harness.run(tiny_cell(CELL, traffic=traffic), SEED, 1.5, False, time.perf_counter())
    assert res["correct"] is False, res["checks"]


def test_sound_run_is_correct():
    """Filtered probes, so that the predicate check is exercised too."""
    res = harness.run(tiny_cell(CELL, traffic="range-1pct"), SEED, 1.5, False,
                      time.perf_counter())
    assert res["correct"] is True, res["checks"]
