"""CPU rehearsal of the harness's own pieces at a tiny size: traffic from a
seed, due-time latency accounting, the reference comparison, reading cells
from ``BENCHMARK.json``, a whole run, and the refusal without a TPU."""

import json
import os
import re
import subprocess
import sys
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

import cell as cell_mod
import datagen
import harness
import reference
from conftest import BENCH, ROOT, tiny_cell

BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
BIG_SEED = 2**31 + 987654321


# -- traffic -----------------------------------------------------------------


@pytest.mark.parametrize("name", CELLS)
def test_traffic_is_a_function_of_the_seed(name):
    c = tiny_cell(name)
    a = datagen.make_probes(c.config, c.traffic, 30.0, 4.0, BIG_SEED)
    b = datagen.make_probes(c.config, c.traffic, 30.0, 4.0, BIG_SEED)
    assert len(a) == 120
    for p, q in zip(a, b):
        assert p.due_s == q.due_s and p.lo == q.lo and np.array_equal(p.query, q.query)


def test_every_seed_offers_the_same_work_in_another_order():
    traffic = cell_mod.load_cell(CELLS[0]).traffic
    a = datagen.arrival_offsets(traffic, 40.0, 10.0, 1)
    b = datagen.arrival_offsets(traffic, 40.0, 10.0, BIG_SEED)
    assert len(a) == len(b) == 400
    assert 0 < a.min() and a.max() < 10.0 and (np.diff(a) > 0).all()
    gaps = lambda d: np.sort(np.diff(np.concatenate([[0.0], d])))  # noqa: E731
    # the same multiset of gaps (the last one closes the window), reordered
    assert np.allclose(np.sort(np.append(gaps(a), 10.0 - a[-1])),
                       np.sort(np.append(gaps(b), 10.0 - b[-1])))
    assert not np.allclose(a, b)


def test_range_filter_passes_one_percent():
    c = cell_mod.load_cell("cohere-768d.knn-steady")
    traffic = cell_mod.load_traffic("range-1pct")
    cfg = dict(c.config, corpus=dict(c.config["corpus"], dim=8))
    rows = datagen.make_table(cfg, BIG_SEED)
    probes = datagen.make_probes(cfg, traffic, 40.0, 5.0, BIG_SEED)
    price = rows.attributes["price"]
    share = np.mean([((price >= p.lo) & (price < p.hi)).mean() for p in probes])
    assert 0.008 < share < 0.012
    assert datagen.predicate_sql(traffic, probes[0]) == (
        f"price >= {probes[0].lo} AND price < {probes[0].lo + 100}")


# -- latency accounting --------------------------------------------------------


class StallingBatcher:
    """Answers each probe 20 ms after submission, except that everything
    submitted in the window's first 0.3 s is held until then (a stall)."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self.stats = None

    def submit(self, q, k=10, filter=None):
        f = Future()
        now = time.perf_counter()
        at = max(now + 0.02, self.t0 + harness.START_DELAY_S + 0.3)
        threading.Timer(at - now, f.set_result, args=([],)).start()
        return f


def test_latency_is_timed_from_the_due_time():
    probes = [datagen.Probe(due_s=t, query=np.zeros(4, np.float32), k=10)
              for t in (0.0, 0.1, 0.2, 0.4, 0.5)]
    win = harness.drive(StallingBatcher(), {"filter": None}, probes, 0.6)
    lat = win.latency_ms
    # the stalled probes wait for the stall's end from their due time ...
    assert lat[0] > lat[1] > lat[2] > 60
    # ... the later ones only for their own service
    assert lat[3] < 60 and lat[4] < 60
    assert (win.lateness_ms < 50).all()


def test_a_probe_with_no_answer_misses_every_limit():
    lat = np.array([10.0, 20.0, np.inf])
    assert harness.percentile(lat, 95) == np.inf
    assert harness.percentile(np.array([10.0] * 19 + [np.inf]), 50) == 10.0


# -- reference comparison ---------------------------------------------------------


def _exact_answers(X, probes, attr=None):
    passing = [None if p.lo is None else (attr >= p.lo) & (attr < p.hi) for p in probes]
    truth = reference.exact_topk(X, np.stack([p.query for p in probes]), 10, passing)
    out = []
    for p, t in zip(probes, truth):
        t = t[t >= 0]
        d = ((X[t].astype(np.float64) - p.query) ** 2).sum(1)
        out.append([(int(r), float(v)) for r, v in zip(t, d)])
    return out


LIMITS = {"dist_err": 1e-6, "recall_at_10": 0.9}


def _tiny(filtered=False):
    rng = np.random.default_rng(0)
    X = rng.standard_normal((500, 16)).astype(np.float32)
    attr = rng.integers(0, 100, 500)
    probes = [datagen.Probe(0.0, rng.standard_normal(16).astype(np.float32), 10,
                            *((int(lo), int(lo) + 30) if filtered else (None, None)))
              for lo in rng.integers(0, 70, 20)]
    return X, attr, probes


@pytest.mark.parametrize("filtered", [False, True])
def test_exact_answers_pass(filtered):
    X, attr, probes = _tiny(filtered)
    checks = reference.compare(X, attr, probes, _exact_answers(X, probes, attr), LIMITS)
    assert reference.passed(checks), checks
    assert checks["recall_at_10"]["value"] == 1.0


@pytest.mark.parametrize("fault,check", [
    ("distance", "dist_err"),   # a distance altered where it is produced
    ("row", "dist_err"),        # a hit names another row than it scored
    ("missing", "unanswered"),  # a probe that never got an answer
    ("short", "bad_hits"),      # fewer than k hits
    ("predicate", "bad_hits"),  # a hit failing its probe's predicate
])
def test_faulty_answers_fail(fault, check):
    X, attr, probes = _tiny(filtered=True)
    answers = _exact_answers(X, probes, attr)
    if fault == "distance":
        answers[3][0] = (answers[3][0][0], answers[3][0][1] * 1.001)
    elif fault == "row":
        answers[3][0] = (answers[3][0][0] + 1, answers[3][0][1])
    elif fault == "missing":
        answers[3] = None
    elif fault == "short":
        answers[3] = answers[3][:-1]
    elif fault == "predicate":
        p = probes[3]
        bad = int(np.flatnonzero((attr < p.lo) | (attr >= p.hi))[0])
        d = float(((X[bad].astype(np.float64) - p.query) ** 2).sum())
        answers[3] = sorted(answers[3][:-1] + [(bad, d)], key=lambda a: a[1])
    checks = reference.compare(X, attr, probes, answers, LIMITS)
    assert not reference.passed(checks)
    c = checks[check]
    assert c["value"] > c["limit"]


def test_row_locator_follows_the_written_layout():
    locate = reference.row_locator(10000, 4, 1024)
    assert locate("t/data/data-00000.vpq", 0, 0) == 0
    assert locate("t/data/data-00001.vpq", 1, 3) == 2500 + 1024 + 3
    assert locate("t/data/data-00003.vpq", 2, 451) == 7500 + 2048 + 451
    assert locate("t/data/data-00003.vpq", 2, 452) == -1  # past the file's rows
    assert locate("t/data/data-00004.vpq", 0, 0) == -1
    assert locate("t/data/other.vpq", 0, 0) == -1


# -- cells and BENCHMARK.json ---------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_each_cell_is_found_by_name(name):
    c = cell_mod.load_cell(name)
    assert c.chips == 1 and c.params["rate_per_s"] > 0
    assert set(c.params["limits"]) == {"dist_err"}
    assert {m["name"] for m in c.end_to_end} == {"probe_p50_ms", "probe_p95_ms", "setup_s"}
    for m in c.per_layer:
        assert callable(cell_mod.reader(m["name"]))
        assert m["moves"] == "probe_p95_ms"


def test_benchmark_json_keeps_to_its_limits():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and 1 <= b["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in b[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and os.path.exists(os.path.join(ROOT, c["file"]))
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] == 1
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in b["per_layer"]:
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
        layers.setdefault(m["layer"], set()).add(m["name"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert len(json.dumps(b)) < 64 * 1024


# -- the entry point ------------------------------------------------------------------


def test_entry_point_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run_cell.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not p.stdout.strip()


def test_a_whole_tiny_run_is_correct():
    c = tiny_cell(CELLS[0])
    res = harness.run(c, BIG_SEED, 1.5, False, time.perf_counter())
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 30
    assert set(res["metrics"]) == {"probe_p50_ms", "probe_p95_ms", "setup_s"}
    assert res["device"]["platform"] == "cpu"
