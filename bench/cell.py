"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

A cell (one entry of ``workloads``) names a configuration and a traffic mix.
Its files:

- ``configs/<config>.json``: the deployment (the file ``configs[].file`` names);
- ``traffic/<traffic>.json``: the traffic mix, read by ``datagen``;
- ``cells/<workload>.json``: the cell's fixed offered rate and the limits of
  its compared numbers (``reference.compare``);
- ``metrics/<metric>.py``: one reader per per-layer metric, ``read(run)``;
- ``work/<kernel>.py``: one counter of a kernel's needed work, ``work(call)``;
- ``peaks.json``: the chip's peaks by ``device_kind``.

Nothing here knows a particular cell, configuration or metric.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    params: dict  # cells/<name>.json
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    workloads = {w["name"]: w for w in bench["workloads"]}
    if name not in workloads:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({sorted(workloads)})")
    w = workloads[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = configs[w["config"]]
    bench_dir = os.path.join(root, "bench")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config_name=cfg["name"],
        config=_json(os.path.join(root, cfg["file"])),
        traffic_name=w["traffic"],
        traffic=load_traffic(w["traffic"], root),
        params=_json(os.path.join(bench_dir, "cells", name + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def load_traffic(name: str, root: str = ROOT) -> dict:
    return _json(os.path.join(root, "bench", "traffic", name + ".json"))


def peaks(device_kind: str, root: str = ROOT) -> dict:
    """The chip's peaks; an unknown kind is an error, never a default."""
    table = _json(os.path.join(root, "bench", "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} has no entry in bench/peaks.json")
    return table[device_kind]


def reader(metric: str, root: str = ROOT) -> Callable:
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    return load_module(path, "bench_metric_" + metric.replace(".", "_")).read


def work_counter(kernel: str, root: str = ROOT):
    path = os.path.join(root, "bench", "work", kernel + ".py")
    return load_module(path, "bench_work_" + kernel)


def work_counters(root: str = ROOT) -> Dict[str, object]:
    d = os.path.join(root, "bench", "work")
    return {f[:-3]: work_counter(f[:-3], root) for f in sorted(os.listdir(d)) if f.endswith(".py")}
