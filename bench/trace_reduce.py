"""Reduce a profiler trace (``.xplane.pb``) to the device numbers.

- busy and idle: the union of the intervals in which an XLA operation ran on
  a device, clipped to the window (the host span ``bench.window``), averaged
  over the devices that ran anything;
- device time per program, by stable name: each XLA module's events, keyed by
  the jitted function's name with the compile-unique suffix removed
  (``jit__beam_search(1234)`` -> ``jit__beam_search``);
- the longest idle gaps, each named by the deepest benchmark host span
  (``layers.SPAN_DEPTH``) that covers its midpoint, or ``host:none`` where no
  probe was in flight.

Roofline shares are the readers' business (``bench/metrics``): they divide
the least time the counted work needs (``bench/work``, ``peaks.json``) by the
device time this module reports for the kernel's program.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"\(\d+\)$")


def stable_name(module: str) -> str:
    return _SUFFIX.sub("", module.strip())


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


@dataclass
class Reduced:
    window_s: float
    busy_s: float  # mean over devices that ran anything
    devices: int
    module_s: Dict[str, float] = field(default_factory=dict)  # summed over devices
    module_calls: Dict[str, int] = field(default_factory=dict)
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _span_depth(name: str, depths: Dict[str, int]) -> Optional[int]:
    for prefix, d in depths.items():
        if name == prefix or name.startswith(prefix + "."):
            return d
    return None


def reduce_trace(path: str, depths: Dict[str, int], top: int = 10) -> Reduced:
    """Reduce one ``.xplane.pb``; ``depths`` ranks the benchmark's host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window: Optional[Tuple[float, float]] = None
    spans: List[Tuple[float, float, str, int]] = []
    device_ops: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    module_events: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    d = _span_depth(ev.name, depths)
                    if d is None:
                        continue
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    else:
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, d))
        elif DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        device_ops[plane.name].append((ev.start_ns, ev.start_ns + ev.duration_ns))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        module_events.append(
                            (stable_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                        )
    if window is None:
        raise ValueError(f"{path}: no '{WINDOW_SPAN}' host span")
    w0, w1 = window
    busy = []
    gaps_all: List[Tuple[float, float]] = []
    for ivs in device_ops.values():
        clipped = [(max(s, w0), min(e, w1)) for s, e in ivs if e > w0 and s < w1]
        merged = _union(clipped)
        busy.append(sum(e - s for s, e in merged))
        prev = w0
        for s, e in merged:
            if s > prev:
                gaps_all.append((prev, s))
            prev = e
        if w1 > prev:
            gaps_all.append((prev, w1))
    module_s: Dict[str, float] = defaultdict(float)
    module_calls: Dict[str, int] = defaultdict(int)
    for name, s, e in module_events:
        if e > w0 and s < w1:
            module_s[name] += (min(e, w1) - max(s, w0)) / 1e9
            module_calls[name] += 1
    spans.sort()
    labelled = []
    for s, e in sorted(gaps_all, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        best = None
        for ss, se, name, d in spans:
            if ss > mid:
                break
            if se >= mid and (best is None or d > best[1]):
                best = (name, d)
        labelled.append((best[0] if best else "host:none", (e - s) / 1e9))
    n_dev = len(busy)
    return Reduced(
        window_s=(w1 - w0) / 1e9,
        busy_s=(sum(busy) / n_dev / 1e9) if n_dev else 0.0,
        devices=n_dev,
        module_s=dict(module_s),
        module_calls=dict(module_calls),
        top_ops=sorted(module_s.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=labelled,
    )
