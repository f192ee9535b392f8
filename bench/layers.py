"""What the benchmark reads from the program, from its own side of the calls.

``Capture`` keeps the ``ProbeReport`` of every ``Coordinator.probe_batch``
call and the micro-batcher's counters.  In a traced run, ``annotate`` also
wraps the public entry of each layer in a ``jax.profiler.TraceAnnotation``
(so the device trace can say what the host was doing in each idle gap) and
records the shapes of each kernel call that a roofline reader needs.  The
program itself is not changed: the wrappers are installed on the classes and
the ``kernels.ops`` module for the run and removed after it.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Dict, List

import numpy as np

# layer spans, most specific last (idle gaps are named by the deepest one)
SPAN_DEPTH = {
    "bench.window": 0,
    "serving.submit": 1,
    "coordinator.probe_batch": 2,
    "scheduler.wave": 3,
    "executor.task": 4,
    "traversal": 5,
    "kernels.ops": 6,
}

# kernels.ops functions whose calls a roofline reader counts; each names the
# ``bench/work`` counter that takes the recorded shapes
COUNTED_OPS = {
    "gather_rerank": "gather_rerank",
}


class Capture:
    """Per-run record of the program's reports, counters and kernel calls."""

    def __init__(self) -> None:
        self.reports: List[object] = []
        self.kernel_calls: List[Dict] = []
        self.recording = False
        self._lock = threading.Lock()
        self._depth = threading.local()

    def wrap_probe_batch(self, coordinator) -> None:
        inner = coordinator.probe_batch

        @functools.wraps(inner)
        def probe_batch(*args, **kwargs):
            rep = inner(*args, **kwargs)
            if self.recording:
                with self._lock:
                    self.reports.append(rep)
            return rep

        coordinator.probe_batch = probe_batch

    def record_kernel(self, name: str, args, kwargs) -> None:
        """Shapes of one ``kernels.ops`` call (outermost call only)."""
        q, points, pids = args[0], args[1], np.asarray(args[2])
        call = {"op": name, "counter": COUNTED_OPS[name], "Q": int(q.shape[0]),
                "N": int(points.shape[0]), "D": int(points.shape[1]),
                "k": int(kwargs["k"] if "k" in kwargs else args[3]),
                "P": int(pids.shape[1])}
        call["valid"] = int(((pids >= 0) & (pids < call["N"])).sum())
        with self._lock:
            self.kernel_calls.append(call)


def _annotated(name: str, fn, capture: Capture = None, counted: str = None):
    import jax

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        depth = getattr(capture._depth, "n", 0) if capture is not None else 0
        if capture is not None:
            capture._depth.n = depth + 1
        try:
            with jax.profiler.TraceAnnotation(name):
                out = fn(*args, **kwargs)
            if counted and depth == 0 and capture.recording:
                capture.record_kernel(counted, args, kwargs)
            return out
        finally:
            if capture is not None:
                capture._depth.n = depth

    return wrapper


@contextlib.contextmanager
def annotate(capture: Capture):
    """Install the traced run's layer spans; restore everything on exit."""
    from repro.core.vamana import VamanaGraph
    from repro.kernels import ops
    from repro.runtime.coordinator import Coordinator
    from repro.runtime.executor import Executor
    from repro.runtime.scheduler import Scheduler
    from repro.serving.serve_loop import ProbeMicroBatcher

    patches = [
        (ProbeMicroBatcher, "submit", "serving.submit"),
        (Coordinator, "probe_batch", "coordinator.probe_batch"),
        (Scheduler, "run_coalesced_wave", "scheduler.wave"),
        (Scheduler, "run_wave", "scheduler.wave"),
        (Executor, "handle", "executor.task"),
        (VamanaGraph, "search", "traversal.search"),
        (VamanaGraph, "search_pq", "traversal.search_pq"),
        (VamanaGraph, "search_masked", "traversal.search_masked"),
    ]
    saved = []
    for owner, attr, span in patches:
        fn = owner.__dict__[attr]
        saved.append((owner, attr, fn))
        setattr(owner, attr, _annotated(span, fn))
    for attr in ("gather_rerank", "masked_exact_topk", "masked_exact_topk_multi",
                 "masked_exact_topk_dedup", "masked_pq_topk", "masked_pq_topk_dedup",
                 "unified_masked_topk_dedup", "exact_distances", "pq_scan"):
        fn = getattr(ops, attr)
        saved.append((ops, attr, fn))
        setattr(ops, attr, _annotated(f"kernels.ops.{attr}", fn, capture, COUNTED_OPS.get(attr)))
    try:
        yield
    finally:
        for owner, attr, fn in reversed(saved):
            setattr(owner, attr, fn)
