"""A kernel's share of its roofline, from recorded calls and the trace.

The least time a call can take is the larger of its needed operations over
the chip's peak rate and its needed bytes over the HBM bandwidth
(``bench/work/<kernel>.py``, ``peaks.json``).  The share is the sum of that
over the window's calls, divided by the device time of the kernel's
programs in the trace.  A float32 contraction is bounded against the bf16
peak: a lower bound on its time, so the share stays at or under 100%.
"""

from __future__ import annotations

import sys
from typing import Optional


def share(run, counter: str, modules) -> Optional[float]:
    """Percent of the roofline; None when the window ran no such call."""
    calls = [c for c in run.kernel_calls if c["counter"] == counter]
    device_s = run.module_seconds(*modules)
    if not calls or device_s <= 0.0:
        return None
    w = run.work[counter]
    compute = memory = need = 0.0
    for c in calls:
        ops, nbytes = w.work(c)
        tc = ops / run.peaks[w.peak(c)]
        tm = nbytes / run.peaks["hbm_bytes_per_s"]
        compute += tc
        memory += tm
        need += max(tc, tm)
    bound = "compute" if compute >= memory else "memory"
    print(f"bench: roofline {counter} calls={len(calls)} device_s={device_s} "
          f"compute_bound_s={compute} memory_bound_s={memory} binds={bound}",
          file=sys.stderr, flush=True)
    return 100.0 * need / device_s
