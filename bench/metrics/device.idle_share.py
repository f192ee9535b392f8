"""Share of the traced window in which no operation ran on the device, in %
(``trace_reduce``: 1 - busy / window, averaged over the chips used)."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0.0:
        return None
    return 100.0 * run.trace.idle_share
