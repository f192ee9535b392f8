"""Mean ``ProbeReport.stage_a_seconds`` of the window's ``probe_batch`` calls,
in ms: routing, planning and the coalesced Stage-A wave (host clock)."""


def read(run):
    if not run.reports:
        return None
    return 1e3 * sum(r.stage_a_seconds for r in run.reports) / len(run.reports)
