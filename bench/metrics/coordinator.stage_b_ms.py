"""Mean ``ProbeReport.stage_b_seconds`` of the window's ``probe_batch`` calls,
in ms: the candidate merge and the Stage-B exact rerank wave (host clock)."""


def read(run):
    if not run.reports:
        return None
    return 1e3 * sum(r.stage_b_seconds for r in run.reports) / len(run.reports)
