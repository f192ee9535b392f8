"""Share of the window's ``MaskedBeam`` probe-shard rows that the traversal
under-delivered and the fused exact masked scan answered again, in %
(``ProbeReport.masked_beam_fallbacks / masked_beam_rows``, summed over the
window's ``probe_batch`` calls)."""


def read(run):
    rows = sum(r.masked_beam_rows for r in run.reports)
    if not rows:
        return None
    return 100.0 * sum(r.masked_beam_fallbacks for r in run.reports) / rows
