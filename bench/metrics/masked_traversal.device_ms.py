"""Device time of the predicate-aware traversal (``core/vamana.py``
``_masked_beam_search``, program ``jit__masked_beam_search``) per
micro-batch of the window, in ms."""

MODULES = ("jit__masked_beam_search",)


def read(run):
    t = run.module_seconds(*MODULES)
    if t <= 0.0 or not run.batches:
        return None
    return 1e3 * t / run.batches
