"""Device time of the graph traversal (``core/vamana.py`` ``_beam_search``,
program ``jit__beam_search``) per micro-batch of the window, in ms."""

MODULES = ("jit__beam_search",)


def read(run):
    t = run.module_seconds(*MODULES)
    if t <= 0.0 or not run.batches:
        return None
    return 1e3 * t / run.batches
