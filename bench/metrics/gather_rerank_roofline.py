"""``gather_rerank``'s share of its roofline, in %: the work a pooled rerank
needs (``bench/work/gather_rerank.py``) over the device time of the Pallas
program ``jit_gather_rerank_pallas``."""

import roofline

MODULES = ("jit_gather_rerank_pallas",)


def read(run):
    return roofline.share(run, "gather_rerank", MODULES)
