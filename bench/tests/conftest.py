"""The benchmark's own tests run on the CPU, at tiny sizes."""

import copy
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_cell(name: str, rate: float = 20.0, traffic: str = None):
    """A cell of ``BENCHMARK.json`` cut to a size a CPU test holds: 2,048
    rows at D=32, two shards and executors, R=16, L=32.  ``traffic`` puts
    another mix of ``bench/traffic`` in the cell's place."""
    import cell as cell_mod

    c = cell_mod.load_cell(name)
    if traffic is not None:
        c.traffic_name = traffic
        c.traffic = cell_mod.load_traffic(traffic)
    cfg = copy.deepcopy(c.config)
    cfg.update(rows=2048, dim=32, files=4, rows_per_group=256, executors=2)
    cfg["corpus"]["dim"] = 32
    cfg["index"].update(R=16, L=32, pq_m=8, num_shards=2)
    c.config = cfg
    c.traffic = dict(c.traffic, warmup_batches=[8, 1])
    c.params = dict(c.params, rate_per_s=rate)
    return c
