"""Record the trace that ``test_program_spans.py`` reads.

    python3 bench/tests/record_program_trace.py <out_dir>      # on one TPU chip

Builds a tiny deployment of the first cell on the chip (2,048 rows at D=32,
two shards, R=16, L=32: the CPU tests' size) and probes it once untraced,
which compiles the traversal.  Then, with the program's own tracing on
(``repro.serving.metrics.set_tracing``) and inside a ``bench.window`` host
span, it sends one batch of 9 probes through ``Coordinator.probe_batch``: a
batch size not seen before, so Stage B's eager rerank compiles under the
program span ``executor.rerank.score`` while the device idles.  None of the
benchmark's own span wrappers is installed.
"""

import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import jax  # noqa: E402

import cell as cell_mod  # noqa: E402
import harness  # noqa: E402
from repro.serving import metrics  # noqa: E402


def main(out: str) -> None:
    cell = cell_mod.load_cell("cohere-768d.knn-steady")
    cell.config = dict(cell.config, rows=2048, dim=32, files=4, rows_per_group=256, executors=2,
                       corpus=dict(cell.config["corpus"], dim=32),
                       index=dict(cell.config["index"], R=16, L=32, pq_m=8, num_shards=2))
    cluster, rows = harness.build(cell, 11, tempfile.mkdtemp(prefix="record_"))
    probe = cluster.coordinator.probe_batch
    probe(harness.TABLE, rows.vectors[:16] + 0.01, 10, use_pq=True)
    metrics.set_tracing(metrics.MetricsRegistry())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with metrics.span("serving.batch", trace_id=1, probes=9, k=10, queue_wait_ms=0.0):
            probe(harness.TABLE, rows.vectors[100:109] + 0.01, 10, use_pq=True)
    jax.profiler.stop_trace()
    metrics.set_tracing(None)
    spans = metrics.drain()
    print(json.dumps({"device": jax.devices()[0].device_kind, "rows": len(rows.vectors),
                      "spans": [(x["name"], x["compiles"]) for x in spans]}))


if __name__ == "__main__":
    main(sys.argv[1])
