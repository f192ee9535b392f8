"""Record the small trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_trace.py <out_dir>      # on one TPU chip

Inside a ``bench.window`` host span it runs, in order: a jitted matmul; a
Pallas ``gather_rerank`` call (program ``jit_gather_rerank_pallas``) under an
``executor.task`` span; a 50 ms host sleep under a ``coordinator.probe_batch``
span (an idle gap the reduction must name after it); then the matmul again.
"""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ops  # noqa: E402


def main(out: str) -> None:
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1024, 128), np.float32))
    q = x[:8]
    pids = jnp.asarray(np.tile(np.arange(128, dtype=np.int32), (8, 1)))
    mm = jax.jit(lambda a: a @ a.T)
    mm(x).block_until_ready()
    jax.block_until_ready(ops.gather_rerank(q, x, pids, 10))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        mm(x).block_until_ready()
        with jax.profiler.TraceAnnotation("executor.task"):
            jax.block_until_ready(ops.gather_rerank(q, x, pids, 10))
        with jax.profiler.TraceAnnotation("coordinator.probe_batch"):
            time.sleep(0.05)
        mm(x).block_until_ready()
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
