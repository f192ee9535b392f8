"""Run one benchmark cell on the chips of this machine.

    python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, traffic and
per-layer readers are found by the names in ``BENCHMARK.json``.  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also ``breakdown``,
and last ``checks``: each compared number beside its limit); the last lines
of standard error repeat the checks.  Exits non-zero, printing no result,
when JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import cell as cell_mod  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cell_mod.load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run_cell: needs {cell.chips} TPU chip(s); JAX found {len(devices)} "
              f"{devices[0].platform!r} device(s)", file=sys.stderr)
        return 3
    cell_mod.peaks(devices[0].device_kind)  # an unknown chip is an error
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"run_cell: the system under test is not at {src}", file=sys.stderr)
        return 4
    sys.path.insert(0, src)

    import harness
    from repro.launch.compile_cache import enable_compile_cache

    # JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache: each
    # checkout keeps its own, so two checkouts compared share nothing
    cache_dir = enable_compile_cache()
    harness.log(f"platform={devices[0].platform} device_kind={devices[0].device_kind} "
                f"device_count={len(devices)} compile_cache={cache_dir} "
                f"init_s={time.perf_counter() - T_START}")
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
