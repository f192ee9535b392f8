"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Usage::

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run kernels    # one suite
"""

import sys
import time
import traceback

SUITES = ["kernels", "index_sizes", "build", "query_paths", "refresh", "recall"]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    wanted = sys.argv[1:] or SUITES
    print("name,us_per_call,derived")
    failures = 0
    for name in wanted:
        mod_name = f"benchmarks.bench_{name}"
        t0 = time.time()
        try:
            mod = __import__(mod_name, fromlist=["main"])
            mod.main()
            print(f"suite.{name},{(time.time()-t0)*1e6:.0f},ok")
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            print(f"suite.{name},{(time.time()-t0)*1e6:.0f},FAILED_{type(e).__name__}")
            failures += 1
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
