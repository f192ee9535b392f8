"""Find the rate a cell's deployment sustains: one process, one set-up, a
list of offered rates, each driven as an open loop for ``--seconds``.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> --rates 10,20,40

For each rate it prints the offered rate, the completed rate (probes over
the time from the window's opening to the last answer), the backlog growth
(the median latency of the window's last quarter of probes against its
first quarter, and how long the answers ran on past the close) and the p50
and p95 probe latency from due time.  A rate counts as sustained where
nothing failed and the last quarter's median latency stays within 1.3x the
first quarter's; a growing backlog shows as a last quarter that waits ever
longer.  The cell's ``rate_per_s`` is then set at about 4/5 of the highest
sustained rate (``PERF.md`` records each sweep).  Exits non-zero without a
TPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import cell as cell_mod  # noqa: E402


def sweep_rates(cell, seed: int, seconds: float, rates, root: str):
    import datagen
    import harness
    import layers

    rows_out = []
    harness.persist_compiles(True)
    with harness.deployment(cell, seed, root, layers.Capture()) as (_cluster, _rows, mb):
        harness.log(f"setup_s={time.perf_counter() - T_START}")
        harness.persist_compiles(False)
        for i, rate in enumerate(rates):
            probes = datagen.make_probes(cell.config, cell.traffic, rate, seconds, seed + i)
            before = mb.stats.batches
            win = harness.drive(mb, cell.traffic, probes, seconds)
            due_ms = np.array([p.due_s * 1e3 for p in probes])
            done_ms = due_ms + win.latency_ms
            last = float(np.max(done_ms)) / 1e3
            quarter = max(1, len(probes) // 4)
            row = {
                "offered_per_s": len(probes) / seconds,
                "completed_per_s": len(probes) / last,
                "drain_after_close_s": last - seconds,
                "p50_first_quarter_ms": float(np.median(win.latency_ms[:quarter])),
                "p50_last_quarter_ms": float(np.median(win.latency_ms[-quarter:])),
                "p50_ms": harness.percentile(win.latency_ms, 50),
                "p95_ms": harness.percentile(win.latency_ms, 95),
                "batches": mb.stats.batches - before,
                "failed": int(sum(a is None for a in win.answers)),
                "generator_late_ms_max": float(np.max(win.lateness_ms)),
            }
            harness.log("sweep " + " ".join(f"{k}={v}" for k, v in row.items()))
            rows_out.append(row)
    return rows_out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated probes per second")
    args = ap.parse_args(argv)
    cell = cell_mod.load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"sweep: needs {cell.chips} TPU chip(s); JAX found {len(devices)} "
              f"{devices[0].platform!r} device(s)", file=sys.stderr)
        return 3
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    rates = [float(r) for r in args.rates.split(",")]
    with tempfile.TemporaryDirectory(prefix="bench_sweep_") as tmp:
        rows = sweep_rates(cell, args.seed, args.seconds, rates, tmp)
    print(json.dumps({"workload": cell.name, "device_kind": devices[0].device_kind,
                      "sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
