"""The control: the plain reference put in the program's place, computed in
a lower precision than the configuration states, held to the same checks.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

For each seed it makes the cell's table and window probes exactly as a run
does, answers every probe with the exact top-k under distances computed on
the default device from bfloat16-rounded vectors (``bf16``: the precision a
later change would be tempted to serve in) and with three-pass bf16
products (``high``, ``Precision.HIGH`` on a TPU), and prints the compared
numbers of ``reference.compare`` for each.  The upper readings of the
cell's limits come from here (``PERF.md``).  No index is built: the control
needs none.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import cell as cell_mod  # noqa: E402
import datagen  # noqa: E402
import reference  # noqa: E402

BLOCK = 256


def low_precision_answers(X, attribute, probes, precision: str):
    """Top-k rows and their distances, computed in ``precision``."""
    import jax
    import jax.numpy as jnp

    if precision == "bf16":
        xs = jnp.asarray(X).astype(jnp.bfloat16)

        def dist(q):
            qb = q.astype(jnp.bfloat16)
            cross = jnp.dot(qb, xs.T, preferred_element_type=jnp.float32)
            qf, xf = qb.astype(jnp.float32), xs.astype(jnp.float32)
            return (qf * qf).sum(1)[:, None] - 2.0 * cross + (xf * xf).sum(1)[None, :]
    elif precision == "high":
        xs = jnp.asarray(X)

        def dist(q):
            cross = jnp.dot(q, xs.T, precision=jax.lax.Precision.HIGH)
            return (q * q).sum(1)[:, None] - 2.0 * cross + (xs * xs).sum(1)[None, :]
    else:
        raise ValueError(precision)
    dist = jax.jit(dist)
    answers = []
    for s in range(0, len(probes), BLOCK):
        block = probes[s : s + BLOCK]
        d = np.asarray(dist(jnp.asarray(np.stack([p.query for p in block]))))
        for j, p in enumerate(block):
            row = d[j]
            if p.lo is not None:
                row = np.where((attribute >= p.lo) & (attribute < p.hi), row, np.inf)
            kk = min(p.k, int(np.isfinite(row).sum()))
            idx = np.argpartition(row, kk - 1)[:kk]
            idx = idx[np.argsort(row[idx], kind="stable")]
            answers.append([(int(i), float(row[i])) for i in idx])
    return answers


def control_checks(cell, seed: int, seconds: float, precision: str):
    rows = datagen.make_table(cell.config, seed)
    probes = datagen.make_probes(
        cell.config, cell.traffic, float(cell.params["rate_per_s"]), seconds, seed)
    flt = cell.traffic.get("filter")
    attr = rows.attributes[flt["column"]] if flt else None
    answers = low_precision_answers(rows.vectors, attr, probes, precision)
    limits = dict(cell.params["limits"])
    limits["recall_at_10"] = float(cell.config["guarantees"]["recall_at_10_min"])
    return reference.compare(rows.vectors, attr, probes, answers, limits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--precisions", default="bf16,high")
    args = ap.parse_args(argv)
    cell = cell_mod.load_cell(args.workload)
    import jax

    kind = jax.devices()[0].device_kind
    for seed in (int(s) for s in args.seeds.split(",")):
        for precision in args.precisions.split(","):
            checks = control_checks(cell, seed, args.seconds, precision)
            print(json.dumps({"workload": cell.name, "seed": seed, "precision": precision,
                              "device_kind": kind, "correct": reference.passed(checks),
                              "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
