"""Data and traffic generation from ``--seed``.

Everything a run feeds the system comes from here: the table's rows and
attributes (the deployment's data), the probes of the measured window (query
vectors, their due times and predicates), and the warm-up probes.  Nothing
here imports the system under test.

Each stream draws from its own ``numpy`` generator keyed by ``(seed,
stream)``, so adding a stream never shifts another.  The arrival schedule
keeps one multiset of gaps for every seed (drawn from the traffic file's own
``gap_seed``) and only permutes it by the run's seed: two seeds offer the
same work in a different order.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np

CENTERS, ROWS, QUERIES, ARRIVALS, FILTERS, ATTRIBUTES, WARMUP, WARMUP_FILTERS = range(8)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


class Corpus:
    """Clustered vectors with low intrinsic dimension, as real embeddings
    have: ``center + z @ basis + noise`` with ``z`` in ``local_dim`` dims,
    normalised to unit length (L2 on unit vectors orders as cosine does).

    Centers spread about as far as the points around them, so clusters
    touch.  Far-apart clusters would leave PQ's 256 codes per subspace naming
    little but the cluster.  (Copied from the repository's ``chip_smoke.py``,
    with the unit normalisation the cosine deployments need.)"""

    def __init__(self, seed: int, spec: dict) -> None:
        g = rng(seed, CENTERS)
        dim, local = int(spec["dim"]), int(spec["local_dim"])
        self.noise = float(spec["noise"])
        self.centers = g.standard_normal((int(spec["clusters"]), dim), dtype=np.float32)
        self.basis = (g.standard_normal((local, dim)) / np.sqrt(local)).astype(np.float32)
        self.seed = seed

    def vectors(self, n: int, stream: int) -> np.ndarray:
        g = rng(self.seed, stream)
        c = g.integers(0, self.centers.shape[0], n)
        z = g.standard_normal((n, self.basis.shape[0]), dtype=np.float32)
        x = self.centers[c] + z @ self.basis
        x += self.noise * g.standard_normal(x.shape, dtype=np.float32)
        x /= np.linalg.norm(x, axis=1, keepdims=True)
        return x


@dataclass
class Table:
    """The rows a run writes, in the order it writes them."""

    vectors: np.ndarray  # (n, D) float32
    attributes: dict  # name -> (n,) int64


def make_table(config: dict, seed: int) -> Table:
    corpus = Corpus(seed, config["corpus"])
    n = int(config["rows"])
    attrs = {}
    g = rng(seed, ATTRIBUTES)
    for name, spec in sorted(config.get("attributes", {}).items()):
        attrs[name] = g.integers(int(spec["low"]), int(spec["high"]), n).astype(np.int64)
    return Table(corpus.vectors(n, ROWS), attrs)


@dataclass
class Probe:
    """One probe: due ``due_s`` seconds after the window opens."""

    due_s: float
    query: np.ndarray
    k: int
    lo: Optional[int] = None  # range predicate [lo, hi) on the filter column
    hi: Optional[int] = None


def predicate_sql(traffic: dict, probe: Probe) -> Optional[str]:
    """The WHERE fragment a user would send for this probe."""
    if probe.lo is None:
        return None
    col = traffic["filter"]["column"]
    return f"{col} >= {probe.lo} AND {col} < {probe.hi}"


def arrival_offsets(traffic: dict, rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times in ``[0, seconds)`` of ``round(rate * seconds)`` probes.

    Poisson arrivals: exponential gaps from the traffic's fixed ``gap_seed``,
    scaled so the ``n + 1`` gaps span the window, then permuted by ``seed``."""
    n = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng(int(traffic.get("gap_seed", 0))).exponential(1.0, n + 1)
    gaps = gaps[rng(seed, ARRIVALS).permutation(n + 1)]
    return np.cumsum(gaps)[:n] / gaps.sum() * seconds


def make_probes(
    config: dict, traffic: dict, rate: float, seconds: float, seed: int
) -> List[Probe]:
    """The window's probes, in due order."""
    due = arrival_offsets(traffic, rate, seconds, seed)
    n = len(due)
    queries = Corpus(seed, config["corpus"]).vectors(n, QUERIES)
    k = int(traffic["k"])
    flt = traffic.get("filter")
    los: List[Optional[int]] = [None] * n
    width = 0
    if flt:
        width = int(flt["width"])
        los = [int(v) for v in rng(seed, FILTERS).integers(
            int(flt["low"]), int(flt["high"]) - width + 1, n)]
    return [
        Probe(float(due[i]), queries[i], k,
              los[i], None if los[i] is None else los[i] + width)
        for i in range(n)
    ]


def warmup_probes(config: dict, traffic: dict, seed: int, n: int) -> List[Probe]:
    """Probes for warm-up: the window's own kind, from a stream the window
    never draws, so warm-up answers nothing the window will ask."""
    corpus = Corpus(seed, config["corpus"])
    queries = corpus.vectors(n, WARMUP)
    flt = traffic.get("filter")
    out = []
    g = rng(seed, WARMUP_FILTERS)
    for i in range(n):
        lo = hi = None
        if flt:
            w = int(flt["width"])
            lo = int(g.integers(int(flt["low"]), int(flt["high"]) - w + 1))
            hi = lo + w
        out.append(Probe(0.0, queries[i], int(traffic["k"]), lo, hi))
    return out
