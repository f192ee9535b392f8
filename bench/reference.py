"""The plain reference and the comparison that decides ``correct``.

The reference is exact nearest-neighbour search in float64 NumPy over the
rows the run wrote (``datagen.Table``), in the order it wrote them.  It
imports nothing of the system under test and reads nothing it made: a
returned hit names its row by (data file, row group, row offset), and the
row's place in the table follows from how the harness split the rows into
files (``row_locator``), not from the system's metadata.

``compare`` holds every answered probe of the window to the deployment's
guarantees and returns the numbers compared, each beside its limit:

- ``unanswered``: window probes with no answer (failed, refused or never
  resolved) — limit 0;
- ``bad_hits``: answers of the wrong length, hits that name no row of the
  table, repeat a row, come out of distance order, or fail the probe's
  predicate — limit 0;
- ``dist_err``: the largest gap between a returned distance and the float64
  squared L2 distance of the row it names, as a share of ``|q|^2 + |x|^2``
  (the scale of the expanded form's rounding) — the limit comes from the
  cell file, set from sound runs and the control (``PERF.md``);
- ``recall_at_10``: mean over answered probes of the share of the exact
  top-10 (over the rows passing the predicate) among the returned top-10 —
  the configuration states its floor.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

BLOCK = 256  # queries per block of the brute-force search


def row_locator(n_rows: int, num_files: int, rows_per_group: int) -> Callable:
    """Map ``(file_path, row_group, row_offset)`` to a table row, or -1.

    Rows were written as ``np.array_split(range(n_rows), num_files)``, file
    ``i`` named ``...-{i:05d}.vpq``, each file cut into ``rows_per_group``
    row groups."""
    bounds = np.cumsum([0] + [len(a) for a in np.array_split(np.arange(n_rows), num_files)])
    pattern = re.compile(r"-(\d+)\.vpq$")

    def locate(file_path: str, row_group: int, row_offset: int) -> int:
        m = pattern.search(file_path)
        if m is None:
            return -1
        f = int(m.group(1))
        if not 0 <= f < num_files or row_group < 0 or not 0 <= row_offset < rows_per_group:
            return -1
        row = int(bounds[f]) + row_group * rows_per_group + row_offset
        return row if row < bounds[f + 1] else -1

    return locate


def exact_topk(
    X: np.ndarray, Q: np.ndarray, k: int, passing: Optional[List[Optional[np.ndarray]]] = None
) -> np.ndarray:
    """Exact top-``k`` rows per query in float64, ``-1`` past the passing
    rows.  ``passing[i]`` (a bool row mask, or None for all rows) restricts
    query ``i``."""
    X64 = X.astype(np.float64)
    x2 = (X64 * X64).sum(1)
    out = np.full((len(Q), k), -1, np.int64)
    for s in range(0, len(Q), BLOCK):
        q = Q[s : s + BLOCK].astype(np.float64)
        d = (q * q).sum(1)[:, None] - 2.0 * (q @ X64.T) + x2[None, :]
        for j in range(len(q)):
            row = d[j]
            mask = passing[s + j] if passing is not None else None
            if mask is not None:
                row = np.where(mask, row, np.inf)
            kk = min(k, int(np.isfinite(row).sum()))
            if kk == 0:
                continue
            idx = np.argpartition(row, kk - 1)[:kk]
            out[s + j, :kk] = idx[np.argsort(row[idx], kind="stable")]
    return out


def compare(
    X: np.ndarray,
    attribute: Optional[np.ndarray],
    probes: Sequence,
    answers: Sequence[Optional[List[Tuple[int, float]]]],
    limits: Dict[str, float],
) -> Dict[str, Dict[str, float]]:
    """Hold the answers to the reference.

    ``answers[i]`` is probe ``i``'s answer as ``(row, distance)`` pairs with
    ``row`` from :func:`row_locator` (-1 = names no row), or None when the
    probe got no answer.  Returns ``{name: {"value", "limit", "op"}}``."""
    unanswered = sum(a is None for a in answers)
    bad = 0
    worst = 0.0
    answered = [i for i, a in enumerate(answers) if a is not None]
    passing: List[Optional[np.ndarray]] = []
    for i in answered:
        p = probes[i]
        passing.append(None if p.lo is None else (attribute >= p.lo) & (attribute < p.hi))
    Q = np.stack([probes[i].query for i in answered]) if answered else np.zeros((0, X.shape[1]))
    k10 = 10
    truth = exact_topk(X, Q, k10, passing) if answered else np.zeros((0, k10), np.int64)
    recalls = []
    for j, i in enumerate(answered):
        p, ans = probes[i], answers[i]
        rows = np.array([r for r, _ in ans], np.int64)
        dists = np.array([d for _, d in ans], np.float64)
        n_pass = len(X) if passing[j] is None else int(passing[j].sum())
        ok = (
            len(ans) == min(p.k, n_pass)
            and (rows >= 0).all()
            and len(set(rows.tolist())) == len(rows)
            and np.isfinite(dists).all()
            and (np.diff(dists) >= 0).all()
        )
        if ok and passing[j] is not None:
            ok = bool(passing[j][rows].all())
        if not ok:
            bad += 1
        live = rows >= 0
        if live.any():
            q = p.query.astype(np.float64)
            x = X[rows[live]].astype(np.float64)
            want = ((x - q) ** 2).sum(1)
            scale = (q * q).sum() + (x * x).sum(1)
            worst = max(worst, float(np.max(np.abs(dists[live] - want) / scale)))
        t = truth[j][truth[j] >= 0]
        top = rows[:k10]
        recalls.append(len(set(top.tolist()) & set(t.tolist())) / max(1, len(t)))
    recall = float(np.mean(recalls)) if recalls else 0.0
    return {
        "unanswered": {"value": unanswered, "limit": 0, "op": "<="},
        "bad_hits": {"value": bad, "limit": 0, "op": "<="},
        "dist_err": {"value": worst, "limit": limits["dist_err"], "op": "<="},
        "recall_at_10": {"value": recall, "limit": limits["recall_at_10"], "op": ">="},
    }


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    """Every compared number within its limit."""
    for c in checks.values():
        ok = c["value"] <= c["limit"] if c["op"] == "<=" else c["value"] >= c["limit"]
        if not ok:
            return False
    return True
