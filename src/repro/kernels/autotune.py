"""Measured (tile_q, tile_n) selection for the scan/rerank kernels.

The masked-scan family and the gather-rerank kernel were shipped with
hard-coded ``(8, 128)`` tiles.  Those are safe everywhere (the VMEM budget
tables in masked_topk.py / rerank.py are computed at them) but not optimal
everywhere: large shards amortize a taller query tile, small feature dims
leave MXU headroom for a wider N tile.  This module picks tiles per
``(shard row-count, D, flavor)`` from a ONE-TIME measured sweep:

- :func:`sweep` times each candidate tiling on a synthetic workload of the
  given shape/flavor (best-of-``repeat``, ``block_until_ready`` fencing)
  and records the winner in a JSON cache next to this file
  (``autotune_cache.json``, committed as a fixture so CI never measures).
- :func:`get_tiles` is the hot-path lookup ops.py calls when a wrapper is
  invoked with ``tile_q=None``: row counts bucket to the next power of two
  and D to the next multiple of 128 so one sweep generalizes; a cache miss
  returns :data:`DEFAULT_TILES`.  So does a fixture swept on another
  platform (``meta.backend``): CPU timings say nothing about TPU tiles.

Never-regress guarantee: the candidate list always contains
:data:`DEFAULT_TILES`, and a challenger must beat the default by more than
``HYSTERESIS`` (5%) to displace it — so in measurement noise the tuned
choice degenerates to exactly the old constants, and the acceptance
criterion "autotuned tiles never regress vs the constants" holds
structurally, not statistically.

CLI (regenerates the committed fixture)::

    PYTHONPATH=src python -m repro.kernels.autotune [--out PATH]
"""

from __future__ import annotations

import argparse
import functools
import json
import time
from pathlib import Path
from typing import Dict, Optional, Tuple

import jax

DEFAULT_TILES: Tuple[int, int] = (8, 128)

# every candidate keeps tile_n a multiple of 128 (lane width) and tile_q a
# multiple of 8 (f32 sublane) — see the Pallas guide's alignment rules
CANDIDATES: Tuple[Tuple[int, int], ...] = (
    DEFAULT_TILES,
    (8, 256),
    (16, 128),
    (16, 256),
    (32, 128),
)

HYSTERESIS = 0.05  # challenger must beat default by >5% to displace it

FLAVORS = ("exact", "exact_bf16", "exact_int8", "pq", "unified", "gather_rerank")

_CACHE_PATH = Path(__file__).with_name("autotune_cache.json")


def _bucket_rows(n_rows: int) -> int:
    """Next power of two, clamped to [128, 2**20] — one sweep point covers
    every shard whose row count rounds to the same bucket."""
    n = max(128, min(int(n_rows), 1 << 20))
    return 1 << (n - 1).bit_length()


def _bucket_dim(d: int) -> int:
    """Next multiple of 128 (the wrappers pad the feature dim there anyway)."""
    return max(128, ((int(d) + 127) // 128) * 128)


def cache_key(n_rows: int, d: int, flavor: str) -> str:
    return f"{flavor}:n{_bucket_rows(n_rows)}:d{_bucket_dim(d)}"


@functools.lru_cache(maxsize=1)
def _load_cache(path_str: str) -> Dict[str, Tuple[int, int]]:
    path = Path(path_str)
    if not path.exists():
        return {}
    try:
        raw = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError):  # unreadable fixture → defaults
        return {}
    if raw.get("meta", {}).get("backend") != jax.devices()[0].platform:
        return {}  # swept on another platform: its timings do not transfer
    tiles = raw.get("tiles", {})
    out: Dict[str, Tuple[int, int]] = {}
    for key, val in tiles.items():
        try:
            tq, tn = int(val[0]), int(val[1])
        except (TypeError, ValueError, IndexError):
            continue
        if (tq, tn) in CANDIDATES:  # never trust tiles we didn't sweep
            out[key] = (tq, tn)
    return out


def get_tiles(
    n_rows: int, d: int, flavor: str, cache_path: Optional[Path] = None
) -> Tuple[int, int]:
    """Tile choice for a kernel dispatch: measured winner when the sweep has
    seen this ``(rows, D, flavor)`` bucket on the running platform,
    :data:`DEFAULT_TILES` otherwise (cache miss, missing fixture, fixture
    swept on another platform, unknown flavor — never an error)."""
    cache = _load_cache(str(cache_path or _CACHE_PATH))
    return cache.get(cache_key(n_rows, d, flavor), DEFAULT_TILES)


def clear_cache() -> None:
    """Drop the memoized fixture (tests swap cache files)."""
    _load_cache.cache_clear()


# -- sweep (offline; never runs on the query path) ---------------------------


def _time_call(fn, repeat: int = 3) -> float:
    """Best-of-``repeat`` wall time of ``fn()`` (jax results are fenced)."""
    fn()  # warm-up: compile + first-touch
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn()
        for leaf in out if isinstance(out, (tuple, list)) else (out,):
            if hasattr(leaf, "block_until_ready"):
                leaf.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def _workload(flavor: str, n_rows: int, d: int, seed: int = 0):
    """Synthetic inputs for one sweep point, mirroring the executor's real
    call shapes (Q=32 coalesced queries, k=32, m=8/K=256 PQ geometry)."""
    import numpy as np

    from repro.kernels import ref

    rng = np.random.default_rng(seed)
    q_n, k = 32, 32
    queries = rng.standard_normal((q_n, d)).astype(np.float32)
    points = rng.standard_normal((n_rows, d)).astype(np.float32)
    mask = (rng.random(n_rows) > 0.4).astype(np.float32)
    if flavor in ("exact", "exact_bf16", "exact_int8"):
        dtype = {"exact": "f32", "exact_bf16": "bf16", "exact_int8": "int8"}[flavor]
        stored, x_scale = ref.quantize_points(points, dtype)
        return {
            "queries": queries, "points": stored, "mask": mask, "k": k,
            "dtype": dtype, "x_scale": x_scale,
        }
    if flavor == "pq":
        m_sub, K = 8, 256
        luts = rng.standard_normal((q_n, m_sub, K)).astype(np.float32)
        codes = rng.integers(0, K, size=(n_rows, m_sub)).astype(np.int32)
        return {"luts": luts, "codes": codes, "mask": mask, "k": k}
    if flavor == "unified":
        m_sub, K = 8, 256
        luts = rng.standard_normal((q_n, m_sub, K)).astype(np.float32)
        codes = rng.integers(0, K, size=(n_rows, m_sub)).astype(np.int32)
        masks = (rng.random((q_n, n_rows)) > 0.4).astype(np.float32)
        flav = rng.integers(0, 2, size=q_n).astype(bool)
        return {
            "queries": queries, "points": points, "luts": luts,
            "codes": codes, "masks": masks, "flavor": flav, "k": k,
        }
    if flavor == "gather_rerank":
        pool = rng.integers(0, n_rows, size=(q_n, 128)).astype(np.int32)
        return {"queries": queries, "points": points, "pool_ids": pool, "k": k}
    raise ValueError(f"unknown flavor {flavor!r}")


def _dispatch(flavor: str, work, tile_q: int, tile_n: int):
    from repro.kernels import ops

    if flavor in ("exact", "exact_bf16", "exact_int8"):
        return ops.masked_exact_topk(
            work["queries"], work["points"], work["mask"], work["k"],
            tile_q=tile_q, tile_n=tile_n,
            dtype=work["dtype"], x_scale=work["x_scale"],
        )
    if flavor == "pq":
        return ops.masked_pq_topk(
            work["luts"], work["codes"], work["mask"], work["k"],
            tile_q=tile_q, tile_n=tile_n,
        )
    if flavor == "unified":
        return ops.unified_masked_topk(
            work["queries"], work["points"], work["luts"], work["codes"],
            work["masks"], work["flavor"], work["k"],
            tile_q=tile_q, tile_n=tile_n,
        )
    if flavor == "gather_rerank":
        return ops.gather_rerank(
            work["queries"], work["points"], work["pool_ids"], work["k"],
            tile_q=tile_q, tile_n=tile_n,
        )
    raise ValueError(f"unknown flavor {flavor!r}")


def sweep_point(flavor: str, n_rows: int, d: int, repeat: int = 3):
    """Measure every candidate at one (rows, D, flavor) point.  Returns
    (winning tiles, {tiles: seconds}).  The default wins ties and anything
    within :data:`HYSTERESIS` of it."""
    work = _workload(flavor, n_rows, d)
    times: Dict[Tuple[int, int], float] = {}
    for tq, tn in CANDIDATES:
        times[(tq, tn)] = _time_call(
            lambda tq=tq, tn=tn: _dispatch(flavor, work, tq, tn), repeat=repeat
        )
    base = times[DEFAULT_TILES]
    best, best_t = DEFAULT_TILES, base
    for tiles, t in times.items():
        if t < best_t and t < base * (1.0 - HYSTERESIS):
            best, best_t = tiles, t
    return best, times


def sweep(
    out_path: Optional[Path] = None,
    flavors=FLAVORS,
    row_counts=(2048, 8192),
    dims=(128, 256),
    repeat: int = 3,
) -> Dict[str, Tuple[int, int]]:
    """Run the full sweep and write the JSON fixture.  Keys collapse by
    bucket, so overlapping (rows, dims) points just overwrite each other."""
    tiles: Dict[str, Tuple[int, int]] = {}
    for flavor in flavors:
        for n_rows in row_counts:
            for d in dims:
                best, times = sweep_point(flavor, n_rows, d, repeat=repeat)
                key = cache_key(n_rows, d, flavor)
                tiles[key] = best
                print(
                    f"{key}: {best}  "
                    + "  ".join(
                        f"{tq}x{tn}={t * 1e3:.2f}ms" for (tq, tn), t in times.items()
                    )
                )
    payload = {
        "meta": {
            "backend": jax.devices()[0].platform,
            "candidates": [list(c) for c in CANDIDATES],
            "hysteresis": HYSTERESIS,
            "workload": "Q=32 k=32 m=8 K=256 best-of-%d" % repeat,
        },
        "tiles": {k: list(v) for k, v in sorted(tiles.items())},
    }
    path = out_path or _CACHE_PATH
    path.write_text(json.dumps(payload, indent=2) + "\n")
    clear_cache()
    return tiles


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=Path, default=_CACHE_PATH)
    ap.add_argument("--repeat", type=int, default=3)
    args = ap.parse_args()
    sweep(out_path=args.out, repeat=args.repeat)


if __name__ == "__main__":
    main()
