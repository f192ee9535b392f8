"""k-means (Lloyd) in JAX — Stage-0 centroid training and PQ codebooks.

The paper's coordinator trains ``k = num_executors × partitions_per_executor``
centroids over a ~1 % sample (§5 Stage 0), and PQ training runs k-means per
subquantizer (§4.3).  Assignment uses the ``kmeans_assign`` kernel; the
update step is a jit'd segment-sum.  Empty clusters are re-seeded from the
points currently farthest from their centroid (standard Lloyd repair).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


def kmeans_plus_plus_init(
    points: np.ndarray, k: int, rng: np.random.Generator, sample_cap: int = 16384
) -> np.ndarray:
    """k-means++ seeding (host-side; runs once per training call)."""
    n = points.shape[0]
    if n > sample_cap:
        points = points[rng.choice(n, size=sample_cap, replace=False)]
        n = sample_cap
    centroids = np.empty((k, points.shape[1]), dtype=np.float32)
    centroids[0] = points[rng.integers(n)]
    d2 = np.full(n, np.inf, dtype=np.float64)
    for i in range(1, k):
        diff = points - centroids[i - 1]
        d2 = np.minimum(d2, np.einsum("nd,nd->n", diff, diff))
        total = d2.sum()
        if total <= 0:
            centroids[i:] = points[rng.integers(n, size=k - i)]
            break
        probs = d2 / total
        centroids[i] = points[rng.choice(n, p=probs)]
    return centroids


@functools.partial(jax.jit, static_argnames=("k",))
def _lloyd_step(
    points: jnp.ndarray, centroids: jnp.ndarray, k: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    assign, dist = ops.kmeans_assign(points, centroids, backend="ref")
    ones = jnp.ones((points.shape[0],), jnp.float32)
    counts = jax.ops.segment_sum(ones, assign, num_segments=k)
    sums = jax.ops.segment_sum(points, assign, num_segments=k)
    new_centroids = sums / jnp.maximum(counts, 1.0)[:, None]
    # keep old centroid where the cluster went empty (repaired on host)
    new_centroids = jnp.where((counts > 0)[:, None], new_centroids, centroids)
    return new_centroids, counts, jnp.sum(dist)


def train_kmeans(
    points: np.ndarray,
    k: int,
    *,
    iters: int = 20,
    seed: int = 0,
    repair_empty: bool = True,
) -> Tuple[np.ndarray, float]:
    """Returns (centroids (k, D) f32, final inertia)."""
    points = np.ascontiguousarray(points, dtype=np.float32)
    n = points.shape[0]
    if k > n:
        raise ValueError(f"k={k} > n={n}")
    rng = np.random.default_rng(seed)
    centroids = kmeans_plus_plus_init(points, k, rng)
    pts_j = jnp.asarray(points)
    inertia = float("inf")
    for _ in range(iters):
        cen_j, counts, inertia_j = _lloyd_step(pts_j, jnp.asarray(centroids), k)
        centroids = np.array(cen_j)  # a copy: the repair below writes to it
        counts = np.asarray(counts)
        inertia = float(inertia_j)
        if repair_empty and (counts == 0).any():
            # re-seed empty clusters at the points farthest from their centroid
            _, dist = ops.kmeans_assign(pts_j, jnp.asarray(centroids), backend="ref")
            far = np.argsort(-np.asarray(dist))
            empties = np.flatnonzero(counts == 0)
            centroids[empties] = points[far[: len(empties)]]
    return centroids, inertia


def assign(points: np.ndarray, centroids: np.ndarray) -> np.ndarray:
    """Host-facing nearest-centroid assignment (used for shard ownership)."""
    idx, _ = ops.kmeans_assign(jnp.asarray(points, dtype=jnp.float32), jnp.asarray(centroids, dtype=jnp.float32), backend="ref")
    return np.asarray(idx)
