"""Pallas TPU kernels for the paper's compute hot spots.

Four kernel families, each with a pure-jnp oracle in
:mod:`repro.kernels.ref` and a padded/jit'd public wrapper in
:mod:`repro.kernels.ops`:

- ``pq_scan``       — PQ asymmetric-distance scan (one-hot-matmul MXU form)
- ``rerank``        — tiled exact-distance matrix for the rerank stage
- ``kmeans_assign`` — K-tiled nearest-centroid assignment (running min)
- ``masked_topk``   — mask-aware exact / PQ-ADC top-k for filtered probes
  (predicate bitmask fused into the tile, in-kernel top-k reduction)

On CPU the kernels run under ``interpret=True`` for validation; production
CPU paths dispatch to the oracles (see ops.py backend rules).

Every f32 contraction in the kernels (and in the oracles) runs at
``Precision.HIGHEST``: on TPU the default f32 matmul is one bf16 pass, and
the expanded distance form |q|^2 - 2 q.x + |x|^2 turns its rounding into a
percent-level distance error (2.4e-2 relative on a TPU v5e at D=768).  The
one-hot contractions (ADC lookups, the pooled score gather) need it too:
at one bf16 pass they round the LUT entries and scores they select.
"""

from repro.kernels.ops import (  # noqa: F401
    exact_distances,
    exact_topk,
    kmeans_assign,
    masked_exact_topk,
    masked_exact_topk_multi,
    masked_pq_topk,
    masked_pq_topk_multi,
    pq_scan,
    pq_scan_topk,
)
