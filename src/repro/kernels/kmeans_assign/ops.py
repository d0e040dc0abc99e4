"""Public ops with padding to the block size."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import compiled_on_tpu
from repro.kernels.kmeans_assign.kmeans import (
    kmeans_assign_pallas, kmeans_update_pallas,
)

_assign = compiled_on_tpu(kmeans_assign_pallas)
_update = compiled_on_tpu(kmeans_update_pallas)


def kmeans_assign(x, centroids, block_n: int = 1024):
    """x: (N,d); centroids: (K,d) -> (assign (N,) int32, dist2 (N,) f32)."""
    N = x.shape[0]
    bn = min(block_n, max(8, N))
    pad = (-N) % bn
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    a, d2 = _assign(x, centroids, block_n=bn)
    return a[:N], d2[:N]


def kmeans_update(x, centroids, valid=None, block_n: int = 1024):
    """One fused k-means step: assignment + per-cluster segment reduce.

    x: (N,d); centroids: (K,d); valid: optional (N,) mask (None = all
    rows valid; padding added here is always masked out). Returns
    (sums (K,d), counts (K,), inertia scalar), all f32.
    """
    N = x.shape[0]
    if valid is None:
        valid = jnp.ones((N,), jnp.float32)
    bn = min(block_n, max(8, N))
    pad = (-N) % bn
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        valid = jnp.pad(valid.astype(jnp.float32), ((0, pad),))
    sums, counts, inertia = _update(
        x, centroids, valid.astype(jnp.float32)[:, None], block_n=bn)
    return sums, counts[0], inertia[0, 0]
