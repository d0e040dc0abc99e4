"""jit'd wrappers with padding to the block size."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.kmeans_assign.kmeans import (
    kmeans_assign_pallas, kmeans_update_pallas,
)


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """None -> compiled where the kernel can lower (TPU), interpreter
    elsewhere — the same auto rule the benchmarks use."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def kmeans_assign(x, centroids, block_n: int = 1024,
                  interpret: Optional[bool] = False):
    """x: (N,d); centroids: (K,d) -> (assign (N,) int32, dist2 (N,) f32)."""
    interpret = _resolve_interpret(interpret)
    N = x.shape[0]
    bn = min(block_n, max(8, N))
    pad = (-N) % bn
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    a, d2 = kmeans_assign_pallas(x, centroids, block_n=bn,
                                 interpret=interpret)
    return a[:N], d2[:N]


def kmeans_update(x, centroids, valid=None, block_n: int = 1024,
                  interpret: Optional[bool] = False):
    """One fused k-means step: assignment + per-cluster segment reduce.

    x: (N,d); centroids: (K,d); valid: optional (N,) mask (None = all
    rows valid; padding added here is always masked out). Returns
    (sums (K,d), counts (K,), inertia scalar), all f32.
    """
    interpret = _resolve_interpret(interpret)
    N = x.shape[0]
    if valid is None:
        valid = jnp.ones((N,), jnp.float32)
    bn = min(block_n, max(8, N))
    pad = (-N) % bn
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        valid = jnp.pad(valid.astype(jnp.float32), ((0, pad),))
    sums, counts, inertia = kmeans_update_pallas(
        x, centroids, valid.astype(jnp.float32)[:, None], block_n=bn,
        interpret=interpret)
    return sums, counts[0], inertia[0, 0]
