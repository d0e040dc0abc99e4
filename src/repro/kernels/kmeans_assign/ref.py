"""Pure-jnp oracle: nearest-centroid assignment."""
from __future__ import annotations

import jax.numpy as jnp


def kmeans_assign_reference(x, centroids):
    """x: (N,d); centroids: (K,d). Returns (assign (N,) int32, dist2 (N,) f32)."""
    x2 = jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    c2 = jnp.sum(jnp.square(centroids.astype(jnp.float32)), axis=-1)
    xc = x.astype(jnp.float32) @ centroids.astype(jnp.float32).T
    d2 = x2 - 2.0 * xc + c2[None, :]
    return jnp.argmin(d2, axis=-1).astype(jnp.int32), jnp.min(d2, axis=-1)


def kmeans_update_reference(x, centroids, valid):
    """Fused k-means step oracle: assignment + masked segment reduction.

    x: (N,d); centroids: (K,d); valid: (N,) mask. Returns
    (sums (K,d) f32, counts (K,) f32, inertia (1,) f32) — the same
    reductions as `kmeans_update_pallas` (fp32 accumulators everywhere).
    """
    import jax
    xf = x.astype(jnp.float32)
    a, d2 = kmeans_assign_reference(xf, centroids)
    v = valid.astype(jnp.float32)
    onehot = jax.nn.one_hot(a, centroids.shape[0],
                            dtype=jnp.float32) * v[:, None]
    sums = jax.lax.dot_general(onehot, xf, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    counts = jnp.sum(onehot, axis=0)
    inertia = jnp.sum(d2 * v)[None]
    return sums, counts, inertia
