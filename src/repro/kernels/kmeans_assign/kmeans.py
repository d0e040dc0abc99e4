"""Tiled nearest-centroid Pallas TPU kernels for universal clustering.

The cross-program experiment assigns 100k+ interval signatures to K
universal archetypes every k-means iteration. Two kernels share the
distance tile math ((N,d)×(d,K) matmul + row argmin, scores in VMEM):

  `kmeans_assign_pallas`   assignment only — per-row (argmin, min-dist).
  `kmeans_update_pallas`   one full k-means step: assignment fused with
      the segment reduction the centroid update needs. Per grid step the
      block's rows are one-hot scattered into fp32 (K,d) sum / (1,K)
      count accumulators that live in the output blocks (every step maps
      to block 0, "arbitrary" semantics), plus the masked inertia — so
      the restart loop never materializes the (N,K) one-hot matrix in
      HBM nor round-trips per-row assignments to the host.

Grid: (N // block_n,). Blocks: x (block_n, d); c (K, d) constant;
assignment outputs are (block_n,) int32/f32; the update takes validity
as a (block_n, 1) column and accumulates into the (K, d) sums, (1, K)
counts and (1, 1) inertia blocks (2-D, as Mosaic requires).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Full f32 contractions. Mosaic's default rounds f32 operands to bf16 on
# the MXU: near-tied distances then pick another centroid (3.6% of store
# rows on a v5e against the f32 reference) and centroid sums lose their
# low bits. With K = 14 the kernels are bound by reading x, not by the
# extra MXU passes.
_F32 = jax.lax.Precision.HIGHEST


def _kmeans_kernel(x_ref, c_ref, a_ref, d_ref):
    x = x_ref[...].astype(jnp.float32)                      # (Bn, d)
    c = c_ref[...].astype(jnp.float32)                      # (K, d)
    x2 = jnp.sum(jnp.square(x), axis=-1, keepdims=True)     # (Bn, 1)
    c2 = jnp.sum(jnp.square(c), axis=-1)                    # (K,)
    xc = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                             precision=_F32,
                             preferred_element_type=jnp.float32)
    d2 = x2 - 2.0 * xc + c2[None, :]                        # (Bn, K)
    a_ref[...] = jnp.argmin(d2, axis=-1).astype(jnp.int32)
    d_ref[...] = jnp.min(d2, axis=-1)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign_pallas(x, centroids, *, block_n: int = 1024,
                         interpret: bool = False):
    """x: (N,d); centroids: (K,d); N % block_n == 0 (wrapper pads)."""
    N, d = x.shape
    K = centroids.shape[0]
    block_n = min(block_n, N)
    assert N % block_n == 0
    grid = (N // block_n,)
    return pl.pallas_call(
        _kmeans_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((K, d), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((N,), jnp.int32),
            jax.ShapeDtypeStruct((N,), jnp.float32),
        ),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(x, centroids)


def _kmeans_update_kernel(x_ref, c_ref, v_ref, s_ref, n_ref, i_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        i_ref[...] = jnp.zeros_like(i_ref)

    # every value stays 2-D: Mosaic cannot relayout 1-D row vectors into
    # columns, so the argmin is the first column that attains the row min
    x = x_ref[...].astype(jnp.float32)                      # (Bn, d)
    c = c_ref[...].astype(jnp.float32)                      # (K, d)
    v = v_ref[...].astype(jnp.float32)                      # (Bn, 1)
    x2 = jnp.sum(jnp.square(x), axis=-1, keepdims=True)
    c2 = jnp.sum(jnp.square(c), axis=-1)
    xc = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                             precision=_F32,
                             preferred_element_type=jnp.float32)
    d2 = x2 - 2.0 * xc + c2[None, :]                        # (Bn, K)
    K = c.shape[0]
    dmin = jnp.min(d2, axis=-1, keepdims=True)              # (Bn, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    a = jnp.min(jnp.where(d2 == dmin, cols, K), axis=-1, keepdims=True)
    onehot = jnp.where(a == cols, v, 0.0)                   # (Bn, K)
    s_ref[...] += jax.lax.dot_general(                      # (K, d)
        onehot, x, (((0,), (0,)), ((), ())), precision=_F32,
        preferred_element_type=jnp.float32)
    n_ref[...] += jnp.sum(onehot, axis=0, keepdims=True)    # (1, K)
    i_ref[...] += jnp.sum(dmin * v, axis=0, keepdims=True)  # (1, 1)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_update_pallas(x, centroids, valid, *, block_n: int = 1024,
                         interpret: bool = False):
    """One fused assignment + segment-reduce over the valid rows.

    x: (N,d); centroids: (K,d); valid: (N,1) mask (0 kills padded rows).
    Returns (sums (K,d) f32, counts (1,K) f32, inertia (1,1) f32) — the
    per-cluster weighted sums / member counts / total min-distance that
    a k-means step needs. N % block_n == 0 (the wrapper pads).
    """
    N, d = x.shape
    K = centroids.shape[0]
    block_n = min(block_n, N)
    assert N % block_n == 0
    grid = (N // block_n,)
    return pl.pallas_call(
        _kmeans_update_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((K, d), lambda i: (0, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((K, d), lambda i: (0, 0)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((K, d), jnp.float32),
            jax.ShapeDtypeStruct((1, K), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(x, centroids, valid)
