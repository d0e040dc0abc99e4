"""Chunked Pallas TPU kernel for the gated delta-rule recurrence.

TPU adaptation (DESIGN.md §3): GPU RWKV kernels keep tiny per-thread
state and rely on warp shuffles; here the per-head state S (dh×dh, fp32)
is *resident in VMEM scratch* across the whole sequence, tokens stream
through in chunks of `chunk` rows, and each token update is two rank-1
VPU ops plus dh-wide reductions. Sequence chunks are a sequential grid
dimension ("arbitrary"), batch×head is parallel.

Grid: (B*H, S // chunk). Blocks:
  r/k/v/w: (1, chunk, dh) VMEM tiles      beta: (1, chunk, 1)
  y:       (1, chunk, dh) output tile
  S_out:   (1, dh, dh) written on the last chunk
Scratch:   S (dh, dh) fp32 — persists across the chunk dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, b_ref, s0_ref,
                y_ref, sf_ref, s_scratch, *, chunk: int):
    c = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(c == 0)
    def _init():
        s_scratch[...] = s0_ref[0]

    def token_step(t, S):
        rt = r_ref[0, t, :].astype(jnp.float32)      # (dh,)
        kt = k_ref[0, t, :].astype(jnp.float32)
        vt = v_ref[0, t, :].astype(jnp.float32)
        wt = w_ref[0, t, :].astype(jnp.float32)
        bt = b_ref[0, pl.ds(t, 1), :].astype(jnp.float32)  # (1, 1)
        S = S * wt[:, None]                          # decay rows (k dim)
        sk = jnp.sum(S * kt[:, None], axis=0)        # Sᵀ k  (dh_v,)
        delta = vt - sk
        # β scales the (1, dh) row first: Mosaic broadcasts a (1, 1)
        # value along lanes or sublanes, not both at once
        S = S + kt[:, None] * (bt * delta[None, :])  # rank-1 update
        y = jnp.sum(S * rt[:, None], axis=0)         # Sᵀ r
        y_ref[0, t, :] = y.astype(y_ref.dtype)
        return S

    S = jax.lax.fori_loop(0, chunk, token_step, s_scratch[...])
    s_scratch[...] = S

    @pl.when(c == nc - 1)
    def _finalize():
        sf_ref[0] = S


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_pallas(r, k, v, w, beta, state, *, chunk: int = 128,
               interpret: bool = False):
    """r,k,v,w: (BH, S, dh); beta: (BH, S, 1); state: (BH, dh, dh) fp32.

    Returns (y (BH,S,dh) fp32, final state (BH,dh,dh) fp32)."""
    BH, S, dh = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, f"seq {S} must divide chunk {chunk}"
    nc = S // chunk
    grid = (BH, nc)
    tile = lambda i, c: (i, c, 0)  # noqa: E731
    out_shapes = (
        jax.ShapeDtypeStruct((BH, S, dh), jnp.float32),
        jax.ShapeDtypeStruct((BH, dh, dh), jnp.float32),
    )
    return pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, dh), tile),
            pl.BlockSpec((1, chunk, dh), tile),
            pl.BlockSpec((1, chunk, dh), tile),
            pl.BlockSpec((1, chunk, dh), tile),
            pl.BlockSpec((1, chunk, 1), tile),
            pl.BlockSpec((1, dh, dh), lambda i, c: (i, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, dh), tile),
            pl.BlockSpec((1, dh, dh), lambda i, c: (i, 0, 0)),
        ),
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(r, k, v, w, beta, state)
