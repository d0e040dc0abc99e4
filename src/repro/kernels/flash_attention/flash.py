"""Streaming-softmax (flash) attention Pallas TPU kernel.

Grid: (B, H, n_q_blocks, n_kv_blocks), kv innermost + sequential.
Blocks (VMEM):
  q:   (block_q, D) tile of head h          — MXU-aligned (block_q % 128 on TPU)
  k/v: (block_k, D) tile of kv-head h//g    — GQA handled in the index_map,
                                              no materialized head repeat
  o:   (block_q, D) written on the last kv block
Scratch: m,l (block_q, 1) fp32 running max/denominator; acc (block_q, D).

Causal/window masking is per-element inside a block; blocks entirely in
the masked region are skipped via pl.when on the block indices (this is
the O(S·W) path for windowed attention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, causal: bool, window: int,
                  scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = jnp.bool_(True)
    if causal:
        run = run & (ki * block_k <= qi * block_q + block_q - 1)
    if window > 0:
        run = run & ((ki + 1) * block_k - 1 >= qi * block_q - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (Bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (Bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal or window > 0:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = jnp.ones((block_q, block_k), jnp.bool_)
            if causal:
                mask = mask & (kpos <= qpos)
            if window > 0:
                mask = mask & (qpos - kpos < window)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                                  # (Bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _out():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                 block_q: int = 128, block_k: int = 128,
                 interpret: bool = False):
    """q: (B,H,S,D); k,v: (B,K,T,D). Returns (B,H,S,D) in q.dtype."""
    B, H, S, D = q.shape
    K, T = k.shape[1], k.shape[2]
    g = H // K
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0
    grid = (B, H, S // block_q, T // block_k)
    return pl.pallas_call(
        functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, window=window, scale=D ** -0.5),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(q, k, v)
