"""Fused masked set-attention Pallas TPU kernel (Stage-2 SAB/PMA hot op).

One program per (batch row, head). Sets are small enough that, unlike
flash attention, keys need not stream: the full (N, M) score matrix
stays resident in VMEM and QKᵀ, the log-frequency key bias, the padding
mask, the softmax, and PV all fuse into a single kernel. At dh 64 in
fp32 the v5e compiler fits the forward and the backward in scoped VMEM
up to max_set 1024, and the forward alone up to 1536; the backward at
1536 and the forward at 2048 run out (tests/test_tpu_compile.py compiles
64 and 512, the multi-threaded regions' 8 threads x 64). The XLA path
materializes the (B, H, N, M) score and probability tensors in HBM
between each of those five steps; here they never leave the core.

The mask is folded into one additive fp32 bias per key (ops.py): 0 for
valid keys, NEG_INF for user-masked keys (same additive collapse the
jnp reference performs, so even fully-masked rows agree bitwise), and
2·NEG_INF for tile-padding keys so they underflow to zero weight below
either tier.

Backward pass (custom VJP): flash-style recompute. The forward saves
only (q, k, v, bias) — no probabilities, no stats — and the backward
kernel re-derives the (N, M) score matrix and softmax in VMEM per
(batch, head) program, then emits all four cotangents fused:

    dV = Pᵀ·dO        dP = dO·Vᵀ        δ = rowsum(dP ⊙ P)
    dS = P ⊙ (dP − δ)                   (softmax Jacobian contraction)
    dQ = scale·dS·K   dK = scale·dSᵀ·Q  db = Σ_{h,n} dS

Because the mask is additive, masked and padded keys have P exactly 0
(fp32 exp underflow below either NEG_INF tier), so their dK/dV/db are
exactly zero — gradients can never leak into masked set slots. db is
emitted per head as (B, H, 1, M) and reduced over heads by the wrapper.

Numerics policy (bf16 inputs at scale): all matmuls accumulate in fp32
(`preferred_element_type`), and SAB probabilities stay fp32 between the
softmax and the PV / dV / dP matmuls — storing P in bf16 would cost
~3 decimal digits exactly where signature fidelity is decided (measured
against the fp32 oracle the parity suite pins). Only the dQ/dK/dV/dO
tensors round to the input dtype at kernel boundaries.

Grid: (B, H). Blocks:
  q/dq:  (1, 1, N, dh) VMEM tiles       k/v/dk/dv: (1, 1, M, dh)
  bias:  (1, 1, M) fp32 over (B, 1, M), shared across heads (index_map
         drops h); the unit axis keeps the block's last two dims legal
  o/do:  (1, 1, N, dh)                  db: (1, 1, 1, M) fp32 per head
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _softmax_from_refs(q_ref, k_ref, b_ref, scale: float):
    """Shared fwd/bwd score recompute: (N, M) fp32 probabilities in VMEM."""
    q = q_ref[0, 0].astype(jnp.float32)                       # (N, dh)
    k = k_ref[0, 0].astype(jnp.float32)                       # (M, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = s + b_ref[0]                                          # (N, M) VMEM
    s = s - jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s)
    return q, k, p / jnp.sum(p, axis=-1, keepdims=True)


def _set_attn_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, *, scale: float):
    _, _, p = _softmax_from_refs(q_ref, k_ref, b_ref, scale)
    v = v_ref[0, 0].astype(jnp.float32)
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0, 0] = o.astype(o_ref.dtype)


def _set_attn_bwd_kernel(q_ref, k_ref, v_ref, b_ref, do_ref,
                         dq_ref, dk_ref, dv_ref, db_ref, *, scale: float):
    """Recompute P from (q, k, bias), then all four cotangents fused."""
    q, k, p = _softmax_from_refs(q_ref, k_ref, b_ref, scale)
    v = v_ref[0, 0].astype(jnp.float32)                       # (M, dh)
    do = do_ref[0, 0].astype(jnp.float32)                     # (N, dh)
    # dV = Pᵀ·dO: contract the query axis
    dv = jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    # dP = dO·Vᵀ, then the softmax Jacobian: dS = P ⊙ (dP − rowsum(dP ⊙ P))
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    delta = jnp.sum(dp * p, axis=-1, keepdims=True)           # (N, 1)
    ds = p * (dp - delta)                                     # (N, M)
    dq = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    dk = jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)
    db_ref[0, 0] = jnp.sum(ds, axis=0, keepdims=True)         # (1, M) this head


def _fwd_call(q, k, v, key_bias, interpret: bool):
    B, H, N, dh = q.shape
    M = k.shape[2]
    qkv_tile = lambda b, h: (b, h, 0, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_set_attn_kernel, scale=dh ** -0.5),
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((1, 1, N, dh), qkv_tile),
            pl.BlockSpec((1, 1, M, dh), qkv_tile),
            pl.BlockSpec((1, 1, M, dh), qkv_tile),
            pl.BlockSpec((1, 1, M), lambda b, h: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, N, dh), qkv_tile),
        out_shape=jax.ShapeDtypeStruct((B, H, N, dh), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(q, k, v, key_bias)


def _bwd_call(q, k, v, key_bias, do, interpret: bool):
    B, H, N, dh = q.shape
    M = k.shape[2]
    qkv_tile = lambda b, h: (b, h, 0, 0)  # noqa: E731
    out_shapes = (
        jax.ShapeDtypeStruct((B, H, N, dh), q.dtype),      # dq
        jax.ShapeDtypeStruct((B, H, M, dh), k.dtype),      # dk
        jax.ShapeDtypeStruct((B, H, M, dh), v.dtype),      # dv
        jax.ShapeDtypeStruct((B, H, 1, M), jnp.float32),   # db per head
    )
    return pl.pallas_call(
        functools.partial(_set_attn_bwd_kernel, scale=dh ** -0.5),
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((1, 1, N, dh), qkv_tile),
            pl.BlockSpec((1, 1, M, dh), qkv_tile),
            pl.BlockSpec((1, 1, M, dh), qkv_tile),
            pl.BlockSpec((1, 1, M), lambda b, h: (b, 0, 0)),
            pl.BlockSpec((1, 1, N, dh), qkv_tile),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, N, dh), qkv_tile),
            pl.BlockSpec((1, 1, M, dh), qkv_tile),
            pl.BlockSpec((1, 1, M, dh), qkv_tile),
            pl.BlockSpec((1, 1, 1, M), lambda b, h: (b, h, 0, 0)),
        ),
        out_shape=out_shapes,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(q, k, v, key_bias, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _set_attention(q, k, v, key_bias, interpret):
    return _fwd_call(q, k, v, key_bias, interpret)


def _set_attention_fwd(q, k, v, key_bias, interpret):
    # flash-style: save only the primals; the backward kernel recomputes
    # the VMEM score matrix instead of checkpointing (B, H, N, M) tensors
    return _fwd_call(q, k, v, key_bias, interpret), (q, k, v, key_bias)


def _set_attention_bwd(interpret, res, do):
    q, k, v, key_bias = res
    dq, dk, dv, db = _bwd_call(q, k, v, key_bias, do, interpret)
    return dq, dk, dv, db.sum(axis=1)   # reduce per-head db over heads


_set_attention.defvjp(_set_attention_fwd, _set_attention_bwd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def set_attention_pallas(q, k, v, key_bias, *, interpret: bool = False):
    """q: (B,H,N,dh); k,v: (B,H,M,dh); key_bias: (B,M) fp32 combined
    frequency-bias + mask + padding bias.

    Shapes must already be tile-aligned (ops.py pads); returns
    (B,H,N,dh) in q.dtype. Differentiable: the custom VJP runs the fused
    backward kernel (see module docstring), so impl="pallas" works for
    training, not just inference."""
    return _set_attention(q, k, v, key_bias[:, None, :], interpret)
