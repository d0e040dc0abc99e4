"""jit'd wrapper: pads set sizes to TPU tiles, folds bias+mask+padding
into the kernel's single additive key bias.

Fully differentiable: the kernel carries a custom VJP (set_attn.py), and
the padding/slicing here is plain jnp, so `jax.grad` through
`masked_set_attention` runs the fused backward kernel. Cotangents of
padded key slots are sliced away; `key_bias` receives its true gradient
(summed over heads and queries); the boolean `key_mask` is non-diff."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import compiled_on_tpu
from repro.kernels.set_attention.set_attn import NEG_INF, set_attention_pallas

_set_attention = compiled_on_tpu(set_attention_pallas)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def masked_set_attention(q, k, v, key_bias=None, key_mask=None):
    """Fused masked, frequency-weighted set attention.

    q: (B,H,N,dh); k,v: (B,H,M,dh); key_bias: (B,M) additive logit bias;
    key_mask: (B,M) valid flags. Returns (B,H,N,dh) in q.dtype.

    Pads N to the fp32 sublane (8) and M to the lane width (128) of the
    VMEM-resident score matrix. Masked keys get an additive NEG_INF
    (matching the reference's fp32 collapse even for fully-masked rows);
    padded keys get 2*NEG_INF so they underflow to zero weight below
    either tier and the result is independent of the padding."""
    B, H, N, dh = q.shape
    M = k.shape[2]
    Np, Mp = _round_up(N, 8), _round_up(M, 128)
    if Np != N:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Np - N), (0, 0)))
    if Mp != M:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Mp - M), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Mp - M), (0, 0)))
    bias = jnp.zeros((B, M), jnp.float32)
    if key_bias is not None:
        bias = bias + key_bias.astype(jnp.float32)
    if key_mask is not None:
        bias = bias + jnp.where(key_mask, 0.0, NEG_INF)
    pad_bias = jnp.full((B, Mp - M), 2.0 * NEG_INF, jnp.float32)
    bias = jnp.concatenate([bias, pad_bias], axis=1)
    o = _set_attention(q, k, v, bias)
    return o[:, :, :N] if Np != N else o
