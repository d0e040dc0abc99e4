"""Set Transformer (Lee et al. 2019) for Stage-2 aggregation (paper §III-B).

Encoder = 2 stacked SABs (self-attention blocks), decoder = PMA (pooling by
multi-head attention with learned seed vectors). Strictly permutation-
invariant: no positional information anywhere, masks handle padding.

Execution-frequency weighting (Fig. 1 bottom): the per-element log-
frequency is (a) concatenated to the input features and (b) added as an
attention-logit bias on keys, so frequent blocks both carry the
information and draw proportionally more attention — while keeping exact
order invariance.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import (
    _init_array, dense_apply, dense_init, layernorm_apply, layernorm_init,
)

NEG_INF = -2.0 ** 30
ATTENTION_BACKENDS = ("xla", "pallas")   # jnp reference, fused kernel


def _mha_init(key, d: int, num_heads: int, dtype):
    ks = jax.random.split(key, 4)
    params = {
        "wq": _init_array(ks[0], (d, d), dtype),
        "wk": _init_array(ks[1], (d, d), dtype),
        "wv": _init_array(ks[2], (d, d), dtype),
        "wo": _init_array(ks[3], (d, d), dtype),
    }
    specs = {k: ("embed", "heads") for k in ("wq", "wk", "wv")}
    specs["wo"] = ("heads", "embed")
    return params, specs


def _mha_apply(params, xq, xk, num_heads: int, key_bias=None, key_mask=None,
               impl: str = "xla"):
    """xq: (B,N,d), xk: (B,M,d). key_bias: (B,M) additive logit bias.

    impl: "xla" (pure jnp) | "pallas", the fused kernel in
    repro/kernels/set_attention; the pipeline and the Stage-2 engine
    check the value where they take it."""
    B, N, d = xq.shape
    M = xk.shape[1]
    dh = d // num_heads
    q = (xq @ params["wq"].astype(xq.dtype)).reshape(B, N, num_heads, dh)
    k = (xk @ params["wk"].astype(xq.dtype)).reshape(B, M, num_heads, dh)
    v = (xk @ params["wv"].astype(xq.dtype)).reshape(B, M, num_heads, dh)
    if impl == "pallas":
        from repro.kernels.set_attention.ops import masked_set_attention
        o = masked_set_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), key_bias=key_bias, key_mask=key_mask)
        o = o.transpose(0, 2, 1, 3).reshape(B, N, d)
    else:
        s = jnp.einsum("bnhd,bmhd->bhnm", q, k).astype(jnp.float32) * (dh ** -0.5)
        if key_bias is not None:
            s = s + key_bias[:, None, None, :]
        if key_mask is not None:
            s = s + jnp.where(key_mask, 0.0, NEG_INF)[:, None, None, :]
        p = jax.nn.softmax(s, axis=-1).astype(xq.dtype)
        o = jnp.einsum("bhnm,bmhd->bnhd", p, v).reshape(B, N, d)
    return o @ params["wo"].astype(xq.dtype)


def _mab_init(key, d: int, num_heads: int, d_ff: int, dtype):
    ks = jax.random.split(key, 4)
    mha, mha_s = _mha_init(ks[0], d, num_heads, dtype)
    ff1, ff1_s = dense_init(ks[1], d, d_ff, dtype, bias=True,
                            in_axis="embed", out_axis="ff")
    ff2, ff2_s = dense_init(ks[2], d_ff, d, dtype, bias=True,
                            in_axis="ff", out_axis="embed")
    n1, n1_s = layernorm_init(d, dtype)
    n2, n2_s = layernorm_init(d, dtype)
    return ({"mha": mha, "ff1": ff1, "ff2": ff2, "norm1": n1, "norm2": n2},
            {"mha": mha_s, "ff1": ff1_s, "ff2": ff2_s, "norm1": n1_s,
             "norm2": n2_s})


def _mab_apply(params, xq, xk, num_heads: int, key_bias=None, key_mask=None,
               impl: str = "xla"):
    h = layernorm_apply(params["norm1"],
                        xq + _mha_apply(params["mha"], xq, xk, num_heads,
                                        key_bias, key_mask, impl))
    ff = dense_apply(params["ff2"], jax.nn.gelu(dense_apply(params["ff1"], h)))
    return layernorm_apply(params["norm2"], h + ff)


def set_transformer_init(key, d_in: int, d_model: int, d_out: int,
                         num_heads: int = 4, num_sabs: int = 2,
                         num_seeds: int = 1, d_ff: int = 0,
                         dtype=jnp.float32):
    """d_in includes any frequency feature channels."""
    d_ff = d_ff or 2 * d_model
    ks = jax.random.split(key, num_sabs + 4)
    in_proj, in_s = dense_init(ks[0], d_in, d_model, dtype, bias=True,
                               in_axis=None, out_axis="embed")
    sabs, sab_specs = [], []
    for i in range(num_sabs):
        p, s = _mab_init(ks[1 + i], d_model, num_heads, d_ff, dtype)
        sabs.append(p)
        sab_specs.append(s)
    pma, pma_s = _mab_init(ks[num_sabs + 1], d_model, num_heads, d_ff, dtype)
    seeds = _init_array(ks[num_sabs + 2], (num_seeds, d_model), dtype, scale=0.5)
    out_proj, out_s = dense_init(ks[num_sabs + 3], d_model * num_seeds, d_out,
                                 dtype, bias=True, in_axis="embed",
                                 out_axis=None)
    params = {"in_proj": in_proj, "sabs": sabs, "pma": pma, "seeds": seeds,
              "out_proj": out_proj}
    specs = {"in_proj": in_s, "sabs": sab_specs, "pma": pma_s,
             "seeds": ("pool", "embed"), "out_proj": out_s}
    return params, specs


def set_transformer_apply(params, x, *, num_heads: int = 4,
                          weights: Optional[jnp.ndarray] = None,
                          mask: Optional[jnp.ndarray] = None,
                          impl: str = "xla"):
    """x: (B, N, d_in) set elements; weights: (B, N) nonneg frequencies;
    mask: (B, N) valid flags. Returns (B, d_out) signature.

    impl selects the attention backend ("xla" | "pallas"); both
    differentiate — the fused kernel has a custom VJP (flash-style
    recompute backward), so Stage-2 training can run the Pallas path end
    to end."""
    B, N, _ = x.shape
    key_bias = None
    if weights is not None:
        logw = jnp.log1p(weights.astype(jnp.float32))
        # normalize so the bias is scale-free across interval lengths
        denom = jnp.maximum(logw.max(axis=-1, keepdims=True), 1e-6)
        key_bias = logw / denom
        x = jnp.concatenate([x, (logw / denom)[..., None].astype(x.dtype)],
                            axis=-1)
    h = dense_apply(params["in_proj"], x)
    for sab in params["sabs"]:
        h = _mab_apply(sab, h, h, num_heads, key_bias, mask, impl)
    seeds = jnp.broadcast_to(params["seeds"][None], (B,) + params["seeds"].shape)
    pooled = _mab_apply(params["pma"], seeds.astype(h.dtype), h, num_heads,
                        key_bias, mask, impl)
    pooled = pooled.reshape(B, -1)
    return dense_apply(params["out_proj"], pooled)
