"""Stage 2: order-invariant, performance-aware signature (paper §III-B).

A frequency-weighted Set Transformer aggregates the BBEs of the blocks
executed in an interval into one signature; a regression head predicts
log1p(CPI). Trained with the triple objective in repro.core.losses.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.losses import combined_stage2_loss, l2_normalize
from repro.models.layers import _init_array
from repro.models.set_transformer import (
    set_transformer_apply, set_transformer_init,
)


@dataclasses.dataclass(frozen=True)
class SignatureConfig:
    bbe_dim: int = 256
    d_model: int = 256
    sig_dim: int = 128
    num_heads: int = 4
    num_sabs: int = 2            # paper: two SABs suffice
    num_seeds: int = 1
    max_set: int = 64            # max distinct blocks per interval batch row
    w_r: float = 1.0             # CPI regression weight
    w_c: float = 0.5             # consistency weight
    dtype: str = "float32"


def signature_init(key, cfg: SignatureConfig):
    k1, k2, k3 = jax.random.split(key, 3)
    dtype = jnp.dtype(cfg.dtype)
    st, st_specs = set_transformer_init(
        k1, d_in=cfg.bbe_dim + 1,  # +1 log-frequency channel
        d_model=cfg.d_model, d_out=cfg.sig_dim, num_heads=cfg.num_heads,
        num_sabs=cfg.num_sabs, num_seeds=cfg.num_seeds, dtype=dtype)
    params = {
        "set_transformer": st,
        "cpi_head": {
            "w1": _init_array(k2, (cfg.sig_dim, cfg.d_model), dtype),
            "b1": jnp.zeros((cfg.d_model,), dtype),
            "w2": _init_array(k3, (cfg.d_model, 1), dtype),
            "b2": jnp.zeros((1,), dtype),
        },
    }
    specs = {
        "set_transformer": st_specs,
        "cpi_head": {"w1": ("embed", "ff"), "b1": ("ff",),
                     "w2": ("ff", None), "b2": (None,)},
    }
    return params, specs


def signature_specs(cfg: SignatureConfig):
    """Logical-axis specs without materializing a parameter tree (the
    specs are plain python; stash them during an abstract trace)."""
    box = {}

    def f():
        p, s = signature_init(jax.random.PRNGKey(0), cfg)
        box["s"] = s
        return p

    jax.eval_shape(f)
    return box["s"]


def signature_apply(params, cfg: SignatureConfig, bbes, freqs, mask,
                    impl: str = "xla"):
    """bbes: (B, N, bbe_dim); freqs: (B, N) execution counts; mask: (B, N).

    impl: attention backend, "xla" | "pallas" (see
    repro/kernels/__init__.py); both differentiate — the fused kernel
    has a custom VJP, so training can run impl="pallas".

    Returns (signature (B, sig_dim) L2-normalized, cpi_pred (B,) log1p-CPI)."""
    sig = set_transformer_apply(params["set_transformer"], bbes,
                                num_heads=cfg.num_heads, weights=freqs,
                                mask=mask, impl=impl)
    sig = l2_normalize(sig)
    h = params["cpi_head"]
    z = jnp.tanh(sig @ h["w1"].astype(sig.dtype) + h["b1"].astype(sig.dtype))
    cpi = (z @ h["w2"].astype(sig.dtype) + h["b2"].astype(sig.dtype))[..., 0]
    return sig, cpi


def stage2_loss(params, cfg: SignatureConfig, batch, impl: str = "xla"):
    """batch: anchor/positive/negative interval sets + anchor CPI.

    Each interval set: {bbes (B,N,D), freqs (B,N), mask (B,N)}; 'cpi' (B,).
    Differentiable under both impls: "pallas" runs the fused
    set-attention kernel's custom VJP (parity-tested to 1e-4 against the
    "xla" gradients)."""
    a_sig, a_cpi = signature_apply(params, cfg, batch["anchor"]["bbes"],
                                   batch["anchor"]["freqs"],
                                   batch["anchor"]["mask"], impl)
    p_sig, _ = signature_apply(params, cfg, batch["positive"]["bbes"],
                               batch["positive"]["freqs"],
                               batch["positive"]["mask"], impl)
    n_sig, _ = signature_apply(params, cfg, batch["negative"]["bbes"],
                               batch["negative"]["freqs"],
                               batch["negative"]["mask"], impl)
    return combined_stage2_loss(a_sig, p_sig, n_sig, a_cpi, batch["cpi"],
                                w_r=cfg.w_r, w_c=cfg.w_c)


def stage2_loss_from_rows(params, cfg: SignatureConfig, matrix, batch,
                          impl: str = "xla"):
    """`stage2_loss` over row-id triplet batches: the training twin of
    the pipeline's device-side set assembly.

    matrix: (V+1, bbe_dim) device-resident BBE matrix whose last row is
    the all-zero sentinel (BBEIndex.ext). batch[k] for k in anchor/
    positive/negative: {"rows" (B,N) int32 into `matrix` — sentinel in
    padded slots, "freqs" (B,N) f32, "mask" (B,N) bool}; batch["cpi"]
    (B,). The three (B,N,D) gathers happen here, inside jit, so each
    train step ships only integer ids from the host."""
    dense: Dict[str, Any] = {
        k: {"bbes": jnp.take(matrix, batch[k]["rows"], axis=0),
            "freqs": batch[k]["freqs"], "mask": batch[k]["mask"]}
        for k in ("anchor", "positive", "negative")}
    dense["cpi"] = batch["cpi"]
    return stage2_loss(params, cfg, dense, impl)


def predict_cpi(params, cfg: SignatureConfig, bbes, freqs, mask,
                impl: str = "xla"):
    """Inverse-transformed CPI prediction."""
    _, logcpi = signature_apply(params, cfg, bbes, freqs, mask, impl)
    return jnp.expm1(logcpi)
