"""RWKV backbone for the Stage-1 basic-block encoder (paper §III-A-2).

Linear-time recurrent transformer with:
  - time-mix: token-shift interpolation feeding r/k/v/decay/β projections,
    then a *gated delta-rule* state update (the RWKV-7 "expressive dynamic
    state evolution" core the paper cites):
        S_t = (diag(w_t) S_{t-1}) (I − β_t k̂_t k̂_tᵀ) + β_t v_t k̂_tᵀ
        y_t = S_tᵀ r_t
    per head, with S ∈ R^{dh×dh}. Constant state, linear time.
  - channel-mix: token-shifted squared-ReLU FFN (classic RWKV).

The recurrence is exactly what `repro/kernels/wkv` implements as a chunked
Pallas kernel. `timemix_apply(impl=)` takes "scan", the jnp oracle, or
"pallas", the kernel: compiled on a TPU, interpreted elsewhere
(repro/kernels/__init__.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import check_backend
from repro.models.layers import _init_array, rmsnorm_apply, rmsnorm_init


def _token_shift(x, shift_state=None):
    """x_{t-1} stream: (B,S,d) -> previous token (zeros at t=0)."""
    if shift_state is None:
        return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    return jnp.concatenate([shift_state[:, None], x], axis=1)[:, :-1]


def timemix_init(key, d_model: int, num_heads: int, dtype):
    dh = d_model // num_heads
    ks = jax.random.split(key, 7)
    params = {
        "mu": jnp.full((5, d_model), 0.5, dtype),  # shift lerp for r,k,v,w,beta
        "wr": _init_array(ks[0], (d_model, d_model), dtype),
        "wk": _init_array(ks[1], (d_model, d_model), dtype),
        "wv": _init_array(ks[2], (d_model, d_model), dtype),
        "ww": _init_array(ks[3], (d_model, num_heads * dh), dtype, scale=0.02),
        "w_bias": jnp.full((d_model,), -2.0, jnp.float32),  # decay ~ sigmoid
        "wbeta": _init_array(ks[4], (d_model, num_heads), dtype, scale=0.02),
        "wo": _init_array(ks[5], (d_model, d_model), dtype),
        "ln_x": jnp.ones((d_model,), dtype),
    }
    specs = {
        "mu": (None, "embed_act"), "wr": ("embed", "heads"),
        "wk": ("embed", "heads"), "wv": ("embed", "heads"),
        "ww": ("embed", "heads"), "w_bias": (None,),
        "wbeta": ("embed", None), "wo": ("heads", "embed"),
        "ln_x": ("embed_act",),
    }
    return params, specs


def _project_rkvwb(params, x, x_prev, num_heads):
    B, S, d = x.shape
    dh = d // num_heads
    mu = params["mu"].astype(x.dtype)
    xr = x * mu[0] + x_prev * (1 - mu[0])
    xk = x * mu[1] + x_prev * (1 - mu[1])
    xv = x * mu[2] + x_prev * (1 - mu[2])
    xw = x * mu[3] + x_prev * (1 - mu[3])
    xb = x * mu[4] + x_prev * (1 - mu[4])
    r = (xr @ params["wr"].astype(x.dtype)).reshape(B, S, num_heads, dh)
    k = (xk @ params["wk"].astype(x.dtype)).reshape(B, S, num_heads, dh)
    v = (xv @ params["wv"].astype(x.dtype)).reshape(B, S, num_heads, dh)
    # per-channel decay in (0,1), biased toward remembering
    w = jax.nn.sigmoid((xw @ params["ww"].astype(x.dtype)).astype(jnp.float32)
                       + params["w_bias"]).reshape(B, S, num_heads, dh)
    beta = jax.nn.sigmoid(
        (xb @ params["wbeta"].astype(x.dtype)).astype(jnp.float32))  # (B,S,H)
    k = k / jnp.maximum(jnp.linalg.norm(k.astype(jnp.float32), axis=-1,
                                        keepdims=True), 1e-6).astype(k.dtype)
    return r, k, v, w, beta


def wkv_scan_ref(r, k, v, w, beta, state: Optional[jnp.ndarray] = None):
    """Pure-jnp oracle of the delta-rule recurrence.

    r,k,v: (B,S,H,dh); w: (B,S,H,dh) decay; beta: (B,S,H).
    Returns (y (B,S,H,dh), final_state (B,H,dh,dh)).  State layout: S[k_dim, v_dim].
    """
    B, S, H, dh = r.shape

    def step(Sm, xs):
        rt, kt, vt, wt, bt = xs  # (B,H,dh)...(B,H)
        Sm = Sm * wt[..., :, None]              # decay rows (k dim)
        Sk = jnp.einsum("bhkv,bhk->bhv", Sm, kt)
        delta = vt - Sk                          # (B,H,dh_v)
        Sm = Sm + bt[..., None, None] * (kt[..., :, None] * delta[..., None, :])
        y = jnp.einsum("bhkv,bhk->bhv", Sm, rt)
        return Sm, y

    S0 = state if state is not None else jnp.zeros((B, H, dh, dh), jnp.float32)
    xs = (r.transpose(1, 0, 2, 3).astype(jnp.float32),
          k.transpose(1, 0, 2, 3).astype(jnp.float32),
          v.transpose(1, 0, 2, 3).astype(jnp.float32),
          w.transpose(1, 0, 2, 3).astype(jnp.float32),
          beta.transpose(1, 0, 2).astype(jnp.float32))
    Sf, ys = jax.lax.scan(step, S0, xs)
    return ys.transpose(1, 0, 2, 3), Sf


def timemix_apply(params, x, num_heads: int, impl: str = "scan",
                  shift_state=None, wkv_state=None, return_state=False):
    B, S, d = x.shape
    x_prev = _token_shift(x, shift_state)
    r, k, v, w, beta = _project_rkvwb(params, x, x_prev, num_heads)
    if check_backend("impl", impl, ("scan", "pallas")) == "pallas":
        from repro.kernels.wkv.ops import wkv_chunked
        y, Sf = wkv_chunked(r, k, v, w, beta, state=wkv_state)
    else:
        y, Sf = wkv_scan_ref(r, k, v, w, beta, state=wkv_state)
    y = y.astype(x.dtype).reshape(B, S, d)
    y = rmsnorm_apply({"scale": params["ln_x"]}, y)
    out = y @ params["wo"].astype(x.dtype)
    if return_state:
        return out, x[:, -1], Sf
    return out


def channelmix_init(key, d_model: int, dtype, expand: int = 4):
    ks = jax.random.split(key, 2)
    params = {
        "mu": jnp.full((d_model,), 0.5, dtype),
        "wk": _init_array(ks[0], (d_model, expand * d_model), dtype),
        "wv": _init_array(ks[1], (expand * d_model, d_model), dtype),
    }
    specs = {"mu": ("embed_act",), "wk": ("embed", "ff"), "wv": ("ff", "embed")}
    return params, specs


def channelmix_apply(params, x, shift_state=None):
    x_prev = _token_shift(x, shift_state)
    mu = params["mu"].astype(x.dtype)
    xk = x * mu + x_prev * (1 - mu)
    h = jnp.square(jax.nn.relu(xk @ params["wk"].astype(x.dtype)))
    return h @ params["wv"].astype(x.dtype)


def rwkv_init_state(batch: int, d_model: int, num_heads: int):
    dh = d_model // num_heads
    return {
        "tm_shift": jnp.zeros((batch, d_model), jnp.float32),
        "cm_shift": jnp.zeros((batch, d_model), jnp.float32),
        "S": jnp.zeros((batch, num_heads, dh, dh), jnp.float32),
    }


def timemix_decode(params, x, shift, S, num_heads: int):
    """x: (B,1,d). Returns (out, new_shift, new_S)."""
    x_prev = shift[:, None].astype(x.dtype)
    r, k, v, w, beta = _project_rkvwb(params, x, x_prev, num_heads)
    y, Sf = wkv_scan_ref(r, k, v, w, beta, state=S)
    B, _, d = x.shape
    y = y.astype(x.dtype).reshape(B, 1, d)
    y = rmsnorm_apply({"scale": params["ln_x"]}, y)
    out = y @ params["wo"].astype(x.dtype)
    return out, x[:, 0].astype(jnp.float32), Sf


def channelmix_decode(params, x, shift):
    x_prev = shift[:, None].astype(x.dtype)
    mu = params["mu"].astype(x.dtype)
    xk = x * mu + x_prev * (1 - mu)
    h = jnp.square(jax.nn.relu(xk @ params["wk"].astype(x.dtype)))
    return h @ params["wv"].astype(x.dtype), x[:, 0].astype(jnp.float32)


def rwkv_block_init(key, d_model: int, num_heads: int, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    tm, tm_s = timemix_init(k1, d_model, num_heads, dtype)
    cm, cm_s = channelmix_init(k2, d_model, dtype)
    n1, n1_s = rmsnorm_init(d_model, dtype)
    n2, n2_s = rmsnorm_init(d_model, dtype)
    return ({"norm1": n1, "time_mix": tm, "norm2": n2, "channel_mix": cm},
            {"norm1": n1_s, "time_mix": tm_s, "norm2": n2_s, "channel_mix": cm_s})


def rwkv_block_apply(params, x, num_heads: int, impl: str = "scan"):
    x = x + timemix_apply(params["time_mix"],
                          rmsnorm_apply(params["norm1"], x), num_heads, impl)
    x = x + channelmix_apply(params["channel_mix"],
                             rmsnorm_apply(params["norm2"], x))
    return x
