"""Model weights from the seed, made on the device in one jitted call.

The trees have the layout the program's Stage-1 encoder
(`repro.core.bbe`) and Stage-2 signature (`repro.core.signature`) read;
the plain reference in `chipbench.reference` reads the same trees. The
benchmark, not the program, makes them, so the reference takes nothing
that the program has made. Pre-training heads are left out: no served
path reads them.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def _normal(key, shape, scale=None):
    if scale is None:                      # fan-in scaled
        scale = 1.0 / math.sqrt(max(1, shape[-2] if len(shape) > 1
                                    else shape[-1]))
    return jax.random.normal(key, shape, jnp.float32) * scale


def _bbe(key, bbe: Dict, vocab: Tuple[int, ...]):
    d = sum(bbe["dim_embeds"])
    L, H = bbe["num_layers"], bbe["num_heads"]
    ks = iter(jax.random.split(key, 32))
    u = lambda shape, lo, hi: jax.random.uniform(  # noqa: E731
        next(ks), shape, jnp.float32, lo, hi)
    stack = lambda shape, scale=None: _normal(  # noqa: E731
        next(ks), (L,) + shape, scale)
    blocks = {
        "norm1": {"scale": u((L, d), 0.8, 1.2)},
        "time_mix": {
            "mu": u((L, 5, d), 0.0, 1.0),
            "wr": stack((d, d)), "wk": stack((d, d)), "wv": stack((d, d)),
            "ww": stack((d, d), 0.02),
            "w_bias": u((L, d), -3.0, -1.0),
            "wbeta": stack((d, H), 0.02),
            "wo": stack((d, d)),
            "ln_x": u((L, d), 0.8, 1.2),
        },
        "norm2": {"scale": u((L, d), 0.8, 1.2)},
        "channel_mix": {
            "mu": u((L, d), 0.0, 1.0),
            "wk": stack((d, 4 * d)),
            "wv": stack((4 * d, d)),
        },
    }
    return {
        "embeds": [_normal(next(ks), (v, e), 0.02)
                   for v, e in zip(vocab, bbe["dim_embeds"])],
        "blocks": blocks,
        "final_norm": {"scale": u((d,), 0.8, 1.2)},
        "pool": {"Wa": _normal(next(ks), (d, d)),
                 "ba": _normal(next(ks), (d,), 0.02),
                 "ua": _normal(next(ks), (d,), 0.1)},
        "out_proj": _normal(next(ks), (d, bbe["bbe_dim"])),
    }


def _dense(key, d_in, d_out):
    k1, k2 = jax.random.split(key)
    return {"w": _normal(k1, (d_in, d_out)),
            "b": _normal(k2, (d_out,), 0.02)}


def _layernorm(key, d):
    k1, k2 = jax.random.split(key)
    return {"scale": jax.random.uniform(k1, (d,), jnp.float32, 0.8, 1.2),
            "bias": _normal(k2, (d,), 0.02)}


def _mab(key, d):
    ks = jax.random.split(key, 8)
    return {"mha": {n: _normal(k, (d, d))
                    for n, k in zip(("wq", "wk", "wv", "wo"), ks[:4])},
            "ff1": _dense(ks[4], d, 2 * d),
            "ff2": _dense(ks[5], 2 * d, d),
            "norm1": _layernorm(ks[6], d),
            "norm2": _layernorm(ks[7], d)}


def _sig(key, sig: Dict):
    d = sig["d_model"]
    ks = jax.random.split(key, sig["num_sabs"] + 6)
    return {
        "set_transformer": {
            "in_proj": _dense(ks[0], sig["bbe_dim"] + 1, d),
            "sabs": [_mab(ks[1 + i], d) for i in range(sig["num_sabs"])],
            "pma": _mab(ks[-5], d),
            "seeds": _normal(ks[-4], (sig["num_seeds"], d), 0.5),
            "out_proj": _dense(ks[-3], d * sig["num_seeds"],
                               sig["sig_dim"]),
        },
        "cpi_head": {"w1": _normal(ks[-2], (sig["sig_dim"], d)),
                     "b1": jnp.zeros((d,), jnp.float32),
                     "w2": _normal(ks[-1], (d, 1)),
                     "b2": jnp.zeros((1,), jnp.float32)},
    }


def key_for(seed: int):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, bbe_items, sig_items, vocab):
    k1, k2 = jax.random.split(key)
    return _bbe(k1, dict(bbe_items), vocab), _sig(k2, dict(sig_items))


def make_weights(seed: int, bbe: Dict, sig: Dict, vocab: Tuple[int, ...]):
    """(Stage-1 params, Stage-2 params), float32, on the default device.
    `bbe`/`sig` are the configuration's width dicts; `vocab` the six
    token-dimension vocabulary sizes."""
    freeze = lambda d: tuple(sorted(  # noqa: E731
        (k, tuple(v) if isinstance(v, list) else v) for k, v in d.items()))
    return _make(key_for(seed), freeze(bbe), freeze(sig), tuple(vocab))
