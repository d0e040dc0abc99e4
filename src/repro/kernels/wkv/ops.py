"""jit'd wrapper: (B,S,H,dh) model layout <-> (B*H,S,dh) kernel layout."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import compiled_on_tpu
from repro.kernels.wkv.wkv import wkv_pallas

_wkv = compiled_on_tpu(wkv_pallas)


def wkv_chunked(r, k, v, w, beta, state: Optional[jnp.ndarray] = None,
                chunk: int = 128):
    """Delta-rule recurrence via the Pallas kernel.

    r,k,v,w: (B,S,H,dh); beta: (B,S,H); state: (B,H,dh,dh) or None.
    Returns (y (B,S,H,dh) fp32, final_state (B,H,dh,dh) fp32)."""
    B, S, H, dh = r.shape
    if state is None:
        state = jnp.zeros((B, H, dh, dh), jnp.float32)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, dh)  # noqa: E731
    rb, kb, vb, wb = fold(r), fold(k), fold(v), fold(w)
    bb = beta.transpose(0, 2, 1).reshape(B * H, S, 1)
    sb = state.reshape(B * H, dh, dh)
    # pad sequence to a chunk multiple (kernel requires divisibility)
    c = min(chunk, S) if S % min(chunk, S) == 0 else S
    if S % c:
        c = S  # fallback: single chunk
    y, sf = _wkv(rb, kb, vb, wb, bb, sb, chunk=c)
    y = y.reshape(B, H, S, dh).transpose(0, 2, 1, 3)
    return y, sf.reshape(B, H, dh, dh)
