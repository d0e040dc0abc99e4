"""jit'd wrapper: model layout (B,S,H,D)/(B,T,K,D) <-> kernel layout."""
from __future__ import annotations

from repro.kernels import compiled_on_tpu
from repro.kernels.flash_attention.flash import flash_pallas

_flash = compiled_on_tpu(flash_pallas)


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    """q: (B,S,H,D); k,v: (B,T,K,D) GQA. Returns (B,S,H,D)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash(qt, kt, vt, causal=causal, window=window,
                 block_q=block_q, block_k=block_k)
    return out.transpose(0, 2, 1, 3)
