"""Operations and bytes that each served call needs, from the problem's
shapes alone.

These count the work the answer requires, not what today's code does:
Stage-2 counts an interval's real set elements, an attach request its
own rows.
Padding, re-assigning rows that did not change and recomputation are
waste, so a PR that stops doing them shows as a gain and never as a
share of a roofline above 100%. A multiply-add is 2 operations; bytes
are float32 (4 bytes) reads and writes of each call's inputs and outputs.
"""
from __future__ import annotations

from typing import Dict

F32 = 4


def _mab_flops(n_q: int, n_k: int, d: int) -> float:
    """Multihead attention block: q/o projections over queries, k/v over
    keys, scores and weighted values, and the 2d-wide feed-forward."""
    return 2.0 * (2 * n_q * d * d + 2 * n_k * d * d + 2 * n_q * n_k * d
                  + 4 * n_q * d * d)


def stage2_flops(n: int, sig: Dict) -> float:
    """One interval with `n` distinct blocks through the Set Transformer:
    input projection, `num_sabs` self-attention blocks over n elements,
    the pooling block (num_seeds queries), the output projection and the
    CPI head."""
    d, s = sig["d_model"], sig["num_seeds"]
    return (2.0 * n * (sig["bbe_dim"] + 1) * d
            + sig["num_sabs"] * _mab_flops(n, n, d) + _mab_flops(s, n, d)
            + 2.0 * s * d * sig["sig_dim"] + 2.0 * sig["sig_dim"] * d
            + 2.0 * d)


def set_attention(n: int, sig: Dict) -> Dict[str, float]:
    """The set-attention kernel's part of one interval: scores and
    weighted values of each SAB (n queries, n keys) and of the pooling
    block (num_seeds queries); bytes are q, k, v, o and the key bias."""
    d, s = sig["d_model"], sig["num_seeds"]
    flops = sig["num_sabs"] * 4.0 * n * n * d + 4.0 * s * n * d
    bytes_ = (sig["num_sabs"] * (4 * n * d + n) + (2 * s * d + 2 * n * d + n)
              ) * F32
    return {"flops": flops, "bytes": float(bytes_)}


def assign(rows: int, k: int, dim: int) -> Dict[str, float]:
    """Nearest-archetype assignment of `rows` signatures: distances to k
    archetypes; reads rows and archetypes, writes label and distance."""
    return {"flops": 2.0 * rows * k * dim,
            "bytes": float((rows * dim + k * dim + 2 * rows) * F32)}
