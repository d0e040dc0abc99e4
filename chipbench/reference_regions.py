"""Plain reference of the service's answers for multi-threaded regions.

A region's set, from the semantics the configuration states
(`chipbench/configs/looppoint-omp8-100k.json`), built region by region
in plain numpy: every (thread t, block j) with `counts[t, j] > 0` and j
outside the threading runtime, with the block's BBE and the frequency
`counts[t, j]`, cut to the top `max_set` by count, ties in thread order,
then block order. The signatures, nearest archetypes and k-means are
`chipbench.reference`'s `stage2`, `nearest` and `lloyd`. Nothing is
imported from the program.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from chipbench import reference as R

LANES = 128


def region_set(counts: np.ndarray, runtime: np.ndarray, max_set: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """One region's set from `counts[t, j]` (T, B): (block columns j,
    counts) of its entries, largest count first."""
    cols = np.tile(np.flatnonzero(~runtime), counts.shape[0])
    c = counts[:, ~runtime].ravel()
    cols, c = cols[c > 0], c[c > 0]
    top = np.argsort(-c, kind="stable")[:max_set]
    return cols[top], c[top]


def region_sets(counts: np.ndarray, runtime: np.ndarray, max_set: int):
    """Sets of regions `counts[i, t, j]` (n, T, B) -> (block columns (n,
    W), freqs, mask), W the largest set rounded up to whole lanes (at
    most `max_set`): slots past a set's end are masked, and masked keys
    take no weight, so W changes no signature."""
    sets = [region_set(c, runtime, max_set) for c in counts]
    w = max([len(s[0]) for s in sets] + [1])
    w = min(-(-w // LANES) * LANES, max_set)
    cols = np.zeros((len(sets), w), np.int64)
    freqs = np.zeros((len(sets), w), np.float32)
    for i, (j, c) in enumerate(sets):
        cols[i, :len(j)] = j
        freqs[i, :len(c)] = c
    return cols, freqs, freqs > 0


def set_sizes(counts: np.ndarray, runtime: np.ndarray, max_set: int
              ) -> np.ndarray:
    """Entries in each region's set."""
    return np.minimum((counts[..., ~runtime] > 0).sum((1, 2)), max_set)


def signatures(params, bbes: np.ndarray, rows: np.ndarray,
               counts: np.ndarray, runtime: np.ndarray, max_set: int,
               heads: int, precision: str = "highest") -> np.ndarray:
    """Signatures (n, sig_dim) of regions `counts` (n, T, B), block j's
    BBE being `bbes[rows[j]]` (any row for a runtime block)."""
    cols, freqs, mask = region_sets(counts, runtime, max_set)
    return R.stage2(params, bbes, rows[cols], freqs, mask, heads, precision,
                    chunk=256)
