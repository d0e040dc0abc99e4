"""Execution tracing at the basic-block level.

The paper partitions dynamic execution into 10M-instruction intervals and
records per-interval basic-block frequencies (the BBV). Executing 1T real
instructions is out of scope offline, so `trace_program` synthesizes the
*block-level statistics* of such a trace directly: per interval it draws a
block-frequency vector from the program's current phase (mixture over hot
loops + sampling noise) and scales counts to the interval's instruction
budget. This is the data gate simulation described in DESIGN.md §3.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

from repro.data.asmgen import Program
from repro.data.isa import BasicBlock, stable_hash

INTERVAL_INSTRS = 10_000_000  # paper: 10M-instruction intervals


@dataclass
class Interval:
    """One sampling interval of a program's execution: one thread, the
    T = 1 case of a `Region`."""
    program: str
    index: int               # position within the program's trace
    counts: Dict[int, int]   # block id -> execution count
    phase_id: int
    working_scale: float     # memory pressure multiplier for this interval
    num_instrs: int

    num_threads = 1

    def set_entries(self):
        """-> (block ids, counts) of the set's candidate entries in the
        dict's order, and 0 runtime entries left out (`Region`'s)."""
        n = len(self.counts)
        return (np.fromiter(self.counts.keys(), np.int64, count=n),
                np.fromiter(self.counts.values(), np.float64, count=n), 0)

    def set_size(self) -> int:
        """How many entries `set_entries()` gives, without building them."""
        return len(self.counts)

    def bbv(self, block_order: List[int], weight_by_len: bool = True,
            block_lens: Dict[int, int] = None) -> np.ndarray:
        """Classic BBV: per-block execution counts (optionally × block size),
        in a fixed block order, L1-normalized."""
        v = np.zeros(len(block_order), dtype=np.float64)
        idx = {b: i for i, b in enumerate(block_order)}
        for bid, c in self.counts.items():
            if bid in idx:
                w = c * (block_lens[bid] if (weight_by_len and block_lens) else 1)
                v[idx[bid]] = w
        s = v.sum()
        return v / s if s > 0 else v


@dataclass
class Region:
    """One multi-threaded sampling region, LoopPoint's unit (Sabu et al.,
    HPCA 2022): T threads over one window of the run.

    Thread t executed block `bids[j]` `counts[t, j]` times. `runtime[j]`
    marks a block of the threading runtime's image (its synchronisation
    and spin code), which LoopPoint filters by image: runtime entries are
    left out of the region's set and carry no instructions into its
    weight.

    The region's set holds every (t, j) with `counts[t, j] > 0` and block
    j in the main image, each with the block's BBE and the frequency
    `counts[t, j]`, cut to the top `max_set` by count; ties keep the
    entries' order, thread by thread, then `bids` order within a thread
    (`repro.core.pipeline.batch_set_ids`). So a thread's imbalance or a
    master thread's serial section shows in the set, while a balanced
    region's T identical copies give the one-thread interval's signature.
    `num_instrs`, the region's store weight, is the main-image
    instructions summed over threads. A single-thread `Interval` is the
    T = 1 case."""
    program: str
    index: int
    bids: np.ndarray         # (B,) int64 block ids
    counts: np.ndarray       # (T, B) int64 executions per thread
    runtime: np.ndarray      # (B,) bool: block of the threading runtime
    num_instrs: int          # main-image instructions, all threads

    @property
    def num_threads(self) -> int:
        return self.counts.shape[0]

    def set_entries(self):
        """-> (block ids, counts) of the set's candidate entries, thread
        by thread, and how many runtime entries with a count were left
        out."""
        main = (self.counts > 0) & ~self.runtime
        _, block = np.nonzero(main)
        return (self.bids[block], self.counts[main],
                int(np.count_nonzero(self.counts[:, self.runtime])))

    def set_size(self) -> int:
        """How many entries `set_entries()` gives, without building them."""
        return int(np.count_nonzero((self.counts > 0) & ~self.runtime))


def trace_program(program: Program, n_intervals: int,
                  interval_instrs: int = INTERVAL_INSTRS,
                  seed: int = 0) -> List[Interval]:
    """Synthesize the interval statistics of a long execution."""
    blocks = {b.bid: b for lp in program.loops for b in lp.blocks}
    intervals: List[Interval] = []
    # unroll the phase schedule cyclically over n_intervals
    schedule: List[int] = []
    while len(schedule) < n_intervals:
        for pi, ph in enumerate(program.phases):
            schedule.extend([pi] * ph.duration)
    schedule = schedule[:n_intervals]

    for it in range(n_intervals):
        rng = np.random.RandomState(stable_hash("ivl", program.pid, seed, it))
        pi = schedule[it]
        phase = program.phases[pi]
        # jitter the loop mixture a little within a phase (real phases drift)
        mix = phase.loop_mix + rng.dirichlet(np.ones(len(program.loops))) * 0.08
        mix = mix / mix.sum()
        counts: Dict[int, int] = {}
        total = 0
        for li, lp in enumerate(program.loops):
            loop_budget = mix[li] * interval_instrs
            if loop_budget < 1:
                continue
            per_block = lp.weights * loop_budget
            for b, w in zip(lp.blocks, per_block):
                c = int(w / max(1, b.num_instrs))
                if c > 0:
                    counts[b.bid] = counts.get(b.bid, 0) + c
                    total += c * b.num_instrs
        intervals.append(Interval(
            program=program.name, index=it, counts=counts, phase_id=pi,
            working_scale=float(phase.working_scale * 2 ** rng.uniform(-0.15, 0.15)),
            num_instrs=total,
        ))
    return intervals


def block_table(programs: List[Program]) -> Dict[int, BasicBlock]:
    """Union of unique blocks across programs (the Stage-1 encoding set)."""
    table: Dict[int, BasicBlock] = {}
    for p in programs:
        for b in p.unique_blocks:
            table[b.bid] = b
    return table
