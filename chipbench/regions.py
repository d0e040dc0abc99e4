"""Multi-threaded LoopPoint-style regions from a seed, and their CPI.

A region is T threads over one window of an OpenMP run (LoopPoint, Sabu
et al., HPCA 2022). Per program and seed, vectorised over (region,
thread), with the program's own loops and phases (`repro.data.asmgen`):

  work       each thread runs its phase's loop mix with a Dirichlet
             jitter of its own (0.08, as `chipbench.gen.trace`), on
             `INTERVAL_INSTRS` scaled by a per-thread imbalance drawn
             uniformly within +-`imbalance`;
  serial     the master thread (t = 0) runs an extra budget, drawn
             uniformly in `serial` x `INTERVAL_INSTRS`, on one loop of
             each phase (the phase's serial section);
  barrier    every thread then waits at the closing barrier in the
             runtime's spin loop until the slowest thread arrives: its
             idle cycles, at one instruction a cycle, split over the
             runtime's blocks by their weights;
  CPI        the slowest thread's cycles on the in-order core
             (`chipbench.gen.inorder_cpi`, per thread, main image) over
             the mean per-thread main-image instructions.

Blocks are the program's (main image) followed by the runtime's, which
every program shares and which the service never encodes.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from chipbench import gen
from repro.data.asmgen import NPB_LIKE, Program, gen_function, gen_program
from repro.data.isa import BasicBlock, stable_hash
from repro.data.trace import INTERVAL_INSTRS, Region

SUITES = dict(gen.SUITES, npb=NPB_LIKE)


def suite_programs(suite: str) -> List[Program]:
    """The programs of `suite`: "spec_int" and "spec_fp" as
    `chipbench.gen.suite_programs` builds them, "npb" alike."""
    if suite in gen.SUITES:
        return gen.suite_programs(suite)
    return [gen_program(stable_hash(suite, name), profile_name=prof,
                        name=name, n_loops=8, n_phases=6)
            for name, prof in SUITES[suite]]


def runtime_image():
    """The threading runtime's barrier spin loop: (blocks, weights)."""
    f = gen_function(stable_hash("omp_runtime", "barrier_wait"),
                     opt_level="O2", profile_name="int_compute", n_blocks=3)
    w = np.random.default_rng(stable_hash("omp_runtime", "weights")
                              ).dirichlet(np.ones(len(f.blocks)) * 4.0)
    return f.blocks, w


@dataclasses.dataclass
class RegionTrace:
    """Region statistics of one program: `counts[i, t, j]` executions of
    block `bids[j]` by thread t in region i; `runtime[j]` marks the
    runtime's blocks."""
    program: Program
    bids: np.ndarray            # (B,) int64, main image then runtime
    runtime: np.ndarray         # (B,) bool
    counts: np.ndarray          # (n, T, B) int64
    num_instrs: np.ndarray      # (n,) int64, main image, all threads
    cpi: np.ndarray             # (n,) float64

    def regions(self, name: str, start: int = 0, stop=None
                ) -> List[Region]:
        """Program-facing `Region` objects for rows [start, stop)."""
        stop = self.counts.shape[0] if stop is None else stop
        return [Region(program=name, index=i, bids=self.bids,
                       counts=self.counts[i], runtime=self.runtime,
                       num_instrs=int(self.num_instrs[i]))
                for i in range(start, stop)]


def _blocks_by_bid(program: Program, bids: np.ndarray) -> List[BasicBlock]:
    table = {b.bid: b for lp in program.loops for b in lp.blocks}
    return [table[int(b)] for b in bids]


def trace(program: Program, n: int, seed: int, threads: int,
          imbalance: float, serial, interval_instrs: int = INTERVAL_INSTRS
          ) -> RegionTrace:
    """n regions of `program` at `threads` threads, from `seed`."""
    rng = gen.rng_for(seed, "regions", program.pid)
    loops = program.loops
    n_loops = len(loops)
    sched = gen.phase_schedule(program, n)
    mix = np.stack([ph.loop_mix for ph in program.phases])[sched][:, None]
    mix = mix + rng.dirichlet(np.ones(n_loops), size=(n, threads)) * 0.08
    mix = mix / mix.sum(axis=-1, keepdims=True)              # (n, T, L)
    scale = np.array([ph.working_scale for ph in program.phases])[sched]
    scale = scale * 2.0 ** rng.uniform(-0.15, 0.15, size=n)
    share = 1.0 + rng.uniform(-imbalance, imbalance, size=(n, threads))
    budget = mix * (share * interval_instrs)[..., None]
    serial_loop = np.random.default_rng(stable_hash(
        "serial", program.pid)).integers(n_loops, size=len(program.phases))
    budget[np.arange(n), 0, serial_loop[sched]] += (
        rng.uniform(*serial, size=n) * interval_instrs)
    loop_of, frac, bids = [], [], []
    for li, lp in enumerate(loops):
        for b, w in zip(lp.blocks, lp.weights):
            loop_of.append(li)
            frac.append(w)
            bids.append(b.bid)
    per_block = budget[..., loop_of]                         # (n, T, B)
    per_block = np.where(per_block >= 1, per_block * np.asarray(frac), 0.0)
    uniq, inv = np.unique(np.asarray(bids, np.int64), return_inverse=True)
    lens = np.array([b.num_instrs for b in _blocks_by_bid(program, uniq)],
                    np.int64)
    counts = np.floor(per_block / np.maximum(1, lens[inv])).astype(np.int64)
    merged = np.zeros((n, threads, uniq.size), np.int64)
    if uniq.size == len(bids):
        merged[..., inv] = counts
    else:                              # a block shared by two loops
        np.add.at(merged.transpose(2, 0, 1), inv, counts.transpose(2, 0, 1))
    counts = merged
    instrs = counts @ lens                                   # (n, T)
    cycles = np.stack([
        gen.inorder_cpi(gen.Trace(program, uniq, counts[:, t], sched, scale,
                                  instrs[:, t])) * instrs[:, t]
        for t in range(threads)], axis=1)
    blocks, weights = runtime_image()
    spin_lens = np.array([b.num_instrs for b in blocks], np.int64)
    wait = cycles.max(axis=1, keepdims=True) - cycles        # (n, T)
    spin = np.floor(wait[..., None] * weights / spin_lens).astype(np.int64)
    return RegionTrace(
        program, np.concatenate([uniq, [b.bid for b in blocks]]),
        np.concatenate([np.zeros(uniq.size, bool),
                        np.ones(len(blocks), bool)]),
        np.concatenate([counts, spin], axis=-1), instrs.sum(axis=1),
        cycles.max(axis=1) / instrs.mean(axis=1))
