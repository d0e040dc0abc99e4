"""Traffic generation from a seed: vectorised copies of the program's
interval tracer and in-order CPI model.

`repro.data.trace.trace_program` draws one `RandomState` per interval
(about 0.3 ms each) and `repro.data.perfmodel.trace_cpi` loops over every
(interval, block) pair in Python; at 10^5 intervals a run's set-up would
spend most of a minute there. The copies below keep the same semantics
(phase schedule, loop-mix jitter of 0.08, per-block budget split, 10 M
instruction intervals, working-scale jitter, the in-order core's latency
terms) with one generator per (program, seed) and array arithmetic.
Program structure (loops, phases, blocks) comes from the program's own
deterministic catalog, `repro.data.asmgen`, which is cheap.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from repro.data.asmgen import SPEC_FP_LIKE, SPEC_INT_LIKE, Program, gen_program
from repro.data.isa import BasicBlock, stable_hash
from repro.data.trace import INTERVAL_INSTRS, Interval

SUITES = {"spec_int": SPEC_INT_LIKE, "spec_fp": SPEC_FP_LIKE}


def suite_programs(suite: str) -> List[Program]:
    """The SPEC-CPU2017-shaped programs of `suite` ("spec_int" or
    "spec_fp"), as `repro.data.asmgen.spec_programs` builds them."""
    return [gen_program(stable_hash("spec", name), profile_name=prof,
                        name=name, n_loops=8, n_phases=6)
            for name, prof in SUITES[suite]]


def rng_for(seed: int, *parts) -> np.random.Generator:
    """One generator per (seed, parts): any whole-number seed, however
    large, and a stream of its own for every program or pool."""
    key = [int(seed) % (1 << 64)]
    if parts:
        key.append(stable_hash(*parts))
    return np.random.default_rng(key)


# ------------------------------------------------------------- intervals
@dataclasses.dataclass
class Trace:
    """Interval statistics of one program: `counts[i, j]` executions of
    block `bids[j]` in interval i (0 where the block did not run)."""
    program: Program
    bids: np.ndarray            # (B,) int64
    counts: np.ndarray          # (n, B) int64
    phase: np.ndarray           # (n,) int64
    working_scale: np.ndarray   # (n,) float64
    num_instrs: np.ndarray      # (n,) int64

    def intervals(self, name: str, start: int = 0, stop=None
                  ) -> List[Interval]:
        """Program-facing `Interval` objects for rows [start, stop)."""
        stop = self.counts.shape[0] if stop is None else stop
        out = []
        for i in range(start, stop):
            nz = np.flatnonzero(self.counts[i])
            out.append(Interval(
                program=name, index=i,
                counts=dict(zip(self.bids[nz].tolist(),
                                self.counts[i, nz].tolist())),
                phase_id=int(self.phase[i]),
                working_scale=float(self.working_scale[i]),
                num_instrs=int(self.num_instrs[i])))
        return out


def phase_schedule(program: Program, n: int) -> np.ndarray:
    """Phase index of each interval: the phases' durations unrolled
    cyclically, as `trace_program` does."""
    one = np.concatenate([np.full(ph.duration, pi)
                          for pi, ph in enumerate(program.phases)])
    return np.resize(one, n).astype(np.int64)


def trace(program: Program, n: int, seed: int,
          interval_instrs: int = INTERVAL_INSTRS) -> Trace:
    """`trace_program` with one generator per (program, seed)."""
    rng = rng_for(seed, "trace", program.pid)
    loops = program.loops
    n_loops = len(loops)
    sched = phase_schedule(program, n)
    mix = np.stack([ph.loop_mix for ph in program.phases])[sched]
    mix = mix + rng.dirichlet(np.ones(n_loops), size=n) * 0.08
    mix = mix / mix.sum(axis=1, keepdims=True)
    scale = np.array([ph.working_scale for ph in program.phases])[sched]
    scale = scale * 2.0 ** rng.uniform(-0.15, 0.15, size=n)
    loop_of, share, ninstr, bids = [], [], [], []
    for li, lp in enumerate(loops):
        for b, w in zip(lp.blocks, lp.weights):
            loop_of.append(li)
            share.append(w)
            ninstr.append(max(1, b.num_instrs))
            bids.append(b.bid)
    loop_of = np.asarray(loop_of)
    budget = mix[:, loop_of] * interval_instrs              # (n, B)
    per_block = np.where(budget >= 1, budget * np.asarray(share), 0.0)
    counts = np.floor(per_block / np.asarray(ninstr)).astype(np.int64)
    bids = np.asarray(bids, np.int64)
    uniq, inv = np.unique(bids, return_inverse=True)
    if uniq.size != bids.size:         # a block shared by two loops
        merged = np.zeros((n, uniq.size), np.int64)
        np.add.at(merged.T, inv, counts.T)
        counts, bids = merged, uniq
    num = counts @ np.asarray([b.num_instrs for b in _blocks_by_bid(
        program, bids)], np.int64)
    return Trace(program, bids, counts, np.asarray(sched), scale, num)


def _blocks_by_bid(program: Program, bids: np.ndarray) -> List[BasicBlock]:
    table = {b.bid: b for lp in program.loops for b in lp.blocks}
    return [table[int(b)] for b in bids]


# ------------------------------------------------------------ CPI model
# The in-order core of `repro.data.perfmodel.INORDER_CPU`.
INORDER = dict(issue_width=1.0, rob_depth=1, mispredict_penalty=3.0,
               l1_bytes=32 << 10, l2_bytes=256 << 10, l3_bytes=4 << 20,
               l1_lat=3.0, l2_lat=12.0, l3_lat=36.0, mem_lat=180.0,
               mlp=1.0, warmup_intervals=0.8)
_MEM_KIND = {"seq": 0.12, "stride": 0.45, "random": 1.0}


def _miss(ws: np.ndarray, cache: float) -> np.ndarray:
    x = ws / cache
    return np.where(ws > 0, x ** 2 / (1.0 + x ** 2), 0.0)


def inorder_cpi(tr: Trace, cpu: Dict = INORDER) -> np.ndarray:
    """Per-interval CPI of `tr` on the in-order core: the block-level
    latency model of `repro.data.perfmodel`, vectorised over intervals
    (working scale and cold-cache factor vary per interval)."""
    blocks = _blocks_by_bid(tr.program, tr.bids)
    feats = [b.features() for b in blocks]
    n_b = np.array([f["n"] for f in feats], np.float64)
    core = np.maximum(n_b, [f["dep_depth"] for f in feats])
    core = core + np.array([f["counts"]["div"] * 18.0 + f["counts"]["fpdiv"]
                            * 10.0 for f in feats]) / cpu["issue_width"]
    loads = np.array([f["loads"] for f in feats], np.float64)
    ws0 = np.array([f["working_set"] for f in feats], np.float64)
    kind = np.array([_MEM_KIND[f["mem_kind"]] for f in feats])
    br = np.array([f["counts"]["branch"] for f in feats], np.float64)
    bias = np.array([f["branch_bias"] for f in feats])
    branch = br * (2.0 * bias * (1.0 - bias) * 0.55 + 0.01) \
        * cpu["mispredict_penalty"]
    idx = np.arange(tr.counts.shape[0], dtype=np.float64)
    cold = np.exp(-idx / cpu["warmup_intervals"])[:, None]   # (n, 1)
    ws = ws0[None, :] * tr.working_scale[:, None]            # (n, B)
    m1 = np.minimum(1.0, _miss(ws, cpu["l1_bytes"]) * kind + cold * 0.5)
    m2 = np.minimum(1.0, _miss(ws, cpu["l2_bytes"]) * kind + cold * 0.8)
    m3 = np.minimum(1.0, _miss(ws, cpu["l3_bytes"]) * kind + cold)
    lat = (cpu["l1_lat"] + m1 * (cpu["l2_lat"] - cpu["l1_lat"])
           + m2 * (cpu["l3_lat"] - cpu["l2_lat"])
           + m3 * (cpu["mem_lat"] - cpu["l3_lat"]))
    hidden = cpu["l1_lat"] if cpu["issue_width"] > 1 else 0.0
    mem = loads * np.maximum(0.0, lat / cpu["mlp"] - hidden)
    cycles_per_exec = core[None, :] + np.where(loads > 0, mem, 0.0) \
        + branch[None, :]                                    # (n, B)
    instrs = tr.counts * n_b[None, :]
    total = instrs.sum(axis=1)
    cycles = (tr.counts * cycles_per_exec).sum(axis=1)
    return np.where(total > 0, cycles / np.maximum(total, 1), 1.0)
