"""Streaming attach against a built base store (`"driver":
"attach_stream"`).

Closed loop, one client: each request appends one `chunk`-interval slice
of an unseen attach-suite program (`ingest_intervals`) and returns its
`estimate`; the request that answers a program's last chunk also evicts
it and vacuums the store (TTL retention of everything but the base
suite), so the store's shapes stay fixed through the window.

The deployment's knowledge base is set up as a service restarted from
disk has it: archetypes and representatives saved by a build and loaded
(`KnowledgeBase.save` / `load`). Here the build is the reference's own
k-means over the reference's signatures of the base store, so that
every table the check holds the answers against is the benchmark's and
none is the program's.
"""
from __future__ import annotations

import dataclasses
import tempfile
from typing import Dict

import numpy as np

from chipbench import counts, gen, reference as R
from chipbench.driver import CONTROL, Driver, rel_err, sample

# A row whose two nearest archetypes lie within TIE of each other in
# squared distance (unit-norm signatures: d2 in [0, 4]), by the
# reference's signatures, may go to either: twelve times twice the
# program's largest distance gap to the reference on the chip, 1.66e-7
# (PERF.md, section 2).
TIE = 4e-6


@dataclasses.dataclass
class Answer:
    instance: int
    chunk: int
    sigs: np.ndarray          # the chunk's stored signatures
    fingerprint: np.ndarray
    est_cpi: float


class AttachStream(Driver):

    def setup(self):
        svc = self.make_service()
        base, new = self.suite("base_suite"), self.suite("attach_suite")
        self.blocks = [b for p in base + new for b in p.unique_blocks]
        svc.ingest_blocks(self.blocks)
        n = self.config["intervals_per_program"]
        traces = [gen.trace(p, n, self.seed) for p in base]
        cpis = [gen.inorder_cpi(tr) for tr in traces]
        for p, tr, cpi in zip(base, traces, cpis):
            svc.ingest_intervals(p.name, tr.intervals(p.name), cpis=cpi)
        self.bbes = self.ref_bbes(self.blocks, self.precision)
        self.load_knowledge(base, traces, cpis)
        t = self.traffic
        m = t["chunk"] * t["chunks_per_program"]
        self.pool = []
        for p in new:
            tr = gen.trace(p, m, self.seed)
            self.pool.append((p.name, tr, tr.intervals(p.name),
                              gen.inorder_cpi(tr)))
        self.answers: Dict[int, Answer] = {}
        for i in range(t["chunks_per_program"]):   # one whole program
            self.request(-1 - i)
        self.answers.clear()

    def load_knowledge(self, base, traces, cpis):
        """k archetypes by the reference's k-means over its signatures of
        the base store, each represented by its nearest member row; saved
        and loaded into the service as its knowledge base."""
        from repro.api.knowledge import KnowledgeBase
        svc, k = self.svc, self.svc.cfg.k
        x = np.concatenate([self.ref_sigs(self.bbes, tr, len(tr.counts),
                                          self.precision) for tr in traces])
        cents, _ = R.lloyd(x, k, self.seed, self.config["kmeans"]["iters"])
        d2 = R.distances(x, cents)
        near = d2.argmin(-1)
        reps = d2.argmin(0)                  # an empty archetype's nearest
        for j in range(k):
            members = np.flatnonzero(near == j)
            if members.size:
                reps[j] = members[d2[members, j].argmin()]
        store = svc.store
        rows = np.concatenate([store.rows_for(p.name) for p in base])[reps]
        kb = KnowledgeBase(store, assign_impl=svc.cfg.assign_impl,
                           build_impl=svc.cfg.build_impl)
        kb.k = k
        kb.archetypes = cents
        kb.rep_global_idx = rows
        kb.rep_uid = np.asarray(store.uids[rows], np.int64)
        kb.rep_program = [store.program_of_row[r] for r in rows]
        kb.rep_cpi = np.concatenate(cpis)[reps].astype(np.float32)
        kb.rep_weight = np.concatenate(
            [tr.num_instrs for tr in traces])[reps].astype(np.float32)
        with tempfile.TemporaryDirectory() as d:
            kb.save(d)
            svc.kb = KnowledgeBase.load(d, store)
        self.archetypes = cents
        self.rep_cpi = kb.rep_cpi.astype(np.float64)

    def slot(self, i: int):
        """(instance, chunk) of request i; warm-up requests are negative."""
        per = self.traffic["chunks_per_program"]
        if i < 0:
            return -1, -1 - i
        return i // per, i % per

    def request(self, i: int):
        inst, c = self.slot(i)
        name, _, ivs, cpi = self.pool[inst % len(self.pool)]
        prog = f"{name}@{inst}"
        k = self.traffic["chunk"]
        svc = self.svc
        with self.spans("ingest_intervals"):
            rows = svc.ingest_intervals(prog, ivs[c * k:(c + 1) * k],
                                        cpis=cpi[c * k:(c + 1) * k])
        with self.spans("estimate"):
            est = svc.estimate(prog)
        self.answers[i] = Answer(inst, c, svc.store.signatures[rows].copy(),
                                 est.fingerprint, est.est_cpi)
        if c == self.traffic["chunks_per_program"] - 1:
            with self.spans("vacuum"):
                svc.evict(prog)
                svc.vacuum()

    def work(self, i: int):
        inst, c = self.slot(i)
        k = self.traffic["chunk"]
        tr = self.pool[inst % len(self.pool)][1]
        w = self.set_work(tr, c * k, (c + 1) * k)
        w["assign"] = counts.assign(k, len(self.archetypes),
                                    self.archetypes.shape[1])
        w["request"] = {"flops": w["stage2"]["flops"]
                        + w["assign"]["flops"]}
        return w

    def release(self):
        done = sorted(self.answers)
        self.picked = [done[j] for j in sample(
            self.rng, len(done), self.traffic["check_requests"])]
        super().release()

    def check(self) -> Dict[str, float]:
        """Each sampled request's stored signatures against the
        reference's, and its estimate against the range that a correct
        nearest-archetype assignment of the reference's signatures of the
        program's rows so far gives (float64 distances, ties within TIE
        either way). With `control`, the answers are the reference's one
        precision below."""
        k = self.traffic["chunk"]
        stop: Dict[int, int] = {}
        for i in self.picked:
            a = self.answers[i]
            j = a.instance % len(self.pool)
            stop[j] = max(stop.get(j, 0), (a.chunk + 1) * k)
        ref = {j: self.ref_sigs(self.bbes, self.pool[j][1], s, self.precision)
               for j, s in stop.items()}
        numbers = self.compare({i: self.answers[i] for i in self.picked},
                               ref)
        self.diag["d2_gap"] = self.gap
        if self.control:
            self.diag["program"] = numbers
            numbers = self.compare(self.control_answers(stop), ref)
            self.diag["control_d2_gap"] = self.gap
        return numbers

    def control_answers(self, stop: Dict[int, int]) -> Dict[int, Answer]:
        """The picked requests' answers as the reference computes them
        one precision below the configuration's."""
        low = CONTROL[self.precision]
        bbes = self.ref_bbes(self.blocks, low)
        sigs = {j: self.ref_sigs(bbes, self.pool[j][1], s, low)
                for j, s in stop.items()}
        k = self.traffic["chunk"]
        out = {}
        for i in self.picked:
            a = self.answers[i]
            j = a.instance % len(self.pool)
            x = sigs[j][:(a.chunk + 1) * k]
            f = R.fingerprint(R.nearest(x, self.archetypes, low),
                              self.pool[j][1].num_instrs[:len(x)],
                              len(self.archetypes))
            out[i] = Answer(a.instance, a.chunk, x[a.chunk * k:], f,
                            float(f @ self.rep_cpi))
        return out

    def compare(self, answers: Dict[int, Answer], ref) -> Dict[str, float]:
        k = self.traffic["chunk"]
        sig_err = fp_err = est_err = gap = 0.0
        for a in answers.values():
            j = a.instance % len(self.pool)
            lo, hi = a.chunk * k, (a.chunk + 1) * k
            x = ref[j][:hi]
            d2 = R.distances(x, self.archetypes)
            sig_err = max(sig_err, rel_err(a.sigs, x[lo:]))
            gap = max(gap, float(np.abs(R.distances(a.sigs, self.archetypes)
                                        - d2[lo:]).max()))
            (f_lo, f_hi), (e_lo, e_hi) = R.answer_range(
                d2, self.pool[j][1].num_instrs[:hi], self.rep_cpi, TIE)
            fp_err = max(fp_err, R.outside(a.fingerprint, f_lo, f_hi))
            est_err = max(est_err, R.outside(a.est_cpi, e_lo, e_hi) / e_lo)
        self.gap = gap        # largest |d2| of an answer's rows off the reference
        return {"sig_err": sig_err, "fp_err": fp_err, "est_err": est_err}


DRIVER = AttachStream
