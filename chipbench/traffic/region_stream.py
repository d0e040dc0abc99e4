"""Streaming attach of multi-threaded regions against a built base store
(`"driver": "region_stream"`).

The request loop, knowledge base and check of `attach_stream`, over
LoopPoint-style regions (`chipbench.regions`) in place of single-thread
intervals: each request appends one `chunk`-region slice of an unseen
attach-suite program (`ingest_intervals`) and returns its `estimate`;
the request that answers a program's last chunk also evicts it and
vacuums the store. Every region has `threads` threads, an imbalance of
+-`imbalance` and a master-thread serial share drawn in `serial`; the
base suite is traced alike. The reference's sets are
`chipbench.reference_regions`'.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from chipbench import counts, regions, reference_regions as RR
from chipbench.traffic.attach_stream import AttachStream


class RegionStream(AttachStream):

    def setup(self):
        svc = self.make_service()
        base, new = self.suite("base_suite"), self.suite("attach_suite")
        self.blocks = [b for p in base + new for b in p.unique_blocks]
        svc.ingest_blocks(self.blocks)
        n = self.config["intervals_per_program"]
        traces = [self.region_trace(p, n) for p in base]
        for p, tr in zip(base, traces):
            svc.ingest_intervals(p.name, tr.regions(p.name), cpis=tr.cpi)
        self.bbes = self.ref_bbes(self.blocks, self.precision)
        self.load_knowledge(base, traces, [tr.cpi for tr in traces])
        t = self.traffic
        m = t["chunk"] * t["chunks_per_program"]
        self.pool = []
        for p in new:
            tr = self.region_trace(p, m)
            self.pool.append((p.name, tr, tr.regions(p.name), tr.cpi))
        self.answers = {}
        for i in range(t["chunks_per_program"]):   # one whole program
            self.request(-1 - i)
        self.answers.clear()

    def suite(self, key: str):
        return regions.suite_programs(self.config[key])

    def region_trace(self, program, n: int) -> regions.RegionTrace:
        t = self.traffic
        return regions.trace(program, n, self.seed, t["threads"],
                             t["imbalance"], t["serial"])

    def ref_sigs(self, bbes, tr: regions.RegionTrace, stop: int,
                 precision: str) -> np.ndarray:
        """The reference's signatures of regions [0, stop) of `tr`."""
        row_of, table = bbes
        rows = np.asarray([row_of.get(int(b), 0) for b in tr.bids])
        return RR.signatures(self.sp, table, rows, tr.counts[:stop],
                             tr.runtime, self.sig["max_set"],
                             self.sig["num_heads"], precision)

    def set_work(self, tr: regions.RegionTrace, start: int, stop: int
                 ) -> Dict[str, Dict[str, float]]:
        """Stage-2 and set-attention work of regions [start, stop): each
        region's entries kept."""
        ns = RR.set_sizes(tr.counts[start:stop], tr.runtime,
                          self.sig["max_set"]).tolist()
        sa = [counts.set_attention(n, self.sig) for n in ns]
        return {"stage2": {"flops": sum(counts.stage2_flops(n, self.sig)
                                        for n in ns)},
                "set_attention": {"flops": sum(s["flops"] for s in sa),
                                  "bytes": sum(s["bytes"] for s in sa)}}


DRIVER = RegionStream
