"""Reduction of a JAX profiler trace to the numbers the metrics read.

The profiler writes `<dir>/plugins/profile/<time>/<host>.xplane.pb`.
Device planes are named `/device:TPU:<n>`; their `XLA Ops` line holds
one event per operation that ran, with its start and duration in ns,
named by its HLO instruction (a Pallas kernel by its jitted wrapper,
e.g. `set_attention_pallas`; a loop by `while`, which spans its body).
The host plane holds the benchmark's `TraceAnnotation` spans on the
same clock. From those:

  busy_s          the union of the operations' intervals inside the
                  window, averaged over the chips used
  idle share      1 - busy_s / window_s
  kernel time     the summed durations of the operations whose name
                  contains a kernel's stable name
  breakdown       the operations that took most device time, and the
                  idle time by the innermost benchmark span open on the
                  host (outside every span: "client", the request loop)
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"


def op_name(event_name: str) -> str:
    """`%set_attention_pallas.4 = f32[...] custom-call(...)` ->
    `set_attention_pallas`: the HLO instruction's name without its
    numeric suffix (the event names are the whole instruction)."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{directory}, found {paths}")
    return paths[0]


def merge(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Reduced:
    """Device operations per chip [(name, start_ns, end_ns)], host spans
    [(name, start_ns, end_ns)] and the window on the trace's clock."""
    ops: Dict[str, List[Tuple[str, float, float]]]
    spans: List[Tuple[str, float, float]]
    start_ns: float
    end_ns: float

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def _in_window(self, ops):
        return [(n, max(s, self.start_ns), min(e, self.end_ns))
                for n, s, e in ops if e > self.start_ns and s < self.end_ns]

    @property
    def busy_s(self) -> float:
        per_chip = [sum(e - s for s, e in merge(
            [(s, e) for _, s, e in self._in_window(ops)]))
            for ops in self.ops.values()]
        return sum(per_chip) / max(len(per_chip), 1) / 1e9

    def idle_share(self) -> float:
        window_ns = self.end_ns - self.start_ns
        return (1.0 - self.busy_s * 1e9 / window_ns) * 100.0

    def kernel_seconds(self, name: str) -> float:
        return sum(e - s for ops in self.ops.values()
                   for n, s, e in self._in_window(ops) if name in n) / 1e9

    def top_ops(self, n: int = 10) -> List[List]:
        tot: Dict[str, float] = collections.Counter()
        for ops in self.ops.values():
            for name, s, e in self._in_window(ops):
                tot[name] += (e - s) / 1e9
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_by_span(self, n: int = 10) -> List[List]:
        """Idle device time inside the window, split at span edges and
        given to the innermost host span open there (the latest opened)."""
        chip = sorted(self.ops)[0]
        busy = merge([(s, e) for _, s, e in self._in_window(self.ops[chip])])
        spans = [(max(s, self.start_ns), min(e, self.end_ns), name)
                 for name, s, e in self.spans
                 if e > self.start_ns and s < self.end_ns]
        points = sorted({self.start_ns, self.end_ns}
                        | {x for b in busy for x in b}
                        | {x for s, e, _ in spans for x in (s, e)})
        starts = sorted(range(len(spans)), key=lambda k: spans[k][0])
        ends = sorted(range(len(spans)), key=lambda k: spans[k][1])
        active: Dict[int, Tuple] = {}
        tot: Dict[str, float] = collections.Counter()
        si = ei = bi = 0
        for a, b in zip(points, points[1:]):
            while ei < len(ends) and spans[ends[ei]][1] <= a:
                active.pop(ends[ei], None)
                ei += 1
            while si < len(starts) and spans[starts[si]][0] <= a:
                k = starts[si]
                if spans[k][1] > a:
                    active[k] = spans[k]
                si += 1
            while bi < len(busy) and busy[bi][1] <= a:
                bi += 1
            if bi < len(busy) and busy[bi][0] <= a:
                continue                                 # device busy
            name = (max(active.values(), key=lambda v: (v[0], -v[1]))[2]
                    if active else "client")
            tot[name] += (b - a) / 1e9
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def breakdown(self) -> Dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_by_span()}


def reduce(path: str, span_names: Sequence[str]) -> Reduced:
    """Read an xplane file. The window runs from the first to the last
    end of the benchmark's `request` spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: Dict[str, List] = {}
    spans: List = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [(op_name(e.name), e.start_ns,
                                        e.start_ns + e.duration_ns)
                                       for e in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name in span_names)
    if not ops:
        raise ValueError(f"no {OPS_LINE!r} line on any {DEVICE_PREFIX}* plane"
                         f" of {path}")
    req = [s for s in spans if s[0] == "request"]
    if not req:
        raise ValueError(f"no 'request' span in {path}")
    return Reduced(ops, spans, min(s for _, s, _ in req),
                   max(e for _, _, e in req))
