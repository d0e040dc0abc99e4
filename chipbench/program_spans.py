"""The program's own spans (`repro.utils.tracing`) in a benchmark run.

The program keeps its most recent spans in memory, timed on the host's
`perf_counter` clock, the clock of `Request.t0`/`t1`. This module takes
those inside the window's requests, sums them per request, and puts them
on the profiler trace's clock, where the device operations are. The
trace's times count from the start of the profiling session, so the
offset between the two clocks is the median, over the window's requests,
of the trace's `request` span start less the host's start of the same
request. Where those offsets spread by more than `CLOCK_TOLERANCE_NS`
the clocks do not map, and what needs the mapping is None.

Every function returns None for a program that records no spans, as
before the recorder existed, and where the recorder's ring no longer
holds the whole window.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import Dict, List, Optional, Tuple

# Largest distance of a request's clock offset from the median: the host
# takes its request start a few microseconds before the profiler does.
CLOCK_TOLERANCE_NS = 200_000

UNATTRIBUTED = "unattributed"


def recorded() -> Optional[List]:
    """The program's recorded spans, oldest first; None where the program
    has no recorder."""
    try:
        from repro.utils import tracing
    except ImportError:
        return None
    return tracing.recent()


def window_spans(run, spans: Optional[List] = None) -> Optional[List]:
    """Spans that opened and closed inside the window (host clock); None
    where there are none to read or the ring dropped part of the window
    (its oldest span closed after the window began)."""
    spans = recorded() if spans is None else spans
    if not spans or not run.requests:
        return None
    start = min(r.t0 for r in run.requests) * 1e9
    end = max(r.t1 for r in run.requests) * 1e9
    if spans[0].t1_ns >= start:
        return None
    return [s for s in spans if s.t0_ns >= start and s.t1_ns <= end]


def per_request_ms(run, name: str, spans: Optional[List] = None
                   ) -> Optional[float]:
    """Mean host time per request in spans called `name`, in ms."""
    got = window_spans(run, spans)
    if got is None:
        return None
    return (sum(s.t1_ns - s.t0_ns for s in got if s.name == name)
            / len(run.requests) / 1e6)


def transfer_mb(run, spans: Optional[List] = None) -> Optional[float]:
    """Mean bytes per request moved between host and device (every
    span's `h2d_bytes` plus `d2h_bytes`), in MB of 10^6 bytes."""
    got = window_spans(run, spans)
    if got is None:
        return None
    total = sum(s.counts.get("h2d_bytes", 0) + s.counts.get("d2h_bytes", 0)
                for s in got)
    return total / len(run.requests) / 1e6


def clock_offset(run) -> Optional[Tuple[float, float]]:
    """(median, largest distance from it) of trace minus host start of
    each request, in ns; None without a trace or where the trace's
    `request` spans are not the window's requests one for one."""
    if run.trace is None:
        return None
    on_trace = sorted(s for n, s, _ in run.trace.spans if n == "request")
    on_host = sorted(t0 for r in run.requests
                     for n, t0, _ in r.spans if n == "request")
    if not on_trace or len(on_trace) != len(on_host):
        return None
    offsets = [a - b * 1e9 for a, b in zip(on_trace, on_host)]
    med = statistics.median(offsets)
    return med, max(abs(o - med) for o in offsets)


def idle_by_program_span(run, spans: Optional[List] = None
                         ) -> Optional[Tuple[Dict[str, float], float]]:
    """({innermost open program span: idle device seconds}, clock spread
    in ns) over the traced window, by the rule of
    `Reduced.idle_by_span`; idle time with no program span open is
    `UNATTRIBUTED`. None where the clocks do not map."""
    got = window_spans(run, spans)
    clock = clock_offset(run)
    if got is None or clock is None or clock[1] > CLOCK_TOLERANCE_NS:
        return None
    off, spread = clock
    mapped = [(s.name, s.t0_ns + off, s.t1_ns + off) for s in got]
    names = {s.name for s in got}
    idle = dataclasses.replace(run.trace, spans=mapped).idle_by_span(
        len(names) + 1)
    return ({UNATTRIBUTED if n == "client" else n: v for n, v in idle},
            spread)
