"""Host spans and transfer counts of the service path.

`span(name, **counts)` opens a `jax.profiler.TraceAnnotation`, so the
span shows in any profiler trace (TensorBoard, Perfetto, an xplane) on
the clock of the device operations, and when it closes appends a `Span`
to a fixed-size in-memory ring holding the process's last `RING` spans.
`recent()` returns them, oldest first. Times are
`time.perf_counter_ns()`; nothing is written to disk.

Spans nest per thread: a span's parent is the span open around it on
the same thread. A root span's id is the request id, which every span
below it carries, and which its annotation carries as the `request_id`
stat (the event's name stays the span's name). Counts are integers that
a span takes while it is open (`s.add(rows=...)`), or that code below
it gives the innermost open span (`add(...)`). The service path counts
its host<->device transfers as `h2d_bytes` and `d2h_bytes`: the
`nbytes` of each array it hands to or takes from the device.

A span records host time only; it adds no device synchronisation.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

RING = 65536


class Span(NamedTuple):
    span_id: int
    parent_id: Optional[int]          # None at a root
    request_id: int                   # the root's span_id
    name: str
    t0_ns: int
    t1_ns: int
    counts: Dict[str, int]


class _Open:
    """A span while it is open; the context manager `span` returns."""
    __slots__ = ("_rec", "name", "span_id", "parent_id", "request_id",
                 "counts", "_stack", "_ann", "_t0")

    def __init__(self, rec: "Recorder", name: str, counts: Dict[str, int]):
        self._rec, self.name, self.counts = rec, name, counts

    def add(self, **counts: int) -> None:
        """Add to this span's counts."""
        for k, v in counts.items():
            self.counts[k] = self.counts.get(k, 0) + int(v)

    def __enter__(self) -> "_Open":
        stack = self._stack = self._rec._stack()
        parent = stack[-1] if stack else None
        self.span_id = next(self._rec._ids)
        self.parent_id = parent.span_id if parent else None
        self.request_id = parent.request_id if parent else self.span_id
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(
            self.name, request_id=self.request_id)
        self._t0 = time.perf_counter_ns()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._ann.__exit__(*exc)
        t1 = time.perf_counter_ns()
        self._stack.pop()
        self._rec._ring.append(Span(self.span_id, self.parent_id,
                                    self.request_id, self.name, self._t0,
                                    t1, self.counts))
        return False


class Recorder:
    """Span ids, the per-thread stacks of open spans and the ring."""

    def __init__(self):
        self._ring: collections.deque = collections.deque(maxlen=RING)
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> List[_Open]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, **counts: int) -> _Open:
        """Context manager: span `name`, starting with `counts`."""
        return _Open(self, name, {k: int(v) for k, v in counts.items()})

    def add(self, **counts: int) -> None:
        """Add to the counts of this thread's innermost open span, if
        any."""
        stack = self._stack()
        if stack:
            stack[-1].add(**counts)

    def recent(self) -> List[Span]:
        return list(self._ring)


_RECORDER = Recorder()
span = _RECORDER.span
add = _RECORDER.add
recent = _RECORDER.recent
