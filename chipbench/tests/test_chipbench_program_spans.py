"""The program's spans in a run: selection of the window, sums per
request, the clock mapping onto a trace, and the readers of the six
metrics that read them."""
import json
import sys

import pytest

from chipbench import harness, program_spans as ps
from chipbench import trace as tr
from repro.utils.tracing import Span

H = 1_000_000_000_000          # host clock (perf_counter ns) of request 0
OFF = -999_999_000_000         # trace clock minus host clock
REQ = 1_000_000                # each request lasts 1 ms, back to back
U = REQ // 100


def _request_spans(i, first_id):
    """Program spans of request i, in hundredths U of a request: ingest
    1-40 with Stage 2 at 10-30, estimate 50-90 with the whole-store
    assignment at 55-85."""
    h = H + i * REQ
    root1, root2 = first_id, first_id + 2
    return [
        Span(first_id + 1, root1, root1, "pipeline.stage2", h + 10 * U,
             h + 30 * U, {"h2d_bytes": 100, "d2h_bytes": 50}),
        Span(root1, None, root1, "service.ingest_intervals", h + 1 * U,
             h + 40 * U, {}),
        Span(first_id + 3, root2, root2, "kb.assign_all", h + 55 * U,
             h + 85 * U, {"h2d_bytes": 1000, "d2h_bytes": 2000}),
        Span(root2, None, root2, "service.estimate", h + 50 * U,
             h + 90 * U, {}),
    ]


def synthetic(jitter=(0, 300, 0)):
    """Three requests; a set-up span before the window and a span after
    it in the ring; on the trace, the device runs at 15-25, 45-47 and
    60-70 U of each request, and each request's trace start is off the
    host's by `jitter` ns."""
    ring = [Span(1, None, 1, "service.build", H - 10_000_000,
                 H - 5_000_000, {"h2d_bytes": 10**9})]
    reqs, ops, trace_spans = [], [], []
    for i, j in enumerate(jitter):
        h = H + i * REQ
        ring += _request_spans(i, 10 * (i + 1))
        reqs.append(harness.Request(i, h / 1e9, (h + REQ) / 1e9, True,
                                    [("request", h / 1e9,
                                      (h + REQ) / 1e9)]))
        t = h + OFF
        trace_spans.append(("request", t + j, t + REQ))
        ops += [("fusion", t + a, t + b) for a, b in
                ((15 * U, 25 * U), (45 * U, 47 * U), (60 * U, 70 * U))]
    ring.append(Span(99, None, 99, "service.vacuum", H + 3 * REQ + 5 * U,
                     H + 3 * REQ + 9 * U, {"h2d_bytes": 10**9}))
    start = trace_spans[0][1]
    reduced = tr.Reduced({"/device:TPU:0": ops}, trace_spans, start,
                         H + 3 * REQ + OFF)
    run = harness.Run(None, None, reqs, 3 * REQ / 1e9, 0.0, {}, reduced)
    return run, ring


def test_window_and_sums_per_request():
    run, ring = synthetic()
    got = ps.window_spans(run, ring)
    assert len(got) == 12
    assert {s.name for s in got} == {"pipeline.stage2", "kb.assign_all",
                                     "service.ingest_intervals",
                                     "service.estimate"}
    assert ps.per_request_ms(run, "pipeline.stage2", ring) == \
        pytest.approx(0.2)
    assert ps.per_request_ms(run, "kb.assign_all", ring) == \
        pytest.approx(0.3)
    assert ps.per_request_ms(run, "store.compact", ring) == 0.0
    assert ps.transfer_mb(run, ring) == pytest.approx(3150 / 1e6)


def test_idle_attributed_to_program_spans():
    run, ring = synthetic()
    off, spread = ps.clock_offset(run)
    assert off == OFF and spread == 300
    idle, spread = ps.idle_by_program_span(run, ring)
    # per request (U): outside every program span 0-1, 40-45, 47-50 and
    # 90-100; ingest 1-10 and 30-40; Stage 2 10-15 and 25-30; estimate
    # 50-55 and 85-90; assignment 55-60 and 70-85
    want = {"unattributed": 19 * U, "service.ingest_intervals": 19 * U,
            "pipeline.stage2": 10 * U, "service.estimate": 10 * U,
            "kb.assign_all": 20 * U}
    assert idle == pytest.approx({k: 3 * v / 1e9 for k, v in want.items()})
    assert sum(idle.values()) == pytest.approx(
        run.trace.window_s - run.trace.busy_s)


def test_none_where_the_clocks_do_not_map():
    late = ps.CLOCK_TOLERANCE_NS + 1
    assert late < REQ
    run, ring = synthetic(jitter=(0, late, 0))
    assert ps.clock_offset(run) == (OFF, late)
    assert ps.idle_by_program_span(run, ring) is None
    # the host-clock sums need no mapping
    assert ps.per_request_ms(run, "pipeline.stage2", ring) == \
        pytest.approx(0.2)
    # a trace whose requests are not the window's, one for one
    run, ring = synthetic()
    run.trace.spans.pop()
    assert ps.clock_offset(run) is None
    assert ps.idle_by_program_span(run, ring) is None


def test_none_where_the_ring_lost_part_of_the_window():
    run, ring = synthetic()
    assert ps.window_spans(run, ring[2:]) is None
    assert ps.transfer_mb(run, []) is None
    assert ps.per_request_ms(run, "pipeline.stage2", ring[2:]) is None


READERS = {"set_assembly_ms": 0.0, "stage2_call_ms": 0.2,
           "assign_all_ms": 0.3, "compact_ms": 0.0,
           "transfer_mb": 3150 / 1e6, "idle_unattributed.attach": 19.0}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader(name, monkeypatch, capsys):
    run, ring = synthetic()
    monkeypatch.setattr(ps, "recorded", lambda: ring)
    assert harness.read_metric(name, run) == pytest.approx(READERS[name])
    err = capsys.readouterr().err
    if name == "idle_unattributed.attach":
        line, = err.splitlines()
        head, rest = line.split(" ", 1)
        table, key, spread = rest.rsplit(" ", 2)
        assert (head, key) == ("idle_by_program_span", "clock_spread_us")
        assert json.loads(table)["kb.assign_all"] == pytest.approx(6e-4)
        assert float(spread) == pytest.approx(0.3)
    else:
        assert err == ""


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_without_the_recorder(name, monkeypatch):
    """On a program from before the recorder every reader gives None."""
    run, _ = synthetic()
    import repro.utils
    monkeypatch.delattr(repro.utils, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.utils.tracing", None)
    assert ps.recorded() is None
    assert harness.read_metric(name, run) is None


def test_the_attach_cell_reports_every_reader():
    cell = harness.resolve("spec17int-100k.attach-stream")
    assert set(READERS) <= {m["name"] for m in cell.per_layer}
