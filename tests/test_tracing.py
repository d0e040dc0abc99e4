"""The span recorder (`repro.utils.tracing`) and the spans and transfer
counts of the service path."""
import re
from pathlib import Path

import numpy as np
import pytest

from repro.utils import tracing

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


def test_nesting_parents_and_request_id():
    rec = tracing.Recorder()
    with rec.span("svc.root") as root:
        with rec.span("a.child") as child:
            with rec.span("a.grandchild") as grand:
                pass
        with rec.span("b.child"):
            pass
    with rec.span("svc.next") as other:
        pass
    spans = {s.name: s for s in rec.recent()}
    assert [s.name for s in rec.recent()] == [
        "a.grandchild", "a.child", "b.child", "svc.root", "svc.next"]
    assert spans["svc.root"].parent_id is None
    assert spans["svc.root"].request_id == root.span_id
    assert spans["a.child"].parent_id == root.span_id
    assert spans["a.grandchild"].parent_id == child.span_id
    assert spans["b.child"].parent_id == root.span_id
    assert {spans[n].request_id for n in
            ("a.child", "a.grandchild", "b.child")} == {root.span_id}
    assert spans["svc.next"].request_id == other.span_id != root.span_id
    assert grand.span_id != child.span_id
    s = spans["svc.root"]
    assert s.t0_ns <= spans["a.child"].t0_ns <= spans["a.child"].t1_ns \
        <= s.t1_ns


def test_counts_from_the_span_and_from_below():
    rec = tracing.Recorder()
    rec.add(rows=5)                        # no span open: dropped
    with rec.span("x.outer", rows=2) as s:
        s.add(rows=3, h2d_bytes=10)
        with rec.span("x.inner"):
            rec.add(d2h_bytes=7)
            rec.add(d2h_bytes=1)
        rec.add(h2d_bytes=np.int64(4))
    inner, outer = rec.recent()
    assert inner.counts == {"d2h_bytes": 8}
    assert outer.counts == {"rows": 5, "h2d_bytes": 14}
    assert all(type(v) is int for v in outer.counts.values())


def test_ring_keeps_the_last_spans():
    rec = tracing.Recorder()
    for i in range(tracing.RING + 10):
        with rec.span("x.y", i=i):
            pass
    got = rec.recent()
    assert len(got) == tracing.RING
    assert got[0].counts["i"] == 10
    assert got[-1].counts["i"] == tracing.RING + 9


def test_spans_recorded_through_an_exception():
    rec = tracing.Recorder()
    with pytest.raises(ValueError):
        with rec.span("x.outer"):
            with rec.span("x.inner"):
                raise ValueError("boom")
    assert [s.name for s in rec.recent()] == ["x.inner", "x.outer"]
    with rec.span("x.after") as s:        # the stack was unwound
        pass
    assert rec.recent()[-1].parent_id is None
    assert rec.recent()[-1].request_id == s.span_id


def test_threads_keep_their_own_nesting():
    """Threads share the ring and the ids, not their open spans."""
    import sys
    import threading
    rec = tracing.Recorder()
    n_threads, per_thread = 16, 500

    def work(t):
        for _ in range(per_thread):
            with rec.span("t.root", thread=t):
                with rec.span("t.child", thread=t):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    spans = rec.recent()
    assert len(spans) == 2 * n_threads * per_thread
    assert len({s.span_id for s in spans}) == len(spans)
    roots = {s.span_id: s for s in spans if s.name == "t.root"}
    for s in spans:
        if s.name == "t.child":
            root = roots[s.parent_id]
            assert s.request_id == root.span_id
            assert s.counts["thread"] == root.counts["thread"]
        else:
            assert s.parent_id is None and s.request_id == s.span_id


def _program_span_names():
    names = set()
    for path in SRC.rglob("*.py"):
        names |= set(re.findall(r'tracing\.span\(\s*"([^"]+)"',
                                path.read_text()))
    return names


def test_no_program_span_is_named_like_a_benchmark_span():
    from chipbench.driver import SPANS
    names = _program_span_names()
    assert {"service.estimate", "pipeline.stage2", "kb.assign_all"} <= names
    assert not names & set(SPANS)
    assert all("." in n for n in names)


@pytest.fixture(scope="module")
def tiny_run():
    """A tiny service built over two programs; a third is ingested and
    estimated in two chunks, evicted and vacuumed, and the spans of those
    six calls are returned with the service and the two chunks."""
    from repro.api import SemanticBBVService, ServiceConfig
    from repro.core.bbe import BBEConfig
    from repro.core.signature import SignatureConfig
    from repro.data.asmgen import spec_programs
    from repro.data.perfmodel import INORDER_CPU, interval_cpi
    from repro.data.trace import block_table, trace_program
    progs = spec_programs("int")[:3]
    bt = block_table(progs)
    ivs = {p.name: trace_program(p, 20 if i == 2 else 16)
           for i, p in enumerate(progs)}
    cpis = {n: [interval_cpi(iv, bt, INORDER_CPU) for iv in v]
            for n, v in ivs.items()}
    cfg = ServiceConfig(
        bbe=BBEConfig(dim_embeds=(48, 8, 8, 8, 8, 8), num_layers=2,
                      num_heads=2, bbe_dim=32, max_len=64),
        sig=SignatureConfig(bbe_dim=32, d_model=32, sig_dim=16, max_set=48,
                            num_heads=2),
        k=3, store_min_capacity=16, signature_batch=32)
    svc = SemanticBBVService.create(cfg)
    svc.ingest_blocks(list(bt.values()))
    for p in progs[:2]:
        svc.ingest_intervals(p.name, ivs[p.name], cpis=cpis[p.name])
    svc.build()
    new = progs[2].name
    with tracing.span("test.mark") as mark:
        pass
    svc.ingest_intervals(new, ivs[new][:10], cpis=cpis[new][:10])
    svc.estimate(new)
    svc.ingest_intervals(new, ivs[new][10:], cpis=cpis[new][10:])
    svc.estimate(new)
    svc.evict(new)
    svc.vacuum()
    spans = [s for s in tracing.recent() if s.span_id > mark.span_id]
    return svc, spans, [ivs[new][:10], ivs[new][10:]]


def _tree(spans):
    """{root name: [child names, each with its own children]} in the
    order the calls made them."""
    by_parent = {}
    for s in sorted(spans, key=lambda s: s.t0_ns):
        by_parent.setdefault(s.parent_id, []).append(s)

    def kids(s):
        return [(c.name, kids(c)) for c in by_parent.get(s.span_id, [])]
    return [(r.name, kids(r)) for r in by_parent[None]]


def test_service_span_tree(tiny_run):
    _, spans, _ = tiny_run
    ingest = ("service.ingest_intervals", [("pipeline.set_assembly", []),
                                           ("pipeline.stage2", []),
                                           ("store.add", [])])
    assert _tree(spans) == [
        ingest,
        ("service.estimate", [("kb.assign_all", [("store.upload", [])]),
                              ("kb.fingerprint", [])]),
        ingest,
        ("service.estimate", [("kb.assign_all", []),
                              ("kb.fingerprint", [])]),
        ("service.evict", []),
        ("service.vacuum", [("store.compact", []),
                            ("kb.apply_remap", [])]),
    ]
    roots = {s.span_id for s in spans if s.parent_id is None}
    assert {s.request_id for s in spans} == roots


def test_service_counts_by_hand(tiny_run):
    """Store: 2 x 16 rows + two chunks of 10 new rows of sig_dim 16 at
    capacity 64; Stage 2: per chunk, one batch of 32 sets for each width
    of the ladder 32, 48 (max_set) that holds some of its sets; k = 3
    archetypes. The first estimate after build assigns the whole store
    in place (one upload, nothing downloaded but the labels); the second
    assigns only its chunk, padded to 16, from the host."""
    svc, spans, chunks = tiny_run
    c = {}
    for s in spans:
        c.setdefault(s.name, []).append(s.counts)
    batch, n_set, sig, cap, k, tail = 32, 48, 16, 64, 3, 16
    # one thread an interval, no runtime image; each set's blocks, to 48
    blocks = [[len(iv.counts) for iv in chunk] for chunk in chunks]

    def width(n):
        return 32 if n <= 32 else n_set
    batches = [(w, [n for n in b if width(n) == w])
               for b in blocks for w in sorted({width(n) for n in b})]
    assert [w for w, _ in batches] == [32, 32]   # sets of 31 blocks
    assert c["pipeline.set_assembly"] == [
        {"rows": len(g), "padded_rows": batch - len(g), "threads": len(g),
         "entries": sum(min(n, n_set) for n in g), "excluded": 0,
         "truncated": sum(max(n - n_set, 0) for n in g), "width": w}
        for w, g in batches]
    # row ids, freqs, mask at each batch's width
    stage2_up = [batch * w * (4 + 4 + 1) for w, _ in batches]
    stage2_down = batch * (sig + 1) * 4         # signatures, log CPI
    assert c["pipeline.stage2"] == [{"h2d_bytes": up,
                                     "d2h_bytes": stage2_down}
                                    for up in stage2_up]
    assert c["store.add"] == 2 * [{"rows": 10}]
    store = cap * sig * 4
    assert c["store.upload"] == [{"h2d_bytes": store}]
    assert c["kb.assign_all"] == [
        {"rows_assigned": 42, "padded_rows": cap - 42, "rows_cached": 0,
         "full_pass": 1,
         "h2d_bytes": k * sig * 4,                  # archetypes
         "d2h_bytes": 2 * cap * 4},                 # assign, dist
        {"rows_assigned": 10, "padded_rows": tail - 10, "rows_cached": 42,
         "full_pass": 0,
         "h2d_bytes": tail * sig * 4 + k * sig * 4,  # chunk, archetypes
         "d2h_bytes": 2 * tail * 4}]
    assert c["kb.fingerprint"] == [{"rows": 10}, {"rows": 20}]
    # no device matrix is resident after the second add: host compaction
    assert c["store.compact"] == [{"rows_before": 52, "rows_after": 32}]
    assert c["kb.apply_remap"] == [{"repinned": 0}]
    assert svc.store.capacity == 32
    _, epoch, labels = svc.kb._row_assign_cache  # carried through the remap
    assert epoch == svc.store.row_epoch and len(labels) == 32
    counts = [x for v in c.values() for x in v]
    h2d = sum(x.get("h2d_bytes", 0) for x in counts)
    d2h = sum(x.get("d2h_bytes", 0) for x in counts)
    assert h2d == (sum(stage2_up) + store + 2 * k * sig * 4
                   + tail * sig * 4)
    assert d2h == (len(batches) * stage2_down + 2 * cap * 4
                   + 2 * tail * 4)
