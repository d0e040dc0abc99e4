"""Multi-threaded LoopPoint-style regions on the service path: their set
assembly, their signatures, and the whole ingest/build/estimate path
against the plain reference (`chipbench/reference_regions.py`), at tiny
widths on seeded random weights."""
import numpy as np
import pytest

from chipbench import reference as R
from chipbench import reference_regions as RR
from chipbench import regions, weights
from repro.api import SemanticBBVService, ServiceConfig
from repro.core.bbe import BBEConfig
from repro.core.pipeline import BBEIndex, SemanticBBVPipeline, batch_set_ids
from repro.core.signature import SignatureConfig
from repro.core.tokenizer import default_tokenizer
from repro.data.asmgen import spec_programs
from repro.data.trace import Interval, Region, trace_program
from repro.utils import tracing

BBE = dict(dim_embeds=[48, 8, 8, 8, 8, 8], num_layers=2, num_heads=2,
           bbe_dim=32, max_len=64)
SIG = dict(bbe_dim=32, d_model=32, sig_dim=16, num_heads=2, num_sabs=2,
           num_seeds=1, max_set=64)


def _pipeline(impl="xla", max_set=SIG["max_set"], seed=3):
    bp, sp = weights.make_weights(seed, BBE, dict(SIG, max_set=max_set),
                                  R.VOCAB)
    bc = BBEConfig(**dict(BBE, dim_embeds=tuple(BBE["dim_embeds"])))
    sc = SignatureConfig(**dict(SIG, max_set=max_set))
    return SemanticBBVPipeline(default_tokenizer(), bc, sc, bp, sp,
                               impl=impl)


def _region(bids, counts, runtime, index=0):
    counts = np.asarray(counts, np.int64)
    return Region(program="r", index=index, bids=np.asarray(bids, np.int64),
                  counts=counts, runtime=np.asarray(runtime, bool),
                  num_instrs=int(counts[:, ~np.asarray(runtime, bool)].sum()))


def _as_region(iv: Interval) -> Region:
    """An interval as the one-thread region it is."""
    return _region(list(iv.counts), [list(iv.counts.values())],
                   np.zeros(len(iv.counts), bool), iv.index)


def _table(bids, dim=SIG["bbe_dim"], seed=0):
    rng = np.random.default_rng(seed)
    return {int(b): rng.standard_normal(dim).astype(np.float32)
            for b in bids}


def _loop_set(item, max_set):
    """The set by its definition, one entry at a time: (bid, count) of
    every thread's main-image blocks with a count, thread by thread, then
    the top max_set by count (a stable sort keeps that order on ties)."""
    if isinstance(item, Interval):
        entries = list(item.counts.items())
    else:
        entries = [(int(b), int(c)) for t in range(item.num_threads)
                   for b, c, rt in zip(item.bids, item.counts[t],
                                       item.runtime) if c > 0 and not rt]
    return sorted(entries, key=lambda e: -e[1])[:max_set], len(entries)


def _random_regions(rng, n, n_blocks=24, n_runtime=3):
    """Regions of 1-6 threads with counts in 0..4 (many ties and zeros)
    over blocks 100.. of which the last `n_runtime` are the runtime's."""
    bids = 100 + rng.permutation(n_blocks + n_runtime)
    runtime = np.arange(n_blocks + n_runtime) >= n_blocks
    return [_region(bids, rng.integers(0, 5, (rng.integers(1, 7),
                                              bids.size)), runtime, i)
            for i in range(n)], bids[~runtime]


@pytest.mark.parametrize("max_set", [8, 40, 200])
def test_region_assembly_matches_a_loop_over_each_region(max_set):
    """Vectorised assembly of a batch that mixes regions and intervals is
    bit-identical to the per-item loop, with runtime entries left out,
    the cut at max_set and ties as the loop breaks them, and counts."""
    rng = np.random.default_rng(7)
    items, main = _random_regions(rng, 30)
    items[5:5] = [Interval("i", 0, {int(b): int(c) for b, c in zip(
        main[:9], rng.integers(1, 4, 9))}, 0, 1.0, 9)]
    index = BBEIndex(_table(main))
    with tracing.span("test") as s:
        rows, freqs, mask = batch_set_ids(items, index, max_set)
    kept = total = 0
    for i, item in enumerate(items):
        want, n = _loop_set(item, max_set)
        kept, total = kept + len(want), total + n
        k = len(want)
        assert mask[i].sum() == k and mask[i, :k].all()
        np.testing.assert_array_equal(
            rows[i, :k], index.rows(np.array([b for b, _ in want])))
        np.testing.assert_array_equal(
            freqs[i, :k], np.array([c for _, c in want], np.float32))
        assert (rows[i, k:] == index.sentinel).all()
        assert (freqs[i, k:] == 0).all()
    regs = [it for it in items if isinstance(it, Region)]
    assert s.counts == {
        "threads": sum(r.num_threads for r in regs) + 1,
        "entries": kept, "truncated": total - kept,
        "excluded": sum(int((r.counts[:, r.runtime] > 0).sum())
                        for r in regs)}


def test_one_thread_region_is_the_interval():
    """T = 1: a region made of an interval assembles bit-identically to
    the interval, which assembles as the per-interval loop does."""
    progs = spec_programs("int")[:3]
    ivs = [iv for p in progs for iv in trace_program(p, 20, seed=4)]
    table = _table({b for iv in ivs for b in iv.counts})
    index = BBEIndex(table)
    pipe = _pipeline(max_set=16)
    want = batch_set_ids(ivs, index, 16)
    got = batch_set_ids([_as_region(iv) for iv in ivs], index, 16)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)
    _, freqs, mask = pipe._batch_sets_looped(ivs, table)
    np.testing.assert_array_equal(want[1], freqs)
    np.testing.assert_array_equal(want[2], mask)


def _rel(a, b):
    return float((np.linalg.norm(a - b, axis=-1)
                  / np.linalg.norm(b, axis=-1)).max())


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_balanced_region_signs_as_its_one_thread_interval(impl):
    """T identical threads are T identical copies of each element: equal
    scores and biases, so every attention weight splits evenly over the
    copies and the sums come back unchanged. Only the order of summation
    differs, a few float32 roundings: 1e-5 relative."""
    rng = np.random.default_rng(11)
    bids = np.arange(500, 512)
    counts = rng.permutation(np.arange(1, 13)) * 1000
    table = _table(bids)
    pipe = _pipeline(impl)
    one = Interval("i", 0, dict(zip(bids.tolist(), counts.tolist())), 0,
                   1.0, 1)
    many = [_region(bids, np.tile(counts, (t, 1)), np.zeros(12, bool))
            for t in (2, 5)]
    want = pipe.interval_signatures([one], table)
    got = pipe.interval_signatures(many, table)
    assert _rel(got, np.repeat(want, 2, 0)) <= 1e-5


def test_thread_order_changes_no_signature():
    """Permuting threads permutes only tied entries of the sorted set
    (none is cut here): the same set in another order, which the Set
    Transformer ignores up to float32 summation order, 1e-5 relative."""
    rng = np.random.default_rng(12)
    regs, main = _random_regions(rng, 6, n_runtime=2)
    table = _table(main)
    perm = [_region(r.bids, r.counts[rng.permutation(r.num_threads)],
                    r.runtime) for r in regs]
    pipe = _pipeline(max_set=200)
    assert _rel(pipe.interval_signatures(perm, table),
                pipe.interval_signatures(regs, table)) <= 1e-5


def test_runtime_blocks_leave_the_signature_alone():
    """Runtime entries of any count, larger than every main-image one
    included, change no bit of the signature and need no BBE; the
    set assembly counts them as excluded."""
    rng = np.random.default_rng(13)
    regs, main = _random_regions(rng, 4, n_runtime=4)
    spun = []
    for r in regs:
        c = r.counts.copy()
        c[:, r.runtime] = rng.integers(0, 10**6, (r.num_threads,
                                                  int(r.runtime.sum())))
        spun.append(_region(r.bids, c, r.runtime))
    table = _table(main)           # no BBE for a runtime block
    pipe = _pipeline(max_set=200)
    np.testing.assert_array_equal(pipe.interval_signatures(spun, table),
                                  pipe.interval_signatures(regs, table))
    with tracing.span("test") as s:
        batch_set_ids(spun, BBEIndex(table), 200)
    assert s.counts["excluded"] == sum(int((r.counts[:, r.runtime] > 0)
                                           .sum()) for r in spun) > 0


# A row whose two nearest archetypes lie within what a signature gap of
# 1e-5 can move a squared distance may go to either: with unit-norm rows
# and archetypes inside the unit ball, |d(d2)| <= 4 |dx| = 4e-5.
TIE = 4e-5


def test_regions_through_the_service_match_the_reference():
    """ingest_intervals -> build -> estimate on regions of an unseen
    program, against the plain reference's region sets, Stage 1 and
    Stage 2 over the same weights. Signatures: float32 on the CPU, where
    products are exact and only summation order differs (~1e-7),
    so 1e-5. Fingerprint and estimate: exact (float64 sums, 1e-12)
    within the range a nearest-archetype assignment of the reference's
    signatures allows under the tie rule."""
    seed, threads, max_set = 2**35 + 3, 4, 48     # sets of ~100 cut to 48
    base = regions.suite_programs("spec_fp")[:3]
    new = regions.suite_programs("npb")[0]
    pipe = _pipeline(max_set=max_set, seed=seed)
    svc = SemanticBBVService(pipe, ServiceConfig(
        bbe=pipe.bbe_cfg, sig=pipe.sig_cfg, impl="xla", k=4,
        build_impl="device"))
    blocks = [b for p in base + [new] for b in p.unique_blocks]
    svc.ingest_blocks(blocks)
    trace = lambda p, n: regions.trace(p, n, seed, threads, 0.2,  # noqa
                                       (0.05, 0.15))
    for p in base:
        tr = trace(p, 40)
        svc.ingest_intervals(p.name, tr.regions(p.name), cpis=tr.cpi)
    svc.build()
    tr = trace(new, 30)
    rows = svc.ingest_intervals(new.name, tr.regions(new.name), cpis=tr.cpi)
    est = svc.estimate(new.name)

    table = R.stage1(pipe.bbe_params, R.tokens(blocks, BBE["max_len"]),
                     BBE["num_heads"])
    row_of = {b.bid: i for i, b in enumerate(blocks)}
    ref = RR.signatures(pipe.sig_params,
                        table, np.array([row_of.get(int(b), 0)
                                         for b in tr.bids]),
                        tr.counts, tr.runtime, max_set, SIG["num_heads"])
    assert _rel(svc.store.signatures[rows], ref) <= 1e-5
    np.testing.assert_array_equal(svc.store.weights[rows], tr.num_instrs)
    d2 = R.distances(ref, svc.kb.archetypes)
    (f_lo, f_hi), (e_lo, e_hi) = R.answer_range(
        d2, tr.num_instrs, svc.kb.rep_cpi, TIE)
    assert R.outside(est.fingerprint, f_lo, f_hi) <= 1e-12
    assert R.outside(est.est_cpi, e_lo, e_hi) <= 1e-12 * e_lo
