"""A Stage-2 row runs at the width of its own set (`set_width`), not at
`max_set`: the same signatures and CPIs as the full-width step, the same
bits whatever a row is batched with, and one compile per width."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference as R
from chipbench import weights
from chipbench.harness import CompileCounter
from repro.core.bbe import BBEConfig
from repro.core.pipeline import (
    BBEIndex, SemanticBBVPipeline, _signature_from_rows, batch_set_ids,
    set_width,
)
from repro.core.signature import SignatureConfig
from repro.core.tokenizer import default_tokenizer
from repro.data.trace import Interval, Region
from repro.utils import tracing

BBE = dict(dim_embeds=[48, 8, 8, 8, 8, 8], num_layers=2, num_heads=2,
           bbe_dim=32, max_len=64)
# 96 is not a power of two: the ladder is 32, 64, 96
SIG = dict(bbe_dim=32, d_model=32, sig_dim=16, num_heads=2, num_sabs=2,
           num_seeds=1, max_set=96)
BATCH = 4


@pytest.mark.parametrize("size,max_set,width", [
    (0, 512, 32), (1, 512, 32), (8, 512, 32), (9, 512, 32), (32, 512, 32),
    (33, 512, 64), (128, 512, 128), (129, 512, 256), (156, 512, 256),
    (248, 512, 256), (256, 512, 256), (257, 512, 512), (512, 512, 512),
    (700, 512, 512), (17, 48, 32), (33, 48, 48), (48, 48, 48),
    (65, 96, 96), (3, 4, 4)])
def test_set_width(size, max_set, width):
    assert set_width(size, max_set) == width


def _pipeline(impl):
    """Seeded untrained weights. Such a CPI head predicts log-CPIs near
    0, where a relative error measures cancellation, not the width: its
    bias is set to log 2, so CPIs sit near 1 as a trained head's do."""
    bp, sp = weights.make_weights(5, BBE, SIG, R.VOCAB)
    sp["cpi_head"]["b2"] = jnp.full_like(sp["cpi_head"]["b2"], np.log(2.0))
    bc = BBEConfig(**dict(BBE, dim_embeds=tuple(BBE["dim_embeds"])))
    return SemanticBBVPipeline(default_tokenizer(), bc, SignatureConfig(**SIG),
                               bp, sp, impl=impl)


def _items(kind, sizes, rng):
    """One item a set size: an interval of that many blocks, or a
    two-thread region with that many (thread, block) entries and one
    runtime block (left out of the set). Blocks are 0..199."""
    out = []
    for i, n in enumerate(sizes):
        cnt = rng.integers(1, 40, n)
        if kind == "interval":
            bids = rng.choice(200, n, replace=False)
            out.append(Interval("p", i, dict(zip(bids.tolist(),
                                                 cnt.tolist())),
                                0, 1.0, int(cnt.sum())))
            continue
        nb = -(-n // 2)
        bids = rng.choice(199, nb + 1, replace=False)
        bids[-1] = 199                                    # runtime block
        counts = np.zeros((2, nb + 1), np.int64)
        counts[0, :nb] = cnt[:nb]
        counts[1, :n - nb] = cnt[nb:]
        counts[:, -1] = 7
        runtime = np.arange(nb + 1) == nb
        out.append(Region("p", i, bids, counts, runtime,
                          int(counts[:, :nb].sum())))
    return out


@pytest.mark.parametrize("kind", ["interval", "region"])
def test_set_size_counts_set_entries(kind):
    """`set_size()` is the length of `set_entries()`, for a region also
    when some threads skip some blocks."""
    items = _items(kind, [0, 1, 9, 40], np.random.default_rng(3))
    if kind == "region":
        items[3].counts[1, :5] = 0
    for it in items:
        assert it.set_size() == len(it.set_entries()[0])


def _full_width(pipe, items, table):
    """(sigs, CPIs) of the batch run at max_set slots."""
    index = BBEIndex(table)
    row_ids, freqs, mask = batch_set_ids(items, index, pipe.sig_cfg.max_set)
    pad = ((0, BATCH - len(items)), (0, 0))
    sig, logcpi = jax.jit(_signature_from_rows, static_argnames=(
        "cfg", "impl"))(pipe.sig_params, pipe.sig_cfg, index.ext,
                        np.pad(row_ids, pad, constant_values=index.sentinel),
                        np.pad(freqs, pad), np.pad(mask, pad),
                        impl=pipe.impl)
    return np.asarray(sig)[:len(items)], np.expm1(np.asarray(logcpi))[
        :len(items)]


def _rel(got, want):
    """Largest row-wise relative gap (element-wise for a vector)."""
    got, want = got.reshape(len(got), -1), want.reshape(len(want), -1)
    return float((np.linalg.norm(got - want, axis=1)
                  / np.linalg.norm(want, axis=1)).max())


def _widths_since(mark):
    """The `width` count of every `pipeline.set_assembly` span after
    `mark`, in order."""
    return [s.counts["width"] for s in tracing.recent()
            if s.span_id > mark.span_id and s.name == "pipeline.set_assembly"]


def _table(rng):
    return {b: rng.standard_normal(SIG["bbe_dim"]).astype(np.float32)
            for b in range(200)}


@pytest.mark.parametrize("longest", [0, 8, 9, 32, 33, 96, 120])
@pytest.mark.parametrize("kind", ["interval", "region"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_width_matches_full_max_set(impl, kind, longest):
    """Signatures and CPIs at each row's width against the same batch at
    full max_set (96); 120 entries are cut to 96. Longest sets of 0 are
    all empty; otherwise the batch mixes the longest with shorter sets,
    which run in a batch of their own width."""
    rng = np.random.default_rng(longest)
    sizes = [longest] + ([longest // 2, 1] if longest else [0, 0])
    items = _items(kind, sizes, rng)
    table = _table(rng)
    pipe = _pipeline(impl)
    with tracing.span("test.mark") as mark:
        pass
    sigs = pipe.interval_signatures(items, table, batch=BATCH)
    cpis = pipe.predict_interval_cpi(items, table, batch=BATCH)
    widths = sorted({set_width(n, SIG["max_set"]) for n in sizes})
    assert _widths_since(mark) == 2 * widths
    want_sigs, want_cpis = _full_width(pipe, items, table)
    assert _rel(sigs, want_sigs) <= 1e-6
    assert _rel(cpis, want_cpis) <= 1e-6


@pytest.mark.parametrize("kind", ["interval", "region"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_row_independent_of_batch_mates(impl, kind):
    """A row's signature and CPI are the same bits whether it is signed
    with rows of other widths, in another split into batches, or alone."""
    rng = np.random.default_rng(7)
    items = _items(kind, [40, 5, 90, 31, 33, 120, 64, 12], rng)
    table = _table(rng)
    pipe = _pipeline(impl)
    sigs = pipe.interval_signatures(items, table, batch=BATCH)
    cpis = pipe.predict_interval_cpi(items, table, batch=BATCH)
    for got in (pipe.interval_signatures(items[::-1], table,
                                         batch=BATCH)[::-1],
                np.concatenate([pipe.interval_signatures([it], table,
                                                         batch=BATCH)
                                for it in items])):
        np.testing.assert_array_equal(got, sigs)
    alone = np.concatenate([pipe.predict_interval_cpi([it], table,
                                                      batch=BATCH)
                            for it in items])
    np.testing.assert_array_equal(alone, cpis)


def test_one_compile_per_width():
    """After one call, sets of other sizes in the same width compile
    nothing; a set in a new width compiles, once; `pipeline.set_assembly`
    counts the width each batch ran at."""
    pipe = _pipeline("xla")
    rng = np.random.default_rng(0)
    table = _table(rng)
    counter = CompileCounter()

    def run(*sizes):
        with tracing.span("test.mark") as mark:
            pass
        counter.count, counter.armed = 0, True
        pipe.interval_signatures(_items("interval", sizes, rng), table,
                                 batch=BATCH)
        counter.armed = False
        return counter.count, _widths_since(mark)

    compiles, widths = run(20, 3)
    assert widths == [32] and compiles > 0
    assert run(17, 25) == (0, [32])
    assert run(32) == (0, [32])
    first, widths = run(40)
    assert widths == [64] and first > 0
    assert run(64, 33) == (0, [64])
    assert run(50, 25) == (0, [32, 64])
    assert run(70, 10, 64) == (first, [32, 64, 96])   # 96 compiles once
    assert run(90, 30, 40) == (0, [32, 64, 96])
