"""The plain reference agrees with the program at tiny widths on the CPU,
where float32 is exact enough that any gap is a difference of equations."""
import jax
import numpy as np
import pytest

from chipbench import gen, reference as R, weights
from repro.api.knowledge import assign_signatures
from repro.core.bbe import BBEConfig
from repro.core.pipeline import SemanticBBVPipeline
from repro.core.signature import SignatureConfig
from repro.core.tokenizer import default_tokenizer

BBE = {"dim_embeds": [48, 8, 8, 8, 8, 8], "num_layers": 2, "num_heads": 2,
       "bbe_dim": 32, "max_len": 64}
SIG = {"bbe_dim": 32, "d_model": 32, "sig_dim": 16, "num_heads": 2,
       "num_sabs": 2, "num_seeds": 1, "max_set": 48}


@pytest.fixture(scope="module")
def world():
    bp, sp = weights.make_weights(2**35 + 3, BBE, SIG, R.VOCAB)
    tok = default_tokenizer()
    bc = BBEConfig(**dict(BBE, dim_embeds=tuple(BBE["dim_embeds"])))
    sc = SignatureConfig(**SIG)
    pipe = SemanticBBVPipeline(tok, bc, sc, bp, sp, impl="xla")
    progs = gen.suite_programs("spec_int")[:2]
    blocks = [b for p in progs for b in p.unique_blocks]
    return pipe, bp, sp, progs, blocks


def test_tokens_equal_the_program_tokeniser(world):
    pipe, _, _, _, blocks = world
    assert tuple(pipe.tok.spec.dim_sizes) == R.VOCAB
    np.testing.assert_array_equal(R.tokens(blocks, 64),
                                  pipe.tok.encode_blocks(blocks, 64))


def test_stage1_and_stage2_match_the_program(world):
    pipe, bp, sp, progs, blocks = world
    tr = gen.trace(progs[0], 100, 7)
    ivs = tr.intervals("p")
    with jax.default_matmul_precision("highest"):
        table = pipe.encode_blocks(blocks, 32)
        sigs = pipe.interval_signatures(ivs, table, 64)
    ref = R.stage1(bp, R.tokens(blocks, 64), 2)
    got = np.stack([table[b.bid] for b in blocks])
    np.testing.assert_allclose(got, ref, atol=1e-5)
    row = {b.bid: i for i, b in enumerate(blocks)}
    cols, freqs, mask = R.top_sets(tr.counts, 48)
    rows = np.asarray([row[int(b)] for b in tr.bids])[cols]
    np.testing.assert_allclose(
        R.stage2(sp, ref, rows, freqs, mask, 2), sigs, atol=1e-5)


def test_nearest_fingerprint_and_lloyd():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(500, 16)).astype(np.float32)
    c = rng.normal(size=(5, 16)).astype(np.float32)
    a, _ = assign_signatures(x, c, "numpy")
    np.testing.assert_array_equal(R.distances(x, c).argmin(-1), a)
    f = R.fingerprint(a, np.full(500, 2.0), 5)
    np.testing.assert_allclose(f, np.bincount(a, minlength=5) / 500)
    cents, assign = R.lloyd(x, 5, seed=1, iters=30)
    np.testing.assert_array_equal(R.distances(x, cents).argmin(-1), assign)
    for j in range(5):
        np.testing.assert_allclose(cents[j], x[assign == j].mean(0),
                                   atol=1e-5)
