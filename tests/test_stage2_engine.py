"""Stage2Engine: row-id batch assembly parity, Trainer-backed training,
exact checkpoint resume, and the on-device gather loss."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import TrainConfig
from repro.core.pipeline import BBEIndex, SemanticBBVPipeline, batch_set_ids
from repro.core.signature import (
    SignatureConfig, signature_init, stage2_loss, stage2_loss_from_rows,
)
from repro.data.trace import Interval
from repro.train.stage2 import Stage2Engine, triplet_row_batch

SIG_CFG = SignatureConfig(bbe_dim=16, d_model=16, sig_dim=8, num_heads=2,
                          num_sabs=1, max_set=8)


def _world(n_blocks=64, n_intervals=24, seed=0):
    rng = np.random.RandomState(seed)
    table = {bid: rng.randn(SIG_CFG.bbe_dim).astype(np.float32)
             for bid in range(n_blocks)}
    ivs = []
    for i in range(n_intervals):
        sel = rng.choice(n_blocks, size=rng.randint(3, 14), replace=False)
        counts = {int(b): int(c) for b, c in
                  zip(sel, rng.randint(1, 1000, sel.size))}
        ivs.append(Interval(program="t", index=i, counts=counts,
                            phase_id=i % 3, working_scale=1.0,
                            num_instrs=10_000))
    return table, ivs


def _row_batch_fn(index, ivs, batch=4):
    """Deterministic-in-step stream of row-id triplet batches."""
    def fn(step):
        rng = np.random.RandomState(1000 + step)
        pick = lambda: [ivs[i] for i in  # noqa: E731
                        rng.randint(len(ivs), size=batch)]
        sets = {"anchor": pick(), "positive": pick(), "negative": pick()}
        cpis = rng.uniform(0.5, 4.0, batch)
        return triplet_row_batch(sets, cpis, index, SIG_CFG.max_set)
    return fn


def test_triplet_row_batch_matches_dense_assembly():
    """Gathering the row-id batch against BBEIndex.ext must be
    bit-identical to the old per-interval interval_set loop."""
    table, ivs = _world()
    index = BBEIndex(table)
    pipe = SemanticBBVPipeline(None, None, SIG_CFG, None, None)
    sets = {"anchor": ivs[:4], "positive": ivs[4:8], "negative": ivs[8:12]}
    batch = triplet_row_batch(sets, np.ones(4), index, SIG_CFG.max_set)
    for key, role_ivs in sets.items():
        dense_b, dense_f, dense_m = pipe._batch_sets_looped(role_ivs, table)
        rows = np.asarray(batch[key]["rows"])
        got = index.ext.take(rows.ravel(), axis=0).reshape(dense_b.shape)
        np.testing.assert_array_equal(got, dense_b)
        np.testing.assert_array_equal(np.asarray(batch[key]["freqs"]),
                                      dense_f)
        np.testing.assert_array_equal(np.asarray(batch[key]["mask"]),
                                      dense_m)


def test_stage2_loss_from_rows_matches_dense_loss():
    table, ivs = _world(seed=3)
    index = BBEIndex(table)
    pipe = SemanticBBVPipeline(None, None, SIG_CFG, None, None)
    params, _ = signature_init(jax.random.PRNGKey(0), SIG_CFG)
    row_batch = _row_batch_fn(index, ivs)(0)
    dense = {}
    for key in ("anchor", "positive", "negative"):
        rows = np.asarray(row_batch[key]["rows"])
        dense[key] = {
            "bbes": jnp.asarray(index.ext.take(rows.ravel(), axis=0)
                                .reshape(rows.shape + (SIG_CFG.bbe_dim,))),
            "freqs": row_batch[key]["freqs"],
            "mask": row_batch[key]["mask"]}
    dense["cpi"] = row_batch["cpi"]
    l_rows, _ = stage2_loss_from_rows(params, SIG_CFG,
                                      jnp.asarray(index.ext), row_batch)
    l_dense, _ = stage2_loss(params, SIG_CFG, dense)
    np.testing.assert_allclose(float(l_rows), float(l_dense), rtol=1e-6)


def test_engine_training_reduces_loss(tmp_path):
    table, ivs = _world(seed=1)
    index = BBEIndex(table)
    params, specs = signature_init(jax.random.PRNGKey(1), SIG_CFG)
    tc = TrainConfig(learning_rate=3e-3, total_steps=25, warmup_steps=2,
                     checkpoint_every=0, checkpoint_dir=str(tmp_path))
    eng = Stage2Engine(SIG_CFG, params, specs, index.ext, tc)
    bf = _row_batch_fn(index, ivs)
    first = eng.step(bf(0))["loss"]
    last = None
    for s in range(1, 25):
        last = eng.step(bf(s))["loss"]
    assert last < first, f"no learning: {first} -> {last}"


def test_engine_checkpoint_exact_resume(tmp_path):
    """Branch A: 8 steps straight. Branch B: 4 steps, checkpoint, restore
    into a FRESH engine, 4 more. Params must match bitwise — Stage-2
    fine-tuning sweeps rely on the Trainer's restart path."""
    table, ivs = _world(seed=2)
    index = BBEIndex(table)
    bf = _row_batch_fn(index, ivs)

    def mk(ckdir, every):
        p, s = signature_init(jax.random.PRNGKey(1), SIG_CFG)
        tc = TrainConfig(learning_rate=1e-3, total_steps=8, warmup_steps=2,
                         checkpoint_every=every, checkpoint_dir=ckdir)
        return Stage2Engine(SIG_CFG, p, s, index.ext, tc)

    ea = mk(str(tmp_path / "a"), 0)
    for s in range(8):
        ea.step(bf(s))

    eb1 = mk(str(tmp_path / "b"), 4)
    eb1.fit(bf, 4, log_every=1000)
    eb1.maybe_checkpoint(force=True)
    eb2 = mk(str(tmp_path / "b"), 4)
    assert eb2.restore() and eb2.step_count == 4
    eb2.fit(bf, 8, log_every=1000)

    fa = jax.tree_util.tree_leaves(ea.params)
    fb = jax.tree_util.tree_leaves(eb2.params)
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_engine_impl_backends_take_a_step(tmp_path, impl):
    """Both attention backends must train through the same engine — on
    the CPU "pallas" interprets exactly the kernel a TPU compiles."""
    table, ivs = _world(seed=4, n_blocks=32, n_intervals=8)
    index = BBEIndex(table)
    params, specs = signature_init(jax.random.PRNGKey(1), SIG_CFG)
    tc = TrainConfig(learning_rate=1e-3, total_steps=2, warmup_steps=1,
                     checkpoint_every=0, checkpoint_dir=str(tmp_path))
    eng = Stage2Engine(SIG_CFG, params, specs, index.ext, tc, impl=impl)
    bf = _row_batch_fn(index, ivs, batch=2)
    m = eng.step(bf(0))
    assert np.isfinite(m["loss"]) and np.isfinite(m["grad_norm"])


def test_batch_set_ids_empty_interval_uses_sentinel():
    table, ivs = _world(seed=5)
    index = BBEIndex(table)
    empty = Interval(program="t", index=0, counts={}, phase_id=0,
                     working_scale=1.0, num_instrs=0)
    rows, freqs, mask = batch_set_ids([empty, ivs[0]], index,
                                      SIG_CFG.max_set)
    assert (rows[0] == index.sentinel).all()
    assert not mask[0].any() and mask[1].any()
    # sentinel row gathers all-zero BBEs
    assert (index.ext[rows[0]] == 0.0).all()
