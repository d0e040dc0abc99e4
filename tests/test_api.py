"""Tests for the `repro.api` service surface: SignatureStore growth +
persistence, KnowledgeBase build/attach/estimate (incl. the attach-
parity acceptance criteria), assignment-kernel impl parity, and the
SemanticBBVService facade end-to-end on a tiny real pipeline."""
import os

import numpy as np
import pytest

from repro.api import (
    CPIEstimate, KnowledgeBase, SemanticBBVService, ServiceConfig,
    SignatureStore, assign_signatures,
)
from repro.core.bbe import BBEConfig
from repro.core.clustering import kmeans, representatives
from repro.core.crossprog import cpi_accuracy, universal_clustering
from repro.core.pipeline import PipelineConfig, SemanticBBVPipeline
from repro.core.signature import SignatureConfig
from repro.data.perfmodel import INORDER_CPU, interval_cpi
from repro.data.trace import block_table, trace_program
from repro.train.stage2 import Stage2Engine


# --------------------------------------------------------------- toy data

def _nearest_numpy(x, c):
    """Numpy nearest-centroid oracle: (assign (N,) int32, dist2 (N,))."""
    x, c = np.asarray(x, np.float32), np.asarray(c, np.float32)
    d2 = (np.sum(x * x, -1, keepdims=True) - 2.0 * (x @ c.T)
          + np.sum(c * c, -1)[None, :])
    return d2.argmin(-1).astype(np.int32), d2.min(-1).astype(np.float32)


def _blob_program(seed, centers, n_per=25, noise=0.05):
    """Synthetic program drawn from shared behavioral blobs; CPI is a
    deterministic function of the blob, so archetype estimation is
    near-exact and cluster occupancy is unambiguous."""
    rng = np.random.RandomState(seed)
    sigs, cpis = [], []
    for ph, c in enumerate(centers):
        sigs.append(c + rng.randn(n_per, centers.shape[1]) * noise)
        cpis.append(np.full(n_per, 1.0 + 2.0 * ph))
    return (np.concatenate(sigs).astype(np.float32),
            np.concatenate(cpis).astype(np.float32))


@pytest.fixture(scope="module")
def blob_centers():
    return (np.random.RandomState(42).randn(3, 8) * 6).astype(np.float32)


def _filled_store(blob_centers, names, weights=None):
    store = SignatureStore(8, min_capacity=16)
    for i, name in enumerate(names):
        s, c = _blob_program(i, blob_centers)
        w = None if weights is None else weights[i]
        store.add(name, s, weights=w, cpis=c)
    return store


# ------------------------------------------------------------------ store

def test_store_pad_and_grow_static_shapes():
    store = SignatureStore(4, min_capacity=8)
    assert store.capacity == 8
    m0 = store.device_matrix
    assert m0.shape == (8, 4)
    store.add("a", np.ones((5, 4), np.float32))
    assert store.capacity == 8                       # still first level
    assert store.device_matrix.shape == m0.shape     # static query shape
    store.add("b", np.full((7, 4), 2.0, np.float32))
    assert len(store) == 12
    assert store.capacity == 16                      # doubled once
    assert store.device_matrix.shape == (16, 4)
    # invalid rows are zero on device (masked by construction)
    np.testing.assert_array_equal(
        np.asarray(store.device_matrix)[12:], 0.0)
    assert store.programs == ["a", "b"]
    np.testing.assert_array_equal(store.rows_for("b"), np.arange(5, 12))


def test_store_append_only_bookkeeping():
    store = SignatureStore(3)
    r1 = store.add("p", np.ones((2, 3), np.float32), weights=[10, 20],
                   cpis=[1.0, 2.0])
    r2 = store.add("p", np.zeros((1, 3), np.float32), weights=[30],
                   cpis=[3.0])
    np.testing.assert_array_equal(np.concatenate([r1, r2]), np.arange(3))
    np.testing.assert_array_equal(store.rows_for("p"), np.arange(3))
    assert store.total_weight == pytest.approx(60.0)
    assert store.version == 2
    with pytest.raises(KeyError):
        store.rows_for("unknown")
    with pytest.raises(ValueError):
        store.add("p", np.ones((2, 5), np.float32))


def test_store_save_load_bit_identical(tmp_path, blob_centers):
    store = _filled_store(blob_centers, ["A", "B"],
                          weights=[np.arange(75) + 1.0,
                                   np.arange(75) + 5.0])
    store.save(str(tmp_path / "store"))
    loaded = SignatureStore.load(str(tmp_path / "store"))
    assert len(loaded) == len(store)
    assert loaded.programs == store.programs
    assert loaded.sig_dim == store.sig_dim
    np.testing.assert_array_equal(loaded.signatures, store.signatures)
    np.testing.assert_array_equal(loaded.weights, store.weights)
    np.testing.assert_array_equal(loaded.cpis, store.cpis)
    assert loaded.program_of_row == store.program_of_row


# ------------------------------------------------- assignment impl parity

def test_assign_impl_parity_kernel_vs_numpy():
    """Acceptance: the kmeans_assign kernel path behind the impl=
    switch must match the numpy reference exactly on assignments."""
    rng = np.random.RandomState(3)
    x = rng.randn(37, 16).astype(np.float32)        # non-tile-aligned N
    c = rng.randn(5, 16).astype(np.float32)
    a_np, d_np = _nearest_numpy(x, c)
    for impl in ("reference", "pallas"):
        a, d = assign_signatures(x, c, impl=impl)
        np.testing.assert_array_equal(a, a_np, err_msg=impl)
        np.testing.assert_allclose(d, d_np, rtol=1e-4, atol=1e-4,
                                   err_msg=impl)


def test_knowledge_base_attach_uses_kernel_impl(blob_centers):
    """attach() through assign_impl="pallas" reproduces the
    reference-impl fingerprints and the numpy oracle's (kernel runs
    inside the query path)."""
    fingerprints = {}
    for impl in ("reference", "pallas"):
        store = _filled_store(blob_centers, ["A", "B"])
        kb = KnowledgeBase(store, assign_impl=impl).build(k=3, seed=0)
        sP, cP = _blob_program(9, blob_centers)
        store.add("P", sP, cpis=cP)
        fingerprints[impl] = kb.attach("P")
    a, _ = _nearest_numpy(sP, kb.archetypes)
    oracle = np.bincount(a, minlength=kb.k) / len(a)
    np.testing.assert_array_equal(fingerprints["pallas"],
                                  fingerprints["reference"])
    np.testing.assert_allclose(fingerprints["reference"], oracle,
                               atol=1e-12)


# ---------------------------------------------------- on-device build

def _align(archetypes, ref):
    """Cluster-label bijection: archetype j -> nearest archetype of `ref`
    (k-means labelings are not canonical)."""
    perm, _ = _nearest_numpy(archetypes, ref)
    assert sorted(perm.tolist()) == list(range(len(ref)))
    return perm


@pytest.mark.parametrize("impl", ["device", "device_kernel"])
def test_device_build_cluster_aligned_with_host(blob_centers, impl):
    """Acceptance: build_impl="device"/"device_kernel" — the jitted
    restart loop over the padded store matrix, Pallas kernels inside for
    device_kernel — must be cluster-aligned bit-compatible with the host
    restart loop (`kmeans` + `representatives`) at tiny k."""
    store = _filled_store(blob_centers, ["A", "B"])
    x = store.signatures.astype(np.float32)
    cents, assign, _ = kmeans(x, 3, seed=0)
    reps = representatives(x, cents, assign)
    dev = KnowledgeBase(store, build_impl=impl).build(k=3, seed=0)
    perm = _align(dev.archetypes, cents)
    rep_cpi = store.cpis[reps].astype(np.float64)
    # identical membership (bit-compatible assignments up to labeling)
    for p in ("A", "B"):
        rows = store.rows_for(p)
        w = store.weights[rows].astype(np.float64)
        host_f = np.zeros(3)
        np.add.at(host_f, assign[rows], w / w.sum())
        f = np.zeros(3)
        np.add.at(f, perm, dev.fingerprints[p])
        np.testing.assert_allclose(f, host_f, atol=1e-12, err_msg=p)
        assert dev.estimate(p).est_cpi == pytest.approx(
            float((host_f * rep_cpi).sum()), rel=1e-6)
    # same representative intervals, module labeling
    np.testing.assert_array_equal(np.sort(dev.rep_global_idx),
                                  np.sort(reps))
    np.testing.assert_allclose(dev.archetypes, cents[perm],
                               rtol=1e-5, atol=1e-5)


def test_device_build_over_grown_padded_store(blob_centers):
    """The device build consumes the pow2-capacity device matrix with a
    pad tail; growing the store (new capacity level) must not leak
    padded zero-rows into clusters or representatives."""
    store = _filled_store(blob_centers, ["A", "B"])   # 150 rows, cap 256
    assert store.capacity > len(store)
    kb = KnowledgeBase(store, build_impl="device").build(k=3, seed=0)
    assert (kb.rep_global_idx < len(store)).all()
    assert kb._all_row_assign().shape == (len(store),)
    for p in ("A", "B"):
        np.testing.assert_allclose(kb.fingerprints[p].sum(), 1.0,
                                   atol=1e-12)


# ------------------------------------------------ backend option values

@pytest.mark.parametrize("option,value", [
    ("impl", "pallas_interpret"), ("assign_impl", "numpy"),
    ("assign_impl", "auto"), ("build_impl", "host"), ("build_impl", "auto"),
])
def test_retired_backend_value_refused(option, value):
    """Each backend option takes its reference or its kernel, nothing
    else: a retired value is refused, naming the two the option takes,
    where the value enters — a service built from its config, and the
    Stage-2 engine (impl) or the knowledge base (the other two)."""
    refused = pytest.raises(ValueError, match=f"^{option} must be one of")
    sig = SignatureConfig(bbe_dim=8, d_model=8, sig_dim=4, max_set=4,
                          num_heads=2)
    bbe = BBEConfig(dim_embeds=(48, 8, 8, 8, 8, 8), num_layers=1,
                    num_heads=2, bbe_dim=8, max_len=16)
    with refused:
        SemanticBBVService.create(
            ServiceConfig(bbe=bbe, sig=sig, **{option: value}))
    if option == "impl":
        with refused:
            Stage2Engine(sig, None, None, None, None, impl=value)
    else:
        with refused:
            KnowledgeBase(SignatureStore(8), **{option: value})


@pytest.mark.parametrize("option,retired,current", [
    ("assign_impl", "numpy", "reference"),
    ("assign_impl", "pallas_interpret", "pallas"),
    ("assign_impl", "auto", "pallas"),
    ("build_impl", "host", "device"),
])
def test_knowledge_base_saved_with_retired_value_loads(
        tmp_path, blob_centers, option, retired, current):
    """A knowledge base saved while the option took `retired` loads with
    the value that replaced it and estimates bit-identically."""
    store = _filled_store(blob_centers, ["A", "B"])
    kb = KnowledgeBase(store).build(k=3, seed=0)
    sP, cP = _blob_program(17, blob_centers)
    store.add("P", sP, cpis=cP)
    before = {p: kb.estimate(p) for p in store.programs}
    setattr(kb, option, retired)           # the meta an older save wrote
    kb.save(str(tmp_path / "knowledge"))
    kb2 = KnowledgeBase.load(str(tmp_path / "knowledge"), store)
    assert getattr(kb2, option) == current
    for p, e1 in before.items():
        e2 = kb2.estimate(p)
        assert e2.est_cpi == e1.est_cpi, p
        np.testing.assert_array_equal(e2.fingerprint, e1.fingerprint)
    # a program ingested after the load is assigned by the loaded backend
    sQ, cQ = _blob_program(18, blob_centers)
    store.add("Q", sQ, cpis=cQ)
    f = kb2.estimate("Q").fingerprint
    a, _ = _nearest_numpy(sQ, kb2.archetypes)
    np.testing.assert_allclose(f, np.bincount(a, minlength=3) / len(a),
                               atol=1e-12)


# ----------------------------------------------------------- attach_many

def test_attach_many_matches_sequential_attach(blob_centers):
    """Acceptance: one batched attach_many pass must produce the same
    fingerprints and CPIEstimates as per-program attach calls."""
    def fresh():
        store = _filled_store(blob_centers, ["A", "B"])
        kb = KnowledgeBase(store).build(k=3, seed=0)
        items = []
        for j, n in enumerate(["P", "Q", "R"]):
            s, c = _blob_program(30 + j, blob_centers)
            items.append((n, s, np.arange(len(s)) + 1.0, c))
        return store, kb, items

    store1, kb1, items = fresh()
    rows = store1.add_many(items)
    assert list(rows) == ["P", "Q", "R"]
    many = kb1.attach_many(["P", "Q", "R"])

    store2, kb2, _ = fresh()
    for n, s, w, c in items:
        store2.add(n, s, weights=w, cpis=c)
    for n in ("P", "Q", "R"):
        f_seq = kb2.attach(n)
        np.testing.assert_array_equal(many[n], f_seq, err_msg=n)
        e1, e2 = kb1.estimate(n), kb2.estimate(n)
        assert e1.est_cpi == e2.est_cpi, n
        assert e1.true_cpi == e2.true_cpi, n
        assert e1.accuracy == e2.accuracy, n
        assert e1.speedup == e2.speedup, n


def test_add_many_single_version_bump(blob_centers):
    store = SignatureStore(8, min_capacity=16)
    s0, c0 = _blob_program(0, blob_centers)
    store.add("A", s0, cpis=c0)
    v = store.version
    items = [("P", s0[:10]), ("Q", s0[10:30], np.ones(20) * 2.0),
             ("P", s0[30:40])]                       # repeated program
    rows = store.add_many(items)
    assert store.version == v + 1                    # ONE bump
    assert len(store) == 75 + 40
    np.testing.assert_array_equal(rows["P"],
                                  np.concatenate([np.arange(75, 85),
                                                  np.arange(105, 115)]))
    np.testing.assert_array_equal(store.rows_for("Q"),
                                  np.arange(85, 105))
    assert store.add_many([]) == {}
    # zero-row programs register (same as add), so attach sees them
    empty = store.add_many([("Z", np.zeros((0, 8), np.float32))])
    assert empty["Z"].shape == (0,)
    assert "Z" in store and store.rows_for("Z").shape == (0,)
    with pytest.raises(ValueError):
        store.add_many([("X", np.ones((2, 5), np.float32))])


# --------------------------------------------------------- attach parity

def test_attach_matches_build_fingerprint_exactly(blob_centers):
    """A program present at build() must fingerprint identically when
    re-attached through the batched kernel query path."""
    store = _filled_store(blob_centers, ["A", "B", "C"])
    kb = KnowledgeBase(store).build(k=3, seed=0)
    built = {p: kb.fingerprints[p].copy() for p in store.programs}
    for p in store.programs:
        attached = kb.attach(p)      # overwrites via the query path
        np.testing.assert_array_equal(attached, built[p], err_msg=p)


def test_attach_unseen_matches_full_rebuild(blob_centers):
    """Acceptance: attaching P to a base built WITHOUT P must match the
    fingerprint a full rebuild INCLUDING P produces (after aligning the
    two bases' cluster labelings — k-means order is not canonical)."""
    base = _filled_store(blob_centers, ["A", "B"])
    kb = KnowledgeBase(base).build(k=3, seed=0)
    sP, cP = _blob_program(11, blob_centers)
    base.add("P", sP, cpis=cP)
    f_attach = kb.attach("P")

    full = _filled_store(blob_centers, ["A", "B"])
    full.add("P", sP, cpis=cP)
    kb_full = KnowledgeBase(full).build(k=3, seed=0)
    # align: archetype j of the rebuild -> nearest archetype of the base
    perm = _align(kb_full.archetypes, kb.archetypes)
    f_rebuild = np.zeros_like(f_attach)
    np.add.at(f_rebuild, perm, kb_full.fingerprints["P"])
    np.testing.assert_allclose(f_attach, f_rebuild, atol=1e-12)
    assert kb.estimate("P").est_cpi == pytest.approx(
        kb_full.estimate("P").est_cpi, rel=1e-3)


def test_rebuild_invalidates_row_assign_cache(blob_centers):
    """Regression: re-build() must drop the whole-store assignment
    cache — stale assignments against the OLD archetypes would index
    out of range (or silently permute) under the new ones."""
    store = _filled_store(blob_centers, ["A", "B"])
    kb = KnowledgeBase(store).build(k=4, seed=0)
    sP, cP = _blob_program(19, blob_centers)
    store.add("P", sP, cpis=cP)
    kb.attach("P")                        # populates the version cache
    kb.build(k=2, seed=0)                 # same store version, new k
    f = kb.attach("P")                    # must NOT reuse k=4 labels
    assert f.shape == (2,)
    np.testing.assert_allclose(f.sum(), 1.0, atol=1e-12)
    assert (kb._all_row_assign() < 2).all()


def test_estimate_refreshes_after_streaming_add(blob_centers):
    """Regression: rows streamed into an already-attached program must
    be reflected by the next estimate, not silently ignored."""
    store = _filled_store(blob_centers, ["A", "B"])
    kb = KnowledgeBase(store).build(k=3, seed=0)
    sP, cP = _blob_program(23, blob_centers)
    third = len(sP) // 3
    store.add("P", sP[:third], cpis=cP[:third])
    f1 = kb.estimate("P").fingerprint.copy()
    store.add("P", sP[third:], cpis=cP[third:])      # streaming ingest
    f2 = kb.estimate("P").fingerprint
    assert not np.array_equal(f1, f2)
    # the refreshed fingerprint covers ALL of P's rows vs the same
    # frozen archetypes
    rows = store.rows_for("P")
    a, _ = kb.assign(store.signatures[rows])
    w = store.weights[rows].astype(np.float64)
    f_exp = np.zeros(kb.k)
    np.add.at(f_exp, a.astype(np.int64), w / w.sum())
    np.testing.assert_allclose(f2, f_exp, atol=1e-12)


def test_estimate_before_build_raises(blob_centers):
    store = _filled_store(blob_centers, ["A"])
    kb = KnowledgeBase(store)
    with pytest.raises(RuntimeError):
        kb.estimate("A")
    with pytest.raises(RuntimeError):
        KnowledgeBase(SignatureStore(8)).build(k=2)


# ------------------------------------------------------------- estimates

def test_estimate_fields_and_weight_aware_speedup(blob_centers):
    w = [np.full(75, 2.0e6), np.linspace(1e6, 5e6, 75)]
    store = _filled_store(blob_centers, ["A", "B"], weights=w)
    kb = KnowledgeBase(store).build(k=3, seed=0)
    est = kb.estimate("B")
    assert isinstance(est, CPIEstimate)
    assert est.k == 3
    np.testing.assert_allclose(est.fingerprint.sum(), 1.0, atol=1e-9)
    assert est.accuracy == cpi_accuracy(est.est_cpi, est.true_cpi)
    # weight-aware: total store weight over the k reps' weights
    total = store.total_weight
    sim = float(store.weights[kb.rep_global_idx].astype(np.float64).sum())
    assert est.total_weight == pytest.approx(total)
    assert est.simulated_weight == pytest.approx(sim)
    assert est.speedup == pytest.approx(total / sim)
    assert est.speedup != pytest.approx(len(store) / kb.k)  # non-uniform


def test_estimate_without_ground_truth(blob_centers):
    store = _filled_store(blob_centers, ["A", "B"])
    kb = KnowledgeBase(store).build(k=3, seed=0)
    sP, _ = _blob_program(13, blob_centers)
    store.add("Q", sP)                               # no cpis
    est = kb.estimate("Q")                           # attach on demand
    assert est.true_cpi is None and est.accuracy is None
    assert np.isfinite(est.est_cpi)


def test_save_load_estimate_bit_identical(tmp_path, blob_centers):
    """Acceptance: SignatureStore (+KB) save -> load -> estimate must be
    bit-identical to the in-memory answer."""
    store = _filled_store(blob_centers, ["A", "B"],
                          weights=[np.arange(75) + 1.0,
                                   np.arange(75) + 3.0])
    kb = KnowledgeBase(store).build(k=3, seed=0)
    sP, cP = _blob_program(17, blob_centers)
    store.add("P", sP, cpis=cP)
    kb.attach("P")
    before = {p: kb.estimate(p) for p in store.programs}

    store.save(str(tmp_path / "store"))
    kb.save(str(tmp_path / "knowledge"))
    store2 = SignatureStore.load(str(tmp_path / "store"))
    kb2 = KnowledgeBase.load(str(tmp_path / "knowledge"), store2)
    for p, e1 in before.items():
        e2 = kb2.estimate(p)
        assert e2.est_cpi == e1.est_cpi, p           # bit-identical
        assert e2.true_cpi == e1.true_cpi, p
        assert e2.accuracy == e1.accuracy, p
        assert e2.speedup == e1.speedup, p
        np.testing.assert_array_equal(e2.fingerprint, e1.fingerprint)


def test_legacy_shim_matches_knowledge_base(blob_centers):
    """universal_clustering warns and reproduces the KnowledgeBase path
    bit-for-bit (same kmeans call, same fingerprint accumulation)."""
    sigs, cpis, pids = [], [], []
    for i, name in enumerate(["A", "B"]):
        s, c = _blob_program(i, blob_centers)
        sigs.append(s)
        cpis.append(c)
        pids += [name] * len(s)
    X, C = np.concatenate(sigs), np.concatenate(cpis)
    with pytest.warns(DeprecationWarning):
        res = universal_clustering(X, pids, C, k=3, seed=0)
    kb = KnowledgeBase(_filled_store(blob_centers, ["A", "B"])).build(
        k=3, seed=0)
    np.testing.assert_array_equal(res.rep_global_idx, kb.rep_global_idx)
    for p in ("A", "B"):
        np.testing.assert_array_equal(res.fingerprints[p],
                                      kb.fingerprints[p])
        assert res.est_cpi[p] == kb.est_cpi[p]
        assert res.accuracy(p) == pytest.approx(
            kb.estimate(p).accuracy, abs=1e-12)


# ------------------------------------------------------- service facade

@pytest.fixture(scope="module")
def tiny_world():
    """Real (untrained) pipeline, the blocks of 3 traced programs, their
    intervals and ground-truth CPIs."""
    from repro.data.asmgen import spec_programs
    progs = spec_programs("int")[:3]
    bt = block_table(progs)
    per_prog = {p.name: trace_program(p, 16) for p in progs}
    cpis = {n: np.array([interval_cpi(iv, bt, INORDER_CPU) for iv in ivs])
            for n, ivs in per_prog.items()}
    cfg = ServiceConfig(
        bbe=BBEConfig(dim_embeds=(48, 8, 8, 8, 8, 8), num_layers=2,
                      num_heads=2, bbe_dim=32, max_len=64),
        sig=SignatureConfig(bbe_dim=32, d_model=32, sig_dim=16, max_set=48,
                            num_heads=2),
        k=6, store_min_capacity=16)
    pipe = SemanticBBVPipeline.from_config(cfg.pipeline_config())
    return pipe, cfg, list(bt.values()), progs, per_prog, cpis


def _fresh_service(tiny_world):
    """A service over the shared pipeline with every block ingested
    (the pipeline memoizes BBEs) and an empty store."""
    pipe, cfg, blocks, *_ = tiny_world
    svc = SemanticBBVService.from_pipeline(pipe, cfg)
    svc.ingest_blocks(blocks)
    return svc


def _ingest_build_attach(svc, names, per_prog, cpis):
    """The full facade flow: ingest all but the last program, build, then
    ingest the held-out last one and attach it (the reuse path)."""
    rows = [svc.ingest_intervals(n, per_prog[n], cpis=cpis[n])
            for n in names[:-1]]
    kb = svc.build()
    svc.ingest_intervals(names[-1], per_prog[names[-1]],
                         cpis=cpis[names[-1]])
    return rows, kb, svc.attach(names[-1])


@pytest.fixture(scope="module")
def tiny_service(tiny_world):
    """A service in the state after the full facade flow: every program
    ingested, the knowledge base built and every program fingerprinted."""
    *_, progs, per_prog, cpis = tiny_world
    svc = _fresh_service(tiny_world)
    _ingest_build_attach(svc, [p.name for p in progs], per_prog, cpis)
    return svc, progs, per_prog, cpis


def test_service_ingest_build_attach_estimate(tiny_world):
    *_, progs, per_prog, cpis = tiny_world
    svc = _fresh_service(tiny_world)
    names = [p.name for p in progs]
    rows, kb, f = _ingest_build_attach(svc, names, per_prog, cpis)
    assert [len(r) for r in rows] == [16] * (len(names) - 1)
    assert kb.k == 6 and kb.built
    np.testing.assert_allclose(f.sum(), 1.0, atol=1e-9)
    for n in names:
        est = svc.estimate(n)
        assert est.program == n
        assert np.isfinite(est.est_cpi) and est.est_cpi > 0
        assert est.accuracy is not None
        assert est.speedup > 1.0
    # fingerprints are distributions over archetypes
    assert set(kb.est_cpi) == set(names)


def test_service_attach_intervals_without_ingest(tiny_service):
    """attach_intervals fingerprints a program that never enters the
    store (pure query); neither the store nor the knowledge base may
    keep any footprint of it."""
    svc, progs, per_prog, cpis = tiny_service
    assert svc.kb.built
    n_before = len(svc.store)
    name = progs[0].name
    f = svc.attach_intervals("ephemeral", per_prog[name])
    assert len(svc.store) == n_before
    np.testing.assert_allclose(f.sum(), 1.0, atol=1e-9)
    np.testing.assert_allclose(f, svc.kb.fingerprints[name], atol=1e-9)
    # pure query: no KB state, no avg_accuracy/save() skew, and a name
    # collision with a stored program cannot shadow it
    assert "ephemeral" not in svc.kb.fingerprints
    assert "ephemeral" not in svc.kb.est_cpi
    before = svc.kb.fingerprints[name].copy()
    svc.attach_intervals(name, per_prog[name][:4])
    np.testing.assert_array_equal(svc.kb.fingerprints[name], before)


def test_service_save_load_roundtrip(tiny_service, tmp_path):
    svc, progs, per_prog, cpis = tiny_service
    out = str(tmp_path / "svc")
    svc.save(out)
    assert os.path.exists(os.path.join(out, "summary.json"))
    svc2 = SemanticBBVService.load(out, svc.pipe)
    assert svc2.store.programs == svc.store.programs
    for n in svc.store.programs:
        e1, e2 = svc.estimate(n), svc2.estimate(n)
        assert e1.est_cpi == e2.est_cpi
        assert e1.speedup == e2.speedup


def test_service_attach_many_before_build_leaves_no_rows(blob_centers):
    """Regression: the Mapping form must fail BEFORE ingesting — orphan
    rows from a failed call would double-ingest on retry after build."""
    svc = SemanticBBVService.create(ServiceConfig(k=3))
    n_before = len(svc.store)
    with pytest.raises(RuntimeError, match="build"):
        svc.attach_many({"P": []})
    assert len(svc.store) == n_before
    assert svc.store.version == 0


def test_service_attach_many_pipelined(tiny_service):
    """Service-level attach_many({program: intervals}) must ingest via
    one add_many + one batched assignment and match what sequential
    ingest_intervals + attach produces on the same knowledge base."""
    svc, progs, per_prog, cpis = tiny_service
    assert svc.kb.built
    names = [p.name for p in progs]
    # sequential oracle fingerprints from the already-attached programs
    want = {n: svc.kb.fingerprints[n].copy() for n in names}
    version_before = svc.store.version
    many = svc.attach_many(
        {f"{n}#clone": per_prog[n] for n in names},
        cpis={f"{n}#clone": cpis[n] for n in names})
    assert svc.store.version == version_before + 1   # one add_many bump
    for n in names:
        np.testing.assert_allclose(many[f"{n}#clone"], want[n],
                                   atol=1e-9, err_msg=n)
        e_clone = svc.estimate(f"{n}#clone")
        e_orig = svc.estimate(n)
        assert e_clone.est_cpi == pytest.approx(e_orig.est_cpi, rel=1e-9)
    # name-sequence form re-attaches already-stored programs in one pass
    again = svc.attach_many(names)
    for n in names:
        np.testing.assert_array_equal(again[n], svc.kb.fingerprints[n])


def test_interval_signatures_many_bit_identical(tiny_service):
    """Cross-program pipelined batching must not change any signature:
    one concatenated stream == per-program calls, bit for bit."""
    svc, progs, per_prog, _ = tiny_service
    by_prog = {p.name: per_prog[p.name] for p in progs}
    batch = svc.cfg.signature_batch
    many = svc.pipe.interval_signatures_many(by_prog, svc.bbe_table,
                                             batch)
    for name, ivs in by_prog.items():
        solo = svc.pipe.interval_signatures(ivs, svc.bbe_table, batch)
        np.testing.assert_array_equal(many[name], solo, err_msg=name)


def test_pipeline_config_validation():
    cfg = PipelineConfig(bbe=BBEConfig(dim_embeds=(48, 8, 8, 8, 8, 8),
                                       num_layers=2, num_heads=2,
                                       bbe_dim=32, max_len=64),
                         sig=SignatureConfig(bbe_dim=16))
    with pytest.raises(ValueError):
        cfg.resolve()
    pipe = SemanticBBVPipeline.from_config(PipelineConfig(
        bbe=BBEConfig(dim_embeds=(48, 8, 8, 8, 8, 8), num_layers=2,
                      num_heads=2, bbe_dim=32, max_len=64)))
    assert pipe.sig_cfg.bbe_dim == 32
