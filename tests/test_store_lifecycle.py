"""Store lifecycle tests: tombstone eviction, device-side compaction,
TTL/LRU policies, KnowledgeBase remap/re-pinning, and the vacuum
entrypoint — including the ISSUE's edge cases (evict-all-rows of a
program, compact-then-load-old-KB, eviction during attach_many, and
bit-identical estimates across vacuum for untouched programs)."""
import numpy as np
import pytest

from repro.api import (
    EvictionPolicy, KnowledgeBase, SignatureStore, assign_signatures,
    select_victims, vacuum,
)
from repro.api.store import _capacity_for
from repro.utils import tracing


def _blob_program(seed, centers, n_per=25, noise=0.05):
    rng = np.random.RandomState(seed)
    sigs, cpis = [], []
    for ph, c in enumerate(centers):
        sigs.append(c + rng.randn(n_per, centers.shape[1]) * noise)
        cpis.append(np.full(n_per, 1.0 + 2.0 * ph))
    return (np.concatenate(sigs).astype(np.float32),
            np.concatenate(cpis).astype(np.float32))


@pytest.fixture(scope="module")
def blob_centers():
    return (np.random.RandomState(7).randn(3, 8) * 6).astype(np.float32)


def _filled_store(blob_centers, names):
    store = SignatureStore(8, min_capacity=16)
    for i, name in enumerate(names):
        s, c = _blob_program(i, blob_centers)
        store.add(name, s, weights=np.arange(len(s)) + 1.0, cpis=c)
    return store


# ---------------------------------------------------------------- eviction

def test_evict_tombstones_not_renumbering(blob_centers):
    store = _filled_store(blob_centers, ["A", "B"])
    n, v = len(store), store.version
    w_total = store.total_weight
    rows_b = store.rows_for("B")
    assert store.evict(rows_b[:10]) == 10
    assert len(store) == n                     # slots unchanged
    assert store.n_alive == n - 10
    assert store.has_tombstones
    assert store.version == v + 1
    # rows_for sees only live rows; other programs untouched
    np.testing.assert_array_equal(store.rows_for("B"), rows_b[10:])
    np.testing.assert_array_equal(store.rows_for("A"), np.arange(75))
    # total_weight drops by exactly the evicted rows' weight
    gone = store.weights[rows_b[:10]].astype(np.float64).sum()
    assert store.total_weight == pytest.approx(w_total - gone)
    # double-evict is a no-op (no version bump)
    v2 = store.version
    assert store.evict(rows_b[:10]) == 0
    assert store.version == v2
    # device mask: zeros exactly at the tombstones + pad tail
    mask = np.asarray(store.device_valid)
    assert mask.shape == (store.capacity,)
    np.testing.assert_array_equal(mask[:n], store.alive_mask)
    np.testing.assert_array_equal(mask[n:], 0.0)
    with pytest.raises(IndexError):
        store.evict(np.array([len(store)]))


def test_evict_all_rows_of_a_program(blob_centers):
    """Edge case: a fully-evicted program stays registered (until
    compact) but is invisible to queries and un-fingerprint-able."""
    store = _filled_store(blob_centers, ["A", "B"])
    kb = KnowledgeBase(store).build(k=3, seed=0)
    assert store.evict_program("B") == 75
    assert "B" in store and store.rows_for("B").shape == (0,)
    with pytest.raises(ValueError, match="no live rows"):
        kb.attach("B")
    with pytest.raises(ValueError, match="no live rows"):
        kb.estimate("B")       # re-attach on shrunk rows must not lie
    # A is untouched and still estimable
    assert np.isfinite(kb.estimate("A").est_cpi)
    # compact drops B from the registry entirely
    store.compact()
    assert "B" not in store
    with pytest.raises(KeyError):
        store.rows_for("B")


def test_touch_is_metadata_only(blob_centers):
    store = _filled_store(blob_centers, ["A"])
    v, clock = store.version, store.clock
    store.touch(np.arange(5))
    assert store.version == v                  # caches stay warm
    assert store.clock == clock + 1
    np.testing.assert_array_equal(store.last_used[:5], clock)
    store.touch(np.zeros(0, np.int64))         # empty touch: no tick
    assert store.clock == clock + 1


# -------------------------------------------------------------- compaction

def test_compact_bit_identical_to_fresh_store(blob_centers):
    store = _filled_store(blob_centers, ["A", "B", "C"])
    n = len(store)
    _ = store.device_matrix                    # force device residency
    rng = np.random.RandomState(0)
    dead = rng.choice(n, size=n // 2, replace=False)
    keep = np.setdiff1d(np.arange(n), dead)
    live_sigs = store.signatures[keep].copy()
    live_uids = store.uids[keep].copy()
    store.evict(dead)
    remap = store.compact()
    # remap: -1 at dead rows, dense ascending at survivors
    assert remap.shape == (n,)
    np.testing.assert_array_equal(remap[dead], -1)
    np.testing.assert_array_equal(remap[keep], np.arange(keep.size))
    # dense again, capacity shrunk to the smallest power of two
    assert len(store) == store.n_alive == keep.size
    assert not store.has_tombstones
    assert store.capacity == _capacity_for(keep.size, 16)
    # bit-identical to a fresh store holding only the live rows — on
    # host AND on the device matrix rebuilt by the gather
    np.testing.assert_array_equal(store.signatures, live_sigs)
    np.testing.assert_array_equal(np.asarray(store.device_matrix),
                                  np.concatenate([
                                      live_sigs,
                                      np.zeros((store.capacity - keep.size,
                                                8), np.float32)]))
    # uids survive (the persistent handle)
    np.testing.assert_array_equal(store.uids, live_uids)
    np.testing.assert_array_equal(store.rows_of_uids(live_uids),
                                  np.arange(keep.size))
    assert (store.rows_of_uids(np.asarray([10**9])) == -1).all()


def test_compact_noop_without_tombstones(blob_centers):
    store = _filled_store(blob_centers, ["A"])
    v = store.version
    remap = store.compact()
    np.testing.assert_array_equal(remap, np.arange(75))
    assert store.version == v                  # nothing happened


def test_save_load_roundtrips_tombstones_bit_identically(
        tmp_path, blob_centers):
    store = _filled_store(blob_centers, ["A", "B"])
    store.touch(np.arange(30, 40))
    store.evict(np.arange(10, 50))
    store.save(str(tmp_path / "store"))
    loaded = SignatureStore.load(str(tmp_path / "store"))
    assert len(loaded) == len(store)
    assert loaded.n_alive == store.n_alive
    assert loaded.clock == store.clock
    np.testing.assert_array_equal(loaded.alive_mask, store.alive_mask)
    np.testing.assert_array_equal(loaded.uids, store.uids)
    np.testing.assert_array_equal(loaded.last_used, store.last_used)
    np.testing.assert_array_equal(loaded.inserted_at, store.inserted_at)
    np.testing.assert_array_equal(loaded.signatures, store.signatures)
    np.testing.assert_array_equal(loaded.rows_for("A"),
                                  store.rows_for("A"))
    # a compaction after reload behaves exactly like pre-save
    r1, r2 = store.compact(), loaded.compact()
    np.testing.assert_array_equal(r1, r2)
    np.testing.assert_array_equal(loaded.signatures, store.signatures)


def test_load_pre_lifecycle_checkpoint(tmp_path, blob_centers):
    """Checkpoints written before the lifecycle fields existed (no
    alive/uids/inserted_at/last_used arrays, no rep_uid) must load as
    an all-alive store with synthesized uids."""
    from repro.train.checkpoint import save_checkpoint

    store = _filled_store(blob_centers, ["A", "B"])
    kb = KnowledgeBase(store).build(k=3, seed=0)
    # write the PR-3-era formats by hand
    save_checkpoint(str(tmp_path / "store"), store.version, {
        "signatures": store.signatures.copy(),
        "weights": store.weights.copy(),
        "cpis": store.cpis.copy(),
    }, meta={"sig_dim": 8, "min_capacity": 16,
             "program_of_row": store.program_of_row})
    save_checkpoint(str(tmp_path / "kb"), 1, {
        "archetypes": kb.archetypes, "rep_cpi": kb.rep_cpi,
        "rep_weight": kb.rep_weight, "rep_global_idx": kb.rep_global_idx,
    }, meta={"k": kb.k, "seed": 0, "assign_impl": "reference",
             "build_impl": "host", "rep_program": kb.rep_program,
             "built_version": store.version,
             "fingerprints": {p: np.asarray(f).tolist()
                              for p, f in kb.fingerprints.items()},
             "est_cpi": kb.est_cpi, "true_cpi": kb.true_cpi})

    loaded = SignatureStore.load(str(tmp_path / "store"))
    assert loaded.n_alive == len(loaded) == len(store)
    np.testing.assert_array_equal(loaded.uids, np.arange(len(store)))
    # missing stamps default to NOW (age 0), not 0 (maximal age) — a
    # TTL vacuum right after upgrading must not evict the whole store
    np.testing.assert_array_equal(loaded.last_used, loaded.clock)
    np.testing.assert_array_equal(loaded.inserted_at, loaded.clock)
    assert select_victims(loaded, EvictionPolicy(ttl=1)).size == 0
    kb2 = KnowledgeBase.load(str(tmp_path / "kb"), loaded)
    np.testing.assert_array_equal(kb2.rep_global_idx, kb.rep_global_idx)
    np.testing.assert_array_equal(kb2.rep_uid,
                                  loaded.uids[kb.rep_global_idx])
    for p in ("A", "B"):
        assert kb2.estimate(p).est_cpi == kb.estimate(p).est_cpi


# -------------------------------------------------- masked device build

@pytest.mark.parametrize("impl", ["host", "device", "device_kernel"])
def test_build_skips_tombstones(blob_centers, impl):
    """A build over a tombstoned store must equal (cluster-aligned) a
    build over a fresh store containing only the live rows — dead rows
    contribute zero mass to seeding, updates and representatives."""
    store = _filled_store(blob_centers, ["A", "B"])
    rng = np.random.RandomState(1)
    dead = rng.choice(len(store), size=40, replace=False)
    store.evict(dead)
    kb = KnowledgeBase(store, build_impl=impl).build(k=3, seed=0)
    # no representative sits on a dead row
    assert store.alive_mask[kb.rep_global_idx].all()
    # every fingerprint is a distribution over live rows only
    for p in ("A", "B"):
        np.testing.assert_allclose(kb.fingerprints[p].sum(), 1.0,
                                   atol=1e-12)
    # the 3 blob centers are recovered despite the holes
    from repro.api import assign_signatures
    perm, d2 = assign_signatures(
        np.asarray(blob_centers, np.float32), kb.archetypes, impl="numpy")
    assert sorted(perm.tolist()) == [0, 1, 2]
    assert (d2 < 0.1).all()


def test_postcompact_build_matches_fresh_store_bitwise(blob_centers):
    """Acceptance: after compact(), build() over the compacted store is
    bit-compatible with a fresh store containing only the live rows
    (same dense arrays, same seeds -> same centroids/assignments)."""
    store = _filled_store(blob_centers, ["A", "B"])
    dead = np.arange(0, 150, 3)
    store.evict(dead)
    store.compact()

    fresh = SignatureStore(8, min_capacity=16)
    keep = np.setdiff1d(np.arange(150), dead)
    for name, lo, hi in (("A", 0, 75), ("B", 75, 150)):
        sel = keep[(keep >= lo) & (keep < hi)]
        s, c = _blob_program(0 if name == "A" else 1, blob_centers)
        w = np.arange(75) + 1.0
        fresh.add(name, s[sel - lo], weights=w[sel - lo],
                  cpis=c[sel - lo])

    np.testing.assert_array_equal(store.signatures, fresh.signatures)
    kb1 = KnowledgeBase(store, build_impl="device").build(k=3, seed=0)
    kb2 = KnowledgeBase(fresh, build_impl="device").build(k=3, seed=0)
    np.testing.assert_array_equal(kb1.archetypes, kb2.archetypes)
    np.testing.assert_array_equal(kb1.rep_global_idx, kb2.rep_global_idx)
    for p in ("A", "B"):
        np.testing.assert_array_equal(kb1.fingerprints[p],
                                      kb2.fingerprints[p])
        assert kb1.estimate(p).est_cpi == kb2.estimate(p).est_cpi


# ----------------------------------------------------- KnowledgeBase remap

def test_apply_remap_moves_and_repins_representatives(blob_centers):
    store = _filled_store(blob_centers, ["A", "B"])
    kb = KnowledgeBase(store).build(k=3, seed=0)
    rep_cpi = kb.rep_cpi.copy()
    rep_weight = kb.rep_weight.copy()
    victim_rep = int(kb.rep_global_idx[0])
    victim_uid = int(kb.rep_uid[0])
    store.evict(np.asarray([victim_rep]))
    remap = store.compact()
    repinned = kb.apply_remap(remap)
    assert repinned == 1
    # every rep points at a live row again, uid bookkeeping consistent
    assert (kb.rep_global_idx >= 0).all()
    assert store.alive_mask[kb.rep_global_idx].all()
    np.testing.assert_array_equal(store.uids[kb.rep_global_idx],
                                  kb.rep_uid)
    assert kb.rep_uid[0] != victim_uid
    # survivors just moved through the remap
    np.testing.assert_array_equal(
        kb.rep_global_idx[1:],
        store.rows_of_uids(kb.rep_uid[1:]))
    # recorded simulation results survive re-pinning
    np.testing.assert_array_equal(kb.rep_cpi, rep_cpi)
    np.testing.assert_array_equal(kb.rep_weight, rep_weight)
    # the new rep is the nearest live member of archetype 0
    alive_assign = kb._all_row_assign()
    j = kb.rep_global_idx[0]
    assert alive_assign[j] == 0


def test_compact_then_load_old_kb_remaps_via_uids(tmp_path, blob_centers):
    """Edge case: a KB saved BEFORE compaction must reload valid against
    the compacted store (uids re-resolve positions; evicted reps
    re-pin), with bit-identical estimates on untouched programs."""
    store = _filled_store(blob_centers, ["A", "B"])
    kb = KnowledgeBase(store).build(k=3, seed=0)
    kb.save(str(tmp_path / "kb"))
    before = {p: kb.estimate(p) for p in ("A", "B")}
    rep_uids = kb.rep_uid.copy()

    victim = int(kb.rep_global_idx[1])
    store.evict(np.concatenate([[victim],
                                store.rows_for("A")[:5]]))
    store.compact()                            # OLD kb was never told

    kb2 = KnowledgeBase.load(str(tmp_path / "kb"), store)
    assert (kb2.rep_global_idx >= 0).all()
    assert store.alive_mask[kb2.rep_global_idx].all()
    # non-evicted reps resolved to their NEW positions via uid
    same = rep_uids != rep_uids[1]
    np.testing.assert_array_equal(kb2.rep_uid[same], rep_uids[same])
    assert kb2.rep_uid[1] != rep_uids[1]       # re-pinned
    # untouched program: est_cpi/accuracy bit-identical (B lost no rows;
    # A did, so only its fingerprint refreshes on demand)
    eB = kb2.estimate("B")
    assert eB.est_cpi == before["B"].est_cpi
    assert eB.true_cpi == before["B"].true_cpi
    assert eB.accuracy == before["B"].accuracy
    np.testing.assert_array_equal(eB.fingerprint,
                                  before["B"].fingerprint)


def test_eviction_during_attach_many(blob_centers):
    """Edge case: rows evicted between ingest and attach_many — the
    batched pass must fingerprint from live rows only, matching a
    sequential attach on the same store state."""
    store = _filled_store(blob_centers, ["A", "B"])
    kb = KnowledgeBase(store).build(k=3, seed=0)
    items = []
    for j, n in enumerate(["P", "Q"]):
        s, c = _blob_program(40 + j, blob_centers)
        items.append((n, s, np.arange(len(s)) + 1.0, c))
    rows = store.add_many(items)
    store.evict(rows["P"][::2])                # half of P dies pre-attach
    many = kb.attach_many(["P", "Q"])

    # oracle: manual fingerprint over P's live rows
    live = store.rows_for("P")
    np.testing.assert_array_equal(live, rows["P"][1::2])
    a, _ = kb.assign(store.signatures[live])
    w = store.weights[live].astype(np.float64)
    f_exp = np.zeros(3)
    np.add.at(f_exp, a.astype(np.int64), w / w.sum())
    np.testing.assert_allclose(many["P"], f_exp, atol=1e-12)
    np.testing.assert_allclose(many["P"].sum(), 1.0, atol=1e-12)
    # attach_many on a fully-evicted program raises, not silently zeros
    store.evict_program("Q")
    with pytest.raises(ValueError, match="no live rows"):
        kb.attach_many(["Q"])


# ------------------------------------------------------------ policies

def _stamped_store():
    """4 rows with controlled last_used stamps: clock advances one tick
    per add, then touches refresh rows 2,3."""
    store = SignatureStore(2, min_capacity=4)
    for i in range(4):
        store.add(f"p{i}", np.full((1, 2), float(i), np.float32))
    store.touch(np.asarray([2]))
    store.touch(np.asarray([3]))
    return store      # last_used = [0,1,2,3] -> [0,1,4,5], clock=6


def test_select_victims_ttl():
    store = _stamped_store()
    assert store.clock == 6
    np.testing.assert_array_equal(
        select_victims(store, EvictionPolicy(ttl=4)), [0, 1])
    np.testing.assert_array_equal(
        select_victims(store, EvictionPolicy(ttl=100)), [])
    np.testing.assert_array_equal(
        select_victims(store, EvictionPolicy(ttl=0)), [0, 1, 2, 3])


def test_select_victims_lru():
    store = _stamped_store()
    np.testing.assert_array_equal(
        select_victims(store, EvictionPolicy(max_rows=2)), [0, 1])
    np.testing.assert_array_equal(
        select_victims(store, EvictionPolicy(max_rows=4)), [])
    # TTL victims don't count against the LRU budget twice
    np.testing.assert_array_equal(
        select_victims(store, EvictionPolicy(ttl=4, max_rows=1)),
        [0, 1, 2])
    with pytest.raises(ValueError):
        EvictionPolicy(ttl=-1)
    with pytest.raises(ValueError):
        EvictionPolicy(compact_dead_fraction=2.0)


def test_vacuum_end_to_end_estimates_bit_identical(blob_centers):
    """Acceptance edge case: vacuum() that evicts program B must leave
    estimate() on untouched program A bit-identical (est/true/accuracy/
    fingerprint), with speedup reflecting the smaller live store."""
    store = _filled_store(blob_centers, ["A", "B"])
    kb = KnowledgeBase(store).build(k=3, seed=0)
    eA = kb.estimate("A")
    store.evict_program("B")
    report = vacuum(store, kb, EvictionPolicy())
    assert report.compacted and report.evicted == 0
    assert report.rows_after == 75
    assert report.capacity_after == 128
    assert (kb.rep_global_idx >= 0).all()
    eA2 = kb.estimate("A")
    assert eA2.est_cpi == eA.est_cpi
    assert eA2.true_cpi == eA.true_cpi
    assert eA2.accuracy == eA.accuracy
    np.testing.assert_array_equal(eA2.fingerprint, eA.fingerprint)
    # B is gone from the knowledge base
    assert "B" not in kb.fingerprints and "B" not in kb.est_cpi
    # speedup denominator (simulated reps) unchanged; numerator shrank
    assert eA2.simulated_weight == eA.simulated_weight
    assert eA2.total_weight < eA.total_weight


def test_vacuum_that_empties_the_store_does_not_crash(blob_centers):
    """Regression: a scheduled vacuum that evicts every live row must
    complete (compacted, zero re-pins) instead of raising mid-mutation;
    a later re-ingest + build() recovers the knowledge base."""
    store = _filled_store(blob_centers, ["A", "B"])
    kb = KnowledgeBase(store).build(k=3, seed=0)
    store.evict_program("A")
    store.evict_program("B")
    report = vacuum(store, kb, EvictionPolicy())
    assert report.compacted and report.repinned == 0
    assert len(store) == 0 and store.capacity == 16
    assert (kb.rep_global_idx == -1).all()
    assert kb.fingerprints == {}
    with pytest.raises(KeyError):
        kb.estimate("A")
    # recovery: fresh rows, fresh build
    s, c = _blob_program(3, blob_centers)
    store.add("C", s, cpis=c)
    kb.build(k=3, seed=0)
    assert store.alive_mask[kb.rep_global_idx].all()
    assert np.isfinite(kb.estimate("C").est_cpi)


def test_service_save_after_eviction_reloads_bit_identical(tmp_path):
    """Regression: service.save() must persist the KB AFTER refreshing
    estimates — evicting rows between the last attach and save() used to
    checkpoint a stale fingerprint while summary.json recorded the fresh
    one, breaking the reload contract (api-smoke's verify_kb_reload)."""
    import json

    from repro.api import SemanticBBVService, ServiceConfig
    from repro.core.bbe import BBEConfig
    from repro.core.signature import SignatureConfig
    from repro.data.asmgen import spec_programs
    from repro.data.perfmodel import INORDER_CPU, interval_cpi
    from repro.data.trace import block_table, trace_program

    progs = spec_programs("int")[:2]
    bt = block_table(progs)
    cfg = ServiceConfig(
        bbe=BBEConfig(dim_embeds=(48, 8, 8, 8, 8, 8), num_layers=2,
                      num_heads=2, bbe_dim=32, max_len=64),
        sig=SignatureConfig(bbe_dim=32, d_model=32, sig_dim=16,
                            max_set=48, num_heads=2),
        k=3, store_min_capacity=16)
    svc = SemanticBBVService.create(cfg)
    svc.ingest_blocks(list(bt.values()))
    for p in progs:
        ivs = trace_program(p, 8)
        svc.ingest_intervals(
            p.name, ivs,
            cpis=[interval_cpi(iv, bt, INORDER_CPU) for iv in ivs])
    svc.build()
    victim = progs[0].name
    svc.estimate(victim)                       # fingerprint goes stale...
    svc.store.evict(svc.store.rows_for(victim)[:4])   # ...right here
    out = str(tmp_path / "svc")
    svc.save(out)

    with open(f"{out}/summary.json") as f:
        summary = json.load(f)
    svc2 = SemanticBBVService.load(out, svc.pipe)
    for name, want in summary["estimates"].items():
        assert svc2.estimate(name).est_cpi == want["est_cpi"], name


def test_vacuum_compact_threshold(blob_centers):
    store = _filled_store(blob_centers, ["A", "B"])
    store.evict(np.arange(10))                 # 10/150 dead
    report = vacuum(store, None,
                    EvictionPolicy(compact_dead_fraction=0.25))
    assert not report.compacted                # below threshold
    assert store.has_tombstones
    report = vacuum(store, None,
                    EvictionPolicy(compact_dead_fraction=0.05))
    assert report.compacted
    assert not store.has_tombstones
    # nothing-to-do pass is mutation-free
    v = store.version
    report = vacuum(store, None, EvictionPolicy())
    assert report.evicted == 0 and not report.compacted
    assert store.version == v


# ------------------------------------------------- per-row label cache

def _assign_all_spans(since):
    return [s.counts for s in tracing.recent()
            if s.name == "kb.assign_all" and s.span_id > since]


def _mark():
    with tracing.span("test.mark") as m:
        pass
    return m.span_id


@pytest.mark.parametrize("impl", ["numpy", "pallas_interpret", "reference"])
def test_incremental_row_assign_matches_whole_store_pass(blob_centers, impl):
    """After every step of a serving sequence the cached labels equal a
    fresh whole-store pass with the same backend (under "reference",
    except where a row's two nearest archetypes lie within 1e-6): only
    new rows are assigned, evictions and an applied remap assign nothing,
    and a compaction the base never saw forces one whole-store pass."""
    store = _filled_store(blob_centers, ["A", "B"])        # 150 rows
    kb = KnowledgeBase(store, assign_impl=impl).build(k=3, seed=0)

    def check(**expect):
        since = _mark()
        labels = kb._all_row_assign().copy()
        spans = _assign_all_spans(since)
        if expect:
            assert len(spans) == 1
            assert {k: spans[0][k] for k in expect} == expect
        else:
            assert spans == []
        full, _ = assign_signatures(store.device_matrix, kb.archetypes, impl)
        full = full[:len(store)]
        assert labels.shape == full.shape
        if impl == "reference":
            x = store.signatures.astype(np.float64)
            c = kb.archetypes.astype(np.float64)
            d2 = np.sort(((x[:, None] - c[None]) ** 2).sum(-1), -1)
            tied = d2[:, 1] - d2[:, 0] < 1e-6
            np.testing.assert_array_equal(labels[~tied], full[~tied])
        else:
            np.testing.assert_array_equal(labels, full)

    check(full_pass=1, rows_assigned=150, padded_rows=256 - 150,
          rows_cached=0)
    sP, cP = _blob_program(30, blob_centers)
    store.add("P", sP[:20], cpis=cP[:20])
    check(full_pass=0, rows_assigned=20, padded_rows=32 - 20,
          rows_cached=150)
    items = [(n, *_blob_program(31 + j, blob_centers)[:1])
             for j, n in enumerate(["Q", "R"])]
    store.add_many([(n, s[:30]) for n, s in items])
    check(full_pass=0, rows_assigned=60, padded_rows=64 - 60,
          rows_cached=170)
    store.evict(store.rows_for("A")[::2])
    store.evict_program("Q")
    check()                                  # nothing new to label
    kb.apply_remap(store.compact())
    assert len(store) == 230 - 38 - 30
    check()                                  # carried through the remap
    store.add("P", sP[20:], cpis=cP[20:])
    check(full_pass=0, rows_assigned=55, padded_rows=64 - 55,
          rows_cached=162)
    store.evict_program("R")
    store.compact()                          # remap never applied
    check(full_pass=1, rows_assigned=187, padded_rows=256 - 187,
          rows_cached=0)
    # the remap of a later compaction, as long as the cache, cannot carry
    # labels across one the base missed (P's rows lie after every
    # representative's row)
    store.evict(store.rows_for("P")[:5])
    store.compact()
    store.add("S", _blob_program(33, blob_centers)[0][:10])
    store.evict(store.rows_for("P")[:5])
    assert kb.apply_remap(store.compact()) == 0
    check(full_pass=1, rows_assigned=187, padded_rows=256 - 187,
          rows_cached=0)


def test_build_load_and_store_swap_reset_row_assign_cache(tmp_path,
                                                          blob_centers):
    """New archetypes (`build`, `KnowledgeBase.load`) or another store
    under the base start from a whole-store pass."""
    store = _filled_store(blob_centers, ["A", "B"])
    kb = KnowledgeBase(store).build(k=3, seed=0)
    sP, cP = _blob_program(50, blob_centers)
    store.add("P", sP, cpis=cP)
    kb.attach("P")
    assert kb._row_assign_cache is not None
    kb.build(k=3, seed=1)
    assert kb._row_assign_cache is None
    kb.attach("P")
    kb.save(str(tmp_path / "kb"))
    kb2 = KnowledgeBase.load(str(tmp_path / "kb"), store)
    assert kb2._row_assign_cache is None
    since = _mark()
    np.testing.assert_array_equal(kb2._all_row_assign(),
                                  kb._all_row_assign())
    assert [c["full_pass"] for c in _assign_all_spans(since)] == [1]

    other = _filled_store(blob_centers, ["A", "B"])
    other.add("P", sP, cpis=cP)
    kb.store = other                          # same length, new store
    since = _mark()
    np.testing.assert_array_equal(kb._all_row_assign(),
                                  kb2._all_row_assign())
    assert [c["full_pass"] for c in _assign_all_spans(since)] == [1]
