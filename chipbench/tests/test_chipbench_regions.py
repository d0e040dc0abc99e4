"""The multi-threaded region traffic: its generator keeps the semantics
the configuration states, and `correct` separates a sound region cell
from one whose regions are signed wrongly."""
import dataclasses
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness, regions

ROOT = Path(__file__).resolve().parents[2]
CELL = "looppoint-omp8-100k.attach-regions"
TINY = {
    "bbe": {"dim_embeds": [48, 8, 8, 8, 8, 8], "num_layers": 2,
            "num_heads": 2, "bbe_dim": 32, "max_len": 64},
    "sig": {"bbe_dim": 32, "d_model": 32, "sig_dim": 16, "num_heads": 2,
            "num_sabs": 2, "num_seeds": 1, "max_set": 128},
    "service": {"impl": "xla", "assign_impl": "reference",
                "build_impl": "device", "k": 14, "encode_batch": 32,
                "signature_batch": 64},
    "intervals_per_program": 40,
}
TINY_TRAFFIC = {"chunk": 20, "chunks_per_program": 2, "check_requests": 3,
                "threads": 4}
PEAKS = {"flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PROGRAM = regions.suite_programs("npb")[3]


def _trace(seed, n=30, threads=8):
    return regions.trace(PROGRAM, n, seed, threads, 0.2, (0.05, 0.15))


def test_same_seed_same_regions_other_seed_other_regions():
    a, b, c = _trace(2**40 + 1), _trace(2**40 + 1), _trace(2**40 + 2)
    assert np.array_equal(a.counts, b.counts)
    assert np.array_equal(a.cpi, b.cpi)
    assert not np.array_equal(a.counts, c.counts)


def test_spin_counts_are_the_runtimes_and_fill_each_wait():
    """Only the runtime's barrier blocks are marked, every count is >= 0,
    and every thread but the slowest spins, the slowest not at all."""
    tr = _trace(7)
    spin_blocks, _ = regions.runtime_image()
    assert tr.bids[tr.runtime].tolist() == [b.bid for b in spin_blocks]
    assert not set(tr.bids[~tr.runtime].tolist()) & {
        b.bid for b in spin_blocks}
    assert (tr.counts >= 0).all()
    spins = tr.counts[..., tr.runtime].sum(-1)               # (n, T)
    assert ((spins == 0).sum(1) == 1).all()
    assert (spins.max(1) > 0).all()


def test_region_weight_is_its_main_image_instructions():
    tr = _trace(9)
    lens = {b.bid: b.num_instrs for b in PROGRAM.unique_blocks}
    main = np.flatnonzero(~tr.runtime)
    per_block = np.array([lens[int(b)] for b in tr.bids[main]])
    want = (tr.counts[..., main] * per_block).sum((1, 2))
    np.testing.assert_array_equal(tr.num_instrs, want)
    assert [r.num_instrs for r in tr.regions("p")] == want.tolist()
    # 8 threads at 10 M instructions each, +-20 % imbalance, serial part
    assert (tr.num_instrs > 6e7).all() and (tr.num_instrs < 1.1e8).all()


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout holding the committed benchmark with tiny widths."""
    root = tmp_path_factory.mktemp("tiny_regions")
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", ".jax_cache",
                                                  "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    path = root / conf["file"]
    path.write_text(json.dumps(dict(json.loads(path.read_text()), **TINY)))
    path = root / "chipbench" / "traffic" / f"{cell['traffic']}.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    **TINY_TRAFFIC)))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def _merge_threads(orig):
    """Each block once, its count summed over threads: the region read
    as one thread."""
    def entries(self):
        bids, counts, excluded = orig(self)
        uniq, inv = np.unique(bids, return_inverse=True)
        return (uniq, np.bincount(inv, weights=counts).astype(np.int64),
                excluded)
    return entries


def _drop_last_thread(orig):
    """The last thread's entries left out."""
    def entries(self):
        return orig(dataclasses.replace(self, counts=self.counts[:-1]))
    return entries


@pytest.mark.parametrize("fault", [None, _merge_threads, _drop_last_thread],
                         ids=["sound", "threads_merged", "thread_dropped"])
def test_check_reads_correct_only_for_sound_regions(tiny_root, monkeypatch,
                                                    fault):
    from repro.data.trace import Region
    if fault is not None:
        monkeypatch.setattr(Region, "set_entries", fault(Region.set_entries))
    r = harness.run_cell(CELL, 2**33 + 5, 0.5, False, CPU, root=tiny_root,
                         peak_table=lambda kind: PEAKS)
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["correct"] == (fault is None), r["checks"]
