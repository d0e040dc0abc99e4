"""`correct` separates sound runs from broken ones.

Each cell runs here on the CPU at tiny widths, past the harness's look
for a chip, against the cell's committed limits: a sound run is correct,
and so is not a run whose timed path is broken underneath, once for each
fault the cell can have: an answer altered where it is produced (a
fingerprint, a stored signature) and half of each Stage-2 set left out.
The control, the reference one matmul precision below the
configuration's ("high" below "highest"), is read on the chip at the
cell's own size (`chipbench/control.py`): on the CPU every float32
product is exact at either setting, so here it only runs in the
program's place.
"""
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {w["name"]: w for w in BENCH["workloads"]}
TINY = {
    "bbe": {"dim_embeds": [48, 8, 8, 8, 8, 8], "num_layers": 2,
            "num_heads": 2, "bbe_dim": 32, "max_len": 64},
    "sig": {"bbe_dim": 32, "d_model": 32, "sig_dim": 16, "num_heads": 2,
            "num_sabs": 2, "num_seeds": 1, "max_set": 48},
    "service": {"impl": "xla", "assign_impl": "reference",
                "build_impl": "device", "k": 14, "encode_batch": 32,
                "signature_batch": 64},
    "intervals_per_program": 120,
}
TINY_TRAFFIC = {"chunk": 30, "chunks_per_program": 3, "check_requests": 4}
PEAKS = {"flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout holding the committed benchmark with tiny widths."""
    root = tmp_path_factory.mktemp("tiny")
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", ".jax_cache",
                                                  "__pycache__"))
    for c in BENCH["configs"]:
        path = root / c["file"]
        path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                        **TINY)))
    for w in BENCH["workloads"]:
        path = root / "chipbench" / "traffic" / f"{w['traffic']}.json"
        t = json.loads(path.read_text())
        path.write_text(json.dumps(dict(t, **{
            k: v for k, v in TINY_TRAFFIC.items() if k in t})))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def run(root, cell, control=False):
    return harness.run_cell(cell, 2**33 + 21, 1.0, False, CPU,
                            control=control, root=root,
                            peak_table=lambda kind: PEAKS)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(tiny_root, cell):
    r = run(tiny_root, cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_runs_in_the_programs_place(tiny_root, cell):
    r = run(tiny_root, cell, control=True)
    assert set(r["diag"]["program"]) == set(r["checks"]) - {
        "window_compiles", "failed_requests"}
    assert r["correct"], r["checks"]     # exact products on the CPU


def _alter_every_fourth(rows):
    out = np.array(rows, copy=True)
    out[::4] = np.roll(out[::4], 1, axis=-1)
    return out


def fault(monkeypatch, kind):
    from repro.api import knowledge
    from repro.core.pipeline import SemanticBBVPipeline as P
    if kind == "half_set":
        orig = P._batch_set_ids

        def half(self, ivs, index):
            """Each interval's set loses the second half of its blocks."""
            ids, freqs, mask = orig(self, ivs, index)
            keep = (mask.sum(1, keepdims=True) + 1) // 2
            return ids, freqs, mask & (np.arange(mask.shape[1]) < keep)
        monkeypatch.setattr(P, "_batch_set_ids", half)
    elif kind == "signature":
        orig = P.interval_signatures
        monkeypatch.setattr(P, "interval_signatures",
                            lambda self, *a, **k: _alter_every_fourth(
                                orig(self, *a, **k)))
    else:
        orig = knowledge.KnowledgeBase._fingerprint

        def shifted(self, a, w):
            f, wp = orig(self, a, w)
            return np.roll(f, 1), wp
        monkeypatch.setattr(knowledge.KnowledgeBase, "_fingerprint", shifted)


@pytest.mark.parametrize("cell,kind", [
    (c, k) for c in sorted(CELLS)
    for k in ("fingerprint", "signature", "half_set")])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, cell,
                                          kind):
    fault(monkeypatch, kind)
    r = run(tiny_root, cell)
    assert not r["correct"], r["checks"]


MIXES = sorted(p.stem for p in (ROOT / "chipbench" / "traffic").glob("*.json"))


@pytest.mark.parametrize("mix", MIXES)
def test_every_traffic_mix_runs_and_agrees_with_the_reference(tiny_root,
                                                              mix):
    """Every request loop drives the service through a window and its
    answers match the reference at CPU float32 (exact products) to
    1e-4."""
    traffic = json.loads((tiny_root / "chipbench" / "traffic"
                          / f"{mix}.json").read_text())
    config = json.loads((ROOT / "chipbench" / "configs"
                         / "spec17int-100k.json").read_text())
    config.update(TINY)
    d = harness.load_driver(traffic["driver"])(config, traffic, 2**40 + 9)
    d.setup()
    reqs, _ = harness.window(d, 0.5)
    d.release()
    assert reqs and all(r.ok for r in reqs)
    numbers = d.check()
    assert numbers and max(numbers.values()) < 1e-4, numbers
