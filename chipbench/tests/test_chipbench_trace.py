"""Trace reduction, operation and byte counts, and the peak table."""
from pathlib import Path

import pytest

from chipbench import counts, peaks
from chipbench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
SPANS = ("request", "ingest_intervals", "estimate", "vacuum")


def synthetic():
    """Window 0-100 ns; the device runs 10-30 and 25-40 (one busy stretch
    of 30) and 70-80; the host is in `ingest_intervals` 0-50 and in
    `estimate` 50-90 inside `request` 0-100."""
    ops = {"/device:TPU:0": [("fusion", 10, 30),
                             ("set_attention_pallas", 25, 40),
                             ("set_attention_pallas", 70, 80)]}
    spans = [("request", 0, 100), ("ingest_intervals", 0, 50),
             ("estimate", 50, 90)]
    return tr.Reduced(ops, spans, 0, 100)


def test_idle_share_and_gap_attribution():
    r = synthetic()
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(40e-9)
    assert r.idle_share() == pytest.approx(60.0)
    gaps = dict(r.idle_by_span())
    # 0-10 and 40-50 in ingest_intervals, 50-70 and 80-90 in estimate,
    # 90-100 only in request
    assert gaps == pytest.approx({"ingest_intervals": 20e-9,
                                  "estimate": 30e-9, "request": 10e-9})


def test_kernel_time_by_name():
    r = synthetic()
    assert r.kernel_seconds("set_attention_pallas") == pytest.approx(25e-9)
    assert r.kernel_seconds("no_such_kernel") == 0.0
    top = r.top_ops()
    assert top[0][0] == "set_attention_pallas"


def test_op_names():
    full = ("%set_attention_pallas.4 = f32[64,2,48,16]{3,2,1,0} custom-call("
            "f32[64,2,48,16] %bitcast.171), custom_call_target=\"x\"")
    assert tr.op_name(full) == "set_attention_pallas"
    assert tr.op_name("%while.27 = (s32[]) while((s32[]) %t)") == "while"
    assert tr.op_name("fusion") == "fusion"


def test_small_chip_trace():
    """A traced window of a tiny attach stream on one TPU v5e."""
    path = DATA / "small.xplane.pb"
    r = tr.reduce(str(path), SPANS)
    assert r.ops and all(k.startswith("/device:TPU:") for k in r.ops)
    assert 0 < r.busy_s < r.window_s
    assert 0 < r.idle_share() < 100
    # the reduction's kernel time is the plain sum over matching events
    from jax.profiler import ProfileData
    want = 0.0
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    want += sum(e.duration_ns for e in line.events
                                if "kmeans_assign_pallas" in e.name
                                and e.start_ns >= r.start_ns
                                and e.start_ns + e.duration_ns <= r.end_ns)
    assert want > 0
    assert r.kernel_seconds("kmeans_assign_pallas") == pytest.approx(
        want / 1e9)
    gaps = r.idle_by_span()
    assert {g[0] for g in gaps} <= set(SPANS) | {"client"}
    idle = (r.window_s - r.busy_s)
    assert sum(g[1] for g in gaps) == pytest.approx(idle, rel=1e-6)


SIG = {"bbe_dim": 256, "d_model": 256, "sig_dim": 128, "num_heads": 4,
       "num_sabs": 2, "num_seeds": 1}


def test_stage2_and_set_attention_counts_by_hand():
    n, d = 34, 256
    sab = 2 * (2 * n * d * d + 2 * n * d * d + 2 * n * n * d + 4 * n * d * d)
    pma = 2 * (2 * d * d + 2 * n * d * d + 2 * n * d + 4 * d * d)
    want = (2 * n * 257 * d + 2 * sab + pma + 2 * d * 128 + 2 * 128 * d
            + 2 * d)
    assert counts.stage2_flops(n, SIG) == want
    sa = counts.set_attention(n, SIG)
    assert sa["flops"] == 2 * 4 * n * n * d + 4 * n * d
    assert sa["bytes"] == 4 * (2 * (4 * n * d + n) + 2 * d + 2 * n * d + n)


def test_assign_count_by_hand():
    a = counts.assign(1000, 14, 128)
    assert a["flops"] == 2 * 1000 * 14 * 128
    assert a["bytes"] == 4 * (1000 * 128 + 14 * 128 + 2000)


def test_peak_table():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks("TPU v99")
