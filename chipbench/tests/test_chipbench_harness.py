"""The benchmark's cells resolve to their files, a cell added as files
plus an entry is found, `BENCHMARK.json` keeps to its format, and the
command refuses a machine without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import driver, harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = harness.resolve(cell, ROOT)
    assert issubclass(harness.load_driver(c.traffic["driver"]),
                      driver.Driver)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    moved = {m["moves"] for m in c.per_layer}
    assert moved <= names, "a per-layer metric's cell reports what it moves"
    assert {"window_compiles", "failed_requests"} <= set(c.limits)


def test_benchmark_json_format():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert 1 <= BENCH["run_seconds"] <= 51
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [x["name"] for x in BENCH["configs"] + BENCH["workloads"]
             + metrics]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in BENCH["workloads"] + BENCH["configs"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert 1 <= len(m["layer"]) <= 200


def test_cell_added_as_files_and_entry_is_found(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", ".jax_cache",
                                                  "__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    base = BENCH["workloads"][0]
    mix = f"{base['traffic']}-long"
    (tmp_path / "chipbench" / "traffic" / f"{mix}.json").write_text(
        json.dumps(dict(harness._load_json(
            ROOT / "chipbench" / "traffic" / f"{base['traffic']}.json"),
            driver="probe_loop", trace_seconds=20)))
    # a new kind of request loop is a module of its own
    (tmp_path / "chipbench" / "traffic" / "probe_loop.py").write_text(
        "from chipbench.driver import Driver\n\n\n"
        "class Probe(Driver):\n    pass\n\n\nDRIVER = Probe\n")
    new = dict(base, name=f"{base['config']}.{mix}", traffic=mix)
    shutil.copy(ROOT / "chipbench" / "limits" / f"{base['name']}.json",
                tmp_path / "chipbench" / "limits" / f"{new['name']}.json")
    (tmp_path / "chipbench" / "metrics" / "long_probe.py").write_text(
        "def read(run):\n    return 1.0\n")
    bench["workloads"].append(new)
    bench["per_layer"].append({
        "name": "long_probe", "unit": "%", "better": "higher",
        "source": "host_clock", "layer": "device",
        "moves": BENCH["per_layer"][0]["moves"], "workloads": [new["name"]]})
    for m in bench["end_to_end"]:
        if base["name"] in m.get("workloads", []):
            m["workloads"].append(new["name"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.resolve(new["name"], tmp_path)
    assert cell.traffic["trace_seconds"] == 20
    assert harness.load_driver("probe_loop", tmp_path).__name__ == "Probe"
    assert "long_probe" in {m["name"] for m in cell.per_layer}
    with pytest.raises(KeyError):
        harness.resolve("no-such.cell", tmp_path)


def test_command_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
