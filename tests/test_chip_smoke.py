"""CPU rehearsal of chip_smoke.py: its phase functions at tiny widths with
the Pallas kernels interpreted, steered from here (the script itself has
no size option), plus its refusal to run without a TPU."""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.api import ServiceConfig
from repro.core.bbe import BBEConfig
from repro.core.signature import SignatureConfig
from repro.data.asmgen import spec_programs
from repro.utils.compile_cache import (
    CHECKOUT_ROOT, DEFAULT_DIR, ENV_VAR, compile_cache_dir,
    enable_compile_cache,
)

SCRIPT = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
TINY_BBE = BBEConfig(dim_embeds=(48, 8, 8, 8, 8, 8), num_layers=2,
                     num_heads=2, bbe_dim=32, max_len=64)
TINY_SIG = SignatureConfig(bbe_dim=32, d_model=32, sig_dim=16, max_set=48,
                           num_heads=2)


def _load_script():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod    # its dataclasses resolve through it
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load_script()


@pytest.fixture(scope="module")
def world(smoke):
    return smoke.make_world(spec_programs("int")[:3], n_intervals=24)


@pytest.fixture(scope="module")
def served(smoke, world):
    cfg = ServiceConfig(bbe=TINY_BBE, sig=TINY_SIG, impl="pallas_interpret",
                        assign_impl="pallas_interpret",
                        build_impl="device_kernel", k=14)
    return smoke.run_service(world, cfg)


def test_main_refuses_cpu_before_any_work(smoke, capsys):
    cache_before = jax.config.jax_compilation_cache_dir
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    lines = capsys.readouterr().out.strip().splitlines()
    assert [json.loads(line)["smoke"] for line in lines] == ["device"]
    assert json.loads(lines[0])["platform"] == "cpu"
    # refused before the compile cache (or anything else) was set up
    assert jax.config.jax_compilation_cache_dir == cache_before


def test_make_world(world):
    assert world.names == [p.name for p in spec_programs("int")[:3]]
    assert all(len(world.intervals[p]) == 24 for p in world.names)
    assert all(world.cpis[p].shape == (24,) and (world.cpis[p] > 0).all()
               for p in world.names)
    bids = {iv_bid for p in world.names for iv in world.intervals[p]
            for iv_bid in iv.counts}
    assert bids <= {b.bid for b in world.blocks}


def test_service_phase(served, world):
    svc, secs, ests = served
    assert len(svc.store) == 3 * 24
    assert svc.kb.k == 14
    assert set(ests) == set(world.names)
    assert set(secs) == {"ingest_blocks", "ingest_intervals", "build",
                         "attach_many", "estimate"}
    held_out = world.names[-1]
    assert held_out in svc.kb.fingerprints
    np.testing.assert_allclose(ests[held_out].fingerprint.sum(), 1.0)


def test_parity_phase(smoke, served, world):
    svc, _, _ = served
    out = smoke.run_parity(svc, world)
    assert out["rows"] == 3 * 24
    assert out["min_cosine"] >= smoke.MIN_SIG_COSINE
    assert out["assign_agreement"] >= smoke.MIN_ASSIGN_AGREEMENT


def test_stage2_phase(smoke, served, world):
    svc, _, _ = served
    out = smoke.run_stage2(svc, world, "pallas_interpret", steps=2, batch=4)
    assert len(out["losses"]) == 2
    assert out["first_loss_rel_diff"] <= smoke.LOSS_RTOL


def test_sharded_build_phase_on_virtual_devices():
    """The --chips 4 phase on four virtual CPU devices, in a child process
    (the host device count is fixed when JAX starts)."""
    code = f"""
import json, sys, numpy as np, jax
from jax.sharding import Mesh
sys.path.insert(0, {os.path.dirname(SCRIPT)!r})
import chip_smoke as smoke
assert jax.device_count() == 4
store = smoke.clustered_store(300, 16, 3, 4)
out = smoke.run_sharded_build(store, Mesh(np.array(jax.devices()), ("data",)),
                              k=4)
print(json.dumps(out))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4 and out["shard_rows"] == [128] * 4
    assert out["mismatched_assignments"] == 0


@pytest.mark.parametrize("env,expected", [
    ({}, DEFAULT_DIR),
    ({ENV_VAR: ""}, DEFAULT_DIR),
    ({ENV_VAR: "/elsewhere/cache"}, "/elsewhere/cache"),
], ids=["unset", "empty", "set"])
def test_compile_cache_dir(env, expected):
    assert compile_cache_dir(env) == expected
    assert DEFAULT_DIR == os.path.join(CHECKOUT_ROOT, ".jax_cache")
    assert os.path.isfile(os.path.join(CHECKOUT_ROOT, "chip_smoke.py"))


@pytest.mark.parametrize("env_dir,sets", [
    (None, DEFAULT_DIR),
    ("/elsewhere/cache", None),     # JAX reads the variable itself
], ids=["unset", "set"])
def test_enable_compile_cache(monkeypatch, env_dir, sets):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    if env_dir is None:
        monkeypatch.delenv(ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(ENV_VAR, env_dir)
    assert enable_compile_cache() == (env_dir or DEFAULT_DIR)
    assert updates == ([] if sets is None
                       else [("jax_compilation_cache_dir", sets)])
