"""Sharding logic (pure) + one real 512-device dry-run cell in a subprocess
(the dry-run needs its own process: XLA device count locks at first init)."""
import os
import subprocess
import sys

import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed.sharding import (
    LOGICAL_RULES, logical_to_pspec, prune_pspec,
)

MESH = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
SINGLE = AbstractMesh((16, 16), ("data", "model"))


def test_logical_rules_basic():
    assert logical_to_pspec(("batch", "seq"), MESH) == P(("pod", "data"), None)
    assert logical_to_pspec(("embed", "ff"), MESH) == P("data", "model")
    assert logical_to_pspec(("vocab", "embed"), MESH) == P("model", "data")
    # unknown mesh axes are dropped (same rules single/multi pod)
    assert logical_to_pspec(("batch",), SINGLE) == P("data")


def test_no_mesh_axis_used_twice():
    spec = logical_to_pspec(("heads", "ff"), MESH)  # both map to model
    axes = [a for part in spec if part for a in
            ((part,) if isinstance(part, str) else part)]
    assert len(axes) == len(set(axes))


def test_prune_small_dims():
    # 8 experts cannot shard over 16-way model axis
    assert prune_pspec(P("model"), (8,), SINGLE) == P(None)
    # batch=1 cannot shard over the data axis
    assert prune_pspec(P(("pod", "data"), None), (1, 128), MESH) == P(None, None)
    # odd vocab drops the model axis
    assert prune_pspec(P("model", "data"), (49155, 2048), SINGLE) == \
        P(None, "data")
    # well-divisible dims keep their axes
    assert prune_pspec(P("data", "model"), (4096, 32768), SINGLE) == \
        P("data", "model")


@settings(max_examples=50, deadline=None)
@given(dim=st.integers(1, 10_000),
       axis=st.sampled_from(["data", "model", ("data", "model")]))
def test_prune_always_valid(dim, axis):
    """After pruning, every kept mesh-axis product divides its dim."""
    spec = prune_pspec(P(axis), (dim,), SINGLE)
    kept = spec[0]
    if kept is None:
        return
    kept = (kept,) if isinstance(kept, str) else kept
    n = 1
    for a in kept:
        n *= dict(SINGLE.shape)[a]
    assert dim % n == 0


@pytest.mark.slow
def test_dryrun_cell_subprocess():
    """Full 512-device lower+compile of one (arch, shape) cell."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "whisper-tiny", "--shape", "train_4k"],
        capture_output=True, text=True, env=env, timeout=560)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "1 OK, 0 SKIP, 0 FAIL" in proc.stdout
