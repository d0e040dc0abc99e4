"""Ahead-of-time compiles of the main path's kernels for a TPU v5e that is
described, not attached, at the paper's widths on one device.

Interpret-mode parity cannot see what the chip's compiler refuses (block
shapes off the (8, 128) tiling, relayouts Mosaic does not implement);
these compiles can. Each test asserts the kernel is really in the program
(`tpu_custom_call`). The topology is described inside a fixture, so
importing this file loads no TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.pipeline import _signature_from_rows, set_width
from repro.core.signature import SignatureConfig, signature_init
from repro.kernels.kmeans_assign.ops import kmeans_assign, kmeans_update
from repro.kernels.set_attention.ops import masked_set_attention
from repro.kernels.wkv.ops import wkv_chunked

STORE_ROWS, SIG_DIM, K = 131072, 128, 14       # 10^5 rows at capacity
SET_B, SET_H, SET_DH = 512, 4, 64             # SignatureConfig() widths
# max_set: SignatureConfig()'s 64, and 512 (8 threads x 64) of the
# multi-threaded regions' configuration; then every other set width a
# served batch can run at (`set_width`'s ladder up to 512)
SET_NS = (64, 512) + tuple(sorted(
    {set_width(n, 512) for n in range(513)} - {64, 512}))
WKV_B, WKV_S, WKV_H, WKV_DH = 256, 128, 6, 64  # BBEConfig() widths


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("block,set_n", [
    (b, n) for n in SET_NS for b in ("sab", "pma")],
    ids=[b if n == SET_NS[0] else f"{b}-{n}" for n in SET_NS
         for b in ("sab", "pma")])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_set_attention_compiles(one_chip, block, set_n, direction):
    n_queries = set_n if block == "sab" else 1
    q = _spec(one_chip, (SET_B, SET_H, n_queries, SET_DH))
    kv = _spec(one_chip, (SET_B, SET_H, set_n, SET_DH))
    bias = _spec(one_chip, (SET_B, set_n))
    mask = _spec(one_chip, (SET_B, set_n), jnp.bool_)

    def fwd(q, k, v, b, m):
        return masked_set_attention(q, k, v, b, m)

    fn = fwd if direction == "fwd" else jax.grad(
        lambda *a: fwd(*a).sum(), argnums=(0, 1, 2, 3))
    assert "tpu_custom_call" in _compiled_text(fn, q, kv, kv, bias, mask)


def test_kmeans_assign_compiles(one_chip):
    text = _compiled_text(
        lambda x, c: kmeans_assign(x, c, interpret=False),
        _spec(one_chip, (STORE_ROWS, SIG_DIM)), _spec(one_chip, (K, SIG_DIM)))
    assert "tpu_custom_call" in text


def test_kmeans_assign_tail_compiles(one_chip):
    """The shape an attach request assigns: its 1,000 new rows, padded
    to the next power of two."""
    text = _compiled_text(
        lambda x, c: kmeans_assign(x, c, interpret=False),
        _spec(one_chip, (1024, SIG_DIM)), _spec(one_chip, (K, SIG_DIM)))
    assert "tpu_custom_call" in text


def test_kmeans_update_compiles(one_chip):
    text = _compiled_text(
        lambda x, c, v: kmeans_update(x, c, v, interpret=False),
        _spec(one_chip, (STORE_ROWS, SIG_DIM)), _spec(one_chip, (K, SIG_DIM)),
        _spec(one_chip, (STORE_ROWS,)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("set_n", SET_NS)
def test_signature_step_compiles(one_chip, set_n):
    """The jitted Stage-2 serving step with impl="pallas" at batch 512."""
    cfg = SignatureConfig(max_set=set_n)
    params = jax.eval_shape(lambda: signature_init(jax.random.PRNGKey(0),
                                                   cfg)[0])
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype), params)
    text = _compiled_text(
        lambda p, m, r, f, k: _signature_from_rows(p, cfg, m, r, f, k,
                                                   impl="pallas"),
        params, _spec(one_chip, (285, cfg.bbe_dim)),
        _spec(one_chip, (512, cfg.max_set), jnp.int32),
        _spec(one_chip, (512, cfg.max_set)),
        _spec(one_chip, (512, cfg.max_set), jnp.bool_))
    assert "tpu_custom_call" in text


def test_wkv_compiles(one_chip):
    """Tiling only: the served Stage-1 path runs the scan recurrence."""
    x = _spec(one_chip, (WKV_B, WKV_S, WKV_H, WKV_DH))
    beta = _spec(one_chip, (WKV_B, WKV_S, WKV_H))
    text = _compiled_text(lambda r, k, v, w, b: wkv_chunked(r, k, v, w, b),
                          x, x, x, x, beta)
    assert "tpu_custom_call" in text
