"""HLO analyzer: trip-count-corrected FLOPs/bytes/collectives on a module
with known costs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo_parse import analyze_hlo
from repro.analysis.roofline import RooflineReport, V5E, roofline_terms


@pytest.fixture(scope="module")
def scan_module_text():
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None

        y, _ = jax.lax.scan(body, x, w)
        return y.sum()

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((7, 64, 64), jnp.float32)
    return jax.jit(f).lower(x, w).compile().as_text()


def test_trip_count_multiplication(scan_module_text):
    st = analyze_hlo(scan_module_text)
    expected = 2 * 64 * 64 * 64 * 7  # 7 iterations of a 64^3 matmul
    assert st.dot_flops == pytest.approx(expected, rel=0.01)
    assert 7 in st.trip_counts.values()


def test_bytes_accessed_reasonable(scan_module_text):
    st = analyze_hlo(scan_module_text)
    w_bytes = 7 * 64 * 64 * 4
    # must at least read the weights once and not explode by >100x
    assert w_bytes < st.bytes_accessed < w_bytes * 100


def test_collectives_counted():
    def f(x):
        return jax.lax.psum(x, "i")

    from jax.sharding import Mesh, PartitionSpec as P
    devs = np.array(jax.devices()[:1])
    mesh = Mesh(devs.reshape(1), ("i",))
    g = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P("i"), out_specs=P()))
    text = g.lower(jax.ShapeDtypeStruct((8,), jnp.float32)).compile().as_text()
    st = analyze_hlo(text)
    assert st.collective_counts.get("all-reduce", 0) >= 1


def test_roofline_terms_math():
    from repro.analysis.hlo_parse import HloStats
    st = HloStats(dot_flops=197e12, bytes_accessed=819e9,
                  collective_bytes={"all-reduce": 50e9})
    rep = roofline_terms(st, arch="x", shape="y", mesh="16x16", chips=256,
                         model_flops=197e12 * 256)
    t = rep.terms(V5E)
    # each term should be exactly 1 second given the v5e constants
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(1.0)
    assert t["collective_s"] == pytest.approx(1.0)
    assert t["useful_flops_ratio"] == pytest.approx(1.0)
