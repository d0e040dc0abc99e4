"""Per-kernel shape/dtype sweeps: Pallas vs jnp oracle (on the CPU the
kernels lower through the Pallas interpreter)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.kmeans_assign.ops import kmeans_assign
from repro.kernels.kmeans_assign.ref import kmeans_assign_reference
from repro.kernels.set_attention.ops import masked_set_attention
from repro.kernels.set_attention.ref import set_attention_reference
from repro.kernels.wkv.ops import wkv_chunked
from repro.kernels.wkv.ref import wkv_reference


def _rand(rng, shape, dtype):
    x = rng.randn(*shape).astype(np.float32)
    return jnp.asarray(x, dtype)


# ---------------------------------------------------------------------------
# wkv
# ---------------------------------------------------------------------------

WKV_CASES = [
    # (B, S, H, dh, chunk, dtype)
    (1, 32, 1, 8, 8, jnp.float32),
    (2, 64, 3, 16, 16, jnp.float32),
    (2, 128, 2, 32, 32, jnp.float32),
    (1, 64, 4, 64, 64, jnp.float32),
    (2, 64, 2, 16, 16, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,H,dh,chunk,dtype", WKV_CASES)
def test_wkv_matches_reference(B, S, H, dh, chunk, dtype):
    rng = np.random.RandomState(B * 1000 + S)
    r = _rand(rng, (B, S, H, dh), dtype)
    k = _rand(rng, (B, S, H, dh), dtype)
    k = k / jnp.maximum(jnp.linalg.norm(k.astype(jnp.float32), axis=-1,
                                        keepdims=True), 1e-6).astype(dtype)
    v = _rand(rng, (B, S, H, dh), dtype)
    w = jnp.asarray(rng.uniform(0.7, 1.0, (B, S, H, dh)), dtype)
    beta = jnp.asarray(rng.uniform(0, 1, (B, S, H)), dtype)
    y_ref, s_ref = wkv_reference(r, k, v, w, beta)
    y_k, s_k = wkv_chunked(r, k, v, w, beta, chunk=chunk)
    atol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               atol=atol, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_ref),
                               atol=atol, rtol=1e-3)


def test_wkv_state_chaining():
    """Processing [first half; second half] with carried state must equal
    one pass — the property decode depends on."""
    rng = np.random.RandomState(0)
    B, S, H, dh = 1, 64, 2, 16
    mk = lambda: _rand(rng, (B, S, H, dh), jnp.float32)  # noqa: E731
    r, k, v = mk(), mk(), mk()
    w = jnp.asarray(rng.uniform(0.8, 1.0, (B, S, H, dh)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (B, S, H)), jnp.float32)
    y_full, s_full = wkv_reference(r, k, v, w, beta)
    h = S // 2
    y1, s1 = wkv_chunked(r[:, :h], k[:, :h], v[:, :h], w[:, :h],
                         beta[:, :h], chunk=16)
    y2, s2 = wkv_chunked(r[:, h:], k[:, h:], v[:, h:], w[:, h:],
                         beta[:, h:], state=s1, chunk=16)
    np.testing.assert_allclose(np.concatenate([y1, y2], 1),
                               np.asarray(y_full), atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), atol=1e-4,
                               rtol=1e-3)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, S, H, K, D, causal, window, bq, bk, dtype)
    (1, 64, 2, 2, 16, True, 0, 16, 16, jnp.float32),
    (2, 64, 4, 2, 32, True, 0, 32, 32, jnp.float32),
    (1, 128, 6, 6, 16, False, 0, 32, 64, jnp.float32),
    (2, 64, 4, 1, 32, True, 32, 32, 32, jnp.float32),
    (1, 128, 8, 2, 64, True, 0, 64, 32, jnp.float32),
    (2, 64, 4, 4, 32, True, 0, 32, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,H,K,D,causal,window,bq,bk,dtype", FLASH_CASES)
def test_flash_matches_reference(B, S, H, K, D, causal, window, bq, bk,
                                 dtype):
    rng = np.random.RandomState(S + H)
    q = _rand(rng, (B, S, H, D), dtype)
    k = _rand(rng, (B, S, K, D), dtype)
    v = _rand(rng, (B, S, K, D), dtype)
    ref = attention_reference(q, k, v, causal=causal, window=window)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk)
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol,
                               rtol=1e-2)


# ---------------------------------------------------------------------------
# set attention (Stage-2 SAB/PMA)
# ---------------------------------------------------------------------------

SET_ATTN_CASES = [
    # (B, H, N, M, dh, weighted, masked, dtype)
    (1, 2, 16, 16, 16, False, False, jnp.float32),
    (2, 4, 64, 64, 64, True, True, jnp.float32),    # SAB at paper scale
    (2, 2, 1, 64, 32, True, True, jnp.float32),     # PMA: one seed query
    (2, 2, 5, 13, 16, True, True, jnp.float32),     # non-divisible sizes
    (1, 3, 17, 33, 8, False, True, jnp.float32),
    (2, 2, 7, 130, 16, True, False, jnp.float32),   # M > one lane tile
    (2, 2, 32, 32, 32, True, True, jnp.bfloat16),
]


def _set_attn_inputs(rng, B, H, N, M, dh, weighted, masked, dtype):
    q = _rand(rng, (B, H, N, dh), dtype)
    k = _rand(rng, (B, H, M, dh), dtype)
    v = _rand(rng, (B, H, M, dh), dtype)
    bias = (jnp.asarray(rng.uniform(0, 1, (B, M)), jnp.float32)
            if weighted else None)
    mask = None
    if masked:
        m = rng.rand(B, M) > 0.3
        m[:, 0] = True  # at least one valid key per row
        mask = jnp.asarray(m)
    return q, k, v, bias, mask


@pytest.mark.parametrize("B,H,N,M,dh,weighted,masked,dtype", SET_ATTN_CASES)
def test_set_attention_matches_reference(B, H, N, M, dh, weighted, masked,
                                         dtype):
    rng = np.random.RandomState(31 * N + M)
    q, k, v, bias, mask = _set_attn_inputs(rng, B, H, N, M, dh, weighted,
                                           masked, dtype)
    ref = set_attention_reference(q, k, v, bias, mask)
    out = masked_set_attention(q, k, v, bias, mask)
    atol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol,
                               rtol=1e-3)


def test_set_transformer_impl_parity():
    """Full Stage-2 model: XLA vs the fused kernel must agree,
    weights + mask engaged (the exact configuration the pipeline runs)."""
    from repro.models.set_transformer import (
        set_transformer_apply, set_transformer_init,
    )
    rng = np.random.RandomState(0)
    B, N, d_in = 3, 23, 16
    params, _ = set_transformer_init(jax.random.PRNGKey(1), d_in=d_in + 1,
                                     d_model=32, d_out=16, num_heads=4)
    x = jnp.asarray(rng.randn(B, N, d_in), jnp.float32)
    w = jnp.asarray(rng.uniform(1, 100, (B, N)), jnp.float32)
    m = rng.rand(B, N) > 0.2
    m[:, 0] = True
    m = jnp.asarray(m)
    y_xla = set_transformer_apply(params, x, num_heads=4, weights=w, mask=m,
                                  impl="xla")
    y_pal = set_transformer_apply(params, x, num_heads=4, weights=w, mask=m,
                                  impl="pallas")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_xla),
                               atol=1e-5, rtol=1e-4)


def test_set_attention_fully_masked_rows_match_reference():
    """Rows with NO valid keys (empty interval sets) must still agree
    with the jnp reference — both collapse to the same fp32-rounded
    uniform softmax over the M real keys, padding excluded."""
    rng = np.random.RandomState(3)
    B, H, N, M, dh = 3, 2, 8, 21, 16
    q, k, v, bias, _ = _set_attn_inputs(rng, B, H, N, M, dh, True, False,
                                        jnp.float32)
    m = rng.rand(B, M) > 0.3
    m[1, :] = False  # one batch row entirely masked
    mask = jnp.asarray(m)
    ref = set_attention_reference(q, k, v, bias, mask)
    out = masked_set_attention(q, k, v, bias, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                               rtol=1e-3)


def test_set_attention_padding_independence():
    """Results must not depend on the wrapper's tile padding: growing M
    with masked-out keys leaves the output unchanged."""
    rng = np.random.RandomState(7)
    q, k, v, bias, mask = _set_attn_inputs(rng, 2, 2, 9, 21, 16, True, True,
                                           jnp.float32)
    out = masked_set_attention(q, k, v, bias, mask)
    pad = 40
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)),
                 constant_values=3.0)  # garbage keys, masked off
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)), constant_values=5.0)
    bp = jnp.pad(bias, ((0, 0), (0, pad)), constant_values=9.0)
    mp = jnp.pad(mask, ((0, 0), (0, pad)))
    out_p = masked_set_attention(q, kp, vp, bp, mp)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out), atol=1e-6)


# ---------------------------------------------------------------------------
# set attention backward (custom VJP, flash-style recompute)
# ---------------------------------------------------------------------------

SET_ATTN_GRAD_CASES = [
    # (B, H, N, M, dh, weighted, masked, dtype)
    (1, 2, 16, 16, 16, False, False, jnp.float32),
    (2, 2, 1, 64, 32, True, True, jnp.float32),     # PMA: one seed query
    (2, 2, 5, 13, 16, True, True, jnp.float32),     # non-tile-aligned
    (1, 3, 17, 33, 8, False, True, jnp.float32),    # masked, unweighted
    (2, 2, 7, 130, 16, True, False, jnp.float32),   # M > one lane tile
    (2, 2, 32, 32, 32, True, True, jnp.bfloat16),   # bf16 fwd+bwd policy
    (2, 2, 5, 13, 16, True, True, jnp.bfloat16),    # bf16 non-aligned
]


@pytest.mark.parametrize("B,H,N,M,dh,weighted,masked,dtype",
                         SET_ATTN_GRAD_CASES)
def test_set_attention_grad_matches_reference(B, H, N, M, dh, weighted,
                                              masked, dtype):
    """jax.grad through the fused kernel (custom VJP) must
    match autodiff of the jnp oracle for q, k, v AND key_bias — across
    masked/unmasked, weighted/unweighted, and non-tile-aligned sizes."""
    rng = np.random.RandomState(7 * N + M)
    q, k, v, bias, mask = _set_attn_inputs(rng, B, H, N, M, dh, True,
                                           masked, dtype)
    if not weighted:
        bias = jnp.zeros_like(bias)   # keep bias diffable, zero signal
    ct = _rand(rng, (B, H, N, dh), jnp.float32)

    def scalar(fn):
        return lambda q, k, v, b: jnp.sum(
            fn(q, k, v, b, mask).astype(jnp.float32) * ct)

    g_ref = jax.grad(scalar(set_attention_reference),
                     argnums=(0, 1, 2, 3))(q, k, v, bias)
    g_pal = jax.grad(
        scalar(lambda *a: masked_set_attention(*a)),
        argnums=(0, 1, 2, 3))(q, k, v, bias)
    atol = 1e-4 if dtype == jnp.float32 else 5e-2
    for name, a, b in zip("dq dk dv dbias".split(), g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(a, np.float32), atol=atol,
                                   rtol=1e-3, err_msg=name)


def test_set_attention_masked_key_grads_exactly_zero():
    """Masked keys sit below the additive NEG_INF tier, so their softmax
    weight underflows to exactly 0 in fp32 — dK, dV, and db of masked
    slots must be EXACTLY zero (no gradient leaks into padded set
    elements), matching the reference's collapse bitwise."""
    rng = np.random.RandomState(11)
    B, H, N, M, dh = 2, 2, 9, 21, 16
    q, k, v, bias, _ = _set_attn_inputs(rng, B, H, N, M, dh, True, False,
                                        jnp.float32)
    m = rng.rand(B, M) > 0.4
    m[:, 0] = True
    mask = jnp.asarray(m)

    def scalar(q, k, v, b):
        return jnp.sum(masked_set_attention(q, k, v, b, mask) ** 2)

    _, dk, dv, db = jax.grad(scalar, argnums=(0, 1, 2, 3))(q, k, v, bias)
    dead = ~m
    assert np.all(np.asarray(dk)[np.broadcast_to(
        dead[:, None, :, None], dk.shape)] == 0.0)
    assert np.all(np.asarray(dv)[np.broadcast_to(
        dead[:, None, :, None], dv.shape)] == 0.0)
    assert np.all(np.asarray(db)[dead] == 0.0)


@pytest.mark.parametrize("weighted,masked", [(True, True), (False, True),
                                             (True, False), (False, False)])
def test_stage2_loss_grad_impl_parity(weighted, masked):
    """End-to-end trainability: jax.grad of stage2_loss through the fused
    kernel path must agree with the XLA path to 1e-4 on every parameter
    leaf — the property Stage-2 impl="pallas" training rests on."""
    from repro.core.signature import (
        SignatureConfig, signature_init, stage2_loss,
    )
    cfg = SignatureConfig(bbe_dim=12, d_model=16, sig_dim=8, num_heads=2,
                          num_sabs=1, max_set=11)   # non-tile-aligned set
    params, _ = signature_init(jax.random.PRNGKey(2), cfg)
    rng = np.random.RandomState(5)
    B, N = 3, cfg.max_set

    def one_set():
        m = rng.rand(B, N) > (0.3 if masked else -1.0)
        m[:, 0] = True
        return {"bbes": jnp.asarray(rng.randn(B, N, cfg.bbe_dim),
                                    jnp.float32),
                "freqs": jnp.asarray(
                    rng.uniform(1, 500, (B, N)) if weighted
                    else np.ones((B, N)), jnp.float32),
                "mask": jnp.asarray(m)}

    batch = {"anchor": one_set(), "positive": one_set(),
             "negative": one_set(),
             "cpi": jnp.asarray(rng.uniform(0.5, 4.0, (B,)), jnp.float32)}

    def grads(impl):
        g = jax.grad(lambda p: stage2_loss(p, cfg, batch, impl)[0])(params)
        return jax.tree_util.tree_leaves_with_path(g)

    for (path_x, gx), (_, gp) in zip(grads("xla"),
                                     grads("pallas")):
        np.testing.assert_allclose(
            np.asarray(gp), np.asarray(gx), atol=1e-4, rtol=1e-3,
            err_msg=jax.tree_util.keystr(path_x))


# ---------------------------------------------------------------------------
# kmeans assign
# ---------------------------------------------------------------------------

KM_CASES = [
    (100, 8, 4, 32, jnp.float32),
    (1000, 64, 14, 128, jnp.float32),
    (513, 32, 30, 64, jnp.float32),   # non-divisible N exercises padding
    (256, 16, 5, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("N,d,K,bn,dtype", KM_CASES)
def test_kmeans_assign_matches_reference(N, d, K, bn, dtype):
    rng = np.random.RandomState(N)
    x = _rand(rng, (N, d), dtype)
    c = _rand(rng, (K, d), dtype)
    a_ref, d_ref = kmeans_assign_reference(x, c)
    a_k, d_k = kmeans_assign(x, c, block_n=bn)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_ref))
    atol = 1e-3 if dtype == jnp.float32 else 1.0
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_ref), atol=atol,
                               rtol=1e-2)


@pytest.mark.parametrize("N,d,K,bn,dtype", KM_CASES)
def test_kmeans_update_matches_reference(N, d, K, bn, dtype):
    """Fused assignment + segment-reduce kernel vs the jnp oracle,
    including a masked pad tail (the store's device-matrix shape)."""
    from repro.kernels.kmeans_assign.ops import kmeans_update
    from repro.kernels.kmeans_assign.ref import kmeans_update_reference
    rng = np.random.RandomState(N)
    x = _rand(rng, (N, d), dtype)
    c = _rand(rng, (K, d), dtype)
    valid = jnp.asarray((np.arange(N) < (3 * N) // 4).astype(np.float32))
    s_k, n_k, i_k = kmeans_update(x, c, valid, block_n=bn)
    s_r, n_r, i_r = kmeans_update_reference(x, c, valid)
    # counts are exact integers; sums/inertia accumulate in fp32
    np.testing.assert_array_equal(np.asarray(n_k), np.asarray(n_r))
    tol = dict(rtol=1e-4, atol=1e-3) if dtype == jnp.float32 else \
        dict(rtol=5e-2, atol=1.0)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), **tol)
    np.testing.assert_allclose(float(i_k), float(i_r[0]), **tol)


def test_kmeans_update_none_valid_counts_everything():
    from repro.kernels.kmeans_assign.ops import kmeans_update
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(100, 8).astype(np.float32))
    c = jnp.asarray(rng.randn(4, 8).astype(np.float32))
    _, counts, _ = kmeans_update(x, c)
    assert float(jnp.sum(counts)) == 100.0
