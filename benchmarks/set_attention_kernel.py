"""Set-attention kernel + signature-batching microbenchmarks.

Three hot paths the fused kernel targets:
  (a) Stage-2 SAB/PMA attention, forward — XLA reference vs the fused
      Pallas kernel.
  (b) the same op in grad mode (value_and_grad) — exercises the custom
      VJP's fused backward kernel, the path Stage-2 training runs.
  (c) interval-set assembly — the old per-interval Python loop vs the
      vectorized `_batch_sets` gather, at 512 intervals × 64-block sets
      (the fig6/table2 working point).

On CPU hosts the Pallas rows run the interpreter (correctness-shaped
numbers only); on a TPU runner the same suite times the compiled kernel.
The JSON record under artifacts/bench/set_attention.json carries the
backend + mode so the perf trajectory never mixes the two regimes.

Rows go to the CSV harness (benchmarks.run); CI uploads the JSON as a
build artifact.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

JSON_PATH = os.path.join("artifacts", "bench", "set_attention.json")


def _time_us(fn, repeat: int = 5) -> float:
    """Median wall-clock microseconds per call (first call = warmup)."""
    jax.block_until_ready(fn())
    ts = []
    for _ in range(repeat):
        t0 = time.monotonic()
        jax.block_until_ready(fn())
        ts.append(time.monotonic() - t0)
    return 1e6 * sorted(ts)[len(ts) // 2]


def _inputs(rng, B, H, N, dh):
    q = jnp.asarray(rng.randn(B, H, N, dh), jnp.float32)
    k = jnp.asarray(rng.randn(B, H, N, dh), jnp.float32)
    v = jnp.asarray(rng.randn(B, H, N, dh), jnp.float32)
    bias = jnp.asarray(rng.rand(B, N), jnp.float32)
    mask = jnp.asarray(rng.rand(B, N) > 0.1)
    return q, k, v, bias, mask


def _bench_kernel(B=64, H=4, N=64, dh=64):
    from repro.kernels.set_attention.ops import masked_set_attention
    from repro.kernels.set_attention.ref import set_attention_reference
    q, k, v, bias, mask = _inputs(np.random.RandomState(0), B, H, N, dh)
    xla = jax.jit(set_attention_reference)
    t_xla = _time_us(lambda: xla(q, k, v, bias, mask))
    t_pal = _time_us(lambda: masked_set_attention(q, k, v, bias, mask),
                     repeat=3)
    return t_xla, t_pal


def _bench_kernel_grad(B=16, H=4, N=64, dh=64):
    """value_and_grad through both impls: the Stage-2 train-step shape.

    The Pallas row runs the custom VJP (forward kernel + fused backward
    kernel with the VMEM score recompute); the XLA row is jax autodiff
    of the reference."""
    from repro.kernels.set_attention.ops import masked_set_attention
    from repro.kernels.set_attention.ref import set_attention_reference
    q, k, v, bias, mask = _inputs(np.random.RandomState(1), B, H, N, dh)

    def scalar(fn):
        return lambda q, k, v, b: jnp.sum(
            jnp.square(fn(q, k, v, b, mask).astype(jnp.float32)))

    g_xla = jax.jit(jax.value_and_grad(scalar(set_attention_reference),
                                       argnums=(0, 1, 2, 3)))
    g_pal = jax.jit(jax.value_and_grad(scalar(masked_set_attention),
                                       argnums=(0, 1, 2, 3)))
    t_xla = _time_us(lambda: g_xla(q, k, v, bias))
    t_pal = _time_us(lambda: g_pal(q, k, v, bias), repeat=3)
    return t_xla, t_pal


def _bench_batch_sets(n_intervals=512, set_size=64, n_blocks=4096):
    from repro.core.bbe import BBEConfig
    from repro.core.pipeline import BBEIndex, SemanticBBVPipeline
    from repro.core.signature import SignatureConfig
    from repro.data.trace import Interval
    sig_cfg = SignatureConfig(bbe_dim=256, max_set=set_size)
    # batching only touches sig_cfg — no params / tokenizer needed
    pipe = SemanticBBVPipeline(None, BBEConfig(), sig_cfg, None, None)
    rng = np.random.RandomState(0)
    table = {bid: rng.randn(sig_cfg.bbe_dim).astype(np.float32)
             for bid in range(n_blocks)}
    ivs = []
    for i in range(n_intervals):
        sel = rng.choice(n_blocks, size=set_size, replace=False)
        counts = {int(b): int(c) for b, c in
                  zip(sel, rng.randint(1, 1000, sel.size))}
        ivs.append(Interval(program="bench", index=i, counts=counts,
                            phase_id=0, working_scale=1.0,
                            num_instrs=10_000))
    index = BBEIndex(table)
    # looped baseline vs what interval_signatures now runs per batch on
    # the host (_batch_set_ids; the BBE payload gather happens on-device)
    t_loop = _time_us(lambda: pipe._batch_sets_looped(ivs, table), repeat=3)
    t_ids = _time_us(lambda: pipe._batch_set_ids(ivs, index), repeat=3)
    # dense materialization (parity path: _batch_set_ids + one gather)
    t_dense = _time_us(lambda: pipe._batch_sets(ivs, index), repeat=3)
    return t_loop, t_ids, t_dense


def run():
    backend = jax.default_backend()
    # the kernel lowers compiled on a TPU, interpreted anywhere else
    mode = "compiled" if backend == "tpu" else "interpret"
    t_xla, t_pal = _bench_kernel()
    tg_xla, tg_pal = _bench_kernel_grad()
    t_loop, t_ids, t_dense = _bench_batch_sets()
    speedup = t_loop / t_ids
    record = {
        "backend": backend,
        "pallas_mode": mode,
        "set_attn_xla_us": t_xla,
        f"set_attn_pallas_{mode}_us": t_pal,
        "set_attn_grad_xla_us": tg_xla,
        f"set_attn_grad_pallas_{mode}_us": tg_pal,
        "batch_sets_looped_us": t_loop,
        "batch_sets_vectorized_us": t_ids,
        "batch_sets_dense_us": t_dense,
        "batch_sets_speedup": speedup,
        "config": {"kernel": "B=64,H=4,N=64,dh=64",
                   "kernel_grad": "B=16,H=4,N=64,dh=64 value_and_grad",
                   "batch_sets": "512 intervals x 64-block sets"},
    }
    os.makedirs(os.path.dirname(JSON_PATH), exist_ok=True)
    with open(JSON_PATH, "w") as f:
        json.dump(record, f, indent=2)
    note = (f"us_per_call ({mode} on {backend})"
            if mode == "interpret" else f"us_per_call (compiled, {backend})")
    return [
        ("set_attn", "sab_attention_xla", f"{t_xla:.0f}", "us_per_call"),
        ("set_attn", f"sab_attention_pallas_{mode}", f"{t_pal:.0f}", note),
        ("set_attn", "sab_attention_grad_xla", f"{tg_xla:.0f}",
         "us_per_call (value_and_grad)"),
        ("set_attn", f"sab_attention_grad_pallas_{mode}", f"{tg_pal:.0f}",
         f"{note} custom-VJP fwd+bwd"),
        ("set_attn", "batch_sets_looped", f"{t_loop:.0f}", "us_per_call"),
        ("set_attn", "batch_sets_vectorized", f"{t_ids:.0f}",
         "us_per_call (host work per signature batch)"),
        ("set_attn", "batch_sets_dense", f"{t_dense:.0f}",
         "us_per_call (bit-identical materialized parity path)"),
        ("set_attn", "batch_sets_speedup", f"{speedup:.1f}x",
         "target >= 5x"),
    ]


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))
