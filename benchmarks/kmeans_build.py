"""KnowledgeBase-build microbenchmark: host vs on-device k-means.

The paper's universal clustering runs k-means (++ init, restarts) over
every interval signature in the store — 100k+ rows at paper scale. Two
build paths are timed on identical synthetic signature blobs:

  host     the legacy `kmeans` wrapper: one jitted dispatch per restart,
           numpy round-trips of centroids + (N,) assignment each time,
           best-of on the host (what `build()` ran before the device
           path existed).
  device   `kmeans_device`: ALL restarts inside one jitted call over the
           padded device-resident matrix (`n_valid` masks the pad tail —
           exactly how `KnowledgeBase(build_impl="device").build` consumes
           `SignatureStore.device_matrix`), expansion-form ++ init,
           best-of argmin on device.

On TPU the device path additionally runs the fused Pallas
assignment/segment-reduce kernels (`use_kernel=True`, compiled); on CPU
hosts it uses the jnp ops (the interpreter would only produce
correctness-shaped numbers). The JSON record under
artifacts/bench/kmeans_build.json carries backend + kernel mode so the
perf trajectory never mixes regimes, and the bench-gate CI job compares
the wall times against benchmarks/baselines/.

Acceptance (ISSUE 4): device_build beats host_build at >= 10k intervals.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

JSON_PATH = os.path.join("artifacts", "bench", "kmeans_build.json")

N_INTERVALS = 10_240          # >= 10k synthetic intervals (acceptance)
SIG_DIM = 64
K = 14                        # the paper's universal archetype count
ITERS = 10
RESTARTS = 3


def _time_us(fn, repeat: int = 3) -> float:
    """Median wall-clock microseconds per call (first call = warmup)."""
    fn()
    ts = []
    for _ in range(repeat):
        t0 = time.monotonic()
        fn()
        ts.append(time.monotonic() - t0)
    return 1e6 * sorted(ts)[len(ts) // 2]


def _synthetic_signatures(n: int, d: int, k: int, seed: int = 0):
    """Blob world: k behavioral archetypes + per-interval noise."""
    rng = np.random.RandomState(seed)
    centers = rng.randn(k, d) * 4.0
    per = n // k
    x = np.concatenate(
        [c + rng.randn(per, d) * 0.3 for c in centers]
        + [centers[0] + rng.randn(n - per * k, d) * 0.3])
    return x.astype(np.float32)


def _padded(x: np.ndarray):
    """Pad rows to the store's pad-and-grow capacity shape."""
    from repro.api.store import _capacity_for
    cap = _capacity_for(x.shape[0])
    pad = np.zeros((cap - x.shape[0], x.shape[1]), np.float32)
    return np.concatenate([x, pad]), x.shape[0]


def run():
    from repro.core.clustering import kmeans, kmeans_device

    backend = jax.default_backend()
    use_kernel = backend == "tpu"
    mode = "pallas_compiled" if use_kernel else "xla_jnp"

    x = _synthetic_signatures(N_INTERVALS, SIG_DIM, K)
    xp, n_valid = _padded(x)

    t_host = _time_us(
        lambda: kmeans(x, K, iters=ITERS, restarts=RESTARTS, seed=0))
    t_dev = _time_us(
        lambda: kmeans_device(x, K, iters=ITERS, restarts=RESTARTS,
                              seed=0, use_kernel=use_kernel))
    # the store path: padded capacity matrix + n_valid mask (what
    # KnowledgeBase(build_impl="device").build actually runs)
    t_dev_pad = _time_us(
        lambda: kmeans_device(xp, K, iters=ITERS, restarts=RESTARTS,
                              seed=0, use_kernel=use_kernel,
                              n_valid=n_valid))
    speedup = t_host / t_dev

    record = {
        "backend": backend,
        "kernel_mode": mode,
        "host_build_us": t_host,
        "device_build_us": t_dev,
        "device_build_padded_us": t_dev_pad,
        "device_speedup": speedup,
        "config": {
            "n_intervals": N_INTERVALS, "sig_dim": SIG_DIM, "k": K,
            "iters": ITERS, "restarts": RESTARTS,
            "padded_capacity": int(xp.shape[0]),
        },
    }
    os.makedirs(os.path.dirname(JSON_PATH), exist_ok=True)
    with open(JSON_PATH, "w") as f:
        json.dump(record, f, indent=2)

    note = f"us_per_build ({mode} on {backend})"
    return [
        ("kmeans_build", "host_build", f"{t_host:.0f}",
         f"us_per_build (legacy per-restart round-trip, {backend})"),
        ("kmeans_build", "device_build", f"{t_dev:.0f}", note),
        ("kmeans_build", "device_build_padded", f"{t_dev_pad:.0f}",
         f"{note} over the pow2-capacity store matrix"),
        ("kmeans_build", "device_speedup", f"{speedup:.1f}x",
         "acceptance: device beats host at >= 10k intervals"),
    ]


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))
