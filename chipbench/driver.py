"""What the benchmark's request loops share.

A traffic file (`chipbench/traffic/<mix>.json`) names its `driver`, a
module of its own, `chipbench/traffic/<driver>.py`, whose `DRIVER` is a
subclass of `Driver` below; the file holds the parameters the driver
reads. A configuration file (`chipbench/configs/<config>.json`) holds
the model widths, the matmul precision, the service's kernel choices and
the deployment's suites. A driver:

  setup()      builds the service from the seed (weights on the device,
               traffic from `chipbench.gen`), fills what the traffic
               needs and warms every shape the window will use;
  request(i)   runs request i through the service's public API;
  work(i)      the operations and bytes request i needed, per layer
               (`chipbench.counts`);
  release()    frees the program's state once the window has closed;
  check()      compares a seeded sample of the window's answers with the
               plain reference (`chipbench.reference`): {number: value}.

`control=True` puts the configuration's control in the program's place
for `check()`: the reference computed one matmul precision below the
configuration's ("high", three bfloat16 passes, below "highest").
"""
from __future__ import annotations

import contextlib
import gc
import time
from typing import Dict, List

import jax
import numpy as np

from chipbench import counts, gen, reference as R, weights

# the host spans the benchmark records around its calls into each layer
SPANS = ("request", "ingest_intervals", "estimate", "vacuum")

# the precision one step below each precision a configuration may state
CONTROL = {"highest": "high"}


class Spans:
    """Host spans of the current request: (name, start, end) on the
    host clock, also written into the profiler's trace when it runs."""

    def __init__(self):
        self.log: List = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.log.append((name, t0, time.perf_counter()))


def sample(rng: np.random.Generator, n: int, k: int) -> np.ndarray:
    """Up to k distinct indices of range(n), sorted."""
    return np.sort(rng.choice(n, size=min(k, n), replace=False))


def rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """Largest row-wise ||got - ref|| / ||ref||."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float((np.linalg.norm(got - ref, axis=-1)
                  / np.maximum(np.linalg.norm(ref, axis=-1), 1e-30)).max())


class Driver:
    """Shared set-up: the service at the configuration's widths and
    precision with weights made from the seed, and the reference's BBEs
    of every block the deployment runs."""

    def __init__(self, config: Dict, traffic: Dict, seed: int,
                 control: bool = False):
        self.config, self.traffic = config, traffic
        self.seed, self.control = int(seed), control
        self.precision = config["matmul_precision"]
        self.spans = Spans()
        self.rng = gen.rng_for(seed, "check")
        # numbers that set the limits but are not compared (control.py)
        self.diag: Dict = {}

    def make_service(self):
        from repro.api import SemanticBBVService, ServiceConfig
        from repro.core.bbe import BBEConfig
        from repro.core.pipeline import SemanticBBVPipeline
        from repro.core.signature import SignatureConfig
        from repro.core.tokenizer import default_tokenizer
        cfg = self.config
        self.bbe, self.sig = dict(cfg["bbe"]), dict(cfg["sig"])
        self.bp, self.sp = weights.make_weights(self.seed, self.bbe, self.sig,
                                                R.VOCAB)
        tok = default_tokenizer()
        if tuple(tok.spec.dim_sizes) != R.VOCAB:
            raise RuntimeError(f"program tokeniser vocabularies "
                               f"{tok.spec.dim_sizes} != reference {R.VOCAB}")
        bc = BBEConfig(**dict(self.bbe, dim_embeds=tuple(
            self.bbe["dim_embeds"]), dtype=cfg["dtype"]))
        sc = SignatureConfig(**dict(self.sig, dtype=cfg["dtype"]))
        svc_cfg = ServiceConfig(bbe=bc, sig=sc, **cfg["service"])
        pipe = SemanticBBVPipeline(tok, bc, sc, self.bp, self.sp,
                                   impl=svc_cfg.impl)
        self.svc = SemanticBBVService(pipe, svc_cfg)
        return self.svc

    def suite(self, key: str) -> List:
        return gen.suite_programs(self.config[key])

    def release(self):
        self.svc = None
        gc.collect()

    def ref_bbes(self, blocks, precision: str) -> Dict:
        """The reference's BBEs of `blocks`: (row of each block id,
        (n, bbe_dim) table)."""
        toks = R.tokens(blocks, self.bbe["max_len"])
        table = R.stage1(self.bp, toks, self.bbe["num_heads"], precision)
        return {b.bid: i for i, b in enumerate(blocks)}, table

    def ref_sigs(self, bbes, tr: gen.Trace, stop: int,
                 precision: str) -> np.ndarray:
        """The reference's signatures of intervals [0, stop) of `tr` over
        BBEs `bbes` = (row of each block id, table)."""
        row_of, table = bbes
        cols, freqs, mask = R.top_sets(tr.counts[:stop], self.sig["max_set"])
        rows = np.asarray([row_of[int(b)] for b in tr.bids])[cols]
        return R.stage2(self.sp, table, rows, freqs, mask,
                        self.sig["num_heads"], precision)

    def set_work(self, tr: gen.Trace, start: int, stop: int
                 ) -> Dict[str, Dict[str, float]]:
        """Stage-2 and set-attention work of intervals [start, stop) of
        `tr`: each interval's real set elements."""
        ns = np.minimum((tr.counts[start:stop] > 0).sum(1),
                        self.sig["max_set"]).tolist()
        sa = [counts.set_attention(n, self.sig) for n in ns]
        return {"stage2": {"flops": sum(counts.stage2_flops(n, self.sig)
                                        for n in ns)},
                "set_attention": {"flops": sum(s["flops"] for s in sa),
                                  "bytes": sum(s["bytes"] for s in sa)}}

