#!/usr/bin/env python3
"""Smoke run of the SemanticBBV service path on a TPU, at the paper's widths.

    python chip_smoke.py             # one chip: phases 1-5 below
    python chip_smoke.py --chips 4   # four chips: the sharded build only

One process drives everything (a chip belongs to one process). The one-chip
run goes through the service entry point, `repro.api.SemanticBBVService`,
with the compiled Pallas kernels named explicitly:

  1. device   refuse anything but a TPU, before any work;
  2. data     10 SPEC-CPU2017-int-shaped programs x 10,000 intervals
              (~10^5 store rows: the paper's 14 points x 7143x speedup),
              ground-truth CPI from the in-order CPU model, all from a seed;
  3. service  BBEConfig() / SignatureConfig() with random parameters:
              ingest blocks, ingest 9 programs, build k=14, attach_many
              the held-out program, estimate all 10;
  4. parity   the same parameters through impl="xla" on the first 4,096
              intervals, and the jnp nearest-centroid reference on the
              whole store, both at matmul precision "highest";
  5. stage2   5 Stage-2 training steps with impl="pallas" (the fused
              set-attention backward), first loss checked against "xla".

`--chips 4` runs only the data-axis build: `KnowledgeBase.build(mesh=...)`
over a 4-device ("data",) mesh against the one-device build.

Every phase prints one JSON line tagged "smoke" (wall seconds, compile
seconds, shapes, counts, parity numbers): these are smoke readings, not
benchmark metrics. The last line is {"ok": true, "device": {...}}; any
failed phase or check exits non-zero before it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import warnings
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.api import (  # noqa: E402
    KnowledgeBase, SemanticBBVService, ServiceConfig, SignatureStore,
    assign_signatures,
)
from repro.config import TrainConfig  # noqa: E402
from repro.core.clustering import kmeans_device, shard_rows  # noqa: E402
from repro.core.pipeline import BBEIndex  # noqa: E402
from repro.core.signature import signature_specs  # noqa: E402
from repro.data.asmgen import Program, spec_programs  # noqa: E402
from repro.data.isa import BasicBlock  # noqa: E402
from repro.data.perfmodel import INORDER_CPU, trace_cpi  # noqa: E402
from repro.data.trace import Interval, block_table, trace_program  # noqa: E402
from repro.kernels.kmeans_assign.ref import (  # noqa: E402
    kmeans_assign_reference,
)
from repro.train.stage2 import Stage2Engine, triplet_row_batch  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

INTERVALS_PER_PROGRAM = 10_000
PARITY_ROWS = 4096
MIN_SIG_COSINE = 0.999          # per-row, kernel vs jnp reference
MIN_ASSIGN_AGREEMENT = 0.999    # share of live rows, kernel vs reference
LOSS_RTOL = 1e-3                # first Stage-2 loss, pallas vs xla
INERTIA_RTOL = 1e-4             # sharded vs one-device build
STAGE2_STEPS = 5
STAGE2_BATCH = 64


def emit(phase: str, **fields) -> None:
    print(json.dumps({"smoke": phase, **fields}), flush=True)


# ----------------------------------------------------------------- phase 1
def check_device(chips: int) -> Dict:
    """Platform of the first device; exits non-zero unless it is a TPU
    and at least `chips` devices are visible."""
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    emit("device", **info)
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{info['platform']!r}")
    if info["count"] < chips:
        raise SystemExit(f"chip_smoke: needs {chips} chips, JAX found "
                         f"{info['count']}")
    return info


# ----------------------------------------------------------------- phase 2
@dataclasses.dataclass
class World:
    programs: List[Program]
    blocks: List[BasicBlock]                # unique blocks of all programs
    intervals: Dict[str, List[Interval]]
    cpis: Dict[str, np.ndarray]             # ground-truth CPI per interval

    @property
    def names(self) -> List[str]:
        return [p.name for p in self.programs]


def make_world(programs: Sequence[Program], n_intervals: int,
               seed: int = 0) -> World:
    """Traced intervals and in-order-CPU CPIs for `programs`."""
    table = block_table(list(programs))
    intervals, cpis = {}, {}
    for p in programs:
        ivs = trace_program(p, n_intervals, seed=seed)
        intervals[p.name] = ivs
        cpis[p.name] = trace_cpi(ivs, table, INORDER_CPU)
    return World(list(programs), list(table.values()), intervals, cpis)


# ----------------------------------------------------------------- phase 3
def run_service(world: World, cfg: ServiceConfig):
    """Drive the service: every program but the last is ingested before
    the build, the last is attached to the frozen archetypes. Returns
    (service, per-step seconds, estimates)."""
    *seen, held_out = world.names
    svc = SemanticBBVService.create(cfg)
    secs = {}
    t = time.perf_counter()
    svc.ingest_blocks(world.blocks)
    secs["ingest_blocks"] = time.perf_counter() - t
    t = time.perf_counter()
    for p in seen:
        svc.ingest_intervals(p, world.intervals[p], cpis=world.cpis[p])
    secs["ingest_intervals"] = time.perf_counter() - t
    t = time.perf_counter()
    svc.build()
    secs["build"] = time.perf_counter() - t
    t = time.perf_counter()
    svc.attach_many({held_out: world.intervals[held_out]},
                    cpis={held_out: world.cpis[held_out]})
    secs["attach_many"] = time.perf_counter() - t
    t = time.perf_counter()
    ests = {p: svc.estimate(p) for p in world.names}
    secs["estimate"] = time.perf_counter() - t

    norms = np.linalg.norm(svc.store.signatures, axis=-1)
    if not np.all(np.abs(norms - 1.0) < 1e-3):
        raise AssertionError(f"signatures not unit-norm: "
                             f"[{norms.min()}, {norms.max()}]")
    for p, e in ests.items():
        if not (np.isfinite(e.est_cpi) and e.est_cpi > 0):
            raise AssertionError(f"{p}: est_cpi {e.est_cpi}")
        if e.accuracy is None or not 0.0 <= e.accuracy <= 1.0:
            raise AssertionError(f"{p}: accuracy {e.accuracy}")
    return svc, secs, ests


# ----------------------------------------------------------------- phase 4
def run_parity(svc: SemanticBBVService, world: World,
               n_rows: int = PARITY_ROWS) -> Dict:
    """Kernel paths against their references on the same parameters.

    Signatures: the service's pipeline against an impl="xla" twin on the
    first `n_rows` stored intervals, both at matmul precision "highest"
    (the served signatures, at default precision, are compared too and
    reported, not judged). Assignment: the service's kernel against
    `kmeans_assign_reference` over every live store row."""
    ivs = [iv for p in world.names for iv in world.intervals[p]][:n_rows]
    ref_pipe = dataclasses.replace(svc.pipe, impl="xla")
    batch = svc.cfg.signature_batch
    with jax.default_matmul_precision("highest"):
        ref = ref_pipe.interval_signatures(ivs, svc.bbe_table, batch)
        got = svc.pipe.interval_signatures(ivs, svc.bbe_table, batch)
    served = svc.store.signatures[:len(ivs)]

    def cosines(a, b):
        return np.sum(a * b, -1) / (np.linalg.norm(a, axis=-1)
                                    * np.linalg.norm(b, axis=-1))

    cos = cosines(got, ref)
    cos_served = cosines(served, ref)

    kb, store = svc.kb, svc.store
    matrix = np.asarray(store.device_matrix)
    a_kernel, _ = assign_signatures(matrix, kb.archetypes, kb.assign_impl)
    with jax.default_matmul_precision("highest"):
        a_ref, _ = kmeans_assign_reference(jnp.asarray(matrix),
                                           jnp.asarray(kb.archetypes))
    live = store.alive_rows
    a_kernel, a_ref = a_kernel[live], np.asarray(a_ref)[live]
    agree = float(np.mean(a_kernel == a_ref))
    # float64 distances: how many rows are near-tied, and who is right
    x = matrix[live].astype(np.float64)
    c = kb.archetypes.astype(np.float64)
    d2 = (x * x).sum(-1)[:, None] - 2.0 * x @ c.T + (c * c).sum(-1)[None]
    gap = np.diff(np.sort(d2, axis=-1)[:, :2], axis=-1)[:, 0]
    a64 = d2.argmin(-1)
    out = {"rows": len(ivs), "min_cosine": float(cos.min()),
           "min_cosine_served": float(cos_served.min()),
           "mean_cosine_served": float(cos_served.mean()),
           "cosine_target": MIN_SIG_COSINE,
           "assign_rows": int(live.size), "assign_agreement": agree,
           "assign_target": MIN_ASSIGN_AGREEMENT,
           "kernel_vs_f64": float(np.mean(a_kernel == a64)),
           "reference_vs_f64": float(np.mean(a_ref == a64)),
           "near_tie_share_1e-5": float(np.mean(gap < 1e-5)),
           "median_d2_gap": float(np.median(gap))}
    if out["min_cosine"] < MIN_SIG_COSINE:
        raise AssertionError(f"signature parity: {out}")
    if agree < MIN_ASSIGN_AGREEMENT:
        raise AssertionError(f"assignment parity: {out}")
    return out


# ----------------------------------------------------------------- phase 5
def triplet_batch(world: World, index: BBEIndex, max_set: int, step: int,
                  batch: int) -> Dict:
    """Anchor and positive from one phase of one program, negative from
    another program; deterministic in `step`."""
    rng = np.random.RandomState(step)
    sets = {k: [] for k in ("anchor", "positive", "negative")}
    cpis = []
    for _ in range(batch):
        pa, pn = rng.choice(world.names, 2, replace=False)
        ivs = world.intervals[pa]
        phase = ivs[rng.randint(len(ivs))].phase_id
        same = [i for i, iv in enumerate(ivs) if iv.phase_id == phase]
        ia, ip = rng.choice(same, 2)
        ivn = world.intervals[pn]
        sets["anchor"].append(ivs[ia])
        sets["positive"].append(ivs[ip])
        sets["negative"].append(ivn[rng.randint(len(ivn))])
        cpis.append(world.cpis[pa][ia])
    return triplet_row_batch(sets, cpis, index, max_set)


def run_stage2(svc: SemanticBBVService, world: World, impl: str,
               steps: int = STAGE2_STEPS, batch: int = STAGE2_BATCH) -> Dict:
    """`steps` training steps of a Stage2Engine on `impl` from the
    service's parameters; the first step's loss must match an "xla"
    engine on the same batch. Runs at matmul precision "highest"."""
    cfg = svc.pipe.sig_cfg
    index = BBEIndex(svc.bbe_table)
    specs = signature_specs(cfg)
    tc = TrainConfig(learning_rate=1e-3, total_steps=steps, warmup_steps=1,
                     checkpoint_every=0)
    batches = [triplet_batch(world, index, cfg.max_set, s, batch)
               for s in range(steps)]
    with jax.default_matmul_precision("highest"):
        ref = Stage2Engine(cfg, svc.pipe.sig_params, specs, index.ext, tc,
                           impl="xla").step(batches[0])
        engine = Stage2Engine(cfg, svc.pipe.sig_params, specs, index.ext,
                              tc, impl=impl)
        metrics = [engine.step(b) for b in batches]
    losses = [m["loss"] for m in metrics]
    gnorms = [m["grad_norm"] for m in metrics]
    if not (np.all(np.isfinite(losses)) and np.all(np.isfinite(gnorms))):
        raise AssertionError(f"non-finite stage-2 step: {metrics}")
    rel = abs(losses[0] - ref["loss"]) / max(abs(ref["loss"]), 1e-12)
    out = {"steps": steps, "batch": batch, "losses": losses,
           "grad_norms": gnorms, "xla_first_loss": ref["loss"],
           "xla_first_grad_norm": ref["grad_norm"],
           "first_loss_rel_diff": rel, "loss_rtol": LOSS_RTOL}
    if rel > LOSS_RTOL:
        raise AssertionError(f"stage-2 loss parity: {out}")
    return out


# ------------------------------------------------------------ --chips 4
def clustered_store(n_rows: int, sig_dim: int, n_programs: int, k: int,
                    seed: int = 0) -> SignatureStore:
    """Unit-norm signatures around `k` well-separated centres, spread
    over `n_programs` programs with weights and CPIs."""
    rng = np.random.RandomState(seed)
    centres = rng.randn(k, sig_dim)
    x = centres[rng.randint(k, size=n_rows)] + 0.05 * rng.randn(n_rows,
                                                                 sig_dim)
    x = (x / np.linalg.norm(x, axis=-1, keepdims=True)).astype(np.float32)
    store = SignatureStore(sig_dim)
    for p, rows in enumerate(np.array_split(np.arange(n_rows), n_programs)):
        store.add(f"prog{p}", x[rows],
                  weights=rng.uniform(0.5, 1.5, rows.size),
                  cpis=rng.uniform(0.5, 4.0, rows.size))
    return store


def run_sharded_build(store: SignatureStore, mesh, k: int = 14,
                      seed: int = 0) -> Dict:
    """`KnowledgeBase.build` with build_impl="device_kernel" over `mesh`
    against the one-device build of the same store. The rows must really
    shard over every device of the mesh, the archetypes must align one to
    one, the aligned assignments must be equal and the inertia close."""
    n_dev = mesh.devices.size
    xd = shard_rows(store.device_matrix, mesh)
    shard_rows_seen = sorted(s.data.shape[0] for s in xd.addressable_shards)
    if (len(xd.sharding.device_set) != n_dev
            or xd.sharding.is_fully_replicated
            or shard_rows_seen != [xd.shape[0] // n_dev] * n_dev):
        raise AssertionError(f"store rows not sharded over {n_dev} devices: "
                             f"{xd.sharding}, shard rows {shard_rows_seen}")
    secs = {}
    t = time.perf_counter()
    one = KnowledgeBase(store, build_impl="device_kernel").build(k, seed)
    secs["build_one_device"] = time.perf_counter() - t
    t = time.perf_counter()
    with warnings.catch_warnings():
        warnings.simplefilter("error")     # "running replicated" fails
        multi = KnowledgeBase(store, build_impl="device_kernel").build(
            k, seed, mesh=mesh)
    secs["build_sharded"] = time.perf_counter() - t

    # the builds' own labels: same static arguments, so the compiled
    # restart loops are reused
    n = len(store)
    c1, a1, i1 = kmeans_device(store.device_matrix, k, seed=seed,
                               use_kernel=True, n_valid=n)
    cm, am, im = kmeans_device(store.device_matrix, k, seed=seed,
                               use_kernel=True, n_valid=n, mesh=mesh)
    perm = ((cm[:, None, :] - c1[None, :, :]) ** 2).sum(-1).argmin(1)
    if sorted(perm.tolist()) != list(range(k)):
        raise AssertionError(f"archetypes do not align: {perm}")
    mismatched = int(np.sum(perm[am] != a1))
    inertia_rel = abs(im - i1) / max(abs(i1), 1e-12)
    fp_err = max(float(np.abs(multi.fingerprints[p]
                              - one.fingerprints[p][perm]).max())
                 for p in store.programs)
    out = {"devices": n_dev, "rows": n, "capacity": store.capacity,
           "shard_rows": shard_rows_seen, "k": k,
           "mismatched_assignments": mismatched,
           "inertia_one_device": i1, "inertia_sharded": im,
           "inertia_rel_diff": inertia_rel, "inertia_rtol": INERTIA_RTOL,
           "max_centroid_diff": float(np.abs(cm - c1[perm]).max()),
           "max_fingerprint_diff": fp_err, **secs}
    if mismatched or inertia_rel > INERTIA_RTOL or fp_err > 1e-9:
        raise AssertionError(f"sharded build differs: {out}")
    return out


# --------------------------------------------------------------------- main
class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (a persistent
    cache hit counts only its read), and persistent-cache hits."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.secs = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.EVENTS:
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def phase(self, name: str, fn):
        """Run fn(), print its smoke line, return its result."""
        c0, h0, t0 = self.secs, self.cache_hits, time.perf_counter()
        result = fn()
        emit(name, wall_s=time.perf_counter() - t0,
             compile_s=self.secs - c0, cache_hits=self.cache_hits - h0,
             **(result if isinstance(result, dict) else {}))
        return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-axis sharded build")
    args = ap.parse_args(argv)
    info = check_device(args.chips)
    cache = enable_compile_cache()
    clock = CompileClock()
    emit("setup", compile_cache=cache)

    if args.chips == 4:
        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        store = clock.phase("data", lambda: clustered_store(
            10 * INTERVALS_PER_PROGRAM, 128, 10, 14))
        clock.phase("sharded_build", lambda: run_sharded_build(store, mesh))
    else:
        world = clock.phase("data", lambda: make_world(
            spec_programs("int"), INTERVALS_PER_PROGRAM))
        cfg = ServiceConfig(impl="pallas", assign_impl="pallas",
                            build_impl="device_kernel", k=14)
        svc, secs, ests = clock.phase(
            "service", lambda: run_service(world, cfg))
        emit("service_detail", steps_s=secs,
             store_rows=len(svc.store), capacity=svc.store.capacity,
             blocks=len(svc.bbe_table),
             bbe=dataclasses.asdict(svc.pipe.bbe_cfg),
             sig=dataclasses.asdict(svc.pipe.sig_cfg),
             speedup=next(iter(ests.values())).speedup,
             est_cpi={p: e.est_cpi for p, e in ests.items()},
             accuracy={p: e.accuracy for p, e in ests.items()})
        clock.phase("parity", lambda: run_parity(svc, world))
        clock.phase("stage2", lambda: run_stage2(svc, world, "pallas"))
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
