"""`SemanticBBVService` — the one-object public surface (Fig 2 + §IV-C
as a service).

Composes the three layers the paper describes:

    pipeline   blocks -> BBEs -> interval signatures (Stage 1 + 2)
    store      append-only, device-resident signature knowledge base
    knowledge  archetypes + fingerprint / estimate queries

Typical flow:

    svc = SemanticBBVService.create(ServiceConfig(sig=..., bbe=...))
    svc.ingest_blocks(unique_blocks)
    svc.ingest_intervals("gcc", intervals, cpis=ground_truth)   # x N
    svc.build()                       # k-means once -> 14 archetypes
    svc.ingest_intervals("new", ...)  # later, unseen program
    est = svc.estimate("new")         # attach (no re-clustering) + CPI

`ingest_intervals`, `attach_intervals` and `attach_many` (given
intervals) run the Stage-2 step, which has one static shape per set
width (`repro.core.pipeline.set_width`: 32, 64, ... up to `max_set`) and
per BBE table size. The first of these calls to meet a width not seen
before, or to follow an `ingest_blocks` that grew the table, compiles
the step once, inside that call.

Configuration is ONE typed dataclass (`ServiceConfig`) instead of the
kwargs sprawl that used to be spread over `SemanticBBVPipeline.create`
and `benchmarks.lab.get_pipeline`.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, Mapping, Optional, Sequence

import numpy as np

from repro.api.knowledge import CPIEstimate, KnowledgeBase
from repro.api.lifecycle import EvictionPolicy, VacuumReport, vacuum
from repro.api.store import SignatureStore
from repro.core.bbe import BBEConfig
from repro.core.pipeline import PipelineConfig, SemanticBBVPipeline
from repro.core.signature import SignatureConfig
from repro.data.isa import BasicBlock
from repro.utils import tracing


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Everything a SemanticBBV service instance needs, typed.

    `bbe`/`sig` default to the module defaults when None (exactly what
    `SemanticBBVPipeline.create()` did). `impl` picks the set-attention
    backend, `assign_impl` the nearest-centroid backend — both are the
    same switches the kernels expose ("auto" resolves per jax backend).
    """
    seed: int = 0
    bbe: Optional[BBEConfig] = None
    sig: Optional[SignatureConfig] = None
    impl: str = "xla"                 # set-attention: xla|pallas|pallas_interpret
    assign_impl: str = "reference"    # nearest-centroid: see knowledge.ASSIGN_IMPLS
    build_impl: str = "host"          # kmeans restart loop: see knowledge.BUILD_IMPLS
    k: int = 14                       # universal archetypes (paper: 14)
    kmeans_seed: int = 0
    encode_batch: int = 256           # Stage-1 block batch
    signature_batch: int = 512        # Stage-2 interval batch
    store_min_capacity: int = 64      # pad-and-grow floor
    # store lifecycle: what vacuum() evicts (TTL/LRU over the store's
    # logical clock; defaults to "nothing" — compaction only)
    eviction: EvictionPolicy = EvictionPolicy()

    def pipeline_config(self) -> PipelineConfig:
        return PipelineConfig(seed=self.seed, bbe=self.bbe, sig=self.sig,
                              impl=self.impl)


class SemanticBBVService:
    """Facade over pipeline + SignatureStore + KnowledgeBase."""

    def __init__(self, pipeline: SemanticBBVPipeline,
                 cfg: Optional[ServiceConfig] = None,
                 store: Optional[SignatureStore] = None,
                 kb: Optional[KnowledgeBase] = None):
        self.pipe = pipeline
        self.cfg = cfg or ServiceConfig(
            bbe=pipeline.bbe_cfg, sig=pipeline.sig_cfg, impl=pipeline.impl)
        self.bbe_table: Dict[int, np.ndarray] = {}
        self.store = store if store is not None else SignatureStore(
            pipeline.sig_cfg.sig_dim,
            min_capacity=self.cfg.store_min_capacity)
        self.kb = kb if kb is not None else KnowledgeBase(
            self.store, assign_impl=self.cfg.assign_impl,
            build_impl=self.cfg.build_impl)

    # ------------------------------------------------------------ factory
    @classmethod
    def create(cls, cfg: ServiceConfig = ServiceConfig()
               ) -> "SemanticBBVService":
        """Fresh (untrained) pipeline from one typed config."""
        pipe = SemanticBBVPipeline.from_config(cfg.pipeline_config())
        return cls(pipe, cfg)

    @classmethod
    def from_pipeline(cls, pipeline: SemanticBBVPipeline,
                      cfg: Optional[ServiceConfig] = None
                      ) -> "SemanticBBVService":
        """Wrap an already-trained pipeline (e.g. the cached lab one)."""
        return cls(pipeline, cfg)

    # ------------------------------------------------------------- ingest
    def ingest_blocks(self, blocks: Sequence[BasicBlock]) -> int:
        """Stage-1 encode new basic blocks into the service's BBE table
        (LRU-cached in the pipeline); returns the table size."""
        with tracing.span("service.ingest_blocks"):
            self.bbe_table.update(
                self.pipe.encode_blocks(list(blocks), self.cfg.encode_batch))
            return len(self.bbe_table)

    def ingest_intervals(self, program: str, intervals: Sequence,
                         cpis: Optional[Sequence[float]] = None
                         ) -> np.ndarray:
        """Signature every interval and append to the store; returns the
        new store row indices. Interval instruction counts become the
        store weights (the weight-aware speedup + fingerprint norm).
        Blocks referenced by the intervals must have been ingested."""
        with tracing.span("service.ingest_intervals"):
            sigs = self.pipe.interval_signatures(
                list(intervals), self.bbe_table, self.cfg.signature_batch)
            weights = [iv.num_instrs for iv in intervals]
            return self.store.add(program, sigs, weights, cpis)

    # ------------------------------------------------------------ queries
    def build(self, k: Optional[int] = None,
              seed: Optional[int] = None) -> KnowledgeBase:
        """Universal clustering over everything ingested so far."""
        with tracing.span("service.build"):
            return self.kb.build(
                k=self.cfg.k if k is None else k,
                seed=self.cfg.kmeans_seed if seed is None else seed)

    def attach(self, program: str) -> np.ndarray:
        """Fingerprint an ingested-after-build program against the
        frozen archetypes (batched nearest-centroid, no re-clustering)."""
        with tracing.span("service.attach"):
            return self.kb.attach(program)

    def attach_many(self, programs,
                    cpis: Optional[Dict[str, Sequence[float]]] = None
                    ) -> Dict[str, np.ndarray]:
        """Multi-tenant attach: fingerprint MANY programs with one
        batched device pass instead of N per-program attach calls.

        `programs` is either a sequence of already-ingested program
        names, or a mapping {program: intervals} to ingest-and-attach:
        signature generation is pipelined across ALL programs in one
        padded batch stream (`interval_signatures_many`), the rows land
        in the store via one `add_many` (single capacity growth, single
        version bump), and their new rows — all programs' together — are
        then assigned against the frozen archetypes in ONE
        nearest-centroid call.
        Bit-identical fingerprints to sequential `attach`.
        """
        with tracing.span("service.attach_many"):
            if isinstance(programs, Mapping):
                # fail BEFORE mutating the append-only store: a built
                # check after ingest would leave orphan rows that a retry
                # double-ingests
                self.kb._require_built()
                by_prog = {p: list(ivs) for p, ivs in programs.items()}
                sigs = self.pipe.interval_signatures_many(
                    by_prog, self.bbe_table, self.cfg.signature_batch)
                self.store.add_many([
                    (p, sigs[p], [iv.num_instrs for iv in ivs],
                     None if cpis is None else cpis.get(p))
                    for p, ivs in by_prog.items()])
                names = list(by_prog)
            else:
                names = list(programs)
            return self.kb.attach_many(names)

    def attach_intervals(self, program: str, intervals: Sequence
                         ) -> np.ndarray:
        """One-shot fingerprint WITHOUT ingesting into the store — a
        pure query that leaves no footprint in the knowledge base
        (use `ingest_intervals` + `estimate` for estimable programs)."""
        with tracing.span("service.attach_intervals"):
            sigs = self.pipe.interval_signatures(
                list(intervals), self.bbe_table, self.cfg.signature_batch)
            return self.kb.attach(program, signatures=sigs,
                                  weights=[iv.num_instrs for iv in intervals])

    def estimate(self, program: str) -> CPIEstimate:
        with tracing.span("service.estimate"):
            est = self.kb.estimate(program)
            # recency stamp AFTER the query (pure metadata: the per-row
            # label cache is untouched)
            self.store.touch(self.store.rows_for(program))
            return est

    # ---------------------------------------------------- store lifecycle
    def evict(self, program: str) -> int:
        """Tombstone every live interval row of `program` (reclaimed at
        the next `vacuum`); returns the number of rows evicted."""
        with tracing.span("service.evict"):
            return self.store.evict_program(program)

    def vacuum(self, policy: Optional[EvictionPolicy] = None
               ) -> VacuumReport:
        """One store-maintenance pass: evict per the policy (default:
        `ServiceConfig.eviction`), compact tombstones out of the padded
        device matrix (one device gather; capacity shrinks back to a
        power of two), and re-pin the knowledge base through the row
        remap — estimates of untouched programs are bit-identical
        before/after (recorded archetype CPIs survive eviction)."""
        with tracing.span("service.vacuum"):
            return vacuum(self.store, self.kb,
                          self.cfg.eviction if policy is None else policy)

    # -------------------------------------------------------- persistence
    def save(self, directory: str) -> str:
        """Persist store + knowledge base (+ a human-readable summary)
        under `directory` via the atomic checkpoint infra."""
        os.makedirs(directory, exist_ok=True)
        self.store.save(os.path.join(directory, "store"))
        summary = {"programs": self.store.programs,
                   "intervals": len(self.store),
                   "live_intervals": self.store.n_alive,
                   "built": self.kb.built}
        if self.kb.built:
            # estimate() BEFORE kb.save(): it re-attaches any program
            # whose live rows changed since the last fingerprint, so the
            # persisted KB and the summary agree (the reload contract).
            # Fully-evicted (not yet compacted) programs have nothing to
            # estimate — registry ghosts until the next vacuum.
            ests = {p: self.kb.estimate(p) for p in self.store.programs
                    if self.store.rows_for(p).size}
            self.kb.save(os.path.join(directory, "knowledge"))
            summary.update(
                k=self.kb.k,
                avg_accuracy=self.kb.avg_accuracy,
                speedup=next(iter(ests.values())).speedup if ests else None,
                estimates={p: {"est_cpi": e.est_cpi, "true_cpi": e.true_cpi,
                               "accuracy": e.accuracy}
                           for p, e in ests.items()})
        with open(os.path.join(directory, "summary.json"), "w") as f:
            json.dump(summary, f, indent=2, sort_keys=True)
        return directory

    @classmethod
    def load(cls, directory: str, pipeline: SemanticBBVPipeline,
             cfg: Optional[ServiceConfig] = None) -> "SemanticBBVService":
        """Rehydrate a saved service around a (trained) pipeline."""
        store = SignatureStore.load(os.path.join(directory, "store"))
        kb_dir = os.path.join(directory, "knowledge")
        kb = (KnowledgeBase.load(kb_dir, store)
              if os.path.isdir(kb_dir) else None)
        return cls(pipeline, cfg, store=store, kb=kb)
