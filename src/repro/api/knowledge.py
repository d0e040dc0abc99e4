"""`KnowledgeBase` — the redesigned cross-program estimation engine.

The paper's headline capability (§IV-C, Fig 5/6) as an incremental
service instead of a one-shot function:

  build(k)    k-means the WHOLE store into k universal behavioral
              archetypes, pick one representative interval each, and
              record the reps' ground-truth CPI — the only "simulation"
              the knowledge base ever requires.
  attach(p)   fingerprint a NEW program against the FROZEN archetypes:
              batched nearest-centroid assignment of its interval
              signatures (no re-clustering — the true reuse use-case).
  estimate(p) typed `CPIEstimate`: estimated CPI from the fingerprint x
              rep-CPI dot product, clamped accuracy when ground truth is
              known, and the weight-aware speedup.

Each base has two backend options, each the jnp reference or the Pallas
kernel; where the kernel is picked it runs compiled on a TPU and
interpreted anywhere else (repro/kernels/__init__.py):
  assign_impl  "reference" (default) | "pallas"         nearest archetype
  build_impl   "device" (default)    | "device_kernel"  k-means restarts

Stored rows are labelled once: the base keeps every row's nearest
archetype and follows the store through appends, evictions and
compactions, so a query assigns only the rows added since the last one,
padded to the next power of two like ad-hoc signatures. Only when the
cache cannot follow the store (a new build, a compaction whose remap it
never saw) is the whole padded `device_matrix` assigned, in place.
Every backend sees O(log N) shapes — one compile per power of two.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.store import SignatureStore, _capacity_for
from repro.core.clustering import kmeans_device, representatives
from repro.core.crossprog import cpi_accuracy, speedup
from repro.kernels import check_backend
from repro.train.checkpoint import (
    latest_checkpoint, restore_checkpoint, save_checkpoint,
)
from repro.utils import tracing

_ASSIGN_BACKENDS = ("reference", "pallas")
_BUILD_BACKENDS = ("device", "device_kernel")
# values an older `save` may have written, read by `load` as their successors
_RETIRED = {
    "assign_impl": {"numpy": "reference", "pallas_interpret": "pallas",
                    "auto": "pallas"},
    "build_impl": {"host": "device"},
}


def assign_signatures(signatures: np.ndarray, centroids: np.ndarray,
                      impl: str = "reference"
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Batched nearest-centroid: (assign (N,) int32, dist2 (N,) f32),
    by the jnp reference or the Pallas `kmeans_assign` kernel
    (parity-tested against each other).

    `signatures` may be a float32 device array (the store's resident
    `device_matrix`): both backends read it where it is, with no host
    copy and no upload.
    """
    if impl == "numpy":
        # chipbench/tests still name the retired numpy oracle; this goes
        # with the backend fields themselves (ROADMAP Queue 3 item 1)
        impl = "reference"
    impl = check_backend("assign_impl", impl, _ASSIGN_BACKENDS)
    on_device = isinstance(signatures, jax.Array)
    x = signatures if on_device else np.asarray(signatures, np.float32)
    c = np.asarray(centroids, np.float32)
    if impl == "reference":
        from repro.kernels.kmeans_assign.ref import kmeans_assign_reference
        a, d2 = kmeans_assign_reference(jnp.asarray(x), jnp.asarray(c))
    else:
        from repro.kernels.kmeans_assign.ops import kmeans_assign
        a, d2 = kmeans_assign(jnp.asarray(x), jnp.asarray(c))
    a, d2 = np.asarray(a), np.asarray(d2)
    tracing.add(h2d_bytes=(0 if on_device else x.nbytes) + c.nbytes,
                d2h_bytes=a.nbytes + d2.nbytes)
    return a, d2


@dataclasses.dataclass(frozen=True)
class CPIEstimate:
    """Typed answer to an `estimate` query.

    `accuracy` is the paper's 1 - |est-true|/true with the divisor
    clamped away from zero and the result clipped to [0, 1]; None when
    the program has no ground-truth CPI. `speedup` is weight-aware:
    (total instructions represented by the knowledge base) /
    (instructions in the k simulated representative intervals).
    """
    program: str
    est_cpi: float
    true_cpi: Optional[float]
    accuracy: Optional[float]
    speedup: float
    fingerprint: np.ndarray          # (k,) archetype occupancy, sums to 1
    k: int
    simulated_weight: float
    total_weight: float


class KnowledgeBase:
    """Archetype knowledge over a `SignatureStore` (build once, attach
    and estimate many). Holds NO interval payload of its own — only the
    k centroids + representative metadata — so it stays tiny next to
    the store."""

    def __init__(self, store: SignatureStore, *,
                 assign_impl: str = "reference",
                 build_impl: str = "device"):
        self.store = store
        self.assign_impl = check_backend("assign_impl", assign_impl,
                                         _ASSIGN_BACKENDS)
        self.build_impl = check_backend("build_impl", build_impl,
                                        _BUILD_BACKENDS)
        self.k = 0
        self.seed = 0
        self.archetypes: Optional[np.ndarray] = None   # (k, d)
        self.rep_global_idx = np.zeros(0, np.int64)    # rows into the store
        self.rep_uid = np.zeros(0, np.int64)           # compaction-stable
        self.rep_program: List[str] = []
        self.rep_cpi = np.zeros(0, np.float32)
        self.rep_weight = np.zeros(0, np.float64)
        self.fingerprints: Dict[str, np.ndarray] = {}
        self.est_cpi: Dict[str, float] = {}
        self.true_cpi: Dict[str, Optional[float]] = {}
        self._built_version: Optional[int] = None
        # (store, store.row_epoch, labels of row slots [0, len(labels)))
        self._row_assign_cache: Optional[
            Tuple[SignatureStore, int, np.ndarray]] = None
        # rows_for(p) size when p was last fingerprinted — detects
        # streaming adds to an already-attached program
        self._attached_nrows: Dict[str, int] = {}

    @property
    def built(self) -> bool:
        return self.archetypes is not None

    def _require_built(self):
        if not self.built:
            raise RuntimeError("KnowledgeBase.build(k) must run before "
                               "attach/estimate queries")

    # -------------------------------------------------------------- build
    def build(self, k: int = 14, seed: int = 0, *,
              mesh=None) -> "KnowledgeBase":
        """Universal clustering over every row currently in the store.

        Uses the same restart keys and ++ init as the legacy
        `universal_clustering`, and fingerprints the already-stored
        programs from k-means' own assignment — bit-compatible with the
        one-shot path. Programs ingested AFTER build are attached
        against the frozen archetypes (`attach`), never re-clustered.

        ALL restarts run in one jitted call directly over the store's
        padded `device_matrix`, optionally sharded over `mesh`'s data
        axes, with the jnp ops or the Pallas kernels inside (`build_impl`).
        """
        with tracing.span("kb.build"):
            return self._build(k, seed, mesh)

    def _build(self, k, seed, mesh) -> "KnowledgeBase":
        if self.store.n_alive == 0:
            raise RuntimeError("cannot build a KnowledgeBase over an "
                               "empty SignatureStore (no live rows)")
        x = np.asarray(self.store.signatures, np.float32)
        # tombstoned store: dead rows get zero mass, through the alive
        # bitmap folded into the jitted loop's validity mask
        tombstoned = self.store.has_tombstones
        cents, assign, _ = kmeans_device(
            self.store.device_matrix, k, seed=seed,
            use_kernel=(self.build_impl == "device_kernel"),
            n_valid=len(self.store), mesh=mesh,
            valid_mask=self.store.device_valid if tombstoned else None)
        if tombstoned:
            alive = self.store.alive_rows
            reps = alive[representatives(x[alive], cents, assign[alive])]
        else:
            reps = representatives(x, cents, assign)
        self.k = int(cents.shape[0])
        self.seed = seed
        self.archetypes = cents.astype(np.float32)
        self.rep_global_idx = np.asarray(reps, np.int64)
        self.rep_uid = np.asarray(self.store.uids[reps], np.int64)
        self.rep_program = [self.store.program_of_row[i] for i in reps]
        self.rep_cpi = self.store.cpis[reps].astype(np.float32)
        self.rep_weight = self.store.weights[reps].astype(np.float64)
        if np.isnan(self.rep_cpi).any():
            raise ValueError(
                "representative intervals lack ground-truth CPI; ingest "
                "intervals with cpis= before build()")
        self.fingerprints.clear()
        self.est_cpi.clear()
        self.true_cpi.clear()
        self._attached_nrows.clear()
        self._row_assign_cache = None   # assignments vs OLD archetypes
        for p in self.store.programs:
            rows = self.store.rows_for(p)
            if rows.size == 0:          # fully evicted: nothing to record
                continue
            self._record(p, assign[rows])
        self._built_version = self.store.version
        return self

    def _fingerprint(self, row_assign: np.ndarray, weights: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """(fingerprint (k,), normalized weights) from assignments."""
        w = np.asarray(weights, np.float64)
        wp = w / max(w.sum(), 1e-30)
        f = np.zeros(self.k)
        np.add.at(f, np.asarray(row_assign, np.int64), wp)
        return f, wp

    def _record(self, program: str, row_assign: np.ndarray) -> np.ndarray:
        """Fingerprint + CPI bookkeeping for a STORED program from its
        per-interval assignments (stamps the row count so streaming adds
        AND evictions trigger a re-attach on the next estimate)."""
        with tracing.span("kb.fingerprint") as s:
            rows = self.store.rows_for(program)
            s.add(rows=rows.size)
            if rows.size == 0:
                raise ValueError(
                    f"program {program!r} has no live rows in the store "
                    "(every interval was evicted) — cannot fingerprint")
            weights = self.store.weights[rows]
            cpis = self.store.cpis[rows]
            f, wp = self._fingerprint(row_assign, weights)
            self.fingerprints[program] = f
            self.est_cpi[program] = float(
                (f * self.rep_cpi.astype(np.float64)).sum())
            if not np.isnan(np.asarray(cpis)).any():
                self.true_cpi[program] = float(
                    (wp * np.asarray(cpis, np.float64)).sum())
            else:
                self.true_cpi[program] = None
            self._attached_nrows[program] = len(rows)
            return f

    # ------------------------------------------------------------ queries
    def assign(self, signatures: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Nearest-archetype assignment for ad-hoc signatures, padded to
        the next power of two so repeat queries reuse compiles."""
        self._require_built()
        x = np.asarray(signatures, np.float32)
        n = x.shape[0]
        cap = _capacity_for(n, 1)
        if cap > n:
            x = np.concatenate(
                [x, np.zeros((cap - n, x.shape[1]), np.float32)])
        a, d2 = assign_signatures(x, self.archetypes, self.assign_impl)
        return a[:n], d2[:n]

    def attach(self, program: str,
               signatures: Optional[np.ndarray] = None,
               weights: Optional[np.ndarray] = None) -> np.ndarray:
        """Fingerprint a new, unseen program against the frozen
        archetypes; returns the (k,) fingerprint.

        With no explicit `signatures`, the program's labels come from
        the base's per-row label cache (`_all_row_assign`): only the
        store rows added since the last query are assigned, in ONE
        batched kernel call, so attaching many late-ingested programs
        costs one device pass over their new rows, not one per program.

        With explicit `signatures` this is a PURE QUERY: nothing is
        recorded into the knowledge base (no est_cpi / avg_accuracy /
        save() footprint), so ad-hoc probes can never shadow a stored
        program. Ingest into the store to make a program estimable.
        """
        self._require_built()
        if signatures is None:
            rows = self.store.rows_for(program)
            row_assign = self._all_row_assign()[rows]
            return self._record(program, row_assign)
        a, _ = self.assign(signatures)
        f, _ = self._fingerprint(
            a, np.ones(len(a)) if weights is None else weights)
        return f

    def attach_many(self, programs: Sequence[str]
                    ) -> Dict[str, np.ndarray]:
        """Fingerprint MANY stored programs in one batched device pass.

        The rows not yet labelled — every program ingested since the
        last query — are assigned against the frozen archetypes in one
        kernel call (`_all_row_assign`); every requested program is then
        recorded from its slice of the shared per-row labels.
        Bit-identical to calling `attach(p)` per program — the
        multi-tenant ingest-then-attach path.
        """
        self._require_built()
        row_assign = self._all_row_assign()
        return {p: self._record(p, row_assign[self.store.rows_for(p)])
                for p in programs}

    def _all_row_assign(self) -> np.ndarray:
        """Nearest-archetype label of every store row slot.

        A row's signature never changes after `add` and the archetypes
        are frozen between builds, so labels are kept across calls and
        only the rows appended since the last call are assigned: read
        from the store's host buffer and padded to the next power of two
        (`assign`). Evictions leave the labels as they are (`rows_for`
        never returns a tombstoned row) and `apply_remap` carries them
        through a compaction. When the rows moved in a way the cache did
        not follow — another store, or a compaction whose remap was never
        applied (`store.row_epoch`) — every row is assigned in one pass
        over the resident `device_matrix`.
        """
        store = self.store
        n = len(store)
        cached = self._row_assign_cache
        follows = (cached is not None and cached[0] is store
                   and cached[1] == store.row_epoch)
        n_cached = len(cached[2]) if follows else 0
        if follows and n_cached == n:
            return cached[2]
        with tracing.span("kb.assign_all") as s:
            if follows:
                m = n - n_cached
                a, _ = self.assign(store.signatures[n_cached:])
                labels = np.concatenate([cached[2], a])
                s.add(rows_assigned=m, padded_rows=_capacity_for(m, 1) - m,
                      rows_cached=n_cached, full_pass=0)
            else:
                a, _ = assign_signatures(store.device_matrix,
                                         self.archetypes, self.assign_impl)
                labels = a[:n]
                s.add(rows_assigned=n, padded_rows=store.capacity - n,
                      rows_cached=0, full_pass=1)
        self._row_assign_cache = (store, store.row_epoch, labels)
        return labels

    # ----------------------------------------------------- store lifecycle
    def apply_remap(self, remap: np.ndarray) -> int:
        """Consume a `SignatureStore.compact()` old->new row remap so the
        knowledge base stays valid across compaction: representative rows
        move to their new positions, fingerprints of programs the
        compaction dropped entirely are pruned, the per-row label cache
        is gathered through the remap, and representatives whose rows
        were evicted are re-pinned to the nearest live member of their
        archetype from those labels.

        Recorded `rep_cpi`/`rep_weight` are KEPT even when re-pinning:
        they are the results of the one-time archetype simulation, which
        evicting the interval row does not undo — so `estimate()` on
        untouched programs is bit-identical across a vacuum.

        Returns the number of representatives that had to be re-pinned.
        """
        with tracing.span("kb.apply_remap") as s:
            repinned = self._apply_remap(remap)
            s.add(repinned=repinned)
            return repinned

    def _apply_remap(self, remap: np.ndarray) -> int:
        self._require_built()
        remap = np.asarray(remap, np.int64)
        old = self.rep_global_idx
        safe = np.clip(old, 0, max(remap.shape[0] - 1, 0))
        self.rep_global_idx = np.where(
            (old >= 0) & (old < remap.shape[0]), remap[safe], -1)
        self._carry_row_assign(remap)
        for p in list(self.fingerprints):
            if p not in self.store:        # compaction dropped the program
                del self.fingerprints[p]
                self.est_cpi.pop(p, None)
                self.true_cpi.pop(p, None)
                self._attached_nrows.pop(p, None)
        return self._repin_dead_reps()

    def _carry_row_assign(self, remap: np.ndarray) -> None:
        """Move the per-row labels through the remap of the store's
        latest compaction (order-preserving, so the surviving labels keep
        their order: new[remap[keep]] = old[keep]). A cache from before
        an earlier compaction, or of another store, is dropped."""
        cached, self._row_assign_cache = self._row_assign_cache, None
        if cached is None or cached[0] is not self.store:
            return
        store, epoch, labels = cached
        if epoch == store.row_epoch:              # the compaction was a no-op
            self._row_assign_cache = cached
        elif epoch == store.row_epoch - 1 and remap.shape[0] >= len(labels):
            kept = labels[remap[:len(labels)] >= 0]
            self._row_assign_cache = (store, store.row_epoch, kept)

    def _repin_dead_reps(self) -> int:
        """Re-pin every representative whose store row is gone (idx -1)
        to the nearest LIVE member of its archetype: the per-row labels
        (`_all_row_assign`) + one segment-reduce (`representatives`)
        shared by all dead reps."""
        dead = np.flatnonzero(self.rep_global_idx < 0)
        if dead.size == 0:
            return 0
        alive = self.store.alive_rows
        if alive.size == 0:
            # store emptied: nothing to pin to. Leave the indices at -1
            # (estimate() paths raise cleanly); the next build() over a
            # re-populated store replaces the representatives wholesale.
            return 0
        x = np.asarray(self.store.signatures, np.float32)
        row_assign = self._all_row_assign()
        reps = alive[representatives(x[alive], self.archetypes,
                                     row_assign[alive])]
        self.rep_global_idx[dead] = reps[dead]
        self.rep_uid[dead] = self.store.uids[reps[dead]]
        for j in dead:
            self.rep_program[j] = self.store.program_of_row[
                self.rep_global_idx[j]]
        return int(dead.size)

    def estimate(self, program: str) -> CPIEstimate:
        """Typed CPI estimate; (re-)attaches the program on demand if it
        was ingested — or gained new rows — after its last fingerprint."""
        self._require_built()
        if (program not in self.fingerprints or
                (program in self.store and
                 self._attached_nrows.get(program)
                 != len(self.store.rows_for(program)))):
            self.attach(program)
        f = self.fingerprints[program]
        est = self.est_cpi[program]
        true = self.true_cpi[program]
        sim_w = float(self.rep_weight.sum())
        total_w = self.store.total_weight
        return CPIEstimate(
            program=program, est_cpi=est, true_cpi=true,
            accuracy=None if true is None else cpi_accuracy(est, true),
            speedup=speedup(total_w, sim_w),
            fingerprint=f, k=self.k,
            simulated_weight=sim_w, total_weight=total_w)

    @property
    def avg_accuracy(self) -> float:
        accs = [cpi_accuracy(self.est_cpi[p], t)
                for p, t in self.true_cpi.items() if t is not None]
        return float(np.mean(accs)) if accs else float("nan")

    # -------------------------------------------------------- persistence
    def save(self, directory: str) -> str:
        self._require_built()
        tree = {
            "archetypes": self.archetypes,
            "rep_cpi": self.rep_cpi,
            "rep_weight": self.rep_weight,
            "rep_global_idx": self.rep_global_idx,
            "rep_uid": self.rep_uid,
        }
        meta = {
            "k": self.k, "seed": self.seed,
            "assign_impl": self.assign_impl,
            "build_impl": self.build_impl,
            "rep_program": self.rep_program,
            "built_version": self._built_version,
            "fingerprints": {p: np.asarray(f).tolist()
                             for p, f in self.fingerprints.items()},
            "est_cpi": self.est_cpi,
            "true_cpi": self.true_cpi,
        }
        return save_checkpoint(directory, self._built_version or 0, tree,
                               meta=meta)

    @classmethod
    def load(cls, directory: str, store: SignatureStore) -> "KnowledgeBase":
        path = latest_checkpoint(directory)
        if path is None:
            raise FileNotFoundError(f"no KB checkpoint under {directory}")
        import msgpack
        with open(os.path.join(path, "manifest.msgpack"), "rb") as f:
            manifest = msgpack.unpackb(f.read())
        keys = ["archetypes", "rep_cpi", "rep_weight", "rep_global_idx"]
        if "rep_uid" in manifest["shapes"]:   # pre-lifecycle checkpoints
            keys.append("rep_uid")
        template = {
            k: np.zeros(manifest["shapes"][k],
                        np.dtype(manifest["dtypes"][k]))
            for k in keys
        }
        tree, _, meta = restore_checkpoint(path, template)
        impls = {o: _RETIRED[o].get(v, v) for o, v in (
            ("assign_impl", meta["assign_impl"]),
            ("build_impl", meta.get("build_impl", "device")))}
        kb = cls(store, **impls)
        kb.k = int(meta["k"])
        kb.seed = int(meta["seed"])
        kb.archetypes = np.asarray(tree["archetypes"], np.float32)
        kb.rep_cpi = np.asarray(tree["rep_cpi"], np.float32)
        kb.rep_weight = np.asarray(tree["rep_weight"], np.float64)
        kb.rep_global_idx = np.asarray(tree["rep_global_idx"], np.int64)
        kb.rep_program = list(meta["rep_program"])
        if "rep_uid" in tree:
            # uids are the compaction-stable handle: re-resolve each
            # representative's CURRENT row position; rows that were
            # evicted/compacted away since save re-pin below
            kb.rep_uid = np.asarray(tree["rep_uid"], np.int64)
            kb.rep_global_idx = store.rows_of_uids(kb.rep_uid)
        else:
            ok = ((kb.rep_global_idx >= 0)
                  & (kb.rep_global_idx < len(store)))
            kb.rep_uid = np.where(
                ok, store.uids[np.clip(kb.rep_global_idx, 0,
                                       max(len(store) - 1, 0))], -1)
        if (kb.rep_global_idx < 0).any():
            kb._repin_dead_reps()
        kb._built_version = meta["built_version"]
        kb.fingerprints = {p: np.asarray(f, np.float64)
                           for p, f in meta["fingerprints"].items()}
        kb.est_cpi = {p: float(v) for p, v in meta["est_cpi"].items()}
        kb.true_cpi = {p: (None if v is None else float(v))
                       for p, v in meta["true_cpi"].items()}
        # loaded fingerprints are current w.r.t. the co-saved store; a
        # store that grew since save re-attaches on the next estimate
        kb._attached_nrows = {p: len(store.rows_for(p))
                              for p in kb.fingerprints if p in store}
        return kb

    # ----------------------------------------------------------- legacy
    def as_cross_program_result(self):
        """`CrossProgramResult` view for the deprecated one-shot API."""
        from repro.core.crossprog import CrossProgramResult
        self._require_built()
        return CrossProgramResult(
            k=self.k,
            rep_global_idx=self.rep_global_idx,
            rep_program=list(self.rep_program),
            rep_cpi=self.rep_cpi,
            fingerprints={p: np.asarray(f)
                          for p, f in self.fingerprints.items()},
            est_cpi=dict(self.est_cpi),
            true_cpi={p: v for p, v in self.true_cpi.items()
                      if v is not None})
