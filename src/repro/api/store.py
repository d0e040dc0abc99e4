"""`SignatureStore` — the persistent knowledge-base substrate.

A store of interval signatures plus the per-interval metadata the
cross-program workflow needs (program label, instruction weight,
ground-truth CPI where known). Two design rules, both borrowed from the
inference path's `BBEIndex`:

  PAD-AND-GROW. Host arrays are allocated at power-of-two capacity and
  doubled on overflow, and `device_matrix` exposes the WHOLE capacity
  buffer (invalid rows zero) as one device array. Batched queries over
  the store therefore see O(log N) distinct shapes over the lifetime of
  the store — every jitted consumer (nearest-centroid assignment, any
  future ANN probe) compiles once per capacity level, not once per
  `add`.

  APPEND-ONLY IDS. Row positions are stable between compactions and
  every row additionally carries a monotonically increasing `uid` that
  survives `compact()` — the handle persisted artifacts (KnowledgeBase
  representatives) use to stay valid across the store's whole lifetime.
  `version` increments per mutation (`add`/`evict`/`compact`), so
  consumers can cache derived state keyed on it; `row_epoch` increments
  only when a compaction drops or moves rows, so per-row state survives
  appends and evictions and is carried through the remap of the latest
  compaction.

LIFECYCLE. Long-running serving ingests forever, so the store is no
longer grow-only: `evict(rows)` tombstones rows (a host bitmap folded
into the `device_valid` mask, so jitted queries and builds skip dead
rows with zero extra host round-trips) and `compact()` closes the gaps
in place, moving only the survivors after the first tombstone, shrinks
capacity back to the smallest power of two, and returns an old->new row
remap.
Per-row `inserted_at`/`last_used` stamps against a logical `clock`
drive the TTL/LRU policies in `repro.api.lifecycle`.

Persistence reuses the training checkpoint infra (atomic rename,
manifest + npz), so a store survives crashes mid-save and a
save -> load round-trip is bit-identical — including tombstones.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.train.checkpoint import (
    latest_checkpoint, restore_checkpoint, save_checkpoint,
)
from repro.utils import tracing

_MIN_CAPACITY = 64

# host columns, each with the value an empty slot holds
_COLUMNS = (("_sigs", 0.0), ("_weights", 0.0), ("_cpis", np.nan),
            ("_alive", False), ("_uids", 0), ("_inserted_at", 0),
            ("_last_used", 0))


def _capacity_for(n: int, minimum: int = _MIN_CAPACITY) -> int:
    cap = max(minimum, 1)
    while cap < n:
        cap *= 2
    return cap


class SignatureStore:
    """Device-resident store of interval signatures with row lifecycle.

    Rows carry (signature (d,), weight, cpi, program). `weight` is the
    interval's instruction count (uniform 1.0 when unknown) — it drives
    both fingerprint normalization and the weight-aware speedup metric.
    It is float64, exact for every integer count below 2**53: a region
    of 8 threads at 10 M instructions each is past float32's 2**24.
    `cpi` is the ground-truth per-interval CPI, NaN when unknown: the
    knowledge base only ever consults it at the k representative
    intervals (the paper's "simulate only the archetypes") and for
    accuracy evaluation.

    `len(store)` is the number of row SLOTS (the append-only indexing
    space, tombstoned rows included); `n_alive` counts live rows.
    """

    def __init__(self, sig_dim: int, min_capacity: int = _MIN_CAPACITY):
        if sig_dim <= 0:
            raise ValueError(f"sig_dim must be positive, got {sig_dim}")
        self.sig_dim = int(sig_dim)
        self.min_capacity = int(min_capacity)
        self.version = 0
        # bumped by each compaction that drops or moves rows: per-row
        # state labelled at one epoch is valid at the next only through
        # the remap that compaction returned
        self.row_epoch = 0
        self._n = 0
        self._n_dead = 0
        self._clock = 0          # logical time: one tick per add/touch
        self._next_uid = 0
        cap = _capacity_for(0, self.min_capacity)
        self._sigs = np.zeros((cap, self.sig_dim), np.float32)
        self._weights = np.zeros((cap,), np.float64)
        self._cpis = np.full((cap,), np.nan, np.float32)
        self._alive = np.zeros((cap,), bool)
        self._uids = np.zeros((cap,), np.int64)
        self._inserted_at = np.zeros((cap,), np.int64)
        self._last_used = np.zeros((cap,), np.int64)
        self._program_of_row: List[str] = []
        self._program_rows: Dict[str, List[int]] = {}
        self._device: Optional[jnp.ndarray] = None
        self._device_valid: Optional[jnp.ndarray] = None

    # ------------------------------------------------------------- shape
    def __len__(self) -> int:
        return self._n

    @property
    def n_alive(self) -> int:
        """Live (non-tombstoned) row count."""
        return self._n - self._n_dead

    @property
    def has_tombstones(self) -> bool:
        return self._n_dead > 0

    @property
    def capacity(self) -> int:
        return self._sigs.shape[0]

    @property
    def clock(self) -> int:
        """Logical time (ticks once per add/touch) — the age reference
        for TTL/LRU eviction policies."""
        return self._clock

    @property
    def programs(self) -> List[str]:
        """Program names in first-insertion order (a fully-evicted
        program stays registered until `compact()` drops its rows; its
        name remains, with zero live rows)."""
        return list(self._program_rows)

    def __contains__(self, program: str) -> bool:
        return program in self._program_rows

    # ------------------------------------------------------------ ingest
    def _grow_to(self, n: int):
        cap = _capacity_for(n, self.min_capacity)
        if cap == self.capacity:
            return
        for name, fill in _COLUMNS:
            src = getattr(self, name)
            dst = np.full((cap,) + src.shape[1:], fill, src.dtype)
            dst[:self._n] = src[:self._n]
            setattr(self, name, dst)
        self._device = None
        self._device_valid = None

    def _validate(self, signatures, weights, cpis):
        sigs = np.asarray(signatures, np.float32)
        if sigs.ndim != 2 or sigs.shape[1] != self.sig_dim:
            raise ValueError(
                f"signatures must be (N, {self.sig_dim}), got {sigs.shape}")
        b = sigs.shape[0]
        w = (np.ones(b, np.float64) if weights is None
             else np.asarray(weights, np.float64))
        c = (np.full(b, np.nan, np.float32) if cpis is None
             else np.asarray(cpis, np.float32))
        if w.shape != (b,) or c.shape != (b,):
            raise ValueError("weights/cpis must be 1-D of len(signatures)")
        return sigs, w, c

    def _append(self, program, sigs, w, c) -> np.ndarray:
        """Write validated rows into already-grown buffers (no version
        bump — callers batch that)."""
        b = sigs.shape[0]
        rows = np.arange(self._n, self._n + b)
        self._sigs[rows] = sigs
        self._weights[rows] = w
        self._cpis[rows] = c
        self._alive[rows] = True
        self._uids[rows] = np.arange(self._next_uid, self._next_uid + b)
        self._inserted_at[rows] = self._clock
        self._last_used[rows] = self._clock
        self._next_uid += b
        self._program_of_row.extend([program] * b)
        self._program_rows.setdefault(program, []).extend(rows.tolist())
        self._n += b
        return rows

    def add(self, program: str, signatures: np.ndarray,
            weights: Optional[Sequence[float]] = None,
            cpis: Optional[Sequence[float]] = None) -> np.ndarray:
        """Append one program's interval rows; returns their row indices.

        A program may be added in several calls (streaming ingest); rows
        accumulate. Signatures are stored as float32 — the dtype every
        query path already uses.
        """
        with tracing.span("store.add") as s:
            sigs, w, c = self._validate(signatures, weights, cpis)
            self._grow_to(self._n + sigs.shape[0])
            rows = self._append(program, sigs, w, c)
            self.version += 1
            self._clock += 1
            self._device = None
            self._device_valid = None
            s.add(rows=rows.size)
            return rows

    def add_many(self, items: Sequence[Tuple]) -> Dict[str, np.ndarray]:
        """Batched ingest: `items` is a sequence of (program, signatures[,
        weights[, cpis]]) tuples. All inputs are validated up front,
        capacity grows ONCE for the total row count (one buffer copy
        instead of one per doubling), and `version` bumps once — so one
        downstream assignment pass over the new rows covers the batch.
        Returns {program: new row indices} (repeated programs accumulate).
        """
        with tracing.span("store.add") as s:
            validated = []
            for item in items:
                program, sigs = item[0], item[1]
                weights = item[2] if len(item) > 2 else None
                cpis = item[3] if len(item) > 3 else None
                validated.append((program,
                                  *self._validate(sigs, weights, cpis)))
            if not validated:
                return {}
            # zero-row programs still register (matching `add`), so a
            # later rows_for/attach sees them instead of raising KeyError
            total = sum(v[1].shape[0] for v in validated)
            self._grow_to(self._n + total)
            out: Dict[str, np.ndarray] = {}
            for program, sigs, w, c in validated:
                rows = self._append(program, sigs, w, c)
                out[program] = (rows if program not in out
                                else np.concatenate([out[program], rows]))
            self.version += 1
            self._clock += 1
            self._device = None
            self._device_valid = None
            s.add(rows=total)
            return out

    # --------------------------------------------------------- lifecycle
    def touch(self, rows: np.ndarray) -> None:
        """Stamp `rows` as used-now (LRU recency). Pure metadata: no
        version bump, so derived-state caches stay warm across reads."""
        r = np.asarray(rows, np.int64)
        if r.size == 0:
            return
        if r.size and (r.min() < 0 or r.max() >= self._n):
            raise IndexError(f"touch rows out of range [0, {self._n})")
        self._last_used[r] = self._clock
        self._clock += 1

    def evict(self, rows: np.ndarray) -> int:
        """Tombstone `rows`: they keep their slot (stable row ids for
        every live consumer) but leave `device_valid`, `rows_for`,
        `total_weight` and all alive-masked queries immediately — the
        bitmap is folded into the device mask jitted builds consume, so
        eviction costs zero device work. Already-dead rows are ignored.
        Returns the number of rows newly evicted; bumps `version` when
        that is non-zero."""
        r = np.asarray(rows, np.int64)
        if r.size == 0:
            return 0
        if r.min() < 0 or r.max() >= self._n:
            raise IndexError(f"evict rows out of range [0, {self._n})")
        newly = r[self._alive[r]]
        newly = np.unique(newly)
        if newly.size == 0:
            return 0
        self._alive[newly] = False
        self._n_dead += int(newly.size)
        self.version += 1
        self._device_valid = None
        return int(newly.size)

    def evict_program(self, program: str) -> int:
        """Tombstone every live row of `program` (the program stays
        registered until the next `compact()`)."""
        return self.evict(self.rows_for(program))

    def compact(self) -> np.ndarray:
        """Drop tombstoned rows and shrink capacity back to the smallest
        power of two. Order-preserving, so a compacted store is
        bit-identical to a fresh store holding only the live rows, and
        the rows before the first tombstone keep their slots: only the
        survivors after it are written, into the same host buffers while
        the capacity holds (into new ones when it shrinks), and the
        vacated slots are reset to what a fresh store holds there. A
        resident device matrix is rebuilt by ONE fixed-shape gather.
        Fully-evicted programs are dropped from the registry.

        Views taken before (`signatures`, `weights`, `cpis`,
        `alive_mask`, `uids`, `last_used`, `inserted_at`) are not valid
        after: the compaction writes into the buffers they show.

        Returns the old->new row remap: (old_len,) int64, -1 for rows
        that no longer exist. Row `uid`s survive compaction — persisted
        consumers (saved KnowledgeBases) re-resolve through them.
        """
        with tracing.span("store.compact") as s:
            remap, moved = self._compact()
            s.add(rows_before=remap.size, rows_after=self._n,
                  rows_moved=moved)
            return remap

    def _compact(self) -> Tuple[np.ndarray, int]:
        """(remap, number of survivors written to another slot)."""
        old_n = self._n
        alive = self._alive[:old_n]
        keep = np.flatnonzero(alive).astype(np.int64)
        m = int(keep.size)
        new_cap = _capacity_for(m, self.min_capacity)
        remap = np.full(old_n, -1, np.int64)
        remap[keep] = np.arange(m)
        if not self.has_tombstones and new_cap == self.capacity:
            return remap, 0                   # nothing to do; no bump

        if self._device is not None:
            # device-side compaction: one gather over the already-
            # resident padded matrix -> the new padded matrix, no
            # re-upload and no per-row host loop
            idx = np.zeros(new_cap, np.int32)
            idx[:m] = keep
            mask = (np.arange(new_cap) < m).astype(np.float32)[:, None]
            self._device = (jnp.take(self._device, jnp.asarray(idx), axis=0)
                            * jnp.asarray(mask))
            tracing.add(h2d_bytes=idx.nbytes + mask.nbytes)
            # the gather reads the old matrix, which may share memory with
            # `_sigs` (a CPU backend uploads without a copy): let it finish
            # before the host columns are rewritten in place
            self._device.block_until_ready()

        # slots [0, f) hold no tombstone, so their rows stay put; only
        # the survivors after the first tombstone move, down to [f, m)
        f = int(np.argmin(alive)) if m < old_n else m
        tail = keep[f:]
        in_place = new_cap == self.capacity
        for name, fill in _COLUMNS:
            src = getattr(self, name)
            if in_place:
                dst = src
            else:
                dst = np.full((new_cap,) + src.shape[1:], fill, src.dtype)
                dst[:f] = src[:f]
            dst[f:m] = src[tail]
            dst[m:old_n] = fill
            setattr(self, name, dst)

        names = self._program_of_row
        names[f:] = [names[i] for i in tail.tolist()]
        new_rows: Dict[str, List[int]] = {}
        for p, old_rows in self._program_rows.items():
            if old_rows and old_rows[-1] < f:  # ascending, all before f
                new_rows[p] = old_rows
                continue
            nr = remap[np.asarray(old_rows, np.int64)]
            nr = nr[nr >= 0]
            if nr.size:
                new_rows[p] = nr.tolist()
        self._program_rows = new_rows
        self._n = m
        self._n_dead = 0
        self.version += 1
        # also when no row moved: slots [m, old_n) are free again, and
        # per-row state kept for them would be read as the next rows'
        self.row_epoch += 1
        self._device_valid = None
        return remap, m - f

    # ------------------------------------------------------------- views
    def rows_for(self, program: str) -> np.ndarray:
        """LIVE rows of `program` (tombstoned rows are invisible; a
        fully-evicted but not-yet-compacted program yields an empty
        array rather than KeyError)."""
        if program not in self._program_rows:
            raise KeyError(f"program {program!r} not in store "
                           f"(have {self.programs})")
        r = np.asarray(self._program_rows[program], np.int64)
        return r[self._alive[r]] if self._n_dead else r

    @property
    def signatures(self) -> np.ndarray:
        """(N, d) row-slot view, TOMBSTONED ROWS INCLUDED (read-only);
        gate with `alive_mask` when the store has tombstones."""
        v = self._sigs[:self._n]
        v.flags.writeable = False
        return v

    @property
    def weights(self) -> np.ndarray:
        v = self._weights[:self._n]
        v.flags.writeable = False
        return v

    @property
    def cpis(self) -> np.ndarray:
        v = self._cpis[:self._n]
        v.flags.writeable = False
        return v

    @property
    def alive_mask(self) -> np.ndarray:
        """(N,) bool: True where the row-slot is live."""
        v = self._alive[:self._n]
        v.flags.writeable = False
        return v

    @property
    def alive_rows(self) -> np.ndarray:
        """Positions of the live rows, ascending."""
        return np.flatnonzero(self._alive[:self._n]).astype(np.int64)

    @property
    def uids(self) -> np.ndarray:
        """(N,) stable per-row uids (strictly increasing in row order;
        survive `compact`)."""
        v = self._uids[:self._n]
        v.flags.writeable = False
        return v

    @property
    def last_used(self) -> np.ndarray:
        v = self._last_used[:self._n]
        v.flags.writeable = False
        return v

    @property
    def inserted_at(self) -> np.ndarray:
        v = self._inserted_at[:self._n]
        v.flags.writeable = False
        return v

    def rows_of_uids(self, uids: np.ndarray) -> np.ndarray:
        """Current row position of each uid; -1 where the uid's row was
        evicted (or never existed). Uids are strictly increasing in row
        order, so this is one searchsorted — no per-uid loop."""
        u = np.asarray(uids, np.int64)
        if self._n == 0 or u.size == 0:
            return np.full(u.shape, -1, np.int64)
        stored = self._uids[:self._n]
        pos = np.searchsorted(stored, u)
        clamped = np.minimum(pos, self._n - 1)
        found = ((pos < self._n) & (stored[clamped] == u)
                 & self._alive[clamped])
        return np.where(found, clamped, -1)

    @property
    def program_of_row(self) -> List[str]:
        return list(self._program_of_row)

    @property
    def total_weight(self) -> float:
        """Total instruction weight of the LIVE rows."""
        w = self._weights[:self._n].astype(np.float64)
        if self._n_dead:
            w = w[self._alive[:self._n]]
        return float(w.sum())

    @property
    def device_matrix(self) -> jnp.ndarray:
        """(capacity, d) device array; rows >= len(self) are zero.
        Tombstoned rows keep their (stale) values — consumers mask them
        via `device_valid`.

        Uploaded lazily and cached until the next `add`; the static
        capacity shape is what keeps downstream jitted queries at one
        compile per capacity level.
        """
        if self._device is None:
            with tracing.span("store.upload", h2d_bytes=self._sigs.nbytes):
                self._device = jnp.asarray(self._sigs)
        return self._device

    @property
    def device_valid(self) -> jnp.ndarray:
        """(capacity,) float32 0/1 mask: 1 at live rows. The tombstone
        bitmap folded into the `n_valid`-style device masks, so jitted
        k-means builds / assignment queries skip dead rows without any
        extra host round-trip."""
        if self._device_valid is None:
            mask = np.zeros(self.capacity, np.float32)
            mask[:self._n] = self._alive[:self._n]
            self._device_valid = jnp.asarray(mask)
            tracing.add(h2d_bytes=mask.nbytes)
        return self._device_valid

    # ------------------------------------------------------- persistence
    def save(self, directory: str) -> str:
        """Checkpoint the store (atomic; bit-identical on reload —
        tombstones, uids and LRU/TTL stamps included)."""
        tree = {
            "signatures": self._sigs[:self._n].copy(),
            "weights": self._weights[:self._n].copy(),
            "cpis": self._cpis[:self._n].copy(),
            "alive": self._alive[:self._n].copy(),
            "uids": self._uids[:self._n].copy(),
            "inserted_at": self._inserted_at[:self._n].copy(),
            "last_used": self._last_used[:self._n].copy(),
        }
        meta = {
            "sig_dim": self.sig_dim,
            "min_capacity": self.min_capacity,
            "program_of_row": list(self._program_of_row),
            "clock": self._clock,
            "next_uid": self._next_uid,
        }
        return save_checkpoint(directory, self.version, tree, meta=meta)

    @classmethod
    def load(cls, directory: str) -> "SignatureStore":
        path = latest_checkpoint(directory)
        if path is None:
            raise FileNotFoundError(f"no store checkpoint under {directory}")
        import msgpack  # same dep as the checkpoint writer
        import os
        with open(os.path.join(path, "manifest.msgpack"), "rb") as f:
            manifest = msgpack.unpackb(f.read())
        keys = ["signatures", "weights", "cpis"]
        # lifecycle arrays are absent from pre-lifecycle checkpoints;
        # default to all-alive with fresh stamps
        lifecycle = [k for k in ("alive", "uids", "inserted_at",
                                 "last_used") if k in manifest["shapes"]]
        template = {
            k: np.zeros(manifest["shapes"][k],
                        np.dtype(manifest["dtypes"][k]))
            for k in keys + lifecycle
        }
        tree, version, meta = restore_checkpoint(path, template)
        sigs = np.asarray(tree["signatures"], np.float32)
        store = cls(int(meta["sig_dim"]),
                    min_capacity=int(meta["min_capacity"]))
        n = sigs.shape[0]
        store._grow_to(n)
        store._sigs[:n] = sigs
        store._weights[:n] = np.asarray(tree["weights"], np.float64)
        store._cpis[:n] = np.asarray(tree["cpis"], np.float32)
        store._alive[:n] = (np.asarray(tree["alive"], bool)
                            if "alive" in tree else True)
        store._uids[:n] = (np.asarray(tree["uids"], np.int64)
                           if "uids" in tree else np.arange(n))
        clock = int(meta.get("clock", version))
        # pre-lifecycle checkpoints carry no stamps: default to NOW
        # (age 0), not 0 (maximal age) — otherwise the first TTL vacuum
        # after an upgrade would evict the whole store
        store._inserted_at[:n] = (
            np.asarray(tree["inserted_at"], np.int64)
            if "inserted_at" in tree else clock)
        store._last_used[:n] = (
            np.asarray(tree["last_used"], np.int64)
            if "last_used" in tree else clock)
        store._program_of_row = list(meta["program_of_row"])
        for i, p in enumerate(store._program_of_row):
            store._program_rows.setdefault(p, []).append(i)
        store._n = n
        store._n_dead = int(n - store._alive[:n].sum())
        store._clock = clock
        store._next_uid = int(meta.get(
            "next_uid", (store._uids[:n].max() + 1) if n else 0))
        store.version = int(version)
        return store

    # ------------------------------------------------------------- misc
    def grouped_rows(self) -> Dict[str, np.ndarray]:
        return {p: self.rows_for(p) for p in self.programs}

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"SignatureStore(n={self._n}, alive={self.n_alive}, "
                f"capacity={self.capacity}, sig_dim={self.sig_dim}, "
                f"programs={len(self.programs)})")
