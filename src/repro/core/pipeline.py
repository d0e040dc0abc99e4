"""End-to-end SemanticBBV pipeline (Fig. 2): glues the tokenizer, the
Stage-1 encoder, and the Stage-2 aggregator. (The public service facade
composing this with the signature store + knowledge base is
`repro.api.SemanticBBVService`.)

Typical flow (see examples/):
    pipe = SemanticBBVPipeline.create(rng)
    bbe_table = pipe.encode_blocks(unique_blocks)       # Stage 1, batched
    sigs = pipe.interval_signatures(intervals, bbe_table)
    cpi = pipe.predict_interval_cpi(intervals, bbe_table)

Host-side batching is fully vectorized: `encode_blocks` memoizes BBEs in
an LRU cache keyed by block content, and partial chunks are padded to the
full batch, never retraced, so Stage 1 compiles one shape. Each Stage-2
row runs at the width of its own set, rounded up a short ladder of
powers of two capped at `max_set` (`set_width`); rows are batched by
width, so a row's signature never depends on the rows it is batched
with. A width not seen before compiles once, mid-request, and every
later batch of that width reuses it. Interval sets are assembled
through `BBEIndex` — the contiguous BBE matrix is uploaded to the device
once per call and each batch ships only (row_ids, freqs, mask); the
(B, N, bbe_dim) gather happens on-device inside the jitted signature
step. At 100k+ intervals the pipeline is bound by device compute, not
Python.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bbe as bbe_mod
from repro.core import signature as sig_mod
from repro.core.tokenizer import MultiDimTokenizer, default_tokenizer
from repro.data.isa import BasicBlock
from repro.kernels import check_backend
from repro.models.set_transformer import ATTENTION_BACKENDS
from repro.utils import tracing

_BBE_CACHE_SIZE = 1 << 16


class BBEIndex:
    """bid -> row lookup over one contiguous BBE matrix.

    Built once per signature call from a {bid: vector} table; afterwards
    every interval-set assembly is integer work plus one gather. Row V
    of `ext` is an all-zero sentinel: padded set slots gather it, so a
    single `take` materializes a whole padded batch."""

    def __init__(self, bbe_table: Dict[int, np.ndarray]):
        n = len(bbe_table)
        bids = np.fromiter(bbe_table.keys(), np.int64, count=n)
        order = np.argsort(bids, kind="stable")
        self.sorted_bids = bids[order]
        self.num_rows = n
        if n:
            self.matrix = np.asarray(list(bbe_table.values()),
                                     np.float32)[order]
        else:
            self.matrix = np.zeros((0, 0), np.float32)
        self._ext: Optional[np.ndarray] = None
        # dense bid->row table when ids are compact (they are for the
        # synthetic substrate); sparse ids fall back to searchsorted
        self._lut: Optional[np.ndarray] = None
        if n and 0 <= int(self.sorted_bids[0]) and \
                int(self.sorted_bids[-1]) < max(4 * n, 1 << 20):
            self._lut = np.full(int(self.sorted_bids[-1]) + 1, -1, np.int64)
            self._lut[self.sorted_bids] = np.arange(n)

    @property
    def sentinel(self) -> int:
        return self.num_rows

    @property
    def ext(self) -> np.ndarray:
        """(V+1, D) matrix with the zero sentinel row appended."""
        if self._ext is None:
            self._ext = np.concatenate(
                [self.matrix, np.zeros((1, self.matrix.shape[1]),
                                       np.float32)])
        return self._ext

    def rows(self, bids: np.ndarray) -> np.ndarray:
        """Row indices for `bids`; KeyError on unknown ids (matching the
        dict-lookup behaviour of the old per-interval loop)."""
        bids = np.asarray(bids, np.int64)
        if self.num_rows == 0:
            if bids.size:
                raise KeyError(f"block ids not in BBE table: "
                               f"{np.unique(bids)[:5].tolist()}")
            return np.zeros(0, np.int64)
        if self._lut is not None:
            clipped = np.clip(bids, 0, self._lut.size - 1)
            idx = self._lut[clipped]
            bad = (idx < 0) | (clipped != bids)
        else:
            idx = np.searchsorted(self.sorted_bids, bids)
            bad = idx >= self.num_rows
            idx = np.where(bad, 0, idx)
            bad |= self.sorted_bids[idx] != bids
        if bad.any():
            raise KeyError(f"block ids not in BBE table: "
                           f"{np.unique(bids[bad])[:5].tolist()}")
        return idx


def _topk_order(seg: np.ndarray, cnts: np.ndarray) -> np.ndarray:
    """Stable order: segment ascending, count descending — identical to
    per-segment `sorted(..., key=lambda kv: -kv[1])`. Integral counts use
    one radix-sortable composite int64 key (~7x faster than lexsort)."""
    ci = cnts.astype(np.int64)
    if (seg.size == 0 or
            ((ci == cnts).all() and int(np.abs(ci).max(initial=0)) < 1 << 40
             and int(seg[-1]) < 1 << 20)):
        return np.argsort(seg * (1 << 41) - ci, kind="stable")
    return np.lexsort((-cnts, seg))


def _set_entries(items):
    """Every item's candidate set entries (`set_entries()`) as flat
    arrays, item by item -> (lens (B,), bids, counts, threads, runtime
    entries left out)."""
    if not items:
        return np.zeros(0, np.int64), np.zeros(0, np.int64), \
            np.zeros(0, np.float64), 0, 0
    bids, cnts, excluded = zip(*(it.set_entries() for it in items))
    lens = np.fromiter(map(len, bids), np.int64, count=len(bids))
    return (lens, np.concatenate(bids),
            np.concatenate(cnts, dtype=np.float64),
            sum(it.num_threads for it in items), sum(excluded))


def batch_set_ids(intervals, index: BBEIndex, max_set: int):
    """Vectorized set assembly WITHOUT the BBE payload, for `Interval`s
    and multi-threaded `Region`s alike (an interval is the one-thread
    case): one stable sort selects each item's top-`max_set` entries by
    count (same order and tie-breaking as the per-interval loop), one
    lookup maps bids to matrix rows. Shared by inference batching
    (pipeline) and Stage-2 training batches (repro.train.stage2).

    Gives the innermost open span the batch's `threads`, the `entries`
    kept, the runtime entries `excluded` and the entries `truncated`
    past `max_set`.

    Returns (row_ids (B,N) int32 — `index.sentinel` in empty slots,
    freqs (B,N) f32, mask (B,N) bool)."""
    B = len(intervals)
    N = max_set
    row_ids = np.full((B, N), index.sentinel, np.int32)
    freqs = np.zeros((B, N), np.float32)
    mask = np.zeros((B, N), bool)
    lens, bids, cnts, threads, excluded = _set_entries(intervals)
    total = kept = bids.size
    if total:
        seg = np.repeat(np.arange(B), lens)
        order = _topk_order(seg, cnts)
        starts = np.concatenate(([0], np.cumsum(lens)[:-1]))
        pos = np.arange(total) - np.repeat(starts, lens)
        keep = pos < N
        rows = index.rows(bids[order][keep])
        b_idx, n_idx = seg[keep], pos[keep]   # seg[order] == seg (grouped)
        row_ids[b_idx, n_idx] = rows
        freqs[b_idx, n_idx] = cnts[order][keep]
        mask[b_idx, n_idx] = True
        kept = rows.size
    tracing.add(threads=threads, entries=kept, excluded=excluded,
                truncated=total - kept)
    return row_ids, freqs, mask


def set_width(size: int, max_set: int) -> int:
    """Slots a Stage-2 row with `size` set entries runs at: the smallest
    power of two that holds them, at least 32, capped at `max_set`, which
    is the last step even when it is not a power of two. Below 32 slots
    the step's work is too small to be worth a shape of its own (the
    set-attention kernel pads keys to 128 lanes anyway), so the ladder
    stays at 5 widths for `max_set` 512. Slots past a set's last entry
    are masked and add exactly zero, so any width that holds the set
    gives the same signature, to the order of summation."""
    return min(max_set, max(32, 1 << (int(size) - 1).bit_length()))


def _signature_from_rows(params, cfg, matrix, row_ids, freqs, mask,
                         impl="xla"):
    """Device-side set assembly: gather BBE rows inside jit so the host
    never materializes (B, N, bbe_dim) batches."""
    bbes = jnp.take(matrix, row_ids, axis=0)
    return sig_mod.signature_apply(params, cfg, bbes, freqs, mask, impl)


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Typed construction config for `SemanticBBVPipeline` (the facade
    `repro.api.ServiceConfig` embeds one) — replaces the positional
    rng/bbe_cfg/sig_cfg/impl kwargs sprawl. None configs resolve to the
    module defaults, with the signature input width tied to the BBE
    output width."""
    seed: int = 0
    bbe: Optional[bbe_mod.BBEConfig] = None
    sig: Optional[sig_mod.SignatureConfig] = None
    impl: str = "xla"   # set-attention backend: xla | pallas (repro/kernels)

    def resolve(self) -> Tuple[bbe_mod.BBEConfig, sig_mod.SignatureConfig]:
        bbe_cfg = self.bbe or bbe_mod.BBEConfig()
        sig_cfg = self.sig or sig_mod.SignatureConfig(
            bbe_dim=bbe_cfg.bbe_dim)
        if sig_cfg.bbe_dim != bbe_cfg.bbe_dim:
            raise ValueError(
                f"sig.bbe_dim ({sig_cfg.bbe_dim}) must match bbe.bbe_dim "
                f"({bbe_cfg.bbe_dim})")
        return bbe_cfg, sig_cfg


@dataclasses.dataclass
class SemanticBBVPipeline:
    tok: MultiDimTokenizer
    bbe_cfg: bbe_mod.BBEConfig
    sig_cfg: sig_mod.SignatureConfig
    bbe_params: dict
    sig_params: dict
    impl: str = "xla"   # Stage-2 attention backend: xla | pallas

    def __post_init__(self):
        check_backend("impl", self.impl, ATTENTION_BACKENDS)

    # ------------------------------------------------------------- factory
    @classmethod
    def create(cls, rng=None, bbe_cfg: Optional[bbe_mod.BBEConfig] = None,
               sig_cfg: Optional[sig_mod.SignatureConfig] = None,
               impl: str = "xla"):
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        k1, k2 = jax.random.split(rng)
        tok = default_tokenizer()
        bbe_cfg = bbe_cfg or bbe_mod.BBEConfig()
        sig_cfg = sig_cfg or sig_mod.SignatureConfig(bbe_dim=bbe_cfg.bbe_dim)
        bbe_params, _ = bbe_mod.bbe_init(k1, bbe_cfg, tok)
        sig_params, _ = sig_mod.signature_init(k2, sig_cfg)
        return cls(tok, bbe_cfg, sig_cfg, bbe_params, sig_params, impl)

    @classmethod
    def from_config(cls, cfg: PipelineConfig) -> "SemanticBBVPipeline":
        """Typed-config twin of `create` (the service-facade entry)."""
        bbe_cfg, sig_cfg = cfg.resolve()
        return cls.create(jax.random.PRNGKey(cfg.seed), bbe_cfg, sig_cfg,
                          impl=cfg.impl)

    # ----------------------------------------------------------- jit cache
    def _jit(self, name: str, builder):
        """Build each jitted entry point ONCE per pipeline — rebuilding
        jax.jit objects per call retraces/compiles every time (measured:
        ~2 s/function in the BCSD benchmark)."""
        cache = self.__dict__.setdefault("_jit_cache", {})
        if name not in cache:
            cache[name] = builder()
        return cache[name]

    # ------------------------------------------------------------- stage 1
    def encode_tokens(self, tokens: np.ndarray, batch: int = 256
                      ) -> np.ndarray:
        """tokens: (N, L, 6) -> BBEs (N, bbe_dim), minibatched + jitted.

        Every chunk — including the last partial one and whole inputs
        smaller than `batch` — is padded to the static (batch, L, 6)
        shape, so one compile serves every call."""
        fn = self._jit("encode", lambda: jax.jit(functools.partial(
            bbe_mod.encode_bbe, cfg=self.bbe_cfg)))
        outs = []
        n = tokens.shape[0]
        for i in range(0, n, batch):
            with tracing.span("pipeline.encode") as s:
                chunk = tokens[i:i + batch]
                got = chunk.shape[0]
                if got < batch:
                    chunk = np.pad(chunk, ((0, batch - got), (0, 0), (0, 0)))
                out = np.asarray(fn(params=self.bbe_params,
                                    tokens=jnp.asarray(chunk)))
                s.add(h2d_bytes=chunk.nbytes, d2h_bytes=out.nbytes,
                      rows=got, padded_rows=batch - got)
            outs.append(out[:got])
        if not outs:
            return np.zeros((0, self.bbe_cfg.bbe_dim), np.float32)
        return np.concatenate(outs, axis=0)

    def encode_blocks(self, blocks: Sequence[BasicBlock], batch: int = 256
                      ) -> Dict[int, np.ndarray]:
        """Stage 1 over blocks, with an LRU cache keyed by block content
        so repeated calls (retraining sweeps, incremental traces) only
        encode blocks they have not seen."""
        state = self.__dict__.setdefault("_bbe_cache", {})
        if state.get("params") is not self.bbe_params:   # params swapped
            state["params"] = self.bbe_params
            state["lru"] = collections.OrderedDict()
        lru: collections.OrderedDict = state["lru"]
        keys = [b.render() for b in blocks]
        fresh, fresh_keys, seen = [], [], set()
        for b, key in zip(blocks, keys):
            if key not in lru and key not in seen:
                fresh.append(b)
                fresh_keys.append(key)
                seen.add(key)
        if fresh:
            toks = self.tok.encode_blocks(fresh, self.bbe_cfg.max_len)
            for key, vec in zip(fresh_keys, self.encode_tokens(toks, batch)):
                lru[key] = vec.copy()   # detach from the batch array
        out = {}
        for b, key in zip(blocks, keys):
            lru.move_to_end(key)
            # copies keep the old ownership contract: callers may mutate
            # the returned table without corrupting the cache
            out[b.bid] = lru[key].copy()
        # evict only after serving: every key of this call was just
        # move_to_end'd, so eviction can't touch entries still in use
        while len(lru) > _BBE_CACHE_SIZE:
            lru.popitem(last=False)
        return out

    # ------------------------------------------------------------- stage 2
    def interval_set(self, interval, bbe_table: Dict[int, np.ndarray]
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One interval -> (bbes (N,D), freqs (N,), mask (N,)) padded to
        max_set, keeping the most frequent blocks if over."""
        N = self.sig_cfg.max_set
        D = self.sig_cfg.bbe_dim
        items = sorted(interval.counts.items(), key=lambda kv: -kv[1])[:N]
        bbes = np.zeros((N, D), np.float32)
        freqs = np.zeros((N,), np.float32)
        mask = np.zeros((N,), bool)
        for i, (bid, cnt) in enumerate(items):
            bbes[i] = bbe_table[bid]
            freqs[i] = cnt
            mask[i] = True
        return bbes, freqs, mask

    def _batch_sets_looped(self, intervals, bbe_table):
        """Per-interval loop kept as the parity oracle for `_batch_sets`
        (tests assert bit-identical output) and the benchmark baseline."""
        sets = [self.interval_set(iv, bbe_table) for iv in intervals]
        bbes = np.stack([s[0] for s in sets])
        freqs = np.stack([s[1] for s in sets])
        mask = np.stack([s[2] for s in sets])
        return bbes, freqs, mask

    def _batch_set_ids(self, intervals, index: BBEIndex):
        """Module-level `batch_set_ids` bound to this pipeline's max_set."""
        return batch_set_ids(intervals, index, self.sig_cfg.max_set)

    def _batch_sets(self, intervals, index: BBEIndex):
        """Dense (bbes (B,N,D), freqs, mask) batch — `_batch_set_ids`
        plus one sentinel gather. Bit-identical to `_batch_sets_looped`."""
        row_ids, freqs, mask = self._batch_set_ids(intervals, index)
        B = len(intervals)
        N = self.sig_cfg.max_set
        D = self.sig_cfg.bbe_dim
        if index.num_rows == 0:
            bbes = np.zeros((B, N, D), np.float32)
        else:
            bbes = index.ext.take(row_ids.ravel(), axis=0).reshape(B, N, D)
        return bbes, freqs, mask

    def _table_index(self, bbe_table):
        """(BBEIndex, device matrix) for a table, cached on table identity
        so back-to-back signature/CPI calls skip the rebuild + re-upload.
        Length is checked too, so growing a table in place invalidates;
        replacing vectors under the same bids requires a new dict."""
        state = self.__dict__.setdefault("_index_cache", {})
        if state.get("table") is not bbe_table or \
                state.get("n") != len(bbe_table):
            index = BBEIndex(bbe_table)
            if index.num_rows:
                matrix = jnp.asarray(index.ext)
                tracing.add(h2d_bytes=index.ext.nbytes)
            else:
                matrix = jnp.zeros((1, self.sig_cfg.bbe_dim), jnp.float32)
            state.update(table=bbe_table, n=len(bbe_table), index=index,
                         matrix=matrix)
        return state["index"], state["matrix"]

    def _run_signature(self, intervals, bbe_table, batch: int):
        """Shared batched Stage-2 driver -> (sigs (B,sig_dim), logcpi (B,)).

        Each item runs at `set_width` of its own set: items are grouped
        by width, in order within a group, and each group is cut into
        batches of `batch`. A row's result therefore depends on its set
        alone, never on the items it shares a batch with: any split of
        the same items into calls gives the same bits. The BBE matrix
        goes to the device once; each batch ships only integer row ids +
        freqs + mask at its width (`batch_set_ids` packs each set's
        entries to the left, so the slice drops only masked slots). A
        group's last partial batch is padded to `batch` rows (all-masked,
        outputs discarded). Each width is one static shape, and so is
        the matrix: a width not seen before, or any width after the BBE
        table has grown, compiles once, mid-request."""
        fn = self._jit(f"signature_{self.impl}", lambda: jax.jit(
            functools.partial(_signature_from_rows, cfg=self.sig_cfg,
                              impl=self.impl)))
        index, matrix = self._table_index(bbe_table)
        widths = np.fromiter(
            (set_width(it.set_size(), self.sig_cfg.max_set)
             for it in intervals), np.int64, count=len(intervals))
        done, sigs, cpis = [], [], []
        for width in np.unique(widths).tolist():
            group = np.flatnonzero(widths == width)
            for i in range(0, len(group), batch):
                take = group[i:i + batch]
                sig, logcpi = self._run_batch(
                    fn, [intervals[j] for j in take], index, matrix,
                    batch, width)
                done.append(take)
                sigs.append(sig)
                cpis.append(logcpi)
        if not sigs:
            return (np.zeros((0, self.sig_cfg.sig_dim), np.float32),
                    np.zeros((0,), np.float32))
        order = np.argsort(np.concatenate(done))
        return (np.concatenate(sigs, axis=0)[order],
                np.concatenate(cpis, axis=0)[order])

    def _run_batch(self, fn, items, index, matrix, batch: int, width: int):
        """One Stage-2 call: `items` (at most `batch`, each set at most
        `width` entries) padded to `batch` rows of `width` slots."""
        with tracing.span("pipeline.set_assembly") as s:
            row_ids, freqs, mask = self._batch_set_ids(items, index)
            row_ids, freqs, mask = (row_ids[:, :width], freqs[:, :width],
                                    mask[:, :width])
            got = row_ids.shape[0]
            if got < batch:
                pad = batch - got
                row_ids = np.pad(row_ids, ((0, pad), (0, 0)),
                                 constant_values=index.sentinel)
                freqs = np.pad(freqs, ((0, pad), (0, 0)))
                mask = np.pad(mask, ((0, pad), (0, 0)))
            s.add(rows=got, padded_rows=batch - got, width=width)
        with tracing.span("pipeline.stage2") as s:
            sig, logcpi = fn(params=self.sig_params, matrix=matrix,
                             row_ids=jnp.asarray(row_ids),
                             freqs=jnp.asarray(freqs),
                             mask=jnp.asarray(mask))
            sig, logcpi = np.asarray(sig), np.asarray(logcpi)
            s.add(h2d_bytes=row_ids.nbytes + freqs.nbytes + mask.nbytes,
                  d2h_bytes=sig.nbytes + logcpi.nbytes)
        return sig[:got], logcpi[:got]

    def interval_signatures(self, intervals, bbe_table, batch: int = 512
                            ) -> np.ndarray:
        """bbe_table is snapshotted per (dict identity, length): growing
        it or passing a new dict refreshes the device copy, but replacing
        vectors under existing bids in the SAME dict requires a new dict
        (or the cached snapshot is reused)."""
        sigs, _ = self._run_signature(intervals, bbe_table, batch)
        return sigs

    def interval_signatures_many(self, intervals_by_program,
                                 bbe_table, batch: int = 512
                                 ) -> Dict[str, np.ndarray]:
        """Signatures for SEVERAL programs in one pipelined batch stream.

        Intervals are concatenated across programs before batching, so
        the padding penalty of a partial batch is paid once per set width
        over the whole stream — not once per program — and the BBE matrix
        upload plus jit cache are shared across the whole call. Returns
        {program: (n_p, sig_dim)} in input order; bit-identical to
        per-program `interval_signatures` calls (a row runs at the width
        of its own set, whatever it is batched with).
        """
        names = list(intervals_by_program)
        flat = [iv for n in names for iv in intervals_by_program[n]]
        sigs = self.interval_signatures(flat, bbe_table, batch)
        out, off = {}, 0
        for n in names:
            count = len(intervals_by_program[n])
            out[n] = sigs[off:off + count]
            off += count
        return out

    def predict_interval_cpi(self, intervals, bbe_table, batch: int = 512
                             ) -> np.ndarray:
        """Same bbe_table snapshot semantics as `interval_signatures`."""
        _, logcpi = self._run_signature(intervals, bbe_table, batch)
        return np.expm1(logcpi)
