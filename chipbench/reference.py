"""Plain reference of the SemanticBBV service's answers.

Written from the model's equations (the paper's §III and the repo's
description of them), in straightforward `jax.numpy` over the weights
that `chipbench.weights` makes: no kernels, no caches, no batching
tricks, and nothing imported from the program under test. It computes
in float32 at `precision` ("highest": float32 products; "high", three
bfloat16 passes, is the control one step below).

  tokens     the six-dimension assembly tokenisation of a basic block
  stage1     RWKV encoder with the gated delta-rule state update,
             attention pooling, projection, L2 normalisation -> BBE
  stage2     frequency-weighted Set Transformer (2 SABs + PMA) -> L2-
             normalised signature
  top_sets   an interval's top-`max_set` blocks by execution count
  distances  squared distances to the archetypes, in float64
  fingerprint, answer_range
             weighted archetype occupancy, and the range of fingerprints
             and CPI estimates (occupancy . representatives' CPI) that a
             correct assignment may give where archetypes tie
  lloyd      k-means (k-means++ seeding, Lloyd iterations)

Departures, each shared with the program by convention: the GELU of the
Set Transformer's feed-forward is the tanh approximation, and norms use
eps 1e-6. Tokens past a block's end are pad; the recurrence runs over
them (they are causal, so they change no valid position) and pooling
masks them.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------- tokens
# opcode -> (class, sets flags, reads flags), the synthetic x86-64 ISA
OPCODES = {
    "mov": ("mov", 0, 0), "movzx": ("mov", 0, 0),
    "add": ("alu", 1, 0), "sub": ("alu", 1, 0), "and": ("alu", 1, 0),
    "or": ("alu", 1, 0), "xor": ("alu", 1, 0), "shl": ("alu", 1, 0),
    "shr": ("alu", 1, 0), "sar": ("alu", 1, 0), "inc": ("alu", 1, 0),
    "dec": ("alu", 1, 0), "neg": ("alu", 1, 0),
    "imul": ("mul", 1, 0), "idiv": ("div", 1, 0), "lea": ("lea", 0, 0),
    "cmp": ("cmp", 1, 0), "test": ("cmp", 1, 0),
    "je": ("branch", 0, 1), "jne": ("branch", 0, 1), "jl": ("branch", 0, 1),
    "jle": ("branch", 0, 1), "jg": ("branch", 0, 1), "jge": ("branch", 0, 1),
    "jb": ("branch", 0, 1), "jae": ("branch", 0, 1),
    "jmp": ("jmp", 0, 0), "push": ("stack", 0, 0), "pop": ("stack", 0, 0),
    "call": ("call", 0, 0), "ret": ("ret", 0, 0), "nop": ("nop", 0, 0),
    "addss": ("fpalu", 0, 0), "subss": ("fpalu", 0, 0),
    "mulss": ("fpmul", 0, 0), "divss": ("fpdiv", 0, 0),
    "addsd": ("fpalu", 0, 0), "mulsd": ("fpmul", 0, 0),
    "movss": ("mov", 0, 0), "sqrtss": ("fpdiv", 0, 0),
    "cvtsi2ss": ("fpalu", 0, 0),
}
GPRS = ["rax", "rbx", "rcx", "rdx", "rsi", "rdi", "r8", "r9", "r10", "r11",
        "r12", "r13", "r14", "r15"]
REGS = GPRS + ["rsp", "rbp"] + [f"xmm{i}" for i in range(16)]
ITYPES = ["none"] + sorted({v[0] for v in OPCODES.values()})
OTYPES = ["none", "opcode", "reg", "mem", "imm", "label"]
RTYPES = ["none", "gpr", "sp", "bp", "xmm"]
ATYPES = ["none", "read", "write", "readwrite"]
FLAGS = ["none", "sets", "reads", "both"]


def _asm_vocab() -> List[str]:
    base = [r for r in REGS if not r.startswith("xmm")]
    return (["<pad>", "<bos>", "<eos>", "<sep>"] + sorted(OPCODES) + REGS
            + ["IMM", "LABEL"] + [f"[{r}+IMM]" for r in base]
            + [f"[{r}+{i}*8+IMM]" for r in base for i in base] + ["[UNK]"])


ASM = {t: i for i, t in enumerate(_asm_vocab())}
VOCAB = (len(ASM), len(ITYPES), len(OTYPES), len(RTYPES), len(ATYPES),
         len(FLAGS))


def _rtype(reg: str) -> int:
    kind = ("sp" if reg == "rsp" else "bp" if reg == "rbp"
            else "xmm" if reg.startswith("xmm") else "gpr")
    return RTYPES.index(kind)


def _instr_rows(ins) -> List[Tuple[int, ...]]:
    cls, sets, reads = OPCODES[ins.opcode]
    fl = FLAGS.index("both" if sets and reads else "sets" if sets
                     else "reads" if reads else "none")
    it = ITYPES.index(cls)
    rows = [(ASM.get(ins.opcode, ASM["[UNK]"]), it, 1, 0, 0, fl)]
    ops = ins.operands
    store = ins.opcode == "push" or (
        bool(ops) and ops[0].kind == "mem" and ins.opcode not in ("cmp",
                                                                  "test"))
    for oi, op in enumerate(ops):
        if op.kind == "mem":
            acc = "write" if oi == 0 and store else "read"
        elif oi == 0 and cls not in ("cmp", "branch", "jmp"):
            acc = "write" if cls in ("mov", "lea") else "readwrite"
        else:
            acc = "read"
        ai = ATYPES.index(acc)
        if op.kind == "reg":
            rows.append((ASM.get(op.reg, ASM["[UNK]"]), it, 2, _rtype(op.reg),
                         ai, fl))
        elif op.kind == "imm":
            rows.append((ASM["IMM"], it, 4, 0, ai, fl))
        elif op.kind == "label":
            rows.append((ASM["LABEL"], it, 5, 0, ai, fl))
        else:
            t = (f"[{op.reg}+{op.index}*8+IMM]" if op.index is not None
                 else f"[{op.reg}+IMM]")
            rows.append((ASM.get(t, ASM["[UNK]"]), it, 3, _rtype(op.reg), ai,
                         fl))
    return rows


def tokens(blocks: Sequence, max_len: int) -> np.ndarray:
    """(n, max_len, 6) int32: BOS, each instruction's tokens then SEP,
    EOS; cut to `max_len`, zero (pad) beyond."""
    out = np.zeros((len(blocks), max_len, 6), np.int32)
    special = lambda name: (ASM[name], 0, 0, 0, 0, 0)  # noqa: E731
    for i, b in enumerate(blocks):
        rows = [special("<bos>")]
        for ins in b.instrs:
            rows.extend(_instr_rows(ins))
            rows.append(special("<sep>"))
        rows.append(special("<eos>"))
        rows = rows[:max_len]
        out[i, :len(rows)] = rows
    return out


# ---------------------------------------------------------------- stage 1
def _rms(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _shift(x):
    """x_{t-1} along the sequence, zero at t = 0."""
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _layer(x, p, H):
    B, L, d = x.shape
    dh = d // H
    tm, cm = p["time_mix"], p["channel_mix"]
    h = _rms(x, p["norm1"]["scale"])
    hp = _shift(h)
    lerp = [h * tm["mu"][i] + hp * (1 - tm["mu"][i]) for i in range(5)]
    r = (lerp[0] @ tm["wr"]).reshape(B, L, H, dh)
    k = (lerp[1] @ tm["wk"]).reshape(B, L, H, dh)
    v = (lerp[2] @ tm["wv"]).reshape(B, L, H, dh)
    w = jax.nn.sigmoid(lerp[3] @ tm["ww"] + tm["w_bias"]).reshape(B, L, H, dh)
    beta = jax.nn.sigmoid(lerp[4] @ tm["wbeta"])                  # (B,L,H)
    k = k / jnp.maximum(jnp.sqrt(jnp.sum(k * k, -1, keepdims=True)), 1e-6)

    # S_t = diag(w_t) S_{t-1} (I - b_t k_t k_t^T) + b_t k_t v_t^T, as the
    # per-step update  S <- w*S;  S <- S + b k (v - S^T k)^T;  y = S^T r
    def step(S, xs):
        rt, kt, vt, wt, bt = xs
        S = S * wt[..., :, None]
        delta = vt - jnp.einsum("bhkv,bhk->bhv", S, kt)
        S = S + bt[..., None, None] * kt[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, rt)

    xs = tuple(a.swapaxes(0, 1) for a in (r, k, v, w, beta))
    _, y = jax.lax.scan(step, jnp.zeros((B, H, dh, dh), x.dtype), xs)
    y = _rms(y.swapaxes(0, 1).reshape(B, L, d), tm["ln_x"])
    x = x + y @ tm["wo"]
    h = _rms(x, p["norm2"]["scale"])
    hk = h * cm["mu"] + _shift(h) * (1 - cm["mu"])
    return x + jnp.square(jax.nn.relu(hk @ cm["wk"])) @ cm["wv"]


@functools.partial(jax.jit, static_argnames=("heads",))
def _stage1(p, toks, heads: int):
    x = jnp.concatenate([tbl[toks[..., i]] for i, tbl in
                         enumerate(p["embeds"])], axis=-1)
    x = x * jnp.float32(x.shape[-1] ** 0.5)
    x, _ = jax.lax.scan(lambda h, lp: (_layer(h, lp, heads), None), x,
                        p["blocks"])
    x = _rms(x, p["final_norm"]["scale"])
    e = jnp.tanh(x @ p["pool"]["Wa"] + p["pool"]["ba"]) @ p["pool"]["ua"]
    e = jnp.where(toks[..., 0] != 0, e, -jnp.inf)
    alpha = jax.nn.softmax(e, axis=-1)
    z = jnp.einsum("bl,bld->bd", alpha, x) @ p["out_proj"]
    return z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-8)


def stage1(params, toks: np.ndarray, heads: int, precision="highest",
           chunk: int = 256) -> np.ndarray:
    """BBEs (n, bbe_dim) float32 of tokenised blocks, `chunk` at a time."""
    out = []
    with jax.default_matmul_precision(precision):
        for i in range(0, toks.shape[0], chunk):
            part = toks[i:i + chunk]
            pad = chunk - part.shape[0]
            if pad:
                part = np.concatenate([part, np.zeros((pad,) + part.shape[1:],
                                                      part.dtype)])
                part[-pad:, 0, 0] = ASM["<bos>"]     # keep pooling defined
            got = np.asarray(_stage1(params, jnp.asarray(part), heads))
            out.append(got[:chunk - pad])
    return np.concatenate(out) if out else np.zeros((0, 0), np.float32)


# ---------------------------------------------------------------- stage 2
def _ln(x, p, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _mab(p, xq, xk, bias, H):
    """Multihead attention block: LN(h + FF(h)), h = LN(xq + MHA)."""
    B, N, d = xq.shape
    M = xk.shape[1]
    dh = d // H
    m = p["mha"]
    q = (xq @ m["wq"]).reshape(B, N, H, dh)
    k = (xk @ m["wk"]).reshape(B, M, H, dh)
    v = (xk @ m["wv"]).reshape(B, M, H, dh)
    s = jnp.einsum("bnhd,bmhd->bhnm", q, k) / jnp.asarray(dh ** 0.5, q.dtype)
    a = jax.nn.softmax(s + bias[:, None, None, :], axis=-1)
    o = jnp.einsum("bhnm,bmhd->bnhd", a, v).reshape(B, N, d) @ m["wo"]
    h = _ln(xq + o, p["norm1"])
    f = jax.nn.gelu(h @ p["ff1"]["w"] + p["ff1"]["b"], approximate=True)
    return _ln(h + f @ p["ff2"]["w"] + p["ff2"]["b"], p["norm2"])


@functools.partial(jax.jit, static_argnames=("heads",))
def _stage2(params, table, rows, freqs, mask, heads: int):
    p = params["set_transformer"]
    bbes = jnp.where(mask[..., None], table[rows], 0.0)
    logw = jnp.log1p(freqs)
    kb = logw / jnp.maximum(logw.max(-1, keepdims=True), 1e-6)
    x = jnp.concatenate([bbes, kb[..., None]], -1)
    bias = jnp.where(mask, kb, -jnp.inf)
    h = x @ p["in_proj"]["w"] + p["in_proj"]["b"]
    for sab in p["sabs"]:
        h = _mab(sab, h, h, bias, heads)
    seeds = jnp.broadcast_to(p["seeds"][None], (h.shape[0],)
                             + p["seeds"].shape)
    z = _mab(p["pma"], seeds, h, bias, heads).reshape(h.shape[0], -1)
    z = z @ p["out_proj"]["w"] + p["out_proj"]["b"]
    return z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-8)


def top_sets(counts: np.ndarray, max_set: int):
    """Each interval's top-`max_set` blocks by execution count, given
    `counts[i, j]` executions of block j in interval i (ties: lower j
    first) -> (block columns j (n, max_set), freqs, mask)."""
    cols = np.argsort(-counts, axis=1, kind="stable")[:, :max_set]
    freqs = np.take_along_axis(counts, cols, 1)
    return cols, freqs.astype(np.float32), freqs > 0


def stage2(params, bbes: np.ndarray, rows: np.ndarray, freqs: np.ndarray,
           mask: np.ndarray, heads: int, precision="highest",
           chunk: int = 512) -> np.ndarray:
    """Signatures (n, sig_dim) float32 of intervals given as sets:
    element j of interval i has the BBE `bbes[rows[i, j]]` and count
    `freqs[i, j]`, where `mask[i, j]`."""
    out = []
    table = jnp.asarray(bbes, jnp.float32)
    with jax.default_matmul_precision(precision):
        for i in range(0, len(rows), chunk):
            # every call at one shape: a short last chunk repeats its
            # first interval, and the repeats are dropped
            take = np.arange(i, i + chunk)
            take[take >= len(rows)] = i
            r, f, m = rows[take], freqs[take], mask[take]
            got = np.asarray(_stage2(
                params, table, jnp.asarray(np.where(m, r, 0), jnp.int32),
                jnp.asarray(f), jnp.asarray(m), heads))
            out.append(got[:len(rows) - i])
    return np.concatenate(out)


# ------------------------------------------------------- archetype queries
def distances(x: np.ndarray, c: np.ndarray) -> np.ndarray:
    """(n, k) squared distances in float64."""
    x = np.asarray(x, np.float64)
    c = np.asarray(c, np.float64)
    return ((x * x).sum(-1)[:, None] - 2.0 * x @ c.T
            + (c * c).sum(-1)[None, :])


@jax.jit
def _nearest(x, c):
    d = (jnp.sum(x * x, -1)[:, None] - 2 * x @ c.T
         + jnp.sum(c * c, -1)[None, :])
    return jnp.argmin(d, -1)


def nearest(x: np.ndarray, c: np.ndarray, precision="highest") -> np.ndarray:
    """Nearest archetype of each row, in float32 at `precision`."""
    with jax.default_matmul_precision(precision):
        return np.asarray(_nearest(jnp.asarray(x, jnp.float32),
                                   jnp.asarray(c, jnp.float32)))


def fingerprint(assign: np.ndarray, weights: np.ndarray, k: int
                ) -> np.ndarray:
    """Instruction-weighted archetype occupancy, summing to 1."""
    w = np.asarray(weights, np.float64)
    return np.bincount(assign, weights=w / w.sum(), minlength=k)


def answer_range(d2: np.ndarray, weights: np.ndarray, rep_cpi: np.ndarray,
                 tie: float):
    """What a correct nearest-archetype answer may be, given each row's
    float64 squared distances `d2` (n, k): a row whose best archetypes
    lie within `tie` of each other may go to any of them. Returns the
    fingerprint's per-archetype [lo, hi] and the estimated CPI's [lo, hi]
    (fingerprint . rep_cpi)."""
    k = d2.shape[1]
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    cand = d2 <= d2.min(-1, keepdims=True) + tie
    amb = cand.sum(-1) > 1
    f_lo = np.bincount(d2[~amb].argmin(-1), weights=w[~amb], minlength=k)
    f_hi = f_lo + (cand[amb] * w[amb, None]).sum(0)
    cpi = np.asarray(rep_cpi, np.float64)
    base = float(f_lo @ cpi)
    spread = np.where(cand[amb], cpi[None, :], np.nan)
    e_lo = base + float(np.nansum(w[amb] * np.nanmin(spread, -1))) \
        if amb.any() else base
    e_hi = base + float(np.nansum(w[amb] * np.nanmax(spread, -1))) \
        if amb.any() else base
    return (f_lo, f_hi), (e_lo, e_hi)


def outside(x, lo, hi) -> float:
    """Summed distance of `x` outside the interval(s) [lo, hi]."""
    x, lo, hi = (np.asarray(v, np.float64) for v in (x, lo, hi))
    return float((np.maximum(lo - x, 0) + np.maximum(x - hi, 0)).sum())


@functools.partial(jax.jit, static_argnames=("k",))
def _lloyd_step(xs, c, k: int):
    """(means of each centroid's rows, assignment) — one Lloyd step."""
    d = (jnp.sum(xs * xs, -1)[:, None] - 2 * xs @ c.T
         + jnp.sum(c * c, -1)[None, :])
    a = jnp.argmin(d, -1)
    one = jax.nn.one_hot(a, k, dtype=xs.dtype)
    n = one.sum(0)
    s = one.T @ xs
    return jnp.where(n[:, None] > 0, s / jnp.maximum(n, 1)[:, None], c), a


def lloyd(x, k: int, seed: int, iters: int = 25):
    """Plain k-means over rows `x`: k-means++ seeding from `seed`, then
    `iters` Lloyd steps in float32. -> (centroids (k, d) float32,
    assignment (n,) to them)."""
    rng = np.random.default_rng(int(seed) % (1 << 64))
    x64 = np.asarray(x, np.float64)
    cents = [x64[rng.integers(len(x64))]]
    d2 = ((x64 - cents[0]) ** 2).sum(-1)
    for _ in range(1, k):
        cents.append(x64[rng.choice(len(x64), p=d2 / d2.sum())])
        d2 = np.minimum(d2, ((x64 - cents[-1]) ** 2).sum(-1))
    xs = jnp.asarray(x, jnp.float32)
    c = jnp.asarray(np.stack(cents), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for _ in range(iters):
            c, _ = _lloyd_step(xs, c, k)
        _, a = _lloyd_step(xs, c, k)
    return np.asarray(c, np.float32), np.asarray(a)
