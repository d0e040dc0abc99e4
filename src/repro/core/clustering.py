"""jit-compiled k-means with kmeans++ seeding — host and on-device builds.

Two build paths share the same per-iteration math:

  `kmeans`          legacy host wrapper: one jitted `kmeans_fit` dispatch
                    per restart, numpy round-trips of the (N,) assignment
                    and (k,d) centroids each time, best-of picked on the
                    host. Kept as the parity anchor and benchmark baseline.
  `kmeans_device`   the scale path: ALL restarts run inside one jitted
                    `kmeans_fit_restarts` call (lax.map over stacked
                    restart keys, best-of argmin on device), directly over
                    a padded device-resident matrix (`n_valid` masks the
                    tail), so only the winning centroids/assignment ever
                    cross back to the host. kmeans++ seeding uses the
                    x²-2xc+c² expansion — an (N,k) scratch instead of the
                    (N,k,d) broadcast the host init materializes per step.

`use_kernel=True` runs the Pallas kernels inside the jitted loop: the
fused `kmeans_update` (assignment + segment-reduced centroid sums/counts,
fp32 accumulators) per iteration and `kmeans_assign` for the final
labels — compiled on TPU, interpreter elsewhere. With a `mesh`, the
kernel ops are shard_map'd over the data axis (per-shard partials psum'd
into replicated (k,d) sums); the jnp path shards via GSPMD from the
input's NamedSharding.

Used for intra-program SimPoint clustering and the 14-archetype
universal clustering (`repro.api.KnowledgeBase.build`).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes the (N, d) data dim shards over (repro.launch.mesh
    convention: "pod" and/or "data"; model axes never split rows)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _data_axis_size(mesh: Mesh) -> int:
    size = 1
    for a in _data_axes(mesh):
        size *= dict(mesh.shape)[a]
    return size


def _row_shard_axes(mesh: Optional[Mesh], n_rows: int):
    """The single place the row-sharding rule lives: the data axes to
    split `n_rows` over, or None when sharding is off (no mesh, size-1
    data axis, or rows that do not divide). Returns a PartitionSpec-
    ready value: one axis name, or a tuple of names."""
    if mesh is None:
        return None
    axes = _data_axes(mesh)
    size = _data_axis_size(mesh)
    if size <= 1 or n_rows % size:
        return None
    return axes if len(axes) > 1 else axes[0]


def shard_rows(x, mesh: Optional[Mesh]):
    """Place x with its leading (row) axis sharded over the mesh's data
    axes; no-op when `_row_shard_axes` says sharding is off."""
    dax = _row_shard_axes(mesh, x.shape[0])
    if dax is None:
        return jnp.asarray(x)
    return jax.device_put(jnp.asarray(x), NamedSharding(mesh, P(dax, None)))


def _assign(x, centroids, use_kernel: bool = False,
            mesh: Optional[Mesh] = None):
    """Nearest-centroid assignment -> (assign (N,), dist2 (N,))."""
    if not use_kernel:
        from repro.kernels.kmeans_assign.ref import kmeans_assign_reference
        return kmeans_assign_reference(x, centroids)
    from repro.kernels.kmeans_assign.ops import kmeans_assign
    dax = _row_shard_axes(mesh, x.shape[0])
    if dax is None:
        return kmeans_assign(x, centroids)
    fn = jax.shard_map(
        kmeans_assign,
        mesh=mesh, in_specs=(P(dax, None), P(None, None)),
        out_specs=(P(dax), P(dax)), check_vma=False)
    return fn(x, centroids)


def _update(x, centroids, valid, use_kernel: bool = False,
            mesh: Optional[Mesh] = None):
    """One fused k-means step: (sums (k,d), counts (k,), inertia)."""
    if not use_kernel:
        from repro.kernels.kmeans_assign.ref import kmeans_update_reference
        v = (jnp.ones((x.shape[0],), jnp.float32) if valid is None
             else valid)
        sums, counts, inertia = kmeans_update_reference(x, centroids, v)
        return sums, counts, inertia[0]
    from repro.kernels.kmeans_assign.ops import kmeans_update
    dax = _row_shard_axes(mesh, x.shape[0])
    if dax is None:
        return kmeans_update(x, centroids, valid)

    def body(xs, c, vs):
        s, n, i = kmeans_update(xs, c, vs)
        return (jax.lax.psum(s, dax), jax.lax.psum(n, dax),
                jax.lax.psum(i, dax))

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(dax, None), P(None, None), P(dax)),
                       out_specs=(P(None, None), P(None), P()),
                       check_vma=False)
    v = (jnp.ones((x.shape[0],), jnp.float32) if valid is None else valid)
    return fn(x, centroids, v)


def kmeans_pp_init(key, x, k: int):
    """kmeans++ seeding (jit-friendly fori_loop)."""
    n = x.shape[0]
    first = jax.random.randint(key, (), 0, n)
    cents = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])

    def body(i, carry):
        cents, key = carry
        key, sub = jax.random.split(key)
        d2 = jnp.min(
            jnp.sum(jnp.square(x[:, None, :] - cents[None, :, :]), -1)
            + jnp.where(jnp.arange(cents.shape[0])[None, :] < i, 0.0, jnp.inf),
            axis=1)
        probs = d2 / jnp.maximum(d2.sum(), 1e-12)
        nxt = jax.random.choice(sub, n, p=probs)
        return cents.at[i].set(x[nxt]), key

    cents, _ = jax.lax.fori_loop(1, k, body, (cents, key))
    return cents


def kmeans_pp_init_weighted(key, x, k: int, valid):
    """kmeans++ over an ARBITRARY validity mask (not just a prefix).

    The tombstone path: a store with evicted rows hands its (N,) 0/1
    alive mask straight to the jitted build and dead rows get zero
    sampling mass — no host-side filtering or re-upload. The first
    centroid is a weighted choice over the mask (the prefix init's
    `randint` cannot express holes), so this init is NOT bit-compatible
    with `kmeans_pp_init_masked`; post-`compact()` stores are dense
    again and take the prefix path.
    """
    n = x.shape[0]
    v = valid.astype(x.dtype)
    vbool = v > 0
    n_eff = jnp.maximum(v.sum(), 1.0)
    first = jax.random.choice(key, n, p=v / jnp.maximum(v.sum(), 1e-30))
    cents = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])
    x2 = jnp.sum(jnp.square(x), axis=-1)

    def body(i, carry):
        cents, key = carry
        key, sub = jax.random.split(key)
        c2 = jnp.sum(jnp.square(cents), axis=-1)
        d2 = x2[:, None] - 2.0 * (x @ cents.T) + c2[None, :]
        d2 = jnp.min(
            d2 + jnp.where(jnp.arange(k)[None, :] < i, 0.0, jnp.inf),
            axis=1)
        d2 = jnp.where(vbool, jnp.maximum(d2, 0.0), 0.0)
        total = d2.sum()
        probs = jnp.where(total > 0, d2 / jnp.maximum(total, 1e-30),
                          v / n_eff)
        nxt = jax.random.choice(sub, n, p=probs)
        return cents.at[i].set(x[nxt]), key

    cents, _ = jax.lax.fori_loop(1, k, body, (cents, key))
    return cents


def kmeans_pp_init_masked(key, x, k: int, n_valid):
    """kmeans++ over the first `n_valid` rows of a padded matrix.

    Distances use the x²-2xc+c² expansion — (N,k) scratch per step
    instead of the (N,k,d) broadcast above (the memory-traffic hot spot
    of the host init at 100k+ rows). Padded rows get zero sampling mass.
    """
    n = x.shape[0]
    valid = jnp.arange(n) < n_valid
    first = jax.random.randint(key, (), 0, jnp.maximum(n_valid, 1))
    cents = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])
    x2 = jnp.sum(jnp.square(x), axis=-1)

    def body(i, carry):
        cents, key = carry
        key, sub = jax.random.split(key)
        c2 = jnp.sum(jnp.square(cents), axis=-1)
        d2 = x2[:, None] - 2.0 * (x @ cents.T) + c2[None, :]
        d2 = jnp.min(
            d2 + jnp.where(jnp.arange(k)[None, :] < i, 0.0, jnp.inf),
            axis=1)
        d2 = jnp.where(valid, jnp.maximum(d2, 0.0), 0.0)
        total = d2.sum()
        uniform = valid / jnp.maximum(n_valid, 1).astype(x.dtype)
        probs = jnp.where(total > 0, d2 / jnp.maximum(total, 1e-30),
                          uniform)
        nxt = jax.random.choice(sub, n, p=probs)
        return cents.at[i].set(x[nxt]), key

    cents, _ = jax.lax.fori_loop(1, k, body, (cents, key))
    return cents


def _fit_one(key, x, k: int, iters: int, use_kernel: bool,
             valid, n_valid, mesh: Optional[Mesh]):
    """Shared seeded-restart body: ++init, `iters` fused steps, final
    assignment. Three validity modes: n_valid set => prefix mask (the
    padded store tail); n_valid None but valid set => arbitrary 0/1 mask
    (tombstoned rows); both None => every row is real."""
    if n_valid is not None:
        cents = kmeans_pp_init_masked(key, x, k, n_valid)
    elif valid is not None:
        cents = kmeans_pp_init_weighted(key, x, k, valid)
    else:
        cents = kmeans_pp_init(key, x, k)

    def step(cents, _):
        sums, counts, inertia = _update(x, cents, valid, use_kernel, mesh)
        new = jnp.where(counts[:, None] > 0,
                        sums / jnp.maximum(counts[:, None], 1.0), cents)
        return new, inertia

    cents, _ = jax.lax.scan(step, cents, None, length=iters)
    a, d2 = _assign(x, cents, use_kernel, mesh)
    if valid is not None:
        d2 = d2 * valid
    return cents, a, d2.sum()


@functools.partial(jax.jit, static_argnames=("k", "iters", "use_kernel"))
def kmeans_fit(key, x, k: int, iters: int = 25, use_kernel: bool = False,
               n_valid=None):
    """x: (N, d) fp32. Returns (centroids (k,d), assign (N,), inertia).

    `n_valid` (traced scalar) masks a padded tail — rows >= n_valid get
    zero weight in every reduction (the store's pad-and-grow device
    matrix can be clustered in place). `use_kernel=True` runs the Pallas
    assignment/segment-reduce kernels inside the loop (compiled on TPU,
    interpreter elsewhere).
    """
    x = x.astype(jnp.float32)
    valid = (None if n_valid is None else
             (jnp.arange(x.shape[0]) < n_valid).astype(jnp.float32))
    return _fit_one(key, x, k, iters, use_kernel, valid, n_valid, None)


@functools.partial(jax.jit,
                   static_argnames=("k", "iters", "use_kernel", "mesh"))
def kmeans_fit_restarts(keys, x, k: int, iters: int = 25,
                        use_kernel: bool = False, n_valid=None,
                        mesh: Optional[Mesh] = None, valid_mask=None):
    """All restarts in ONE dispatch; best-of-inertia picked on device.

    keys: (R, 2) stacked PRNG keys (the host wrapper stacks the same
    per-restart keys `kmeans` uses). Returns (centroids, assign,
    inertia, best_restart). Restarts run sequentially via lax.map (the
    Pallas ops need no vmap batching rule); each one's data-parallel work
    is sharded over the mesh's data axes when `mesh` is given.

    `valid_mask` ((N,) 0/1, traced) supersedes `n_valid`: rows where it
    is zero — a tombstoned store's dead rows, not just the padded tail —
    get zero weight in seeding, every update and the final inertia, all
    inside the same jitted call (no host-side filtering/gather).
    """
    x = x.astype(jnp.float32)
    if valid_mask is not None:
        nv = None
        valid = valid_mask.astype(jnp.float32)
    else:
        nv = x.shape[0] if n_valid is None else n_valid
        valid = (jnp.arange(x.shape[0]) < nv).astype(jnp.float32)

    def one(key):
        cents, _, inertia = _fit_one(key, x, k, iters, use_kernel,
                                     valid, nv, mesh)
        return cents, inertia

    cents_all, inertia_all = jax.lax.map(one, keys)
    best = jnp.argmin(inertia_all)
    cents = cents_all[best]
    a, d2 = _assign(x, cents, use_kernel, mesh)
    return cents, a, (d2 * valid).sum(), best


def kmeans(x: np.ndarray, k: int, iters: int = 25, seed: int = 0,
           restarts: int = 3, use_kernel: bool = False
           ) -> Tuple[np.ndarray, np.ndarray, float]:
    """Legacy host-facing wrapper: one device dispatch + host round-trip
    per restart, best-of on the host. Parity anchor for `kmeans_device`."""
    best = None
    for r in range(restarts):
        key = jax.random.PRNGKey(seed * 1000 + r)
        c, a, inertia = kmeans_fit(key, jnp.asarray(x), k, iters, use_kernel)
        inertia = float(inertia)
        if best is None or inertia < best[2]:
            best = (np.asarray(c), np.asarray(a), inertia)
    return best


def kmeans_device(x, k: int, iters: int = 25, seed: int = 0,
                  restarts: int = 3, use_kernel: bool = False,
                  n_valid: Optional[int] = None,
                  mesh: Optional[Mesh] = None, valid_mask=None
                  ) -> Tuple[np.ndarray, np.ndarray, float]:
    """End-to-end on-device build over a (possibly padded) matrix.

    Same restart keys and per-iteration math as `kmeans`, but the whole
    restart loop is one jitted call: x is uploaded (or already device-
    resident, e.g. `SignatureStore.device_matrix`) once, sharded over the
    mesh's data axes when given, and only the winning (k,d) centroids +
    (n_valid,) assignment return to the host. Cluster-aligned compatible
    with `kmeans` (seeding uses the expansion form of the distances, so
    last-ulp rounding may differ — cluster structure does not).

    `valid_mask` ((N,) 0/1) extends the prefix `n_valid` mask to
    arbitrary holes — the tombstone bitmap of a store with evicted rows.
    The returned assignment still covers rows [0, n_valid); entries at
    dead rows are meaningless and must be masked by the caller.
    """
    if (mesh is not None and _row_shard_axes(mesh, x.shape[0]) is None
            and _data_axis_size(mesh) > 1):
        # a real data axis exists but the rows don't divide over it
        import warnings
        warnings.warn(
            f"kmeans_device: rows ({x.shape[0]}) do not divide the "
            f"mesh's {_data_axis_size(mesh)}-way data axis — running "
            "replicated; pad rows to a multiple of the data-axis size "
            "to shard", stacklevel=2)
    xd = shard_rows(x, mesh)
    n = int(xd.shape[0] if n_valid is None else n_valid)
    keys = jnp.stack([jax.random.PRNGKey(seed * 1000 + r)
                      for r in range(restarts)])
    if valid_mask is None:
        c, a, inertia, _ = kmeans_fit_restarts(
            keys, xd, k, iters, use_kernel, jnp.int32(n), mesh)
    else:
        c, a, inertia, _ = kmeans_fit_restarts(
            keys, xd, k, iters, use_kernel, None, mesh,
            valid_mask=jnp.asarray(valid_mask))
    return np.asarray(c), np.asarray(a[:n]), float(inertia)


def representatives(x: np.ndarray, centroids: np.ndarray,
                    assign: np.ndarray) -> np.ndarray:
    """Index of the member closest to each centroid (SimPoint rep points).
    Empty clusters get the globally closest point.

    One segment-reduce instead of a per-cluster Python loop: rows sort by
    (cluster, distance-to-own-centroid, row) and the first row of each
    cluster segment wins — same member and tie-breaking (lowest row index
    among equal distances) as the loop, without materializing (N,k,d).
    """
    n = x.shape[0]
    k = centroids.shape[0]
    if n == 0:
        return np.zeros(k, dtype=np.int64)
    xf = np.asarray(x, np.float64)
    cf = np.asarray(centroids, np.float64)
    d2_all = (np.sum(xf * xf, -1, keepdims=True) - 2.0 * (xf @ cf.T)
              + np.sum(cf * cf, -1)[None, :])              # (N, k)
    # empty-cluster fallback: global argmin per centroid column
    reps = d2_all.argmin(axis=0).astype(np.int64)
    assign = np.asarray(assign, np.int64)
    rows = np.arange(n)
    order = np.lexsort((rows, d2_all[rows, assign], assign))
    seg = assign[order]
    first = np.ones(n, bool)
    first[1:] = seg[1:] != seg[:-1]
    reps[seg[first]] = order[first]
    return reps
