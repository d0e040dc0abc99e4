"""Production mesh definitions.

Kept as FUNCTIONS (not module constants) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any jax
initialization.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod mesh: 16×16 (256 chips) per pod; 2 pods = 512 chips.

    Axes: "data" carries DP + FSDP weight sharding, "model" carries TP/EP,
    "pod" (multi-pod) is the slow-link DP axis (gradient compression lives
    there)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist (tests / single host): 1D data mesh."""
    n = len(jax.devices())
    return _make_mesh((n,), ("data",))
