# Pallas TPU kernels for the framework's compute hot spots:
#   wkv             — Stage-1 RWKV delta-rule recurrence (chunked, state in VMEM)
#   flash_attention — streaming-softmax attention for the zoo archs
#   set_attention   — fused masked, frequency-weighted set attention for the
#                     Stage-2 Set Transformer SAB/PMA (scores stay in VMEM)
#   kmeans_assign   — tiled distance+argmin for universal clustering
# Each package has: <name>.py (pl.pallas_call + BlockSpec), ops.py (the
# public op), ref.py (pure-jnp oracle used by the allclose test sweeps).
#
# Where a kernel runs is the platform's choice, not a caller's: each ops.py
# wraps its kernel in `compiled_on_tpu`, which lowers it compiled for a TPU
# and interpreted for anything else. A caller chooses only kernel or jnp
# reference, through one two-valued option per family, which
# `check_backend` holds to its values where the value enters: set
# attention impl="xla"|"pallas" (SemanticBBVPipeline, Stage2Engine),
# knowledge base assign_impl="reference"|"pallas" and
# build_impl="device"|"device_kernel" (KnowledgeBase), Stage-1 timemix
# impl="scan"|"pallas" (timemix_apply).
from __future__ import annotations

import functools
import inspect
from typing import Callable, Sequence

import jax


def check_backend(option: str, value: str, choices: Sequence[str]) -> str:
    """`value` if it is one of `choices`, else a ValueError naming them."""
    if value not in choices:
        raise ValueError(f"{option} must be one of {tuple(choices)}, "
                         f"got {value!r}")
    return value


def compiled_on_tpu(kernel: Callable) -> Callable:
    """The op of `kernel(*arrays, interpret=..., **static)`: compiled
    where it lowers for a TPU, interpreted where it lowers for anything
    else. The choice is `jax.lax.platform_dependent`'s, made at lowering,
    so the compiler only ever sees the branch for its own platform. The
    op is jitted under the kernel's own name, which the device trace
    shows as the kernel's HLO instruction name."""
    static = [n for n, p in inspect.signature(kernel).parameters.items()
              if p.kind is p.KEYWORD_ONLY and n != "interpret"]

    @functools.wraps(kernel)
    def op(*arrays, **kw):
        return jax.lax.platform_dependent(
            *arrays,
            tpu=functools.partial(kernel, interpret=False, **kw),
            default=functools.partial(kernel, interpret=True, **kw))
    return jax.jit(op, static_argnames=static)
