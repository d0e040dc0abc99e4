"""JAX's persistent compilation cache for the repo's entry points.

Entry points (`chip_smoke.py`, `benchmarks/run.py`, `repro.launch.train`)
call `enable_compile_cache()` at the start of `main`, never at import, so
tests and library users keep JAX's own default.

Where the cache lives:
  * `JAX_COMPILATION_CACHE_DIR` set: JAX already reads that directory from
    the environment, and nothing else is set;
  * otherwise `.jax_cache/` at the root of the checkout. The path is fixed
    (no temp name, PID or time in it): it is part of the cache key, so a
    directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
from typing import Mapping

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/utils/compile_cache.py -> the checkout root
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def compile_cache_dir(environ: Mapping[str, str] = os.environ) -> str:
    """The directory the cache uses under `environ`."""
    return environ.get(ENV_VAR) or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on (see module docstring); returns its
    directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
