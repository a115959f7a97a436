"""Where the program runs, and where it keeps what it builds to run
again: the one "is this a TPU" predicate and the one cache directory.
"""
from __future__ import annotations

import os

import jax

__all__ = ["on_tpu", "cache_dir", "configure_compile_cache"]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def on_tpu() -> bool:
    """Whether JAX's default backend is a TPU. Kernel routing, the
    Pallas interpret switch, the fused-optimizer decision and the
    device API all ask this one question."""
    return jax.default_backend() == "tpu"


def cache_dir() -> str:
    """The directory for JAX's persistent compilation cache and for
    what this package persists beside it (the Pallas autotune table,
    custom-op builds): ``JAX_COMPILATION_CACHE_DIR`` when set, else a
    fixed ``.jax_cache`` under the checkout. The path is part of the
    compile cache's key, so it is never built from a temp dir, a pid or
    the time."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(_REPO_ROOT, ".jax_cache")


def configure_compile_cache() -> None:
    """Turn the persistent compilation cache on at the default path.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    this sets nothing."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
