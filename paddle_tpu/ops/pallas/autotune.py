"""Kernel autotune: runtime config selection + persistent cache.

TPU-native analog of the reference's kernel autotuner
(paddle/phi/kernels/autotune/auto_tune_base.h + cache.h +
switch_autotune.cc): a kernel exposes candidate configs (Pallas block
sizes); the first execution of a given shape-key times each candidate on
the real device and caches the winner — in memory and on disk
(``autotune.json`` in :func:`core.backend.cache_dir`, beside the
compile cache), so later processes skip the sweep.

Off by default (FLAGS_kernel_autotune / env FLAGS_kernel_autotune=1):
each sweep costs one compile per candidate. Disabled automatically in
Pallas interpret mode (CPU tests) where timings are meaningless.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from ...core.backend import cache_dir
from ...core.flags import GLOBAL_FLAGS
from ._util import interpret_mode

GLOBAL_FLAGS.define("kernel_autotune", False,
                    "sweep Pallas kernel configs per shape and cache the "
                    "fastest (reference: phi/kernels/autotune)")

_CACHE_PATH = os.path.join(cache_dir(), "autotune.json")


class AutotuneCache:
    def __init__(self, path: str = _CACHE_PATH):
        self._path = path
        self._mem: Dict[str, Any] = {}
        self._loaded = False
        self._lock = threading.Lock()

    def _load(self):
        if self._loaded:
            return
        self._loaded = True
        try:
            with open(self._path) as f:
                data = json.load(f)
            if not isinstance(data, dict):
                raise ValueError(
                    f"expected a JSON object, got {type(data).__name__}")
            self._mem.update(data)
        except FileNotFoundError:
            pass
        except (OSError, ValueError, TypeError) as e:
            # a corrupt / truncated / wrong-shaped cache file must not
            # poison the import of the first tuned kernel: discard it
            # (the next sweep rewrites it) and say so once
            import warnings
            warnings.warn(
                f"discarding corrupt autotune cache {self._path} "
                f"({type(e).__name__}: {e}); re-tuning from scratch",
                RuntimeWarning, stacklevel=3)
            self._mem.clear()

    def get(self, key: str):
        with self._lock:
            self._load()
            return self._mem.get(key)

    def put(self, key: str, value):
        with self._lock:
            self._load()
            self._mem[key] = value
            # atomic publish: write a PRIVATE temp file (pid-suffixed so
            # concurrent processes never interleave writes into one
            # temp) and os.replace it over the cache — a reader can see
            # the old file or the new file, never a torn one
            tmp = f"{self._path}.{os.getpid()}.tmp"
            try:
                os.makedirs(os.path.dirname(self._path), exist_ok=True)
                with open(tmp, "w") as f:
                    json.dump(self._mem, f)
                os.replace(tmp, self._path)
            except OSError:
                try:                  # disk cache is best-effort, but a
                    os.unlink(tmp)    # half-written temp must not leak
                except OSError:
                    pass


_cache = AutotuneCache()


def resolve_candidate(cache_key: str, candidates: Sequence[Any],
                      build: Callable[[Any], Callable], args: Tuple):
    """Resolve one tunable config at a kernel call site.

    With FLAGS_kernel_autotune on: eager calls sweep on device via
    :func:`autotune`; traced / interpret-mode calls read the persistent
    cache (winners stored as an INDEX into the candidate list) and fall
    back to ``candidates[0]``. With the flag off (the default), the
    cache is NOT consulted and every call deterministically uses
    ``candidates[0]`` — the same convention flash attention's tuned
    path has always used, keeping default-flag numerics independent of
    whatever a cache file on disk happens to hold. The single shared
    home for this resolution — the fused decode-block kernels and the
    unfused paged-decode kernel key the SAME table, so the read
    convention must not be able to drift between them.
    """
    if len(candidates) == 1:
        return candidates[0]
    traced = any(isinstance(a, jax.core.Tracer)
                 for a in jax.tree_util.tree_leaves(args))
    if traced or interpret_mode() or \
            not GLOBAL_FLAGS.get("kernel_autotune"):
        hit = _cache.get(cache_key) \
            if GLOBAL_FLAGS.get("kernel_autotune") else None
        if hit is not None and 0 <= int(hit) < len(candidates):
            return candidates[int(hit)]
        return candidates[0]
    return autotune(cache_key, candidates, build, args)


def _sync(x):
    jax.block_until_ready(x)


def autotune(cache_key: str, candidates: Sequence[Any],
             build: Callable[[Any], Callable], args: Tuple,
             warmup: int = 1, iters: int = 3):
    """Pick the fastest candidate config for ``cache_key``.

    ``cache_key`` is the pre-formatted persistent-cache key — callers
    with a traced read path (e.g. flash attention's
    ``autotune_cache_key``) pass the same string to both the sweep and
    the read so the two encodings can never drift.

    ``build(config) -> fn``; fn(*args) is timed. Returns the winning
    config. With autotune disabled (or in interpret mode) returns
    ``candidates[0]`` without sweeping.
    """
    if not candidates:
        raise ValueError("no candidate configs")
    if len(candidates) == 1 or interpret_mode() or \
            not GLOBAL_FLAGS.get("kernel_autotune"):
        return candidates[0]
    ck = cache_key
    hit = _cache.get(ck)
    if hit is not None:
        # stored as index into the candidate list (configs are static)
        idx = int(hit)
        if 0 <= idx < len(candidates):
            return candidates[idx]
    best_i, best_t = 0, float("inf")
    for i, cfg in enumerate(candidates):
        try:
            fn = build(cfg)
            for _ in range(warmup):
                _sync(fn(*args))
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(*args)
            _sync(out)
            dt = (time.perf_counter() - t0) / iters
        except Exception:
            continue  # config invalid for this shape — skip
        if dt < best_t:
            best_i, best_t = i, dt
    if best_t == float("inf"):
        # every candidate failed (bad shapes / transient OOM): fall back
        # to the default WITHOUT poisoning the persistent cache
        return candidates[0]
    _cache.put(ck, best_i)
    return candidates[best_i]
