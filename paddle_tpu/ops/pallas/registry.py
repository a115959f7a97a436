"""Kernel registry: fused/unfused variant dispatch by shape class.

TPU-native analog of the reference's kernel-factory selection
(paddle/phi/core/kernel_factory.cc picks a kernel by backend/layout/
dtype key): an OP (e.g. ``decode_mlp_block``) owns several VARIANTS
(a Pallas megakernel, a jnp composition, ...), each with a ``supports``
predicate over a static shape/dtype/platform *meta* dict. ``dispatch``
returns the highest-priority supported variant — so the serving decode
step routes through the fused kernel exactly where it is legal (weights
fit the VMEM budget, supported head dim, real TPU) and falls back to
the unfused composition everywhere else (interpret mode, oversized
hidden dims) without the caller special-casing anything.

Dispatch happens at TRACE time with static inputs only, so a jitted
program bakes in one deterministic choice per shape class; anything
that can change the choice (platform, forced variant, the meta values)
must therefore key the caller's program cache.

``force()`` pins an op to a named variant for a ``with`` block —
tests and the audit catalog use it to trace the Pallas path on CPU
(interpret mode) where auto-dispatch would pick the composition.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

__all__ = ["KernelVariant", "KernelRegistry", "KERNELS"]


@dataclass
class KernelVariant:
    """One implementation of an op. ``supports(meta)`` returns True, or
    False, or a (False, reason) pair for ``explain`` — it must be pure
    in ``meta`` (dispatch is replayed at trace time and the result must
    be deterministic)."""
    op: str
    name: str
    fn: Callable
    priority: int = 0
    supports: Optional[Callable[[Dict[str, Any]], Any]] = None
    tags: Tuple[str, ...] = ()

    def check(self, meta: Dict[str, Any]):
        """-> (supported: bool, reason: str)."""
        if "pallas" in self.tags:
            from ._util import gspmd_refusal
            why = gspmd_refusal()
            if why is not None:
                return False, why
        if self.supports is None:
            return True, "unconditional"
        r = self.supports(dict(meta))
        if isinstance(r, tuple):
            ok, reason = r
            return bool(ok), str(reason)
        return bool(r), ("supported" if r else "unsupported")


class KernelRegistry:
    """op name -> priority-ordered variants. Registration is latest-
    wins per (op, variant) so a re-import or test monkey-register
    replaces rather than duplicates."""

    def __init__(self):
        self._ops: Dict[str, List[KernelVariant]] = {}
        self._forced = threading.local()
        self._cache_keys: Dict[str, Tuple[Tuple[str, ...],
                                          Dict[str, str]]] = {}

    # -- registration --------------------------------------------------
    def register(self, op: str, name: str, fn: Callable, *,
                 priority: int = 0, supports=None,
                 tags: Tuple[str, ...] = ()) -> KernelVariant:
        var = KernelVariant(op=op, name=name, fn=fn, priority=priority,
                            supports=supports, tags=tuple(tags))
        lst = [v for v in self._ops.get(op, []) if v.name != name]
        lst.append(var)
        lst.sort(key=lambda v: -v.priority)
        self._ops[op] = lst
        return var

    def declare_cache_key(self, op: str, fields, covers=None) -> None:
        """Declare the meta keys ``op``'s CALLERS fold into their
        program-cache / autotune keys — explicitly (route keys like
        generation.py's ``_PAGED_CACHE`` tuple, the trainer's
        ``_fused_train_key``) or implicitly via the jit trace signature
        (every shape/dtype-derived key). The ``DISPATCH_KEY_GAP``
        registry lint (:mod:`paddle_tpu.analysis.kernel_rules`)
        instruments ``supports()`` and flags any meta key it reads that
        this declaration does not cover — the thrice-fixed
        stale-dispatch-route class, turned from a review item into a
        gate. ``covers`` maps a derived key to the declared key that
        subsumes it (e.g. ``{"itemsize": "dtype"}``)."""
        self._cache_keys[op] = (tuple(fields), dict(covers or {}))

    def cache_key_decl(self, op: str):
        """(declared_fields, covers) for ``op``, or None if the op has
        never declared its dispatch-key coverage."""
        return self._cache_keys.get(op)

    def variant(self, op: str, name: str) -> KernelVariant:
        for v in self._ops.get(op, []):
            if v.name == name:
                return v
        raise KeyError(f"kernel op {op!r} has no variant {name!r} "
                       f"(registered: {[v.name for v in self._ops.get(op, [])]})")

    def variants(self, op: str) -> List[KernelVariant]:
        return list(self._ops.get(op, []))

    def ops(self) -> List[str]:
        return sorted(self._ops)

    # -- forcing (tests / audit catalog) -------------------------------
    def force(self, op: str, name: str):
        """Context manager pinning ``op`` to variant ``name`` (bypasses
        ``supports`` — the caller asserts legality, e.g. interpret-mode
        tests). Nested forces stack; exit restores the previous pin."""
        registry = self
        registry.variant(op, name)       # fail fast on a typo'd name

        class _Force:
            def __enter__(self_f):
                stack = getattr(registry._forced, "stack", None)
                if stack is None:
                    stack = registry._forced.stack = []
                stack.append((op, name))
                return registry

            def __exit__(self_f, *exc):
                registry._forced.stack.pop()
                return False
        return _Force()

    def forced_state(self) -> Tuple[Tuple[str, str], ...]:
        """Immutable snapshot of this thread's active force pins
        (outermost first). Dispatch consults the pin at TRACE time, so
        any caller that caches traced programs across calls must fold
        this snapshot into its cache key — otherwise a program traced
        under a pin is silently replayed for unpinned calls (and vice
        versa)."""
        return tuple(getattr(self._forced, "stack", []) or [])

    def _forced_for(self, op: str) -> Optional[str]:
        for o, n in reversed(getattr(self._forced, "stack", []) or []):
            if o == op:
                return n
        return None

    # -- dispatch ------------------------------------------------------
    @contextlib.contextmanager
    def record(self):
        """Collect ``{op: variant}`` for every :meth:`dispatch` this
        thread makes inside the block. Wrapped around the trace of a
        program it is the record of what that program compiled in:
        the same calls, not a second reading of the predicates."""
        prev = getattr(self._forced, "picked", None)
        picked = self._forced.picked = {}
        try:
            yield picked
        finally:
            self._forced.picked = prev

    def dispatch(self, op: str, meta: Dict[str, Any]
                 ) -> Tuple[str, Callable]:
        """Highest-priority supported variant -> (name, fn). Raises if
        the op is unknown or NO variant supports ``meta`` (every op
        should register an unconditional fallback)."""
        name, fn = self._select(op, meta)
        picked = getattr(self._forced, "picked", None)
        if picked is not None:
            picked[op] = name
        return name, fn

    def _select(self, op, meta):
        forced = self._forced_for(op)
        if forced is not None:
            return forced, self.variant(op, forced).fn
        cands = self._ops.get(op)
        if not cands:
            raise KeyError(f"no kernel variants registered for {op!r}")
        for v in cands:
            ok, _ = v.check(meta)
            if ok:
                return v.name, v.fn
        raise RuntimeError(
            f"no variant of {op!r} supports meta={meta!r}: "
            + "; ".join(f"{v.name}: {v.check(meta)[1]}" for v in cands))

    def explain(self, op: str, meta: Dict[str, Any]) -> List[Dict]:
        """Per-variant (name, priority, supported, reason, selected) —
        for tests and ``ServingEngine.metrics`` style introspection."""
        sel = None
        try:
            sel, _ = self._select(op, meta)
        except (KeyError, RuntimeError):
            pass
        out = []
        for v in self._ops.get(op, []):
            ok, reason = v.check(meta)
            out.append({"name": v.name, "priority": v.priority,
                        "supported": ok, "reason": reason,
                        "selected": v.name == sel})
        return out


KERNELS = KernelRegistry()
