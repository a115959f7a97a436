"""Shared Pallas helpers."""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.backend import on_tpu
from ...core.flags import GLOBAL_FLAGS

# Routes the training hot path (chunked lm-head+CE, SwiGLU, RMSNorm
# backward, the residual+norm epilogue) through the fused Pallas kernels
# where the registry supports them. Defined here — the ONE shared home — because
# both norms.py and fused_train.py consult it and neither may import
# the other.
GLOBAL_FLAGS.define(
    "fused_train", True,
    "route the training hot path (fused linear+cross-entropy, SwiGLU, "
    "RMSNorm backward) through the fused Pallas training kernels where "
    "the registry supports them (0 = always the unfused composition, "
    "for A/B diagnosis)")


def fused_train_mode(mode=None) -> str:
    """Normalize a fused-train mode knob to ``auto | pallas | ref``.

    ``None`` reads FLAGS_fused_train (the global default); explicit
    ``False``/``0``/"ref" pins the unfused composition, "pallas"/
    "force" pins the Pallas kernels (tests / audit tracing on CPU),
    ``True``/"auto" means registry dispatch. Dispatch consults this at
    TRACE time, so any caller caching traced programs must fold the
    resolved mode (and ``KERNELS.forced_state()``) into its cache key.
    """
    if mode is None:
        mode = GLOBAL_FLAGS.get("fused_train")
    if mode in (False, 0, "ref"):
        return "ref"
    if mode in ("pallas", "force"):
        return "pallas"
    if mode in (True, 1, None, "auto"):
        return "auto"
    raise ValueError(
        f"fused_train mode must be auto|pallas|ref, got {mode!r}")


def dispatch_fused_variant(op: str, meta, mode=None):
    """The ONE fused-training mode contract: resolve ``op`` to a
    callable — registry dispatch in "auto" (highest-priority variant
    whose ``supports(meta)`` admits the shape class), a pinned variant
    for "pallas"/"ref". Every fused-train op wrapper
    (``fused_linear_ce``, ``fused_swiglu``, ``residual_rms_norm``, the
    RMSNorm backward) routes through here so the contract cannot drift
    between copies."""
    from .registry import KERNELS
    mode = fused_train_mode(mode)
    if mode == "auto":
        return KERNELS.dispatch(op, meta)[1]
    return KERNELS.variant(
        op, "pallas_fused" if mode == "pallas" else "unfused").fn

# Pages-per-grid-step autotune candidates of the fused prefill attention
# kernel (a grid step fetches this many pages through BlockSpecs; pages
# are processed sequentially, so the choice only affects pipelining,
# never numerics). The paged-decode kernel fetches for itself, reduces
# a block of pages at once, and has its own space
# (``paged_attention.PAGE_BLOCK_CANDIDATES``).
PAGE_STEP_CANDIDATES = (1, 2, 4)


def online_softmax_page_update(q, k, v, pg, bs, seq_len, scale,
                               kv, groups, m_scr, l_scr, acc_scr):
    """One KV page's online-softmax update against ``m/l/acc`` scratch.

    The page-streaming reduction body of the fused prefill attention
    kernel (its paged history). The paged-decode kernel reduces a block
    of pages at once (``paged_attention._block_update``); its tests keep
    this update as a second reference.
    ``q`` [H, hd], ``k``/``v`` [BS, KV, hd] — all f32 (callers dequant/
    upcast first); ``pg`` is the page index, tokens at/after
    ``seq_len`` are masked out. All literals explicitly f32/i32: the
    body can be retraced at LOWERING time outside the no_x64 window.
    """
    f32 = jnp.float32
    tok = pg * jnp.int32(bs) + jax.lax.broadcasted_iota(
        jnp.int32, (1, bs), 1)[0]
    valid = tok < seq_len                                 # (BS,)
    s_rows = []
    for kvh in range(kv):
        qg = q[kvh * groups:(kvh + 1) * groups, :]        # (g, hd)
        kk = k[:, kvh, :]                                 # (BS, hd)
        s_rows.append(jax.lax.dot_general(
            qg, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=f32))                  # (g, BS)
    s = jnp.concatenate(s_rows, axis=0) * f32(scale)      # (H, BS)
    s = jnp.where(valid[None, :], s, f32(-jnp.inf))
    m_prev = m_scr[:]                                     # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # a fully-invalid page cannot happen (callers guard with pl.when):
    # all--inf rows only arise when seq_len <= pg*bs — excluded
    p = jnp.exp(s - m_new)
    p = jnp.where(valid[None, :], p, f32(0.0))
    alpha = jnp.exp(m_prev - m_new)
    l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
    pv_rows = []
    for kvh in range(kv):
        ps = p[kvh * groups:(kvh + 1) * groups, :]        # (g, BS)
        vv = v[:, kvh, :]                                 # (BS, hd)
        pv_rows.append(jax.lax.dot_general(
            ps, vv, (((1,), (0,)), ((), ())),
            preferred_element_type=f32))                  # (g, hd)
    acc_scr[:] = acc_scr[:] * alpha + jnp.concatenate(pv_rows, axis=0)
    m_scr[:] = m_new


# Process-wide override for Pallas interpret mode. None = auto (off-TPU →
# interpret). Tests set False to compile the kernels for a described,
# unattached chip from a process whose default backend is the CPU.
_FORCE_INTERPRET: bool | None = None


def set_force_interpret(value: bool | None) -> None:
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = value


def interpret_mode() -> bool:
    """Whether pallas_call sites should run in interpreter mode."""
    if _FORCE_INTERPRET is not None:
        return _FORCE_INTERPRET
    return not on_tpu()


# ---------------------------------------------------------------------------
# GSPMD scope: a compiled Mosaic kernel cannot be partitioned for you
# ---------------------------------------------------------------------------
_GSPMD = threading.local()


@contextlib.contextmanager
def gspmd_program(n_devices: int):
    """Trace-time scope around the body of a ``jax.jit`` program that
    GSPMD partitions over ``n_devices`` (the sharded ``Trainer`` step).
    JAX refuses to lower a Mosaic kernel there ("Mosaic kernels cannot
    be automatically partitioned. Please wrap the call in a
    shard_map."), so inside a scope over more than one device
    :func:`gspmd_refusal` makes kernel routing take the compositions.
    A ``shard_map`` body (tensor-parallel serving) is manual over the
    whole mesh, lowers its kernels per shard, and needs no scope."""
    prev = getattr(_GSPMD, "n", 1)
    _GSPMD.n = int(n_devices)
    try:
        yield
    finally:
        _GSPMD.n = prev


def gspmd_refusal():
    """None, or why a compiled Mosaic kernel cannot be emitted into the
    program being traced. Read by the registry for every variant tagged
    "pallas" and by the direct routers (``pallas_route``)."""
    n = getattr(_GSPMD, "n", 1)
    if n > 1 and not interpret_mode():
        return (f"GSPMD program over {n} devices: Mosaic kernels cannot "
                "be automatically partitioned (wrap the call in a "
                "shard_map)")
    return None


def pallas_route() -> bool:
    """Whether a direct router (rms_norm, flash_attention, the Mamba-2
    state launches) takes its Pallas kernel: a TPU backend, and a
    program a Mosaic kernel can be lowered into."""
    return on_tpu() and gspmd_refusal() is None


# ---------------------------------------------------------------------------
# kernel-launch capture: the geometry-audit layer
# ---------------------------------------------------------------------------
def fused_vmem_budget() -> int:
    """The scoped-VMEM budget the fused kernels' dispatch predicates
    honor (``PADDLE_TPU_FUSED_VMEM_BUDGET``, default 10 MiB of the
    16 MiB window — the rest stays free for double-buffered pipeline
    windows and fp32 scratch). The ONE shared home: supports()
    predicates, autotune candidate lists, program-cache route keys and
    the kernel-geometry auditor all read this value, so it cannot
    drift between them."""
    return int(os.environ.get("PADDLE_TPU_FUSED_VMEM_BUDGET",
                              10 * 2 ** 20))


@dataclasses.dataclass(frozen=True)
class KernelOperand:
    """One blocked operand of a captured Pallas launch: the array's
    abstract geometry plus its BlockSpec's (block_shape, index_map).
    ``block_shape`` None = whole-array operand (memory-space spec, no
    index map). ``space`` is a best-effort label ("vmem"/"smem"/"any").
    An operand in ``any`` space stays in HBM and the kernel copies out
    of it for itself: ``fetched_bytes(*prefetch)`` is the launch's own
    declaration of how many bytes that is, given the scalar-prefetch
    operands (``audited_pallas_call(fetched_bytes=...)``)."""
    shape: Tuple[int, ...]
    dtype: str
    block_shape: Optional[Tuple] = None
    index_map: Optional[Callable] = None
    space: str = "vmem"
    fetched_bytes: Optional[Callable] = None


@dataclasses.dataclass
class KernelLaunchSpec:
    """Trace-time record of one ``pl.pallas_call`` launch: everything
    the kernel-geometry rules (:mod:`paddle_tpu.analysis.kernel_rules`)
    need to prove grid coverage, block bounds, write injectivity and
    the VMEM window budget — captured at the audited_pallas_call
    boundary, never by re-parsing kernel code."""
    name: str
    grid: Tuple[int, ...]
    num_scalar_prefetch: int = 0
    prefetch: Tuple[Tuple[Tuple[int, ...], str], ...] = ()
    inputs: Tuple[KernelOperand, ...] = ()
    outputs: Tuple[KernelOperand, ...] = ()
    scratch: Tuple[Tuple[Tuple[int, ...], str, str], ...] = ()
    accum_outputs: Tuple[int, ...] = ()
    vmem_budget: int = 0
    interpret: bool = False
    input_output_aliases: Dict[int, int] = dataclasses.field(
        default_factory=dict)
    kernel: Optional[Callable] = None


_CAPTURE = threading.local()


class capture_kernel_launches:
    """Context manager collecting every :class:`KernelLaunchSpec`
    recorded by :func:`audited_pallas_call` while tracing under it.

    ``with capture_kernel_launches() as specs: jax.eval_shape(fn, ...)``
    — capture is thread-local and stack-nested (an inner capture also
    feeds the outer one), and costs nothing when no capture is active
    (the serving/training hot paths never pay for the audit layer)."""

    def __init__(self):
        self.specs = []

    def __enter__(self):
        stack = getattr(_CAPTURE, "stack", None)
        if stack is None:
            stack = _CAPTURE.stack = []
        stack.append(self.specs)
        return self.specs

    def __exit__(self, *exc):
        _CAPTURE.stack.pop()
        return False


def _record_launch(spec: KernelLaunchSpec) -> None:
    for sink in getattr(_CAPTURE, "stack", []) or []:
        sink.append(spec)


def _space_label(block_spec) -> str:
    ms = getattr(block_spec, "memory_space", None)
    if ms is None:
        return "vmem"
    s = str(ms).lower()
    for label in ("smem", "vmem", "any"):
        if label in s:
            return label
    return s or "vmem"


def _operand(arg, block_spec, fetched_bytes=None) -> KernelOperand:
    shape = tuple(getattr(arg, "shape", ()) or ())
    dtype = str(getattr(arg, "dtype", "?"))
    bs = getattr(block_spec, "block_shape", None)
    return KernelOperand(
        shape=shape, dtype=dtype,
        block_shape=tuple(bs) if bs is not None else None,
        index_map=getattr(block_spec, "index_map", None),
        space=_space_label(block_spec), fetched_bytes=fetched_bytes)


def _scratch_record(s):
    shape = tuple(getattr(s, "shape", ()) or ())
    try:
        dtype = str(jnp.dtype(getattr(s, "dtype", None)))
    except TypeError:
        dtype = str(getattr(s, "dtype", "?"))
    ms = str(getattr(s, "memory_space", "")).lower() \
        or type(s).__name__.lower()
    # a semaphore array (DMA completion) lives in semaphore memory:
    # like SMEM it is no part of the VMEM window
    space = next((label for label in ("smem", "semaphore")
                  if label in ms), "vmem")
    return (shape, dtype, space)


def audited_pallas_call(kernel, *, name: str = None, grid,
                        in_specs, out_specs, out_shape,
                        scratch_shapes=None, num_scalar_prefetch: int = 0,
                        input_output_aliases=None, interpret: bool = False,
                        accum_outputs: Tuple[int, ...] = (),
                        fetched_bytes: Optional[Dict[int, Callable]] = None):
    """The ONE ``pl.pallas_call`` gateway for every kernel in this
    package (the coverage test asserts no other call site exists).

    Signature-compatible with the plain-grid ``pallas_call`` kwargs;
    ``num_scalar_prefetch > 0`` builds the
    ``pltpu.PrefetchScalarGridSpec`` internally so scalar-prefetch
    launches capture through the same path. ``accum_outputs`` DECLARES
    the output indices whose index map intentionally revisits a block
    across grid steps (sequential accumulation / write-once-at-last-
    step patterns) — the WRITE_RACE rule flags any undeclared revisit.
    ``fetched_bytes`` DECLARES, per input index, what the kernel copies
    for itself out of an input it keeps in ``pl.ANY`` space (see
    :class:`KernelOperand`): such an input has no blocks to count.

    When a :class:`capture_kernel_launches` context is active on this
    thread, invoking the returned callable records a
    :class:`KernelLaunchSpec` (grid, per-operand BlockSpecs + avals,
    scratch shapes, the active VMEM budget) before delegating to the
    real ``pl.pallas_call``; with no capture active the only overhead
    is one Python frame at trace time.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    in_specs = list(in_specs)
    out_specs_flat = (list(out_specs)
                      if isinstance(out_specs, (list, tuple))
                      else [out_specs])
    out_shape_flat = (list(out_shape)
                      if isinstance(out_shape, (list, tuple))
                      else [out_shape])
    scratch = list(scratch_shapes) if scratch_shapes else []

    kname = name
    if kname is None:
        base = kernel.func if isinstance(kernel, functools.partial) \
            else kernel
        kname = getattr(base, "__name__", "pallas_kernel")

    # ``name`` reaches the compiled program (the custom call's
    # kernel_name and op_name), so a kernel can be found by its audited
    # launch name in ``compiled.as_text()`` and in a profiler trace
    if num_scalar_prefetch:
        kw = {"input_output_aliases": dict(input_output_aliases)} \
            if input_output_aliases else {}
        call = pl.pallas_call(
            kernel, name=kname,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=num_scalar_prefetch,
                grid=tuple(grid), in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=tuple(scratch)),
            out_shape=out_shape, interpret=interpret, **kw)
    else:
        kw: Dict[str, Any] = dict(grid=tuple(grid), in_specs=in_specs,
                                  out_specs=out_specs,
                                  out_shape=out_shape,
                                  interpret=interpret)
        if scratch:
            kw["scratch_shapes"] = scratch
        if input_output_aliases:
            kw["input_output_aliases"] = dict(input_output_aliases)
        call = pl.pallas_call(kernel, name=kname, **kw)

    def wrapped(*args):
        if getattr(_CAPTURE, "stack", None):
            pre = args[:num_scalar_prefetch]
            blocked = args[num_scalar_prefetch:]
            _record_launch(KernelLaunchSpec(
                name=kname, grid=tuple(int(g) for g in grid),
                num_scalar_prefetch=int(num_scalar_prefetch),
                prefetch=tuple(
                    (tuple(getattr(a, "shape", ()) or ()),
                     str(getattr(a, "dtype", "?"))) for a in pre),
                inputs=tuple(
                    _operand(a, s, (fetched_bytes or {}).get(i))
                    for i, (a, s) in enumerate(zip(blocked, in_specs))),
                outputs=tuple(_operand(sh, s) for sh, s in
                              zip(out_shape_flat, out_specs_flat)),
                scratch=tuple(_scratch_record(s) for s in scratch),
                accum_outputs=tuple(accum_outputs),
                vmem_budget=fused_vmem_budget(),
                interpret=bool(interpret),
                input_output_aliases=dict(input_output_aliases or {}),
                kernel=kernel))
        return call(*args)

    return wrapped


def compiled_kernel_counts(hlo_text: str) -> Dict[str, int]:
    """{audited launch name: count} of the Pallas custom calls in a
    compiled program's ``as_text()``. ``audited_pallas_call`` passes its
    ``name`` to ``pl.pallas_call``, which puts it in the custom call's
    ``op_name`` (``.../<name>/pallas_call``, inside ``jvp(...)`` /
    ``transpose(...)`` wrappers for a custom_vjp's rules); this is the
    one reduction from program text back to those names."""
    import re
    found: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        m = re.search(r'op_name="([^"]*)/pallas_call', line)
        if m:
            name = re.findall(r"[a-z][a-z0-9_]*",
                              m.group(1).split("/")[-1])[-1]
            found[name] = found.get(name, 0) + 1
    return found


def no_x64(fn):
    """Trace ``fn`` with x64 disabled.

    paddle_tpu enables jax_enable_x64 globally for Paddle's int64/float64
    dtype parity, but under x64 Mosaic emits i64 scalars in the kernel
    wrapper that the TPU backend fails to legalize ("func.return (i32,
    i64)" — 32-bit SREGs on v5e). Kernel inputs are all <=32-bit, so
    tracing the pallas_call under x64=False is semantics-preserving and
    makes the kernels compile on real chips.
    """
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.enable_x64(False):
            return fn(*args, **kwargs)
    return wrapper
