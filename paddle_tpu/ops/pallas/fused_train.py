"""Fused training-path Pallas kernels (Liger-kernel style).

BENCH_r05 pinned llama training MFU at ~2.6% — the step is bound by HBM
traffic, not FLOPs. Per Liger Kernel (arXiv:2410.10989), the dominant
term is the lm-head + cross-entropy: materializing ``[T, V]`` logits
(and their gradient) moves hundreds of MB per step through HBM that a
chunked fused kernel never has to. This module is the training-side
mirror of :mod:`.fused_decode_block`:

- ``fused_linear_ce``: chunked lm-head + cross entropy with a
  ``custom_vjp``. Forward streams (token-chunk × vocab-chunk) logit
  tiles through VMEM computing an online logsumexp and the picked-label
  term; backward RECOMPUTES each logit tile and contracts it into
  ``grad_hidden`` and ``grad_head`` in the same pass — neither the
  ``[T, V]`` logits nor their gradient ever touch HBM. Replaces the
  XLA ``lax.scan`` half-measure in ``models/_common.py`` (which
  rematerializes chunk logits in backward but still round-trips the
  f32 logit chunks and per-chunk softmax through HBM, with no fused
  grad). ``ignore_index`` semantics identical to
  ``masked_cross_entropy``: any negative label (-1, -100, ...) is
  ignored, the loss is the masked token mean.
- ``fused_swiglu``: SwiGLU forward and backward as one Pallas kernel
  each (f32 interior, tiled over the intermediate dim like
  ``decode_mlp_block``), so the backward is one fused pass instead of
  XLA's sigmoid/product chain re-streaming g/u.

Both ops register in the kernel registry with ``supports(meta)``
predicates (VMEM-budget aware, like the decode megakernels) and the
EXACT pre-fusion composition as the ``unfused`` fallback, so dispatch
falling back — interpret mode, oversized tiles — is bit-identical to
the pre-fusion training path. The RMSNorm backward + residual+norm
epilogue that complete the set live in :mod:`.norms`.

Dispatch happens at TRACE time (flag + registry state), so train-step
program caches key on ``fused_train_mode()`` + ``KERNELS.forced_state()``
(see ``distributed/trainer.py`` / ``jit/train_step.py``).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import (audited_pallas_call, dispatch_fused_variant,
                    fused_vmem_budget, interpret_mode as _interpret,
                    no_x64)
from .registry import KERNELS

__all__ = [
    "fused_linear_ce", "linear_ce_ref", "linear_ce_pallas",
    "linear_ce_autotune_key", "fused_swiglu", "swiglu_ref",
    "swiglu_pallas", "swiglu_autotune_key", "ce_meta", "swiglu_meta",
]


# the SAME scoped-VMEM budget knob the decode megakernels honor
# (``PADDLE_TPU_FUSED_VMEM_BUDGET``) — one envelope for all fused kernels
_vmem_budget = fused_vmem_budget


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# ---------------------------------------------------------------------------
# fused linear + cross entropy
# ---------------------------------------------------------------------------
def _ce_fwd_kernel(x_ref, h_ref, lab_ref, lse_ref, pick_ref,
                   m_scr, l_scr, p_scr, *, v_real, bt, bv):
    """Grid (nv, nt), token chunks INNER: the head tile (the big
    operand) is fetched once per vocab chunk and stays VMEM-resident
    while every token chunk streams past it. Per-token online-lse
    state lives in (T_pad, 1) scratch (persists across the whole
    sequential grid). All literals explicitly f32/i32 — the body can
    be retraced at lowering time outside the no_x64 window."""
    j = pl.program_id(0)                       # vocab chunk
    i = pl.program_id(1)                       # token chunk (inner)
    f32 = jnp.float32
    sl = pl.ds(i * bt, bt)

    @pl.when(j == 0)
    def _init():
        m_scr[sl] = jnp.full((bt, 1), -jnp.inf, f32)
        l_scr[sl] = jnp.zeros((bt, 1), f32)
        p_scr[sl] = jnp.zeros((bt, 1), f32)

    s = jnp.dot(x_ref[:], h_ref[:],
                preferred_element_type=f32)             # (bt, bv)
    cols = jnp.int32(j) * jnp.int32(bv) + jax.lax.broadcasted_iota(
        jnp.int32, (bt, bv), 1)
    # vocab padding: head pad columns are zeros → logit 0 would corrupt
    # the logsumexp; mask them to -inf (a real label never points here)
    s = jnp.where(cols < jnp.int32(v_real), s, f32(-jnp.inf))
    lab = lab_ref[:]                                    # (bt, 1) i32
    p_scr[sl] = p_scr[sl] + jnp.sum(
        jnp.where(cols == lab, s, f32(0.0)), axis=1, keepdims=True)
    m_prev = m_scr[sl]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    l_scr[sl] = l_scr[sl] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(s - m_new), axis=1, keepdims=True)
    m_scr[sl] = m_new

    @pl.when(j == pl.num_programs(0) - 1)
    def _fin():
        lse_ref[:] = m_scr[sl] + jnp.log(l_scr[sl])
        pick_ref[:] = p_scr[sl]


def _ce_tile(x_ref, h_ref, lab_ref, lse_ref, coef_ref, j, bv, v_real):
    """Recompute one (bt, bv) softmax-grad tile: P = (softmax − onehot)
    · coef · valid. Shared by both backward kernels — the recompute
    contract has exactly one definition. Pad columns: s = −inf →
    p = 0, onehot never matches → the tile contributes nothing."""
    f32 = jnp.float32
    s = jnp.dot(x_ref[:], h_ref[:], preferred_element_type=f32)
    cols = jnp.int32(j) * jnp.int32(bv) + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(cols < jnp.int32(v_real), s, f32(-jnp.inf))
    lab = lab_ref[:]                                    # (bt, 1)
    p = jnp.exp(s - lse_ref[:])
    onehot = (cols == lab).astype(f32)
    valid = (lab >= 0).astype(f32)                      # (bt, 1)
    return (p - onehot) * (valid * coef_ref[0, 0])


def _ce_dx_kernel(x_ref, h_ref, lab_ref, lse_ref, coef_ref, dx_ref,
                  acc_scr, *, v_real, bv):
    """Grid (nt, nv), vocab INNER: ``grad_hidden`` accumulates across
    vocab chunks in (bt, D) f32 scratch, written once per token
    chunk."""
    j = pl.program_id(1)
    P = _ce_tile(x_ref, h_ref, lab_ref, lse_ref, coef_ref, j, bv, v_real)

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    acc_scr[:] = acc_scr[:] + jax.lax.dot_general(
        P, h_ref[:].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # (bt, D)

    @pl.when(j == pl.num_programs(1) - 1)
    def _fin():
        dx_ref[:] = acc_scr[:].astype(dx_ref.dtype)


def _ce_dh_kernel(x_ref, h_ref, lab_ref, lse_ref, coef_ref, dh_ref,
                  acc_scr, *, v_real, bv):
    """Grid (nv, nt), token INNER: ``grad_head`` accumulates across
    token chunks in (D, bv) f32 scratch, written once per vocab
    chunk."""
    j = pl.program_id(0)
    i = pl.program_id(1)
    P = _ce_tile(x_ref, h_ref, lab_ref, lse_ref, coef_ref, j, bv, v_real)

    @pl.when(i == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    acc_scr[:] = acc_scr[:] + jax.lax.dot_general(
        x_ref[:].astype(jnp.float32), P, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (D, bv)

    @pl.when(i == pl.num_programs(1) - 1)
    def _fin():
        dh_ref[:] = acc_scr[:].astype(dh_ref.dtype)


# (block_t, block_v) candidates; filtered against the VMEM budget like
# the fused-MLP tiles (the sweep and the predicate consume one list)
# (64, 256) last: the one tile inside the budget at D=4096 bf16 (the
# published Llama-7B width), where (128, 256) models 10.1 MiB; compiled
# for v5e it passes there, as does every narrower model's first fit
_CE_BLOCK_CANDIDATES = ((256, 512), (128, 512), (256, 1024),
                        (512, 512), (128, 256), (64, 256))


def linear_ce_autotune_key(T, D, V, dtype, budget=None) -> str:
    """Persistent autotune-cache key for the fused linear+CE block
    pair. The VMEM budget keys the entry (winners are indices into the
    budget-fitting candidate list — the ``mlp_autotune_key``
    convention)."""
    budget = _vmem_budget() if budget is None else int(budget)
    return f"fused_linear_ce|{(int(T), int(D), int(V), str(jnp.dtype(dtype)), budget)}"


def _ce_vmem_need(bt, bv, D, itemsize):
    """Worst-case per-grid-step VMEM bytes across the fwd/dx/dh
    kernels at tile (bt, bv): double-buffered x + head tiles, the f32
    logit tile, and the larger of the two f32 grad accumulators."""
    io = 2 * (bt * D * itemsize + D * bv * itemsize)
    logits = bt * bv * 4
    acc = max(bt * D, D * bv) * 4
    return io + logits + acc


def _ce_fitting_candidates(T, D, itemsize, budget=None):
    budget = _vmem_budget() if budget is None else int(budget)
    return [(bt, bv) for bt, bv in _CE_BLOCK_CANDIDATES
            if _ce_vmem_need(bt, bv, D, itemsize) <= budget]


def _ce_blocks(x2, head, lab):
    """Resolve (block_t, block_v) — budget-fitting candidates through
    the shared autotune table (eager calls sweep forward+backward,
    traced calls read the persisted winner), clamped to the problem."""
    T, D = x2.shape
    V = head.shape[1]
    it = jnp.dtype(x2.dtype).itemsize
    # ONE budget read per trace: fitting list + autotune key must see
    # the same value (the budget-in-meta contract)
    budget = _vmem_budget()
    cands = _ce_fitting_candidates(T, D, it, budget) \
        or [_CE_BLOCK_CANDIDATES[-1]]
    # clamping tiny problems dedups candidates that collapse together
    cands = list(dict.fromkeys(
        (min(bt, _round_up(T, 8)), min(bv, _round_up(V, 128)))
        for bt, bv in cands))
    if len(cands) == 1:
        return cands[0]
    from .autotune import resolve_candidate
    ck = linear_ce_autotune_key(T, D, V, x2.dtype, budget)

    def build(cfg):
        bt_, bv_ = cfg

        def fn(a, h, l):
            # time the full fwd+bwd the trainer runs, not just fwd
            return jax.value_and_grad(
                lambda aa, hh: linear_ce_pallas(aa, hh, l, block_t=bt_,
                                                block_v=bv_),
                argnums=(0, 1))(a, h)
        return fn
    return resolve_candidate(ck, cands, build, (x2, head, lab))


@no_x64
def _ce_fwd_call(x2, head, lab2, v_real, bt, bv):
    """Run the forward kernel on the PADDED 2-D problem:
    x2 (T_pad, D), head (D, V_pad), lab2 (T_pad, 1) →
    (lse, picked) both (T_pad, 1) f32."""
    T, D = x2.shape
    V = head.shape[1]
    nt, nv = T // bt, V // bv
    lse, pick = audited_pallas_call(
        functools.partial(_ce_fwd_kernel, v_real=v_real, bt=bt, bv=bv),
        name="linear_ce_fwd",
        # both per-token outputs are revisited every vocab chunk
        # (online-lse state in scratch, written at the last chunk)
        accum_outputs=(0, 1),
        grid=(nv, nt),
        in_specs=[pl.BlockSpec((bt, D), lambda j, i: (i, 0)),
                  pl.BlockSpec((D, bv), lambda j, i: (0, j)),
                  pl.BlockSpec((bt, 1), lambda j, i: (i, 0))],
        out_specs=[pl.BlockSpec((bt, 1), lambda j, i: (i, 0)),
                   pl.BlockSpec((bt, 1), lambda j, i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, 1), jnp.float32),
                   jax.ShapeDtypeStruct((T, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((T, 1), jnp.float32)] * 3,
        interpret=_interpret(),
    )(x2, head, lab2)
    return lse, pick


@no_x64
def _ce_bwd_call(x2, head, lab2, lse, coef, v_real, bt, bv):
    """Both backward kernels on the padded problem → (dx, dhead)."""
    T, D = x2.shape
    V = head.shape[1]
    nt, nv = T // bt, V // bv
    args = (x2, head, lab2, lse, coef)
    dx = audited_pallas_call(
        functools.partial(_ce_dx_kernel, v_real=v_real, bv=bv),
        name="linear_ce_bwd_dx",
        # grad_hidden accumulates across vocab chunks in scratch
        accum_outputs=(0,),
        grid=(nt, nv),
        in_specs=[pl.BlockSpec((bt, D), lambda i, j: (i, 0)),
                  pl.BlockSpec((D, bv), lambda i, j: (0, j)),
                  pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((bt, D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, D), x2.dtype),
        scratch_shapes=[pltpu.VMEM((bt, D), jnp.float32)],
        interpret=_interpret(),
    )(*args)
    dh = audited_pallas_call(
        functools.partial(_ce_dh_kernel, v_real=v_real, bv=bv),
        name="linear_ce_bwd_dh",
        # grad_head accumulates across token chunks in scratch
        accum_outputs=(0,),
        grid=(nv, nt),
        in_specs=[pl.BlockSpec((bt, D), lambda j, i: (i, 0)),
                  pl.BlockSpec((D, bv), lambda j, i: (0, j)),
                  pl.BlockSpec((bt, 1), lambda j, i: (i, 0)),
                  pl.BlockSpec((bt, 1), lambda j, i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda j, i: (0, 0))],
        out_specs=pl.BlockSpec((D, bv), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((D, V), head.dtype),
        scratch_shapes=[pltpu.VMEM((D, bv), jnp.float32)],
        interpret=_interpret(),
    )(*args)
    return dx, dh


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _linear_ce_vjp(x2, head, lab2, bt, bv):
    loss, _ = _linear_ce_fwd(x2, head, lab2, bt, bv)
    return loss


def _masked_mean(lse, pick, lab2):
    """(lse − picked) masked-mean — f32 throughout, identical staging
    to ``masked_cross_entropy``'s ``/ max(count, 1)``."""
    valid = lab2 >= 0
    ce = jnp.where(valid[:, 0], (lse - pick)[:, 0], jnp.float32(0.0))
    count = jnp.sum(valid).astype(jnp.float32)
    return jnp.sum(ce) / jnp.maximum(count, jnp.float32(1.0)), count


def _linear_ce_fwd(x2, head, lab2, bt, bv):
    v_real = head.shape[1]
    vp = _round_up(v_real, bv)
    headp = head if vp == v_real else jnp.pad(head,
                                              ((0, 0), (0, vp - v_real)))
    lse, pick = _ce_fwd_call(x2, headp, lab2, v_real, bt, bv)
    loss, count = _masked_mean(lse, pick, lab2)
    return loss, (x2, head, lab2, lse, count)


def _linear_ce_bwd(bt, bv, res, g):
    x2, head, lab2, lse, count = res
    v_real = head.shape[1]
    vp = _round_up(v_real, bv)
    headp = head if vp == v_real else jnp.pad(head,
                                              ((0, 0), (0, vp - v_real)))
    coef = (g.astype(jnp.float32)
            / jnp.maximum(count, jnp.float32(1.0))).reshape(1, 1)
    dx, dh = _ce_bwd_call(x2, headp, lab2, lse, coef, v_real, bt, bv)
    if vp != v_real:
        dh = dh[:, :v_real]
    return dx, dh, None    # labels: no grad


_linear_ce_vjp.defvjp(_linear_ce_fwd, _linear_ce_bwd)


def linear_ce_pallas(hidden, head, labels, block_t=None, block_v=None):
    """Pallas chunked lm-head + cross entropy (fused custom_vjp).

    hidden [..., D] (any leading shape), head [D, V], labels [...] int
    (negative = ignore). Token/vocab padding is applied OUTSIDE the
    custom_vjp with plain (linear) jnp ops, so autodiff transposes the
    pad/reshape and the kernels only ever see aligned 2-D tiles.
    """
    d = hidden.shape[-1]
    flat = hidden.reshape(-1, d)
    lab = labels.reshape(-1)
    t = flat.shape[0]
    v = head.shape[1]
    if block_t is None or block_v is None:
        bt0, bv0 = _ce_blocks(flat, head, lab)
        block_t = block_t or bt0
        block_v = block_v or bv0
    bt = min(int(block_t), _round_up(t, 8))
    bv = min(int(block_v), _round_up(v, 128))
    tp = _round_up(t, bt)
    if tp != t:
        flat = jnp.pad(flat, ((0, tp - t), (0, 0)))
        lab = jnp.pad(lab, (0, tp - t), constant_values=-1)
    lab2 = jnp.asarray(lab, jnp.int32).reshape(tp, 1)
    return _linear_ce_vjp(flat, head, lab2, bt, bv)


def linear_ce_ref(hidden, head, labels):
    """The EXACT pre-fusion composition (``models/_common.py``'s
    lax.scan chunked lm-head+CE) — dispatch falling back here is
    bit-identical to the pre-fusion training path."""
    from ...models._common import fused_linear_cross_entropy
    return fused_linear_cross_entropy(hidden, head, labels)


def ce_meta(T, D, V, dtype) -> dict:
    """Static dispatch metadata for one fused-linear-CE call site —
    everything the ``supports`` predicate reads, built at trace time
    from static shapes only."""
    dtype = jnp.dtype(dtype)
    return {"T": int(T), "D": int(D), "V": int(V), "dtype": str(dtype),
            "itemsize": int(dtype.itemsize),
            "interpret": bool(_interpret()),
            # a real dispatch input (reshapes the fitting-candidate
            # list), so it rides in the meta where the cache-key lint
            # can see it — not as a hidden env read
            "vmem_budget": int(_vmem_budget())}


def _supports_ce(meta):
    if meta["interpret"]:
        return False, "interpret mode (off-TPU): composition is faster"
    fits = _ce_fitting_candidates(meta["T"], meta["D"], meta["itemsize"],
                                  meta["vmem_budget"])
    if not fits:
        return False, (f"no (block_t, block_v) tile fits the "
                       f"{meta['vmem_budget'] >> 20}MiB VMEM budget at "
                       f"D={meta['D']}")
    return True, f"fits VMEM at blocks {fits[0]}"


KERNELS.register("fused_linear_ce", "pallas_fused",
                 lambda hidden, head, labels: linear_ce_pallas(
                     hidden, head, labels),
                 priority=10, supports=_supports_ce,
                 tags=("train", "pallas"))
KERNELS.register("fused_linear_ce", "unfused", linear_ce_ref,
                 priority=0, tags=("train",))
# the shape/dtype keys live in the train-step trace signature; mode,
# force pins, the VMEM budget and interpret are in _fused_train_key
KERNELS.declare_cache_key(
    "fused_linear_ce",
    ("T", "D", "V", "dtype", "interpret", "vmem_budget"),
    covers={"itemsize": "dtype"})


def fused_linear_ce(hidden, head, labels, mode=None):
    """Chunked lm-head + cross entropy, registry-dispatched.

    ``mode``: None reads FLAGS_fused_train; "auto" dispatches (Pallas
    where supported, the scan composition elsewhere); "pallas"/"ref"
    pin a variant. Semantics identical to
    ``masked_cross_entropy(hidden @ head, labels)`` (negative labels
    ignored, fp32 masked token mean).
    """
    fn = dispatch_fused_variant(
        "fused_linear_ce",
        ce_meta(int(np.prod(hidden.shape[:-1])), hidden.shape[-1],
                head.shape[1], hidden.dtype), mode)
    return fn(hidden, head, labels)


# ---------------------------------------------------------------------------
# fused SwiGLU forward + backward
# ---------------------------------------------------------------------------
def _swiglu_fwd_kernel(g_ref, u_ref, o_ref):
    gf = g_ref[:].astype(jnp.float32)
    uf = u_ref[:].astype(jnp.float32)
    o_ref[:] = (gf * jax.nn.sigmoid(gf) * uf).astype(o_ref.dtype)


def _swiglu_bwd_kernel(g_ref, u_ref, d_ref, dg_ref, du_ref):
    f32 = jnp.float32
    gf = g_ref[:].astype(f32)
    uf = u_ref[:].astype(f32)
    df = d_ref[:].astype(f32)
    sig = jax.nn.sigmoid(gf)
    sil = gf * sig
    # d silu(g)/dg = sig · (1 + g · (1 − sig))
    dg_ref[:] = (df * uf * (sig + sil * (f32(1.0) - sig))
                 ).astype(dg_ref.dtype)
    du_ref[:] = (df * sil).astype(du_ref.dtype)


_SWIGLU_F_CANDIDATES = (2048, 1024, 4096, 512)


def swiglu_autotune_key(R, F, dtype) -> str:
    """Persistent autotune-cache key for the fused-SwiGLU intermediate
    tile (index-into-candidates convention, shared table)."""
    return f"fused_swiglu|{(int(R), int(F), str(jnp.dtype(dtype)))}"


def _swiglu_row_block(R, bf, dtype):
    """Rows per tile: ~512KiB per block buffer — the backward has 5
    block-sized windows (g, u, d in; dg, du out), each double-buffered
    by Mosaic, so 5 x 2 x 512KiB = 5MiB plus the f32 interior stays
    well inside the 16MiB scoped-VMEM envelope (a 2MiB/buffer budget
    would pipeline ~20MiB and OOM a v5e at the flagship F)."""
    it = jnp.dtype(dtype).itemsize
    # whole sublane tiles only (8 rows of 32-bit, 16 of bf16): Mosaic
    # refuses a block whose row count is not a multiple of the tile
    sub = 8 * max(1, 4 // it)
    br = max(sub, (512 * 1024) // max(1, bf * it) // sub * sub)
    return min(br, _round_up(R, sub))


def _swiglu_bf(g2, u2):
    """Resolve the intermediate tile — divisor candidates only (a
    ragged tail would need masking the elementwise kernel doesn't do)
    through the shared autotune table."""
    R, F = g2.shape
    cands = [f for f in _SWIGLU_F_CANDIDATES if f <= F and F % f == 0] \
        or [F]
    if len(cands) == 1:
        return cands[0]
    from .autotune import resolve_candidate
    ck = swiglu_autotune_key(R, F, g2.dtype)

    def build(bf_):
        def fn(g, u):
            return jax.value_and_grad(
                lambda gg, uu: swiglu_pallas(gg, uu, block_f=bf_)
                .astype(jnp.float32).sum(), argnums=(0, 1))(g, u)
        return fn
    return resolve_candidate(ck, cands, build, (g2, u2))


def _swiglu_pad(a, br):
    n = a.shape[0]
    pad = (-n) % br
    if pad:
        a = jnp.concatenate([a, jnp.zeros((pad, a.shape[1]), a.dtype)])
    return a


@no_x64
def _swiglu_fwd_call(g2, u2, br, bf):
    R, F = g2.shape
    return audited_pallas_call(
        _swiglu_fwd_kernel,
        name="swiglu_fwd",
        grid=(R // br, F // bf),
        in_specs=[pl.BlockSpec((br, bf), lambda i, j: (i, j))] * 2,
        out_specs=pl.BlockSpec((br, bf), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, F), g2.dtype),
        interpret=_interpret(),
    )(g2, u2)


@no_x64
def _swiglu_bwd_call(g2, u2, d2, br, bf):
    R, F = g2.shape
    return audited_pallas_call(
        _swiglu_bwd_kernel,
        name="swiglu_bwd",
        grid=(R // br, F // bf),
        in_specs=[pl.BlockSpec((br, bf), lambda i, j: (i, j))] * 3,
        out_specs=[pl.BlockSpec((br, bf), lambda i, j: (i, j))] * 2,
        out_shape=[jax.ShapeDtypeStruct((R, F), g2.dtype),
                   jax.ShapeDtypeStruct((R, F), u2.dtype)],
        interpret=_interpret(),
    )(g2, u2, d2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _swiglu_vjp(g2, u2, br, bf):
    return _swiglu_fwd_call(g2, u2, br, bf)


def _swiglu_fwd_rule(g2, u2, br, bf):
    return _swiglu_fwd_call(g2, u2, br, bf), (g2, u2)


def _swiglu_bwd_rule(br, bf, res, d):
    g2, u2 = res
    return _swiglu_bwd_call(g2, u2, d, br, bf)


_swiglu_vjp.defvjp(_swiglu_fwd_rule, _swiglu_bwd_rule)


def swiglu_pallas(gate, up, block_f=None):
    """Fused SwiGLU silu(gate) · up on [..., F] (one Pallas kernel each
    way, f32 interior)."""
    F = gate.shape[-1]
    orig = gate.shape
    g2 = gate.reshape(-1, F)
    u2 = up.reshape(-1, F)
    R = g2.shape[0]
    if block_f is None:
        bf = _swiglu_bf(g2, u2)
    else:
        bf = int(block_f)
        if F % bf:
            raise ValueError(f"block_f={bf} must divide F={F}")
    br = _swiglu_row_block(R, bf, gate.dtype)
    g2 = _swiglu_pad(g2, br)
    u2 = _swiglu_pad(u2, br)
    out = _swiglu_vjp(g2, u2, br, bf)
    return out[:R].reshape(orig)


def swiglu_ref(gate, up):
    """The EXACT pre-fusion composition (``ops.swiglu`` with two
    operands)."""
    return jax.nn.silu(gate) * up


def swiglu_meta(R, F, dtype) -> dict:
    dtype = jnp.dtype(dtype)
    return {"R": int(R), "F": int(F), "dtype": str(dtype),
            "itemsize": int(dtype.itemsize),
            "interpret": bool(_interpret())}


def _supports_swiglu(meta):
    if meta["interpret"]:
        return False, "interpret mode (off-TPU): composition is faster"
    return True, "elementwise: any shape tiles"


KERNELS.register("fused_swiglu", "pallas_fused",
                 lambda g, u: swiglu_pallas(g, u),
                 priority=10, supports=_supports_swiglu,
                 tags=("train", "pallas"))
KERNELS.register("fused_swiglu", "unfused", swiglu_ref,
                 priority=0, tags=("train",))
KERNELS.declare_cache_key(
    "fused_swiglu", ("R", "F", "dtype", "interpret"),
    covers={"itemsize": "dtype"})


def fused_swiglu(gate, up, mode=None):
    """SwiGLU, registry-dispatched (see :func:`fused_linear_ce` for
    the mode contract)."""
    fn = dispatch_fused_variant(
        "fused_swiglu",
        swiglu_meta(int(np.prod(gate.shape[:-1])), gate.shape[-1],
                    gate.dtype), mode)
    return fn(gate, up)
