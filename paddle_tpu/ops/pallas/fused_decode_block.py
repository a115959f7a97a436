"""Fused decode-block Pallas kernels for the serving hot path.

BENCH_r05 showed the paged decode step round-tripping activations
through HBM between ~6 small programs per transformer block, with the
isolated Pallas kernels winning only 1.1-1.37x each — the bound is
memory traffic, not FLOPs. Per ClusterFusion++ (full transformer-block
decoding fusion) and FlashFuser (PAPERS.md), this module fuses the
per-block decode path into TWO Pallas kernels that keep the activations
in VMEM between stages:

- ``decode_attn_block``: pre-attention RMSNorm + QKV projection + RoPE
  + paged attention over the existing KV pools (fp32/bf16 and int8
  cache variants, new token folded into the online softmax from VMEM
  scratch so the pool write can happen after the kernel) + output
  projection + residual add. One kernel launch instead of rmsnorm,
  3 projections, rope, pool write, attention, o_proj and the residual.
- ``decode_mlp_block``: post-attention RMSNorm + gated MLP (SwiGLU)
  + residual, tiled over the intermediate dim so the weight working set
  fits VMEM at any model width (block size autotuned).
- ``decode_block_fused``: the SINGLE-LAUNCH block kernel — both stages
  above in ONE grid (attention page steps first, MLP intermediate
  tiles after), with the attn->MLP residual held in f32 VMEM scratch
  so it never round-trips HBM between the stages. Legal only where the
  COMBINED weight windows (resident attention tiles + double-buffered
  MLP tiles, at the worst-case pages-per-step and block_f candidates)
  fit the scoped-VMEM envelope (``PADDLE_TPU_SCOPED_VMEM_BUDGET``,
  default 16 MiB) — which the int8/int4 weight_dtype classes of PR 15
  made true at the flagship serving shapes while plain bf16 flagship
  weights still fall back to the two-kernel route above. Priority 0 is
  the exact two-stage sequence (``decode_block_composed``), so every
  fallback tier stays bit-identical to the route it replaces.

The weights of one block ride resident in VMEM (constant-index blocks
are fetched once per kernel invocation), so fusion is only legal where
they fit: each variant registers a ``supports`` predicate with the
kernel registry (:mod:`.registry`) and dispatch falls back to the
``unfused`` composition — the EXACT building-block sequence of
``inference.generation._paged_decode_step``, bit-identical to the
pre-fusion path — in interpret mode, for unsupported head dims, or
when the per-block weights exceed the VMEM budget
(``PADDLE_TPU_FUSED_VMEM_BUDGET``, default 10 MiB out of the 16 MiB
scoped-VMEM window, leaving room for double-buffered KV pages and the
fp32 scratch).

Acceptance contract: greedy output through the fused path must match
the unfused path bit-for-bit wherever the ``unfused`` variant is
selected, and token-for-token on TPU (tests/test_fused_decode_block.py
pins both; the tier-1 engine stream asserts exact parity).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.flags import GLOBAL_FLAGS
from ._util import (PAGE_STEP_CANDIDATES, audited_pallas_call,
                    clamped_page_index, fused_vmem_budget,
                    interpret_mode as _interpret, no_x64,
                    online_softmax_page_update)
from .registry import KERNELS

__all__ = [
    "fused_attn_block_pallas", "fused_mlp_block_pallas",
    "fused_decode_block_pallas", "decode_block_composed",
    "attn_block_ref", "mlp_block_ref", "decode_meta",
    "decode_meta_dims",
    "attn_qkv_ref", "attn_out_ref", "launch_operands", "UNFUSED",
    "resolve_decode_blocks", "resolve_decode_step",
    "mlp_autotune_key", "attn_autotune_key", "block_autotune_key",
    "weight_dtype_of", "scoped_vmem_budget",
]

GLOBAL_FLAGS.define(
    "fused_decode", True,
    "route the paged decode step through the fused decode-block "
    "kernels where the registry supports them (0 = always the unfused "
    "composition, for A/B diagnosis)")


# the ONE budget knob, shared with fused_train/generation/the kernel
# auditor — re-exported under the historic name for its import sites
_vmem_budget = fused_vmem_budget

#: the documented v5e scoped-VMEM OOM point (the kernel auditor's
#: envelope constant, mirrored here so ops/ never imports analysis/)
_SCOPED_VMEM_BYTES = 16 << 20


def scoped_vmem_budget() -> int:
    """The scoped-VMEM envelope the SINGLE-LAUNCH block kernel budgets
    its combined windows against: ``PADDLE_TPU_SCOPED_VMEM_BUDGET``
    (default 16 MiB — the whole per-core scoped window), raised to the
    fused dispatch budget when an operator configures a larger one.
    Same resolution as the kernel auditor's
    :func:`paddle_tpu.analysis.kernel_rules.scoped_vmem_envelope`, so
    a shape the dispatch predicate admits can never overcommit the
    envelope the auditor enforces. Read per trace and carried in the
    dispatch meta (``scoped_vmem_budget``) + the program-cache route
    keys — a changed envelope must retrace, never replay."""
    import os
    env = int(os.environ.get("PADDLE_TPU_SCOPED_VMEM_BUDGET",
                             _SCOPED_VMEM_BYTES))
    return max(env, _vmem_budget())


# ---------------------------------------------------------------------------
# weight-quantization plumbing (r18): int8 / packed-int4 weight tiles
# stream through VMEM and dequantize in-register — the scale applies in
# the matmul EPILOGUE (per-OUTPUT-channel scales commute with the
# contraction: x @ (q * s) == (x @ q) * s), so the integer tile is what
# HBM moves and the interior stays f32
# ---------------------------------------------------------------------------
def _wq_parts(w):
    """Array-or-quantized-leaf normalization -> (weights, scale, bits,
    pack_axis). Quantized leaves are the PTQ harness's
    ``{"qw8"|"qw4": q, "scale": s}`` dicts (quantization/ptq.py); the
    output channel is always the last axis, and an int4 leaf packed
    along its LAST axis (down_proj packs its output dim) is recognized
    by the halved byte count vs the scale length."""
    if isinstance(w, dict):
        scale = w["scale"]
        if "qw4" in w:
            qw = w["qw4"]
            axis = 1 if qw.shape[-1] * 2 == scale.shape[-1] else 0
            return qw, scale, 4, axis
        return w["qw8"], scale, 8, 0
    return w, None, 0, 0


def weight_dtype_of(*ws):
    """The weight-dtype class string a set of weight leaves carries
    ("int8" | "int4" | None for plain arrays) — feeds the dispatch
    metas' ``weight_dtype`` key. Mixing modes across one block's
    weights is rejected: the kernels stream all tiles of a block under
    one bit width."""
    bits = {_wq_parts(w)[2] for w in ws}
    if len(bits) != 1:
        raise ValueError(
            "all block weights must share one weight-quant mode, got "
            f"bit widths {sorted(bits)}")
    b = bits.pop()
    return {8: "int8", 4: "int4"}.get(b)


def _kernel_weight(ref, bits, dt, axis=0):
    """Load one weight tile at the model dtype ``dt``: plain tiles pass
    through; int8 casts (|q| <= 127 is exact in bf16); packed int4
    unpacks through :func:`quantization.quanters.unpack_int4` — the
    SINGLE definition of the halves convention, shared with the
    dequantize-then-matmul fallback, so the two routes can never
    decode different weights. (It is jnp-traceable with
    explicitly-typed shift amounts, so it lowers inside the kernel
    body even when retraced outside the no_x64 window.)"""
    w = ref[:]
    if not bits:
        return w
    if bits == 4:
        from ...quantization.quanters import unpack_int4
        w = unpack_int4(w, axis=axis)
    return w.astype(dt)


def _silu_mul(g, u):
    """``jax.nn.silu(g) * u`` at the model dtype, with the sigmoid taken
    in f32: Mosaic's ``logistic`` lowering broadcasts an f32 ``1.0`` into
    the operand's vector type, which fails MLIR verification for bf16
    operands. The f32 sigmoid rounded back to ``g.dtype`` is the value
    XLA's bf16 ``logistic`` produces, so the composition's op order
    (sigmoid -> * g -> * u, each rounded to the model dtype) holds."""
    sg = jax.nn.sigmoid(g.astype(jnp.float32)).astype(g.dtype)
    return g * sg * u


def _split_heads(t, n, hd):
    """(1, n*hd) -> (n, hd) by lane slices stacked along the sublane
    axis. Mosaic has no layout for the ``reshape`` that splits a lane
    dim into heads narrower than a 128-lane tile ("infer-vector-layout:
    unsupported shape cast"); slices + concatenate compile at any head
    dim. f32 operands only: one-row pieces of a packed dtype do not."""
    return jnp.concatenate([t[:, h * hd:(h + 1) * hd] for h in range(n)],
                           axis=0)


def _merge_heads(t):
    """(n, hd) -> (1, n*hd): the inverse of :func:`_split_heads`."""
    return jnp.concatenate([t[h:h + 1, :] for h in range(t.shape[0])],
                           axis=1)


def _weight_itemsize(meta) -> float:
    """Bytes per weight element under the meta's weight-dtype class —
    what the supports() VMEM math charges for weight tiles."""
    wd = meta.get("weight_dtype")
    if wd == "int8":
        return 1.0
    if wd == "int4":
        return 0.5
    return float(meta["itemsize"])


# ---------------------------------------------------------------------------
# attention-stage megakernel
# ---------------------------------------------------------------------------
def _attn_block_kernel(bt_ref, len_ref, x_ref, nw_ref, wq_ref, wk_ref,
                       wv_ref, wo_ref, sin_ref, cos_ref, *rest,
                       scale, bs, kv, groups, eps, pp, quant, residual,
                       wq_bits=0):
    i = 0
    if wq_bits:
        sqw_ref, skw_ref, svw_ref, sow_ref = rest[:4]
        i = 4
    k_refs = rest[i:i + pp]
    v_refs = rest[i + pp:i + 2 * pp]
    i += 2 * pp
    if quant:
        ksc_ref, vsc_ref = rest[i:i + 2]
        i += 2
    xo_ref, kn_ref, vn_ref = rest[i:i + 3]
    q_scr, ka_scr, va_scr, m_scr, l_scr, acc_scr = rest[i + 3:]

    b = pl.program_id(0)
    mi = pl.program_id(1)
    seq_len = len_ref[b]          # tokens already in the pool (excl. new)
    dt = x_ref.dtype
    hd = q_scr.shape[1]
    hd2 = hd // 2
    # every literal is explicitly typed: the kernel body (like the index
    # maps) can be retraced at LOWERING time outside the no_x64 window,
    # where a bare python literal becomes f64/i64 and breaks the
    # already-specialized f32/i32 call signatures
    f32 = jnp.float32
    epsf = f32(eps)
    scalef = f32(scale)

    @pl.when(mi == 0)
    def _prologue():
        # RMSNorm — same staging as ops.rms_norm_ref: fp32 moment, cast
        # back to the model dtype BEFORE the weight multiply
        xf = x_ref[0].astype(jnp.float32)                     # (1, D)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        h = (xf * jax.lax.rsqrt(ms + epsf)).astype(dt) * nw_ref[:]

        def proj(w_ref, s_ref):
            # dequant rides in the matmul EPILOGUE: the integer tile
            # feeds the MXU at model dtype and the per-output-channel
            # f32 scale multiplies the f32 product row
            t = jnp.dot(h, _kernel_weight(w_ref, wq_bits, dt),
                        preferred_element_type=jnp.float32)
            return t * s_ref[:] if wq_bits else t

        q = proj(wq_ref, sqw_ref if wq_bits else None)
        k = proj(wk_ref, skw_ref if wq_bits else None)
        v = proj(wv_ref, svw_ref if wq_bits else None)
        sinr, cosr = sin_ref[0], cos_ref[0]                   # (1, hd2)

        def rope(t, n):
            # mimic the unfused op order exactly: the projection lands
            # at model dtype, apply_rope recasts to f32 and rotates
            t = _split_heads(t.astype(dt).astype(jnp.float32), n, hd)
            t1, t2 = t[:, :hd2], t[:, hd2:]
            return jnp.concatenate([t1 * cosr - t2 * sinr,
                                    t2 * cosr + t1 * sinr], axis=-1)

        qr = rope(q, kv * groups).astype(dt)                  # (H, hd)
        kr = rope(k, kv).astype(dt)                           # (KV, hd)
        vm = _split_heads(v, kv, hd).astype(dt)
        kn_ref[0] = kr          # raw new-token K/V: the caller owns the
        vn_ref[0] = vm          # pool write (quantizing if int8)
        q_scr[:] = qr.astype(jnp.float32)
        if quant:
            # attention must see dequant(quant(new K/V)) — the same
            # values the unfused path reads back from the int8 pool
            ks = ksc_ref[0][:, None]
            vs = vsc_ref[0][:, None]
            kq = jnp.clip(jnp.round(kr.astype(jnp.float32) / ks),
                          f32(-127), f32(127))
            vq = jnp.clip(jnp.round(vm.astype(jnp.float32) / vs),
                          f32(-127), f32(127))
            ka_scr[:] = kq * ks
            va_scr[:] = vq * vs
        else:
            pool_dt = k_refs[0].dtype
            ka_scr[:] = kr.astype(pool_dt).astype(jnp.float32)
            va_scr[:] = vm.astype(pool_dt).astype(jnp.float32)
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # -- stream the live pages (online softmax, exact across pages) ----
    for j in range(pp):
        pg = mi.astype(jnp.int32) * jnp.int32(pp) + jnp.int32(j) \
            if hasattr(mi, "astype") else jnp.int32(mi * pp + j)

        @pl.when(pg * jnp.int32(bs) < seq_len)
        def _page(k_ref=k_refs[j], v_ref=v_refs[j], pg=pg):
            k = k_ref[0].astype(jnp.float32)                  # (BS, KV, hd)
            v = v_ref[0].astype(jnp.float32)
            if quant:
                k = k * ksc_ref[0][None, :, None]
                v = v * vsc_ref[0][None, :, None]
            # the reduction body is SHARED with the unfused paged
            # decode kernel (their bit-parity contract)
            online_softmax_page_update(q_scr[:], k, v, pg, bs, seq_len,
                                       scale, kv, groups,
                                       m_scr, l_scr, acc_scr)

    @pl.when(mi == pl.num_programs(1) - 1)
    def _epilogue():
        # fold in the NEW token (position seq_len, always unmasked) from
        # VMEM scratch — the pool write happens after the kernel
        q = q_scr[:]
        ka = ka_scr[:]
        va = va_scr[:]
        s_rows = []
        for kvh in range(kv):
            qg = q[kvh * groups:(kvh + 1) * groups, :]
            s_rows.append(jax.lax.dot_general(
                qg, ka[kvh:kvh + 1, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))          # (g, 1)
        s_new = jnp.concatenate(s_rows, axis=0) * scalef      # (H, 1)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, s_new)
        alpha = jnp.exp(m_prev - m_new)       # 0 when no page ran (m=-inf)
        p = jnp.exp(s_new - m_new)            # > 0: l_fin never zero
        l_fin = alpha * l_scr[:] + p
        pv_rows = []
        for kvh in range(kv):
            pg = p[kvh * groups:(kvh + 1) * groups, :]
            pv_rows.append(pg * va[kvh:kvh + 1, :])           # (g, hd)
        acc_fin = acc_scr[:] * alpha + jnp.concatenate(pv_rows, axis=0)
        attn = _merge_heads(acc_fin / l_fin).astype(dt)       # (1, H*hd)
        o = jnp.dot(attn,
                    _kernel_weight(wo_ref, wq_bits, dt),
                    preferred_element_type=jnp.float32)
        if wq_bits:
            o = o * sow_ref[:]
        # residual=False returns the bare o-projection: the tensor-
        # parallel caller psums the per-shard partials across the head
        # axis FIRST and adds the (replicated) residual after
        xo_ref[0] = (x_ref[0] + o.astype(dt)) if residual \
            else o.astype(dt)


def attn_autotune_key(B, H, KV, hd, BS, MB, dtype, pool_dtype,
                      weight_dtype=None) -> str:
    """Persistent autotune-cache key for the fused attention kernel's
    pages-per-grid-step (single source of truth for sweep + read).
    ``pool_dtype`` keys the cache variant: an int8 pool moves half the
    page bytes and adds scale inputs, so it is a distinct shape class
    (mirroring ``decode_meta``'s dispatch keying). ``weight_dtype``
    ("int8"/"int4") appends the same way — quantized weight tiles move
    1/2x-1/4x the bytes, a distinct pipelining class; None keeps the
    historic fp key unchanged."""
    base = (B, H, KV, hd, BS, MB, str(dtype), str(pool_dtype))
    if weight_dtype:
        base = base + (str(weight_dtype),)
    return f"fused_attn_pages|{base}"


def _tuned_pages(key_str, candidates, build, args):
    """Tunable-config resolution, delegated to the shared
    :func:`..autotune.resolve_candidate` (one read convention for every
    kernel sharing the persistent table)."""
    from .autotune import resolve_candidate
    return resolve_candidate(key_str, candidates, build, args)


@no_x64
def fused_attn_block_pallas(x, nw, wq, wk, wv, wo, sin, cos,
                            k_pool, v_pool, block_tables, seq_lens,
                            kv_scales=None, eps=1e-6,
                            pages_per_step=None, residual=True):
    """Fused attention stage of one decode block.

    x: [B, D] residual stream; nw: [D] (already at x.dtype);
    wq [D, H*hd], wk/wv [D, KV*hd], wo [H*hd, D]; sin/cos: full rope
    tables [T, hd//2]; pools [N, BS, KV, hd] (int8 with ``kv_scales``);
    block_tables [B, MB]; seq_lens [B] — the count of tokens already in
    the pool (the new token goes at position ``seq_lens``; attention
    covers ``seq_lens + 1`` tokens, the new one folded in from VMEM).

    Returns (x_out [B, D], k_new [B, KV, hd], v_new [B, KV, hd]); the
    caller writes k_new/v_new into the pools (``write_to_pool[_quant]``)
    exactly as the unfused path does. ``residual=False`` returns the
    bare o-projection instead of ``x + o`` — the tensor-parallel step
    runs this kernel per head shard and all-reduces the partials before
    adding the replicated residual.
    """
    B, D = x.shape
    N, BS, KV, hd = k_pool.shape
    MB = block_tables.shape[1]
    # weight-quant normalization: quantized leaf dicts split into the
    # integer tile + per-output-channel scale; the ORIGINAL leaves stay
    # in the autotune args so the tuning recursion re-parses them
    wq_in, wk_in, wv_in, wo_in = wq, wk, wv, wo
    wq, sqw, bits, _ = _wq_parts(wq)
    wk, skw, _, _ = _wq_parts(wk)
    wv, svw, _, _ = _wq_parts(wv)
    wo, sow, _, _ = _wq_parts(wo)
    weight_dtype = weight_dtype_of(wq_in, wk_in, wv_in, wo_in)
    E = wq.shape[1]
    H = E // hd
    groups = H // KV
    scale = 1.0 / math.sqrt(hd)
    quant = kv_scales is not None

    if pages_per_step is None:
        cands = [p for p in PAGE_STEP_CANDIDATES if p <= MB]
        ck = attn_autotune_key(B, H, KV, hd, BS, MB, x.dtype,
                               k_pool.dtype, weight_dtype)
        args = (x, nw, wq_in, wk_in, wv_in, wo_in, sin, cos, k_pool,
                v_pool, block_tables, seq_lens)

        def build(pp_):
            return lambda *a: fused_attn_block_pallas(
                *a, kv_scales=kv_scales, eps=eps, pages_per_step=pp_,
                residual=residual)[0]

        pages_per_step = _tuned_pages(ck, cands or [1], build, args)
    pp = max(1, min(int(pages_per_step), MB))

    sin_b = jnp.take(jnp.asarray(sin), seq_lens, axis=0)     # (B, hd2)
    cos_b = jnp.take(jnp.asarray(cos), seq_lens, axis=0)

    # per-sequence rows ride as (1, 1, W) blocks of a (B, 1, W) view:
    # Mosaic tiles the LAST TWO block dims (8 sublanes x 128 lanes)
    # unless they span the array's own, which a one-row block of a
    # (B, W) array cannot
    row = lambda b, mi, bt, ln: (b, 0, 0)                # noqa: E731
    const = lambda b, mi, bt, ln: (0, 0)                 # noqa: E731

    def page_index(j):
        return clamped_page_index(BS, pp, j)

    in_specs = [
        pl.BlockSpec((1, 1, D), row),                     # x
        pl.BlockSpec((1, D), const),                      # norm weight
        # weight tiles ride at their STORED shapes (int4 halves the
        # pack axis), resident per kernel invocation like the fp tiles
        pl.BlockSpec(tuple(wq.shape), const),             # wq
        pl.BlockSpec(tuple(wk.shape), const),             # wk
        pl.BlockSpec(tuple(wv.shape), const),             # wv
        pl.BlockSpec(tuple(wo.shape), const),             # wo
        pl.BlockSpec((1, 1, hd // 2), row),               # sin row
        pl.BlockSpec((1, 1, hd // 2), row),               # cos row
    ]
    inputs = [x.reshape(B, 1, D), nw.reshape(1, D), wq, wk, wv, wo,
              sin_b.reshape(B, 1, hd // 2), cos_b.reshape(B, 1, hd // 2)]
    if bits:
        # per-output-channel f32 scales, one const row per projection
        for s in (sqw, skw, svw, sow):
            in_specs.append(pl.BlockSpec((1, s.shape[-1]), const))
            inputs.append(jnp.asarray(s, jnp.float32).reshape(1, -1))
    in_specs += [pl.BlockSpec((1, BS, KV, hd), page_index(j))
                 for j in range(pp)]                      # k pages
    in_specs += [pl.BlockSpec((1, BS, KV, hd), page_index(j))
                 for j in range(pp)]                      # v pages
    inputs += [k_pool] * pp + [v_pool] * pp
    if quant:
        in_specs += [pl.BlockSpec((1, KV), const)] * 2
        inputs += [jnp.asarray(kv_scales[0], jnp.float32).reshape(1, KV),
                   jnp.asarray(kv_scales[1], jnp.float32).reshape(1, KV)]

    xo, kn, vn = audited_pallas_call(
        functools.partial(_attn_block_kernel, scale=scale, bs=BS, kv=KV,
                          groups=groups, eps=eps, pp=pp, quant=quant,
                          residual=residual, wq_bits=bits),
        name="decode_attn_block",
        num_scalar_prefetch=2,
        grid=(B, pl.cdiv(MB, pp)),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, D), row),
            pl.BlockSpec((1, KV, hd), row),
            pl.BlockSpec((1, KV, hd), row),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, hd), jnp.float32),     # q
            pltpu.VMEM((KV, hd), jnp.float32),    # new K (attention view)
            pltpu.VMEM((KV, hd), jnp.float32),    # new V (attention view)
            pltpu.VMEM((H, 1), jnp.float32),      # m
            pltpu.VMEM((H, 1), jnp.float32),      # l
            pltpu.VMEM((H, hd), jnp.float32),     # acc
        ],
        # all three outputs are per-sequence blocks revisited across the
        # page steps (prologue/epilogue writes under pl.when)
        accum_outputs=(0, 1, 2),
        out_shape=[jax.ShapeDtypeStruct((B, 1, D), x.dtype),
                   jax.ShapeDtypeStruct((B, KV, hd), x.dtype),
                   jax.ShapeDtypeStruct((B, KV, hd), x.dtype)],
        interpret=_interpret(),
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(seq_lens, jnp.int32), *inputs)
    return xo.reshape(B, D), kn, vn


# ---------------------------------------------------------------------------
# MLP-stage megakernel
# ---------------------------------------------------------------------------
def _mlp_block_kernel(_layer_ref, x_ref, nw_ref, wg_ref, wu_ref, wd_ref,
                      *rest, eps, residual, wq_bits=0):
    if wq_bits:
        sg_ref, su_ref, sd_ref = rest[:3]
        rest = rest[3:]
    o_ref, h_scr, acc_scr = rest
    j = pl.program_id(0)
    dt = x_ref.dtype

    @pl.when(j == 0)
    def _pre():
        xf = x_ref[:].astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        # jnp.float32(eps): the body can be retraced at lowering time
        # outside the no_x64 window (see _attn_block_kernel)
        h_scr[:] = (xf * jax.lax.rsqrt(ms + jnp.float32(eps))
                    ).astype(dt) * nw_ref[:]
        acc_scr[:] = jnp.zeros_like(acc_scr)

    h = h_scr[:]
    # gate/up pack along the CONTRACTION dim (rows, axis 0), down along
    # its OUTPUT dim (columns, axis 1) — the axis each F-tile fully
    # covers; quantized scales apply in the f32 epilogue
    g = jnp.dot(h, _kernel_weight(wg_ref, wq_bits, dt, axis=0),
                preferred_element_type=jnp.float32)
    u = jnp.dot(h, _kernel_weight(wu_ref, wq_bits, dt, axis=0),
                preferred_element_type=jnp.float32)
    if wq_bits:
        g = g * sg_ref[:]
        u = u * su_ref[:]
    ff = _silu_mul(g.astype(dt), u.astype(dt))    # swiglu, model dtype
    dn = jnp.dot(ff, _kernel_weight(wd_ref, wq_bits, dt, axis=1),
                 preferred_element_type=jnp.float32)
    if wq_bits:
        dn = dn * sd_ref[:]
    acc_scr[:] = acc_scr[:] + dn

    @pl.when(j == pl.num_programs(0) - 1)
    def _fin():
        # residual=False: bare down-projection partial (see attn kernel)
        o_ref[:] = (x_ref[:] + acc_scr[:].astype(dt)) if residual \
            else acc_scr[:].astype(dt)


# 128 last: it only ever becomes the default pick where nothing wider
# fits (D=4096 bf16), so narrower models keep their tile
_MLP_BLOCK_CANDIDATES = (512, 256, 1024, 2048, 128)


def mlp_autotune_key(B, D, F, dtype, budget=None,
                     weight_dtype=None) -> str:
    """Persistent autotune-cache key for the fused MLP kernel's
    intermediate-dim block size. The VMEM budget is part of the key:
    winners are stored as an INDEX into the budget-fitting candidate
    list, so a different ``PADDLE_TPU_FUSED_VMEM_BUDGET`` (which
    reshapes that list) must read a different cache entry — not decode
    a stale index against the wrong candidates. ``weight_dtype``
    ("int8"/"int4") appends the quantized-weight shape class the same
    way (it too reshapes the fitting list); None keeps the historic
    fp key."""
    budget = _vmem_budget() if budget is None else int(budget)
    base = (B, D, F, str(dtype), budget)
    if weight_dtype:
        base = base + (str(weight_dtype),)
    return f"fused_mlp_block|{base}"


def _mlp_candidates(F: int):
    """Intermediate-dim tile sizes: divisors of F only (a ragged last
    block would multiply garbage columns into the accumulator)."""
    cands = [c for c in _MLP_BLOCK_CANDIDATES if c <= F and F % c == 0]
    return cands or [F]


def _mlp_vmem_need(B: int, D: int, itemsize: int, bf: int,
                   w_itemsize: float = None) -> int:
    """Per-grid-step VMEM bytes at tile ``bf``: the 3 weight tiles and
    the x / out rows as the pipeline holds them — TWO buffers each (the
    F-tiles move every step; compiled for v5e, D=4096 bf=384 and D=6144
    bf=256, 18 MiB of tile buffers, are refused where a single-buffer
    count admitted them) — plus the h/acc scratch rows and the body's
    f32 rows (xf, the down-projection) and g/u/ff tiles.
    ``w_itemsize``: bytes per weight ELEMENT (1 for int8, 0.5 for
    packed int4 — which also adds the f32 scale rows); defaults to the
    activation itemsize (plain fp weights)."""
    if w_itemsize is None:
        w_itemsize = itemsize
    scales = (2 * bf + D) * 4 if w_itemsize != itemsize else 0
    return 2 * (int(3 * D * bf * w_itemsize) + scales) \
        + B * D * (4 * itemsize + itemsize + 4 + 2 * 4) + 3 * B * bf * 4


def _mlp_fitting_candidates(B: int, D: int, F: int, itemsize: int,
                            budget: int = None,
                            w_itemsize: float = None):
    """The divisor candidates that fit the VMEM budget. Dispatch
    (``_supports_mlp``), the traced default pick, and the autotune
    sweep all consume THIS list — a supported-and-dispatched kernel can
    therefore never compile over the budget its predicate promised.
    ``budget`` rides as a parameter (supports() passes the meta's
    ``vmem_budget`` key) so the env read stays a VISIBLE dispatch
    input, not a hidden one the cache-key lint cannot see."""
    budget = _vmem_budget() if budget is None else int(budget)
    return [bf for bf in _mlp_candidates(F)
            if _mlp_vmem_need(B, D, itemsize, bf, w_itemsize) <= budget]


@no_x64
def fused_mlp_block_pallas(x, nw, wg, wu, wd, eps=1e-6, block_f=None,
                           residual=True, layer=None):
    """Fused MLP stage of one decode block: RMSNorm + SwiGLU + residual.

    x: [B, D]; nw: [D] at x.dtype; wg/wu: [D, F]; wd: [F, D]. Tiled over
    F in ``block_f`` columns (autotuned, divisors of F) so only
    3*D*block_f weight elements are VMEM-resident per grid step.
    ``residual=False`` returns the bare down-projection (tensor-parallel
    partial — the caller all-reduces, then adds the residual).
    ``layer``: nw / wg / wu / wd (plain or quantized leaves) are the
    STACKED per-layer arrays ([L, D], [L, D, F], [L, F, D]) and this is
    the layer to run (an int or a traced int32 scalar): the launch
    takes the whole arrays and its index maps address the layer, so
    inside a loop over layers no one-layer copy of the weights is made.
    Bit-identical to passing each array's ``[layer]`` slice.
    """
    B, D = x.shape
    # weight-quant normalization (the attn wrapper's idiom): original
    # leaves stay in the autotune args so the recursion re-parses them
    wg_in, wu_in, wd_in = wg, wu, wd
    wg, sg, bits, _ = _wq_parts(wg)
    wu, su, _, _ = _wq_parts(wu)
    wd, sd, _, _ = _wq_parts(wd)
    weight_dtype = weight_dtype_of(wg_in, wu_in, wd_in)
    F = wg.shape[-1]
    w_it = {8: 1.0, 4: 0.5}.get(bits)
    if block_f is None:
        it = jnp.dtype(x.dtype).itemsize
        # ONE budget read per trace: the fitting list and the autotune
        # key must see the same value (the budget-in-meta contract)
        budget = _vmem_budget()
        # budget-fitting tiles only; a forced call with nothing fitting
        # (tests, interpret) gets the smallest divisor tile
        cands = _mlp_fitting_candidates(B, D, F, it, budget, w_it) \
            or [min(_mlp_candidates(F))]
        ck = mlp_autotune_key(B, D, F, x.dtype, budget, weight_dtype)

        def build(bf):
            return lambda *a: fused_mlp_block_pallas(*a, eps=eps,
                                                     block_f=bf,
                                                     residual=residual,
                                                     layer=layer)

        block_f = _tuned_pages(ck, cands, build,
                               (x, nw, wg_in, wu_in, wd_in))
    bf = int(block_f)
    if F % bf:
        # grid=(F // bf,) floor-drops a ragged tail block: a non-divisor
        # tile would silently never feed the last F % bf columns into
        # the down-projection accumulator. (int4 needs no extra tile
        # constraint: the F axis is never the packed axis — gate/up
        # pack rows (D), down packs columns (D), both fully covered by
        # every F-tile.)
        raise ValueError(f"block_f={bf} must divide the intermediate "
                         f"dim F={F}")

    # every per-layer operand is a stack [L, ...] with a squeezed block
    # dimension that the layer (the scalar-prefetch operand) indexes;
    # one layer's arrays are a stack of one
    per_layer = [nw, wg, wu, wd] + ([sg, su, sd] if bits else [])
    if layer is None:
        per_layer, layer = [a[None] for a in per_layer], 0
    nw, wg, wu, wd, *scales = per_layer
    const = lambda j, l: (0, 0)                           # noqa: E731
    row = lambda j, l: (l[0], 0, 0)                       # noqa: E731
    f_cols = lambda j, l: (l[0], 0, j)                    # noqa: E731
    f_rows = lambda j, l: (l[0], j, 0)                    # noqa: E731
    # stored-shape tiles: int4 halves gate/up rows (pack axis 0 = the
    # contraction dim, fully covered by every tile) and down COLUMNS
    # (pack axis 1 = its output dim); the F-axis tiling is over the
    # UNPACKED coordinate for gate/up and over wd's packed rows 1:1
    gu_rows = wg.shape[-2]
    wd_cols = wd.shape[-1]
    bf_wd = bf                            # wd rows tile the F axis 1:1
    in_specs = [pl.BlockSpec((B, D), const),
                pl.BlockSpec((None, 1, D), row),
                pl.BlockSpec((None, gu_rows, bf), f_cols),
                pl.BlockSpec((None, gu_rows, bf), f_cols),
                pl.BlockSpec((None, bf_wd, wd_cols), f_rows)]
    inputs = [x, nw.reshape(-1, 1, D), wg, wu, wd]
    if bits:
        in_specs += [pl.BlockSpec((None, 1, bf), f_cols),
                     pl.BlockSpec((None, 1, bf), f_cols),
                     pl.BlockSpec((None, 1, D), row)]
        inputs += [jnp.asarray(sc, jnp.float32).reshape(-1, 1, n)
                   for sc, n in zip(scales, (F, F, D))]
    out = audited_pallas_call(
        functools.partial(_mlp_block_kernel, eps=eps, residual=residual,
                          wq_bits=bits),
        name="decode_mlp_block",
        num_scalar_prefetch=1,
        # the output block is revisited every intermediate tile (down-
        # projection accumulated in scratch, written at the last tile)
        accum_outputs=(0,),
        grid=(F // bf,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((B, D), const),
        out_shape=jax.ShapeDtypeStruct((B, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((B, D), x.dtype),
                        pltpu.VMEM((B, D), jnp.float32)],
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), *inputs)
    return out


# ---------------------------------------------------------------------------
# single-launch block megakernel: attn + MLP in ONE grid, the attn->MLP
# residual resident in f32 VMEM scratch (never written to HBM)
# ---------------------------------------------------------------------------
def _block_fused_kernel(bt_ref, len_ref, x_ref, nw_ref, wq_ref, wk_ref,
                        wv_ref, wo_ref, pw_ref, wg_ref, wu_ref, wd_ref,
                        sin_ref, cos_ref, *rest, scale, bs, kv, groups,
                        eps, pp, np_, nf, quant, wq_bits=0):
    """One transformer block's decode step in a single launch.

    Grid = (B, NP + NF): steps [0, NP) stream the live KV pages
    (attention phase — the shared ``online_softmax_page_update`` body,
    exactly as ``_attn_block_kernel``), step NP-1 closes attention
    (new-token fold + o_proj) and hands the residual to step NP..NS-1,
    the MLP intermediate tiles (exactly ``_mlp_block_kernel``'s math).
    The handoff lives in ``r_scr`` (f32 [1, D] VMEM) — the one tensor
    the two-kernel composition round-trips through HBM per block."""
    i = 0
    if wq_bits:
        (sqw_ref, skw_ref, svw_ref, sow_ref,
         sg_ref, su_ref, sd_ref) = rest[:7]
        i = 7
    k_refs = rest[i:i + pp]
    v_refs = rest[i + pp:i + 2 * pp]
    i += 2 * pp
    if quant:
        ksc_ref, vsc_ref = rest[i:i + 2]
        i += 2
    xo_ref, kn_ref, vn_ref = rest[i:i + 3]
    (q_scr, ka_scr, va_scr, m_scr, l_scr, acc_scr,
     r_scr, h_scr, f_scr) = rest[i + 3:]

    b = pl.program_id(0)
    s = pl.program_id(1)
    seq_len = len_ref[b]
    dt = x_ref.dtype
    hd = q_scr.shape[1]
    hd2 = hd // 2
    # explicitly-typed literals: the body can be retraced at LOWERING
    # time outside the no_x64 window (see _attn_block_kernel)
    f32 = jnp.float32
    epsf = f32(eps)
    scalef = f32(scale)

    @pl.when(s == 0)
    def _prologue():
        # identical staging to _attn_block_kernel's prologue: RMSNorm,
        # QKV projections (epilogue-scaled when weight-quantized), RoPE,
        # new-token K/V out + attention-view scratch, m/l/acc init
        xf = x_ref[0].astype(jnp.float32)                     # (1, D)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        h = (xf * jax.lax.rsqrt(ms + epsf)).astype(dt) * nw_ref[:]

        def proj(w_ref, s_ref):
            t = jnp.dot(h, _kernel_weight(w_ref, wq_bits, dt),
                        preferred_element_type=jnp.float32)
            return t * s_ref[:] if wq_bits else t

        q = proj(wq_ref, sqw_ref if wq_bits else None)
        k = proj(wk_ref, skw_ref if wq_bits else None)
        v = proj(wv_ref, svw_ref if wq_bits else None)
        sinr, cosr = sin_ref[0], cos_ref[0]                   # (1, hd2)

        def rope(t, n):
            t = _split_heads(t.astype(dt).astype(jnp.float32), n, hd)
            t1, t2 = t[:, :hd2], t[:, hd2:]
            return jnp.concatenate([t1 * cosr - t2 * sinr,
                                    t2 * cosr + t1 * sinr], axis=-1)

        qr = rope(q, kv * groups).astype(dt)                  # (H, hd)
        kr = rope(k, kv).astype(dt)                           # (KV, hd)
        vm = _split_heads(v, kv, hd).astype(dt)
        kn_ref[0] = kr
        vn_ref[0] = vm
        q_scr[:] = qr.astype(jnp.float32)
        if quant:
            ks = ksc_ref[0][:, None]
            vs = vsc_ref[0][:, None]
            kq = jnp.clip(jnp.round(kr.astype(jnp.float32) / ks),
                          f32(-127), f32(127))
            vq = jnp.clip(jnp.round(vm.astype(jnp.float32) / vs),
                          f32(-127), f32(127))
            ka_scr[:] = kq * ks
            va_scr[:] = vq * vs
        else:
            pool_dt = k_refs[0].dtype
            ka_scr[:] = kr.astype(pool_dt).astype(jnp.float32)
            va_scr[:] = vm.astype(pool_dt).astype(jnp.float32)
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # -- attention phase: stream the live pages. The predicate is
    # automatically false for every MLP step (s >= NP implies
    # pg*bs >= MB*bs > seq_len), so no phase guard is needed here
    for j in range(pp):
        pg = s.astype(jnp.int32) * jnp.int32(pp) + jnp.int32(j) \
            if hasattr(s, "astype") else jnp.int32(s * pp + j)

        @pl.when(pg * jnp.int32(bs) < seq_len)
        def _page(k_ref=k_refs[j], v_ref=v_refs[j], pg=pg):
            k = k_ref[0].astype(jnp.float32)                  # (BS, KV, hd)
            v = v_ref[0].astype(jnp.float32)
            if quant:
                k = k * ksc_ref[0][None, :, None]
                v = v * vsc_ref[0][None, :, None]
            online_softmax_page_update(q_scr[:], k, v, pg, bs, seq_len,
                                       scale, kv, groups,
                                       m_scr, l_scr, acc_scr)

    @pl.when(s == jnp.int32(np_ - 1))
    def _attn_epilogue():
        # close attention exactly as _attn_block_kernel's epilogue —
        # but land the residual in f32 VMEM scratch instead of HBM,
        # and run the post-attention RMSNorm right here so the MLP
        # tiles only consume h_scr
        q = q_scr[:]
        ka = ka_scr[:]
        va = va_scr[:]
        s_rows = []
        for kvh in range(kv):
            qg = q[kvh * groups:(kvh + 1) * groups, :]
            s_rows.append(jax.lax.dot_general(
                qg, ka[kvh:kvh + 1, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))          # (g, 1)
        s_new = jnp.concatenate(s_rows, axis=0) * scalef      # (H, 1)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, s_new)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_new - m_new)
        l_fin = alpha * l_scr[:] + p
        pv_rows = []
        for kvh in range(kv):
            pg = p[kvh * groups:(kvh + 1) * groups, :]
            pv_rows.append(pg * va[kvh:kvh + 1, :])           # (g, hd)
        acc_fin = acc_scr[:] * alpha + jnp.concatenate(pv_rows, axis=0)
        attn = _merge_heads(acc_fin / l_fin).astype(dt)       # (1, H*hd)
        o = jnp.dot(attn,
                    _kernel_weight(wo_ref, wq_bits, dt),
                    preferred_element_type=jnp.float32)
        if wq_bits:
            o = o * sow_ref[:]
        # the residual-in-VMEM contract: the attn->MLP handoff stays
        # f32 in scratch for the rest of the launch
        resid = x_ref[0].astype(jnp.float32) + o              # (1, D)
        r_scr[:] = resid
        ms2 = jnp.mean(jnp.square(resid), axis=-1, keepdims=True)
        h_scr[:] = (resid * jax.lax.rsqrt(ms2 + epsf)
                    ).astype(dt) * pw_ref[:]
        f_scr[:] = jnp.zeros_like(f_scr)

    @pl.when(s >= jnp.int32(np_))
    def _mlp_tile():
        # one intermediate tile, _mlp_block_kernel's math verbatim
        h = h_scr[:]
        g = jnp.dot(h, _kernel_weight(wg_ref, wq_bits, dt, axis=0),
                    preferred_element_type=jnp.float32)
        u = jnp.dot(h, _kernel_weight(wu_ref, wq_bits, dt, axis=0),
                    preferred_element_type=jnp.float32)
        if wq_bits:
            g = g * sg_ref[:]
            u = u * su_ref[:]
        ff = _silu_mul(g.astype(dt), u.astype(dt))
        dn = jnp.dot(ff, _kernel_weight(wd_ref, wq_bits, dt, axis=1),
                     preferred_element_type=jnp.float32)
        if wq_bits:
            dn = dn * sd_ref[:]
        f_scr[:] = f_scr[:] + dn

    @pl.when(s == jnp.int32(np_ + nf - 1))
    def _fin():
        xo_ref[0] = (r_scr[:] + f_scr[:]).astype(dt)


def block_autotune_key(B, D, H, KV, hd, F, BS, MB, dtype, pool_dtype,
                       budget, weight_dtype=None) -> str:
    """Persistent autotune-cache key for the single-launch block
    kernel's JOINT (pages_per_step, block_f) tunable. The scoped
    budget is part of the key (it reshapes the fitting block_f list,
    and winners are stored as an index into the pair list — the
    ``mlp_autotune_key`` contract); ``weight_dtype`` appends the
    quantized-weight shape class the same way."""
    base = (B, D, H, KV, hd, F, BS, MB, str(dtype), str(pool_dtype),
            int(budget))
    if weight_dtype:
        base = base + (str(weight_dtype),)
    return f"fused_block|{base}"


def _block_vmem_need(meta, bf: int) -> int:
    """Combined-window VMEM bytes for the single-launch kernel at MLP
    tile ``bf``: BOTH weight window sets double-buffered (the resident
    attention tiles + the streamed MLP tiles — the conservative charge
    the ISSUE's dispatch contract names), the scale rows, the K/V page
    windows at the WORST-case pages-per-step candidate, the activation
    rows, and the f32 scratch (attention state + residual/h/MLP
    accumulator)."""
    D, H, KV, hd = meta["D"], meta["H"], meta["KV"], meta["hd"]
    it = meta["itemsize"]
    wit = _weight_itemsize(meta)
    attn_w = int((2 * D * H * hd + 2 * D * KV * hd) * wit)
    mlp_w = int(3 * D * bf * wit)
    scales = 0
    if wit != it:
        scales = (H * hd + 2 * KV * hd + D) * 4   # attn scale rows
        scales += (2 * bf + D) * 4                # mlp scale tiles
    page = meta["BS"] * KV * hd * (1 if meta["quant"] else it)
    pages = 4 * max(PAGE_STEP_CANDIDATES) * page
    scratch = (2 * H * hd + 2 * KV * hd + 2 * H + 2 * D) * 4 \
        + D * it
    return 2 * (attn_w + mlp_w) + scales + pages + scratch + 4 * D * it


def _block_fitting_candidates(meta):
    """The MLP tile sizes whose COMBINED window set fits the scoped
    envelope. Dispatch (``_supports_block``), the traced default pick
    and the autotune sweep all consume THIS list (the
    ``_mlp_fitting_candidates`` contract: a supported-and-dispatched
    launch can never compile over the envelope its predicate
    promised)."""
    return [bf for bf in _mlp_candidates(meta["F"])
            if _block_vmem_need(meta, bf) <= meta["scoped_vmem_budget"]]


@no_x64
def fused_decode_block_pallas(x, nw, wq, wk, wv, wo, pw, wg, wu, wd,
                              sin, cos, k_pool, v_pool, block_tables,
                              seq_lens, kv_scales=None, eps=1e-6,
                              pages_per_step=None, block_f=None):
    """ONE Pallas launch for a full decode block: RMSNorm + QKV + RoPE
    + paged attention (new token folded from VMEM; the pool write stays
    with the caller) + o_proj + residual + RMSNorm + SwiGLU + residual.

    Arguments are the union of the two stage kernels': ``nw``/``pw``
    are the input/post norm weights (at x.dtype), the seven projection
    weights ride plain or as PTQ int8/int4 leaves (in-register dequant,
    epilogue scales — the PR-15 idiom). Returns
    (x_out [B, D], k_new [B, KV, hd], v_new [B, KV, hd]).

    The attn->MLP residual lives in f32 VMEM scratch for the whole
    launch — the two-kernel composition's one HBM round-trip per block
    that this kernel exists to delete. (The f32 handoff means the
    megakernel is a roundoff-level variant of the composition, not a
    bit-identical one; bit-parity holds on every FALLBACK tier, which
    runs the exact building-block sequence.)"""
    B, D = x.shape
    N, BS, KV, hd = k_pool.shape
    MB = block_tables.shape[1]
    # weight-quant normalization; ORIGINAL leaves stay in the autotune
    # args so the tuning recursion re-parses them
    originals = (wq, wk, wv, wo, wg, wu, wd)
    wq, sqw, bits, _ = _wq_parts(wq)
    wk, skw, _, _ = _wq_parts(wk)
    wv, svw, _, _ = _wq_parts(wv)
    wo, sow, _, _ = _wq_parts(wo)
    wg, sg, _, _ = _wq_parts(wg)
    wu, su, _, _ = _wq_parts(wu)
    wd, sd, _, _ = _wq_parts(wd)
    weight_dtype = weight_dtype_of(*originals)
    E = wq.shape[1]
    H = E // hd
    groups = H // KV
    F = wg.shape[1]
    scale = 1.0 / math.sqrt(hd)
    quant = kv_scales is not None

    if pages_per_step is None or block_f is None:
        budget = scoped_vmem_budget()
        meta = decode_meta_dims(B, D, H, KV, hd, F, BS, MB, x.dtype,
                                k_pool.dtype, quant,
                                weight_dtype=weight_dtype)
        bfs = _block_fitting_candidates(meta) \
            or [min(_mlp_candidates(F))]
        pps = [p for p in PAGE_STEP_CANDIDATES if p <= MB] or [1]
        pairs = [(p, f) for p in pps for f in bfs]
        ck = block_autotune_key(B, D, H, KV, hd, F, BS, MB, x.dtype,
                                k_pool.dtype, budget, weight_dtype)
        o_wq, o_wk, o_wv, o_wo, o_wg, o_wu, o_wd = originals
        args = (x, nw, o_wq, o_wk, o_wv, o_wo, pw, o_wg, o_wu, o_wd,
                sin, cos, k_pool, v_pool, block_tables, seq_lens)

        def build(pair):
            pp_, bf_ = pair
            return lambda *a: fused_decode_block_pallas(
                *a, kv_scales=kv_scales, eps=eps, pages_per_step=pp_,
                block_f=bf_)[0]

        pages_per_step, block_f = _tuned_pages(ck, pairs, build, args)
    pp = max(1, min(int(pages_per_step), MB))
    bf = int(block_f)
    if F % bf:
        # same floor-drop hazard as fused_mlp_block_pallas: a ragged
        # tail tile would silently never reach the accumulator
        raise ValueError(f"block_f={bf} must divide the intermediate "
                         f"dim F={F}")
    np_ = -(-MB // pp)                 # attention page steps
    nf = F // bf                       # MLP intermediate tiles

    sin_b = jnp.take(jnp.asarray(sin), seq_lens, axis=0)     # (B, hd2)
    cos_b = jnp.take(jnp.asarray(cos), seq_lens, axis=0)

    # (1, 1, W) row blocks of (B, 1, W) views, as in
    # fused_attn_block_pallas
    row = lambda b, s, bt, ln: (b, 0, 0)                 # noqa: E731
    const = lambda b, s, bt, ln: (0, 0)                  # noqa: E731

    def _mlp_jf(s):
        # clamped tile coordinate: parks on tile 0 through the
        # attention phase (the fetched block is simply unused there),
        # walks the F tiles across the MLP steps — all-int32 for the
        # lowering-time retrace outside no_x64 (clamped_page_index's
        # idiom, which the page specs below reuse verbatim)
        return jnp.clip(s.astype(jnp.int32) - jnp.int32(np_),
                        jnp.int32(0), jnp.int32(nf - 1))

    mlp_col = lambda b, s, bt, ln: (0, _mlp_jf(s))       # noqa: E731
    mlp_row = lambda b, s, bt, ln: (_mlp_jf(s), 0)       # noqa: E731

    def page_index(j):
        return clamped_page_index(BS, pp, j)

    gu_rows = wg.shape[0]
    wd_cols = wd.shape[1]
    in_specs = [
        pl.BlockSpec((1, 1, D), row),                     # x
        pl.BlockSpec((1, D), const),                      # input norm
        pl.BlockSpec(tuple(wq.shape), const),             # wq
        pl.BlockSpec(tuple(wk.shape), const),             # wk
        pl.BlockSpec(tuple(wv.shape), const),             # wv
        pl.BlockSpec(tuple(wo.shape), const),             # wo
        pl.BlockSpec((1, D), const),                      # post norm
        pl.BlockSpec((gu_rows, bf), mlp_col),             # wg tile
        pl.BlockSpec((gu_rows, bf), mlp_col),             # wu tile
        pl.BlockSpec((bf, wd_cols), mlp_row),             # wd tile
        pl.BlockSpec((1, 1, hd // 2), row),               # sin row
        pl.BlockSpec((1, 1, hd // 2), row),               # cos row
    ]
    inputs = [x.reshape(B, 1, D), nw.reshape(1, D), wq, wk, wv, wo,
              pw.reshape(1, D), wg, wu, wd,
              sin_b.reshape(B, 1, hd // 2), cos_b.reshape(B, 1, hd // 2)]
    if bits:
        for s_ in (sqw, skw, svw, sow):
            in_specs.append(pl.BlockSpec((1, s_.shape[-1]), const))
            inputs.append(jnp.asarray(s_, jnp.float32).reshape(1, -1))
        in_specs += [pl.BlockSpec((1, bf), mlp_col),
                     pl.BlockSpec((1, bf), mlp_col),
                     pl.BlockSpec((1, D), const)]
        inputs += [jnp.asarray(sg, jnp.float32).reshape(1, F),
                   jnp.asarray(su, jnp.float32).reshape(1, F),
                   jnp.asarray(sd, jnp.float32).reshape(1, D)]
    in_specs += [pl.BlockSpec((1, BS, KV, hd), page_index(j))
                 for j in range(pp)]                      # k pages
    in_specs += [pl.BlockSpec((1, BS, KV, hd), page_index(j))
                 for j in range(pp)]                      # v pages
    inputs += [k_pool] * pp + [v_pool] * pp
    if quant:
        in_specs += [pl.BlockSpec((1, KV), const)] * 2
        inputs += [jnp.asarray(kv_scales[0], jnp.float32).reshape(1, KV),
                   jnp.asarray(kv_scales[1], jnp.float32).reshape(1, KV)]

    xo, kn, vn = audited_pallas_call(
        functools.partial(_block_fused_kernel, scale=scale, bs=BS,
                          kv=KV, groups=groups, eps=eps, pp=pp,
                          np_=np_, nf=nf, quant=quant, wq_bits=bits),
        name="decode_block_fused",
        num_scalar_prefetch=2,
        grid=(B, np_ + nf),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, D), row),
            pl.BlockSpec((1, KV, hd), row),
            pl.BlockSpec((1, KV, hd), row),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, hd), jnp.float32),     # q
            pltpu.VMEM((KV, hd), jnp.float32),    # new K (attn view)
            pltpu.VMEM((KV, hd), jnp.float32),    # new V (attn view)
            pltpu.VMEM((H, 1), jnp.float32),      # m
            pltpu.VMEM((H, 1), jnp.float32),      # l
            pltpu.VMEM((H, hd), jnp.float32),     # acc
            pltpu.VMEM((1, D), jnp.float32),      # residual (f32, HBM-free)
            pltpu.VMEM((1, D), x.dtype),          # post-norm h
            pltpu.VMEM((1, D), jnp.float32),      # MLP accumulator
        ],
        # all three outputs are per-sequence blocks revisited across
        # the combined grid (prologue/epilogue writes under pl.when)
        accum_outputs=(0, 1, 2),
        out_shape=[jax.ShapeDtypeStruct((B, 1, D), x.dtype),
                   jax.ShapeDtypeStruct((B, KV, hd), x.dtype),
                   jax.ShapeDtypeStruct((B, KV, hd), x.dtype)],
        interpret=_interpret(),
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(seq_lens, jnp.int32), *inputs)
    return xo.reshape(B, D), kn, vn


def decode_block_composed(x, nw, wq, wk, wv, wo, pw, wg, wu, wd, sin,
                          cos, k_pool, v_pool, block_tables, seq_lens,
                          kv_scales=None, eps=1e-6):
    """Priority-0 fallback for ``decode_block_fused``: the EXACT
    two-stage sequence, each stage registry-dispatched — on TPU the two
    stage megakernels, off-TPU / oversized the unfused composition —
    so every fallback tier is bit-identical to the two-kernel route it
    stands in for, by construction. The MLP stage reads no pool state,
    so running it before the caller's pool write is the same math as
    the interleaved two-kernel order."""
    B, D = x.shape
    _, BS, KV, hd = k_pool.shape
    MB = block_tables.shape[1]
    # stored q_proj/gate tiles keep their OUTPUT dim unpacked (int4
    # packs rows for D-contracting tiles), so H/F read off the shapes
    H = _wq_parts(wq)[0].shape[1] // hd
    F = _wq_parts(wg)[0].shape[1]
    meta = decode_meta_dims(B, D, H, KV, hd, F, BS, MB, x.dtype,
                            k_pool.dtype, kv_scales is not None,
                            weight_dtype=weight_dtype_of(
                                wq, wk, wv, wo, wg, wu, wd))
    attn_fn, mlp_fn, _ = resolve_decode_blocks(meta, "auto")
    xo, k_new, v_new = attn_fn(x, nw, wq, wk, wv, wo, sin, cos,
                               k_pool, v_pool, block_tables, seq_lens,
                               kv_scales, eps)
    xo = mlp_fn(xo, pw, wg, wu, wd, eps)
    return xo, k_new, v_new


# ---------------------------------------------------------------------------
# unfused reference variants — the EXACT pre-fusion building-block
# sequence, so dispatch falling back here is bit-identical to the
# original ``_paged_decode_step`` math
# ---------------------------------------------------------------------------
def attn_qkv_ref(x, nw, wq, wk, wv, sin, cos, seq_lens, eps=1e-6):
    """First half of the unfused attention stage: RMSNorm, the q/k/v
    projections and RoPE at each slot's position. Returns (q [B, H, hd],
    k_new, v_new [B, KV, hd]); head counts are read off the weights, so
    a tensor-parallel shard gets its local heads."""
    from .. import rms_norm as fused_rms_norm
    from ..rope import apply_rope
    from ...quantization.quanters import maybe_dequantize

    # quantized weight leaves take the DEQUANTIZE-THEN-MATMUL route
    # here — the priority-0 fallback contract is bit-identical to that
    # composition by construction
    B = x.shape[0]
    hd = sin.shape[-1] * 2               # the rope table is [T, hd // 2]
    pos_ids = seq_lens[:, None]
    h = fused_rms_norm(x[:, None], nw, eps)[:, 0]
    q, k, v = ((h @ maybe_dequantize(w, x.dtype)).reshape(B, 1, -1, hd)
               for w in (wq, wk, wv))
    q = apply_rope(q, sin, cos, position_ids=pos_ids)
    k = apply_rope(k, sin, cos, position_ids=pos_ids)
    return q[:, 0], k[:, 0], v[:, 0]


def attn_out_ref(x, q, wo, k_pool, v_pool, block_tables, seq_lens,
                 kv_scales=None, residual=True, layer=None, gather=None):
    """Second half: paged attention over pools that ALREADY hold the
    new token (so it runs over ``seq_lens + 1``), the output projection
    and the residual. ``layer``: the pools are the decode loop's
    carried stack and the attention launch addresses that layer.
    ``gather``: applied to the [B, H_loc, hd] heads before the
    projection (the tensor-parallel "gather" placement's all-gather)."""
    from ..paged_attention import (paged_attention_decode,
                                   paged_attention_decode_quant)
    from ...quantization.quanters import maybe_dequantize

    if kv_scales is None:
        attn = paged_attention_decode(q, k_pool, v_pool, block_tables,
                                      seq_lens + 1, layer=layer)
    else:
        attn = paged_attention_decode_quant(
            q, k_pool, v_pool, block_tables, seq_lens + 1, *kv_scales,
            layer=layer)
    if gather is not None:
        attn = gather(attn)
    o = attn.reshape(x.shape[0], -1).astype(x.dtype) \
        @ maybe_dequantize(wo, x.dtype)
    return x + o if residual else o


def attn_block_ref(x, nw, wq, wk, wv, wo, sin, cos, k_pool, v_pool,
                   block_tables, seq_lens, kv_scales=None, eps=1e-6,
                   residual=True):
    """The unfused attention stage over ONE layer's pools, with the
    stage kernels' contract: returns (x, k_new, v_new) and leaves the
    pool write to the caller. Attention has to see the new token, so it
    runs over a local copy of the pools that holds it; the decode loop,
    which carries the pools, calls the two halves itself around its one
    in-place write instead."""
    from ..paged_attention import write_to_pool, write_to_pool_quant

    q, k_new, v_new = attn_qkv_ref(x, nw, wq, wk, wv, sin, cos, seq_lens,
                                   eps)
    if kv_scales is None:
        kp, vp = write_to_pool(k_pool, v_pool, block_tables, seq_lens,
                               k_new.astype(k_pool.dtype),
                               v_new.astype(v_pool.dtype))
    else:
        kp, vp = write_to_pool_quant(k_pool, v_pool, block_tables,
                                     seq_lens, k_new, v_new, *kv_scales)
    return attn_out_ref(x, q, wo, kp, vp, block_tables, seq_lens,
                        kv_scales, residual), k_new, v_new


def mlp_block_ref(x, nw, wg, wu, wd, eps=1e-6, residual=True,
                  gather=None):
    """``gather``: applied to the [B, F_loc] SwiGLU columns before the
    down projection (the "gather" placement's all-gather)."""
    from .. import rms_norm as fused_rms_norm, swiglu as fused_swiglu
    from ...quantization.quanters import maybe_dequantize

    wg = maybe_dequantize(wg, x.dtype)
    wu = maybe_dequantize(wu, x.dtype)
    wd = maybe_dequantize(wd, x.dtype)
    h = fused_rms_norm(x[:, None], nw, eps)[:, 0]
    ff = fused_swiglu(h @ wg, h @ wu)
    if gather is not None:
        ff = gather(ff)
    o = ff @ wd
    return x + o if residual else o


# ---------------------------------------------------------------------------
# registry: shape-class dispatch with the composition as fallback
# ---------------------------------------------------------------------------
def decode_meta_dims(B, D, H, KV, hd, F, BS, MB, dtype, pool_dtype,
                     quant, tp=1, weight_dtype=None) -> dict:
    """Static dispatch metadata from raw dims — the ONE builder of
    everything the ``supports`` predicates read. The serving/generate
    paths go through :func:`decode_meta`; eager sweeps (bench
    flash_tune) that have no model config call this directly, so their
    dispatch cannot drift from the traced read sites.

    ``tp``: tensor-parallel degree. The tensor-parallel step builds the
    meta from its PER-SHARD dims (H/KV/F here are the LOCAL head and
    intermediate counts as seen inside shard_map), so the VMEM math in
    the predicates is already local; ``tp`` rides alongside so a shard
    of a tp=N mesh is a distinct shape class from a tp=1 model that
    happens to share the local dims (their program caches must not
    collide, and the dispatch report can say which it served)."""
    dtype = jnp.dtype(dtype)
    return {
        "B": int(B), "D": int(D), "H": int(H), "KV": int(KV),
        "hd": int(hd), "F": int(F), "BS": int(BS), "MB": int(MB),
        "dtype": str(dtype), "itemsize": int(dtype.itemsize),
        "pool_dtype": str(jnp.dtype(pool_dtype)),
        "quant": bool(quant), "interpret": bool(_interpret()),
        "tp": int(tp),
        # the weight-dtype CLASS ("int8"/"int4" quantized trees, else
        # the model dtype): it reshapes the VMEM math and the tile
        # candidate lists, and it is static in the trace signature
        # (the param tree's structure carries it)
        "weight_dtype": str(weight_dtype) if weight_dtype
        else str(dtype),
        # the budget is a real dispatch input (it reshapes supports()
        # and the block_f candidate list), so it rides in the meta —
        # visible to the DISPATCH_KEY_GAP lint like every other key
        "vmem_budget": int(_vmem_budget()),
        # the scoped envelope the SINGLE-LAUNCH kernel budgets its
        # combined windows against (the per-stage kernels budget their
        # weight-resident share against vmem_budget above); a dispatch
        # input like the rest, so it rides in the meta and the route key
        "scoped_vmem_budget": int(scoped_vmem_budget()),
    }


def decode_meta(cfg, B, BS, MB, pool_dtype, quant, tp=1,
                weight_dtype=None) -> dict:
    """Static dispatch metadata for one decode step — everything the
    ``supports`` predicates read. Built at trace time from static
    shapes only, so dispatch is deterministic per program."""
    return decode_meta_dims(B, cfg.hidden_size, cfg.num_attention_heads,
                            cfg.num_key_value_heads, cfg.head_dim,
                            cfg.intermediate_size, BS, MB, cfg.dtype,
                            pool_dtype, quant, tp=tp,
                            weight_dtype=weight_dtype)


def _wq_even_reason(meta, dims):
    """int4 packing pairs the two halves of the pack axis — every
    packed dimension must be even. ``dims``: (name, value) pairs."""
    if meta.get("weight_dtype") != "int4":
        return None
    for name, v in dims:
        if v % 2:
            return (f"packed-int4 weights need an even {name} "
                    f"(got {v}): packing pairs the axis halves")
    return None


def _supports_attn(meta):
    if meta["interpret"]:
        return False, "interpret mode (off-TPU): composition is faster"
    hd = meta["hd"]
    if hd % 8 != 0 or hd < 16:
        return False, f"head_dim {hd} not a multiple of 8 (lane tiling)"
    if meta["H"] % meta["KV"] != 0:
        return False, "H not a multiple of KV"
    D, H, KV = meta["D"], meta["H"], meta["KV"]
    it = meta["itemsize"]
    why = _wq_even_reason(meta, (("hidden_size", D),
                                 ("H*head_dim", H * hd)))
    if why:
        return False, why
    wit = _weight_itemsize(meta)
    weights = int((2 * D * H * hd + 2 * D * KV * hd) * wit)
    if wit != it:          # per-output-channel f32 scale rows
        weights += (H * hd + 2 * KV * hd + D) * 4
    page = meta["BS"] * KV * hd * (1 if meta["quant"] else it)
    scratch = (2 * H * hd + 2 * KV * hd + 2 * H) * 4
    # page windows at the WORST-case autotune choice: the tuner may
    # pick any pages-per-step candidate, each holding a K and a V page
    # input block, double-buffered by the pipeline — supports() must
    # admit only shapes that fit whatever the sweep later selects
    pages = 4 * max(PAGE_STEP_CANDIDATES)
    need = weights + pages * page + scratch + 4 * D * it
    budget = meta["vmem_budget"]
    if need > budget:
        return False, (f"block weights + pages need ~{need >> 20}MiB "
                       f"VMEM > budget {budget >> 20}MiB")
    return True, f"fits VMEM (~{need >> 20}MiB)"


def _supports_mlp(meta):
    if meta["interpret"]:
        return False, "interpret mode (off-TPU): composition is faster"
    D, F, B = meta["D"], meta["F"], meta["B"]
    why = _wq_even_reason(meta, (("hidden_size", D),))
    if why:
        return False, why
    fits = _mlp_fitting_candidates(B, D, F, meta["itemsize"],
                                   meta["vmem_budget"],
                                   _weight_itemsize(meta))
    if fits:
        return True, f"fits VMEM at block_f={fits[0]}"
    return False, (f"no intermediate tile of F={F} fits the "
                   f"{meta['vmem_budget'] >> 20}MiB VMEM budget")


def _supports_block(meta):
    """Dispatch predicate for the SINGLE-LAUNCH block kernel. Stricter
    than the per-stage predicates by construction: BOTH weight window
    sets (resident attention tiles + double-buffered MLP tiles, at the
    worst-case pages-per-step and block_f candidates) must fit the
    scoped-VMEM envelope together — bf16 flagship shapes fail this and
    fall back to the two-kernel route; int8/int4 weight classes fit."""
    if meta["interpret"]:
        return False, "interpret mode (off-TPU): composition is faster"
    if meta.get("tp", 1) != 1:
        return False, ("tensor-parallel decode runs the per-stage "
                       "kernels inside shard_map")
    hd = meta["hd"]
    if hd % 8 != 0 or hd < 16:
        return False, f"head_dim {hd} not a multiple of 8 (lane tiling)"
    if meta["H"] % meta["KV"] != 0:
        return False, "H not a multiple of KV"
    why = _wq_even_reason(meta, (("hidden_size", meta["D"]),
                                 ("H*head_dim", meta["H"] * hd)))
    if why:
        return False, why
    fits = _block_fitting_candidates(meta)
    if fits:
        return True, (f"attn+MLP windows fit the scoped envelope at "
                      f"block_f={fits[0]}")
    budget = meta["scoped_vmem_budget"]
    return False, (f"combined attn+MLP weight windows (double-buffered)"
                   f" exceed the {budget >> 20}MiB scoped-VMEM envelope")


def _attn_pallas_variant(x, nw, wq, wk, wv, wo, sin, cos, k_pool,
                         v_pool, block_tables, seq_lens,
                         kv_scales=None, eps=1e-6, residual=True):
    return fused_attn_block_pallas(x, nw, wq, wk, wv, wo, sin, cos,
                                   k_pool, v_pool, block_tables,
                                   seq_lens, kv_scales=kv_scales,
                                   eps=eps, residual=residual)


def _mlp_pallas_variant(x, nw, wg, wu, wd, eps=1e-6, residual=True,
                        layer=None):
    return fused_mlp_block_pallas(x, nw, wg, wu, wd, eps=eps,
                                  residual=residual, layer=layer)


def _block_pallas_variant(x, nw, wq, wk, wv, wo, pw, wg, wu, wd, sin,
                          cos, k_pool, v_pool, block_tables, seq_lens,
                          kv_scales=None, eps=1e-6):
    return fused_decode_block_pallas(x, nw, wq, wk, wv, wo, pw, wg, wu,
                                     wd, sin, cos, k_pool, v_pool,
                                     block_tables, seq_lens,
                                     kv_scales=kv_scales, eps=eps)


KERNELS.register("decode_attn_block", "pallas_fused",
                 _attn_pallas_variant, priority=10,
                 supports=_supports_attn, tags=("serving", "pallas"))
KERNELS.register("decode_attn_block", "unfused", attn_block_ref,
                 priority=0, tags=("serving",))
KERNELS.register("decode_mlp_block", "pallas_fused", _mlp_pallas_variant,
                 priority=10, supports=_supports_mlp,
                 tags=("serving", "pallas"))
KERNELS.register("decode_mlp_block", "unfused", mlp_block_ref,
                 priority=0, tags=("serving",))
# the single-launch op sits ABOVE the two-kernel composition: priority
# 10 is the megakernel (gated by the combined-window predicate),
# priority 0 re-runs the exact two-stage sequence — dispatch falling
# back here IS the two-kernel route, bit-identically
KERNELS.register("decode_block_fused", "pallas_block",
                 _block_pallas_variant, priority=10,
                 supports=_supports_block, tags=("serving", "pallas"))
KERNELS.register("decode_block_fused", "composed", decode_block_composed,
                 priority=0, tags=("serving",))
# every decode_meta_dims key is either in the jitted decode program's
# trace signature (the shape/dtype keys; tp via the sharded local
# shapes + the mesh baked into the shard_map'd program) or in
# generation.py's _PAGED_CACHE route tuple / the engine's program key
# (pins, the VMEM budget, the interpret override, the mesh) — the
# registry lint holds supports() to this declaration
_DECODE_KEY_FIELDS = ("B", "D", "H", "KV", "hd", "F", "BS", "MB",
                      "dtype", "pool_dtype", "quant", "interpret",
                      "tp", "weight_dtype", "vmem_budget",
                      "scoped_vmem_budget")
_DECODE_KEY_COVERS = {"itemsize": "dtype"}
KERNELS.declare_cache_key("decode_attn_block", _DECODE_KEY_FIELDS,
                          covers=_DECODE_KEY_COVERS)
KERNELS.declare_cache_key("decode_mlp_block", _DECODE_KEY_FIELDS,
                          covers=_DECODE_KEY_COVERS)
KERNELS.declare_cache_key("decode_block_fused", _DECODE_KEY_FIELDS,
                          covers=_DECODE_KEY_COVERS)


def resolve_decode_blocks(meta: dict, mode="auto"):
    """Resolve the two decode-block ops for one program.

    ``mode``: "auto"/True — registry dispatch (Pallas where supported,
    composition elsewhere); "pallas" — force the fused kernels (tests /
    audit tracing on CPU); "ref" — force the composition. Returns
    (attn_fn, mlp_fn, variant_dict)."""
    if mode in ("auto", True, None):
        a_name, a_fn = KERNELS.dispatch("decode_attn_block", meta)
        m_name, m_fn = KERNELS.dispatch("decode_mlp_block", meta)
    elif mode in ("pallas", "force"):
        a_name, m_name = "pallas_fused", "pallas_fused"
        a_fn = KERNELS.variant("decode_attn_block", a_name).fn
        m_fn = KERNELS.variant("decode_mlp_block", m_name).fn
    elif mode == "ref":
        a_name = m_name = "unfused"
        a_fn = KERNELS.variant("decode_attn_block", a_name).fn
        m_fn = KERNELS.variant("decode_mlp_block", m_name).fn
    elif mode == "block":
        raise ValueError(
            "fused_decode='block' selects the SINGLE-LAUNCH kernel — "
            "resolve it through resolve_decode_step, not the two-stage "
            "resolver")
    else:
        raise ValueError(
            f"fused_decode mode must be auto|pallas|ref|block, "
            f"got {mode!r}")
    return a_fn, m_fn, {"attn": a_name, "mlp": m_name}


def resolve_decode_step(meta: dict, mode="auto"):
    """Resolve ONE decode step's kernels, single-launch aware.

    Returns ``(block_fn, attn_fn, mlp_fn, variants)``. When the
    single-launch op wins — mode="block" forces it, auto modes dispatch
    it through the registry (the combined-window predicate + any force
    pin) — ``block_fn`` is the whole-block callable and the per-stage
    fns are None. Otherwise ``block_fn`` is None and the per-stage pair
    comes from :func:`resolve_decode_blocks` exactly as before, so
    every non-block tier is bit-identical to the pre-block route. The
    ``variants`` dict always carries all three keys ("block", "attn",
    "mlp") — the observability schema reads them unconditionally."""
    if mode == "block":
        b_name = "pallas_block"
        b_fn = KERNELS.variant("decode_block_fused", b_name).fn
        return b_fn, None, None, {"block": b_name, "attn": b_name,
                                  "mlp": b_name}
    a_fn, m_fn, names = resolve_decode_blocks(meta, mode)
    if mode in ("auto", True, None):
        b_name, b_fn = KERNELS.dispatch("decode_block_fused", meta)
        if b_name == "pallas_block":
            return b_fn, None, None, {"block": b_name, "attn": b_name,
                                      "mlp": b_name}
    return None, a_fn, m_fn, {"block": "composed", **names}


#: the names :func:`resolve_decode_step` reports for the composition
UNFUSED = {"block": "composed", "attn": "unfused", "mlp": "unfused"}


def launch_operands(names: dict, quant: bool = False) -> dict:
    """How each Pallas launch of one layer of the decode loop gets its
    per-layer operands (a layer of the carried KV pools, of the stacked
    MLP weights) under the resolved variants ``names``:
    ``{launch name: "index" | "slice"}``. "index": the launch takes the
    whole stacked array and addresses the layer itself; "slice": its
    wrapper takes one layer's array, which XLA has to copy out for it.
    The loop (``inference.generation._decode_step``) hands operands
    over by these same variant names, so this is its record of which
    launches the no-copy mechanism reaches. Variants that launch
    nothing (the XLA compositions, off the TPU) are not listed."""
    from ..paged_attention import paged_kernel_routed
    if names["block"] == "pallas_block":
        return {"decode_block_fused": "slice"}
    out = {}
    if names["attn"] == "pallas_fused":
        out["decode_attn_block"] = "slice"
    elif not quant and paged_kernel_routed():   # int8 pools attend in XLA
        out["paged_attention_decode"] = "index"
    if names["mlp"] == "pallas_fused":
        out["decode_mlp_block"] = "index"
    return out
