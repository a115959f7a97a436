"""Fused prefill-block Pallas kernels: ragged chunked prefill writing
straight into the paged KV pools.

Decode is fused (fused_decode_block.py, PR 6) and training is fused
(fused_train.py, PR 7); prefill — the path that sets TTFT, saturates
the disaggregated prefill group and feeds every fleet replica's radix
cache — still ran the unfused per-chunk building blocks: gather the
request's pages into a dense [MB*BS] view, run ``cached_forward``
(RMSNorm + QKV + RoPE + dense masked attention + o_proj + SwiGLU per
layer, paying full pad FLOPs on the bucket-padded chunk), and scatter
the WHOLE dense view back through the write table. Per
FlashAttention-2-on-CUTLASS and FlashFuser (PAPERS.md), this module
fuses the per-layer prefill chunk into two kernels:

- ``prefill_attn_block``: pre-attention RMSNorm + QKV projection +
  RoPE + flash-style causal attention — the chunk's query rows stream
  the request's LIVE paged-KV history (warm suffix prefill over shared
  prefix pages reads the pools directly, no dense gather) with an
  online softmax, then fold the chunk's own K/V from VMEM scratch
  under the in-chunk causal mask — + output projection + residual.
  The chunk's rope'd K/V come back as dense outputs and the CALLER
  scatters exactly the chunk's token positions into the pools through
  the prefix-cache WRITE table (``ops.paged_attention
  .write_chunk_to_pool``): the COW contract's redirect is preserved,
  and the per-chunk pool traffic drops from the whole MB*BS dense
  view to the chunk's own tokens.
- ``prefill_mlp_block``: post-attention RMSNorm + SwiGLU + residual —
  the decode MLP megakernel (row-count agnostic) re-registered for the
  prefill shape class with its own dispatch predicate.

RAGGED handling: the chunk is padded to its bucket width P, but only
``n_valid`` rows are real prompt tokens. The valid length rides as a
scalar-prefetch bound; query-row blocks entirely past it skip ALL
compute (``pl.when``), and history pages at/after ``pos0`` are both
skipped and fetch-clamped (the paged-attention clamp idiom) — a
mixed-length chunk stops paying pad FLOPs.

Fallback contract: the priority-0 ``unfused`` variants are the exact
per-layer building blocks of the dense chunk composition. Dispatch in
the serving engine is ALL-OR-NOTHING per chunk program: unless BOTH
ops resolve to the Pallas megakernels, the engine runs the verbatim
pre-fusion chunk (gather + ``cached_forward`` + scatter), so the
fallback is bit-identical to the original path by construction —
interpret mode (CPU tier-1), unsupported head dims, and chunks whose
weights + scratch exceed ``PADDLE_TPU_FUSED_VMEM_BUDGET`` all take it.

Acceptance contract: greedy output through the fused-prefill flag must
match the unfused chunk path bit-for-bit wherever the fallback is
selected (cold AND prefix-cache warm, fp32/bf16/int8 pools, colocated
and disaggregated engines — tests/test_fused_prefill_block.py pins
this), and kernel-level parity vs the composition holds to float
tolerance under interpret mode.
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
                    fused_vmem_budget, interpret_mode as _interpret,
                    no_x64, online_softmax_page_update)
from .fused_decode_block import (_kernel_weight, _mlp_fitting_candidates,
                                 _mlp_pallas_variant, _weight_itemsize,
                                 _wq_even_reason, _wq_parts,
                                 mlp_block_ref, weight_dtype_of)
from .registry import KERNELS

__all__ = [
    "fused_prefill_attn_pallas", "prefill_attn_block_ref",
    "prefill_mlp_block_ref", "prefill_meta", "prefill_meta_dims",
    "resolve_prefill_blocks", "prefill_fused_selected",
    "prefill_attn_autotune_key",
]

GLOBAL_FLAGS.define(
    "fused_prefill", True,
    "route the bucketed chunked-prefill programs through the fused "
    "prefill-block kernels where the registry supports them (0 = "
    "always the unfused gather/cached_forward/scatter chunk, for A/B "
    "diagnosis)")

_vmem_budget = fused_vmem_budget

# query-row block candidates (divisors of the bucket width only: the
# grid is (P // BQ, ...) and a ragged q block would drop rows)
_PREFILL_BQ_CANDIDATES = (32, 64, 16, 128)


def _bq_candidates(P: int):
    c = [b for b in _PREFILL_BQ_CANDIDATES if b <= P and P % b == 0]
    return c or [P]


# ---------------------------------------------------------------------------
# attention-stage megakernel
# ---------------------------------------------------------------------------
def _prefill_attn_kernel(tab_ref, b_ref, x_ref, nw_ref, wq_ref, wk_ref,
                         wv_ref, wo_ref, sin_ref, cos_ref, *rest,
                         scale, bs, kv, groups, eps, pp, bq, nh, quant,
                         residual, wq_bits=0):
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
    (q_scr, kc_scr, vc_scr, qb_scr, m_scr, l_scr, acc_scr) = rest[i + 3:]

    qi = pl.program_id(0)
    mi = pl.program_id(1)
    pos0 = b_ref[0]          # tokens already in the pool (the history)
    n_valid = b_ref[1]       # real rows of this chunk (rest is pad)
    P, D = x_ref.shape
    hd = qb_scr.shape[1]
    hd2 = hd // 2
    H = kv * groups
    dt = x_ref.dtype
    # explicitly-typed literals: the body can be retraced at LOWERING
    # time outside the no_x64 window (the fused_decode_block precedent)
    f32 = jnp.float32
    row_live = qi * jnp.int32(bq) < n_valid

    @pl.when((qi == 0) & (mi == 0))
    def _prologue():
        # RMSNorm + QKV + RoPE for the WHOLE chunk, once per kernel
        # invocation (scratch persists across the sequential grid)
        xf = x_ref[:].astype(f32)                          # (P, D)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        h = (xf * jax.lax.rsqrt(ms + f32(eps))).astype(dt) * nw_ref[:]

        def proj(w_ref, s_ref):
            # quantized tiles dequant in the matmul EPILOGUE: the
            # per-output-channel f32 scale row multiplies the f32
            # product (the fused_decode_block contract)
            t = jnp.dot(h, _kernel_weight(w_ref, wq_bits, dt),
                        preferred_element_type=f32)
            return t * s_ref[:] if wq_bits else t

        q = proj(wq_ref, sqw_ref if wq_bits else None)
        k = proj(wk_ref, skw_ref if wq_bits else None)
        v = proj(wv_ref, svw_ref if wq_bits else None)
        sinr, cosr = sin_ref[:], cos_ref[:]                # (P, hd2)

        def rope(t, n):
            # mimic the unfused op order: the projection lands at model
            # dtype, apply_rope recasts to f32 and rotates per column
            # pair; (P, n*hd) stays row-major through the rotation
            t = t.astype(dt).astype(f32).reshape(P, n, hd)
            t1, t2 = t[:, :, :hd2], t[:, :, hd2:]
            s_, c_ = sinr[:, None, :], cosr[:, None, :]
            return jnp.concatenate([t1 * c_ - t2 * s_,
                                    t2 * c_ + t1 * s_], axis=-1)

        qr = rope(q, H).astype(dt)                         # (P, H, hd)
        kr = rope(k, kv).astype(dt)                        # (P, KV, hd)
        vm = v.astype(dt).reshape(P, kv, hd)
        kn_ref[:] = kr        # raw chunk K/V: the caller owns the pool
        vn_ref[:] = vm        # write (quantizing if int8)
        # (P, n, hd) -> (P, n*hd) is a contiguous reshape; column
        # slices per head read back (rows, hd) panels
        q_scr[:] = qr.reshape(P, H * hd)
        # chunk self-attention sees the model-dtype values (the dense
        # composition writes astype(view dtype) into its view BEFORE
        # attending — int8 quantization only applies to the POOL write)
        kc_scr[:] = kr.reshape(P, kv * hd)
        vc_scr[:] = vm.reshape(P, kv * hd)

    @pl.when(row_live & (mi == 0))
    def _init():
        # this q block's rows, head-major ((h, r) -> row h*bq + r) so
        # the shared online-softmax body's per-kv-head row grouping
        # (groups*bq rows per kv head) lines up; fully-pad q blocks
        # never touch their softmax state (the ragged skip)
        qb_scr[:] = jnp.concatenate(
            [q_scr[pl.ds(qi * bq, bq), h * hd:(h + 1) * hd]
             for h in range(H)], axis=0).astype(f32)
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # -- stream the HISTORY pages (positions < pos0): warm prefix pages
    # and earlier chunks of this prompt, read straight from the pools.
    # Every q row of the chunk sits at position >= pos0, so plain
    # causality holds page-wide and the shared reduction body's
    # "tokens at/after seq_len are masked" contract (seq_len = pos0)
    # is exactly the history mask.
    for j in range(pp):
        pg = mi.astype(jnp.int32) * jnp.int32(pp) + jnp.int32(j) \
            if hasattr(mi, "astype") else jnp.int32(mi * pp + j)

        @pl.when(row_live & (mi < nh) & (pg * jnp.int32(bs) < pos0))
        def _page(k_ref=k_refs[j], v_ref=v_refs[j], pg=pg):
            k = k_ref[0].astype(f32)                   # (BS, KV, hd)
            v = v_ref[0].astype(f32)
            if quant:
                k = k * ksc_ref[0][None, :, None]
                v = v * vsc_ref[0][None, :, None]
            online_softmax_page_update(qb_scr[:], k, v, pg, bs, pos0,
                                       scale, kv, groups * bq,
                                       m_scr, l_scr, acc_scr)

    @pl.when(jnp.logical_not(row_live) & (mi == nh))
    def _pad_block():
        # a fully-pad q block skips all compute, but its output block
        # must still be WRITTEN: compiled buffers are uninitialized,
        # and a NaN left in a pad row would reach the VALID rows of
        # the NEXT layer through 0 * NaN in its chunk-fold matmul
        # (pad rows of x feed that layer's K/V columns). Zeros keep
        # every row finite at every depth; pad K/V rows land in the
        # scratch page either way.
        xo_ref[:] = jnp.zeros(xo_ref.shape, xo_ref.dtype)

    @pl.when(row_live & (mi == nh))
    def _epilogue():
        # fold the chunk's own K/V from VMEM scratch under the
        # in-chunk causal mask, then o_proj + residual
        q = qb_scr[:]                                  # (H*bq, hd)
        s_rows, pv_src = [], []
        for kvh in range(kv):
            qg = q[kvh * groups * bq:(kvh + 1) * groups * bq, :]
            kk = kc_scr[:, kvh * hd:(kvh + 1) * hd].astype(f32)
            s_rows.append(jax.lax.dot_general(
                qg, kk, (((1,), (1,)), ((), ())),
                preferred_element_type=f32))           # (g*bq, P)
        s = jnp.concatenate(s_rows, axis=0) * f32(scale)   # (H*bq, P)
        # causal within the chunk: row r (chunk position qi*bq + r%bq)
        # attends chunk columns j <= its position
        r_pos = qi * jnp.int32(bq) + jax.lax.broadcasted_iota(
            jnp.int32, (H * bq, P), 0) % jnp.int32(bq)
        c_pos = jax.lax.broadcasted_iota(jnp.int32, (H * bq, P), 1)
        keep = c_pos <= r_pos
        s = jnp.where(keep, s, f32(-jnp.inf))
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev,
                            jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(keep, p, f32(0.0))
        alpha = jnp.exp(m_prev - m_new)    # 0 when no history ran
        l_fin = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        for kvh in range(kv):
            ps = p[kvh * groups * bq:(kvh + 1) * groups * bq, :]
            vv = vc_scr[:, kvh * hd:(kvh + 1) * hd].astype(f32)
            pv_src.append(jax.lax.dot_general(
                ps, vv, (((1,), (0,)), ((), ())),
                preferred_element_type=f32))           # (g*bq, hd)
        acc_fin = acc_scr[:] * alpha + jnp.concatenate(pv_src, axis=0)
        # j == r is always kept, so l_fin > 0 on every row
        attn = acc_fin / l_fin                         # (H*bq, hd)
        rows = jnp.concatenate(
            [attn[h * bq:(h + 1) * bq, :] for h in range(H)],
            axis=1).astype(dt)                         # (bq, H*hd)
        o = jnp.dot(rows, _kernel_weight(wo_ref, wq_bits, dt),
                    preferred_element_type=f32)
        if wq_bits:
            o = o * sow_ref[:]
        xr = x_ref[pl.ds(qi * bq, bq), :]
        xo_ref[:] = (xr + o.astype(dt)) if residual else o.astype(dt)


def prefill_attn_autotune_key(P, D, H, KV, hd, BS, MB, dtype,
                              pool_dtype, budget=None,
                              weight_dtype=None) -> str:
    """Persistent autotune key for the fused prefill attention kernel's
    (block_q, pages_per_step) pair. The VMEM budget is part of the key:
    winners are stored as an index into the budget-filtered candidate
    list (the fused-MLP precedent). ``weight_dtype`` ("int8"/"int4")
    appends the quantized-weight shape class; None keeps the historic
    fp key."""
    budget = _vmem_budget() if budget is None else int(budget)
    base = (P, D, H, KV, hd, BS, MB, str(jnp.dtype(dtype)),
            str(jnp.dtype(pool_dtype)), budget)
    if weight_dtype:
        base = base + (str(weight_dtype),)
    return f"fused_prefill_attn|{base}"


def _attn_scratch_bytes(P, H, KV, hd, bq, itemsize) -> int:
    """Scratch bytes at query-block width ``bq``: the chunk's q/k/v
    panels at model dtype plus the per-block f32 online-softmax state."""
    return (P * H * hd + 2 * P * KV * hd) * itemsize \
        + (H * bq * hd + H * bq) * 4 \
        + H * bq * hd * 4 + 2 * H * bq * 4


def _attn_vmem_need(meta, bq, pp) -> int:
    D, H, KV, hd = meta["D"], meta["H"], meta["KV"], meta["hd"]
    P, BS = meta["P"], meta["BS"]
    it = meta["itemsize"]
    wit = _weight_itemsize(meta)
    weights = int((2 * D * H * hd + 2 * D * KV * hd) * wit)
    if wit != it:          # per-output-channel f32 scale rows
        weights += (H * hd + 2 * KV * hd + D) * 4
    page = BS * KV * hd * (1 if meta["quant"] else it)
    io = P * D * it + 2 * bq * D * it \
        + 2 * P * (hd // 2) * 4 + 2 * 2 * P * KV * hd * it
    return weights + io + 4 * pp * page \
        + _attn_scratch_bytes(P, H, KV, hd, bq, it)


def _attn_candidates(meta):
    """(block_q, pages_per_step) pairs that fit the VMEM budget —
    dispatch, the traced default pick, and the autotune sweep all
    consume THIS list (the budget-in-meta contract)."""
    pps = [p for p in PAGE_STEP_CANDIDATES if p <= meta["MB"]] or [1]
    budget = meta["vmem_budget"]
    return [(bq, pp) for bq in _bq_candidates(meta["P"]) for pp in pps
            if _attn_vmem_need(meta, bq, pp) <= budget]


@no_x64
def fused_prefill_attn_pallas(x, nw, wq, wk, wv, wo, sin, cos,
                              k_pool, v_pool, table, pos0, n_valid,
                              kv_scales=None, eps=1e-6, block_q=None,
                              pages_per_step=None, residual=True):
    """Fused attention stage of one prefill-chunk block.

    x: [P, D] the chunk's residual-stream rows (bucket-padded; only the
    first ``n_valid`` are real prompt tokens); nw: [D] at x.dtype;
    wq [D, H*hd], wk/wv [D, KV*hd], wo [H*hd, D]; sin/cos: rope rows
    for ABSOLUTE positions pos0..pos0+P-1, [P, hd//2] f32;
    pools [N, BS, KV, hd] (int8 with ``kv_scales``); table [MB] int32 —
    this request's READ table; pos0/n_valid: int32 scalars.

    Returns (x_out [P, D], k_new [P, KV, hd], v_new [P, KV, hd]); the
    caller scatters k_new/v_new's first ``n_valid`` rows into the pools
    through the WRITE table (``write_chunk_to_pool[_quant]``) exactly
    as the dense composition's scatter would, preserving the
    prefix-cache COW redirect. Rows past ``n_valid`` of x_out are
    unspecified (their compute is skipped — the ragged contract).
    """
    P, D = x.shape
    N, BS, KV, hd = k_pool.shape
    MB = table.shape[0]
    # weight-quant normalization (the fused_decode_block idiom): the
    # ORIGINAL leaves stay in the autotune args for the recursion
    wq_in, wk_in, wv_in, wo_in = wq, wk, wv, wo
    wq, sqw, bits, _ = _wq_parts(wq)
    wk, skw, _, _ = _wq_parts(wk)
    wv, svw, _, _ = _wq_parts(wv)
    wo, sow, _, _ = _wq_parts(wo)
    weight_dtype = weight_dtype_of(wq_in, wk_in, wv_in, wo_in)
    H = wq.shape[1] // hd
    groups = H // KV
    scale = 1.0 / math.sqrt(hd)
    quant = kv_scales is not None

    if block_q is None or pages_per_step is None:
        from .autotune import resolve_candidate
        meta = prefill_meta_dims(P, D, H, KV, hd, 4 * D, BS, MB,
                                 x.dtype, k_pool.dtype, quant,
                                 weight_dtype=weight_dtype)
        cands = _attn_candidates(meta) \
            or [(min(_bq_candidates(P)), 1)]
        ck = prefill_attn_autotune_key(P, D, H, KV, hd, BS, MB,
                                       x.dtype, k_pool.dtype,
                                       meta["vmem_budget"],
                                       weight_dtype)

        def build(cfg_):
            bq_, pp_ = cfg_
            return lambda *a: fused_prefill_attn_pallas(
                *a, kv_scales=kv_scales, eps=eps, block_q=bq_,
                pages_per_step=pp_, residual=residual)[0]

        block_q, pages_per_step = resolve_candidate(
            ck, cands, build,
            (x, nw, wq_in, wk_in, wv_in, wo_in, sin, cos, k_pool,
             v_pool, table, pos0, n_valid))
    bq = max(1, min(int(block_q), P))
    if P % bq:
        raise ValueError(f"block_q={bq} must divide the chunk width "
                         f"P={P} (a ragged q block would drop rows)")
    pp = max(1, min(int(pages_per_step), MB))
    nh = pl.cdiv(MB, pp)

    const = lambda qi, mi, tab, b: (0, 0)             # noqa: E731
    qrow = lambda qi, mi, tab, b: (qi, 0)             # noqa: E731
    c3 = lambda qi, mi, tab, b: (0, 0, 0)             # noqa: E731

    def page_index(j):
        # clamp dead/at-the-fold fetches to the last HISTORY page so
        # Mosaic's revisit-elision skips the copy; all-int32 (index
        # maps retrace at lowering time outside the no_x64 window)
        def f(qi, mi, tab_ref, b_ref):
            last = jnp.maximum(b_ref[0] - jnp.int32(1),
                               jnp.int32(0)) // jnp.int32(BS)
            idx = jnp.minimum(mi.astype(jnp.int32) * jnp.int32(pp)
                              + jnp.int32(j), last)
            return (tab_ref[idx], 0, 0, 0)
        return f

    in_specs = [
        pl.BlockSpec((P, D), const),                  # x (whole chunk)
        pl.BlockSpec((1, D), const),                  # norm weight
        # weight tiles at their STORED shapes (int4 halves the rows)
        pl.BlockSpec(tuple(wq.shape), const),         # wq
        pl.BlockSpec(tuple(wk.shape), const),         # wk
        pl.BlockSpec(tuple(wv.shape), const),         # wv
        pl.BlockSpec(tuple(wo.shape), const),         # wo
        pl.BlockSpec((P, hd // 2), const),            # sin rows
        pl.BlockSpec((P, hd // 2), const),            # cos rows
    ]
    inputs = [x, nw.reshape(1, D), wq, wk, wv, wo,
              jnp.asarray(sin, jnp.float32),
              jnp.asarray(cos, jnp.float32)]
    if bits:
        for s in (sqw, skw, svw, sow):
            in_specs.append(pl.BlockSpec((1, s.shape[-1]), const))
            inputs.append(jnp.asarray(s, jnp.float32).reshape(1, -1))
    in_specs += [pl.BlockSpec((1, BS, KV, hd), page_index(j))
                 for j in range(pp)]                  # k history pages
    in_specs += [pl.BlockSpec((1, BS, KV, hd), page_index(j))
                 for j in range(pp)]                  # v history pages
    inputs += [k_pool] * pp + [v_pool] * pp
    if quant:
        in_specs += [pl.BlockSpec((1, KV), const)] * 2
        inputs += [jnp.asarray(kv_scales[0], jnp.float32).reshape(1, KV),
                   jnp.asarray(kv_scales[1], jnp.float32).reshape(1, KV)]

    xo, kn, vn = audited_pallas_call(
        functools.partial(_prefill_attn_kernel, scale=scale, bs=BS,
                          kv=KV, groups=groups, eps=eps, pp=pp, bq=bq,
                          nh=int(nh), quant=quant, residual=residual,
                          wq_bits=bits),
        name="prefill_attn_block",
        num_scalar_prefetch=2,
        # the +1 grid step past the history pages folds the chunk's
        # own K/V and writes the q block's output
        grid=(P // bq, int(nh) + 1),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bq, D), qrow),
            pl.BlockSpec((P, KV, hd), c3),
            pl.BlockSpec((P, KV, hd), c3),
        ],
        scratch_shapes=[
            pltpu.VMEM((P, H * hd), x.dtype),         # q (whole chunk)
            pltpu.VMEM((P, KV * hd), x.dtype),        # chunk K
            pltpu.VMEM((P, KV * hd), x.dtype),        # chunk V
            pltpu.VMEM((H * bq, hd), jnp.float32),    # q block (f32)
            pltpu.VMEM((H * bq, 1), jnp.float32),     # m
            pltpu.VMEM((H * bq, 1), jnp.float32),     # l
            pltpu.VMEM((H * bq, hd), jnp.float32),    # acc
        ],
        # all three outputs are blocks revisited across the page axis
        # (prologue/epilogue writes under pl.when)
        accum_outputs=(0, 1, 2),
        out_shape=[jax.ShapeDtypeStruct((P, D), x.dtype),
                   jax.ShapeDtypeStruct((P, KV, hd), x.dtype),
                   jax.ShapeDtypeStruct((P, KV, hd), x.dtype)],
        interpret=_interpret(),
    )(jnp.asarray(table, jnp.int32),
      jnp.stack([jnp.asarray(pos0, jnp.int32),
                 jnp.asarray(n_valid, jnp.int32)]), *inputs)
    return xo, kn, vn


# ---------------------------------------------------------------------------
# unfused reference variants — the EXACT per-layer building blocks of
# the dense chunk composition (gather + cached_forward + scatter), so
# the kernel parity tests compare against the original math. The
# serving engines go further: when dispatch does not select the Pallas
# pair they run the VERBATIM pre-fusion chunk program, bit-identical
# by construction.
# ---------------------------------------------------------------------------
def prefill_attn_block_ref(x, nw, wq, wk, wv, wo, sin, cos, k_pool,
                           v_pool, table, pos0, n_valid, kv_scales=None,
                           eps=1e-6, residual=True):
    """Dense composition of the attention stage: gather the request's
    pages into a [MB*BS] view (dequantizing int8 pools like the chunk
    runner), run ``_cached_layer``'s attention half at absolute
    positions pos0..pos0+P-1, and return (x_out, k_new, v_new). Pays
    full pad FLOPs — ``n_valid`` rides only for signature parity."""
    from .. import rms_norm as fused_rms_norm
    from ..rope import apply_rope
    from ...quantization.quanters import maybe_dequantize

    # quantized leaves take the DEQUANTIZE-THEN-MATMUL route (the
    # priority-0 fallback contract)
    wq = maybe_dequantize(wq, x.dtype)
    wk = maybe_dequantize(wk, x.dtype)
    wv = maybe_dequantize(wv, x.dtype)
    wo = maybe_dequantize(wo, x.dtype)
    P, D = x.shape
    N, BS, KV, hd = k_pool.shape
    MB = table.shape[0]
    T = MB * BS
    H = wq.shape[1] // hd
    scale = 1.0 / math.sqrt(hd)
    kc = jnp.take(k_pool, table, axis=0).reshape(T, KV, hd)
    vc = jnp.take(v_pool, table, axis=0).reshape(T, KV, hd)
    if kv_scales is not None:
        ksc, vsc = kv_scales
        kc = (kc.astype(jnp.float32)
              * ksc[None, :, None]).astype(x.dtype)
        vc = (vc.astype(jnp.float32)
              * vsc[None, :, None]).astype(x.dtype)
    h = fused_rms_norm(x[None], nw, eps)[0]
    q = (h @ wq).reshape(1, P, H, hd)
    k = (h @ wk).reshape(1, P, KV, hd)
    v = (h @ wv).reshape(1, P, KV, hd)
    # sin/cos are the chunk's PRE-GATHERED rope rows, so row i already
    # encodes absolute position pos0 + i
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    k_new, v_new = k[0], v[0]
    # index operands must share one integer width (pos0 arrives i32
    # from the chunk runners; a bare 0 would promote to i64 under the
    # global x64 flag)
    z = jnp.asarray(pos0, jnp.int32), jnp.int32(0), jnp.int32(0)
    kc = jax.lax.dynamic_update_slice(kc, k_new.astype(kc.dtype), z)
    vc = jax.lax.dynamic_update_slice(vc, v_new.astype(vc.dtype), z)
    rep = H // KV
    if rep > 1:
        kc = jnp.repeat(kc, rep, axis=1)
        vc = jnp.repeat(vc, rep, axis=1)
    scores = jnp.einsum("phd,thd->hpt", q[0].astype(jnp.float32),
                        kc.astype(jnp.float32)) * scale
    t_idx = jnp.arange(T)[None, None, :]
    q_idx = pos0 + jnp.arange(P)[None, :, None]
    scores = jnp.where(t_idx <= q_idx, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("hpt,thd->phd", probs, vc.astype(jnp.float32))
    o = attn.astype(x.dtype).reshape(P, H * hd) @ wo
    return (x + o if residual else o), k_new, v_new


def prefill_mlp_block_ref(x, nw, wg, wu, wd, eps=1e-6, residual=True):
    """``_cached_layer``'s MLP half over the chunk rows (identical math
    to the decode MLP composition — row count is the only difference)."""
    return mlp_block_ref(x, nw, wg, wu, wd, eps=eps, residual=residual)


# ---------------------------------------------------------------------------
# registry: shape-class dispatch with the composition as fallback
# ---------------------------------------------------------------------------
def prefill_meta_dims(P, D, H, KV, hd, F, BS, MB, dtype, pool_dtype,
                      quant, weight_dtype=None) -> dict:
    """Static dispatch metadata for one prefill-chunk program — the ONE
    builder of everything the ``supports`` predicates read. ``P`` is
    the bucket width (chunk rows); the rest mirrors
    :func:`fused_decode_block.decode_meta_dims`."""
    dtype = jnp.dtype(dtype)
    return {
        "P": int(P), "D": int(D), "H": int(H), "KV": int(KV),
        "hd": int(hd), "F": int(F), "BS": int(BS), "MB": int(MB),
        "dtype": str(dtype), "itemsize": int(dtype.itemsize),
        "pool_dtype": str(jnp.dtype(pool_dtype)),
        "quant": bool(quant), "interpret": bool(_interpret()),
        # the weight-dtype class (the fused_decode_block contract):
        # static in the trace signature via the param tree's structure
        "weight_dtype": str(weight_dtype) if weight_dtype
        else str(dtype),
        "vmem_budget": int(_vmem_budget()),
    }


def prefill_meta(cfg, P, BS, MB, pool_dtype, quant,
                 weight_dtype=None) -> dict:
    """Dispatch metadata from a model config + chunk geometry (built at
    trace time from static shapes only)."""
    return prefill_meta_dims(P, cfg.hidden_size,
                             cfg.num_attention_heads,
                             cfg.num_key_value_heads, cfg.head_dim,
                             cfg.intermediate_size, BS, MB, cfg.dtype,
                             pool_dtype, quant,
                             weight_dtype=weight_dtype)


def _supports_prefill_attn(meta):
    if meta["interpret"]:
        return False, "interpret mode (off-TPU): composition is faster"
    hd = meta["hd"]
    if hd % 8 != 0 or hd < 16:
        return False, f"head_dim {hd} not a multiple of 8 (lane tiling)"
    if hd % 128 != 0:
        # the kernel splits (P, n*hd) panels into (P, n, hd) heads with
        # a reshape; the chip's compiler has a layout for it only when
        # a head fills whole 128-lane tiles (compiled for v5e: hd=128
        # passes, hd=64 is refused). A per-head column-slice redesign
        # would lift this (ROADMAP S3).
        return False, (f"head_dim {hd} is narrower than a 128-lane tile:"
                       " Mosaic refuses the per-head split "
                       "('infer-vector-layout: unsupported shape cast', "
                       "tpu.reshape (P, H*hd) -> (P, H, hd))")
    if meta["H"] % meta["KV"] != 0:
        return False, "H not a multiple of KV"
    if meta["P"] % 8 != 0:
        return False, (f"chunk width P={meta['P']} not a multiple of 8 "
                       "(sublane tiling)")
    why = _wq_even_reason(meta, (("hidden_size", meta["D"]),
                                 ("H*head_dim",
                                  meta["H"] * meta["hd"])))
    if why:
        return False, why
    cands = _attn_candidates(meta)
    if not cands:
        need = _attn_vmem_need(meta, min(_bq_candidates(meta["P"])), 1)
        return False, (f"chunk weights + scratch need ~{need >> 20}MiB "
                       f"VMEM > budget {meta['vmem_budget'] >> 20}MiB")
    return True, (f"fits VMEM at (block_q, pages)={cands[0]} "
                  f"(~{_attn_vmem_need(meta, *cands[0]) >> 20}MiB)")


def _supports_prefill_mlp(meta):
    if meta["interpret"]:
        return False, "interpret mode (off-TPU): composition is faster"
    P, D, F = meta["P"], meta["D"], meta["F"]
    why = _wq_even_reason(meta, (("hidden_size", D),))
    if why:
        return False, why
    fits = _mlp_fitting_candidates(P, D, F, meta["itemsize"],
                                   meta["vmem_budget"],
                                   _weight_itemsize(meta))
    if fits:
        return True, f"fits VMEM at block_f={fits[0]}"
    return False, (f"no intermediate tile of F={F} fits the "
                   f"{meta['vmem_budget'] >> 20}MiB VMEM budget")


def _attn_pallas_variant(x, nw, wq, wk, wv, wo, sin, cos, k_pool,
                         v_pool, table, pos0, n_valid, kv_scales=None,
                         eps=1e-6, residual=True):
    return fused_prefill_attn_pallas(
        x, nw, wq, wk, wv, wo, sin, cos, k_pool, v_pool, table, pos0,
        n_valid, kv_scales=kv_scales, eps=eps, residual=residual)


KERNELS.register("prefill_attn_block", "pallas_fused",
                 _attn_pallas_variant, priority=10,
                 supports=_supports_prefill_attn,
                 tags=("serving", "pallas"))
KERNELS.register("prefill_attn_block", "unfused", prefill_attn_block_ref,
                 priority=0, tags=("serving",))
# the MLP kernel is row-count agnostic — the decode megakernel serves
# the prefill shape class under its own op name (its own supports()
# over P rows, its own dispatch report)
KERNELS.register("prefill_mlp_block", "pallas_fused",
                 _mlp_pallas_variant, priority=10,
                 supports=_supports_prefill_mlp,
                 tags=("serving", "pallas"))
KERNELS.register("prefill_mlp_block", "unfused", prefill_mlp_block_ref,
                 priority=0, tags=("serving",))
# every prefill_meta_dims key is either in the jitted chunk program's
# trace signature (the shape/dtype keys; P via the bucket width) or in
# the engines' prefill-route key (pins, the VMEM budget, the interpret
# override) — the registry lint holds supports() to this declaration
_PREFILL_KEY_FIELDS = ("P", "D", "H", "KV", "hd", "F", "BS", "MB",
                       "dtype", "pool_dtype", "quant", "interpret",
                       "weight_dtype", "vmem_budget")
_PREFILL_KEY_COVERS = {"itemsize": "dtype"}
KERNELS.declare_cache_key("prefill_attn_block", _PREFILL_KEY_FIELDS,
                          covers=_PREFILL_KEY_COVERS)
KERNELS.declare_cache_key("prefill_mlp_block", _PREFILL_KEY_FIELDS,
                          covers=_PREFILL_KEY_COVERS)


def resolve_prefill_blocks(meta: dict, mode="auto"):
    """Resolve the two prefill-chunk ops for one bucket program.

    ``mode``: "auto"/True — registry dispatch; "pallas" — force the
    fused kernels (tests / audit tracing on CPU); "ref" — force the
    composition. Returns (attn_fn, mlp_fn, variant_dict)."""
    if mode in ("auto", True, None):
        a_name, a_fn = KERNELS.dispatch("prefill_attn_block", meta)
        m_name, m_fn = KERNELS.dispatch("prefill_mlp_block", meta)
    elif mode in ("pallas", "force"):
        a_name = m_name = "pallas_fused"
        a_fn = KERNELS.variant("prefill_attn_block", a_name).fn
        m_fn = KERNELS.variant("prefill_mlp_block", m_name).fn
    elif mode == "ref":
        a_name = m_name = "unfused"
        a_fn = KERNELS.variant("prefill_attn_block", a_name).fn
        m_fn = KERNELS.variant("prefill_mlp_block", m_name).fn
    else:
        raise ValueError(
            f"fused_prefill mode must be auto|pallas|ref, got {mode!r}")
    return a_fn, m_fn, {"attn": a_name, "mlp": m_name}


def prefill_fused_selected(meta: dict, mode) -> bool:
    """Whether the fused pool-direct chunk program should be built for
    this shape class: ALL-OR-NOTHING — both ops must resolve to the
    Pallas megakernels, otherwise the caller runs the verbatim
    pre-fusion chunk (the bit-identical fallback contract)."""
    if not mode or mode == "ref":
        return False
    _, _, names = resolve_prefill_blocks(meta, mode)
    return (names["attn"] == "pallas_fused"
            and names["mlp"] == "pallas_fused")
