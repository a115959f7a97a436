"""Flash attention Pallas TPU kernels (fwd + bwd).

TPU-native replacement for the reference's flash-attn CUDA dynload
(paddle/phi/kernels/gpu/flash_attn_kernel.cu:517 → phi::dynload::
flash_attn_fwd, varlen path at :137): blockwise online-softmax attention
tiled for VMEM, with a custom_vjp whose backward is also a Pallas kernel
pair (dq pass + dkv pass).

Capabilities beyond the round-1 kernel:
- native GQA: K/V carry ``kvh < h`` heads; the kernel indexes the KV head
  for each Q head via the BlockSpec index map instead of materializing
  ``repeat_kv`` copies (saves group× KV HBM traffic).
- segment ids (varlen/packed sequences): attention is confined to equal
  segment ids; combined with causal this gives per-sequence causal masks
  for packed batches — the TPU analog of the reference's cu_seqlens
  varlen kernel.
- optional additive bias [b|1, h|1, sq, sk] (ALiBi, relative-position);
  constant by default — pass ``bias_grad=True`` for a learned bias
  (dbias from the dq pass costs a full [b*h, sq, sk] fp32 HBM write in
  backward, so it is opt-in).
- causal block pruning: K/V block fetches above the diagonal are clamped
  to the diagonal block in the index map, so Mosaic's revisit-elision
  skips the copy — fully-masked blocks cost neither compute (pl.when)
  nor HBM reads (~2× fwd speedup for causal).

Layout: public API takes [batch, seq, heads, head_dim] (paddle flash-attn
convention) and transposes to [batch*heads, seq, head_dim] internally so
(seq, head_dim) are the trailing MXU-tiled dims. Row statistics (lse,
delta) ride in a (bh, 1, sq) layout — Mosaic wants the last two block
dims (8,128)-divisible or equal to the array dims.

Block sizes default to (512, 512) on the sequence dims — multiples of the
bf16 (16, 128) tile.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import (audited_pallas_call, interpret_mode as _interpret,
                    no_x64)

DEFAULT_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)


_BLOCK = 512


def _block_sizes(sq, sk, override=None):
    if override is not None:
        return min(override[0], sq), min(override[1], sk)
    bq = min(_BLOCK, sq)
    bk = min(_BLOCK, sk)
    return bq, bk


def flash_supports(sq, sk):
    """-> (supported, reason): whether the kernels tile these sequence
    lengths exactly. The grids are ``cdiv(s, block)`` with no bound mask
    on the tail block, so a length past one block that is not a block
    multiple would read rows past the array (NaN under the interpreter,
    garbage on the chip) into the softmax. The router sends such shapes
    to the jnp composition."""
    for name, s in (("q", sq), ("kv", sk)):
        if s > _BLOCK and s % _BLOCK:
            return False, (f"{name} length {s} is past one {_BLOCK}-row "
                           f"block and not a multiple of it")
    return True, "sequence lengths tile exactly"


def _mask(s, qi, ki, bq, bk, causal, seg_q, seg_k, off=0):
    """Apply causal/segment masks to a [bq, bk] score block. Returns
    (masked scores, valid bool mask or None). The valid mask must also
    zero the probabilities (p = exp(s - m)): with every score at
    DEFAULT_MASK_VALUE the row max equals it and exp(s - m) would be 1
    everywhere — a fully-masked row would silently return the mean of V
    (and leak garbage into dk/dv in backward)."""
    m = None
    if causal:
        # bottom-right aligned (FlashAttention-2 convention, matches the
        # _ref_attention fallback): query row r attends keys <= r + sk - sq
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        m = (q_pos + off) >= k_pos
    if seg_q is not None:
        same = seg_q[:, None] == seg_k[None, :]
        m = same if m is None else (m & same)
    if m is None:
        return s, None
    return jnp.where(m, s, DEFAULT_MASK_VALUE), m


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _dropout_keep(seed, qbh, qi, ki, bq, bk, rate):
    """[bq, bk] keep mask from a counter-based hash (murmur3 finalizer)
    of the ABSOLUTE (query-head, q position, k position) coordinates.

    The forward and BOTH backward kernels regenerate the identical mask
    from the same (seed, coordinates) — no cross-kernel RNG state, and
    unlike pltpu.prng_* it also runs in interpret mode on CPU. The
    per-element dropout decision is position-keyed, so it is invariant
    to block-size autotuning."""
    qpos = (qi * bq
            + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
    kpos = (ki * bk
            + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
    x = (qpos.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         ^ kpos.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
         ^ (seed.astype(jnp.uint32)
            + qbh.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    # top-24-bit uniform. Mosaic cannot lower a direct
    # uint32->float32 cast; (x >> 8) < 2^24 fits int32 exactly,
    # so detour through a (free) signed bitcast before the float cast.
    u = (x >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24))
    return u >= rate


def _fwd_kernel(*refs, scale, causal, bq, bk, has_seg, has_bias,
                off, dropout=0.0):
    i = 3
    bias_ref = seg_q_ref = seg_k_ref = seed_ref = None
    q_ref, k_ref, v_ref = refs[0], refs[1], refs[2]
    if has_bias:
        bias_ref = refs[i]
        i += 1
    if has_seg:
        seg_q_ref, seg_k_ref = refs[i], refs[i + 1]
        i += 2
    if dropout > 0.0:
        seed_ref = refs[i]
        i += 1
    o_ref, lse_ref, m_scr, l_scr, acc_scr = refs[i:i + 5]

    bh_id = pl.program_id(0)   # hoisted: program_id is not legal inside
    qi = pl.program_id(1)      # the pl.when branch in interpret mode
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        run = (ki * bk) <= (qi * bq + bq - 1 + off)

    @pl.when(run)
    def _body():
        q = q_ref[0, :, :]  # [bq, d]
        k = k_ref[0, :, :]  # [bk, d]
        v = v_ref[0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if has_bias:
            s = s + bias_ref[0, :, :].astype(jnp.float32)
        seg_q = seg_q_ref[0, :] if has_seg else None
        seg_k = seg_k_ref[0, :] if has_seg else None
        s, valid = _mask(s, qi, ki, bq, bk, causal, seg_q, seg_k, off)
        m_prev = m_scr[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # [bq, bk]
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        # normalizer uses PRE-dropout probabilities (dropout applies
        # after softmax, reference flash_attn_kernel.cu semantics)
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        p_acc = p
        if dropout > 0.0:
            keep = _dropout_keep(seed_ref[0], bh_id, qi, ki,
                                 bq, bk, dropout)
            p_acc = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout))
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p_acc.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, :, :] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, :] = (m_scr[:] + jnp.log(l_safe))[:, 0]


def _kv_index(h, kvh, causal, bq, bk, off=0):
    """K/V BlockSpec index map: GQA head folding + causal diagonal clamp
    (clamped repeats elide the HBM copy — Mosaic only issues a copy when
    the block index changes)."""
    groups = h // kvh

    def idx(b, i, j):
        kb = (b // h) * kvh + (b % h) // groups
        if causal:
            j = jnp.clip((i * bq + bq - 1 + off) // bk, 0, j)
        return (kb, j, 0)

    return idx


def _bias_index(h, bias_b, bias_h, b_total, causal, bq, bk, clamp, off=0):
    def idx(b, i, j):
        bi = 0 if bias_b == 1 else b // h
        hi = 0 if bias_h == 1 else b % h
        if causal and clamp:
            j = jnp.clip((i * bq + bq - 1 + off) // bk, 0, j)
        return (bi * bias_h + hi, i, j)

    return idx


def _seg_specs(h, bq, bk, causal, clamp_k=True, off=0):
    def q_idx(b, i, j):
        return (b // h, 0, i)

    def k_idx(b, i, j):
        if causal and clamp_k:
            j = jnp.clip((i * bq + bq - 1 + off) // bk, 0, j)
        return (b // h, 0, j)

    return (pl.BlockSpec((None, 1, bq), q_idx),
            pl.BlockSpec((None, 1, bk), k_idx))


def _unpack_meta(meta):
    """meta = (h, kvh, bias_b, bias_h, bias_grad[, blocks[, dropout]])
    -> (h, kvh, bias_b, bias_h, blocks, dropout)."""
    h, kvh, bias_b, bias_h = meta[0], meta[1], meta[2], meta[3]
    blocks = meta[5] if len(meta) >= 6 else None
    dropout = meta[6] if len(meta) >= 7 else 0.0
    return h, kvh, bias_b, bias_h, blocks, dropout


@no_x64
def _fwd(q, k, v, bias, seg_q, seg_k, scale, causal, meta, seed=None):
    """q: [bh, sq, d]; k/v: [bkvh, sk, d] → (o [bh, sq, d], lse [bh, sq]).
    bias: [bias_bh, sq, sk] or None; seg_q/seg_k: [b, 1, s] int32 or None.
    meta = (h, kvh, bias_b, bias_h, bias_grad[, blocks[, dropout]]) —
    static geometry; ``seed`` [1] uint32 feeds the in-kernel dropout."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    h, kvh, bias_b, bias_h, blocks, dropout = _unpack_meta(meta)
    bq, bk = _block_sizes(sq, sk, blocks)
    off = sk - sq
    grid = (bh, pl.cdiv(sq, bq), pl.cdiv(sk, bk))
    has_bias, has_seg = bias is not None, seg_q is not None

    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, d), _kv_index(h, kvh, causal, bq, bk, off)),
        pl.BlockSpec((1, bk, d), _kv_index(h, kvh, causal, bq, bk, off)),
    ]
    args = [q, k, v]
    if has_bias:
        in_specs.append(pl.BlockSpec(
            (1, bq, bk),
            _bias_index(h, bias_b, bias_h, bh, causal, bq, bk, True, off)))
        args.append(bias)
    if has_seg:
        sq_spec, sk_spec = _seg_specs(h, bq, bk, causal, off=off)
        in_specs += [sq_spec, sk_spec]
        args += [seg_q, seg_k]
    if dropout > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, has_seg=has_seg,
                               has_bias=has_bias, off=off, dropout=dropout)
    o, lse = audited_pallas_call(
        kernel,
        name="flash_attention_fwd",
        # o and lse blocks are revisited across the k-block axis
        # (online softmax in scratch, written at the last k block)
        accum_outputs=(0, 1),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(*args)
    return o, lse.reshape(bh, sq)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(*refs, scale, causal, bq, bk, has_seg, has_bias,
                   has_dbias, off, dropout=0.0):
    i = 3
    bias_ref = seg_q_ref = seg_k_ref = seed_ref = None
    q_ref, k_ref, v_ref = refs[0], refs[1], refs[2]
    if has_bias:
        bias_ref = refs[i]
        i += 1
    if has_seg:
        seg_q_ref, seg_k_ref = refs[i], refs[i + 1]
        i += 2
    if dropout > 0.0:
        seed_ref = refs[i]
        i += 1
    do_ref, lse_ref, delta_ref = refs[i:i + 3]
    i += 3
    if has_dbias:
        dq_ref, dbias_ref, dq_scr = refs[i:i + 3]
    else:
        dq_ref, dq_scr = refs[i:i + 2]
        dbias_ref = None

    bh_id = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True
    if causal:
        run = (ki * bk) <= (qi * bq + bq - 1 + off)

    @pl.when(run)
    def _body():
        q = q_ref[0, :, :]
        k = k_ref[0, :, :]
        v = v_ref[0, :, :]
        do = do_ref[0, :, :].astype(jnp.float32)
        lse = lse_ref[0, 0, :][:, None]
        delta = delta_ref[0, 0, :][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + bias_ref[0, :, :].astype(jnp.float32)
        seg_q = seg_q_ref[0, :] if has_seg else None
        seg_k = seg_k_ref[0, :] if has_seg else None
        s, valid = _mask(s, qi, ki, bq, bk, causal, seg_q, seg_k, off)
        p = jnp.exp(s - lse)  # [bq, bk]
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout > 0.0:
            # O = (D o P) V with D = keep/(1-r): dP = D o (dO V^T); the
            # delta trick still holds since rowsum(P o dP) = rowsum(dO o O)
            keep = _dropout_keep(seed_ref[0], bh_id, qi, ki,
                                 bq, bk, dropout)
            dp = jnp.where(keep, dp, 0.0) * (1.0 / (1.0 - dropout))
        ds = p * (dp - delta)  # dbias (pre-scale)
        if dbias_ref is not None:
            dbias_ref[0, :, :] = ds.astype(dbias_ref.dtype)
        ds = ds * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(jnp.logical_not(run))
        def _skipped():
            if dbias_ref is not None:
                dbias_ref[0, :, :] = jnp.zeros(
                    dbias_ref.shape[1:], dbias_ref.dtype)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0, :, :] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, bq, bk, nq, groups, has_seg,
                    has_bias, off, dropout=0.0, h=0, kvh=0):
    i = 3
    bias_ref = seg_q_ref = seg_k_ref = seed_ref = None
    q_ref, k_ref, v_ref = refs[0], refs[1], refs[2]
    if has_bias:
        bias_ref = refs[i]
        i += 1
    if has_seg:
        seg_q_ref, seg_k_ref = refs[i], refs[i + 1]
        i += 2
    if dropout > 0.0:
        seed_ref = refs[i]
        i += 1
    do_ref, lse_ref, delta_ref = refs[i:i + 3]
    i += 3
    dk_ref, dv_ref, dk_scr, dv_scr = refs[i:i + 4]

    bkv_id = pl.program_id(0)
    ki = pl.program_id(1)
    t = pl.program_id(2)          # t = g * nq + qi
    qi = t % nq

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        run = (qi * bq + bq - 1 + off) >= (ki * bk)

    @pl.when(run)
    def _body():
        q = q_ref[0, :, :]
        k = k_ref[0, :, :]
        v = v_ref[0, :, :]
        do = do_ref[0, :, :].astype(jnp.float32)
        lse = lse_ref[0, 0, :][:, None]
        delta = delta_ref[0, 0, :][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + bias_ref[0, :, :].astype(jnp.float32)
        seg_q = seg_q_ref[0, :] if has_seg else None
        seg_k = seg_k_ref[0, :] if has_seg else None
        s, valid = _mask(s, qi, ki, bq, bk, causal, seg_q, seg_k, off)
        p = jnp.exp(s - lse)  # [bq, bk]
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        p_v = p
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout > 0.0:
            # same mask as the forward: query-head index reconstructed
            # from the kv-head grid (bkv_id over B*kvh, group g = t // nq)
            qbh = (bkv_id // kvh) * h + (bkv_id % kvh) * groups + t // nq
            keep = _dropout_keep(seed_ref[0], qbh, qi, ki, bq, bk,
                                 dropout)
            inv = 1.0 / (1.0 - dropout)
            p_v = jnp.where(keep, p, 0.0) * inv   # dV sees D o P
            dp = jnp.where(keep, dp, 0.0) * inv   # dP = D o (dO V^T)
        dv_scr[:] += jax.lax.dot_general(
            p_v.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale  # [bq, bk]
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0, :, :] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, :, :] = dv_scr[:].astype(dv_ref.dtype)


@no_x64
def _bwd_impl(q, k, v, bias, seg_q, seg_k, o, lse, do, scale, causal,
              meta, seed=None):
    bh, sq, d = q.shape
    bkvh, sk, _ = k.shape
    h, kvh, bias_b, bias_h, blocks, dropout = _unpack_meta(meta)
    bias_grad = meta[4]
    bq, bk = _block_sizes(sq, sk, blocks)
    off = sk - sq
    groups = h // kvh
    has_bias, has_seg = bias is not None, seg_q is not None
    has_dbias = has_bias and bias_grad
    nq, nk = pl.cdiv(sq, bq), pl.cdiv(sk, bk)

    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)  # [bh, sq]
    lse3 = lse.reshape(bh, 1, sq)
    delta3 = delta.reshape(bh, 1, sq)

    # ---- dq (+ dbias) pass: grid (bh, nq, nk) --------------------------
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, d), _kv_index(h, kvh, causal, bq, bk, off)),
        pl.BlockSpec((1, bk, d), _kv_index(h, kvh, causal, bq, bk, off)),
    ]
    args = [q, k, v]
    if has_bias:
        # dbias needs every (i, j) block written -> no clamping then
        in_specs.append(pl.BlockSpec(
            (1, bq, bk),
            _bias_index(h, bias_b, bias_h, bh, causal, bq, bk,
                        not has_dbias, off)))
        args.append(bias)
    if has_seg:
        sq_spec, sk_spec = _seg_specs(h, bq, bk, causal, off=off)
        in_specs += [sq_spec, sk_spec]
        args += [seg_q, seg_k]
    if dropout > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)
    in_specs += [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
    ]
    args += [do, lse3, delta3]

    out_specs = [pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((bh, sq, d), q.dtype)]
    if has_dbias:
        out_specs.append(pl.BlockSpec((1, bq, bk),
                                      lambda b, i, j: (b, i, j)))
        out_shape.append(jax.ShapeDtypeStruct((bh, sq, sk), jnp.float32))

    res = audited_pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, has_seg=has_seg, has_bias=has_bias,
                          has_dbias=has_dbias, off=off, dropout=dropout),
        name="flash_attention_bwd_dq",
        # dq accumulates across the k-block axis in scratch (the dbias
        # output, when present, IS injective: one block per (i, j))
        accum_outputs=(0,),
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=_interpret(),
    )(*args)
    if has_dbias:
        dq, dbias_full = res
    else:
        (dq,) = res if isinstance(res, (tuple, list)) else (res,)
        dbias_full = None

    # ---- dkv pass: grid (bkvh, nk, groups*nq) --------------------------
    def q_row(b, j, t):
        g = t // nq
        i = t % nq
        if causal:
            i = jnp.maximum(i, (j * bk - off) // bq)
        return ((b // kvh) * h + (b % kvh) * groups + g, i, 0)

    def stat_row(b, j, t):
        g = t // nq
        i = t % nq
        if causal:
            i = jnp.maximum(i, (j * bk - off) // bq)
        return ((b // kvh) * h + (b % kvh) * groups + g, 0, i)

    def kv_idx(b, j, t):
        return (b, j, 0)

    in_specs2 = [
        pl.BlockSpec((1, bq, d), q_row),
        pl.BlockSpec((1, bk, d), kv_idx),
        pl.BlockSpec((1, bk, d), kv_idx),
    ]
    args2 = [q, k, v]
    if has_bias:
        def bias_idx(b, j, t):
            g = t // nq
            i = t % nq
            if causal:
                i = jnp.maximum(i, (j * bk - off) // bq)
            hq = (b % kvh) * groups + g
            bi = 0 if bias_b == 1 else b // kvh
            hi = 0 if bias_h == 1 else hq
            return (bi * bias_h + hi, i, j)
        in_specs2.append(pl.BlockSpec((1, bq, bk), bias_idx))
        args2.append(bias)
    if has_seg:
        def seg_q_idx(b, j, t):
            i = t % nq
            if causal:
                i = jnp.maximum(i, (j * bk - off) // bq)
            return (b // kvh, 0, i)

        def seg_k_idx(b, j, t):
            return (b // kvh, 0, j)
        in_specs2 += [pl.BlockSpec((None, 1, bq), seg_q_idx),
                      pl.BlockSpec((None, 1, bk), seg_k_idx)]
        args2 += [seg_q, seg_k]
    if dropout > 0.0:
        in_specs2.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args2.append(seed)
    in_specs2 += [
        pl.BlockSpec((1, bq, d), q_row),
        pl.BlockSpec((1, 1, bq), stat_row),
        pl.BlockSpec((1, 1, bq), stat_row),
    ]
    args2 += [do, lse3, delta3]

    dk, dv = audited_pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, groups=groups,
                          has_seg=has_seg, has_bias=has_bias, off=off,
                          dropout=dropout, h=h, kvh=kvh),
        name="flash_attention_bwd_dkv",
        # dk/dv accumulate across the fused (group, q-block) axis
        accum_outputs=(0, 1),
        grid=(bkvh, nk, groups * nq),
        in_specs=in_specs2,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, t: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bkvh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bkvh, sk, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=_interpret(),
    )(*args2)
    return dq, dk, dv, dbias_full


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _flash(q, k, v, bias, seg_q, seg_k, seed, scale, causal, meta):
    o, _ = _fwd(q, k, v, bias, seg_q, seg_k, scale, causal, meta,
                seed=seed)
    return o


def _flash_fwd_rule(q, k, v, bias, seg_q, seg_k, seed, scale, causal,
                    meta):
    o, lse = _fwd(q, k, v, bias, seg_q, seg_k, scale, causal, meta,
                  seed=seed)
    return o, (q, k, v, bias, seg_q, seg_k, seed, o, lse)


def _flash_bwd_rule(scale, causal, meta, res, do):
    q, k, v, bias, seg_q, seg_k, seed, o, lse = res
    dq, dk, dv, dbias_full = _bwd_impl(q, k, v, bias, seg_q, seg_k, o, lse,
                                       do, scale, causal, meta, seed=seed)
    dbias = None
    if dbias_full is not None:
        dbias = dbias_full
        bh = q.shape[0]
        h, kvh, bias_b, bias_h = meta[0], meta[1], meta[2], meta[3]
        b = bh // h
        dbias = dbias.reshape(b, h, q.shape[1], k.shape[1])
        if bias_h == 1:
            dbias = dbias.sum(axis=1, keepdims=True)
        if bias_b == 1:
            dbias = dbias.sum(axis=0, keepdims=True)
        dbias = dbias.reshape(bias_b * bias_h, q.shape[1], k.shape[1]) \
            .astype(bias.dtype)
    return dq, dk, dv, dbias, None, None, None  # segs + seed: no grads


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention_pallas(q, k, v, causal=False, scale=None, bias=None,
                           segment_ids=None, kv_segment_ids=None,
                           bias_grad=False, dropout_rate=0.0,
                           dropout_seed=None):
    """Public API, paddle layout [batch, seq, heads, head_dim].

    - GQA: ``k``/``v`` may carry fewer heads than ``q`` (h % kvh == 0).
    - ``bias``: additive logits bias, [b|1, h|1, sq, sk]. Treated as a
      CONSTANT unless ``bias_grad=True``: the backward for a learned bias
      materializes a full [b*h, sq, sk] fp32 dbias in HBM, so it is
      opt-in; with the default, the bias cotangent is symbolically zero.
    - ``segment_ids`` / ``kv_segment_ids``: [b, sq] / [b, sk] int32;
      attention is confined to equal ids (packed varlen batches).
    - ``dropout_rate`` > 0: IN-KERNEL attention dropout after softmax
      (reference flash_attn_kernel.cu Philox path): the keep mask is a
      counter-based hash of absolute positions regenerated identically
      by the backward kernels, seeded by ``dropout_seed`` (uint32
      scalar; drawn from the framework RNG when None).
    """
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    sk = k.shape[1]
    assert h % kvh == 0, f"query heads {h} not a multiple of kv heads {kvh}"
    s = scale if scale is not None else 1.0 / (d ** 0.5)

    qt = jnp.swapaxes(q, 1, 2).reshape(b * h, sq, d)
    kt = jnp.swapaxes(k, 1, 2).reshape(b * kvh, sk, d)
    vt = jnp.swapaxes(v, 1, 2).reshape(b * kvh, sk, d)

    bias_arg = None
    bias_b = bias_h = 1
    if bias is not None:
        assert bias.ndim == 4, "bias must be [b|1, h|1, sq, sk]"
        bias_b, bias_h = bias.shape[0], bias.shape[1]
        bias_arg = bias.reshape(bias_b * bias_h, sq, sk)
    seg_q_arg = seg_k_arg = None
    if segment_ids is not None:
        seg_q_arg = jnp.asarray(segment_ids, jnp.int32).reshape(b, 1, sq)
        kv_seg = kv_segment_ids if kv_segment_ids is not None \
            else segment_ids
        seg_k_arg = jnp.asarray(kv_seg, jnp.int32).reshape(b, 1, sk)

    blocks = _tuned_blocks(qt, kt, vt, bias_arg, seg_q_arg, seg_k_arg,
                           s, causal, (h, kvh, bias_b, bias_h))
    rate = float(dropout_rate)
    seed_arg = None
    if rate > 0.0:
        if dropout_seed is None:
            from ...core.random import next_key
            dropout_seed = jax.random.randint(
                next_key(), (), 0, jnp.iinfo(jnp.int32).max,
                dtype=jnp.int32)
        seed_arg = jnp.asarray(dropout_seed, jnp.uint32).reshape(1)
    meta = (h, kvh, bias_b, bias_h, bool(bias_grad), blocks, rate)
    o = _flash(qt, kt, vt, bias_arg, seg_q_arg, seg_k_arg, seed_arg,
               s, causal, meta)
    return jnp.swapaxes(o.reshape(b, h, sq, d), 1, 2)


_BLOCK_CANDIDATES = ((512, 512), (256, 512), (512, 256), (1024, 512),
                     (256, 1024))


def autotune_cache_key(bh, sq, sk, kv_bh, d, causal, dtype,
                       has_bias=False, has_seg=False) -> str:
    """Single source of truth for the flash-attention autotune cache
    key (bench.py's flash_tune sweep reports winners by this key)."""
    key = (bh, sq, sk, kv_bh, d, causal, str(dtype), has_bias, has_seg)
    return f"flash_attention|{key}"


def _tuned_blocks(qt, kt, vt, bias_arg, seg_q, seg_k, s, causal, geom):
    """Autotuned (bq, bk) for this shape (reference:
    phi/kernels/autotune/auto_tune_base.h). Eager calls with
    FLAGS_kernel_autotune sweep the candidates; traced calls reuse the
    persistent cache (tuning cannot run while tracing)."""
    from .autotune import autotune, _cache, GLOBAL_FLAGS, interpret_mode
    bh, sq, d = qt.shape
    sk = kt.shape[1]
    if sq < 1024 and sk < 1024:
        return None  # single/double block — nothing to tune
    ck = autotune_cache_key(bh, sq, sk, kt.shape[0], d, causal, qt.dtype,
                            bias_arg is not None, seg_q is not None)
    if isinstance(qt, jax.core.Tracer) or interpret_mode() or             not GLOBAL_FLAGS.get("kernel_autotune"):
        hit = _cache.get(ck) if GLOBAL_FLAGS.get("kernel_autotune") else None
        if hit is not None and 0 <= int(hit) < len(_BLOCK_CANDIDATES):
            return _BLOCK_CANDIDATES[int(hit)]
        return None

    def build(cfg):
        meta = geom + (False, cfg)

        def run(q_, k_, v_):
            o, _ = _fwd(q_, k_, v_, bias_arg, seg_q, seg_k, s, causal,
                        meta)
            return o
        return run

    return autotune(ck, list(_BLOCK_CANDIDATES), build, (qt, kt, vt))
