"""Pallas paged-attention decode kernel.

TPU-native replacement for the reference's fused paged KV-cache decode
kernel (paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu
/ block_attn.h). The XLA composition in ops/paged_attention.py gathers
``[B, MB*BS, KV, hd]`` K/V into HBM every step; this kernel instead streams
each sequence's pages through VMEM directly from the pool:

- ``block_tables`` and ``seq_lens`` ride as SCALAR PREFETCH operands
  (PrefetchScalarGridSpec), so the K/V BlockSpec index maps dereference
  the page table on the fly — the pool is the kernel input, no gather.
- grid = (B, MB): pages of one sequence stream sequentially with the
  usual double-buffered pipeline; online softmax (m/l/acc scratch) makes
  the reduction exact across pages.
- pages at/after a sequence's length are skipped (pl.when) AND their
  fetch is clamped to the sequence's last valid page, so Mosaic's
  revisit-elision skips the HBM copy.
- GQA-aware: per KV head, the ``group`` query heads attend the same page
  (one [g, BS] matmul per KV head per page).
- the pool operand is the STACKED ``[L, N, BS, KV, hd]`` buffer and the
  layer's index a third scalar-prefetch operand: inside a loop over
  layers the launch reads its layer's pages out of the whole carried
  pool, so XLA never has to make a one-layer slice for it.

The per-sequence work is proportional to its real length in pages, not
MB, and the only HBM traffic is one read of the live pages.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import (PAGE_STEP_CANDIDATES, audited_pallas_call,
                    clamped_page_index, interpret_mode as _interpret,
                    no_x64, online_softmax_page_update)


def _decode_kernel(bt_ref, len_ref, _layer_ref, q_ref, *rest, scale, bs,
                   kv, groups, pp):
    k_refs = rest[:pp]
    v_refs = rest[pp:2 * pp]
    o_ref, m_scr, l_scr, acc_scr = rest[2 * pp:]
    b = pl.program_id(0)
    mi = pl.program_id(1)
    seq_len = len_ref[b]
    # explicitly-typed literals: the body can be retraced at LOWERING
    # time outside the no_x64 window (jit callers), where bare python
    # literals become f64/i64 and break the specialized call signatures
    f32 = jnp.float32
    zerof = f32(0.0)

    @pl.when(mi == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # pages-per-grid-step (pp) is an autotune candidate: more pages per
    # step = fewer grid iterations and deeper copy pipelining, at pp
    # extra VMEM page buffers — processed sequentially, so the online
    # softmax is bit-identical across pp choices
    for j in range(pp):
        pg = mi.astype(jnp.int32) * jnp.int32(pp) + jnp.int32(j) \
            if hasattr(mi, "astype") else jnp.int32(mi * pp + j)

        @pl.when(pg * jnp.int32(bs) < seq_len)
        def _body(k_ref=k_refs[j], v_ref=v_refs[j], pg=pg):
            # the reduction body is SHARED with the fused decode-block
            # attention kernel (their bit-parity contract)
            online_softmax_page_update(
                q_ref[0].astype(jnp.float32),             # [H, hd]
                k_ref[0].astype(jnp.float32),             # [BS, KV, hd]
                v_ref[0].astype(jnp.float32),
                pg, bs, seq_len, scale, kv, groups,
                m_scr, l_scr, acc_scr)

    @pl.when(mi == pl.num_programs(1) - 1)
    def _finish():
        l = l_scr[:]
        l_safe = jnp.where(l == zerof, f32(1.0), l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def paged_autotune_key(B, H, KV, hd, BS, MB, dtype) -> str:
    """Single source of truth for the paged-decode autotune cache key
    (sweeps and traced reads must agree, like flash attention's)."""
    return f"paged_decode|{(B, H, KV, hd, BS, MB, str(dtype))}"


def _tuned_page_step(q, k_pool, v_pool, block_tables, seq_lens, MB,
                     layer):
    """Pages-per-grid-step for this shape, resolved through the shared
    :func:`.autotune.resolve_candidate` (traced/interpret calls read
    the persistent cache; eager calls with FLAGS_kernel_autotune sweep
    the candidates on device — reference: phi/kernels/autotune)."""
    from .autotune import resolve_candidate
    B, H, hd = q.shape
    BS, KV = k_pool.shape[-3:-1]
    cands = [p for p in PAGE_STEP_CANDIDATES if p <= MB]
    if len(cands) <= 1:
        return 1

    def build(pp):
        return lambda *a: paged_attention_decode_pallas(
            *a, pages_per_step=pp, layer=layer)

    return resolve_candidate(
        paged_autotune_key(B, H, KV, hd, BS, MB, q.dtype), cands,
        build, (q, k_pool, v_pool, block_tables, seq_lens))


@no_x64
def paged_attention_decode_pallas(q, k_pool, v_pool, block_tables,
                                  seq_lens, scale=None,
                                  pages_per_step=None, layer=None):
    """q: [B, H, hd]; pools: [N, BS, KV, hd]; block_tables: [B, MB] int32;
    seq_lens: [B] int32 → [B, H, hd]. seq_len 0 slots return 0.

    ``layer``: the pools are the stacked [L, N, BS, KV, hd] and this is
    the layer to attend over (an int or a traced int32 scalar); the
    result is bit-identical to passing ``pool[layer]``, and no slice of
    the pool is made.

    ``pages_per_step``: KV pages fetched per grid step (1/2/4). None
    resolves through the autotune cache (``paged_autotune_key``); the
    choice only affects pipelining, never numerics."""
    B, H, hd = q.shape
    BS, KV = k_pool.shape[-3:-1]
    MB = block_tables.shape[1]
    groups = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    if pages_per_step is None:
        pages_per_step = _tuned_page_step(q, k_pool, v_pool,
                                          block_tables, seq_lens, MB,
                                          layer)
    pp = max(1, min(int(pages_per_step), MB))
    if layer is None:       # one layer's pool is a stack of one
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0

    def kv_index(j):
        return clamped_page_index(BS, pp, j)

    out = audited_pallas_call(
        functools.partial(_decode_kernel, scale=scale, bs=BS, kv=KV,
                          groups=groups, pp=pp),
        name="paged_attention_decode",
        num_scalar_prefetch=3,
        grid=(B, pl.cdiv(MB, pp)),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, mi, *_: (b, 0, 0)),
            *[pl.BlockSpec((None, 1, BS, KV, hd), kv_index(j))
              for j in range(pp)],
            *[pl.BlockSpec((None, 1, BS, KV, hd), kv_index(j))
              for j in range(pp)],
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, mi, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
        # the sequence's output block is revisited every page step
        # (online softmax in scratch, written once at the last page)
        accum_outputs=(0,),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=_interpret(),
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(seq_lens, jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q,
      *([k_pool] * pp), *([v_pool] * pp))
    return out
