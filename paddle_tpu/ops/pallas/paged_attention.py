"""Pallas paged-attention decode kernel.

TPU-native replacement for the reference's fused paged KV-cache decode
kernel (paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu
/ block_attn.h). The XLA composition in ops/paged_attention.py gathers
``[B, MB*BS, KV, hd]`` K/V into HBM every step; this kernel instead
fetches each sequence's own pages into VMEM directly from the pool:

- grid = (B,): one grid step a slot. ``block_tables``, ``seq_lens`` and
  the layer index ride as SCALAR PREFETCH operands
  (PrefetchScalarGridSpec); ``q`` and the output are ``(1, H, hd)``
  blocks.
- the pools are the STACKED ``[L, N, BS, KV, hd]`` buffers, passed whole
  and unblocked (``pl.ANY``): the kernel fetches for itself. Inside a
  slot's step a ``fori_loop`` runs over the slot's blocks of ``P`` live
  pages; a block is fetched by ``2 * P`` async copies (one page of K
  and one of V each, the page read off ``block_tables[b, .]``, the
  layer off the third prefetch operand) into a VMEM double buffer, and
  the next block's copies start before the current block is reduced.
  No slice of the pool is ever made, and a page at or after a
  sequence's length is neither visited nor fetched (nor is its
  block-table entry read).
- online softmax (m/l/acc scratch) makes the reduction exact across
  pages; every page goes through ``online_softmax_page_update`` in page
  order, so the result does not depend on ``P``.
- GQA-aware: per KV head, the ``group`` query heads attend the same page
  (one [g, BS] matmul per KV head per page).

A launch's time follows its live pages: on the v5e at B=32, KV=8 about
1.7 us a slot of the grid, 1.4 us more a live slot (its first block's
fetch is exposed) and 0.40 us a live page, which is the per-page
reduction (a page's fetch is 0.08 us of HBM time and is hidden); see
PERF.md.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import (audited_pallas_call, fused_vmem_budget,
                    interpret_mode as _interpret, no_x64,
                    online_softmax_page_update)

# Pages a slot fetches per loop iteration (the ``pages_per_step``
# autotune space). A traced call with FLAGS_kernel_autotune off runs
# ``candidates[0]``, which is what every serving cell runs: 8 measured
# best on the v5e at B=32/KV=8 (0.341 ms a launch over 600 live pages,
# against 0.347 at 4 and 0.350 at 16) and within 1% of 16 at the
# four-chip shard's KV=2 (PERF.md, PR 28).
PAGE_BLOCK_CANDIDATES = (8, 16, 4)


def _decode_kernel(bt_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sem, m_scr, l_scr, acc_scr, *, scale, bs,
                   kv, groups, pp, mb):
    # explicitly-typed literals: the body can be retraced at LOWERING
    # time outside the no_x64 window (jit callers), where bare python
    # literals become f64/i64 and break the specialized call signatures
    i32, f32 = jnp.int32, jnp.float32
    b = pl.program_id(0)
    seq_len = len_ref[b]
    layer = layer_ref[0]
    # never past the table, whatever length a caller hands in: a page
    # number read beyond it would send a copy anywhere in HBM
    n_pages = jnp.minimum((seq_len + i32(bs - 1)) // i32(bs), i32(mb))

    m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    def page_copies(blk, j):
        # the table is read for live pages only (callers guard with
        # pl.when), so garbage past a slot's length is never fetched
        page = bt_ref[b, blk * i32(pp) + i32(j)]
        half = blk % i32(2)
        return [pltpu.make_async_copy(hbm.at[layer, page],
                                      buf.at[half, j], sem.at[t, half, j])
                for t, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf)))]

    def for_live_pages(blk, fn):
        for j in range(pp):
            pl.when(blk * i32(pp) + i32(j) < n_pages)(
                functools.partial(fn, blk, j))

    def start_page(blk, j):
        for c in page_copies(blk, j):
            c.start()

    def wait_page(blk, j):
        for c in page_copies(blk, j):
            c.wait()

    def reduce_page(blk, j):
        # pages go through the reduction in page order whatever pp is
        half = blk % i32(2)
        online_softmax_page_update(
            q_ref[0].astype(f32),                         # [H, hd]
            k_buf[half, j].astype(f32),                   # [BS, KV, hd]
            v_buf[half, j].astype(f32),
            blk * i32(pp) + i32(j), bs, seq_len, scale, kv, groups,
            m_scr, l_scr, acc_scr)

    def wait_and_reduce(blk, j):
        wait_page(blk, j)
        reduce_page(blk, j)

    for_live_pages(i32(0), start_page)

    def full_block(blk, carry):
        # the next block's 2*pp fetches fly while this one is reduced;
        # a block of pp live pages is straight-line code (no branch a
        # page), so one page's products can overlap another's softmax
        for_live_pages(blk + i32(1), start_page)
        for j in range(pp):
            wait_page(blk, j)
        for j in range(pp):
            reduce_page(blk, j)
        return carry

    n_full = n_pages // i32(pp)
    jax.lax.fori_loop(i32(0), n_full, full_block, i32(0))
    # what is left of the slot: fewer than pp pages, each behind a guard
    for_live_pages(n_full, wait_and_reduce)

    l = l_scr[:]
    l_safe = jnp.where(l == f32(0.0), f32(1.0), l)
    o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def paged_autotune_key(B, H, KV, hd, BS, MB, dtype) -> str:
    """Single source of truth for the paged-decode autotune cache key
    (sweeps and traced reads must agree, like flash attention's)."""
    return f"paged_decode|{(B, H, KV, hd, BS, MB, str(dtype))}"


def _page_bytes(BS, KV, hd, dtype) -> int:
    return BS * KV * hd * jnp.dtype(dtype).itemsize


def page_block_candidates(BS, KV, hd, MB, dtype):
    """:data:`PAGE_BLOCK_CANDIDATES` that a table of ``MB`` pages can
    fill and whose K and V double buffers fit the VMEM budget."""
    page = _page_bytes(BS, KV, hd, dtype)
    return [p for p in PAGE_BLOCK_CANDIDATES
            if p <= MB and 4 * p * page <= fused_vmem_budget()] or [1]


def _tuned_page_step(q, k_pool, v_pool, block_tables, seq_lens, MB,
                     layer):
    """Pages per loop iteration for this shape, resolved through the
    shared :func:`.autotune.resolve_candidate` (traced/interpret calls
    read the persistent cache; eager calls with FLAGS_kernel_autotune
    sweep the candidates on device — reference: phi/kernels/autotune)."""
    from .autotune import resolve_candidate
    B, H, hd = q.shape
    BS, KV = k_pool.shape[-3:-1]
    cands = page_block_candidates(BS, KV, hd, MB, k_pool.dtype)

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

    ``pages_per_step``: KV pages a slot fetches per loop iteration
    (:data:`PAGE_BLOCK_CANDIDATES`). None resolves through the autotune
    cache (``paged_autotune_key``); the choice only affects how many
    fetches are in flight, never numerics."""
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

    page = _page_bytes(BS, KV, hd, k_pool.dtype)

    def pool_bytes_fetched(_bt, lens, _layer):
        # what the kernel copies out of ONE pool: the slots' live pages
        return page * sum(min(pl.cdiv(int(lens[b]), BS), MB)
                          for b in range(B))

    out = audited_pallas_call(
        functools.partial(_decode_kernel, scale=scale, bs=BS, kv=KV,
                          groups=groups, pp=pp, mb=MB),
        name="paged_attention_decode",
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pp, BS, KV, hd), k_pool.dtype),
            pltpu.VMEM((2, pp, BS, KV, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2, pp)),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        fetched_bytes={1: pool_bytes_fetched, 2: pool_bytes_fetched},
        interpret=_interpret(),
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(seq_lens, jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, k_pool, v_pool)
    return out
