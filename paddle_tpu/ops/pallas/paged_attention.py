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
  and unblocked (``pl.ANY``): the kernel fetches for itself. It sees a
  page as the ``[BS * KV, hd]`` rows it is in memory (row ``r``: token
  ``r // KV`` under KV head ``r % KV``; a merge of neighbouring axes, no
  byte of the pool moves). Inside a slot's step a ``fori_loop`` runs
  over the slot's blocks of ``P`` live pages; a block is fetched by
  ``2 * P`` async copies (one page of K and one of V each, the page read
  off ``block_tables[b, .]``, the layer off the third prefetch operand)
  into a VMEM double buffer, and the next block's copies start before
  the current block is reduced. No slice of the pool is ever made, and
  a page at or after a sequence's length is neither visited nor fetched
  (nor is its block-table entry read).
- a block is reduced AT ONCE (``_block_update``): its ``P * BS * KV``
  rows meet all ``H`` query heads in one score product ``[H, hd] x
  [hd, P*BS*KV]``, one ``max / exp / sum`` over lane-dense rows, one
  rescale of the accumulator and one value product. A row's columns
  under another KV head are masked with the dead positions, so the MXU
  does ``KV`` times the useful multiplies; it is idle anyway, and no
  head is sliced out of a page. Scores, softmax and the m/l/acc scratch
  are float32. bfloat16 q, K and V go to the MXU as they are (exact
  products, float32 sums) and the weights as two bfloat16 terms;
  float32 operands are reduced in float32.
- a slot's last block, if its tokens do not fill it, is one update
  masked by position. Dead rows there hold what an earlier block or
  slot left in VMEM: their weights are zero AND their rows of V are
  selected away (``0 x NaN`` is NaN), so they contribute exactly zero.
- a sliding-window layer's launch (``first``) gets each slot's first
  live position as a fourth prefetch operand: the loop starts at the
  page that holds it, the table is read as a ring, and every block (a
  window is a handful) is one update masked at both ends.
- online softmax across blocks: the reduction's order follows ``P``,
  so ``pages_per_step`` moves the last float32 places of the result
  (the reference is ``paged_attention_decode_xla``, to a tolerance:
  tests/test_paged_attention_kernel.py).

A launch's time inside the decode program, on the v5e at B=32, KV=8,
``P`` = 16: 34-57 us whatever the lengths (32 grid steps, each live
slot's first block an exposed fetch) + 0.083-0.091 us a live page,
which is the page's fetch (64 KiB at 819 GB/s: 0.08 us); 109 us over
decode-sat's 620 pages (my chip runs, PR 30; PERF.md section 5). With a
softmax update a page and a product a KV head a page (PR 28) a page
cost 0.414 us and the launch 283 us.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import (audited_pallas_call, fused_vmem_budget,
                    interpret_mode as _interpret, no_x64)

# Pages a slot fetches, and reduces in one softmax update, per loop
# iteration (the ``pages_per_step`` autotune space). A traced call with
# FLAGS_kernel_autotune off runs ``candidates[0]``, which is what every
# serving cell runs. A block is one reduction, so the choice moves the
# result's last float32 places, and its time (v5e, my chip runs, PR 30;
# PERF.md section 5): inside the decode program 16 and 8 are a launch
# of 108.6 and 109.6 us on decode-sat, 97.2 and 99.4 on chat (a page
# 0.083 | 0.099 us, the rest 57 | 48); in a loop of launches, us at 4 |
# 8 | 16 | 32 pages, B=32/KV=8 over 1,094 pages in 12 slots 255 | 214 |
# 193 | 207, the four-chip shard's KV=2 over 670 pages 125 | 101 | 85 |
# 72. A slot of one token (an idle slot is handed length 1) pays a
# whole masked block: 0.3 us more at 16 than at 8.
PAGE_BLOCK_CANDIDATES = (16, 8, 4)


def _block_update(q, k, v, n_live, scale, kv, groups, m_scr, l_scr,
                  acc_scr, n_dead=None):
    """One online-softmax update over a block's ``T`` flattened rows.

    ``q`` [H, hd]; ``k``/``v`` [T, hd], row ``r`` holding token
    ``r // kv`` of the block under KV head ``r % kv``. Every head meets
    every row in ONE score product and ONE value product (the MXU does
    ``kv`` times the useful multiplies and is idle anyway); a head's
    columns under another KV head are masked with the dead positions.
    ``n_live``: None for a block whose every token is live, else the
    count of leading rows that are (tokens before the slot's length,
    times ``kv``): the rest, whatever the buffer holds there (a stale
    page of an earlier block or slot: ``0 x NaN`` is NaN), are selected
    out of V too and contribute exactly zero."""
    i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16
    H, T = q.shape[0], k.shape[0]
    # bfloat16 operands go to the MXU as they are (exact products,
    # float32 sums); anything else is reduced in float32
    exact = all(x.dtype == bf16 for x in (q, k, v))
    if not exact:
        q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=f32) * f32(scale)
    col = jax.lax.broadcasted_iota(i32, (H, T), 1)
    row = jax.lax.broadcasted_iota(i32, (H, T), 0)
    if kv & (kv - 1) == 0 and groups & (groups - 1) == 0:   # shifts
        live = (col & i32(kv - 1)) == (row >> i32(groups.bit_length() - 1))
    else:
        live = jax.lax.rem(col, i32(kv)) == jax.lax.div(row, i32(groups))
    if n_live is not None:
        live = live & (col < n_live)
        vrow = jax.lax.broadcasted_iota(i32, v.shape, 0)
        dead = vrow >= n_live
        if n_dead is not None:
            live = live & (col >= n_dead)
            dead = dead | (vrow < n_dead)
        v = jnp.where(dead, jnp.zeros_like(v), v)
    s = jnp.where(live, s, f32(-jnp.inf))
    m_prev = m_scr[:]                                     # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # every row has a live column in every block visited, so m_new is
    # finite and a masked column's weight is exp(-inf) = 0 exactly
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
    dims = (((1,), (0,)), ((), ()))
    if exact:
        # p as two bfloat16 terms (a plain cast is another result): the
        # products are exact, their float32 sum keeps 16 bits of p
        hi = p.astype(bf16)
        lo = (p - hi.astype(f32)).astype(bf16)
        pv = (jax.lax.dot_general(hi, v, dims, preferred_element_type=f32)
              + jax.lax.dot_general(lo, v, dims,
                                    preferred_element_type=f32))
    else:
        pv = jax.lax.dot_general(p, v, dims, preferred_element_type=f32)
    acc_scr[:] = acc_scr[:] * alpha + pv
    m_scr[:] = m_new


def _decode_kernel(bt_ref, len_ref, layer_ref, *refs, scale, bs, kv,
                   groups, pp, mb, windowed=False):
    # explicitly-typed literals: the body can be retraced at LOWERING
    # time outside the no_x64 window (jit callers), where bare python
    # literals become f64/i64 and break the specialized call signatures
    i32, f32 = jnp.int32, jnp.float32
    if windowed:        # a fourth prefetch operand: first live positions
        first_ref, *refs = refs
    (q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, m_scr, l_scr,
     acc_scr) = refs
    b = pl.program_id(0)
    layer = layer_ref[0]
    page_rows = bs * kv                  # a page, flattened: [BS*KV, hd]
    # never past the table, whatever length a caller hands in: a page
    # number read beyond it would send a copy anywhere in HBM
    seq_len = jnp.minimum(len_ref[b], i32(mb * bs))
    if windowed:
        # the slot's pages from its first live one on; the table is a
        # ring, so a length past mb * bs is in order and what must not
        # pass the ring is the count of pages held at once
        first = jnp.clip(first_ref[b], i32(0), len_ref[b])
        page0 = first // i32(bs)
        seq_len = jnp.minimum(len_ref[b] - page0 * i32(bs), i32(mb * bs))
        head = first - page0 * i32(bs)   # dead tokens of the first page
    n_pages = (seq_len + i32(bs - 1)) // i32(bs)

    m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    def page_copies(blk, j):
        # the table is read for live pages only (callers guard with
        # pl.when), so garbage past a slot's length is never fetched
        col = blk * i32(pp) + i32(j)
        if windowed:
            col = jax.lax.rem(page0 + col, i32(mb))
        page = bt_ref[b, col]
        half = blk % i32(2)
        return [pltpu.make_async_copy(
            hbm.at[layer, page],
            buf.at[half, pl.ds(j * page_rows, page_rows)],
            sem.at[t, half, j])
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

    def reduce_block(blk, n_live=None, n_dead=None):
        half = blk % i32(2)
        _block_update(q_ref[0], k_buf[half], v_buf[half], n_live, scale,
                      kv, groups, m_scr, l_scr, acc_scr, n_dead=n_dead)

    for_live_pages(i32(0), start_page)

    def full_block(blk, carry):
        # the next block's 2*pp fetches fly while this one is reduced
        for_live_pages(blk + i32(1), start_page)
        for j in range(pp):
            wait_page(blk, j)
        reduce_block(blk)
        return carry

    def window_block(blk, carry):
        # a window is a handful of blocks, its first and its last cut
        # by position: every block is one update masked at both ends
        for_live_pages(blk + i32(1), start_page)
        for_live_pages(blk, wait_page)
        left = seq_len - blk * i32(pp * bs)
        reduce_block(blk, jnp.minimum(left, i32(pp * bs)) * i32(kv),
                     jnp.where(blk == i32(0), head, i32(0)) * i32(kv))
        return carry

    if windowed:
        jax.lax.fori_loop(i32(0), (n_pages + i32(pp - 1)) // i32(pp),
                          window_block, i32(0))
    else:
        # a block whose every token is live is reduced with no position
        # mask; what is left of the slot (fewer than pp * bs tokens,
        # some of them inside a page) is one update masked by position
        n_full = seq_len // i32(pp * bs)
        jax.lax.fori_loop(i32(0), n_full, full_block, i32(0))

        @pl.when(n_full * i32(pp) < n_pages)
        def _rest():
            for_live_pages(n_full, wait_page)
            reduce_block(n_full,
                         (seq_len - n_full * i32(pp * bs)) * i32(kv))

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
                                  pages_per_step=None, layer=None,
                                  first=None):
    """q: [B, H, hd]; pools: [N, BS, KV, hd]; block_tables: [B, MB] int32;
    seq_lens: [B] int32 → [B, H, hd]. seq_len 0 slots return 0.

    ``layer``: the pools are the stacked [L, N, BS, KV, hd] and this is
    the layer to attend over (an int or a traced int32 scalar); the
    result is bit-identical to passing ``pool[layer]``, and no slice of
    the pool is made.

    ``pages_per_step``: KV pages a slot fetches, and reduces in one
    softmax update, per loop iteration (:data:`PAGE_BLOCK_CANDIDATES`).
    None resolves through the autotune cache (``paged_autotune_key``).
    The choice sets the order of the float32 reduction, so it moves the
    result's last float32 places.

    ``first`` [B] int32: a sliding-window layer's launch. Each slot's
    first live position rides as a fourth scalar-prefetch operand; the
    loop starts at the page that holds it, masks the rows before it,
    reads the table as a ring (logical block ``n`` in column ``n %
    MB``) and fetches nothing that lies behind it. None is the program
    without any of that; with ``first`` all zero and lengths within the
    table the two give the same bits."""
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
    # a page as the block's reduction reads it, [BS * KV, hd]: row r is
    # token r // KV under KV head r % KV. A merge of neighbouring axes
    # of a row-major buffer: no byte of the pool moves
    k_pool = k_pool.reshape(k_pool.shape[:2] + (BS * KV, hd))
    v_pool = v_pool.reshape(v_pool.shape[:2] + (BS * KV, hd))
    buf_shape = (2, pp * BS * KV, hd)

    def pool_bytes_fetched(_bt, lens, _layer, first=None):
        # what the kernel copies out of ONE pool: the pages it visits,
        # a slot's live pages from its first live one on
        def pages(b):
            start = 0 if first is None else min(int(first[b]),
                                                int(lens[b])) // BS
            return min(pl.cdiv(int(lens[b]), BS) - start, MB)
        return page * sum(pages(b) for b in range(B))

    windowed = first is not None
    prefetch = (jnp.asarray(block_tables, jnp.int32),
                jnp.asarray(seq_lens, jnp.int32),
                jnp.asarray(layer, jnp.int32).reshape(1))
    if windowed:
        prefetch += (jnp.asarray(first, jnp.int32),)
    out = audited_pallas_call(
        functools.partial(_decode_kernel, scale=scale, bs=BS, kv=KV,
                          groups=groups, pp=pp, mb=MB, windowed=windowed),
        name="paged_attention_decode",
        num_scalar_prefetch=len(prefetch),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM(buf_shape, k_pool.dtype),
            pltpu.VMEM(buf_shape, v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2, pp)),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        fetched_bytes={1: pool_bytes_fetched, 2: pool_bytes_fetched},
        interpret=_interpret(),
    )(*prefetch, q, k_pool, v_pool)
    return out
