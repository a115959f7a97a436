"""Paged KV-cache attention for serving.

TPU-native redesign of the reference's paged-attention inference kernels
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu and
block_attn.h — "block multi-head attention" with a paged KV cache): the KV
cache lives in a pool of fixed-size blocks; each sequence owns a list of
block ids (its block table), so cache memory is allocated in O(block_size)
units instead of max_seq_len per sequence.

Layout choices for TPU:
- pools are [num_blocks, block_size, KV_heads, head_dim] so a block gather
  (jnp.take on axis 0) is a contiguous HBM read and the trailing
  [head_dim] axis stays lane-aligned (128) for the MXU/VPU;
- decode attention is one fused einsum over the gathered blocks — XLA fuses
  the gather + QK^T + softmax + PV chain; block_tables make the gather
  bounded by max_blocks_per_seq, not the pool size.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .pallas._util import interpret_mode
from .pallas.registry import KERNELS


def paged_attention_decode(q, k_pool, v_pool, block_tables, seq_lens,
                           scale: Optional[float] = None, layer=None,
                           k_scale=None, v_scale=None, first=None):
    """Single-step decode attention over a paged cache.

    q:            [B, H, hd]     query for the current position
    k_pool/v_pool:[N, BS, KV, hd] physical block pools
    block_tables: [B, MB] int32  physical block id per logical block
    seq_lens:     [B]    int32   valid tokens per sequence (incl. current)
    layer:        the pools are the stacked [L, N, BS, KV, hd] and this
                  is the layer to attend over (the decode loop's carried
                  pools: the kernel addresses the layer itself)
    k_scale/v_scale: [KV] per-head dequant scales of int8 pools
    first:        [B]    int32   a sliding-window layer: each slot's
                  first live position. Only positions ``first[b] <= j <
                  seq_lens[b]`` are attended, only their pages visited,
                  and the table is a RING: logical block ``n`` sits in
                  column ``n % MB`` (a slot holds at most MB pages at
                  once; what lies behind the window went back to the
                  pool and its column was reused). None: every position
                  before ``seq_lens[b]``, column ``n`` (today's program)
    returns       [B, H, hd]

    The kernel registry chooses the launch (op ``paged_attention_decode``):
    ``pallas`` (ops/pallas/paged_attention.py, pages streamed through
    VMEM off scalar-prefetched block tables) where
    :func:`_supports_pallas` holds, else the ``xla`` gather+einsum.
    Every decode program gets its attention here, so they all get the
    same choice. A kernel failure on TPU raises.
    """
    _, fn = KERNELS.dispatch(
        "paged_attention_decode",
        decode_attention_meta(k_pool.dtype, q.shape[-1]))
    return fn(q, k_pool, v_pool, block_tables, seq_lens, scale=scale,
              k_scale=k_scale, v_scale=v_scale, layer=layer, first=first)


def decode_attention_meta(pool_dtype, head_dim: int = 128) -> dict:
    """What the ``paged_attention_decode`` variants' predicates read."""
    return {"backend": jax.default_backend(),
            "interpret": bool(interpret_mode()),
            "pool_dtype": str(jnp.dtype(pool_dtype)),
            "head_dim": int(head_dim)}


def _supports_pallas(meta):
    # a program GSPMD partitions is refused by the registry itself, for
    # every variant tagged "pallas" (_util.gspmd_refusal)
    if meta["interpret"]:
        return False, "interpret mode (off-TPU): composition is faster"
    if meta["backend"] != "tpu":
        return False, (f"default backend is {meta['backend']!r}: a "
                       "Mosaic kernel compiles for a TPU only")
    if meta["pool_dtype"] == "int8":
        return False, ("int8 pools: the kernel fetches pages at the "
                       "pool's dtype and takes no scales; the "
                       "composition dequantizes in its gather")
    if meta["head_dim"] % 128:
        return False, (f"head_dim {meta['head_dim']}: the kernel's page "
                       "rows [BS*KV, head_dim] and its q and accumulator "
                       "blocks need whole 128-lane rows (the v5e compiler "
                       "refuses the launch at head_dim 64)")
    return True, "TPU backend, compiled kernel, pools at the model dtype"


def _pallas_variant(q, k_pool, v_pool, block_tables, seq_lens, scale=None,
                    k_scale=None, v_scale=None, layer=None, first=None):
    from .pallas.paged_attention import paged_attention_decode_pallas
    if k_scale is not None or v_scale is not None:
        raise ValueError("paged_attention_decode variant 'pallas' takes "
                         "no int8 pools (its supports() refuses them); "
                         "the 'xla' variant dequantizes in its gather")
    return paged_attention_decode_pallas(
        q, k_pool, v_pool, block_tables, seq_lens, scale=scale,
        layer=layer, first=first)


def paged_attention_decode_xla(q, k_pool, v_pool, block_tables, seq_lens,
                               scale: Optional[float] = None,
                               k_scale=None, v_scale=None, layer=None,
                               first=None):
    """Gather+einsum reference path (always XLA, any backend).
    ``k_scale``/``v_scale`` [KV]: per-head dequant for int8 pools —
    applied right after the gather so the rest of the math is shared
    with the bf16 path. ``layer``: stacked pools, read at that layer.
    ``first``: the window's first live position a slot, the table a
    ring (see :func:`paged_attention_decode`): a column's tokens get
    the positions of the one logical block inside ``[first // BS,
    first // BS + MB)`` that maps to it, and the rows of V outside the
    window are selected away (a page given back may hold anything)."""
    if layer is not None:
        k_pool, v_pool = k_pool[layer], v_pool[layer]
    B, H, hd = q.shape
    N, BS, KV, _ = k_pool.shape
    MB = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    # gather each sequence's blocks: [B, MB, BS, KV, hd] → [B, T, KV, hd]
    k = jnp.take(k_pool, block_tables, axis=0).reshape(B, MB * BS, KV, hd)
    v = jnp.take(v_pool, block_tables, axis=0).reshape(B, MB * BS, KV, hd)
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale[None, None, :, None]
    if v_scale is not None:
        v = v.astype(jnp.float32) * v_scale[None, None, :, None]
    rep = H // KV
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    scores = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    T = MB * BS
    if first is None:
        mask = jnp.arange(T)[None, None, :] < seq_lens[:, None, None]
    else:
        first = jnp.asarray(first, jnp.int32)
        blk0 = first // BS                                   # [B]
        col = jnp.arange(MB, dtype=jnp.int32)[None, :]
        blk = blk0[:, None] + (col - blk0[:, None]) % MB     # [B, MB]
        pos = (blk[:, :, None] * BS
               + jnp.arange(BS, dtype=jnp.int32)).reshape(B, T)
        live = (pos >= first[:, None]) & (pos < seq_lens[:, None])
        v = jnp.where(live[:, :, None, None], v, jnp.zeros_like(v))
        mask = live[:, None, :]
    # finite mask value: a padding slot with seq_len 0 would otherwise get
    # an all--inf row and softmax NaN; zero its output instead
    scores = jnp.where(mask, scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bht,bthd->bhd", probs, v.astype(jnp.float32))
    out = jnp.where(seq_lens[:, None, None] > 0, out, 0.0)
    return out.astype(q.dtype)


KERNELS.register("paged_attention_decode", "pallas", _pallas_variant,
                 priority=10, supports=_supports_pallas,
                 tags=("serving", "pallas"))
KERNELS.register("paged_attention_decode", "xla",
                 paged_attention_decode_xla, priority=0,
                 tags=("serving",))
# "interpret" rides in every decode program cache's route key
# (generation.kernel_route); the backend is the process's and the pool
# dtype is in the jit signature
KERNELS.declare_cache_key("paged_attention_decode",
                          ("backend", "interpret", "pool_dtype",
                           "head_dim"))


def write_to_pool(k_pool, v_pool, block_tables, seq_lens, k_new, v_new,
                  layer=None, ring=False):
    """Append one token's K/V per sequence into the paged pools.

    k_new/v_new: [B, KV, hd] for the token at position seq_lens[b] (0-based
    position == current length before append). Returns updated pools.
    ``layer``: the pools are the stacked [L, N, BS, KV, hd] and the
    rows land at ``[layer, page, slot]``: one scatter into the whole
    buffer, which a loop that carries the pools performs in place and
    which touches no other layer's pages.
    ``ring``: the table is a sliding-window layer's ring (logical block
    ``n`` in column ``n % MB``), and a slot that is not decoding
    (``seq_lens`` 0) writes the scratch page whatever its row holds: a
    request that is still prefilling keeps its true row there.
    """
    BS = k_pool.shape[-3]
    pos = seq_lens                       # position to write
    blk_idx = pos // BS                  # logical block
    offset = pos % BS
    if ring:
        blk_idx = blk_idx % block_tables.shape[1]
    phys = jnp.take_along_axis(block_tables, blk_idx[:, None],
                               axis=1)[:, 0]          # [B]
    if ring:
        phys = jnp.where(seq_lens > 0, phys, 0)
    at = (phys, offset) if layer is None else (layer, phys, offset)
    k_pool = k_pool.at[at].set(k_new)
    v_pool = v_pool.at[at].set(v_new)
    return k_pool, v_pool


def write_chunk_to_pool(k_pool, v_pool, wtable, pos0, n_valid,
                        k_new, v_new):
    """Scatter one prefill chunk's K/V into the paged pools.

    k_new/v_new: [P, KV, hd] for token positions pos0..pos0+P-1 of ONE
    request; ``wtable`` [MB] is the request's WRITE table (prefix-cache
    shared pages redirected to scratch page 0, the COW contract), and
    rows at/after ``n_valid`` (bucket padding) are redirected to the
    scratch page too — so the fused prefill path writes exactly the
    chunk's own tokens instead of re-scattering the whole dense view,
    and can never touch a shared page whatever it computes.
    """
    P = k_new.shape[0]
    BS = k_pool.shape[1]
    rows = jnp.arange(P, dtype=jnp.int32)
    pos = jnp.asarray(pos0, jnp.int32) + rows
    valid = rows < jnp.asarray(n_valid, jnp.int32)
    page = jnp.where(valid, jnp.take(jnp.asarray(wtable, jnp.int32),
                                     pos // BS), 0)
    off = pos % BS
    k_pool = k_pool.at[page, off].set(k_new.astype(k_pool.dtype))
    v_pool = v_pool.at[page, off].set(v_new.astype(v_pool.dtype))
    return k_pool, v_pool


def write_chunk_to_pool_quant(k_pool, v_pool, wtable, pos0, n_valid,
                              k_new, v_new, k_scale, v_scale):
    """``write_chunk_to_pool`` for int8 pools: the chunk's K/V quantize
    with the static per-head scales on the way in (the same formula as
    ``quant_cache``, so re-quantizing untouched positions stays exact)."""
    def q(x, s):
        return jnp.clip(jnp.round(x.astype(jnp.float32)
                                  / s[None, :, None]),
                        -127, 127).astype(jnp.int8)
    return write_chunk_to_pool(k_pool, v_pool, wtable, pos0, n_valid,
                               q(k_new, k_scale), q(v_new, v_scale))


# -- int8 cache quantization (static per-head scales) -----------------------
# Reference capability: block_multihead_attention's cache_k/v quant —
# paddle/phi/kernels/fusion/gpu/block_attn.h int8 cache load path with
# static [num_head] dequant scales. On TPU this is purely a memory
# optimization: int8 pools halve KV HBM (2x batch at the same footprint);
# the attention math runs bf16/fp32 after a per-head dequant multiply that
# XLA fuses into the gather consumer.

def quantize_pools(k_pool, v_pool):
    """bf16/f32 pools [N, BS, KV, hd] -> (int8 pools, k_scale [KV],
    v_scale [KV]) with symmetric per-head absmax scales (unwritten
    slots are zero-initialized, so whole-pool absmax is safe)."""
    def one(p):
        amax = jnp.max(jnp.abs(p.astype(jnp.float32)), axis=(0, 1, 3))
        scale = jnp.maximum(amax / 127.0, 1e-8)              # [KV]
        q = jnp.clip(jnp.round(p.astype(jnp.float32)
                               / scale[None, None, :, None]),
                     -127, 127).astype(jnp.int8)
        return q, scale
    kq, ks = one(k_pool)
    vq, vs = one(v_pool)
    return kq, vq, ks, vs


def dequant_cache(x, scale):
    """int8 dense cache view [L, B, T, KV, hd] -> fp32 with per-layer-
    per-head scales [L, KV] (the serving engine's chunked prefill pulls
    quantized pages into a dense view through this)."""
    return x.astype(jnp.float32) * scale[:, None, None, :, None]


def quant_cache(x, scale):
    """Inverse of ``dequant_cache``: fp dense view -> int8 with the same
    static scales. round(clip(q*s/s)) == q, so requantizing positions
    that were only dequantized (not rewritten) is exact."""
    return jnp.clip(jnp.round(x.astype(jnp.float32)
                              / scale[:, None, None, :, None]),
                    -127, 127).astype(jnp.int8)


def write_to_pool_quant(k_pool, v_pool, block_tables, seq_lens,
                        k_new, v_new, k_scale, v_scale, layer=None):
    """``write_to_pool`` for int8 pools: the new token's K/V quantize
    with the static per-head scales on the way in."""
    def q(x, s):
        return jnp.clip(jnp.round(x.astype(jnp.float32)
                                  / s[None, :, None]),
                        -127, 127).astype(jnp.int8)
    return write_to_pool(k_pool, v_pool, block_tables, seq_lens,
                         q(k_new, k_scale), q(v_new, v_scale),
                         layer=layer)


def paged_attention_decode_quant(q, k_pool, v_pool, block_tables,
                                 seq_lens, k_scale, v_scale,
                                 scale: Optional[float] = None,
                                 layer=None):
    """Decode attention over int8 pools: gather int8 (the HBM win),
    dequant per head, then the SAME attention math as the bf16 path."""
    return paged_attention_decode(q, k_pool, v_pool, block_tables,
                                  seq_lens, scale=scale, layer=layer,
                                  k_scale=k_scale, v_scale=v_scale)


class BlockManager:
    """Host-side physical block allocator (reference: the block-table
    bookkeeping AnalysisPredictor does around block_multihead_attention).
    Not jitted — runs in the serving loop between steps.

    Pages are REF-COUNTED so one physical page can back multiple block
    tables (the radix prefix cache shares prompt-prefix pages across
    requests, inference/prefix_cache.py): ``allocate`` hands out pages
    at refcount 1, ``attach`` appends already-populated shared pages to
    a table (incref), ``release`` decrefs every table entry and a page
    returns to the free list only when its count hits 0. When the free
    list runs dry, the ``reclaim`` callback (the prefix cache's LRU
    eviction) gets one chance to free cold cached pages before the
    allocator gives up."""

    def __init__(self, num_blocks: int, block_size: int,
                 max_blocks_per_seq: int, window: Optional[int] = None,
                 window_blocks: int = 0, window_ring: int = 0):
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.free = list(range(num_blocks - 1, -1, -1))
        self.tables = {}            # seq_id -> list of physical block ids
        self.refcount = np.zeros(num_blocks, np.int32)
        self.reclaim = None         # callback(n_pages) -> pages freed
        # the second page class, of a model with sliding-window layers:
        # pages of a pool of its own that a request holds only while
        # they lie inside its window (None: the model has no such layer)
        self.window = (WindowPages(window_blocks, block_size, window,
                                   window_ring)
                       if window else None)

    def alloc_page(self) -> int:
        """Pop one free page at refcount 1 (sole owner: the caller)."""
        if not self.free and self.reclaim is not None:
            self.reclaim(1)
        if not self.free:
            raise RuntimeError("KV cache pool exhausted")
        p = self.free.pop()
        if self.refcount[p] != 0:
            raise RuntimeError(
                f"free list corrupt: page {p} has refcount "
                f"{int(self.refcount[p])}")
        self.refcount[p] = 1
        return p

    def incref(self, page: int):
        if self.refcount[page] <= 0:
            raise RuntimeError(
                f"incref on unowned page {page}: sharing a freed page "
                "would alias live KV data")
        self.refcount[page] += 1

    def decref(self, page: int) -> bool:
        """Drop one reference; returns True when the page was freed.
        Going below zero is a bookkeeping bug, never silently allowed —
        it means a page was double-released while possibly shared."""
        rc = int(self.refcount[page]) - 1
        if rc < 0:
            raise RuntimeError(f"refcount of page {page} went negative")
        self.refcount[page] = rc
        if rc == 0:
            self.free.append(page)
            return True
        return False

    def fork(self, src_page: int) -> int:
        """Copy-on-write allocation: a fresh page destined to receive a
        copy of ``src_page`` (the owner of the pools performs the device
        copy). The source is pinned for the duration so the reclaim
        callback cannot evict it while the fork is in flight."""
        self.incref(src_page)
        try:
            return self.alloc_page()
        finally:
            self.decref(src_page)

    def attach(self, seq_id: int, pages, owned: bool = False):
        """Append already-populated pages (a matched shared prefix, or
        a COW fork whose reference is transferred) to a sequence's
        table. Must run before ``allocate`` fills the suffix."""
        table = self.tables.setdefault(seq_id, [])
        for p in pages:
            if not owned:
                self.incref(p)
            table.append(p)
        return table

    def allocate(self, seq_id: int, num_tokens: int):
        need = (num_tokens + self.block_size - 1) // self.block_size
        table = self.tables.setdefault(seq_id, [])
        shortfall = (need - len(table)) - len(self.free)
        if shortfall > 0 and self.reclaim is not None:
            # one batched eviction pass instead of a tree walk per page
            self.reclaim(shortfall)
        while len(table) < need:
            table.append(self.alloc_page())
        return table

    def append_token(self, seq_id: int, cur_len: int):
        """Ensure capacity for one more token; returns the table."""
        return self.allocate(seq_id, cur_len + 1)

    def release(self, seq_id: int):
        """Give back everything ``seq_id`` holds, of both page classes."""
        for b in self.tables.pop(seq_id, []):
            self.decref(b)
        if self.window is not None:
            self.window.release(seq_id)

    def table_array(self, seq_ids) -> np.ndarray:
        out = np.zeros((len(seq_ids), self.max_blocks_per_seq), np.int32)
        for i, sid in enumerate(seq_ids):
            t = self.tables.get(sid, [])
            out[i, :len(t)] = t
        return out

    def check(self, raise_on_violation: bool = True):
        """Cheap structural invariant sweep over the allocator — the
        single definition shared by the lifecycle model checker
        (analysis/lifecycle.py) and the engines' opt-in per-step
        self-check (``PADDLE_TPU_CHECK_INVARIANTS=1``). Returns the
        list of violation strings (empty = clean); raises instead when
        ``raise_on_violation``.

        Checked here (manager-local; the cross-structure refcount
        EQUALITY needs the radix tree and lives in
        ``PrefixCache.check``):

        - refcounts never negative; free-list pages have refcount 0;
        - no duplicate or out-of-range free-list entries;
        - page conservation: every page is either free or referenced
          (refcount > 0) — no page is ever lost;
        - every table entry is a valid page id with refcount >= the
          number of table references to it (a table can never hold
          more references than the refcount records);
        - the window page class (:meth:`WindowPages.check`), where the
          model has one.
        """
        problems = []
        seen_free = set()
        for p in self.free:
            if not (0 <= p < self.num_blocks):
                problems.append(f"free list holds invalid page {p}")
                continue
            if p in seen_free:
                problems.append(f"page {p} appears twice in free list")
            seen_free.add(p)
            if int(self.refcount[p]) != 0:
                problems.append(
                    f"free page {p} has refcount "
                    f"{int(self.refcount[p])} (must be 0)")
        table_refs = np.zeros(self.num_blocks, np.int64)
        for sid, table in self.tables.items():
            for p in table:
                if not (0 <= p < self.num_blocks):
                    problems.append(
                        f"table {sid} holds invalid page {p}")
                    continue
                table_refs[p] += 1
        for p in range(self.num_blocks):
            rc = int(self.refcount[p])
            if rc < 0:
                problems.append(f"page {p} refcount negative ({rc})")
            if rc == 0 and p not in seen_free:
                problems.append(
                    f"page {p} leaked: refcount 0 but not in free list")
            if rc > 0 and p in seen_free:
                problems.append(
                    f"page {p} in free list with refcount {rc}")
            if rc < int(table_refs[p]):
                problems.append(
                    f"page {p} refcount {rc} < {int(table_refs[p])} "
                    "table references (tables over-share the page)")
        if self.window is not None:
            problems += self.window.check()
        if problems and raise_on_violation:
            raise RuntimeError(
                "BlockManager.check failed:\n  " + "\n  ".join(problems))
        return problems


class WindowPages:
    """The page class of sliding-window layers (host side, beside
    :class:`BlockManager`'s own, which keeps a request's pages to its
    end): a pool of ``num_blocks`` pages of which a request holds only
    those that cover positions a later query can still see.

    A free list, not a ring of fixed pages a slot: a page that falls
    behind a request's window goes back at once and the next request to
    ask gets it, so the pool is sized by what the traffic holds (a
    short request never holds a window's worth), and ``reserve``
    admits by each request's own worst case. What a slot's ROW of the
    device table holds is a ring all the same: logical block ``n`` sits
    in column ``n % ring``, so the row is as wide as the most pages a
    request holds at once, not as its longest length. Page 0 is the
    scratch page (padding and idle slots write there) and is never
    handed out. No page is shared: a reference count would say 0 or 1,
    so ownership is the tables themselves."""

    def __init__(self, num_blocks: int, block_size: int, window: int,
                 ring: int):
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.window = int(window)
        self.ring = int(ring)
        self.free = list(range(self.num_blocks - 1, 0, -1))
        # seq_id -> {logical block: page}; a request's blocks are one
        # run, kept beside the table as [first, end) so that a decode
        # step that changes nothing costs two comparisons
        self.tables = {}
        self.held = {}
        self.reserved = {}      # seq_id -> most pages it may hold at once
        self.released = 0       # pages given back before their request ended

    def need(self, num_tokens: int) -> int:
        """The most pages a request of ``num_tokens`` holds at once."""
        return min(-(-int(num_tokens) // self.block_size), self.ring)

    def can_reserve(self, num_tokens: int) -> bool:
        return (sum(self.reserved.values()) + self.need(num_tokens)
                <= self.num_blocks - 1)

    def reserve(self, seq_id: int, num_tokens: int):
        """Admission: set aside the request's worst case, so that no
        ``advance`` of a running request can find the pool dry."""
        if not self.can_reserve(num_tokens):
            raise RuntimeError("window page pool exhausted")
        self.reserved[seq_id] = self.need(num_tokens)
        self.tables.setdefault(seq_id, {})
        self.held.setdefault(seq_id, (0, 0))

    def advance(self, seq_id: int, lo: int, hi: int):
        """Hold exactly the pages that cover positions ``[lo, hi)``
        (``lo`` may be negative: the window reaches back past the
        start): pages wholly before ``lo`` go back to the free list,
        pages up to ``hi`` are taken from it. Returns (pages released,
        whether the row changed)."""
        BS = self.block_size
        first, end = max(0, lo) // BS, (hi - 1) // BS + 1
        was = self.held[seq_id]
        if (first, end) == was:
            return 0, False
        if end - first > self.ring:
            raise RuntimeError(
                f"positions [{lo}, {hi}) need {end - first} window "
                f"pages at once; a slot's ring has {self.ring}")
        table = self.tables[seq_id]
        gone = range(was[0], min(first, was[1]))
        for n in gone:
            self.free.append(table.pop(n))
        self.released += len(gone)
        for n in range(max(first, was[1]), end):
            if not self.free:
                raise RuntimeError("window page pool exhausted: a "
                                   "request holds more than it reserved")
            table[n] = self.free.pop()
        self.held[seq_id] = (first, end)
        return len(gone), True

    def first_block(self, seq_id: int) -> int:
        """The first logical block the request still holds."""
        return self.held[seq_id][0]

    def row(self, seq_id: int) -> np.ndarray:
        """The request's row of the device table: [ring] int32, logical
        block ``n`` in column ``n % ring``, the scratch page elsewhere."""
        out = np.zeros((self.ring,), np.int32)
        for n, p in self.tables.get(seq_id, {}).items():
            out[n % self.ring] = p
        return out

    def release(self, seq_id: int):
        for p in self.tables.pop(seq_id, {}).values():
            self.free.append(p)
        self.reserved.pop(seq_id, None)
        self.held.pop(seq_id, None)

    def check(self):
        """Violations of this class's invariants (strings; empty =
        clean): every page but the scratch page is free or held by
        exactly one request, never both, never lost; a request holds no
        more than it reserved, in distinct columns of its ring; the
        reservations fit the pool."""
        problems = []
        seen = {}
        for p in self.free:
            if not (0 < p < self.num_blocks):
                problems.append(f"window free list holds invalid page {p}")
            elif p in seen:
                problems.append(f"window page {p} appears twice in free "
                                "list")
            seen[p] = "free"
        for sid, table in self.tables.items():
            if len(table) > self.reserved.get(sid, 0):
                problems.append(
                    f"table {sid} holds {len(table)} window pages, "
                    f"reserved {self.reserved.get(sid, 0)}")
            if sorted(table) != list(range(*self.held.get(sid, (0, 0)))):
                problems.append(f"table {sid}: window blocks "
                                f"{sorted(table)} are not the run "
                                f"{self.held.get(sid)} it is said to hold")
            if len(table) > self.ring:
                problems.append(f"table {sid}: more window blocks than a "
                                "ring has columns")
            for p in table.values():
                if not (0 < p < self.num_blocks):
                    problems.append(
                        f"table {sid} holds invalid window page {p}")
                elif p in seen:
                    problems.append(
                        f"window page {p} held by table {sid} and "
                        f"{seen[p]}")
                seen[p] = f"table {sid}"
        for p in range(1, self.num_blocks):
            if p not in seen:
                problems.append(f"window page {p} leaked: neither free "
                                "nor held")
        if sum(self.reserved.values()) > self.num_blocks - 1:
            problems.append("window reservations exceed the pool")
        return problems
