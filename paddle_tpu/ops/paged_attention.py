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
                           k_scale=None, v_scale=None):
    """Single-step decode attention over a paged cache.

    q:            [B, H, hd]     query for the current position
    k_pool/v_pool:[N, BS, KV, hd] physical block pools
    block_tables: [B, MB] int32  physical block id per logical block
    seq_lens:     [B]    int32   valid tokens per sequence (incl. current)
    layer:        the pools are the stacked [L, N, BS, KV, hd] and this
                  is the layer to attend over (the decode loop's carried
                  pools: the kernel addresses the layer itself)
    k_scale/v_scale: [KV] per-head dequant scales of int8 pools
    returns       [B, H, hd]

    The kernel registry chooses the launch (op ``paged_attention_decode``):
    ``pallas`` (ops/pallas/paged_attention.py, pages streamed through
    VMEM off scalar-prefetched block tables) where
    :func:`_supports_pallas` holds, else the ``xla`` gather+einsum.
    Every decode program gets its attention here, so they all get the
    same choice. A kernel failure on TPU raises.
    """
    _, fn = KERNELS.dispatch("paged_attention_decode",
                             decode_attention_meta(k_pool.dtype))
    return fn(q, k_pool, v_pool, block_tables, seq_lens, scale=scale,
              k_scale=k_scale, v_scale=v_scale, layer=layer)


def decode_attention_meta(pool_dtype) -> dict:
    """What the ``paged_attention_decode`` variants' predicates read."""
    return {"backend": jax.default_backend(),
            "interpret": bool(interpret_mode()),
            "pool_dtype": str(jnp.dtype(pool_dtype))}


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
    return True, "TPU backend, compiled kernel, pools at the model dtype"


def _pallas_variant(q, k_pool, v_pool, block_tables, seq_lens, scale=None,
                    k_scale=None, v_scale=None, layer=None):
    from .pallas.paged_attention import paged_attention_decode_pallas
    if k_scale is not None or v_scale is not None:
        raise ValueError("paged_attention_decode variant 'pallas' takes "
                         "no int8 pools (its supports() refuses them); "
                         "the 'xla' variant dequantizes in its gather")
    return paged_attention_decode_pallas(
        q, k_pool, v_pool, block_tables, seq_lens, scale=scale,
        layer=layer)


def paged_attention_decode_xla(q, k_pool, v_pool, block_tables, seq_lens,
                               scale: Optional[float] = None,
                               k_scale=None, v_scale=None, layer=None):
    """Gather+einsum reference path (always XLA, any backend).
    ``k_scale``/``v_scale`` [KV]: per-head dequant for int8 pools —
    applied right after the gather so the rest of the math is shared
    with the bf16 path. ``layer``: stacked pools, read at that layer."""
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
    mask = jnp.arange(T)[None, None, :] < seq_lens[:, None, None]
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
                          ("backend", "interpret", "pool_dtype"))


def write_to_pool(k_pool, v_pool, block_tables, seq_lens, k_new, v_new,
                  layer=None):
    """Append one token's K/V per sequence into the paged pools.

    k_new/v_new: [B, KV, hd] for the token at position seq_lens[b] (0-based
    position == current length before append). Returns updated pools.
    ``layer``: the pools are the stacked [L, N, BS, KV, hd] and the
    rows land at ``[layer, page, slot]``: one scatter into the whole
    buffer, which a loop that carries the pools performs in place and
    which touches no other layer's pages.
    """
    BS = k_pool.shape[-3]
    pos = seq_lens                       # position to write
    blk_idx = pos // BS                  # logical block
    offset = pos % BS
    phys = jnp.take_along_axis(block_tables, blk_idx[:, None],
                               axis=1)[:, 0]          # [B]
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
                 max_blocks_per_seq: int):
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.free = list(range(num_blocks - 1, -1, -1))
        self.tables = {}            # seq_id -> list of physical block ids
        self.refcount = np.zeros(num_blocks, np.int32)
        self.reclaim = None         # callback(n_pages) -> pages freed

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
        for b in self.tables.pop(seq_id, []):
            self.decref(b)

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
          more references than the refcount records).
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
        if problems and raise_on_violation:
            raise RuntimeError(
                "BlockManager.check failed:\n  " + "\n  ".join(problems))
        return problems
