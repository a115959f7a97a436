"""Autoregressive generation with a KV cache.

TPU-native redesign of the reference's fused-transformer decode path
(paddle/phi/kernels/fusion/gpu/fused_multi_transformer_kernel.cu +
masked_multihead_attention — per-step CUDA kernels over a growing cache):
here prefill and decode are two jitted programs with static shapes; the
decode loop is a ``lax.scan`` over steps carrying the cache, so the whole
generation runs as ONE XLA program — no per-token host round trips.

Cache layout: [L, B, T_max, KV, hd] stacked on the layer axis to match the
model's scanned layer params (models/llama.py).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models import llama as _llama
from ..ops.rope import build_rope_cache, apply_rope


@dataclass
class GenerationConfig:
    """reference: python/paddle/... generation knobs of
    paddlenlp-style generate(); the sampling surface of the serving path."""

    max_new_tokens: int = 64
    temperature: float = 1.0
    top_k: int = 0            # 0 = disabled
    top_p: float = 1.0        # 1.0 = disabled
    eos_token_id: int = -1    # -1 = never stop early
    greedy: bool = False
    # serving-scheduler knobs (ServingEngine/DisaggregatedEngine
    # submit() defaults; ignored by the static generate paths):
    # priority CLASS, lower = more urgent; deadline_s bounds queue
    # wait — a request still queued past it is rejected, not admitted
    # late (inference/admission.py)
    priority: int = 1
    deadline_s: Optional[float] = None


def _mm(h, w):
    """``h @ w`` where ``w`` may be a quantized weight leaf
    (``{"qw8"|"qw4": q, "scale": s}`` — quantization/ptq.py): quantized
    leaves DEQUANTIZE-THEN-MATMUL at the activation dtype, the
    priority-0 fallback contract every unfused matmul site shares (so
    the unfused route is bit-identical to that composition by
    construction)."""
    from ..quantization.quanters import maybe_dequantize
    return h @ maybe_dequantize(w, h.dtype)


def _wq_mode(params):
    """The weight-quant mode a param tree carries (None/"int8"/"int4"),
    read off the tree STRUCTURE — static at trace time, so dispatch
    metas and program-cache route keys can carry it."""
    from ..quantization.ptq import weight_quant_mode
    return weight_quant_mode(params)


def _repeat_kv(x, n):
    """[B, T, KV, hd] -> [B, T, KV*n, hd] (dense-cache GQA expansion)."""
    if n == 1:
        return x
    b, t, kv, hd = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, t, kv, n, hd)) \
        .reshape(b, t, kv * n, hd)


def init_cache(cfg: _llama.LlamaConfig, batch: int, max_len: int,
               dtype=None):
    dtype = dtype or cfg.dtype
    L, KV, hd = (cfg.num_hidden_layers, cfg.num_key_value_heads,
                 cfg.head_dim)
    shape = (L, batch, max_len, KV, hd)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def _cached_layer(lp, x, sin, cos, cfg, kc, vc, pos):
    """Decoder block over S new tokens at absolute position ``pos``,
    reading/writing the cache. kc/vc: [B, T, KV, hd]."""
    from ..ops import rms_norm as fused_rms_norm, swiglu as fused_swiglu
    from ..ops.pallas.fused_decode_block import qkv_project

    H, KV, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    b, s, _ = x.shape
    T = kc.shape[1]
    with jax.named_scope("layer/qkv"):
        h = fused_rms_norm(x, lp["input_norm"].astype(x.dtype),
                           cfg.rms_norm_eps)
        q, k, v = qkv_project(h, lp, (H, KV, hd))
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    with jax.named_scope("layer/kv_write"):
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                          (0, pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                          (0, pos, 0, 0))

    with jax.named_scope("layer/attention"):
        rep = H // KV
        kk = _repeat_kv(kc, rep)    # [B, T, H, hd]
        vv = _repeat_kv(vc, rep)
        scale = 1.0 / math.sqrt(hd)
        scores = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                            kk.astype(jnp.float32)) * scale
        # causal over absolute positions: query i at pos+i sees keys
        # <= pos+i
        t_idx = jnp.arange(T)[None, None, None, :]
        q_idx = pos + jnp.arange(s)[None, None, :, None]
        scores = jnp.where(t_idx <= q_idx, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhst,bthd->bshd", probs,
                          vv.astype(jnp.float32))
    with jax.named_scope("layer/attn_out"):
        attn = attn.astype(x.dtype).reshape(b, s, H * hd)
        x = x + _mm(attn, lp["o_proj"])
    with jax.named_scope("layer/mlp"):
        h = fused_rms_norm(x, lp["post_norm"].astype(x.dtype),
                           cfg.rms_norm_eps)
        ff = fused_swiglu(_mm(h, lp["gate_proj"]), _mm(h, lp["up_proj"]))
        x = x + _mm(ff, lp["down_proj"])
    return x, kc, vc


def cached_forward(params: Dict, tokens, cfg: _llama.LlamaConfig,
                   k_cache, v_cache, pos):
    """Forward over S tokens starting at absolute position ``pos``.
    Returns (logits [B, S, V], k_cache, v_cache)."""
    with jax.named_scope("embed"):
        x = jnp.take(params["embed_tokens"], tokens, axis=0)
    T = k_cache.shape[2]
    s = tokens.shape[1]
    with jax.named_scope("layer/qkv"):        # the rotary table
        sin_full, cos_full = build_rope_cache(T, cfg.head_dim,
                                              base=cfg.rope_theta)
        sin = jax.lax.dynamic_slice_in_dim(sin_full, pos, s, axis=0)
        cos = jax.lax.dynamic_slice_in_dim(cos_full, pos, s, axis=0)

    def scan_fn(carry, xs):
        lp, kc, vc = xs
        x, kc, vc = _cached_layer(lp, carry, sin, cos, cfg, kc, vc, pos)
        return x, (kc, vc)

    from ..ops import rms_norm as fused_rms_norm
    with jax.named_scope("layers"):
        x, (k_cache, v_cache) = jax.lax.scan(
            scan_fn, x, (params["layers"], k_cache, v_cache))
    with jax.named_scope("head"):
        x = fused_rms_norm(x, params["final_norm"].astype(x.dtype),
                           cfg.rms_norm_eps)
        head = params.get("lm_head")
        if head is None:
            head = params["embed_tokens"].T
        return x @ head, k_cache, v_cache


def sample_token(logits, key, gen: GenerationConfig):
    """[B, V] → [B] next tokens. Greedy / temperature / top-k / top-p."""
    logits = logits.astype(jnp.float32)
    if gen.greedy or gen.temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / jnp.maximum(gen.temperature, 1e-6)
    if gen.top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -gen.top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if gen.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep smallest set with cumulative prob >= top_p (always keep top-1)
        cutoff_idx = jnp.sum(cum < gen.top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None],
                                     axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


_RUN_CACHE: Dict = {}
_PAGED_CACHE: Dict = {}
_KEY_CACHE: Dict = {}


def _cache_get(cache: Dict, key):
    """LRU read: re-insert on hit so dict order tracks recency — with
    plain FIFO eviction the hottest serving shape can be the oldest
    entry and get evicted on every insertion (a ~1s retrace per
    request, exactly what these caches exist to prevent)."""
    hit = cache.get(key)
    if hit is not None:
        del cache[key]
        cache[key] = hit
    return hit


def _key_for(seed: int):
    """One 8-byte h2d per distinct seed, not per call."""
    k = _cache_get(_KEY_CACHE, seed)
    if k is None:
        if len(_KEY_CACHE) > 64:
            _KEY_CACHE.pop(next(iter(_KEY_CACHE)))
        k = _KEY_CACHE[seed] = jax.random.key(seed)
    return k


def generate(params: Dict, input_ids, cfg: _llama.LlamaConfig,
             gen: Optional[GenerationConfig] = None,
             seed: int = 0) -> jax.Array:
    """Greedy/sampling generation. input_ids [B, S_in] → [B, S_in + N].

    One jitted program: prefill, then a lax.scan of N decode steps. The
    reference's serving loop launches per-token kernels; on TPU the whole
    loop compiles once and the cache is donated between steps.
    """
    gen = gen or GenerationConfig()
    B, S = input_ids.shape
    T = S + gen.max_new_tokens

    # the compiled runner is cached per (model-config field values,
    # geometry, sampling knobs): defining + jitting `run` fresh on every
    # call forced a full retrace per generate() (fresh function
    # identity), ~1s of host time per serving request. Value-keying
    # keeps a mutated cfg from serving stale traced constants
    ck = (dataclasses.astuple(cfg), B, S, dataclasses.astuple(gen))
    cached = _cache_get(_RUN_CACHE, ck)
    if cached is not None:
        return cached(params, input_ids, _key_for(seed))

    @partial(jax.jit, static_argnums=())
    def run(params, input_ids, key):
        k_cache, v_cache = init_cache(cfg, B, T)
        logits, k_cache, v_cache = cached_forward(
            params, input_ids, cfg, k_cache, v_cache, 0)
        first = sample_token(logits[:, -1], key, gen)
        done0 = (first == gen.eos_token_id)

        def step(carry, i):
            tok, kc, vc, key, done = carry
            key, sub = jax.random.split(key)
            logits, kc, vc = cached_forward(
                params, tok[:, None], cfg, kc, vc, S + i)
            nxt = sample_token(logits[:, -1], sub, gen)
            nxt = jnp.where(done, gen.eos_token_id, nxt)
            done = done | (nxt == gen.eos_token_id)
            return (nxt, kc, vc, key, done), tok

        # step i feeds carry token and emits it as ys[i]; with carry
        # starting at `first`, ys == [first, g1, …, g_{N-1}] — exactly the
        # N generated tokens (the final carry token is the N+1-th, unused)
        _, toks = jax.lax.scan(
            step, (first, k_cache, v_cache, key, done0),
            jnp.arange(gen.max_new_tokens))
        return jnp.concatenate([input_ids, toks.transpose(1, 0)], axis=1)

    if len(_RUN_CACHE) > 16:    # bound: evict the oldest runner only —
        # clearing all would re-trace every hot serving shape
        _RUN_CACHE.pop(next(iter(_RUN_CACHE)))
    _RUN_CACHE[ck] = run
    return run(params, input_ids, _key_for(seed))


# ---------------------------------------------------------------------------
# Paged-KV serving path
# ---------------------------------------------------------------------------
def _fused_prefill_mode(fused_prefill):
    """Normalize a ``fused_prefill`` knob: None reads the global flag
    (default ON — "on where supported": dispatch still falls back to
    the verbatim unfused chunk off-TPU / for unsupported shapes)."""
    from ..core.flags import GLOBAL_FLAGS
    from ..ops.pallas import fused_prefill_block  # noqa: F401 — flag
    if fused_prefill is None:
        fused_prefill = bool(GLOBAL_FLAGS.get("fused_prefill"))
    if fused_prefill is False:
        return False
    if fused_prefill is True:
        return "auto"
    if fused_prefill in ("auto", "pallas", "ref"):
        return fused_prefill
    raise ValueError(f"fused_prefill must be bool|auto|pallas|ref, "
                     f"got {fused_prefill!r}")


def kernel_route():
    """The trace-time inputs, beyond the jit signature, that can reshape
    a program whose kernels the registry dispatches: the registry's
    force-pin stack, the VMEM budget (reshapes supports() and the tile
    candidate lists) and the interpret override. Every cache that holds
    such a program folds this into its key, so a changed route
    retraces and never replays."""
    from ..ops.pallas._util import fused_vmem_budget, interpret_mode
    from ..ops.pallas.registry import KERNELS
    return (KERNELS.forced_state(), fused_vmem_budget(),
            bool(interpret_mode()))


def _prefill_route(mode):
    """:func:`kernel_route` for a fused-prefill chunk program (empty
    when the knob is off; a forced mode consults no pin)."""
    if not mode:
        return ()
    route = kernel_route()
    return route if mode in ("auto", True) else ((),) + route[1:]


def _fused_prefill_forward(params, toks, cfg, k_pools, v_pools, table,
                           wtable, pos0, n_valid, kv_scales=None,
                           mode="auto"):
    """One request's prefill chunk through the fused prefill-block
    kernels, pool-direct (ops/pallas/fused_prefill_block.py).

    toks: [P] int32 bucket-padded chunk tokens (``n_valid`` real);
    pools [L, N, BS, KV, hd]; table/wtable [MB] — the request's READ
    table and prefix-cache WRITE table. Per layer: ONE fused attention
    kernel (RMSNorm + QKV + RoPE + flash attention over the paged
    history + the chunk's own K/V + o_proj + residual), the chunk's
    K/V scattered into the pools through the write table
    (``write_chunk_to_pool[_quant]`` — only the chunk's own positions,
    not the whole dense view), and ONE fused MLP kernel. Returns
    (logits [P, V], k_pools, v_pools). Callers guard with
    :func:`fused_prefill_block.prefill_fused_selected` — when dispatch
    does not pick BOTH Pallas kernels they run the verbatim unfused
    chunk instead (the bit-identical fallback contract).
    """
    from ..ops import rms_norm as fused_rms_norm
    from ..ops.paged_attention import (write_chunk_to_pool,
                                       write_chunk_to_pool_quant)
    from ..ops.pallas.fused_decode_block import split_qkv
    from ..ops.pallas.fused_prefill_block import (prefill_meta,
                                                  resolve_prefill_blocks)

    dims = (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim)
    P = toks.shape[0]
    BS = k_pools.shape[2]
    MB = table.shape[0]
    meta = prefill_meta(cfg, P, BS, MB, k_pools.dtype,
                        kv_scales is not None,
                        weight_dtype=_wq_mode(params))
    attn_fn, mlp_fn, _ = resolve_prefill_blocks(meta, mode)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed_tokens"], toks, axis=0)   # [P, D]
    pos0 = jnp.asarray(pos0, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    with jax.named_scope("layer/qkv"):        # the rotary table
        sin_full, cos_full = build_rope_cache(MB * BS, cfg.head_dim,
                                              base=cfg.rope_theta)
        sin = jax.lax.dynamic_slice_in_dim(sin_full, pos0, P, axis=0)
        cos = jax.lax.dynamic_slice_in_dim(cos_full, pos0, P, axis=0)
    wtable = jnp.asarray(wtable, jnp.int32)

    def layer(x, xs):
        if kv_scales is None:
            lp, kp, vp = xs
            scales = None
        else:
            lp, kp, vp, ksc, vsc = xs
            scales = (ksc, vsc)
        # one launch holds norm, q/k/v, rotary, attention and o_proj
        with jax.named_scope("layer/attention"):
            x, k_new, v_new = attn_fn(
                x, lp["input_norm"].astype(x.dtype), *split_qkv(lp, dims),
                lp["o_proj"], sin, cos, kp, vp, table, pos0, n_valid,
                scales, cfg.rms_norm_eps)
        with jax.named_scope("layer/kv_write"):
            if scales is None:
                kp, vp = write_chunk_to_pool(kp, vp, wtable, pos0,
                                             n_valid, k_new, v_new)
            else:
                kp, vp = write_chunk_to_pool_quant(
                    kp, vp, wtable, pos0, n_valid, k_new, v_new, ksc, vsc)
        with jax.named_scope("layer/mlp"):
            x = mlp_fn(x, lp["post_norm"].astype(x.dtype),
                       lp["gate_proj"], lp["up_proj"], lp["down_proj"],
                       cfg.rms_norm_eps)
        return x, (kp, vp)

    scan_xs = (params["layers"], k_pools, v_pools) if kv_scales is None \
        else (params["layers"], k_pools, v_pools) + tuple(kv_scales)
    with jax.named_scope("layers"):
        x, (k_pools, v_pools) = jax.lax.scan(layer, x, scan_xs)
    with jax.named_scope("head"):
        x = fused_rms_norm(x[None], params["final_norm"].astype(x.dtype),
                           cfg.rms_norm_eps)[0]
        head = params.get("lm_head")
        if head is None:
            head = params["embed_tokens"].T
        return x @ head, k_pools, v_pools


def _mesh_route(sm):
    """The mesh's contribution to a program-cache key: axis name, tp
    degree, collective placement and the device identities (two meshes
    over different chips must not share a compiled program)."""
    if sm is None:
        return ()
    return (sm.axis, sm.tp, sm.collective,
            tuple(int(d.id) for d in sm.mesh.devices.flat))


def _paged_chunk_runner(cfg, gen, quant=False, sm=None, wq=None):
    """Jitted n-step decode scan, cached per (cfg values, gen values) —
    a fresh jit per generate_paged call would re-trace the whole L-layer
    scan every serving request. ``sm``: an optional ServingMesh — the
    scan body then runs the tensor-parallel decode step under shard_map
    (inference/tp.py), still ONE jitted program per chunk size.
    ``wq``: the weight-quant mode ("int8"/"int4"/None) — it rides in
    the param tree's STRUCTURE (the jit signature would retrace
    anyway), but it also reshapes kernel dispatch at trace time, so it
    keys this cache explicitly, beside :func:`kernel_route` (a program
    traced inside a ``KERNELS.force(...)`` block must not be replayed
    for unpinned calls)."""
    ck = (dataclasses.astuple(cfg), dataclasses.astuple(gen), bool(quant),
          kernel_route(), _mesh_route(sm), wq)
    cached = _cache_get(_PAGED_CACHE, ck)
    if cached is not None:
        return cached
    if sm is None:
        step = _decode_step
    else:
        def step(params, tok, cfg_, kp, vp, block_tables, seq_lens,
                 kv_scales=None):
            # one shard_map per decode step inside the scan body (the
            # ONE wiring, shared with the engine's decode program):
            # per-shard forward, sampling on the replicated logits
            # outside — shard_map'd random ops and typed keys disagree
            # across jax versions, and logits are replicated anyway
            extra = tuple(kv_scales) if kv_scales is not None else ()
            return sm.sharded_decode_fn(
                cfg_, quant=kv_scales is not None)(
                params, tok, seq_lens, block_tables, kp, vp, *extra)

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(5, 6))
    def chunk_fn(n, params, tok, key, done, k_pools, v_pools, seq_lens,
                 block_tables, kv_scales=None):
        def body(carry, _):
            tok, key, done, seq_lens, kp, vp = carry
            logits, kp, vp = step(
                params, tok, cfg, kp, vp, block_tables, seq_lens,
                kv_scales=kv_scales)
            key, sub = jax.random.split(key)
            nxt = sample_token(logits, sub, gen)
            nxt = jnp.where(done, gen.eos_token_id, nxt)
            done = done | (nxt == gen.eos_token_id)
            return (nxt, key, done, seq_lens + 1, kp, vp), nxt

        carry, toks = jax.lax.scan(
            body, (tok, key, done, seq_lens, k_pools, v_pools), None,
            length=n)
        tok, key, done, seq_lens, k_pools, v_pools = carry
        return toks, tok, key, done, seq_lens, k_pools, v_pools

    if len(_PAGED_CACHE) > 16:
        _PAGED_CACHE.pop(next(iter(_PAGED_CACHE)))
    _PAGED_CACHE[ck] = chunk_fn
    return chunk_fn


def _layer_loop(params, x, k_pools, v_pools, kv_scales, layer,
                stacked=()):
    """The decode program's ONE loop over layers, which copies nothing.

    ``layer(x, l, lp, kp, vp, scales) -> (x, kp, vp)`` runs layer ``l``
    (an int32 scalar). The KV pools ``[L, N, BS, KV, hd]`` are loop
    CARRY beside ``x``: a layer writes its token into them in place
    (``write_to_pool(..., layer=l)``) and the program's donated input
    pool is its output pool. As a scan's stacked input and output they
    were copied whole every step and held twice in HBM. ``lp`` holds
    layer ``l``'s slice of each leaf of ``params["layers"]`` except the
    leaves named in ``stacked``, which it holds WHOLE, for launches
    that address the layer themselves (a Pallas launch needs a whole
    buffer, so a slice would be copied out for it). A slice that an
    XLA matmul consumes is free where the compiler fuses it into the
    dot: it does for ``o_proj`` and for the engine's fused ``qkv_proj``
    (``fused_decode_block.qkv_project``), which are read in place; the
    three leaves ``q_proj`` / ``k_proj`` / ``v_proj`` of any other tree
    it copies out a layer and re-lays out before it multiplies
    (tests/test_chip_compile.py holds both). ``scales``: layer ``l``'s
    int8-pool scales, or None.
    """
    layers = params["layers"]
    whole = {k: layers[k] for k in stacked}
    sliced = {k: v for k, v in layers.items() if k not in whole}

    def body(carry, xs):
        x, kp, vp = carry
        l, lp, scales = xs
        return layer(x, l, {**lp, **whole}, kp, vp, scales), None

    n = k_pools.shape[0]
    with jax.named_scope("layers"):      # the loop's own bookkeeping
        (x, k_pools, v_pools), _ = jax.lax.scan(
            body, (x, k_pools, v_pools),
            (jnp.arange(n, dtype=jnp.int32), sliced, kv_scales))
    return x, k_pools, v_pools


def _decode_step(params, tok, cfg, k_pools, v_pools, block_tables,
                 seq_lens, kv_scales=None, axis=None, collective=None):
    """One decode token per sequence over paged pools: every dense
    decode program (single-device, and the per-shard body of both
    tensor-parallel placements) is this one function.

    tok: [B] int32 current tokens; k_pools/v_pools: [L, N, BS, KV, hd];
    block_tables: [B, MB]; seq_lens: [B] lengths INCLUDING the current
    token's position (i.e. the new token is written at seq_lens, and
    attention runs over seq_lens+1 tokens).
    ``kv_scales``: (k_scale [L, KV], v_scale [L, KV]) when the pools are
    int8 (static per-head cache quantization — reference block_attn.h
    int8 cache mode): halves KV HBM, the attention math stays fp32.
    ``axis`` / ``collective``: inside ``shard_map`` over that mesh axis
    every array is the local shard (inference/tp.py has the
    placements): "psum" all-reduces each stage's partial projection,
    "gather" all-gathers before o_proj / down_proj and runs the MLP
    composition, whose matmuls then see the single-device operands.

    A layer is q/k/v projections and RoPE in XLA, the token's one
    in-place pool write, ``paged_attention_decode`` over the carried
    pools at that layer, the output projection, then
    ``decode_mlp_block``. The kernel registry picks both launches at
    trace time from what it can observe (``KERNELS.record`` around a
    trace is the record of what it picked). A Pallas ``decode_mlp_block``
    takes the stacked MLP weights whole and addresses the layer itself,
    like the attention launch its pools
    (:func:`...fused_decode_block.launch_operands`).
    Returns (logits [B, V], k_pools, v_pools).
    """
    from ..ops import rms_norm as fused_rms_norm
    from ..ops.paged_attention import write_to_pool, write_to_pool_quant
    from ..ops.pallas import fused_decode_block as fdb
    from ..ops.pallas.registry import KERNELS
    from .tp import _lm_head, _local_dims

    mlp_w = ("post_norm", "gate_proj", "up_proj", "down_proj")
    mlp_name, mlp_fn = "unfused", fdb.mlp_block_ref
    if collective != "gather":
        mlp_name, mlp_fn = KERNELS.dispatch(
            "decode_mlp_block", fdb.decode_meta_dims(
                tok.shape[0], cfg.hidden_size, _local_dims(params, cfg)[2],
                cfg.dtype, weight_dtype=_wq_mode(params)))
    mlp_by_index = "decode_mlp_block" in fdb.launch_operands(
        {"decode_mlp_block": mlp_name})
    eps = cfg.rms_norm_eps
    dims = (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim)
    with jax.named_scope("layer/qkv"):
        sin, cos = build_rope_cache(cfg.max_position_embeddings,
                                    cfg.head_dim, base=cfg.rope_theta)
    # "psum": a stage returns its bare projection partial, ONE
    # all-reduce rebuilds the replicated residual stream (the partial
    # sums associate differently than the single-device reduction:
    # roundoff-parity, documented in inference/tp.py)
    residual = collective != "psum"
    gather = None
    if collective == "gather":
        # heads / columns shard contiguously, so a tiled all-gather
        # rebuilds the exact single-device tensor
        def gather(t):
            return jax.lax.all_gather(t, axis, axis=1, tiled=True)

    def add(x, out):
        return out if residual else x + jax.lax.psum(out, axis)

    # the named scopes are observability.PROGRAM_SCOPES: a reader of a
    # device trace finds each operation's by them (metadata only)
    def layer(x, l, lp, kp, vp, scales):
        # the composition reads the new token from the pool: write it
        # first (once, in place), then attend over the carried pools at
        # this layer
        with jax.named_scope("layer/qkv"):
            q, k_new, v_new = fdb.attn_qkv_ref(
                x, lp["input_norm"].astype(x.dtype), lp, dims, sin, cos,
                seq_lens, eps)
        with jax.named_scope("layer/kv_write"):
            if scales is None:
                kp, vp = write_to_pool(kp, vp, block_tables, seq_lens,
                                       k_new.astype(kp.dtype),
                                       v_new.astype(vp.dtype), layer=l)
            else:
                kp, vp = write_to_pool_quant(
                    kp, vp, block_tables, seq_lens, k_new, v_new, *scales,
                    layer=l)
        # attn_out_ref names its two halves itself: layer/attention,
        # layer/attn_out
        out = fdb.attn_out_ref(
            x, q, lp["o_proj"], kp, vp, block_tables, seq_lens, scales,
            residual, layer=l, gather=gather)
        with jax.named_scope("layer/attn_out"):
            x = add(x, out)
        kw = {}
        if mlp_by_index:            # lp holds the MLP leaves whole
            kw["layer"] = l
        elif gather is not None:    # the composition (above)
            kw["gather"] = gather
        with jax.named_scope("layer/mlp"):
            out = mlp_fn(x, lp["post_norm"].astype(x.dtype),
                         lp["gate_proj"], lp["up_proj"], lp["down_proj"],
                         eps, residual=residual, **kw)
            return add(x, out), kp, vp

    with jax.named_scope("embed"):
        x = jnp.take(params["embed_tokens"], tok, axis=0)    # [B, D]
    x, k_pools, v_pools = _layer_loop(
        params, x, k_pools, v_pools, kv_scales, layer,
        stacked=mlp_w if mlp_by_index else ())
    with jax.named_scope("head"):
        x = fused_rms_norm(x[:, None],
                           params["final_norm"].astype(x.dtype),
                           cfg.rms_norm_eps)[:, 0]
        return x @ _lm_head(params), k_pools, v_pools


_FUSED_PREFILL_CACHE: Dict = {}


def _suffix_prefill_runner(cfg, P, MB, mode):
    """Jitted pool-direct fused suffix prefill for the prefix-store
    path: one sequence's un-cached suffix (exact length ``P`` — no
    bucket padding here, so ``n_valid == P``) through
    :func:`_fused_prefill_forward`, pools donated so the persistent
    store's pools update in place. Cached per (cfg values, suffix
    length, table width, mode, prefill route)."""
    ck = (dataclasses.astuple(cfg), P, MB, mode, _prefill_route(mode))
    cached = _cache_get(_FUSED_PREFILL_CACHE, ck)
    if cached is not None:
        return cached

    @functools.partial(jax.jit, donate_argnums=(4, 5))
    def run(params, toks, pos0, table, k_pools, v_pools, wtable):
        logits, k_pools, v_pools = _fused_prefill_forward(
            params, toks, cfg, k_pools, v_pools, table, wtable, pos0,
            jnp.int32(P), kv_scales=None, mode=mode)
        return logits[P - 1], k_pools, v_pools

    if len(_FUSED_PREFILL_CACHE) > 16:
        _FUSED_PREFILL_CACHE.pop(next(iter(_FUSED_PREFILL_CACHE)))
    _FUSED_PREFILL_CACHE[ck] = run
    return run


_TP_PREFILL_CACHE: Dict = {}


def _tp_prefill_runner(cfg, sm, B, S, T):
    """Jitted tensor-parallel prefill for generate_paged: builds the
    LOCAL dense cache inside the per-shard body (KV_loc heads) and runs
    the tensor-parallel ``cached_forward`` mirror. Cached per
    (cfg values, geometry, mesh route) like the chunk runner."""
    import dataclasses as _dc
    from ..core.jax_compat import shard_map_norep
    from .tp import _tp_cached_forward

    ck = (_dc.astuple(cfg), B, S, T, _mesh_route(sm))
    cached = _cache_get(_TP_PREFILL_CACHE, ck)
    if cached is not None:
        return cached
    L, KV, hd = (cfg.num_hidden_layers, cfg.num_key_value_heads,
                 cfg.head_dim)
    KV_loc = KV // sm.tp
    rep = sm.replicated
    cache_spec = sm.pool_spec      # [L, B, T, KV, hd]: axis 3 again

    def fwd(params, toks):
        shape = (L, B, T, KV_loc, hd)
        kc, vc = jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)
        return _tp_cached_forward(params, toks, cfg, kc, vc, 0,
                                  axis=sm.axis,
                                  collective=sm.collective)

    fn = jax.jit(shard_map_norep(fwd, sm.mesh,
                                 (sm.param_specs(cfg), rep),
                                 (rep, cache_spec, cache_spec)))
    if len(_TP_PREFILL_CACHE) > 16:
        _TP_PREFILL_CACHE.pop(next(iter(_TP_PREFILL_CACHE)))
    _TP_PREFILL_CACHE[ck] = fn
    return fn


def generate_paged(params: Dict, input_ids, cfg: _llama.LlamaConfig,
                   gen: Optional[GenerationConfig] = None,
                   block_size: int = 16, seed: int = 0,
                   cache_dtype=None, prefix_cache=None,
                   observability=None, mesh=None,
                   fused_prefill=None, weight_quant=None):
    """vLLM-style serving loop over a paged KV cache.

    ``cache_dtype="int8"``: static per-head cache quantization
    (reference block_attn.h int8 cache mode) — KV pools take half the
    HBM, so the same footprint serves 2x the batch; scales calibrate
    from the prefill KV.

    ``prefix_cache``: opt-in ``PagedKVCacheStore``
    (inference/prefix_cache.py) whose pools/radix tree persist across
    calls — each sequence longest-prefix-matches its prompt against
    previously generated sequences and prefills only the un-cached
    suffix. bf16/f32 caches only (the per-call int8 recalibration is
    incompatible with pages that outlive the call, so int8 cleanly opts
    out here; the ServingEngine's static-scale int8 mode does share).

    Prefill runs through the dense-cache path, the dense cache is repacked
    into block pools, then each decode step is one jitted program using
    the Pallas paged-attention kernel (block-table-driven page streaming).
    The host owns page allocation (BlockManager) between steps — the
    reference's AnalysisPredictor does the same bookkeeping around
    block_multihead_attention.

    ``observability``: an optional ``paddle_tpu.observability
    .Observability`` harness. When given, the call records host-side
    phase timings (prefill dispatch, per-chunk decode dispatch) into
    its timeline/histograms and samples pool gauges — purely
    observational: no extra device syncs, identical outputs.

    ``fused_prefill``: route the PREFIX-STORE suffix prefill through
    the fused prefill-block kernels (ops/pallas/fused_prefill_block.py)
    where dispatch supports them — the suffix runs pool-direct (no
    dense gather/scatter) with the warm prefix pages read as paged
    history. None reads FLAGS_fused_prefill (default ON); the unfused
    chunk composition is the bit-identical fallback everywhere
    dispatch rejects. The COLD path's one-shot dense prefill (which
    repacks into pools afterwards) is not a chunked program and is
    unaffected by this knob.

    ``mesh``: a ``ServingMesh`` (or 1-D jax Mesh / int tp) — prefill
    and every decode chunk run tensor-parallel over the head axis
    (inference/tp.py): pools and projections shard, the residual
    stream and logits stay replicated, still ONE jitted program per
    chunk size. collective="gather" is bit-identical to mesh=None;
    the default "psum" placement is roundoff-parity (documented).

    ``weight_quant``: "int8"/"int4" — per-channel weight quantization
    on the decode + prefill hot paths (quantization/ptq.py). A plain
    fp tree is quantized in ONE shot on the way in (host-side absmax);
    an already-quantized tree (``ptq.quantize_weights``, e.g. with
    activation-aware clipping) rides as-is and None adopts its mode.
    Where the registry dispatches the fused MLP / prefill kernels,
    int8/int4 tiles stream through VMEM and dequantize in-register;
    everywhere else the route is dequantize-then-matmul by construction.
    """
    import time as _time

    import numpy as np
    from ..ops.paged_attention import BlockManager
    from ..quantization.ptq import ensure_quantized
    from .tp import normalize_mesh

    gen = gen or GenerationConfig()
    if observability is True:      # mirror ServingEngine's normalization
        from ..observability import Observability
        observability = Observability()
    sm = normalize_mesh(mesh)
    params, wq_mode = ensure_quantized(params, weight_quant)
    if wq_mode is not None and sm is not None:
        raise ValueError(
            "generate_paged(weight_quant=...) does not take a mesh: "
            "sharding quantized weight trees (packed int4 + per-channel"
            " scales) over tp > 1 is named headroom — run quantized "
            "serving single-device, or use ServingEngine with tp=1 "
            "groups")
    if sm is not None:
        ok, reason = sm.supports(cfg)
        if not ok:
            raise ValueError(f"generate_paged(mesh=...): {reason}")
        if prefix_cache is not None:
            raise NotImplementedError(
                "generate_paged(prefix_cache=...) does not take a mesh:"
                " the persistent store owns single-device pools that "
                "outlive the call. Use ServingEngine(mesh=..., "
                "prefix_cache=True) for sharded prefix sharing")
    if prefix_cache is not None:
        return _generate_paged_prefix(
            params, input_ids, cfg, gen, block_size, seed, cache_dtype,
            prefix_cache, observability,
            fused_prefill=_fused_prefill_mode(fused_prefill),
            wq=wq_mode)
    obs = observability or None
    B, S = input_ids.shape
    T = S + gen.max_new_tokens
    if T > cfg.max_position_embeddings:
        raise ValueError(
            f"prompt+max_new_tokens = {T} exceeds max_position_embeddings "
            f"= {cfg.max_position_embeddings} (rope table bound)")
    L, KV, hd = (cfg.num_hidden_layers, cfg.num_key_value_heads,
                 cfg.head_dim)
    BS = block_size
    MB = -(-T // BS)
    num_blocks = B * MB + 1

    # prefill with the dense cache, then repack into pools
    t0 = _time.perf_counter() if obs is not None else 0.0
    if sm is None:
        k_cache, v_cache = init_cache(cfg, B, T)
        logits, k_cache, v_cache = cached_forward(
            params, input_ids, cfg, k_cache, v_cache, 0)
    else:
        # the dense cache is built LOCAL inside the sharded program;
        # the repack below then runs eagerly on the sharded arrays
        # (page axis unsharded — no collectives)
        params = sm.shard(params, sm.param_specs(cfg))
        logits, k_cache, v_cache = _tp_prefill_runner(cfg, sm, B, S, T)(
            params, jnp.asarray(input_ids))
    if obs is not None:
        # host dispatch time (device completes async; forcing it here
        # would add a sync the serving path is asserted not to have)
        dur = (_time.perf_counter() - t0) * 1e3
        obs.hist("prefill_chunk_ms").observe(dur)
        obs.timeline.record("prefill_chunk", dur_ms=dur, pos0=0,
                            n=int(B * S), bucket=int(S))

    mgr = BlockManager(num_blocks, BS, MB)
    for sid in range(B):
        # allocate the whole generation upfront: the jitted step uses a
        # static table, and unallocated slots would default to page 0 and
        # collide across sequences
        mgr.allocate(sid, T)
    tables = mgr.table_array(range(B))

    pool_shape = (L, num_blocks, BS, KV, hd)
    k_pools = jnp.zeros(pool_shape, k_cache.dtype)
    v_pools = jnp.zeros(pool_shape, v_cache.dtype)
    if sm is not None:
        k_pools = sm.shard(k_pools, sm.pool_spec)
        v_pools = sm.shard(v_pools, sm.pool_spec)
    # dense [L, B, T, KV, hd] -> pages
    pad = MB * BS - T
    kc = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    vc = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    kc = kc.reshape(L, B, MB, BS, KV, hd)
    vc = vc.reshape(L, B, MB, BS, KV, hd)
    flat_tables = jnp.asarray(tables.reshape(-1), jnp.int32)
    k_pools = k_pools.at[:, flat_tables].set(
        kc.reshape(L, B * MB, BS, KV, hd))
    v_pools = v_pools.at[:, flat_tables].set(
        vc.reshape(L, B * MB, BS, KV, hd))

    kv_scales = None
    if cache_dtype in ("int8", jnp.int8):
        # static per-layer-per-head scales from the prefill KV (the
        # reference's static cachekv-quant calibration point); pools
        # shrink 2x and decode dequants per head in the gather consumer
        from ..ops.paged_attention import quantize_pools
        k_pools, v_pools, k_sc, v_sc = jax.vmap(quantize_pools)(
            k_pools, v_pools)
        kv_scales = (k_sc, v_sc)
    elif cache_dtype not in (None, "bfloat16", "float32",
                             jnp.bfloat16, jnp.float32):
        raise ValueError(f"cache_dtype must be bfloat16|float32|int8, "
                         f"got {cache_dtype!r}")

    # Chunked decode: pages for the whole generation are allocated
    # upfront (static tables), so no host bookkeeping is needed between
    # steps — run chunk_size decode steps as ONE jitted lax.scan
    # (sampling included) per host dispatch. The previous per-token host
    # loop paid eager sampling ops plus a BLOCKING np.asarray d2h per
    # token. Between chunks the host
    # can still reclaim finished sequences (the vLLM-style scheduling
    # point the reference's AnalysisPredictor has). The jitted chunk
    # runner is cached per (config values, sampling knobs) like
    # generate()'s — shapes and the static n key jit's own cache.
    chunk_fn = _paged_chunk_runner(cfg, gen, quant=kv_scales is not None,
                                   sm=sm, wq=wq_mode)

    key = _key_for(seed)
    tok = sample_token(logits[:, -1], key, gen)
    done = tok == gen.eos_token_id
    chunks = [tok[:, None]]
    seq_lens = jnp.full((B,), S, jnp.int32)
    bt = jnp.asarray(tables, jnp.int32)
    chunk = max(1, int(os.environ.get("PADDLE_TPU_DECODE_CHUNK", "32")))
    left = gen.max_new_tokens - 1
    if obs is not None:
        obs.sample_gauges(_time.perf_counter(), {
            "pages_free": len(mgr.free),
            "pages_in_use": num_blocks - len(mgr.free)})
    while left > 0:
        n = min(chunk, left)
        t0 = _time.perf_counter() if obs is not None else 0.0
        toks, tok, key, done, seq_lens, k_pools, v_pools = chunk_fn(
            n, params, tok, key, done, k_pools, v_pools, seq_lens, bt,
            kv_scales)
        if obs is not None:
            dur = (_time.perf_counter() - t0) * 1e3
            obs.hist("decode_step_ms").observe(dur / n)
            obs.timeline.record("decode_step", dur_ms=dur,
                                live_slots=B, tokens=int(n * B))
        chunks.append(toks.transpose(1, 0))  # [n, B] -> [B, n]
        left -= n
    toks = jnp.concatenate(chunks, axis=1)
    return jnp.concatenate([input_ids, toks], axis=1)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scatter_prefill_pages(kp, vp, wtable, kc, vc):
    """Scatter one sequence's dense prefill view back into the pools
    through its WRITE table. Donation keeps the pools in place — an
    eager ``.at[].set`` here would materialize two whole-pool copies
    per sequence per call."""
    L, _, BS, KV, hd = kp.shape
    MB = wtable.shape[0]
    kc = kc.reshape(L, MB, BS, KV, hd).astype(kp.dtype)
    vc = vc.reshape(L, MB, BS, KV, hd).astype(vp.dtype)
    return kp.at[:, wtable].set(kc), vp.at[:, wtable].set(vc)


def _generate_paged_prefix(params, input_ids, cfg, gen, block_size,
                           seed, cache_dtype, store,
                           observability=None, fused_prefill=False,
                           wq=None):
    """``generate_paged`` over a persistent ``PagedKVCacheStore``.

    Admission longest-prefix-matches each prompt against the store's
    radix tree (full pages shared in place, partial tail via COW fork)
    and prefills only the un-cached suffix — one ``cached_forward``
    over a dense gathered view per sequence, because each sequence has
    its own start position. The scatter back to the pools goes through
    a write table whose shared entries are redirected to the scratch
    page, so shared pages are never written. Decode reuses the cold
    path's jitted chunk runner unchanged; finished sequences are
    indexed back into the tree (trimmed at the first EOS) instead of
    freed."""
    import numpy as np

    if cache_dtype not in (None, "bfloat16", "float32",
                           jnp.bfloat16, jnp.float32):
        raise ValueError(
            "generate_paged(prefix_cache=...) supports bf16/f32 caches "
            f"only, got cache_dtype={cache_dtype!r}: the int8 path "
            "recalibrates per call, which cannot share pages that "
            "outlive the call (use ServingEngine's static-scale int8)")
    if int(block_size) != store.block_size:
        raise ValueError(
            f"block_size {block_size} != prefix store block_size "
            f"{store.block_size}")
    B, S = input_ids.shape
    T = S + gen.max_new_tokens
    if T > cfg.max_position_embeddings:
        raise ValueError(
            f"prompt+max_new_tokens = {T} exceeds max_position_embeddings "
            f"= {cfg.max_position_embeddings} (rope table bound)")
    L, KV, hd = (cfg.num_hidden_layers, cfg.num_key_value_heads,
                 cfg.head_dim)
    BS = store.block_size
    MB = -(-T // BS)
    mgr, cache = store.mgr, store.cache
    prompts = np.asarray(input_ids, np.int32)

    seq_ids, matched_ns, shared_ns = [], [], []
    tables = np.zeros((B, MB), np.int32)
    for b in range(B):
        sid = store.next_seq_id
        store.next_seq_id += 1
        got = cache.acquire(prompts[b], S - 1, MB)
        if got is None:
            for done_sid in seq_ids:
                mgr.release(done_sid)
            raise RuntimeError(
                f"prefix store pool exhausted: batch needs up to "
                f"{B * MB} pages, store has {store.num_blocks - 1}")
        pages, matched, shared = got
        mgr.attach(sid, pages, owned=True)
        t = mgr.allocate(sid, T)
        tables[b, :len(t)] = t
        seq_ids.append(sid)
        matched_ns.append(matched)
        shared_ns.append(shared)

    import time as _time

    obs = observability or None
    if obs is not None:
        obs.sample_gauges(_time.perf_counter(), {
            "pages_free": len(mgr.free),
            "pages_in_use": store.num_blocks - len(mgr.free),
            "prefix_tree_pages": cache.cached_pages})

    # suffix prefill, one sequence at a time (per-sequence pos0).
    # With ``fused_prefill`` and dispatch selecting the Pallas pair,
    # the suffix runs POOL-DIRECT (the warm prefix pages are the paged
    # history, the suffix K/V scatter through the write table) —
    # otherwise the verbatim gather/cached_forward/scatter composition.
    from ..ops.pallas.fused_prefill_block import (prefill_fused_selected,
                                                  prefill_meta)
    logits_last = []
    for b in range(B):
        M = matched_ns[b]
        wt = tables[b].copy()
        wt[:shared_ns[b]] = 0              # never write a shared page
        if obs is not None:
            t0 = _time.perf_counter()
        use_fused = fused_prefill and prefill_fused_selected(
            prefill_meta(cfg, S - M, BS, MB, store.k_pools.dtype,
                         False, weight_dtype=wq), fused_prefill)
        if use_fused:
            run = _suffix_prefill_runner(cfg, S - M, MB, fused_prefill)
            lg_last, store.k_pools, store.v_pools = run(
                params, jnp.asarray(prompts[b, M:]),
                jnp.asarray(M, jnp.int32),
                jnp.asarray(tables[b], jnp.int32),
                store.k_pools, store.v_pools,
                jnp.asarray(wt, jnp.int32))
            logits_last.append(lg_last[None])
        else:
            tb = jnp.asarray(tables[b], jnp.int32)
            kc = jnp.take(store.k_pools, tb, axis=1) \
                .reshape(L, 1, MB * BS, KV, hd)
            vc = jnp.take(store.v_pools, tb, axis=1) \
                .reshape(L, 1, MB * BS, KV, hd)
            lg, kc, vc = cached_forward(
                params, jnp.asarray(prompts[b:b + 1, M:]), cfg, kc, vc,
                M)
            store.k_pools, store.v_pools = _scatter_prefill_pages(
                store.k_pools, store.v_pools,
                jnp.asarray(wt, jnp.int32), kc, vc)
            logits_last.append(lg[:, -1])
        if obs is not None:
            dur = (_time.perf_counter() - t0) * 1e3
            obs.hist("prefill_chunk_ms").observe(dur)
            obs.timeline.record("prefill_chunk", req_id=seq_ids[b],
                                dur_ms=dur, pos0=M, n=int(S - M),
                                matched_tokens=M,
                                variant=("pallas" if use_fused
                                         else "ref"))

    key = _key_for(seed)
    tok = sample_token(jnp.concatenate(logits_last, axis=0), key, gen)
    done = tok == gen.eos_token_id
    chunks = [tok[:, None]]
    seq_lens = jnp.full((B,), S, jnp.int32)
    bt = jnp.asarray(tables, jnp.int32)
    chunk_fn = _paged_chunk_runner(cfg, gen, quant=False, wq=wq)
    k_pools, v_pools = store.k_pools, store.v_pools
    chunk = max(1, int(os.environ.get("PADDLE_TPU_DECODE_CHUNK", "32")))
    left = gen.max_new_tokens - 1
    while left > 0:
        n = min(chunk, left)
        if obs is not None:
            t0 = _time.perf_counter()
        toks, tok, key, done, seq_lens, k_pools, v_pools = chunk_fn(
            n, params, tok, key, done, k_pools, v_pools, seq_lens, bt,
            None)
        if obs is not None:
            dur = (_time.perf_counter() - t0) * 1e3
            obs.hist("decode_step_ms").observe(dur / n)
            obs.timeline.record("decode_step", dur_ms=dur,
                                live_slots=B, tokens=int(n * B))
        chunks.append(toks.transpose(1, 0))
        left -= n
    store.k_pools, store.v_pools = k_pools, v_pools
    out = jnp.concatenate(chunks, axis=1)            # [B, N]

    out_np = np.asarray(out)
    for b in range(B):
        # KV is valid for prompt + N-1 generated tokens (the last one's
        # KV was never written); forced-eos padding after the first EOS
        # is not meaningful traffic, so the index stops there
        valid = gen.max_new_tokens - 1
        if gen.eos_token_id >= 0:
            hits = np.nonzero(out_np[b] == gen.eos_token_id)[0]
            if hits.size:
                valid = min(valid, int(hits[0]) + 1)
        seq = np.concatenate([prompts[b], out_np[b, :valid]])
        cache.insert(seq, list(mgr.tables.get(seq_ids[b], ())))
        mgr.release(seq_ids[b])
    return jnp.concatenate([jnp.asarray(input_ids), out], axis=1)
