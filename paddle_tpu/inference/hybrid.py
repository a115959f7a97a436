"""The serving programs of a model that is run BY ITS LAYER PATTERN
(``models/pattern.py``: ``models/granite_hybrid.py``,
``models/mellum.py``, ``models/nemotron_h.py``): one decode step over
all slots, and one prefill chunk of one request, both driven by what
the config says of each kind of layer (``cfg.kinds``), and what
``ServingEngine`` reads of such a model (:class:`ServedPattern`).

A layer is its HALVES (``pattern.LayerKind``): a mixer (Mamba-2 or
attention) or none, then an expert half or none. Granite's and Mellum
2's layers are both; a Nemotron-H layer is one of the three alone.
Each program writes each half once (its ``mamba``, ``attention`` and
``_moe``) and ``_run_pattern`` puts a layer together from its kind:
no program asks which family it runs.

Three kinds of per-request state live side by side:

- keys and values a request keeps to its end: the paged pools
  ``[Lg, N, BS, KV, hd]`` of the layers whose page class is "global",
  addressed through block tables as for every other model;
- keys and values behind a sliding window: pools of their own
  ``[Lw, Nw, BS, KV, hd]`` for the layers whose page class is "window",
  addressed through a RING a slot (``win_tables`` [slots, R]: logical
  block ``n`` in column ``n % R``); the host gives a page back to the
  pool once it lies behind the window (``ops/paged_attention
  .WindowPages``), so a launch is handed each slot's first live
  position and visits nothing before it;
- a Mamba layer's state, indexed by SLOT and never paged:

    ssm   [Lm, slots, N, H*hp]    the recurrence's state (state_dtype)
    conv  [Lm, slots, K-1, C]     the convolution's last K-1 inputs

``state`` is the dict of what the model has of the last two, plus
``stats`` (int [5]: routing counts, summed on the device). Both
programs take it as an argument, carry it through their loops beside
the global pools, write the layer (and, in a chunk, the slot) they are
at in place, and return it; the engine donates it. A slot that is not
decoding has ``dt = 0`` in the decode step, which leaves its state bit
for bit, and writes its keys to the scratch page; a chunk's padding
likewise.

The layer pattern is run as ``pattern.runs(cfg)``: repeats of a unit of
layers, each run ONE loop over its kinds' stacked weights. Runs of
equal layers come first, so a period of "5 Mamba, 1 attention, 4
Mamba" compiles two loop bodies and one attention layer, not ten
layers, and "3 window, 1 full" twice compiles four loops of two
bodies; a pattern that alternates ("MEMEM*EMEMEM*EME") has no two
equal neighbours and runs as units of two layers: (ME) x 2 and (EM) x
3 are a loop each.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..models import pattern as pt
from ..ops import mamba2
from ..ops.moe_experts import expert_counts
from ..ops.paged_attention import paged_attention_decode, write_to_pool

__all__ = ["ServedPattern", "served_pattern", "init_state", "decode_step",
           "prefill_chunk", "reset_slot", "chunk_attention"]

F32 = jnp.float32
# leaves of params["moe"] that a loop hands to the expert launch whole
_EXPERT_STACKS = ("w_in", "w_out")
# keys a chunk's attention reads at once (a block of the live keys)
CHUNK_KEY_BLOCK = 512

_NO_SNAPSHOTS = (
    "a recurrent layer's state lives in its slot and there are no "
    "state snapshots yet (a copy of a slot's state kept beside its KV "
    "pages), which {what} needs to {need}")


@dataclasses.dataclass(frozen=True)
class ServedPattern:
    """What ``ServingEngine`` reads of a model served by its pattern:
    its pools and their page classes, its per-slot state, the options
    it cannot take yet. One description; the engine asks it and holds
    no model's name."""
    recurrent_layers: int        # layers with a state a slot
    window_layers: int           # layers whose pages go back behind ...
    window: int                  # ... this many positions (0: none)
    expert_layers: int = 0       # layers with an expert half

    @property
    def prefix_skip_counter(self) -> str:
        """The counter of requests the prefix cache would have looked
        up: the cache stays off for this model, by the mechanism that
        is missing."""
        return ("prefix_skipped_recurrent" if self.recurrent_layers
                else "prefix_skipped_window")

    @property
    def counters(self):
        """Counters this model adds to ``engine.counters``."""
        names = [self.prefix_skip_counter, "expert_assignments",
                 "expert_assignments_held", "expert_load_max",
                 "experts_touched_held", "expert_layer_steps"]
        if self.recurrent_layers:
            names.append("state_resets")
        if self.window:
            names += ["window_pages_released", "kv_tokens_held_window",
                      "kv_tokens_seen_window", "kv_pages_live_global"]
        return tuple(names)

    def ring(self, block_size: int, largest_chunk: int) -> int:
        """Pages a slot's window ring holds: a chunk's queries reach
        ``window - 1`` positions back from its first, and its own keys
        are written before it attends."""
        return -(-(self.window + largest_chunk) // block_size) + 1

    def refuse(self, mesh=None, weight_quant=None, cache_dtype=None,
               kv_offload=False):
        """What such a model cannot be served with yet, each refused by
        the mechanism that is missing: a host tier and preemption
        (state snapshots; prefix sharing across two page lifetimes), a
        mesh (an expert exchange, a sharded recurrent state), quantized
        weights (the expert stacks and Mamba projections have no
        dequantizing route), int8 pools (calibrated through the dense
        decoder). Every pattern-run family is refused the same ones: a
        layer's halves change none of these mechanisms."""
        if kv_offload:
            raise ValueError(
                "ServingEngine(kv_offload=...): " + (
                    _NO_SNAPSHOTS.format(what="the host tier",
                                         need="restore a spilled prefix")
                    if self.recurrent_layers else
                    "the host tier spills the prefix cache's pages, and "
                    "the prefix cache cannot share a prefix across two "
                    "page lifetimes yet (a window layer's pages behind "
                    "the window are gone when the prefix is matched)"))
        if mesh is not None:
            raise ValueError(
                "ServingEngine(mesh=...): the recurrent and expert layers "
                "have no sharded placement yet (inference/tp.py shards "
                "attention heads and MLP columns, and has no expert "
                "exchange)")
        if weight_quant is not None:
            raise ValueError(
                "ServingEngine(weight_quant=...): the quantized leaves' "
                "dequantize-then-matmul route does not cover the expert "
                "stacks or the Mamba projections")
        if cache_dtype in ("int8", jnp.int8):
            raise ValueError(
                'ServingEngine(cache_dtype="int8"): the int8 pools\' scales '
                "are calibrated through the dense decoder's forward pass")

    def refuse_preemption(self):
        if self.recurrent_layers:
            raise RuntimeError("preemption: " + _NO_SNAPSHOTS.format(
                what="a preempted request",
                need="resume where it was evicted"))

def served_pattern(cfg):
    """The :class:`ServedPattern` of ``cfg``, or None for a model that
    is not run by a pattern (the dense decoder)."""
    kinds = getattr(cfg, "kinds", None)
    if not kinds:
        return None
    used = [kinds[name] for name in cfg.pattern]
    windows = {k.window for k in used if k.pool == "window"}
    if len(windows) > 1:
        raise ValueError(f"window layers of several widths {windows}: "
                         "the window page class has one")
    return ServedPattern(
        sum(k.mixer == "mamba" for k in used),
        sum(k.pool == "window" for k in used),
        windows.pop() if windows else 0,
        sum(k.experts for k in used))


def init_state(cfg, slots: int, state_dtype=F32, window_blocks: int = 0,
               block_size: int = 16, ring: int = 0):
    """Zeroed pools for ``slots`` slots: what the model has of the
    recurrent state and of the window layers' pages and tables."""
    state = {"stats": jnp.zeros((5,), jnp.asarray(0).dtype)}
    if getattr(cfg, "num_recurrent_layers", 0):
        ssm, conv = cfg.state_shapes(slots)
        state.update(ssm=jnp.zeros(ssm, state_dtype),
                     conv=jnp.zeros(conv, cfg.dtype))
    lw = getattr(cfg, "num_window_layers", 0)
    if lw:
        shape = (lw, window_blocks, block_size, cfg.num_key_value_heads,
                 cfg.head_dim)
        state.update(k_win=jnp.zeros(shape, cfg.dtype),
                     v_win=jnp.zeros(shape, cfg.dtype),
                     win_tables=jnp.zeros((slots, ring), jnp.int32))
    return state


def reset_slot(state, slot):
    """Zero one slot's recurrent state (admission), in place when the
    caller donates ``state``."""
    slot = jnp.asarray(slot, jnp.int32)

    def zeroed(pool):
        start = (jnp.int32(0), slot) + (jnp.int32(0),) * (pool.ndim - 2)
        return jax.lax.dynamic_update_slice(
            pool, jnp.zeros((pool.shape[0], 1) + pool.shape[2:],
                            pool.dtype), start)

    ssm, conv = zeroed(state["ssm"]), zeroed(state["conv"])
    return {**state, "ssm": ssm, "conv": conv}


def _moe(params, h, cfg, l, live, stats):
    """A layer's expert half inside a loop, at layer ``l`` (traced) of
    ``params["moe"]``, and the running routing counts (the last is the
    number of expert layers run: what the others are summed over)."""
    moe = params["moe"]
    mp = pt.at_layer({k: v for k, v in moe.items()
                      if k not in _EXPERT_STACKS}, l)
    mp.update({k: moe[k] for k in _EXPERT_STACKS})
    x, experts = pt.moe_block(mp, h, cfg, layer=l)
    if stats is not None:
        with jax.named_scope("layer/router"):
            c = expert_counts(experts, live, cfg.num_experts,
                              cfg.num_local_experts,
                              cfg.expert_offset).astype(stats.dtype)
            stats = jnp.stack([stats[0] + c[0], stats[1] + c[1],
                               jnp.maximum(stats[2], c[2]),
                               stats[3] + c[3], stats[4] + 1])
    return x, stats


# the pools a program carries through its loops, in the carry's order
_POOLS = ("ssm", "conv", "k", "v", "k_win", "v_win")


def _pools_of(kind):
    """The carried pools a kind's mixer reads and writes."""
    if kind.mixer == "mamba":
        return ("ssm", "conv")
    if kind.mixer == "attention":
        return ("k_win", "v_win") if kind.pool == "window" else ("k", "v")
    return ()


def _run_pattern(cfg, x, k_pools, v_pools, state, mixers, expert_half,
                 stats=()):
    """The pattern as ``pattern.runs(cfg)``, a loop each, a layer put
    together from its kind's halves. ``mixers[kind.mixer](kind)``
    returns that mixer's half ``(x, *pools, m) -> (h, *pools)`` at
    layer ``m`` of the kind's stack (what it computes once a run, a
    table or a page list, it computes when it is made);
    ``expert_half(h, e, stats) -> (x, stats)`` is the expert half at
    layer ``e`` of ``params["moe"]``. A loop carries the pools its
    unit's mixers write and ``stats`` (() or (the routing counts,)).
    Returns (x, k_pools, v_pools, state, stats)."""
    state = dict(state)
    pools = {"k": k_pools, "v": v_pools,
             **{n: state[n] for n in _POOLS if n in state}}
    for run in pt.runs(cfg):
        used = {n for mem in run.members for n in _pools_of(mem.kind)}
        names = [n for n in _POOLS if n in used]
        # each loop's own bookkeeping reads "layers" in a trace
        with jax.named_scope("layers"):
            halves = [mixers[mem.kind.mixer](mem.kind)
                      if mem.kind.mixer else None for mem in run.members]

            def body(i, carry, run=run, names=names, halves=halves):
                x, *rest = carry
                held = dict(zip(names, rest))
                stats = tuple(rest[len(names):])
                for mem, mixer in zip(run.members, halves):
                    m, e = mem.at(i)
                    if mixer is not None:
                        mine = _pools_of(mem.kind)
                        x, *new = mixer(x, *(held[n] for n in mine), m)
                        held.update(zip(mine, new))
                    if mem.kind.experts:
                        x, stats = expert_half(x, e, stats)
                return (x, *(held[n] for n in names), *stats)

            x, *rest = jax.lax.fori_loop(
                0, run.repeats, body,
                (x, *(pools[n] for n in names), *stats))
        pools.update(zip(names, rest))
        stats = tuple(rest[len(names):])
    state.update({n: pools[n] for n in pools if n in state})
    return x, pools["k"], pools["v"], state, stats


def decode_step(params, tok, cfg, k_pools, v_pools, block_tables,
                seq_lens, state):
    """One token for every slot. tok, seq_lens: [S] (a slot that is not
    decoding has seq_len 0: its KV write lands in the scratch page and
    its recurrent state is left as it is). Returns (logits [S, V],
    k_pools, v_pools, state)."""
    # the named scopes are observability.PROGRAM_SCOPES: a reader of a
    # device trace finds each operation's by them (metadata only)
    active = seq_lens > 0
    with jax.named_scope("embed"):
        x = pt.embed(params, tok, cfg)

    def mamba(kind):
        def half(x, ssm, conv, m):
            with jax.named_scope("layer/mixer_in"):
                lp = pt.at_layer(params[kind.stack], m)
                z, xbc, dt = pt.mamba_in(lp, x, cfg)
                xbc, tail = mamba2.conv_update(
                    xbc, lp["conv_w"], lp["conv_b"],
                    jax.lax.dynamic_index_in_dim(conv, m, 0, False),
                    active)
                conv = jax.lax.dynamic_update_index_in_dim(conv, tail, m,
                                                           0)
                xs, b, c = pt.split_xbc(xbc, cfg)
                dt = jnp.where(active[:, None], dt, 0.0)
                a = -jnp.exp(lp["A_log"].astype(F32))
            y, ssm = mamba2.ssm_update(xs, dt, a, b, c, lp["D"], ssm, m)
            with jax.named_scope("layer/mixer_out"):
                h = pt.mamba_out(lp, x, y, z, cfg)
            return h, ssm, conv
        return half

    def attention(kind):
        # a window layer's launch gets each slot's first live position
        # and its ring of the window pool; a global layer's neither
        windowed = kind.pool == "window"
        tables = state["win_tables"] if windowed else block_tables
        first = (jnp.maximum(seq_lens + 1 - kind.window, 0)
                 if windowed else None)

        def half(x, kp, vp, a):
            with jax.named_scope("layer/qkv"):
                lp = pt.at_layer(params[kind.stack], a)
                q, k, v = pt.attn_qkv(lp, x, cfg, kind, seq_lens)
            with jax.named_scope("layer/kv_write"):
                kp, vp = write_to_pool(
                    kp, vp, tables, seq_lens, k.astype(kp.dtype),
                    v.astype(vp.dtype), layer=a, ring=windowed)
            with jax.named_scope("layer/attention"):
                o = paged_attention_decode(
                    q, kp, vp, tables, seq_lens + 1,
                    scale=cfg.attention_multiplier, layer=a, first=first)
            with jax.named_scope("layer/attn_out"):
                h = pt.residual(
                    x, o.reshape(x.shape[0], -1).astype(x.dtype)
                    @ lp["o_proj"], cfg)
            return h, kp, vp
        return half

    def expert_half(h, e, stats):
        x, stat = _moe(params, h, cfg, e, active, stats[0])
        return x, (stat,)

    x, k_pools, v_pools, state, (stats,) = _run_pattern(
        cfg, x, k_pools, v_pools, state,
        {"mamba": mamba, "attention": attention}, expert_half,
        (state["stats"],))
    state["stats"] = stats
    with jax.named_scope("head"):
        logits = pt.lm_logits(params, x, cfg).astype(F32)
    return logits, k_pools, v_pools, state


def chunk_attention(q, kp, vp, layer, table, q_pos, lo, hi, scale,
                    window=None, ring=False):
    """Attention of a chunk's queries over the request's LIVE keys,
    read from the pool in blocks.

    q [P, H, hd] at absolute positions ``q_pos`` [P]; kp / vp the
    stacked pools [L, N, BS, KV, hd], read at ``layer`` through the
    request's ``table`` (``ring``: logical block ``n`` in column ``n %
    width``); the live keys are the positions ``[lo, hi)`` (the chunk's
    own among them: they were written before this is called). A query
    sees ``j <= q_pos`` and, with ``window``, ``j > q_pos - window``.
    The loop's trip count follows ``hi - lo``, not the table's width:
    a block is ``CHUNK_KEY_BLOCK`` keys gathered page by page, one
    online-softmax update in float32. Rows of V outside ``[lo, hi)``
    are selected away (a page given back, or never written, may hold
    anything). Returns [P, H * hd]."""
    P, H, hd = q.shape
    BS, KV = kp.shape[2], kp.shape[3]
    G = H // KV
    width = table.shape[0]
    pages = max(1, min(CHUNK_KEY_BLOCK // BS, width))
    T = pages * BS
    i32 = jnp.int32
    lo, hi = jnp.asarray(lo, i32), jnp.asarray(hi, i32)
    q_pos = q_pos.astype(i32)
    qg = q.reshape(P, KV, G, hd).astype(F32)
    table = jnp.asarray(table, i32)

    def block(blk, carry):
        m, l, acc = carry
        cols = blk * pages + jnp.arange(pages, dtype=i32)
        cols = cols % width if ring else jnp.minimum(cols, width - 1)
        page = jnp.take(table, cols)
        kb = kp[layer, page].reshape(T, KV, hd)
        vb = vp[layer, page].reshape(T, KV, hd)
        kpos = blk * T + jnp.arange(T, dtype=i32)
        held = (kpos >= lo) & (kpos < hi)
        see = held[None, :] & (kpos[None, :] <= q_pos[:, None])
        if window is not None:
            see = see & (kpos[None, :] > q_pos[:, None] - window)
        s = jnp.einsum("pngh,tnh->ngpt", qg, kb.astype(F32)) * scale
        s = jnp.where(see[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # a query with no key in any block so far keeps m = -inf
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe)
        alpha = jnp.exp(m - m_safe)
        vb = jnp.where(held[:, None, None], vb, jnp.zeros_like(vb))
        acc = acc * alpha + jnp.einsum("ngpt,tnh->ngph", p, vb.astype(F32))
        return m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True), acc

    m0 = jnp.full((KV, G, P, 1), -jnp.inf, F32)
    init = (m0, jnp.zeros((KV, G, P, 1), F32),
            jnp.zeros((KV, G, P, hd), F32))
    _, l, acc = jax.lax.fori_loop(lo // T, (hi + T - 1) // T, block, init)
    o = acc / jnp.where(l == 0, 1.0, l)
    return o.transpose(2, 0, 1, 3).reshape(P, H * hd).astype(q.dtype)


def prefill_chunk(params, toks, cfg, k_pools, v_pools, table, wtable,
                  pos0, n_valid, slot, state):
    """One chunk of one request's prompt: ``toks`` [P] (``n_valid``
    real) at positions ``pos0``.. of the request in slot ``slot``.

    A Mamba layer continues from the slot's state and leaves the state
    after the last real token there; padding advances nothing (its
    ``dt`` is 0 and the convolution's tail is taken at the last real
    token). An attention layer writes the chunk's keys and values
    through the write table of its page class (padding lands in the
    scratch page) and then attends over the request's live keys in
    blocks (:func:`chunk_attention`): a global layer over everything
    up to the chunk's end, a window layer over at most ``window - 1``
    positions before the chunk's first. Returns (the logits [1, V] of
    the last real position, k_pools, v_pools, state)."""
    P = toks.shape[0]
    BS = k_pools.shape[2]
    pos0 = jnp.asarray(pos0, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    with jax.named_scope("embed"):
        rows = jnp.arange(P, dtype=jnp.int32)
        valid = rows < n_valid
        pos = pos0 + rows
        off = pos % BS
        x = pt.embed(params, toks, cfg)

    def mamba(kind):
        def half(x, ssm, conv, m):
            with jax.named_scope("layer/mixer_in"):
                lp = pt.at_layer(params[kind.stack], m)
                z, xbc, dt = pt.mamba_in(lp, x, cfg)
                xbc, tail = mamba2.causal_conv1d(
                    xbc, lp["conv_w"], lp["conv_b"], conv[m, slot],
                    n_valid)
                conv = conv.at[m, slot].set(tail)
                xs, b, c = pt.split_xbc(xbc, cfg)
                dt = jnp.where(valid[:, None], dt, 0.0)
                a = -jnp.exp(lp["A_log"].astype(F32))
            # the slot's state out of the pool and back belongs to the
            # scan (benchmarks' ssd_scan cost counts both launches)
            with jax.named_scope("ssd_scan"):
                before = mamba2.slot_state(ssm, m, slot)
            y, new = mamba2.ssd_scan(xs, dt, a, b, c, lp["D"], before,
                                     block=cfg.mamba_chunk_size)
            with jax.named_scope("ssd_scan"):
                ssm = mamba2.set_slot_state(ssm, m, slot, new)
            with jax.named_scope("layer/mixer_out"):
                h = pt.mamba_out(lp, x, y, z, cfg)
            return h, ssm, conv
        return half

    def attention(kind):
        windowed = kind.pool == "window"
        with jax.named_scope("layer/kv_write"):
            if windowed:
                tab = jnp.take(state["win_tables"], slot, axis=0)
                wtab, col = tab, (pos // BS) % tab.shape[0]
                lo = jnp.maximum(pos0 - (kind.window - 1), 0)
            else:
                tab, wtab, col = table, wtable, pos // BS
                lo = jnp.int32(0)
            # the chunk's own rows through the WRITE table (shared
            # pages and padding land in the scratch page)
            page = jnp.where(valid, jnp.take(
                jnp.asarray(wtab, jnp.int32), col), 0)

        def half(x, kp, vp, a):
            with jax.named_scope("layer/qkv"):
                lp = pt.at_layer(params[kind.stack], a)
                q, k, v = pt.attn_qkv(lp, x, cfg, kind, pos)
            # one scatter into the carried stack, then the live keys
            # (the chunk's among them) in blocks out of it
            with jax.named_scope("layer/kv_write"):
                kp = kp.at[a, page, off].set(k.astype(kp.dtype))
                vp = vp.at[a, page, off].set(v.astype(vp.dtype))
            with jax.named_scope("layer/attention"):
                o = chunk_attention(
                    q, kp, vp, a, tab, pos, lo, pos0 + n_valid,
                    cfg.attention_multiplier, kind.window, windowed)
            with jax.named_scope("layer/attn_out"):
                h = pt.residual(x, o @ lp["o_proj"], cfg)
            return h, kp, vp
        return half

    def expert_half(h, e, stats):
        return _moe(params, h, cfg, e, valid, None)[0], stats

    x, k_pools, v_pools, state, _ = _run_pattern(
        cfg, x, k_pools, v_pools, state,
        {"mamba": mamba, "attention": attention}, expert_half)
    with jax.named_scope("head"):
        last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=0)
        logits = pt.lm_logits(params, last, cfg).astype(F32)
    return logits, k_pools, v_pools, state
