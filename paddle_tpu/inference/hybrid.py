"""The serving programs of a model with recurrent layers beside its
attention layers (``models/granite_hybrid.py``): one decode step over
all slots, and one prefill chunk of one request.

Two kinds of per-request state live side by side. Keys and values stay
in the paged pools ``[La, N, BS, KV, hd]`` (only the attention layers
have any), addressed through block tables as for every other model. A
Mamba layer's state is indexed by SLOT and never paged:

    ssm   [Lm, slots, N, H*hp]    the recurrence's state (state_dtype)
    conv  [Lm, slots, K-1, C]     the convolution's last K-1 inputs
    stats int [3]                 routing counts, summed on the device

``state`` is that dict. Both programs take it as an argument, carry it
through their loops beside the KV pools, write the layer (and, in a
chunk, the slot) they are at in place, and return it; the engine
donates it. A slot that is not decoding has ``dt = 0`` in the decode
step, which leaves its state bit for bit; a chunk's padding likewise.

The layer pattern is run as its segments of equal layers
(``cfg.segments()``): each run of Mamba layers is ONE loop over the
stacked weights, so a period of "5 Mamba, 1 attention, 4 Mamba"
compiles two loop bodies and one attention layer, not ten layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models import granite_hybrid as gh
from ..ops import mamba2
from ..ops.moe_experts import expert_counts
from ..ops.paged_attention import paged_attention_decode, write_to_pool

__all__ = ["init_state", "decode_step", "prefill_chunk", "reset_slot"]

F32 = jnp.float32
# leaves of params["moe"] that a loop hands to the expert launch whole
_EXPERT_STACKS = ("w_in", "w_out")


def init_state(cfg, slots: int, state_dtype=F32):
    """Zeroed state pools for ``slots`` slots."""
    ssm, conv = cfg.state_shapes(slots)
    return {"ssm": jnp.zeros(ssm, state_dtype),
            "conv": jnp.zeros(conv, cfg.dtype),
            "stats": jnp.zeros((3,), jnp.asarray(0).dtype)}


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
    """A layer's second half inside a loop at layer ``l`` (traced), and
    the running routing counts."""
    moe = params["moe"]
    mp = gh.at_layer({k: v for k, v in moe.items()
                      if k not in _EXPERT_STACKS}, l)
    mp.update({k: moe[k] for k in _EXPERT_STACKS})
    x, experts = gh.moe_block(mp, h, cfg, layer=l)
    if stats is not None:
        c = expert_counts(experts, live, cfg.num_experts,
                          cfg.num_local_experts,
                          cfg.expert_offset).astype(stats.dtype)
        stats = jnp.stack([stats[0] + c[0], stats[1] + c[1],
                           jnp.maximum(stats[2], c[2])])
    return x, stats


def decode_step(params, tok, cfg, k_pools, v_pools, block_tables,
                seq_lens, state):
    """One token for every slot. tok, seq_lens: [S] (a slot that is not
    decoding has seq_len 0: its KV write lands in the scratch page and
    its recurrent state is left as it is). Returns (logits [S, V],
    k_pools, v_pools, state)."""
    active = seq_lens > 0
    x = gh.embed(params, tok, cfg)

    def mamba_layers(carry, l0, m0, n):
        def body(i, carry):
            x, ssm, conv, stats = carry
            l, m = l0 + i, m0 + i
            lp = gh.at_layer(params["mamba"], m)
            z, xbc, dt = gh.mamba_in(lp, x, cfg)
            xbc, tail = mamba2.conv_update(
                xbc, lp["conv_w"], lp["conv_b"],
                jax.lax.dynamic_index_in_dim(conv, m, 0, False), active)
            conv = jax.lax.dynamic_update_index_in_dim(conv, tail, m, 0)
            xs, b, c = gh.split(xbc, cfg)
            y, ssm = mamba2.ssm_update(
                xs, jnp.where(active[:, None], dt, 0.0),
                -jnp.exp(lp["A_log"].astype(F32)), b, c, lp["D"], ssm, m)
            h = gh.mamba_out(lp, x, y, z, cfg)
            x, stats = _moe(params, h, cfg, l, active, stats)
            return x, ssm, conv, stats
        return jax.lax.fori_loop(0, n, body, carry)

    def attn_layers(carry, l0, a0, n):
        def body(i, carry):
            x, kp, vp, stats = carry
            l, a = l0 + i, a0 + i
            lp = gh.at_layer(params["attn"], a)
            q, k, v = gh.attn_qkv(lp, x, cfg)
            kp, vp = write_to_pool(kp, vp, block_tables, seq_lens,
                                   k.astype(kp.dtype), v.astype(vp.dtype),
                                   layer=a)
            o = paged_attention_decode(
                q, kp, vp, block_tables, seq_lens + 1,
                scale=cfg.attention_multiplier, layer=a)
            h = x + cfg.residual_multiplier * (
                o.reshape(x.shape[0], -1).astype(x.dtype) @ lp["o_proj"])
            x, stats = _moe(params, h, cfg, l, active, stats)
            return x, kp, vp, stats
        return jax.lax.fori_loop(0, n, body, carry)

    ssm, conv, stats = state["ssm"], state["conv"], state["stats"]
    for kind, l0, n, k0 in cfg.segments():
        if kind == "mamba":
            x, ssm, conv, stats = mamba_layers((x, ssm, conv, stats),
                                               l0, k0, n)
        else:
            x, k_pools, v_pools, stats = attn_layers(
                (x, k_pools, v_pools, stats), l0, k0, n)
    return (gh.lm_logits(params, x, cfg).astype(F32), k_pools, v_pools,
            {"ssm": ssm, "conv": conv, "stats": stats})


def prefill_chunk(params, toks, cfg, k_pools, v_pools, table, wtable,
                  pos0, n_valid, slot, state):
    """One chunk of one request's prompt: ``toks`` [P] (``n_valid``
    real) at positions ``pos0``.. of the request in slot ``slot``.

    A Mamba layer continues from the slot's state and leaves the state
    after the last real token there; padding advances nothing (its
    ``dt`` is 0 and the convolution's tail is taken at the last real
    token). An attention layer attends over the request's pages (a
    dense view through ``table``) and the chunk, and writes the chunk's
    keys and values through the write table. Returns (the logits
    [1, V] of the last real position, k_pools, v_pools, state)."""
    P = toks.shape[0]
    BS = k_pools.shape[2]
    MB = table.shape[0]
    pos0 = jnp.asarray(pos0, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    rows = jnp.arange(P, dtype=jnp.int32)
    valid = rows < n_valid
    pos = pos0 + rows
    page = jnp.where(valid, jnp.take(jnp.asarray(wtable, jnp.int32),
                                     pos // BS), 0)
    off = pos % BS
    x = gh.embed(params, toks, cfg)

    def mamba_layers(carry, l0, m0, n):
        def body(i, carry):
            x, ssm, conv = carry
            l, m = l0 + i, m0 + i
            lp = gh.at_layer(params["mamba"], m)
            z, xbc, dt = gh.mamba_in(lp, x, cfg)
            xbc, tail = mamba2.causal_conv1d(
                xbc, lp["conv_w"], lp["conv_b"], conv[m, slot], n_valid)
            conv = conv.at[m, slot].set(tail)
            xs, b, c = gh.split(xbc, cfg)
            y, new = mamba2.ssd_scan(
                xs, jnp.where(valid[:, None], dt, 0.0),
                -jnp.exp(lp["A_log"].astype(F32)), b, c, lp["D"],
                mamba2.slot_state(ssm, m, slot),
                block=cfg.mamba_chunk_size)
            ssm = mamba2.set_slot_state(ssm, m, slot, new)
            h = gh.mamba_out(lp, x, y, z, cfg)
            x, _ = _moe(params, h, cfg, l, valid, None)
            return x, ssm, conv
        return jax.lax.fori_loop(0, n, body, carry)

    def attn_layers(carry, l0, a0, n):
        def body(i, carry):
            x, kp, vp = carry
            l, a = l0 + i, a0 + i
            lp = gh.at_layer(params["attn"], a)
            q, k, v = gh.attn_qkv(lp, x, cfg)
            # the request's pages as a dense view, the chunk laid in
            kc = jnp.take(kp[a], table, axis=0).reshape(MB * BS, *k.shape[1:])
            vc = jnp.take(vp[a], table, axis=0).reshape(MB * BS, *v.shape[1:])
            kc = jax.lax.dynamic_update_slice_in_dim(
                kc, k.astype(kc.dtype), pos0, axis=0)
            vc = jax.lax.dynamic_update_slice_in_dim(
                vc, v.astype(vc.dtype), pos0, axis=0)
            o = gh.attn_dense(q, kc, vc, pos, cfg)
            # the chunk's own rows through the WRITE table (shared
            # pages and padding land in the scratch page), one scatter
            # into the carried stack
            kp = kp.at[a, page, off].set(k.astype(kp.dtype))
            vp = vp.at[a, page, off].set(v.astype(vp.dtype))
            h = x + cfg.residual_multiplier * (o @ lp["o_proj"])
            x, _ = _moe(params, h, cfg, l, valid, None)
            return x, kp, vp
        return jax.lax.fori_loop(0, n, body, carry)

    ssm, conv = state["ssm"], state["conv"]
    for kind, l0, n, k0 in cfg.segments():
        if kind == "mamba":
            x, ssm, conv = mamba_layers((x, ssm, conv), l0, k0, n)
        else:
            x, k_pools, v_pools = attn_layers((x, k_pools, v_pools),
                                              l0, k0, n)
    last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=0)
    return (gh.lm_logits(params, last, cfg).astype(F32), k_pools, v_pools,
            {**state, "ssm": ssm, "conv": conv})
