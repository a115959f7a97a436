"""A dropless expert layer that is told which experts it holds.

``route`` scores every token against ALL the experts of the model (the
router keeps its published width), takes the ``top_k`` largest logits
and softmaxes those. ``moe_experts`` then computes, for the experts
``[offset, offset + held)`` whose weights it was given, the part of

    sum_j gate_j * W_out_j ( silu(g_j) * v_j ),   [g_j | v_j] = W_in_j u

that those experts contribute, and leaves the rest out: on a chip that
holds a share of a layer's experts the absent ones' part is added by
the chips that hold them (their exchange is not this function's, and
nothing here stands in for it). With every expert held it is the whole
layer.

No token is dropped: the assignments are sorted by expert and the
experts' matrices are applied group by group with
``jax.lax.ragged_dot`` (a grouped matrix product: on the chip one
launch that visits each held expert's rows once, whatever their
number). The assignments to experts held elsewhere sort behind the last
group and are masked out of the sum.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["route", "moe_experts", "expert_counts", "gated_mlp"]

F32 = jnp.float32


def route(u, w_router, top_k):
    """u [T, D], w_router [D, E] -> (gates [T, k] float32, experts
    [T, k] int32): the top-k logits, softmaxed among themselves."""
    logits = jnp.dot(u, w_router.astype(u.dtype),
                     preferred_element_type=F32)
    top, experts = jax.lax.top_k(logits, top_k)
    return jax.nn.softmax(top, axis=-1), experts.astype(jnp.int32)


def gated_mlp(u, w_in, w_out):
    """``W_out(silu(g) * v)``, ``[g | v] = W_in u``: the shared MLP."""
    h = u @ w_in
    f = h.shape[-1] // 2
    return (jax.nn.silu(h[..., :f]) * h[..., f:]) @ w_out


def moe_experts(u, gates, experts, w_in, w_out, offset=0, layer=None):
    """The held experts' part of the layer for tokens u [T, D].

    gates / experts: [T, k] from :func:`route`; w_in: [held, D, 2F];
    w_out: [held, F, D]; ``offset``: the first held expert's number
    among all. ``layer``: the two are the stacks [L, held, ...] of a
    loop over layers and this is the layer to use: the launch is handed
    the whole stack as L x held groups of which only that layer's have
    rows (a slice would be copied out for it, 0.68 GB a layer at the
    published widths). Returns out [T, D]."""
    T, D = u.shape
    k = experts.shape[1]
    held = w_in.shape[-3]
    F = w_out.shape[-2]
    with jax.named_scope("moe_experts"):
        local = experts - jnp.int32(offset)
        here = (local >= 0) & (local < held)
        # held assignments by expert, the others behind the last group
        key = jnp.where(here, local, held).reshape(-1)          # [T*k]
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(key, held, dtype=jnp.int32),
                        axis=0, dtype=jnp.int32)
        if layer is not None:
            L = w_in.shape[0]
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((L * held,), jnp.int32), sizes,
                (jnp.asarray(layer, jnp.int32) * held,))
            w_in = w_in.reshape(L * held, *w_in.shape[2:])
            w_out = w_out.reshape(L * held, *w_out.shape[2:])
        rows = jnp.take(u, order // k, axis=0)                  # [T*k, D]
        h = jax.lax.ragged_dot(rows, w_in, sizes)
        act = (jax.nn.silu(h[:, :F]) * h[:, F:]).astype(u.dtype)
        o = jax.lax.ragged_dot(act, w_out, sizes)               # [T*k, D]
        w = jnp.where(here, gates, 0.0).reshape(-1)[order]
        # rows past the last group are whatever the launch left there
        o = jnp.where((w > 0)[:, None], o.astype(F32) * w[:, None], 0.0)
        back = jnp.argsort(order)                               # unsort
        out = jnp.sum(jnp.take(o, back, axis=0).reshape(T, k, D), axis=1)
        return out.astype(u.dtype)


def expert_counts(experts, live, num_experts, held, offset=0):
    """int32 [3] of one layer's routing over the rows that are real
    tokens (``live`` [T] bool): assignments, those to the held experts,
    and the largest number any one expert (of all) received."""
    lv = live[:, None]
    local = experts - jnp.int32(offset)
    here = (local >= 0) & (local < held) & lv
    load = jnp.sum(jax.nn.one_hot(experts, num_experts, dtype=jnp.int32)
                   * lv[..., None], axis=(0, 1))
    return jnp.stack([jnp.sum(lv).astype(jnp.int32) * experts.shape[1],
                      jnp.sum(here).astype(jnp.int32),
                      jnp.max(load).astype(jnp.int32)])
