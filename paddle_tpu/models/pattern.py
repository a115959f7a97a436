"""What a decoder that is run BY ITS LAYER PATTERN says about each kind
of layer, in one place, and the layer halves such models share.

A config class of such a family (``granite_hybrid.GraniteHybridConfig``,
``mellum.MellumConfig``) maps every word of its ``layer_types`` to a
:class:`LayerKind`: which mixer the layer runs, which page class its
keys and values live in (and so how long a request keeps them), how far
back a query sees, and which rotary table it uses. The parameter tree
(one stack of mixer weights a kind), the cache manager
(``ops/paged_attention.BlockManager``: a pool a page class), the two
serving programs (``inference/hybrid.py``) and the full-sequence
``forward`` all read that one description.

The halves below are written once for all kinds: norm, q/k/v with the
kind's rotary table, dense attention with the kind's window (the
full-sequence program's), the expert layer with or without a shared
MLP beside it, embedding and head.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import rms_norm as fused_rms_norm
from ..ops.moe_experts import gated_mlp, moe_experts, route
from ..ops.rope import rope_frequencies, rotate_half

__all__ = ["LayerKind", "segments", "norm", "at_layer", "attn_qkv",
           "attn_dense", "moe_block", "residual", "embed", "lm_logits"]

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """One word of ``layer_types``.

    ``mixer``: "mamba" | "attention". ``stack``: the key of the
    parameter tree that holds this kind's stacked mixer weights.
    ``pool``: the page class of its keys and values: "global" (a
    request keeps its pages to its end), "window" (it gives back what
    lies behind the window while it runs), None (no keys and values: a
    recurrent state a slot instead). ``window``: how many positions a
    query sees, itself included (None: all before it). ``rope``: the
    kind's section of ``rope_parameters`` (None: no position
    embedding)."""
    name: str
    mixer: str
    stack: str
    pool: Optional[str] = None
    window: Optional[int] = None
    rope: Optional[Tuple[Tuple[str, object], ...]] = None

    def rope_table(self, head_dim):
        """(inv_freq [head_dim // 2], attention factor) or None."""
        if self.rope is None:
            return None
        return rope_frequencies(head_dim, dict(self.rope))


def segments(pattern):
    """Runs of equal layers: [(kind name, first layer, number of
    layers, first index among the layers of that kind)]."""
    out, seen = [], {}
    for l, kind in enumerate(pattern):
        if out and out[-1][0] == kind:
            out[-1][2] += 1
        else:
            out.append([kind, l, 1, seen.get(kind, 0)])
        seen[kind] = seen.get(kind, 0) + 1
    return [tuple(s) for s in out]


# -- layer halves ------------------------------------------------------------
def norm(x, weight, eps):
    """RMSNorm over the last axis of x [..., D] (the ops pack's)."""
    flat = x.reshape(1, -1, x.shape[-1])
    return fused_rms_norm(flat, weight.astype(x.dtype), eps).reshape(x.shape)


def at_layer(tree, i):
    """Layer ``i``'s slice of every stacked leaf."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        tree)


def attn_qkv(lp, x, cfg, kind=None, pos=None):
    """Norm and the three projections on x [T, D]: q [T, H, hd], k and
    v [T, KV, hd]; q and k rotated by ``kind``'s table at the absolute
    positions ``pos`` [T] where the kind has one."""
    T = x.shape[0]
    h = norm(x, lp["input_norm"], cfg.rms_norm_eps)
    q = (h @ lp["q_proj"]).reshape(T, cfg.num_attention_heads, -1)
    k = (h @ lp["k_proj"]).reshape(T, cfg.num_key_value_heads, -1)
    v = (h @ lp["v_proj"]).reshape(T, cfg.num_key_value_heads, -1)
    table = kind.rope_table(q.shape[-1]) if kind is not None else None
    if table is not None:
        q = rotate_half(q, pos, *table)
        k = rotate_half(k, pos, *table)
    return q, k, v


def attn_dense(q, k, v, q_pos, cfg, window=None):
    """Causal attention of q [P, H, hd] at absolute positions ``q_pos``
    [P] over keys and values [T, KV, hd] at positions 0..T-1; with
    ``window`` a query sees itself and the ``window - 1`` before it."""
    P, H, hd = q.shape
    T, KV, _ = k.shape
    qg = q.reshape(P, KV, H // KV, hd).astype(F32)
    s = jnp.einsum("pngh,tnh->ngpt", qg, k.astype(F32)) \
        * cfg.attention_multiplier
    back = q_pos[:, None] - jnp.arange(T)[None, :]
    see = back >= 0
    if window is not None:
        see = see & (back < window)
    s = jnp.where(see[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("ngpt,tnh->pngh", p, v.astype(F32))
    return o.reshape(P, H * hd).astype(q.dtype)


def moe_block(mp, h, cfg, layer=None):
    """The layer's second half on h [T, D]: the experts held here and,
    where the family has one (``shared_in`` among the leaves), the
    shared MLP. ``mp`` is one layer's slice of ``params["moe"]``,
    except that with ``layer`` given its two expert leaves are the
    whole stacks (``moe_experts`` then addresses the layer itself).
    Returns (x', experts [T, k])."""
    with jax.named_scope("layer/router"):
        u = norm(h, mp["post_norm"], cfg.rms_norm_eps)
        gates, experts = route(u, mp["router"], cfg.num_experts_per_tok)
    out = moe_experts(u, gates, experts, mp["w_in"], mp["w_out"],
                      offset=cfg.expert_offset, layer=layer)
    with jax.named_scope("layer/mlp"):
        if "shared_in" in mp:
            out = out + gated_mlp(u, mp["shared_in"], mp["shared_out"])
        return h + _times(out, cfg.residual_multiplier), experts


def _times(x, m):
    return x if m == 1 else m * x


def residual(x, y, cfg):
    """``x + r y``."""
    return x + _times(y, cfg.residual_multiplier)


def embed(params, tokens, cfg):
    x = jnp.take(params["embed_tokens"], tokens, axis=0)
    m = cfg.embedding_multiplier
    return x if m == 1 else x * jnp.asarray(m, x.dtype)


def lm_logits(params, x, cfg):
    """Final norm and the head: ``lm_head`` [D, V] where the family
    unties it, else the embedding's transpose."""
    x = norm(x, params["final_norm"], cfg.rms_norm_eps)
    head = params["lm_head"] if "lm_head" in params \
        else params["embed_tokens"].T
    lg = x @ head
    return lg if cfg.logits_scaling == 1 else lg / cfg.logits_scaling
