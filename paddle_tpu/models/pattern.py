"""What a decoder that is run BY ITS LAYER PATTERN says about each kind
of layer, in one place, and the layer halves such models share.

A config class of such a family (``granite_hybrid.GraniteHybridConfig``,
``mellum.MellumConfig``, ``nemotron_h.NemotronHConfig``) maps every word
of its layer pattern to a :class:`LayerKind`: the layer's HALVES (a
mixer or none, an expert half or none: a granite or Mellum 2 layer is
both, a Nemotron-H layer is one), which page class its keys and values
live in (and so how long a request keeps them), how far back a query
sees, and which rotary table it uses. The parameter tree (one stack of
mixer weights a kind, ``moe`` over the layers that have an expert
half), the cache manager (``ops/paged_attention.BlockManager``: a pool
a page class), the two serving programs (``inference/hybrid.py``) and
the full-sequence ``forward`` all read that one description;
:func:`runs` gives the loops the programs make of a pattern.

The halves below are written once for all kinds: norm, q/k/v with the
kind's rotary table, dense attention with the kind's window (the
full-sequence program's), the Mamba-2 mixer's two ends, the expert half
as the config describes it (``cfg.expert_half``:
``ops/moe_experts.ExpertHalf``) with or without a shared MLP beside it,
embedding and head.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import mamba2
from ..ops import rms_norm as fused_rms_norm
from ..ops.moe_experts import ExpertHalf, mlp, moe_experts, route
from ..ops.rope import rope_frequencies, rotate_half

__all__ = ["LayerKind", "Run", "Member", "segments", "runs", "norm",
           "at_layer", "attn_qkv", "attn_dense", "mamba_in", "mamba_out",
           "split_xbc", "moe_block", "residual", "embed", "lm_logits"]

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """One word of the layer pattern: a layer's halves.

    ``mixer``: "mamba" | "attention" | None (the layer has no mixer:
    it is an expert half alone). ``stack``: the key of the parameter
    tree that holds this kind's stacked mixer weights. ``experts``: the
    layer has an expert half (its weights are the next layer of
    ``params["moe"]``, which is stacked over such layers only).
    ``pool``: the page class of its keys and values: "global" (a
    request keeps its pages to its end), "window" (it gives back what
    lies behind the window while it runs), None (no keys and values: a
    recurrent state a slot instead). ``window``: how many positions a
    query sees, itself included (None: all before it). ``rope``: the
    kind's section of ``rope_parameters`` (None: no position
    embedding)."""
    name: str
    mixer: Optional[str]
    stack: Optional[str]
    pool: Optional[str] = None
    window: Optional[int] = None
    rope: Optional[Tuple[Tuple[str, object], ...]] = None
    experts: bool = True

    def __post_init__(self):
        if self.mixer not in ("mamba", "attention", None):
            raise ValueError(f"layer kind {self.name!r}: mixer "
                             f"{self.mixer!r}")
        if self.mixer is None and not self.experts:
            raise ValueError(f"layer kind {self.name!r} has no half")

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


@dataclasses.dataclass(frozen=True)
class Member:
    """One layer of a run's unit: its kind, and where its weights lie
    in repeat ``i`` of the run: layer ``k0 + i * dk`` of the kind's
    mixer stack, layer ``e0 + i * de`` of ``params["moe"]``."""
    kind: LayerKind
    k0: int
    dk: int
    e0: int
    de: int

    def at(self, i):
        """(mixer layer, expert layer) in repeat ``i`` (traced)."""
        e = self.e0 + (i if self.de == 1 else i * self.de)
        return self.k0 + (i if self.dk == 1 else i * self.dk), e


@dataclasses.dataclass(frozen=True)
class Run:
    """``repeats`` times a unit of layers, from layer ``first`` on: ONE
    loop of the serving programs, whose body is the unit."""
    first: int
    repeats: int
    members: Tuple[Member, ...]


# layers of a loop body at most: two halves make a whole layer
_UNIT = 2


def runs(cfg):
    """The pattern as the loops the serving programs run: repeats of a
    UNIT of one or two layers, so that a loop's body compiles once
    whatever its repeats. Runs of equal layers are found first (granite
    and Mellum 2 come out as their :func:`segments`); a pattern whose
    layers are ONE half each alternates, and "MEMEM*EMEMEM" is (ME) x
    2, M, *, (EM) x 3: as sixteen loops of one layer the Nemotron-H
    cell's three programs compile in ~150 s on the chip, as nine loops
    of ten bodies in ~60 (PERF.md, PR 43). Greedy from the left: the
    unit length that covers most layers by repeating at least twice,
    else one layer."""
    pattern, kinds = tuple(cfg.pattern), cfg.kinds
    widest = _UNIT
    out, seen, n_exp, l = [], {}, 0, 0
    while l < len(pattern):
        best = (1, 1)
        for u in range(1, widest + 1):
            unit, r = pattern[l:l + u], 1
            while pattern[l + r * u:l + (r + 1) * u] == unit:
                r += 1
            if (r >= 2 or u == 1) and u * r > best[0] * best[1]:
                best = (u, r)
        u, r = best
        unit = pattern[l:l + u]
        in_unit = {n: unit.count(n) for n in unit}
        exp_in_unit = sum(kinds[n].experts for n in unit)
        members, k_at, e_at = [], dict(seen), n_exp
        for n in unit:
            members.append(Member(kinds[n], k_at.get(n, 0), in_unit[n],
                                  e_at, exp_in_unit))
            k_at[n] = k_at.get(n, 0) + 1
            e_at += kinds[n].experts
        out.append(Run(l, r, tuple(members)))
        for n, c in in_unit.items():
            seen[n] = seen.get(n, 0) + r * c
        n_exp += r * exp_in_unit
        l += u * r
    return out


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


def mamba_in(lp, x, cfg):
    """Norm and in_proj of a Mamba-2 layer on x [T, D]: (z [T, d_in],
    xBC [T, C] before the convolution, dt [T, H] float32 after
    softplus). ``in_proj``'s columns are [z | xBC | dt] and, where
    those are no whole number of 128 lanes, zeros up to one (a family's
    tree says so): the chip lays a product whose columns are not whole
    lanes out column-major, and with it everything the convolution
    reads and writes, the slots' tails among them."""
    h = norm(x, lp["input_norm"], cfg.rms_norm_eps)
    zxd = h @ lp["in_proj"]
    d_in, C = cfg.mamba_d_inner, cfg.mamba_conv_dim
    z, xbc = zxd[:, :d_in], zxd[:, d_in:d_in + C]
    dt = zxd[:, d_in + C:d_in + C + cfg.mamba_n_heads]
    dt = jax.nn.softplus(dt.astype(F32) + lp["dt_bias"].astype(F32)[None])
    return z, xbc, dt


def mamba_out(lp, x, y, z, cfg):
    """Gate, the norm over each B/C group's channels of d_in (all of
    d_in with one group), out_proj and the residual."""
    g = y.reshape(y.shape[0], -1).astype(F32) * jax.nn.silu(z.astype(F32))
    groups = cfg.mamba_n_groups
    if groups > 1:
        g = g.reshape(g.shape[0], groups, -1)
    var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    g = (g * jax.lax.rsqrt(var + cfg.rms_norm_eps)).reshape(y.shape[0], -1) \
        * lp["norm"].astype(F32)
    return residual(x, g.astype(x.dtype) @ lp["out_proj"], cfg)


def split_xbc(xbc, cfg):
    return mamba2.split_xbc(xbc, cfg.mamba_n_heads, cfg.mamba_d_head,
                            cfg.mamba_n_groups, cfg.mamba_d_state)


def moe_block(mp, h, cfg, layer=None):
    """A layer's expert half on h [T, D]: norm, the route, the experts
    held here and, where the family has one (``shared_in`` among the
    leaves), the shared MLP, as ``cfg.expert_half`` describes them;
    ``router_bias`` among the leaves is the bias of the choice. ``mp``
    is one layer's slice of ``params["moe"]``, except that with
    ``layer`` given its two expert leaves are the whole stacks
    (``moe_experts`` then addresses the layer itself). Returns (x',
    experts [T, k])."""
    half = getattr(cfg, "expert_half", None) or ExpertHalf()
    with jax.named_scope("layer/router"):
        u = norm(h, mp["post_norm"], cfg.rms_norm_eps)
        gates, experts = route(u, mp["router"], cfg.num_experts_per_tok,
                               half.scoring, mp.get("router_bias"),
                               half.scale)
    out = moe_experts(u, gates, experts, mp["w_in"], mp["w_out"],
                      offset=cfg.expert_offset, layer=layer, act=half.act)
    with jax.named_scope("layer/mlp"):
        if "shared_in" in mp:
            out = out + mlp(u, mp["shared_in"], mp["shared_out"], half.act)
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
