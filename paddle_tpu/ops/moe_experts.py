"""A dropless expert layer that is told which experts it holds.

``route`` scores every token against ALL the experts of the model (the
router keeps its published width) and chooses ``top_k`` of them, by
what the model's config says (:class:`ExpertHalf`): the largest logits,
softmaxed among themselves; or sigmoid scores in float32, chosen by
score plus a per-expert bias that takes no part in the gates,
renormalised over the chosen and scaled. ``moe_experts`` then computes,
for the experts ``[offset, offset + held)`` whose weights it was given,
the part of

    sum_j gate_j * W_out_j act(W_in_j u)

that those experts contribute (``act``: ``silu(g) * v`` over ``[g | v]``
for a gated expert of three matrices, ``relu(h)^2`` for one of two),
and leaves the rest out: on a chip that holds a share of a layer's
experts the absent ones' part is added by the chips that hold them
(their exchange is not this function's, and nothing here stands in for
it). With every expert held it is the whole layer.

No token is dropped: the assignments are sorted by expert and the
experts' matrices are applied group by group with
``jax.lax.ragged_dot`` (a grouped matrix product: on the chip one
launch that visits each held expert's rows once, whatever their
number). The assignments to experts held elsewhere sort behind the last
group and are masked out of the sum.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .pallas.registry import KERNELS

__all__ = ["ExpertHalf", "route", "moe_experts", "expert_counts", "mlp",
           "gated_mlp"]

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class ExpertHalf:
    """What a model's config says of its expert layers.

    ``scoring``: "softmax" (the top-k logits, softmaxed among
    themselves) | "sigmoid" (float32 sigmoid scores; the choice is of
    score + the router's bias leaf, the gates are the scores at the
    chosen, over their sum, times ``scale``). ``act``: "silu_gated"
    (``silu(g) * v``, ``[g | v] = W_in u``: three matrices an expert) |
    "relu2" (``relu(W_in u)^2``: two); the shared MLP beside the
    experts, where the family has one, is of the same form."""
    scoring: str = "softmax"
    scale: float = 1.0
    act: str = "silu_gated"

    def __post_init__(self):
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"expert scoring {self.scoring!r}")
        if self.act not in ("silu_gated", "relu2"):
            raise ValueError(f"expert activation {self.act!r}")


def route(u, w_router, top_k, scoring="softmax", bias=None, scale=1.0):
    """u [T, D], w_router [D, E] -> (gates [T, k] float32, experts
    [T, k] int32). "softmax": the top-k logits, softmaxed among
    themselves. "sigmoid": ``s = sigmoid(logits)`` in float32; the
    top-k of ``s + bias`` (``bias`` [E], for the choice only) are
    chosen; their gates are ``s`` there over the chosen's sum, times
    ``scale``."""
    logits = jnp.dot(u, w_router.astype(u.dtype),
                     preferred_element_type=F32)
    if scoring == "softmax":
        top, experts = jax.lax.top_k(logits, top_k)
        return jax.nn.softmax(top, axis=-1), experts.astype(jnp.int32)
    s = jax.nn.sigmoid(logits)
    choice = s if bias is None else s + bias.astype(F32)[None, :]
    _, experts = jax.lax.top_k(choice, top_k)
    top = jnp.take_along_axis(s, experts, axis=-1)
    gates = top / jnp.sum(top, axis=-1, keepdims=True)
    return gates * jnp.asarray(scale, F32), experts.astype(jnp.int32)


def _act(h, act):
    """An expert's (or the shared MLP's) hidden rows from ``W_in u``."""
    if act == "relu2":
        return jnp.square(jax.nn.relu(h))
    f = h.shape[-1] // 2
    return jax.nn.silu(h[..., :f]) * h[..., f:]


def mlp(u, w_in, w_out, act="silu_gated"):
    """``W_out act(W_in u)``: the shared MLP."""
    return _act(u @ w_in, act) @ w_out


def gated_mlp(u, w_in, w_out):
    """``W_out(silu(g) * v)``, ``[g | v] = W_in u``."""
    return mlp(u, w_in, w_out)


def experts_meta(w_in, w_out, act) -> dict:
    """What the ``moe_experts`` variants' predicates read."""
    from .pallas._util import interpret_mode
    return {"backend": jax.default_backend(),
            "interpret": bool(interpret_mode()),
            "K": int(w_in.shape[-2]), "N": int(w_in.shape[-1]),
            "F": int(w_out.shape[-2]), "act": act,
            "dtype": str(jnp.dtype(w_in.dtype))}


def moe_experts(u, gates, experts, w_in, w_out, offset=0, layer=None,
                act="silu_gated"):
    """The held experts' part of the layer for tokens u [T, D].

    gates / experts: [T, k] from :func:`route`; w_in: [held, D, 2F]
    ("silu_gated") or [held, D, F'] ("relu2"; F' >= F: columns past F
    are storage, see below); w_out: [held, F, D]; ``offset``: the first
    held expert's number among all. ``layer``: the two are the stacks
    [L, held, ...] of a loop over layers and this is the layer to use.
    Returns out [T, D].

    Two launches compute it, chosen in the kernel registry
    (``KERNELS.explain("moe_experts", experts_meta(...))``): XLA's
    ``ragged_dot`` over the assignments sorted by expert, and, where
    that would tile the experts' matrices 128 x 128 (widths that are
    odd multiples of 128), ``ops/pallas/moe_experts.py``'s, which lays
    the assignments out in blocks of rows and visits the touched
    experts only.

    Both read the matrices row-major with the columns on the lanes. An
    expert width that is no whole number of 128 lanes (1856 = 14.5 x
    128) is one the chip stores COLUMN-major by default, and a launch
    would be handed a re-laid-out copy of the whole stack in every step
    (4.3 GB at 7 layers x 64 experts); so such a family keeps
    ``w_in``'s columns rounded up to whole lanes, the extra ones zero
    (``relu(0)^2 = 0``: they add nothing), and the hidden rows are cut
    back to ``w_out``'s F."""
    with jax.named_scope("moe_experts"):
        _, fn = KERNELS.dispatch("moe_experts",
                                 experts_meta(w_in, w_out, act))
        return fn(u, gates, experts, w_in, w_out, offset, layer, act)


def _held(experts, offset, held):
    """(here [T, k]: the assignment is to an expert held here; key
    [T*k]: that expert's number among the held, ``held`` for the
    others)."""
    local = experts - jnp.int32(offset)
    here = (local >= 0) & (local < held)
    return here, jnp.where(here, local, held).reshape(-1)


def _ragged(u, gates, experts, w_in, w_out, offset, layer, act):
    """XLA's grouped product. With ``layer`` the launch is handed the
    whole stack as L x held groups of which only that layer's have rows
    (a slice would be copied out for it, 0.68 GB a layer at granite's
    widths)."""
    T, D = u.shape
    k = experts.shape[1]
    held = w_in.shape[-3]
    here, key = _held(experts, offset, held)
    # held assignments by expert, the others behind the last group
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
    hidden = _act(h, act)[:, :w_out.shape[-2]].astype(u.dtype)
    o = jax.lax.ragged_dot(hidden, w_out, sizes)            # [T*k, D]
    w = jnp.where(here, gates, 0.0).reshape(-1)[order]
    # rows past the last group are whatever the launch left there
    o = jnp.where((w > 0)[:, None], o.astype(F32) * w[:, None], 0.0)
    back = jnp.argsort(order)                               # unsort
    out = jnp.sum(jnp.take(o, back, axis=0).reshape(T, k, D), axis=1)
    return out.astype(u.dtype)


def _grouped(u, gates, experts, w_in, w_out, offset, layer, act):
    """``ops/pallas/moe_experts.py``'s launches over the assignments
    laid out by expert in blocks of rows: no sort, and no row of an
    expert held elsewhere."""
    from .pallas import moe_experts as launch
    T, D = u.shape
    k = experts.shape[1]
    here, key = _held(experts, offset, w_in.shape[-3])
    dest, src, block_expert, n_used = launch.layout(key, w_in.shape[-3])
    rows = jnp.take(u, src // k, axis=0)                    # [blocks*TM, D]
    hidden = launch.grouped_product(rows, w_in, layer, block_expert,
                                    n_used, act=act)
    o = launch.grouped_product(hidden, w_out, layer, block_expert, n_used)
    # each assignment's row back, in (token, choice) order
    o = jnp.take(o, jnp.minimum(dest, o.shape[0] - 1), axis=0)
    w = jnp.where(here, gates, 0.0).reshape(-1)
    o = jnp.where((w > 0)[:, None], o.astype(F32) * w[:, None], 0.0)
    return jnp.sum(o.reshape(T, k, D), axis=1).astype(u.dtype)


def _supports_grouped(meta):
    from .pallas.moe_experts import supports
    if meta["backend"] != "tpu" or meta["interpret"]:
        return False, "no TPU: the interpreter would run the launch"
    return supports(meta["K"], meta["N"], meta["F"], meta["act"],
                    meta["dtype"])


KERNELS.register("moe_experts", "pallas_grouped", _grouped, priority=10,
                 supports=_supports_grouped, tags=("serving", "pallas"))
KERNELS.register("moe_experts", "xla_ragged", _ragged, priority=0,
                 tags=("serving",))
# the widths, activation and type are in the jit signature or the
# config; "interpret" rides in every program cache's route key
KERNELS.declare_cache_key("moe_experts", ("backend", "interpret", "K", "N",
                                          "F", "act", "dtype"))


def expert_counts(experts, live, num_experts, held, offset=0):
    """int32 [4] of one layer's routing over the rows that are real
    tokens (``live`` [T] bool): assignments, those to the held experts,
    the largest number any one expert (of all) received, and how many
    of the held experts received any (the experts whose matrices a
    launch that visits the touched ones has to fetch)."""
    lv = live[:, None]
    local = experts - jnp.int32(offset)
    here = (local >= 0) & (local < held) & lv
    load = jnp.sum(jax.nn.one_hot(experts, num_experts, dtype=jnp.int32)
                   * lv[..., None], axis=(0, 1))
    touched = jnp.sum(jax.lax.dynamic_slice_in_dim(load, offset, held) > 0)
    return jnp.stack([jnp.sum(lv).astype(jnp.int32) * experts.shape[1],
                      jnp.sum(here).astype(jnp.int32),
                      jnp.max(load).astype(jnp.int32),
                      touched.astype(jnp.int32)])
