"""Mellum 2 family (``model_type: mellum``): a decoder whose layers
alternate, by a published pattern, between sliding-window attention and
full attention, every layer followed by a sparse expert layer with no
shared MLP beside it.

    h      = x + Attn(rmsnorm(x))
    x'     = h + experts(rmsnorm(h))
    logits = rmsnorm(x_L) @ W_head           (the head is not tied)

- attention: grouped queries, no bias, scale ``1 / sqrt(head_dim)``,
  rotate-half RoPE with the table of the layer's kind
  (``rope_parameters``: plain frequencies on ``sliding_attention``
  layers, YaRN with its attention factor on ``full_attention`` ones;
  ``ops/rope.rope_frequencies``). A ``sliding_attention`` layer's query
  at position ``i`` sees ``j`` with ``0 <= i - j < sliding_window``; a
  ``full_attention`` layer's sees every ``j <= i``;
- experts: ``ops/moe_experts.py``: the router scores all
  ``num_experts``, the ``num_experts_per_tok`` largest are softmaxed
  among themselves (``norm_topk_prob``: the softmax over all,
  renormalised over the chosen, is the same numbers); this program
  holds ``num_local_experts`` of them from ``expert_offset`` on and
  computes their part of the sum.

Not computed, because the published config has no key for them: a
normalisation of q and k, and a multi-token-prediction head.

The parameter tree stacks each kind of layer on a leading axis
(``window`` over the sliding-window layers, ``full`` over the
full-attention ones, ``moe`` over all), so that a run of equal layers
is one loop. ``forward`` is the full-sequence program (no cache); the
serving programs are the pattern-driven ones of ``inference/hybrid.py``
and share the layer halves of ``models/pattern.py``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .pattern import (LayerKind, at_layer, attn_dense, attn_qkv, embed,
                      lm_logits, moe_block, residual, segments)

__all__ = ["MellumConfig", "init_params", "forward", "MELLUM_TINY"]

F32 = jnp.float32

_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}


@dataclasses.dataclass
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    intermediate_size: int = 7168          # a dense MLP's: no layer has one
    moe_intermediate_size: int = 896       # one expert's width
    num_hidden_layers: int = 28
    # the published patterns; entries beyond num_hidden_layers are unused
    layer_types: Tuple[str, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    # the router's width; the experts HELD by this program and where
    # they start among them (None: all are held)
    num_experts: int = 64
    num_local_experts: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    sliding_window: int = 1024
    rope_parameters: Optional[Dict[str, Dict]] = None
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # the multipliers other pattern-run families have (models/pattern.py)
    embedding_multiplier = 1
    residual_multiplier = 1
    logits_scaling = 1

    def __post_init__(self):
        if not self.layer_types:
            self.layer_types = tuple(
                "full_attention" if i % 4 == 3 else "sliding_attention"
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) < self.num_hidden_layers:
            raise ValueError("layer_types is shorter than "
                             "num_hidden_layers")
        unknown = set(self.pattern) - set(_ROPE)
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}: this "
                             f"family has {sorted(_ROPE)}")
        self.mlp_layer_types = tuple(self.mlp_layer_types
                                     or ("sparse",) * len(self.layer_types))
        if set(self.mlp_layer_types[:self.num_hidden_layers]) != {"sparse"}:
            raise ValueError("mlp_layer_types: only 'sparse' layers are "
                             "built (the published pattern has no other)")
        if self.rope_parameters is None:
            self.rope_parameters = {k: dict(v) for k, v in _ROPE.items()}
        if self.num_local_experts is None:
            self.num_local_experts = self.num_experts
        if self.expert_offset + self.num_local_experts > self.num_experts:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.num_local_experts}) are not among the "
                f"{self.num_experts} the router scores")
        if not self.norm_topk_prob:
            raise ValueError("norm_topk_prob false: the expert layer "
                             "renormalises the chosen gates "
                             "(ops/moe_experts.route)")
        if self.tie_word_embeddings:
            raise ValueError("mellum unties its head")

    # -- derived ---------------------------------------------------------
    @property
    def attention_multiplier(self):
        return 1.0 / math.sqrt(self.head_dim)

    @property
    def pattern(self) -> Tuple[str, ...]:
        return self.layer_types[:self.num_hidden_layers]

    @property
    def kinds(self) -> Dict[str, LayerKind]:
        """What each word of ``layer_types`` is (models/pattern.py)."""
        def rope(name):
            return tuple(sorted(self.rope_parameters[name].items()))
        return {
            "sliding_attention": LayerKind(
                "sliding_attention", "attention", "window", pool="window",
                window=self.sliding_window,
                rope=rope("sliding_attention")),
            "full_attention": LayerKind(
                "full_attention", "attention", "full", pool="global",
                rope=rope("full_attention"))}

    @property
    def num_kv_layers(self) -> int:
        """Layers whose pages a request keeps to its end (the global
        pools' depth)."""
        return sum(t == "full_attention" for t in self.pattern)

    @property
    def num_window_layers(self) -> int:
        """Layers whose pages go back behind the window (the window
        pools' depth)."""
        return sum(t == "sliding_attention" for t in self.pattern)

    def segments(self):
        return segments(self.pattern)


MELLUM_TINY = MellumConfig(
    vocab_size=512, hidden_size=64, moe_intermediate_size=32,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_experts=8, num_experts_per_tok=3, sliding_window=12,
    max_position_embeddings=1024, dtype=jnp.float32)


def init_params(cfg: MellumConfig, key=None, dtype=None) -> Dict:
    """Seeded parameters in the stacked layout."""
    dtype = dtype or cfg.dtype
    key = key if key is not None else jax.random.key(0)
    D, E, held = cfg.hidden_size, cfg.num_experts, cfg.num_local_experts
    F = cfg.moe_intermediate_size
    H, KV, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    L = cfg.num_hidden_layers
    ks = iter(jax.random.split(key, 16))

    def w(*shape, std=0.02):
        return (jax.random.normal(next(ks), shape, F32) * std).astype(dtype)

    def attn(n):
        return {"input_norm": jnp.ones((n, D), F32),
                "q_proj": w(n, D, H * hd), "k_proj": w(n, D, KV * hd),
                "v_proj": w(n, D, KV * hd), "o_proj": w(n, H * hd, D)}

    return {
        "embed_tokens": w(cfg.vocab_size, D),
        "lm_head": w(D, cfg.vocab_size),
        "final_norm": jnp.ones((D,), F32),
        "window": attn(cfg.num_window_layers),
        "full": attn(cfg.num_kv_layers),
        "moe": {"post_norm": jnp.ones((L, D), F32),
                "router": w(L, D, E),
                "w_in": w(L, held, D, 2 * F), "w_out": w(L, held, F, D)},
    }


def forward(params: Dict, tokens, cfg: MellumConfig):
    """Logits [S, V] of one sequence ``tokens`` [S], no cache."""
    pos = jnp.arange(tokens.shape[0])
    x = embed(params, tokens, cfg)
    seen = {}
    for l, name in enumerate(cfg.pattern):
        kind = cfg.kinds[name]
        i = seen.get(name, 0)
        seen[name] = i + 1
        lp = at_layer(params[kind.stack], i)
        q, k, v = attn_qkv(lp, x, cfg, kind, pos)
        h = residual(x, attn_dense(q, k, v, pos, cfg, kind.window)
                     @ lp["o_proj"], cfg)
        x, _ = moe_block(at_layer(params["moe"], l), h, cfg)
    return lm_logits(params, x, cfg)
