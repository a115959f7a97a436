"""Granite-4.0-H family (``model_type: granitemoehybrid``): a decoder
whose layers alternate, by a published pattern, between a Mamba-2
mixer and a grouped-query attention mixer without position embedding,
each followed by a sparse expert layer plus one shared gated MLP.

With ``e`` = embedding_multiplier, ``r`` = residual_multiplier,
``a`` = attention_multiplier:

    x0     = e * E[tok]
    h      = x + r * mixer(rmsnorm(x))
    x'     = h + r * (experts(u) + shared(u)),   u = rmsnorm(h)
    logits = rmsnorm(x_L) @ E^T / logits_scaling

- attention mixer: GQA, no bias, no RoPE, ``softmax(a q k^T) v``;
- Mamba-2 mixer: ``[z | xBC | dt] = in_proj(u)``; ``xBC`` through a
  depthwise causal convolution and SiLU, split into x, B, C;
  ``dt = softplus(dt + dt_bias)``; the recurrence of ``ops/mamba2.py``;
  ``out_proj(rmsnorm_w(y * silu(z)))``;
- experts: ``ops/moe_experts.py`` — the router scores all
  ``num_experts``; this program holds ``num_local_experts`` of them,
  starting at ``expert_offset``, and computes their part of the sum.

The parameter tree stacks each kind of layer on a leading axis
(``mamba`` over the Mamba layers, ``attn`` over the attention layers,
``moe`` over all layers), so that a run of equal layers is one loop.
``forward`` is the full-sequence program (no cache); the serving
programs are in ``inference/hybrid.py`` and share the layer halves
defined here.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import mamba2
from .pattern import (LayerKind, at_layer, attn_dense, attn_qkv,  # noqa: F401
                      embed, lm_logits, mamba_in, mamba_out, moe_block,
                      norm, segments, split_xbc)

__all__ = ["GraniteHybridConfig", "init_params", "forward",
           "GRANITE_HYBRID_TINY"]

F32 = jnp.float32


@dataclasses.dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 4096
    intermediate_size: int = 768           # one expert's width
    shared_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    # the published pattern; layers beyond num_hidden_layers are unused
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    # experts HELD by this program, and where they start among the
    # num_experts the router scores (None: all are held)
    num_local_experts: int = 72
    num_experts: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_tok: int = 10
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = True
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if not self.layer_types:
            # granite-4.0-h-small's period of ten
            self.layer_types = tuple(
                "attention" if i % 10 == 5 else "mamba"
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) < self.num_hidden_layers:
            raise ValueError("layer_types is shorter than "
                             "num_hidden_layers")
        if self.num_experts is None:
            self.num_experts = self.num_local_experts
        if self.expert_offset + self.num_local_experts > self.num_experts:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.num_local_experts}) are not among the "
                f"{self.num_experts} the router scores")
        if not self.tie_word_embeddings:
            raise ValueError("granitemoehybrid ties its embeddings")

    # -- derived sizes ------------------------------------------------
    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def pattern(self) -> Tuple[str, ...]:
        return self.layer_types[:self.num_hidden_layers]

    @property
    def num_kv_layers(self) -> int:
        """Layers that hold keys and values (the paged pools' depth)."""
        return sum(t == "attention" for t in self.pattern)

    @property
    def num_recurrent_layers(self) -> int:
        """Layers that hold a recurrent state (the state pools' depth)."""
        return sum(t == "mamba" for t in self.pattern)

    @property
    def mamba_d_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self):
        return (self.mamba_d_inner
                + 2 * self.mamba_n_groups * self.mamba_d_state)

    @property
    def kinds(self) -> Dict[str, LayerKind]:
        """What each word of ``layer_types`` is (models/pattern.py)."""
        return {"mamba": LayerKind("mamba", "mamba", "mamba"),
                "attention": LayerKind("attention", "attention", "attn",
                                       pool="global")}

    def segments(self):
        """Runs of equal layers: [(kind, first layer, number of layers,
        first index among the layers of that kind)]."""
        return segments(self.pattern)

    def state_shapes(self, slots: int):
        """(ssm [Lm, slots, N, H*hp], conv tail [Lm, slots, K-1, C]):
        both with the long axis last, the one the chip tiles by 128."""
        lm = self.num_recurrent_layers
        return ((lm, slots, self.mamba_d_state, self.mamba_d_inner),
                (lm, slots, self.mamba_d_conv - 1, self.mamba_conv_dim))


GRANITE_HYBRID_TINY = GraniteHybridConfig(
    vocab_size=512, hidden_size=64, intermediate_size=32,
    shared_intermediate_size=48, num_hidden_layers=4,
    layer_types=("mamba", "mamba", "attention", "mamba"),
    num_attention_heads=4, num_key_value_heads=2, num_local_experts=8,
    num_experts_per_tok=3, mamba_n_heads=4, mamba_d_head=16,
    mamba_d_state=16, mamba_chunk_size=8, max_position_embeddings=256,
    dtype=jnp.float32)


def init_params(cfg: GraniteHybridConfig, key=None, dtype=None) -> Dict:
    """Seeded parameters in the stacked layout. ``A_log`` and
    ``dt_bias`` are drawn so that a step's decay ``exp(dt A)`` lies
    inside (0, 1) and the state remembers over tens to thousands of
    positions (dt in [1e-3, 1e-1] as Mamba-2 draws it, A in [0.1, 1]),
    so that tests see what the state carries."""
    dtype = dtype or cfg.dtype
    key = key if key is not None else jax.random.key(0)
    D, E, held = cfg.hidden_size, cfg.num_experts, cfg.num_local_experts
    F, Fs = cfg.intermediate_size, cfg.shared_intermediate_size
    H, KV, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    Hm, d_in, C = cfg.mamba_n_heads, cfg.mamba_d_inner, cfg.mamba_conv_dim
    L, Lm, La = (cfg.num_hidden_layers, cfg.num_recurrent_layers,
                 cfg.num_kv_layers)
    ks = iter(jax.random.split(key, 24))

    def w(*shape, std=0.02):
        return (jax.random.normal(next(ks), shape, F32) * std).astype(dtype)

    dt = jnp.exp(jax.random.uniform(next(ks), (Lm, Hm), F32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {
        # small beside the layers' output: the head is tied to it and
        # it enters the stream times embedding_multiplier, so at 0.02 a
        # token's own logit would be the largest at every position
        "embed_tokens": w(cfg.vocab_size, D, std=0.02 / 16),
        "final_norm": jnp.ones((D,), F32),
        "mamba": {
            "input_norm": jnp.ones((Lm, D), F32),
            "in_proj": w(Lm, D, d_in + C + Hm),
            "conv_w": w(Lm, cfg.mamba_d_conv, C, std=0.3),
            "conv_b": jnp.zeros((Lm, C), F32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1
            "A_log": jnp.log(jax.random.uniform(next(ks), (Lm, Hm), F32,
                                                0.1, 1.0)),
            "D": jnp.ones((Lm, Hm), F32),
            "norm": jnp.ones((Lm, d_in), F32),
            "out_proj": w(Lm, d_in, D),
        },
        "attn": {
            "input_norm": jnp.ones((La, D), F32),
            "q_proj": w(La, D, H * hd), "k_proj": w(La, D, KV * hd),
            "v_proj": w(La, D, KV * hd), "o_proj": w(La, H * hd, D),
        },
        "moe": {
            "post_norm": jnp.ones((L, D), F32),
            "router": w(L, D, E),
            "w_in": w(L, held, D, 2 * F), "w_out": w(L, held, F, D),
            "shared_in": w(L, D, 2 * Fs), "shared_out": w(L, Fs, D),
        },
    }


# -- the layer halves forward and the serving programs share live in
# models/pattern.py (the Mamba-2 mixer's two ends among them)


def forward(params: Dict, tokens, cfg: GraniteHybridConfig):
    """Logits [S, V] of one sequence ``tokens`` [S], no cache: every
    Mamba layer scans the whole sequence from a zero state."""
    S = tokens.shape[0]
    Q = min(cfg.mamba_chunk_size, S)
    pad = -S % Q
    toks = jnp.pad(tokens, (0, pad))
    valid = jnp.arange(S + pad) < S
    x = embed(params, toks, cfg)
    pos = jnp.arange(S + pad)
    mi = ai = 0
    for l, kind in enumerate(cfg.pattern):
        if kind == "mamba":
            lp = at_layer(params["mamba"], mi)
            mi += 1
            z, xbc, dt = mamba_in(lp, x, cfg)
            tail = jnp.zeros((cfg.mamba_d_conv - 1, cfg.mamba_conv_dim),
                             x.dtype)
            xbc, _ = mamba2.causal_conv1d(xbc, lp["conv_w"], lp["conv_b"],
                                          tail, S)
            xs, b, c = split_xbc(xbc, cfg)
            s0 = jnp.zeros((cfg.mamba_d_state, cfg.mamba_d_inner), F32)
            y, _ = mamba2.ssd_scan(
                xs, jnp.where(valid[:, None], dt, 0.0),
                -jnp.exp(lp["A_log"].astype(F32)), b, c, lp["D"], s0,
                block=Q)
            h = mamba_out(lp, x, y, z, cfg)
        else:
            lp = at_layer(params["attn"], ai)
            ai += 1
            q, k, v = attn_qkv(lp, x, cfg)
            h = x + cfg.residual_multiplier * (
                attn_dense(q, k, v, pos, cfg) @ lp["o_proj"])
        x, _ = moe_block(at_layer(params["moe"], l), h, cfg)
    return lm_logits(params, x, cfg)[:S]
