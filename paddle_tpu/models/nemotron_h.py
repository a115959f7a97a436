"""Nemotron-H family (``model_type: nemotron_h``; Nemotron 3 Nano): a
decoder whose every layer is ONE half, chosen by the layer's letter in
the published ``hybrid_override_pattern``:

    u  = rmsnorm(x; w_l),    x' = x + f(u)
    logits = rmsnorm(x_L) @ W_head          (untied, no multipliers)

- ``M``, Mamba-2: ``[z | xBC | dt] = in_proj(u)`` with ``d_inner =
  mamba_num_heads x mamba_head_dim`` (not ``expand`` x hidden); ``xBC``
  through a depthwise causal convolution and SiLU, split into x, B, C of
  ``n_groups`` B/C groups (head ``h`` reads group ``h // (heads /
  groups)``); ``dt = softplus(dt + dt_bias)``, not clamped; the
  recurrence of ``ops/mamba2.py``; ``out_proj(groupnorm_w(y *
  silu(z)))``, the RMS norm taken over EACH GROUP's ``d_inner /
  n_groups`` channels;
- ``*``, attention: grouped queries (``num_attention_heads x head_dim``
  is not the hidden size), no bias, scale ``1 / sqrt(head_dim)``, and
  NO position embedding: the family's published implementation applies
  none in its attention (``rope_theta`` and ``partial_rotary_factor``
  are in the file and read by nothing);
- ``E``, experts: ``ops/moe_experts.py`` with ``ExpertHalf("sigmoid",
  routed_scaling_factor, "relu2")``: float32 sigmoid scores over all
  ``num_experts``; the ``num_experts_per_tok`` largest of score +
  ``e_score_correction_bias`` are chosen (``n_group = topk_group = 1``:
  no limiting to groups of experts); gates are the scores at the
  chosen, over their sum (``norm_topk_prob``), times the scaling;
  experts and the shared MLP are two matrices with ``relu(.)^2``
  between. This program holds ``n_routed_experts`` of them from
  ``expert_offset`` on and computes their part of the sum.

The pattern has no ``-`` (dense MLP) layer, so ``intermediate_size`` is
read by nothing; such a letter is refused.

The parameter tree stacks each half on a leading axis (``mamba``,
``attn``, and ``moe`` over the ``E`` layers only). Two stacked
matrices are stored with their columns rounded up to whole lanes of
128, the extra columns zero (``lanes``): an expert's first matrix (1856
-> 1920; ``ops/moe_experts.moe_experts`` says why) and the Mamba-2
``in_proj`` (4096 + 6144 + 64 = 10304 -> 10368; ``pattern.mamba_in``). ``forward`` is the
full-sequence program (no cache); the serving programs are the
pattern-driven ones of ``inference/hybrid.py`` and share the layer
halves of ``models/pattern.py``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import mamba2
from ..ops.moe_experts import ExpertHalf
from .pattern import (LayerKind, at_layer, attn_dense, attn_qkv, embed,
                      lm_logits, mamba_in, mamba_out, moe_block, residual,
                      segments, split_xbc)

__all__ = ["NemotronHConfig", "init_params", "forward", "NEMOTRON_H_TINY"]

F32 = jnp.float32

# Nemotron-3-Nano-30B-A3B's 52 layers
_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass
class NemotronHConfig:
    """The published keys, under their published names."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    # the published pattern; letters beyond num_hidden_layers are unused
    hybrid_override_pattern: str = _PATTERN
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    # experts HELD by this program, and where they start among the
    # num_experts the router scores (None: all are held)
    n_routed_experts: int = 128
    num_experts: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856      # one expert's width
    moe_shared_expert_intermediate_size: int = 3712
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    mlp_hidden_act: str = "relu2"
    mamba_hidden_act: str = "silu"
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # the multipliers other pattern-run families have (models/pattern.py)
    embedding_multiplier = 1
    residual_multiplier = 1
    logits_scaling = 1

    def __post_init__(self):
        if len(self.hybrid_override_pattern) < self.num_hidden_layers:
            raise ValueError("hybrid_override_pattern is shorter than "
                             "num_hidden_layers")
        unknown = set(self.pattern) - set("ME*")
        if unknown:
            raise ValueError(
                f"hybrid_override_pattern holds {sorted(unknown)}: M "
                "(Mamba-2), E (experts) and * (attention) are built (a "
                "'-' layer is a dense MLP half, which no published "
                "pattern of this size has)")
        if self.num_experts is None:
            self.num_experts = self.n_routed_experts
        if self.expert_offset + self.n_routed_experts > self.num_experts:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.n_routed_experts}) are not among the "
                f"{self.num_experts} the router scores")
        for key, want in (("n_group", 1), ("topk_group", 1),
                          ("norm_topk_prob", True),
                          ("n_shared_experts", 1),
                          ("mlp_hidden_act", "relu2"),
                          ("mamba_hidden_act", "silu"),
                          ("tie_word_embeddings", False)):
            if getattr(self, key) != want:
                raise ValueError(
                    f"{key}={getattr(self, key)!r}: this family is built "
                    f"for {want!r} (selection limited to groups of "
                    "experts, unnormalised gates, several shared MLPs, "
                    "another activation and a tied head are not)")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("mamba_num_heads does not divide into "
                             "n_groups")

    # -- what models/pattern.py and inference/hybrid.py read ------------
    @property
    def pattern(self) -> Tuple[str, ...]:
        return tuple(self.hybrid_override_pattern[:self.num_hidden_layers])

    @property
    def kinds(self) -> Dict[str, LayerKind]:
        """What each letter of the pattern is: one half a layer."""
        return {"M": LayerKind("M", "mamba", "mamba", experts=False),
                "*": LayerKind("*", "attention", "attn", pool="global",
                               experts=False),
                "E": LayerKind("E", None, None)}

    @property
    def expert_half(self) -> ExpertHalf:
        return ExpertHalf("sigmoid", self.routed_scaling_factor,
                          self.mlp_hidden_act)

    def segments(self):
        """Runs of equal layers (here every run is one layer; the
        serving programs loop over ``pattern.runs``, units of up to
        two layers)."""
        return segments(self.pattern)

    def _count(self, letter):
        return sum(t == letter for t in self.pattern)

    @property
    def num_kv_layers(self) -> int:
        return self._count("*")

    @property
    def num_recurrent_layers(self) -> int:
        return self._count("M")

    @property
    def num_expert_layers(self) -> int:
        return self._count("E")

    @property
    def num_local_experts(self) -> int:
        return self.n_routed_experts

    @property
    def expert_storage_width(self) -> int:
        """Columns ``moe.w_in`` is stored with."""
        return lanes(self.moe_intermediate_size)

    @property
    def in_proj_storage_width(self) -> int:
        """Columns ``mamba.in_proj`` [z | xBC | dt] is stored with."""
        return lanes(self.mamba_d_inner + self.mamba_conv_dim
                     + self.mamba_num_heads)

    @property
    def rms_norm_eps(self):
        return self.layer_norm_epsilon

    @property
    def attention_multiplier(self):
        return 1.0 / math.sqrt(self.head_dim)

    # the Mamba-2 mixer's sizes under the names pattern.py reads
    mamba_n_heads = property(lambda self: self.mamba_num_heads)
    mamba_d_head = property(lambda self: self.mamba_head_dim)
    mamba_d_state = property(lambda self: self.ssm_state_size)
    mamba_n_groups = property(lambda self: self.n_groups)
    mamba_d_conv = property(lambda self: self.conv_kernel)
    mamba_chunk_size = property(lambda self: self.chunk_size)

    @property
    def mamba_d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self):
        return self.mamba_d_inner + 2 * self.n_groups * self.ssm_state_size

    def state_shapes(self, slots: int):
        """(ssm [Lm, slots, N, H*hp], conv tail [Lm, slots, K-1, C]):
        both with the long axis last, the one the chip tiles by 128."""
        lm = self.num_recurrent_layers
        return ((lm, slots, self.ssm_state_size, self.mamba_d_inner),
                (lm, slots, self.conv_kernel - 1, self.mamba_conv_dim))


def lanes(width: int) -> int:
    """``width`` rounded up to whole lanes of 128: the columns a
    stacked matrix is stored with, zeros past its own."""
    return -(-width // 128) * 128


NEMOTRON_H_TINY = NemotronHConfig(
    vocab_size=512, hidden_size=48, num_hidden_layers=9,
    hybrid_override_pattern="MEMEM*EME", num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
    mamba_head_dim=16, ssm_state_size=16, n_groups=4, chunk_size=8,
    n_routed_experts=8, num_experts_per_tok=3, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=40, max_position_embeddings=256,
    dtype=jnp.float32)


def init_params(cfg: NemotronHConfig, key=None, dtype=None) -> Dict:
    """Seeded parameters in the stacked layout. ``A_log`` and
    ``dt_bias`` are drawn as ``granite_hybrid.init_params`` draws them
    (a step's decay inside (0, 1), a state that remembers); the
    router's bias is small and non-zero, so that the choice differs
    from the order of the scores."""
    dtype = dtype or cfg.dtype
    key = key if key is not None else jax.random.key(0)
    D, E, held = cfg.hidden_size, cfg.num_experts, cfg.n_routed_experts
    F, Fs = (cfg.moe_intermediate_size,
             cfg.moe_shared_expert_intermediate_size)
    H, KV, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    Hm, d_in, C = cfg.mamba_num_heads, cfg.mamba_d_inner, cfg.mamba_conv_dim
    Lm, La, Le = (cfg.num_recurrent_layers, cfg.num_kv_layers,
                  cfg.num_expert_layers)
    ks = iter(jax.random.split(key, 24))

    def w(*shape, std=0.02, stored=None):
        x = (jax.random.normal(next(ks), shape, F32) * std).astype(dtype)
        if stored is None:
            return x
        return jnp.pad(x, ((0, 0),) * (x.ndim - 1)
                       + ((0, stored - shape[-1]),))

    dt = jnp.exp(jax.random.uniform(next(ks), (Lm, Hm), F32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "embed_tokens": w(cfg.vocab_size, D),
        "lm_head": w(D, cfg.vocab_size),
        "final_norm": jnp.ones((D,), F32),
        "mamba": {
            "input_norm": jnp.ones((Lm, D), F32),
            "in_proj": w(Lm, D, d_in + C + Hm,
                         stored=cfg.in_proj_storage_width),
            "conv_w": w(Lm, cfg.conv_kernel, C, std=0.3),
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
        # post_norm: the norm in front of the expert half, which for an
        # E layer is the layer's own
        "moe": {
            "post_norm": jnp.ones((Le, D), F32),
            "router": w(Le, D, E),
            "router_bias": jax.random.normal(next(ks), (Le, E), F32) * 0.02,
            "w_in": w(Le, held, D, F, stored=cfg.expert_storage_width),
            "w_out": w(Le, held, F, D),
            "shared_in": w(Le, D, Fs), "shared_out": w(Le, Fs, D),
        },
    }


def forward(params: Dict, tokens, cfg: NemotronHConfig):
    """Logits [S, V] of one sequence ``tokens`` [S], no cache: every
    Mamba layer scans the whole sequence from a zero state."""
    S = tokens.shape[0]
    Q = min(cfg.chunk_size, S)
    pad = -S % Q
    toks = jnp.pad(tokens, (0, pad))
    valid = jnp.arange(S + pad) < S
    pos = jnp.arange(S + pad)
    x = embed(params, toks, cfg)
    seen = {"M": 0, "*": 0, "E": 0}
    for name in cfg.pattern:
        kind, i = cfg.kinds[name], seen[name]
        seen[name] += 1
        if kind.mixer == "mamba":
            lp = at_layer(params[kind.stack], i)
            z, xbc, dt = mamba_in(lp, x, cfg)
            tail = jnp.zeros((cfg.conv_kernel - 1, cfg.mamba_conv_dim),
                             x.dtype)
            xbc, _ = mamba2.causal_conv1d(xbc, lp["conv_w"], lp["conv_b"],
                                          tail, S)
            xs, b, c = split_xbc(xbc, cfg)
            s0 = jnp.zeros((cfg.ssm_state_size, cfg.mamba_d_inner), F32)
            y, _ = mamba2.ssd_scan(
                xs, jnp.where(valid[:, None], dt, 0.0),
                -jnp.exp(lp["A_log"].astype(F32)), b, c, lp["D"], s0,
                block=Q)
            x = mamba_out(lp, x, y, z, cfg)
        elif kind.mixer == "attention":
            lp = at_layer(params[kind.stack], i)
            q, k, v = attn_qkv(lp, x, cfg, kind, pos)
            x = residual(x, attn_dense(q, k, v, pos, cfg) @ lp["o_proj"],
                         cfg)
        if kind.experts:
            x, _ = moe_block(at_layer(params["moe"], i), x, cfg)
    return lm_logits(params, x, cfg)[:S]
