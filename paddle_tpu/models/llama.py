"""LLaMA family — the flagship model (BASELINE config 3, the north star).

Two tiers:
1. **Functional core** (this file's ``init_params``/``forward``/
   ``build_forward``): pure pytree params + jax functions with GSPMD
   sharding rules — the performance path used by the Trainer, bench, and
   the multichip dryrun. RMSNorm/rope/flash-attention route through the
   ops/ pack (Pallas on TPU).
2. **Layer API** (``LlamaForCausalLM``): Paddle-style nn.Layer built on the
   fleet TP layers for eager/dygraph use.

Sharding rules (mesh axes [dp, fsdp, tp, sp] — SURVEY.md §7 step 4):
- embeddings/vocab: vocab dim on tp, hidden on fsdp
- attn qkv/o and mlp in/out projections: alternate (fsdp, tp)/(tp, fsdp) —
  Megatron layout, collectives ride ICI on tp
- activations: [batch→dp, seq→sp]
GQA (num_key_value_heads < num_attention_heads) supported.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.rope import build_rope_cache, apply_rope
from ._common import masked_cross_entropy as _masked_cross_entropy
from ..ops import rms_norm as fused_rms_norm
from ..ops.flash_attention import flash_attention
from ..ops.pallas.fused_train import (fused_linear_ce,
                                      fused_swiglu as _fused_swiglu_train)
from ..ops.pallas.norms import residual_rms_norm as _residual_rms_norm

__all__ = ["LlamaConfig", "init_params", "forward", "loss_fn",
           "build_forward", "param_shardings", "tp_param_specs",
           "LLAMA_7B", "LLAMA_TINY"]


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # fused training-path kernels (Liger-style): None reads
    # FLAGS_fused_train (default on); False/"ref" pins the unfused
    # composition (bit-identical to the pre-fusion path), "pallas"
    # forces the Pallas kernels (tests / audit tracing on CPU)
    fused_train: Any = None

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


LLAMA_7B = LlamaConfig()
LLAMA_TINY = LlamaConfig(vocab_size=512, hidden_size=128,
                         intermediate_size=256, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=2,
                         max_position_embeddings=256)


def init_params(cfg: LlamaConfig, key=None, dtype=None) -> Dict:
    """Initialise the parameter pytree (layers stacked on a leading axis for
    scan-friendly layout — one compiled layer body instead of L copies)."""
    dtype = dtype or cfg.dtype
    key = key if key is not None else jax.random.key(0)
    D, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    H = cfg.num_attention_heads
    KV = cfg.num_key_value_heads
    hd = cfg.head_dim
    L = cfg.num_hidden_layers
    k = jax.random.split(key, 10)
    std = 0.02

    def nrm(kk, shape, fan_in=None):
        return (jax.random.normal(kk, shape, dtype=jnp.float32) * std
                ).astype(dtype)

    params = {
        "embed_tokens": nrm(k[0], (V, D)),
        "layers": {
            "input_norm": jnp.ones((L, D), dtype=jnp.float32),
            "q_proj": nrm(k[1], (L, D, H * hd)),
            "k_proj": nrm(k[2], (L, D, KV * hd)),
            "v_proj": nrm(k[3], (L, D, KV * hd)),
            "o_proj": nrm(k[4], (L, H * hd, D)),
            "post_norm": jnp.ones((L, D), dtype=jnp.float32),
            "gate_proj": nrm(k[5], (L, D, F)),
            "up_proj": nrm(k[6], (L, D, F)),
            "down_proj": nrm(k[7], (L, F, D)),
        },
        "final_norm": jnp.ones((D,), dtype=jnp.float32),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = nrm(k[8], (D, V))
    return params


def param_shardings(mesh: Mesh, cfg: LlamaConfig) -> Dict:
    """PartitionSpecs per param (the sharding 'rules' — the analog of the
    reference's per-op spmd_rules applied to weights)."""
    have = set(mesh.axis_names)
    fsdp = "fsdp" if "fsdp" in have else ("sharding"
                                          if "sharding" in have else None)
    tp = "tp" if "tp" in have else ("mp" if "mp" in have else None)

    def s(*names):
        return P(*[n if n in have or n is None else None for n in names])

    specs = {
        "embed_tokens": s(tp, fsdp),
        "layers": {
            "input_norm": s(None, None),
            "q_proj": s(None, fsdp, tp),
            "k_proj": s(None, fsdp, tp),
            "v_proj": s(None, fsdp, tp),
            "o_proj": s(None, tp, fsdp),
            "post_norm": s(None, None),
            "gate_proj": s(None, fsdp, tp),
            "up_proj": s(None, fsdp, tp),
            "down_proj": s(None, tp, fsdp),
        },
        "final_norm": s(None),
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = s(fsdp, tp)
    return specs


def tp_param_specs(cfg: LlamaConfig, axis: str = "tp",
                   collective: str = "psum", params=None) -> Dict:
    """PartitionSpecs for SERVING tensor parallelism over a 1-D mesh:
    head-axis (Megatron) sharding of the per-layer projections, with
    everything the replicated residual stream touches kept replicated
    (embedding, norms, lm_head) so greedy sampling runs identically on
    every shard.

    ``collective="psum"`` row-shards o_proj/down_proj (their partial
    products all-reduce, one psum per sub-block — the bandwidth-optimal
    placement). ``collective="gather"`` keeps o_proj/down_proj
    REPLICATED and all-gathers the per-shard attention heads / MLP
    columns instead: every matmul then has exactly the single-device
    operands and shapes, which is what makes that mode's greedy output
    bit-identical (inference/tp.py documents the contract).

    ``params``: pass the actual tree when it may carry the serving
    engine's fused ``qkv_proj`` leaf (column-sharded like the three it
    replaces) or QUANTIZED
    weight leaves (``{"qw8"|"qw4": q, "scale": s}`` —
    quantization/ptq.py): the spec tree must mirror the tree's
    structure. The integer tile keeps the base weight's spec
    (column sharding survives packing — int4 packs the hidden axis,
    never the output columns of q/k/v/gate/up) and the
    per-output-channel scales shard with the output columns (or stay
    replicated for the row-sharded o/down projections)."""
    col = P(None, None, axis)                  # shard output columns
    row = P(None, axis, None) if collective == "psum" else P(None, None,
                                                             None)
    specs = {
        "embed_tokens": P(None, None),
        "layers": {
            "input_norm": P(None, None),
            "q_proj": col, "k_proj": col, "v_proj": col,
            "o_proj": row,
            "post_norm": P(None, None),
            "gate_proj": col, "up_proj": col,
            "down_proj": row,
        },
        "final_norm": P(None),
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, None)
    if params is not None:
        layers = params.get("layers", {})
        if "qkv_proj" in layers:
            # the serving engine's fused leaf (fused_decode_block
            # .fuse_qkv, made per shard): a shard's columns are
            # [q_loc | k_loc | v_loc] of its own heads. The spec is how
            # the engine's programs take the leaf it placed, never a
            # way to place a global [q | k | v] (the engine refuses one)
            for k in ("q_proj", "k_proj", "v_proj"):
                del specs["layers"][k]
            specs["layers"]["qkv_proj"] = col
        for k, w in layers.items():
            if isinstance(w, dict):
                base = specs["layers"][k]
                qk = "qw8" if "qw8" in w else "qw4"
                s_spec = P(None, axis) if base[-1] == axis \
                    else P(None, None)
                specs["layers"][k] = {qk: base, "scale": s_spec}
    return specs


def _decoder_layer(layer_params, x, sin, cos, cfg: LlamaConfig,
                   attn_mask=None):
    """One decoder block on [B, S, D]."""
    H, KV, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    h = fused_rms_norm(x, layer_params["input_norm"].astype(x.dtype),
                       cfg.rms_norm_eps, mode=cfg.fused_train)
    b, s, _ = h.shape
    q = (h @ layer_params["q_proj"]).reshape(b, s, H, hd)
    kk = (h @ layer_params["k_proj"]).reshape(b, s, KV, hd)
    v = (h @ layer_params["v_proj"]).reshape(b, s, KV, hd)
    q = apply_rope(q, sin, cos)
    kk = apply_rope(kk, sin, cos)
    # GQA handled natively by the kernel (KV heads indexed, not repeated)
    attn = flash_attention(q, kk, v, causal=True)
    attn = attn.reshape(b, s, H * hd)
    # fused training path (Liger-style): the residual add + post-norm
    # collapse into one kernel and SwiGLU's fwd/bwd each run as one
    # pass; the dispatched fallback is the EXACT pre-fusion
    # composition, so mode "ref" / off-TPU is bit-identical to the
    # pre-fusion block
    x, h = _residual_rms_norm(attn @ layer_params["o_proj"], x,
                              layer_params["post_norm"].astype(x.dtype),
                              cfg.rms_norm_eps, mode=cfg.fused_train)
    ff = _fused_swiglu_train(h @ layer_params["gate_proj"],
                             h @ layer_params["up_proj"],
                             mode=cfg.fused_train)
    x = x + ff @ layer_params["down_proj"]
    return x


def forward_hidden(params: Dict, tokens, cfg: LlamaConfig,
                   positions=None) -> jax.Array:
    """Final-norm hidden states [B, S, D]. Layer loop is a lax.scan over
    the stacked layer params (single compiled block; PP slicing reuses
    the same body). The fused loss applies the lm head in chunks instead
    of materializing [B, S, V] logits."""
    x = jnp.take(params["embed_tokens"], tokens, axis=0)
    sin, cos = build_rope_cache(tokens.shape[1], cfg.head_dim,
                                base=cfg.rope_theta)
    if positions is not None:
        sin = jnp.take(sin, positions, axis=0)
        cos = jnp.take(cos, positions, axis=0)

    body = partial(_decoder_layer, sin=sin, cos=cos, cfg=cfg)
    if cfg.remat:
        body = jax.checkpoint(body)

    def scan_fn(carry, layer_params):
        return body(layer_params, carry), None

    x, _ = jax.lax.scan(scan_fn, x, params["layers"])
    return fused_rms_norm(x, params["final_norm"].astype(x.dtype),
                          cfg.rms_norm_eps, mode=cfg.fused_train)


def forward(params: Dict, tokens, cfg: LlamaConfig,
            positions=None) -> jax.Array:
    """Logits for [B, S] int tokens (hidden states @ lm head)."""
    x = forward_hidden(params, tokens, cfg, positions)
    head = params.get("lm_head")
    if head is None:
        head = params["embed_tokens"].T
    return x @ head


def loss_fn(params: Dict, tokens, labels, cfg: LlamaConfig) -> jax.Array:
    """Next-token cross entropy in fp32 via the fused chunked
    lm-head+CE — full [B, S, V] logits are never materialized (the
    reference's fused c_softmax_with_cross_entropy has the same goal for
    vocab-sharded logits). Registry-dispatched: the Pallas custom_vjp
    kernel on TPU (neither logits nor their gradient touch HBM), the
    lax.scan composition elsewhere (``cfg.fused_train`` pins a
    variant)."""
    # observability.PROGRAM_SCOPES; the backward keeps them inside
    # ``transpose(jvp(...))``
    with jax.named_scope("forward"):
        hidden = forward_hidden(params, tokens, cfg)
    with jax.named_scope("loss"):
        head = params.get("lm_head")
        if head is None:
            head = params["embed_tokens"].T
        return fused_linear_ce(hidden, head, labels, mode=cfg.fused_train)


def build_forward(cfg: LlamaConfig, key=None):
    """(fn, params) pair for compile checks."""
    params = init_params(cfg, key)

    def fn(params, tokens):
        return forward(params, tokens, cfg)

    return fn, params


# ---------------------------------------------------------------------------
# Layer-API tier (Paddle-style), built on fleet TP layers when a hybrid
# topology is active, plain layers otherwise.
# ---------------------------------------------------------------------------
def _lazy_layer_api():
    from .. import nn
    from ..core.tensor import Tensor, dispatch
    from ..nn import functional as Fn

    class LlamaMLP(nn.Layer):
        def __init__(self, cfg: LlamaConfig):
            super().__init__()
            self.gate_proj = nn.Linear(cfg.hidden_size,
                                       cfg.intermediate_size,
                                       bias_attr=False)
            self.up_proj = nn.Linear(cfg.hidden_size, cfg.intermediate_size,
                                     bias_attr=False)
            self.down_proj = nn.Linear(cfg.intermediate_size,
                                       cfg.hidden_size, bias_attr=False)

        def forward(self, x):
            return self.down_proj(
                Fn.swiglu(self.gate_proj(x), self.up_proj(x)))

    class LlamaAttention(nn.Layer):
        def __init__(self, cfg: LlamaConfig):
            super().__init__()
            self.cfg = cfg
            D, H, KV, hd = (cfg.hidden_size, cfg.num_attention_heads,
                            cfg.num_key_value_heads, cfg.head_dim)
            self.q_proj = nn.Linear(D, H * hd, bias_attr=False)
            self.k_proj = nn.Linear(D, KV * hd, bias_attr=False)
            self.v_proj = nn.Linear(D, KV * hd, bias_attr=False)
            self.o_proj = nn.Linear(H * hd, D, bias_attr=False)

        def forward(self, x, position_ids=None):
            cfg = self.cfg
            b, s, _ = x.shape
            H, KV, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
            from ..tensor.manipulation import reshape
            q = reshape(self.q_proj(x), [b, s, H, hd])
            k = reshape(self.k_proj(x), [b, s, KV, hd])
            v = reshape(self.v_proj(x), [b, s, KV, hd])

            def rope_and_attend(qv, kv, vv):
                sin, cos = build_rope_cache(s, hd, base=cfg.rope_theta)
                qv = apply_rope(qv, sin, cos)
                kv = apply_rope(kv, sin, cos)
                return flash_attention(qv, kv, vv, causal=True)
            out = dispatch(rope_and_attend, (q, k, v), name="llama_attention")
            out = reshape(out, [b, s, H * hd])
            return self.o_proj(out)

    class LlamaDecoderLayer(nn.Layer):
        def __init__(self, cfg: LlamaConfig):
            super().__init__()
            self.input_layernorm = nn.RMSNorm(cfg.hidden_size,
                                              cfg.rms_norm_eps)
            self.self_attn = LlamaAttention(cfg)
            self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                       cfg.rms_norm_eps)
            self.mlp = LlamaMLP(cfg)

        def forward(self, x):
            x = x + self.self_attn(self.input_layernorm(x))
            x = x + self.mlp(self.post_attention_layernorm(x))
            return x

    class LlamaModel(nn.Layer):
        def __init__(self, cfg: LlamaConfig):
            super().__init__()
            self.cfg = cfg
            self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
            self.layers = nn.LayerList(
                [LlamaDecoderLayer(cfg)
                 for _ in range(cfg.num_hidden_layers)])
            self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

        def forward(self, input_ids):
            x = self.embed_tokens(input_ids)
            for layer in self.layers:
                x = layer(x)
            return self.norm(x)

    class LlamaForCausalLM(nn.Layer):
        def __init__(self, cfg: LlamaConfig):
            super().__init__()
            self.cfg = cfg
            self.llama = LlamaModel(cfg)
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     bias_attr=False)

        def forward(self, input_ids, labels=None):
            hidden = self.llama(input_ids)
            logits = self.lm_head(hidden)
            if labels is not None:
                from ..nn import functional as Fn
                loss = Fn.cross_entropy(
                    logits.reshape([-1, self.cfg.vocab_size]),
                    labels.reshape([-1]), ignore_index=-100)
                return loss, logits
            return logits

    return (LlamaMLP, LlamaAttention, LlamaDecoderLayer, LlamaModel,
            LlamaForCausalLM)


def __getattr__(name):
    if name in ("LlamaMLP", "LlamaAttention", "LlamaDecoderLayer",
                "LlamaModel", "LlamaForCausalLM"):
        classes = _lazy_layer_api()
        mapping = dict(zip(("LlamaMLP", "LlamaAttention",
                            "LlamaDecoderLayer", "LlamaModel",
                            "LlamaForCausalLM"), classes))
        import sys
        mod = sys.modules[__name__]
        for k, v in mapping.items():
            setattr(mod, k, v)
        return mapping[name]
    raise AttributeError(name)
