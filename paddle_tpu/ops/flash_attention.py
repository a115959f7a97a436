"""Flash attention.

reference: paddle/phi/kernels/gpu/flash_attn_kernel.cu:517 (dynload of the
flash-attn CUDA library; varlen path at :137). TPU-native: a Pallas kernel
(ops/pallas/flash_attention.py) with the blockwise online-softmax algorithm,
native GQA, segment-id (varlen) masking and additive bias; this module
routes to it on TPU and to a fused-friendly jnp composition elsewhere.

Layout: [batch, seq, heads, head_dim] (paddle flash-attn convention).
K/V may carry fewer heads than Q (GQA) on both paths.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .pallas._util import pallas_route


def _ref_attention(q, k, v, causal=False, scale=None, bias=None,
                   segment_ids=None, kv_segment_ids=None,
                   dropout_rate=0.0, dropout_seed=None):
    d = q.shape[-1]
    h, kvh = q.shape[2], k.shape[2]
    if kvh != h:
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * s
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    ql, kl = logits.shape[-2], logits.shape[-1]
    mask = jnp.ones((ql, kl), bool)
    if causal:
        mask = jnp.tril(mask, k=kl - ql)
    mask = mask[None, None]
    if segment_ids is not None:
        kv_seg = kv_segment_ids if kv_segment_ids is not None \
            else segment_ids
        mask = mask & (segment_ids[:, None, :, None] ==
                       kv_seg[:, None, None, :])
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    if dropout_rate and dropout_rate > 0.0:
        # EXACT same position-keyed hash mask as the Pallas kernels (one
        # "block" spanning the full matrix), so ref and kernel agree
        # bit-for-mask under a shared seed
        from .pallas.flash_attention import _dropout_keep
        b = q.shape[0]
        seed = jnp.asarray(dropout_seed, jnp.uint32)
        bh = jnp.arange(b * h, dtype=jnp.int32)
        keep = jax.vmap(lambda i: _dropout_keep(
            seed, i, jnp.int32(0), jnp.int32(0), ql, kl,
            float(dropout_rate)))(bh).reshape(b, h, ql, kl)
        p = jnp.where(keep, p, 0.0) / (1.0 - dropout_rate)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    # rows with no valid key (segment padding) must yield 0, not uniform avg
    if segment_ids is not None:
        any_valid = jnp.any(mask, axis=-1)  # [b, h|1, q]
        out = jnp.where(jnp.swapaxes(any_valid, 1, 2)[..., None], out, 0.0)
    return out.astype(q.dtype)


from ..core.flags import GLOBAL_FLAGS

GLOBAL_FLAGS.define(
    "use_flash_attention", True,
    "route attention through the Pallas flash kernel on TPU "
    "(0 = jnp composition, for A/B perf diagnosis)")


def flash_attention(q, k, v, causal=False, scale=None, bias=None,
                    segment_ids=None, kv_segment_ids=None, bias_grad=False,
                    dropout_rate=0.0, dropout_seed=None):
    if bias is not None and not bias_grad:
        bias = jax.lax.stop_gradient(bias)
    if dropout_rate and dropout_rate > 0.0 and dropout_seed is None:
        # draw once here so the pallas path and any ref fallback of the
        # SAME call share one seed
        from ..core.random import next_key
        dropout_seed = jax.random.randint(
            next_key(), (), 0, jnp.iinfo(jnp.int32).max, dtype=jnp.int32)
    if GLOBAL_FLAGS.get("use_flash_attention") and pallas_route():
        from .pallas.flash_attention import (flash_attention_pallas,
                                             flash_supports)
        if flash_supports(q.shape[1], k.shape[1])[0]:
            return flash_attention_pallas(
                q, k, v, causal=causal, scale=scale, bias=bias,
                segment_ids=segment_ids, kv_segment_ids=kv_segment_ids,
                bias_grad=bias_grad, dropout_rate=dropout_rate,
                dropout_seed=dropout_seed)
    return _ref_attention(q, k, v, causal=causal, scale=scale, bias=bias,
                          segment_ids=segment_ids,
                          kv_segment_ids=kv_segment_ids,
                          dropout_rate=dropout_rate,
                          dropout_seed=dropout_seed)


def segment_ids_from_cu_seqlens(cu_seqlens, total: int):
    """[n+1] cumulative lengths -> [total] int32 segment ids; positions past
    cu_seqlens[-1] get id -1 (masked against every real segment)."""
    pos = jnp.arange(total, dtype=jnp.int32)
    seg = jnp.searchsorted(jnp.asarray(cu_seqlens, jnp.int32), pos,
                           side="right").astype(jnp.int32) - 1
    n = cu_seqlens.shape[0] - 1
    return jnp.where(seg >= n, -1, seg)
