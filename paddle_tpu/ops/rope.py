"""Rotary position embedding (reference CUDA kernel:
paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu; python API
python/paddle/incubate/nn/functional/fused_rotary_position_embedding.py).

Pure-jnp implementation: XLA fuses the elementwise rotation into adjacent
ops, so a Pallas kernel buys nothing here — the win on TPU is avoiding
materialised sin/cos broadcasts, which this formulation achieves.
"""
from __future__ import annotations

import math
from typing import Optional

import numpy as np
import jax.numpy as jnp


def build_rope_cache(seq_len: int, head_dim: int, base: float = 10000.0,
                     dtype=jnp.float32):
    """Return (sin, cos) of shape [seq_len, head_dim//2]."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, head_dim, 2,
                                          dtype=jnp.float32) / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.sin(freqs).astype(dtype), jnp.cos(freqs).astype(dtype)


def apply_rope(x, sin=None, cos=None, position_ids=None,
               use_neox_rotary_style=True, base=10000.0):
    """x: [batch, seq, heads, head_dim]."""
    b, s, h, d = x.shape
    if sin is None or cos is None:
        sin, cos = build_rope_cache(s, d, base=base)
    sin = jnp.asarray(sin)
    cos = jnp.asarray(cos)
    if sin.ndim == 4:  # [1, s, 1, d] paddle convention: take half
        sin = sin[0, :, 0, : d // 2] if sin.shape[-1] == d else sin[0, :, 0]
        cos = cos[0, :, 0, : d // 2] if cos.shape[-1] == d else cos[0, :, 0]
    if position_ids is not None:
        sin = jnp.take(sin, position_ids, axis=0)  # [b, s, d/2]
        cos = jnp.take(cos, position_ids, axis=0)
        sin = sin[:, :, None, :]
        cos = cos[:, :, None, :]
    else:
        sin = sin[None, :, None, :]
        cos = cos[None, :, None, :]
    xf = x.astype(jnp.float32)
    if use_neox_rotary_style:
        x1 = xf[..., : d // 2]
        x2 = xf[..., d // 2:]
        out = jnp.concatenate([x1 * cos - x2 * sin,
                               x2 * cos + x1 * sin], axis=-1)
    else:  # GPT-J interleaved
        x1 = xf[..., 0::2]
        x2 = xf[..., 1::2]
        r1 = x1 * cos - x2 * sin
        r2 = x2 * cos + x1 * sin
        out = jnp.stack([r1, r2], axis=-1).reshape(xf.shape)
    return out.astype(x.dtype)


# -- a frequency table per attention kind --------------------------------
def rope_frequencies(head_dim: int, rope_parameters: dict):
    """(inv_freq float32 [head_dim // 2], attention_factor) of one
    attention kind, from its section of a config's ``rope_parameters``.

    ``rope_type`` "default": ``inv_freq_k = theta^(-2k / head_dim)``,
    factor 1. "yarn" (the static form: applied at every position):

        extrap = theta^(-2k / d),   interp = extrap / factor
        c(r)   = d ln(L0 / (2 pi r)) / (2 ln theta), clipped to [0, d-1]
        low, high = floor(c(beta_fast)), ceil(c(beta_slow))
        ramp   = clip((k - low) / (high - low), 0, 1)
        inv_freq = interp * ramp + extrap * (1 - ramp)

    with ``L0`` = original_max_position_embeddings; cos and sin are
    multiplied by ``attention_factor`` (0.1 ln(factor) + 1 where the
    config leaves it out)."""
    theta = float(rope_parameters["rope_theta"])
    d = int(head_dim)
    k = np.arange(d // 2, dtype=np.float64)
    extrap = theta ** (-2.0 * k / d)
    kind = rope_parameters.get("rope_type", "default")
    if kind == "default":
        return extrap.astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: only 'default' and 'yarn' "
                         "have a frequency table here")
    factor = float(rope_parameters["factor"])
    low, high = yarn_correction_range(d, rope_parameters)
    if low == high:
        high += 0.001
    ramp = np.clip((k - low) / (high - low), 0.0, 1.0)
    inv_freq = (extrap / factor) * ramp + extrap * (1.0 - ramp)
    att = rope_parameters.get("attention_factor")
    if att is None:
        att = 0.1 * math.log(factor) + 1.0
    return inv_freq.astype(np.float32), float(att)


def yarn_correction_range(head_dim: int, rope_parameters: dict):
    """(low, high): the frequency pairs between which YaRN's ramp runs
    from extrapolation (k <= low) to interpolation (k >= high)."""
    theta = float(rope_parameters["rope_theta"])
    L0 = float(rope_parameters["original_max_position_embeddings"])

    def c(rotations):
        return (head_dim * math.log(L0 / (2 * math.pi * rotations))
                / (2 * math.log(theta)))

    low = math.floor(c(float(rope_parameters.get("beta_fast", 32))))
    high = math.ceil(c(float(rope_parameters.get("beta_slow", 1))))
    return max(low, 0), min(high, head_dim - 1)


def rotate_half(x, positions, inv_freq, factor=1.0):
    """Rotate-half RoPE of x [T, heads, head_dim] at ``positions`` [T]
    with one kind's table (:func:`rope_frequencies`), in float32."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)
    return apply_rope(x[None], jnp.sin(ang) * factor,
                      jnp.cos(ang) * factor)[0]
