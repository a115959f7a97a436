"""Fused op pack.

TPU-native replacement for the reference's CUDA fused kernels
(paddle/phi/kernels/fusion/): Pallas kernels where they beat XLA fusion,
jnp compositions (which XLA fuses) elsewhere. Each op is a pure jax function
usable under jit/vjp; Pallas variants carry custom_vjp.

Routing: flash_attention / rms_norm / paged_attention_decode run the
Pallas kernel on a TPU backend and the jnp composition elsewhere. The
composition is chosen by the backend, a flag or a shape predicate —
never by catching a kernel error: on a TPU a kernel that fails to
import, trace or compile raises.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# -- rms_norm ---------------------------------------------------------------
def rms_norm_ref(x, weight, epsilon=1e-6):
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(ms + epsilon)
            ).astype(x.dtype) * weight


def rms_norm(x, weight, epsilon=1e-6, mode=None):
    """``mode`` (fused-train contract: None reads FLAGS_fused_train,
    "pallas"/"ref" pin) selects the Pallas BACKWARD variant on TPU; a
    "pallas" pin also forces the Pallas kernel off-TPU (interpret
    mode — how tests and the audit catalog trace it on CPU)."""
    from .pallas._util import fused_train_mode, pallas_route
    if pallas_route() or fused_train_mode(mode) == "pallas":
        from .pallas.norms import rms_norm_pallas
        return rms_norm_pallas(x, weight, epsilon, mode)
    return rms_norm_ref(x, weight, epsilon)


# -- layer_norm -------------------------------------------------------------
def layer_norm_ref(x, weight, bias, epsilon=1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = ((xf - mean) * jax.lax.rsqrt(var + epsilon)).astype(x.dtype)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


def layer_norm(x, weight, bias, epsilon=1e-5):
    return layer_norm_ref(x, weight, bias, epsilon)


# -- rope -------------------------------------------------------------------
def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None,
                                    use_neox_rotary_style=True):
    """reference: python/paddle/incubate/nn/functional/
    fused_rotary_position_embedding.py. Layout [b, s, h, d]."""
    from .rope import apply_rope
    outs = []
    for t in (q, k, v):
        if t is None:
            outs.append(None)
        else:
            outs.append(apply_rope(t, sin, cos, position_ids,
                                   use_neox_rotary_style))
    return tuple(outs)


# -- swiglu -----------------------------------------------------------------
def swiglu(x, y=None):
    if y is None:
        a, b = jnp.split(x, 2, axis=-1)
    else:
        a, b = x, y
    return jax.nn.silu(a) * b


from . import flash_attention  # noqa: E402,F401
from . import rope  # noqa: E402,F401
