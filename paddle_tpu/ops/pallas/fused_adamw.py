"""Fused AdamW Pallas kernel.

TPU-native analog of the reference's fused_adam/adamw CUDA kernel
(paddle/phi/kernels/fusion/gpu/fused_adam_kernel.cu; python API
python/paddle/incubate/nn/functional — fused adamw): one VMEM pass updates
param + both moments (+ bf16 shadow) with no intermediate HBM traffic.
Operates on the flattened concatenation of all params (multi-tensor apply).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import (audited_pallas_call, interpret_mode as _interpret,
                    no_x64)
from .registry import KERNELS


#: elements per grid step. The kernel's fp32 interior (p, g, m, v and
#: the updated values, all block-sized) lives on the scoped-VMEM stack
#: beside the eight double-buffered block windows: compiled for v5e, a
#: 131072-element block is refused ("RESOURCE_EXHAUSTED ... memory space
#: vmem while allocating on stack"), 65536 compiles with bf16 moments,
#: and 32768 compiles at every dtype mix with margin.
BLOCK = 32768


def _adamw_kernel(p_ref, g_ref, m_ref, v_ref, lr_ref, bc_ref,
                  *outs, b1, b2, eps, wd, shadow):
    p_out, m_out, v_out = outs[0], outs[1], outs[2]
    p = p_ref[:].astype(jnp.float32)
    # bc_ref = [1/(1-b1^t), 1/(1-b2^t), grad_scale]: the bias corrections
    # are computed OUTSIDE the kernel (in-kernel b**t emitted math.powf,
    # which Mosaic fails to legalize) and the grad-clip scale rides along
    # so clipping fuses into the same HBM pass
    g = g_ref[:].astype(jnp.float32) * bc_ref[2]
    # moments may be stored reduced-precision (bf16 optimizer-state
    # policy); the update math always runs fp32
    m = m_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    lr = lr_ref[0]
    m_n = b1 * m + (1 - b1) * g
    v_n = b2 * v + (1 - b2) * g * g
    mhat = m_n * bc_ref[0]
    vhat = v_n * bc_ref[1]
    p_n = p * (1.0 - lr * wd) - lr * mhat / (jnp.sqrt(vhat) + eps)
    p_out[:] = p_n.astype(p_out.dtype)
    m_out[:] = m_n.astype(m_out.dtype)
    v_out[:] = v_n.astype(v_out.dtype)
    if shadow:
        outs[3][:] = p_n.astype(outs[3].dtype)


@no_x64
def fused_adamw(param, grad, moment1, moment2, lr, step,
                beta1=0.9, beta2=0.999, epsilon=1e-8, weight_decay=0.01,
                grad_scale=None, shadow_dtype=None):
    """All tensors 1-D (flatten+concat upstream); lr/step scalars.

    ``grad_scale`` (scalar, e.g. the grad-clip factor) is applied to the
    gradient inside the kernel. ``shadow_dtype`` adds a fourth output: the
    updated parameter cast to that dtype in the same pass (AMP master-
    weight training writes the bf16 model shadow for free).
    """
    n = param.shape[0]
    block = min(BLOCK, n)
    # pad to a block multiple rather than shrinking the block: the
    # largest-divisor fallback degrades to block=1 (a grid of n
    # sequential invocations) for awkward/prime n from direct callers
    pad = (-n) % block
    if pad:
        param = jnp.concatenate(
            [param, jnp.zeros((pad,), param.dtype)])
        grad = jnp.concatenate([grad, jnp.zeros((pad,), grad.dtype)])
        moment1 = jnp.concatenate(
            [moment1, jnp.zeros((pad,), moment1.dtype)])
        moment2 = jnp.concatenate(
            [moment2, jnp.zeros((pad,), moment2.dtype)])
        n += pad
    lr_arr = jnp.asarray([lr], jnp.float32)
    t = jnp.asarray(step, jnp.float32)
    scale = jnp.asarray(1.0 if grad_scale is None else grad_scale,
                        jnp.float32)
    bc_arr = jnp.stack([1.0 / (1.0 - beta1 ** t),
                        1.0 / (1.0 - beta2 ** t),
                        scale]).astype(jnp.float32)
    shadow = shadow_dtype is not None
    out_specs = [pl.BlockSpec((block,), lambda i: (i,)) for _ in range(3)]
    out_shape = [
        jax.ShapeDtypeStruct((n,), param.dtype),
        jax.ShapeDtypeStruct((n,), moment1.dtype),
        jax.ShapeDtypeStruct((n,), moment2.dtype),
    ]
    if shadow:
        out_specs.append(pl.BlockSpec((block,), lambda i: (i,)))
        out_shape.append(jax.ShapeDtypeStruct((n,), shadow_dtype))
    out = audited_pallas_call(
        functools.partial(_adamw_kernel, b1=beta1, b2=beta2, eps=epsilon,
                          wd=weight_decay, shadow=shadow),
        name="fused_adamw",
        grid=(pl.cdiv(n, block),),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={0: 0, 2: 1, 3: 2},
        interpret=_interpret(),
    )(param, grad, moment1, moment2, lr_arr, bc_arr)
    if pad:
        out = [o[:n - pad] for o in out]
    return out


@no_x64
def adamw_update_ref(param, grad, moment1, moment2, lr, step,
                     beta1=0.9, beta2=0.999, epsilon=1e-8,
                     weight_decay=0.01, grad_scale=None,
                     shadow_dtype=None):
    """The eager jnp composition of :func:`fused_adamw` — the
    priority-0 ``unfused`` registry fallback. Op order mirrors the
    kernel exactly (same bias-correction staging, fp32 interior, same
    literal types under ``no_x64``), so dispatch falling back here —
    interpret mode, off-TPU — keeps the update math the kernel's."""
    f32 = jnp.float32
    t = jnp.asarray(step, f32)
    scale = jnp.asarray(1.0 if grad_scale is None else grad_scale, f32)
    bc0 = (1.0 / (1.0 - beta1 ** t)).astype(f32)
    bc1 = (1.0 / (1.0 - beta2 ** t)).astype(f32)
    lr32 = jnp.asarray(lr, f32)
    p = param.astype(f32)
    g = grad.astype(f32) * scale
    m = moment1.astype(f32)
    v = moment2.astype(f32)
    m_n = beta1 * m + (1 - beta1) * g
    v_n = beta2 * v + (1 - beta2) * g * g
    mhat = m_n * bc0
    vhat = v_n * bc1
    p_n = p * (1.0 - lr32 * weight_decay) \
        - lr32 * mhat / (jnp.sqrt(vhat) + epsilon)
    out = [p_n.astype(param.dtype), m_n.astype(moment1.dtype),
           v_n.astype(moment2.dtype)]
    if shadow_dtype is not None:
        out.append(p_n.astype(shadow_dtype))
    return out


def adamw_meta(n, dtype, moment_dtype, shadow) -> dict:
    """Static dispatch metadata for one fused-AdamW call site."""
    dtype = jnp.dtype(dtype)
    return {"n": int(n), "dtype": str(dtype),
            "moment_dtype": str(jnp.dtype(moment_dtype)),
            "shadow": bool(shadow), "interpret": bool(_interpret())}


def _supports_adamw(meta):
    if meta["interpret"]:
        return False, "interpret mode (off-TPU): composition is faster"
    return True, "flat multi-tensor: any length blocks"


KERNELS.register("fused_adamw", "pallas_fused", fused_adamw,
                 priority=10, supports=_supports_adamw,
                 tags=("train", "optimizer", "pallas"))
KERNELS.register("fused_adamw", "unfused", adamw_update_ref, priority=0,
                 tags=("train", "optimizer"))
# all dispatch inputs beyond the traced shapes/dtypes are covered by the
# trainer's program-cache key (_fused_train_key: force pins + VMEM
# budget + interpret) — the DISPATCH_KEY_GAP registry lint checks the
# supports() reads against this declaration
KERNELS.declare_cache_key(
    "fused_adamw", ("n", "dtype", "moment_dtype", "shadow", "interpret"))


def adamw_update(param, grad, moment1, moment2, lr, step, **kw):
    """Fused-AdamW update, registry-dispatched: the Pallas multi-tensor
    kernel where supported (real TPU), the bit-matching eager jnp
    composition elsewhere (interpret mode); ``KERNELS.force`` pins a
    variant for tests/audits. Dispatch happens at TRACE time, so jit
    callers key their program caches on the registry's forced state +
    interpret (the trainer's ``_fused_train_key``)."""
    _, fn = KERNELS.dispatch(
        "fused_adamw",
        adamw_meta(param.shape[0], param.dtype, moment1.dtype,
                   kw.get("shadow_dtype") is not None))
    return fn(param, grad, moment1, moment2, lr, step, **kw)
