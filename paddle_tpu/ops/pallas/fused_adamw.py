"""Fused AdamW Pallas kernel.

TPU-native analog of the reference's fused_adam/adamw CUDA kernel
(paddle/phi/kernels/fusion/gpu/fused_adam_kernel.cu; python API
python/paddle/incubate/nn/functional — fused adamw): one VMEM pass updates
param + both moments (+ bf16 shadow) with no intermediate HBM traffic.
Operates on the flattened concatenation of all params (multi-tensor apply),
streamed in lane-dense 2-D blocks: the launch sees the flat vectors as
``(rows, LANES)`` arrays and takes ``(ROWS, LANES)`` of each a grid step.

Why 2-D. A 1-D fp32 block lies on ONE sublane of each (8, 128) tile, so
the 1-D kernel this replaces (``pl.BlockSpec((32768,), ...)``) used an
eighth of every register, load, store and of VMEM: 73.6 ms for the
training cell's 704.7 M parameters (22 B each: 15.5 GB, 18.9 ms at the
v5e's 819 GB/s), and the chip's compiler refused a 131072-element block
"while allocating on stack". In ``(rows, 128)`` blocks the same
arithmetic takes 23.4 ms alone (662 GB/s) and 22.1 inside the training
step; XLA's own fusion over the flat vectors reads 23.4 and 22.0: an
elementwise pass has no more on this chip, and the step with the launch
is the faster end to end (19,576 | 19,480 tokens/s).
Numbers: chip runs of PR 33, ``PERF.md`` section 6.
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


#: lanes of the 2-D view: 128 and no wider. A ``(rows, 128)`` array's
#: tiled layout is byte for byte the flat vector's (an (8, 128) fp32 tile
#: is 1024 consecutive elements; bf16 pairs rows the way the flat layout
#: pairs halves of a tile), so ``flat.reshape(rows, 128)`` compiles to a
#: ``bitcast`` and the trainer's flat state, its checkpoints and whatever
#: slices it by offset stay as they are. A wider minor dimension is
#: another tiling of the same numbers: taken from the flat state XLA
#: re-lays every operand out (at a quarter of the cell's size 17.7 ms
#: with 512 lanes and 29.6 with 1024, against 5.9), and handed arrays
#: made ``(rows, 512)`` the launch is no faster than at 128 (5.92 | 5.93).
LANES = 128

#: rows of a grid step's block, and of the strip the interior works on.
#: The eight block windows (four in, four out) are double-buffered in
#: scoped VMEM; the fp32 interior stays in registers because the kernel
#: walks its block in STRIP-row strips (a block-sized interior is what
#: the compiler refused in the 1-D kernel). ROWS is the largest that the
#: v5e's compiler takes at every dtype mix the registry admits: 4096
#: rows are refused at every mix ("vmem while allocating on stack"),
#: 2048 fill 15 of the 16 MB with fp32 moments and a shadow and compile.
#: On the chip 512, 1024 and 2048 rows read the same alone (23.36 |
#: 23.43 | 23.42 ms) and 256 are slower (24.1); inside the training step
#: 2048 rows take 22.1 ms and 1024 22.2. Strips of 32, 64 and 128 rows
#: read the same; 16 (one bf16 tile) is slower in small blocks (24.0 at
#: 512 rows). STRIP is a multiple of 16.
ROWS = 2048
STRIP = 32

#: elements the trainer pads its flat state to: 256 rows of the view, a
#: whole number of tiles at every dtype. Unchanged from the 1-D kernel,
#: so a flat state has the length it had and older checkpoints restore;
#: the launch's last block may be ragged (Pallas masks its writes).
BLOCK = 32768


def variant_record(picked, n):
    """``Trainer.metrics()["optimizer_variant"]`` of a step that traced
    :func:`adamw_update` over a flat state of ``n`` elements, from the
    registry's record of that trace (``KERNELS.record()``): the variant's
    name and the ``[R, W]`` blocks the Pallas launch streams the state
    in (XLA's fusion has none)."""
    name = picked.get("fused_adamw")
    rows = -(-n // (16 * LANES)) * 16
    return {"variant": name,
            "block": [min(ROWS, rows), LANES] if name == "pallas_fused"
            else None}


def _adamw_kernel(p_ref, g_ref, m_ref, v_ref, lr_ref, bc_ref,
                  *outs, b1, b2, eps, wd, strip):
    lr = lr_ref[0]
    # bc_ref = [1/(1-b1^t), 1/(1-b2^t), grad_scale]: the bias corrections
    # are computed OUTSIDE the kernel (in-kernel b**t emitted math.powf,
    # which Mosaic fails to legalize) and the grad-clip scale rides along
    # so clipping fuses into the same HBM pass
    bc0, bc1, scale = bc_ref[0], bc_ref[1], bc_ref[2]

    def update(i, carry):
        r = pl.ds(pl.multiple_of(i * strip, strip), strip)
        p = p_ref[r, :].astype(jnp.float32)
        g = g_ref[r, :].astype(jnp.float32) * scale
        # moments may be stored reduced-precision (bf16 optimizer-state
        # policy); the update math always runs fp32
        m = m_ref[r, :].astype(jnp.float32)
        v = v_ref[r, :].astype(jnp.float32)
        m_n = b1 * m + (1 - b1) * g
        v_n = b2 * v + (1 - b2) * g * g
        mhat = m_n * bc0
        vhat = v_n * bc1
        p_n = p * (1.0 - lr * wd) - lr * mhat / (jnp.sqrt(vhat) + eps)
        # outs = (param, moment1, moment2[, shadow])
        for o, val in zip(outs, (p_n, m_n, v_n, p_n)):
            o[r, :] = val.astype(o.dtype)
        return carry

    jax.lax.fori_loop(0, p_ref.shape[0] // strip, update, 0)


def _scalars(lr, step, beta1, beta2, grad_scale):
    lr_arr = jnp.asarray([lr], jnp.float32)
    t = jnp.asarray(step, jnp.float32)
    scale = jnp.asarray(1.0 if grad_scale is None else grad_scale,
                        jnp.float32)
    bc_arr = jnp.stack([1.0 / (1.0 - beta1 ** t),
                        1.0 / (1.0 - beta2 ** t),
                        scale]).astype(jnp.float32)
    return lr_arr, bc_arr


@no_x64
def fused_adamw_2d(param, grad, moment1, moment2, lr, step,
                   beta1=0.9, beta2=0.999, epsilon=1e-8, weight_decay=0.01,
                   grad_scale=None, shadow_dtype=None):
    """The launch: all tensors ``(rows, LANES)``, ``rows`` a multiple of
    16 (whole tiles at every dtype); any number of rows, the last block
    may be ragged. Arguments as :func:`fused_adamw`; master and both
    moments are updated in place (``input_output_aliases``)."""
    rows, lanes = param.shape
    if lanes != LANES or rows % 16:
        raise ValueError(
            f"fused_adamw_2d takes (rows, {LANES}) operands with rows a "
            f"multiple of 16, got {param.shape}")
    block_rows = min(ROWS, rows)
    strip = STRIP if block_rows % STRIP == 0 else 16
    lr_arr, bc_arr = _scalars(lr, step, beta1, beta2, grad_scale)
    out_dtypes = [param.dtype, moment1.dtype, moment2.dtype]
    if shadow_dtype is not None:
        out_dtypes.append(shadow_dtype)
    block = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return audited_pallas_call(
        functools.partial(_adamw_kernel, b1=beta1, b2=beta2, eps=epsilon,
                          wd=weight_decay, strip=strip),
        name="fused_adamw",
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[block, block, block, block, smem, smem],
        out_specs=[block] * len(out_dtypes),
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), d)
                   for d in out_dtypes],
        input_output_aliases={0: 0, 2: 1, 3: 2},
        interpret=_interpret(),
    )(param, grad, moment1, moment2, lr_arr, bc_arr)


def fused_adamw(param, grad, moment1, moment2, lr, step,
                beta1=0.9, beta2=0.999, epsilon=1e-8, weight_decay=0.01,
                grad_scale=None, shadow_dtype=None):
    """All tensors 1-D (flatten+concat upstream); lr/step scalars.

    ``grad_scale`` (scalar, e.g. the grad-clip factor) is applied to the
    gradient inside the kernel. ``shadow_dtype`` adds a fourth output: the
    updated parameter cast to that dtype in the same pass (AMP master-
    weight training writes the bf16 model shadow for free).

    The flat operands are viewed ``(n / LANES, LANES)`` for
    :func:`fused_adamw_2d`, a bitcast where ``n`` is a multiple of
    ``16 * LANES`` (the trainer pads its state to ``BLOCK``); an awkward
    ``n`` from a direct caller is padded up to that first, and the
    outputs keep ``n``.
    """
    n = param.shape[0]
    pad = (-n) % (16 * LANES)

    def view(x):
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
        return x.reshape(-1, LANES)

    out = fused_adamw_2d(
        view(param), view(grad), view(moment1), view(moment2), lr, step,
        beta1=beta1, beta2=beta2, epsilon=epsilon,
        weight_decay=weight_decay, grad_scale=grad_scale,
        shadow_dtype=shadow_dtype)
    return [o.reshape(-1)[:n] if pad else o.reshape(-1) for o in out]


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
    return True, (f"flat multi-tensor: any length, ({ROWS}, {LANES}) "
                  "blocks of its 2-D view")


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
    kernel where supported (real TPU), the eager jnp composition of
    the same arithmetic elsewhere (interpret mode; equal to the launch
    but for where a compiler contracts a multiply and an add into one
    rounding); ``KERNELS.force`` pins a
    variant for tests/audits. Dispatch happens at TRACE time, so jit
    callers key their program caches on the registry's forced state +
    interpret (the trainer's ``_fused_train_key``)."""
    _, fn = KERNELS.dispatch(
        "fused_adamw",
        adamw_meta(param.shape[0], param.dtype, moment1.dtype,
                   kw.get("shadow_dtype") is not None))
    return fn(param, grad, moment1, moment2, lr, step, **kw)
