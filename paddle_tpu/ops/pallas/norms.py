"""Fused normalization Pallas kernels.

TPU-native replacement for the reference fused norm CUDA kernels
(paddle/phi/kernels/fusion/gpu/fused_rms_norm* via
python/paddle/incubate/nn/functional/fused_rms_norm.py). One VMEM pass:
load row block, compute the fp32 moment, scale, write — saving the extra
HBM round-trip XLA sometimes emits for the two-pass formulation.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import (audited_pallas_call, dispatch_fused_variant,
                    interpret_mode as _interpret, no_x64)
from .registry import KERNELS


def _rms_fwd_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    o_ref[:] = (x * inv).astype(o_ref.dtype) * w_ref[0, :]


def _rms_rows(x):
    n = int(np.prod(x.shape[:-1]))
    return x.reshape(n, x.shape[-1])


def _row_block(n, d, itemsize):
    """Row-block that keeps the kernel inside the 16MB scoped-VMEM
    budget. in+out blocks are double-buffered, so a (512, 4096) bf16
    block (2 x 2 x 4MB = 16.03MB with the weight) OOMs VMEM on v5e —
    budget 2MB per block buffer and the fp32 temporaries fit
    comfortably. Callers pad the row count up to a block multiple
    (``_pad_rows``) rather than shrinking the block: the old
    largest-divisor fallback degraded to block=1 for prime n."""
    # whole sublane tiles (16 rows covers bf16's packing and f32's 8):
    # Mosaic refuses a row block that is neither a tile multiple nor
    # the whole array (d=1536 bf16 gave 682 rows)
    cap = max(16, (2 * 1024 * 1024) // max(1, d * itemsize) // 16 * 16)
    return min(cap, n)


def _pad_rows(x2, block):
    """Pad (n, d) rows to a block multiple; returns (padded, orig_n)."""
    n = x2.shape[0]
    pad = (-n) % block
    if pad:
        x2 = jnp.concatenate(
            [x2, jnp.zeros((pad, x2.shape[1]), x2.dtype)])
    return x2, n


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rms_norm_pallas(x, weight, epsilon=1e-6, mode=None):
    """``mode`` (static) picks the BACKWARD variant — None reads
    FLAGS_fused_train, "pallas"/"ref" pin (the fused-train mode
    contract); the forward is always this Pallas kernel."""
    return _rms_fwd(x, weight, epsilon)[0]


@no_x64
def _rms_fwd(x, weight, epsilon):
    orig_shape = x.shape
    d = x.shape[-1]
    x2 = _rms_rows(x)
    block = _row_block(x2.shape[0], d, x.dtype.itemsize)
    x2, n = _pad_rows(x2, block)
    out = audited_pallas_call(
        functools.partial(_rms_fwd_kernel, eps=epsilon),
        name="rms_norm_fwd",
        grid=(pl.cdiv(x2.shape[0], block),),
        # weight rides as a (1, d) block: Mosaic requires >=2-D blocks with
        # lane-aligned trailing dims; 1-D specs fail to legalize
        in_specs=[pl.BlockSpec((block, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x2.shape[0], d), x.dtype),
        interpret=_interpret(),
    )(x2, weight.reshape(1, d))
    return out[:n].reshape(orig_shape), (x, weight)


def _rms_bwd_ref(epsilon, res, g):
    """The EXACT pre-fusion backward composition (XLA-fused jnp) —
    the registry fallback, bit-identical to the pre-PR path."""
    x, weight = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = weight.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + epsilon)
    xhat = xf * inv
    dw = jnp.sum(gf * xhat,
                 axis=tuple(range(x.ndim - 1))).astype(weight.dtype)
    gw = gf * wf
    dx = inv * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    return dx.astype(x.dtype), dw


def _rms_bwd_kernel(x_ref, w_ref, g_ref, dx_ref, dw_ref, dw_scr, *,
                    eps):
    """One VMEM pass per row block: recompute the fp32 moment, emit the
    row's dx and fold its dw contribution into (1, d) f32 scratch —
    written once at the last block (the dw reduction crosses blocks,
    so the grid must stay sequential over rows). Padded rows are
    all-zero x AND g → xhat = 0, contributions 0. Literals explicitly
    f32: the body can be retraced at lowering time outside the no_x64
    window."""
    i = pl.program_id(0)
    f32 = jnp.float32
    xf = x_ref[:].astype(f32)
    gf = g_ref[:].astype(f32)
    wf = w_ref[:].astype(f32)                             # (1, d)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + f32(eps))
    xhat = xf * inv
    gw = gf * wf
    dx = inv * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    dx_ref[:] = dx.astype(dx_ref.dtype)

    @pl.when(i == 0)
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr)

    dw_scr[:] = dw_scr[:] + jnp.sum(gf * xhat, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _fin():
        dw_ref[:] = dw_scr[:].astype(dw_ref.dtype)


@no_x64
def rms_norm_bwd_pallas(x, weight, g, epsilon=1e-6):
    """Pallas RMSNorm backward: (dx [like x], dw [d]) in one kernel —
    completes the fp32-moment Pallas forward so the backward stops
    re-streaming x/g through XLA's multi-op chain."""
    d = x.shape[-1]
    x2 = _rms_rows(x)
    g2 = _rms_rows(g)
    block = _row_block(x2.shape[0], d, max(x.dtype.itemsize, 4))
    x2, n = _pad_rows(x2, block)
    g2, _ = _pad_rows(g2, block)
    dx, dw = audited_pallas_call(
        functools.partial(_rms_bwd_kernel, eps=epsilon),
        name="rms_norm_bwd",
        # dw revisits block (0, 0) every grid step (cross-row reduction
        # folded in scratch, written once at the last step)
        accum_outputs=(1,),
        grid=(pl.cdiv(x2.shape[0], block),),
        in_specs=[pl.BlockSpec((block, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0)),
                  pl.BlockSpec((block, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block, d), lambda i: (i, 0)),
                   pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((x2.shape[0], d), x.dtype),
                   jax.ShapeDtypeStruct((1, d), weight.dtype)],
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        interpret=_interpret(),
    )(x2, weight.reshape(1, d), g2)
    return dx[:n].reshape(x.shape), dw.reshape(d)


def _rms_bwd_pallas_variant(epsilon, res, g):
    x, weight = res
    return rms_norm_bwd_pallas(x, weight, g, epsilon)


def rms_bwd_meta(rows, d, dtype) -> dict:
    """Static dispatch metadata for the RMSNorm-backward site."""
    dtype = jnp.dtype(dtype)
    return {"rows": int(rows), "d": int(d), "dtype": str(dtype),
            "itemsize": int(dtype.itemsize),
            "interpret": bool(_interpret())}


def _supports_rms_bwd(meta):
    if meta["interpret"]:
        return False, "interpret mode (off-TPU): composition is faster"
    return True, "row-blocked: any shape tiles"


KERNELS.register("rms_norm_bwd", "pallas_fused", _rms_bwd_pallas_variant,
                 priority=10, supports=_supports_rms_bwd,
                 tags=("train", "pallas"))
KERNELS.register("rms_norm_bwd", "unfused", _rms_bwd_ref, priority=0,
                 tags=("train",))
KERNELS.declare_cache_key(
    "rms_norm_bwd", ("rows", "d", "dtype", "interpret"),
    covers={"itemsize": "dtype"})


def _rms_bwd(epsilon, mode, res, g):
    """Backward of the Pallas RMSNorm forward, resolved at trace time
    through the fused-train mode contract: the call site's ``mode``
    (e.g. a model's ``cfg.fused_train`` pin) wins; None reads
    FLAGS_fused_train and registry-dispatches — the fused Pallas
    kernel where supported, the exact jnp composition elsewhere
    (interpret mode / flag off)."""
    x, _ = res
    n = int(np.prod(x.shape[:-1]))
    fn = dispatch_fused_variant(
        "rms_norm_bwd", rms_bwd_meta(n, x.shape[-1], x.dtype), mode)
    return fn(epsilon, res, g)


rms_norm_pallas.defvjp(lambda x, w, eps, mode: _rms_fwd(x, w, eps),
                       _rms_bwd)


# -- fused residual + RMSNorm epilogue --------------------------------------
def _res_rms_fwd_kernel(d_ref, x_ref, w_ref, y_ref, h_ref, *, eps):
    """y = x + delta (model dtype, the composition's op order), then
    the fp32-moment norm of y — one VMEM pass instead of the add
    round-tripping the residual stream through HBM before the norm
    reads it back."""
    s = x_ref[:] + d_ref[:]
    y_ref[:] = s
    sf = s.astype(jnp.float32)
    ms = jnp.mean(jnp.square(sf), axis=-1, keepdims=True)
    h_ref[:] = (sf * jax.lax.rsqrt(ms + jnp.float32(eps))
                ).astype(h_ref.dtype) * w_ref[0, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _res_rms_vjp(delta, x, weight, epsilon, mode):
    return _res_rms_fwd(delta, x, weight, epsilon)[0]


@no_x64
def _res_rms_fwd_call(delta, x, weight, epsilon):
    orig_shape = x.shape
    d = x.shape[-1]
    d2 = _rms_rows(delta)
    x2 = _rms_rows(x)
    # 4 block-sized windows (delta, x in; y, h out), all double-buffered,
    # plus the f32 interior — _row_block budgets 2MiB per buffer for a
    # 1-in/1-out kernel, so scale the itemsize by the window count to
    # stay inside the same envelope (D=2048 bf16 would otherwise sit at
    # exactly the 16MiB v5e OOM point _row_block's docstring documents)
    block = _row_block(x2.shape[0], d, x.dtype.itemsize * 4)
    d2, n = _pad_rows(d2, block)
    x2, _ = _pad_rows(x2, block)
    y, h = audited_pallas_call(
        functools.partial(_res_rms_fwd_kernel, eps=epsilon),
        name="residual_rms_norm_fwd",
        grid=(pl.cdiv(x2.shape[0], block),),
        in_specs=[pl.BlockSpec((block, d), lambda i: (i, 0)),
                  pl.BlockSpec((block, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((block, d), lambda i: (i, 0)),
                   pl.BlockSpec((block, d), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((x2.shape[0], d), x.dtype),
                   jax.ShapeDtypeStruct((x2.shape[0], d), x.dtype)],
        interpret=_interpret(),
    )(d2, x2, weight.reshape(1, d))
    return y[:n].reshape(orig_shape), h[:n].reshape(orig_shape)


def _res_rms_fwd(delta, x, weight, epsilon):
    y, h = _res_rms_fwd_call(delta, x, weight, epsilon)
    return (y, h), (y, weight)


def _res_rms_bwd(epsilon, mode, res, gs):
    """(gy, gh) → (d_delta, dx, dw): the norm backward runs on the
    SAVED sum y (the rms_norm_bwd kernel / composition, resolved
    through the SAME mode the epilogue was called with), and the
    residual cotangent gy folds in with one add — ds flows identically
    into both addends."""
    y, weight = res
    gy, gh = gs
    dn, dw = _rms_bwd(epsilon, mode, (y, weight), gh)
    ds = dn + gy
    return ds, ds, dw


_res_rms_vjp.defvjp(lambda d, x, w, eps, mode: _res_rms_fwd(d, x, w, eps),
                    _res_rms_bwd)


def residual_rms_norm_pallas(delta, x, weight, epsilon=1e-6, mode=None):
    """Fused residual-add + RMSNorm: returns (y, h) with
    y = x + delta (the new residual stream) and h = rms_norm(y) · w.
    ``mode`` (static) threads the fused-train pin into the norm
    backward."""
    return _res_rms_vjp(delta, x, weight, epsilon, mode)


def residual_rms_norm_ref(delta, x, weight, epsilon=1e-6, mode=None):
    """The EXACT pre-fusion composition: plain add, then ``ops.rms_norm``
    (Pallas forward on TPU, jnp off it) — dispatch falling back here is
    bit-identical to the pre-fusion block. ``mode`` reaches the norm's
    backward so a "ref" pin keeps the WHOLE path pre-fusion on TPU."""
    from .. import rms_norm as fused_rms_norm
    y = x + delta
    return y, fused_rms_norm(y, weight, epsilon, mode=mode)


def _supports_res_rms(meta):
    if meta["interpret"]:
        return False, "interpret mode (off-TPU): composition is faster"
    return True, "row-blocked: any shape tiles"


KERNELS.register("rms_norm_residual", "pallas_fused",
                 residual_rms_norm_pallas, priority=10,
                 supports=_supports_res_rms, tags=("train", "pallas"))
KERNELS.register("rms_norm_residual", "unfused", residual_rms_norm_ref,
                 priority=0, tags=("train",))
KERNELS.declare_cache_key(
    "rms_norm_residual", ("rows", "d", "dtype", "interpret"),
    covers={"itemsize": "dtype"})


def residual_rms_norm(delta, x, weight, epsilon=1e-6, mode=None):
    """Residual-add + RMSNorm epilogue, registry-dispatched (mode
    contract as in :func:`.fused_train.fused_linear_ce`). ``mode`` is
    passed through to the selected variant: the norm BACKWARD inside
    either variant follows the same pin."""
    n = int(np.prod(x.shape[:-1]))
    fn = dispatch_fused_variant(
        "rms_norm_residual", rms_bwd_meta(n, x.shape[-1], x.dtype), mode)
    return fn(delta, x, weight, epsilon, mode=mode)


# -- fused layer_norm -------------------------------------------------------
def _ln_fwd_kernel(x_ref, w_ref, b_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    xhat = (x - mean) * jax.lax.rsqrt(var + eps)
    o_ref[:] = xhat.astype(o_ref.dtype) * w_ref[0, :] + b_ref[0, :]


@no_x64
def layer_norm_pallas(x, weight, bias, epsilon=1e-5):
    orig_shape = x.shape
    d = x.shape[-1]
    x2 = _rms_rows(x)
    block = _row_block(x2.shape[0], d, x.dtype.itemsize)
    x2, n = _pad_rows(x2, block)
    out = audited_pallas_call(
        functools.partial(_ln_fwd_kernel, eps=epsilon),
        name="layer_norm_fwd",
        grid=(pl.cdiv(x2.shape[0], block),),
        in_specs=[pl.BlockSpec((block, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x2.shape[0], d), x.dtype),
        interpret=_interpret(),
    )(x2, weight.reshape(1, d), bias.reshape(1, d))
    return out[:n].reshape(orig_shape)
