"""Canonical kernel catalog for the geometry-audit gate.

``tools/kernel_audit.py`` and the tier-1 ``pytest -m kernel_audit``
test need one shared, deterministic set of "the Pallas launches this
framework ships": every registered kernel op (and the kernels inside
their custom_vjp backwards) traced at TWO shape classes — ``tiny``
(the CPU test shapes) and the ``flagship`` serving/training shapes the
bench configs actually run (bench_serving_engine's engine dims,
bench_llama's rung dims). Audits only TRACE (``jax.eval_shape`` under
:class:`~paddle_tpu.ops.pallas._util.capture_kernel_launches`), so the
flagship shapes cost abstract evaluation, not interpret-mode compute.

Each case declares the launch names it must capture: a case that stops
reaching one of its kernels produces a ``COVERAGE_GAP`` finding rather
than silently shrinking the gate (the no-silent-caps rule). The union
of those declarations, :data:`ALL_KERNEL_NAMES`, is the coverage
contract the tier-1 test pins against the ``pl.pallas_call`` sites in
``ops/pallas/``.

The deliberate REGRESSION specimen (the verbatim PRE-FIX non-divisor
``block_f`` fused-MLP launch whose floor-divided grid drops the
trailing intermediate columns — the review-caught bug the divisor
guard now rejects) is opt-in via :func:`build_demo_kernel_regression`
and never part of the default catalog.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

from .auditor import AuditReport
from .kernel_rules import check_launch, dispatch_key_rule
from .rules import Finding

__all__ = ["KernelCase", "kernel_cases", "capture_case", "audit_kernels",
           "audit_kernel_registry", "build_demo_kernel_regression",
           "ALL_KERNEL_NAMES", "KERNEL_CASE_NAMES", "FLOP_FORMULAS",
           "modeled_flops", "flop_formula_findings"]


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One audited (kernel family, shape class): ``build()`` returns a
    trace-only ``(fn, abstract_args)`` pair; ``kernels`` declares the
    launch names tracing it must capture."""
    op: str
    case: str
    kernels: Tuple[str, ...]
    build: Callable[[], Tuple[Callable, tuple]]

    @property
    def name(self) -> str:
        return f"{self.op}@{self.case}"


def _sds(shape, dtype):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


# -- per-family builders ------------------------------------------------
# flagship dims mirror the bench configs: bench_serving_engine's engine
# (D=1024, H=KV=16, hd=64, F=4096, BS=16, capacity 8, bf16) and
# bench_llama's rung (D=2048, F=5504, V=32000, batch 2 x seq 2048, bf16)


def _rms_case(rows, d, dtype):
    def build():
        import jax
        import jax.numpy as jnp
        from ..ops.pallas.norms import rms_norm_pallas

        def fn(x, w):
            return jax.value_and_grad(
                lambda a, b: rms_norm_pallas(a, b, 1e-6, "pallas")
                .astype(jnp.float32).sum(), argnums=(0, 1))(x, w)
        return fn, (_sds((rows, d), dtype), _sds((d,), dtype))
    return build


def _res_rms_case(rows, d, dtype):
    def build():
        import jax
        import jax.numpy as jnp
        from ..ops.pallas.norms import residual_rms_norm_pallas

        def fn(delta, x, w):
            def loss(dd, xx, ww):
                y, h = residual_rms_norm_pallas(dd, xx, ww, 1e-6,
                                                mode="pallas")
                return (y.astype(jnp.float32).sum()
                        + h.astype(jnp.float32).sum())
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(
                delta, x, w)
        s = _sds((rows, d), dtype)
        return fn, (s, s, _sds((d,), dtype))
    return build


def _layer_norm_case(rows, d, dtype):
    def build():
        from ..ops.pallas.norms import layer_norm_pallas

        def fn(x, w, b):
            return layer_norm_pallas(x, w, b, 1e-5)
        return fn, (_sds((rows, d), dtype), _sds((d,), dtype),
                    _sds((d,), dtype))
    return build


def _adamw_case(n, dtype, mdtype, shadow_dtype):
    def build():
        from ..ops.pallas.fused_adamw import fused_adamw

        def fn(p, g, m, v):
            return fused_adamw(p, g, m, v, 1e-3, 2.0,
                               shadow_dtype=shadow_dtype)
        return fn, (_sds((n,), dtype), _sds((n,), dtype),
                    _sds((n,), mdtype), _sds((n,), mdtype))
    return build


def _paged_case(B, H, KV, hd, BS, N, MB, dtype, pp=None, L=None,
                scale=None):
    """``L``: the pools are the stacked ``[L, N, BS, KV, hd]`` and the
    layer a traced operand, as the decode programs' layer loops call
    the launch."""
    def build():
        from ..ops.pallas.paged_attention import (
            paged_attention_decode_pallas)

        def fn(q, kp, vp, bt, ln, *layer):
            return paged_attention_decode_pallas(
                q, kp, vp, bt, ln, scale=scale, pages_per_step=pp,
                layer=layer[0] if layer else None)
        pool = _sds(((L,) if L else ()) + (N, BS, KV, hd), dtype)
        return fn, (_sds((B, H, hd), dtype), pool, pool,
                    _sds((B, MB), "int32"), _sds((B,), "int32"),
                    *([_sds((), "int32")] if L else []))
    return build


def _ssm_case(S, H, hp, N, Lm, dtype):
    """The Mamba-2 state pool's three launches: one decode token of
    every slot, and a prefill chunk's read and write of one slot."""
    def build():
        from ..ops.pallas import mamba2 as pm

        def fn(decay, xdt, b, c, pool):
            y, pool = pm.ssm_update_pallas(decay, xdt, b, c, pool, 1)
            state = pm.slot_state_read(pool, 1, 0)
            return y, pm.slot_state_write(pool, 1, 0, state)
        R = H * hp
        return fn, (_sds((S, R), "float32"), _sds((S, R), "float32"),
                    _sds((S, N), "float32"), _sds((S, N), "float32"),
                    _sds((Lm, S, N, R), dtype))
    return build


def _flash_case(B, S, H, KVH, hd, dtype, causal=True, bias=False,
                seg=False):
    def build():
        import jax
        import jax.numpy as jnp
        from ..ops.pallas.flash_attention import flash_attention_pallas

        def fn(q, k, v, *extra):
            kw = {}
            i = 0
            if bias:
                kw["bias"] = extra[i]
                kw["bias_grad"] = True
                i += 1
            if seg:
                kw["segment_ids"] = extra[i]
                i += 1

            def loss(qq, kk, vv):
                return flash_attention_pallas(
                    qq, kk, vv, causal=causal, **kw) \
                    .astype(jnp.float32).sum()
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        args = [_sds((B, S, H, hd), dtype),
                _sds((B, S, KVH, hd), dtype),
                _sds((B, S, KVH, hd), dtype)]
        if bias:
            args.append(_sds((1, 1, S, S), "float32"))
        if seg:
            args.append(_sds((B, S), "int32"))
        return fn, tuple(args)
    return build


def _wq_sds(shape, wq, pack_axis=0):
    """Abstract quantized weight leaf (quantization/ptq.py format):
    int8 keeps the dense shape, packed int4 halves ``pack_axis``; the
    per-output-channel f32 scale always spans the LAST axis."""
    if wq == "int4":
        qshape = list(shape)
        qshape[pack_axis] //= 2
        return {"qw4": _sds(tuple(qshape), "int8"),
                "scale": _sds((shape[-1],), "float32")}
    return {"qw8": _sds(shape, "int8"),
            "scale": _sds((shape[-1],), "float32")}


def _prefill_attn_case(P, D, H, KV, hd, BS, N, MB, dtype, quant=False,
                       pos0=0, bq=None, pp=None, wq=None):
    def build():
        import jax.numpy as jnp
        from ..ops.pallas.fused_prefill_block import (
            fused_prefill_attn_pallas)

        pool_dt = "int8" if quant else dtype

        def fn(x, nw, wq_, wk_, wv_, wo_, sin, cos, kp, vp, tab, *sc):
            kv_scales = (sc[0], sc[1]) if quant else None
            return fused_prefill_attn_pallas(
                x, nw, wq_, wk_, wv_, wo_, sin, cos, kp, vp, tab,
                jnp.int32(pos0), jnp.int32(P), kv_scales=kv_scales,
                block_q=bq, pages_per_step=pp)

        def w(shape):
            return _wq_sds(shape, wq) if wq else _sds(shape, dtype)
        args = [_sds((P, D), dtype), _sds((D,), dtype),
                w((D, H * hd)), w((D, KV * hd)),
                w((D, KV * hd)), w((H * hd, D)),
                _sds((P, hd // 2), "float32"),
                _sds((P, hd // 2), "float32"),
                _sds((N, BS, KV, hd), pool_dt),
                _sds((N, BS, KV, hd), pool_dt),
                _sds((MB,), "int32")]
        if quant:
            args += [_sds((KV,), "float32"), _sds((KV,), "float32")]
        return fn, tuple(args)
    return build


def _mlp_block_case(B, D, F, dtype, wq=None):
    def build():
        from ..ops.pallas.fused_decode_block import fused_mlp_block_pallas

        def fn(x, nw, wg, wu, wd):
            return fused_mlp_block_pallas(x, nw, wg, wu, wd)

        def w(shape, pack_axis=0):
            return _wq_sds(shape, wq, pack_axis) if wq \
                else _sds(shape, dtype)
        return fn, (_sds((B, D), dtype), _sds((D,), dtype),
                    w((D, F)), w((D, F)),
                    # down_proj packs its OUTPUT axis (the F tiles
                    # never split it — the ptq.WQ_KEYS contract)
                    w((F, D), pack_axis=1))
    return build


def _linear_ce_case(T, D, V, dtype):
    def build():
        import jax
        from ..ops.pallas.fused_train import linear_ce_pallas

        def fn(hidden, head, labels):
            return jax.value_and_grad(
                lambda h, w: linear_ce_pallas(h, w, labels),
                argnums=(0, 1))(hidden, head)
        return fn, (_sds((T, D), dtype), _sds((D, V), dtype),
                    _sds((T,), "int32"))
    return build


def _swiglu_case(R, F, dtype):
    def build():
        import jax
        import jax.numpy as jnp
        from ..ops.pallas.fused_train import swiglu_pallas

        def fn(g, u):
            return jax.value_and_grad(
                lambda gg, uu: swiglu_pallas(gg, uu)
                .astype(jnp.float32).sum(), argnums=(0, 1))(g, u)
        return fn, (_sds((R, F), dtype), _sds((R, F), dtype))
    return build


_CE_KERNELS = ("linear_ce_fwd", "linear_ce_bwd_dx", "linear_ce_bwd_dh")
_FLASH_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
                  "flash_attention_bwd_dkv")
_SSM_KERNELS = ("ssm_update", "ssm_state_read", "ssm_state_write")


def kernel_cases() -> List[KernelCase]:
    """The default gate set: every Pallas kernel family at its tiny +
    flagship shape classes (building is import-cheap; tracing happens
    in :func:`capture_case`)."""
    C = KernelCase
    return [
        C("rms_norm", "tiny", ("rms_norm_fwd", "rms_norm_bwd"),
          _rms_case(24, 128, "float32")),
        C("rms_norm", "flagship_train", ("rms_norm_fwd", "rms_norm_bwd"),
          _rms_case(4096, 2048, "bfloat16")),
        C("rms_norm_residual", "tiny",
          ("residual_rms_norm_fwd", "rms_norm_bwd"),
          _res_rms_case(24, 128, "float32")),
        C("rms_norm_residual", "flagship_train",
          ("residual_rms_norm_fwd", "rms_norm_bwd"),
          _res_rms_case(4096, 2048, "bfloat16")),
        C("layer_norm", "tiny", ("layer_norm_fwd",),
          _layer_norm_case(24, 128, "float32")),
        C("layer_norm", "flagship_train", ("layer_norm_fwd",),
          _layer_norm_case(4096, 1024, "float32")),
        C("fused_adamw", "tiny", ("fused_adamw",),
          _adamw_case(1024, "float32", "float32", None)),
        C("fused_adamw", "flagship_train", ("fused_adamw",),
          _adamw_case(4 << 20, "float32", "bfloat16", "bfloat16")),
        # the most VMEM a (ROWS, LANES) block takes (fp32 moments and a
        # shadow) and a flat count that leaves a ragged last block, as
        # the trainer's BLOCK-padded state does
        C("fused_adamw", "f32_moments_ragged", ("fused_adamw",),
          _adamw_case((4 << 20) + (32 << 10), "float32", "float32",
                      "bfloat16")),
        C("paged_attention", "tiny", ("paged_attention_decode",),
          _paged_case(2, 4, 2, 16, 8, 8, 4, "float32")),
        C("paged_attention", "flagship_serving",
          ("paged_attention_decode",),
          _paged_case(8, 16, 16, 64, 16, 128, 24, "bfloat16")),
        C("paged_attention", "flagship_serving_pp4",
          ("paged_attention_decode",),
          _paged_case(8, 16, 16, 64, 16, 128, 24, "bfloat16", pp=4)),
        C("paged_attention", "stacked_pool_layer_operand",
          ("paged_attention_decode",),
          _paged_case(32, 32, 8, 128, 16, 3072, 160, "bfloat16", L=16)),
        C("ssm_state", "tiny", _SSM_KERNELS,
          _ssm_case(3, 4, 32, 16, 2, "float32")),
        C("ssm_state", "flagship_serving", _SSM_KERNELS,
          _ssm_case(64, 128, 64, 128, 9, "float32")),
        C("flash_attention", "tiny", _FLASH_KERNELS,
          _flash_case(1, 128, 4, 2, 64, "float32")),
        C("flash_attention", "tiny_bias_seg", _FLASH_KERNELS,
          _flash_case(1, 128, 4, 2, 64, "float32", bias=True, seg=True)),
        C("flash_attention", "flagship_train", _FLASH_KERNELS,
          _flash_case(4, 2048, 16, 8, 128, "bfloat16")),
        C("decode_mlp_block", "tiny", ("decode_mlp_block",),
          _mlp_block_case(2, 32, 64, "float32")),
        C("decode_mlp_block", "flagship_serving", ("decode_mlp_block",),
          _mlp_block_case(8, 1024, 4096, "bfloat16")),
        C("decode_mlp_block", "tiny_int4_weights", ("decode_mlp_block",),
          _mlp_block_case(2, 32, 64, "float32", wq="int4")),
        C("decode_mlp_block", "flagship_serving_int8_weights",
          ("decode_mlp_block",),
          _mlp_block_case(8, 1024, 4096, "bfloat16", wq="int8")),
        C("decode_mlp_block", "flagship_serving_int4_weights",
          ("decode_mlp_block",),
          _mlp_block_case(8, 1024, 4096, "bfloat16", wq="int4")),
        # fused prefill: tiny (warm mid-page start) + the
        # bench_serving_engine shape class at a warm-suffix bucket
        # (P=64; the 10MiB dispatch budget binds the largest buckets
        # at this width — the audit's 16MiB window model still fits)
        C("prefill_attn_block", "tiny", ("prefill_attn_block",),
          _prefill_attn_case(16, 32, 4, 2, 16, 8, 9, 6, "float32",
                             pos0=10)),
        C("prefill_attn_block", "flagship_serving",
          ("prefill_attn_block",),
          _prefill_attn_case(64, 1024, 16, 16, 64, 16, 129, 24,
                             "bfloat16", pos0=128)),
        C("prefill_attn_block", "flagship_serving_int8",
          ("prefill_attn_block",),
          _prefill_attn_case(64, 1024, 16, 16, 64, 16, 129, 24,
                             "bfloat16", quant=True, pos0=128)),
        C("prefill_attn_block", "flagship_serving_int8_weights",
          ("prefill_attn_block",),
          _prefill_attn_case(64, 1024, 16, 16, 64, 16, 129, 24,
                             "bfloat16", pos0=128, wq="int8")),
        C("prefill_attn_block", "flagship_serving_int4_weights",
          ("prefill_attn_block",),
          _prefill_attn_case(64, 1024, 16, 16, 64, 16, 129, 24,
                             "bfloat16", pos0=128, wq="int4")),
        # the prefill MLP op dispatches the decode MLP megakernel at
        # chunk-row counts — audited at the bucket widths
        C("prefill_mlp_block", "flagship_serving", ("decode_mlp_block",),
          _mlp_block_case(64, 1024, 4096, "bfloat16")),
        C("fused_linear_ce", "tiny", _CE_KERNELS,
          _linear_ce_case(24, 64, 96, "float32")),
        C("fused_linear_ce", "flagship_train", _CE_KERNELS,
          _linear_ce_case(4096, 2048, 32000, "bfloat16")),
        C("fused_swiglu", "tiny", ("swiglu_fwd", "swiglu_bwd"),
          _swiglu_case(16, 64, "float32")),
        C("fused_swiglu", "flagship_train", ("swiglu_fwd", "swiglu_bwd"),
          _swiglu_case(4096, 5504, "bfloat16")),
    ]


KERNEL_CASE_NAMES: Tuple[str, ...] = tuple(
    c.name for c in kernel_cases())

#: every audited launch name — the coverage contract the tier-1 test
#: pins against the audited_pallas_call sites under ops/pallas/
ALL_KERNEL_NAMES = frozenset(
    k for c in kernel_cases() for k in c.kernels)


# -- modeled FLOPs (the roofline numerator) -----------------------------
# One formula per audited launch name, evaluated on the CAPTURED
# KernelLaunchSpec, so the model prices the geometry that actually
# launched (quantized weight tiles keep their output dim, so the dense
# matmul FLOPs extract unchanged from the packed shapes). Conventions:
# a matmul [m,k]x[k,n] is 2mkn; softmax/norm elementwise work is
# charged at small documented constants; causal halving in flash
# attention and live-page raggedness in paged attention are
# DELIBERATELY ignored — the model is the max-traffic full-table
# bound, matching the bytes model's full-sample page walk.


def _prod(shape) -> int:
    out = 1
    for s in shape:
        out *= int(s)
    return out


def _pool_dims(spec):
    """(page_size, head_dim) from the first KV-pool operand of a paged
    kernel: (N, BS, KV, hd), or the stacked (L, N, BS, KV, hd) of the
    launches that address their layer themselves."""
    for op in spec.inputs:
        if len(op.shape) in (4, 5):
            return int(op.shape[-3]), int(op.shape[-1])
    raise ValueError(f"{spec.name}: no KV-pool operand")


def _flops_rms_fwd(spec):
    # square + mean-reduce + rsqrt-scale + weight mul ≈ 4 flops/elem
    return 4.0 * _prod(spec.inputs[0].shape)


def _flops_rms_bwd(spec):
    # recompute the norm (4) + dx chain rule (~5) + dw accumulate (1)
    return 10.0 * _prod(spec.inputs[0].shape)


def _flops_res_rms_fwd(spec):
    # the residual add (1) + the rms_norm_fwd epilogue (4)
    return 5.0 * _prod(spec.inputs[0].shape)


def _flops_layer_norm_fwd(spec):
    # mean + centered variance + rsqrt-scale + affine ≈ 6 flops/elem
    return 6.0 * _prod(spec.inputs[0].shape)


def _flops_adamw(spec):
    # moment updates (6) + bias correction + decoupled decay + step (6)
    return 12.0 * _prod(spec.inputs[0].shape)


def _flops_paged_decode(spec):
    B, H, hd = (int(s) for s in spec.inputs[0].shape)
    MB = int(spec.prefetch[0][0][1])
    BS, _ = _pool_dims(spec)
    # q·K (2) + p·V (2) per head over every page of the table: the
    # lengths of the bytes model's probe (the launch's ``fetched_bytes``
    # on the ``full`` sample), where all B * MB pages are live
    return 4.0 * B * H * hd * MB * BS


def _flops_decode_mlp_block(spec):
    B, D = (int(s) for s in spec.inputs[0].shape)
    F = int(spec.inputs[2].shape[-1])       # gate: the stacked (L, D, F)
    # norm + gate/up/down matmuls + silu·mul epilogue (~4/f-elem)
    return B * (4.0 * D + 6.0 * D * F + 4.0 * F)


def _flops_prefill_attn_block(spec):
    P, D = (int(s) for s in spec.inputs[0].shape)
    Hhd = int(spec.inputs[2].shape[1])
    KVhd = int(spec.inputs[3].shape[1])
    MB = int(spec.prefetch[0][0][0])
    BS, _ = _pool_dims(spec)
    # norm + projections + pool-direct flash over the FULL paged
    # history (causal masking inside the window is ignored)
    return (4.0 * P * D + 2.0 * P * D * Hhd + 4.0 * P * D * KVhd
            + 2.0 * P * Hhd * D + 4.0 * P * Hhd * MB * BS)


def _flash_dims(spec):
    bh, sq, d = (int(s) for s in spec.inputs[0].shape)
    sk = int(spec.inputs[1].shape[1])
    return bh, sq, sk, d


def _flops_flash_fwd(spec):
    bh, sq, sk, d = _flash_dims(spec)
    # qk^T (2) + p·v (2); causal halving deliberately ignored
    return 4.0 * bh * sq * sk * d


def _flops_flash_bwd_dq(spec):
    bh, sq, sk, d = _flash_dims(spec)
    # recompute s (2) + dp = do·v^T (2) + dq = ds·k (2)
    return 6.0 * bh * sq * sk * d


def _flops_flash_bwd_dkv(spec):
    bh, sq, sk, d = _flash_dims(spec)
    # recompute s (2) + dp (2) + dv = p^T·do (2) + dk = ds^T·q (2)
    return 8.0 * bh * sq * sk * d


def _ce_dims(spec):
    T, D = (int(s) for s in spec.inputs[0].shape)
    V = int(spec.inputs[1].shape[1])
    return T, D, V


def _flops_ce_fwd(spec):
    T, D, V = _ce_dims(spec)
    # logits matmul (2TDV) + online-lse exp/accumulate (~3/logit)
    return 2.0 * T * D * V + 3.0 * T * V


def _flops_ce_bwd(spec):
    T, D, V = _ce_dims(spec)
    # recompute logits (2TDV) + coef matmul for dx / dhead (2TDV)
    return 4.0 * T * D * V


def _flops_swiglu_fwd(spec):
    # silu (≈4: sigmoid + mul) + gate·up mul
    return 5.0 * _prod(spec.inputs[0].shape)


def _flops_swiglu_bwd(spec):
    # recompute silu/sigmoid chain + both input grads
    return 10.0 * _prod(spec.inputs[0].shape)


#: launch name -> FLOPs formula over the captured spec. The coverage
#: contract: every ALL_KERNEL_NAMES member must have an entry —
#: :func:`flop_formula_findings` turns a gap into a gate finding.
def _flops_ssm_update(spec):
    # decay multiply, outer product (multiply, add), readout
    # (multiply, add) and the store's convert: 6 a state element of
    # the one layer the launch touches (its grid covers exactly it)
    pool = spec.inputs[-1].shape
    return 6.0 * _prod(pool[1:])


def _flops_none(spec):
    return 0.0          # a copy: bytes only


FLOP_FORMULAS: Dict[str, Callable] = {
    "ssm_update": _flops_ssm_update,
    "ssm_state_read": _flops_none,
    "ssm_state_write": _flops_none,
    "rms_norm_fwd": _flops_rms_fwd,
    "rms_norm_bwd": _flops_rms_bwd,
    "residual_rms_norm_fwd": _flops_res_rms_fwd,
    "layer_norm_fwd": _flops_layer_norm_fwd,
    "fused_adamw": _flops_adamw,
    "paged_attention_decode": _flops_paged_decode,
    "decode_mlp_block": _flops_decode_mlp_block,
    "prefill_attn_block": _flops_prefill_attn_block,
    "flash_attention_fwd": _flops_flash_fwd,
    "flash_attention_bwd_dq": _flops_flash_bwd_dq,
    "flash_attention_bwd_dkv": _flops_flash_bwd_dkv,
    "linear_ce_fwd": _flops_ce_fwd,
    "linear_ce_bwd_dx": _flops_ce_bwd,
    "linear_ce_bwd_dh": _flops_ce_bwd,
    "swiglu_fwd": _flops_swiglu_fwd,
    "swiglu_bwd": _flops_swiglu_bwd,
}


def modeled_flops(spec) -> Optional[float]:
    """Modeled FLOPs for one captured launch, or None when the kernel
    has no registered formula (a FLOP_FORMULA_GAP finding, not a
    silent zero)."""
    fn = FLOP_FORMULAS.get(spec.name)
    if fn is None:
        return None
    return float(fn(spec))


def flop_formula_findings() -> List[Finding]:
    """COVERAGE_GAP-style findings for audited kernels with no flop
    formula — the no-silent-caps rule applied to the roofline
    numerator: a kernel the catalog audits but the cost model cannot
    price would silently fall out of every roofline report."""
    out = []
    for name in sorted(ALL_KERNEL_NAMES - set(FLOP_FORMULAS)):
        out.append(Finding(
            rule="kernel_auditor", code="FLOP_FORMULA_GAP",
            severity="error", program="flop_formulas", site=name,
            message=(f"audited kernel {name!r} has no registered flop "
                     "formula in kernel_catalog.FLOP_FORMULAS — its "
                     "roofline row would silently report no model; "
                     "register a formula next to its cases"),
            detail={"kernel": name,
                    "registered": sorted(FLOP_FORMULAS)}))
    return out


def capture_case(case: KernelCase):
    """Trace one case under launch capture. Returns (specs, error)."""
    import jax
    from ..ops.pallas._util import capture_kernel_launches

    fn, args = case.build()
    try:
        with capture_kernel_launches() as specs:
            jax.eval_shape(fn, *args)
        return specs, None
    except Exception as e:  # noqa: BLE001 — a broken trace is a finding
        return [], e


def audit_case(case: KernelCase) -> AuditReport:
    """Capture + run every geometry rule for one case. A trace failure
    or a declared-but-uncaptured kernel is itself a finding — the gate
    must not shrink silently."""
    report = AuditReport(program=case.name,
                         rules_run=["kernel_geometry"])
    specs, err = capture_case(case)
    if err is not None:
        report.findings.append(Finding(
            rule="kernel_auditor", code="TRACE_ERROR", severity="error",
            program=case.name, site=type(err).__name__,
            message=(f"kernel case failed to trace: "
                     f"{type(err).__name__}: {err}"),
            detail={"exception": type(err).__name__}))
        report.meta["trace_error"] = str(err)
        return report
    captured = {s.name for s in specs}
    for missing in sorted(set(case.kernels) - captured):
        report.findings.append(Finding(
            rule="kernel_auditor", code="COVERAGE_GAP", severity="error",
            program=case.name, site=missing,
            message=(f"case declares kernel {missing!r} but tracing "
                     f"captured only {sorted(captured)} — a launch "
                     "stopped routing through audited_pallas_call (or "
                     "the case no longer reaches it)"),
            detail={"declared": sorted(case.kernels),
                    "captured": sorted(captured)}))
    for spec in specs:
        report.findings.extend(check_launch(spec, program=case.name))
    report.meta["kernels"] = sorted(captured)
    report.meta["launches"] = len(specs)
    return report


# -- registry lint ------------------------------------------------------


def _lint_metas() -> Dict[str, dict]:
    """Representative flagship meta per registered op, built through
    the SAME meta builders the call sites use (so the lint instruments
    the real key set, not a hand-copied one)."""
    import jax.numpy as jnp
    from ..ops.pallas.fused_adamw import adamw_meta
    from ..ops.pallas.fused_decode_block import decode_meta_dims
    from ..ops.pallas.fused_prefill_block import prefill_meta_dims
    from ..ops.pallas.fused_train import ce_meta, swiglu_meta
    from ..ops.pallas.norms import rms_bwd_meta

    from ..ops.moe_experts import experts_meta
    from ..ops.paged_attention import decode_attention_meta
    import jax
    stack = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    prefill = prefill_meta_dims(64, 1024, 16, 16, 64, 4096, 16, 24,
                                jnp.bfloat16, jnp.bfloat16, False)
    return {
        "paged_attention_decode": decode_attention_meta(jnp.bfloat16),
        # two-matrix relu² experts at widths XLA tiles 128 x 128
        "moe_experts": experts_meta(stack(7, 64, 2688, 1920),
                                    stack(7, 64, 1856, 2688), "relu2"),
        "decode_mlp_block": decode_meta_dims(8, 1024, 4096, jnp.bfloat16),
        "prefill_attn_block": prefill,
        "prefill_mlp_block": prefill,
        "fused_linear_ce": ce_meta(4096, 2048, 32000, jnp.bfloat16),
        "fused_swiglu": swiglu_meta(4096, 5504, jnp.bfloat16),
        "rms_norm_bwd": rms_bwd_meta(4096, 2048, jnp.bfloat16),
        "rms_norm_residual": rms_bwd_meta(4096, 2048, jnp.bfloat16),
        "fused_adamw": adamw_meta(4 << 20, jnp.float32, jnp.bfloat16,
                                  True),
    }


def audit_kernel_registry() -> AuditReport:
    """The DISPATCH_KEY_GAP lint over every registered kernel op. An op
    the lint has no sample meta for is itself a finding: adding a
    kernel op means teaching the auditor its shape class."""
    from ..ops.pallas.registry import KERNELS

    report = AuditReport(program="kernel_registry",
                         rules_run=["dispatch_key"])
    metas = _lint_metas()
    for op in KERNELS.ops():
        meta = metas.get(op)
        if meta is None:
            report.findings.append(Finding(
                rule="kernel_geometry", code="DISPATCH_KEY_GAP",
                severity="error", program="kernel_registry",
                site=f"{op}:no-sample",
                message=(f"registered kernel op {op!r} has no lint "
                         "sample meta in the kernel catalog — its "
                         "supports() reads cannot be checked against "
                         "the declared cache-key coverage"),
                detail={"op": op}))
            continue
        report.findings.extend(dispatch_key_rule(
            KERNELS, op, meta, program="kernel_registry"))
    report.meta["ops"] = KERNELS.ops()
    return report


def audit_kernels(names: Optional[List[str]] = None,
                  registry_lint: bool = True) -> List[AuditReport]:
    """Audit the catalog (all cases, or the ``op`` / ``op@case``
    subset) + the registry lint. Mirrors ``catalog.build_catalog``'s
    unknown-name contract: a typo'd selection raises instead of gating
    nothing."""
    cases = kernel_cases()
    if names is not None:
        wanted = set(names)
        known = {c.name for c in cases} | {c.op for c in cases} \
            | {"kernel_registry"}
        unknown = wanted - known
        if unknown:
            raise ValueError(
                f"unknown kernel case(s): {sorted(unknown)} — known: "
                f"{sorted(known)}")
        cases = [c for c in cases
                 if c.name in wanted or c.op in wanted]
        registry_lint = registry_lint and "kernel_registry" in wanted
    reports = [audit_case(c) for c in cases]
    if registry_lint:
        reports.append(audit_kernel_registry())
        # the roofline cost model's coverage half rides the same gate:
        # an audited kernel without a flop formula is a finding, so
        # FLOP_FORMULAS can never silently lag ALL_KERNEL_NAMES
        flops_report = AuditReport(program="flop_formulas",
                                   rules_run=["flop_formulas"])
        flops_report.findings.extend(flop_formula_findings())
        flops_report.meta["registered"] = sorted(FLOP_FORMULAS)
        reports.append(flops_report)
    return reports


# -- demo regression ----------------------------------------------------


def build_demo_kernel_regression() -> AuditReport:
    """The PRE-FIX non-divisor ``block_f`` fused-MLP launch, verbatim:
    ``grid=(F // bf,)`` with ``F % bf != 0`` floor-drops the ragged
    tail tile, so the last ``F % bf`` intermediate columns never feed
    the down-projection accumulator — greedy decode silently computes
    with a truncated MLP. The shipped kernel now REJECTS non-divisor
    tiles; this specimen re-creates the exact pre-fix launch so the
    CLI's ``--demo-regression`` proves the gate still catches the
    class (and CI self-checks exit code 2)."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..ops.pallas._util import (audited_pallas_call,
                                    capture_kernel_launches)
    from ..ops.pallas.fused_decode_block import _mlp_block_kernel

    B, D, F, bf = 2, 32, 96, 64   # F % bf = 32 columns silently dropped

    def prefix_mlp(x, nw, wg, wu, wd, eps=1e-6):
        const = lambda j: (0, 0)                          # noqa: E731
        return audited_pallas_call(
            # (None: the layer operand today's launch prefetches)
            functools.partial(_mlp_block_kernel, None, eps=eps,
                              residual=True),
            name="demo_prefix_mlp_block",
            accum_outputs=(0,),
            grid=(F // bf,),           # the bug: floor, not cdiv+guard
            in_specs=[pl.BlockSpec((B, D), const),
                      pl.BlockSpec((1, D), const),
                      pl.BlockSpec((D, bf), lambda j: (0, j)),
                      pl.BlockSpec((D, bf), lambda j: (0, j)),
                      pl.BlockSpec((bf, D), lambda j: (j, 0))],
            out_specs=pl.BlockSpec((B, D), const),
            out_shape=jax.ShapeDtypeStruct((B, D), x.dtype),
            scratch_shapes=[pltpu.VMEM((B, D), x.dtype),
                            pltpu.VMEM((B, D), jnp.float32)],
            interpret=True,
        )(x, nw.reshape(1, D), wg, wu, wd)

    report = AuditReport(program="demo_prefix_mlp_block@tiny",
                         rules_run=["kernel_geometry"])
    with capture_kernel_launches() as specs:
        jax.eval_shape(
            prefix_mlp, _sds((B, D), "float32"), _sds((D,), "float32"),
            _sds((D, F), "float32"), _sds((D, F), "float32"),
            _sds((F, D), "float32"))
    for spec in specs:
        report.findings.extend(
            check_launch(spec, program=report.program))
    return report
