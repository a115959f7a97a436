"""The decode block's MLP launch and the compositions around it.

One decode step of the serving engines runs, per transformer block
(``inference.generation._decode_step``):

- the attention stage as XLA: :func:`attn_qkv_ref` (RMSNorm, the q/k/v
  projections, RoPE), the pool write, then :func:`attn_out_ref`
  (``ops.paged_attention.paged_attention_decode`` over the carried
  pools, the output projection, the residual);
- ``decode_mlp_block``: post-attention RMSNorm + gated MLP (SwiGLU) +
  residual. Its ``pallas_fused`` variant (:func:`fused_mlp_block_pallas`)
  is ONE launch tiled over the intermediate dim, so the weight working
  set fits VMEM at any model width (tile size autotuned); it takes the
  stacked per-layer weights whole and addresses the layer itself, and
  streams int8 / packed-int4 weight tiles with the dequantization in
  the matmul epilogue. Its ``unfused`` variant (:func:`mlp_block_ref`)
  is the jnp composition: the reference the tests compare against and
  what runs off the TPU.

Both launches are chosen by the kernel registry (:mod:`.registry`) from
what it can observe: ``decode_mlp_block`` here by :func:`_supports_mlp`
(a compiled kernel, a tile that fits ``PADDLE_TPU_FUSED_VMEM_BUDGET``,
default 10 MiB of the 16 MiB scoped-VMEM window), the attention launch
in :mod:`paddle_tpu.ops.paged_attention`. Wherever dispatch selects the
compositions, the step is bit-identical to the building-block sequence
(tests/test_fused_decode_block.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import (audited_pallas_call, fused_vmem_budget,
                    interpret_mode as _interpret, no_x64)
from .registry import KERNELS

__all__ = [
    "fused_mlp_block_pallas", "mlp_block_ref", "attn_qkv_ref",
    "attn_out_ref", "decode_meta_dims", "launch_operands",
    "mlp_autotune_key", "weight_dtype_of", "QKV_LEAVES", "fuse_qkv",
    "qkv_project", "split_qkv", "local_heads",
]


# the ONE budget knob, shared with fused_train/generation/the kernel
# auditor — re-exported under the historic name for its import sites
_vmem_budget = fused_vmem_budget


# ---------------------------------------------------------------------------
# weight-quantization plumbing (r18): int8 / packed-int4 weight tiles
# stream through VMEM and dequantize in-register — the scale applies in
# the matmul EPILOGUE (per-OUTPUT-channel scales commute with the
# contraction: x @ (q * s) == (x @ q) * s), so the integer tile is what
# HBM moves and the interior stays f32
# ---------------------------------------------------------------------------
def _wq_parts(w):
    """Array-or-quantized-leaf normalization -> (weights, scale, bits,
    pack_axis). Quantized leaves are the PTQ harness's
    ``{"qw8"|"qw4": q, "scale": s}`` dicts (quantization/ptq.py); the
    output channel is always the last axis, and an int4 leaf packed
    along its LAST axis (down_proj packs its output dim) is recognized
    by the halved byte count vs the scale length."""
    if isinstance(w, dict):
        scale = w["scale"]
        if "qw4" in w:
            qw = w["qw4"]
            axis = 1 if qw.shape[-1] * 2 == scale.shape[-1] else 0
            return qw, scale, 4, axis
        return w["qw8"], scale, 8, 0
    return w, None, 0, 0


def weight_dtype_of(*ws):
    """The weight-dtype class string a set of weight leaves carries
    ("int8" | "int4" | None for plain arrays) — feeds the dispatch
    metas' ``weight_dtype`` key. Mixing modes across one block's
    weights is rejected: the kernels stream all tiles of a block under
    one bit width."""
    bits = {_wq_parts(w)[2] for w in ws}
    if len(bits) != 1:
        raise ValueError(
            "all block weights must share one weight-quant mode, got "
            f"bit widths {sorted(bits)}")
    b = bits.pop()
    return {8: "int8", 4: "int4"}.get(b)


def _kernel_weight(ref, bits, dt, axis=0):
    """Load one weight tile at the model dtype ``dt``: plain tiles pass
    through; int8 casts (|q| <= 127 is exact in bf16); packed int4
    unpacks through :func:`quantization.quanters.unpack_int4` — the
    SINGLE definition of the halves convention, shared with the
    dequantize-then-matmul fallback, so the two routes can never
    decode different weights. (It is jnp-traceable with
    explicitly-typed shift amounts, so it lowers inside the kernel
    body even when retraced outside the no_x64 window.)"""
    w = ref[:]
    if not bits:
        return w
    if bits == 4:
        from ...quantization.quanters import unpack_int4
        w = unpack_int4(w, axis=axis)
    return w.astype(dt)


def _silu_mul(g, u):
    """``jax.nn.silu(g) * u`` at the model dtype, with the sigmoid taken
    in f32: Mosaic's ``logistic`` lowering broadcasts an f32 ``1.0`` into
    the operand's vector type, which fails MLIR verification for bf16
    operands. The f32 sigmoid rounded back to ``g.dtype`` is the value
    XLA's bf16 ``logistic`` produces, so the composition's op order
    (sigmoid -> * g -> * u, each rounded to the model dtype) holds."""
    sg = jax.nn.sigmoid(g.astype(jnp.float32)).astype(g.dtype)
    return g * sg * u


def _weight_itemsize(meta) -> float:
    """Bytes per weight element under the meta's weight-dtype class —
    what the supports() VMEM math charges for weight tiles."""
    wd = meta.get("weight_dtype")
    if wd == "int8":
        return 1.0
    if wd == "int4":
        return 0.5
    return float(meta["itemsize"])



# ---------------------------------------------------------------------------
# MLP-stage megakernel
# ---------------------------------------------------------------------------
def _mlp_block_kernel(_layer_ref, x_ref, nw_ref, wg_ref, wu_ref, wd_ref,
                      *rest, eps, residual, wq_bits=0):
    if wq_bits:
        sg_ref, su_ref, sd_ref = rest[:3]
        rest = rest[3:]
    o_ref, h_scr, acc_scr = rest
    j = pl.program_id(0)
    dt = x_ref.dtype

    @pl.when(j == 0)
    def _pre():
        xf = x_ref[:].astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        # jnp.float32(eps): the body can be retraced at LOWERING time
        # outside the no_x64 window, where a bare python literal becomes
        # f64 and breaks the already-specialized f32 call signature
        h_scr[:] = (xf * jax.lax.rsqrt(ms + jnp.float32(eps))
                    ).astype(dt) * nw_ref[:]
        acc_scr[:] = jnp.zeros_like(acc_scr)

    h = h_scr[:]
    # gate/up pack along the CONTRACTION dim (rows, axis 0), down along
    # its OUTPUT dim (columns, axis 1) — the axis each F-tile fully
    # covers; quantized scales apply in the f32 epilogue
    g = jnp.dot(h, _kernel_weight(wg_ref, wq_bits, dt, axis=0),
                preferred_element_type=jnp.float32)
    u = jnp.dot(h, _kernel_weight(wu_ref, wq_bits, dt, axis=0),
                preferred_element_type=jnp.float32)
    if wq_bits:
        g = g * sg_ref[:]
        u = u * su_ref[:]
    ff = _silu_mul(g.astype(dt), u.astype(dt))    # swiglu, model dtype
    dn = jnp.dot(ff, _kernel_weight(wd_ref, wq_bits, dt, axis=1),
                 preferred_element_type=jnp.float32)
    if wq_bits:
        dn = dn * sd_ref[:]
    acc_scr[:] = acc_scr[:] + dn

    @pl.when(j == pl.num_programs(0) - 1)
    def _fin():
        # residual=False: the bare down-projection partial (the
        # tensor-parallel caller all-reduces, then adds the residual)
        o_ref[:] = (x_ref[:] + acc_scr[:].astype(dt)) if residual \
            else acc_scr[:].astype(dt)


# 128 last: it only ever becomes the default pick where nothing wider
# fits (D=4096 bf16), so narrower models keep their tile
_MLP_BLOCK_CANDIDATES = (512, 256, 1024, 2048, 128)


def mlp_autotune_key(B, D, F, dtype, budget=None,
                     weight_dtype=None) -> str:
    """Persistent autotune-cache key for the fused MLP kernel's
    intermediate-dim block size. The VMEM budget is part of the key:
    winners are stored as an INDEX into the budget-fitting candidate
    list, so a different ``PADDLE_TPU_FUSED_VMEM_BUDGET`` (which
    reshapes that list) must read a different cache entry — not decode
    a stale index against the wrong candidates. ``weight_dtype``
    ("int8"/"int4") appends the quantized-weight shape class the same
    way (it too reshapes the fitting list); None keeps the historic
    fp key."""
    budget = _vmem_budget() if budget is None else int(budget)
    base = (B, D, F, str(dtype), budget)
    if weight_dtype:
        base = base + (str(weight_dtype),)
    return f"fused_mlp_block|{base}"


def _mlp_candidates(F: int):
    """Intermediate-dim tile sizes: divisors of F only (a ragged last
    block would multiply garbage columns into the accumulator)."""
    cands = [c for c in _MLP_BLOCK_CANDIDATES if c <= F and F % c == 0]
    return cands or [F]


def _mlp_vmem_need(B: int, D: int, itemsize: int, bf: int,
                   w_itemsize: float = None) -> int:
    """Per-grid-step VMEM bytes at tile ``bf``: the 3 weight tiles and
    the x / out rows as the pipeline holds them — TWO buffers each (the
    F-tiles move every step; compiled for v5e, D=4096 bf=384 and D=6144
    bf=256, 18 MiB of tile buffers, are refused where a single-buffer
    count admitted them) — plus the h/acc scratch rows and the body's
    f32 rows (xf, the down-projection) and g/u/ff tiles.
    ``w_itemsize``: bytes per weight ELEMENT (1 for int8, 0.5 for
    packed int4 — which also adds the f32 scale rows); defaults to the
    activation itemsize (plain fp weights)."""
    if w_itemsize is None:
        w_itemsize = itemsize
    scales = (2 * bf + D) * 4 if w_itemsize != itemsize else 0
    return 2 * (int(3 * D * bf * w_itemsize) + scales) \
        + B * D * (4 * itemsize + itemsize + 4 + 2 * 4) + 3 * B * bf * 4


def _mlp_fitting_candidates(B: int, D: int, F: int, itemsize: int,
                            budget: int = None,
                            w_itemsize: float = None):
    """The divisor candidates that fit the VMEM budget. Dispatch
    (``_supports_mlp``), the traced default pick, and the autotune
    sweep all consume THIS list — a supported-and-dispatched kernel can
    therefore never compile over the budget its predicate promised.
    ``budget`` rides as a parameter (supports() passes the meta's
    ``vmem_budget`` key) so the env read stays a VISIBLE dispatch
    input, not a hidden one the cache-key lint cannot see."""
    budget = _vmem_budget() if budget is None else int(budget)
    return [bf for bf in _mlp_candidates(F)
            if _mlp_vmem_need(B, D, itemsize, bf, w_itemsize) <= budget]


@no_x64
def fused_mlp_block_pallas(x, nw, wg, wu, wd, eps=1e-6, block_f=None,
                           residual=True, layer=None):
    """Fused MLP stage of one decode block: RMSNorm + SwiGLU + residual.

    x: [B, D]; nw: [D] at x.dtype; wg/wu: [D, F]; wd: [F, D]. Tiled over
    F in ``block_f`` columns (autotuned, divisors of F) so only
    3*D*block_f weight elements are VMEM-resident per grid step.
    ``residual=False`` returns the bare down-projection (tensor-parallel
    partial — the caller all-reduces, then adds the residual).
    ``layer``: nw / wg / wu / wd (plain or quantized leaves) are the
    STACKED per-layer arrays ([L, D], [L, D, F], [L, F, D]) and this is
    the layer to run (an int or a traced int32 scalar): the launch
    takes the whole arrays and its index maps address the layer, so
    inside a loop over layers no one-layer copy of the weights is made.
    Bit-identical to passing each array's ``[layer]`` slice.
    """
    B, D = x.shape
    # weight-quant normalization: quantized leaf dicts split into the
    # integer tile + per-output-channel scale; the ORIGINAL leaves stay
    # in the autotune args so the tuning recursion re-parses them
    wg_in, wu_in, wd_in = wg, wu, wd
    wg, sg, bits, _ = _wq_parts(wg)
    wu, su, _, _ = _wq_parts(wu)
    wd, sd, _, _ = _wq_parts(wd)
    weight_dtype = weight_dtype_of(wg_in, wu_in, wd_in)
    F = wg.shape[-1]
    w_it = {8: 1.0, 4: 0.5}.get(bits)
    if block_f is None:
        it = jnp.dtype(x.dtype).itemsize
        # ONE budget read per trace: the fitting list and the autotune
        # key must see the same value (the budget-in-meta contract)
        budget = _vmem_budget()
        # budget-fitting tiles only; a forced call with nothing fitting
        # (tests, interpret) gets the smallest divisor tile
        cands = _mlp_fitting_candidates(B, D, F, it, budget, w_it) \
            or [min(_mlp_candidates(F))]
        ck = mlp_autotune_key(B, D, F, x.dtype, budget, weight_dtype)

        def build(bf):
            return lambda *a: fused_mlp_block_pallas(*a, eps=eps,
                                                     block_f=bf,
                                                     residual=residual,
                                                     layer=layer)

        # the shared read convention of every kernel on the persistent
        # autotune table
        from .autotune import resolve_candidate
        block_f = resolve_candidate(ck, cands, build,
                                    (x, nw, wg_in, wu_in, wd_in))
    bf = int(block_f)
    if F % bf:
        # grid=(F // bf,) floor-drops a ragged tail block: a non-divisor
        # tile would silently never feed the last F % bf columns into
        # the down-projection accumulator. (int4 needs no extra tile
        # constraint: the F axis is never the packed axis — gate/up
        # pack rows (D), down packs columns (D), both fully covered by
        # every F-tile.)
        raise ValueError(f"block_f={bf} must divide the intermediate "
                         f"dim F={F}")

    # every per-layer operand is a stack [L, ...] with a squeezed block
    # dimension that the layer (the scalar-prefetch operand) indexes;
    # one layer's arrays are a stack of one
    per_layer = [nw, wg, wu, wd] + ([sg, su, sd] if bits else [])
    if layer is None:
        per_layer, layer = [a[None] for a in per_layer], 0
    nw, wg, wu, wd, *scales = per_layer
    const = lambda j, l: (0, 0)                           # noqa: E731
    row = lambda j, l: (l[0], 0, 0)                       # noqa: E731
    f_cols = lambda j, l: (l[0], 0, j)                    # noqa: E731
    f_rows = lambda j, l: (l[0], j, 0)                    # noqa: E731
    # stored-shape tiles: int4 halves gate/up rows (pack axis 0 = the
    # contraction dim, fully covered by every tile) and down COLUMNS
    # (pack axis 1 = its output dim); the F-axis tiling is over the
    # UNPACKED coordinate for gate/up and over wd's packed rows 1:1
    gu_rows = wg.shape[-2]
    wd_cols = wd.shape[-1]
    bf_wd = bf                            # wd rows tile the F axis 1:1
    in_specs = [pl.BlockSpec((B, D), const),
                pl.BlockSpec((None, 1, D), row),
                pl.BlockSpec((None, gu_rows, bf), f_cols),
                pl.BlockSpec((None, gu_rows, bf), f_cols),
                pl.BlockSpec((None, bf_wd, wd_cols), f_rows)]
    inputs = [x, nw.reshape(-1, 1, D), wg, wu, wd]
    if bits:
        in_specs += [pl.BlockSpec((None, 1, bf), f_cols),
                     pl.BlockSpec((None, 1, bf), f_cols),
                     pl.BlockSpec((None, 1, D), row)]
        inputs += [jnp.asarray(sc, jnp.float32).reshape(-1, 1, n)
                   for sc, n in zip(scales, (F, F, D))]
    out = audited_pallas_call(
        functools.partial(_mlp_block_kernel, eps=eps, residual=residual,
                          wq_bits=bits),
        name="decode_mlp_block",
        num_scalar_prefetch=1,
        # the output block is revisited every intermediate tile (down-
        # projection accumulated in scratch, written at the last tile)
        accum_outputs=(0,),
        grid=(F // bf,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((B, D), const),
        out_shape=jax.ShapeDtypeStruct((B, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((B, D), x.dtype),
                        pltpu.VMEM((B, D), jnp.float32)],
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), *inputs)
    return out


# ---------------------------------------------------------------------------
# the XLA compositions: the attention stage's two halves (the decode
# loop performs its one in-place pool write between them) and the MLP
# stage's reference variant
# ---------------------------------------------------------------------------
QKV_LEAVES = ("q_proj", "k_proj", "v_proj")


def _concat_qkv(q, k, v):
    return jnp.concatenate([q, k, v], axis=-1)


def fuse_qkv(layers, wrap=lambda concat: concat):
    """The serving engine's step from the tree it is handed to the tree
    its programs read (``ServingEngine._fuse_qkv``, the one caller
    outside the tests): ``layers`` (the stacked per-layer parameters)
    with the three projection stacks ``[L, D, n * hd]`` as the ONE leaf
    ``qkv_proj`` ``[L, D, (H + 2 KV) * hd]``, ``[q | k | v]`` along the
    last axis: the form :func:`qkv_project` reads in place. ``wrap``
    takes the concatenation to what runs it: the engine's jit, over a
    mesh under ``shard_map`` so that a shard holds the columns of ITS
    heads (a column split of a global [q | k | v] would not: the engine
    refuses a tree fused elsewhere); the tests' ``jax.eval_shape``."""
    rest = {k: v for k, v in layers.items() if k not in QKV_LEAVES}
    rest["qkv_proj"] = wrap(_concat_qkv)(*(layers[k] for k in QKV_LEAVES))
    return rest


def local_heads(width, dims):
    """(H_loc, KV_loc) of a fused ``qkv_proj`` leaf ``width`` columns
    wide: ``dims`` is the MODEL's (H, KV, hd), and head-axis sharding
    keeps the ratio of query to key heads on every shard."""
    H, KV, hd = dims
    kv = width // hd * KV // (H + 2 * KV)
    return width // hd - 2 * kv, kv


def _qkv_ranges(t, dims):
    """The q, k and v column ranges of ``t [..., (H_loc + 2 KV_loc) *
    hd]``: the fused leaf itself, or a product over it."""
    h_loc, kv_loc = local_heads(t.shape[-1], dims)
    return jnp.split(t, [h_loc * dims[2], (h_loc + kv_loc) * dims[2]],
                     axis=-1)


def split_qkv(lp, dims):
    """One layer's (wq, wk, wv) whatever the tree holds: the fused
    leaf's column ranges, or the three leaves (plain or quantized) as
    they are: for a launch that takes the three separately."""
    if "qkv_proj" in lp:
        return tuple(_qkv_ranges(lp["qkv_proj"], dims))
    return tuple(lp[k] for k in QKV_LEAVES)


def qkv_project(h, lp, dims):
    """The q/k/v projections of rows ``h [..., D]`` by one layer's
    parameters ``lp``: (q ``[..., H_loc, hd]``, k, v ``[..., KV_loc,
    hd]``). The tree's STRUCTURE decides the form, as it decides the
    weight class: where ``lp`` holds the fused ``qkv_proj``
    (:func:`fuse_qkv`; the serving engine's tree) it is ONE product
    whose result is split, which XLA computes reading layer ``l`` of the
    stack in place; where it holds the three leaves (every other
    caller, and a quantized tree: DEQUANTIZE-THEN-MATMUL) it is the
    three products. Same operands, same accumulation, column for
    column. ``dims``: the model's (H, KV, hd); a tensor-parallel shard
    gets its local heads, read off the weights."""
    from ...quantization.quanters import maybe_dequantize

    if "qkv_proj" in lp:
        q, k, v = _qkv_ranges(h @ lp["qkv_proj"], dims)
    else:
        q, k, v = (h @ maybe_dequantize(lp[name], h.dtype)
                   for name in QKV_LEAVES)
    return tuple(t.reshape(*t.shape[:-1], -1, dims[2]) for t in (q, k, v))


def attn_qkv_ref(x, nw, lp, dims, sin, cos, seq_lens, eps=1e-6):
    """First half of the unfused attention stage: RMSNorm, the q/k/v
    projections (:func:`qkv_project` over the layer's parameters
    ``lp``) and RoPE at each slot's position. Returns (q [B, H, hd],
    k_new, v_new [B, KV, hd]); a tensor-parallel shard gets its local
    heads."""
    from .. import rms_norm as fused_rms_norm
    from ..rope import apply_rope

    pos_ids = seq_lens[:, None]
    h = fused_rms_norm(x[:, None], nw, eps)[:, 0]
    q, k, v = qkv_project(h, lp, dims)
    q = apply_rope(q[:, None], sin, cos, position_ids=pos_ids)
    k = apply_rope(k[:, None], sin, cos, position_ids=pos_ids)
    return q[:, 0], k[:, 0], v


def attn_out_ref(x, q, wo, k_pool, v_pool, block_tables, seq_lens,
                 kv_scales=None, residual=True, layer=None, gather=None):
    """Second half: paged attention over pools that ALREADY hold the
    new token (so it runs over ``seq_lens + 1``), the output projection
    and the residual. ``layer``: the pools are the decode loop's
    carried stack and the attention launch addresses that layer.
    ``gather``: applied to the [B, H_loc, hd] heads before the
    projection (the tensor-parallel "gather" placement's all-gather)."""
    from ..paged_attention import paged_attention_decode
    from ...quantization.quanters import maybe_dequantize

    k_scale, v_scale = kv_scales or (None, None)
    with jax.named_scope("layer/attention"):
        attn = paged_attention_decode(q, k_pool, v_pool, block_tables,
                                      seq_lens + 1, layer=layer,
                                      k_scale=k_scale, v_scale=v_scale)
    with jax.named_scope("layer/attn_out"):
        if gather is not None:
            attn = gather(attn)
        o = attn.reshape(x.shape[0], -1).astype(x.dtype) \
            @ maybe_dequantize(wo, x.dtype)
        return x + o if residual else o


def mlp_block_ref(x, nw, wg, wu, wd, eps=1e-6, residual=True,
                  gather=None):
    """``gather``: applied to the [B, F_loc] SwiGLU columns before the
    down projection (the "gather" placement's all-gather)."""
    from .. import rms_norm as fused_rms_norm, swiglu as fused_swiglu
    from ...quantization.quanters import maybe_dequantize

    wg = maybe_dequantize(wg, x.dtype)
    wu = maybe_dequantize(wu, x.dtype)
    wd = maybe_dequantize(wd, x.dtype)
    h = fused_rms_norm(x[:, None], nw, eps)[:, 0]
    ff = fused_swiglu(h @ wg, h @ wu)
    if gather is not None:
        ff = gather(ff)
    o = ff @ wd
    return x + o if residual else o


# ---------------------------------------------------------------------------
# registry: shape-class dispatch with the composition as fallback
# ---------------------------------------------------------------------------
def decode_meta_dims(B, D, F, dtype, weight_dtype=None) -> dict:
    """Static dispatch metadata of ``decode_mlp_block`` from raw dims:
    everything :func:`_supports_mlp` reads, built at trace time from
    static shapes only, so dispatch is deterministic per program. Inside
    a tensor-parallel shard ``F`` is the LOCAL intermediate count (the
    VMEM math is then already per shard)."""
    dtype = jnp.dtype(dtype)
    return {
        "B": int(B), "D": int(D), "F": int(F),
        "dtype": str(dtype), "itemsize": int(dtype.itemsize),
        "interpret": bool(_interpret()),
        # the weight-dtype CLASS ("int8"/"int4" quantized trees, else
        # the model dtype): it reshapes the VMEM math and the tile
        # candidate lists, and it is static in the trace signature
        # (the param tree's structure carries it)
        "weight_dtype": str(weight_dtype) if weight_dtype
        else str(dtype),
        # the budget is a real dispatch input (it reshapes supports()
        # and the block_f candidate list), so it rides in the meta —
        # visible to the DISPATCH_KEY_GAP lint like every other key
        "vmem_budget": int(_vmem_budget()),
    }


def _wq_even_reason(meta, dims):
    """int4 packing pairs the two halves of the pack axis — every
    packed dimension must be even. ``dims``: (name, value) pairs."""
    if meta.get("weight_dtype") != "int4":
        return None
    for name, v in dims:
        if v % 2:
            return (f"packed-int4 weights need an even {name} "
                    f"(got {v}): packing pairs the axis halves")
    return None


def _supports_mlp(meta):
    if meta["interpret"]:
        return False, "interpret mode (off-TPU): composition is faster"
    D, F, B = meta["D"], meta["F"], meta["B"]
    why = _wq_even_reason(meta, (("hidden_size", D),))
    if why:
        return False, why
    fits = _mlp_fitting_candidates(B, D, F, meta["itemsize"],
                                   meta["vmem_budget"],
                                   _weight_itemsize(meta))
    if fits:
        return True, f"fits VMEM at block_f={fits[0]}"
    return False, (f"no intermediate tile of F={F} fits the "
                   f"{meta['vmem_budget'] >> 20}MiB VMEM budget")


def _mlp_pallas_variant(x, nw, wg, wu, wd, eps=1e-6, residual=True,
                        layer=None):
    return fused_mlp_block_pallas(x, nw, wg, wu, wd, eps=eps,
                                  residual=residual, layer=layer)



KERNELS.register("decode_mlp_block", "pallas_fused", _mlp_pallas_variant,
                 priority=10, supports=_supports_mlp,
                 tags=("serving", "pallas"))
KERNELS.register("decode_mlp_block", "unfused", mlp_block_ref,
                 priority=0, tags=("serving",))
# every decode_meta_dims key is either in the jitted decode program's
# trace signature (the shape/dtype keys) or in the decode programs'
# route key (``inference.generation.kernel_route``: pins, the VMEM
# budget, the interpret override) — the registry lint holds supports()
# to this declaration
KERNELS.declare_cache_key(
    "decode_mlp_block",
    ("B", "D", "F", "dtype", "interpret", "weight_dtype", "vmem_budget"),
    covers={"itemsize": "dtype"})


def launch_operands(picked: dict) -> dict:
    """How each Pallas launch of one layer of the decode loop gets its
    per-layer operands (a layer of the carried KV pools, of the stacked
    MLP weights), given the variants dispatch ``picked`` for the trace
    (``{op: variant}``, :meth:`KernelRegistry.record`):
    ``{launch name: "index"}`` — the launch takes the whole stacked
    array and addresses the layer itself, so XLA copies no one-layer
    slice out for it. Variants that launch nothing (the XLA
    compositions, off the TPU) are not listed."""
    pallas = {"paged_attention_decode": "pallas",
              "decode_mlp_block": "pallas_fused"}
    return {op: "index" for op, name in pallas.items()
            if picked.get(op) == name}
