"""Real-chip compiles, without the chip: the TPU's compiler is installed
here and compiles for a DESCRIBED ``v5e:2x2`` topology (the
on-chip-measurement guide, section 2). What interpret mode cannot show —
a block shape the tiling refuses, a Mosaic op with no layout, a kernel
over the scoped-VMEM limit, a kernel GSPMD cannot partition — the
compiler says here, at no chip time.

One case per main-path kernel at the ``chip_smoke.py`` widths (Llama-7B:
D=4096, 32 heads x 128, F=11008, V=32000), the decode launches at the
benchmark cells' own shapes, one per fused prefill kernel at the shape
class where ``supports()`` selects it, each asserting that dispatch
selects what is compiled and that the compiled program holds the named
``tpu_custom_call``; then whole programs, the engine's decode program
among them, whose ``decode_variant`` must name the launches the
compiled program holds. A compile that passes is not a chip run.

The routers ask ``jax.default_backend()`` and see the CPU here, so the
cases compile the kernels themselves, with ``interpret`` steered by the
test (``set_force_interpret(False)``), not by an option of the program.
"""
import dataclasses
import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

import paddle_tpu  # noqa: F401 — x64 mode, as every kernel caller has it
from paddle_tpu.analysis import kernel_catalog as kc
from paddle_tpu.ops.pallas import _util
from paddle_tpu.ops.pallas import fused_adamw as fa
from paddle_tpu.ops.pallas import fused_decode_block as fdb
from paddle_tpu.ops.pallas import fused_prefill_block as fpb
from paddle_tpu.ops.pallas import fused_train as ft
from paddle_tpu.ops.pallas import norms
from paddle_tpu.ops.pallas.flash_attention import flash_supports
from paddle_tpu.ops.pallas.registry import KERNELS

# chip_smoke.py's widths (LlamaConfig defaults) and its engine geometry
D, H, KV, HD, F, V = 4096, 32, 32, 128, 11008, 32000
CAP, BS, MB, NPAGES = 4, 16, 40, 129       # ServingEngine defaults
T, SEQ = 4096, 2048                        # batch 2 x seq 2048
BF16 = "bfloat16"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e}")


@pytest.fixture(autouse=True)
def _compile_for_the_chip():
    """Kernels lower through Mosaic (not the interpreter), and the
    persistent compile cache stays out of it: an entry compiled for a
    described chip is written but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    _util.set_force_interpret(False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    _util.set_force_interpret(None)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _kernels(compiled):
    """Audited launch names of the Pallas custom calls in a program."""
    return set(_util.compiled_kernel_counts(compiled.as_text()))


def _compile(topo, build):
    fn, args = build()
    one = SingleDeviceSharding(topo.devices[0])
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        args)
    return jax.jit(fn).lower(*args).compile()


def _decode_meta(b=CAP, d=D, f=F, wq=None):
    return fdb.decode_meta_dims(b, d, f, BF16, weight_dtype=wq)


def _selects(op, meta, variant="pallas_fused"):
    return KERNELS.dispatch(op, meta)[0] == variant


def _ssm_update_case(S, R, N, G, Lm):
    """``ssm_update_pallas`` on a float32 pool [Lm, S, N, R] with G B/C
    groups, layer a traced operand."""
    def build():
        from paddle_tpu.ops.pallas.mamba2 import ssm_update_pallas
        f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
        bc = (S, N) if G == 1 else (S, G, N)
        return ssm_update_pallas, (
            f32(S, R), f32(S, R), f32(*bc), f32(*bc), f32(Lm, S, N, R),
            jax.ShapeDtypeStruct((), jnp.int32))
    return build


def _experts_meta():
    from paddle_tpu.ops.moe_experts import experts_meta
    bf = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731
    return {**experts_meta(bf(7, 64, 2688, 1920), bf(7, 64, 1856, 2688),
                           "relu2"), "backend": "tpu", "interpret": False}


def _moe_grouped_case(T, L=7, held=64, D=2688, Fs=1920, F=1856, k=6):
    """One expert layer of the Nemotron-H cell over T tokens: the
    layout and both launches, the layer a traced operand."""
    def build():
        from paddle_tpu.ops import moe_experts as me
        bf = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731

        def fn(u, router, w_in, w_out, layer):
            gates, experts = me.route(u, router, k, "sigmoid", None, 2.5)
            return me._grouped(u, gates, experts, w_in, w_out, 0, layer,
                               "relu2")
        return fn, (bf(T, D), bf(D, 2 * held), bf(L, held, D, Fs),
                    bf(L, held, F, D), jax.ShapeDtypeStruct((), jnp.int32))
    return build


# (id, builder, launch names the program must hold, "does dispatch
# select this kernel on the chip?" or None where no registry op routes it)
CASES = [
    # -- the unfused decode route at the smoke widths -------------------
    ("paged_attention", kc._paged_case(CAP, H, KV, HD, BS, NPAGES, MB,
                                       BF16),
     {"paged_attention_decode"}, None),
    # -- the same launch as the benchmark's cells make it: the stacked
    #    pools left in HBM, the layer a traced operand, the block of
    #    pages resolved from the shapes (B, H, KV, hd, BS, pages, MB) --
    ("paged_attention_mistral_one_chip",
     kc._paged_case(32, 32, 8, 128, 16, 3072, 160, BF16, L=16),
     {"paged_attention_decode"}, None),
    ("paged_attention_mistral_tp4_shard",
     kc._paged_case(32, 8, 2, 128, 16, 5120, 160, BF16, L=32),
     {"paged_attention_decode"}, None),
    ("paged_attention_granite_layer",
     kc._paged_case(64, 32, 8, 128, 16, 8192, 128, BF16, L=1,
                    scale=1.0 / 128),
     {"paged_attention_decode"}, None),
    # 2 KV heads of 16 query heads each (page rows BS * KV = 32)
    ("paged_attention_nemotron_layer",
     kc._paged_case(128, 32, 2, 128, 16, 32768, 256, BF16, L=2),
     {"paged_attention_decode"}, None),
    # -- the one-token state update: granite's one B/C group (blocks of
    #    2048 lanes inside the group) and Nemotron-H's eight (a block
    #    holds four groups of 512 lanes) --------------------------------
    ("ssm_update_one_group", _ssm_update_case(64, 8192, 128, 1, 9),
     {"ssm_update"}, None),
    ("ssm_update_eight_groups", _ssm_update_case(128, 4096, 128, 8, 7),
     {"ssm_update"}, None),
    # -- the grouped product over the touched experts, both products of a
    #    Nemotron-H expert layer (the second reads 1856 of the hidden
    #    rows' 1920 stored columns), at a decode step's and a chunk's rows
    ("moe_grouped_decode", _moe_grouped_case(128), {"moe_grouped"},
     lambda: _selects("moe_experts", _experts_meta(), "pallas_grouped")),
    ("moe_grouped_chunk", _moe_grouped_case(512), {"moe_grouped"}, None),
    ("rms_norm_decode_rows", kc._rms_case(CAP, D, BF16),
     {"rms_norm_fwd", "rms_norm_bwd"}, None),
    ("decode_mlp_block", kc._mlp_block_case(CAP, D, F, BF16),
     {"decode_mlp_block"},
     lambda: _selects("decode_mlp_block", _decode_meta())),
    # -- the same launch at the Mistral cells' widths (32 slots, D=4096,
    #    F=14336): the weight_int8 control's tiles and packed int4 with
    #    the dequantization in the kernel, and the four-chip shard's
    #    quarter of the intermediate columns -----------------------------
    ("decode_mlp_block_int8_weights",
     kc._mlp_block_case(32, 4096, 14336, BF16, wq="int8"),
     {"decode_mlp_block"},
     lambda: _selects("decode_mlp_block",
                      _decode_meta(32, 4096, 14336, wq="int8"))),
    ("decode_mlp_block_int4_weights",
     kc._mlp_block_case(32, 4096, 14336, BF16, wq="int4"),
     {"decode_mlp_block"},
     lambda: _selects("decode_mlp_block",
                      _decode_meta(32, 4096, 14336, wq="int4"))),
    ("decode_mlp_block_tp4_shard",
     kc._mlp_block_case(32, 4096, 14336 // 4, BF16),
     {"decode_mlp_block"},
     lambda: _selects("decode_mlp_block",
                      _decode_meta(32, 4096, 14336 // 4))),
    # -- the training step at the smoke widths --------------------------
    ("flash_attention", kc._flash_case(2, SEQ, H, KV, HD, BF16),
     set(kc._FLASH_KERNELS),
     lambda: flash_supports(SEQ, SEQ)[0]),
    ("rms_norm", kc._rms_case(T, D, BF16),
     {"rms_norm_fwd", "rms_norm_bwd"},
     lambda: _selects("rms_norm_bwd", norms.rms_bwd_meta(T, D, BF16))),
    ("rms_norm_residual", kc._res_rms_case(T, D, BF16),
     {"residual_rms_norm_fwd", "rms_norm_bwd"},
     lambda: _selects("rms_norm_residual",
                      norms.rms_bwd_meta(T, D, BF16))),
    ("fused_linear_ce", kc._linear_ce_case(T, D, V, BF16),
     set(kc._CE_KERNELS),
     lambda: _selects("fused_linear_ce", ft.ce_meta(T, D, V, BF16))),
    ("fused_swiglu", kc._swiglu_case(T, F, BF16),
     {"swiglu_fwd", "swiglu_bwd"},
     lambda: _selects("fused_swiglu", ft.swiglu_meta(T, F, BF16))),
    ("fused_adamw", kc._adamw_case(4 << 20, "float32", BF16, BF16),
     {"fused_adamw"},
     lambda: _selects("fused_adamw",
                      fa.adamw_meta(4 << 20, "float32", BF16, True))),
    # -- the fused prefill kernels, where supports() selects them (the
    #    catalog's D=1024 serving class; at the smoke widths their
    #    resident weights are refused on the VMEM budget) ---------------
    ("prefill_attn_block_hd128",
     kc._prefill_attn_case(64, 1024, 8, 8, 128, 16, 129, 24, BF16,
                           pos0=128),
     {"prefill_attn_block"},
     lambda: _selects("prefill_attn_block", fpb.prefill_meta_dims(
         64, 1024, 8, 8, 128, 4096, 16, 24, BF16, BF16, False))),
    ("prefill_mlp_block", kc._mlp_block_case(64, 1024, 4096, BF16),
     {"decode_mlp_block"},
     lambda: _selects("prefill_mlp_block", fpb.prefill_meta_dims(
         64, 1024, 16, 16, 64, 4096, 16, 24, BF16, BF16, False))),
]


@pytest.mark.parametrize("name,build,want,selected", CASES,
                         ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(topo, name, build, want, selected):
    if selected is not None:
        assert selected(), f"dispatch does not select {name} on the chip"
    assert want <= _kernels(_compile(topo, build))


@pytest.mark.parametrize("K,N,held", [
    (2688, 1920, 64),     # the Nemotron-H cell's first product
    (1856, 2688, 64),     # ... and its second (F x K)
    (4096, 1536, 36),     # granite's gated pair of columns
    (2304, 1792, 64),     # Mellum 2's
], ids=["nemotron_in", "nemotron_out", "granite", "mellum2"])
def test_xla_tile_is_what_the_compiler_writes(topo, K, N, held):
    """``ops/pallas/moe_experts.xla_tile`` restates a rule of XLA's (a
    ``ragged_dot`` dimension is tiled by the largest power of two up to
    512 that divides it) and ``supports()`` hands the expert layer to
    the Pallas launch where that rule gives 128 x 128. The rule is not
    JAX's to keep: this reads the tiling out of the compiled text at
    the benchmark's widths, so an upgrade that changes it fails here
    and not silently in a cell (ROADMAP S1: the predicate goes when
    ``moe_grouped`` replaces ``ragged_dot``)."""
    from paddle_tpu.ops.pallas.moe_experts import xla_tile

    def build():
        bf = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731
        return (lambda x, w, sizes: jax.lax.ragged_dot(x, w, sizes),
                (bf(768, K), bf(held, K, N),
                 jax.ShapeDtypeStruct((held,), jnp.int32)))
    found = re.findall(r'ragged_dot_tiling="?(\d+),(\d+),(\d+)',
                       _compile(topo, build).as_text())
    assert found, "the compiled text names no ragged_dot_tiling"
    # (rows, contracted, columns) of the launch
    assert {(int(k), int(n)) for _, k, n in found} \
        == {(xla_tile(K), xla_tile(N))}


# the training cell's flat optimizer state (mistral-7b-v0.3-train-l2:
# 704,663,552 parameters, padded as Trainer._flat_layout pads it)
N_TRAIN = -(-704_663_552 // fa.BLOCK) * fa.BLOCK


def _state_sized_relayouts(text, n):
    """Instructions of a compiled program that COPY an array the size
    of the flat optimizer state into another layout, whatever shape
    they give it (XLA turned a slice of the flat master into
    ``f32[n / 4096, 4096] reshape``: 8 ms a step at the training cell);
    a view that costs nothing compiles to ``bitcast``."""
    found = []
    for m in re.finditer(r"= (?:f32|bf16)\[([0-9,]+)\]\S* "
                         r"(?:copy|reshape|transpose)\(.*", text):
        if np.prod([int(d) for d in m.group(1).split(",")]) == n:
            found.append(m.group(0)[:160])
    return found


@pytest.mark.parametrize("grad,moments,shadow", [
    ("float32", BF16, BF16),            # the training cell's mix
    (BF16, BF16, BF16),
    ("float32", "float32", BF16),       # the most VMEM a block takes
    ("float32", "float32", None),
], ids=["cell", "bf16_grad", "f32_moments", "f32_moments_no_shadow"])
def test_fused_adamw_streams_the_cells_flat_state_in_place(
        topo, grad, moments, shadow):
    """Every dtype mix the registry admits, at the training cell's
    padded count, compiles with ``(ROWS, LANES)`` blocks, and the
    ``(rows, 128)`` view of the flat vectors is a bitcast both ways: the
    program holds no temporary and no state-sized copy."""
    def fn(p, g, m, v, lr, step, scale):
        return fa.fused_adamw(p, g, m, v, lr, step, grad_scale=scale,
                              shadow_dtype=shadow)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=SingleDeviceSharding(topo.devices[0]))
    assert _selects("fused_adamw",
                    fa.adamw_meta(N_TRAIN, "float32", moments, bool(shadow)))
    # master and moments donated, as the Trainer's step donates its state
    # (without that XLA copies them to keep the caller's arrays whole)
    compiled = jax.jit(fn, donate_argnums=(0, 2, 3)).lower(
        on_chip((N_TRAIN,), "float32"), on_chip((N_TRAIN,), grad),
        on_chip((N_TRAIN,), moments), on_chip((N_TRAIN,), moments),
        *[on_chip((), "float32")] * 3).compile()
    assert "fused_adamw" in _kernels(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert not _state_sized_relayouts(compiled.as_text(), N_TRAIN)


def test_every_refusal_on_the_chip_names_its_reason():
    """What dispatch refuses at the smoke widths, and what the chip's
    compiler refused outright, falls back with a reason a person can
    act on — so ``decode_variant`` and ``explain()`` tell the truth."""
    p64 = fpb.prefill_meta_dims(64, 1024, 16, 16, 64, 4096, 16, 24, BF16,
                                BF16, False)
    p7b = fpb.prefill_meta_dims(128, D, H, KV, HD, F, BS, MB, BF16, BF16,
                                False)
    refused = {
        ("prefill_attn_block", "pallas_fused"): (p7b, "VMEM"),
        ("prefill_mlp_block", "pallas_fused"): (p7b, "VMEM"),
    }
    for (op, variant), (meta, word) in refused.items():
        (row,) = [r for r in KERNELS.explain(op, meta)
                  if r["name"] == variant]
        assert not row["supported"] and word in row["reason"], row
        assert not [r for r in KERNELS.explain(op, meta)
                    if r["selected"] and r["name"] == variant]
    # the compiler's own refusal, quoted: heads narrower than a lane tile
    ok, why = fpb._supports_prefill_attn(p64)
    assert not ok and "unsupported shape cast" in why
    # a sequence the flash grid cannot tile exactly goes to the ref
    ok, why = flash_supports(600, 600)
    assert not ok and "600" in why


def test_refused_prefill_shape_is_what_the_compiler_refuses(topo):
    """The hd=64 refusal above is the compiler's, not ours: forcing the
    kernel at that shape raises the quoted Mosaic error."""
    build = kc._prefill_attn_case(64, 1024, 16, 16, 64, 16, 129, 24, BF16,
                                  pos0=128)
    with pytest.raises(Exception, match="unsupported shape cast"):
        _compile(topo, build)


# ---------------------------------------------------------------------------
# whole programs: the Trainer step, on one device and GSPMD-sharded on four
# ---------------------------------------------------------------------------
def _lowered_train_step(topo, mesh_cfg):
    """The Trainer's jitted step lowered for described devices: shapes
    only (``jax.device_put`` to a described device fails), shardings
    attached by hand as ``init_state`` would place them."""
    from paddle_tpu.distributed.trainer import Trainer, make_mesh
    from paddle_tpu.models import llama

    cfg = dataclasses.replace(llama.LLAMA_TINY, num_key_value_heads=4,
                              max_position_embeddings=512)
    mesh = make_mesh(mesh_cfg, devices=list(topo.devices))
    specs = llama.param_shardings(mesh, cfg)
    tr = Trainer(lambda p, t, l: llama.loss_fn(p, t, l, cfg), mesh, specs,
                 fused_optimizer=False)
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0)))

    def placed(dtype=None):
        return jax.tree_util.tree_map(
            lambda v, s: jax.ShapeDtypeStruct(
                v.shape, dtype or v.dtype, sharding=NamedSharding(mesh, s)),
            params, specs)

    rep = NamedSharding(mesh, P())
    state = (placed(), placed(jnp.float32), placed(jnp.float32),
             placed(jnp.float32),
             jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
    toks = jax.ShapeDtypeStruct((4, 512), jnp.int32,
                                sharding=NamedSharding(mesh, tr.data_spec))
    tr._build()
    return tr._step_fn.lower(state, np.float32(1e-3), toks, toks)


def test_train_step_on_one_device_runs_the_training_kernels(
        topo, monkeypatch):
    from paddle_tpu.distributed.trainer import MeshConfig
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    found = _kernels(_lowered_train_step(topo, MeshConfig()).compile())
    assert set(kc._FLASH_KERNELS) | set(kc._CE_KERNELS) <= found


def test_training_cell_step_holds_the_2d_launch_and_no_state_sized_copy(
        topo, monkeypatch):
    """The benchmark's training configuration (Mistral-7B widths, depth
    2, fp32 master, bf16 moments) as the one-chip Trainer compiles it:
    the step holds the ``fused_adamw`` launch, ``optimizer_variant`` is
    the record of that trace (variant and block geometry), and no
    instruction re-lays the flat master, moments, gradient or shadow
    out (the launch's 2-D view of them is a bitcast)."""
    import json
    from paddle_tpu.distributed.trainer import (MeshConfig, Trainer,
                                                make_mesh)
    from paddle_tpu.models import llama
    from paddle_tpu.ops.pallas.autotune import GLOBAL_FLAGS

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "mistral-7b-v0.3-train-l2.json")) as f:
        conf = json.load(f)
    cfg = llama.LlamaConfig(dtype=jnp.bfloat16, **{
        k: conf[k] for k in conf["program"]["config_keys"]})
    opt = conf["trainer"]
    mesh = make_mesh(MeshConfig(), devices=list(topo.devices[:1]))
    tr = Trainer(lambda p, t, l: llama.loss_fn(p, t, l, cfg), mesh,
                 llama.param_shardings(mesh, cfg), lr=opt["lr"],
                 b1=opt["b1"], b2=opt["b2"],
                 weight_decay=opt["weight_decay"],
                 grad_clip=opt["grad_clip"],
                 moment_dtype=getattr(jnp, opt["moment_dtype"]))
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0)))
    # what init_state decides on the chip, from shapes alone
    tr._fused, tr._flat_meta = True, tr._flat_layout(params)
    n = sum(tr._flat_meta[2]) + tr._flat_meta[4]
    assert n == N_TRAIN

    def on_chip(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    state = (jax.tree_util.tree_map(
        lambda v: on_chip(v.shape, v.dtype), params),
        on_chip((n,), jnp.float32), on_chip((n,), jnp.bfloat16),
        on_chip((n,), jnp.bfloat16), on_chip((), jnp.int32))
    toks = on_chip((2, 2048), jnp.int32, tr.data_spec)
    autotune = GLOBAL_FLAGS.get("kernel_autotune")
    GLOBAL_FLAGS.set("kernel_autotune", False)     # as the cell sets it
    try:
        tr._build()
        compiled = tr._step_fn.lower(
            state, np.float32(opt["lr"]), toks, toks).compile()
    finally:
        GLOBAL_FLAGS.set("kernel_autotune", autotune)
    assert "fused_adamw" in _kernels(compiled)
    assert tr.metrics()["optimizer_variant"] == {
        "variant": "pallas_fused", "block": [fa.ROWS, fa.LANES]}
    assert not _state_sized_relayouts(compiled.as_text(), n)


def test_gspmd_sharded_train_step_compiles_without_mosaic_kernels(
        topo, monkeypatch):
    """JAX refuses to lower a Mosaic kernel into a program GSPMD
    partitions ("Mosaic kernels cannot be automatically partitioned"):
    on a 4-device mesh the Trainer's routing takes the compositions,
    and the step compiles for the 2x2 chips with its collectives."""
    from paddle_tpu.distributed.trainer import MeshConfig
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _lowered_train_step(
        topo, MeshConfig(fsdp=2, tp=2)).compile()
    assert _kernels(compiled) == set()
    assert re.search(r"\ball-reduce(-start)?\(", compiled.as_text())


# ---------------------------------------------------------------------------
# the decode program of the benchmark's serving configurations: its layer
# loop carries the KV pools and hands the kernels whole buffers (PR 26)
# ---------------------------------------------------------------------------
def _lowered_decode_program(topo, config, tree="engine", chunk=None):
    """The engine's decode program (``serving._make_decode_fn``: the
    configuration's decode forward, greedy sampling, the engine's
    donation) lowered at a benchmark configuration's shapes for
    described devices. ``tree``: "engine" is the tree as the engine
    keeps a dense model's (q/k/v as the one leaf ``qkv_proj``),
    "three_leaf" as every other caller hands it over. ``chunk``: lower
    the dense prefill chunk of that many tokens (the forward of
    ``serving._make_prefill_fn_ref``, over a mesh of ``_tp``, over the
    request's dense view) in the decode program's place."""
    import importlib
    import json
    from jax.sharding import Mesh
    from paddle_tpu.inference import generation as G, hybrid
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.inference.tp import ServingMesh

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           config + ".json")) as f:
        conf = json.load(f)
    module, cls = conf["program"]["config"].split(":")
    model = importlib.import_module(module)
    cfg = getattr(model, cls)(**{k: conf[k]
                                 for k in conf["program"]["config_keys"]})
    pattern = hybrid.served_pattern(cfg)
    eng, tp = conf["engine"], conf["engine"].get("mesh", 1)
    mesh = Mesh(np.array(topo.devices[:tp]), ("tp",))
    params = jax.eval_shape(lambda: model.init_params(cfg))
    if pattern is None and tree == "engine":
        params["layers"] = fdb.fuse_qkv(
            params["layers"], lambda f: functools.partial(jax.eval_shape, f))
    specs = jax.tree_util.tree_map(lambda _: P(), params)
    pool_spec = P()
    if pattern is not None:
        step = lambda p, tok, seq, tab, kp, vp, st: hybrid.decode_step(  # noqa: E731
            p, tok, cfg, kp, vp, tab, seq, st)
    elif tp == 1:
        step = lambda p, tok, seq, tab, kp, vp: G._decode_step(  # noqa: E731
            p, tok, cfg, kp, vp, tab, seq)
    else:
        sm = ServingMesh(mesh)
        specs, pool_spec = sm.param_specs(cfg, params), sm.pool_spec
        step = sm.sharded_decode_fn(cfg, quant=False, params=params)

    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    params = jax.tree_util.tree_map(
        lambda v, s: sds(v.shape, v.dtype, s), params, specs)
    C, BS = eng["capacity"], eng["block_size"]
    pool = sds((getattr(cfg, "num_kv_layers", cfg.num_hidden_layers),
                eng["num_blocks"], BS, cfg.num_key_value_heads,
                cfg.head_dim), cfg.dtype, pool_spec)
    state = ()
    if pattern is not None:
        # the model's own state (a recurrent state a slot, the window
        # layers' pools and rings): one more donated argument
        state = (_pattern_state(cfg, pattern, eng, sds),)

    def program(params, tok, seq_lens, tables, temps, key, k_pools,
                v_pools, *state):
        logits, k_pools, v_pools, *state = step(
            params, tok, seq_lens, tables, k_pools, v_pools, *state)
        return (jnp.argmax(logits, -1).astype(jnp.int32),
                jnp.where(seq_lens > 0, seq_lens + 1, 0), key, k_pools,
                v_pools, *state)

    if chunk is not None:
        view = sds((cfg.num_hidden_layers, 1, eng["max_seq_len"] + chunk,
                    cfg.num_key_value_heads, cfg.head_dim), cfg.dtype,
                   pool_spec)
        fwd = lambda p, toks, kc, vc, pos: G.cached_forward(  # noqa: E731
            p, toks, cfg, kc, vc, pos)
        if tp > 1:
            from paddle_tpu.core.jax_compat import shard_map_norep
            from paddle_tpu.inference.tp import _tp_cached_forward
            fwd = shard_map_norep(
                lambda p, toks, kc, vc, pos: _tp_cached_forward(
                    p, toks, cfg, kc, vc, pos, axis=sm.axis,
                    collective=sm.collective),
                mesh, (specs, P(), pool_spec, pool_spec, P()),
                (P(), pool_spec, pool_spec))
        return jax.jit(fwd, donate_argnums=(2, 3)).lower(
            params, sds((1, chunk), jnp.int32), view, view,
            sds((), jnp.asarray(0).dtype)), None
    donate = ServingEngine._DECODE_DONATE + (
        (8,) if pattern is not None else ())
    lowered = jax.jit(program, donate_argnums=donate).lower(
        params, sds((C,), jnp.int32), sds((C,), jnp.int32),
        sds((C, -(-eng["max_seq_len"] // BS)), jnp.int32),
        sds((C,), jnp.float32), sds((2,), jnp.uint32), pool, pool, *state)
    return lowered, int(np.prod(pool.shape)) * 2 // tp


def _pattern_state(cfg, pattern, eng, sds):
    """A pattern-run model's state at an engine's geometry, as structs."""
    from paddle_tpu.inference import hybrid
    C, BS = eng["capacity"], eng["block_size"]
    ring = pattern.ring(BS, max(eng["prefill_buckets"])) \
        if pattern.window else 0
    return jax.tree_util.tree_map(
        lambda v: sds(v.shape, v.dtype), jax.eval_shape(
            lambda: hybrid.init_state(cfg, C, jnp.float32, C * ring + 1,
                                      BS, ring)))


_DENSE_LAUNCHES = {"paged_attention_decode", "decode_mlp_block"}


@pytest.mark.parametrize("config,launches,share", [
    pytest.param(c, k, n, id=c) for c, k, n in (
        ("mistral-7b-v0.3-l16", _DENSE_LAUNCHES, 8),
        ("mistral-7b-v0.3-tp4", _DENSE_LAUNCHES, 8),
        # one attention layer's pool (0.27 GB) beside nine expert layers'
        # buffers (0.06 GB of temporaries): under half a pool
        ("granite-4.0-h-small-l10-e36",
         {"paged_attention_decode", "ssm_update"}, 2),
        # two page classes: the global pool (0.54 GB) is the measure,
        # the window pools ride in the state and are written in place
        ("mellum2-12b-a2.5b-l8", {"paged_attention_decode"}, 2),
        # both pools of two attention layers (0.54 GB each) and seven
        # layers of state ride in the carry; the expert stacks are read
        # in place (their first matrix stored in whole lanes: handed
        # 1856 columns the launch gets a 4.3 GB copy of the stack)
        ("nemotron-3-nano-30b-a3b-l16-e64",
         {"paged_attention_decode", "ssm_update", "moe_grouped"}, 2))])
def test_decode_program_holds_no_second_copy_of_a_pool(
        topo, monkeypatch, config, launches, share):
    """The layer loop's pools are carried and written in place and its
    kernels read whole buffers by layer index: the compiled program's
    temporaries are far smaller than one KV pool (as scan inputs and
    outputs both pools were held twice: 4.1 GB of temporaries at l16),
    and the launches the registry picks on the chip are in it — the
    hybrid model's attention layer through the same dispatch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lowered, pool_bytes = _lowered_decode_program(topo, config)
    compiled = lowered.compile()
    assert launches <= _kernels(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < pool_bytes // share


def test_nemotron_chunk_keeps_the_conv_tails_in_their_layout(
        topo, monkeypatch):
    """The 512-token chunk of the Nemotron-H cell: its temporaries are
    a fraction of a KV pool. With ``in_proj`` 10304 columns wide (no
    whole number of lanes) the compiler laid the product, the
    convolution and with them the slots' tails out column-major: the
    tails' pool [7, 128, 3, 6144] with the THREE taps on the lanes,
    1.3 GB of padding carried through every loop (temporaries 1.51 GB);
    stored 10368 wide they stay as they are (0.15 GB)."""
    import json
    from paddle_tpu.inference import hybrid
    from paddle_tpu.models import nemotron_h as nh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b-l16-e64.json")) as f:
        conf = json.load(f)
    cfg = nh.NemotronHConfig(**{k: conf[k]
                                for k in conf["program"]["config_keys"]})
    eng = conf["engine"]
    one = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one),
            tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    C, BS, MB = (eng["capacity"], eng["block_size"],
                 eng["max_seq_len"] // eng["block_size"])
    params = sds(jax.eval_shape(lambda: nh.init_params(cfg)))
    pool = sds(jax.ShapeDtypeStruct(
        (cfg.num_kv_layers, eng["num_blocks"], BS, cfg.num_key_value_heads,
         cfg.head_dim), cfg.dtype))
    state = sds(jax.eval_shape(
        lambda: hybrid.init_state(cfg, C, jnp.float32, 0, BS, 0)))

    def chunk(params, toks, table, pos0, n, slot, kp, vp, st):
        return hybrid.prefill_chunk(params, toks, cfg, kp, vp, table,
                                    table, pos0, n, slot, st)

    compiled = jax.jit(chunk, donate_argnums=(6, 7, 8)).lower(
        params, i32(512), i32(MB), i32(), i32(), i32(), pool, pool,
        state).compile()
    assert {"ssm_state_read", "ssm_state_write"} <= _kernels(compiled)
    pool_bytes = int(np.prod(pool.shape)) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 2


# ---------------------------------------------------------------------------
# the layer loop reads q/k/v in place (PR 42): over the engine's tree no
# layer of a projection stack is copied out or re-laid out before its product
# ---------------------------------------------------------------------------
_LAYER_OF_A_STACK = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = \(?bf16\[1,4096,(\d+)\]\{[^}]*\}.*? "
    r"(copy|fusion|copy-start|copy-done|slice-start|slice-done)\(")


def _staged_projection_layers(text):
    """[(instruction, columns, opcode)] of a compiled dense program: the
    instructions of the layer loop's body whose RESULT is one layer of a
    projection stack, ``bf16[1, 4096, n]`` with ``n`` >= 1024 — a
    stand-alone ``dynamic-slice`` fusion that copies the layer out, a
    ``copy`` that re-lays it out, or the asynchronous forms the compiler
    stages an operand by (``copy-start`` / ``-done``, ``slice-start`` /
    ``-done``: no ``op_name``, so wherever they stand). A product that
    reads its layer in place has the ``dynamic-slice`` inside its own
    fusion, whose result is activations."""
    found = []
    for line in text.splitlines():
        m = _LAYER_OF_A_STACK.match(line)
        if m and int(m.group(2)) >= 1024 and (
                "layers/while/body" in line or "-" in m.group(3)):
            found.append((m.group(1), int(m.group(2)), m.group(3)))
    return found


_BODY = 'metadata={op_name="jit(step)/layers/while/body/dynamic_slice"}'
_RECORDED = {       # a line as the compiler prints it -> what is found
    "slice_fusion": (
        "  %constant_dynamic-slice_fusion.6 = bf16[1,4096,4096]{2,1,0:T(8,128)"
        "(2,1)S(1)} fusion(%get-tuple-element.7, %dynamic_slice.1), "
        "kind=kLoop, calls=%fused_computation.9, " + _BODY,
        [("constant_dynamic-slice_fusion.6", 4096, "fusion")]),
    "copy": (
        "  %copy.40 = bf16[1,4096,4096]{1,2,0:T(8,128)(2,1)S(1)} "
        "copy(%constant_dynamic-slice_fusion.6), " + _BODY,
        [("copy.40", 4096, "copy")]),
    # the asynchronous forms carry no op_name
    "copy_start": (
        "  %copy-start.3 = (bf16[1,4096,1536]{2,1,0:T(8,128)(2,1)S(1)}, "
        "bf16[1,4096,1536]{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) "
        "copy-start(%dynamic_slice.143)",
        [("copy-start.3", 1536, "copy-start")]),
    "slice_done": (
        "  %slice-done.2 = bf16[1,4096,1536]{2,1,0:T(8,128)(2,1)S(1)} "
        "slice-done(%slice-start.2)",
        [("slice-done.2", 1536, "slice-done")]),
    # not a layer of a projection stack: a shard's 256-column k_proj,
    # the request's KV view, a fusion outside the loop's body, and a
    # product whose dynamic-slice sits inside it (activations come out)
    "narrow": (
        "  %copy.58 = bf16[1,4096,256]{1,2,0:T(8,128)(2,1)S(1)} "
        "copy(%constant_dynamic-slice_fusion.13), " + _BODY, []),
    "kv_view": (
        "  %slice-done.3 = bf16[8,1,3072,2,128]{4,3,2,1,0:T(2,128)(2,1)"
        "S(1)} slice-done(%slice-start.3)", []),
    "outside_the_body": (
        "  %fusion.3 = bf16[1,4096,4096]{2,1,0:T(8,128)(2,1)} "
        "fusion(%param.1), kind=kLoop, calls=%fused_computation.2, "
        'metadata={op_name="jit(step)/head/dot_general"}', []),
    "read_in_place": (
        "  %fusion.97 = bf16[8,6144]{1,0:T(8,128)(2,1)} fusion("
        "%get-tuple-element.9, %fusion.33, %dynamic_slice.1), "
        "kind=kOutput, calls=%fused_computation.70, " + _BODY, []),
}


@pytest.mark.parametrize("line", _RECORDED)
def test_staged_projection_layers_reads_the_compilers_lines(line):
    text, want = _RECORDED[line]
    assert _staged_projection_layers("HloModule m\n" + text + "\n") == want


@pytest.mark.parametrize("config,chunk,tree", [
    pytest.param(c, n, t, id=f"{c}-{'decode' if n is None else n}-{t}")
    for c, n, t in (
        ("mistral-7b-v0.3-l16", None, "engine"),
        ("mistral-7b-v0.3-tp4", None, "engine"),
        ("mistral-7b-v0.3-l16", 512, "engine"),
        ("mistral-7b-v0.3-tp4", 128, "engine"),
        ("mistral-7b-v0.3-tp4", 512, "engine"),
        # the three leaves of every other caller's tree ARE staged: the
        # engine cases above do not pass by looking in the wrong place
        ("mistral-7b-v0.3-l16", None, "three_leaf"),
        ("mistral-7b-v0.3-tp4", None, "three_leaf"),
        ("mistral-7b-v0.3-l16", 512, "three_leaf"),
        ("mistral-7b-v0.3-tp4", 128, "three_leaf"),
        ("mistral-7b-v0.3-tp4", 512, "three_leaf"))])
def test_layer_loop_reads_the_engines_qkv_stack_in_place(
        topo, monkeypatch, config, chunk, tree):
    """Per layer the compiler copied ``q_proj[l]`` / ``k_proj[l]`` /
    ``v_proj[l]`` out of their stacks into on-chip scratch (memory
    space 1), transposed them there (``{2,1,0}`` -> ``{1,2,0}``) and
    multiplied over the copies: 2.0 ms of a 13.0 ms decode step on the
    chip (PERF.md). Over the one leaf ``qkv_proj`` the product reads its
    layer in place, as ``o_proj``'s always did."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lowered, _ = _lowered_decode_program(topo, config, tree, chunk)
    staged = _staged_projection_layers(lowered.compile().as_text())
    if tree == "engine":
        assert staged == []
    else:
        kinds = [k for _, _, k in staged]
        assert kinds.count("copy") >= 1 and kinds.count("fusion") >= 1, \
            staged


# ---------------------------------------------------------------------------
# decode_variant is compiled reality: what an engine reports of its decode
# program names the launches the program compiled for the chip holds
# ---------------------------------------------------------------------------
DECODE_LAUNCHES = {"paged_attention_decode", "decode_mlp_block"}
ENGINES = {     # id -> (engine options, the launches the chip runs)
    "single_device": ({}, DECODE_LAUNCHES),
    "tp_psum": ({"mesh": ("psum",)}, DECODE_LAUNCHES),
    # the MLP composition: its matmuls see the single-device operands
    "tp_gather": ({"mesh": ("gather",)}, {"paged_attention_decode"}),
    # the attention kernel takes no int8 pool
    "int8_pool": ({"cache_dtype": "int8"}, {"decode_mlp_block"}),
    # expert layers: no decode_mlp_block stage
    "hybrid": ({"hybrid": True}, {"paged_attention_decode"}),
}


def _engine(options):
    """An engine small enough to build here and wide enough for Mosaic
    (head_dim 128, 1024-wide rows), on the CPU's devices."""
    from paddle_tpu.inference import ServingEngine, ServingMesh
    from paddle_tpu.models import granite_hybrid as gh, llama
    options = dict(options)
    if options.pop("hybrid", False):
        cfg = gh.GraniteHybridConfig(
            vocab_size=512, hidden_size=512, intermediate_size=128,
            shared_intermediate_size=256, num_hidden_layers=3,
            layer_types=("mamba", "attention", "mamba"),
            num_attention_heads=4, num_key_value_heads=2,
            num_local_experts=4, num_experts_per_tok=2,
            mamba_n_heads=16, mamba_d_head=64, mamba_d_state=128,
            max_position_embeddings=256)
        params = gh.init_params(cfg)
    else:
        cfg = llama.LlamaConfig(
            vocab_size=512, hidden_size=1024, intermediate_size=4096,
            num_hidden_layers=2, num_attention_heads=8,
            num_key_value_heads=8, max_position_embeddings=256)
        params = llama.init_params(cfg, jax.random.key(0))
    if "mesh" in options:
        options["mesh"] = ServingMesh.make(tp=2,
                                           collective=options["mesh"][0])
    eng = ServingEngine(params, cfg, capacity=8, block_size=16,
                        max_seq_len=128, prefill_buckets=(32,), **options)
    if eng._quant:      # what the first prompt's calibration leaves
        L, KV = cfg.num_hidden_layers, cfg.num_key_value_heads
        eng._kv_scales = (jnp.ones((L, KV)), jnp.ones((L, KV)))
    return eng


def _decode_program_for_the_chip(topo, eng):
    """The engine's own decode program (its one maker, recording what
    dispatch picks as it traces) lowered for described devices: the
    arguments are the audit spec's shapes, placed as the engine places
    them, and a sharded engine's mesh is the described chips'."""
    from jax.sharding import Mesh
    from paddle_tpu.inference import ServingMesh
    (spec,) = [s for s in eng.program_specs(register=False)
               if s.name.startswith("serving_decode")]
    sm = eng._mesh
    if sm is None:
        mesh = Mesh(np.array(topo.devices[:1]), ("one",))
        specs = [P()] * len(spec.args)
    else:
        mesh = Mesh(np.array(topo.devices[:sm.tp]), (sm.axis,))
        eng._mesh = sm = ServingMesh(mesh, sm.axis, sm.collective)
        specs = [sm.param_specs(eng.cfg, eng.params)] + [P()] * 5 \
            + [sm.pool_spec] * 2

    def place(tree, spec):      # one spec for every leaf, or a tree
        if isinstance(spec, P):
            spec = jax.tree_util.tree_map(lambda _: spec, tree)
        return jax.tree_util.tree_map(
            lambda v, s: jax.ShapeDtypeStruct(
                v.shape, v.dtype, sharding=NamedSharding(mesh, s)),
            tree, spec)

    args = [place(a, s) for a, s in zip(spec.args, specs)]
    return eng._make_decode_fn().lower(*args).compile()


@pytest.mark.parametrize("engine", ENGINES)
def test_decode_variant_names_the_compiled_launches(topo, monkeypatch,
                                                    engine):
    options, launches = ENGINES[engine]
    eng = _engine(options)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    found = _kernels(_decode_program_for_the_chip(topo, eng))
    v = eng.decode_variant
    assert set(v["operands"]) == found & DECODE_LAUNCHES == launches
    assert (v["attn"], v["mlp"]) == (
        "pallas" if "paged_attention_decode" in found else "xla",
        "pallas_fused" if "decode_mlp_block" in found else "unfused")
    # the dense engines keep q/k/v as one leaf; the hybrid model's tree
    # has no such stacks
    assert v["qkv"] == ("per_leaf" if engine == "hybrid" else "fused_stack")
    assert eng.counters["decode_traces"] == 1
