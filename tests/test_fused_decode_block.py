"""Fused decode-block megakernels (ops/pallas/fused_decode_block.py),
the kernel registry (ops/pallas/registry.py), and the PR's satellites
(autotune-cache robustness, per-kernel bench gate, paged-decode
pages-per-step tuning).

Parity contract: wherever registry dispatch selects the ``unfused``
composition (always on CPU/interpret), the fused decode step is
BIT-identical to the pre-fusion ``_paged_decode_step`` — asserted
through a >=20-request ServingEngine stream and at the step level.
The Pallas megakernels themselves (forced, interpret mode) match the
composition to fp32 roundoff across randomized shapes, fp32 and int8
cache.
"""
import functools
import importlib.util
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.models import llama
from paddle_tpu.inference import GenerationConfig, ServingEngine
from paddle_tpu.inference.generation import (_fused_decode_step,
                                             _fused_mode,
                                             _paged_decode_step,
                                             generate_paged)
from paddle_tpu.ops.pallas import fused_decode_block as fdb
from paddle_tpu.ops.pallas.registry import KernelRegistry

pytestmark = pytest.mark.fused

CFG = llama.LlamaConfig(vocab_size=97, hidden_size=64,
                        intermediate_size=128, num_hidden_layers=2,
                        num_attention_heads=4, num_key_value_heads=2,
                        max_position_embeddings=128, dtype=jnp.float32,
                        remat=False)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(0), dtype=jnp.float32)


def _engine(params, **kw):
    kw.setdefault("capacity", 3)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("max_seq_len", 64)
    return ServingEngine(params, CFG, **kw)


def _rope_tables(T, hd):
    inv = 1.0 / (10000.0 ** (np.arange(0, hd, 2) / hd))
    t = np.arange(T)[:, None] * inv[None, :]
    return jnp.asarray(np.sin(t), jnp.float32), \
        jnp.asarray(np.cos(t), jnp.float32)


def _attn_case(rng, B, D, KV, groups, hd, BS, MB, quant=False):
    H = KV * groups
    N = B * MB + 2
    dt = jnp.float32
    mk = lambda *s: jnp.asarray(rng.randn(*s) * 0.07, dt)  # noqa: E731
    x = mk(B, D)
    nw = jnp.asarray(rng.rand(D) + 0.5, dt)
    wq, wk, wv = mk(D, H * hd), mk(D, KV * hd), mk(D, KV * hd)
    wo = mk(H * hd, D)
    sin, cos = _rope_tables(BS * MB, hd)
    bt = jnp.asarray(rng.permutation(N)[:B * MB].reshape(B, MB),
                     jnp.int32)
    # one slot mid-page, one empty (seq_len 0: only the new token), one
    # page-aligned when B allows
    lens = [int(rng.randint(1, BS * MB)), 0] + \
        [int(rng.randint(0, BS * MB)) for _ in range(B - 2)]
    lens = jnp.asarray(lens[:B], jnp.int32)
    if quant:
        kp = jnp.asarray(rng.randint(-127, 128, (N, BS, KV, hd)),
                         jnp.int8)
        vp = jnp.asarray(rng.randint(-127, 128, (N, BS, KV, hd)),
                         jnp.int8)
        scales = (jnp.asarray(rng.rand(KV) * 0.1 + 0.01, jnp.float32),
                  jnp.asarray(rng.rand(KV) * 0.1 + 0.01, jnp.float32))
    else:
        kp, vp = mk(N, BS, KV, hd), mk(N, BS, KV, hd)
        scales = None
    return (x, nw, wq, wk, wv, wo, sin, cos, kp, vp, bt, lens), scales


# ---------------------------------------------------------------------------
# kernel-level parity (forced Pallas, interpret mode) — randomized shapes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_attn_block_parity_randomized(seed):
    rng = np.random.RandomState(seed)
    B = int(rng.randint(1, 4))
    KV = int(rng.choice([1, 2, 4]))
    groups = int(rng.choice([1, 2, 3]))
    hd = int(rng.choice([8, 16, 32]))
    BS = int(rng.choice([4, 8, 16]))
    MB = int(rng.randint(2, 5))
    D = int(rng.choice([32, 48, 64]))
    args, _ = _attn_case(rng, B, D, KV, groups, hd, BS, MB)
    xf, kf, vf = fdb.fused_attn_block_pallas(*args)
    xr, kr, vr = fdb.attn_block_ref(*args)
    np.testing.assert_allclose(np.asarray(xf), np.asarray(xr),
                               atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(kf), np.asarray(kr),
                               atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(vf), np.asarray(vr),
                               atol=2e-5, rtol=1e-5)


def test_attn_block_parity_int8_cache():
    rng = np.random.RandomState(3)
    args, scales = _attn_case(rng, B=2, D=64, KV=2, groups=2, hd=16,
                              BS=8, MB=3, quant=True)
    xf, kf, vf = fdb.fused_attn_block_pallas(*args, kv_scales=scales)
    xr, kr, vr = fdb.attn_block_ref(*args, kv_scales=scales)
    # the fused kernel folds dequant(quant(new K/V)) in VMEM; the ref
    # reads the same values back from the int8 pool — fp32 roundoff only
    np.testing.assert_allclose(np.asarray(xf), np.asarray(xr),
                               atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(kf), np.asarray(kr),
                               atol=2e-5, rtol=1e-5)


def test_attn_block_pages_per_step_invariant():
    """pages_per_step only changes pipelining: pages are still processed
    sequentially in order, so the online softmax is bit-identical."""
    rng = np.random.RandomState(4)
    args, _ = _attn_case(rng, B=2, D=32, KV=2, groups=2, hd=16, BS=4,
                         MB=4)
    outs = [fdb.fused_attn_block_pallas(*args, pages_per_step=pp)[0]
            for pp in (1, 2, 4)]
    np.testing.assert_array_equal(np.asarray(outs[0]),
                                  np.asarray(outs[1]))
    np.testing.assert_array_equal(np.asarray(outs[0]),
                                  np.asarray(outs[2]))


@pytest.mark.parametrize("D,F", [(32, 64), (64, 256), (48, 96)])
def test_mlp_block_parity(D, F):
    rng = np.random.RandomState(D + F)
    dt = jnp.float32
    mk = lambda *s: jnp.asarray(rng.randn(*s) * 0.07, dt)  # noqa: E731
    x, nw = mk(3, D), jnp.asarray(rng.rand(D) + 0.5, dt)
    wg, wu, wd = mk(D, F), mk(D, F), mk(F, D)
    got = fdb.fused_mlp_block_pallas(x, nw, wg, wu, wd)
    want = fdb.mlp_block_ref(x, nw, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)
    # tiling over F changes only the accumulation grouping (fp32 acc)
    tiled = fdb.fused_mlp_block_pallas(x, nw, wg, wu, wd,
                                       block_f=F // 2)
    np.testing.assert_allclose(np.asarray(tiled), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


def test_mlp_candidates_divide_evenly():
    """A ragged last tile would multiply garbage columns into the
    accumulator — candidates must divide F exactly."""
    for F in (96, 128, 512, 1024, 4096):
        cands = fdb._mlp_candidates(F)
        assert cands, F
        assert all(F % c == 0 for c in cands), (F, cands)
    assert fdb._mlp_candidates(100) == [100]   # no divisor candidate


# ---------------------------------------------------------------------------
# single-launch decode block (r20): kernel parity, dispatch contract,
# mode="block" plumbing
# ---------------------------------------------------------------------------
def _block_case(rng, wq_bits=0, quant=False):
    """Full-block args at the clamp-edge decode shapes: the attention
    case above + post-norm and SwiGLU weights (ragged F), the weight
    tree optionally PTQ-quantized (down_proj packs its F rows)."""
    B, D, KV, groups, hd, BS, MB, F = 2, 32, 2, 1, 16, 8, 3, 96
    args, scales = _attn_case(rng, B, D, KV, groups, hd, BS, MB,
                              quant=quant)
    (x, nw, wq, wk, wv, wo, sin, cos, kp, vp, bt, lens) = args
    mk = lambda *s: jnp.asarray(rng.randn(*s) * 0.07,    # noqa: E731
                                jnp.float32)
    pw = jnp.asarray(rng.rand(D) + 0.5, jnp.float32)
    wg, wu, wd = mk(D, F), mk(D, F), mk(F, D)
    ws = (wq, wk, wv, wo, wg, wu, wd)
    if wq_bits:
        from paddle_tpu.quantization import ptq as _ptq
        ws = tuple(_ptq.quantize_leaf(w, wq_bits)
                   for w in (wq, wk, wv, wo, wg, wu)) \
            + (_ptq.quantize_leaf(wd, wq_bits, pack_axis=1),)
    return (x, nw, ws[0], ws[1], ws[2], ws[3], pw, ws[4], ws[5],
            ws[6], sin, cos, kp, vp, bt, lens), scales


@pytest.mark.parametrize("wq_bits", [0, 8, 4], ids=["fp", "w8", "w4"])
def test_decode_block_single_launch_parity(wq_bits):
    """The single-launch megakernel (forced, interpret) matches the
    priority-0 composed route to fp32 roundoff — the attn->MLP residual
    handoff through f32 VMEM scratch changes only op grouping. Plain,
    int8 and packed-int4 weight trees."""
    rng = np.random.RandomState(20 + wq_bits)
    full, _ = _block_case(rng, wq_bits=wq_bits)
    got = fdb.fused_decode_block_pallas(*full, pages_per_step=2,
                                        block_f=32)
    want = fdb.decode_block_composed(*full)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=5e-5, rtol=1e-5)


def test_decode_block_parity_int8_pool_and_tunable_invariance():
    """int8 KV pool (dequant in VMEM, scales per head) and the joint
    (pages_per_step, block_f) tunables: every choice is the same math
    to fp32 roundoff."""
    rng = np.random.RandomState(30)
    full, scales = _block_case(rng, quant=True)
    want = fdb.decode_block_composed(*full, kv_scales=scales)
    for pp, bf in ((1, 96), (2, 32), (4, 48)):
        got = fdb.fused_decode_block_pallas(*full, kv_scales=scales,
                                            pages_per_step=pp,
                                            block_f=bf)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=5e-5, rtol=1e-5,
                                       err_msg=f"pp={pp} bf={bf}")


def test_block_dispatch_flagship_weight_quant_contract():
    """The acceptance bar: at the flagship serving class the combined
    bf16 attn+MLP windows exceed the scoped-VMEM envelope (two-kernel
    composed route, reason naming the envelope), while int8/int4 weight
    variants fit and dispatch the single-launch kernel."""
    from paddle_tpu.ops.pallas.registry import KERNELS

    def m(wq=None):
        meta = fdb.decode_meta_dims(8, 1024, 16, 16, 64, 4096, 16, 24,
                                    jnp.bfloat16, jnp.bfloat16, False,
                                    weight_dtype=wq)
        meta["interpret"] = False
        return meta
    assert KERNELS.dispatch("decode_block_fused", m())[0] == "composed"
    assert KERNELS.dispatch("decode_block_fused",
                            m("int8"))[0] == "pallas_block"
    assert KERNELS.dispatch("decode_block_fused",
                            m("int4"))[0] == "pallas_block"
    rej = [r for r in KERNELS.explain("decode_block_fused", m())
           if r["name"] == "pallas_block"][0]
    assert not rej["supported"] and "envelope" in rej["reason"]


def test_block_mode_resolver_contract():
    """mode='block' pins the single-launch kernel through
    resolve_decode_step; auto on CPU keeps the composed tier (per-stage
    fns, bit parity); the two-stage resolver refuses 'block' with a
    pointer at resolve_decode_step."""
    meta = fdb.decode_meta(CFG, B=2, BS=4, MB=4,
                           pool_dtype=jnp.float32, quant=False)
    b_fn, a_fn, m_fn, names = fdb.resolve_decode_step(meta, "block")
    assert b_fn is not None and a_fn is None and m_fn is None
    assert names == {"block": "pallas_block", "attn": "pallas_block",
                     "mlp": "pallas_block"}
    b_fn, a_fn, m_fn, names = fdb.resolve_decode_step(meta, "auto")
    assert b_fn is None and a_fn is not None and m_fn is not None
    assert names == {"block": "composed", "attn": "unfused",
                     "mlp": "unfused"}
    with pytest.raises(ValueError, match="resolve_decode_step"):
        fdb.resolve_decode_blocks(meta, "block")
    with pytest.raises(ValueError, match="auto|pallas|ref|block"):
        fdb.resolve_decode_step(meta, "bogus")
    assert _fused_mode("block") == "block"


def test_paged_decode_pages_per_step_invariant():
    """Satellite: the unfused paged-decode kernel's pages-per-step is an
    autotune candidate now — every choice must stay bit-identical."""
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_pallas)
    rng = np.random.RandomState(5)
    B, H, KV, hd, BS, MB, N = 3, 4, 2, 16, 4, 4, 14
    q = jnp.asarray(rng.randn(B, H, hd) * 0.1, jnp.float32)
    kp = jnp.asarray(rng.randn(N, BS, KV, hd) * 0.1, jnp.float32)
    vp = jnp.asarray(rng.randn(N, BS, KV, hd) * 0.1, jnp.float32)
    bt = jnp.asarray(rng.permutation(N)[:B * MB].reshape(B, MB),
                     jnp.int32)
    lens = jnp.asarray([0, 7, BS * MB - 1], jnp.int32)
    outs = [np.asarray(paged_attention_decode_pallas(
        q, kp, vp, bt, lens, pages_per_step=pp)) for pp in (1, 2, 4)]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


# ---------------------------------------------------------------------------
# registry dispatch
# ---------------------------------------------------------------------------
def test_registry_priority_and_fallback():
    reg = KernelRegistry()
    reg.register("op", "fast", lambda: "fast", priority=10,
                 supports=lambda m: (m["n"] < 8, "n too big"))
    reg.register("op", "ref", lambda: "ref", priority=0)
    assert reg.dispatch("op", {"n": 4})[0] == "fast"
    assert reg.dispatch("op", {"n": 100})[0] == "ref"
    ex = reg.explain("op", {"n": 100})
    assert [e["name"] for e in ex] == ["fast", "ref"]
    assert not ex[0]["supported"] and ex[0]["reason"] == "n too big"
    assert ex[1]["selected"]


def test_registry_latest_wins_and_errors():
    reg = KernelRegistry()
    reg.register("op", "v", lambda: 1)
    reg.register("op", "v", lambda: 2)          # replaces, no duplicate
    assert len(reg.variants("op")) == 1
    assert reg.variant("op", "v").fn() == 2
    with pytest.raises(KeyError):
        reg.dispatch("missing", {})
    with pytest.raises(KeyError):
        reg.variant("op", "nope")
    reg.register("op2", "only", lambda: 0, supports=lambda m: False)
    with pytest.raises(RuntimeError, match="no variant"):
        reg.dispatch("op2", {})


def test_registry_force_stacks():
    reg = KernelRegistry()
    reg.register("op", "a", lambda: "a", priority=10)
    reg.register("op", "b", lambda: "b", priority=0)
    assert reg.dispatch("op", {})[0] == "a"
    with reg.force("op", "b"):
        assert reg.dispatch("op", {})[0] == "b"
        with reg.force("op", "a"):
            assert reg.dispatch("op", {})[0] == "a"
        assert reg.dispatch("op", {})[0] == "b"
    assert reg.dispatch("op", {})[0] == "a"
    with pytest.raises(KeyError):
        reg.force("op", "typo")


def test_dispatch_interpret_falls_back_unfused():
    """On CPU (interpret mode) auto dispatch must select the unfused
    composition — that is what makes the engine parity exact."""
    meta = fdb.decode_meta(CFG, B=2, BS=4, MB=4,
                           pool_dtype=jnp.float32, quant=False)
    assert meta["interpret"]
    attn_fn, mlp_fn, names = fdb.resolve_decode_blocks(meta, "auto")
    assert names == {"attn": "unfused", "mlp": "unfused"}
    assert attn_fn is fdb.attn_block_ref
    assert mlp_fn is fdb.mlp_block_ref
    # forcing still returns the Pallas variants (tests / audit catalog)
    _, _, forced = fdb.resolve_decode_blocks(meta, "pallas")
    assert forced == {"attn": "pallas_fused", "mlp": "pallas_fused"}
    with pytest.raises(ValueError, match="auto|pallas|ref"):
        fdb.resolve_decode_blocks(meta, "bogus")


def test_vmem_budget_gates_fused_variant(monkeypatch):
    """Oversized block weights must fail the ``supports`` predicate with
    a reason naming the VMEM budget, even off interpret mode. The
    budget rides IN the meta (decode_meta reads the env at build time —
    i.e. at trace time, when the _PAGED_CACHE route key is computed),
    so the shrunken-budget meta is rebuilt the way a retrace would."""
    meta = fdb.decode_meta(CFG, B=2, BS=4, MB=4,
                           pool_dtype=jnp.float32, quant=False)
    meta["interpret"] = False
    assert meta["vmem_budget"] == fdb._vmem_budget()
    ok, why = fdb._supports_attn(dict(meta))
    assert ok, why                               # tiny cfg fits
    monkeypatch.setenv("PADDLE_TPU_FUSED_VMEM_BUDGET", "1024")
    meta = fdb.decode_meta(CFG, B=2, BS=4, MB=4,
                           pool_dtype=jnp.float32, quant=False)
    meta["interpret"] = False
    assert meta["vmem_budget"] == 1024
    ok, why = fdb._supports_attn(dict(meta))
    assert not ok and "VMEM" in why
    ok, why = fdb._supports_mlp(dict(meta))
    assert not ok and "VMEM" in why


# ---------------------------------------------------------------------------
# decode-step + engine parity (the acceptance bar)
# ---------------------------------------------------------------------------
def _step_inputs(params, rng, B=2, BS=4, MB=4, quant=False):
    L = CFG.num_hidden_layers
    KV, hd = CFG.num_key_value_heads, CFG.head_dim
    N = B * MB + 1
    if quant:
        kp = jnp.asarray(rng.randint(-127, 128, (L, N, BS, KV, hd)),
                         jnp.int8)
        vp = jnp.asarray(rng.randint(-127, 128, (L, N, BS, KV, hd)),
                         jnp.int8)
        scales = (
            jnp.asarray(rng.rand(L, KV) * 0.1 + 0.01, jnp.float32),
            jnp.asarray(rng.rand(L, KV) * 0.1 + 0.01, jnp.float32))
    else:
        kp = jnp.asarray(rng.randn(L, N, BS, KV, hd) * 0.1, jnp.float32)
        vp = jnp.asarray(rng.randn(L, N, BS, KV, hd) * 0.1, jnp.float32)
        scales = None
    tok = jnp.asarray(rng.randint(0, 97, (B,)), jnp.int32)
    bt = jnp.asarray(rng.permutation(N)[:B * MB].reshape(B, MB),
                     jnp.int32)
    lens = jnp.asarray([5, 0][:B], jnp.int32)
    return tok, kp, vp, bt, lens, scales


@pytest.mark.parametrize("quant", [False, True],
                         ids=["fp32", "int8"])
def test_fused_step_bit_parity_and_pallas_closeness(params, quant):
    """mode='auto' (composition on CPU) is BIT-identical to the
    pre-fusion step; mode='pallas' (forced megakernels, interpret)
    matches to fp32 roundoff — fp32 and int8 cache."""
    rng = np.random.RandomState(6 + quant)
    tok, kp, vp, bt, lens, scales = _step_inputs(params, rng,
                                                 quant=quant)
    lg0, kp0, vp0 = _paged_decode_step(params, tok, CFG, kp, vp, bt,
                                       lens, kv_scales=scales)
    lg1, kp1, vp1 = _fused_decode_step(params, tok, CFG, kp, vp, bt,
                                       lens, kv_scales=scales,
                                       mode="auto")
    np.testing.assert_array_equal(np.asarray(lg0), np.asarray(lg1))
    np.testing.assert_array_equal(np.asarray(kp0), np.asarray(kp1))
    np.testing.assert_array_equal(np.asarray(vp0), np.asarray(vp1))
    lg2, kp2, vp2 = _fused_decode_step(params, tok, CFG, kp, vp, bt,
                                       lens, kv_scales=scales,
                                       mode="pallas")
    np.testing.assert_allclose(np.asarray(lg2), np.asarray(lg0),
                               atol=5e-5, rtol=1e-5)
    # the megakernel's QKV+rope op order differs from the composition
    # by fp32 roundoff, so the written pool values are 1-ulp close (and
    # EXACTLY equal under int8, where quantization re-snaps them)
    assert_pool = np.testing.assert_array_equal if quant else \
        functools.partial(np.testing.assert_allclose, atol=1e-6,
                          rtol=1e-5)
    assert_pool(np.asarray(kp2), np.asarray(kp0))
    assert_pool(np.asarray(vp2), np.asarray(vp0))


@pytest.mark.parametrize("cdt", [None, "int8"], ids=["fp32", "int8"])
def test_engine_stream_fused_vs_unfused_bit_parity(params, cdt):
    """>=20-request mixed-length greedy stream: the fused-decode engine
    (default-on flag) must produce bit-identical tokens to an engine
    pinned to the pre-fusion step, and keep the zero-retrace steady
    state (1 decode program, <=1 trace per prefill bucket)."""
    rng = np.random.RandomState(7)
    specs = [(int(rng.randint(3, 15)), int(rng.randint(2, 6)))
             for _ in range(22)]
    prompts = [rng.randint(0, 97, (S,)).astype(np.int32)
               for S, _ in specs]

    def run(fused):
        eng = _engine(params, cache_dtype=cdt, fused_decode=fused)
        rs = [eng.submit(p, GenerationConfig(max_new_tokens=N,
                                             greedy=True))
              for p, (_, N) in zip(prompts, specs)]
        eng.drain()
        assert all(r.done for r in rs)
        return eng, [r.tokens for r in rs]

    eng_f, toks_f = run(None)      # flag default: fused auto
    eng_u, toks_u = run(False)     # pinned pre-fusion step
    assert toks_f == toks_u
    c = eng_f.counters
    assert c["requests_completed"] == 22
    assert c["decode_traces"] == 1, c
    assert set(c["prefill_traces"]) <= {8, 16}
    assert all(n <= 1 for n in c["prefill_traces"].values()), c
    assert eng_f.metrics()["decode_variant"]["mode"] == "auto"
    assert eng_u.decode_variant == {"mode": "unfused",
                                    "block": "composed",
                                    "attn": "unfused",
                                    "mlp": "unfused",
                                    "operands": {}}


def test_engine_forced_pallas_smoke(params):
    """fused_decode='pallas' runs the actual megakernel decode program
    (interpret mode on CPU) end to end and names its program spec for
    the audit gate."""
    eng = _engine(params, capacity=2, prefill_buckets=(8,),
                  fused_decode="pallas")
    assert eng.decode_variant == {"mode": "pallas",
                                  "block": "composed",
                                  "attn": "pallas_fused",
                                  "mlp": "pallas_fused",
                                  "operands": {
                                      "decode_attn_block": "slice",
                                      "decode_mlp_block": "index"}}
    assert any(s.name == "serving_decode_fused"
               for s in eng.program_specs(register=False))
    rng = np.random.RandomState(8)
    rs = [eng.submit(rng.randint(0, 97, (6,)).astype(np.int32),
                     GenerationConfig(max_new_tokens=3, greedy=True))
          for _ in range(2)]
    eng.drain()
    assert all(r.done and len(r.tokens) == 3 for r in rs)
    assert eng.counters["decode_traces"] == 1


def test_engine_forced_block_smoke(params):
    """fused_decode='block' runs the single-launch decode program end
    to end (interpret mode on CPU), names the serving_decode_block spec
    for the audit gate, and its greedy tokens match the auto engine
    (the composed tier the block kernel is a roundoff variant of)."""
    eng = _engine(params, capacity=2, prefill_buckets=(8,),
                  fused_decode="block")
    assert eng.decode_variant == {"mode": "block",
                                  "block": "pallas_block",
                                  "attn": "pallas_block",
                                  "mlp": "pallas_block",
                                  "operands": {
                                      "decode_block_fused": "slice"}}
    assert any(s.name == "serving_decode_block"
               for s in eng.program_specs(register=False))
    rng = np.random.RandomState(12)
    prompts = [rng.randint(0, 97, (6,)).astype(np.int32)
               for _ in range(2)]
    g = GenerationConfig(max_new_tokens=3, greedy=True)
    rs = [eng.submit(p, g) for p in prompts]
    eng.drain()
    assert all(r.done and len(r.tokens) == 3 for r in rs)
    assert eng.counters["decode_traces"] == 1
    eng_a = _engine(params, capacity=2, prefill_buckets=(8,))
    rs_a = [eng_a.submit(p, g) for p in prompts]
    eng_a.drain()
    assert [r.tokens for r in rs] == [r.tokens for r in rs_a]


def test_block_mode_is_single_device(params):
    """The single-launch kernel runs outside shard_map: a mesh engine
    pinned to 'block' is rejected at construction, and the TP decode
    body refuses the mode before tracing anything."""
    from paddle_tpu.inference import ServingMesh
    from paddle_tpu.inference import tp as tp_mod
    with pytest.raises(ValueError, match="single-device"):
        _engine(params, mesh=ServingMesh.make(tp=2),
                fused_decode="block")
    with pytest.raises(ValueError, match="single-device"):
        tp_mod._tp_decode_step(params, None, CFG, None, None, None,
                               None, fused="block")


def test_generate_paged_fused_flag_parity(params):
    rng = np.random.RandomState(9)
    prompts = jnp.asarray(rng.randint(0, 97, (2, 8)), jnp.int32)
    g = GenerationConfig(max_new_tokens=6, greedy=True)
    base = np.asarray(generate_paged(params, prompts, CFG, g,
                                     fused_decode=False))
    fused = np.asarray(generate_paged(params, prompts, CFG, g))
    np.testing.assert_array_equal(base, fused)
    # the forced single-launch route decodes the same greedy tokens
    # (roundoff-level logits variant of the composition)
    block = np.asarray(generate_paged(params, prompts, CFG, g,
                                      fused_decode="block"))
    np.testing.assert_array_equal(base, block)
    with pytest.raises(ValueError, match="fused_decode"):
        _fused_mode("bogus")
    assert _fused_mode(None) == "auto"       # flag defaults on
    assert _fused_mode(True) == "auto"
    assert _fused_mode(False) is False


# ---------------------------------------------------------------------------
# satellite: autotune-cache robustness
# ---------------------------------------------------------------------------
def test_autotune_cache_discards_corrupt_file(tmp_path):
    from paddle_tpu.ops.pallas.autotune import AutotuneCache
    p = tmp_path / "autotune.json"
    p.write_text('{"k": 1')                     # truncated write
    with pytest.warns(RuntimeWarning, match="corrupt autotune cache"):
        cache = AutotuneCache(str(p))
        assert cache.get("k") is None
    cache.put("k2", 3)                          # rewrites a clean cache
    assert json.loads(p.read_text()) == {"k2": 3}


def test_autotune_cache_discards_wrong_shape(tmp_path):
    from paddle_tpu.ops.pallas.autotune import AutotuneCache
    p = tmp_path / "autotune.json"
    p.write_text("[1, 2, 3]")                   # valid JSON, not a dict
    with pytest.warns(RuntimeWarning, match="corrupt autotune cache"):
        assert AutotuneCache(str(p)).get("k") is None


def test_autotune_cache_atomic_write(tmp_path):
    """put() must publish via temp + os.replace: the cache file is a
    complete JSON document at every point and no temp files leak."""
    from paddle_tpu.ops.pallas.autotune import AutotuneCache
    p = tmp_path / "autotune.json"
    cache = AutotuneCache(str(p))
    for i in range(5):
        cache.put(f"k{i}", i)
        assert json.loads(p.read_text()) == {f"k{j}": j
                                             for j in range(i + 1)}
    assert not list(tmp_path.glob("*.tmp"))
    fresh = AutotuneCache(str(p))               # round-trips
    assert fresh.get("k3") == 3


# ---------------------------------------------------------------------------
# satellite: per-kernel bench regression gate
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def gate():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "kernel_bench_gate.py")
    spec = importlib.util.spec_from_file_location("kernel_bench_gate",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bank(tmp, name, cases, wrap_parsed=False):
    doc = {"kernels": {"cases": cases}}
    if wrap_parsed:
        doc = {"parsed": doc}
    (tmp / name).write_text(json.dumps(doc))


def test_gate_flags_regression(gate, tmp_path):
    _bank(tmp_path, "BENCH_r01.json",
          {"k1": {"us_pallas": 100.0}, "k2": {"us_pallas": 50.0}})
    cap = {"kernels": {"cases": {"k1": {"us_pallas": 200.0},
                                 "k2": {"us_pallas": 55.0},
                                 "k3": {"us_pallas": 10.0}}}}
    res = gate.gate_capture(cap, threshold=0.30, repo=str(tmp_path))
    assert res["status"] == "regressed"
    assert set(res["regressions"]) == {"k1"}     # k2: +10% < threshold
    assert res["regressions"]["k1"]["ratio"] == 2.0
    assert res["new"] == ["k3"]
    assert res["checked"] == 2


def test_gate_best_across_trajectory_and_parsed_wrapper(gate, tmp_path):
    """The reference is the trajectory's MINIMUM, including captures
    wrapped under BENCH_rNN's 'parsed' key."""
    _bank(tmp_path, "BENCH_r01.json", {"k1": {"us_pallas": 100.0}})
    _bank(tmp_path, "BENCH_r02.json", {"k1": {"us_pallas": 80.0}},
          wrap_parsed=True)
    cap = {"kernels": {"cases": {"k1": {"us_pallas": 99.0}}}}
    res = gate.gate_capture(cap, threshold=0.2, repo=str(tmp_path))
    assert res["status"] == "regressed"          # 99 vs best 80 = 1.24x
    assert res["regressions"]["k1"]["banked_best"] == 80.0
    res = gate.gate_capture(cap, threshold=0.3, repo=str(tmp_path))
    assert res["status"] == "pass"


def test_gate_skips_without_reference(gate, tmp_path):
    cap = {"kernels": {"cases": {"k1": {"us_pallas": 10.0}}}}
    assert gate.gate_capture(cap, repo=str(tmp_path))["status"] == \
        "no_reference"
    _bank(tmp_path, "BENCH_r01.json", {"k1": {"us_pallas": 100.0}})
    interp = {"kernels": {"interpret": True,
                          "cases": {"k1": {"us_pallas": 900.0}}}}
    assert gate.gate_capture(interp, repo=str(tmp_path))["status"] == \
        "no_reference"                           # interpret: no timing


def test_gate_names_skipped_keys_instead_of_bare_pass(gate, tmp_path):
    """Trajectory files exist but share no kernel key with the capture:
    the gate must say exactly which keys it skipped (and exit 0 as a
    SKIP, not report a vacuous pass), and a partial overlap must list
    the banked keys the capture stopped timing."""
    _bank(tmp_path, "BENCH_r01.json", {"old_kernel": {"us_pallas": 50.0},
                                       "k1": {"us_pallas": 100.0}})
    cap = {"kernels": {"cases": {"renamed": {"us_pallas": 10.0}}}}
    res = gate.gate_capture(cap, repo=str(tmp_path))
    assert res["status"] == "no_reference"
    assert "k1" in res["note"] and "renamed" in res["note"]
    assert res["skipped_banked"] == ["k1", "old_kernel"]
    # partial overlap: gate runs, but the dropped key is named
    cap = {"kernels": {"cases": {"k1": {"us_pallas": 90.0}}}}
    res = gate.gate_capture(cap, repo=str(tmp_path))
    assert res["status"] == "pass" and res["checked"] == 1
    assert res["skipped_banked"] == ["old_kernel"]


def test_gate_cli_exit_codes(gate, tmp_path):
    _bank(tmp_path, "BENCH_r01.json", {"k1": {"us_pallas": 100.0}})
    cap = tmp_path / "fresh.json"
    out = tmp_path / "gate.json"
    cap.write_text(json.dumps(
        {"kernels": {"cases": {"k1": {"us_pallas": 300.0}}}}))
    rc = gate.main(["--capture", str(cap), "--repo", str(tmp_path),
                    "--json", str(out), "--quiet"])
    assert rc == 1
    assert json.loads(out.read_text())["status"] == "regressed"
    cap.write_text(json.dumps(
        {"kernels": {"cases": {"k1": {"us_pallas": 90.0}}}}))
    assert gate.main(["--capture", str(cap), "--repo", str(tmp_path),
                      "--quiet"]) == 0
    assert gate.main(["--quiet"]) == 3           # no --capture
    assert gate.main(["--capture", str(tmp_path / "missing.json"),
                      "--quiet"]) == 3
