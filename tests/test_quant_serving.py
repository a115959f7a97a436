"""Quantized serving (r18): int8/int4 weights on the decode + prefill
hot paths — the PTQ harness (quantization/ptq.py), the quantized-weight
megakernel variants (fused_decode_block / fused_prefill_block), and the
engine/generate routing behind ``weight_quant=``.

Parity contract: wherever dispatch selects the ``unfused`` composition
(always on CPU/interpret), the quantized route is BIT-identical to
dequantize-then-matmul by construction (every unfused matmul site goes
through the ONE ``maybe_dequantize`` helper). The Pallas megakernels
themselves (forced, interpret mode) dequantize in-register in the
matmul epilogue and match the composition to fp32 roundoff. int8
weights hold greedy output within a small documented flip budget vs fp
on the engine stream; int4 is a bandwidth/accuracy trade the bench
quantifies (random un-finetuned test weights flip far more than real
checkpoints — only int8 carries an engine-level budget here).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.models import llama
from paddle_tpu.inference import GenerationConfig, ServingEngine
from paddle_tpu.inference.generation import (_decode_step,
                                             generate_paged)
from paddle_tpu.ops.pallas import fused_decode_block as fdb
from paddle_tpu.ops.pallas import fused_prefill_block as fpb
from paddle_tpu.quantization import ptq, quanters

pytestmark = pytest.mark.quant

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


# ---------------------------------------------------------------------------
# quanters: pack/unpack round trip + the fixed scale contract
# ---------------------------------------------------------------------------
def test_int4_pack_unpack_byte_roundtrip():
    rng = np.random.RandomState(0)
    q = rng.randint(-7, 8, (12, 10)).astype(np.int8)
    for axis in (0, 1):
        p = quanters.pack_int4(q, axis=axis)
        assert p.dtype == np.int8
        assert p.shape[axis] == q.shape[axis] // 2
        u = np.asarray(quanters.unpack_int4(p, axis=axis))
        np.testing.assert_array_equal(u, q)
    # packing an ODD axis is a structural error, not silent truncation
    with pytest.raises(ValueError, match="odd"):
        quanters.pack_int4(q[:11], axis=0)


def test_quantize_scale_contract_flat_f32_symmetric():
    """The kernel contract the satellite fixed: per-OUTPUT-channel FLAT
    f32 scales (no keepdims) and a symmetric integer range."""
    rng = np.random.RandomState(1)
    w = rng.randn(16, 6).astype(np.float32)
    q8, s8 = quanters.quantize_to_int8(w, axis=-1)
    assert s8.shape == (6,) and s8.dtype == np.float32
    assert q8.min() >= -127 and q8.max() <= 127
    q4, s4 = quanters.quantize_to_int4(w, axis=-1)
    assert s4.shape == (6,) and q4.min() >= -7 and q4.max() <= 7
    # dequant error bounded by half a step per channel
    assert np.all(np.abs(q8 * s8[None] - w) <= s8[None] / 2 + 1e-7)
    # int8_matmul consumes the flat scales directly
    x = rng.randn(8, 16).astype(np.float32)
    xs = np.abs(x).max() / 127.0
    xq = np.clip(np.round(x / xs), -127, 127).astype(np.int8)
    out = np.asarray(quanters.int8_matmul(jnp.asarray(xq),
                                          jnp.asarray(q8), xs, s8))
    assert out.shape == (8, 6)
    rel = np.abs(out - x @ w).max() / np.abs(x @ w).max()
    assert rel < 0.05


def test_dequantize_weight_infers_pack_axis():
    """down_proj packs its OUTPUT axis; everything else packs the
    contraction axis — dequantize_weight must reconstruct both from
    the byte-count/scale-length relation alone."""
    rng = np.random.RandomState(2)
    w = rng.randn(8, 6).astype(np.float32)
    for pack_axis in (0, 1):
        leaf = ptq.quantize_leaf(w, 4, pack_axis=pack_axis)
        deq = np.asarray(quanters.dequantize_weight(leaf))
        assert deq.shape == w.shape
        step = np.asarray(leaf["scale"])[None, :]
        assert np.all(np.abs(deq - w) <= step / 2 + 1e-7)


# ---------------------------------------------------------------------------
# PTQ harness
# ---------------------------------------------------------------------------
def test_ptq_tree_structure_and_mode_detection(params):
    assert ptq.weight_quant_mode(params) is None
    for bits, mode in ((8, "int8"), (4, "int4")):
        qp = ptq.quantize_weights(params, bits=bits)
        assert ptq.weight_quant_mode(qp) == mode
        layers = qp["layers"]
        for k, pack_axis in ptq.WQ_KEYS.items():
            leaf = layers[k]
            qkey = "qw8" if bits == 8 else "qw4"
            assert set(leaf) == {qkey, "scale"}
            orig = np.asarray(params["layers"][k]).shape
            got = tuple(leaf[qkey].shape)
            want = list(orig)
            if bits == 4:
                want[pack_axis] //= 2
            assert got == tuple(want), (k, got, want)
            # scales: per-layer, per-OUTPUT-channel (last axis), f32
            assert tuple(leaf["scale"].shape) == (orig[0], orig[-1])
            assert leaf["scale"].dtype == jnp.float32
        # norms / embedding / head stay fp
        assert layers["input_norm"].dtype == params["layers"][
            "input_norm"].dtype
        assert qp["embed_tokens"].dtype == params["embed_tokens"].dtype
    # double quantization is rejected, mismatched modes are rejected
    qp = ptq.quantize_weights(params, bits=8)
    with pytest.raises(ValueError, match="already"):
        ptq.quantize_weights(qp, bits=8)
    with pytest.raises(ValueError, match="int4"):
        ptq.ensure_quantized(qp, "int4")
    # ensure_quantized adopts a carried mode and validates a match
    same, mode = ptq.ensure_quantized(qp, None)
    assert same is qp and mode == "int8"
    same, mode = ptq.ensure_quantized(qp, "int8")
    assert same is qp and mode == "int8"


def test_ptq_scale_determinism(params):
    """One-shot PTQ is deterministic: two runs over the same fp tree
    produce byte-identical integer tiles and scales."""
    a = ptq.quantize_weights(params, bits=4)
    b = ptq.quantize_weights(params, bits=4)
    for k in ptq.WQ_KEYS:
        np.testing.assert_array_equal(np.asarray(a["layers"][k]["qw4"]),
                                      np.asarray(b["layers"][k]["qw4"]))
        np.testing.assert_array_equal(
            np.asarray(a["layers"][k]["scale"]),
            np.asarray(b["layers"][k]["scale"]))


def test_ptq_activation_aware_clip(params):
    """The first-prompt activation-aware path: absmax capture has the
    right shapes, the clip search never increases the activation-
    weighted error, and the result still serves."""
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, 97, (12,)).astype(np.int32)
    aa = ptq.activation_absmax(params, CFG, prompt)
    L, D, F = (CFG.num_hidden_layers, CFG.hidden_size,
               CFG.intermediate_size)
    E = CFG.num_attention_heads * CFG.head_dim
    assert aa["q_proj"].shape == (L, D)
    assert aa["o_proj"].shape == (L, E)
    assert aa["down_proj"].shape == (L, F)
    qp = ptq.quantize_weights(params, bits=4, act_absmax=aa)
    base = ptq.quantize_weights(params, bits=4)
    for k in ("q_proj", "down_proj"):
        a = np.asarray(aa[k], np.float64)[:, :, None] ** 2
        w = np.asarray(params["layers"][k], np.float64)
        err_aa = (((w - np.asarray(quanters.dequantize_weight(
            qp["layers"][k]), np.float64)) ** 2) * a).sum()
        err_pl = (((w - np.asarray(quanters.dequantize_weight(
            base["layers"][k]), np.float64)) ** 2) * a).sum()
        assert err_aa <= err_pl + 1e-12, k
    eng = _engine(qp)
    r = eng.submit(prompt, GenerationConfig(max_new_tokens=3,
                                            greedy=True))
    eng.drain()
    assert r.done and len(r.tokens) == 3


def test_weight_hbm_bytes_reduction(params):
    fp = ptq.weight_hbm_bytes(params)
    i8 = ptq.weight_hbm_bytes(ptq.quantize_weights(params, bits=8))
    i4 = ptq.weight_hbm_bytes(ptq.quantize_weights(params, bits=4))
    assert fp / i8 > 1.8          # fp32 test weights: ~4x - scales
    assert fp / i4 > 3.5


# ---------------------------------------------------------------------------
# kernel parity (forced Pallas, interpret) vs the dequant composition
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
@pytest.mark.parametrize("D,F", [(32, 96), (64, 256)])
def test_mlp_kernel_parity_quantized_weights(bits, D, F):
    """Incl. the F=96 no-large-divisor tile class and an explicit even
    tile under int4 (wd packs its OUTPUT axis — the tiling proof)."""
    rng = np.random.RandomState(D + F + bits)
    dt = jnp.float32
    mk = lambda *s: jnp.asarray(rng.randn(*s) * 0.07, dt)  # noqa: E731
    x, nw = mk(3, D), jnp.asarray(rng.rand(D) + 0.5, dt)
    wg = ptq.quantize_leaf(mk(D, F), bits)
    wu = ptq.quantize_leaf(mk(D, F), bits)
    wd = ptq.quantize_leaf(mk(F, D), bits, pack_axis=1)
    got = fdb.fused_mlp_block_pallas(x, nw, wg, wu, wd)
    want = fdb.mlp_block_ref(x, nw, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=1e-5)
    tiled = fdb.fused_mlp_block_pallas(x, nw, wg, wu, wd,
                                       block_f=F // 2)
    np.testing.assert_allclose(np.asarray(tiled), np.asarray(want),
                               atol=3e-5, rtol=1e-5)
    if bits == 4 and F % 3 == 0:
        # an ODD F-tile is legal under int4: F is never the packed
        # axis (gate/up pack rows, down packs columns — every tile
        # fully covers the packed dim)
        odd = fdb.fused_mlp_block_pallas(x, nw, wg, wu, wd, block_f=3)
        np.testing.assert_allclose(np.asarray(odd), np.asarray(want),
                                   atol=3e-5, rtol=1e-5)


@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
def test_prefill_kernel_parity_quantized_weights(bits):
    """Warm mid-page start + ragged valid rows, quantized weights."""
    rng = np.random.RandomState(20 + bits)
    P, D, H, KV, hd, BS, MB = 16, 32, 4, 2, 16, 8, 5
    N = MB + 3
    dt = jnp.float32
    mk = lambda *s: jnp.asarray(rng.randn(*s) * 0.07, dt)  # noqa: E731
    x, nw = mk(P, D), jnp.asarray(rng.rand(D) + 0.5, dt)
    q = lambda w: ptq.quantize_leaf(w, bits)               # noqa: E731
    wq, wk, wv = q(mk(D, H * hd)), q(mk(D, KV * hd)), q(mk(D, KV * hd))
    wo = q(mk(H * hd, D))
    pos0, n_valid = 10, 13
    inv = 1.0 / (10000.0 ** (np.arange(0, hd, 2) / hd))
    ang = (pos0 + np.arange(P))[:, None] * inv[None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    kp, vp = mk(N, BS, KV, hd), mk(N, BS, KV, hd)
    tab = jnp.asarray(rng.permutation(N - 1)[:MB] + 1, jnp.int32)
    args = (x, nw, wq, wk, wv, wo, sin, cos, kp, vp, tab,
            jnp.int32(pos0), jnp.int32(n_valid))
    xf, kf, vf = fpb.fused_prefill_attn_pallas(*args)
    xr, kr, vr = fpb.prefill_attn_block_ref(*args)
    np.testing.assert_allclose(np.asarray(xf[:n_valid]),
                               np.asarray(xr[:n_valid]),
                               atol=3e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(kf), np.asarray(kr),
                               atol=3e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# registry dispatch with the weight_dtype meta key
# ---------------------------------------------------------------------------
def test_flagship_dispatch_int8_and_int4():
    """Acceptance bar: BOTH quantized classes dispatch the fused
    variants on the flagship serving shape class (D=1024/F=4096) off
    interpret mode — weight quant widens the VMEM fit, never shrinks
    it."""
    for wd in ("int8", "int4"):
        meta = fdb.decode_meta_dims(8, 1024, 4096, jnp.bfloat16,
                                    weight_dtype=wd)
        meta["interpret"] = False
        ok, why = fdb._supports_mlp(dict(meta))
        assert ok, (wd, why)
        from paddle_tpu.ops.pallas.registry import KERNELS
        assert KERNELS.dispatch("decode_mlp_block", meta)[0] == \
            "pallas_fused"
        # the prefill kernel serves heads that fill a 128-lane tile
        pmeta = fpb.prefill_meta_dims(64, 1024, 8, 8, 128, 4096, 16,
                                      24, jnp.bfloat16, jnp.bfloat16,
                                      False, weight_dtype=wd)
        pmeta["interpret"] = False
        assert KERNELS.dispatch("prefill_attn_block", pmeta)[0] == \
            "pallas_fused"


def test_dispatch_reason_strings_and_int4_odd_reject():
    """VMEM-fallback + packing-constraint reasons are human-readable;
    an odd hidden size cleanly rejects int4 (falls back, never packs
    garbage)."""
    from paddle_tpu.ops.pallas.registry import KERNELS
    meta2 = fdb.decode_meta_dims(2, 33, 96, jnp.float32,
                                 weight_dtype="int4")
    meta2["interpret"] = False
    ok, why = fdb._supports_mlp(dict(meta2))
    assert not ok and "even" in why and "int4" in why
    # the VMEM budget reason still names the budget under weight quant
    meta3 = fdb.decode_meta_dims(8, 1024, 4096, jnp.bfloat16,
                                 weight_dtype="int8")
    meta3["interpret"] = False
    meta3["vmem_budget"] = 1024
    ok, why = fdb._supports_mlp(dict(meta3))
    assert not ok and "VMEM" in why
    # interpret mode: auto dispatch falls back with a reason
    meta4 = fdb.decode_meta_dims(2, CFG.hidden_size,
                                 CFG.intermediate_size, jnp.float32,
                                 weight_dtype="int8")
    assert meta4["interpret"] and meta4["weight_dtype"] == "int8"
    (row,) = [r for r in KERNELS.explain("decode_mlp_block", meta4)
              if r["name"] == "pallas_fused"]
    assert not row["supported"] and "interpret" in row["reason"]
    assert KERNELS.dispatch("decode_mlp_block", meta4)[0] == "unfused"


def test_weight_dtype_rides_in_declared_cache_keys():
    """The DISPATCH_KEY_GAP contract: weight_dtype is a declared cache
    key for the three serving ops that stream weight tiles (the
    registry lint gates the reads)."""
    from paddle_tpu.ops.pallas.registry import KERNELS
    for op in ("decode_mlp_block",
               "prefill_attn_block", "prefill_mlp_block"):
        fields, _ = KERNELS.cache_key_decl(op)
        assert "weight_dtype" in fields, op


def test_mixed_weight_modes_rejected():
    with pytest.raises(ValueError, match="one weight-quant mode"):
        fdb.weight_dtype_of(jnp.zeros((4, 4)),
                            ptq.quantize_leaf(np.zeros((4, 4)), 8))


# ---------------------------------------------------------------------------
# step-level + engine-level routing
# ---------------------------------------------------------------------------
def _step_inputs(rng, B=2, BS=4, MB=4):
    L = CFG.num_hidden_layers
    KV, hd = CFG.num_key_value_heads, CFG.head_dim
    N = B * MB + 1
    kp = jnp.asarray(rng.randn(L, N, BS, KV, hd) * 0.1, jnp.float32)
    vp = jnp.asarray(rng.randn(L, N, BS, KV, hd) * 0.1, jnp.float32)
    tok = jnp.asarray(rng.randint(0, 97, (B,)), jnp.int32)
    bt = jnp.asarray(rng.permutation(N)[:B * MB].reshape(B, MB),
                     jnp.int32)
    lens = jnp.asarray([5, 0][:B], jnp.int32)
    return tok, kp, vp, bt, lens


@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
def test_quantized_fallback_bit_identical_to_dequant_matmul(params,
                                                            bits):
    """The acceptance contract: on CPU (dispatch -> the compositions)
    the decode step over a quantized tree is BIT-identical to the step
    pinned to them over the same tree — both are dequantize-then-matmul
    through the one shared helper."""
    from paddle_tpu.ops.pallas.registry import KERNELS
    qp = ptq.quantize_weights(params, bits=bits)
    rng = np.random.RandomState(6 + bits)
    tok, kp, vp, bt, lens = _step_inputs(rng)
    with KERNELS.force("paged_attention_decode", "xla"), \
            KERNELS.force("decode_mlp_block", "unfused"):
        lg0, kp0, vp0 = _decode_step(qp, tok, CFG, kp, vp, bt, lens)
    lg1, kp1, vp1 = _decode_step(qp, tok, CFG, kp, vp, bt, lens)
    np.testing.assert_array_equal(np.asarray(lg0), np.asarray(lg1))
    np.testing.assert_array_equal(np.asarray(kp0), np.asarray(kp1))
    # and the pinned Pallas launches (the MLP's in-kernel dequant)
    # stay roundoff-close
    with KERNELS.force("paged_attention_decode", "pallas"), \
            KERNELS.force("decode_mlp_block", "pallas_fused"):
        lg2, _, _ = _decode_step(qp, tok, CFG, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(lg2), np.asarray(lg0),
                               atol=5e-5, rtol=1e-5)


def test_engine_stream_int8_weights(params):
    """20+-request mixed-length greedy stream on int8 weights: steady
    state stays 1 decode program + <=1 trace per bucket with zero
    retrace warnings, metrics carry the weight_quant_variant snapshot,
    and greedy output stays within a small documented flip budget vs
    the fp engine (<= 10% of tokens on these random test weights; real
    checkpoints sit far lower)."""
    rng = np.random.RandomState(7)
    specs = [(int(rng.randint(3, 15)), int(rng.randint(2, 6)))
             for _ in range(22)]
    prompts = [rng.randint(0, 97, (S,)).astype(np.int32)
               for S, _ in specs]

    def run(wq):
        eng = _engine(params, weight_quant=wq, observability=True)
        rs = [eng.submit(p, GenerationConfig(max_new_tokens=N,
                                             greedy=True))
              for p, (_, N) in zip(prompts, specs)]
        eng.drain()
        assert all(r.done for r in rs)
        return eng, [r.tokens for r in rs]

    eng_q, toks_q = run("int8")
    eng_f, toks_f = run(None)
    c = eng_q.counters
    assert c["requests_completed"] == 22
    assert c["decode_traces"] == 1, c
    assert all(n <= 1 for n in c["prefill_traces"].values()), c
    m = eng_q.metrics()
    assert m["retrace_warnings"] == 0
    assert m["weight_quant_variant"]["mode"] == "int8"
    assert m["weight_quant_variant"]["attn"] == "xla"      # CPU route
    assert eng_f.metrics()["weight_quant_variant"] == {"mode": "off"}
    total = sum(len(t) for t in toks_f)
    flips = sum(a != b for tf, tq in zip(toks_f, toks_q)
                for a, b in zip(tf, tq))
    assert flips / total <= 0.10, (flips, total)


def test_logit_error_budget_int8(params):
    """Dense-forward logits on a fixed prompt: int8 weight quant stays
    within a small absolute budget of fp at the test shapes (the bench
    reports the same number at the bench shapes)."""
    from paddle_tpu.inference.generation import cached_forward, init_cache
    rng = np.random.RandomState(11)
    toks = jnp.asarray(rng.randint(0, 97, (1, 24)), jnp.int32)
    kc, vc = init_cache(CFG, 1, 24)
    ref = np.asarray(cached_forward(params, toks, CFG, kc, vc, 0)[0],
                     np.float32)
    qp = ptq.quantize_weights(params, bits=8)
    kc, vc = init_cache(CFG, 1, 24)
    got = np.asarray(cached_forward(qp, toks, CFG, kc, vc, 0)[0],
                     np.float32)
    err = np.abs(got - ref).max()
    spread = ref.max() - ref.min()
    assert err < 0.05 * max(spread, 1e-6), (err, spread)


def test_engine_int8_weights_with_int8_kv_cache(params):
    """Weight quant composes with the int8 KV cache (orthogonal
    quantizations: weights per-channel static, KV per-head one-shot)."""
    rng = np.random.RandomState(13)
    eng = _engine(params, weight_quant="int8", cache_dtype="int8")
    rs = [eng.submit(rng.randint(0, 97, (6,)).astype(np.int32),
                     GenerationConfig(max_new_tokens=3, greedy=True))
          for _ in range(4)]
    eng.drain()
    assert all(r.done and len(r.tokens) == 3 for r in rs)
    assert eng.counters["decode_traces"] == 1
    assert eng.counters["calibration_traces"] >= 1     # KV calibration


def test_generate_paged_weight_quant_matches_engine(params):
    """generate_paged(weight_quant=) and the engine run the same
    dequantize-then-matmul math — greedy outputs agree token for
    token."""
    rng = np.random.RandomState(15)
    prompt = rng.randint(0, 97, (8,)).astype(np.int32)
    g = GenerationConfig(max_new_tokens=5, greedy=True)
    out = np.asarray(generate_paged(params, jnp.asarray(prompt[None]),
                                    CFG, g, block_size=4,
                                    weight_quant="int8"))[0, 8:]
    eng = _engine(params, weight_quant="int8")
    r = eng.submit(prompt, g)
    eng.drain()
    np.testing.assert_array_equal(out, np.asarray(r.tokens))
    # pre-quantized trees ride as-is; a mesh is cleanly rejected
    qp = ptq.quantize_weights(params, bits=8)
    out2 = np.asarray(generate_paged(qp, jnp.asarray(prompt[None]),
                                     CFG, g, block_size=4))[0, 8:]
    np.testing.assert_array_equal(out, out2)
    with pytest.raises(ValueError, match="mesh"):
        generate_paged(params, jnp.asarray(prompt[None]), CFG, g,
                       weight_quant="int8", mesh=1)


def test_engine_rejects_tp_gt1_weight_quant(params):
    from paddle_tpu.inference import ServingMesh
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = ServingMesh.make(tp=2)
    with pytest.raises(ValueError, match="tp=2"):
        _engine(params, weight_quant="int8", mesh=mesh)


def test_disagg_weight_quant_parity(params):
    """DisaggregatedEngine threads weight_quant to both groups; greedy
    output is bit-identical to the colocated quantized engine."""
    from paddle_tpu.inference.disagg import DisaggregatedEngine
    rng = np.random.RandomState(17)
    prompts = [rng.randint(0, 97, (int(rng.randint(3, 12)),))
               .astype(np.int32) for _ in range(6)]
    g = GenerationConfig(max_new_tokens=4, greedy=True)
    devs = jax.devices()
    eng = DisaggregatedEngine(params, CFG, capacity=2, prefill_slots=1,
                              block_size=4, max_seq_len=64,
                              prefill_buckets=(16,),
                              # tp=1 groups (the quantized-tree
                              # contract; multi-chip groups reject)
                              prefill_devices=devs[:1],
                              decode_devices=devs[1:2] or devs[:1],
                              weight_quant="int8")
    rs = [eng.submit(p, g) for p in prompts]
    eng.drain()
    co = _engine(params, capacity=2, block_size=4, max_seq_len=64,
                 prefill_buckets=(16,), weight_quant="int8")
    rs2 = [co.submit(p, g) for p in prompts]
    co.drain()
    assert [r.tokens for r in rs] == [r.tokens for r in rs2]
    m = eng.metrics()
    assert m["groups"]["decode"]["weight_quant_variant"]["mode"] == \
        "int8"


def test_audit_clean_for_wq_program(params):
    """The quantized-weight engine's programs audit clean (the
    serving_decode_wq catalog entry rides the same hook)."""
    eng = _engine(params, weight_quant="int8")
    reports = eng.audit(register=False)
    bad = [f for r in reports for f in r.findings
           if f.severity in ("error", "warning")]
    assert not bad, bad
