"""The decode step's two launches (``paged_attention_decode``,
``decode_mlp_block``: ops/paged_attention.py, ops/pallas/
fused_decode_block.py), the kernel registry that chooses them
(ops/pallas/registry.py), and satellites (autotune-cache robustness,
per-kernel bench gate, paged-decode pages-per-step tuning).

Parity contract: auto dispatch on the CPU selects the XLA compositions,
so the decode step there is BIT-identical to the step pinned to them
(``KERNELS.force``) — asserted through a >=20-request ServingEngine
stream and at the step level. The Pallas launches (pinned, interpret
mode) match the compositions to fp32 roundoff, and the attention stage
matches dense float32 attention over the same tokens.
"""
import contextlib
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
from paddle_tpu.inference import generation as G
from paddle_tpu.inference.generation import _decode_step, generate_paged
from paddle_tpu.ops import paged_attention as PA
from paddle_tpu.ops.pallas import fused_decode_block as fdb
from paddle_tpu.ops.pallas.registry import KERNELS, KernelRegistry

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


@contextlib.contextmanager
def _pinned(attn, mlp):
    """Both decode launches pinned (None leaves one to dispatch)."""
    with contextlib.ExitStack() as st:
        if attn:
            st.enter_context(KERNELS.force("paged_attention_decode", attn))
        if mlp:
            st.enter_context(KERNELS.force("decode_mlp_block", mlp))
        yield


PALLAS = ("pallas", "pallas_fused")
REFERENCE = ("xla", "unfused")


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
# the attention stage: attn_qkv_ref -> pool write -> attn_out_ref, against
# dense float32 attention over the same tokens
# ---------------------------------------------------------------------------
def _dense_attention_stage(args, scales):
    """x + softmax(q K^T) V Wo over each slot's history read out of the
    pool through its table plus the new token, in numpy float64: the
    plain reference, sharing no code with the program."""
    from paddle_tpu.quantization.quanters import maybe_dequantize
    x, nw, wq, wk, wv, wo, sin, cos, kp, vp, bt, lens = [
        np.asarray(maybe_dequantize(a, jnp.float32), np.float64)
        if isinstance(a, dict) else np.asarray(a) for a in args]
    B, D = x.shape
    BS, KV, hd = kp.shape[1:]
    H = wq.shape[1] // hd
    h = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * nw

    def rope(t, pos):
        t1, t2 = t[..., :hd // 2], t[..., hd // 2:]
        s, c = sin[pos], cos[pos]
        return np.concatenate([t1 * c - t2 * s, t2 * c + t1 * s], -1)

    out = np.zeros_like(x, dtype=np.float64)
    for b in range(B):
        n = int(lens[b])
        q = rope((h[b] @ wq).reshape(H, hd), n)
        k_new = rope((h[b] @ wk).reshape(KV, hd), n)
        v_new = (h[b] @ wv).reshape(KV, hd)
        pages = kp[bt[b]].reshape(-1, KV, hd)[:n].astype(np.float64)
        vpages = vp[bt[b]].reshape(-1, KV, hd)[:n].astype(np.float64)
        if scales is not None:      # what an int8 pool holds of a token
            ks, vs = (np.asarray(s, np.float64)[:, None] for s in scales)
            pages, vpages = pages * ks, vpages * vs
            k_new = np.clip(np.round(k_new / ks), -127, 127) * ks
            v_new = np.clip(np.round(v_new / vs), -127, 127) * vs
        k = np.concatenate([pages, k_new[None]]).repeat(H // KV, 1)
        v = np.concatenate([vpages, v_new[None]]).repeat(H // KV, 1)
        sc = np.einsum("hd,thd->ht", q, k) / np.sqrt(hd)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[b] = x[b] + np.einsum("ht,thd->hd", p, v).reshape(-1) @ wo
    return out


def _attention_stage(args, scales, fused=False):
    x, nw, wq, wk, wv, wo, sin, cos, kp, vp, bt, lens = args
    lp = dict(zip(fdb.QKV_LEAVES, (wq, wk, wv)))
    hd = kp.shape[-1]
    dims = (fdb._wq_parts(wq)[0].shape[-1] // hd, kp.shape[-2], hd)
    if fused:       # the serving engine's form of the same weights
        lp = fdb.fuse_qkv(lp)
    q, k_new, v_new = fdb.attn_qkv_ref(x, nw, lp, dims, sin, cos, lens)
    if scales is None:
        kp, vp = PA.write_to_pool(kp, vp, bt, lens, k_new, v_new)
    else:
        kp, vp = PA.write_to_pool_quant(kp, vp, bt, lens, k_new, v_new,
                                        *scales)
    return fdb.attn_out_ref(x, q, wo, kp, vp, bt, lens, scales)


def _random_dims(seed):
    rng = np.random.RandomState(seed)
    return dict(B=int(rng.randint(1, 4)), KV=int(rng.choice([1, 2, 4])),
                groups=int(rng.choice([1, 2, 3])),
                hd=int(rng.choice([8, 16, 32])),
                BS=int(rng.choice([4, 8, 16])), MB=int(rng.randint(2, 5)),
                D=int(rng.choice([32, 48, 64])))


STAGE = dict(B=2, D=64, KV=2, groups=2, hd=16, BS=8, MB=3)
ATTENTION_STAGE_CASES = {
    "seed0": (_random_dims(0), {}), "seed1": (_random_dims(1), {}),
    "seed2": (_random_dims(2), {}),
    "int8_pool": (STAGE, {"quant": True}),
    # the benchmark's head layouts: Mistral's 32/8, its four-chip shard
    "gqa_32_8": (dict(STAGE, KV=8, groups=4), {}),
    "gqa_8_2": (dict(STAGE, KV=2, groups=4), {}),
    "w8": (STAGE, {"bits": 8}), "w4": (STAGE, {"bits": 4}),
    # q/k/v as the engine's one leaf: one product, split
    "fused_seed1": (_random_dims(1), {"fused": True}),
    "fused_gqa_32_8": (dict(STAGE, KV=8, groups=4), {"fused": True}),
    "fused_gqa_8_2": (dict(STAGE, KV=2, groups=4), {"fused": True}),
}


@pytest.mark.parametrize("case", ATTENTION_STAGE_CASES)
def test_attention_stage_matches_dense_attention(case):
    """The stage every decode program runs, with the Pallas
    ``paged_attention_decode`` pinned (interpret mode) wherever its
    predicate would admit it on a chip. An int8 pool is refused by
    name and attends through the ``xla`` variant."""
    dims, opt = ATTENTION_STAGE_CASES[case]
    rng = np.random.RandomState(sum(map(ord, case)))
    args, scales = _attn_case(rng, quant=opt.get("quant", False), **dims)
    if "bits" in opt:
        from paddle_tpu.quantization import ptq
        args = args[:2] + tuple(ptq.quantize_leaf(w, opt["bits"])
                                for w in args[2:6]) + args[6:]
    if scales is None:
        with _pinned("pallas", None):
            got = _attention_stage(args, scales, opt.get("fused", False))
    else:
        meta = dict(PA.decode_attention_meta(jnp.int8), interpret=False,
                    backend="tpu")
        rows = {r["name"]: r for r in
                KERNELS.explain("paged_attention_decode", meta)}
        assert rows["xla"]["selected"]
        assert "int8 pools" in rows["pallas"]["reason"]
        with pytest.raises(ValueError, match="no int8 pools"), \
                _pinned("pallas", None):
            _attention_stage(args, scales)
        got = _attention_stage(args, scales)
    np.testing.assert_allclose(np.asarray(got),
                               _dense_attention_stage(args, scales),
                               atol=3e-5, rtol=1e-5)


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


def test_paged_decode_pages_per_step_invariant():
    """The paged-decode kernel's pages-per-step is an autotune
    candidate: it sets how many pages one softmax update reduces, so
    every choice gives the reference's attention and differs from
    another in the last float32 places only."""
    from paddle_tpu.ops.paged_attention import paged_attention_decode_xla
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
    want = np.asarray(paged_attention_decode_xla(q, kp, vp, bt, lens))
    for out in outs:
        np.testing.assert_allclose(out, want, rtol=2e-6, atol=2e-8)


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
    meta = fdb.decode_meta_dims(2, CFG.hidden_size,
                                CFG.intermediate_size, jnp.float32)
    assert meta["interpret"]
    assert KERNELS.dispatch("decode_mlp_block", meta) == \
        ("unfused", fdb.mlp_block_ref)
    assert KERNELS.dispatch(
        "paged_attention_decode",
        PA.decode_attention_meta(jnp.float32)) == \
        ("xla", PA.paged_attention_decode_xla)
    # a pin still returns the Pallas variants (tests / audit catalog),
    # and dispatch() records what it handed out
    with _pinned(*PALLAS), KERNELS.record() as picked:
        assert KERNELS.dispatch("decode_mlp_block", meta)[0] == \
            "pallas_fused"
    assert picked == {"decode_mlp_block": "pallas_fused"}
    assert fdb.launch_operands(picked) == {"decode_mlp_block": "index"}


def test_vmem_budget_gates_fused_variant(monkeypatch):
    """Oversized block weights must fail the ``supports`` predicate with
    a reason naming the VMEM budget, even off interpret mode. The
    budget rides IN the meta (decode_meta_dims reads the env at build time —
    i.e. at trace time, when the programs' route key is computed), so
    the shrunken-budget meta is rebuilt the way a retrace would."""
    def meta():
        return dict(fdb.decode_meta_dims(2, CFG.hidden_size,
                                         CFG.intermediate_size,
                                         jnp.float32), interpret=False)
    assert meta()["vmem_budget"] == fdb._vmem_budget()
    ok, why = fdb._supports_mlp(meta())
    assert ok, why                               # tiny cfg fits
    monkeypatch.setenv("PADDLE_TPU_FUSED_VMEM_BUDGET", "1024")
    assert meta()["vmem_budget"] == 1024
    ok, why = fdb._supports_mlp(meta())
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
    """Auto dispatch (the compositions on CPU) is BIT-identical to the
    step pinned to them; pinned to the Pallas launches (interpret) it
    matches to fp32 roundoff — fp32 and int8 cache (whose attention
    stays with the ``xla`` variant: the kernel takes no int8 pool)."""
    rng = np.random.RandomState(6 + quant)
    tok, kp, vp, bt, lens, scales = _step_inputs(params, rng,
                                                 quant=quant)

    def step():
        return _decode_step(params, tok, CFG, kp, vp, bt, lens,
                            kv_scales=scales)
    with _pinned(*REFERENCE):
        lg0, kp0, vp0 = step()
    lg1, kp1, vp1 = step()
    np.testing.assert_array_equal(np.asarray(lg0), np.asarray(lg1))
    np.testing.assert_array_equal(np.asarray(kp0), np.asarray(kp1))
    np.testing.assert_array_equal(np.asarray(vp0), np.asarray(vp1))
    with _pinned(None if quant else "pallas", "pallas_fused"), \
            KERNELS.record() as picked:
        lg2, kp2, vp2 = step()
    assert picked == {"paged_attention_decode":
                      "xla" if quant else "pallas",
                      "decode_mlp_block": "pallas_fused"}
    np.testing.assert_allclose(np.asarray(lg2), np.asarray(lg0),
                               atol=5e-5, rtol=1e-5)
    # a later layer's token is written from a residual stream that
    # differs by fp32 roundoff, so the written pool values are 1-ulp
    # close (and EXACTLY equal under int8, where quantization re-snaps)
    assert_pool = np.testing.assert_array_equal if quant else \
        functools.partial(np.testing.assert_allclose, atol=1e-6,
                          rtol=1e-5)
    assert_pool(np.asarray(kp2), np.asarray(kp0))
    assert_pool(np.asarray(vp2), np.asarray(vp0))


def _stream(rng, n=22):
    specs = [(int(rng.randint(3, 15)), int(rng.randint(2, 6)))
             for _ in range(n)]
    return [(rng.randint(0, 97, (S,)).astype(np.int32), N)
            for S, N in specs]


def _serve(eng, stream):
    rs = [eng.submit(p, GenerationConfig(max_new_tokens=N, greedy=True))
          for p, N in stream]
    eng.drain()
    assert all(r.done for r in rs)
    return [r.tokens for r in rs]


@pytest.mark.parametrize("cdt", [None, "int8"], ids=["fp32", "int8"])
def test_engine_stream_fused_vs_unfused_bit_parity(params, cdt):
    """>=20-request mixed-length greedy stream: the engine as every
    caller builds it must produce bit-identical tokens to an engine
    pinned to the XLA compositions, and keep the zero-retrace steady
    state (1 decode program, <=1 trace per prefill bucket)."""
    stream = _stream(np.random.RandomState(7))
    eng_f = _engine(params, cache_dtype=cdt)
    toks_f = _serve(eng_f, stream)
    eng_u = _engine(params, cache_dtype=cdt)
    with _pinned(*REFERENCE):
        toks_u = _serve(eng_u, stream)
    assert toks_f == toks_u
    c = eng_f.counters
    assert c["requests_completed"] == 22
    assert c["decode_traces"] == 1, c
    assert set(c["prefill_traces"]) <= {8, 16}
    assert all(n <= 1 for n in c["prefill_traces"].values()), c
    assert eng_f.metrics()["decode_variant"] == eng_u.decode_variant == {
        "attn": "xla", "mlp": "unfused", "operands": {},
        "qkv": "fused_stack"}


def test_engine_forced_pallas_smoke(params):
    """Both launches pinned: the engine runs the actual Pallas decode
    program (interpret mode on CPU) end to end, and reports what its
    trace picked — nothing before it has traced."""
    eng = _engine(params, capacity=2, prefill_buckets=(8,))
    assert eng.decode_variant == {"attn": None, "mlp": None,
                                  "operands": {}, "qkv": None}
    rng = np.random.RandomState(8)
    with _pinned(*PALLAS):
        rs = [eng.submit(rng.randint(0, 97, (6,)).astype(np.int32),
                         GenerationConfig(max_new_tokens=3, greedy=True))
              for _ in range(2)]
        eng.drain()
        # the audit's clone traces under the same pins and leaves the
        # live report alone
        assert any(s.name == "serving_decode"
                   for s in eng.program_specs(register=False))
    assert all(r.done and len(r.tokens) == 3 for r in rs)
    assert eng.counters["decode_traces"] == 1
    assert eng.decode_variant == {
        "attn": "pallas", "mlp": "pallas_fused",
        "operands": {"paged_attention_decode": "index",
                     "decode_mlp_block": "index"},
        "qkv": "fused_stack"}


@pytest.mark.parametrize("op,variant", [
    ("paged_attention_decode", "pallas"),
    ("decode_mlp_block", "pallas_fused")])
def test_force_pin_retraces_decode_programs(params, op, variant):
    """Dispatch reads the pin at TRACE time: a pin on either decode op
    must retrace the engine's decode program and miss ``_PAGED_CACHE``,
    never replay the program the unpinned route compiled."""
    eng = _engine(params, capacity=2, prefill_buckets=(8,))
    stream = _stream(np.random.RandomState(11), n=2)
    base = _serve(eng, stream)
    assert eng.counters["decode_traces"] == 1
    slot = {"paged_attention_decode": "attn", "decode_mlp_block": "mlp"}
    assert eng.decode_variant[slot[op]] != variant
    with KERNELS.force(op, variant):
        pinned = _serve(eng, stream)
        assert eng.counters["decode_traces"] == 2
        assert eng.decode_variant[slot[op]] == variant
        assert op in eng.decode_variant["operands"]
        _serve(eng, stream)                       # same pin: replayed
        assert eng.counters["decode_traces"] == 2
    assert pinned == base                         # greedy, roundoff apart

    gen = GenerationConfig(max_new_tokens=4, greedy=True)
    plain = G._paged_chunk_runner(CFG, gen)
    assert G._paged_chunk_runner(CFG, gen) is plain
    with KERNELS.force(op, variant):
        assert G._paged_chunk_runner(CFG, gen) is not plain
    assert G._paged_chunk_runner(CFG, gen) is plain


@pytest.mark.parametrize("call", ["ServingEngine", "generate_paged"])
def test_removed_option_is_refused_by_name(params, call):
    """``fused_decode=`` is gone, not swallowed: which kernel runs is
    the registry's choice, and a test's pin."""
    prompts = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(TypeError, match="fused_decode"):
        if call == "ServingEngine":
            ServingEngine(params, CFG, fused_decode="pallas")
        else:
            generate_paged(params, prompts, CFG, fused_decode=False)


def test_generate_paged_fused_flag_parity(params):
    rng = np.random.RandomState(9)
    prompts = jnp.asarray(rng.randint(0, 97, (2, 8)), jnp.int32)
    g = GenerationConfig(max_new_tokens=6, greedy=True)
    with _pinned(*REFERENCE):
        base = np.asarray(generate_paged(params, prompts, CFG, g))
    auto = np.asarray(generate_paged(params, prompts, CFG, g))
    np.testing.assert_array_equal(base, auto)
    # the Pallas launches decode the same greedy tokens (roundoff-level
    # logits variant of the compositions)
    with _pinned(*PALLAS):
        pallas = np.asarray(generate_paged(params, prompts, CFG, g))
    np.testing.assert_array_equal(base, pallas)


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
