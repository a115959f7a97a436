"""Fused prefill-block megakernels (ops/pallas/fused_prefill_block.py):
ragged chunked prefill writing straight into the paged KV pools.

Contract under test:
- kernel-level parity (interpret mode, forced Pallas) vs the exact
  dense composition at the ragged edges — 1 valid row, all-full chunk,
  prime valid lengths, warm mid-page starts, int8 pools;
- registry dispatch/force/fallback + the VMEM-budget fallback with a
  readable reason string;
- engine-level: greedy output through FLAGS_fused_prefill (default ON)
  is BIT-identical to fused_prefill=False wherever dispatch falls back
  (which is everywhere on CPU) — cold AND prefix-cache warm, fp32 and
  int8 pools, colocated AND disaggregated engines; a forced-pallas
  engine keeps steady state at <=1 prefill program per bucket with
  zero retrace warnings over a 20+-request stream.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.models import llama
from paddle_tpu.inference import GenerationConfig, ServingEngine
from paddle_tpu.ops.pallas import fused_prefill_block as fpb
from paddle_tpu.ops.pallas.registry import KERNELS

pytestmark = pytest.mark.fused_prefill

CFG = llama.LlamaConfig(vocab_size=97, hidden_size=64,
                        intermediate_size=128, num_hidden_layers=2,
                        num_attention_heads=4, num_key_value_heads=2,
                        max_position_embeddings=160, dtype=jnp.float32,
                        remat=False)

_RNG = np.random.RandomState(11)


def _f32(*shape):
    return jnp.asarray(_RNG.randn(*shape) * 0.3, jnp.float32)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(0), dtype=jnp.float32)


def _kernel_inputs(P=16, D=32, H=4, KV=2, hd=16, BS=8, MB=6, pos0=0,
                   quant=False, seed=0):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s) * 0.3, jnp.float32)  # noqa: E731
    N = MB + 3
    x, nw = f(P, D), jnp.abs(f(D)) + 0.5
    wq, wk, wv = f(D, H * hd), f(D, KV * hd), f(D, KV * hd)
    wo = f(H * hd, D)
    inv = 1.0 / (10000.0 ** (np.arange(0, hd, 2) / hd))
    ang = (pos0 + np.arange(P))[:, None] * inv[None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    if quant:
        kp = jnp.asarray(rng.randint(-127, 127, (N, BS, KV, hd)),
                         jnp.int8)
        vp = jnp.asarray(rng.randint(-127, 127, (N, BS, KV, hd)),
                         jnp.int8)
        sc = (jnp.abs(f(KV)) * 0.05 + 0.01,
              jnp.abs(f(KV)) * 0.05 + 0.01)
    else:
        kp, vp = f(N, BS, KV, hd), f(N, BS, KV, hd)
        sc = None
    tab = jnp.asarray(rng.permutation(N - 1)[:MB] + 1, jnp.int32)
    return (x, nw, wq, wk, wv, wo, sin, cos, kp, vp, tab), sc


def _compare(args, sc, pos0, n_valid, tol=1e-4):
    ref = fpb.prefill_attn_block_ref(*args, jnp.int32(pos0),
                                     jnp.int32(n_valid), sc)
    with KERNELS.force("prefill_attn_block", "pallas_fused"):
        got = jax.jit(
            lambda *a: fpb.fused_prefill_attn_pallas(*a, kv_scales=sc)
        )(*args, jnp.int32(pos0), jnp.int32(n_valid))
    for name, g, r in zip(("xo", "kn", "vn"), got, ref):
        ga, ra = np.asarray(g), np.asarray(r)
        if name == "xo":
            # rows past n_valid are unspecified (their compute is
            # skipped — the ragged contract); compare the live rows
            ga, ra = ga[:n_valid], ra[:n_valid]
        np.testing.assert_allclose(ga, ra, rtol=tol, atol=tol,
                                   err_msg=name)


# -- kernel parity at the ragged edges ---------------------------------

@pytest.mark.parametrize("pos0,n_valid", [
    (0, 16),      # cold, all-full chunk
    (0, 1),       # 1 valid row (the minimum suffix)
    (0, 13),      # prime valid length, cold
    (10, 13),     # warm mid-page start (COW-fork tail territory)
    (29, 7),      # warm start late in the window, prime remainder
    (8, 16),      # page-aligned warm start, full chunk
])
def test_kernel_parity_ragged_edges_fp32(pos0, n_valid):
    args, sc = _kernel_inputs(pos0=pos0, seed=pos0 * 31 + n_valid)
    _compare(args, sc, pos0, n_valid)


def test_kernel_parity_int8_pool(params):
    args, sc = _kernel_inputs(pos0=10, quant=True, seed=5)
    _compare(args, sc, 10, 13, tol=2e-4)


def test_kernel_parity_wide_chunk_multiple_q_blocks():
    """P=32 with block_q=16 forced: two q blocks, the second partially
    valid — the per-block online-softmax state must reset per block."""
    args, sc = _kernel_inputs(P=32, MB=8, pos0=16, seed=9)
    ref = fpb.prefill_attn_block_ref(*args, jnp.int32(16),
                                     jnp.int32(19), sc)
    got = fpb.fused_prefill_attn_pallas(*args, jnp.int32(16),
                                        jnp.int32(19), block_q=16,
                                        pages_per_step=2)
    np.testing.assert_allclose(np.asarray(got[0])[:19],
                               np.asarray(ref[0])[:19],
                               rtol=1e-4, atol=1e-4)


def test_kernel_rejects_non_divisor_block_q():
    args, sc = _kernel_inputs(P=16, seed=3)
    with pytest.raises(ValueError, match="block_q"):
        fpb.fused_prefill_attn_pallas(*args, jnp.int32(0),
                                      jnp.int32(16), block_q=5,
                                      pages_per_step=1)


def test_chunk_pool_write_redirects_pad_and_shared_pages():
    """write_chunk_to_pool: valid rows land at their positions through
    the WRITE table; pad rows and shared (redirected) pages land in
    scratch page 0 — a shared page's bytes never change."""
    from paddle_tpu.ops.paged_attention import write_chunk_to_pool
    L_BS, KV, hd, MB = 8, 2, 16, 4
    kp = jnp.zeros((9, L_BS, KV, hd), jnp.float32)
    vp = jnp.zeros_like(kp)
    wtable = jnp.asarray([0, 3, 5, 7], jnp.int32)   # page 0 = shared
    kn = jnp.ones((16, KV, hd), jnp.float32)
    vn = jnp.full((16, KV, hd), 2.0, jnp.float32)
    # pos0=8 -> logical pages 1..2; n_valid=10 -> 6 pad rows
    kp2, vp2 = write_chunk_to_pool(kp, vp, wtable, 8, 10, kn, vn)
    kp2 = np.asarray(kp2)
    assert np.all(kp2[3, :8] == 1.0)            # page 1 fully written
    assert np.all(kp2[5, 0:2] == 1.0)           # first 2 rows of page 2
    assert np.all(kp2[5, 2:] == 0.0)            # pad rows NOT here
    assert np.all(kp2[7] == 0.0)                # untouched page
    assert np.all(np.asarray(vp2)[3, :8] == 2.0)


# -- registry dispatch --------------------------------------------------

def test_dispatch_falls_back_under_interpret_with_reason():
    meta = fpb.prefill_meta_dims(32, 64, 4, 2, 16, 128, 8, 8,
                                 jnp.float32, jnp.float32, False)
    meta["interpret"] = True
    rows = KERNELS.explain("prefill_attn_block", meta)
    sel = [r for r in rows if r["selected"]]
    assert sel and sel[0]["name"] == "unfused"
    assert all(isinstance(r["reason"], str) and r["reason"]
               for r in rows)


def test_dispatch_vmem_budget_fallback():
    """A bucket whose weights + scratch exceed the budget falls back
    with the budget named; a generous budget admits it."""
    meta = fpb.prefill_meta_dims(128, 1024, 8, 8, 128, 4096, 16, 24,
                                 jnp.bfloat16, jnp.bfloat16, False)
    meta["interpret"] = False
    meta["vmem_budget"] = 1 << 20          # 1 MiB: nothing fits
    ok, why = fpb._supports_prefill_attn(meta)
    assert not ok and "VMEM" in why
    meta["vmem_budget"] = 64 << 20
    ok, why = fpb._supports_prefill_attn(meta)
    assert ok, why


def test_dispatch_rejects_bad_head_dim_and_ragged_bucket():
    meta = fpb.prefill_meta_dims(32, 40, 2, 2, 20, 96, 8, 8,
                                 jnp.float32, jnp.float32, False)
    meta["interpret"] = False
    ok, why = fpb._supports_prefill_attn(meta)
    assert not ok and "head_dim" in why
    meta2 = fpb.prefill_meta_dims(13, 512, 4, 2, 128, 128, 8, 8,
                                  jnp.float32, jnp.float32, False)
    meta2["interpret"] = False
    ok, why = fpb._supports_prefill_attn(meta2)
    assert not ok and "P=13" in why


def test_dispatch_refuses_sub_lane_head_dim_with_the_compilers_reason():
    """The chip's compiler has no layout for the per-head split of a
    head narrower than a 128-lane tile (tests/test_chip_compile.py
    compiles the hd=128 class): hd=64 falls back, naming the error."""
    meta = fpb.prefill_meta_dims(64, 1024, 16, 16, 64, 4096, 16, 24,
                                 jnp.bfloat16, jnp.bfloat16, False)
    meta["interpret"] = False
    ok, why = fpb._supports_prefill_attn(meta)
    assert not ok and "unsupported shape cast" in why
    assert not fpb.prefill_fused_selected(meta, "auto")


def test_resolve_modes_and_selected_gate():
    meta = fpb.prefill_meta_dims(16, 32, 4, 2, 16, 64, 8, 6,
                                 jnp.float32, jnp.float32, False)
    _, _, names = fpb.resolve_prefill_blocks(meta, "pallas")
    assert names == {"attn": "pallas_fused", "mlp": "pallas_fused"}
    _, _, names = fpb.resolve_prefill_blocks(meta, "ref")
    assert names == {"attn": "unfused", "mlp": "unfused"}
    with pytest.raises(ValueError):
        fpb.resolve_prefill_blocks(meta, "nope")
    # on CPU (interpret) auto dispatch rejects -> fused chunk not built
    assert not fpb.prefill_fused_selected(meta, "auto")
    assert fpb.prefill_fused_selected(meta, "pallas")
    assert not fpb.prefill_fused_selected(meta, False)


# -- engine integration -------------------------------------------------

def _stream(eng, n=8, seed=3, max_new=6, lens=(4, 40)):
    rng = np.random.RandomState(seed)
    reqs = [eng.submit(rng.randint(0, 97, (int(s),)).astype(np.int32),
                       GenerationConfig(max_new_tokens=max_new,
                                        greedy=True))
            for s in rng.randint(lens[0], lens[1], n)]
    eng.drain()
    return [r.output_ids for r in reqs]


def _engine(params, **kw):
    kw.setdefault("capacity", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_buckets", (16, 32))
    kw.setdefault("max_seq_len", 96)
    return ServingEngine(params, CFG, **kw)


def test_engine_default_flag_bit_identical_to_unfused(params):
    """FLAGS_fused_prefill default ON: on CPU dispatch falls back to
    the VERBATIM unfused chunk — greedy output is bit-identical to an
    explicitly-unfused engine, and the variant report says so."""
    a = _engine(params)
    b = _engine(params, fused_prefill=False)
    outs_a, outs_b = _stream(a), _stream(b)
    assert all(np.array_equal(x, y) for x, y in zip(outs_a, outs_b))
    assert a.prefill_variant["attn"] == "unfused"
    assert a.metrics()["prefill_variant"]["mode"] == "auto"
    assert b.prefill_variant == {"mode": "unfused", "attn": "unfused",
                                 "mlp": "unfused"}


def test_engine_prefix_cache_warm_bit_identical(params):
    """Warm suffix prefill over shared prefix pages: default-flag
    engine vs unfused engine, bit-identical outputs AND identical
    prefix-cache hit accounting."""
    rng = np.random.RandomState(9)
    sysp = rng.randint(0, 97, (24,)).astype(np.int32)
    prompts = [np.concatenate([sysp, rng.randint(0, 97, (5 + i,))])
               .astype(np.int32) for i in range(4)]

    def run(fp):
        eng = _engine(params, prefix_cache=True, num_blocks=64,
                      fused_prefill=fp)
        outs = []
        for p in prompts:
            r = eng.submit(p, GenerationConfig(max_new_tokens=5,
                                               greedy=True))
            eng.drain()
            outs.append(r.output_ids)
        return outs, eng._pcache.stats["tokens_skipped"]

    oa, skip_a = run(None)
    ob, skip_b = run(False)
    assert all(np.array_equal(x, y) for x, y in zip(oa, ob))
    assert skip_a == skip_b > 0


@pytest.mark.parametrize("cache_dtype", [None, "int8"])
def test_engine_forced_pallas_stream_token_parity(params, cache_dtype):
    """A forced-pallas engine (interpret mode) over a 20+-request
    mixed-arrival stream: greedy token parity with the unfused engine,
    <=1 prefill program per bucket, 1 decode program, zero retrace
    warnings."""
    ref = _engine(params, capacity=3, cache_dtype=cache_dtype,
                  fused_prefill=False)
    eng = _engine(params, capacity=3, cache_dtype=cache_dtype,
                  fused_prefill="pallas", observability=True)
    # warm both buckets + the decode program outside the watched window
    rng = np.random.RandomState(4)
    for s in (10, 20):
        eng.submit(rng.randint(0, 97, (s,)).astype(np.int32),
                   GenerationConfig(max_new_tokens=2, greedy=True))
    eng.drain()
    eng.reset_metrics()                     # arms the retrace watchdog
    outs_ref = _stream(ref, n=22, seed=13)
    outs = _stream(eng, n=22, seed=13)
    match = sum(bool(np.array_equal(a, b))
                for a, b in zip(outs, outs_ref))
    # interpret-mode Pallas vs the composition is roundoff-parity;
    # greedy argmax absorbs it in fp32 — but int8 pool writes ROUND
    # (round(x/s) is discontinuous), so a ~1e-6 perturbation can flip
    # a quantized cell and cascade through greedy decode: allow a
    # couple of boundary flips there, exact elsewhere
    floor = len(outs) if cache_dtype is None else len(outs) - 2
    assert match >= floor, f"{match}/{len(outs)} matched"
    m = eng.metrics()
    assert m["retrace_warnings"] == 0
    assert all(v == 1 for v in m["prefill_traces"].values()), \
        m["prefill_traces"]
    assert m["decode_traces"] == 1
    assert m["prefill_variant"] == {"mode": "pallas",
                                    "attn": "pallas_fused",
                                    "mlp": "pallas_fused"}
    assert m["prefill_pad_tokens"] > 0       # ragged chunks occurred


def test_engine_program_cache_keys_the_pin_route(params):
    """A chunk program traced under a KERNELS.force pin must not be
    replayed for unpinned calls: the per-bucket cache keys the route."""
    eng = _engine(params)
    outs1 = _stream(eng, n=2, seed=1)
    n_keys = len(eng._prefill_fns)
    with KERNELS.force("prefill_attn_block", "pallas_fused"), \
            KERNELS.force("prefill_mlp_block", "pallas_fused"):
        _stream(eng, n=2, seed=2)
    assert len(eng._prefill_fns) > n_keys    # distinct route entries
    outs3 = _stream(eng, n=2, seed=1)
    ref = _engine(params, fused_prefill=False)
    assert all(np.array_equal(a, b)
               for a, b in zip(outs3, _stream(ref, n=2, seed=1)))
    assert all(np.array_equal(a, b) for a, b in zip(outs1, outs3))


def test_engine_pallas_pin_rejected_on_tp_mesh(params):
    from paddle_tpu.inference import ServingMesh
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = ServingMesh.make(tp=2, collective="psum")
    with pytest.raises(ValueError, match="fused_prefill"):
        _engine(params, mesh=mesh, fused_prefill="pallas")
    # auto mode on a tp>1 mesh quietly keeps the unfused chunk
    eng = _engine(params, mesh=mesh)
    assert eng.prefill_variant["attn"] == "unfused"


def test_disagg_engine_parity_with_colocated(params):
    """Disaggregated engine with the default fused_prefill flag vs the
    colocated unfused engine: greedy output bit-identical (CPU
    dispatch falls back on both, so the flag must not perturb the
    handoff path)."""
    from paddle_tpu.inference.disagg import DisaggregatedEngine
    ref = _engine(params, capacity=2, fused_prefill=False)
    devs = jax.devices()
    eng = DisaggregatedEngine(params, CFG, capacity=2, prefill_slots=1,
                              prefill_devices=devs[:1],
                              decode_devices=devs[1:2] or devs[:1],
                              block_size=8, max_seq_len=96,
                              prefill_buckets=(16, 32))
    outs_ref = _stream(ref, n=6, seed=21)
    rng = np.random.RandomState(21)
    reqs = [eng.submit(rng.randint(0, 97, (int(s),)).astype(np.int32),
                       GenerationConfig(max_new_tokens=6, greedy=True))
            for s in rng.randint(4, 40, 6)]
    eng.drain()
    outs = [r.output_ids for r in reqs]
    assert all(np.array_equal(a, b) for a, b in zip(outs, outs_ref))


def test_generate_paged_prefix_store_fused_matches(params):
    """generate_paged(prefix_cache=store, fused_prefill=...): forced
    pallas (interpret) matches the unfused suffix path token-for-token
    on cold AND warm calls."""
    from paddle_tpu.inference.generation import generate_paged
    from paddle_tpu.inference.prefix_cache import PagedKVCacheStore
    rng = np.random.RandomState(2)
    toks = jnp.asarray(rng.randint(0, 97, (1, 20)), jnp.int32)
    toks2 = jnp.asarray(
        np.concatenate([np.asarray(toks)[:, :16],
                        rng.randint(0, 97, (1, 8))], axis=1), jnp.int32)
    g = GenerationConfig(max_new_tokens=5, greedy=True)

    def run(fp):
        store = PagedKVCacheStore(CFG, block_size=8, num_blocks=64)
        a = np.asarray(generate_paged(params, toks, CFG, g,
                                      block_size=8, prefix_cache=store,
                                      fused_prefill=fp))
        b = np.asarray(generate_paged(params, toks2, CFG, g,
                                      block_size=8, prefix_cache=store,
                                      fused_prefill=fp))
        return a, b

    a0, b0 = run(False)
    a1, b1 = run("pallas")
    assert np.array_equal(a0, a1) and np.array_equal(b0, b1)


def test_fused_prefill_audit_spec_is_clean(params):
    """A forced-pallas-prefill engine's bucket program audits clean
    (the serving_prefill_fused catalog entry's contract)."""
    from paddle_tpu.analysis import audit_spec
    eng = _engine(params, prefill_buckets=(16,),
                  fused_prefill="pallas")
    specs = [s for s in eng.program_specs(register=False)
             if s.name.startswith("serving_prefill_fused")]
    assert len(specs) == 1
    rep = audit_spec(specs[0])
    bad = [f for f in rep.findings if f.severity != "info"]
    assert not bad, [f.to_dict() for f in bad]
