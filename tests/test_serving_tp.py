"""Tensor-parallel sharded serving (inference/tp.py +
ServingEngine(mesh=...) + generate_paged(mesh=...)) on the forced
8-device virtual CPU mesh (conftest).

The acceptance bar (ISSUE 9): a tp-sharded engine serves a 20+-request
mixed-arrival stream with greedy parity vs the single-device engine —
BIT-identical for the documented collective="gather" placement,
token-identical for the default "psum" placement — with exactly 1
decode program and <=1 trace per prefill bucket under tp=2 and tp=4,
prefix-cache warm-vs-cold parity under sharding, clean rejection of
non-divisible head counts, and the sharded decode jaxpr carrying
exactly its DECLARED collectives (the jax_compat.axis_size static-
lookup regression)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.models import llama
from paddle_tpu.inference import (GenerationConfig, ServingEngine,
                                  ServingMesh, generate_paged)
from paddle_tpu.inference.tp import tp_reject_reason

pytestmark = pytest.mark.serving_tp

CFG = llama.LlamaConfig(vocab_size=97, hidden_size=64,
                        intermediate_size=128, num_hidden_layers=2,
                        num_attention_heads=4, num_key_value_heads=4,
                        max_position_embeddings=160,
                        dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(0), dtype=jnp.float32)


@pytest.fixture(scope="module")
def ref_stream(params):
    """The single-device engine's greedy output over THE 22-request
    mixed-arrival stream — the parity reference every placement is
    held to (computed once per module)."""
    return _mixed_stream(_engine(params))


def _engine(params, mesh=None, **kw):
    kw.setdefault("capacity", 3)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("max_seq_len", 64)
    return ServingEngine(params, CFG, mesh=mesh, **kw)


def _mixed_stream(eng, n=22, seed=7, max_new=5):
    """n requests arriving in WAVES interleaved with engine steps, so
    admission happens while other slots are mid-prefill/decode (the
    continuous-batching path, not one static batch)."""
    rng = np.random.RandomState(seed)
    sizes = rng.randint(4, 14, n)
    reqs = []
    for i, s in enumerate(sizes):
        reqs.append(eng.submit(
            rng.randint(0, 97, (int(s),)).astype(np.int32),
            GenerationConfig(max_new_tokens=max_new, greedy=True)))
        if i % 3 == 2:           # a couple of steps between waves
            eng.step()
            eng.step()
    eng.drain()
    return [r.output_ids for r in reqs]


def _same(a, b):
    return all(np.array_equal(x, y) for x, y in zip(a, b))


# -- greedy parity over a 20+-request mixed-arrival stream -------------

def test_gather_bit_parity_tp2_tp4_and_program_counts(params,
                                                      ref_stream):
    """collective="gather" is the documented BIT-identical placement:
    every matmul sees the exact single-device operands. One decode
    program + <=1 trace per prefill bucket must hold under sharding."""
    ref = ref_stream
    for tp in (2, 4):
        eng = _engine(params,
                      mesh=ServingMesh.make(tp=tp, collective="gather"))
        out = _mixed_stream(eng)
        assert _same(ref, out), f"tp={tp} greedy output diverged"
        m = eng.metrics()
        assert m["decode_traces"] == 1
        assert all(v <= 1 for v in m["prefill_traces"].values())
        assert m["mesh"] == {"axis": "tp", "tp": tp,
                             "collective": "gather"}


def test_psum_token_parity_tp4(params, ref_stream):
    """The default "psum" placement re-associates the o/down-proj
    reductions (documented roundoff-parity); greedy TOKENS must still
    agree on this fixed stream."""
    eng = _engine(params, mesh=ServingMesh.make(tp=4,
                                                collective="psum"))
    out = _mixed_stream(eng)
    assert _same(ref_stream, out)
    assert eng.metrics()["decode_traces"] == 1


def test_tp1_mesh_is_bit_identical_both_placements(params):
    """A 1-shard mesh is the identity: both placements must match the
    meshless engine bit-for-bit (psum/all_gather over one device)."""
    ref = _mixed_stream(_engine(params), n=6)
    for coll in ("psum", "gather"):
        out = _mixed_stream(
            _engine(params, mesh=ServingMesh.make(tp=1,
                                                  collective=coll)),
            n=6)
        assert _same(ref, out), coll


def test_zero_steady_state_retraces_after_warmup(params):
    eng = _engine(params, mesh=ServingMesh.make(tp=2),
                  observability=True)
    _mixed_stream(eng, n=8)
    eng.reset_metrics()          # arms the retrace watchdog
    _mixed_stream(eng, n=8, seed=11)
    m = eng.metrics()
    assert m["retrace_warnings"] == 0
    assert m["decode_traces"] == 1


# -- prefix cache under sharding ---------------------------------------

def test_prefix_cache_warm_vs_cold_parity_under_sharding(params):
    """The radix tree shares page INDICES; pages shard their head-dim
    contents — COW/eviction logic is untouched, and a warm sharded
    request must produce bit-identical output to the cold one."""
    mesh = ServingMesh.make(tp=2, collective="gather")
    ref = _mixed_stream(_engine(params), n=8)
    eng = _engine(params, mesh=mesh, prefix_cache=True)
    cold = _mixed_stream(eng, n=8)
    assert _same(ref, cold)
    warm = _mixed_stream(eng, n=8)      # same seed -> same prompts
    assert _same(cold, warm)
    assert eng.metrics()["prefix_cache"]["hits"] > 0


def test_int8_cache_sharded_parity(params):
    """int8 pools shard like bf16 ones (scales shard with their KV
    heads); sharded int8 greedy output must match single-device int8
    bit-for-bit under the gather placement."""
    ref = _mixed_stream(_engine(params, cache_dtype="int8"), n=8)
    out = _mixed_stream(
        _engine(params, cache_dtype="int8",
                mesh=ServingMesh.make(tp=2, collective="gather")), n=8)
    assert _same(ref, out)


# -- rejection / construction ------------------------------------------

def test_non_divisible_head_count_rejected_with_reason(params):
    ok, reason = ServingMesh.make(tp=3).supports(CFG)
    assert not ok and "not divisible by tp=3" in reason
    with pytest.raises(ValueError, match="not divisible by tp=3"):
        _engine(params, mesh=ServingMesh.make(tp=3))
    assert tp_reject_reason(CFG, 4) is None
    assert "intermediate_size" in tp_reject_reason(
        llama.LlamaConfig(vocab_size=97, hidden_size=64,
                          intermediate_size=101, num_hidden_layers=1,
                          num_attention_heads=4,
                          num_key_value_heads=4), 2)


def test_mesh_argument_normalization(params):
    from jax.sharding import Mesh
    eng = _engine(params, mesh=2)                   # int tp degree
    assert eng.metrics()["mesh"]["tp"] == 2
    raw = Mesh(np.array(jax.devices()[:2]), ("model",))
    eng = _engine(params, mesh=raw)                 # bare 1-D jax mesh
    assert eng.metrics()["mesh"]["axis"] == "model"
    with pytest.raises(ValueError, match="1-D mesh"):
        _engine(params, mesh=Mesh(
            np.array(jax.devices()[:4]).reshape(2, 2), ("a", "b")))
    with pytest.raises(ValueError, match="collective"):
        ServingMesh.make(tp=2, collective="allgatherz")


# -- collective observability ------------------------------------------

def test_flight_recorder_counts_declared_collectives(params):
    eng = _engine(params, mesh=ServingMesh.make(tp=2,
                                                collective="psum"),
                  observability=True)
    _mixed_stream(eng, n=6)
    m = eng.metrics()
    col = m["collectives"]
    # psum placement: one aggregated task per decode step / prefill
    # chunk, byte counts from the static [2L, B, D] payload shape
    assert col["calls"]["psum@tp"] > 0
    assert col["bytes"]["psum@tp"] > 0
    snap = col["latency_ms"]["psum@tp"]
    assert snap["count"] == col["calls"]["psum@tp"]
    # raw recorder counters never leak as top-level metric keys
    assert "collective_calls" not in m and "collective_bytes" not in m
    # reset_metrics restarts call/byte counters WITH the latency
    # histograms: the collectives sub-dict always reports one window
    eng.reset_metrics()
    _mixed_stream(eng, n=3, seed=5)
    m = eng.metrics()
    col = m["collectives"]
    assert col["calls"]["psum@tp"] == \
        col["latency_ms"]["psum@tp"]["count"] > 0
    # gather placement names its op accordingly
    eng2 = _engine(params, mesh=ServingMesh.make(tp=2,
                                                 collective="gather"),
                   observability=True)
    _mixed_stream(eng2, n=4)
    assert eng2.metrics()["collectives"]["calls"]["all_gather@tp"] > 0


# -- generate_paged(mesh=...) ------------------------------------------

def test_generate_paged_mesh_parity(params):
    rng = np.random.RandomState(3)
    ids = jnp.asarray(rng.randint(0, 97, (3, 12)).astype(np.int32))
    g = GenerationConfig(max_new_tokens=8, greedy=True)
    ref = np.asarray(generate_paged(params, ids, CFG, g))
    got = np.asarray(generate_paged(
        params, ids, CFG, g,
        mesh=ServingMesh.make(tp=4, collective="gather")))
    assert np.array_equal(ref, got)
    tok = np.asarray(generate_paged(
        params, ids, CFG, g,
        mesh=ServingMesh.make(tp=2, collective="psum")))
    assert np.array_equal(ref, tok)


def test_generate_paged_mesh_rejects_prefix_store(params):
    from paddle_tpu.inference import PagedKVCacheStore
    store = PagedKVCacheStore(CFG, num_blocks=32, block_size=4)
    with pytest.raises(NotImplementedError, match="ServingEngine"):
        generate_paged(params, jnp.zeros((1, 4), jnp.int32), CFG,
                       GenerationConfig(max_new_tokens=2, greedy=True),
                       block_size=4, prefix_cache=store, mesh=2)


# -- declared-collectives jaxpr regression (axis_size satellite) -------

def _collective_counts(jaxpr, counts):
    from paddle_tpu.analysis.rules import iter_subjaxprs
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("psum", "all_gather", "ppermute",
                                  "all_to_all", "reduce_scatter"):
            counts[eqn.primitive.name] = \
                counts.get(eqn.primitive.name, 0) + 1
        for _, sub, _ in iter_subjaxprs(eqn):
            _collective_counts(sub, counts)
    return counts


@pytest.mark.parametrize("coll,expect", [
    ("psum", {"psum": 2}),           # one per sub-block, in the scan body
    ("gather", {"all_gather": 2}),
])
def test_decode_jaxpr_carries_exactly_declared_collectives(
        params, coll, expect):
    """jax_compat.axis_size resolves STATICALLY: the sharded decode
    jaxpr must contain exactly the two declared collectives per layer
    scan body and nothing else — a psum(1, axis) fallback emitting a
    collective per axis_size call site would show up here."""
    eng = _engine(params, mesh=ServingMesh.make(tp=2, collective=coll))
    spec = [s for s in eng.program_specs(register=False)
            if s.name == "serving_decode_tp"][0]
    closed = jax.make_jaxpr(spec.fn)(*spec.args)
    counts = _collective_counts(closed.jaxpr, {})
    assert counts == expect, counts


def test_axis_size_static_lookup_inside_shard_map():
    from paddle_tpu.core.jax_compat import axis_size, shard_map_norep
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))

    def body(x):
        return x * axis_size("tp")

    out = jax.jit(shard_map_norep(body, mesh, P("tp"), P("tp")))(
        jnp.ones((4, 2)))
    assert float(np.asarray(out)[0, 0]) == 4.0
    closed = jax.make_jaxpr(jax.jit(shard_map_norep(
        body, mesh, P("tp"), P("tp"))))(jnp.ones((4, 2)))
    assert _collective_counts(closed.jaxpr, {}) == {}


# -- audit wiring ------------------------------------------------------

def test_catalog_tp_specs_audit_clean():
    from paddle_tpu.analysis import audit_spec
    from paddle_tpu.analysis.catalog import (CATALOG_PROGRAMS,
                                             build_catalog)
    assert "serving_decode_tp" in CATALOG_PROGRAMS
    assert "serving_prefill_tp_16" in CATALOG_PROGRAMS
    specs = build_catalog(names=["serving_decode_tp",
                                 "serving_prefill_tp_16"],
                          register=False)
    assert sorted(s.name for s in specs) == [
        "serving_decode_tp", "serving_prefill_tp_16"]
    for s in specs:
        assert s.mesh_axes == ("tp",)
        rep = audit_spec(s)
        assert rep.findings == [], [f.fingerprint for f in rep.findings]


def test_demo_tp_regression_fires_unknown_axis():
    """The mismatched-axis injection: the REAL per-shard decode body
    declared over the wrong mesh axis must trip the collective rule."""
    from paddle_tpu.analysis import audit_spec
    from paddle_tpu.analysis.catalog import build_demo_tp_regression
    rep = audit_spec(build_demo_tp_regression())
    codes = {f.code for f in rep.findings}
    assert "UNKNOWN_COLLECTIVE_AXIS" in codes, codes
    f = next(f for f in rep.findings
             if f.code == "UNKNOWN_COLLECTIVE_AXIS")
    assert f.detail["axis"] == "tp"
    assert f.detail["in_scope"] == ["model"]


def test_shard_mlp_meta_is_local_and_every_key_is_declared():
    """A shard dispatches ``decode_mlp_block`` on its LOCAL intermediate
    columns, and every key of the meta is one the op declares its
    callers' program caches cover."""
    from paddle_tpu.ops.pallas.fused_decode_block import decode_meta_dims
    from paddle_tpu.ops.pallas.registry import KERNELS
    meta = decode_meta_dims(2, 64, 64 // 2, jnp.float32)
    assert meta["F"] == 32
    fields, covers = KERNELS.cache_key_decl("decode_mlp_block")
    assert set(meta) == set(fields) | set(covers)
