"""The serving engine's fused q/k/v stack (PR 42): a dense model's
``q_proj`` / ``k_proj`` / ``v_proj`` as the one leaf ``qkv_proj``
(``fused_decode_block.fuse_qkv``), read by the one helper
``qkv_project`` in the decode program, the dense chunk and their
tensor-parallel mirrors. On the CPU, at small sizes: the fused product
is the three products side by side, a mesh's shard holds the columns of
its own heads, a quantized tree keeps its three leaves, and
``decode_variant["qkv"]`` names the form the decode program traced over.
What the compiler makes of the two forms on the chip is
tests/test_chip_compile.py's.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.models import llama
from paddle_tpu.inference import (GenerationConfig, ServingEngine,
                                  ServingMesh, generate)
from paddle_tpu.inference.tp import _local_dims
from paddle_tpu.ops.pallas import fused_decode_block as fdb
from paddle_tpu.quantization import ptq

# grouped heads, so that a wrong column order cannot pass: 8 query
# heads on 4 key heads, two query heads and one key head a shard of 4
CFG = llama.LlamaConfig(vocab_size=97, hidden_size=64,
                        intermediate_size=128, num_hidden_layers=2,
                        num_attention_heads=8, num_key_value_heads=4,
                        max_position_embeddings=160,
                        dtype=jnp.float32, remat=False)
DIMS = (CFG.num_attention_heads, CFG.num_key_value_heads, CFG.head_dim)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(0), dtype=jnp.float32)


def _engine(params, **kw):
    return ServingEngine(params, CFG, capacity=3, block_size=4,
                         max_seq_len=64, prefill_buckets=(8, 16), **kw)


def _serve(eng, n=7, seed=3, max_new=5):
    rng = np.random.RandomState(seed)
    reqs = [eng.submit(rng.randint(0, 97, (int(s),)).astype(np.int32),
                       GenerationConfig(max_new_tokens=max_new,
                                        greedy=True))
            for s in rng.randint(4, 30, n)]
    eng.drain()
    return [list(r.tokens) for r in reqs]


# -- the helper: one product over the fused leaf, or three ---------------

ROWS = {"decode_rows": (8,), "chunk_128": (1, 128)}
HEADS = {"mistral_32_8": (32, 8), "tp4_shard_8_2": (8, 2), "mha_4_4": (4, 4)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", HEADS)
@pytest.mark.parametrize("rows", ROWS)
def test_qkv_project_fused_leaf_equals_three_leaves(rows, heads, dtype):
    """Same operands, same accumulation, column for column: within a
    product's rounding at the dtype (no backend promises more: the
    CPU's blocks a product by its width, and the last bits follow)."""
    (H, KV), hd, D = HEADS[heads], 16, 96
    rng = np.random.RandomState(H + len(rows))
    mk = lambda *s: jnp.asarray(rng.randn(*s) * 0.1, dtype)  # noqa: E731
    lp = {"q_proj": mk(D, H * hd), "k_proj": mk(D, KV * hd),
          "v_proj": mk(D, KV * hd)}
    h = mk(*ROWS[rows], D)
    want = fdb.qkv_project(h, lp, (H, KV, hd))
    fused = fdb.fuse_qkv(lp)
    assert set(fused) == {"qkv_proj"}
    assert fused["qkv_proj"].shape == (D, (H + 2 * KV) * hd)
    got = fdb.qkv_project(h, fused, (H, KV, hd))
    ulp = {"float32": 2.0 ** -23, "bfloat16": 2.0 ** -8}[dtype]
    for g, w, n in zip(got, want, (H, KV, KV)):
        assert g.shape == w.shape == (*ROWS[rows], n, hd)
        g, w = (np.asarray(t, np.float32) for t in (g, w))
        np.testing.assert_allclose(g, w, rtol=4 * ulp,
                                   atol=4 * ulp * np.abs(w).max())


@pytest.mark.parametrize("tp", [1, 2, 4])
def test_a_shards_leaf_splits_into_its_own_heads(tp):
    """``local_heads`` / ``split_qkv`` / ``_local_dims`` read a shard's
    head counts off the fused leaf's width and the model's ratio."""
    H, KV, hd = DIMS
    L, D = 2, CFG.hidden_size
    rng = np.random.RandomState(tp)
    mk = lambda n: jnp.asarray(rng.randn(L, D, n * hd), jnp.float32)  # noqa: E731
    layers = {"q_proj": mk(H // tp), "k_proj": mk(KV // tp),
              "v_proj": mk(KV // tp),
              "gate_proj": jnp.zeros((L, D, CFG.intermediate_size // tp))}
    fused = fdb.fuse_qkv(layers)
    width = fused["qkv_proj"].shape[-1]
    assert fdb.local_heads(width, DIMS) == (H // tp, KV // tp)
    assert _local_dims({"layers": fused}, CFG) \
        == _local_dims({"layers": layers}, CFG) \
        == (H // tp, KV // tp, CFG.intermediate_size // tp)
    for got, name in zip(fdb.split_qkv(fused, DIMS), fdb.QKV_LEAVES):
        assert np.array_equal(got, layers[name])
    assert all(a is b for a, b in zip(fdb.split_qkv(layers, DIMS),
                                      (layers[k] for k in fdb.QKV_LEAVES)))


def test_param_specs_learn_the_fused_leaf(params):
    sm = ServingMesh.make(tp=4)
    fused = {**params, "layers": fdb.fuse_qkv(params["layers"])}
    specs = sm.param_specs(CFG, fused)["layers"]
    assert set(specs) == set(fused["layers"])
    assert specs["qkv_proj"] == P(None, None, "tp")
    plain = sm.param_specs(CFG, params)["layers"]
    assert set(plain) == set(params["layers"]) and "qkv_proj" not in plain


# -- the engine's tree ----------------------------------------------------

def test_engine_keeps_one_leaf_and_reports_it(params):
    eng = _engine(params)
    layers = eng.params["layers"]
    assert "qkv_proj" in layers
    assert not set(fdb.QKV_LEAVES) & set(layers)
    L, D = CFG.num_hidden_layers, CFG.hidden_size
    H, KV, hd = DIMS
    assert layers["qkv_proj"].shape == (L, D, (H + 2 * KV) * hd)
    # nothing of the caller's is consumed: its tree serves it as before
    assert set(fdb.QKV_LEAVES) <= set(params["layers"])
    assert eng.decode_variant["qkv"] is None        # no program yet
    toks = _serve(eng)
    assert eng.decode_variant["qkv"] == "fused_stack"
    assert eng.metrics()["decode_variant"]["qkv"] == "fused_stack"
    assert eng.counters["decode_traces"] == 1
    # the engine against the dense reference over the caller's three
    # leaves: the same greedy tokens
    rng = np.random.RandomState(3)
    for s, got in zip(rng.randint(4, 30, 7), toks):
        prompt = rng.randint(0, 97, (int(s),)).astype(np.int32)
        want = generate(params, prompt[None], CFG,
                        GenerationConfig(max_new_tokens=5, greedy=True))
        assert got == list(np.asarray(want)[0, int(s):])


@pytest.mark.parametrize("mesh", [None, 4], ids=["one_device", "tp4"])
def test_a_tree_fused_elsewhere_is_refused(params, mesh):
    """One way in. A column split of a global [q | k | v] hands shard 0
    query heads only and ``local_heads`` would read them as [q | k | v]:
    wrong tokens and no error. The engine makes the leaf itself, per
    shard, and refuses one made elsewhere, on one device too."""
    fused = {**params, "layers": fdb.fuse_qkv(params["layers"])}
    kw = {} if mesh is None else {"mesh": ServingMesh.make(tp=mesh)}
    with pytest.raises(ValueError, match="qkv_proj"):
        _engine(fused, **kw)


@pytest.mark.parametrize("collective", ["psum", "gather"])
def test_four_shards_serve_the_one_device_engines_tokens(params,
                                                         collective):
    """Over a mesh the leaf is made per shard, [q_loc | k_loc | v_loc]
    of that shard's heads: a column-sharded global concatenation would
    hand shard 0 query heads only, and these tokens would differ."""
    want = _serve(_engine(params))
    sm = ServingMesh.make(tp=4, collective=collective)
    eng = _engine(params, mesh=sm)
    leaf = eng.params["layers"]["qkv_proj"]
    assert not set(fdb.QKV_LEAVES) & set(eng.params["layers"])
    assert leaf.sharding.spec == P(None, None, "tp")
    H, KV, hd = DIMS
    h_loc, kv_loc = H // 4 * hd, KV // 4 * hd
    for i, shard in enumerate(sorted(leaf.addressable_shards,
                                     key=lambda s: s.index[2].start)):
        got = np.asarray(shard.data)
        for name, lo, n in (("q_proj", 0, h_loc), ("k_proj", h_loc, kv_loc),
                            ("v_proj", h_loc + kv_loc, kv_loc)):
            assert np.array_equal(
                got[..., lo:lo + n],
                np.asarray(params["layers"][name])[..., i * n:(i + 1) * n])
    assert _serve(eng) == want
    assert eng.decode_variant["qkv"] == "fused_stack"
    assert eng.counters["decode_traces"] == 1
    assert all(n <= 1 for n in eng.counters["prefill_traces"].values())


def test_a_meshs_three_stacks_are_gone_before_the_pools(params,
                                                        monkeypatch):
    """Over a mesh the three sharded stacks are the constructor's own
    copies (``ServingMesh.shard``): when the pools are made none is
    alive beside the leaf, so the peak of a shard's memory is not a
    leaf higher (on one device they are the caller's, and stay)."""
    H, KV, hd = DIMS
    L, D = CFG.num_hidden_layers, CFG.hidden_size
    stacks = {(L, D, H * hd), (L, D, KV * hd)}
    at_the_pools, zeros = [], jnp.zeros

    def watched(shape, *a, **kw):
        if len(shape) == 5:                  # a KV pool
            at_the_pools.append([
                x.shape for x in jax.live_arrays()
                if x.shape in stacks and len(x.sharding.device_set) == 4
                and x.sharding.spec == P(None, None, "tp")])
        return zeros(shape, *a, **kw)

    monkeypatch.setattr(jnp, "zeros", watched)
    eng = _engine(params, mesh=ServingMesh.make(tp=4))
    assert eng.params["layers"]["qkv_proj"].shape == (
        L, D, (H + 2 * KV) * hd)
    assert at_the_pools and not any(at_the_pools), at_the_pools


@pytest.mark.parametrize("bits", [8, 4])
def test_a_quantized_tree_keeps_its_three_leaves(params, bits):
    """int8 / int4 leaves are dicts with per-channel scales and packed
    rows: they stay as they are, the report says so, and the engine
    serves what the dense path over the same tree generates."""
    qp = ptq.quantize_weights(params, bits=bits)
    eng = _engine(qp)
    assert set(fdb.QKV_LEAVES) <= set(eng.params["layers"])
    assert "qkv_proj" not in eng.params["layers"]
    toks = _serve(eng, n=3)
    assert eng.decode_variant["qkv"] == "per_leaf"
    rng = np.random.RandomState(3)
    for s, got in zip(rng.randint(4, 30, 3), toks):
        prompt = rng.randint(0, 97, (int(s),)).astype(np.int32)
        want = generate(qp, prompt[None], CFG,
                        GenerationConfig(max_new_tokens=5, greedy=True))
        assert got == list(np.asarray(want)[0, int(s):])


def test_fused_prefill_kernels_take_the_leafs_three_ranges(params):
    """The fused prefill launch takes wq / wk / wv apart: over the
    engine's tree it is handed the leaf's column ranges and serves the
    unfused chunk's tokens."""
    want = _serve(_engine(params, fused_prefill=False), n=4)
    eng = _engine(params, fused_prefill="ref")
    assert _serve(eng, n=4) == want
    assert eng.prefill_variant["mode"] == "ref"
