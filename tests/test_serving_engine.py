"""Continuous-batching ServingEngine (inference/serving.py): exact
parity with single-request generate, slot recycle + page release, and
the zero-retrace steady state (<=1 trace per prefill bucket + 1 decode
program over a 30-request mixed-arrival stream)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.models import llama
from paddle_tpu.inference import (GenerationConfig, ServingEngine,
                                  generate)

CFG = llama.LlamaConfig(vocab_size=97, hidden_size=64,
                        intermediate_size=128, num_hidden_layers=2,
                        num_attention_heads=4, num_key_value_heads=2,
                        max_position_embeddings=128, dtype=jnp.float32,
                        remat=False)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(0), dtype=jnp.float32)


def _engine(params, **kw):
    kw.setdefault("capacity", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("max_seq_len", 64)
    return ServingEngine(params, CFG, **kw)


def test_outputs_match_single_request_generate(params):
    """Per-request greedy outputs must equal generate() exactly, across
    mixed prompt lengths / max_new_tokens and with capacity < requests
    (so admission waits and slots recycle mid-stream)."""
    rng = np.random.RandomState(0)
    eng = _engine(params)
    specs = [(5, 6), (9, 4), (13, 5), (7, 3), (21, 5)]  # (S, N); 21 > 16
    reqs = []                                           # -> multi-chunk
    for S, N in specs:
        p = rng.randint(0, 97, (S,)).astype(np.int32)
        reqs.append((p, eng.submit(
            p, GenerationConfig(max_new_tokens=N, greedy=True))))
    eng.drain()
    for (S, N), (p, r) in zip(specs, reqs):
        want = np.asarray(generate(
            params, jnp.asarray(p)[None], CFG,
            GenerationConfig(max_new_tokens=N, greedy=True)))[0, S:]
        np.testing.assert_array_equal(np.asarray(r.tokens), want)
        assert r.done and r.ttft is not None


def test_slot_recycle_and_page_release(params):
    """Finished requests must release every KV page and free their slot
    for the queue; a stream of 6 requests through 2 slots only works if
    recycling does."""
    rng = np.random.RandomState(1)
    eng = _engine(params, capacity=2)
    free0 = len(eng.mgr.free)
    rs = [eng.submit(rng.randint(0, 97, (6,)).astype(np.int32),
                     GenerationConfig(max_new_tokens=4, greedy=True))
          for _ in range(6)]
    # mid-stream: at most 2 in flight, the rest queued on slots
    eng.step()
    in_flight = sum(s.phase != "idle" for s in eng._slots)
    assert 1 <= in_flight <= 2
    assert len(eng.mgr.free) < free0
    eng.drain()
    assert all(r.done for r in rs)
    assert eng.counters["requests_completed"] == 6
    assert len(eng.mgr.free) == free0        # every page came back
    assert all(s.phase == "idle" for s in eng._slots)
    assert eng.idle


def test_steady_state_traces_over_30_request_stream(params):
    """The acceptance bar: a 30-request mixed-arrival stream (staggered
    submits, mixed lengths, greedy and sampled) completes with exactly
    1 decode program and <=1 trace per prefill bucket."""
    rng = np.random.RandomState(2)
    eng = _engine(params, capacity=3)
    pending = []
    for i in range(30):
        S = int(rng.randint(3, 17))
        N = int(rng.randint(2, 7))
        g = GenerationConfig(max_new_tokens=N, greedy=bool(i % 2),
                             temperature=0.8)
        pending.append((rng.randint(0, 97, (S,)).astype(np.int32), g))
    submitted = []
    # mixed arrivals: a few requests trickle in between scheduler steps
    while pending or not eng.idle:
        for _ in range(min(len(pending), 1 + int(rng.randint(0, 3)))):
            p, g = pending.pop(0)
            submitted.append(eng.submit(p, g))
        eng.step()
    assert len(submitted) == 30
    assert all(r.done for r in submitted)
    c = eng.counters
    assert c["requests_completed"] == 30
    assert c["decode_traces"] == 1, c
    assert set(c["prefill_traces"]) <= {8, 16}
    assert all(n <= 1 for n in c["prefill_traces"].values()), c
    assert c["calibration_traces"] == 0
    m = eng.metrics()
    assert 0.0 < m["slot_utilization"] <= 1.0
    assert m["tokens_per_sec"] > 0
    assert m["ttft_ms_mean"] is not None and m["ttft_ms_mean"] > 0


@pytest.mark.parametrize("kind", ["dense", "sharded", "hybrid"])
def test_one_decode_maker_traces_once_and_donates_its_carry(params, kind):
    """Every engine's decode program comes from the one maker around the
    forward picked at construction (dense, the same step per shard, the
    hybrid model's with its recurrent state): over a 30-request
    mixed-arrival stream each traces exactly one decode program, and
    every call donates all it carries (tokens, lengths, key, both
    pools, and the state where there is one)."""
    from paddle_tpu.inference import ServingMesh
    from paddle_tpu.models import granite_hybrid as gh
    assert not [n for n in vars(ServingEngine)
                if n.startswith("_make_decode_fn_")]
    cfg, kw = CFG, {}
    if kind == "sharded":
        kw["mesh"] = ServingMesh.make(tp=2)
    if kind == "hybrid":
        cfg = gh.GRANITE_HYBRID_TINY
        params = gh.init_params(cfg, jax.random.key(3))
    eng = ServingEngine(params, cfg, capacity=3, block_size=4,
                        prefill_buckets=(8, 16), max_seq_len=64, **kw)
    donated = eng._DECODE_DONATE + ((8,) if kind == "hybrid" else ())
    make, calls = eng._make_decode_fn, []

    def spying_maker():
        fn = make()

        def call(*args):
            out = fn(*args)
            calls.append(all(
                leaf.is_deleted() for i in donated
                for leaf in jax.tree_util.tree_leaves(args[i])))
            return out
        return call
    eng._make_decode_fn = spying_maker

    rng = np.random.RandomState(2)
    pending = [(rng.randint(0, cfg.vocab_size,
                            (int(rng.randint(3, 17)),)).astype(np.int32),
                GenerationConfig(max_new_tokens=int(rng.randint(2, 7)),
                                 greedy=True)) for _ in range(30)]
    submitted = []
    while pending or not eng.idle:
        for _ in range(min(len(pending), 1 + int(rng.randint(0, 3)))):
            submitted.append(eng.submit(*pending.pop(0)))
        eng.step()
    assert len(submitted) == 30 and all(r.done for r in submitted)
    assert eng.counters["decode_traces"] == 1, eng.counters
    assert len(calls) == eng.counters["decode_steps"] and all(calls)
    assert (eng._state is not None) == (kind == "hybrid")


def test_eos_stops_request_early(params):
    rng = np.random.RandomState(3)
    eng = _engine(params)
    p = rng.randint(0, 97, (9,)).astype(np.int32)
    g = GenerationConfig(max_new_tokens=6, greedy=True)
    probe = eng.submit(p, g)
    eng.drain()
    eos = probe.tokens[1]           # force eos at a greedy token
    expect = probe.tokens[:probe.tokens.index(eos) + 1]
    r = eng.submit(p, GenerationConfig(max_new_tokens=6, greedy=True,
                                       eos_token_id=eos))
    eng.drain()
    assert r.tokens == expect       # stops AT the first eos occurrence
    assert r.done and len(r.tokens) < 6


def test_int8_cache_path(params):
    """cache_dtype='int8': pools store int8, scales calibrate once from
    the first admitted prompt, and the greedy stream completes with
    valid tokens (token-exactness vs fp is not guaranteed under
    quantization; logits tolerance is covered in
    test_serving_attention)."""
    rng = np.random.RandomState(4)
    eng = _engine(params, cache_dtype="int8")
    rs = [eng.submit(rng.randint(0, 97, (s,)).astype(np.int32),
                     GenerationConfig(max_new_tokens=5, greedy=True))
          for s in (6, 11, 9)]
    eng.drain()
    assert eng._k_pools.dtype == jnp.int8
    assert eng.counters["calibration_traces"] == 1
    assert eng.counters["decode_traces"] == 1
    for r in rs:
        assert len(r.tokens) == 5
        assert all(0 <= t < 97 for t in r.tokens)


def test_submit_validation(params):
    eng = _engine(params)
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.submit(np.zeros(60, np.int32),
                   GenerationConfig(max_new_tokens=10))
    with pytest.raises(ValueError, match="empty"):
        eng.submit(np.zeros(0, np.int32))
    with pytest.raises(NotImplementedError, match="top-k"):
        eng.submit(np.zeros(4, np.int32),
                   GenerationConfig(max_new_tokens=2, top_k=5))


def test_backpressure_waits_for_pages(params):
    """A request that fits the pool but not the CURRENT free pages must
    wait in the queue (FIFO) and run after a release — not crash the
    allocator."""
    rng = np.random.RandomState(5)
    # pool of 9 usable pages (block_size 4): two 24-token requests use
    # 6 pages each, so the second waits for the first to finish
    eng = _engine(params, capacity=2, num_blocks=10)
    g = GenerationConfig(max_new_tokens=4, greedy=True)
    r1 = eng.submit(rng.randint(0, 97, (20,)).astype(np.int32), g)
    r2 = eng.submit(rng.randint(0, 97, (20,)).astype(np.int32), g)
    eng.step()
    assert sum(s.phase != "idle" for s in eng._slots) == 1  # r2 queued
    eng.drain()
    assert r1.done and r2.done
    assert len(r1.tokens) == 4 and len(r2.tokens) == 4
