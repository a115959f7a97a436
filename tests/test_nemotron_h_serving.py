"""A model whose every layer is ONE half (Mamba-2 | attention |
experts) through ``ServingEngine``: the same ``submit`` / ``step`` /
``drain``, scheduler, ``BlockManager`` and state manager as the other
pattern-run models, no option of its own. Small size on the CPU,
against the benchmark's plain reference (the recurrence, the route
written out, float32)."""
import dataclasses
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from paddle_tpu.inference import GenerationConfig, ServingEngine
from paddle_tpu.models import nemotron_h as nh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmarks.reference import nemotron_h as ref  # noqa: E402
from test_nemotron_h import model_of  # noqa: E402

CFG = nh.NEMOTRON_H_TINY
GEOMETRY = dict(capacity=3, block_size=8, num_blocks=64, max_seq_len=128,
                prefill_buckets=(8, 32))


@pytest.fixture(scope="module")
def params():
    return nh.init_params(CFG, jax.random.key(3))


def engine(params, cfg=CFG, **kw):
    return ServingEngine(params, cfg, **{**GEOMETRY, **kw})


def prompts(sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
            for n in sizes]


def serve(eng, ps, new=6, **kw):
    reqs = [eng.submit(p, GenerationConfig(max_new_tokens=new,
                                           greedy=True), **kw)
            for p in ps]
    eng.drain()
    return reqs


def margins(params, req, cfg=CFG):
    """Each served token's LOGIT below the reference's best there."""
    return ref.token_gaps(params, model_of(cfg), req.prompt,
                          np.asarray(req.tokens, np.int32), pad_to=16)


def test_served_tokens_are_the_references_choice(params):
    """Prefill in chunks, then decoding, through the engine: prompts
    that span three chunks (70 over buckets of 32), fill a bucket
    exactly, and are shorter than the smallest bucket, more requests
    than slots, so slots are reused and prefill chunks interleave with
    decode steps. At every served position the served token's logit is
    the float32 reference's largest (a gap of 0, or of float32 rounding
    where two logits tie: 1e-6)."""
    eng = engine(params)
    reqs = serve(eng, prompts([70, 5, 32, 9, 40, 3]), new=7)
    for r in reqs:
        assert len(r.tokens) == 7
        assert margins(params, r).max() < 1e-6
    assert len({t for r in reqs for t in r.tokens}) > 6
    c = eng.counters
    assert c["decode_traces"] == 1
    assert c["prefill_traces"] == {8: 1, 32: 1}
    assert c["state_resets"] == 6


def test_the_benchmark_judges_the_last_third_of_a_request(params):
    """``served_margins`` (what the benchmark's ``correct`` takes its
    mean over) is the last third of ``token_gaps``: the tokens whose
    logits carry the most of the recurrent state's history; the tests
    here hold EVERY token (``margins`` above)."""
    eng = engine(params)
    (req,) = serve(eng, prompts([13], seed=9), new=10)
    every = margins(params, req)
    assert every.shape == (10,)
    judged = ref.served_margins(params, model_of(CFG), req.prompt,
                                np.asarray(req.tokens, np.int32), pad_to=16)
    np.testing.assert_array_equal(judged, every[6:])
    assert [ref.last_third(np.arange(n)).size for n in (1, 2, 3, 64, 1024)] \
        == [1, 1, 1, 22, 342]


def test_a_slot_does_not_leak_its_last_request(params):
    a, b = prompts([37, 21], seed=1)
    eng = engine(params, capacity=1)
    _, second = serve(eng, [a, b])
    fresh = engine(params, capacity=1)
    alone, = serve(fresh, [b])
    assert second.tokens == alone.tokens
    assert margins(params, second).max() < 1e-6


def test_family_counters_read_under_recurrent(params):
    """Held 4 of 8 experts: the routing counts of the four EXPERT
    layers (of nine) ride with the state and read under
    ``metrics()["recurrent"]`` as granite's do, with the state
    manager's and the prefix cache's."""
    cfg = dataclasses.replace(CFG, n_routed_experts=4, num_experts=8,
                              expert_offset=4)
    part = dict(params, moe={**params["moe"],
                             "w_in": params["moe"]["w_in"][:, 4:],
                             "w_out": params["moe"]["w_out"][:, 4:]})
    eng = engine(part, cfg, prefix_cache=True)
    reqs = serve(eng, prompts([10, 26, 7], seed=6), new=5)
    m = eng.metrics()["recurrent"]
    assert m is eng.metrics()["pattern"] or m == eng.metrics()["pattern"]
    assert (m["recurrent_layers"], m["kv_layers"]) == (4, 1)
    assert m["state_resets"] == 3 and m["prefix_skipped_recurrent"] == 3
    e = m["experts"]
    live, steps = (eng.counters["live_slot_steps"],
                   eng.counters["decode_steps"])
    assert e["assignments"] == live * 3 * cfg.num_expert_layers
    assert 0 < e["assignments_held"] < e["assignments"]
    assert (e["held"], e["of"], e["offset"]) == (4, 8, 4)
    assert e["load_skew"] == pytest.approx(
        e["load_max"] / (e["assignments"] / (8 * 4 * steps)), rel=1e-3)
    # held experts that got a token, a layer a step: no more than are
    # held, nor than a step's assignments to them
    touched = eng.counters["experts_touched_held"]
    assert eng.counters["expert_layer_steps"] == 4 * steps
    assert e["touched_held"] == pytest.approx(touched / (4 * steps),
                                              abs=1e-3)
    assert 0 < touched <= min(4 * 4 * steps, e["assignments_held"])
    for r in reqs:          # the held half's tokens, to the reference
        assert margins(part, r, cfg).max() < 1e-6
    ssm, conv = cfg.state_shapes(3)
    assert m["state_bytes"] == 4 * int(np.prod(ssm)) \
        + 4 * int(np.prod(conv))


@pytest.mark.parametrize("kw,match", [
    (dict(mesh=2), "expert exchange"),
    (dict(weight_quant="int8"), "expert stacks"),
    (dict(cache_dtype="int8"), "calibrated through the dense"),
    (dict(prefix_cache=True, kv_offload=True), "state snapshots"),
], ids=["mesh", "weight_quant", "cache_int8", "kv_offload"])
def test_refusals_name_what_is_missing(params, kw, match):
    with pytest.raises(ValueError, match=match):
        engine(params, **kw)


def test_state_dtype_is_the_state_pools(params):
    eng = engine(params, state_dtype="bfloat16")
    assert eng._state["ssm"].dtype == jnp.bfloat16
    r, = serve(eng, prompts([50], seed=5), new=10)
    assert len(r.tokens) == 10
    assert eng.metrics()["recurrent"]["state_dtype"] == "bfloat16"


def test_programs_audit_clean(params):
    eng = engine(params)
    reports = eng.audit(register=False)
    assert [r.program for r in reports] == [
        "serving_decode", "serving_prefill_8", "serving_prefill_32"]
    for r in reports:
        assert [f for f in r.findings if f.severity == "error"] == [], \
            r.to_dict()
