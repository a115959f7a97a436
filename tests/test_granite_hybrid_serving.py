"""A model with recurrent layers through ``ServingEngine``: the same
``submit`` / ``step`` / ``drain`` as the dense decoder, with the slots'
recurrent state beside the paged KV cache. Small size on the CPU,
against the benchmark's plain reference (the recurrence, float32)."""
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from paddle_tpu.inference import GenerationConfig, ServingEngine
from paddle_tpu.models import granite_hybrid as gh
from paddle_tpu.models import llama
from paddle_tpu.observability import SERVE_SPANS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmarks.reference import granite_hybrid as ref  # noqa: E402
from test_granite_hybrid import model_of  # noqa: E402

CFG = gh.GRANITE_HYBRID_TINY
GEOMETRY = dict(capacity=3, block_size=8, num_blocks=64, max_seq_len=128,
                prefill_buckets=(8, 32))


@pytest.fixture(scope="module")
def params():
    return gh.init_params(CFG, jax.random.key(3))


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
    """Each served token's logit below the reference's best there."""
    return ref.served_margins(params, model_of(cfg), req.prompt,
                              np.asarray(req.tokens, np.int32), pad_to=16)


def test_served_tokens_are_the_references_choice(params):
    """(b) through the engine: prompts that span three chunks (70 over
    buckets of 32), fill a bucket exactly, and are shorter than the
    smallest bucket, more requests than slots, so slots are reused and
    prefill chunks interleave with decode steps. Every served token is
    the float32 reference's own choice at its position (a gap of 0, or
    of rounding where two logits tie)."""
    eng = engine(params)
    reqs = serve(eng, prompts([70, 5, 32, 9, 40, 3]), new=7)
    for r in reqs:
        assert len(r.tokens) == 7
        assert margins(params, r).max() < 1e-6
    # it is not one token repeated: the layers decide
    assert len({t for r in reqs for t in r.tokens}) > 6
    c = eng.counters
    assert c["decode_traces"] == 1
    assert c["prefill_traces"] == {8: 1, 32: 1}
    assert c["state_resets"] == 6


def test_a_slot_does_not_leak_its_last_request(params):
    """(f) One slot, two requests one after the other: the second's
    tokens are those a fresh engine serves it, though the slot's state
    still held the first's when it was admitted."""
    a, b = prompts([37, 21], seed=1)
    eng = engine(params, capacity=1)
    first, second = serve(eng, [a, b])
    fresh = engine(params, capacity=1)
    alone, = serve(fresh, [b])
    assert second.tokens == alone.tokens
    assert margins(params, second).max() < 1e-6
    # and the state the slot is left with is the second request's alone
    for key in ("ssm", "conv"):
        np.testing.assert_allclose(np.asarray(eng._state[key]),
                                   np.asarray(fresh._state[key]),
                                   atol=1e-6)
    # without the reset the first request's state would be carried on
    leaky = engine(params, capacity=1)
    leaky._state_reset_fn = lambda state, slot: state
    serve(leaky, [a, b])
    assert float(jnp.abs(leaky._state["ssm"]
                         - fresh._state["ssm"]).max()) > 1e-4


def test_state_reset_is_a_span_inside_admit(params):
    eng = engine(params, observability=True)
    serve(eng, prompts([12, 30]))
    names = [e.name for e in eng.observability.timeline.events()]
    assert "serve/state_reset" in SERVE_SPANS
    assert names.count("serve/state_reset") == 2
    # a child of serve/admit: recorded before the admit that holds it
    first_reset = names.index("serve/state_reset")
    assert "serve/admit" in names[first_reset:]


def test_prefix_cache_is_off_and_counted(params):
    """(g) A prefix match would skip tokens whose recurrent state
    nobody stored: with recurrent layers the cache stays off, every
    request it would have looked up is counted, and what is served is
    still right, also for requests that share a long prefix."""
    shared = prompts([40], seed=2)[0]
    ps = [np.concatenate([shared, t]) for t in prompts([5, 9, 3], seed=3)]
    eng = engine(params, prefix_cache=True)
    reqs = serve(eng, ps)
    assert eng.counters["prefix_skipped_recurrent"] == 3
    assert eng._pcache is None and "prefix_cache" not in eng.metrics()
    assert eng.counters["prefix_hit_tokens"] == 0
    assert eng.counters["prefill_tokens"] == sum(p.size for p in ps)
    for r in reqs:
        assert margins(params, r).max() < 1e-6
    plain = engine(params)
    serve(plain, ps)
    assert plain.counters["prefix_skipped_recurrent"] == 0


def test_what_needs_state_snapshots_is_refused(params):
    """(g) Preemption and the host tier would have to keep a copy of a
    slot's recurrent state beside its pages: refused, by that name."""
    with pytest.raises(ValueError, match="state snapshots"):
        engine(params, prefix_cache=True, kv_offload=True)
    eng = engine(params, capacity=1)
    low = eng.submit(prompts([20])[0],
                     GenerationConfig(max_new_tokens=12, greedy=True),
                     priority=5)
    for _ in range(4):
        eng.step()
    assert eng.live_slots == 1
    # a more urgent request waits for the slot; it does not evict
    high = eng.submit(prompts([6], seed=4)[0],
                      GenerationConfig(max_new_tokens=4, greedy=True),
                      priority=0)
    eng.drain()
    assert eng.counters["preemptions"] == 0
    assert low.preemptions == 0 and len(low.tokens) == 12
    assert high.admit_t >= low.finish_t
    assert margins(params, high).max() < 1e-6
    with pytest.raises(RuntimeError, match="state snapshots"):
        eng._preempt(0)


@pytest.mark.parametrize("kw,match", [
    (dict(mesh=2), "expert exchange"),
    (dict(weight_quant="int8"), "expert stacks"),
    (dict(cache_dtype="int8"), "calibrated through the dense"),
], ids=["mesh", "weight_quant", "cache_int8"])
def test_other_refusals_name_what_is_missing(params, kw, match):
    with pytest.raises(ValueError, match=match):
        engine(params, **kw)


def test_state_dtype_belongs_to_recurrent_models():
    cfg = llama.LLAMA_TINY
    with pytest.raises(ValueError, match="no recurrent layer"):
        ServingEngine(llama.init_params(cfg), cfg, state_dtype="bfloat16",
                      **GEOMETRY)


def test_state_dtype_is_the_state_pools(params):
    eng = engine(params, state_dtype="bfloat16")
    assert eng._state["ssm"].dtype == jnp.bfloat16
    r, = serve(eng, prompts([50], seed=5), new=10)
    assert len(r.tokens) == 10
    m = eng.metrics()["recurrent"]
    assert m["state_dtype"] == "bfloat16"
    full = engine(params).metrics()["recurrent"]
    assert full["state_dtype"] == "float32"
    ssm, conv = CFG.state_shapes(3)
    assert full["state_bytes"] == 4 * int(np.prod(ssm)) \
        + 4 * int(np.prod(conv))
    assert m["state_bytes"] == full["state_bytes"] - 2 * int(np.prod(ssm))


def test_engine_reckons_the_layers_that_hold_kv(params):
    """The pools are as deep as the attention layers (1 of 4 here), a
    page's bytes count those layers, and the dense decoder's roofline
    model says plainly that it does not reckon this model."""
    eng = engine(params)
    KV, hd = CFG.num_key_value_heads, CFG.head_dim
    assert eng._k_pools.shape == (1, 64, 8, KV, hd)
    assert eng._page_nbytes == 2 * 1 * 8 * KV * hd * 4
    roof = eng.metrics()["roofline"]
    assert roof["reckoned"] is False and "recurrent" in roof["why"]
    m = eng.metrics()["recurrent"]
    assert (m["recurrent_layers"], m["kv_layers"]) == (3, 1)
    # the dense decoder's numbers are what they were: every layer
    cfg = llama.LLAMA_TINY
    dense = ServingEngine(llama.init_params(cfg), cfg, **GEOMETRY)
    assert dense._k_pools.shape[0] == cfg.num_hidden_layers
    assert dense._page_nbytes == 2 * cfg.num_hidden_layers * 8 \
        * cfg.num_key_value_heads * cfg.head_dim \
        * jnp.dtype(cfg.dtype).itemsize
    assert dense.metrics()["roofline"]["layers"] == cfg.num_hidden_layers
    assert "recurrent" not in dense.metrics()


def test_expert_counts_are_summed_on_the_device(params):
    """Held 4 of 8 experts: the routing counts ride with the state,
    reach ``counters`` only through ``metrics()``, and restart with
    ``reset_metrics()``."""
    import dataclasses
    cfg = dataclasses.replace(CFG, num_local_experts=4, num_experts=8,
                              expert_offset=4)
    part = dict(params, moe={**params["moe"],
                             "w_in": params["moe"]["w_in"][:, 4:],
                             "w_out": params["moe"]["w_out"][:, 4:]})
    eng = engine(part, cfg)
    reqs = serve(eng, prompts([10, 26, 7], seed=6), new=5)
    assert eng.counters["expert_assignments"] == 0       # not read yet
    m = eng.metrics()["recurrent"]["experts"]
    steps = eng.counters["decode_steps"]
    live = eng.counters["live_slot_steps"]
    assert m["assignments"] == live * 3 * cfg.num_hidden_layers
    assert 0 < m["assignments_held"] < m["assignments"]
    assert (m["held"], m["of"], m["offset"]) == (4, 8, 4)
    assert 1 <= m["load_max"] <= 3
    assert m["load_skew"] == pytest.approx(
        m["load_max"] / (m["assignments"] / (8 * 4 * steps)), rel=1e-3)
    for r in reqs:          # the held half's tokens, to the reference
        assert margins(part, r, cfg).max() < 1e-6
    eng.reset_metrics()
    assert eng.metrics()["recurrent"]["experts"]["assignments"] == 0
    assert "expert_assignments" not in eng.metrics()     # frozen key set


def test_dense_engine_counts_prefix_tokens():
    """The counters the prefix-sessions cell reads: prompt tokens the
    radix tree was asked about and those it matched."""
    cfg = llama.LLAMA_TINY
    eng = ServingEngine(llama.init_params(cfg), cfg, prefix_cache=True,
                        **GEOMETRY)
    shared = prompts([40], seed=7)[0]
    first = np.concatenate([shared, prompts([6], seed=8)[0]])
    second = np.concatenate([shared, prompts([9], seed=9)[0]])
    serve(eng, [first])
    assert (eng.counters["prefix_lookup_tokens"],
            eng.counters["prefix_hit_tokens"]) == (46, 0)
    serve(eng, [second])
    assert eng.counters["prefix_lookup_tokens"] == 46 + 49
    assert eng.counters["prefix_hit_tokens"] == 40
    assert "prefix_hit_tokens" not in eng.metrics()
    eng.reset_metrics()
    assert eng.counters["prefix_hit_tokens"] == 0


def test_programs_audit_clean(params):
    """The static audit of the engine's programs (donation, carry,
    retrace hazards) covers the state argument too."""
    eng = engine(params)
    reports = eng.audit(register=False)
    assert [r.program for r in reports] == [
        "serving_decode", "serving_prefill_8", "serving_prefill_32"]
    for r in reports:
        assert [f for f in r.findings if f.severity == "error"] == [], \
            r.to_dict()
