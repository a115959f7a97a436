"""What PR 27 added to the benchmark: the hybrid configuration's cost
model (counts by hand at one shape), its reference's share, the session
generator (the same sessions for every seed, each turn's prompt a
prefix of the next), the reducer that cuts a computation out of one
program's executions, and both new cells end to end on the tiny preset.
"""
import json
import os

import numpy as np
import pytest

from benchmarks import harness, run as bench_run
from benchmarks.trace import Trace

G4H, PFX = "granite4h-chat-short", "mistral7b-prefix-sessions"
PEAK = harness.load_json("peaks.json")["TPU v5 lite"]


@pytest.fixture(scope="module")
def granite():
    cfg = harness.load_json("configs", "granite-4.0-h-small-l10-e36.json")
    return cfg, {k: cfg[k] for k in cfg["published_keys"]}


# -- the configuration's file ------------------------------------------
def test_config_keeps_the_published_keys(granite):
    """Every key of the published config.json stands in the file under
    its own name; the three reduced ones say what they were."""
    cfg, model = granite
    published = {
        "hidden_size": 4096, "intermediate_size": 768,
        "shared_intermediate_size": 1536, "num_attention_heads": 32,
        "num_key_value_heads": 8, "num_experts_per_tok": 10,
        "mamba_n_heads": 128, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
        "mamba_chunk_size": 256, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "attention_multiplier": 0.0078125,
        "logits_scaling": 16, "rms_norm_eps": 1e-05,
        "tie_word_embeddings": True, "position_embedding_type": "nope",
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid"}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert len(cfg["layer_types"]) == 40            # kept whole
    assert [i for i, t in enumerate(cfg["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    assert set(cfg["reduced"]) == {"num_hidden_layers",
                                   "num_local_experts", "vocab_size"}
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"],
            cfg["vocab_size"]) == (10, 36, 50176)
    assert (model["num_experts"], model["expert_offset"]) == (72, 0)
    assert cfg["engine"]["prefix_cache"] is False
    assert cfg["engine"]["state_dtype"] == "float32"
    assert cfg["controls"]["state_bf16"] == {
        "engine": {"state_dtype": "bfloat16"}}


def test_weights_hold_the_share_and_count(granite):
    """Only the held experts are made; the parameter count is the
    issue's arithmetic (4,757 M: 8.86 GiB in bf16)."""
    _, model = granite
    weights = harness.plugin("weights", "granite_hybrid")
    table = weights.shapes(model)
    assert table["moe"]["w_in"] == (36, 4096, 1536)
    assert table["moe"]["router"] == (4096, 72)
    assert table["mamba"]["in_proj"] == (4096, 8192 + 8448 + 128)
    n = weights.count(model)
    assert abs(n - 4757e6) < 3e6
    tiny = harness.load_json(
        "configs", "granite-4.0-h-small-l10-e36.json")["rehearse"]["model"]
    tree = weights.make({**model, **tiny}, 3)
    assert tree["moe"]["w_in"].shape[:2] == (4, tiny["num_local_experts"])
    a = np.exp(np.asarray(tree["mamba"]["A_log"], np.float64))
    assert 0.1 <= a.min() and a.max() <= 1.0       # a state that remembers


# -- the cost model, by hand --------------------------------------------
def test_cost_model_counts_by_hand(granite):
    _, model = granite
    cm = harness.plugin("cost_models", "granite_hybrid")
    z = cm.dims(model)
    assert (z["L"], z["Lm"], z["La"]) == (10, 9, 1)
    assert cm.mamba_params(z) == 4096 * 16768 + 8192 * 4096
    assert cm.attn_params(z) == 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert cm.expert_params(z) == 3 * 4096 * 768
    # 64 tokens: (62/72)^64 of an expert's chances to stay untouched
    assert cm.touched(z, 64) == pytest.approx(36 * (1 - (62 / 72) ** 64))
    assert cm.touched(z, 64) > 35.99 and cm.touched(z, 1) == pytest.approx(5)

    # one layer's expert launches for 64 tokens: 64 x 10 x 36/72 = 320
    # rows through 9.4 M weights each way; all 36 experts' weights
    flops, moved = cm.moe_experts(model, 64)
    assert flops == pytest.approx(2 * 320 * 9437184)
    assert moved == pytest.approx(
        cm.touched(z, 64) * 9437184 * 2 + 320 * (2 * 4096 + 3 * 768) * 2)

    # one layer's state update for 64 slots: 64 x 128 x 64 x 128
    # float32 elements read and written: 0.5 GiB; 6 operations each
    flops, moved = cm.ssm_update(model, 64)
    elems = 64 * 128 * 64 * 128
    assert flops == 6 * elems
    assert moved == 2 * elems * 4 + 64 * (8448 + 2 * 8192) * 2
    assert 2 * elems * 4 == 2 ** 29

    # the whole step, 64 live slots with 16 k tokens of context: the
    # issue's 6.8 + 4.8 + 2.7 GB, and the KV beside them
    flops, moved = cm.decode_step(model, 64, 64, 16384)
    always = (9 * cm.mamba_params(z) + cm.attn_params(z)
              + 10 * (4096 * 72 + 3 * 4096 * 1536) + 4096 * 50176)
    assert always * 2 == pytest.approx(2.72e9, rel=0.01)
    experts = 10 * cm.moe_experts(model, 64)[1]
    state = 9 * cm.ssm_update(model, 64)[1]
    assert experts == pytest.approx(6.8e9, rel=0.01)
    assert state == pytest.approx(4.85e9, rel=0.01)
    kv = (16384 + 64) * 1 * 2 * 8 * 128 * 2
    assert moved == pytest.approx(always * 2 + experts + state + kv
                                  + 64 * 50176 * 4)
    least, bound = cm.least_seconds(flops, moved, PEAK)
    assert bound == "memory" and least == pytest.approx(0.0176, rel=0.02)
    assert (experts + state) / moved > 0.8        # the cell's reason

    # by name, as the readers call them: per execution (all layers)
    shape = {"slots": 64, "live_slots": 64.0, "live_tokens": 16384.0}
    assert cm.PROGRAMS["decode_step"](model, shape) == (flops, moved)
    assert cm.KERNELS["moe_experts"](model, shape)[1] == \
        pytest.approx(experts)
    assert cm.KERNELS["ssm_update"](model, shape)[1] == pytest.approx(state)
    # fewer live slots: less state, fewer experts touched
    half = cm.KERNELS["ssm_update"](model, {**shape, "live_slots": 32})
    assert half[1] == pytest.approx(state / 2)
    # the chunked scan, for 512 prompt tokens of one layer, by hand
    flops, moved = cm.ssd_scan(model, 512)
    per_token = 256 * 128 + 128 * 256 * 64 + 2 * 128 * 2 * 64 * 128
    assert flops == 512 * per_token
    assert moved == 512 * (8448 + 128 + 8192) * 2 + 512 * 8192 * 4
    assert cm.KERNELS["ssd_scan"](model, {"tokens": 512})[0] == 9 * flops


# -- the reference's share ----------------------------------------------
def test_reference_is_given_the_same_share(granite):
    """The reference computes the held experts' part only: handed the
    other half it gives another result, and the two halves and the
    shared MLP, counted once, are the uncut layer."""
    import jax.numpy as jnp
    ref = harness.plugin("reference", "granite_hybrid")
    rng = np.random.default_rng(0)
    D, E, F, Fs = 32, 8, 16, 24

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
    layer = {"router": w(D, E), "w_in": w(E, D, 2 * F), "w_out": w(E, F, D),
             "shared_in": w(D, 2 * Fs), "shared_out": w(Fs, D)}
    u = w(10, D) * 5

    def part(held, offset):
        sz = {"F": F, "k": 3, "held": held, "offset": offset}
        return np.asarray(ref.experts_and_shared(u, {
            **layer, "w_in": layer["w_in"][offset:offset + held],
            "w_out": layer["w_out"][offset:offset + held]}, sz))
    shared = np.asarray(ref._mm(
        (lambda h: (h[:, :Fs] / (1 + np.exp(-h[:, :Fs]))) * h[:, Fs:])(
            np.asarray(ref._mm(u, layer["shared_in"]))),
        layer["shared_out"]))
    lo, hi, whole = part(4, 0), part(4, 4), part(8, 0)
    assert np.abs(lo - hi).max() > 1e-3
    np.testing.assert_allclose(lo + hi - shared, whole, atol=1e-5)


def test_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(harness.HERE, "reference", "granite_hybrid.py")
    with open(path) as fh:
        tree = ast.parse(fh.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert names <= {"functools", "jax", "numpy"}, names


# -- the session generator ----------------------------------------------
class Driver:
    """What drivers/serving_engine.py does with a generator: submits
    what is due, and says ``finished(r.spec)`` from a frame that holds
    the request's record as ``r``."""

    class Record:
        def __init__(self, spec, tokens):
            self.spec = spec
            self.req = type("Req", (), {"tokens": tokens})()

    def __init__(self, gen, answer):
        self.gen, self.answer, self.seen = gen, answer, []

    def run(self, until, dt=0.05, serve_s=0.2):
        now, live = 0.0, []
        while now < until:
            for spec in self.gen.due(now):
                self.seen.append(spec)
                live.append((now + serve_s, spec))
            for done_t, spec in [x for x in live if x[0] <= now]:
                live.remove((done_t, spec))
                r = self.Record(spec, self.answer(spec))
                self.gen.finished(r.spec)
            now += dt
        return self.seen


def _sessions_mix(**over):
    mix = harness.load_mix("prefix-sessions")
    mix.update(mix["rehearse"])
    mix.update(over)
    return mix


def _answer(spec):
    return list(np.random.default_rng(
        [spec["session"], spec["turn"], 99]).integers(
            0, 512, spec["max_new_tokens"]))


def test_sessions_are_the_same_for_every_seed(monkeypatch):
    """Which system prompt, every length and every start are drawn from
    the mix's order_seed: two seeds offer the same sessions in the same
    order and differ only in the tokens."""
    Gen = harness.plugin("generators", "open_loop_sessions").Generator
    mix = _sessions_mix()
    a, b = (Gen(mix, seed, 6.0, 512) for seed in (1, 2 ** 31 + 5))
    win = lambda g: [s for s in g.sessions if s["phase"] == "window"]  # noqa: E731
    shape = lambda g: [(s["system"], s["user_len"], s["output_len"])  # noqa: E731
                       for s in win(g)]
    assert shape(a) == shape(b)
    assert len(win(a)) == round(mix["arrivals"]["rate_per_s"] * 6.0)
    assert [r["due"] for r in a.pending if r["phase"] == "window"] == \
        [r["due"] for r in b.pending if r["phase"] == "window"]
    assert a.offered() == b.offered()
    assert any((x["prompt"][:8] != y["prompt"][:8]).any()
               for x, y in zip(a.pending, b.pending))
    assert Gen(mix, 1, 6.0, 512).pending[3]["prompt"].tolist() == \
        a.pending[3]["prompt"].tolist()             # a seed repeats


def test_each_turn_extends_the_one_before(monkeypatch):
    """Turn k's prompt is the system prompt, the earlier turns (user
    part and SERVED tokens) and a new user part; it falls due think_s
    after the answer; the window cuts a session."""
    mod = harness.plugin("generators", "open_loop_sessions")
    clock = {"t": 0.0}
    monkeypatch.setattr(mod.time, "perf_counter", lambda: clock["t"])
    mix = _sessions_mix(think_s=0.5)
    gen = mod.Generator(mix, 7, 6.0, 512)

    class Stepped(Driver):
        def run(self, until, dt=0.05, serve_s=0.2):
            now, live = 0.0, []
            while now < until:
                clock["t"] = 100.0 + now
                for spec in self.gen.due(now):
                    self.seen.append(spec)
                    live.append((now + serve_s, spec))
                for item in [x for x in live if x[0] <= now]:
                    live.remove(item)
                    r = self.Record(item[1], self.answer(item[1]))
                    self.gen.finished(r.spec)
                now += dt
            return self.seen

    seen = Stepped(gen, _answer).run(gen.end + 2.0)
    assert gen.answers_unseen == 0
    by = {}
    for spec in seen:
        by.setdefault(spec["session"], []).append(spec)
    assert max(len(v) for v in by.values()) == mix["turns"]
    assert min(len(v) for v in by.values()) < mix["turns"]    # the cut
    assert all(spec["due"] <= gen.end for spec in seen)
    for turns in by.values():
        s = gen.sessions[turns[0]["session"]]
        system = gen.system[s["system"]]
        assert [t["turn"] for t in turns] == list(range(len(turns)))
        assert turns[0]["prompt"][:system.size].tolist() == system.tolist()
        for prev, nxt in zip(turns, turns[1:]):
            history = np.concatenate([prev["prompt"], _answer(prev)])
            assert nxt["shared_tokens"] == history.size
            assert nxt["prompt"][:history.size].tolist() == history.tolist()
            assert nxt["prompt"].size == history.size \
                + s["user_len"][nxt["turn"]]
            assert nxt["due"] >= prev["due"] + 0.2 + 0.5 - 0.06
    # the longest session the full mix can offer fits the engine
    full = harness.load_mix("prefix-sessions")
    assert max(full["system_prompts"]) + full["turns"] * (
        full["user_len"]["max"] + full["output_len"]["max"]) == 2560
    assert harness.load_json(
        "configs", "mistral-7b-v0.3-l16.json")["engine"]["max_seq_len"] \
        == 2560


def test_unseen_answers_are_filled_and_counted():
    Gen = harness.plugin("generators", "open_loop_sessions").Generator
    gen = Gen(_sessions_mix(), 3, 6.0, 512)
    spec = gen.due(1.0)[0]
    gen.finished(spec)                 # no record ``r`` in this frame
    assert gen.answers_unseen == 1
    nxt = [r for r in gen.pending if r["session"] == spec["session"]][0]
    assert nxt["prompt"].size == spec["prompt"].size \
        + spec["max_new_tokens"] + gen.sessions[spec["session"]][
            "user_len"][1]


# -- the reducer that cuts a computation out of one program --------------
def test_scope_roofline_reads_one_programs_operations():
    read = harness.plugin("reducers", "scope_roofline").read
    cm = harness.plugin("cost_models", "granite_hybrid")
    model = {k: v for k, v in harness.load_json(
        "configs", "granite-4.0-h-small-l10-e36.json").items()}
    ms = 1_000_000
    ops = [
        # a decode step of 30 ms with two launches of 4 ms and one other
        (0, 30 * ms, "%while.1 = () while()"),
        (1 * ms, 4 * ms, "%ssm_update.20 = (f32[64,1,8192]) custom-call()"),
        (6 * ms, 4 * ms, "%ssm_update.21 = (f32[64,1,8192]) custom-call()"),
        (11 * ms, 9 * ms, "%ragged-dot-none.1 = bf16[640,4096] custom-call()"),
        # the same launch name inside a prefill chunk: not counted
        (40 * ms, 5 * ms, "%ssm_update.3 = (f32[1,1,8192]) custom-call()"),
        (46 * ms, 2 * ms, "%fusion.9 = f32[2,256,1,128,64] fusion()"),
        (49 * ms, 1 * ms, "%ssm_state_write.2 = f32[9,64,128,8192] custom-call()"),
    ]
    modules = [(0, 30 * ms, "jit_step(123)"), (40 * ms, 12 * ms,
                                               "jit_chunk(77)")]
    trace = Trace({0: ops}, {0: modules}, [])
    shape = {"slots": 64, "live_slots": 64.0, "live_tokens": 9000.0}
    sources = {"trace": trace, "shape": shape, "peak": PEAK, "model": model,
               "cost_model": cm, "traced": {
                   "engine0": {"prefill_tokens": 100},
                   "engine1": {"prefill_tokens": 612}},
               "programs": {"decode": r"^jit_step\(",
                            "prefill": r"^jit_chunk\("}}
    args = {"program": "decode", "cost": "ssm_update",
            "match": [r"^%ssm_update[.\d]* = "]}
    least = cm.least_seconds(*cm.KERNELS["ssm_update"](model, shape),
                             PEAK)[0]
    assert read(sources, args) == pytest.approx(100 * least / 0.008)
    scan = {"program": "prefill", "cost": "ssd_scan", "per": "tokens",
            "counter": "prefill_tokens",
            "match": [r"^%ssm_state_(read|write)[.\d]* = ",
                      r"f32\[(2,256|1,128),"]}
    least = cm.least_seconds(*cm.KERNELS["ssd_scan"](
        model, {"tokens": 512}), PEAK)[0]
    assert read(sources, scan) == pytest.approx(100 * least / 0.003)
    # nothing to read: a program without the launch, a rehearsal
    assert read(sources, {**args, "match": ["^%nothing"]}) is None
    assert read({**sources, "peak": None}, args) is None
    assert read({**sources, "trace": None}, args) is None
    with pytest.raises(ValueError, match="of its roofline"):
        read({**sources, "shape": {**shape, "live_slots": 64000.0}}, args)


# -- both cells end to end on the tiny preset ----------------------------
def last_line(capsys, *argv):
    bench_run.main(["--rehearse", *argv])
    out = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    checks = [json.loads(l)["check"] for l in out if l.startswith('{"check"')]
    return json.loads(out[-1]), checks


def test_granite_cell_end_to_end(capsys):
    line, checks = last_line(capsys, "--workload", G4H, "--seed",
                             str(2 ** 31 + 11), "--seconds", "3",
                             "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    assert [c["compared"] for c in checks if not c["ok"]] == []
    m = line["metrics"]          # a traced run's: the per-layer ones
    assert 40 < m["expert_held_share_pct"]["value"] < 60
    assert m["expert_load_skew"]["value"] >= 1.0
    for name in ("slot_util_pct.g4h", "mixed_step_pct.g4h",
                 "step_host_ms.g4h", "ttft_p95_ms.g4h"):
        assert name in m, name
    # no chip, no peak: the roofline shares are left out, not invented
    assert not [k for k in m if "roofline" in k]


def test_granite_lower_precision_control_is_not_correct(capsys):
    """The reference computed in the nearest precision below the
    configuration's (every product's operands in fp8) is not correct, by
    the mean gap."""
    line, checks = last_line(capsys, "--workload", G4H, "--seed", "7",
                             "--seconds", "3", "--trace", "0",
                             "--control", "ref_fp8")
    assert line["correct"] is False
    assert "mean gap over the sampled tokens" in [
        c["compared"] for c in checks if not c["ok"]]


def test_granite_state_bf16_control_runs(capsys):
    """The program's own lower-precision path: the recurrent state kept
    in bfloat16. It serves and is read like any run; at the tiny preset
    (as on the chip, PERF.md) its gaps are not told from a sound run's,
    so no verdict is asserted here."""
    line, checks = last_line(capsys, "--workload", G4H, "--seed", "7",
                             "--seconds", "3", "--trace", "0",
                             "--control", "state_bf16")
    assert line["failed"] == 0 and line["attempted"] > 0
    gaps = [c for c in checks if "gap" in c["compared"]]
    assert len(gaps) == 2 and all(c["value"] >= 0 for c in gaps)


def test_sessions_cell_end_to_end(capsys):
    line, checks = last_line(capsys, "--workload", PFX, "--seed", "5",
                             "--seconds", "3", "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    assert [c["compared"] for c in checks if not c["ok"]] == []
    m = line["metrics"]
    # later turns find their history in the radix tree
    assert m["prefix_hit_pct"]["value"] > 50
    assert "step_host_ms.pfx" in m
