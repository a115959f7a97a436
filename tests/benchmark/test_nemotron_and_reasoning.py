"""What PR 43 added to the benchmark: the Nemotron-3-Nano configuration
(the published keys verbatim, its cut, its deployment and its bytes
from the shapes), its cost model against hand counts (a relu^2 expert
is two products a row, the state update at 8 B/C groups), the
reference's independence and its share of an uncut layer, the reasoning
mix, the scoped per-layer metrics, and the controls' teeth on the tiny
preset."""
import json
import os

import numpy as np
import pytest

from benchmarks import harness, run as bench_run

CELL = "nemotron3nano-reason-steady"
NAME = "nemotron-3-nano-30b-a3b-l16-e64"
PEAK = harness.load_json("peaks.json")["TPU v5 lite"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
BENCH = harness.load_benchmark()


@pytest.fixture(scope="module")
def nemotron():
    cfg = harness.load_json("configs", NAME + ".json")
    return cfg, {k: cfg[k] for k in cfg["published_keys"]}


# -- the configuration's file ------------------------------------------
def test_config_keeps_the_published_keys(nemotron):
    """No width differs from the published file; the three reduced
    keys say what they were; what the file holds beyond the published
    keys is listed with its reason."""
    cfg, model = nemotron
    widths = {
        "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
        "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
        "chunk_size": 128, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128,
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "intermediate_size": 1856, "num_experts_per_tok": 6,
        "routed_scaling_factor": 2.5, "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "tie_word_embeddings": False,
        "layer_norm_epsilon": 1e-05, "expand": 2,
        "max_position_embeddings": 262144}
    for key, value in widths.items():
        assert cfg[key] == value, key
    assert len(cfg["hybrid_override_pattern"]) == 52       # kept whole
    assert cfg["hybrid_override_pattern"][:16] == "MEMEM*EMEMEM*EME"
    assert set(cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                   "vocab_size"}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (16, 64, 65536)
    assert "52 -> 16" in cfg["reduced"]["num_hidden_layers"]
    assert "128 -> 64 HELD" in cfg["reduced"]["n_routed_experts"]
    assert "131072 -> 65536" in cfg["reduced"]["vocab_size"]
    # the router keeps its published width
    assert (model["num_experts"], model["expert_offset"]) == (128, 0)
    for key in ("num_experts", "expert_offset", "position_embedding",
                "d_inner", "e_score_correction_bias", "state", "weights"):
        assert cfg["assumed"][key], key
    assert "no position embedding" in cfg["assumed"]["position_embedding"]
    assert "two chips share each layer" in cfg["deployment"]
    assert cfg["engine"] == {
        "capacity": 128, "block_size": 16, "num_blocks": 32768,
        "max_seq_len": 4096, "prefill_buckets": [128, 512],
        "prefix_cache": False, "state_dtype": "float32"}
    assert set(cfg["controls"]) == {"state_bf16", "ref_fp8"}
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["source"] == cfg["source"]
    assert set(entry["reduced"]) == set(cfg["reduced"])


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_config_differs_from_the_catalog_only_where_it_says(nemotron):
    cfg, _ = nemotron
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"])


def test_program_config_reads_the_file(nemotron):
    """The program's config class takes the file's keys as they are
    published, and says of each letter what its layer is."""
    cfg, _ = nemotron
    klass = harness.resolve(cfg["program"]["config"])
    mcfg = klass(**{k: cfg[k] for k in cfg["program"]["config_keys"]})
    assert "".join(mcfg.pattern) == "MEMEM*EMEMEM*EME"
    assert (mcfg.num_recurrent_layers, mcfg.num_expert_layers,
            mcfg.num_kv_layers) == (7, 7, 2)
    assert (mcfg.num_experts, mcfg.num_local_experts,
            mcfg.expert_offset) == (128, 64, 0)
    assert (mcfg.mamba_d_inner, mcfg.mamba_conv_dim) == (4096, 6144)
    assert mcfg.state_shapes(128) == ((7, 128, 128, 4096),
                                      (7, 128, 3, 6144))


def test_bytes_follow_from_the_shapes(nemotron):
    """ISSUE 43's reckoning, from the shapes."""
    _, model = nemotron
    cm = harness.plugin("cost_models", "nemotron_h")
    z = cm.dims(model)
    assert (z["Lm"], z["Le"], z["La"]) == (7, 7, 2)
    assert round((cm.mamba_params(z) + 2688 + 4096 + 3 * 64
                  + 5 * 6144) / 1e6, 2) == 38.74
    assert round((cm.attn_params(z) + 2688) / 1e6, 2) == 23.40
    assert round(cm.expert_params(z) / 1e6, 2) == 9.98
    whole = cm.dense_moe_params(z) + 128 * cm.expert_params(z)
    assert round(whole / 1e6) == 1297
    held = cm.dense_moe_params(z) + 64 * cm.expert_params(z) + 2688 + 128
    assert round(held / 1e6, 1) == 658.9
    assert cm.total_params(model) == 5_282_534_208
    assert round(cm.total_params(model) * 2 / 1e9, 2) == 10.57
    weights = harness.plugin("weights", "nemotron_h")
    assert weights.count(model) == cm.total_params(model)
    assert weights.storage_width(1856) == 1920
    # state: 128 x 4096 x 4 B a slot a Mamba layer; KV: 2,048 B a token
    assert 128 * 4096 * 4 == 2_097_152
    assert round(7 * 128 * (2_097_152 + 3 * 6144 * 2) / 1e9, 2) == 1.91
    assert 2 * 2 * 2 * 128 * 2 == 2048
    assert round(32768 * 16 * 2048 / 1e9, 2) == 1.07
    full = dict(model, num_hidden_layers=52, n_routed_experts=128,
                vocab_size=131072)
    assert round(cm.total_params(full) / 1e9, 2) == 31.58


def test_cost_model_counts_by_hand(nemotron):
    _, model = nemotron
    cm = harness.plugin("cost_models", "nemotron_h")
    # one layer's experts over 100 tokens: a held expert is touched with
    # probability 1 - (1 - 6/128)^100; an expert is TWO matrices
    flops, moved = cm.moe_experts(model, 100)
    per = 2 * 2688 * 1856
    rows = 100 * 6 * 64 / 128
    assert flops == 2 * rows * per
    hit = 64 * (1 - (1 - 6 / 128) ** 100)
    assert moved == pytest.approx(hit * per * 2
                                  + rows * (2 * 2688 + 2 * 1856) * 2)
    # the state update: the state read and written once whatever the
    # number of groups, six operations an element
    f, b = cm.ssm_update(model, 100)
    elems = 100 * 64 * 64 * 128
    assert f == 6 * elems
    assert b == 2 * elems * 4 + 100 * (6144 + 2 * 4096) * 2
    one_group = dict(model, n_groups=1)
    assert cm.ssm_update(one_group, 100)[0] == f
    # the scan counts C.B^T once a group
    assert cm.ssd_scan(model, 512)[0] > cm.ssd_scan(one_group, 512)[0]
    shape = {"slots": 128, "live_slots": 100.0, "live_tokens": 50000.0}
    af, ab = cm.paged_attention_decode(model, shape)
    assert ab == 2 * 50100 * 2 * 128 * 2 + 2 * 100 * 32 * 128 * 2
    assert af == 4 * 32 * 128 * 50100
    always = (7 * (2688 * (4096 + 6144 + 64) + 4096 * 2688)
              + 2 * (2 * 2688 * 4096 + 2 * 2688 * 256)
              + 7 * (2688 * 128 + 2 * 2688 * 3712) + 2688 * 65536)
    F, B = cm.decode_step(model, shape)
    assert B == pytest.approx(always * 2 + 7 * moved + 7 * b + 2 * ab
                              + 100 * 65536 * 4)
    assert F == pytest.approx(2 * always * 100 + 7 * flops + 7 * f
                              + 2 * af)
    least, bound = cm.least_seconds(F, B, PEAK)
    assert bound == "memory" and 14e-3 < least < 17e-3
    # the held experts and the state are nine tenths of the step's
    # least bytes, keys and values under 1%
    assert (7 * moved + 7 * b) / B > 0.88 and 2 * ab / B < 0.01
    assert set(cm.KERNELS) == {"moe_experts", "ssm_update", "ssd_scan",
                               "paged_attention_decode"}
    assert cm.KERNELS["moe_experts"](model, shape) == (7 * flops, 7 * moved)
    assert cm.KERNELS["ssm_update"](model, shape) == (7 * f, 7 * b)
    assert cm.PROGRAMS["decode_step"](model, shape) == (F, B)
    # handed the program's own count of the held experts that got a
    # token (a layer a step), the uniform expectation is not used:
    # skewed routing touches fewer, and a launch that skips the
    # untouched would read over its roofline against the expectation
    assert cm.moe_experts(model, 100, fetched=50.0)[1] == pytest.approx(
        50 * per * 2 + rows * (2 * 2688 + 2 * 1856) * 2)
    counted = dict(shape, experts_touched=50.0)
    assert cm.KERNELS["moe_experts"](model, counted)[1] == pytest.approx(
        7 * cm.moe_experts(model, 100, fetched=50.0)[1])
    assert cm.PROGRAMS["decode_step"](model, counted)[1] == pytest.approx(
        B - 7 * (hit - 50) * per * 2)
    cf, cb = cm.PROGRAMS["prefill_chunk"](model, {"tokens": 512})
    # every held expert's weights once; ~670 M parameters a token
    assert 10.5e9 < cb < 10.7e9 and 6.5e11 < cf < 7.5e11


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(harness.HERE, "reference", "nemotron_h.py")
    src = open(path).read()
    assert "paddle_tpu" not in src.split('"""', 2)[2]
    assert "HIGHEST" in src and "lax.top_k" not in src


def test_traffic_is_reasoning_shaped():
    mix = harness.load_mix("reason-steady-n3n")
    assert mix["generator"] == "open_loop" and mix["order_seed"] == 43
    assert (mix["warm_s"], mix["grace_s"], mix["trace_s"]) == (20, 45, 5)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.8, "min": 32, "max": 1024}
    assert mix["output_len"]["median"] >= 2 * mix["prompt_len"]["median"] \
        or "halved" in mix["why"]
    assert mix["shared_prefix"] is None
    gen = harness.plugin("generators", "open_loop").Generator(
        mix, 7, 51, 65536)
    offered = gen.offered()
    assert offered["window_requests"] >= 250
    # the same work in the same order for every seed
    other = harness.plugin("generators", "open_loop").Generator(
        mix, 8, 51, 65536)
    assert other.offered() == offered


def test_cell_and_metrics_are_entered_at_the_end():
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["workloads"][-1]["chips"] == 1
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert BENCH["configs"][-1]["name"] == NAME
    for m in BENCH["end_to_end"]:
        if m["name"] in ("ttft_mean_ms", "tpot_p95_ms"):
            assert m["workloads"][-1] == CELL
    mine = [m for m in BENCH["per_layer"] if m["name"].endswith(".n3n")]
    # PR 47's import_s, a metric of every cell, was entered after them
    older = [m for m in BENCH["per_layer"] if m["name"] != "import_s"]
    assert len(mine) == 17 and older[-17:] == mine
    e2e = harness.metrics_of(BENCH, "end_to_end", CELL)
    assert {m["name"] for m in e2e} == {"setup_s", "ttft_mean_ms",
                                        "tpot_p95_ms"}
    twins = {m["name"]: m for m in BENCH["per_layer"]}
    for m in mine:
        assert m["workloads"] == [CELL]
        spec = harness.load_json("layer_metrics", m["name"] + ".json")
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} \
            == {k: m[k] for k in ("unit", "better", "source", "layer",
                                  "moves")}
        harness.plugin("reducers", spec["reducer"])
        twin = twins.get(m["name"].replace(".n3n", ".g4h"))
        if twin is not None:
            assert twin["moves"] == m["moves"], m["name"]
    # found by scope, whatever implements them
    for name in ("moe_experts", "ssm_update", "ssd_scan"):
        spec = harness.load_json("layer_metrics",
                                 f"{name}_scope_roofline.n3n.json")
        if name == "moe_experts":     # through the program's own count
            assert spec["reducer"] == "roofline_counted"
            spec = spec["args"]
        assert spec["reducer"] == "scoped_roofline"
        assert spec["args"]["scopes"] == [name]
    # the two shares that charge expert bytes are handed the held
    # experts the program counted as touched, a layer a decode step
    for name in ("moe_experts_scope_roofline", "decode_program_roofline"):
        spec = harness.load_json("layer_metrics", name + ".n3n.json")
        assert spec["args"]["shape"] == {"experts_touched": [
            "experts_touched_held", "expert_layer_steps"]}


def test_counted_share_hands_the_cost_model_the_programs_count(
        monkeypatch):
    """``moe_experts_scope_roofline.n3n`` goes through
    ``roofline_counted``: the cost model's shape gains the held experts
    that got a token, a layer a decode step, from two counters that the
    engine reads from the device TOGETHER (so a snapshot taken between
    two reads still holds a pair); a program without them reads
    nothing and does not raise."""
    spec = harness.load_json("layer_metrics",
                             "moe_experts_scope_roofline.n3n.json")
    seen, real = {}, harness.plugin

    class Inner:
        @staticmethod
        def read(sources, args):
            seen.update(shape=sources["shape"], args=args)
            return 1.0

    monkeypatch.setattr(
        harness, "plugin", lambda kind, name: Inner
        if (kind, name) == ("reducers", "scoped_roofline")
        else real(kind, name))
    counted = real("reducers", "roofline_counted")
    sources = {"shape": {"slots": 128, "live_slots": 60.0},
               "traced": {
                   "engine0": {"experts_touched_held": 700,
                               "expert_layer_steps": 14},
                   "engine1": {"experts_touched_held": 6300,
                               "expert_layer_steps": 114}}}
    assert counted.read(sources, spec["args"]) == 1.0
    assert seen["shape"] == {"slots": 128, "live_slots": 60.0,
                             "experts_touched": 56.0}
    assert seen["args"]["scopes"] == ["moe_experts"]
    del sources["traced"]["engine0"]["experts_touched_held"]
    assert counted.read(sources, spec["args"]) is None


# -- the controls' teeth, on the tiny preset ---------------------------
def last_line(capsys, *argv):
    bench_run.main(["--rehearse", "--trace", "0", "--workload", CELL,
                    *argv])
    out = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    checks = [json.loads(l)["check"] for l in out
              if l.startswith('{"check"')]
    return json.loads(out[-1]), checks


def test_sound_run_is_correct(capsys):
    line, checks = last_line(capsys, "--seed", "3", "--seconds", "4")
    assert line["correct"] is True
    assert [c["compared"] for c in checks if not c["ok"]] == []


def test_fp8_reference_is_not_correct(capsys):
    line, checks = last_line(capsys, "--seed", "1", "--seconds", "4",
                             "--control", "ref_fp8")
    assert line["correct"] is False
    assert any("gap" in c["compared"] for c in checks if not c["ok"])


def test_bfloat16_state_runs_on_the_tiny_preset(capsys):
    """``state_bf16`` must end ``correct: false`` at the cell's own size
    on the chip (the configuration's ``tolerance.why`` has the
    readings). On the tiny preset it cannot: the whole model is served
    in bfloat16 there and a 16 x 16 state of a few dozen positions
    rounds no worse than the activations around it (the rehearse
    preset's ``why`` has both readings); what is held here is that the
    control switches the state's type and the run completes."""
    line, checks = last_line(capsys, "--seed", "1", "--seconds", "4",
                             "--control", "state_bf16")
    assert line["failed"] == 0 and line["attempted"] > 0
    assert all("value" in c and "limit" in c for c in checks)
    cfg = harness.load_json("configs", NAME + ".json")
    assert cfg["controls"]["state_bf16"] == {
        "engine": {"state_dtype": "bfloat16"}}
    assert np.isfinite([c["value"] for c in checks
                        if "gap" in c["compared"]]).all()
