"""What PR 31 added to the benchmark: the Mellum 2 configuration (the
published keys verbatim, its cut and its bytes from the shapes), its
cost model against hand counts, the reference's independence, the burst
generator (the same work in the same order for every seed, its counts a
phase, no request outside its phase), the reader that hands a cost
model numbers the program counted, and both new cells end to end on the
tiny preset."""
import json
import os

import numpy as np
import pytest

from benchmarks import harness, run as bench_run

M2, BST = "mellum2-code-mixed", "mistral7b-chat-burst"
PEAK = harness.load_json("peaks.json")["TPU v5 lite"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def mellum():
    cfg = harness.load_json("configs", "mellum2-12b-a2.5b-l8.json")
    return cfg, {k: cfg[k] for k in cfg["published_keys"]}


# -- the configuration's file ------------------------------------------
def test_config_keeps_the_published_keys(mellum):
    """Every published key stands in the file under its own name and
    value; the one reduced key says what it was."""
    cfg, model = mellum
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}}
    assert len(cfg["layer_types"]) == 28            # kept whole
    assert [i for i, t in enumerate(cfg["layer_types"])
            if t == "full_attention"] == [3, 7, 11, 15, 19, 23, 27]
    assert cfg["mlp_layer_types"] == ["sparse"] * 28
    assert set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["num_hidden_layers"] == 8
    assert "28 -> 8" in cfg["reduced"]["num_hidden_layers"]
    assert "host" in cfg["reduced"]["num_hidden_layers"]
    # what the file holds beyond the published keys is listed, with why
    assert (model["num_local_experts"], model["expert_offset"]) == (64, 0)
    for key in ("num_local_experts", "qk_norm", "mtp_head", "window",
                "weights"):
        assert cfg["assumed"][key], key
    assert cfg["source"].endswith(
        "JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json")
    assert cfg["engine"] == {
        "capacity": 32, "block_size": 16, "num_blocks": 16385,
        "max_seq_len": 25600,
        "prefill_buckets": cfg["engine"]["prefill_buckets"],
        "prefix_cache": False}
    assert set(cfg["controls"]) >= {"ref_fp8", "window_ignored"}
    # the tiny preset's window is shorter than its sequences
    assert cfg["rehearse"]["model"]["sliding_window"] < \
        harness.load_mix("code-mixed-m2")["rehearse"]["prompt_len"]["median"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_config_differs_from_the_catalog_only_where_it_says(mellum):
    cfg, _ = mellum
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers"}


def test_bytes_follow_from_the_shapes(mellum):
    """ISSUE 31's reckoning, from the shapes: a layer, the cut, the
    whole model, a page, both pools."""
    _, model = mellum
    cm = harness.plugin("cost_models", "mellum")
    z = cm.dims(model)
    outside = cm.attn_params(z) + z["D"] * z["E"] + 2 * z["D"]
    assert outside == 2 * 2304 * 4096 + 2 * 2304 * 512 + 2304 * 64 + 4608
    assert round(outside / 1e6, 2) == 21.39
    assert round(cm.expert_params(z) / 1e6, 3) == 6.193
    assert round(cm.layer_params(z) / 1e6, 2) == 417.75
    assert cm.total_params(model) == 3_794_966_784
    assert round(cm.total_params(model) * 2 / 1e9, 2) == 7.59
    full = dict(model, num_hidden_layers=28)
    assert round(cm.total_params(full) / 1e9, 2) == 12.15
    weights = harness.plugin("weights", "mellum")
    assert weights.count(model) == cm.total_params(model)
    assert cm.kv_page_bytes(model) == 32 * 1024
    assert round(16384 * 2 * 32768 / 1e9, 2) == 1.07       # global pools
    ring = -(-(1024 + 512) // 16) + 1     # ISSUE 31's, buckets to 512
    assert ring == 97
    assert round((32 * ring + 1) * 6 * 32768 / 1e9, 2) == 0.61
    ring = -(-(1024 + 2048) // 16) + 1    # the 2048 bucket that runs
    assert ring == 193
    assert round((32 * ring + 1) * 6 * 32768 / 1e9, 2) == 1.21
    assert round(16384 * 8 * 32768 / 1e9, 2) == 4.29       # held uniformly


def test_cost_model_counts_by_hand(mellum):
    _, model = mellum
    cm = harness.plugin("cost_models", "mellum")
    z = cm.dims(model)
    assert (z["L"], z["Lw"], z["Lg"], z["W"]) == (8, 6, 2, 1024)
    # one layer's experts over 12 tokens: every expert is touched with
    # probability 1 - (7/8)^12; a token's rows use 8 experts
    flops, moved = cm.moe_experts(model, 12)
    per = 3 * 2304 * 896
    assert flops == 2 * 12 * 8 * per
    hit = 64 * (1 - (7 / 8) ** 12)
    assert moved == pytest.approx(hit * per * 2
                                  + 12 * 8 * (2 * 2304 + 3 * 896) * 2)
    # the mean launch of a step: 2 global layers over every live token,
    # 6 window layers over what the program counted inside the window
    shape = {"slots": 32, "live_slots": 12, "live_tokens": 72000,
             "win_tokens": 12 * 1030}
    f, b = cm.paged_attention_decode(model, shape)
    kv = 2 * 4 * 128 * 2                        # K and V of one token
    q_o = 2 * 12 * 32 * 128 * 2
    assert b == pytest.approx((2 * (72012 * kv + q_o)
                               + 6 * (12360 * kv + q_o)) / 8)
    assert f == pytest.approx(4 * 32 * 128 * (2 * 72012 + 6 * 12360) / 8)
    # without the count the window layers are reckoned at one token a
    # slot: a share can only come out lower
    lean = {k: v for k, v in shape.items() if k != "win_tokens"}
    assert cm.paged_attention_decode(model, lean)[1] < b
    # a decode step: the weights every token uses, once; the experts
    # touched; the attention launches; the logits
    always = 8 * (2 * 2304 * 4096 + 2 * 2304 * 512 + 2304 * 64) \
        + 2304 * 98304
    F, B = cm.decode_step(model, shape)
    assert B == pytest.approx(always * 2 + 8 * (moved + b)
                              + 12 * 98304 * 4)
    assert F == pytest.approx(2 * always * 12 + 8 * (flops + f))
    least, bound = cm.least_seconds(F, B, PEAK)
    assert bound == "memory" and 6e-3 < least < 9e-3
    assert set(cm.KERNELS) == {"moe_experts", "paged_attention_decode"}
    assert cm.KERNELS["moe_experts"](model, shape) == (8 * flops, 8 * moved)
    assert cm.PROGRAMS["decode_step"](model, shape) == (F, B)


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(harness.HERE, "reference", "mellum.py")
    src = open(path).read()
    assert "paddle_tpu" not in src.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in src
    assert "HIGHEST" in src


def test_counted_shape_reaches_the_cost_model():
    """``roofline_counted`` hands the inner reader the driver's shape
    plus what the program counted over the traced window; without the
    counters (a commit before them) it reads nothing and does not
    raise."""
    reader = harness.plugin("reducers", "roofline_counted")
    seen = {}

    class Inner:
        @staticmethod
        def read(sources, args):
            seen.update(shape=sources["shape"], args=args)
            return 42.0

    args = {"shape": {"win_tokens": ["kv_tokens_held_window",
                                     "decode_steps"]},
            "reducer": "kernel_roofline", "args": {"kernels": ["k"]}}
    sources = {"shape": {"slots": 32, "live_slots": 10.0},
               "traced": {"engine0": {"kv_tokens_held_window": 1000,
                                      "decode_steps": 10},
                          "engine1": {"kv_tokens_held_window": 31000,
                                      "decode_steps": 40}}}
    real = harness.plugin
    harness.plugin = lambda kind, name: Inner if (
        kind, name) == ("reducers", "kernel_roofline") else real(kind, name)
    try:
        assert reader.read(sources, args) == 42.0
        assert seen["shape"] == {"slots": 32, "live_slots": 10.0,
                                 "win_tokens": 1000.0}
        assert seen["args"] == {"kernels": ["k"]}
        old = {"shape": sources["shape"],
               "traced": {"engine0": {"decode_steps": 1},
                          "engine1": {"decode_steps": 9}}}
        assert reader.read(old, args) is None
        assert reader.read({"shape": None, "traced": None}, args) is None
    finally:
        harness.plugin = real


# -- the burst generator ---------------------------------------------------
def burst_mix(rehearse=False):
    mix = harness.load_mix("chat-burst")
    if rehearse:
        mix.update(mix["rehearse"])
    return mix


def test_burst_mix_states_its_rates_as_numbers():
    mix, steady = burst_mix(), harness.load_mix("chat-steady")
    a = mix["arrivals"]
    assert mix["generator"] == "open_loop_bursts"
    assert (a["cycle_s"], a["on_s"]) == (10, 3)
    knee = a["on_rate_per_s"] / 1.5
    assert a["off_rate_per_s"] == pytest.approx(0.4 * knee, abs=0.051)
    for key in ("prompt_len", "output_len", "warm_s", "grace_s",
                "trace_s"):
        assert mix[key] == steady[key], key
    assert mix["order_seed"] == 32


def test_bursts_offer_the_same_work_to_every_seed():
    gen = harness.plugin("generators", "open_loop_bursts")
    mix = burst_mix()
    a, b = (gen.Generator(mix, seed, 51, 32768)
            for seed in (3, 2 ** 31 + 17))
    wa, wb = ([r for r in g.requests if r["phase"] == "window"]
              for g in (a, b))
    assert [(r["due"], r["prompt"].size, r["max_new_tokens"])
            for r in wa] == [(r["due"], r["prompt"].size,
                              r["max_new_tokens"]) for r in wb]
    assert any((x["prompt"] != y["prompt"]).any()
               for x, y in zip(wa, wb) if x["prompt"].size > 8)
    assert a.offered() == {**b.offered()}
    # the lengths are chat-steady's distribution, whole
    sizes = sorted(r["prompt"].size for r in wa)
    assert sizes[0] >= 32 and sizes[-1] <= 2048
    assert 300 < sizes[len(sizes) // 2] < 480


def test_bursts_keep_their_counts_and_their_phases():
    gen = harness.plugin("generators", "open_loop_bursts")
    mix = burst_mix()
    arr = mix["arrivals"]
    on, off = arr["on_rate_per_s"], arr["off_rate_per_s"]
    parts = gen.phases(arr, 51.0)
    assert [round(t, 6) for t, _, _ in parts] == [
        0, 3, 10, 13, 20, 23, 30, 33, 40, 43, 50]
    assert parts[-1] == (50.0, 1.0, on)           # the span cuts a cycle
    g = gen.Generator(mix, 5, 51, 32768)
    win = [r for r in g.requests if r["phase"] == "window"]
    assert [r["due"] for r in win] == sorted(r["due"] for r in win)
    for t, length, rate in parts:
        lo, hi = g.warm_s + t, g.warm_s + t + length
        mine = [r for r in win if r["part"] == (lo, hi)]
        assert len(mine) == int(round(rate * length))
        assert all(lo <= r["due"] < hi for r in mine)
    assert len(win) == sum(int(round(r * n)) for _, n, r in parts)
    assert g.offered()["rate_per_s"] == pytest.approx(
        (3 * on + 7 * off) / 10)
    # a burst is denser than the lull that follows it
    burst = [r for r in win if r["part"][0] == g.warm_s]
    lull = [r for r in win if r["part"][0] == g.warm_s + 3]
    assert len(burst) / 3 > 2 * len(lull) / 7
    # the driver's calls
    assert g.next_due() == g.requests[0]["due"]
    first = g.due(g.requests[0]["due"])
    assert first and g.next_due() > first[-1]["due"] - 1e-9


# -- both cells end to end on the tiny preset ----------------------------
def last_line(capsys, *argv):
    bench_run.main(["--rehearse", *argv])
    out = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    checks = [json.loads(l)["check"] for l in out if l.startswith('{"check"')]
    return json.loads(out[-1]), checks


def test_mellum_cell_end_to_end(capsys):
    line, checks = last_line(capsys, "--workload", M2, "--seed",
                             str(2 ** 31 + 11), "--seconds", "3",
                             "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    assert [c["compared"] for c in checks if not c["ok"]] == []
    m = line["metrics"]          # a traced run's: the per-layer ones
    # the tiny window is shorter than the sequences: pages went back
    assert 5 < m["window_kv_held_pct"]["value"] < 95
    assert m["window_release_ms"]["value"] > 0
    assert m["expert_load_skew.m2"]["value"] >= 1.0
    for name in ("slot_util_pct.m2", "mixed_step_pct.m2",
                 "step_host_ms.m2", "ttft_p95_ms.m2",
                 "queue_wait_p95_ms.m2"):
        assert name in m, name
    # no chip, no peak: the roofline shares are left out, not invented
    assert not [k for k in m if "roofline" in k]


@pytest.mark.parametrize("control,by", [
    ("ref_fp8", "mean gap over the sampled tokens"),
    ("window_ignored", "mean gap over the sampled tokens")])
def test_mellum_controls_are_not_correct(capsys, control, by):
    """The reference in fp8, and a reference whose window layers see
    everything, each end ``correct: false``."""
    line, checks = last_line(capsys, "--workload", M2, "--seed", "7",
                             "--seconds", "3", "--trace", "0",
                             "--control", control)
    assert line["correct"] is False
    assert by in [c["compared"] for c in checks if not c["ok"]]


def test_burst_cell_end_to_end(capsys):
    line, checks = last_line(capsys, "--workload", BST, "--seed", "7",
                             "--seconds", "3", "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    assert [c["compared"] for c in checks if not c["ok"]] == []
    m = line["metrics"]
    for name in ("ttft_p95_ms.bst", "queue_wait_p95_ms.bst",
                 "slot_util_pct.bst", "mixed_step_pct.bst"):
        assert name in m, name
