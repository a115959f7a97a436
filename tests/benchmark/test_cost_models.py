"""The cost model against counts worked by hand at the published
widths of Mistral-7B-v0.3 (D=4096, 32/8 heads x 128, F=14336, V=32768)."""
import pytest

from benchmarks import harness
from benchmarks.cost_models import dense_decoder as cm
from benchmarks.weights import dense_decoder_stacked as weights

SERVE = harness.load_json("configs", "mistral-7b-v0.3-l16.json")
TRAIN = harness.load_json("configs", "mistral-7b-v0.3-train-l2.json")
PEAK = harness.load_json("peaks.json")["TPU v5 lite"]
LAYER = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336   # 218,103,808


def test_parameter_counts():
    assert LAYER == 218_103_808
    assert cm.layer_matmul_params(SERVE) == LAYER
    assert cm.matmul_params(SERVE) == 16 * LAYER + 4096 * 32768
    assert weights.count(SERVE) == 3_758_231_552
    assert weights.count(TRAIN) == 704_663_552


def test_one_decode_step_by_hand():
    flops, moved = cm.decode_step(SERVE, slots=32, live_tokens=16384)
    w = 3_623_878_656 * 2 + 33 * 4096 * 4          # weights + norms
    kv_read = 16384 * 65536                        # 64 KiB a token
    kv_write = 32 * 65536
    io = 32 * 4096 * 2 + 32 * 32768 * 4
    assert moved == w + kv_read + kv_write + io == 8_328_593_408
    assert flops == 2 * 3_623_878_656 * 32 + 4 * 4096 * 16 * 16384
    assert flops == 236_223_201_280
    least, bound = cm.least_seconds(flops, moved, PEAK)
    assert bound == "memory"
    assert least == pytest.approx(10.169e-3, rel=1e-3)


def test_one_train_step_by_hand():
    per_token = cm.train_flops_per_token(TRAIN, 2048)
    assert per_token == 6 * (2 * LAYER + 4096 * 32768) \
        + 3 * 2 * 4096 * 2048 * 2 == 3_523_215_360
    flops, tokens = cm.train_step(TRAIN, 2, 2048)
    assert tokens == 4096 and flops == 14_431_090_114_560


def test_kernel_launches_by_hand():
    shape = {"slots": 32, "live_tokens": 16384, "batch": 2, "seq": 2048}
    flops, moved = cm.KERNELS["decode_mlp_block"](SERVE, shape)
    assert flops == 6 * 32 * 4096 * 14336 == 11_274_289_152
    assert moved == 3 * 4096 * 14336 * 2 + 4096 * 4 + 2 * 32 * 4096 * 2
    flops, moved = cm.KERNELS["paged_attention_decode"](SERVE, shape)
    assert flops == 4 * 4096 * 16384
    assert moved == 16384 * 4096 + 2 * 32 * 4096 * 2     # 4 KiB a token
    fwd, _ = cm.KERNELS["flash_attention_fwd"](TRAIN, shape)
    assert fwd == 68_719_476_736
    dq, _ = cm.KERNELS["flash_attention_bwd_dq"](TRAIN, shape)
    dkv, _ = cm.KERNELS["flash_attention_bwd_dkv"](TRAIN, shape)
    assert (dq, dkv) == (fwd * 3 // 2, fwd * 2)
    assert set(cm.PROGRAMS) == {"decode_step"}


def test_least_seconds_picks_the_larger_bound():
    assert cm.least_seconds(197e12, 1.0, PEAK) == (1.0, "compute")
    assert cm.least_seconds(1.0, 819e9, PEAK) == (1.0, "memory")


def test_unknown_device_is_an_error():
    with pytest.raises(SystemExit):
        harness.peak_table("TPU v9 imaginary")
    assert harness.peak_table("TPU v5 lite")["flops_per_s"] == 197e12
