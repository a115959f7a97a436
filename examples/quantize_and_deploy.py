"""Quantized deployment: PTQ-calibrate -> int8 layers -> jit.save
(StableHLO) -> Predictor with the AOT executable cache; plus the
weight-only int8 path for LLM-style weights."""
import tempfile

import numpy as np

from _common import setup

setup()

import paddle_tpu as paddle                                # noqa: E402
import paddle_tpu.nn as nn                                 # noqa: E402
from paddle_tpu.inference import (Config,                  # noqa: E402
                                  create_predictor)
from paddle_tpu.nn.quant import (weight_only_linear,       # noqa: E402
                                 weight_quantize)
from paddle_tpu.quantization import PTQ                    # noqa: E402
from paddle_tpu.static import InputSpec                    # noqa: E402


def main():
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(32, 64), nn.ReLU(), nn.Linear(64, 8))
    net.eval()
    rng = np.random.RandomState(0)
    calib = [paddle.to_tensor(rng.randn(16, 32).astype(np.float32))
             for _ in range(4)]
    x = calib[0]
    ref = net(x).numpy()

    # post-training quantization: observe -> convert to int8 layers
    ptq = PTQ()
    observed = ptq.quantize(net, inplace=False)
    for c in calib:
        observed(c)
    int8_net = ptq.convert(observed)

    with tempfile.TemporaryDirectory() as d:
        path = f"{d}/model_int8"
        paddle.jit.save(int8_net, path,
                        input_spec=[InputSpec([16, 32], "float32")])
        pred = create_predictor(Config(path))
        out = pred.run([x])[0].numpy()
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    print(f"int8 predictor vs float eager: rel err {rel:.4f}")

    # weight-only int8 (LLM serving): weights stored int8, math in fp
    w = paddle.to_tensor(rng.randn(64, 32).astype(np.float32))
    q, scale = weight_quantize(w, algo="weight_only_int8")
    y = weight_only_linear(paddle.to_tensor(
        rng.randn(4, 64).astype(np.float32)), q, weight_scale=scale)
    print(f"weight_only_linear: {q.shape} int8 weights -> out {y.shape}")


if __name__ == "__main__":
    main()
