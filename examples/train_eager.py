"""Paddle-style eager training, then the same loop as ONE fused XLA
program per step (forward+backward+optimizer, donated buffers)."""
import numpy as np

from _common import setup

setup()

import paddle_tpu as paddle           # noqa: E402
import paddle_tpu.nn as nn            # noqa: E402
import paddle_tpu.nn.functional as F  # noqa: E402


def main():
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(64, 128), nn.ReLU(), nn.Linear(128, 10))
    opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters())
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(256, 64).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, 256))

    # eager: per-op dispatch + autograd tape, debugger-friendly
    for i in range(3):
        loss = F.cross_entropy(net(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        print(f"eager step {i}: loss {float(loss):.4f}")

    # compiled: the whole update is one donated XLA program
    step = paddle.jit.train_step(net, F.cross_entropy, opt,
                                 amp_level="O1", amp_dtype="bfloat16")
    for i in range(5):
        loss = step(x, y)
    print(f"fused train_step: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
