"""Multi-process collective worker, launched by
``python -m paddle_tpu.distributed.launch`` in test_multiprocess.py
(reference pattern: test/collective/collective_allreduce_api.py run under
test_communication_api_base.py:64).

Runs real cross-process collectives + a data-parallel train step and
writes per-rank results for the parent test to compare.
"""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle              # noqa: E402
import paddle_tpu.distributed as dist    # noqa: E402


def main():
    out_dir = sys.argv[1]
    env = dist.init_parallel_env()
    rank = dist.get_rank()
    world = dist.get_world_size()
    results = {"rank": rank, "world": world}

    # all_reduce: each rank contributes rank+1 -> sum = world*(world+1)/2
    t = paddle.to_tensor(np.full((4,), float(rank + 1), np.float32))
    dist.all_reduce(t)
    results["all_reduce"] = np.asarray(t._value).tolist()

    # all_gather
    gathered = []
    src = paddle.to_tensor(np.full((2,), float(rank * 10), np.float32))
    dist.all_gather(gathered, src)
    results["all_gather"] = [np.asarray(g._value).tolist() for g in gathered]

    # broadcast from rank 0
    b = paddle.to_tensor(np.full((3,), float(rank + 7), np.float32))
    dist.broadcast(b, src=0)
    results["broadcast"] = np.asarray(b._value).tolist()

    # DP train step: same model, rank-dependent data shard; after grad
    # allreduce(avg) all ranks must hold identical params
    paddle.seed(0)
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    net = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(0.1, parameters=net.parameters())
    rng = np.random.RandomState(100 + rank)
    x = paddle.to_tensor(rng.randn(8, 4).astype(np.float32))
    y = paddle.to_tensor(rng.randn(8, 2).astype(np.float32))
    loss = F.mse_loss(net(x), y)
    loss.backward()
    for p in net.parameters():
        dist.all_reduce(p.grad, op=dist.ReduceOp.AVG)
    opt.step()
    results["params"] = {k: np.asarray(v._value).tolist()
                         for k, v in net.state_dict().items()}
    results["loss"] = float(loss)

    with open(os.path.join(out_dir, f"rank_{rank}.json"), "w") as f:
        json.dump(results, f)
    print(f"worker rank {rank}/{world} OK")


if __name__ == "__main__":
    main()
