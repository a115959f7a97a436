"""4-process sub-group collective worker (launched by
``paddle_tpu.distributed.launch`` in test_multiprocess.py).

Exercises REAL cross-process eager collectives over 2-of-4-rank groups
(reference: python/paddle/distributed/collective.py:195 new_group): the
odd group {1,3} all-reduces and broadcasts, the even group {0,2}
all-gathers — concurrently, on disjoint device sets.
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
    dist.init_parallel_env()
    rank = dist.get_rank()
    world = dist.get_world_size()
    results = {"rank": rank, "world": world}

    # Both groups exist on every process; only members call into them.
    odd = dist.new_group([1, 3])
    even = dist.new_group([0, 2])

    if rank in (1, 3):
        # sub-group all_reduce: 1 + 3 = 4
        t = paddle.to_tensor(np.full((2,), float(rank), np.float32))
        dist.all_reduce(t, group=odd)
        results["sub_all_reduce"] = np.asarray(t._value).tolist()
        # sub-group broadcast from global rank 3
        b = paddle.to_tensor(np.full((2,), float(rank * 100), np.float32))
        dist.broadcast(b, src=3, group=odd)
        results["sub_broadcast"] = np.asarray(b._value).tolist()
    else:
        # sub-group all_gather over {0, 2}: [rank+5] -> [[5],[7]]
        gathered = []
        src = paddle.to_tensor(np.full((2,), float(rank + 5), np.float32))
        dist.all_gather(gathered, src, group=even)
        results["sub_all_gather"] = [np.asarray(g._value).tolist()
                                     for g in gathered]

    if rank in (1, 3):
        # sub-group reduce_scatter: each contributes [r, r, r, r] (len 4),
        # sum = [4]*4, member pos p keeps rows [2p:2p+2]
        rs_out = paddle.to_tensor(np.zeros((2,), np.float32))
        rs_in = paddle.to_tensor(np.full((4,), float(rank), np.float32))
        dist.reduce_scatter(rs_out, rs_in, group=odd)
        results["sub_reduce_scatter"] = np.asarray(rs_out._value).tolist()
        # sub-group all_to_all: member p sends [p*10+0, p*10+1]
        pos = [1, 3].index(rank)
        outs, ins = [], [
            paddle.to_tensor(np.full((2,), float(pos * 10 + j), np.float32))
            for j in range(2)]
        dist.all_to_all(outs, ins, group=odd)
        results["sub_all_to_all"] = [np.asarray(o._value).tolist()
                                     for o in outs]
    else:
        # sub-group scatter from global rank 2: rank 2 provides the list
        sc = paddle.to_tensor(np.zeros((2,), np.float32))
        tl = None
        if rank == 2:
            tl = [paddle.to_tensor(np.full((2,), float(50 + i), np.float32))
                  for i in range(2)]
        dist.scatter(sc, tl, src=2, group=even)
        results["sub_scatter"] = np.asarray(sc._value).tolist()

    # world collective afterwards still works (no state leakage)
    w = paddle.to_tensor(np.full((2,), 1.0, np.float32))
    dist.all_reduce(w)
    results["world_all_reduce"] = np.asarray(w._value).tolist()

    # non-member no-op: rank 0/2 calling the odd group's all_reduce must
    # leave the tensor untouched and not deadlock
    nm = paddle.to_tensor(np.full((2,), 42.0, np.float32))
    if rank in (0, 2):
        dist.all_reduce(nm, group=odd)
    results["non_member"] = np.asarray(nm._value).tolist()

    with open(os.path.join(out_dir, f"rank_{rank}.json"), "w") as f:
        json.dump(results, f)
    print(f"subgroup worker rank {rank}/{world} OK")


if __name__ == "__main__":
    main()
