"""The parameter-server workload, TPU-native: a mesh-sharded sparse
embedding table with entry-gated admission and sparse Adagrad — rows
live sharded over the mesh (capacity scales with the slice), lookups
are GSPMD gathers, updates touch only the pulled rows."""
import numpy as np

from _common import setup

jax = setup()

import jax.numpy as jnp                                    # noqa: E402
from jax.sharding import Mesh                              # noqa: E402
from paddle_tpu.distributed.fleet import (                 # noqa: E402
    CountFilterEntry, ShardedSparseTable)


def main():
    mesh = Mesh(np.array(jax.devices()), ("mp",))
    table = ShardedSparseTable(
        num_rows=4096, dim=16, mesh=mesh, optimizer="adagrad", lr=0.1,
        entry=CountFilterEntry(2))     # rows admit after 2 sightings
    w, acc, counts = table.weight, table.accum, table.counts

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 4096, (64,)), jnp.int32)
    tgt = jnp.asarray(rng.randn(64, 16), jnp.float32)

    for step in range(4):
        counts = table.observe(counts, ids)
        loss, w, acc = table.grad_and_update(
            w, acc, ids, lambda rows: jnp.mean((rows - tgt) ** 2),
            counts=counts)
        print(f"step {step}: loss {float(loss):.4f} "
              f"(admitted rows train, fresh rows gated)")


if __name__ == "__main__":
    main()
