"""Elastic-training worker for the end-to-end failover test
(tests/test_launch.py::test_elastic_end_to_end).

Reference flow being reproduced (fleet/elastic/manager.py:126 watch ->
re-rank -> relaunch + flex_checkpoint resume): a 4-node world trains a
GSPMD-sharded quadratic; one trainer crashes mid-run; the surviving
controllers re-rank to a 3-node world and respawn; the respawned workers
load the 4-way-sharded distributed checkpoint into the 3-device mesh
(reshard-on-load) and training resumes where it left off.

Every rank:
- joins the jax coordination service (gloo CPU collectives);
- holds W sharded over all processes' devices (NamedSharding, rows);
- runs deterministic full-batch GD so the loss trajectory is exactly
  reproducible across incarnations;
- saves the sharded distributed checkpoint every step;
- the victim rank (ELASTIC_VICTIM, incarnation 0 only) exits hard after
  CRASH_STEP steps, simulating a machine loss.
"""
import json
import os
import re
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# the test-suite conftest leaks --xla_force_host_platform_device_count=8
# into child env; under jax.distributed that would give EVERY process 8
# local devices, so "global" meshes land entirely on process 0's devices
# and no cross-process collective ever happens. One device per process.
os.environ["XLA_FLAGS"] = re.sub(
    r"--xla_force_host_platform_device_count=\d+", "",
    os.environ.get("XLA_FLAGS", "")).strip()

import numpy as np
import jax
import jax.numpy as jnp

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import paddle_tpu.distributed as dist                      # noqa: E402
from paddle_tpu.core.tensor import Tensor                  # noqa: E402
from paddle_tpu.distributed.checkpoint.save_load import (  # noqa: E402
    load_state_dict, save_state_dict)

ROWS, COLS, N = 24, 4, 64
TOTAL_STEPS = 12
CRASH_STEP = 5
LR = 0.05


def latest_complete_ckpt(root):
    """Newest per-step checkpoint dir where EVERY rank of the saving
    world finished: all per-rank metadata fragments present and every
    referenced shard file on disk. A crash mid-save leaves an incomplete
    dir (the dead rank's fragment/file missing) which must be skipped —
    resuming from a MIXED-step checkpoint silently corrupts the state
    (reference: per-step save_dirs + completeness check in fleet
    auto-recovery)."""
    import glob
    for d in sorted(glob.glob(os.path.join(root, "step_*")),
                    reverse=True):
        frags = sorted(glob.glob(os.path.join(d, "metadata_*.json")))
        if not frags:
            continue
        try:
            metas = [json.load(open(fp)) for fp in frags]
        except (OSError, json.JSONDecodeError):
            continue
        world = metas[0].get("world", 1)
        if len(frags) < world:
            continue   # some rank never finished its save
        files = {s["file"] for m in metas
                 for shards in m["shards"].values() for s in shards}
        if all(os.path.exists(os.path.join(d, f)) for f in files):
            return d
    return None


def main():
    out_dir = sys.argv[1]
    ckpt = os.path.join(out_dir, "ckpt")
    job = int(os.environ.get("PADDLE_JOB_ID", "0"))
    victim = int(os.environ.get("ELASTIC_VICTIM", "-1"))

    dist.init_parallel_env()
    rank = dist.get_rank()
    world = dist.get_world_size()

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    assert len(jax.devices()) == world, \
        (len(jax.devices()), world, os.environ.get("XLA_FLAGS"))
    mesh = Mesh(np.array(jax.devices()), ("fsdp",))
    sh = NamedSharding(mesh, P("fsdp"))

    rng = np.random.RandomState(0)
    A = jnp.asarray(rng.randn(N, ROWS), jnp.float32)
    b = jnp.asarray(rng.randn(N, COLS), jnp.float32)
    w_host = rng.randn(ROWS, COLS).astype(np.float32) * 0.1

    def to_mesh(host):
        return jax.make_array_from_callback(
            host.shape, sh, lambda idx: host[idx])

    w = to_mesh(w_host)
    start = 0
    resume_dir = latest_complete_ckpt(ckpt)
    if resume_dir is not None:
        state = {"w": Tensor(w), "step": 0}
        load_state_dict(state, resume_dir)
        w = state["w"]._value
        start = int(np.asarray(state["step"])) + 1

    @jax.jit
    def step(w):
        loss, g = jax.value_and_grad(
            lambda w: jnp.mean((A @ w - b) ** 2))(w)
        return w - LR * g, loss

    losses = []
    with mesh:
        for i in range(start, TOTAL_STEPS):
            w, loss = step(w)
            losses.append(float(loss))
            save_state_dict({"w": Tensor(w), "step": i},
                            os.path.join(ckpt, f"step_{i:04d}"))
            if job == 0 and rank == victim and i + 1 >= CRASH_STEP:
                # simulated machine loss: no cleanup, no goodbye
                os._exit(13)

    # w spans all processes' devices (np.asarray on it would raise, and
    # a process_allgather would spin up a second gloo context at
    # teardown — flaky on a loaded box). Each rank reports only its OWN
    # shard + offset; the test reassembles the global array.
    shard = w.addressable_shards[0]
    res = {"rank": rank, "world": world, "job": job, "start": start,
           "losses": losses,
           "w_offset": int(shard.index[0].start or 0),
           "w_local": np.asarray(shard.data).tolist()}
    with open(os.path.join(out_dir, f"rank{rank}_job{job}.json"),
              "w") as f:
        json.dump(res, f)
    print(f"elastic worker rank {rank}/{world} job {job} done "
          f"(steps {start}..{TOTAL_STEPS - 1})")


if __name__ == "__main__":
    main()
