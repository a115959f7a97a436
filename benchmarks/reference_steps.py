#!/usr/bin/env python3
"""The training cell's plain reference, in a process of its own.

    python3 benchmarks/reference_steps.py --workload <cell> --seed <n> --out <file>

The trainer's driver starts this before its own process touches JAX,
waits for it to end, and reads the numbers it wrote: the reference then
never shares the device's allocator with the program, so
``memory_peak_bytes`` of the run is the program's alone (in one process
the float32 reference's 12.2 GB peak hid the trainer's, PR 23). It
follows the configuration's first optimizer steps in float32 from the
same seed, and with ``--control`` also in the lower precision.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, run as bench_run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    known, rest = ap.parse_known_args(argv)
    args = bench_run.parse(rest)
    ctx = bench_run.context(args, harness.load_benchmark(),
                            time.perf_counter())
    import jax
    import jax.numpy as jnp
    harness.require_chips(jax, ctx["chips"], args.rehearse)
    cfg, mix, model = ctx["config"], ctx["mix"], ctx["model"]
    gen = harness.plugin("generators", mix["generator"]).Generator(
        mix, ctx["seed"], ctx["seconds"], model["vocab_size"])
    weights = harness.plugin("weights", cfg["weights"])
    reference = harness.plugin("reference", cfg["reference"])
    src = gen.batches()
    first = [tuple(jnp.asarray(a) for a in next(src))
             for _ in range(int(ctx["tolerance"]["steps"]))]
    opt = dict(ctx["trainer_options"], eps=1e-8)
    dtype = getattr(jnp, cfg["dtype"])

    def follow(fake_quant):
        return reference.adamw_steps(
            weights.make(model, ctx["seed"], dtype), model, first, opt,
            fake_quant=fake_quant)

    out = {"ref": follow(None), "low": None}
    if ctx.get("reference_fake_quant"):
        out["low"] = follow(ctx["reference_fake_quant"])
    out["memory_peak_bytes"] = harness.memory_peak_bytes(jax, ctx["chips"])
    with open(known.out, "w") as fh:
        json.dump(out, fh)


if __name__ == "__main__":
    main()
