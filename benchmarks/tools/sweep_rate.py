#!/usr/bin/env python3
"""Find an open-loop cell's knee: one process, one engine, a window at
each rate.

    python3 benchmarks/tools/sweep_rate.py --workload <cell> --rates 1.0 1.15 ...

For each rate it runs the cell's own loop (warm phase, window, drain)
with ``arrivals.rate_per_s`` replaced, and prints one JSON line: output
tokens the window offered and emitted, the queue at the window's middle
and end, and the tails. The knee is the highest rate at which at least
95% of the offered output tokens were emitted inside the window and the
queue at its end is no longer than at its middle; the mix file then
holds 0.8 x knee. Needs the cell's chips; ``--rehearse`` as in run.py.
"""
import argparse
import copy
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness, run as bench_run  # noqa: E402
from benchmarks.drivers import serving_engine as drv  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    args = bench_run.parse(["--workload", a.workload, "--seed", str(a.seed)]
                           + (["--seconds", str(a.seconds)]
                              if a.seconds else [])
                           + (["--rehearse"] if a.rehearse else []))
    ctx = bench_run.context(args, harness.load_benchmark(),
                            time.perf_counter())
    import jax
    import numpy as np
    harness.require_chips(jax, ctx["chips"], a.rehearse)
    import paddle_tpu  # noqa: F401
    ctx["compiles"] = harness.CompileCounter()
    engine, _ = drv.build(ctx, jax)
    gen_cfg = harness.resolve(ctx["config"]["program"]["generation_config"])
    vocab = ctx["model"]["vocab_size"]
    drv.warm_up(engine, gen_cfg, vocab, np.random.default_rng(a.seed))
    spans = harness.Spans()
    for rate in a.rates:
        mix = copy.deepcopy(ctx["mix"])
        mix["arrivals"]["rate_per_s"] = rate
        one = dict(ctx, mix=mix)
        gen = harness.plugin("generators", mix["generator"]).Generator(
            mix, a.seed, ctx["seconds"], vocab)
        done, counts = drv.serve(one, engine, gen, gen_cfg, spans, None)
        win, e2e, samples = drv.window_metrics(done, counts,
                                               ctx["seconds"], False)
        offered = gen.offered()["window_output_tokens"]
        harness.say(sweep={
            "rate_per_s": rate, "offered_out_tokens": offered,
            "emitted_in_window": counts["window_tokens"],
            "share": counts["window_tokens"] / offered,
            "queue_mid": counts.get("queue_mid"),
            "queue_end": counts.get("queue_end"),
            "unfinished": len(counts["unfinished"]),
            "ttft_p95_ms": harness.percentile(samples["ttft_ms"], 95),
            **e2e})
        engine.drain()


if __name__ == "__main__":
    main()
