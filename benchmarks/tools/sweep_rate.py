#!/usr/bin/env python3
"""Find an open-loop cell's knee: one process, one engine, a window at
each rate.

    python3 benchmarks/tools/sweep_rate.py --workload <cell> --rates 1.0 1.15 ...

For each rate it runs the cell's own loop (warm phase, window, drain)
with ``arrivals.rate_per_s`` replaced, and prints one JSON line: output
tokens the window offered and emitted, the queue at the window's middle
and end, and the tails. The knee is the highest rate at which the queue
at the window's end is no longer than at its middle and ``ttft_p95_ms``
is still on the lower rates' plateau (40 s a rate, geometric steps of
1.15); the mix file then holds 0.8 x knee, a new file with ``base`` the
old one. Needs the cell's chips; ``--rehearse`` as in run.py.

    python3 benchmarks/tools/sweep_rate.py --pick <sweep output> [...]

reads kept sweep lines again (no chip) and prints the knee by that rule,
as ``knee()`` below states it, and 0.8 x knee to one decimal. A rate may
be swept more than once (give it again in ``--rates``, or in another
process with another ``--seed``): its windows then vote.

The rate found, the cell is held to the rule of ``tools/spread.py``
(ISSUE 47): over ten runs of one commit the spread of every end-to-end
metric it is judged on is at most half the metric's bound, and its tail
of ``tpot_ms`` is the highest of p95 / p90 / p75 that leaves ten of the
window's requests beyond it (200 / 100 / 40 requests a window).
"""
import argparse
import copy
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness, run as bench_run  # noqa: E402
from benchmarks.drivers import serving_engine as drv  # noqa: E402


OFF_PLATEAU = 1.5        # x the plateau's ttft_p95_ms: off it (the sweeps
#                          of PR 27, 31 and 43 left theirs by 1.6 x or more)


def window_sustained(row, plateau):
    """One window: nothing stayed unfinished, the queue at its end is no
    longer than at its middle (two waiting requests are no queue), and
    ``ttft_p95_ms`` is at most ``OFF_PLATEAU`` x the plateau."""
    return (not row["unfinished"]
            and row["queue_end"] <= max(row["queue_mid"], 2)
            and row["ttft_p95_ms"] <= OFF_PLATEAU * plateau)


def knee(rows):
    """The knee rule on a sweep's lines (dicts with ``rate_per_s``,
    ``queue_mid``, ``queue_end``, ``unfinished``, ``ttft_p95_ms``), one
    or more windows a rate. The plateau is the median, over the three
    lowest rates swept, of each rate's median ``ttft_p95_ms``. A rate is
    sustained when more than half of its windows are
    (``window_sustained``). The knee is the highest rate that is
    sustained with EVERY lower rate swept: a rate that fails ends the
    search, whatever a higher one reads, and one window is never set
    aside as chance (sweep the rate again, and the windows vote). A
    sweep whose highest rate is still sustained has not found the knee,
    and says so (``reached`` false)."""
    by_rate = {}
    for r in sorted(rows, key=lambda r: r["rate_per_s"]):
        by_rate.setdefault(r["rate_per_s"], []).append(r)
    rates = list(by_rate)
    medians = sorted(statistics.median(r["ttft_p95_ms"] for r in by_rate[x])
                     for x in rates[:3])
    plateau = medians[len(medians) // 2]
    good = []
    for rate in rates:
        ok = sum(window_sustained(r, plateau) for r in by_rate[rate])
        if 2 * ok <= len(by_rate[rate]):
            break
        good.append(rate)
    if not good:
        return None
    return {"knee": good[-1], "rate": round(0.8 * good[-1], 1),
            "plateau_ttft_p95_ms": plateau,
            "windows": {str(x): len(by_rate[x]) for x in rates},
            "reached": good[-1] < rates[-1]}


def pick(paths):
    rows = []
    for path in paths:
        with open(path) as fh:
            rows += [json.loads(l)["sweep"] for l in fh if '"sweep"' in l]
    print(json.dumps(knee(rows)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pick", nargs="+", metavar="SWEEP_OUTPUT")
    ap.add_argument("--workload")
    ap.add_argument("--rates", type=float, nargs="+")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    if a.pick:
        return pick(a.pick)
    if not (a.workload and a.rates):
        ap.error("--workload and --rates, or --pick")
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
