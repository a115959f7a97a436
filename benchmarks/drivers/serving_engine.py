"""Drives the program's serving engine under a traffic mix.

One loop in the chip's own process: it submits what is due, calls the
engine's ``step()``, and sleeps only while the engine is idle. Every
latency is taken here, from outside the program, on the host's clock:
a request is timed from the instant it was *due*, its tokens are
stamped when the ``step()`` that produced them returns.

The generator runs ``warm_s`` seconds before the window opens (set-up);
the window takes the requests that fall due inside it; afterwards the
engine drains for ``grace_s`` and what has not finished then has failed.
With ``--trace 1`` the profiler covers the window's last ``trace_s``
seconds, so that starting and stopping it disturb nothing measured.
"""
import gc
import time

import numpy as np

from benchmarks import harness
from benchmarks.harness import clock, say


# The tails of ``tpot_ms`` a window reports; BENCHMARK.json judges a cell
# on ONE: the highest that leaves 10 of the window's requests beyond it
# and whose spread over the cell's ten runs is at most half its bound
# (PERF.md 2 holds each cell's runs); the others stay in the ``summary``
# line.
TPOT_TAILS = (75, 90, 95)


class Record:
    __slots__ = ("spec", "req", "due_t", "submit_t", "first_t", "last_t",
                 "seen", "in_window")

    def __init__(self, spec, req, due_t, submit_t, in_window):
        self.spec, self.req = spec, req
        self.due_t, self.submit_t = due_t, submit_t
        self.first_t = self.last_t = None
        self.seen = 0
        self.in_window = in_window


def build(ctx, jax):
    """(engine, weight maker) from the configuration's file: the
    weights are the benchmark's, the engine the program's. The driver
    keeps no copy of the weights through the window (an engine that
    quantizes them would then hold two); the check makes them again
    from the seed once the engine is freed."""
    cfg, prog = ctx["config"], ctx["config"]["program"]
    model = ctx["model"]
    for flag, value in prog.get("flags", {}).items():
        harness.resolve(prog["flag_store"]).set(flag, value)
    import jax.numpy as jnp
    mcfg = harness.resolve(prog["config"])(
        dtype=getattr(jnp, cfg["dtype"]),
        **{k: model[k] for k in prog["config_keys"]})
    weights = harness.plugin("weights", cfg["weights"])

    def make_weights():
        return weights.make(model, ctx["seed"], getattr(jnp, cfg["dtype"]))

    opts = dict(ctx["engine_options"])
    opts["prefill_buckets"] = tuple(opts["prefill_buckets"])
    engine = harness.resolve(prog["engine"])(
        make_weights(), mcfg, seed=ctx["seed"] % (2 ** 31), **opts)
    return engine, make_weights


def warm_up(engine, gen_cfg, vocab, rng):
    """Compile every program the window can reach: the decode program,
    each prefill bucket (one prompt that fills it, and one longer than
    the largest, which is chunked), and the prefix cache's page copy,
    which a prompt that shares a page and a half with an earlier one
    sets off (with random tokens the window meets such a prompt by
    chance, and would compile the copy inside it)."""
    buckets, page = engine.buckets, engine.block_size
    lens = list(buckets) + [buckets[-1] + buckets[0]]
    prompts = [rng.integers(0, vocab, n, dtype=np.int32) for n in lens]
    fork = prompts[-1].copy()
    fork[page + page // 2:] = rng.integers(0, vocab,
                                           fork.size - page - page // 2)
    for wave in (prompts, [fork]):
        for p in wave:
            engine.submit(p, gen_cfg(max_new_tokens=4, greedy=True))
        engine.drain()
    engine.reset_metrics()


def serve(ctx, engine, gen, gen_cfg, spans, profiler):
    """The warm phase, the window and the drain: (records, counts)."""
    seconds, mix = ctx["seconds"], ctx["mix"]
    grace_s = float(mix["grace_s"])
    t0 = clock()
    t_open, t_close = t0 + gen.warm_s, t0 + gen.warm_s + seconds
    t_trace = t_close - min(float(mix["trace_s"]), seconds)
    live, done = [], []
    counts = {"window_tokens": 0, "steps": 0, "t0": t0, "t_open": t_open,
              "t_close": t_close, "traced": None, "compiles": None}
    traced = None
    opened = closed = False
    while True:
        now = clock()
        if "queue_mid" not in counts and now >= (t_open + t_close) / 2:
            counts["queue_mid"] = engine.queue_depth
        if not opened and now >= t_open:
            opened = True
            ctx["compiles"].reset()
        if (profiler is not None and traced is None and not closed
                and now >= t_trace):
            traced = {"t0": now, "engine0": dict(engine.counters),
                      "steps": 0, "live_tokens": 0, "live_slots": 0}
            profiler.start()
        if not closed and now >= t_close:
            closed = True
            counts["compiles"] = ctx["compiles"].n
            counts["engine_metrics"] = engine.metrics()
            counts["queue_end"] = engine.queue_depth
            if traced is not None:
                traced["t1"] = now
                traced["engine1"] = dict(engine.counters)
                profiler.stop()
                counts["traced"], traced = traced, None
            if gen.closed:
                break
        if closed and (not any(r.in_window for r in live)
                       or now >= t_close + grace_s):
            break
        # an open loop's schedule ends with the window, so whatever is
        # due is submitted, also when a long step carried the loop past
        # the close; a closed loop has left the loop by then
        with spans.span("submit"):
            for spec in gen.due(now - t0):
                due_t = now if gen.closed else t0 + spec["due"]
                req = engine.submit(spec["prompt"], gen_cfg(
                    max_new_tokens=spec["max_new_tokens"], greedy=True))
                live.append(Record(spec, req, due_t, now,
                                   due_t >= t_open))
        if engine.idle:
            nxt = gen.next_due()
            with spans.span("idle_sleep"):
                time.sleep(max(0.0, min(0.002, (t0 + nxt - now)
                                        if nxt is not None else 0.002)))
            continue
        with spans.span("engine_step"):
            engine.step()
        now = clock()
        counts["steps"] += 1
        in_window = t_open <= now < t_close
        still, ctx_tokens, decoding = [], 0, 0
        for r in live:
            n = len(r.req.tokens)
            if n > r.seen:
                if r.first_t is None:
                    r.first_t = now
                if in_window:
                    counts["window_tokens"] += n - r.seen
                r.seen = n
            if r.req.done:
                r.last_t = now
                gen.finished(r.spec)
                done.append(r)
            else:
                still.append(r)
                if n:
                    decoding += 1
                    ctx_tokens += r.spec["prompt"].size + n
        live = still
        if traced is not None:
            traced["steps"] += 1
            traced["live_tokens"] += ctx_tokens
            traced["live_slots"] += decoding
    # a closed loop is cut at the window's close with its callers still
    # waiting: those are not failures, an open loop's stragglers are
    counts["unfinished"] = ([] if gen.closed
                            else [r for r in live if r.in_window])
    return done, counts


def window_metrics(done, counts, seconds, closed_loop):
    """The end-to-end metrics, and the earlier lines' summaries."""
    t_open, t_close = counts["t_open"], counts["t_close"]
    if closed_loop:
        win = [r for r in done if t_open <= r.last_t < t_close]
    else:
        win = [r for r in done if r.in_window]
    ttft = [(r.first_t - r.due_t) * 1e3 for r in win]
    tpot = [(r.last_t - r.first_t) / (r.seen - 1) * 1e3
            for r in win if r.seen > 1]
    late = [(r.submit_t - r.due_t) * 1e3 for r in win]
    wait = [(r.req.admit_t - r.due_t) * 1e3 for r in win
            if r.req.admit_t is not None]
    tails = ({f"p{q}": harness.percentile(tpot, q) for q in TPOT_TAILS}
             if tpot else {})
    out = {"out_tok_s": counts["window_tokens"] / seconds}
    if not closed_loop and ttft:
        out["ttft_mean_ms"] = sum(ttft) / len(ttft)
        out.update({f"tpot_{p}_ms": v for p, v in tails.items()})
    say(summary={"ttft_ms": harness.summary(ttft),
                 "tpot_ms": {**harness.summary(tpot), **tails},
                 "generator_late_ms": harness.summary(late),
                 "queue_wait_ms": harness.summary(wait),
                 "window_tokens": counts["window_tokens"],
                 "engine_steps": counts["steps"]})
    return win, out, {"queue_wait_ms": wait, "ttft_ms": ttft,
                      "tpot_ms": tpot,
                      "tpot_tokens": [r.seen for r in win if r.seen > 1]}


def pick_sample(win, seed, n):
    """A seeded sample of finished requests, the longest and the
    shortest among them."""
    by_len = sorted(win, key=lambda r: r.spec["prompt"].size + r.seen)
    rng = np.random.default_rng([int(seed), 0x5A3B1E])
    picked = {id(by_len[0]): by_len[0], id(by_len[-1]): by_len[-1]}
    for i in rng.permutation(len(by_len)):
        if len(picked) >= min(n, len(by_len)):
            break
        picked.setdefault(id(by_len[i]), by_len[i])
    return list(picked.values())


def check_served(ctx, params, win, counts, engine_facts):
    """Hold the window's own tokens to the plain reference."""
    tol, cfg = ctx["tolerance"], ctx["config"]
    ref = harness.plugin("reference", cfg["reference"])
    chk = harness.Check()
    chk.true("compilations inside the window == 0",
             counts["compiles"] == 0, counts["compiles"])
    chk.true("decode programs traced == 1",
             engine_facts["decode_traces"] == 1,
             engine_facts["decode_traces"])
    chk.true("every finished request has all its tokens",
             all(r.seen == r.spec["max_new_tokens"] for r in win),
             len(win))
    chk.true("requests finished inside the window > 0", bool(win), len(win))
    if win:
        margins = []
        for r in pick_sample(win, ctx["seed"], int(tol["sample"])):
            margins.append(ref.served_margins(
                params, ctx["model"], r.spec["prompt"],
                np.asarray(r.req.tokens, np.int32),
                fake_quant=ctx.get("reference_fake_quant")))
        allm = np.concatenate(margins)
        say(reference={"requests": len(margins), "tokens": int(allm.size),
                       "flipped": int((allm > 0).sum())})
        chk.le("widest gap of a served token's logit below the "
               "reference's best", allm.max(), tol["margin_max"])
        chk.le("mean gap over the sampled tokens", allm.mean(),
               tol["margin_mean"])
    chk.report()
    return chk.ok


def run(ctx):
    import jax
    from benchmarks import trace as trace_mod

    cfg, mix = ctx["config"], ctx["mix"]
    spans = harness.Spans(annotate=ctx["trace"])
    gen_cfg = harness.resolve(cfg["program"]["generation_config"])
    phases = ctx["phases"]
    engine, make_weights = build(ctx, jax)
    vocab = ctx["model"]["vocab_size"]
    gen = harness.plugin("generators", mix["generator"]).Generator(
        mix, ctx["seed"], ctx["seconds"], vocab)
    say(offered=gen.offered(), cell=ctx["cell"]["name"], seed=ctx["seed"])
    phases.mark("build_s")
    warm_up(engine, gen_cfg, vocab,
            np.random.default_rng([ctx["seed"], 0x3A93]))
    phases.mark("warm_up_s")
    say(decode_variant=engine.metrics()["decode_variant"],
        prefill_variant=engine.metrics()["prefill_variant"])
    profiler = trace_mod.Profiler(ctx["trace_dir"]) if ctx["trace"] else None

    done, counts = serve(ctx, engine, gen, gen_cfg, spans, profiler)
    phases["warm_traffic_s"] = counts["t_open"] - counts["t0"]
    win, e2e, samples = window_metrics(done, counts, ctx["seconds"],
                                       gen.closed)
    e2e["setup_s"] = counts["t_open"] - ctx["t_imported"]
    facts = counts["engine_metrics"]
    failed = len(counts["unfinished"])
    attempted = len(win) + failed
    memory_peak = harness.memory_peak_bytes(jax, ctx["chips"])
    traced = counts["traced"]
    shape = {"slots": engine.capacity} if traced else None
    if traced and traced["steps"]:
        shape["live_tokens"] = traced["live_tokens"] / traced["steps"]
        shape["live_slots"] = traced["live_slots"] / traced["steps"]
    del engine, done
    gc.collect()

    with spans.span("check"):
        correct = check_served(ctx, make_weights(), win, counts, facts)
    sources = {"samples": samples, "engine": facts, "counts": counts,
               "spans": spans.rows, "model": ctx["model"],
               "programs": cfg["program"]["programs"],
               "shape": shape, "peak": ctx["peak"], "chips": ctx["chips"],
               "cost_model": harness.plugin("cost_models",
                                            cfg["cost_model"]),
               "trace": profiler.load() if profiler else None,
               "traced": counts["traced"], "import_s": ctx["import_s"]}
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "end_to_end": e2e, "sources": sources,
            "memory_peak_bytes": memory_peak}
