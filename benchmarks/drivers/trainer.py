"""Drives the program's one-device ``Trainer`` under a batch stream.

Set-up builds ONE object, the compiled step with its state, drives it
from the seed through its first three steps, and hands that same object
to the window. Those steps go through the window's own call and feed
(``Trainer.prefetch`` -> ``Trainer.step``), on rows that all differ; the
plain reference follows the same three steps in float32 in a process of
its own, before this one touches JAX (``benchmarks/reference_steps.py``),
and only its numbers are kept.

The window: one step is always queued behind the one that runs (the
host dispatches step n+1, then waits for step n's loss), so the device
never waits for the host and the host sees each step complete. It opens
at a step's completion and closes at the first completion ``--seconds``
or more later; ``train_tok_s`` is the tokens of the steps completed in
between over exactly that time, so no step is cut in half.
"""
import json
import os
import statistics
import subprocess
import sys
import threading

import numpy as np

from benchmarks import harness
from benchmarks.harness import clock, say


def before_jax(ctx, argv):
    """Run the reference in its own process and wait for it: this one
    has not touched JAX yet, so the chip is free for the child."""
    out = os.path.join(ctx["trace_dir"], "reference.json")
    os.makedirs(ctx["trace_dir"], exist_ok=True)
    t0 = clock()
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "reference_steps.py"),
         "--out", out, *argv], stdout=subprocess.DEVNULL)
    if proc.returncode != 0:
        raise harness.Refused(
            f"the reference's process exited {proc.returncode}")
    with open(out) as fh:
        ctx["reference"] = json.load(fh)
    os.remove(out)
    ctx["reference_s"] = clock() - t0


def build(ctx, jax):
    """(trainer, weight maker) as the configuration's file says."""
    import jax.numpy as jnp
    cfg, prog, model = ctx["config"], ctx["config"]["program"], ctx["model"]
    for flag, value in prog.get("flags", {}).items():
        harness.resolve(prog["flag_store"]).set(flag, value)
    dtype = getattr(jnp, cfg["dtype"])
    mcfg = harness.resolve(prog["config"])(
        dtype=dtype, **{k: model[k] for k in prog["config_keys"]})
    mesh = harness.resolve(prog["make_mesh"])(
        harness.resolve(prog["mesh_config"])(),
        devices=jax.devices()[:ctx["chips"]])
    loss_fn = harness.resolve(prog["loss"])
    opt = ctx["trainer_options"]
    trainer = harness.resolve(prog["trainer"])(
        lambda p, t, l: loss_fn(p, t, l, mcfg), mesh,
        harness.resolve(prog["param_shardings"])(mesh, mcfg),
        lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
        weight_decay=opt["weight_decay"], grad_clip=opt["grad_clip"],
        moment_dtype=getattr(jnp, opt["moment_dtype"]))
    weights = harness.plugin("weights", cfg["weights"])
    return trainer, lambda: weights.make(model, ctx["seed"], dtype)


def leaf_slices(tree):
    """[(name, offset, size)] of the tree's leaves in flattening order:
    how the fused optimizer lays its flat state out."""
    import jax
    out, off = [], 0
    for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        out.append((name, off, int(np.prod(v.shape))))
        off += out[-1][2]
    return out


def state_leaf_norms(state_part, slices, minus=None):
    """Per-leaf L2 norms of a piece of the optimizer's state, flat (the
    fused layout) or a tree; ``minus`` (a parameter tree) is subtracted
    leaf by leaf first. One jitted call: the slices and differences are
    never whole arrays on the device beside the trainer's state."""
    import jax
    import jax.numpy as jnp

    def norms(part, minus):
        if isinstance(part, jax.Array) and part.ndim == 1:
            leaves = [jax.lax.slice(part, (off,), (off + n,))
                      for _, off, n in slices]
        else:
            leaves = [jnp.ravel(v) for v in jax.tree_util.tree_leaves(part)]
        takes = (jax.tree_util.tree_leaves(minus) if minus is not None
                 else [None] * len(leaves))
        out = []
        for v, m in zip(leaves, takes):
            v = v.astype(jnp.float32)
            if m is not None:
                v = v - jnp.ravel(m).astype(jnp.float32)
            out.append(jnp.sqrt(jnp.sum(jnp.square(v))))
        return out

    values = jax.jit(norms)(state_part, minus)
    return {name: float(v) for (name, _, _), v in zip(slices, values)}


def worst_leaf_gap(got, want):
    """Largest |got - want| over the leaves, against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    floor = statistics.median(want.values())
    worst = max(want, key=lambda k: abs(got[k] - want[k])
                / max(want[k], floor))
    return abs(got[worst] - want[worst]) / max(want[worst], floor), worst


def first_steps(ctx, trainer, state, feed, make_weights, n):
    """The program's first ``n`` steps through the window's own call:
    losses, per-leaf norms of the first gradient as the optimizer got
    it (from its first moment after one step) and of the master
    weights' change after ``n``."""
    b1 = ctx["trainer_options"]["b1"]
    slices = leaf_slices(state.params)
    losses, first_grad = [], None
    for i in range(n):
        state, m = trainer.step(state, *next(feed))
        losses.append(float(m["loss"]))
        if i == 0:
            first_grad = {k: v / (1.0 - b1) for k, v in
                          state_leaf_norms(state.mu, slices).items()}
    start = make_weights()
    moved = state_leaf_norms(state.master, slices, minus=start)
    del start
    return state, {"losses": losses, "first_grad": first_grad,
                   "moved": moved}


def window(ctx, trainer, state, feed, spans, profiler, tokens_per_step):
    """(state, losses, facts): the warm steps, then the measured ones."""
    import jax
    seconds = ctx["seconds"]
    trace_s = min(float(ctx["mix"]["trace_s"]), seconds)
    with spans.span("train_step"):
        state, pending = trainer.step(state, *next(feed))

    def advance():
        """Dispatch the next step, then wait for the one before it:
        (that step's loss, the instant it was seen complete)."""
        nonlocal state, pending
        with spans.span("prefetch"):
            batch = next(feed)
        with spans.span("train_step"):
            state, queued = trainer.step(state, *batch)
        with spans.span("wait_previous_step"):
            loss = float(pending["loss"])
        pending = queued
        return loss, clock()

    for _ in range(int(ctx["mix"]["warm_steps"])):
        _, t_open = advance()
    ctx["compiles"].reset()
    losses, traced, now = [], None, t_open
    while now < t_open + seconds:
        if (profiler is not None and traced is None
                and now >= t_open + seconds - trace_s):
            traced = {"t0": now, "tokens": 0}
            profiler.start()
        loss, now = advance()
        losses.append(loss)
        if traced is not None:
            traced["tokens"] += tokens_per_step
    compiles = ctx["compiles"].n
    if traced is not None:
        traced["t1"] = now
        profiler.stop()
    jax.block_until_ready(state.params)
    return state, losses, {"t_open": t_open, "t_close": now,
                           "steps": len(losses), "compiles": compiles,
                           "traced": traced}


def gaps(ref, got):
    """The numbers compared: [(what, value, the tolerance's key)]."""
    out = [(f"|loss - reference's| at step {i}", abs(a - b), "loss_abs")
           for i, (a, b) in enumerate(zip(got["losses"], ref["losses"]), 1)]
    gap, leaf = worst_leaf_gap(got["first_grad"], ref["first_grad"])
    out.append((f"first gradient's norm, worst leaf ({leaf})", gap,
                "first_grad_rel"))
    gap, leaf = worst_leaf_gap(got["moved"], ref["moved"])
    out.append((f"norm of the weights' change after {len(ref['losses'])} "
                f"steps, worst leaf ({leaf})", gap, "moved_rel"))
    return out


def check(ctx, ref, judged, losses, facts):
    tol = ctx["tolerance"]
    chk = harness.Check()
    chk.true("compilations inside the window == 0",
             facts["compiles"] == 0, facts["compiles"])
    chk.true("every loss of the window is finite",
             bool(np.all(np.isfinite(losses))), len(losses))
    for what, value, key in gaps(ref, judged):
        chk.le(what, value, tol[key])
    chk.report()
    return chk.ok


def run(ctx):
    import jax
    import jax.numpy as jnp
    from benchmarks import trace as trace_mod

    cfg, mix, model = ctx["config"], ctx["mix"], ctx["model"]
    spans = harness.Spans(annotate=ctx["trace"])
    gen = harness.plugin("generators", mix["generator"]).Generator(
        mix, ctx["seed"], ctx["seconds"], model["vocab_size"])
    say(offered=gen.offered(), cell=ctx["cell"]["name"], seed=ctx["seed"])
    phases = ctx["phases"]
    trainer, make_weights = build(ctx, jax)
    n_ref = int(ctx["tolerance"]["steps"])
    ref, low = ctx["reference"]["ref"], ctx["reference"]["low"]
    say(reference={"steps": n_ref, "seconds": ctx["reference_s"],
                   "memory_peak_bytes":
                       ctx["reference"]["memory_peak_bytes"],
                   "losses": ref["losses"]})

    state = trainer.init_state(make_weights())
    phases.mark("build_s")
    feed = trainer.prefetch(gen.batches(), depth=int(mix["prefetch_depth"]))
    try:
        state, got = first_steps(ctx, trainer, state, feed, make_weights,
                                 n_ref)
        say(first_steps=got["losses"], fused_optimizer=bool(trainer._fused))
        t_stepped = phases.mark("first_steps_s")
        profiler = (trace_mod.Profiler(ctx["trace_dir"]) if ctx["trace"]
                    else None)
        tokens_per_step = gen.offered()["tokens_per_step"]
        state, losses, facts = window(ctx, trainer, state, feed, spans,
                                      profiler, tokens_per_step)
    finally:
        # close() only raises the producer thread's stop flag; a thread
        # still staging a batch when the interpreter exits aborts the
        # process ("exception not rethrown"), so wait for it to end
        feed.close()
        for t in threading.enumerate():
            if t.name == "device-prefetch":
                t.join(timeout=30)
    phases["warm_steps_s"] = facts["t_open"] - t_stepped
    elapsed = facts["t_close"] - facts["t_open"]
    rate = facts["steps"] * tokens_per_step / elapsed
    say(summary={"steps": facts["steps"], "window_s": elapsed,
                 "step_ms": 1e3 * elapsed / facts["steps"],
                 "loss_first": losses[0], "loss_last": losses[-1]})
    memory_peak = harness.memory_peak_bytes(jax, ctx["chips"])
    if low:          # a control run: the program's own gaps, for the record
        say(program_gaps=[[w, v] for w, v, _ in gaps(ref, got)])
    correct = check(ctx, ref, low if low else got, losses, facts)
    sources = {"spans": spans.rows, "model": model, "peak": ctx["peak"],
               "chips": ctx["chips"], "traced": facts["traced"],
               "programs": cfg["program"]["programs"],
               "shape": {"batch": gen.batch, "seq": gen.seq},
               "cost_model": harness.plugin("cost_models",
                                            cfg["cost_model"]),
               "trace": profiler.load() if profiler else None,
               "import_s": ctx["import_s"]}
    return {"correct": correct, "attempted": facts["steps"], "failed": 0,
            "end_to_end": {"train_tok_s": rate,
                           "setup_s": facts["t_open"] - ctx["t_imported"]},
            "sources": sources, "memory_peak_bytes": memory_peak}
