"""A computation's share of its roofline inside ONE of the programs: the
device time of the operations that belong to it, among those that ran
inside executions of the program named ``program`` (the configuration's
``program.programs`` pattern), against the least time the cost model
gives it there.

An operation belongs to the computation when one of the ``match``
patterns is found in its event name. A Pallas launch is named by its
``name=`` (``%ssm_update.3 = ...``); an operation XLA made keeps the
program's ``jax.named_scope`` in its ``op_name`` metadata, which the
event name carries where the profiler writes the whole instruction;
XLA's own grouped product is ``%ragged-dot...``. The same launch name
also occurs in the other programs (the expert launches run in decode
and in prefill), which is why the operations are first cut to the
program's executions.

``per``: "execution" - the cost model's entry gives one execution's
cost from the traced window's shape; "tokens" - it gives the cost of
the prompt tokens the engine counted in the traced window
(``counter``), whatever chunks they came in. None where the trace
holds no such operation (a commit of the program without it)."""
import re

from benchmarks.harness import say
from benchmarks.trace import share_pct


def matching_seconds(trace, runs, patterns, device=0):
    """(operations, seconds) of the leaf operations on ``device`` that
    start inside one of ``runs`` [(start, end)] and match a pattern."""
    rxs = [re.compile(p) for p in patterns]
    runs = sorted(runs)
    n, total, j = 0, 0.0, 0
    for name, start, dur, leaf in sorted(trace.self_times(device),
                                         key=lambda e: e[1]):
        while j < len(runs) and runs[j][1] <= start:
            j += 1
        if j == len(runs):
            break
        if start < runs[j][0] or not any(r.search(name) for r in rxs):
            continue
        n += 1
        total += dur / 1e9
    return n, total


def read(sources, args):
    trace, patterns = sources.get("trace"), sources.get("programs", {})
    shape, traced = sources.get("shape"), sources.get("traced")
    if trace is None or not shape or args["program"] not in patterns:
        return None
    if not sources.get("peak"):      # a rehearsal: no chip, no peak
        return None
    cm = sources["cost_model"]
    if args["cost"] not in getattr(cm, "KERNELS", {}):
        return None
    rx = re.compile(patterns[args["program"]])
    runs = [(s, s + d) for s, d, name in trace.modules.get(0, ())
            if rx.search(name)]
    ops, measured = matching_seconds(trace, runs, args["match"])
    if not ops or not measured:
        return None
    shape = dict(shape)
    times = len(runs)
    if args.get("per", "execution") == "tokens":
        c0, c1 = traced["engine0"], traced["engine1"]
        shape["tokens"] = c1[args["counter"]] - c0[args["counter"]]
        times = 1
        if shape["tokens"] <= 0:
            return None
    flops, moved = cm.KERNELS[args["cost"]](sources["model"], shape)
    one, bound = cm.least_seconds(flops, moved, sources["peak"])
    say(roofline={"scope": args["cost"], "program": args["program"],
                  "operations": ops, "executions": len(runs),
                  "bound": bound, "flops": flops, "bytes": moved,
                  "least_s": one * times, "measured_s": measured})
    return share_pct(one * times, measured, args["cost"])
