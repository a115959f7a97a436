"""A compiled program's share of its roofline: the least time the chip
could take for one execution (the cost model's operations and bytes
against the peak table) over the mean device time of an execution."""
from benchmarks.trace import share_pct
from benchmarks.harness import say


def read(sources, args):
    trace, patterns = sources.get("trace"), sources.get("programs", {})
    shape = sources.get("shape")
    if trace is None or not shape or args["program"] not in patterns:
        return None
    if not sources.get("peak"):      # a rehearsal: no chip, no peak
        return None
    runs = trace.module_runs(patterns[args["program"]])
    if not runs:
        return None
    cm = sources["cost_model"]
    flops, moved = cm.PROGRAMS[args["cost"]](sources["model"], shape)
    least, bound = cm.least_seconds(flops, moved, sources["peak"])
    say(roofline={"program": args["program"], "bound": bound,
                  "flops": flops, "bytes": moved, "least_s": least,
                  "measured_s": sum(runs) / len(runs), "shape": shape})
    return share_pct(least, sum(runs) / len(runs), args["program"])
