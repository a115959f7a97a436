"""A kernel's share of its roofline: for each named kernel, launches in
the trace x the least time of one launch (cost model, peak table), over
the summed device time of those launches."""
from benchmarks.trace import share_pct
from benchmarks.harness import say


def read(sources, args):
    trace, shape = sources.get("trace"), sources.get("shape")
    if trace is None or not shape:
        return None
    if not sources.get("peak"):      # a rehearsal: no chip, no peak
        return None
    cm = sources["cost_model"]
    least = measured = 0.0
    for kernel in args["kernels"]:
        launches, seconds = trace.kernel(kernel)
        if not launches:
            continue
        flops, moved = cm.KERNELS[kernel](sources["model"], shape)
        one, bound = cm.least_seconds(flops, moved, sources["peak"])
        say(roofline={"kernel": kernel, "launches": launches,
                      "bound": bound, "flops": flops, "bytes": moved,
                      "least_s_a_launch": one,
                      "measured_s_a_launch": seconds / launches})
        least += one * launches
        measured += seconds
    if not measured:
        return None
    return share_pct(least, measured, "+".join(args["kernels"]))
