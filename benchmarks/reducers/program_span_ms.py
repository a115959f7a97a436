"""Mean length of one of the PROGRAM's own spans in the traced window,
less the time of the children named under ``less`` (each instant of a
parent counted once, however the children nest): the profiler's host
line, on the device trace's clock. ``serve/step`` less its two blocking
reads is the host's own work in a step. None where the trace holds no
such span (a commit of the program without the spans)."""
from benchmarks.trace import union_ns


def spans_named(host, name):
    """Sorted (start, end) of the host events named ``name`` (a
    ``TraceAnnotation``'s metadata is kept apart from its name)."""
    return sorted((s, s + d) for s, d, n in host if n == name)


def read(sources, args):
    trace = sources.get("trace")
    if trace is None:
        return None
    parents = spans_named(trace.host, args["span"])
    if not parents:
        return None
    less = set(args.get("less", ()))
    kids = sorted((s, d) for s, d, n in trace.host if n in less)
    total, j = 0.0, 0
    for s, e in parents:               # parents ascend and do not overlap
        while j < len(kids) and kids[j][0] < s:
            j += 1
        k = j
        while k < len(kids) and kids[k][0] < e:
            k += 1
        total += (e - s) - union_ns(kids[j:k])
        j = k
    return total / len(parents) / 1e6
