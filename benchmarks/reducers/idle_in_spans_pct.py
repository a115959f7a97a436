"""Share of the traced window in which device 0 ran no operation while
the program was inside one of its spans named ``span``: the overlap of
the device's idle gaps with those spans, whatever host event lies
innermost there (JAX's own ``DevicePut`` inside ``serve/table_upload``
is still the step's). What is left of the device's idle share fell
between the spans: the caller's loop, the generator. None where the
trace holds no such span."""
from benchmarks.reducers.program_span_ms import spans_named
from benchmarks.trace import merged


def read(sources, args):
    trace = sources.get("trace")
    if trace is None:
        return None
    spans = spans_named(trace.host, args["span"])
    if not spans:
        return None
    lo, hi = trace.bounds_ns()
    busy = merged((s, d) for s, d, _ in trace.ops.get(0, ()))
    inside, j = 0.0, 0
    for s, e in spans:                 # spans and busy both ascend
        while j < len(busy) and busy[j][1] <= s:
            j += 1
        k, covered = j, 0.0
        while k < len(busy) and busy[k][0] < e:
            covered += min(busy[k][1], e) - max(busy[k][0], s)
            k += 1
        inside += (e - s) - covered
    return 100.0 * inside / (hi - lo)
