"""A roofline share whose cost model needs more of the traced window's
shape than the driver's means of live slots and live tokens: numbers
the PROGRAM counted there. ``shape`` maps a key of the cost model's
shape to a pair of engine counters; the key gets the first counter's
growth over the second's, both over the traced window
(``traced["engine0"]`` -> ``["engine1"]``): the tokens the window
layers' launches visit in a mean decode step, say. The share itself is
the reducer named ``reducer`` (``kernel_roofline``, ``scope_roofline``,
``program_roofline``) with ``args``, handed that shape. None where the
program does not count one of them (a commit without the counters), or
the inner reader finds nothing."""
from benchmarks import harness


def read(sources, args):
    traced, shape = sources.get("traced"), sources.get("shape")
    if not traced or not shape:
        return None
    c0, c1 = traced.get("engine0", {}), traced.get("engine1", {})
    counted = {}
    for key, (num, den) in args["shape"].items():
        if any(k not in c for k in (num, den) for c in (c0, c1)):
            return None
        grew = c1[den] - c0[den]
        if grew <= 0:
            return None
        counted[key] = (c1[num] - c0[num]) / grew
    inner = harness.plugin("reducers", args["reducer"])
    return inner.read({**sources, "shape": {**shape, **counted}},
                      args["args"])
