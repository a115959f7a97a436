"""One of the engine's counters over another, both as they grew over
the traced window (``traced["engine0"]`` -> ``["engine1"]``), in
percent. None where the program does not count ``num`` or ``den``, or
``den`` did not move."""


def read(sources, args):
    traced = sources.get("traced")
    if not traced:
        return None
    c0, c1 = traced.get("engine0", {}), traced.get("engine1", {})
    num, den = args["num"], args["den"]
    if any(k not in c for k in (num, den) for c in (c0, c1)):
        return None
    grew = c1[den] - c0[den]
    if grew <= 0:
        return None
    return 100.0 * (c1[num] - c0[num]) / grew
