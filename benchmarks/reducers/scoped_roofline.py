"""A computation's share of its roofline inside ONE program, the
computation being a ``jax.named_scope`` of the program
(``PROGRAM_SCOPES``) and not an instruction's text or an array's shape:
whatever launch or fusion implements the scope, the share follows it.

The operations under ``scopes`` are found as ``scoped_ms`` finds them
(the program's registry of compiled programs, joined to the trace's
executions). The arithmetic is ``scope_roofline``'s, untouched: it is
handed the trace cut to those operations and a ``match`` that takes
them all, with the same ``program``, ``cost``, ``per`` and ``counter``;
through ``roofline_counted`` where the cost model wants the program's
counters. None where ``scoped_ms`` reads None or no operation lies
under the scope."""
from benchmarks.reducers import scope_roofline, scoped_ms


class Cut:
    """What ``scope_roofline`` reads of a trace, holding only the
    operations already chosen."""

    def __init__(self, trace, rows):
        self.modules, self._rows = trace.modules, rows

    def self_times(self, device=0):
        return self._rows


def read(sources, args):
    found = scoped_ms.joined(sources, args["program"])
    if found is None:
        return None
    keep = scoped_ms.selector(args)
    rows = [(r.name, r.start, r.ns, True) for r in found.rows if keep(r)]
    if not rows:
        return None
    inner = {k: v for k, v in args.items() if k not in ("scopes", "select")}
    return scope_roofline.read({**sources, "trace": Cut(sources["trace"],
                                                        rows)},
                               {**inner, "match": [""]})
