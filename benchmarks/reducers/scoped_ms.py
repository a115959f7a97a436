"""Device milliseconds per execution of ONE program, of the operations
that ran inside its executions and belong to ``scopes`` — or, with
``"select": "xla_made"``, that are neither a Pallas launch nor a
collective, whatever their scope.

A trace's event names carry no scope: the instruction's text without
its metadata. The program keeps a registry of what it compiled
(``paddle_tpu.observability.programs``: the owner of each jitted program
notes it at its first dispatch under a profiler session, so a program
the traced window never ran is not there, and neither are its
operations) and gives, for each, {instruction name: the
``jax.named_scope`` that issued it}. One process runs the program
and these readers, so the registry is imported here, not handed over in
``sources`` (the trainer's driver hands the readers nothing of the
``Trainer``). An execution of the trace is joined to the noted program
whose instructions hold every operation seen inside it, name and
result type (the number in ``jit_chunk(<number>)`` is no attribute of
the executable): two buckets' chunk programs, both ``jit_chunk``, whose
``fusion.1`` lie in different scopes, are kept apart. The operations
are cut to the program's executions as ``scope_roofline.matching_seconds``
cuts them: by their start, with the trace's own self times (a ``while``
spans its body), so an operation outside every execution is left out.

``scopes``: names of ``PROGRAM_SCOPES`` as shell patterns
(``optimizer/*``), matched against the innermost such name of an
operation's scope. None where the program has no registry (a commit
before it), noted nothing, the trace has no ``XLA Modules`` line (a
rehearsal on the CPU), or no execution could be joined.

The first reader of a traced run also prints, once a program of the
configuration's ``program.programs`` that ran (a bucket's chunk program
is one), an earlier line ``{"program_scopes": {"program", "module",
"noted", "executions", "execution_ms", "ms": [[scope, ms an execution],
...], "top": [[instruction, scope, ms], ...], "top_unnamed": [...]}}``:
the table ``PERF.md`` §5 is written from."""
import collections
import fnmatch
import re

from benchmarks.harness import say
from benchmarks.reducers.exposed_collective_pct import is_collective
from benchmarks.trace import op_head

LAUNCH = re.compile(r'\scustom-call\(.*custom_call_target="tpu_custom_call"')
UNNAMED = "(unnamed)"
_JOINS = {}        # (id(trace), program, device) -> (trace, Join or None)


def registry():
    """The program's registry of compiled programs, or None."""
    try:
        from paddle_tpu.observability import programs
    except ImportError:          # a commit of the program without it
        return None
    return programs


def is_launch(name):
    return bool(LAUNCH.search(name.partition(" = ")[2]))


#: one operation inside a joined execution: its event name, start and
#: self time (ns), its scope (or None), the innermost PROGRAM_SCOPES name
#: in it (or None), the key of the noted program it ran in
Row = collections.namedtuple("Row", "name start ns scope named noted")


class Join:
    """The executions of one of the configuration's programs, joined to
    what the registry noted: ``rows`` [Row] of every operation inside a
    joined execution; ``noted`` {a noted program's key: (its module's
    name, its executions, their summed seconds)}."""

    def __init__(self, rows, noted):
        self.rows, self.noted = rows, noted
        self.executions = sum(n for _, n, _ in noted.values())

    def seconds(self, keep):
        return sum(r.ns for r in self.rows if keep(r)) / 1e9

    def tables(self, top=12):
        """One table a noted program (a bucket's chunk program is one)."""
        for key, (module, n, run_s) in sorted(self.noted.items()):
            by_scope, by_op = {}, {}
            for r in self.rows:
                if r.noted != key:
                    continue
                k = r.named or r.scope or UNNAMED
                by_scope[k] = by_scope.get(k, 0.0) + r.ns / 1e9
                head = op_head(r.name)
                by_op[head, k] = by_op.get((head, k), 0.0) + r.ns / 1e9
            ms = sorted(([k, 1e3 * v / n] for k, v in by_scope.items()),
                        key=lambda kv: -kv[1])
            ops = sorted(([h, k, 1e3 * v / n]
                          for (h, k), v in by_op.items()),
                         key=lambda r: -r[2])
            yield {"module": module, "noted": key, "executions": n,
                   "execution_ms": 1e3 * run_s / n, "ms": ms,
                   "top": ops[:top],
                   "top_unnamed": [[h, v] for h, k, v in ops
                                   if k == UNNAMED][:top]}


def _join(trace, reg, pattern, device):
    rx = re.compile(pattern)
    runs = sorted((s, s + d, name) for s, d, name
                  in trace.modules.get(device, ()) if rx.search(name))
    if not runs:
        return None
    inside = {}                 # execution's name -> its operations
    j = 0
    for name, start, dur, _ in sorted(trace.self_times(device),
                                      key=lambda e: e[1]):
        while j < len(runs) and runs[j][1] <= start:
            j += 1
        if j == len(runs):
            break
        if start >= runs[j][0]:
            inside.setdefault(runs[j][2], []).append((name, start, dur))
    rows, noted = [], {}
    for module, ops in inside.items():
        prog = reg.REGISTRY.find(module, {op[0] for op in ops})
        if prog is None:
            continue
        mine = [e - s for s, e, name in runs if name == module]
        _, n, run_s = noted.get(prog.key, (None, 0, 0.0))
        noted[prog.key] = (prog.name, n + len(mine),
                           run_s + sum(mine) / 1e9)
        for name, start, dur in ops:
            scope = prog.scope(name)
            rows.append(Row(name, start, dur, scope, reg.resolve(scope),
                            prog.key))
    return Join(rows, noted) if noted else None


def joined(sources, program, device=0):
    """The ``Join`` of ``program`` (a key of the configuration's
    ``program.programs``) in this run's trace, or None; made once."""
    trace, patterns = sources.get("trace"), sources.get("programs", {})
    reg = registry()
    if (trace is None or reg is None or program not in patterns
            or not trace.modules.get(device)
            or not reg.REGISTRY.scopes()):
        return None
    if (id(trace), program, device) not in _JOINS:
        _JOINS.clear()                  # one trace a run: every program
        for name, pattern in patterns.items():    # of it, and its table
            found = _join(trace, reg, pattern, device)
            _JOINS[id(trace), name, device] = (trace, found)
            for table in (found.tables() if found is not None else ()):
                say(program_scopes={"program": name, **table})
    return _JOINS[id(trace), program, device][1]


def selector(args):
    """rows -> bool, from a metric's ``args``."""
    if args.get("select") == "xla_made":
        return lambda r: not (is_launch(r.name) or is_collective(r.name))
    pats = args["scopes"]
    return lambda r: r.named is not None and any(
        fnmatch.fnmatchcase(r.named, p) for p in pats)


def read(sources, args):
    found = joined(sources, args["program"])
    if found is None:
        return None
    return 1e3 * found.seconds(selector(args)) / found.executions
