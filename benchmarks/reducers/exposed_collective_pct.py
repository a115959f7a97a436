"""Share of the traced window in which a collective ran on device 0
and no other operation did, in percent. None where the trace holds no
collective at all.

Its own walk, not ``Trace.exposed_collective_share``: that one knows a
collective by the instruction's NAME, and the psum of a ``shard_map``
is written ``%psum.14 = bf16[32,4096]{...} all-reduce(%fusion.92), ...``
(looked at in the program compiled for a v5e:2x2, PR 24). So an
operation counts as a collective by its name or by its HLO opcode."""
import re

from benchmarks.trace import COLLECTIVE, merged, op_key

OPCODE = re.compile(r"\s(all-reduce|all-gather|reduce-scatter|"
                    r"collective-permute|all-to-all)(-start|-done)?\(")


def is_collective(name):
    return bool(COLLECTIVE.match(op_key(name))
                or OPCODE.search(name.partition(" = ")[2]))


def read(sources, args):
    trace = sources.get("trace")
    if trace is None:
        return None
    coll = merged((s, d) for s, d, name in trace.ops.get(0, ())
                  if is_collective(name))
    if not coll:
        return None
    # what else ran: the leaves (a ``while`` spans its body's operations)
    comp = merged((s, d) for name, s, d, leaf in trace.self_times(0)
                  if leaf and not is_collective(name))
    exposed, j = 0.0, 0
    for s, e in coll:                  # both lists ascend
        cur = s
        while j < len(comp) and comp[j][1] <= cur:
            j += 1
        k = j
        while k < len(comp) and comp[k][0] < e:
            exposed += max(0.0, comp[k][0] - cur)
            cur = max(cur, comp[k][1])
            k += 1
        exposed += max(0.0, e - cur)
    lo, hi = trace.bounds_ns()
    return 100.0 * exposed / (hi - lo)
