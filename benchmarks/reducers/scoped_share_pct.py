"""Share, in percent, of one program's device time (the operations
inside its executions, ``scoped_ms`` says how they are found) whose
operation resolves to a name of the program's ``PROGRAM_SCOPES`` — or
matches ``scopes``, where the metric gives them. With no ``scopes`` it
is the health of the join itself: a program whose compiled text came
from a cache entry written before a scope was added reads low here.
None where ``scoped_ms`` reads None."""
from benchmarks.reducers import scoped_ms


def read(sources, args):
    found = scoped_ms.joined(sources, args["program"])
    if found is None:
        return None
    total = found.seconds(lambda r: True)
    if not total:
        return None
    keep = (scoped_ms.selector(args) if "scopes" in args
            else (lambda r: r.named is not None))
    return 100.0 * found.seconds(keep) / total
