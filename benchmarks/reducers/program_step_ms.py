"""Mean device time of one execution of a compiled program, from the
trace's ``XLA Modules`` line; the program's name pattern is in the
configuration's file under ``program.programs``."""


def read(sources, args):
    trace, patterns = sources.get("trace"), sources.get("programs", {})
    if trace is None or args["program"] not in patterns:
        return None
    runs = trace.module_runs(patterns[args["program"]])
    if not runs:
        return None
    return 1e3 * sum(runs) / len(runs)
