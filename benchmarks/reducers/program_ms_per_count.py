"""Device time of a program's executions in the traced window over
what the program counted there (``counter`` of the engine, by ``per``):
milliseconds a thousand prompt tokens for the prefill programs."""


def read(sources, args):
    trace, patterns = sources.get("trace"), sources.get("programs", {})
    traced = sources.get("traced")
    if trace is None or not traced or args["program"] not in patterns:
        return None
    runs = trace.module_runs(patterns[args["program"]])
    n = traced["engine1"][args["counter"]] - traced["engine0"][args["counter"]]
    if not runs or n <= 0:
        return None
    return 1e3 * sum(runs) / (n / args["per"])
