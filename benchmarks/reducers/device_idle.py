"""Share of the traced window in which no operation ran on the device
(union of the device's operation intervals, mean over the chips)."""


def read(sources, args):
    trace = sources.get("trace")
    if trace is None:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s())
