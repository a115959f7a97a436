"""A percentile of one of the driver's per-request samples (host clock:
the harness's own stamps and the engine's request records)."""
from benchmarks.harness import percentile


def read(sources, args):
    values = (sources.get("samples") or {}).get(args["sample"])
    if not values:
        return None
    return percentile(values, args["q"])
