"""Mean length of one of the harness's own host spans (host clock)."""


def read(sources, args):
    rows = (sources.get("spans") or {}).get(args["span"])
    if not rows:
        return None
    return 1e3 * sum(e - s for s, e in rows) / len(rows)
