"""One number the harness itself took and handed over under a key of
``sources`` (host clock), such as ``import_s``."""


def read(sources, args):
    value = sources.get(args["key"])
    return None if value is None else float(value)
