"""A number the program counts itself, read from its ``metrics()`` at
the window's close: ``path`` walks the nested dict."""


def read(sources, args):
    node = sources.get("engine")
    for key in args["path"]:
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    return float(node) * args.get("scale", 1.0)
