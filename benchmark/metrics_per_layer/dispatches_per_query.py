"""Program dispatches of the window (runtime/fuse.py's counter, the one
``compile_metrics()`` mirrors per query) over the queries it completed."""


def read(ctx):
    if not ctx["done"]:
        return None
    d = ctx["after"]["fuse"]["dispatches"] - ctx["before"]["fuse"]["dispatches"]
    return d / len(ctx["done"])
