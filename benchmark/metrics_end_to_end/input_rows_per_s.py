"""All the work over all the time: the rows of the base tables that each
completed query's text names (Parquet footers of the generated data, never
an engine counter), summed over the window, over the window's real length."""


def read(ctx):
    if not ctx["done"] or ctx["window_s"] <= 0:
        return None
    rows = sum(ctx["queries"][d["query"]]["input_rows"] for d in ctx["done"])
    return rows / ctx["window_s"]
