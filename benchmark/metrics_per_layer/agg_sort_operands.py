"""Operands of the widest group sort: the largest ``sort_operands`` that an
aggregate's span counted (``HashAggregate.group_sort``, or ``.agg`` /
``.chain`` where the count stands there) in the queries that lie in the
traced span. The chip's compiler takes its time by a sort's operands, and
the sort its passes. None where the program counts no such thing."""

from benchmark.metrics_per_layer._spans import window_queries


def read(ctx):
    queries, span = window_queries(ctx), ctx.get("traced_span")
    if not queries or not span:
        return None
    widest = None
    for q, d in zip(queries, sorted(ctx["done"], key=lambda d: d["end"])):
        if min(d["end"], span[1]) <= max(d["start"], span[0]):
            continue
        for s in q["spans"]:
            n = s["counts"].get("sort_operands")
            if s["name"].startswith("HashAggregate.") and n is not None:
                widest = n if widest is None else max(widest, n)
    return widest
