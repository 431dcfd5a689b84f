"""Text to an admitted plan, mean a query of the window: the program's span
``sql.parse`` (TpuSession.sql: text to logical plan; under
``endpoint.request`` when served, else on the caller's thread just before
the query) plus ``query.plan`` (overrides, stage split, footprint estimate,
up to the scheduler's door, inside collect()). The first front-end reading
the served cell has: the harness sees no such span through the endpoint."""

from benchmark.metrics_per_layer._spans import seconds, window_queries


def read(ctx):
    queries = window_queries(ctx)
    if not queries:
        return None
    if not any(seconds(q["spans"], "query.plan") for q in queries):
        return None
    total = sum(seconds(q["spans"], "query.plan")
                + (seconds([q["parse"]], "sql.parse") if q["parse"] else 0.0)
                for q in queries)
    return total / len(queries) * 1e3
