"""Python garbage collection inside the window's queries: the ``gc`` spans
(runtime/tracing.py, one a collection, on the thread that ran it, under the
span open there) that belong to a query, summed a query, mean over the
window's queries, in milliseconds. 0 where the program records collections
(the harness's own ``gc.collect()`` before the window is one) and none fell
inside a query; None where it records none."""

from benchmark.metrics_per_layer._spans import (_recorded, seconds,
                                               window_queries)


def read(ctx):
    queries = window_queries(ctx)
    if not queries:
        return None
    total = sum(seconds(q["spans"], "gc") for q in queries)
    if not total and not any(s["name"] == "gc" for s in _recorded() or ()):
        return None
    return 1e3 * total / len(queries)
