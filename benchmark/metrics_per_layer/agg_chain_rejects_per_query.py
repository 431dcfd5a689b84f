"""Steps of the group-by chain that ran and were thrown away, a query:
``HashAggregate.chain`` spans that count ``accepted`` 0, over the window's
queries. A rejected step's batch is aggregated a second time, unchained, and
merged on its own; it should read 0. None where the program counts no
``accepted`` (or runs no chain)."""

from benchmark.metrics_per_layer._spans import window_queries


def read(ctx):
    queries = window_queries(ctx)
    if not queries:
        return None
    steps = [s["counts"]["accepted"] for q in queries for s in q["spans"]
             if s["name"] == "HashAggregate.chain"
             and "accepted" in s["counts"]]
    if not steps:
        return None
    return sum(1 for a in steps if not a) / len(queries)
