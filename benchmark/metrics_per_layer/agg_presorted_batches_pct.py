"""The share of the window's aggregate batches that were grouped over their
input order, with no sort: ``HashAggregate.agg`` and ``HashAggregate.chain``
spans that count ``presorted`` 1, over those of the aggregates that group by
one key on the sort path (``keys`` 1, ``path`` ``sort``), the only ones whose
input the key-stats probe can prove sorted. None where the program counts no
``presorted`` or no such span is there."""

from benchmark.metrics_per_layer._spans import window_queries

NAMES = ("HashAggregate.agg", "HashAggregate.chain")


def read(ctx):
    queries = window_queries(ctx)
    if not queries:
        return None
    counted = presorted = 0
    for q in queries:
        for s in q["spans"]:
            c = s["counts"]
            if (s["name"] in NAMES and "presorted" in c
                    and c.get("keys") == 1 and c.get("path") == "sort"):
                counted += 1
                presorted += int(c["presorted"] == 1)
    if not counted:
        return None
    return 100.0 * presorted / counted
