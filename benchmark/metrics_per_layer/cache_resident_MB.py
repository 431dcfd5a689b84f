"""Bytes the plan cache holds through the window: ``bytes`` of the program's
``cache.materialize`` spans (one a ``CacheNode``, when a query first needs
it: the device bytes of the batches it kept, values, validity and padding to
capacity; it also counts ``rows``, ``partitions``, ``batches``, ``capacity``,
``columns``, ``tier``), summed over the caches filled before the window
closed. The cell's cache is filled in set-up and goes with the session, so
it is alive all through the window. None where the program records no such
span (a commit before it had one)."""

from benchmark.metrics_per_layer import _spans


def read(ctx):
    queries, spans = _spans.window_queries(ctx), _spans._recorded()
    if not queries or not spans:
        return None
    closes = max(q["root"]["t1"] for q in queries)
    held = [s["counts"]["bytes"] for s in spans
            if s["name"] == "cache.materialize" and "bytes" in s["counts"]
            and s["t1"] <= closes]
    if not held:
        return None
    return sum(held) / 1e6
