"""Share of the cached batches handed to the window's queries that were not
in HBM when asked for: ``CachedScan.read`` spans (one a cached batch a query,
under the query's root; counts ``rows``, ``capacity``, ``bytes``, ``tier``)
whose ``tier`` is ``host`` or ``disk`` (the batch had been demoted and came
back through an upload at site ``cache.unspill``) over all of them. 0 in a
sound run: the cached bytes and a query's working set fit the chip. None
without such spans."""

from benchmark.metrics_per_layer._spans import window_queries


def read(ctx):
    queries = window_queries(ctx)
    if not queries:
        return None
    tiers = [s["counts"]["tier"] for q in queries for s in q["spans"]
             if s["name"] == "CachedScan.read" and "tier" in s["counts"]]
    if not tiers:
        return None
    return 100.0 * sum(t != "device" for t in tiers) / len(tiers)
