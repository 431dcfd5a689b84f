"""Live rows over padded capacity where the host holds both: the ``rows``
and ``capacity`` counts of the window's ``sync.*`` spans (a count just
read) and ``FileScan.devdecode`` spans (the footer's row count), summed.
What S6 (padding) is judged by; no device-to-host read is made for it."""

from benchmark.metrics_per_layer._spans import window_queries


def read(ctx):
    queries = window_queries(ctx)
    if not queries:
        return None
    rows = capacity = 0
    for q in queries:
        for s in q["spans"]:
            c = s["counts"]
            if (s["name"].startswith("sync.")
                    or s["name"] == "FileScan.devdecode") \
                    and c.get("capacity") and c.get("rows") is not None:
                rows += c["rows"]
                capacity += c["capacity"]
    if capacity <= 0:
        return None
    return 100.0 * rows / capacity
