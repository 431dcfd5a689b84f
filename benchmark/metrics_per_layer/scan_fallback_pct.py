"""Share of the window's host-to-device bytes that the fallback reader
carried (movement ledger, edge h2d, site scan.fallback)."""

from benchmark.metrics_per_layer._counters import h2d_delta


def read(ctx):
    d = h2d_delta(ctx)
    total = sum(d.values())
    if total <= 0:
        return None
    return 100.0 * d.get("scan.fallback", 0) / total
