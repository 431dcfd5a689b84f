"""Host-to-device bytes of the window (movement ledger, edge h2d) over the
queries it completed."""

from benchmark.metrics_per_layer._counters import h2d_delta


def read(ctx):
    total = sum(h2d_delta(ctx).values())
    if not ctx["done"] or total <= 0:
        return None
    return total / len(ctx["done"]) / 1e6
