"""Host seconds a query spends inside ``FileScan.devdecode`` (one span a
row group, io/filescan.py), summed over the scan's threads, mean a query of
the window: the range that held 92 % of Q1's idle time under one name."""

from benchmark.metrics_per_layer._spans import mean_seconds_a_query


def read(ctx):
    return mean_seconds_a_query(ctx, "FileScan.devdecode")
