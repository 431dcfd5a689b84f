"""What handing the result to the caller costs the host: the
``collect.to_arrow`` spans (exec/base.py ``TpuExec.execute_collect``, one a
result batch: the device-to-host reads of its columns and their Arrow
arrays) summed a query, mean over the window's queries, in milliseconds.
None without such spans (the served path converts under
``endpoint.encode``)."""

from benchmark.metrics_per_layer._spans import mean_seconds_a_query


def read(ctx):
    secs = mean_seconds_a_query(ctx, "collect.to_arrow")
    return None if secs is None else 1e3 * secs
