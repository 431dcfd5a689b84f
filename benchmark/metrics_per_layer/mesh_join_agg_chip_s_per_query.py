"""Device seconds a query spends in the join, aggregate and sort programs
(``jit_srt_HashJoin*``, ``jit_srt_HashAggregateExec*``, ``jit_srt_SortExec*``)
inside the traced span, SUMMED over the cell's chips: the chip time the
partitions' work costs. Partitions that run each on its own chip cost about
what one chip would spend on the whole; partitions that XLA left replicated
cost that on every chip, four times as much."""

import re

from benchmark.metrics_per_layer import _mesh

WORK = re.compile(r"^(jit_)?srt_(HashJoin|HashAggregateExec|SortExec)")


def read(ctx):
    return _mesh.device_seconds_a_query(ctx, WORK)
