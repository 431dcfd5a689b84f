"""Device seconds a query spends in its fused join chains: programs named
``jit_srt_HashJoinChain_*`` (the chain's probe, and the program that lands
its output at its bucket where the program has one) inside the traced span,
over the queries the span holds. ``join_sort_device_s_per_query`` holds the
same seconds among the rest of the spine, in the cells it lists. None where
the trace holds no chain."""

import re

from benchmark.metrics_per_layer import _rollup

CHAIN = re.compile(r"^(jit_)?srt_HashJoinChain_")


def read(ctx):
    return _rollup.seconds_a_query(ctx, CHAIN)
