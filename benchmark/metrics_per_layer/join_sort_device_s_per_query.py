"""Device seconds a query spends in the join and sort spine: programs named
``jit_srt_HashJoin*``, ``jit_srt_BroadcastHashJoin*`` and ``jit_srt_SortExec*``
inside the traced span, over the queries the span holds. Programs run one at
a time on a chip, so their seconds add up."""

import re

from benchmark.metrics_per_layer._programs import (program_runs,
                                                   queries_in_span)

SPINE = re.compile(r"^(jit_)?srt_(HashJoin|BroadcastHashJoin|SortExec)")


def read(ctx):
    runs, queries = program_runs(ctx), queries_in_span(ctx)
    if runs is None or queries <= 0:
        return None
    spine = [secs for name, secs in runs if SPINE.match(name)]
    if not spine:
        return None
    return sum(spine) / queries
