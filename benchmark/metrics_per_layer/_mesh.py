"""What the readers of the mesh exchange share: the device seconds of the
programs whose names match, inside the traced span, a query."""

import re

from benchmark.metrics_per_layer._programs import (program_runs,
                                                   queries_in_span)

EXCHANGE = re.compile(r"^(jit_)?srt_MeshExchange")


def device_seconds_a_query(ctx, pattern):
    """Seconds of the matching programs SUMMED over the cell's chips, over
    the queries the traced span holds; None without a trace or a match."""
    runs, queries = program_runs(ctx), queries_in_span(ctx)
    if runs is None or queries <= 0:
        return None
    secs = [s for name, s in runs if pattern.match(name)]
    if not secs:
        return None
    return sum(secs) / queries
