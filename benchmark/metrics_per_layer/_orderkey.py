"""What the readers of Q18's aggregate by ``l_orderkey`` share: the device
seconds of the aggregate's programs (``jit_srt_HashAggregateExec*``: the
probe, the update, the chain, the merge and the finalize) inside the traced
span, which also hold the small five-key group-by after the joins, and
their share of the HBM roofline (``benchmark/q18_bytes.py``'s bytes over the
chip's peak bytes/s, over those seconds): grouping a sorted key and summing
does no matrix work worth counting. No trace, no such program or no data:
None, never 0."""

import re

from benchmark import q18_bytes
from benchmark.metrics_per_layer import _rollup
from benchmark.metrics_per_layer._programs import queries_in_span

AGG = re.compile(r"^(jit_)?srt_HashAggregateExec")


def seconds_a_query(ctx):
    return _rollup.seconds_a_query(ctx, AGG)


def roofline_pct(ctx):
    secs, queries = _rollup.device_seconds(ctx, AGG), queries_in_span(ctx)
    peaks = ctx.get("peaks")
    if not secs or queries <= 0 or not peaks:
        return None
    moved = q18_bytes.for_run(ctx)
    if moved is None:
        return None
    least_s = queries * moved["bytes"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / secs
