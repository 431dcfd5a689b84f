"""What the readers of query 67's two steps share: the device seconds of a
step's programs inside the traced span, and a step's share of the HBM
roofline (``benchmark/rollup_bytes.py``'s bytes, from the data alone, over
the chip's peak bytes/s, over those seconds). Bandwidth binds: expanding,
sorting keys, summing and ranking do no matrix work worth counting. No trace,
no such program or no data: None, never 0."""

import re

from benchmark import rollup_bytes
from benchmark.metrics_per_layer._programs import (program_runs,
                                                   queries_in_span)

ROLLUP_AGG = re.compile(r"^(jit_)?srt_(ExpandExec|HashAggregateExec)")
WINDOW = re.compile(r"^(jit_)?srt_WindowExec")


def device_seconds(ctx, pattern):
    """Seconds of the programs that match, inside the traced span, or None
    where the trace holds no such program."""
    runs = program_runs(ctx)
    if runs is None:
        return None
    secs = [s for name, s in runs if pattern.match(name)]
    return sum(secs) if secs else None


def seconds_a_query(ctx, pattern):
    secs, queries = device_seconds(ctx, pattern), queries_in_span(ctx)
    if secs is None or queries <= 0:
        return None
    return secs / queries


def roofline_pct(ctx, pattern, step: str):
    secs, queries = device_seconds(ctx, pattern), queries_in_span(ctx)
    peaks = ctx.get("peaks")
    if not secs or queries <= 0 or not peaks:
        return None
    moved = rollup_bytes.for_run(ctx)
    if moved is None:
        return None
    least_s = queries * moved[step] / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / secs
