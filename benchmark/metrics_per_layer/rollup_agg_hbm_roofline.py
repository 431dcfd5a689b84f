"""The ROLLUP aggregate's share of the HBM roofline: the bytes that the nine
grouping sets must read and the groups they must write
(``rollup_bytes.step_bytes``: ``rollup``) over the chip's peak bytes/s, over
the device seconds of ``jit_srt_ExpandExec*`` and
``jit_srt_HashAggregateExec*`` inside the traced span."""

from benchmark.metrics_per_layer import _rollup


def read(ctx):
    return _rollup.roofline_pct(ctx, _rollup.ROLLUP_AGG, "rollup")
