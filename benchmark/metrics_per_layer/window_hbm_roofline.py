"""The window's share of the HBM roofline: every group read once and written
once with its rank (``rollup_bytes.step_bytes``: ``window``) over the chip's
peak bytes/s, over the device seconds of ``jit_srt_WindowExec*`` inside the
traced span."""

from benchmark.metrics_per_layer import _rollup


def read(ctx):
    return _rollup.roofline_pct(ctx, _rollup.WINDOW, "window")
