"""Mean wait in the scheduler's admission queue over the window, from the
endpoint's STATS text (histogram srt_admission_wait_seconds)."""

from benchmark.metrics_per_layer._counters import histogram_delta


def read(ctx):
    d = histogram_delta(ctx, "srt_admission_wait_seconds")
    if d is None or d[1] <= 0:
        return None
    return d[0] / d[1] * 1e3
