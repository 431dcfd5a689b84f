"""Share of the traced query's wall time in which no operation ran on the
device (benchmark/trace_reduce.py)."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or trace["window_s"] <= 0 or trace["stand_in"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
