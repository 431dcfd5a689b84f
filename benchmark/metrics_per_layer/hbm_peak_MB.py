"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, read when
the window has closed."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 1e6 if ctx["memory_peak_bytes"] else None
