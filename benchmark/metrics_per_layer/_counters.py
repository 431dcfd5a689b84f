"""What the readers of counters share: deltas over the window."""


def h2d_delta(ctx) -> dict:
    before, after = ctx["before"]["h2d_by_site"], ctx["after"]["h2d_by_site"]
    return {k: v - before.get(k, 0) for k, v in after.items()}


def histogram_delta(ctx, family: str):
    """(sum, count) that a histogram family of the endpoint's STATS text
    gained over the window, or None where there is no such text."""
    def totals(text):
        got = {}
        for line in text.splitlines():
            name, _, value = line.strip().rpartition(" ")
            if name in (family + "_sum", family + "_count"):
                got[name] = float(value)
        return got.get(family + "_sum"), got.get(family + "_count")
    if not ctx["stats_before"] or not ctx["stats_after"]:
        return None
    s0, c0 = totals(ctx["stats_before"])
    s1, c1 = totals(ctx["stats_after"])
    if s1 is None or c1 is None:
        return None
    return s1 - (s0 or 0.0), c1 - (c0 or 0.0)
