"""Whole-query share of the HBM roofline, from the profiler trace.

Least time the chip could take = decoded bytes of the columns that the text
names (benchmark/query_bytes.py: from the text and the Parquet footers, so
the same work whatever implements it) over the chip's peak bytes/s. Bandwidth
binds: scan, filter, join and aggregate do no matrix work worth counting.
Divided by the seconds in which an operation ran on the device inside the
traced span. A query that lies partly inside the span counts for that part.
"""


def read(ctx):
    trace, span, peaks = ctx["trace"], ctx["traced_span"], ctx["peaks"]
    if not trace or not span or not peaks or trace["busy_s"] <= 0:
        return None
    asked = 0.0
    for d in ctx["done"]:
        inside = min(d["end"], span[1]) - max(d["start"], span[0])
        if inside > 0 and d["end"] > d["start"]:
            asked += (ctx["queries"][d["query"]]["column_bytes"]
                      * inside / (d["end"] - d["start"]))
    if asked <= 0:
        return None
    least_s = asked / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
