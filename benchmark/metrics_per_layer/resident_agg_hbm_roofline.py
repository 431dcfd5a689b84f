"""The aggregate's share of the HBM roofline when its input is resident.

Least time = the decoded bytes of the columns the text names
(``column_bytes`` of benchmark/query_bytes.py: from the text and the Parquet
footers, each value the query needs read once, so the same work whatever
implements it; padding, validity, cached columns that are not read and the
group-by's temporaries are what an implementation adds) over the chip's peak
bytes/s. Bandwidth binds: a filter, a few products and a masked group-by of
six groups do no matrix work worth counting. Divided by the device seconds
of the programs ``jit_srt_HashAggregateExec*`` inside the traced span (the
update step, the chain, the merge and the finalize, whichever the plan ran).

Read only where every batch the traced queries aggregated came out of the
cache in HBM (``CachedScan.read`` spans, ``tier`` ``device``): over a scan
the same programs also hold the deferred decodes, and the bytes above do not
describe them. No spans, no trace or no such program: None, never 0.
"""

import re

from benchmark.metrics_per_layer._programs import program_runs
from benchmark.metrics_per_layer._spans import window_queries

AGGREGATE = re.compile(r"^(jit_)?srt_HashAggregateExec")


def read(ctx):
    peaks, span = ctx.get("peaks"), ctx.get("traced_span")
    queries, runs = window_queries(ctx), program_runs(ctx)
    if not peaks or not span or not queries or runs is None:
        return None
    asked, tiers = 0.0, []
    for q, d in zip(queries, sorted(ctx["done"], key=lambda d: d["end"])):
        inside = min(d["end"], span[1]) - max(d["start"], span[0])
        if inside <= 0 or d["end"] <= d["start"]:
            continue
        asked += (ctx["queries"][d["query"]]["column_bytes"]
                  * inside / (d["end"] - d["start"]))
        tiers += [s["counts"].get("tier") for s in q["spans"]
                  if s["name"] == "CachedScan.read"]
    secs = sum(s for name, s in runs if AGGREGATE.match(name))
    if not tiers or any(t != "device" for t in tiers):
        return None
    if secs <= 0 or asked <= 0:
        return None
    return 100.0 * (asked / peaks["hbm_bytes_per_s"]) / secs
