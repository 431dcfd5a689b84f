"""How often the fused join chain runs for one stream batch: runs of the
program ``jit_srt_HashJoinChain_probe`` inside the traced span over the
``HashJoinChain.probe`` spans (one a stream batch) of the queries that lie
in it. 1.0 where every batch's output bucket was predicted, or cut from a
larger one; each batch above that is a chain run made twice. None where the
trace holds no chain run or the queries no such span."""

import re

from benchmark.metrics_per_layer._programs import program_runs
from benchmark.metrics_per_layer._spans import window_queries

PROBE = re.compile(r"^(jit_)?srt_HashJoinChain_probe$")


def read(ctx):
    runs, queries = program_runs(ctx), window_queries(ctx)
    span = ctx.get("traced_span")
    if runs is None or not queries or not span:
        return None
    batches = 0
    for q, d in zip(queries, sorted(ctx["done"], key=lambda d: d["end"])):
        if min(d["end"], span[1]) > max(d["start"], span[0]):
            batches += sum(s["name"] == "HashJoinChain.probe"
                           for s in q["spans"])
    chain_runs = sum(1 for name, _ in runs if PROBE.match(name))
    if not chain_runs or not batches:
        return None
    return chain_runs / batches
