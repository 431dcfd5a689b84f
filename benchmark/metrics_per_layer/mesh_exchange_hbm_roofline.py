"""The exchanges' share of the HBM roofline. Least time = the least bytes the
exchanges of the traced query must move (benchmark/mesh_bytes.py, from the
``MeshExchange.collective`` spans) over the chips' summed peak bytes/s;
divided by the time the exchange's programs held a chip (their device
seconds, mean over the chips). Bandwidth binds: hashing a key and compacting
a column do no matrix work. No reading without the spans or the trace: a
share of a roofline is never 0 for want of data."""

from benchmark import mesh_bytes
from benchmark.metrics_per_layer import _mesh
from benchmark.metrics_per_layer._programs import queries_in_span
from benchmark.metrics_per_layer._spans import window_queries


def read(ctx):
    peaks, span = ctx.get("peaks"), ctx.get("traced_span")
    queries = window_queries(ctx)
    chips = int(ctx["cell"]["chips"])
    summed = _mesh.device_seconds_a_query(ctx, _mesh.EXCHANGE)
    if not peaks or not span or not queries or not summed:
        return None
    # the bytes of the queries inside the traced span, each for the part of
    # it that lies inside (as the device seconds are counted)
    asked = 0.0
    for q, d in zip(queries, sorted(ctx["done"], key=lambda d: d["end"])):
        inside = min(d["end"], span[1]) - max(d["start"], span[0])
        if inside > 0 and d["end"] > d["start"]:
            asked += (mesh_bytes.least_exchange_bytes(q["spans"])
                      * inside / (d["end"] - d["start"]))
    if asked <= 0:
        return None
    least_s = asked / queries_in_span(ctx) / (chips * peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (summed / chips)
