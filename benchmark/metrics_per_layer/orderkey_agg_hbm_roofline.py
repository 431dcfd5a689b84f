"""The share of the HBM roofline of Q18's aggregate by ``l_orderkey``: the
bytes that ``q18_bytes.orderkey_bytes`` counts (every lineitem row's key and
quantity read once, every group written once with its sum) over the chip's
peak bytes/s, over the device seconds of ``jit_srt_HashAggregateExec*``
inside the traced span."""

from benchmark.metrics_per_layer import _orderkey


def read(ctx):
    return _orderkey.roofline_pct(ctx)
