"""Device seconds a query spends in the exchange's own programs
(``jit_srt_MeshExchange*``: the SPMD step and the cut of each partition out
of its chip's shard) inside the traced span: the mean over the cell's chips,
over the queries the span holds. The SPMD step runs on every chip at once,
so the mean is how long the stage holds a chip."""

from benchmark.metrics_per_layer import _mesh


def read(ctx):
    summed = _mesh.device_seconds_a_query(ctx, _mesh.EXCHANGE)
    if summed is None:
        return None
    return summed / int(ctx["cell"]["chips"])
