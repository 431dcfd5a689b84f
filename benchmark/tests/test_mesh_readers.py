"""The readers of the mesh exchange (PR 29), each on a hand-made ``ctx`` and
span list whose answers are known by hand."""

import pytest

from benchmark import mesh_bytes, run
from benchmark.metrics_per_layer import (
    _spans, mesh_exchange_device_s_per_query, mesh_exchange_hbm_roofline,
    mesh_host_hop_MB_per_query, mesh_join_agg_chip_s_per_query)

S = 1_000_000_000
T0 = 7000 * S
READERS = (mesh_exchange_device_s_per_query, mesh_exchange_hbm_roofline,
           mesh_host_hop_MB_per_query, mesh_join_agg_chip_s_per_query)
PEAKS = {"hbm_bytes_per_s": 1000.0}


def span(name, sid, parent, t0, t1, **counts):
    return {"name": name, "id": sid, "parent": parent, "trace": None,
            "thread": "client-0", "t0": T0 + int(t0 * S),
            "t1": T0 + int(t1 * S), "counts": counts}


def exchange(sid, parent, t, rows, row_bytes, d2h, h2d):
    return [
        span("MeshExchange.map", sid, parent, t, t + 0.1, rows=rows,
             d2h_bytes=d2h, partitions=1),
        span("MeshExchange.ingest", sid + 1, parent, t + 0.1, t + 0.2,
             rows=rows, h2d_bytes=h2d, capacity=64),
        span("MeshExchange.collective", sid + 2, parent, t + 0.2, t + 0.3,
             rows=rows, capacity=64, columns=2, row_bytes=row_bytes,
             devices=4, operand_bytes=16 * 64 * row_bytes,
             partitioner="hash"),
        span("sync.count", sid + 3, parent, t + 0.3, t + 0.4, rows=rows,
             capacity=1024)]


def mesh_spans():
    """A warm-up query, then two queries of the window (10 s behind)."""
    return ([span("query", 1, None, 0.0, 9.0)]
            + exchange(2, 1, 1.0, rows=999, row_bytes=99, d2h=9e9, h2d=9e9)
            + [span("query", 10, None, 20.0, 24.0)]
            + exchange(11, 10, 20.5, rows=100, row_bytes=10, d2h=3e6,
                       h2d=1e6)
            + exchange(21, 10, 21.5, rows=50, row_bytes=20, d2h=2e6,
                       h2d=2e6)
            + [span("query", 30, None, 24.0, 28.0)]
            + exchange(31, 30, 24.5, rows=200, row_bytes=10, d2h=5e6,
                       h2d=3e6))


def mesh_ctx(monkeypatch, runs, spans=None, **over):
    monkeypatch.setattr(_spans, "_recorded",
                        lambda: mesh_spans() if spans is None else spans)
    ctx = {"cell": {"chips": 4}, "peaks": PEAKS, "_program_runs": runs,
           "traced_span": (10.0, 14.0),
           "done": [{"client": 0, "query": "q3", "start": 10.0, "end": 14.0},
                    {"client": 0, "query": "q3", "start": 14.0,
                     "end": 18.0}]}
    ctx.update(over)
    return ctx


RUNS = ([("jit_srt_MeshExchange_hash", 0.5)] * 4        # one a chip
        + [("jit_srt_MeshExchange_range", 0.25)] * 4
        + [("jit_srt_MeshExchange_slice", 0.05)] * 4
        + [("jit_srt_HashJoin_probe", 1.0), ("jit_srt_HashJoin_probe", 1.5),
           ("jit_srt_HashJoin_emit", 0.5),
           ("jit_srt_HashAggregateExec_finalize", 0.25),
           ("jit_srt_SortExec", 0.75), ("jit_srt_FilterExec", 9.0),
           ("jit_shard_step", 9.0), ("jit_gather", 9.0)])


def test_the_bytes_by_hand():
    spans = mesh_spans()
    # the warm-up's and both queries': 2 x rows x row_bytes each
    assert mesh_bytes.least_exchange_bytes(spans) == 2 * (
        999 * 99 + 100 * 10 + 50 * 20 + 200 * 10)
    query_a = [s for s in spans if s["parent"] == 10]
    assert mesh_bytes.host_hop_bytes(query_a) == 3e6 + 1e6 + 2e6 + 2e6
    assert mesh_bytes.least_exchange_bytes([]) == 0
    # a span from before it had the counts adds nothing and does not raise
    assert mesh_bytes.least_exchange_bytes(
        [span("MeshExchange.collective", 1, None, 0, 1)]) == 0


def test_mesh_readers_by_hand(monkeypatch):
    ctx = mesh_ctx(monkeypatch, RUNS)
    # one query in the traced span; exchange programs 4 x 0.8 s over 4 chips
    assert mesh_exchange_device_s_per_query.read(ctx) == pytest.approx(0.8)
    # join 3.0 + aggregate 0.25 + sort 0.75, summed over the chips
    assert mesh_join_agg_chip_s_per_query.read(ctx) == pytest.approx(4.0)
    # query A: (4 + 4) MB, query B: 8 MB
    assert mesh_host_hop_MB_per_query.read(ctx) == pytest.approx(8.0)
    # query A alone lies in the span: 2 x (1000 + 1000) bytes over
    # 4 x 1000 bytes/s = 1 s least, over 0.8 s a chip
    assert mesh_exchange_hbm_roofline.read(ctx) == pytest.approx(125.0)


def test_half_a_query_in_the_span(monkeypatch):
    ctx = mesh_ctx(monkeypatch, RUNS, traced_span=(12.0, 14.0))
    # half of query A: the seconds are those of half a query, the bytes too
    assert mesh_exchange_device_s_per_query.read(ctx) == pytest.approx(1.6)
    assert mesh_exchange_hbm_roofline.read(ctx) == pytest.approx(
        100 * (4000 / 4000) / 1.6)


@pytest.mark.parametrize("why", ["no_trace", "no_span_buffer", "parent",
                                 "no_exchange_program", "no_traced_span"])
def test_no_reading_is_not_zero(monkeypatch, why):
    """A commit without the spans or the program names (the parent), or a run
    without a trace: every reader returns None and none raises."""
    runs, spans, over = RUNS, None, {}
    if why == "no_trace":
        runs = None
    elif why == "no_span_buffer":
        spans = []
    elif why == "parent":       # jit_shard_step, no MeshExchange span
        runs = [r for r in RUNS if "MeshExchange" not in r[0]]
        spans = [s for s in mesh_spans()
                 if not s["name"].startswith("MeshExchange")]
    elif why == "no_exchange_program":
        runs = [r for r in RUNS if "MeshExchange" not in r[0]]
    elif why == "no_traced_span":
        over = {"traced_span": None}
    ctx = mesh_ctx(monkeypatch, runs, spans, **over)
    if why == "no_span_buffer":
        monkeypatch.setattr(_spans, "_recorded", lambda: None)
    silent = {
        "no_trace": (mesh_exchange_device_s_per_query,
                     mesh_exchange_hbm_roofline,
                     mesh_join_agg_chip_s_per_query),
        "no_span_buffer": (mesh_exchange_hbm_roofline,
                           mesh_host_hop_MB_per_query),
        "parent": (mesh_exchange_device_s_per_query,
                   mesh_exchange_hbm_roofline, mesh_host_hop_MB_per_query),
        "no_exchange_program": (mesh_exchange_device_s_per_query,
                                mesh_exchange_hbm_roofline),
        "no_traced_span": (mesh_exchange_device_s_per_query,
                           mesh_exchange_hbm_roofline,
                           mesh_join_agg_chip_s_per_query)}[why]
    for reader in READERS:
        value = reader.read(ctx)
        if reader in silent:
            assert value is None, reader.__name__
        else:
            assert value is not None and value > 0, reader.__name__


def test_the_four_entries_and_the_cell():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    new = {m["name"]: m for m in bench["per_layer"][-4:]}
    assert list(new) == [
        "mesh_exchange_device_s_per_query", "mesh_exchange_hbm_roofline",
        "mesh_host_hop_MB_per_query", "mesh_join_agg_chip_s_per_query"]
    for m in new.values():
        assert m["workloads"] == ["tpch_sf1_mesh4.q3"]
        assert m["moves"] == "input_rows_per_s"
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == ("tpch_sf1_mesh4.q3", "tpch_sf1_mesh4", "q3", 4)
    assert len(cell["why"]) <= 200
    _cell, _entry, config, traffic, _ = run.find_cell("tpch_sf1_mesh4.q3")
    assert config["session_conf"]["spark.rapids.tpu.mesh.enabled"] is True
    assert config["session_conf"]["spark.rapids.tpu.mesh.devices"] == 4
    # laid over the shared file: its session conf is still there
    assert config["session_conf"]["spark.rapids.tpu.pipeline.enabled"]
    assert config["files_per_partition"] == 1 and config["chips"] == 4
    assert traffic["clients"] == 1
