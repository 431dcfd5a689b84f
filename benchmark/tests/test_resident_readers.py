"""The readers of the plan cache (PR 33), each on a hand-made ``ctx`` and span
list whose answers are known by hand; the query module's text against
``queries/q1.sql``; and the cell's rehearsal on the CPU."""

import os
import re

import pytest

from benchmark import query_bytes, run
from benchmark.metrics_per_layer import (
    _spans, cache_resident_MB, cached_scan_host_ms_per_query,
    cached_scan_unspill_pct, resident_agg_hbm_roofline)
from benchmark.queries import q1_resident

S = 1_000_000_000
T0 = 7000 * S
READERS = (cache_resident_MB, cached_scan_unspill_pct,
           cached_scan_host_ms_per_query, resident_agg_hbm_roofline)
PEAKS = {"hbm_bytes_per_s": 1000.0}
CELL = "tpch_sf1_resident.q1"


def span(name, sid, parent, t0, t1, **counts):
    return {"name": name, "id": sid, "parent": parent, "trace": None,
            "thread": "client-0", "t0": T0 + int(t0 * S),
            "t1": T0 + int(t1 * S), "counts": counts}


def read_span(sid, parent, t, ms, rows, tier="device"):
    return span("CachedScan.read", sid, parent, t, t + ms / 1e3, rows=rows,
                capacity=1024, bytes=69 * 1024, tier=tier)


def resident_spans(tiers=("device", "device", "device", "device")):
    """The warm-up query, which fills the cache inside its planning, then
    two queries of the window (10 s behind) of two cached batches each."""
    return [span("query", 1, None, 0.0, 9.0),
            span("query.plan", 2, 1, 0.0, 5.0),
            span("cache.materialize", 3, 2, 0.1, 4.9, tier="device",
                 rows=1500, partitions=1, batches=2, capacity=2048,
                 columns=9, bytes=150_000_000),
            read_span(4, 1, 6.0, 1.0, 1000), read_span(5, 1, 7.0, 1.0, 500),
            span("query", 10, None, 20.0, 24.0),
            read_span(11, 10, 20.5, 2.0, 1000, tiers[0]),
            read_span(12, 10, 21.5, 4.0, 500, tiers[1]),
            span("query", 30, None, 24.0, 28.0),
            read_span(31, 30, 24.5, 1.0, 1000, tiers[2]),
            read_span(32, 30, 25.5, 1.0, 500, tiers[3])]


def ctx_of(monkeypatch, runs, spans=None, **over):
    monkeypatch.setattr(_spans, "_recorded",
                        lambda: resident_spans() if spans is None else spans)
    ctx = {"cell": {"chips": 1}, "peaks": PEAKS, "_program_runs": runs,
           "traced_span": (10.0, 14.0),
           "queries": {"q1_resident": {"column_bytes": 200}},
           "done": [{"client": 0, "query": "q1_resident", "start": 10.0,
                     "end": 14.0},
                    {"client": 0, "query": "q1_resident", "start": 14.0,
                     "end": 18.0}]}
    ctx.update(over)
    return ctx


RUNS = [("jit_srt_HashAggregateExec", 0.1),
        ("jit_srt_HashAggregateExec_chain", 0.25),
        ("jit_srt_HashAggregateExec_finalize", 0.05),
        ("jit_srt_SortExec", 9.0), ("jit_gather", 9.0)]
# what the same text runs over a scan (tpch_sf1_batch.q1, or a parent whose
# cache opens no span): the same program names, the decodes beside them
SCAN_RUNS = RUNS + [("jit_srt_ParquetScan_decode_runs", 0.2)]


def test_resident_readers_by_hand(monkeypatch):
    ctx = ctx_of(monkeypatch, RUNS)
    assert cache_resident_MB.read(ctx) == pytest.approx(150.0)
    assert cached_scan_unspill_pct.read(ctx) == 0.0
    # (2 + 4) ms and (1 + 1) ms, mean of the window's two queries
    assert cached_scan_host_ms_per_query.read(ctx) == pytest.approx(4.0)
    # 200 bytes over 1000 bytes/s = 0.2 s least, over 0.4 s of aggregate
    assert resident_agg_hbm_roofline.read(ctx) == pytest.approx(50.0)


def test_half_a_query_in_the_span(monkeypatch):
    ctx = ctx_of(monkeypatch, RUNS, traced_span=(12.0, 14.0))
    # half a query's bytes over the seconds the span holds
    assert resident_agg_hbm_roofline.read(ctx) == pytest.approx(25.0)


def test_a_batch_that_came_back_is_counted_and_silences_the_roofline(
        monkeypatch):
    spans = resident_spans(("host", "device", "disk", "device"))
    ctx = ctx_of(monkeypatch, RUNS, spans)
    assert cached_scan_unspill_pct.read(ctx) == pytest.approx(50.0)
    # the traced query waited for an upload: its aggregate seconds are not
    # those of resident input
    assert resident_agg_hbm_roofline.read(ctx) is None
    # a cache filled after the window closed was not held through it
    late = resident_spans() + [
        span("cache.materialize", 40, None, 30.0, 31.0, tier="device",
             rows=1, partitions=1, batches=1, capacity=8, columns=1,
             bytes=9_000_000)]
    assert cache_resident_MB.read(ctx_of(monkeypatch, RUNS, late)) \
        == pytest.approx(150.0)


@pytest.mark.parametrize("why", ["no_trace", "no_span_buffer", "parent",
                                 "batch_q1", "no_aggregate_program",
                                 "no_traced_span"])
def test_no_reading_is_not_zero(monkeypatch, why):
    """A commit whose cache opens no span (the parent), the same text over a
    scan (``tpch_sf1_batch.q1``), or a run without a trace: the readers
    return None, never 0, and none raises."""
    runs, spans, over = RUNS, None, {}
    if why == "no_trace":
        runs = None
    elif why == "no_span_buffer":
        spans = []
    elif why in ("parent", "batch_q1"):
        runs = SCAN_RUNS
        spans = [s for s in resident_spans()
                 if s["name"] not in ("cache.materialize", "CachedScan.read")]
    elif why == "no_aggregate_program":
        runs = [r for r in RUNS if "HashAggregate" not in r[0]]
    elif why == "no_traced_span":
        over = {"traced_span": None}
    ctx = ctx_of(monkeypatch, runs, spans, **over)
    if why == "no_span_buffer":
        monkeypatch.setattr(_spans, "_recorded", lambda: None)
    silent = {"no_trace": (resident_agg_hbm_roofline,),
              "no_span_buffer": READERS, "parent": READERS,
              "batch_q1": READERS,
              "no_aggregate_program": (resident_agg_hbm_roofline,),
              "no_traced_span": (resident_agg_hbm_roofline,)}[why]
    for reader in READERS:
        value = reader.read(ctx)
        if reader in silent:
            assert value is None, reader.__name__
        else:
            assert value is not None and value >= 0, reader.__name__
    assert cached_scan_unspill_pct.read(ctx) in (None, 0.0)


def test_the_module_holds_q1s_text_and_names_what_q1_names():
    with open(os.path.join(run.HERE, "queries", "q1.sql")) as f:
        q1 = f.read()
    assert q1_resident.TEXT == q1
    module = run.load_query("q1_resident")["text"]
    assert q1 in module
    # the harness counts rows and bytes from the words of the whole file:
    # of the generator's tables and columns it names exactly what q1.sql does
    from benchmark.datagen import tpch
    from benchmark.reference import q1 as ref_q1, q1_resident as ref
    with open(tpch.__file__) as f:
        known = {w for w in query_bytes.words(f.read())
                 if re.fullmatch(r"[lopcsnr]_[a-z]+", w)}
    known |= {"lineitem", "orders", "customer", "supplier", "nation", "region",
              "part", "partsupp"}
    assert {"l_orderkey", "l_suppkey", "o_orderkey"} <= known
    assert query_bytes.words(module) & known == query_bytes.words(q1) & known
    assert query_bytes.words(q1) & known == (
        {"lineitem"} | set(ref_q1.COLUMNS["lineitem"]))
    assert ref.COLUMNS is ref_q1.COLUMNS and ref.reference is ref_q1.reference


def test_the_entries_and_the_cell():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for reader in READERS:
        m = per_layer[reader.__name__.rsplit(".", 1)[-1]]
        assert m["workloads"] == [CELL] and m["moves"] == "input_rows_per_s"
    assert per_layer["resident_agg_hbm_roofline"]["unit"] == "%"
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("tpch_sf1_resident", "q1_resident", 1)
    assert len(cell["why"]) <= 200 and "tpch_sf1_batch.q1" in cell["why"]
    _cell, entry, config, traffic, _ = run.find_cell(CELL)
    assert len(entry["source"]) <= 200
    assert config["session_conf"][
        "spark.rapids.tpu.sql.cache.serializer"] == "device"
    # laid over the shared file: its session conf and data are still there
    assert config["session_conf"]["spark.rapids.tpu.pipeline.enabled"]
    assert config["scale_factor"] == 1 and config["entry"] == "sql"
    assert config["reduced"] == run.load_config(
        "benchmark/configs/tpch_sf1_batch.json")["reduced"]
    assert set(config["reduced_detail"]) == set(config["reduced"])
    assert traffic == dict(traffic, loop="closed", clients=1,
                           queries=["q1_resident"])
    batch = run.load_config("benchmark/configs/tpch_sf1_batch.json")
    assert config["guarantees"][:len(batch["guarantees"])] \
        == batch["guarantees"]
    assert "never an answer" in config["guarantees"][-1]


def test_the_cell_rehearses_on_the_cpu_and_agrees_with_the_reference(
        tmp_path):
    """``rehearse.py --workload tpch_sf1_resident.q1 --trace 1``: the whole
    run at SF 0.01, the cache filled by the set-up's execution, every query
    of the window served from it and equal to the reference; the span
    readers find their spans (the roofline needs a chip's trace)."""
    r = run.run_cell(CELL, 2**31 + 33, 1.0, True, rehearsal=True, scale=0.01,
                     workdir=str(tmp_path))
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 2
    assert r["compared"]["float_gap"][0] < 1e-9
    m = r["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    assert m["cached_scan_unspill_pct"]["value"] == 0.0
    assert m["cache_resident_MB"]["value"] > 0
    assert m["cached_scan_host_ms_per_query"]["value"] > 0
    assert "h2d_MB_per_query" not in m and "scan_fallback_pct" not in m
    assert "resident_agg_hbm_roofline" not in m
