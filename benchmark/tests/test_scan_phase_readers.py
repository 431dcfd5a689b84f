"""The readers of the scan's host phases, the collect and the collections
(``scan.read`` / ``scan.stage`` / ``scan.fallback``, ``collect.to_arrow``,
``gc``) on hand-made spans and a hand-made reduced trace: their arithmetic,
and no reading where the program has no such span, where the buffer dropped
one, or where the trace is a stand-in."""

import pytest

from benchmark.metrics_per_layer import (collect_host_ms_per_query,
                                         gc_pause_ms_per_query,
                                         scan_fallback_host_s_per_query,
                                         scan_idle_pct,
                                         scan_read_host_s_per_query,
                                         scan_stage_host_s_per_query)
from spark_rapids_tpu.runtime import tracing

S = 1_000_000_000
T0 = 7000 * S
SPAN_READERS = (scan_read_host_s_per_query, scan_stage_host_s_per_query,
                scan_fallback_host_s_per_query, collect_host_ms_per_query,
                gc_pause_ms_per_query)


def span(name, sid, parent, t0, t1, thread="client-0", **counts):
    return {"name": name, "id": sid, "parent": parent, "trace": None,
            "thread": thread, "t0": T0 + int(t0 * S), "t1": T0 + int(t1 * S),
            "counts": counts}


def chunk(sid, parent, t, fused=True):
    """A column chunk at ``t`` on the scan's thread: 0.1 s of read, then
    0.3 s of stage or 0.5 s of fallback, under its ``scan.column``."""
    out = [span("scan.column", sid, parent, t, t + 0.9, "srt-pipe-scan",
                path="fused" if fused else "fallback"),
           span("scan.read", sid + 1, sid, t, t + 0.1, "srt-pipe-scan",
                bytes=10, pages=1, native=1)]
    if fused:
        out.append(span("scan.stage", sid + 2, sid, t + 0.1, t + 0.4,
                        "srt-pipe-scan", values=8, arrays=5, bytes=40))
    else:
        out.append(span("scan.fallback", sid + 2, sid, t + 0.1, t + 0.6,
                        "srt-pipe-scan", rows=8, reason="page", bytes=64))
    return out


def window_spans(phases=True, fallback=True, gc_in_query=True):
    """A warm-up query, then two of the window, 10 s behind the harness;
    query A scans a fused and a fallback chunk, query B one fused chunk."""
    out = [span("gc", 1, None, 0.0, 0.2, generation=2, collected=9,
                uncollectable=0),                    # the harness's own
           span("query", 2, None, 0.5, 9.0)]
    out += [span("query", 10, None, 20.0, 23.9),
            span("FileScan.devdecode", 11, 10, 20.1, 22.0, "srt-pipe-scan")]
    out += chunk(20, 11, 20.1) + chunk(30, 11, 21.0, fused=not fallback)
    out += [span("collect.to_arrow", 12, 10, 23.0, 23.004, rows=4,
                 columns=10, bytes=400),
            span("gc", 13, 20 + 2, 20.2, 20.203, "srt-pipe-scan",
                 generation=0, collected=0, uncollectable=0)]
    out += [span("query", 40, None, 24.0, 27.9),
            span("FileScan.devdecode", 41, 40, 24.1, 25.0, "srt-pipe-scan")]
    out += chunk(50, 41, 24.1)
    out += [span("collect.to_arrow", 42, 40, 27.0, 27.002, rows=4,
                 columns=10, bytes=400),
            span("collect.to_arrow", 43, 40, 27.5, 27.502, rows=0,
                 columns=10, bytes=400)]
    if not phases:
        out = [s for s in out if s["name"] not in
               ("scan.read", "scan.stage", "scan.fallback",
                "collect.to_arrow", "gc")]
    if not gc_in_query:
        out = [s for s in out if s["name"] != "gc" or s["parent"] is None]
    return out


def ctx_of(monkeypatch, spans, dropped=0, trace=None):
    monkeypatch.setattr(tracing, "recorded", lambda: list(spans))
    monkeypatch.setattr(tracing, "dropped", lambda: dropped)
    return {"done": [{"client": 0, "query": "q3", "start": 10.0, "end": 14.0},
                     {"client": 0, "query": "q3", "start": 14.0,
                      "end": 18.0}],
            "trace": trace}


def test_the_phases_by_hand(monkeypatch):
    ctx = ctx_of(monkeypatch, window_spans())
    # read: 0.1 a chunk, three chunks over two queries
    assert scan_read_host_s_per_query.read(ctx) == pytest.approx(0.15)
    # stage: 0.3 a fused chunk, two fused of three
    assert scan_stage_host_s_per_query.read(ctx) == pytest.approx(0.3)
    # fallback: one chunk of 0.5 s in query A
    assert scan_fallback_host_s_per_query.read(ctx) == pytest.approx(0.25)
    # collect: 4 ms in A, 2 + 2 in B
    assert collect_host_ms_per_query.read(ctx) == pytest.approx(4.0)
    # gc: 3 ms in A under its stage; the harness's, outside, not counted
    assert gc_pause_ms_per_query.read(ctx) == pytest.approx(1.5)


def test_nothing_fell_back_and_nothing_collected_read_zero(monkeypatch):
    ctx = ctx_of(monkeypatch,
                 window_spans(fallback=False, gc_in_query=False))
    assert scan_fallback_host_s_per_query.read(ctx) == 0.0
    assert gc_pause_ms_per_query.read(ctx) == 0.0
    assert scan_stage_host_s_per_query.read(ctx) == pytest.approx(0.45)


@pytest.mark.parametrize("why", ["no_phase_spans", "dropped", "no_buffer",
                                 "no_queries"])
def test_no_reading_is_not_zero(monkeypatch, why):
    """A commit without the spans (the parent), a buffer that dropped one,
    a program without a buffer, a window whose queries do not match."""
    spans = window_spans(phases=why != "no_phase_spans")
    ctx = ctx_of(monkeypatch, spans, dropped=int(why == "dropped"))
    if why == "no_buffer":
        monkeypatch.delattr(tracing, "recorded")
    if why == "no_queries":
        ctx["done"] = ctx["done"] * 3
    for reader in SPAN_READERS:
        assert reader.read(ctx) is None, reader.__name__


def trace(stand_in=False, gaps=None):
    return {"window_s": 2.0, "busy_s": 1.5, "stand_in": stand_in,
            "idle_gaps": gaps if gaps is not None else [
                ["FileScan.devdecode > scan.column", 0.05],
                ["scan.column > scan.fallback", 0.2],
                ["scan.stage > shard_args", 0.03],
                ["scan.column > gc", 0.1],
                ["scan.read", 0.02],
                ["query > PjitFunction(dynamic_slice)", 0.06],
                ["sync.count > np.asarray(jax.Array)", 0.04]]}


def test_scan_idle_share_by_hand(monkeypatch):
    # 0.05 + 0.2 + 0.03 + 0.02 of a 2 s window; the collection and the
    # collect's reads are not the scan's
    ctx = ctx_of(monkeypatch, [], trace=trace())
    assert scan_idle_pct.read(ctx) == pytest.approx(15.0)
    assert scan_idle_pct.names_scan("FileScan.devdecode > scan.column")
    assert scan_idle_pct.names_scan("scan.column > scan.fallback")
    assert not scan_idle_pct.names_scan("scan.column > gc")
    assert not scan_idle_pct.names_scan("MeshExchange.map > np.asarray")


@pytest.mark.parametrize("tr", [None, trace(stand_in=True),
                                trace(gaps=[["query.plan", 0.1],
                                            ["scan.stage > gc", 0.1]])],
                         ids=["no_trace", "stand_in", "no_scan_label"])
def test_scan_idle_share_gives_no_reading(monkeypatch, tr):
    assert scan_idle_pct.read(ctx_of(monkeypatch, [], trace=tr)) is None


def test_the_entries_and_their_cells():
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    scanning = ["tpch_sf1_batch.q1", "tpch_sf1_batch.q5",
                "tpch_sf1_served.q3x2", "tpch_sf1_batch.q3",
                "tpch_sf1_mesh4.q3", "tpcds_sf1_batch.q67"]
    for name, unit, source, layer, cells in (
            ("scan_read_host_s_per_query", "s/query", "program_span",
             "io scan", scanning),
            ("scan_stage_host_s_per_query", "s/query", "program_span",
             "io scan", scanning),
            ("scan_fallback_host_s_per_query", "s/query", "program_span",
             "io scan", scanning),
            ("scan_idle_pct", "%", "device_trace", "io scan", scanning),
            ("collect_host_ms_per_query", "ms", "program_span",
             "exec operators and dispatch",
             ["tpch_sf1_batch.q1", "tpch_sf1_batch.q5", "tpch_sf1_batch.q3",
              "tpch_sf1_mesh4.q3", "tpch_sf1_resident.q1",
              "tpcds_sf1_batch.q67"]),
            ("gc_pause_ms_per_query", "ms", "program_span",
             "exec operators and dispatch",
             [w["name"] for w in bench["workloads"]])):
        m = entries[name]
        assert (m["unit"], m["better"], m["source"], m["moves"],
                m["layer"]) == (unit, "lower", source, "input_rows_per_s",
                                layer), name
        assert m["workloads"] == cells, name
