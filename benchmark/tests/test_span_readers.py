"""The readers of the program's own spans and program names (PR 26), each
on a hand-made ``ctx`` and span list whose answers are known by hand."""

import os
import shutil

import pytest

from benchmark import run, trace_reduce
from benchmark.metrics_per_layer import (
    _programs, _spans, endpoint_reply_ms, eager_programs_per_query,
    join_sort_device_s_per_query, live_rows_pct, plan_ms,
    scan_host_s_per_query, scan_offpath_chunks_pct, sync_wait_s_per_query)

S = 1_000_000_000          # the program's clock ticks in ns
T0 = 5000 * S              # ... and starts nowhere near the harness's zero
SPAN_READERS = (plan_ms, scan_host_s_per_query, scan_offpath_chunks_pct,
                sync_wait_s_per_query, live_rows_pct, endpoint_reply_ms)
TRACE_READERS = (eager_programs_per_query, join_sort_device_s_per_query)


def span(name, sid, parent, t0, t1, thread="client-0", **counts):
    """t0 and t1 in seconds after T0."""
    return {"name": name, "id": sid, "parent": parent, "trace": None,
            "thread": thread, "t0": T0 + int(t0 * S), "t1": T0 + int(t1 * S),
            "counts": counts}


def batch_spans():
    """A warm-up query, then two queries of the window on one thread."""
    return [
        span("sql.parse", 1, None, 0.0, 0.5),             # the warm-up's
        span("query", 2, None, 0.5, 9.0),
        span("query.plan", 3, 2, 0.5, 1.5),
        span("FileScan.devdecode", 4, 2, 2.0, 8.0, "srt-pipe-scan",
             rows=1, capacity=1000),
        # query A: 10.0 to 14.0 on the harness's clock 10 s behind
        span("sql.parse", 10, None, 20.000, 20.002),
        span("query", 11, None, 20.010, 23.990),
        span("query.plan", 12, 11, 20.010, 20.014),
        span("query.admission", 13, 11, 20.014, 20.015),
        span("pipeline.scan", 14, 11, 20.020, 23.000, "srt-pipe-scan"),
        span("FileScan.devdecode", 15, 14, 20.1, 21.1, "srt-pipe-scan",
             rows=600, capacity=1024),
        span("scan.column", 16, 15, 20.1, 20.4, "srt-pipe-scan",
             path="fused"),
        span("scan.column", 17, 15, 20.4, 21.0, "srt-pipe-scan",
             path="pages"),
        span("scan.page", 18, 17, 20.4, 20.9, "srt-pipe-scan", values=600),
        span("FileScan.devdecode", 19, 14, 21.5, 22.0, "srt-pipe-scan",
             rows=400, capacity=1024),
        span("scan.column", 20, 19, 21.5, 21.7, "srt-pipe-scan",
             path="fused"),
        span("scan.column", 21, 19, 21.7, 22.0, "srt-pipe-scan",
             path="fallback"),
        span("sync.count", 22, 11, 23.0, 23.25, rows=24, capacity=1024),
        span("broadcast.wait", 23, 11, 23.3, 23.8),
        span("sync.count", 24, 11, 23.85, 23.9, pairs=7),   # no capacity
        # query B: 14.0 to 18.0
        span("sql.parse", 30, None, 24.000, 24.004),
        span("query", 31, None, 24.010, 27.990),
        span("query.plan", 32, 31, 24.010, 24.016),
        span("FileScan.devdecode", 33, 31, 24.1, 24.6, "srt-pipe-scan",
             rows=1000, capacity=1024),
        span("scan.column", 34, 33, 24.1, 24.6, "srt-pipe-scan",
             path="fused"),
        span("sync.status", 35, 31, 25.0, 25.25, rows=0, capacity=0),
        span("sync.matched", 36, 31, 26.0, 26.5, rows=24, capacity=1024),
    ]


def batch_done():
    return [{"client": 0, "query": "q1", "start": 10.0, "end": 14.0},
            {"client": 0, "query": "q1", "start": 14.0, "end": 18.0}]


def served_spans():
    """Two clients, one query each, through the endpoint."""
    out = []
    for i, (t, conn, worker) in enumerate(
            [(20.0, "conn-1", "srt-endpoint-w1"),
             (20.5, "conn-2", "srt-endpoint-w2")]):
        b = 100 * (i + 1)
        out += [
            span("endpoint.request", b, None, t, t + 4.0, conn, batches=2),
            span("sql.parse", b + 1, b, t + 0.001, t + 0.003, conn),
            span("query", b + 2, b, t + 0.01, t + 3.9, worker),
            span("query.plan", b + 3, b + 2, t + 0.01, t + 0.015, worker),
            span("endpoint.encode", b + 4, b + 2, t + 3.0, t + 3.002, worker),
            span("endpoint.send", b + 5, b + 2, t + 3.002, t + 3.003, worker),
            span("endpoint.encode", b + 6, b + 2, t + 3.5, t + 3.504, worker),
            span("endpoint.send", b + 7, b + 2, t + 3.504, t + 3.505, worker),
        ]
    return out


def served_done():
    return [{"client": 0, "query": "q3", "start": 9.99, "end": 14.02},
            {"client": 1, "query": "q3", "start": 10.49, "end": 14.52}]


def ctx_of(monkeypatch, spans, done):
    monkeypatch.setattr(_spans, "_recorded", lambda: spans)
    return {"done": done}


def test_batch_readers_by_hand(monkeypatch):
    ctx = ctx_of(monkeypatch, batch_spans(), batch_done())
    queries = _spans.window_queries(ctx)
    assert [q["root"]["id"] for q in queries] == [11, 31]
    assert [q["parse"]["id"] for q in queries] == [10, 30]
    assert all(q["request"] is None for q in queries)
    # parse 2 + plan 4 ms, parse 4 + plan 6 ms
    assert plan_ms.read(ctx) == pytest.approx((6.0 + 10.0) / 2)
    # devdecode 1.0 + 0.5 s in A, 0.5 s in B; the warm-up's 6 s are not in
    assert scan_host_s_per_query.read(ctx) == pytest.approx(1.0)
    # five chunks, one on the page path and one through pyarrow
    assert scan_offpath_chunks_pct.read(ctx) == pytest.approx(40.0)
    # A: 0.25 + 0.5 + 0.05, B: 0.25 + 0.5
    assert sync_wait_s_per_query.read(ctx) == pytest.approx(0.775)
    # devdecode 600 + 400 + 1000 of 3 x 1024, syncs 24 + 24 of 2 x 1024
    assert live_rows_pct.read(ctx) == pytest.approx(100 * 2048 / 5120)
    assert endpoint_reply_ms.read(ctx) is None      # no such span here


def test_served_readers_by_hand(monkeypatch):
    ctx = ctx_of(monkeypatch, served_spans(), served_done())
    queries = _spans.window_queries(ctx)
    assert [q["root"]["id"] for q in queries] == [102, 202]
    assert [q["request"]["id"] for q in queries] == [100, 200]
    assert [q["parse"]["id"] for q in queries] == [101, 201]
    assert plan_ms.read(ctx) == pytest.approx(2.0 + 5.0)
    assert endpoint_reply_ms.read(ctx) == pytest.approx(2 + 1 + 4 + 1)
    for reader in (scan_host_s_per_query, scan_offpath_chunks_pct,
                   sync_wait_s_per_query, live_rows_pct):
        assert reader.read(ctx) is None, reader.__name__


@pytest.mark.parametrize("why", ["no_buffer", "a_drop", "too_few_roots",
                                 "root_outside_its_interval", "no_query"])
def test_no_reading_where_the_roots_do_not_match(monkeypatch, why):
    spans, done = batch_spans(), batch_done()
    if why == "no_buffer":          # a program from before it had one
        spans = None
    elif why == "a_drop":
        from spark_rapids_tpu.runtime import tracing
        monkeypatch.setattr(tracing, "dropped", lambda: 1)
        assert _spans._recorded() is None
        spans = None
    elif why == "too_few_roots":
        done = done + [{"client": 0, "query": "q1", "start": 18.0,
                        "end": 22.0}] * 2
    elif why == "root_outside_its_interval":
        done[1] = dict(done[1], end=17.0)     # the root runs 3.98 s
    elif why == "no_query":
        done = []
    ctx = ctx_of(monkeypatch, spans, done)
    assert _spans.window_queries(ctx) is None
    for reader in SPAN_READERS:
        assert reader.read(ctx) is None, reader.__name__


def test_a_query_inside_a_query_is_not_a_root():
    spans = batch_spans() + [span("query", 40, 31, 24.2, 24.3),
                             span("sync.count", 41, 40, 24.2, 24.3)]
    queries = _spans.match_queries(spans, batch_done())
    assert [q["root"]["id"] for q in queries] == [11, 31]
    assert 41 in {s["id"] for s in queries[1]["spans"]}


def trace_ctx(runs):
    return {"cell": {"chips": 1}, "_program_runs": runs,
            "traced_span": (10.0, 14.0),
            "done": [{"start": 10.0, "end": 14.0},      # all of it inside
                     {"start": 8.0, "end": 12.0},       # half of it
                     {"start": 14.0, "end": 18.0}]}     # none of it


def test_trace_readers_by_hand():
    runs = ([("jit_srt_HashJoin_probe", 1.5), ("jit_srt_SortExec", 0.75),
             ("jit_srt_BroadcastHashJoin_build", 0.25),
             ("jit_srt_HashAggregateExec", 4.0)]
            + [("jit_convert_element_type", 0.001)] * 20
            + [("jit__broadcast_arrays", 0.002)] * 10)
    ctx = trace_ctx(runs)
    assert _programs.queries_in_span(ctx) == pytest.approx(1.5)
    assert eager_programs_per_query.read(ctx) == pytest.approx(30 / 1.5)
    assert join_sort_device_s_per_query.read(ctx) == pytest.approx(2.5 / 1.5)
    # a scan-only query has no spine program: no reading, not 0
    q1 = trace_ctx([("jit_srt_HashAggregateExec", 1.0), ("jit_less", 0.1)])
    assert join_sort_device_s_per_query.read(q1) is None
    assert eager_programs_per_query.read(q1) == pytest.approx(1 / 1.5)
    for reader in TRACE_READERS:
        assert reader.read(trace_ctx(None)) is None
        assert reader.read(dict(trace_ctx(runs), traced_span=None)) is None


def test_program_runs_come_from_this_runs_trace_file(monkeypatch, tmp_path):
    logdir = tmp_path / "rehearsal" / "trace" / "plugins" / "profile" / "x"
    os.makedirs(logdir)
    shutil.copy(trace_reduce.RECORDED, logdir / "small.xplane.pb")
    monkeypatch.setattr(_programs, "_WORK", str(tmp_path))
    reduced = trace_reduce.reduce_file(trace_reduce.RECORDED)
    ctx = {"cell": {"chips": 1}, "trace": reduced}
    runs = _programs.program_runs(ctx)
    assert len(runs) == reduced["program_runs"] == 6
    assert {name for name, _ in runs} == {"jit__lambda"}     # no (digits)
    # clipped to the traced span, as the reduction's own table is
    assert sum(secs for _, secs in runs) == pytest.approx(
        sum(secs for _, secs in reduced["device_programs"]))
    # another run's file (another count of program runs) gives no reading,
    # nor does a trace without a TPU plane, nor a run without a trace
    other = dict(reduced, program_runs=7)
    assert _programs.program_runs({"cell": {"chips": 1},
                                   "trace": other}) is None
    stand_in = dict(reduced, stand_in=True)
    assert _programs.program_runs({"cell": {"chips": 1},
                                   "trace": stand_in}) is None
    assert _programs.program_runs({"cell": {"chips": 1},
                                   "trace": None}) is None
    assert [r[0] for r in _programs.by_name(trace_reduce.RECORDED)] == \
        ["jit__lambda"]


def test_the_eight_entries_list_their_cells():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cells = [w["name"] for w in bench["workloads"]]
    new = {m["name"]: m for m in bench["per_layer"][-8:]}
    assert list(new) == [
        "plan_ms", "scan_host_s_per_query", "scan_offpath_chunks_pct",
        "sync_wait_s_per_query", "live_rows_pct", "eager_programs_per_query",
        "join_sort_device_s_per_query", "endpoint_reply_ms"]
    assert all(m["moves"] == "input_rows_per_s" for m in new.values())
    assert new["endpoint_reply_ms"]["workloads"] == ["tpch_sf1_served.q3x2"]
    assert "tpch_sf1_batch.q1" not in \
        new["join_sort_device_s_per_query"]["workloads"]
    for name in ("plan_ms", "scan_host_s_per_query", "live_rows_pct",
                 "scan_offpath_chunks_pct", "sync_wait_s_per_query",
                 "eager_programs_per_query"):
        assert new[name]["workloads"] == cells, name
