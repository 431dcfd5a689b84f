"""Distributed tracing plane: cross-process trace propagation, span files,
Perfetto export + critical path, live serving metrics, compile telemetry.

Covers the telemetry contracts of runtime/tracing.py + tools/profiler.py
trace: a per-query trace id derived from the query id rides the MiniCluster
task protocol (surviving an exec_kill respawn), spans from every process
merge into one clock-offset-corrected Chrome trace, the endpoint serves a
Prometheus-style STATS snapshot backed by the fixed-bucket histograms in
runtime/metrics.py, and fuse compile/dispatch deltas reach
last_query_metrics() (the zero-retrace denominator)."""

import importlib.util
import json
import os
import pathlib
import sys

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.cluster import MiniCluster
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.runtime import eventlog
from spark_rapids_tpu.runtime import faults as FLT
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import tracing
from spark_rapids_tpu.session import TpuSession

REPO = pathlib.Path(__file__).resolve().parent.parent


def _profiler():
    spec = importlib.util.spec_from_file_location(
        "profiler_mod", REPO / "tools" / "profiler.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_state():
    FLT.reset()
    tracing.clear_events()
    yield
    FLT.reset()
    tracing.clear_events()
    tracing.shutdown_spans()
    tracing.set_process_trace(None)
    eventlog.set_clock_offset(0.0)


# ---------------------------------------------------------------------------
# histogram bucket math
# ---------------------------------------------------------------------------

def test_histogram_bucket_math():
    h = M.Histogram("t", bounds=(0.1, 1.0, 10.0))
    for v in (0.05, 0.05, 0.5, 2.0, 50.0):
        h.observe(v)
    snap = h.snapshot()
    # bucket i counts v <= bounds[i]; the 4th bucket is the +inf overflow
    assert snap["counts"] == [2, 1, 1, 1]
    assert snap["count"] == 5
    assert abs(snap["sum"] - 52.6) < 1e-9
    assert snap["min"] == 0.05 and snap["max"] == 50.0
    # percentiles are monotone in q and clamped to observed [min, max]
    ps = [h.percentile(q) for q in (0.1, 0.5, 0.9, 0.99)]
    assert ps == sorted(ps)
    assert ps[0] >= 0.05 and ps[-1] <= 50.0
    assert h.percentile(1.0) == 50.0
    assert M.Histogram("empty").percentile(0.5) is None


def test_histogram_registry_and_percentile_helper():
    M.histogram("test.reg.lat").observe(0.2)
    M.histogram("test.reg.lat").observe(0.4)
    snap = M.histograms_snapshot()["test.reg.lat"]
    assert snap["count"] == 2
    pct = M.histogram_percentiles("test.reg.lat")
    assert pct["count"] == 2 and pct["p50"] <= pct["p95"] <= pct["p99"]
    assert M.histogram_percentiles("no.such.histogram") is None


# ---------------------------------------------------------------------------
# clock-offset correction
# ---------------------------------------------------------------------------

def test_clock_offset_estimator():
    # symmetric latency: exact recovery of the remote clock skew
    # local sends at 100.0, remote (running 7s ahead) answers at 107.05,
    # local receives at 100.1 -> offset ≈ -7 (remote + offset = local)
    off = tracing.estimate_clock_offset(100.0, 107.05, 100.1)
    assert abs(off - (-7.0)) < 1e-9
    # the error of any estimate is bounded by half the round trip
    off = tracing.estimate_clock_offset(100.0, 107.0, 100.5)
    assert abs(off - (-6.75)) < 1e-9


def test_clock_offset_correction_in_merge(tmp_path):
    """Two processes whose RAW timestamps order wrongly must order
    correctly once each record's `off` correction is applied."""
    prof = _profiler()
    # driver: query window [1000, 1001]
    (tmp_path / "spans-1-a.jsonl").write_text(json.dumps(
        {"name": "query", "ph": "X", "ts": 1000.0, "dur": 1.0, "pid": 1,
         "proc": "driver", "tid": "MainThread", "trace": "t1"}) + "\n")
    # executor clock runs 10s BEHIND: raw ts 990.5 is really 1000.5
    (tmp_path / "spans-2-b.jsonl").write_text(json.dumps(
        {"name": "task.map", "ph": "X", "ts": 990.5, "dur": 0.2, "off": 10.0,
         "pid": 2, "proc": "executor-0", "tid": "MainThread",
         "trace": "t1"}) + "\n")
    records, violations = prof.load_spans(str(tmp_path))
    assert violations == []
    tid, spans = prof.pick_trace(records, "t1")
    assert len(spans) == 2
    by_name = {s["name"]: s for s in spans}
    assert by_name["task.map"]["_t0"] == pytest.approx(1000.5)
    # inside the driver window — uncorrected it would precede it entirely
    assert by_name["query"]["_t0"] < by_name["task.map"]["_t0"]
    window, chain, blame = prof.critical_path(spans)
    assert window["wall_s"] == pytest.approx(1.0)
    names = [c["name"] for c in chain]
    assert "task.map" in names
    task = next(c for c in chain if c["name"] == "task.map")
    assert task["start_s"] == pytest.approx(0.5)
    assert blame.get("compute", 0) == pytest.approx(0.2)


def test_eventlog_records_carry_pid_and_offset(tmp_path):
    eventlog.set_clock_offset(3.25)
    path = eventlog.configure(str(tmp_path))
    try:
        eventlog.emit("endpoint.start", query=None, host="x", port=1)
    finally:
        eventlog.shutdown()
        eventlog.set_clock_offset(0.0)
    rec = json.loads(open(path).read().strip())
    assert rec["pid"] == os.getpid()
    assert rec["offset"] == 3.25
    assert isinstance(rec["ts"], float)
    assert eventlog.validate_record(rec) == []


# ---------------------------------------------------------------------------
# span files + trace context
# ---------------------------------------------------------------------------

def test_span_file_schema_and_trace_precedence(tmp_path):
    path = tracing.configure_spans(str(tmp_path), process="driver")
    reg = M.MetricsRegistry("DEBUG")
    timer = reg.metric("opTime")
    with tracing.trace_context("tls-trace"):
        with tracing.trace_range("ProjectExec", timer):
            pass
    tracing.set_process_trace("proc-trace")
    with tracing.span("task.map", split=3):
        pass
    tracing.span_event("oom.retry", site="joins.build")
    tracing.set_process_trace(None)
    with tracing.span("orphan"):
        pass
    tracing.shutdown_spans()
    recs = [json.loads(ln) for ln in open(path)]
    for r in recs:
        assert tracing.validate_span(r) == [], r
    by_name = {r["name"]: r for r in recs}
    # thread-local context beats everything; process default fills in for
    # executor-style threads; no ambient context -> None
    assert by_name["ProjectExec"]["trace"] == "tls-trace"
    assert by_name["task.map"]["trace"] == "proc-trace"
    assert by_name["oom.retry"]["trace"] == "proc-trace"
    assert by_name["oom.retry"]["ph"] == "i"
    assert by_name["orphan"]["trace"] is None
    # the metric side of trace_range still accumulated
    assert timer.value > 0
    assert by_name["ProjectExec"]["dur"] > 0
    # the merged primitive: complete spans gain an id and a parent, instants
    # stay as they were, and a span's counts are its args
    assert by_name["task.map"]["args"] == {"split": 3}
    for name in ("ProjectExec", "task.map", "orphan"):
        assert isinstance(by_name[name]["id"], int)
        assert by_name[name]["parent"] is None
    assert "id" not in by_name["oom.retry"]


def test_span_file_carries_the_tree_without_the_profiler_switch(tmp_path):
    """The file sink is fed from the same record as the in-memory buffer,
    whether or not sql.trace.enabled is on; only the buffer needs it."""
    def tree():
        with tracing.trace_context("t1"), tracing.span("query") as q:
            with tracing.trace_range("FilterExec") as f:
                f.set(rows=7)
            with tracing.span("FilterExec"):
                pass
        return q.id

    tracing.drain()
    path = tracing.configure_spans(str(tmp_path), process="driver")
    root_off = tree()
    assert tracing.recorded() == []
    tracing.set_enabled(True)
    try:
        root_on = tree()
    finally:
        tracing.set_enabled(False)
    tracing.shutdown_spans()
    recs = [json.loads(ln) for ln in open(path)]
    assert all(tracing.validate_span(r) == [] for r in recs)
    # a collection while tracing is on is a span of its own, "gc"
    recs = [r for r in recs if r["name"] != "gc"]
    assert [r["name"] for r in recs] == ["FilterExec", "FilterExec",
                                         "query"] * 2
    for root, part in ((root_off, recs[:3]), (root_on, recs[3:])):
        assert part[2]["id"] == root and part[2]["parent"] is None
        assert [r["parent"] for r in part[:2]] == [root, root]
        assert part[0]["args"] == {"rows": 7} and "args" not in part[1]
    mem = [s for s in tracing.drain() if s["name"] != "gc"]
    assert [(s["name"], s["id"], s["parent"]) for s in mem] == \
        [(r["name"], r["id"], r["parent"]) for r in recs[3:]]
    assert all(s["trace"] == "t1" for s in mem)


def test_chrome_trace_schema(tmp_path):
    prof = _profiler()
    path = tracing.configure_spans(str(tmp_path), process="driver")
    with tracing.trace_context("c1"), tracing.span("query"):
        with tracing.span("FilterExec"):
            pass
        tracing.span_event("spill", bytes=10)
    tracing.shutdown_spans()
    records, violations = prof.load_spans(str(tmp_path))
    assert violations == []
    tid, spans = prof.pick_trace(records)
    assert tid == "c1" and len(spans) == 3
    trace = prof.chrome_trace(spans)
    evs = trace["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    body = [e for e in evs if e["ph"] != "M"]
    assert {m["name"] for m in meta} == {"process_name", "thread_name"}
    for e in body:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
        assert isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert "dur" in e
        else:
            assert e["ph"] == "i"
        assert e["args"]["trace"] == "c1"
    # instants for span events ride along
    assert any(e["ph"] == "i" and e["name"] == "spill" for e in body)


def test_malformed_span_file_is_a_violation(tmp_path):
    prof = _profiler()
    (tmp_path / "spans-9-z.jsonl").write_text('{"broken json\n')
    records, violations = prof.load_spans(str(tmp_path))
    assert records == [] and violations
    # missing-field records are violations too, not crashes
    (tmp_path / "spans-9-z.jsonl").write_text(
        json.dumps({"name": "x", "ph": "X", "ts": 1.0}) + "\n")
    records, violations = prof.load_spans(str(tmp_path))
    assert records == [] and any("dur" in v or "pid" in v
                                 for v in violations)


# ---------------------------------------------------------------------------
# MiniCluster propagation with one exec_kill recompute
# ---------------------------------------------------------------------------

def test_minicluster_trace_propagation_with_exec_kill(tmp_path):
    """The full distributed contract: one trace id across driver + 3
    executor processes, surviving an executor SIGKILL mid-map-stage (the
    respawned incarnation's spans carry the SAME trace id), merging into a
    schema-valid Chrome trace with a non-empty critical path."""
    prof = _profiler()
    rng = np.random.default_rng(11)
    t = pa.table({"k": pa.array(rng.integers(0, 13, 3000), type=pa.int64()),
                  "v": pa.array(rng.integers(0, 100, 3000),
                                type=pa.int64())})
    spark = TpuSession()
    df = (spark.create_dataframe(t, num_partitions=6)
          .group_by(F.col("k")).agg(F.sum(F.col("v")).alias("s")))
    exp = sorted(map(tuple, (r.values() for r
                             in df.collect_host().to_pylist())))

    settings = {
        "spark.rapids.tpu.trace.dir": str(tmp_path),
        # SIGKILL executor 0 after its first map task parked blocks
        "spark.rapids.tpu.test.faults": "exec_kill:cluster.map.0:1@1",
    }
    tracing.configure_spans(str(tmp_path), process="driver")
    base = M.resilience_snapshot()
    with MiniCluster(n_executors=3, conf=RapidsConf(settings),
                     platform="cpu") as c:
        got = c.collect(df)
    tracing.shutdown_spans()
    delta = {k: v - base[k] for k, v in M.resilience_snapshot().items()
             if v - base[k]}
    assert delta.get("executorsLost", 0) >= 1, delta
    assert delta.get("stagePartialRecomputes", 0) >= 1, delta
    assert sorted(map(tuple, (r.values() for r in got.to_pylist()))) == exp

    records, violations = prof.load_spans(str(tmp_path))
    assert violations == [], violations[:5]
    trace_id, spans = prof.pick_trace(records)
    assert trace_id.startswith("cluster-")
    # spans from the driver AND >= 3 executor incarnations (the original
    # three minus the killed one plus its respawn) share the trace id
    pids = {s["pid"] for s in spans}
    procs = {s["proc"] for s in spans}
    assert len(pids) >= 4, (pids, procs)
    assert "driver" in procs
    assert sum(1 for p in procs if p.startswith("executor-")) >= 3, procs
    # executor-0 appears under TWO pids: the killed incarnation wrote task
    # spans before dying, the respawn wrote the recompute's — same trace
    exec0_pids = {s["pid"] for s in spans if s["proc"] == "executor-0"}
    assert len(exec0_pids) >= 2, (exec0_pids, procs)
    # Chrome export + critical path (the ci.sh gate's in-suite twin)
    trace = prof.chrome_trace(spans)
    assert len(trace["traceEvents"]) > len(spans)   # + metadata lanes
    window, chain, blame = prof.critical_path(spans)
    assert window is not None and chain, (window, chain)
    assert window["name"] == "cluster.query"
    assert sum(blame.values()) <= window["wall_s"] + 1e-6
    assert max(blame, key=blame.get) in (
        "compute", "decode", "exchange", "queue-wait", "other")
    # task spans exist on both stages
    names = {s["name"] for s in spans}
    assert "task.map" in names and "task.result" in names


# ---------------------------------------------------------------------------
# STATS over the endpoint
# ---------------------------------------------------------------------------

def test_stats_roundtrip_over_endpoint():
    from spark_rapids_tpu.runtime.endpoint import EndpointClient
    spark = TpuSession()
    spark.create_or_replace_temp_view(
        "t", spark.create_dataframe(
            pa.table({"k": [1, 2, 2], "v": [1.0, 2.0, 3.0]})))
    ep = spark.serve()
    try:
        cli = EndpointClient(("127.0.0.1", ep.port))
        out = cli.submit("select k, sum(v) s from t group by k order by k",
                         trace="client-trace-7")
        assert out.num_rows == 2
        # the client's trace id rode the SUBMIT frame into the collector
        # (the summary frame reads it back off qm.trace_id server-side)
        assert cli.last_summary["trace"] == "client-trace-7"
        txt = cli.stats()
    finally:
        ep.shutdown(grace_s=2)
    assert "srt_queries_admitted_total" in txt
    assert 'srt_resilience_total{counter="numOomRetries"}' in txt
    assert "srt_scheduler_queue_depth" in txt
    assert 'srt_gauge{name="endpoint.connections"}' in txt
    # histogram families: latency per priority class + admission wait,
    # cumulative buckets ending in +Inf == count
    assert 'srt_query_latency_seconds_bucket{priority="0",le="+Inf"}' in txt
    assert "srt_admission_wait_seconds_count" in txt
    inf = [ln for ln in txt.splitlines()
           if ln.startswith('srt_query_latency_seconds_bucket{priority="0"')
           and 'le="+Inf"' in ln]
    cnt = [ln for ln in txt.splitlines()
           if ln.startswith('srt_query_latency_seconds_count')]
    assert inf and cnt and inf[0].split()[-1] == cnt[0].split()[-1]


def test_stats_disabled_returns_typed_error():
    from spark_rapids_tpu.runtime.endpoint import EndpointClient
    spark = TpuSession({"spark.rapids.tpu.endpoint.stats.enabled": "false"})
    ep = spark.serve()
    try:
        cli = EndpointClient(("127.0.0.1", ep.port))
        with pytest.raises(RuntimeError, match="stats.enabled"):
            cli.stats()
    finally:
        ep.shutdown(grace_s=2)


# ---------------------------------------------------------------------------
# compile/retrace telemetry
# ---------------------------------------------------------------------------

def test_compile_metrics_zero_retrace_on_second_run():
    spark = TpuSession()
    t = pa.table({"k": pa.array([1, 2, 2, 3] * 50, type=pa.int64()),
                  "v": pa.array(list(range(200)), type=pa.int64())})
    df = (spark.create_dataframe(t)
          .filter(F.col("v") >= 10)
          .group_by(F.col("k")).agg(F.sum(F.col("v")).alias("s")))
    df.collect()
    first = spark.last_query_metrics().compile_metrics()
    assert first["dispatches"] > 0
    df.collect()
    second = spark.last_query_metrics().compile_metrics()
    # the retrace denominator: an identical second run replays cached
    # kernels — zero new XLA compiles, same order of dispatches
    assert second["compiles"] == 0, (first, second)
    assert second["dispatches"] > 0
    # surfaced in the annotated plan header (explain(metrics=True))
    header = df.explain(metrics=True).splitlines()[0]
    assert "compiles=0" in header and "dispatches=" in header


def test_compile_metrics_in_query_end_event(tmp_path):
    spark = TpuSession()
    path = eventlog.configure(str(tmp_path))
    try:
        t = pa.table({"a": pa.array([1, 2, 3], type=pa.int64())})
        spark.create_dataframe(t).filter(F.col("a") > 1).collect()
    finally:
        eventlog.shutdown()
    ends = [json.loads(ln) for ln in open(path)
            if '"query.end"' in ln]
    assert ends, "no query.end recorded"
    rec = ends[-1]
    assert isinstance(rec["compiles"], int)
    assert isinstance(rec["dispatches"], int)
    assert rec["dispatches"] > 0
