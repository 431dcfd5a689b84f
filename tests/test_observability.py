"""Query-level observability: metric-annotated plans, the structured event
log and the profiling analyzer.

Covers: metrics-level gating (collection AND snapshot), the _NoopMetric
add_lazy leak fix, query-tagged span events, query-scoped resilience
isolation, event-log schema round-trip (every emitted event parses, carries
query attribution where required, and timestamps are monotonic), the
tools/profiler.py report path, and an end-to-end TPC-H q18 run whose
annotated explain's per-node row counts match the collected result."""

import json
import os
import subprocess
import sys

import pyarrow as pa
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.runtime import eventlog as EL
from spark_rapids_tpu.runtime import faults
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import tracing
from spark_rapids_tpu.session import TpuSession

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_state():
    EL.shutdown()
    faults.reset()
    M.reset_global_registry()
    tracing.clear_events()
    yield
    EL.shutdown()
    faults.reset()
    M.reset_global_registry()
    tracing.clear_events()


# -- metric levels ------------------------------------------------------------

def test_noop_metric_drops_add_lazy():
    reg = M.MetricsRegistry("ESSENTIAL")
    m = reg.metric("debugOnly", M.DEBUG)
    assert type(m) is M._NoopMetric
    # add_lazy on an above-level metric must DROP the value like add/set do:
    # appending device scalars to _pending on a metric whose value is never
    # read would pin them (and their device buffers) forever
    m.add_lazy(7)
    m.add_lazy(object())
    assert m._pending == []
    assert m.value == 0


def test_metrics_level_gates_collection_and_snapshot():
    for level, visible in (("ESSENTIAL", {"e"}),
                           ("MODERATE", {"e", "m"}),
                           ("DEBUG", {"e", "m", "d"})):
        reg = M.MetricsRegistry(level)
        reg.metric("e", M.ESSENTIAL).add(1)
        reg.metric("m", M.MODERATE).add(2)
        reg.metric("d", M.DEBUG).add(3)
        snap = reg.snapshot()
        assert set(snap) == visible, level
        # above-level metrics drop updates entirely (collection gating)
        for name in {"e", "m", "d"} - visible:
            assert reg.metric(name).value == 0


def test_gpu_metric_lazy_fold_and_timed():
    m = M.GpuMetric("x")
    m.add_lazy(5)          # int fast-path
    m.add_lazy(pa.scalar(7).as_py() + 0)   # still int
    assert m.value == 12
    with m.timed():
        pass
    assert m.value >= 12


# -- span-event query tagging -------------------------------------------------

def test_span_events_tagged_and_filterable_by_query():
    c1 = M.QueryMetricsCollector()
    c2 = M.QueryMetricsCollector()
    with M.collector_context(c1):
        tracing.span_event("oom.retry", site="t1")
    with M.collector_context(c2):
        tracing.span_event("oom.retry", site="t2")
    tracing.span_event("oom.retry", site="untagged")
    assert len(tracing.recent_events("oom.retry")) == 3
    own = tracing.recent_events("oom.retry", query=c1.query_id)
    assert [e[1]["site"] for e in own] == ["t1"]
    own2 = tracing.recent_events(query=c2.query_id)
    assert [e[1]["site"] for e in own2] == ["t2"]


def test_trace_range_metric_both_paths():
    m = M.GpuMetric("t")
    with tracing.trace_range("r", m):
        pass
    v1 = m.value
    assert v1 > 0
    tracing.drain()
    tracing.set_enabled(True)
    try:
        with tracing.trace_range("r", m):
            pass
    finally:
        tracing.set_enabled(False)
    assert m.value > v1
    # on, the same range is also one record of the in-memory buffer, and
    # its duration is what the metric gained
    # (a collection in between would be a span of its own, "gc")
    rec, = [r for r in tracing.drain() if r["name"] != "gc"]
    assert rec["name"] == "r" and rec["t1"] - rec["t0"] == m.value - v1


def test_query_root_span_says_what_the_plan_was_and_what_came_out():
    tracing.drain()
    tracing.set_enabled(True)
    try:
        s = TpuSession()
        df = s.create_dataframe(pa.table({"a": [1, 2, 3, 4]}))
        s.create_or_replace_temp_view("obs_t", df)
        out = s.sql("select a from obs_t where a > 1").collect()
    finally:
        tracing.set_enabled(False)
    spans = {r["name"]: r for r in tracing.drain()}
    root = spans["query"]
    assert root["parent"] is None and root["counts"]["rows"] == out.num_rows
    assert root["counts"]["query"] == s.last_query_metrics().query_id
    assert root["trace"] == root["counts"]["query"]
    assert spans["sql.parse"]["parent"] is None
    assert spans["sql.parse"]["t1"] <= root["t0"]
    plan = spans["query.plan"]
    assert plan["parent"] == root["id"]
    assert plan["counts"]["operators"] >= 2 and plan["counts"]["stages"] >= 1
    assert plan["counts"]["fallback"] == 0
    assert spans["query.admission"]["parent"] == root["id"]
    assert plan["t1"] <= spans["query.admission"]["t0"]


def test_stop_profile_unregisters_atexit(monkeypatch):
    import atexit
    calls = []
    monkeypatch.setattr("jax.profiler.start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr("jax.profiler.stop_trace",
                        lambda: calls.append(("stop",)))
    registered = []
    monkeypatch.setattr(atexit, "register",
                        lambda fn: registered.append(fn) or fn)
    monkeypatch.setattr(atexit, "unregister",
                        lambda fn: registered.remove(fn))
    for _ in range(3):
        tracing.start_profile("/tmp/obs-prof-test")
        assert len(registered) == 1     # repeated cycles must not stack
        tracing.stop_profile()
        assert registered == []
    assert calls.count(("stop",)) == 3


# -- query-scoped collection --------------------------------------------------

def _session(**extra):
    return TpuSession(dict(extra))


def test_collector_registers_nodes_and_self_time():
    spark = _session()
    df = spark.create_dataframe(
        pa.table({"k": [1, 2, 2, 3] * 50, "v": [1.0, 2.0, 3.0, 4.0] * 50}))
    q = df.group_by("k").agg(F.sum("v").alias("s"))
    out = q.collect()
    qm = spark.last_query_metrics()
    assert qm is not None and qm.wall_s > 0
    nodes = [n for n in qm.node_summaries() if n["id"] is not None]
    assert nodes, "no exec registered with the collector"
    agg = [n for n in nodes if "Aggregate" in n["name"]]
    assert agg and agg[0]["metrics"]["numOutputRows"] == out.num_rows
    assert sum(n["metrics"].get("selfTime", 0) for n in nodes) > 0
    annotated = q.explain(metrics=True)
    assert qm.query_id in annotated
    assert "numOutputRows" in annotated and "selfTime" in annotated


def test_explain_metrics_before_action():
    spark = _session()
    df = spark.create_dataframe(pa.table({"a": [1, 2, 3]}))
    s = df.explain(metrics=True)
    assert "no completed action" in s


def test_query_resilience_isolated_across_queries():
    """resilience_add pins each increment to the AMBIENT query's scoped
    registry (not a start/finish delta of the process-wide one, which
    CONCURRENT queries mutate inside each other's windows — the
    multi-tenant scheduler's attribution contract)."""
    c1 = M.QueryMetricsCollector()
    c2 = M.QueryMetricsCollector()
    # interleaved increments, the shape a concurrent peer produces: the old
    # delta attribution would have charged c2's retries to c1 as well
    with M.collector_context(c1):
        M.resilience_add(M.NUM_OOM_RETRIES, 2)
    with M.collector_context(c2):
        M.resilience_add(M.NUM_OOM_RETRIES, 3)
        M.resilience_add(M.FETCH_RECOMPUTES)
    c1.finish()
    c2.finish()
    # the process-wide registry accumulates; the scoped registries isolate
    assert M.resilience_snapshot()[M.NUM_OOM_RETRIES] == 5
    assert c1.query_resilience()[M.NUM_OOM_RETRIES] == 2
    assert c1.query_resilience()[M.FETCH_RECOMPUTES] == 0
    assert c2.query_resilience()[M.NUM_OOM_RETRIES] == 3
    assert c2.query_resilience()[M.FETCH_RECOMPUTES] == 1


def test_node_frame_self_time_subtracts_children():
    import time
    parent = M.GpuMetric("p")
    child = M.GpuMetric("c")
    with M.node_frame(1, parent):
        assert M.current_node() == 1
        with M.node_frame(2, child):
            assert M.current_node() == 2
            time.sleep(0.02)
    assert M.current_node() is None
    assert child.value >= 15e6
    assert parent.value < child.value   # child time subtracted from parent


# -- event log ----------------------------------------------------------------

def test_eventlog_schema_roundtrip(tmp_path):
    spark = _session(**{
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
        "spark.rapids.tpu.eventLog.healthSample.intervalSeconds": 0.05})
    df = spark.create_dataframe(
        pa.table({"k": [1, 2, 3] * 100, "v": [1.0, 2.0, 3.0] * 100}),
        num_partitions=2)
    res = df.group_by("k").agg(F.sum("v").alias("s")).sort("k").collect()
    assert res.num_rows == 3
    EL.emit_health()
    path = EL.current_path()
    EL.shutdown()
    recs = [json.loads(line) for line in open(path)]
    assert recs, "empty event log"
    # every emitted event parses and passes the shared schema validator
    for r in recs:
        assert EL.validate_record(r) == [], r
    # monotonic timestamps across the whole file
    ts = [r["t"] for r in recs]
    assert ts == sorted(ts)
    events = {r["event"] for r in recs}
    assert {"query.start", "query.end", "batch",
            "stage.map.start", "stage.map.end"} <= events
    qid = spark.last_query_metrics().query_id
    for r in recs:
        if r["event"] in EL.QUERY_SCOPED_EVENTS:
            assert r["query"] == qid
    end = [r for r in recs if r["event"] == "query.end"][0]
    assert end["wall_s"] > 0
    node_names = {n["name"] for n in end["nodes"] if n["id"] is not None}
    assert any("Aggregate" in n for n in node_names)
    health = [r for r in recs if r["event"] == "executor.health"]
    assert health and health[-1]["device_initialized"]
    assert "hbm_used_bytes" in health[-1]


def test_eventlog_disabled_is_noop(tmp_path):
    assert not EL.enabled()
    EL.emit("spill", bytes=1)        # must not throw, must not write
    spark = _session()
    df = spark.create_dataframe(pa.table({"a": [1, 2, 3]}))
    df.collect()
    assert EL.current_path() is None


def test_eventlog_spill_and_oom_attribution(tmp_path):
    """Injected join-build OOMs land in the event log attributed to the plan
    node that was executing (the acceptance-criteria chaos shape)."""
    spark = _session(**{
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
        "spark.rapids.tpu.test.faults": "oom:joins.build:1"})
    left = spark.create_dataframe(
        pa.table({"k": list(range(200)), "v": [1.0] * 200}))
    right = spark.create_dataframe(
        pa.table({"k": list(range(0, 200, 2)), "w": [2.0] * 100}))
    out = left.join(right, on="k").agg(F.sum((F.col("v") + F.col("w")))
                                       .alias("t")).collect()
    assert out.num_rows == 1
    path = EL.current_path()
    EL.shutdown()
    recs = [json.loads(line) for line in open(path)]
    ooms = [r for r in recs if r["event"] == "oom.retry"]
    assert ooms, "injected OOM never reached the event log"
    qid = spark.last_query_metrics().query_id
    end = [r for r in recs if r["event"] == "query.end"
           and r["query"] == qid][0]
    nodes_by_id = {n["id"]: n for n in end["nodes"] if n["id"] is not None}
    hit = [nodes_by_id[r["node"]]["name"] for r in ooms
           if r.get("node") in nodes_by_id]
    assert hit and all(("Join" in n or "Broadcast" in n or "Coalesce" in n)
                       for n in hit), hit
    # the query-scoped resilience delta sees the recovery too
    assert end["resilience"]["numOomRetries"] >= 1


# -- profiler tool ------------------------------------------------------------

def _run_profiler(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "profiler.py"), *args],
        capture_output=True, text=True, cwd=REPO)


def test_profiler_report_and_compare(tmp_path):
    spark = _session(**{"spark.rapids.tpu.eventLog.dir": str(tmp_path)})
    df = spark.create_dataframe(
        pa.table({"k": [1, 2, 3] * 200, "v": [1.0, 2.0, 3.0] * 200}),
        num_partitions=2)
    q = df.group_by("k").agg(F.sum("v").alias("s")).sort("k")
    assert q.collect().num_rows == 3
    path = EL.current_path()
    # second run in a fresh file for --compare
    spark2 = _session(**{"spark.rapids.tpu.eventLog.dir": str(tmp_path)})
    assert q.collect().num_rows == 3
    path2 = EL.current_path()
    EL.shutdown()
    assert path != path2

    proc = _run_profiler("report", path, "--json")
    assert proc.returncode == 0, proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["violations"] == []
    assert len(rep["queries"]) == 1
    q0 = rep["queries"][0]
    assert q0["operators"] and q0["wall_s"] > 0
    assert q0["operators"][0]["self_s"] >= q0["operators"][-1]["self_s"]
    assert any("ShuffleExchangeExec" in s["node"] for s in q0["shuffles"])

    text = _run_profiler("report", path)
    assert text.returncode == 0 and "top operators by self time" in text.stdout

    cmp_proc = _run_profiler("report", path, "--compare", path2)
    assert cmp_proc.returncode == 0, cmp_proc.stderr
    assert "wall" in cmp_proc.stdout and "-> " in cmp_proc.stdout


def test_profiler_flags_schema_violations(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"event":"nope","ts":1.0,"t":1.0}\n'
                   'not json at all\n')
    proc = _run_profiler("report", str(bad))
    assert proc.returncode == 1
    assert "SCHEMA VIOLATION" in proc.stderr


# -- end-to-end: TPC-H q18 ----------------------------------------------------

def test_q18_annotated_explain_row_counts(tmp_path):
    from spark_rapids_tpu.benchmarks import tpch
    paths = tpch.generate(0.005, str(tmp_path / "tpch"))
    spark = _session()
    dfs = tpch.load(spark, paths)
    tb = tpch.load_np(paths)
    df = tpch.q18(dfs)
    got = df.collect()
    qm = spark.last_query_metrics()
    assert qm is not None
    summaries = [n for n in qm.node_summaries() if n["id"] is not None]
    assert len(summaries) >= 5
    # the ROOT exec's output row count is the collected result's height
    root = summaries[0]
    assert root["depth"] == 0
    assert root["metrics"]["numOutputRows"] == got.num_rows
    # scan nodes account for every input row of the three scanned tables
    scan_rows = sum(n["metrics"]["numOutputRows"] for n in summaries
                    if "Scan" in n["name"])
    expected = sum(len(tb[t]["%s_orderkey" % p])
                   for t, p in (("lineitem", "l"), ("orders", "o")))
    expected += len(tb["customer"]["c_custkey"])
    assert scan_rows == expected
    # the join build is visible as a distinct metric on some plan node
    assert any(n["metrics"].get("buildSelfTime", 0) > 0 for n in summaries)
    # self-time attribution is populated
    total_self = sum(n["metrics"].get("selfTime", 0)
                     for n in summaries) / 1e9
    assert 0 < total_self
    annotated = df.explain(metrics=True)
    assert f"numOutputRows={got.num_rows}" in annotated.splitlines()[1]
