"""The span primitive of runtime/tracing.py: one tree a query, recorded in
memory on ``perf_counter_ns`` and, under a profiler capture, as host ranges
of the same names on the capture's clock; and the names that reach the
device programs (runtime/fuse.py)."""

import gc
import glob
import threading
import time
import tracemalloc

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax

from spark_rapids_tpu import types as T
from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.io import parquet_native as PN
from spark_rapids_tpu.runtime import fuse
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import pipeline as P
from spark_rapids_tpu.runtime import tracing
from spark_rapids_tpu.runtime.endpoint import EndpointClient
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.sql.tpch_queries import SQL_QUERIES


@pytest.fixture
def traced():
    """Tracing on, and Python's automatic collections held off: with tracing
    on each collection is a ``gc`` span, and a test counts the spans it made
    itself (a test of the ``gc`` span collects explicitly)."""
    automatic = gc.isenabled()
    gc.disable()
    tracing.drain()
    tracing.set_enabled(True)
    yield
    tracing.set_enabled(False)
    tracing.drain()
    if automatic:
        gc.enable()


def by_name(spans, name):
    return [s for s in spans if s["name"] == name]


def ancestors(spans, span):
    ids = {s["id"]: s for s in spans}
    out = []
    while span["parent"] is not None and span["parent"] in ids:
        span = ids[span["parent"]]
        out.append(span["name"])
    return out


# -- the primitive ------------------------------------------------------------

def test_trace_range_and_span_are_one_primitive(traced):
    m = M.GpuMetric("t")
    with tracing.span("outer", query="q") as outer:
        with tracing.trace_range("inner", m, rows=3) as inner:
            inner.set(capacity=8)
    assert type(outer) is type(inner) is tracing._Span
    inner_r, outer_r = tracing.recorded()      # a child closes first
    assert (inner_r["name"], outer_r["name"]) == ("inner", "outer")
    assert inner_r["parent"] == outer_r["id"] and outer_r["parent"] is None
    assert inner_r["counts"] == {"rows": 3, "capacity": 8}
    assert outer_r["counts"] == {"query": "q"}
    assert inner_r["thread"] == threading.current_thread().name
    assert outer_r["t0"] <= inner_r["t0"] <= inner_r["t1"] <= outer_r["t1"]
    assert m.value == inner_r["t1"] - inner_r["t0"]


def test_self_time_is_duration_minus_children(traced):
    with tracing.span("parent"):
        time.sleep(0.02)
        with tracing.span("child"):
            time.sleep(0.03)
        with tracing.span("child"):
            time.sleep(0.01)
    spans = tracing.recorded()
    table = tracing.summarize(spans)
    parent = by_name(spans, "parent")[0]
    kids = sum(s["t1"] - s["t0"] for s in by_name(spans, "child"))
    assert table["child"]["count"] == 2
    assert table["child"]["total_s"] == pytest.approx(kids / 1e9)
    assert table["child"]["self_s"] == pytest.approx(kids / 1e9)
    want = (parent["t1"] - parent["t0"] - kids) / 1e9
    assert table["parent"]["self_s"] == pytest.approx(want)
    assert 0.015 < want < table["parent"]["total_s"] - 0.035


def test_a_child_on_another_thread_is_not_taken_off_self_time(traced):
    with tracing.span("parent") as parent:
        t = threading.Thread(
            target=lambda: tracing.span("side", parent=parent.id)
            .__enter__().__exit__(None, None, None))
        t.start()
        t.join()
    table = tracing.summarize(tracing.recorded())
    assert table["parent"]["self_s"] == table["parent"]["total_s"]


def test_off_path_allocates_nothing_and_records_nothing(monkeypatch,
                                                        tmp_path):
    tracing.set_enabled(False)
    tracing.shutdown_spans()
    tracing.drain()
    assert tracing.span("x") is tracing.NO_SPAN
    assert tracing.trace_range("x") is tracing.NO_SPAN
    assert tracing.child_of(tracing.current_span()) is tracing.NO_SPAN
    assert not tracing.NO_SPAN and tracing.NO_SPAN.id is None
    # the gc hook exists only while tracing is on, and once
    assert tracing._on_gc not in gc.callbacks
    tracing.set_enabled(True)
    tracing.set_enabled(True)
    assert gc.callbacks.count(tracing._on_gc) == 1
    tracing.set_enabled(False)
    assert tracing._on_gc not in gc.callbacks
    tracing.drain()
    # the scan's and the collect's sites get the shared no-op
    made = []
    span = tracing.span
    monkeypatch.setattr(tracing, "span", lambda name, **kw: made.append(
        (name, span(name, **kw))) or made[-1][1])
    f = str(tmp_path / "off.parquet")
    pq.write_table(pa.table({"coded": np.arange(64) % 3,
                             "plain": np.arange(64) * 0.5}), f,
                   compression="NONE", use_dictionary=["coded"])
    session = TpuSession({
        "spark.rapids.tpu.sql.parquet.deviceDecode.enabled": "true"})
    assert session.read_parquet(f).collect().num_rows == 64
    assert {"scan.column", "scan.read", "scan.stage", "scan.fallback",
            "collect.to_arrow"} <= {name for name, _ in made}
    assert all(sp is tracing.NO_SPAN for _, sp in made)
    monkeypatch.undo()

    def sites(n):
        for _ in range(n):
            with tracing.span("x"):
                pass
            with tracing.trace_range("y") as sp:
                if sp:
                    sp.set(rows=1)
    sites(100)
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    sites(10_000)
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(d.size_diff for d in after.compare_to(before, "filename")
                if d.traceback[0].filename == tracing.__file__)
    assert grown == 0
    assert tracing.recorded() == [] and tracing.dropped() == 0
    # a metric still gets its time, and nothing else happens
    m = M.GpuMetric("t")
    with tracing.trace_range("z", m) as sp:
        assert not sp
    assert m.value > 0 and tracing.recorded() == []


def test_the_buffer_is_bounded_and_counts_what_it_drops(traced, monkeypatch):
    import collections
    monkeypatch.setattr(tracing, "MAX_RECORDS", 4)
    monkeypatch.setattr(tracing, "_records", collections.deque(maxlen=4))
    for i in range(6):
        with tracing.span(f"s{i}"):
            pass
    assert [s["name"] for s in tracing.recorded()] == ["s2", "s3", "s4", "s5"]
    assert tracing.dropped() == 2
    assert len(tracing.drain()) == 4
    assert tracing.recorded() == [] and tracing.dropped() == 0


def test_a_collection_is_a_gc_span_under_the_span_it_interrupts(traced):
    with tracing.span("work") as work:
        gc.collect()
    (g,) = by_name(tracing.recorded(), "gc")
    assert g["parent"] == work.id
    assert g["thread"] == threading.current_thread().name
    assert g["counts"]["generation"] == 2
    assert set(g["counts"]) == {"generation", "collected", "uncollectable"}
    assert g["counts"]["collected"] >= 0 and g["counts"]["uncollectable"] >= 0


def test_a_collection_inside_the_buffer_lock_does_not_deadlock(traced):
    """A collection can start at any bytecode, among them the ones where
    its thread holds the buffer's lock: its span closes inside it."""
    def collect_holding_the_lock():
        with tracing._records_lock:
            gc.collect()

    t = threading.Thread(target=collect_holding_the_lock)
    t.start()
    t.join(timeout=60)
    assert not t.is_alive()
    assert len(by_name(tracing.recorded(), "gc")) == 1


def test_threads_share_the_buffer_and_keep_their_own_trees(traced):
    import os
    import sys
    workers, rounds = 4 * (os.cpu_count() or 2), 200

    def work(i):
        for r in range(rounds):
            with tracing.span("outer", worker=i) as outer:
                with tracing.span("inner", worker=i, of=outer.id):
                    pass

    threads = [threading.Thread(target=work, args=(i,), name=f"w{i}")
               for i in range(workers)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    spans = tracing.recorded()
    assert len(spans) == 2 * workers * rounds and tracing.dropped() == 0
    assert len({s["id"] for s in spans}) == len(spans)
    for s in spans:
        assert s["thread"] == f"w{s['counts']['worker']}"
        if s["name"] == "inner":
            assert s["parent"] == s["counts"]["of"]
        else:
            assert s["parent"] is None


def test_start_profile_passes_profile_options(monkeypatch):
    calls = []
    monkeypatch.setattr(
        "jax.profiler.start_trace",
        lambda d, profiler_options=None: calls.append((d, profiler_options)))
    monkeypatch.setattr("jax.profiler.stop_trace", lambda: None)
    tracing.start_profile("/tmp/spans-prof-test", python_tracer_level=0)
    tracing.stop_profile()
    (outdir, options), = calls
    assert outdir == "/tmp/spans-prof-test"
    assert options.python_tracer_level == 0


# -- across threads -----------------------------------------------------------

def test_parent_and_trace_id_cross_a_pipeline_worker_thread(traced):
    def produce():
        for i in range(3):
            with tracing.span("produce.item", i=i):
                yield i

    collector = M.QueryMetricsCollector()
    collector.trace_id = "trace-pipe"
    with M.collector_context(collector), tracing.span("query") as root:
        items = list(P.stage_iterator(produce(), edge="unit"))
    assert items == [0, 1, 2]
    spans = tracing.recorded()
    edge, = by_name(spans, "pipeline.unit")
    assert edge["parent"] == root.id
    assert edge["thread"] == "srt-pipe-unit" != threading.current_thread().name
    made = by_name(spans, "produce.item")
    assert [s["counts"]["i"] for s in made] == [0, 1, 2]
    for s in made:
        assert s["parent"] == edge["id"] and s["thread"] == "srt-pipe-unit"
        assert s["trace"] == "trace-pipe"
        assert ancestors(spans, s) == ["pipeline.unit", "query"]


def test_parent_and_trace_id_cross_an_endpoint_worker_thread(traced):
    session = TpuSession()
    session.create_or_replace_temp_view("t", session.create_dataframe(
        pa.table({"a": [1, 2, 3, 4], "b": [1.0, 2.0, 3.0, 4.0]})))
    endpoint = session.serve(port=0)
    try:
        client = EndpointClient(("127.0.0.1", endpoint.port), timeout_s=120)
        rows = client.submit(
            "select a, sum(b) s from t where a > 1 group by a order by a",
            trace="trace-client").to_pylist()
    finally:
        endpoint.shutdown()
    assert [r["a"] for r in rows] == [2, 3, 4]
    spans = tracing.recorded()
    request, = by_name(spans, "endpoint.request")
    parse, = by_name(spans, "sql.parse")
    query, = by_name(spans, "query")
    assert request["parent"] is None
    assert parse["parent"] == request["id"]
    assert parse["thread"] == request["thread"]
    assert query["parent"] == request["id"]
    assert query["thread"].startswith("srt-endpoint-w")
    assert query["thread"] != request["thread"]
    assert request["counts"]["batches"] == 1 and request["counts"]["rows"] == 3
    assert request["counts"]["bytes"] > 0
    for name in ("query.plan", "query.admission", "endpoint.encode",
                 "endpoint.send"):
        s, = by_name(spans, name)
        assert s["parent"] == query["id"] and s["thread"] == query["thread"]
    # every span the query made, on whatever thread, is in its tree and
    # carries the client's trace id
    workers = {s["thread"] for s in spans
               if "query" in ancestors(spans, s)}
    assert any(t.startswith("srt-pipe-") for t in workers)
    for s in spans:
        if s is not request and s is not parse:
            assert ancestors(spans, s)[-1] == "endpoint.request", s
            assert s["trace"] == "trace-client", s


# -- the scan's spans ------------------------------------------------------

def test_a_planted_rle_run_shows_as_one_column_decoded_by_runs(
        traced, tmp_path):
    n = 4096
    r = np.random.default_rng(11)
    plain = r.integers(0, 9, n)
    planted = r.integers(0, 9, n)
    # eight-value groups of one value: Parquet's hybrid encoder writes a run
    planted[1024:1024 + 64] = 5
    t = pa.table({"plain": plain, "planted": planted,
                  "price": r.integers(0, 50, n).astype(np.float64),
                  "unique": r.random(n)})    # no dictionary: pyarrow reads it
    f = str(tmp_path / "rle.parquet")
    pq.write_table(t, f, compression="NONE", use_dictionary=["plain",
                   "planted", "price"])
    schema = T.StructType.from_arrow(t.schema)
    got = PN.read_row_group_device(f, 0, schema).to_arrow()
    for name in t.column_names:
        assert got.column(name).to_pylist() == t.column(name).to_pylist()
    spans = tracing.recorded()
    cols = {s["counts"]["column"]: s for s in by_name(spans, "scan.column")}
    assert {c: s["counts"]["path"] for c, s in cols.items()} == {
        "plain": "fused", "planted": "fused", "price": "fused",
        "unique": "fallback"}
    # the run does not change the path: one program either way, and the
    # span says which and from how many rows of the segment table
    assert {c: s["counts"].get("decode") for c, s in cols.items()} == {
        "plain": "packed", "planted": "runs", "price": "packed",
        "unique": None}
    assert cols["planted"]["counts"]["rle"] >= 1
    assert cols["planted"]["counts"]["segments"] >= 2
    assert cols["plain"]["counts"]["segments"] == 1
    assert cols["plain"]["counts"]["rle"] == 0
    assert cols["plain"]["counts"]["packed"] >= 1
    for s in cols.values():
        assert s["counts"]["decoded_bytes"] > 0
        assert s["counts"]["encoded_bytes"] > 0
    assert cols["planted"]["counts"]["pages"] >= 1
    assert not by_name(spans, "scan.page")


def test_a_chunk_s_host_work_is_its_read_then_its_stage_or_fallback(
        traced, tmp_path):
    """Under each ``scan.column``: ``scan.read`` (file read, page scan,
    dictionary), then ``scan.stage`` (host preparation and puts, up to the
    decode's dispatch) for a chunk the fused decode takes, or
    ``scan.fallback`` (pyarrow) with the reason it left the fused path."""
    n = 4096
    r = np.random.default_rng(37)
    t = pa.table({"coded": r.integers(0, 9, n),    # nine values: 4 bits
                  "plain": r.random(n)})
    f = str(tmp_path / "phases.parquet")
    pq.write_table(t, f, compression="NONE", use_dictionary=["coded"])
    md = pq.ParquetFile(f).metadata
    got = PN.read_row_group_device(
        f, 0, T.StructType.from_arrow(t.schema)).to_arrow()
    assert got.to_pylist() == t.to_pylist()
    spans = tracing.recorded()
    cols = {s["counts"]["column"]: s for s in by_name(spans, "scan.column")}
    # the chunk's own span counts what it counted before
    assert {c: s["counts"]["path"] for c, s in cols.items()} == {
        "coded": "fused", "plain": "fallback"}
    assert set(cols["coded"]["counts"]) == {
        "column", "path", "decode", "segments", "pages", "packed", "rle",
        "encoded_bytes", "decoded_bytes"}
    assert set(cols["plain"]["counts"]) == {
        "column", "path", "encoded_bytes", "decoded_bytes"}
    kids = {c: {k["name"]: k for k in spans if k["parent"] == s["id"]}
            for c, s in cols.items()}
    assert set(kids["coded"]) == {"scan.read", "scan.stage"}
    assert set(kids["plain"]) == {"scan.read", "scan.fallback"}
    assert kids["coded"]["scan.read"]["counts"] == {
        "bytes": md.row_group(0).column(0).total_compressed_size,
        "pages": 1, "native": 1}
    # the page walk raised before its first page: the read has its bytes
    assert kids["plain"]["scan.read"]["counts"] == {
        "bytes": md.row_group(0).column(1).total_compressed_size}
    # five puts: the 2,048 packed bytes, the nine int64 dictionary values,
    # 4,096 definition levels, the present and the row count
    assert kids["coded"]["scan.stage"]["counts"] == {
        "values": n, "arrays": 5, "bytes": 2048 + 9 * 8 + n + 4 + 4}
    assert kids["plain"]["scan.fallback"]["counts"] == {
        "rows": n, "reason": "encodings",
        "bytes": cols["plain"]["counts"]["decoded_bytes"]}
    for c, s in cols.items():
        first, second = sorted(kids[c].values(), key=lambda k: k["t0"])
        assert first["name"] == "scan.read"
        assert s["t0"] <= first["t0"] <= first["t1"] <= second["t0"] \
            <= second["t1"] <= s["t1"]
        assert first["thread"] == second["thread"] == s["thread"]


@pytest.mark.parametrize("codec, options, reason", [
    ("BROTLI", {}, "codec"),
    # a dictionary past its page's limit: the writer goes on in PLAIN pages
    ("NONE", {"dictionary_pagesize_limit": 256, "data_page_size": 256,
              "write_batch_size": 64}, "page")])
def test_a_fallback_counts_why_the_chunk_left_the_fused_path(
        traced, tmp_path, codec, options, reason):
    t = pa.table({"coded": np.arange(1000)})
    f = str(tmp_path / "why.parquet")
    pq.write_table(t, f, compression=codec, use_dictionary=True, **options)
    got = PN.read_row_group_device(f, 0, T.StructType.from_arrow(t.schema))
    assert got.to_arrow().to_pylist() == t.to_pylist()
    (fb,) = by_name(tracing.recorded(), "scan.fallback")
    assert fb["counts"]["reason"] == reason


def test_a_collect_is_one_span_a_result_batch_under_its_query(
        traced, tmp_path):
    for i in range(2):
        pq.write_table(pa.table({"a": np.arange(100 * i, 100 * i + 60)}),
                       str(tmp_path / f"part-{i}.parquet"))
    out = TpuSession().read_parquet(str(tmp_path),
                                    files_per_partition=1).collect()
    assert out.num_rows == 120
    spans = tracing.recorded()
    got = by_name(spans, "collect.to_arrow")
    assert len(got) == 2
    assert sum(s["counts"]["rows"] for s in got) == out.num_rows
    for s in got:
        assert ancestors(spans, s)[-1] == "query"
        assert s["counts"]["columns"] == 1 and s["counts"]["bytes"] > 0


# -- the joins' spans ---------------------------------------------------------

def test_a_join_says_how_each_build_is_probed(traced):
    """A chain of two joins and a join on duplicate keys through the
    session: every build's
    `HashJoin.build_prep` is a child of its build span and carries the probe
    mode with what decided it; every probe span carries its hops' modes, and
    the chain's how its output landed at its bucket."""
    spark = TpuSession()
    n = 3000
    r = np.random.default_rng(4)
    fact = spark.create_dataframe(pa.table({
        "k": pa.array(r.integers(0, 500, n) + 10_000, pa.int64()),
        "j": pa.array(r.integers(0, 40, n), pa.int64()),
        "d": pa.array(r.integers(0, 9, n), pa.int64())}))
    uniq = spark.create_dataframe(pa.table({
        "k": pa.array(np.arange(400) + 10_000, pa.int64()),
        "a": pa.array(np.arange(400), pa.int64())}))
    uniq2 = spark.create_dataframe(pa.table({
        "j": pa.array(np.arange(40), pa.int64()),
        "b": pa.array(np.arange(40) * 2, pa.int64())}))
    dup = spark.create_dataframe(pa.table({
        "d": pa.array([1, 1, 2, 3], pa.int64()),
        "c": pa.array([5, 6, 7, 8], pa.int64())}))
    assert fact.join(uniq, on="k").join(uniq2, on="j").collect().num_rows > 0
    assert fact.join(dup, on="d").collect().num_rows > 0
    spans = tracing.recorded()
    preps = by_name(spans, "HashJoin.build_prep")
    by_rows = {s["counts"]["rows"]: s["counts"] for s in preps}
    assert {rows: c["mode"] for rows, c in by_rows.items()} == {
        400: "dense", 40: "dense", 4: "two"}
    assert by_rows[400]["domain"] == 400 and by_rows[400]["table_slots"] == 512
    assert by_rows[40]["domain"] == 40 and by_rows[40]["table_slots"] == 64
    assert by_rows[4]["domain"] == 3 and by_rows[4]["table_slots"] == 0
    for s in preps:
        assert s["counts"]["capacity"] >= s["counts"]["rows"]
        assert ancestors(spans, s)[0] in ("HashJoin.build",
                                          "BroadcastHashJoin.build")
    chain = by_name(spans, "HashJoinChain.probe")
    assert chain and {s["counts"]["modes"] for s in chain} == {"dense+dense"}
    # how the chain's output came to its bucket: the one stream batch runs
    # at its own capacity, and its 2,367 survivors need that bucket
    (probe,) = chain
    (read,) = [s for s in by_name(spans, "sync.count")
               if s["parent"] == probe["id"]]
    assert read["counts"] == {"rows": 2367, "capacity": 4096}
    assert {k: probe["counts"][k]
            for k in ("landed", "capacity_pred", "capacity_out")} == {
        "landed": "hit", "capacity_pred": 4096, "capacity_out": 4096}
    # no hop's key is a build column, so no build column is gathered at its
    # hop; of the four, the USING join's projection hoisted into the second
    # hop drops `uniq.k`, and the other three are gathered after the
    # compaction
    assert (probe["counts"]["deferred_cols"], probe["counts"]["hop_cols"]) \
        == (3, 0), probe["counts"]
    single = by_name(spans, "HashJoin.probe")
    assert single and {s["counts"]["mode"] for s in single} == {"two"}


# -- under the profiler: one clock, and names on the device programs --------

@pytest.fixture(scope="module")
def q1_capture(tmp_path_factory):
    """TPC-H q1 at SF 0.01 with tracing on: one warm run, then one run under
    jax.profiler. Returns (spans of the captured run, host events, module
    names of the programs that ran)."""
    from jax.profiler import ProfileData
    paths = tpch.generate(0.01, str(tmp_path_factory.mktemp("tpch")))
    automatic = gc.isenabled()
    gc.disable()        # one collection, in the capture, below
    tracing.set_enabled(True)
    try:
        # the device decode is the chip's scan path; the CPU platform only
        # takes it when told to
        spark = TpuSession({
            "spark.rapids.tpu.pipeline.enabled": True,
            "spark.rapids.tpu.sql.parquet.deviceDecode.enabled": "true"})
        tpch.load(spark, paths, files_per_partition=2)
        spark.sql(SQL_QUERIES["q1"]).collect()
        tracing.drain()
        logdir = str(tmp_path_factory.mktemp("capture"))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=options)
        try:
            gc.collect()
            rows = spark.sql(SQL_QUERIES["q1"]).collect().num_rows
        finally:
            jax.profiler.stop_trace()
        spans = tracing.drain()
    finally:
        tracing.set_enabled(False)
        if automatic:
            gc.enable()
    assert rows == 4
    xplane, = glob.glob(logdir + "/plugins/profile/*/*.xplane.pb")
    events, modules = [], set()
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_module" in stats:
                    modules.add(str(stats["hlo_module"]))
                events.append((e.name, e.start_ns, stats))
    return spans, events, modules


def test_every_span_is_a_host_range_of_the_capture_on_one_clock(q1_capture):
    spans, events, _ = q1_capture
    root, = by_name(spans, "query")
    # the root's annotation says when it opened on the program's clock
    marks = [(start, stats) for name, start, stats in events
             if name == "query" and stats.get("query") ==
             root["counts"]["query"]]
    (start_ns, stats), = marks
    assert stats["t0_ns"] == root["t0"]
    offset = start_ns - root["t0"]
    starts = {}
    for name, start, _ in events:
        starts.setdefault(name, []).append(start)
    assert len(spans) > 20
    for want in ("sql.parse", "query.plan", "query.admission",
                 "FileScan.devdecode", "scan.column", "scan.read",
                 "scan.stage", "HashAggregate.agg", "collect.to_arrow", "gc"):
        assert by_name(spans, want), want
    for s in spans:
        nearest = min(abs(x - (s["t0"] + offset))
                      for x in starts.get(s["name"], [float("inf")]))
        assert nearest < 1e6, (s["name"], nearest)


def test_fused_programs_carry_their_kernels_names(q1_capture):
    _, _, modules = q1_capture
    fused = {m for m in modules if m.startswith("jit_srt_")}
    assert any(m.startswith("jit_srt_HashAggregateExec") for m in fused), \
        modules
    assert not any(m.startswith("jit_traced") for m in modules), modules


def test_program_names_are_identifiers_of_low_cardinality():
    assert fuse.program_name("HashJoin.emit") == "srt_HashJoin_emit"
    assert fuse.program_name("host compact-2") == "srt_host_compact_2"
    k1 = fuse.BatchKernel(lambda x: x + 1, "ParquetScan.decode", key=("a", 1))
    k2 = fuse.BatchKernel(lambda x: x + 2, "ParquetScan.decode", key=("a", 2))
    x = jax.numpy.ones(4)
    names = {k._jit.lower(x).as_text().split("@", 1)[1].split(" ", 1)[0]
             for k in (k1, k2)}
    assert names == {"jit_srt_ParquetScan_decode"}


def test_operator_scopes_reach_the_op_metadata():
    from spark_rapids_tpu.expr.core import Col
    from spark_rapids_tpu.ops import joining as J

    def kernel(b, s):
        bk = [Col(b, jax.numpy.ones(8, bool), T.LONG)]
        sk = [Col(s, jax.numpy.ones(8, bool), T.LONG)]
        return J.join_ranks(bk, 8, 8, sk, 8, 8)

    k = fuse.BatchKernel(kernel, "HashJoin.probe")
    x = jax.numpy.arange(8)
    text = k._jit.lower(x, x).as_text(debug_info=True)
    assert "jit(srt_HashJoin_probe)/join_ranks/tuple_ranks/" in text
