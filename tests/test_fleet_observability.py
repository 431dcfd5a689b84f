"""Fleet observability plane tests (PR 18): cross-replica query journeys
(one journey id spanning submit_with_retry's replica rotation, terminal
``query.journey`` records per attempt, profiler.py journey's merged
failover timeline), the fleet-wide stats rollup (aggregate == sum of
per-replica counters, dead replicas reported UNREACHABLE in place), the
black-box flight recorder (bounded ring fed by eventlog.emit, dump on
stuck-query detection, the dump path riding the victim's lease record
into the survivor's ``fleet.adopt``), SLO accounting, and the
trace-id-stable-across-failover regression."""

import json
import os
import pathlib
import signal
import subprocess
import sys
import threading
import time

import pyarrow as pa
import pytest

from spark_rapids_tpu.runtime import blackbox, eventlog, faults
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime.endpoint import (EndpointClient, QueryEndpoint,
                                               merge_fleet_stats,
                                               parse_stats_text,
                                               render_fleet_stats)
from spark_rapids_tpu.runtime.fleet import FleetDirectory
from spark_rapids_tpu.session import TpuSession

SQL = "select k % 5 kk, sum(v) s, count(*) c from t group by kk order by kk"

REPO = pathlib.Path(__file__).resolve().parent.parent


def _session(extra=None):
    spark = TpuSession(dict(extra or {}))
    spark.create_or_replace_temp_view(
        "t", spark.create_dataframe(
            pa.table({"k": list(range(200)),
                      "v": [float(i) / 3 for i in range(200)]}),
            num_partitions=4))
    return spark


def _wait(pred, timeout_s=20.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return pred()


def _read_events(log_dir):
    out = []
    for f in sorted(pathlib.Path(log_dir).glob("*.jsonl")):
        for ln in f.read_text().splitlines():
            try:
                out.append(json.loads(ln))
            except ValueError:
                pass
    return out


def _journeys(records, jid=None):
    return [r for r in records if r.get("event") == "query.journey"
            and (jid is None or r.get("journey") == jid)]


def _profiler(*args):
    r = subprocess.run(
        [sys.executable, str(REPO / "tools" / "profiler.py"), *args],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    return r.returncode, r.stdout, r.stderr


@pytest.fixture(autouse=True)
def _clean_observability_plane():
    yield
    faults.reset()
    eventlog.shutdown()
    # the recorder is process-global: restore the default ring and drop the
    # dump directory so one test's config cannot leak into the next
    blackbox.reset()
    blackbox.configure(max_events=blackbox.DEFAULT_MAX_EVENTS)
    blackbox._dir = None


# -- query journeys ------------------------------------------------------------

def test_journey_served_then_cached_records(tmp_path):
    spark = _session({
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
        "spark.rapids.tpu.endpoint.resultCache.enabled": True})
    ep = QueryEndpoint(spark)
    cli = EndpointClient(("127.0.0.1", ep.port), timeout_s=30)
    try:
        first = cli.submit(SQL).to_pylist()
        j1 = cli.last_journey
        assert cli.submit(SQL).to_pylist() == first
        j2 = cli.last_journey
        assert j1 != j2 and j1.startswith("j-")
        # the summary frame echoes the journey plane
        s = cli.last_summary
        assert s["journey"] == j2 and s["attempt"] == 1
        assert s["replica"] == f"127.0.0.1:{ep.port}"
    finally:
        ep.shutdown(grace_s=5)
    eventlog.shutdown()

    recs = _read_events(tmp_path)
    (served,) = _journeys(recs, j1)
    assert served["outcome"] == "served" and served["attempt"] == 1
    assert served["replica"] == f"127.0.0.1:{ep.port}"
    assert served["wall_s"] >= 0 and isinstance(served["traces"], int)
    (cached,) = _journeys(recs, j2)
    assert cached["outcome"] == "cached" and cached["traces"] == 0
    assert cached["query"] == served["query"]   # replays the recorded run

    logs = sorted(str(f) for f in tmp_path.glob("*.jsonl"))
    rc, out, err = _profiler("journey", *logs)
    assert rc == 0, err
    assert "outcome served" in out and "outcome cached" in out


def test_journey_spans_failover_and_trace_rides_along(tmp_path):
    """The tentpole timeline: attempt 1 dies by replica timeout on a hung
    replica, attempt 2 serves on the next one — ONE journey id, and (the
    retry-trace regression) ONE trace id equal to it across both attempts."""
    spark = _session({
        "spark.rapids.tpu.eventLog.dir": str(tmp_path / "log"),
        "spark.rapids.tpu.fleet.dir": str(tmp_path / "fleet"),
        "spark.rapids.tpu.fleet.heartbeat.intervalSeconds": 0.2})
    ep_bad = QueryEndpoint(spark)
    ep_good = QueryEndpoint(spark)
    cli = EndpointClient([("127.0.0.1", ep_bad.port),
                          ("127.0.0.1", ep_good.port)], timeout_s=60)
    retries = []
    try:
        ep_bad.request_timeout = 0.3
        faults.configure("slow:agg.update:12", seed=1)
        rows = cli.submit_with_retry(
            SQL, on_retry=lambda a, d: (retries.append(a), faults.reset()),
        ).to_pylist()
        assert rows == spark.sql(SQL).collect().to_pylist()
        assert retries == [1]
        jid = cli.last_journey
        # the trace id defaults to the journey id and SURVIVES the retry:
        # the serving attempt's summary carries it, so both attempts' spans
        # share one distributed trace
        assert cli.last_summary["trace"] == jid
        assert cli.last_summary["attempt"] == 2
        bad_rid, good_rid = (ep_bad.fleet.replica_id,
                             ep_good.fleet.replica_id)
    finally:
        faults.reset()
        ep_bad.request_timeout = 0.0
        ep_bad.shutdown(grace_s=5)
        ep_good.shutdown(grace_s=5)
    eventlog.shutdown()

    recs = _read_events(tmp_path / "log")
    jrecs = sorted(_journeys(recs, jid), key=lambda r: r["attempt"])
    assert [r["attempt"] for r in jrecs] == [1, 2]
    assert jrecs[0]["outcome"] == "replica_timeout"
    assert jrecs[0]["replica"] == bad_rid
    assert jrecs[1]["outcome"] == "served"
    assert jrecs[1]["replica"] == good_rid

    logs = sorted(str(f) for f in (tmp_path / "log").glob("*.jsonl"))
    rc, out, err = _profiler("journey", *logs, "--journey", jid, "--json")
    assert rc == 0, err
    (jn,) = json.loads(out)["journeys"]
    assert jn["failovers"] == 1 and jn["outcome"] == "served"
    assert jn["attempts"][1]["failover_from"] == bad_rid
    assert len(jn["replicas"]) == 2


def test_explicit_trace_id_is_preserved_across_retry():
    spark = _session()
    ep = QueryEndpoint(spark)
    cli = EndpointClient(("127.0.0.1", ep.port), timeout_s=30)
    try:
        cli.submit_with_retry(SQL, trace="tr-explicit")
        assert cli.last_summary["trace"] == "tr-explicit"
        assert cli.last_summary["journey"] == cli.last_journey
    finally:
        ep.shutdown(grace_s=5)


# -- SLO layer -----------------------------------------------------------------

def test_slo_breach_accounting_and_stats(tmp_path):
    spark = _session({
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
        "spark.rapids.tpu.endpoint.slo.latencyTargetSeconds": 1e-4})
    ep = QueryEndpoint(spark)
    cli = EndpointClient(("127.0.0.1", ep.port), timeout_s=30)
    try:
        cli.submit(SQL)
        # the terminal journey record lands just after the summary frame
        assert _wait(lambda: ep.slo.snapshot()["served"] == 1)
        snap = ep.slo.snapshot()
        assert snap["breaches"] == 1
        assert snap["availability"] == 1.0   # slow, but it DID serve
        text = cli.stats()
        assert 'srt_slo_latency_target_seconds 0.0001' in text
        assert 'srt_slo_total{event="breaches"} 1' in text
        health = ep._fleet_health()
        assert health["slo"]["breaches"] == 1
    finally:
        ep.shutdown(grace_s=5)
    eventlog.shutdown()
    breaches = [r for r in _read_events(tmp_path)
                if r.get("event") == "slo.breach"]
    assert breaches and breaches[0]["journey"] == cli.last_journey
    assert breaches[0]["wall_s"] > breaches[0]["target_s"]


# -- fleet stats rollup --------------------------------------------------------

def test_fleet_stats_aggregate_equals_per_replica_sum():
    spark1, spark2 = _session(), _session()
    ep1, ep2 = QueryEndpoint(spark1), QueryEndpoint(spark2)
    try:
        EndpointClient(("127.0.0.1", ep1.port), timeout_s=30).submit(SQL)
        EndpointClient(("127.0.0.1", ep2.port), timeout_s=30).submit(SQL)
        # a dead address rides in the list: reported, never hides the rest
        cli = EndpointClient([("127.0.0.1", ep1.port),
                              ("127.0.0.1", ep2.port),
                              ("127.0.0.1", 1)], timeout_s=10)
        fs = cli.fleet_stats()
        assert fs["live"] == 2 and fs["total"] == 3
        live = [r for r in fs["replicas"].values() if r["ok"]]
        assert len(live) == 2
        dead = fs["replicas"]["127.0.0.1:1"]
        assert not dead["ok"] and dead["error"]
        for series, total in fs["aggregate"]["counters"].items():
            assert total == pytest.approx(
                sum(r["counters"].get(series, 0.0) for r in live)), series
        # a counter that definitely moved shows up in the aggregate (both
        # endpoints share this process's metrics registry, so assert the
        # sum invariant rather than an absolute count)
        admitted = "srt_queries_admitted_total"
        per_rep = [r["counters"][admitted] for r in live]
        assert fs["aggregate"]["counters"][admitted] == sum(per_rep) >= 2.0
        text = render_fleet_stats(fs)
        assert "UNREACHABLE" in text
        assert "fleet aggregate (2/3 replicas)" in text
        assert admitted in text
    finally:
        ep1.shutdown(grace_s=5)
        ep2.shutdown(grace_s=5)


def test_parse_stats_text_counters_and_gauges():
    text = ("# HELP srt_x things\n"
            "# TYPE srt_x counter\n"
            'srt_x{k="a"} 3\n'
            'srt_x{k="b"} 4.5\n'
            "# TYPE srt_g gauge\n"
            "srt_g 7\n"
            "# TYPE srt_h histogram\n"
            'srt_h_bucket{le="1"} 9\n')
    parsed = parse_stats_text(text)
    assert parsed["counters"] == {'srt_x{k="a"}': 3.0, 'srt_x{k="b"}': 4.5}
    assert parsed["gauges"] == {"srt_g": 7.0}
    merged = merge_fleet_stats({"a:1": text, "a:2": text,
                                "a:3": OSError("down")})
    assert merged["live"] == 2 and merged["total"] == 3
    assert merged["aggregate"]["counters"]['srt_x{k="a"}'] == 6.0


def test_tpu_client_stats_fans_out_and_fleet_stats_cli(tmp_path):
    spark = _session()
    ep = QueryEndpoint(spark)
    try:
        EndpointClient(("127.0.0.1", ep.port), timeout_s=30).submit(SQL)
        from tools import tpu_client
        addresses = f"127.0.0.1:{ep.port},127.0.0.1:1"
        # stats: one live + one dead replica -> rc 0, both sections printed
        assert tpu_client.main(["--addresses", addresses, "stats"]) == 0
        assert tpu_client.main(["--addresses", addresses,
                                "fleet-stats"]) == 0
        # no replica reachable -> rc 2 for both modes
        assert tpu_client.main(["--addresses", "127.0.0.1:1", "stats"]) == 2
        assert tpu_client.main(["--addresses", "127.0.0.1:1",
                                "fleet-stats"]) == 2
    finally:
        ep.shutdown(grace_s=5)


# -- black-box flight recorder -------------------------------------------------

def test_blackbox_ring_is_bounded_and_default_on(tmp_path):
    assert blackbox.enabled()   # default on, no configuration needed
    eventlog.configure(str(tmp_path))
    blackbox.configure(max_events=4, directory=str(tmp_path))
    blackbox.reset()
    for i in range(10):
        eventlog.emit("endpoint.start", query=None, seq=i)
    assert blackbox.ring_len() == 4   # bounded: only the most recent kept
    blackbox.set_inflight_provider(
        lambda: [{"query": "q-1", "journey": "j-t", "sql": SQL}])
    path = blackbox.dump("test_reason")
    assert path == str(tmp_path / f"blackbox-{os.getpid()}.json")
    bb = json.loads(pathlib.Path(path).read_text())
    assert bb["reason"] == "test_reason" and bb["pid"] == os.getpid()
    assert [e["seq"] for e in bb["events"]] == [6, 7, 8, 9]
    assert bb["inflight"][0]["journey"] == "j-t"
    # per-reason throttle: an immediate second dump is suppressed
    assert blackbox.dump("test_reason") is None
    assert blackbox.dump("other_reason") is not None
    # the dump announces itself in the event log
    eventlog.shutdown()
    dumps = [r for r in _read_events(tmp_path)
             if r.get("event") == "blackbox.dump"]
    assert dumps and dumps[0]["reason"] == "test_reason"
    assert dumps[0]["inflight"] == 1


def test_blackbox_disabled_and_unconfigured_are_noops(tmp_path):
    blackbox.configure(max_events=0)
    assert not blackbox.enabled() and blackbox.ring_len() == 0
    eventlog.configure(str(tmp_path))
    eventlog.emit("endpoint.start", query=None)
    assert blackbox.ring_len() == 0
    assert blackbox.dump("whatever") is None   # no ring -> no dump
    blackbox.configure(max_events=8)           # re-enable, but no directory
    blackbox._dir = None
    eventlog.emit("endpoint.start", query=None)
    assert blackbox.ring_len() == 1
    assert blackbox.dump_path() is None
    assert blackbox.dump("whatever") is None   # no directory -> no dump


def test_blackbox_overhead_contract_without_eventlog():
    """eventlog.emit is the ring's only feeder: with no event log configured
    emit() returns before building a record, so the recorder's steady-state
    cost in an untelemetered process is literally nothing."""
    eventlog.shutdown()
    blackbox.reset()
    eventlog.emit("endpoint.start", query=None)
    assert blackbox.ring_len() == 0


def test_session_knobs_configure_recorder(tmp_path):
    _session({"spark.rapids.tpu.eventLog.dir": str(tmp_path),
              "spark.rapids.tpu.flightRecorder.maxEvents": 7})
    assert blackbox.enabled()
    assert blackbox._ring.maxlen == 7
    assert blackbox.dump_path() == str(
        tmp_path / f"blackbox-{os.getpid()}.json")


def test_fleet_adopt_carries_blackbox_pointer(tmp_path):
    fleet_dir, log_dir = tmp_path / "fleet", tmp_path / "log"
    log_dir.mkdir()
    eventlog.configure(str(log_dir))
    dead = FleetDirectory(str(fleet_dir), lease_timeout_s=0.2,
                          heartbeat_interval_s=0)
    dead.register("127.0.0.1", 1111,
                  extra={"lease_timeout_s": 0.2,
                         "blackbox": "/scratch/blackbox-1111.json"})
    dead._hb_stop.set()   # simulate the SIGKILL: record left behind
    time.sleep(0.4)
    survivor = FleetDirectory(str(fleet_dir), lease_timeout_s=0.2,
                              heartbeat_interval_s=0)
    survivor.register("127.0.0.1", 2222)
    survivor.renew()
    assert survivor.sweep_expired() == [dead.replica_id]
    # the victim's final record became a departed- tombstone
    (tomb,) = survivor.departed()
    assert tomb["replica"] == dead.replica_id
    assert tomb["blackbox"] == "/scratch/blackbox-1111.json"
    assert tomb["adopted_by"] == survivor.replica_id
    assert tomb["departed"] > 0
    survivor.deregister()
    eventlog.shutdown()
    (adopt,) = [r for r in _read_events(log_dir)
                if r.get("event") == "fleet.adopt"]
    assert adopt["blackbox"] == "/scratch/blackbox-1111.json"
    assert adopt["replica"] == dead.replica_id
    # the roster still explains the dead replica
    rc, out, err = _profiler("fleet", str(fleet_dir), "--json")
    assert rc == 0, err
    roster = json.loads(out)
    assert roster["departed"] == 1
    (gone,) = [r for r in roster["replicas"] if r["status"] == "departed"]
    assert gone["blackbox"] == "/scratch/blackbox-1111.json"


def test_profiler_fleet_judges_liveness_from_embedded_timeout(tmp_path):
    fd = FleetDirectory(str(tmp_path), lease_timeout_s=0.2,
                        heartbeat_interval_s=0)
    fd.register("127.0.0.1", 1, extra={"lease_timeout_s": 0.2})
    rc, out, _ = _profiler("fleet", str(tmp_path), "--json")
    assert rc == 0
    assert json.loads(out)["replicas"][0]["status"] == "live"
    time.sleep(0.4)
    rc, out, _ = _profiler("fleet", str(tmp_path), "--json")
    assert json.loads(out)["replicas"][0]["status"] == "expired"
    fd.deregister()
    rc, _, err = _profiler("fleet", str(tmp_path))
    assert rc == 1 and "no membership records" in err


# -- heartbeat health roster ---------------------------------------------------

def test_lease_record_embeds_health_rollup(tmp_path):
    spark = _session({
        "spark.rapids.tpu.fleet.dir": str(tmp_path),
        "spark.rapids.tpu.fleet.heartbeat.intervalSeconds": 0.2,
        "spark.rapids.tpu.endpoint.resultCache.enabled": True})
    ep = QueryEndpoint(spark)
    cli = EndpointClient(("127.0.0.1", ep.port), timeout_s=30)
    try:
        cli.submit(SQL)
        cli.submit(SQL)   # a result-cache hit for the hit-rate gauge

        def _health():
            m = ep.fleet.members()
            return m[0].get("health") if m else None

        assert _wait(lambda: (_health() or {}).get("result_cache",
                                                   {}).get("hits") == 1)
        h = _health()
        assert h["active_queries"] == 0
        assert h["result_cache"] == {"hits": 1, "misses": 1}
        assert "hbm_watermark_bytes" in h and "fuse" in h
        assert h["resilience"] == {} or all(h["resilience"].values())
        m = ep.fleet.members()[0]
        assert m["lease_timeout_s"] == ep.fleet.lease_timeout_s
        rc, out, err = _profiler("fleet", str(tmp_path))
        assert rc == 0, err
        assert "[live]" in out and "result_cache 1h/1m" in out
    finally:
        ep.shutdown(grace_s=5)


# -- SIGKILL: the dump survives, the survivor explains it ----------------------

def _spawn_victim(fleet_dir, log_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, str(REPO / "tools" / "fleet_replica.py"),
         "--fleet-dir", str(fleet_dir), "--synthetic", "200",
         "--lease-timeout", "3", "--heartbeat", "0.5",
         "--request-timeout", "1.0",
         "--eventlog-dir", str(log_dir),
         "--faults", "hang:endpoint.send:1"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env)
    deadline = time.monotonic() + 240
    port = None
    while time.monotonic() < deadline:
        ln = proc.stdout.readline()
        if ln.startswith("READY "):
            port = int(ln.split()[1])
            break
        if proc.poll() is not None:
            break
    if port is None:
        proc.kill()
        raise AssertionError("victim replica never became READY")
    threading.Thread(target=proc.stdout.read, daemon=True).start()
    return proc, port


@pytest.mark.slow
def test_sigkill_blackbox_dump_and_merged_journey(tmp_path):
    """The post-mortem contract end to end with a real victim PROCESS: the
    hung victim's heartbeat watchdog dumps the flight recorder (naming
    the in-flight journey) and closes the journey as replica_timeout
    BEFORE the SIGKILL; the in-process survivor serves attempt 2, adopts
    the lease with the blackbox path on fleet.adopt, and profiler.py
    journey renders the whole story from the merged logs."""
    fleet_dir, log_dir = tmp_path / "fleet", tmp_path / "log"
    log_dir.mkdir()
    spark = TpuSession({
        "spark.rapids.tpu.eventLog.dir": str(log_dir),
        "spark.rapids.tpu.fleet.dir": str(fleet_dir),
        "spark.rapids.tpu.fleet.lease.timeoutSeconds": 3,
        "spark.rapids.tpu.fleet.heartbeat.intervalSeconds": 0.5})
    spark.create_or_replace_temp_view(
        "t", spark.create_dataframe(
            pa.table({"k": pa.array([i % 50 for i in range(200)],
                                    type=pa.int64()),
                      "v": pa.array([float(i) for i in range(200)],
                                    type=pa.float64())}),
            num_partitions=2))
    oracle = spark.sql(SQL).collect().to_pylist()
    ep = QueryEndpoint(spark)
    victim, vport = _spawn_victim(fleet_dir, log_dir)
    bb_path = log_dir / f"blackbox-{victim.pid}.json"
    flight = {}
    try:
        cli = EndpointClient([("127.0.0.1", vport), ("127.0.0.1", ep.port)],
                             timeout_s=120)

        def run():
            try:
                flight["rows"] = cli.submit_with_retry(SQL).to_pylist()
                flight["journey"] = cli.last_journey
            except BaseException as e:  # noqa: BLE001 — asserted below
                flight["error"] = repr(e)[:200]

        t = threading.Thread(target=run, daemon=True)
        t.start()
        # let the query hang at its first result frame, age past the 1s
        # request timeout, and a 0.5s heartbeat run the watchdog + dump
        assert _wait(bb_path.exists, timeout_s=30), \
            "victim never dumped its flight recorder"
        os.kill(victim.pid, signal.SIGKILL)
        t.join(timeout=240)
        assert flight.get("rows") == oracle, flight
        jid = flight["journey"]

        bb = json.loads(bb_path.read_text())
        assert bb["reason"] == "stuck_query" and bb["pid"] == victim.pid
        named = [i for i in bb["inflight"] if i["journey"] == jid]
        assert named and named[0]["sql"].startswith("select k % 5")
        assert named[0]["timed_out"] is True
        assert bb["events"]

        # the survivor adopts the victim's lease, blackbox pointer attached
        assert _wait(lambda: not (
            fleet_dir / f"replica-127.0.0.1-{vport}-{victim.pid}.json"
        ).exists(), timeout_s=30), "victim lease never adopted"
    finally:
        try:
            victim.kill()
        except OSError:
            pass
        victim.wait(timeout=30)
        ep.shutdown(grace_s=5)
    eventlog.shutdown()

    recs = _read_events(log_dir)
    (adopt,) = [r for r in recs if r.get("event") == "fleet.adopt"
                and r.get("dead_pid") == victim.pid]
    assert adopt["blackbox"] == str(bb_path)
    jrecs = sorted(_journeys(recs, jid), key=lambda r: r["attempt"])
    assert [r["outcome"] for r in jrecs] == ["replica_timeout", "served"]
    assert jrecs[0]["stuck"] is True and str(victim.pid) in jrecs[0]["replica"]
    assert jrecs[1]["traces"] == 0   # the survivor served from warm state

    logs = sorted(str(f) for f in log_dir.glob("*.jsonl"))
    rc, out, err = _profiler("journey", *logs, "--journey", jid, "--json")
    assert rc == 0, err
    (jn,) = json.loads(out)["journeys"]
    assert jn["failovers"] >= 1 and jn["outcome"] == "served"
    rc, out, err = _profiler("fleet", str(fleet_dir), "--json")
    assert rc == 0, err
    roster = json.loads(out)
    (gone,) = [r for r in roster["replicas"]
               if r["status"] == "departed" and r.get("pid") == victim.pid]
    assert gone["blackbox"] == str(bb_path)
    assert gone.get("health"), "tombstone lost the last-known health"
