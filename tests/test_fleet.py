"""Serving-fleet tests (runtime/fleet.py + the fleet halves of
runtime/endpoint.py): on-disk membership with lease expiry, exactly-once
adoption with write-intent reclaim, client replica lists with failover
rotation, the fleet-only retryable request-timeout rejection, the
parameterized-plan result cache (hit / catalog-epoch invalidation), the
multi-process shared-store contracts (history merge under the advisory
lock, stage-cache racing-prune degradation), and the headline chaos
scenario — a replica SIGKILLed mid-stream with the client failing over to
a survivor bit-identically."""

import gc
import os
import pathlib
import signal
import socket
import subprocess
import sys
import threading
import time

import pyarrow as pa
import pytest

from spark_rapids_tpu.runtime import faults
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import scheduler as SCHED
from spark_rapids_tpu.runtime import stage_cache
from spark_rapids_tpu.runtime.endpoint import (EndpointClient, QueryEndpoint,
                                               _parse_addresses)
from spark_rapids_tpu.runtime.fleet import FleetDirectory, _is_write_intent
from spark_rapids_tpu.runtime.history import PlanHistoryStore
from spark_rapids_tpu.runtime.result_cache import ResultCache
from spark_rapids_tpu.session import TpuSession

SQL = "select k % 5 kk, sum(v) s, count(*) c from t group by kk order by kk"


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


def _failovers():
    return M.resilience_snapshot()["replicaFailovers"]


# -- membership + lease --------------------------------------------------------

def test_register_members_lease_expiry_and_renew(tmp_path):
    fd = FleetDirectory(str(tmp_path), lease_timeout_s=0.3,
                        heartbeat_interval_s=0)
    rid = fd.register("127.0.0.1", 1234)
    assert rid == f"127.0.0.1-1234-{os.getpid()}"
    assert [m["replica"] for m in fd.members()] == [rid]
    assert fd.addresses() == [("127.0.0.1", 1234)]
    time.sleep(0.5)
    # the lease (mtime) expired: dropped from the live view, still on disk
    assert fd.members() == []
    assert [m["replica"] for m in fd.members(live_only=False)] == [rid]
    fd.renew()
    assert [m["replica"] for m in fd.members()] == [rid]
    fd.deregister()
    assert fd.members(live_only=False) == []


def test_renew_rewrites_a_vanished_record(tmp_path):
    fd = FleetDirectory(str(tmp_path), lease_timeout_s=5,
                        heartbeat_interval_s=0)
    fd.register("127.0.0.1", 1, stores=["/tmp/x"])
    (rec,) = tmp_path.glob("replica-*.json")
    rec.unlink()    # the fleet dir was cleaned underneath the replica
    fd.renew()
    assert [m["replica"] for m in fd.members()] == [fd.replica_id]
    assert fd.members()[0]["stores"] == ["/tmp/x"]
    fd.deregister()


def test_write_intent_matching():
    pid = 123
    assert _is_write_intent("e.xc.tmp.123", pid)            # stage cache
    assert _is_write_intent("e.xc.tmp.123-7", pid)          # threaded seq
    assert _is_write_intent("plan_history.json.tmp.123", pid)
    assert not _is_write_intent("e.xc.tmp.1234", pid)       # other pid
    assert not _is_write_intent("e.xc.tmp.999-123", pid)    # seq != owner
    assert not _is_write_intent("e.xc", pid)                # durable entry
    assert not _is_write_intent("e.tmp", pid)               # no pid marker


def test_sweep_adopts_expired_lease_and_reclaims_intents(tmp_path):
    fleet, store = tmp_path / "fleet", tmp_path / "store"
    store.mkdir()
    dead = FleetDirectory(str(fleet), lease_timeout_s=0.3,
                          heartbeat_interval_s=0)
    dead.register("127.0.0.1", 1111, stores=[str(store)])
    pid = os.getpid()
    orphans = [store / f"aa.xc.tmp.{pid}", store / f"bb.xc.tmp.{pid}-3"]
    keep = [store / "cc.xc.tmp.999999999",   # another replica's intent
            store / "dd.xc"]                 # a durable entry
    for f in orphans + keep:
        f.write_bytes(b"x")

    survivor = FleetDirectory(str(fleet), lease_timeout_s=0.3,
                              heartbeat_interval_s=0)
    survivor.register("127.0.0.1", 2222)
    time.sleep(0.5)
    survivor.renew()     # own lease fresh; the dead replica's is expired
    adoptions_before = M.resilience_snapshot()["fleetAdoptions"]

    assert survivor.sweep_expired() == [dead.replica_id]
    assert not any(f.exists() for f in orphans)
    assert all(f.exists() for f in keep)
    s = survivor.stats()
    assert s["adoptions"] == 1 and s["reclaimed_intents"] == 2
    assert M.resilience_snapshot()["fleetAdoptions"] == adoptions_before + 1
    # the dead replica's record is gone; a second sweep adopts nothing
    assert survivor.sweep_expired() == []
    assert [m["replica"] for m in survivor.members()] == [survivor.replica_id]
    survivor.deregister()


def test_adoption_is_exactly_once_across_concurrent_sweepers(tmp_path):
    dead = FleetDirectory(str(tmp_path), lease_timeout_s=0.2,
                          heartbeat_interval_s=0)
    dead.register("127.0.0.1", 1111)
    time.sleep(0.4)
    # two unregistered observers (e.g. standbys) race to adopt: the fleet
    # advisory lock serializes them, so exactly one wins
    sweepers = [FleetDirectory(str(tmp_path), lease_timeout_s=0.2,
                               heartbeat_interval_s=0) for _ in range(2)]
    barrier = threading.Barrier(2)

    def sweep(fd):
        barrier.wait()
        fd.sweep_expired()

    threads = [threading.Thread(target=sweep, args=(fd,)) for fd in sweepers]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert sum(fd.adoptions for fd in sweepers) == 1


def test_heartbeat_thread_renews_and_stops(tmp_path):
    fd = FleetDirectory(str(tmp_path), lease_timeout_s=5,
                        heartbeat_interval_s=0.1)
    fd.register("127.0.0.1", 1)
    assert _wait(lambda: fd.heartbeats >= 2)
    name = f"srt-fleet-hb-{1}"
    assert any(t.name == name for t in threading.enumerate())
    fd.deregister()
    assert _wait(lambda: not any(t.name == name
                                 for t in threading.enumerate()))


# -- client replica lists ------------------------------------------------------

def test_parse_addresses_forms():
    assert _parse_addresses(("h", 1)) == [("h", 1)]
    assert _parse_addresses("127.0.0.1:80") == [("127.0.0.1", 80)]
    assert _parse_addresses("h1:1, h2:2") == [("h1", 1), ("h2", 2)]
    assert _parse_addresses([("h1", 1), "h2:2"]) == [("h1", 1), ("h2", 2)]
    for bad in ("", ",", [], ":80"):
        with pytest.raises(ValueError):
            _parse_addresses(bad)


def test_rotate_single_address_is_a_noop():
    cli = EndpointClient(("h", 1))
    before = _failovers()
    assert cli.rotate() == ("h", 1)
    assert cli.address == ("h", 1) and _failovers() == before


def test_rotate_multi_address_counts_failovers():
    cli = EndpointClient("h1:1,h2:2,h3:3")
    before = _failovers()
    assert cli.address == ("h1", 1)
    assert cli.rotate() == ("h2", 2)
    assert cli.rotate() == ("h3", 3)
    assert cli.rotate() == ("h1", 1)     # wraps
    assert _failovers() == before + 3


def test_connection_refused_rotates_to_live_replica():
    spark = _session()
    ep = QueryEndpoint(spark)
    # a port that refuses: bound then released, nobody listening
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    dead_port = probe.getsockname()[1]
    probe.close()
    try:
        direct = spark.sql(SQL).collect().to_pylist()
        cli = EndpointClient([("127.0.0.1", dead_port),
                              ("127.0.0.1", ep.port)], timeout_s=30)
        before = _failovers()
        retries = []
        rows = cli.submit_with_retry(
            SQL, on_retry=lambda a, d: retries.append(a)).to_pylist()
        assert rows == direct
        assert retries and _failovers() >= before + 1
        assert cli.address == ("127.0.0.1", ep.port)
    finally:
        ep.shutdown(grace_s=5)


def test_fleet_request_timeout_is_retryable_rejection(tmp_path):
    spark = _session({
        "spark.rapids.tpu.fleet.dir": str(tmp_path / "fleet"),
        "spark.rapids.tpu.fleet.heartbeat.intervalSeconds": 0.2})
    ep = QueryEndpoint(spark)
    cli = EndpointClient(("127.0.0.1", ep.port), timeout_s=30)
    assert ep.fleet is not None
    try:
        direct = spark.sql(SQL).collect().to_pylist()
        ep.request_timeout = 0.3
        faults.configure("slow:agg.update:12", seed=1)
        # on a fleet the request-timeout kill surfaces RETRYABLE (the query
        # belongs on a surviving peer), not as the non-retryable typed
        # cancellation a solo endpoint keeps
        with pytest.raises(SCHED.QueryRejectedError) as ei:
            cli.submit(SQL)
        assert ei.value.reason == "replica_timeout"
        assert ei.value.backoff_hint_s > 0
        assert ei.value.replica == ep.fleet.replica_id
        assert _wait(lambda: ep.active_queries() == 0)
        faults.reset()
        ep.request_timeout = 0.0
        assert cli.submit_with_retry(SQL).to_pylist() == direct
    finally:
        faults.reset()
        ep.request_timeout = 0.0
        ep.shutdown(grace_s=5)
    # the clean shutdown deregistered this replica's lease
    assert not list((tmp_path / "fleet").glob("replica-*.json"))


# -- result cache --------------------------------------------------------------

def test_result_cache_lru_bounds_and_epoch_drop():
    rc = ResultCache(max_bytes=100, max_entries=2)
    k1, k2, k3 = (ResultCache.key(0, f"sig{i}", f"q{i}") for i in range(3))
    assert rc.put(k1, [b"x" * 40], {"q": 1})
    assert rc.put(k2, [b"y" * 40], {"q": 2})
    assert rc.get(k1)["summary"] == {"q": 1}   # refreshes k1's recency
    assert rc.put(k3, [b"z" * 40], {"q": 3})   # over budget: evicts LRU k2
    assert rc.get(k2) is None
    assert rc.get(k1) and rc.get(k3)
    assert rc.evictions == 1
    # a result larger than the whole byte budget is simply not admitted
    assert not rc.put(ResultCache.key(0, "big", "qb"), [b"w" * 200], {})
    # a newer catalog epoch drops every stale entry
    assert rc.put(ResultCache.key(1, "sig", "q"), [b"a"], {})
    assert rc.stale_drops == 2 and rc.get(k1) is None


def test_endpoint_result_cache_hit_and_catalog_invalidation():
    spark = _session({"spark.rapids.tpu.endpoint.resultCache.enabled": True})
    ep = QueryEndpoint(spark)
    cli = EndpointClient(("127.0.0.1", ep.port), timeout_s=30)
    assert ep.result_cache is not None
    try:
        first = cli.submit(SQL).to_pylist()
        assert not (cli.last_summary or {}).get("cached")
        # identical SQL: served bit-identically from the recorded frames,
        # without touching the scheduler
        second = cli.submit(SQL).to_pylist()
        assert second == first
        assert cli.last_summary.get("cached") is True
        assert ep.result_cache.hits == 1
        # catalog change: replacing the view bumps the session epoch, so
        # the stale result can never serve again
        spark.create_or_replace_temp_view(
            "t", spark.create_dataframe(
                pa.table({"k": list(range(200)),
                          "v": [float(i) for i in range(200)]}),
                num_partitions=4))
        third = cli.submit(SQL).to_pylist()
        assert not (cli.last_summary or {}).get("cached")
        assert third != first
        assert third == spark.sql(SQL).collect().to_pylist()
    finally:
        ep.shutdown(grace_s=5)


# -- shared-store multi-process contracts --------------------------------------

def test_stage_cache_racing_prune_is_warned_retrace(tmp_path):
    store = stage_cache.StageCacheStore(str(tmp_path))
    store.save("e1", b"payload")
    assert store.load("e1") == b"payload"
    # a peer replica's LRU prune unlinks the entry behind this store's back
    os.unlink(tmp_path / "e1.xc")
    with pytest.warns(RuntimeWarning, match="pruned by a concurrent"):
        assert store.load("e1") is None
    assert store.pruned_misses == 1
    # an entry this process never saw is a plain miss, not a pruned race
    assert store.load("never-seen") is None
    assert store.pruned_misses == 1 and store.misses == 2


def test_stage_cache_prune_tolerates_vanishing_files(tmp_path):
    store = stage_cache.StageCacheStore(str(tmp_path), max_bytes=64)
    store.save("a", b"x" * 40)
    store.save("b", b"y" * 40)   # prunes the older entry down to max_bytes
    assert store.entries() == ["b"]
    assert store.total_bytes() == 40


_HISTORY_CHILD = r"""
import sys, time
from spark_rapids_tpu.runtime.history import PlanHistoryStore
st = PlanHistoryStore(sys.argv[1])
for i in range(25):
    st.record(sys.argv[2], {"out_rows": i, "peak_device_bytes": 100 + i})
    time.sleep(0.002)
print("DONE", st.shape_count())
"""


@pytest.mark.slow
def test_history_two_process_merge_under_advisory_lock(tmp_path):
    """Two real writer PROCESSES hammer one history directory: without the
    cross-process advisory lock their load->merge->replace windows overlap
    and the later replace silently drops the other replica's shapes."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = (str(pathlib.Path(__file__).resolve().parent.parent)
                         + os.pathsep + env.get("PYTHONPATH", ""))
    procs = [subprocess.Popen(
        [sys.executable, "-c", _HISTORY_CHILD, str(tmp_path), fp],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env)
        for fp in ("fp-a", "fp-b")]
    for p in procs:
        out, _ = p.communicate(timeout=240)
        assert p.returncode == 0 and "DONE" in out, out
    st = PlanHistoryStore(str(tmp_path))
    a, b = st.lookup("fp-a"), st.lookup("fp-b")
    assert a and b, "one writer's shapes were dropped by the other's replace"
    assert a["runs"] == 25 and b["runs"] == 25
    assert a["peak_device_bytes"] == 124 and b["peak_device_bytes"] == 124


# -- mid-stream SIGKILL failover ----------------------------------------------

def _spawn_victim(fleet_dir, faults_spec):
    repo = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, str(repo / "tools" / "fleet_replica.py"),
         "--fleet-dir", str(fleet_dir), "--synthetic", "200",
         "--lease-timeout", "3", "--heartbeat", "0.5",
         "--faults", faults_spec],
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
def test_sigkill_midstream_failover_bit_identical(tmp_path):
    """The headline failover contract: a victim replica PROCESS (hung by a
    hang fault at its first result frame, so the kill lands mid-stream) is
    SIGKILLed while serving; the client's submit_with_retry rotates to the
    in-process survivor and the result is bit-identical — with zero leaked
    buffers, permits, or threads on the survivor."""
    from spark_rapids_tpu.runtime.memory import DeviceManager
    from spark_rapids_tpu.runtime.semaphore import TpuSemaphore

    # the survivor serves the SAME deterministic synthetic table the victim
    # builds (tools/fleet_replica.py --synthetic), so results are
    # bit-comparable across the fleet
    spark = TpuSession({})
    spark.create_or_replace_temp_view(
        "t", spark.create_dataframe(
            pa.table({"k": pa.array([i % 50 for i in range(200)],
                                    type=pa.int64()),
                      "v": pa.array([float(i) for i in range(200)],
                                    type=pa.float64())}),
            num_partitions=2))
    oracle = spark.sql(SQL).collect().to_pylist()
    cat = DeviceManager.get().catalog
    buffers_base = cat.num_buffers

    ep = QueryEndpoint(spark)
    victim, vport = _spawn_victim(tmp_path / "fleet", "hang:endpoint.send:1")
    flight, retries = {}, []
    try:
        cli = EndpointClient([("127.0.0.1", vport), ("127.0.0.1", ep.port)],
                             timeout_s=120)
        failovers_before = _failovers()

        def run():
            try:
                flight["rows"] = cli.submit_with_retry(
                    SQL, on_retry=lambda a, d: retries.append(a)).to_pylist()
            except BaseException as e:  # noqa: BLE001 — asserted below
                flight["error"] = repr(e)[:200]

        t = threading.Thread(target=run, daemon=True)
        t.start()
        time.sleep(1.5)     # the victim is hung at its first result frame
        os.kill(victim.pid, signal.SIGKILL)
        t.join(timeout=240)
        assert not t.is_alive(), "failover client never finished"
        assert flight.get("rows") == oracle, flight
        assert retries, "the kill missed the in-flight window"
        assert _failovers() >= failovers_before + 1
        assert cli.address == ("127.0.0.1", ep.port)
    finally:
        try:
            victim.kill()
        except OSError:
            pass
        victim.wait(timeout=30)
        ep.shutdown(grace_s=5)

    # nothing leaked on the survivor: buffers, permits, threads
    gc.collect()
    assert _wait(lambda: cat.num_buffers <= buffers_base)
    assert cat.num_buffers <= buffers_base
    assert not TpuSemaphore.get()._holders
    assert _wait(lambda: not any(
        th.name.startswith(("srt-pipe-", "srt-endpoint", "srt-fleet"))
        for th in threading.enumerate()))
