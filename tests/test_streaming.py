"""Continuous-ingestion tests (streaming/*): the crash-consistent epoch
journal protocol (begin/commit, attempt fencing, corrupt-refusal), the
durable idempotent batch log (directory tail + CRC-verified endpoint
APPEND), incremental windowed aggregation with watermark retirement and a
steady state that retraces nothing, exactly-once recovery — a crash
between begin and commit replays bit-identically, a corrupt state
snapshot rebuilds from the consumed batch log — and the staleness
contract: an APPEND through any replica invalidates every replica's
result cache via the shared fleet catalog epoch."""

import gc
import json
import os
import pathlib
import signal
import socket
import struct
import subprocess
import sys
import threading
import time

import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.runtime import eventlog, faults
from spark_rapids_tpu.runtime import fleet as FL
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime.checksum import block_checksum
from spark_rapids_tpu.runtime.endpoint import (MSG_APPEND, EndpointClient,
                                               QueryEndpoint)
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.shuffle.transport import TransportError
from spark_rapids_tpu.streaming import (EpochCoordinator, EpochJournal,
                                        JournalCorruptError,
                                        StreamingSource, validate_doc)
from spark_rapids_tpu.streaming.journal import FILE as JOURNAL_FILE
from spark_rapids_tpu.streaming.source import ipc_to_table, table_to_ipc

REPO = pathlib.Path(__file__).resolve().parent.parent

SQL = "select k, sum(v) s, count(*) c from clicks group by k order by k"

# every coordinator in this module uses the same shape, so the compiled
# epoch kernels are shared across tests (and with the persistent cache)
KEYS, AGGS = ["k"], [("sum", "v"), ("count", "v"), ("max", "v")]


def _batch(i, rows=8):
    """Deterministic batch i: 2 keys, event time spans one 10s window."""
    base = i * 10
    return pa.table({
        "k": pa.array([j % 2 for j in range(rows)], type=pa.int64()),
        "v": pa.array([float(base + j) for j in range(rows)],
                      type=pa.float64()),
        "ts": pa.array([base + j for j in range(rows)], type=pa.int64())})


def _coord(spark, src, windowed=True, **kw):
    if windowed:
        kw.setdefault("time_column", "ts")
        kw.setdefault("window_seconds", 10)
    return EpochCoordinator(spark, src, keys=KEYS, aggs=AGGS, **kw)


def _oracle_state(tables, windowed=True):
    """Independent pyarrow recomputation of the expected state table."""
    tbl = pa.concat_tables(tables)
    group = list(KEYS)
    if windowed:
        tbl = tbl.append_column("window", pa.array(
            [t - (t % 10) for t in tbl["ts"].to_pylist()],
            type=pa.int64()))
        group.append("window")
    agg = tbl.group_by(group).aggregate(
        [("v", "sum"), ("v", "count"), ("v", "max")])
    agg = agg.rename_columns(group + ["sum_v", "count_v", "max_v"])
    return agg.sort_by([(c, "ascending") for c in group])


def _rows(tbl, group):
    """Order-and-type-insensitive row view for oracle comparison."""
    out = []
    for r in tbl.sort_by([(c, "ascending") for c in group]).to_pylist():
        out.append({k: (float(v) if isinstance(v, (int, float)) else v)
                    for k, v in r.items()})
    return out


def _wait(pred, timeout_s=30.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return pred()


@pytest.fixture(autouse=True)
def _clean_streaming_plane():
    yield
    faults.reset()
    eventlog.shutdown()


# -- journal protocol ----------------------------------------------------------

def test_journal_begin_commit_and_attempt_fencing(tmp_path):
    j = EpochJournal(str(tmp_path), source="s")
    assert j.committed_epoch() == 0 and j.pending() is None
    assert j.begin(1, ["b-0", "b-1"]) == 1
    assert j.pending()["batch_ids"] == ["b-0", "b-1"]
    # re-beginning the SAME pending epoch is the recovery replay: the
    # attempt bump is the stale-partial fence
    assert j.begin(1, ["b-0", "b-1"]) == 2
    rec = j.commit(1, state_checksum=7, state_rows=2, state_bytes=64,
                   rows_in=16)
    assert rec["attempt"] == 2 and j.committed_epoch() == 1
    assert j.pending() is None
    assert j.is_consumed("b-0") and j.is_consumed("b-1")
    assert not j.is_consumed("b-9")
    # protocol bugs raise instead of corrupting exactly-once state
    with pytest.raises(ValueError, match="out of order"):
        j.begin(3, ["b-2"])
    with pytest.raises(ValueError, match="already-consumed"):
        j.begin(2, ["b-1"])
    with pytest.raises(ValueError, match="without a matching begin"):
        j.commit(2, state_checksum=0, state_rows=0, state_bytes=0)
    j.begin(2, ["b-2"])
    with pytest.raises(ValueError, match="out of order"):
        j.begin(3, ["b-3"])     # can't skip past the pending epoch either
    # commit folds consumed + advances the epoch in ONE atomic replace
    j.commit(2, state_checksum=1, state_rows=1, state_bytes=8)
    doc = j.snapshot()
    assert doc["committed_epoch"] == 2
    assert doc["consumed"] == ["b-0", "b-1", "b-2"]
    assert validate_doc(doc) == []


def test_journal_refuses_corruption_and_validate_doc(tmp_path):
    j = EpochJournal(str(tmp_path), source="s")
    j.begin(1, ["b-0"])
    j.commit(1, state_checksum=1, state_rows=1, state_bytes=8)
    path = tmp_path / JOURNAL_FILE
    good = json.loads(path.read_text())
    # torn/garbage journal: the stream refuses to run — silently degrading
    # to empty would re-consume every committed batch
    path.write_text("{ not json")
    with pytest.raises(JournalCorruptError, match="unreadable"):
        j.snapshot()
    # schema violations are refused too, and validate_doc names them
    bad = dict(good, committed_epoch=5)
    path.write_text(json.dumps(bad))
    with pytest.raises(JournalCorruptError, match="violates its schema"):
        j.snapshot()
    assert any("last commit" in e for e in validate_doc(bad))
    assert any("not committed_epoch+1" in e for e in validate_doc(
        dict(good, begin={"epoch": 9, "attempt": 1, "batch_ids": ["x"]})))
    assert any("already-consumed" in e for e in validate_doc(
        dict(good, begin={"epoch": 2, "attempt": 1, "batch_ids": ["b-0"]})))
    assert any("not contiguous" in e for e in validate_doc(
        dict(good, commits=[dict(good["commits"][0]),
                            dict(good["commits"][0], epoch=3)])))
    assert validate_doc(good) == []
    path.write_text(json.dumps(good))
    assert j.committed_epoch() == 1


def test_journal_history_bounded_but_protocol_state_is_not(tmp_path):
    j = EpochJournal(str(tmp_path), source="s", max_commits=3)
    for e in range(1, 8):
        j.begin(e, [f"b-{e}"])
        j.commit(e, state_checksum=e, state_rows=1, state_bytes=8)
    doc = j.snapshot()
    assert len(doc["commits"]) == 3
    assert doc["committed_epoch"] == 7
    assert len(doc["consumed"]) == 7    # never truncated: the exactly-once set
    assert validate_doc(doc) == []


# -- batch log -----------------------------------------------------------------

def test_source_append_idempotent_and_crc_verified(tmp_path):
    src = StreamingSource("clicks", str(tmp_path))
    assert src.append_table("b-0000", _batch(0)) is True
    assert src.append_table("b-0000", _batch(0)) is False   # idempotent
    assert src.list_batches() == ["b-0000"]
    with pytest.raises(ValueError, match="invalid batch id"):
        src.append_table("../evil", _batch(0))
    with pytest.raises(ValueError, match="schema"):
        src.append_table("b-0001", pa.table({"z": [1]}))
    # the wire path: CRC verified BEFORE the duplicate shortcut, and a
    # mismatch is a retryable transport fault, not a duplicate ack
    body = table_to_ipc(_batch(1))
    with pytest.raises(TransportError, match="checksum mismatch"):
        src.append_ipc("b-0001", body, block_checksum(body) ^ 1)
    assert src.list_batches() == ["b-0000"]
    tbl, fresh = src.append_ipc("b-0001", body, block_checksum(body))
    assert fresh and tbl.equals(_batch(1))
    _, fresh = src.append_ipc("b-0001", body, block_checksum(body))
    assert not fresh
    assert ipc_to_table(body).equals(_batch(1))
    # write intents and dotfiles never surface as batches
    (tmp_path / "b-0009.parquet.tmp.123").write_bytes(b"torn")
    (tmp_path / ".hidden.parquet").write_bytes(b"x")
    assert src.list_batches() == ["b-0000", "b-0001"]


# -- epoch lifecycle -----------------------------------------------------------

def test_epoch_lifecycle_watermark_and_steady_state(tmp_path):
    """The tentpole happy path: five epochs of incremental windowed
    aggregation, state matching a full recomputation oracle every epoch,
    watermark retirement holding state flat, a steady state that compiles
    NOTHING, and zero resilience events / leaked buffers."""
    from spark_rapids_tpu.runtime.memory import DeviceManager
    res_before = M.resilience_snapshot()
    cat = DeviceManager.get().catalog
    buffers_base = cat.num_buffers
    spark = TpuSession({
        "spark.rapids.tpu.streaming.watermark.delaySeconds": 20,
        "spark.rapids.tpu.eventLog.dir": str(tmp_path / "log")})
    src = spark.create_stream_source("clicks", str(tmp_path / "stream"))
    coord = _coord(spark, src)
    try:
        seen, state_rows = [], []
        for i in range(5):
            ack = spark.streaming_append("clicks", f"b-{i:04d}", _batch(i))
            assert not ack["duplicate"] and ack["rows"] == 8
            seen.append(_batch(i))
            rec = coord.run_epoch()
            assert rec["epoch"] == i + 1 and rec["attempt"] == 1
            assert rec["rows_in"] == 8
            state_rows.append(rec["state_rows"])
            # state == oracle over everything ingested, minus retirement
            oracle = _oracle_state(seen)
            wm = coord.watermark
            if wm is not None:
                oracle = oracle.filter(
                    pc.greater_equal(oracle["window"],
                                     pa.scalar(wm, type=pa.int64())))
            got = coord.state_table()
            assert _rows(got, KEYS + ["window"]) == \
                _rows(oracle, KEYS + ["window"])
        # watermark (delay 20s, 10s windows): exactly 3 live windows x 2
        # keys once retirement starts — state bytes stay flat forever
        assert state_rows[-2:] == [6, 6]
        assert coord.journal.last_commit()["retired_rows"] == 2
        assert coord.watermark == 20
        # steady state retraces nothing: the two plan shapes (first epoch,
        # union+merge) are compiled by epoch 3; 4 and 5 compile ZERO
        assert coord.last_epoch_compiles == 0
        assert coord.journal.last_commit()["compiles"] == 0
        # nothing new -> no epoch, no journal write
        assert coord.run_epoch() is None
        # a duplicate append is acked but consumed batches never re-ingest
        ack = spark.streaming_append("clicks", "b-0000", _batch(0))
        assert ack["duplicate"]
        assert coord.run_epoch() is None
        # the SQL surface sees every appended row (views re-resolve fresh)
        assert spark.sql("select count(*) c from clicks") \
            .collect().to_pylist() == [{"c": 40}]
        assert validate_doc(coord.journal.snapshot()) == []
    finally:
        coord.close()
    # a clean stream is resilience-silent: no replays, no rebuilds, and
    # every other counter untouched
    assert M.resilience_snapshot() == res_before
    eventlog.shutdown()
    recs = [json.loads(ln) for f in (tmp_path / "log").glob("*.jsonl")
            for ln in f.read_text().splitlines()]
    kinds = [r.get("event") for r in recs]
    assert kinds.count("stream.append") == 5       # duplicates emit nothing
    assert kinds.count("stream.epoch.begin") == 5
    assert kinds.count("stream.epoch.commit") == 5
    # the retained state buffer is released by close(): no leaks
    gc.collect()
    assert _wait(lambda: cat.num_buffers <= buffers_base)


def test_commit_crash_replays_pending_epoch_bit_identical(tmp_path):
    """A crash in the commit window (work done, journal not yet advanced)
    must replay the SAME batch ids on restart and land bit-identically
    with an unkilled run — the exactly-once headline, in-process."""
    res_before = M.resilience_snapshot()["streamEpochReplays"]
    spark = TpuSession({"spark.rapids.tpu.streaming.maxBatchesPerEpoch": 1})
    live_dir, oracle_dir = tmp_path / "live", tmp_path / "oracle"
    src = StreamingSource("clicks", str(live_dir))
    osrc = StreamingSource("clicks", str(oracle_dir))
    for i in range(3):
        src.append_table(f"b-{i:04d}", _batch(i))
        osrc.append_table(f"b-{i:04d}", _batch(i))
    coord = _coord(spark, src)
    oracle = _coord(spark, osrc)
    try:
        for _ in range(2):
            coord.run_epoch()
        # the armed commit fault fires AFTER the epoch's query and state
        # snapshot, BEFORE the journal write — the exact crash window
        faults.configure("error:streaming.epoch.commit:1", seed=1)
        with pytest.raises(RuntimeError, match="fault-injection"):
            coord.run_epoch()
        faults.reset()
        doc = coord.journal.snapshot()
        assert doc["committed_epoch"] == 2
        assert doc["begin"]["epoch"] == 3
        assert doc["begin"]["batch_ids"] == ["b-0002"]
        # a FRESH coordinator (the restarted process) recovers: the pending
        # epoch replays under a bumped attempt, counted as resilience
        recovered = _coord(spark, src)
        try:
            rec = recovered.recover()
            assert rec["epoch"] == 3 and rec["attempt"] == 2
            assert rec["batch_ids"] == ["b-0002"]
            assert recovered.journal.committed_epoch() == 3
            assert recovered.recover() is None      # nothing left pending
            for _ in range(3):
                oracle.run_epoch()
            assert recovered.state_table().equals(oracle.state_table())
            assert rec["state_checksum"] == \
                oracle.journal.last_commit()["state_checksum"]
            assert M.resilience_snapshot()["streamEpochReplays"] == \
                res_before + 1
        finally:
            recovered.close()
    finally:
        coord.close()
        oracle.close()


def test_corrupt_state_snapshot_rebuilds_from_batch_log(tmp_path):
    """A committed snapshot failing its journal checksum is detected (never
    silently served) and rebuilt by re-aggregating the consumed batch log —
    landing on the exact committed state."""
    res_before = M.resilience_snapshot()["streamStateRebuilds"]
    spark = TpuSession({})
    src = StreamingSource("clicks", str(tmp_path))
    for i in range(3):
        src.append_table(f"b-{i:04d}", _batch(i))
    coord = _coord(spark, src, windowed=False)
    try:
        rec = coord.run_epoch()
        assert rec["epoch"] == 1 and rec["state_rows"] == 2
        committed = coord.state_table()
    finally:
        coord.close()
    snap = tmp_path / "_state" / "state-1.arrow"
    snap.write_bytes(b"\x00" * 16 + snap.read_bytes()[16:])
    fresh = _coord(spark, src, windowed=False)
    try:
        got = fresh.state_table()     # recovery path: checksum fails -> rebuild
        assert got.equals(committed)
        assert M.resilience_snapshot()["streamStateRebuilds"] == \
            res_before + 1
        # the rebuilt state carries forward: the next epoch merges onto it
        src.append_table("b-0003", _batch(3))
        rec = fresh.run_epoch()
        assert rec["epoch"] == 2
        assert _rows(fresh.state_table(), KEYS) == _rows(
            _oracle_state([_batch(i) for i in range(4)], windowed=False),
            KEYS)
    finally:
        fresh.close()


# -- session + endpoint surfaces -----------------------------------------------

def test_endpoint_append_wire_result_cache_and_staleness(tmp_path):
    """The wire path end to end: APPEND through the endpoint is durable
    before its ack, idempotent on retry, and every APPEND bumps the
    catalog epoch so a cached result can never serve stale rows."""
    spark = TpuSession({
        "spark.rapids.tpu.endpoint.resultCache.enabled": True})
    src = spark.create_stream_source("clicks", str(tmp_path / "stream"))
    ep = QueryEndpoint(spark)
    cli = EndpointClient(("127.0.0.1", ep.port), timeout_s=30)
    try:
        ack = cli.append("clicks", "b-0000", _batch(0))
        assert not ack["duplicate"] and ack["rows"] == 8
        assert ack["replica"] == f"127.0.0.1:{ep.port}"
        assert src.has_batch("b-0000")          # the ack meant durable
        first = cli.submit(SQL).to_pylist()
        base = _oracle_state([_batch(0)], windowed=False)
        assert [(r["k"], r["s"], r["c"]) for r in first] == [
            (r["k"], r["sum_v"], int(r["count_v"]))
            for r in base.to_pylist()]
        assert cli.submit(SQL).to_pylist() == first
        assert cli.last_summary.get("cached") is True
        # a duplicate APPEND (the blind-retry path) acks but changes nothing
        epoch_before = spark.catalog_epoch
        ack = cli.append("clicks", "b-0000", _batch(0))
        assert ack["duplicate"] and spark.catalog_epoch == epoch_before
        assert cli.submit(SQL).to_pylist() == first
        assert cli.last_summary.get("cached") is True
        # a FRESH append invalidates: the very next submit reruns and sees
        # the new rows
        ack = cli.append("clicks", "b-0001", _batch(1))
        assert not ack["duplicate"]
        assert spark.catalog_epoch == epoch_before + 1
        rows = cli.submit(SQL).to_pylist()
        assert not (cli.last_summary or {}).get("cached")
        assert rows != first
        oracle = _oracle_state([_batch(0), _batch(1)], windowed=False)
        assert [(r["k"], r["s"], r["c"]) for r in rows] == [
            (r["k"], r["sum_v"], int(r["count_v"]))
            for r in oracle.to_pylist()]
    finally:
        ep.shutdown(grace_s=5)


def test_append_retry_rotates_to_live_replica(tmp_path):
    spark = TpuSession({})
    spark.create_stream_source("clicks", str(tmp_path / "stream"))
    ep = QueryEndpoint(spark)
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    dead_port = probe.getsockname()[1]
    probe.close()
    try:
        cli = EndpointClient([("127.0.0.1", dead_port),
                              ("127.0.0.1", ep.port)], timeout_s=30)
        retries = []
        ack = cli.append_with_retry(
            "clicks", "b-0000", _batch(0),
            on_retry=lambda a, d: retries.append(a))
        assert not ack["duplicate"] and retries
        assert cli.address == ("127.0.0.1", ep.port)
        # the retried path is idempotent by construction
        assert cli.append_with_retry("clicks", "b-0000",
                                     _batch(0))["duplicate"]
    finally:
        ep.shutdown(grace_s=5)


def test_client_disconnect_mid_append_leaves_no_torn_batch(tmp_path):
    """A client dying mid-frame must leave NOTHING: no batch file, no
    half-parsed ingest — and the next real APPEND proceeds normally."""
    spark = TpuSession({})
    src = spark.create_stream_source("clicks", str(tmp_path / "stream"))
    ep = QueryEndpoint(spark)
    try:
        sock = socket.create_connection(("127.0.0.1", ep.port), timeout=10)
        # frame header promises 4096 payload bytes; send 16 and vanish
        sock.sendall(struct.pack("<BI", MSG_APPEND, 4096) + b"x" * 16)
        sock.close()
        time.sleep(0.2)
        assert src.list_batches() == []
        assert not any(".tmp." in n
                       for n in os.listdir(str(tmp_path / "stream")))
        cli = EndpointClient(("127.0.0.1", ep.port), timeout_s=30)
        assert not cli.append("clicks", "b-0000", _batch(0))["duplicate"]
        assert src.list_batches() == ["b-0000"]
    finally:
        ep.shutdown(grace_s=5)


def test_shared_catalog_epoch_invalidates_peer_replica_cache(tmp_path):
    """The cross-replica staleness regression: replica B's result cache
    holds a stream query; an APPEND lands through replica A. The shared
    fleet catalog epoch must invalidate B's entry — B re-runs and serves
    the fresh rows, never the cached stale ones."""
    fleet_dir = str(tmp_path / "fleet")
    # the shared-epoch primitive itself
    assert FL.shared_catalog_epoch(fleet_dir) == 0
    assert FL.bump_shared_catalog_epoch(fleet_dir) == 1
    assert FL.bump_shared_catalog_epoch(fleet_dir) == 2
    assert FL.shared_catalog_epoch(fleet_dir) == 2

    conf = {"spark.rapids.tpu.fleet.dir": fleet_dir,
            "spark.rapids.tpu.fleet.heartbeat.intervalSeconds": 0.2,
            "spark.rapids.tpu.endpoint.resultCache.enabled": True}
    sdir = str(tmp_path / "stream")
    sa, sb = TpuSession(dict(conf)), TpuSession(dict(conf))
    sa.create_stream_source("clicks", sdir)
    sb.create_stream_source("clicks", sdir)
    sa.streaming_append("clicks", "b-0000", _batch(0))
    ep_a, ep_b = QueryEndpoint(sa), QueryEndpoint(sb)
    try:
        cli_a = EndpointClient(("127.0.0.1", ep_a.port), timeout_s=30)
        cli_b = EndpointClient(("127.0.0.1", ep_b.port), timeout_s=30)
        first = cli_b.submit(SQL).to_pylist()
        assert cli_b.submit(SQL).to_pylist() == first
        assert cli_b.last_summary.get("cached") is True
        # append through A; B's next submit must NOT serve its cache
        ack = cli_a.append("clicks", "b-0001", _batch(1))
        assert not ack["duplicate"]
        rows = cli_b.submit(SQL).to_pylist()
        assert not (cli_b.last_summary or {}).get("cached")
        assert rows != first
        oracle = _oracle_state([_batch(0), _batch(1)], windowed=False)
        assert [(r["k"], r["s"], r["c"]) for r in rows] == [
            (r["k"], r["sum_v"], int(r["count_v"]))
            for r in oracle.to_pylist()]
    finally:
        ep_a.shutdown(grace_s=5)
        ep_b.shutdown(grace_s=5)


# -- crash recovery across real processes --------------------------------------

_CRASH_CHILD = r"""
import sys
import jax
jax.config.update("jax_platforms", "cpu")
import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu.runtime import faults
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.streaming import EpochCoordinator, StreamingSource

src_dir, n_clean, spec = sys.argv[1], int(sys.argv[2]), sys.argv[3]
spark = TpuSession({"spark.rapids.tpu.streaming.maxBatchesPerEpoch": 1})
src = StreamingSource("clicks", src_dir)
coord = EpochCoordinator(spark, src, keys=["k"],
                         aggs=[("sum", "v"), ("count", "v"), ("max", "v")],
                         time_column="ts", window_seconds=10)
for _ in range(n_clean):
    coord.run_epoch()
print("COMMITTED", coord.journal.committed_epoch(), flush=True)
faults.configure(spec, seed=1)
coord.run_epoch()
print("SURVIVED", flush=True)     # must never be reached
"""


def _spawn_crash_child(src_dir, n_clean, spec):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [sys.executable, "-c", _CRASH_CHILD, str(src_dir), str(n_clean),
         spec],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env)


def _run_oracle(spark, directory, batches, n_epochs):
    osrc = StreamingSource("clicks", str(directory))
    for b, t in batches:
        osrc.append_table(b, t)
    oracle = _coord(spark, osrc)
    try:
        for _ in range(n_epochs):
            oracle.run_epoch()
        return oracle.state_table(), oracle.journal.last_commit()
    finally:
        oracle.close()


@pytest.mark.slow
def test_exec_kill_mid_commit_replays_bit_identical(tmp_path):
    """A real coordinator PROCESS is SIGKILLed inside the commit window
    (state snapshot written, journal not advanced — exec_kill at the
    streaming.epoch.commit site). A fresh coordinator adopting the stream
    replays the pending epoch bit-identically with an unkilled oracle,
    and the dead attempt's orphan snapshot is never adopted."""
    res_before = M.resilience_snapshot()["streamEpochReplays"]
    src_dir = tmp_path / "stream"
    batches = [(f"b-{i:04d}", _batch(i)) for i in range(3)]
    src = StreamingSource("clicks", str(src_dir))
    for b, t in batches:
        src.append_table(b, t)
    child = _spawn_crash_child(src_dir, 2,
                               "exec_kill:streaming.epoch.commit:1")
    out, _ = child.communicate(timeout=300)
    assert "COMMITTED 2" in out and "SURVIVED" not in out, out
    assert child.returncode == -signal.SIGKILL
    journal = EpochJournal(str(src_dir / "_state"), source="clicks")
    pending = journal.pending()
    assert pending == {"epoch": 3, "batch_ids": ["b-0002"], "attempt": 1,
                       "prev_state_checksum": pending["prev_state_checksum"]}
    # the dead attempt got as far as its epoch-3 snapshot — the fence must
    # keep it un-adopted until the replayed commit names it
    assert (src_dir / "_state" / "state-3.arrow").exists()

    spark = TpuSession({"spark.rapids.tpu.streaming.maxBatchesPerEpoch": 1})
    recovered = _coord(spark, src)
    try:
        # the SIGKILLed child's flock died with it: recovery acquires the
        # owner lock immediately instead of deadlocking
        rec = recovered.recover()
        assert rec["epoch"] == 3 and rec["attempt"] == 2
        assert rec["batch_ids"] == ["b-0002"]
        state = recovered.state_table()
        oracle_state, oracle_commit = _run_oracle(
            spark, tmp_path / "oracle", batches, 3)
        assert state.equals(oracle_state)
        assert rec["state_checksum"] == oracle_commit["state_checksum"]
        assert M.resilience_snapshot()["streamEpochReplays"] == \
            res_before + 1
        assert validate_doc(recovered.journal.snapshot()) == []
    finally:
        recovered.close()


@pytest.mark.slow
def test_sigkill_between_begin_and_commit_replays(tmp_path):
    """The other crash point: the coordinator process dies AFTER journaling
    epoch.begin but BEFORE the state snapshot exists at all (hung at the
    streaming.state site, then SIGKILLed). Recovery replays from the
    begin record's pinned batch ids, bit-identical with the oracle."""
    res_before = M.resilience_snapshot()["streamEpochReplays"]
    src_dir = tmp_path / "stream"
    batches = [(f"b-{i:04d}", _batch(i)) for i in range(2)]
    src = StreamingSource("clicks", str(src_dir))
    for b, t in batches:
        src.append_table(b, t)
    child = _spawn_crash_child(src_dir, 1, "hang:streaming.state:1")
    try:
        journal = EpochJournal(str(src_dir / "_state"), source="clicks")
        assert _wait(lambda: (child.poll() is None
                              and (p := journal.pending()) is not None
                              and p["epoch"] == 2), timeout_s=300)
        time.sleep(0.3)     # let the child reach the hang point
        os.kill(child.pid, signal.SIGKILL)
        child.communicate(timeout=60)
        assert child.returncode == -signal.SIGKILL
    finally:
        if child.poll() is None:
            child.kill()
    assert not (src_dir / "_state" / "state-2.arrow").exists()

    spark = TpuSession({"spark.rapids.tpu.streaming.maxBatchesPerEpoch": 1})
    recovered = _coord(spark, src)
    try:
        rec = recovered.run_epoch()     # run_epoch recovers first
        assert rec["epoch"] == 2 and rec["attempt"] == 2
        oracle_state, oracle_commit = _run_oracle(
            spark, tmp_path / "oracle", batches, 2)
        assert recovered.state_table().equals(oracle_state)
        assert rec["state_checksum"] == oracle_commit["state_checksum"]
        assert M.resilience_snapshot()["streamEpochReplays"] == \
            res_before + 1
    finally:
        recovered.close()


# -- cross-replica fleet e2e ---------------------------------------------------

def _spawn_replica(fleet_dir, stream_spec):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, str(REPO / "tools" / "fleet_replica.py"),
         "--fleet-dir", str(fleet_dir), "--synthetic", "20",
         "--lease-timeout", "3", "--heartbeat", "0.5", "--result-cache",
         "--stream-source", stream_spec],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env)
    deadline = time.monotonic() + 300
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
        raise AssertionError("replica never became READY")
    threading.Thread(target=proc.stdout.read, daemon=True).start()
    return proc, port


@pytest.mark.slow
def test_two_process_fleet_append_staleness_and_cli(tmp_path):
    """Two real replica PROCESSES share one batch log and one fleet dir.
    An APPEND shipped through replica A (via the tpu_client CLI, riding
    the fleet rotation) must invalidate replica B's warmed result cache —
    and the duplicate re-send of the same batch id stays a no-op."""
    sdir = tmp_path / "stream"
    sdir.mkdir()
    # the directory-tail ingestion path: a producer drops a parquet file in
    pq.write_table(_batch(0), sdir / "b-0000.parquet")
    a = b = None
    try:
        a, aport = _spawn_replica(tmp_path / "fleet", f"clicks:{sdir}")
        b, bport = _spawn_replica(tmp_path / "fleet", f"clicks:{sdir}")
        cli_b = EndpointClient(("127.0.0.1", bport), timeout_s=120)
        first = cli_b.submit_with_retry(SQL).to_pylist()
        assert cli_b.submit(SQL).to_pylist() == first
        assert cli_b.last_summary.get("cached") is True

        batch_file = tmp_path / "b1.parquet"
        pq.write_table(_batch(1), batch_file)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        cmd = [sys.executable, str(REPO / "tools" / "tpu_client.py"),
               "--addresses", f"127.0.0.1:{aport},127.0.0.1:{bport}",
               "append", "--source", "clicks", "--batch", "b-0001",
               "--file", str(batch_file)]
        r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           timeout=300)
        assert r.returncode == 0, r.stderr
        assert "OK append source=clicks batch=b-0001 rows=8" in r.stderr
        r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           timeout=300)
        assert r.returncode == 0 and "duplicate" in r.stderr, r.stderr

        rows = cli_b.submit(SQL).to_pylist()
        assert not (cli_b.last_summary or {}).get("cached")
        assert rows != first
        oracle = _oracle_state([_batch(0), _batch(1)], windowed=False)
        assert [(r["k"], r["s"], r["c"]) for r in rows] == [
            (r["k"], r["sum_v"], int(r["count_v"]))
            for r in oracle.to_pylist()]
    finally:
        for proc in (a, b):
            if proc is not None:
                proc.kill()
                proc.wait(timeout=30)
