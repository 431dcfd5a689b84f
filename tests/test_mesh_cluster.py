"""Unified mesh-cluster plane: N executor processes x M local mesh devices.

ROADMAP item 4 / the elastic mesh-cluster plane: a MiniCluster executor
drives a LOCAL device mesh (one jitted shard_map dispatch computes every
lane's Spark-exact partition ids per wave, with the wave's map-output
statistics psum-ed over ICI), while shuffle blocks still cross executors
over the TCP transport. Robustness is the contract under test:

- combined-plane results are BIT-IDENTICAL to the TCP-only plane (and to
  a single-process run for the q18 ladder query);
- a mesh participant killed or hung inside the collective is surfaced
  by the PR-5 heartbeat/deadline machinery and the task transparently
  re-plans onto the per-split TCP path under a bumped epoch — degraded
  mode, counted in meshDegradedFallbacks, never a hang and never a
  whole-query heal;
- movement-aware placement schedules reduce tasks on the executor holding
  the most map-output bytes (with spill-aware demotion when that host is
  over its budget proxy);
- the disk-spill tier's ENOSPC is typed (SpillCapacityError) and rides
  the existing OOM recovery ladder.
"""

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.cluster import MiniCluster
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.runtime import faults as FLT
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import tracing
from spark_rapids_tpu.session import TpuSession

N_EXEC = 2
N_SPLITS = 6
MESH_CONF = {"spark.rapids.tpu.cluster.mesh.enabled": "true",
             "spark.rapids.tpu.cluster.mesh.devicesPerExecutor": 4}


@pytest.fixture(autouse=True)
def _clean_chaos_state():
    FLT.reset()
    tracing.clear_events()
    yield
    FLT.reset()
    tracing.clear_events()


@pytest.fixture(scope="module")
def spark():
    return TpuSession()


@pytest.fixture(scope="module")
def df(spark):
    rng = np.random.default_rng(7)
    t = pa.table({"k": pa.array(rng.integers(0, 13, 3000), type=pa.int64()),
                  "v": pa.array(rng.random(3000))})
    return (spark.create_dataframe(t, num_partitions=N_SPLITS)
            .group_by(F.col("k")).agg(F.sum(F.col("v")).alias("s")))


@pytest.fixture(scope="module")
def tcp_table(df):
    """The TCP-only-plane oracle: same query, same cluster shape, mesh
    off — every combined-plane run must reproduce these exact bytes."""
    with MiniCluster(n_executors=N_EXEC, platform="cpu") as c:
        return c.collect(df)


def _run_mesh(df, extra=None, no_heal=True):
    base = M.resilience_snapshot()
    conf = RapidsConf(dict(MESH_CONF, **(extra or {})))
    with MiniCluster(n_executors=N_EXEC, conf=conf, platform="cpu") as c:
        heals = []
        orig = c._heal
        c._heal = lambda: (heals.append(1), orig())[-1]
        got = c.collect(df)
        stats = {"mesh": dict(c.mesh_stats),
                 "placement": dict(c.placement_stats),
                 "widths": list(c._mesh), "mesh_ok": list(c._mesh_ok),
                 "heals": len(heals), "task_log": list(c.task_log),
                 "alive": [p.is_alive() for p in c._procs]}
    end = M.resilience_snapshot()
    delta = {k: end[k] - base[k] for k in end if end[k] - base[k]}
    if no_heal:
        assert stats["heals"] == 0, \
            f"whole-query heal fired; degraded fallback expected ({delta})"
    return got, delta, stats


# -- the combined plane, healthy ---------------------------------------------

def test_mesh_plane_bit_identical_and_grouped(df, tcp_table):
    """Mesh plane on: map splits run as mesh task groups (one task drives
    several lanes on one executor's local mesh) and the result is
    bit-identical to the TCP-only plane, with zero resilience noise."""
    got, delta, stats = _run_mesh(df)
    assert got.equals(tcp_table), "mesh plane result differs from TCP plane"
    assert stats["widths"] == [4] * N_EXEC, stats
    assert stats["mesh"]["mesh_tasks"] >= 1, stats
    assert stats["mesh"]["waves"] >= 1, stats
    assert stats["mesh"]["degraded"] == 0, stats
    assert any(op == "map.mesh" for op, _ in stats["task_log"]), stats
    assert not delta, f"healthy mesh run left resilience noise: {delta}"
    names = {n for n, _ in tracing.recent_events()}
    assert "mesh.attach" in names, names


def test_mesh_width_respects_conf_and_availability(df):
    """devicesPerExecutor narrower than the visible 8 devices: the
    handshake reports the conf'd width and groups are sized to it."""
    got, _, stats = _run_mesh(
        df, {"spark.rapids.tpu.cluster.mesh.devicesPerExecutor": 3})
    assert stats["widths"] == [3] * N_EXEC, stats
    mesh_tasks = [op for op, _ in stats["task_log"] if op == "map.mesh"]
    assert mesh_tasks, stats
    with MiniCluster(n_executors=N_EXEC, platform="cpu") as c:
        assert got.equals(c.collect(df))


# -- degraded-mode fallback ---------------------------------------------------

def test_mesh_participant_kill_degrades_to_tcp(df, tcp_table):
    """A mesh participant SIGKILLed inside the collective (mesh_kill
    site): the loss is detected, the group's lanes re-plan per-split onto
    the TCP path under a bumped epoch, and the result stays
    bit-identical — counter-checked, no whole-query heal."""
    got, delta, stats = _run_mesh(
        df, {"spark.rapids.tpu.test.faults": "exec_kill:cluster.mesh.1:1"})
    assert got.equals(tcp_table), "mesh-kill result is not bit-identical"
    assert delta.get("executorsLost", 0) >= 1, delta
    assert delta.get("meshDegradedFallbacks", 0) >= 1, delta
    assert all(stats["alive"]), "pool not restored"
    names = {n for n, _ in tracing.recent_events()}
    assert {"mesh.degraded", "mesh.detach", "mesh.attach",
            "executor.lost"} <= names, names


def test_mesh_failure_degrades_without_executor_loss(df, tcp_table):
    """The mesh itself failing (chips unavailable / collective error) with
    the executor alive: a TRANSPARENT re-plan — no executor lost, no
    task-attempt strike charged, the slot's mesh distrusted, result
    bit-identical."""
    got, delta, stats = _run_mesh(
        df,
        {"spark.rapids.tpu.test.faults": "error:cluster.mesh.begin.0:1"})
    assert got.equals(tcp_table)
    assert delta.get("meshDegradedFallbacks", 0) >= 1, delta
    assert delta.get("executorsLost", 0) == 0, delta
    assert delta.get("taskAttempts", 0) == 0, \
        f"degradation must not charge attempt strikes: {delta}"
    assert stats["mesh_ok"][0] is False, stats
    names = {n for n, _ in tracing.recent_events()}
    assert "mesh.degraded" in names, names


def test_mesh_hang_surfaced_by_deadline_not_a_hang(df, tcp_table):
    """A task hung INSIDE the mesh collective (mesh_hang site) is
    detected by the PR-5 task-deadline machinery — the executor is killed
    and replaced, the lanes degrade to TCP, and the query completes
    bit-identically instead of hanging."""
    got, delta, stats = _run_mesh(
        df, {"spark.rapids.tpu.cluster.task.timeoutSeconds": 5.0,
             "spark.rapids.tpu.test.faults": "hang:cluster.mesh.0:1"})
    assert got.equals(tcp_table)
    assert delta.get("executorsLost", 0) >= 1, delta
    assert delta.get("meshDegradedFallbacks", 0) >= 1, delta
    assert all(stats["alive"]), stats


# -- movement-aware placement -------------------------------------------------

def test_tracker_movement_statistics():
    from spark_rapids_tpu.cluster.minicluster import MapOutputTracker
    tr = MapOutputTracker()
    tr.register_shuffle(1, None, None, "plain", [0, 1])
    tr.register_map_output(1, 0, 0, sizes=[100, 5])
    tr.register_map_output(1, 1, 1, sizes=[10, 50])
    assert tr.bytes_by_executor([1], 0) == {0: 100, 1: 10}
    assert tr.bytes_by_executor([1], 1) == {0: 5, 1: 50}
    assert tr.executor_load(0) == 105 and tr.executor_load(1) == 60
    # invalidation drops the bytes with the hosts and bumps the epoch
    tr.invalidate_splits(1, [0])
    assert tr.epoch(1) == 1
    assert tr.bytes_by_executor([1], 0) == {1: 10}
    assert tr.executor_load(0) == 0


def test_placement_policy_preferred_does_not_advance_rotation():
    from spark_rapids_tpu.cluster.minicluster import PlacementPolicy
    p = PlacementPolicy(3, seed=0)
    assert p.pick({0, 1, 2}, preferred=2) == 2
    # the round-robin cursor was not consumed by the preferred pick
    assert p.pick({0, 1, 2}) == 0
    assert p.pick({0, 1, 2}) == 1
    # a preferred executor the spec already failed on is ignored
    assert p.pick({0, 1}, prefer_not={0}, preferred=0) == 1


def test_movement_aware_placement_prefers_byte_dominant_host(spark):
    """One map split -> one executor holds ALL map-output bytes; the first
    reduce task must land exactly there (a local block-store read), with
    preferred hits counted."""
    rng = np.random.default_rng(3)
    t = pa.table({"k": pa.array(rng.integers(0, 7, 2000), type=pa.int64()),
                  "v": pa.array(rng.random(2000))})
    df1 = (spark.create_dataframe(t, num_partitions=1)
           .group_by(F.col("k")).agg(F.sum(F.col("v")).alias("s")))
    with MiniCluster(n_executors=N_EXEC, platform="cpu") as c:
        got = c.collect(df1)
        log = list(c.task_log)
        stats = dict(c.placement_stats)
    byte_host = next(ei for op, ei in log if op == "map")
    first_reduce = next(ei for op, ei in log if op == "result")
    assert first_reduce == byte_host, (log, stats)
    assert stats["preferred"] >= 1, stats
    exp = {r["k"]: r["s"] for r in df1.collect_host().to_pylist()}
    assert {r["k"]: r["s"] for r in got.to_pylist()} == pytest.approx(exp)


def test_spill_aware_demotion_when_host_over_budget(spark):
    """With placement.maxLoadedBytes shrunk below the parked bytes, the
    byte-dominant pick is DEMOTED back to round-robin (placement.demoted
    event + counter) instead of piling work on a spilling host."""
    rng = np.random.default_rng(3)
    t = pa.table({"k": pa.array(rng.integers(0, 7, 2000), type=pa.int64()),
                  "v": pa.array(rng.random(2000))})
    df1 = (spark.create_dataframe(t, num_partitions=1)
           .group_by(F.col("k")).agg(F.sum(F.col("v")).alias("s")))
    conf = RapidsConf(
        {"spark.rapids.tpu.cluster.placement.maxLoadedBytes": "1"})
    with MiniCluster(n_executors=N_EXEC, conf=conf, platform="cpu") as c:
        got = c.collect(df1)
        stats = dict(c.placement_stats)
    assert stats["demoted"] >= 1, stats
    assert stats["preferred"] == 0, stats
    names = {n for n, _ in tracing.recent_events()}
    assert "placement.demoted" in names, names
    exp = {r["k"]: r["s"] for r in df1.collect_host().to_pylist()}
    assert {r["k"]: r["s"] for r in got.to_pylist()} == pytest.approx(exp)


# -- typed ENOSPC on the disk-spill tier --------------------------------------

def test_spill_capacity_error_is_typed_and_retryable(tmp_path):
    """The disk-full fault at the spill writer surfaces as the typed,
    retryable SpillCapacityError (an OOM-class error), not a raw
    OSError."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.runtime.memory import BufferCatalog
    from spark_rapids_tpu.runtime.retry import (DeviceOomError,
                                                SpillCapacityError)

    def make(seed):
        r = np.random.default_rng(seed)
        return ColumnarBatch.from_arrow(pa.table(
            {"a": pa.array(r.integers(0, 1000, 4096), type=pa.int64())}))

    one = make(0).device_memory_size()
    cat = BufferCatalog(device_budget=int(one * 1.2),
                        host_budget=int(one * 0.5),
                        spill_dir=str(tmp_path))
    cat.add_batch(make(1))
    FLT.configure("disk_full:spill.write:1")
    with pytest.raises(SpillCapacityError) as ei:
        cat.add_batch(make(2))      # forces device->host->disk: ENOSPC
    assert isinstance(ei.value, DeviceOomError) and ei.value.retryable
    assert ("disk_full", "spill.write") in FLT.injected_log()
    # accounting stayed consistent: nothing half-moved to the disk tier
    assert cat.disk_bytes == 0 and cat.spilled_to_disk_bytes == 0


def test_spill_capacity_error_rides_oom_ladder(tmp_path):
    """SpillCapacityError routed through the EXISTING recovery ladder:
    call_with_retry absorbs the injected ENOSPC (spill-only retry) and the
    registration succeeds on the second attempt, with the recovery visible
    in the oom-retry counter."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.runtime import retry as R
    from spark_rapids_tpu.runtime.memory import BufferCatalog

    def make(seed):
        r = np.random.default_rng(seed)
        return ColumnarBatch.from_arrow(pa.table(
            {"a": pa.array(r.integers(0, 1000, 4096), type=pa.int64())}))

    one = make(0).device_memory_size()
    cat = BufferCatalog(device_budget=int(one * 1.2),
                        host_budget=int(one * 0.5),
                        spill_dir=str(tmp_path))
    cat.add_batch(make(1))
    base = M.resilience_snapshot()
    FLT.configure("disk_full:spill.write:1")
    bid = R.call_with_retry(lambda: cat.add_batch(make(2)),
                            scope="exchange.write", catalog=cat)
    got = cat.acquire_batch(bid).to_arrow()
    assert got.equals(make(2).to_arrow())
    delta = M.resilience_snapshot()
    assert delta[M.NUM_OOM_RETRIES] - base[M.NUM_OOM_RETRIES] >= 1
    assert ("disk_full", "spill.write") in FLT.injected_log()


# -- spawn-handshake hardening ------------------------------------------------

def test_spawn_handshake_retry_on_transient_failure(monkeypatch):
    """One transient bring-up failure must cost a retry (visible as an
    executor.spawn.retry event), not the slot."""
    calls = {"n": 0}
    orig = MiniCluster._spawn_executor_once

    def flaky(self, ei, arm_faults=True):
        calls["n"] += 1
        if calls["n"] == 2:     # first bring-up of slot 1 dies
            raise RuntimeError("executor 1 died during bring-up (injected)")
        return orig(self, ei, arm_faults)

    monkeypatch.setattr(MiniCluster, "_spawn_executor_once", flaky)
    with MiniCluster(n_executors=N_EXEC, platform="cpu") as c:
        assert all(p.is_alive() for p in c._procs)
    events = [(n, a) for n, a in tracing.recent_events()
              if n == "executor.spawn.retry"]
    assert events and events[0][1]["executor"] == 1, events


def test_spawn_retry_exhaustion_still_raises(monkeypatch):
    def dead(self, ei, arm_faults=True):
        raise RuntimeError(f"executor {ei} never came up (injected)")

    monkeypatch.setattr(MiniCluster, "_spawn_executor_once", dead)
    with pytest.raises(RuntimeError, match="never came up"):
        MiniCluster(n_executors=1, platform="cpu")
    events = [n for n, _ in tracing.recent_events()
              if n == "executor.spawn.retry"]
    assert events, "retry must be attempted (and logged) before giving up"


# -- LocalMesh unit coverage --------------------------------------------------

def test_local_mesh_wave_matches_per_batch_pids():
    """The stacked shard_map pid program is bit-exact with the per-batch
    partitioner (the property that makes mesh->TCP degradation sound), and
    the psum-ed wave statistics count every live row exactly once."""
    from spark_rapids_tpu.columnar.arrow import table_to_device
    from spark_rapids_tpu.distributed.mesh import LocalMesh
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.shuffle import partitioning as SP

    rng = np.random.default_rng(0)
    batches = [table_to_device(pa.table(
        {"k": pa.array(rng.integers(0, 99, n), type=pa.int64()),
         "v": pa.array(rng.random(n))})) for n in (700, 300, 1000)]
    part = SP.HashPartitioner([col("k")], 5).bind(batches[0].schema)
    lm = LocalMesh(4)
    pids_list, counts = lm.partition_wave(batches, part)
    for b, pids in zip(batches, pids_list):
        ref, mesh = part.partition(b), SP.slice_into_partitions(
            b, pids, part.num_partitions)
        assert len(ref) == len(mesh)
        for (p1, b1), (p2, b2) in zip(ref, mesh):
            assert p1 == p2 and b1.to_arrow().equals(b2.to_arrow())
    assert counts.sum() == sum(b.num_rows for b in batches)


def test_local_mesh_string_keys_fall_back_per_batch():
    """String keys: per-lane dictionaries cannot be trace-time constants
    of one stacked program, so the wave falls back to the per-batch pid
    path (counts None) — still bit-exact."""
    from spark_rapids_tpu.columnar.arrow import table_to_device
    from spark_rapids_tpu.distributed.mesh import LocalMesh
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.shuffle import partitioning as SP

    words = ["alpha", "beta", "gamma", "delta"]
    batches = [table_to_device(pa.table(
        {"s": pa.array([words[(i + off) % 4] for i in range(n)])}))
        for off, n in ((0, 64), (2, 32))]
    part = SP.HashPartitioner([col("s")], 3).bind(batches[0].schema)
    lm = LocalMesh(2)
    pids_list, counts = lm.partition_wave(batches, part)
    assert counts is None
    for b, pids in zip(batches, pids_list):
        assert np.array_equal(
            np.asarray(pids)[:b.num_rows],
            np.asarray(part.part_ids(b))[:b.num_rows])


def test_local_mesh_shrink_raises_degraded():
    from spark_rapids_tpu.distributed.mesh import (LocalMesh,
                                                   MeshDegradedError)
    from spark_rapids_tpu.columnar.arrow import table_to_device
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.shuffle import partitioning as SP
    lm = LocalMesh(2)
    batches = [table_to_device(pa.table(
        {"k": pa.array([1, 2, 3], type=pa.int64())})) for _ in range(3)]
    part = SP.HashPartitioner([col("k")], 2).bind(batches[0].schema)
    with pytest.raises(MeshDegradedError, match="shrank"):
        lm.partition_wave(batches, part)     # 3 lanes > 2 devices


# -- two-level exchange: partition content over ICI ---------------------------

def test_two_level_exchange_bit_identical_and_rides_ici(df, tcp_table):
    """Default-on two-level plane: reduce partitions owned by this
    executor move lane->lane as all_to_all over ICI (ici_rows counted,
    consumers placed at the owner), and the result stays bit-identical
    to the TCP-only plane with zero resilience noise."""
    got, delta, stats = _run_mesh(df)
    assert got.equals(tcp_table), "two-level result differs from TCP plane"
    assert stats["mesh"]["ici_rows"] > 0, stats
    assert stats["placement"].get("owner", 0) >= 1, stats
    assert stats["mesh"]["degraded"] == 0, stats
    assert not delta, f"two-level run left resilience noise: {delta}"


def test_two_level_off_keeps_content_off_ici(df, tcp_table):
    """The twoLevel knob off: same mesh grouping, same bytes, but no
    partition content rides ICI (the pid program's psum is all that
    touches the collective plane)."""
    got, _, stats = _run_mesh(
        df, {"spark.rapids.tpu.cluster.mesh.exchange.twoLevel": "false"})
    assert got.equals(tcp_table)
    assert stats["mesh"]["ici_rows"] == 0, stats
    assert stats["mesh"]["mesh_tasks"] >= 1, stats


def test_two_level_string_keys_fall_back_without_breaking_group(spark):
    """String keys cannot ride the stacked all_to_all program (per-batch
    dictionaries), so the wave falls back to per-batch slice-and-park —
    WITHOUT degrading the mesh group or charging a fallback."""
    words = ["alpha", "beta", "gamma", "delta", "epsilon"]
    rng = np.random.default_rng(11)
    t = pa.table({"s": pa.array([words[i % 5] for i in
                                 rng.integers(0, 5, 2000)]),
                  "v": pa.array(rng.random(2000))})
    sdf = (spark.create_dataframe(t, num_partitions=N_SPLITS)
           .group_by(F.col("s")).agg(F.sum(F.col("v")).alias("t")))
    with MiniCluster(n_executors=N_EXEC, platform="cpu") as c:
        tcp = c.collect(sdf)
    got, delta, stats = _run_mesh(sdf)
    assert got.equals(tcp), "string-key fallback is not bit-identical"
    assert stats["mesh"]["mesh_tasks"] >= 1, stats
    assert stats["mesh"]["ici_rows"] == 0, stats
    assert stats["mesh"]["degraded"] == 0, stats
    assert not delta, delta


def test_mesh_kill_mid_all_to_all_degrades_to_tcp(df, tcp_table):
    """An executor SIGKILLed INSIDE the content all_to_all: the loss is
    detected, the group re-plans per-split onto TCP under a bumped epoch
    (partial intra-mesh shards dropped with the dead store — bit-identity
    is the no-leak proof), counted in meshDegradedFallbacks."""
    got, delta, stats = _run_mesh(
        df, {"spark.rapids.tpu.test.faults":
             "exec_kill:cluster.mesh.exchange.1:1"})
    assert got.equals(tcp_table), "kill-mid-exchange is not bit-identical"
    assert delta.get("executorsLost", 0) >= 1, delta
    assert delta.get("meshDegradedFallbacks", 0) >= 1, delta
    assert all(stats["alive"]), "pool not restored"
    names = {n for n, _ in tracing.recent_events()}
    assert {"mesh.degraded", "executor.lost"} <= names, names


def test_mesh_exchange_error_degrades_transparently(df, tcp_table):
    """The all_to_all itself failing with the executor alive: transparent
    re-plan onto per-split TCP — surviving partial writes are dropped via
    drop_map_output under the bumped epoch, no executor lost, no attempt
    strike charged, result bit-identical."""
    got, delta, stats = _run_mesh(
        df, {"spark.rapids.tpu.test.faults":
             "error:cluster.mesh.exchange.0:1"})
    assert got.equals(tcp_table)
    assert delta.get("meshDegradedFallbacks", 0) >= 1, delta
    assert delta.get("executorsLost", 0) == 0, delta
    assert delta.get("taskAttempts", 0) == 0, \
        f"degradation must not charge attempt strikes: {delta}"
    names = {n for n, _ in tracing.recent_events()}
    assert "mesh.degraded" in names, names


# -- the q18 ladder query over the combined plane -----------------------------

def _load_multisplit(spark, paths):
    """Load each TPC-H table as an explicit sorted file LIST (one file per
    split) — directory loads collapse to a single FilePartition, which
    would leave nothing for a mesh group to exchange."""
    import os
    dfs = {}
    for name, p in paths.items():
        if os.path.isdir(p):
            fs = sorted(os.path.join(p, f) for f in os.listdir(p)
                        if f.endswith(".parquet"))
            dfs[name] = spark.read_parquet(fs, files_per_partition=1)
        else:
            dfs[name] = spark.read_parquet(p)
        spark.create_or_replace_temp_view(name, dfs[name])
    return dfs


@pytest.mark.slow
def test_mesh_cluster_q18_bit_identical_vs_single_process(tmp_path_factory):
    """TPC-H q18 on a 2-executor MiniCluster driving local meshes: the
    two-level plane (content over ICI) reproduces the TCP-only cluster
    bytes, the twoLevel-off mesh bytes, AND the single-process result."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from spark_rapids_tpu.benchmarks import tpch
    data = str(tmp_path_factory.mktemp("tpch")) + "/sf001"
    paths = tpch.generate(0.01, data)
    spark = TpuSession()
    dfs = _load_multisplit(spark, paths)
    q18 = tpch.QUERIES["q18"](dfs)
    single = q18.collect()
    with MiniCluster(n_executors=N_EXEC, platform="cpu") as c:
        tcp = c.collect(q18)
    conf = RapidsConf(MESH_CONF)
    with MiniCluster(n_executors=N_EXEC, conf=conf, platform="cpu") as c:
        mesh = c.collect(q18)
        stats = dict(c.mesh_stats)
    off_conf = RapidsConf(dict(
        MESH_CONF,
        **{"spark.rapids.tpu.cluster.mesh.exchange.twoLevel": "false"}))
    with MiniCluster(n_executors=N_EXEC, conf=off_conf,
                     platform="cpu") as c:
        mesh_off = c.collect(q18)
        stats_off = dict(c.mesh_stats)
    assert mesh.equals(tcp), "two-level q18 differs from TCP plane"
    assert mesh.equals(single), "two-level q18 differs from 1-process"
    assert mesh.equals(mesh_off), "two-level q18 differs from twoLevel=off"
    assert stats["mesh_tasks"] >= 1 and stats["degraded"] == 0, stats
    assert stats["ici_rows"] > 0, stats
    assert stats_off["ici_rows"] == 0, stats_off
