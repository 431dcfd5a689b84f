"""Fine-grained fault recovery in the MiniCluster driver scheduler.

Mirrors Spark's task-level fault-tolerance contracts (task retry with
attempt limits, executor exclusion, FetchFailed → recompute only the lost
map outputs) against the driver scheduler in cluster/minicluster.py: an
injected executor SIGKILL (`exec_kill` fault kind) mid-stage must recover
through the lineage-scoped ladder — respawn the slot, re-run ONLY the dead
peer's map splits under a bumped shuffle epoch, re-fetch — to a result
bit-identical with a clean run, with recovery cost proportional to the
loss (proven by the resilience counters) and the whole-query `_heal()`
fallback never firing."""

import time

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

N_EXEC = 3
N_SPLITS = 6


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
def clean_table(df):
    """The fault-free oracle: the SAME query on the SAME cluster shape with
    no chaos armed — every recovery test must reproduce these bytes."""
    with MiniCluster(n_executors=N_EXEC, platform="cpu") as c:
        return c.collect(df)


def _run_chaos(df, settings, no_heal=True, warm=False):
    """Collect `df` on a 3-executor cluster with `settings`; returns
    (table, resilience-counter deltas, cluster stats dict)."""
    base = M.resilience_snapshot()
    conf = RapidsConf(settings)
    with MiniCluster(n_executors=N_EXEC, conf=conf, platform="cpu") as c:
        heals = []
        orig = c._heal
        c._heal = lambda: (heals.append(1), orig())[-1]
        if warm:
            c.collect(df)       # absorb cold-compile latency (see @SKIP)
        got = c.collect(df)
        stats = {"heals": len(heals), "blacklist": set(c._blacklist),
                 "gen": list(c._gen),
                 "alive": [p.is_alive() for p in c._procs]}
    end = M.resilience_snapshot()
    delta = {k: end[k] - base[k] for k in end if end[k] - base[k]}
    if no_heal:
        assert stats["heals"] == 0, \
            f"whole-query heal fired; partial recovery expected ({delta})"
    return got, delta, stats


def test_exec_kill_mid_map_stage_bit_identical(df, clean_table):
    """SIGKILL one of 3 executors mid-map-stage (after its first map task
    parked blocks, via @SKIP): the driver must recompute ONLY the dead
    peer's splits and still produce the clean run's exact bytes."""
    got, delta, stats = _run_chaos(
        df, {"spark.rapids.tpu.test.faults": "exec_kill:cluster.map.0:1@1"})
    assert got.equals(clean_table), "recovered result is not bit-identical"
    assert delta.get("executorsLost", 0) >= 1
    assert delta.get("stagePartialRecomputes", 0) >= 1
    # proportionality: strictly fewer map tasks re-ran than a full stage
    assert 1 <= delta.get("mapTasksRecomputed", 0) < N_SPLITS, delta
    assert all(stats["alive"]), "pool not restored"


def test_exec_kill_mid_result_stage_bit_identical(df, clean_table):
    got, delta, stats = _run_chaos(
        df, {"spark.rapids.tpu.test.faults": "exec_kill:cluster.result.1:1"})
    assert got.equals(clean_table)
    assert delta.get("executorsLost", 0) >= 1
    # the dead peer hosted map splits reducers still need: partial recompute
    assert delta.get("stagePartialRecomputes", 0) >= 1
    assert all(stats["alive"])
    names = {n for n, _ in tracing.recent_events()}
    assert {"executor.lost", "stage.recompute.partial"} <= names, names


def test_partial_recompute_covers_exactly_the_lost_splits(df, clean_table):
    """Kill an executor AFTER the map stage completed, with the host map
    captured first: the recompute counter must equal the dead peer's split
    count, and only the dead slot's incarnation may bump (no pool heal)."""
    base = M.resilience_snapshot()
    with MiniCluster(n_executors=N_EXEC, platform="cpu") as c:
        state = {"lost": None}

        def kill_zero(cl):
            if state["lost"] is None:
                st = cl._tracker.state(cl._tracker.sids()[0])
                state["lost"] = sorted(
                    s for s, h in st.hosts.items() if h == 0)
                cl._procs[0].kill()
                cl._procs[0].join(timeout=5)

        c._after_stage_hook = kill_zero
        got = c.collect(df)
        gens = list(c._gen)
    delta = {k: v - base[k]
             for k, v in M.resilience_snapshot().items() if v - base[k]}
    assert got.equals(clean_table)
    assert 1 <= len(state["lost"]) < N_SPLITS
    assert delta.get("mapTasksRecomputed", 0) == len(state["lost"]), \
        (delta, state["lost"])
    assert gens[0] == 2 and gens[1:] == [1, 1], gens


def test_task_failure_retries_then_blacklists(df, clean_table):
    """Two injected task failures on the same executor: each retry lands
    elsewhere, the second strike blacklists the slot, the query succeeds."""
    got, delta, stats = _run_chaos(
        df, {"spark.rapids.tpu.test.faults": "error:cluster.map.1:2"})
    assert got.equals(clean_table)
    assert delta.get("taskAttempts", 0) >= 2
    assert delta.get("executorsBlacklisted", 0) == 1
    assert stats["blacklist"] == {1}
    ev = [a for n, a in tracing.recent_events("task.attempt")]
    assert any(a.get("reason") == "failure" for a in ev), ev


def test_task_attempts_exhaust_to_query_failure(df):
    """More consecutive failures than cluster.task.maxFailures: the query
    must surface the task's error, not loop forever."""
    conf = RapidsConf({
        "spark.rapids.tpu.test.faults": "error:cluster.map:99",
        "spark.rapids.tpu.cluster.task.maxFailures": 2,
        # keep every slot placeable so exhaustion (not ExecutorLostError →
        # heal-ladder) terminates the query
        "spark.rapids.tpu.cluster.blacklist.maxTaskFailures": 99})
    with MiniCluster(n_executors=N_EXEC, conf=conf, platform="cpu") as c:
        with pytest.raises(RuntimeError, match="failed 2 times"):
            c.collect(df)


@pytest.mark.slow
def test_task_timeout_kills_hang_and_retries(df, clean_table):
    """A hung task past cluster.task.timeoutSeconds: the driver kills the
    hung executor, charges a timeout attempt, and retries elsewhere.
    Warm-up query first — a COLD first task's XLA compile would trip any
    honest deadline (the @SKIP arms the hang for query 2)."""
    got, delta, stats = _run_chaos(
        df, {"spark.rapids.tpu.test.faults": "hang:cluster.map.2:1@2",
             "spark.rapids.tpu.cluster.task.timeoutSeconds": 12.0},
        warm=True)
    assert got.equals(clean_table)
    assert delta.get("executorsLost", 0) >= 1
    assert delta.get("taskAttempts", 0) >= 1
    ev = [a for n, a in tracing.recent_events("task.attempt")]
    assert any(a.get("reason") == "timeout" for a in ev), ev


@pytest.mark.slow
def test_speculation_dedup_bit_identical(df, clean_table):
    """A hung straggler with speculation on: the duplicate wins the race,
    the loser's map output is discarded (dedup keyed by (shuffle, split)),
    and the result is still the clean run's exact bytes — no duplicated or
    lost blocks."""
    got, delta, stats = _run_chaos(
        df, {"spark.rapids.tpu.test.faults": "hang:cluster.map.0:1@2",
             "spark.rapids.tpu.cluster.speculation.enabled": True,
             "spark.rapids.tpu.cluster.speculation.multiplier": 1.5,
             "spark.rapids.tpu.cluster.task.timeoutSeconds": 12.0},
        warm=True)
    assert got.equals(clean_table)
    assert delta.get("speculationWon", 0) >= 1, delta


def test_heartbeat_expiry_recovers_between_queries(df, clean_table):
    """A silent death between queries is caught by the driver's poll of the
    heartbeat manager's expire_dead, and the slot is respawned through the
    same lineage-scoped path."""
    conf = RapidsConf(
        {"spark.rapids.tpu.cluster.heartbeat.timeoutSeconds": 0.4})
    with MiniCluster(n_executors=N_EXEC, conf=conf, platform="cpu") as c:
        assert c.collect(df).equals(clean_table)
        c._procs[1].kill()
        c._procs[1].join(timeout=5)
        time.sleep(0.6)
        assert c.check_liveness() == [1]
        assert all(p.is_alive() for p in c._procs)
        assert c.collect(df).equals(clean_table)
    ev = [a for n, a in tracing.recent_events("executor.lost")]
    assert any(a.get("reason") == "heartbeat.expired" for a in ev), ev


def test_all_empty_result_keeps_declared_schema(spark):
    """Satellite: an all-empty multi-executor result must derive its schema
    from the plan's declared output, not the first schema-less reply."""
    rng = np.random.default_rng(9)
    t = pa.table({"k": pa.array(rng.integers(0, 9, 400), type=pa.int64()),
                  "v": pa.array(rng.random(400))})
    df_empty = (spark.create_dataframe(t, num_partitions=4)
                .filter(F.col("k") < F.lit(-1))
                .group_by(F.col("k")).agg(F.sum(F.col("v")).alias("s")))
    with MiniCluster(n_executors=N_EXEC, platform="cpu") as c:
        out = c.collect(df_empty)
    assert out.num_rows == 0
    assert out.column_names == ["k", "s"]
    assert out.schema.field("k").type == pa.int64()
    assert out.schema.field("s").type == pa.float64()


def test_shutdown_reaps_all_executor_processes(df):
    """Satellite: shutdown() must escalate terminate → kill and join so no
    executor outlives the cluster, even one killed uncleanly mid-life."""
    c = MiniCluster(n_executors=N_EXEC, platform="cpu")
    try:
        c.collect(df)
        c._procs[2].kill()      # an already-dead slot must not hang reaping
    finally:
        c.shutdown()
    assert all(p is not None and not p.is_alive() for p in c._procs)
    for conn in c._conns:
        assert conn.closed
