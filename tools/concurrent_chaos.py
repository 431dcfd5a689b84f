"""Concurrent-chaos gate: 4 concurrent TPC-H queries, one killed by its
deadline, one with injected join-build OOMs, one shed at the front door.

The multi-tenant isolation contract (runtime/scheduler.py), proven end to
end in one process:

  - q18 runs with ``oom:joins.build:2`` armed: both injected OOMs land in
    ITS join builds (it launches first, with a head start over the peers),
    the PR-2 retry ladder recovers, and its result is bit-identical to a
    solo run — with the recovery visible ONLY in q18's query-scoped
    resilience counters.
  - q5 runs under ``scheduler.query.deadlineSeconds`` sized to fire
    mid-query: it dies with QueryDeadlineError, draining its pipeline
    without leaking threads, device buffers, or semaphore permits.
  - q1 and q3 are the survivors: bit-identical to solo runs, with EVERY
    query-scoped resilience counter zero — a peer's OOM recovery and a
    peer's cancellation must not leak into their scopes.
  - a 5th submission sheds on queue timeout with a retryable
    QueryRejectedError whose backoff hint survives a pickle round-trip
    (the serving-endpoint contract).

All four lifecycle outcomes land in the structured event log
(query.admitted / query.deadline / query.shed / query.end-with-oom.retry),
which ci.sh then asserts on.

Usage:
  python tools/concurrent_chaos.py --data-dir DIR --eventlog-dir DIR
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import pickle
import sys
import threading
import time


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="concurrent_chaos.py",
                                description=__doc__)
    p.add_argument("--data-dir", required=True)
    p.add_argument("--eventlog-dir", required=True)
    p.add_argument("--sf", type=float, default=0.01)
    args = p.parse_args(argv)

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    import jax
    jax.config.update("jax_platforms", "cpu")
    import spark_rapids_tpu  # noqa: F401  (enables x64)
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.runtime import eventlog
    from spark_rapids_tpu.runtime import faults
    from spark_rapids_tpu.runtime import scheduler as SCHED
    from spark_rapids_tpu.runtime.memory import DeviceManager
    from spark_rapids_tpu.runtime.semaphore import TpuSemaphore
    from spark_rapids_tpu.session import TpuSession

    paths = tpch.generate(args.sf, args.data_dir)
    base_conf = {
        "spark.rapids.tpu.sql.format.parquet.reader.type": "COALESCING",
        "spark.rapids.tpu.pipeline.enabled": True,
    }

    def query_df(spark, name):
        dfs = tpch.load(spark, paths, files_per_partition=4)
        return getattr(tpch, name)(dfs)

    # -- solo baselines (faults off, before the event log opens) -------------
    solo_spark = TpuSession(base_conf)
    solo = {name: query_df(solo_spark, name).collect().to_pylist()
            for name in ("q1", "q3", "q18")}
    # warm q5 (first run pays the compiles), THEN measure: the deadline must
    # be sized off the warm wall the chaos run will actually see
    query_df(solo_spark, "q5").collect()
    q5_wall0 = time.perf_counter()
    query_df(solo_spark, "q5").collect()
    q5_wall = time.perf_counter() - q5_wall0

    cat = DeviceManager.get().catalog
    buffers_base = cat.num_buffers

    # -- arm the chaos run ----------------------------------------------------
    TpuSession(dict(base_conf, **{
        "spark.rapids.tpu.eventLog.dir": args.eventlog_dir,
        "spark.rapids.tpu.scheduler.maxConcurrent": 4,
        "spark.rapids.tpu.test.faults": "oom:joins.build:2",
        "spark.rapids.tpu.test.faults.seed": 7,
    }))

    outcomes: dict = {}
    lock = threading.Lock()

    def record(name, **kv):
        with lock:
            outcomes[name] = kv

    def run_query(name, delay_s, conf_extra=None):
        time.sleep(delay_s)
        spark = TpuSession(dict(base_conf, **(conf_extra or {})))
        df = query_df(spark, name)
        try:
            rows = df.collect().to_pylist()
            qm = df._last_collector
            record(name, rows=rows, query_id=qm.query_id,
                   resilience={k: v for k, v in
                               qm.query_resilience().items() if v})
        except SCHED.QueryCancelledError as e:
            record(name, error=type(e).__name__, reason=e.reason)
        except BaseException as e:  # noqa: BLE001 — reported, asserted below
            record(name, error=type(e).__name__, detail=repr(e)[:200])

    # q18 first (alone for its head start) so the 2 armed join-build OOMs
    # land in ITS builds, not a survivor's; its split floor drops so the
    # sf0.01-sized build batches stay splittable (the PR-2 chaos test's
    # setting). The deadline is sized off the measured solo q5 wall so it
    # fires mid-query — under 4-way concurrency q5 only runs slower
    threads = [
        threading.Thread(target=run_query, args=("q18", 0.0), kwargs={
            "conf_extra": {
                "spark.rapids.tpu.memory.retry.splitFloorBytes": "1b"}},
            daemon=True),
        threading.Thread(target=run_query, args=("q5", 0.35), kwargs={
            "conf_extra": {
                "spark.rapids.tpu.scheduler.query.deadlineSeconds":
                    max(0.02, q5_wall / 3)}}, daemon=True),
        threading.Thread(target=run_query, args=("q1", 0.40), daemon=True),
        threading.Thread(target=run_query, args=("q3", 0.45), daemon=True),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    faults.reset()

    # 5th submission against a deterministically full scheduler: a direct
    # occupant ticket holds the one slot, so the session's submission
    # queues and sheds at its 50ms queue timeout — no wall-clock race with
    # the (already finished) chaos queries
    sched = SCHED.QueryScheduler.get()
    occupant = f"occupant-{id(sched):x}"
    sched.submit(occupant, 1, description="shed-gate occupant")
    saved_max = sched.max_concurrent
    sched.max_concurrent = 1
    shed_err = None
    try:
        spark5 = TpuSession(dict(base_conf, **{
            "spark.rapids.tpu.scheduler.queue.timeoutSeconds": 0.05}))
        query_df(spark5, "q1").collect()
    except SCHED.QueryRejectedError as e:
        shed_err = e
    finally:
        sched.max_concurrent = saved_max
        sched.release(occupant)
    eventlog.shutdown()

    # -- assertions -----------------------------------------------------------
    failures = []

    def check(cond, msg):
        if not cond:
            failures.append(msg)

    # survivors bit-identical to solo, with clean query scopes
    for name in ("q1", "q3"):
        o = outcomes.get(name, {})
        check(o.get("rows") == solo[name], f"{name} rows differ from solo")
        check(not o.get("resilience"),
              f"{name} resilience leaked: {o.get('resilience')}")
    # each query ran under a scope of its own
    qids = [o["query_id"] for o in outcomes.values() if "query_id" in o]
    check(len(set(qids)) == len(qids) >= 3, f"query ids not distinct: {qids}")
    # the OOM victim recovered bit-identically, recovery in ITS scope only
    o18 = outcomes.get("q18", {})
    check(o18.get("rows") == solo["q18"], "q18 rows differ from solo")
    check(o18.get("resilience", {}).get("numOomRetries", 0) >= 1,
          f"q18 saw no oom retry in its scope: {o18.get('resilience')}")
    # the deadline victim died with the typed error
    o5 = outcomes.get("q5", {})
    check(o5.get("error") == "QueryDeadlineError",
          f"q5 outcome was {o5}, wanted QueryDeadlineError")
    # the 5th submission shed with a round-trippable backoff hint
    check(shed_err is not None, "5th submission was not shed")
    if shed_err is not None:
        rt = pickle.loads(pickle.dumps(shed_err))
        check(rt.retryable and rt.backoff_hint_s > 0
              and rt.backoff_hint_s == shed_err.backoff_hint_s,
              f"QueryRejectedError round-trip lost the hint: {vars(rt)}")
    # nothing leaked: threads, device buffers, semaphore permits
    gc.collect()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and (
            cat.num_buffers > buffers_base
            or any(t.name.startswith("srt-pipe-")
                   for t in threading.enumerate())):
        time.sleep(0.1)
    check(cat.num_buffers <= buffers_base,
          f"leaked {cat.num_buffers - buffers_base} catalog buffers")
    check(not TpuSemaphore.get()._holders,
          f"leaked semaphore permits: {TpuSemaphore.get()._holders}")
    stragglers = [t.name for t in threading.enumerate()
                  if t.name.startswith("srt-pipe-")]
    check(not stragglers, f"leaked pipeline threads: {stragglers}")

    print(json.dumps({
        "outcomes": {k: {kk: vv for kk, vv in v.items() if kk != "rows"}
                     for k, v in outcomes.items()},
        "shed": (None if shed_err is None else {
            "backoff_hint_s": shed_err.backoff_hint_s,
            "reason": shed_err.reason}),
        "failures": failures,
    }, default=str))
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
