"""Fleet-chaos gate: replicated warm-cache endpoints with a replica
SIGKILLed mid-stream, client failover to a survivor, and lease adoption.

The fleet contract (runtime/fleet.py + runtime/endpoint.py), proven with
real replica PROCESSES (tools/fleet_replica.py) over shared on-disk state:

  - **Warm-state sharing**: replica A compiles the workload into the shared
    stage cache (its STATS show traces > 0); replica B, started fresh
    afterwards, serves the same shapes with traces == 0 — the Theseus-style
    warm standby, hot from its first query.
  - **No-faults fleet run**: concurrent clients spread across both replicas
    get bit-identical results with every query-scoped resilience counter
    zero AND every process-wide resilience counter zero on both replicas —
    replication with no faults is invisible to every recovery ladder.
  - **Mid-stream SIGKILL failover**: a victim replica (hung by an armed
    hang fault at its first result-frame send, so the kill
    deterministically lands mid-stream) is SIGKILLed while serving; the
    client's ``submit_with_retry`` sees a retryable TransportError,
    rotates to the survivor, and the result is bit-identical to the solo
    oracle.
  - **Lease adoption**: the survivor's sweeper adopts the victim's expired
    lease — membership record unlinked, the victim's orphaned shared-store
    write intents (``*.tmp.<pid>``) reclaimed, a ``fleet.adopt`` event in
    the event log, ``fleetAdoptions`` counted on the survivor.
  - **Survivor health**: after the chaos the survivor still serves
    bit-identically, with zero leaked buffers (memoryLeakedBuffers == 0),
    an idle scheduler, and zero active queries.
  - **Fleet-stats rollup**: with both replicas live, the fleet-aggregate
    counters (EndpointClient.fleet_stats) equal an INDEPENDENT re-sum of
    each replica's raw Prometheus text — the rollup invents and loses
    nothing.
  - **Black-box flight recorder**: the victim gets a request timeout, so
    its heartbeat watchdog detects the hung query and dumps
    ``blackbox-<pid>.json`` BEFORE the SIGKILL lands; the dump names the
    in-flight query (journey id + SQL), and the survivor's ``fleet.adopt``
    event carries the dump's path.
  - **Cross-replica journey**: ``profiler.py journey`` over every
    replica's event log renders the failover under ONE journey id —
    attempt 1 replica_timeout on the victim, attempt 2 served on the
    survivor with traces == 0 — exiting 0 (no schema violations).
  - **Fleet roster**: ``profiler.py fleet`` lists the dead victim from its
    ``departed-`` tombstone — last-known health and blackbox path intact.

Usage:
  python tools/fleet_chaos.py --work-dir DIR [--sf 0.01]
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import re
import signal
import subprocess
import sys
import threading
import time


def _stat_value(stats_text: str, pattern: str) -> float:
    """Last value of the first STATS line matching `pattern` (regex)."""
    for ln in stats_text.splitlines():
        if re.search(pattern, ln) and not ln.startswith("# "):
            return float(ln.rsplit(None, 1)[1])
    raise AssertionError(f"no STATS line matches {pattern!r}")


def _counter_series(stats_text: str) -> dict:
    """Independent counter parse of one raw Prometheus exposition —
    deliberately NOT endpoint.parse_stats_text, so comparing the fleet
    aggregate against a re-sum of these is a real cross-check."""
    out, family, kind = {}, None, None
    for ln in stats_text.splitlines():
        if ln.startswith("# TYPE "):
            _, _, family, kind = ln.split(None, 3)
            continue
        if not ln.strip() or ln.startswith("#"):
            continue
        series, val = ln.rsplit(None, 1)
        if kind == "counter" and series.split("{", 1)[0] == family:
            out[series] = out.get(series, 0.0) + float(val)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="fleet_chaos.py", description=__doc__)
    p.add_argument("--work-dir", required=True,
                   help="scratch root: fleet/stage-cache/history/eventlog/"
                        "data subdirs are created inside")
    p.add_argument("--sf", type=float, default=0.01)
    p.add_argument("--ready-timeout", type=float, default=240.0)
    args = p.parse_args(argv)

    root = pathlib.Path(args.work_dir)
    dirs = {name: root / name for name in
            ("fleet", "stage_cache", "history", "eventlog", "data")}
    for d in dirs.values():
        d.mkdir(parents=True, exist_ok=True)

    repo = pathlib.Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(repo))
    import jax
    jax.config.update("jax_platforms", "cpu")
    import spark_rapids_tpu  # noqa: F401  (enables x64)
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.runtime import metrics as M
    from spark_rapids_tpu.runtime.endpoint import EndpointClient
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.sql.tpch_queries import SQL_QUERIES

    failures = []

    def check(cond, msg):
        if not cond:
            failures.append(msg)

    # -- solo oracle: same engine, same data, NO shared stores ---------------
    # (the solo session must not touch the stage cache, or "replica A
    # compiled the shapes" would be pre-warmed from this process)
    paths = tpch.generate(args.sf, str(dirs["data"]))
    solo_spark = TpuSession({
        "spark.rapids.tpu.sql.format.parquet.reader.type": "COALESCING",
        "spark.rapids.tpu.pipeline.enabled": True,
    })
    tpch.load(solo_spark, paths, files_per_partition=4)
    workload = ("q1", "q3", "q5")
    solo = {q: solo_spark.sql(SQL_QUERIES[q]).collect().to_pylist()
            for q in workload}

    # generous lease so a GIL stall during a replica's compile burst can't
    # transiently expire a LIVE member (spurious adoption would trip the
    # no-faults zero-counter gate); the victim's lease still expires within
    # seconds of the SIGKILL
    lease_timeout, heartbeat = 8.0, 1.0

    def spawn_replica(tag, faults=None, request_timeout=None):
        cmd = [sys.executable, str(repo / "tools" / "fleet_replica.py"),
               "--fleet-dir", str(dirs["fleet"]),
               "--data-dir", str(dirs["data"]), "--sf", str(args.sf),
               "--stage-cache-dir", str(dirs["stage_cache"]),
               "--history-dir", str(dirs["history"]),
               "--eventlog-dir", str(dirs["eventlog"]),
               "--lease-timeout", str(lease_timeout),
               "--heartbeat", str(heartbeat)]
        if faults:
            cmd += ["--faults", faults]
        if request_timeout is not None:
            cmd += ["--request-timeout", str(request_timeout)]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True, env=env)
        deadline = time.monotonic() + args.ready_timeout
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
            raise RuntimeError(f"replica {tag} never became READY")
        # drain the replica's stdout so a chatty child can't fill the pipe
        threading.Thread(target=proc.stdout.read, daemon=True).start()
        print(f"replica {tag}: pid={proc.pid} port={port}", file=sys.stderr)
        return proc, ("127.0.0.1", port)

    report = {}

    # -- phase 1: replica A compiles the workload into the shared cache ------
    proc_a, addr_a = spawn_replica("A")
    cli_a = EndpointClient(addr_a, timeout_s=300)
    for q in workload:
        rows = cli_a.submit(SQL_QUERIES[q]).to_pylist()
        check(rows == solo[q], f"warm {q} on A diverged from solo")
    a_traces = _stat_value(cli_a.stats(), r'srt_fuse_total\{kind="traces"\}')
    check(a_traces > 0, f"replica A compiled nothing (traces={a_traces})")
    report["a_traces"] = a_traces

    # -- phase 2: fresh replica B + no-faults fleet load ----------------------
    proc_b, addr_b = spawn_replica("B")
    outcomes = {}
    lock = threading.Lock()

    def fleet_client(name, q, primary):
        # each worker leads with its own primary replica so both serve load
        addrs = [addr_a, addr_b] if primary == 0 else [addr_b, addr_a]
        cli = EndpointClient(addrs, timeout_s=300)
        try:
            rows = cli.submit_with_retry(SQL_QUERIES[q]).to_pylist()
            with lock:
                outcomes[name] = {"rows": rows, "summary": cli.last_summary}
        except BaseException as e:  # noqa: BLE001 — reported, asserted below
            with lock:
                outcomes[name] = {"error": repr(e)[:200]}

    workers = [threading.Thread(target=fleet_client,
                                args=(f"{q}@{i}", q, i % 2), daemon=True)
               for i, q in enumerate(workload * 2)]
    for t in workers:
        t.start()
    for t in workers:
        t.join(timeout=300)
    for name, o in outcomes.items():
        q = name.split("@")[0]
        check(o.get("rows") == solo[q],
              f"no-faults fleet {name} diverged ({o.get('error', 'rows')})")
        check(not (o.get("summary") or {}).get("resilience"),
              f"no-faults fleet {name} leaked scoped resilience: "
              f"{o.get('summary')}")
    cli_b = EndpointClient(addr_b, timeout_s=300)
    stats_b = cli_b.stats()
    b_traces = _stat_value(stats_b, r'srt_fuse_total\{kind="traces"\}')
    check(b_traces == 0,
          f"replica B retraced {b_traces} shapes replica A had compiled")
    report["b_traces"] = b_traces
    # load spread over two live replicas is routing, not recovery
    check(not any(M.resilience_snapshot().values()),
          f"no-faults fleet load counted client-side resilience: "
          f"{M.resilience_snapshot()}")
    for stats_text, tag in ((cli_a.stats(), "A"), (stats_b, "B")):
        for ln in stats_text.splitlines():
            if ln.startswith("srt_resilience_total"):
                check(ln.endswith(" 0"),
                      f"no-faults replica {tag} resilience nonzero: {ln}")
    check(_stat_value(stats_b, r"srt_fleet_live_members") == 2,
          "replica B does not see 2 live members")

    # -- phase 2b: fleet-stats rollup over the two live replicas -------------
    # the aggregate must equal an INDEPENDENT re-sum of each replica's raw
    # exposition for every counter series — the rollup invents nothing
    fleet_cli = EndpointClient([addr_a, addr_b], timeout_s=300)
    fs = fleet_cli.fleet_stats()
    check(fs["live"] == 2 and fs["total"] == 2,
          f"fleet-stats saw {fs['live']}/{fs['total']} replicas, want 2/2")
    resum = {}
    for rep in fs["replicas"].values():
        for series, v in _counter_series(rep.get("raw", "")).items():
            resum[series] = resum.get(series, 0.0) + v
    agg = fs["aggregate"]["counters"]
    check(set(agg) == set(resum),
          f"fleet aggregate counter families diverge from the re-sum: "
          f"{sorted(set(agg) ^ set(resum))[:8]}")
    for series in resum:
        if abs(agg.get(series, 0.0) - resum[series]) > 1e-9:
            check(False, f"fleet aggregate {series}={agg.get(series)} != "
                         f"sum of per-replica {resum[series]}")
    report["fleet_counter_series"] = len(resum)

    # -- phase 3: SIGKILL a victim mid-stream; client fails over --------------
    # the victim's armed hang fault hangs q5 forever at its first result
    # frame (endpoint.send is a maybe_inject_any site, so "hang" fires
    # there), so the kill deterministically lands while the client is
    # mid-stream (a timed slow fault loses the race when the shared stage
    # cache makes the query finish in under the kill delay). The victim
    # also gets a request timeout: its connection thread is the hung one,
    # so the HEARTBEAT watchdog must detect the stuck query, close its
    # journey (replica_timeout) and dump the flight recorder — all before
    # the SIGKILL, which is exactly the post-mortem the dump exists for.
    proc_v, addr_v = spawn_replica("victim", faults="hang:endpoint.send:1",
                                   request_timeout=1.0)
    flight = {}
    retries = []

    def failover_client():
        cli = EndpointClient([addr_v, addr_b], timeout_s=300)
        try:
            flight["rows"] = cli.submit_with_retry(
                SQL_QUERIES["q5"],
                on_retry=lambda a, d: retries.append(a)).to_pylist()
            flight["summary"] = cli.last_summary
            flight["journey"] = cli.last_journey
        except BaseException as e:  # noqa: BLE001
            flight["error"] = repr(e)[:200]

    ft = threading.Thread(target=failover_client, daemon=True)
    ft.start()
    # long enough for the query to hang, age past the 1s request timeout,
    # and a heartbeat (1s) to run the watchdog sweep + blackbox dump
    time.sleep(4.0)
    os.kill(proc_v.pid, signal.SIGKILL)
    killed_at = time.monotonic()
    # plant an orphaned write intent under the victim's pid: the mid-write
    # state a crash leaves in the shared store, reclaimed only by adoption
    orphan = dirs["stage_cache"] / f"deadbeef.xc.tmp.{proc_v.pid}-0"
    orphan.write_bytes(b"half-written executable")
    ft.join(timeout=300)
    check(flight.get("rows") == solo["q5"],
          f"failover result diverged: {flight.get('error', 'rows')}")
    check(retries, "client never retried — the kill missed the in-flight "
                   "window")
    snap = M.resilience_snapshot()
    check(snap.get("replicaFailovers", 0) >= 1,
          f"no replica failover counted client-side: {snap}")
    report["failover_retries"] = len(retries)

    # -- phase 4: a survivor adopts the victim's lease ------------------------
    victim_lease = dirs["fleet"] / f"replica-127.0.0.1-{addr_v[1]}-{proc_v.pid}.json"
    deadline = time.monotonic() + lease_timeout + 6 * heartbeat + 10
    while time.monotonic() < deadline and (victim_lease.exists()
                                           or orphan.exists()):
        time.sleep(0.1)
    report["adoption_s"] = round(time.monotonic() - killed_at, 2)
    check(not victim_lease.exists(), "victim lease never adopted")
    check(not orphan.exists(), "victim's orphaned write intent not reclaimed")
    adoptions = sum(_stat_value(c.stats(), r'srt_fleet_total\{event="adoptions"\}')
                    for c in (cli_a, cli_b))
    check(adoptions >= 1, f"no adoption counted on survivors ({adoptions})")
    adopt_events = []
    for f in dirs["eventlog"].glob("*.jsonl"):
        for ln in f.read_text().splitlines():
            try:
                rec = json.loads(ln)
            except ValueError:
                continue
            if rec.get("event") == "fleet.adopt":
                adopt_events.append(rec)
    check(adopt_events, "no fleet.adopt event in the event log")
    check(any(rec.get("dead_pid") == proc_v.pid for rec in adopt_events),
          f"fleet.adopt events name the wrong pid: {adopt_events}")

    # -- phase 4b: the victim's black-box dump survived the SIGKILL ----------
    bb_path = dirs["eventlog"] / f"blackbox-{proc_v.pid}.json"
    check(bb_path.exists(), "victim wrote no blackbox dump before dying")
    jny = flight.get("journey")
    check(jny, "client recorded no journey id for the failover flight")
    if bb_path.exists():
        bb = json.loads(bb_path.read_text())
        check(bb.get("reason") == "stuck_query",
              f"blackbox dumped for {bb.get('reason')!r}, want stuck_query")
        named = [i for i in bb.get("inflight", [])
                 if i.get("journey") == jny]
        check(named, f"blackbox in-flight registry does not name the "
                     f"hung journey {jny}: {bb.get('inflight')}")
        check(named and named[0].get("sql"),
              "blackbox in-flight entry carries no SQL")
        check(bb.get("events"), "blackbox event ring is empty")
        check(any(rec.get("blackbox") == str(bb_path)
                  for rec in adopt_events),
              f"no fleet.adopt event carries the victim's blackbox path "
              f"{bb_path}")
        report["blackbox_inflight"] = len(bb.get("inflight", []))

    # -- phase 4c: profiler renders the cross-replica failover timeline ------
    logs = sorted(str(f) for f in dirs["eventlog"].glob("*.jsonl"))
    jr = subprocess.run(
        [sys.executable, str(repo / "tools" / "profiler.py"), "journey",
         *logs, "--journey", str(jny), "--json"],
        capture_output=True, text=True)
    check(jr.returncode == 0,
          f"profiler journey exited {jr.returncode}: {jr.stderr[:500]}")
    if jr.returncode == 0:
        ja = json.loads(jr.stdout)
        js = ja.get("journeys", [])
        check(len(js) == 1, f"journey {jny} rendered {len(js)} times")
        attempts = js[0]["attempts"] if js else []
        check(len(attempts) >= 2,
              f"failover journey has {len(attempts)} attempts, want >= 2")
        if len(attempts) >= 2:
            a1, a2 = attempts[0], attempts[-1]
            check(a1["outcome"] == "replica_timeout"
                  and str(proc_v.pid) in str(a1["replica"]),
                  f"attempt 1 should be replica_timeout on the victim: {a1}")
            check(a2["outcome"] == "served" and a2["traces"] == 0
                  and str(proc_v.pid) not in str(a2["replica"]),
                  f"attempt 2 should be served warm on a survivor: {a2}")
            check(js[0]["failovers"] >= 1,
                  f"no failover derived in the merged timeline: {js[0]}")

    # -- phase 4d: the fleet roster still explains the dead victim -----------
    fr = subprocess.run(
        [sys.executable, str(repo / "tools" / "profiler.py"), "fleet",
         str(dirs["fleet"]), "--json"],
        capture_output=True, text=True)
    check(fr.returncode == 0,
          f"profiler fleet exited {fr.returncode}: {fr.stderr[:500]}")
    if fr.returncode == 0:
        roster = json.loads(fr.stdout)
        dead = [r for r in roster["replicas"]
                if r["status"] == "departed" and r.get("pid") == proc_v.pid]
        check(dead, f"victim pid {proc_v.pid} missing from the departed "
                    f"roster: {[r.get('replica') for r in roster['replicas']]}")
        if dead:
            check(dead[0].get("health", {}).get("active_queries") is not None,
                  f"victim tombstone lost its last-known health: {dead[0]}")
            check(dead[0].get("blackbox") == str(bb_path),
                  f"victim tombstone lost its blackbox path: {dead[0]}")
        check(roster["live"] >= 2, f"live survivors missing from the "
                                   f"roster: {roster['live']}")

    # -- phase 5: survivor health after the chaos -----------------------------
    rows = cli_b.submit(SQL_QUERIES["q1"]).to_pylist()
    check(rows == solo["q1"], "survivor q1 diverged after the chaos")
    stats_b = cli_b.stats()
    check(_stat_value(stats_b,
                      r'srt_resilience_total\{counter="memoryLeakedBuffers"\}')
          == 0, "survivor leaked catalog buffers")
    check(_stat_value(stats_b, r"srt_scheduler_running") == 0,
          "survivor scheduler still busy")
    check(_stat_value(stats_b, r"srt_scheduler_queue_depth") == 0,
          "survivor queue not drained")

    # -- graceful shutdown of the survivors -----------------------------------
    for proc, tag in ((proc_a, "A"), (proc_b, "B")):
        proc.send_signal(signal.SIGTERM)
    for proc, tag in ((proc_a, "A"), (proc_b, "B")):
        try:
            rc = proc.wait(timeout=90)
            check(rc == 0, f"replica {tag} drain exited {rc}")
        except subprocess.TimeoutExpired:
            proc.kill()
            failures.append(f"replica {tag} did not drain within 90s")
    check(not list(dirs["fleet"].glob("replica-*.json")),
          "leases left behind after graceful drain")

    report["adopt_events"] = len(adopt_events)
    report["failures"] = failures
    print(json.dumps(report, default=str))
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
