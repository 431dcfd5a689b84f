#!/usr/bin/env bash
# Single entry point for CI and local premerge (reference premerge scripts role).
set -euo pipefail
cd "$(dirname "$0")"

echo "== unit + integration suite (virtual 8-device CPU mesh) =="
python -m pytest tests/ -q

echo "== driver entry points =="
JAX_PLATFORMS=cpu python -c "
import jax
import __graft_entry__ as g
fn, args = g.entry()
out = jax.jit(fn)(*args)
assert out is not None
print('entry() ok')"
python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun ok')"

echo "== on-chip tool dry-runs (CPU platform) =="
# The commands that run on the chip (chip_smoke.py is the first of them),
# end-to-end on the CPU platform at a tiny scale, so that their control flow
# cannot regress unseen. None of these lines is a device measurement.
JAX_PLATFORMS=cpu python chip_smoke.py --rehearse | tail -1
python tools/tpu_correctness.py --dryrun-cpu --out /tmp/ci_tpu_correctness.json
python - <<'PYEOF'
import json
d = json.load(open("/tmp/ci_tpu_correctness.json"))
assert d["ok"] and d["platform"] == "cpu", d
print("correctness dry-run ok:", len(d["checks"]), "checks")
PYEOF

echo "== radix spine: kernel interpret tests =="
# the Pallas kernels' arithmetic in interpret mode
JAX_PLATFORMS=cpu python -m pytest tests/test_pallas.py \
  tests/test_readahead.py -q

echo "== chaos: task-scoped OOM retry + deterministic fault injection =="
# fast chaos gate (fixed fault seeds inside the suite, so the injection
# schedule can never drift between runs): injected join-build OOMs and
# dropped fetches must recover to bit-identical results, with the recovery
# visible in the resilience counters
JAX_PLATFORMS=cpu python -m pytest tests/test_retry_faults.py -q

echo "== pipelined executor: q18 A/B bit-identity + chaos with the pipeline on =="
# q18 with pipeline.enabled=true and =false (the reader config that feeds the
# pipeline) must give the same rows; which is faster is the chip's to say
JAX_PLATFORMS=cpu python - <<'PYEOF'
import spark_rapids_tpu  # noqa: F401  (enables x64)
from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.session import TpuSession

paths = tpch.generate(0.01, "/tmp/tpch_ci_sf0.01")

def run(pipeline_on):
    spark = TpuSession({
        "spark.rapids.tpu.sql.format.parquet.reader.type": "COALESCING",
        "spark.rapids.tpu.pipeline.enabled": pipeline_on})
    dfs = tpch.load(spark, paths, files_per_partition=4)
    return tpch.q18(dfs).collect().to_pylist()

assert run(True) == run(False), "pipeline on/off results differ"
print("pipeline A/B ok: q18 rows identical with the pipeline on and off")
PYEOF
# chaos once with the pipeline explicitly on: an injected worker-thread
# decode fault must fail cleanly (no leaked registrations/threads) and an
# injected split-OOM inside a pipeline segment must recover bit-identically
JAX_PLATFORMS=cpu python -m pytest tests/test_pipeline.py -q

echo "== whole-stage chain fusion: >=3x per-batch dispatch drop, bit-identical =="
# the broadcast-join probe chains (q18's agg->orders->customer shape, q5's
# orders->customer hops) must collapse to ~1 dispatch per stream batch: the
# chain-region dispatch count (the spine of BHJ/Project/Filter nodes the
# chain absorbed) drops >=3x vs stageFusion.enabled=false, with bit-identical
# rows. q18's canonical HAVING>300 yields 0 rows at SF 0.01 (no emits to
# save on the unfused side), so the flowing-rows ratio is asserted on q5 and
# on q18's own plan shape with the threshold lowered; canonical q18 asserts
# chain formation + bit-identity.
JAX_PLATFORMS=cpu python - <<'PYEOF'
import jax
import spark_rapids_tpu  # noqa: F401  (enables x64)
import spark_rapids_tpu.functions as F
from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.runtime import stats as STATS

# 12 files -> 12 stream batches: enough for the per-hop one-off build-prep
# dispatches to amortize out of the region ratio
paths = tpch.generate(0.01, "/tmp/tpch_ci_sf0.01_f12", files_per_table=12)
c = F.col

def q18_flow(dfs):
    # q18's exact plan shape with the HAVING threshold lowered so every
    # stream batch carries matches through both probe hops
    li = dfs["lineitem"]
    big = (li.group_by(c("l_orderkey"))
           .agg(F.sum(c("l_quantity")).alias("sum_qty"))
           .filter(c("sum_qty") > F.lit(30.0)))
    orders = dfs["orders"].select(
        c("o_orderkey").alias("l_orderkey"), c("o_custkey"),
        c("o_orderdate"), c("o_totalprice"))
    cust = dfs["customer"].select(c("c_custkey").alias("o_custkey"))
    return big.join(orders, on="l_orderkey").join(cust, on="o_custkey")

def chain_region(root):
    # unfused: the stream spine the chain would absorb (topmost BHJ down
    # through stream children over BHJ/Project/Filter, excluding the scan)
    def find(n):
        if type(n).__name__ == "BroadcastHashJoinExec":
            return n
        for ch in n.children:
            r = find(ch)
            if r is not None:
                return r
    n, out = find(root), []
    while type(n).__name__ in ("BroadcastHashJoinExec", "ProjectExec",
                               "FilterExec"):
        out.append(n)
        si = ((0 if n.stream_is_left else 1)
              if type(n).__name__ == "BroadcastHashJoinExec" else 0)
        n = n.children[si]
    return out

def find_chain(n):
    if type(n).__name__ == "BroadcastHashJoinChainExec":
        return n
    for ch in n.children:
        r = find_chain(ch)
        if r is not None:
            return r

def run(make_df, fusion):
    spark = TpuSession({"spark.rapids.tpu.sql.stageFusion.enabled": fusion})
    dfs = tpch.load(spark, paths, files_per_partition=12)
    df = make_df(dfs)
    df.collect()                        # warm: traces + capacity predictions
    rows = sorted(map(tuple, (r.values()
                              for r in df.collect().to_pylist())))
    cl = df._last_collector
    disp = {e["id"]: e["dispatches"] or 0 for e in STATS.node_table(cl)}
    if fusion:
        chain = find_chain(cl.root)
        assert chain is not None, "no chain formed"
        return rows, disp[chain._node_id]
    assert find_chain(cl.root) is None, "chain formed with fusion off"
    return rows, sum(disp.get(n._node_id, 0) for n in chain_region(cl.root))

for name, make_df in (("q5", tpch.q5), ("q18-flow", q18_flow)):
    r_on, reg_on = run(make_df, True)
    r_off, reg_off = run(make_df, False)
    assert r_on == r_off, f"{name}: fused rows differ"
    assert len(r_on) > 0, f"{name}: no rows flowed through the chain"
    ratio = reg_off / max(reg_on, 1)
    print(f"chain gate: {name} region dispatches unfused={reg_off} "
          f"fused={reg_on} ({ratio:.2f}x)")
    assert ratio >= 3.0, f"{name}: chain dispatch drop {ratio:.2f}x < 3x"

# canonical q18 (empty output at this SF): chain forms, rows bit-identical
r_on, _ = run(tpch.q18, True)
r_off, _ = run(tpch.q18, False)
assert r_on == r_off, "q18: fused rows differ"
print("chain gate: q18 canonical bit-identical (chain formed)")
PYEOF

echo "== persistent stage cache: warm-start q18 replays with 0 traces =="
# cross-process contract: a fresh session pointed at a populated cache dir
# must replay every fused stage from serialized executables — zero Python
# retraces, zero XLA compiles (each heredoc below is its own process)
stage_cache_dir=$(mktemp -d /tmp/srt_stagecache.XXXXXX)
for phase in populate replay; do
SRT_CI_PHASE="$phase" SRT_CI_CACHE_DIR="$stage_cache_dir" \
JAX_PLATFORMS=cpu python - <<'PYEOF'
import os
import jax
import spark_rapids_tpu  # noqa: F401  (enables x64)
from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.runtime import fuse, stage_cache

phase = os.environ["SRT_CI_PHASE"]
paths = tpch.generate(0.01, "/tmp/tpch_ci_sf0.01")
spark = TpuSession({
    "spark.rapids.tpu.sql.stage.cache.enabled": True,
    "spark.rapids.tpu.sql.stage.cache.dir": os.environ["SRT_CI_CACHE_DIR"]})
dfs = tpch.load(spark, paths, files_per_partition=4)
tpch.q18(dfs).collect()
st = stage_cache.get()
traces = fuse.stage_metrics()["traces"]
print(f"stage-cache gate [{phase}]: traces={traces} hits={st.hits} "
      f"saves={st.saves}")
if phase == "populate":
    assert st.saves > 0, "populate session saved no stage executables"
else:
    assert traces == 0, f"warm-start q18 retraced {traces} stages"
    assert st.hits > 0, "warm-start session hit no cache entries"
PYEOF
done
rm -rf "$stage_cache_dir"

echo "== scan-side chain: bit-identity + warm-start replay of fused scan stages =="
# the scan-floor gate: q1 and q18 with the full scan-side
# chain on (device decode + encoded upload + fused decode→filter→partial-agg
# + chained group-by) must be bit-identical to the arrow path, and a FRESH
# process pointed at the populated stage cache must replay every fused scan
# stage (EncodedCol signatures included) with zero Python retraces
scan_cache_dir=$(mktemp -d /tmp/srt_scancache.XXXXXX)
for phase in populate replay; do
SRT_CI_PHASE="$phase" SRT_CI_CACHE_DIR="$scan_cache_dir" \
JAX_PLATFORMS=cpu python - <<'PYEOF'
import os
import jax
import spark_rapids_tpu  # noqa: F401  (enables x64)
from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.runtime import fuse, stage_cache

phase = os.environ["SRT_CI_PHASE"]
paths = tpch.generate(0.01, "/tmp/tpch_ci_sf0.01_f12", files_per_table=12)
ON = {
    "spark.rapids.tpu.sql.stageFusion.enabled": True,
    "spark.rapids.tpu.sql.parquet.deviceDecode.enabled": True,
    "spark.rapids.tpu.sql.parquet.encodedUpload.enabled": True,
    "spark.rapids.tpu.sql.stage.cache.enabled": True,
    "spark.rapids.tpu.sql.stage.cache.dir": os.environ["SRT_CI_CACHE_DIR"]}

def run(query, conf):
    spark = TpuSession(dict(conf))
    dfs = tpch.load(spark, paths, files_per_partition=3)
    return tpch.QUERIES[query](dfs).collect().to_pylist()

if phase == "populate":
    for q in ("q1", "q18"):
        on = run(q, ON)
        off = run(q, {
            "spark.rapids.tpu.sql.stageFusion.enabled": False,
            "spark.rapids.tpu.sql.parquet.deviceDecode.enabled": False})
        assert on == off, f"{q}: encoded scan-chain rows differ from arrow"
    st = stage_cache.get()
    print(f"scan gate [populate]: q1/q18 bit-identical, saves={st.saves}")
    assert st.saves > 0, "populate session saved no stage executables"
else:
    run("q1", ON)
    run("q18", ON)
    traces = fuse.stage_metrics()["traces"]
    st = stage_cache.get()
    print(f"scan gate [replay]: traces={traces} hits={st.hits}")
    assert traces == 0, f"warm-start fused scan stages retraced {traces}"
    assert st.hits > 0, "warm-start session hit no cache entries"
PYEOF
done
rm -rf "$scan_cache_dir"

echo "== scan-side chain: encoded-upload h2d pricing via profiler.py movement =="
# the movement read-out must PRICE the win: q1 (scan-heavy, dictionary-
# friendly columns) re-run with dense device upload moves >=1.3x the PCIe
# bytes of the encoded run, as replayed from the event logs by the
# profiler's movement plane — the gate reads the TOOL, not the in-process
# ledger, so the read-out path itself stays honest
scan_mv_enc=$(mktemp -d)
scan_mv_den=$(mktemp -d)
for mode in enc den; do
if [ "$mode" = enc ]; then obs="$scan_mv_enc"; else obs="$scan_mv_den"; fi
SRT_CI_MODE="$mode" SRT_OBS_DIR="$obs" JAX_PLATFORMS=cpu python - <<'PYEOF'
import os
import jax
import spark_rapids_tpu  # noqa: F401  (enables x64)
from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.runtime import eventlog

paths = tpch.generate(0.01, "/tmp/tpch_ci_sf0.01_f12", files_per_table=12)
spark = TpuSession({
    "spark.rapids.tpu.sql.stageFusion.enabled": True,
    "spark.rapids.tpu.sql.parquet.deviceDecode.enabled": True,
    "spark.rapids.tpu.sql.parquet.encodedUpload.enabled":
        os.environ["SRT_CI_MODE"] == "enc",
    "spark.rapids.tpu.eventLog.dir": os.environ["SRT_OBS_DIR"],
    "spark.rapids.tpu.movement.sample.intervalBytes": "64k"})
dfs = tpch.load(spark, paths, files_per_partition=3)
tpch.QUERIES["q1"](dfs).collect()
eventlog.shutdown()
PYEOF
done
for d in "$scan_mv_enc" "$scan_mv_den"; do
  python tools/profiler.py movement "$d"/events-*.jsonl --json \
    > "$d/movement.json"
done
python - "$scan_mv_enc/movement.json" "$scan_mv_den/movement.json" <<'PYEOF'
import json, sys

def h2d(p):
    m = json.load(open(p))
    return sum(f["bytes"] for f in m["flows"] if f["edge"] == "h2d")

enc, den = h2d(sys.argv[1]), h2d(sys.argv[2])
ratio = den / max(enc, 1)
print(f"scan movement gate: q1 h2d dense={den}B encoded={enc}B "
      f"({ratio:.2f}x)")
assert enc > 0, "no h2d flow in the encoded run's movement plane"
assert ratio >= 1.3, f"encoded upload h2d drop {ratio:.2f}x < 1.3x"
PYEOF
rm -rf "$scan_mv_enc" "$scan_mv_den"

echo "== cluster chaos: executor kill mid-q18 on a 3-executor MiniCluster =="
# losing 1 of 3 executors mid-query must cost ~1/N of a stage, not the
# query: the killed run must be bit-identical to the clean run, recompute
# strictly fewer map tasks than a full re-run, never reach the whole-query
# heal fallback, and leave the recovery ladder visible in the event log
# a real script file, not a heredoc: the spawn-based executor bootstrap
# re-imports __main__, and stdin cannot be re-imported
chaos_dir=$(mktemp -d)
JAX_PLATFORMS=cpu python tools/cluster_chaos.py \
  --data-dir /tmp/tpch_ci_sf0.01 --eventlog-dir "$chaos_dir" --query q18
# executors write their own events-*.jsonl (clock-offset-stamped) into the
# same dir now; the ladder assertions read the DRIVER's file, identified by
# the driver-only executor.lost event
chaos_log=$(grep -l "executor.lost" "$chaos_dir"/events-*.jsonl | head -1)
python - "$chaos_log" <<'PYEOF'
import json, sys
events = [json.loads(ln)["event"] for ln in open(sys.argv[1]) if ln.strip()]
assert "executor.lost" in events, sorted(set(events))
assert events.count("stage.recompute.partial") >= 1, sorted(set(events))
print("chaos event log ok:", events.count("executor.lost"),
      "executor.lost,", events.count("stage.recompute.partial"),
      "stage.recompute.partial")
PYEOF
# the profiler's recovery table must replay the ladder from the same log
# (rc is not gated here: the cluster driver emits no per-query operator
# breakdown, which the report treats as an error for SESSION logs)
python tools/profiler.py report "$chaos_log" > /tmp/chaos_profile.txt || true
grep -q "recovery (task attempt" /tmp/chaos_profile.txt
grep -q "partial recompute shuffle=" /tmp/chaos_profile.txt
# distributed trace of the SAME 3-executor q18 chaos run: the per-process
# span files (driver + executors + the respawned incarnation) must merge
# into one Perfetto-loadable Chrome trace sharing the query's trace id,
# and the critical-path table must be non-empty and name a bounding edge
python tools/profiler.py trace "$chaos_dir" --out /tmp/chaos_trace.json \
  > /tmp/chaos_trace.txt
grep -q "critical path" /tmp/chaos_trace.txt
grep -q "bounding edge:" /tmp/chaos_trace.txt
python - /tmp/chaos_trace.json <<'PYEOF'
import json, sys
t = json.load(open(sys.argv[1]))
evs = [e for e in t["traceEvents"] if e["ph"] != "M"]
meta = [e for e in t["traceEvents"] if e["ph"] == "M"]
assert evs and meta, (len(evs), len(meta))
for e in evs:
    assert {"name", "ph", "ts", "pid", "tid"} <= set(e), e
    assert e["ph"] != "X" or "dur" in e, e
pids = {e["pid"] for e in evs}
# counter samples (ph C) carry numeric series only, no trace-id arg
traces = {e["args"].get("trace") for e in evs
          if e.get("args") and e["ph"] != "C"}
assert len(pids) >= 2, pids      # driver + executor lanes
assert len(traces) == 1, traces  # every span carries the query's trace id
# executor MEMORY lanes: the merged trace must carry per-process memory
# counter tracks from >=2 processes (executors allocate shuffle blobs in
# their own catalogs; their samples ride the same span files)
mem_pids = {e["pid"] for e in evs
            if e["ph"] == "C" and e["name"] == "memory"}
assert len(mem_pids) >= 2, ("memory counter lanes", mem_pids)
print("chaos chrome trace ok:", len(evs), "events from", len(pids),
      "processes, trace", traces.pop(), "memory lanes from",
      len(mem_pids), "processes")
PYEOF
# a malformed span file must fail the trace export loudly
bad_dir=$(mktemp -d); echo '{broken json' > "$bad_dir/spans-1-x.jsonl"
if python tools/profiler.py trace "$bad_dir" >/dev/null 2>&1; then
  echo "profiler trace accepted a malformed span file"; exit 1
fi
rm -rf "$bad_dir"
rm -rf "$chaos_dir"

echo "== mesh-cluster chaos: unified plane, mesh kill mid-q18 -> degraded TCP fallback =="
# the combined N-process x M-chip plane (ROADMAP item 4): a 2-executor
# MiniCluster, each executor driving a 4-device local mesh. The script
# asserts the whole contract: the CLEAN mesh run used mesh tasks with every
# resilience counter zero (meshDegradedFallbacks rides the all-zero gate),
# and the killed run — a participant SIGKILLed INSIDE the mesh collective —
# degraded its group to the per-split TCP path under a bumped epoch,
# recomputed earlier stages' lost splits lineage-scoped, never reached the
# whole-query heal, and stayed bit-identical
mesh_dir=$(mktemp -d)
JAX_PLATFORMS=cpu python tools/cluster_chaos.py \
  --data-dir /tmp/tpch_ci_sf0.01 --eventlog-dir "$mesh_dir" --query q18 \
  --mesh --executors 2
# the degraded-mode ladder must be visible in the DRIVER's event log
mesh_log=$(grep -l "mesh.degraded" "$mesh_dir"/events-*.jsonl | head -1)
python - "$mesh_log" <<'PYEOF'
import json, sys
events = [json.loads(ln)["event"] for ln in open(sys.argv[1]) if ln.strip()]
for want in ("mesh.attach", "mesh.detach", "mesh.degraded", "executor.lost"):
    assert want in events, (want, sorted(set(events)))
print("mesh chaos event log ok:",
      events.count("mesh.attach"), "mesh.attach,",
      events.count("mesh.degraded"), "mesh.degraded,",
      events.count("mesh.detach"), "mesh.detach")
PYEOF
rm -rf "$mesh_dir"
# mesh-plane unit/integration suite: wave pid bit-exactness vs the
# per-batch partitioner, kill/hang/error degraded fallbacks,
# movement-aware placement + spill-aware demotion, the typed-ENOSPC OOM
# ladder, and spawn-handshake retry
JAX_PLATFORMS=cpu python -m pytest tests/test_mesh_cluster.py -q -m 'not slow'

echo "== two-level exchange: intra-mesh content over ICI (movement gate) =="
# q18 twice on a 2-executor x 4-chip mesh cluster (child processes, so the
# cumulative per-process ledgers stay separable): twoLevel=off vs on must
# show >=2x fewer loopback/TCP shuffle payload bytes with the savings
# appearing on the ici.collective edge, and bit-identical result digests
tl_dir=$(mktemp -d)
JAX_PLATFORMS=cpu python tools/movement_gate.py \
  --data-dir /tmp/tpch_ci_sf0.01 --eventlog-dir "$tl_dir" --query q18 \
  --executors 2 --two-level-compare
# the profiler read-out separates the two exchange levels at a glance
python tools/profiler.py movement "$tl_dir"/twolevel-on/events-*.jsonl \
  > /tmp/tl_readout.txt
grep -q "exchange levels:" /tmp/tl_readout.txt
grep -q "intra-mesh(ici)=" /tmp/tl_readout.txt
rm -rf "$tl_dir"

echo "== sf1 q18 out-of-core completion smoke (>=2 executors) =="
# the scale-out proof: q18 at sf1 completes on 2 executors with BOTH
# memory tiers shrunk below the working set (device -> host -> disk
# spill asserted from the ledger), two-level exchange on; auto-skip
# (logged) on a 1-core box, per the gate's >=2-executor contract
if [ "$(nproc)" -ge 2 ]; then
  ooc_dir=$(mktemp -d)
  JAX_PLATFORMS=cpu python tools/movement_gate.py \
    --data-dir /tmp/tpch_ci_sf1 --eventlog-dir "$ooc_dir" --query q18 \
    --executors 2 --ooc-smoke --scale 1.0 --ooc-limit 256m
  rm -rf "$ooc_dir"
else
  echo "SKIP: sf1 out-of-core smoke needs >=2 cores, have $(nproc)"
fi

echo "== multi-tenant: concurrent chaos (cancel + OOM + shed isolation) =="
# 4 concurrent TPC-H queries: one killed by its deadline, one recovering
# injected join-build OOMs, two survivors bit-identical to solo runs with
# EVERY query-scoped resilience counter zero; a 5th submission sheds with a
# pickle-round-tripped backoff hint; nothing leaks (threads/buffers/permits)
mt_dir=$(mktemp -d)
JAX_PLATFORMS=cpu python tools/concurrent_chaos.py \
  --data-dir /tmp/tpch_ci_sf0.01 --eventlog-dir "$mt_dir"
mt_log=$(ls "$mt_dir"/*.jsonl | head -1)
python - "$mt_log" <<'PYEOF'
import json, sys
events = [json.loads(ln)["event"] for ln in open(sys.argv[1]) if ln.strip()]
# all four lifecycle outcomes visible in one log: admitted queries, the
# deadline kill, the shed submission (after queueing), and the OOM recovery
for want in ("query.admitted", "query.deadline", "query.queued",
             "query.shed", "oom.retry", "query.end"):
    assert want in events, (want, sorted(set(events)))
print("multi-tenant event log ok:",
      events.count("query.admitted"), "admitted,",
      events.count("query.deadline"), "deadline,",
      events.count("query.shed"), "shed,",
      events.count("oom.retry"), "oom.retry")
PYEOF
# the profiler renders the admission/lifecycle table from the same log
python tools/profiler.py report "$mt_log" > /tmp/mt_profile.txt || true
grep -q "admission / lifecycle" /tmp/mt_profile.txt
grep -q "deadline q" /tmp/mt_profile.txt
grep -q "shed " /tmp/mt_profile.txt
rm -rf "$mt_dir"
# scheduler + lifecycle unit/integration suite (cancellation leak checks,
# admission, shed round-trip, CRC corruption ladders, eventlog rotation)
JAX_PLATFORMS=cpu python -m pytest tests/test_scheduler.py -q

echo "== serving endpoint: wire chaos (mid-stream kill + shed + SIGTERM drain) =="
# concurrent clients against the Arrow-over-TCP endpoint: one client killed
# while its query is in flight (disconnect → CancelToken → clean drain), a
# submission shed over the wire with its backoff hint arriving typed, then
# a real SIGTERM drain under load — the in-flight query finishes
# bit-identically, a mid-drain submission sheds with reason=draining, and
# nothing leaks (threads/buffers/permits)
ep_dir=$(mktemp -d)
JAX_PLATFORMS=cpu python tools/endpoint_chaos.py \
  --data-dir /tmp/tpch_ci_sf0.01 --eventlog-dir "$ep_dir"
ep_log=$(ls "$ep_dir"/*.jsonl | head -1)
python - "$ep_log" <<'PYEOF'
import json, sys
events = [json.loads(ln)["event"] for ln in open(sys.argv[1]) if ln.strip()]
for want in ("endpoint.start", "client.connected", "client.disconnected",
             "query.cancelled", "query.shed", "server.drain",
             "endpoint.stop"):
    assert want in events, (want, sorted(set(events)))
print("endpoint event log ok:",
      events.count("client.connected"), "connected,",
      events.count("client.disconnected"), "disconnected,",
      events.count("query.shed"), "shed,",
      events.count("server.drain"), "server.drain")
PYEOF
rm -rf "$ep_dir"
# endpoint + transport unit/integration suite (frame fuzz, CRC corruption,
# disconnect cancellation both FIN and RST, drain, exception pickles)
JAX_PLATFORMS=cpu python -m pytest tests/test_endpoint.py \
  tests/test_transport.py -q

echo "== serving fleet: chaos gate (warm replicas, SIGKILL failover, lease adoption) =="
# three real replica PROCESSES behind one fleet directory + shared stage
# cache: replica A compiles the workload, a fresh replica B serves the same
# shapes with ZERO retraces; a no-faults fleet load keeps every resilience
# counter zero on both replicas; a victim replica is SIGKILLed mid-stream
# and the client's submit_with_retry fails over to a survivor
# bit-identically; a survivor adopts the victim's expired lease and
# reclaims its orphaned shared-store write intents. The fleet observability
# plane gates inside the same harness: the victim's blackbox dump survives
# the SIGKILL naming the in-flight query, the survivor's fleet.adopt
# carries the dump path, profiler.py journey renders the cross-replica
# failover timeline with rc=0, profiler.py fleet lists the dead victim's
# tombstone, and the fleet-stats aggregate equals an independent re-sum of
# every replica's raw counters
fleet_dir=$(mktemp -d)
JAX_PLATFORMS=cpu python tools/fleet_chaos.py --work-dir "$fleet_dir"
rm -rf "$fleet_dir"
# fleet membership / journey / blackbox / client rotation / result-cache
JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py tests/test_fleet_observability.py -q

echo "== streaming: exactly-once epoch chaos (kill mid-commit, bit-identical replay) =="
# a >=20-epoch windowed-agg stream through the epoch coordinator: state
# rows/bytes must stay FLAT under the watermark (retirement works), the
# steady-state tail must run with zero compiles, a child coordinator
# SIGKILLed by exec_kill INSIDE the commit window must replay its epoch
# bit-identically at attempt 2 (streamEpochReplays counted exactly once),
# the profiler's streaming read-out must schema-validate the journal (and
# reject a corrupted copy), and a single-giant-epoch oracle must reproduce
# the exact final state + checksum (merge associativity cross-check)
stream_dir=$(mktemp -d)
JAX_PLATFORMS=cpu python tools/stream_chaos.py --work-dir "$stream_dir"
rm -rf "$stream_dir"
# streaming unit/integration suite: journal fencing + corruption refusal,
# CRC-verified idempotent APPEND, commit-crash + snapshot-corruption
# recovery, endpoint wire path, cross-replica staleness
JAX_PLATFORMS=cpu python -m pytest tests/test_streaming.py -q -m 'not slow'

echo "== observability: event log + span plane + profiler gate =="
# run the q18 ladder query with the event log AND the span plane both on
# (what they cost is read on the chip, PERF.md section 6, PR 26):
# tools/profiler.py must replay the log into a report with a clean schema
# and a non-empty operator breakdown (join build named)
obs_dir=$(mktemp -d)
JAX_PLATFORMS=cpu SRT_OBS_DIR="$obs_dir" python - <<'PYEOF'
import os
import spark_rapids_tpu  # noqa: F401  (enables x64)
from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.runtime import eventlog

paths = tpch.generate(0.01, "/tmp/tpch_ci_sf0.01")
# the fine-grained watermark timeline and the movement ledger's sampling
# (64k intervals) are on, so the profiler steps below have samples to read
spark = TpuSession({
    "spark.rapids.tpu.eventLog.dir": os.environ["SRT_OBS_DIR"],
    "spark.rapids.tpu.eventLog.healthSample.intervalSeconds": 0.5,
    "spark.rapids.tpu.trace.dir": os.environ["SRT_OBS_DIR"],
    "spark.rapids.tpu.memory.profile.watermarkIntervalBytes": "64k",
    "spark.rapids.tpu.movement.sample.intervalBytes": "64k",
    "spark.rapids.tpu.memory.leak.check": "true"})
df = tpch.q18(tpch.load(spark, paths, files_per_partition=4))
for _ in range(3):
    df.collect()
eventlog.shutdown()
from spark_rapids_tpu.runtime import tracing
tracing.shutdown_spans()
# the black-box flight recorder is ON by default: its ring must have been
# recording during the run, holding the most recent event-log records for a
# crash dump
from spark_rapids_tpu.runtime import blackbox
assert blackbox.enabled() and blackbox.ring_len() > 0, (
    blackbox.enabled(), blackbox.ring_len())
PYEOF
obs_log=$(ls "$obs_dir"/events-*.jsonl | head -1)
python tools/profiler.py report "$obs_log" --json > /tmp/obs_report.json
python -c '
import json
r = json.load(open("/tmp/obs_report.json"))
assert r["violations"] == [], r["violations"][:5]
qs = [q for q in r["queries"] if q["operators"]]
assert qs, "no query with a non-empty operator breakdown"
q18 = qs[-1]
names = " ".join(o["op"] for o in q18["operators"])
assert "(build)" in names, names   # the join build is a distinct line item
print("profiler gate ok:", len(qs), "queries,",
      len(q18["operators"]), "operators, self-time coverage",
      q18["coverage"])
'
# memory observability plane from the SAME q18 run: the heap profiler must
# attribute >=90% of the recorded peak to NAMED allocation sites, the
# watermark timeline must be monotone, and a clean run reports zero leaks
python tools/profiler.py memory "$obs_log" > /tmp/obs_memory.txt
grep -q "watermark timeline" /tmp/obs_memory.txt
grep -q "no leaks detected" /tmp/obs_memory.txt
python tools/profiler.py memory "$obs_log" --json > /tmp/obs_memory.json
python -c '
import json
m = json.load(open("/tmp/obs_memory.json"))
assert m["watermarks"], "no watermark samples"
marks = [w["watermark_bytes"] for w in m["watermarks"]]
assert marks == sorted(marks), "watermark ran backwards"
assert not m["leaks"], m["leaks"]
assert m["peak_attribution"] is not None and m["peak_attribution"] >= 0.9, \
    (m["peak_attribution"], m["peak"])
assert m["queries"] and all(q["peak_device_bytes"] > 0 for q in m["queries"])
print("memory profiler gate ok:", len(m["watermarks"]), "samples, peak",
      m["peak"]["device_bytes"], "B, attribution", m["peak_attribution"],
      "to sites", sorted(m["peak"]["sites"]))
'
# the SAME run's span file must export to a Perfetto-loadable trace with a
# non-empty critical path (single-process: operator trace_range spans) AND
# per-process memory counter lanes (ph C) alongside the span lanes
python tools/profiler.py trace "$obs_dir" --out /tmp/obs_trace.json \
  > /tmp/obs_trace.txt
grep -q "bounding edge:" /tmp/obs_trace.txt
python - /tmp/obs_trace.json <<'PYEOF'
import json, sys
t = json.load(open(sys.argv[1]))
cs = [e for e in t["traceEvents"] if e["ph"] == "C" and e["name"] == "memory"]
assert cs, "no memory counter-track samples in the chrome trace"
for e in cs:
    assert set(e["args"]) == {"device_bytes", "host_bytes", "disk_bytes"}, e
print("memory counter lanes ok:", len(cs), "samples")
PYEOF
rm -rf "$obs_dir"

echo "== movement plane: per-link byte ledger gate (3-executor q18) =="
# q18 on a same-host 3-executor MiniCluster: the merged per-process ledgers
# must cover the driver-registered map-output bytes (>=90%), classify every
# transport byte loopback/local (tcp exactly 0 — the misattribution
# regression), leave the retry edge at zero with no faults armed, and keep
# every network edge at exactly zero on the single-process no-shuffle path
mv_dir=$(mktemp -d)
JAX_PLATFORMS=cpu python tools/movement_gate.py \
  --data-dir /tmp/tpch_ci_sf0.01 --eventlog-dir "$mv_dir" --query q18
# the movement read-out merges every per-process event log into one matrix
python tools/profiler.py movement "$mv_dir"/events-*.jsonl \
  > /tmp/mv_readout.txt
grep -q "byte matrix" /tmp/mv_readout.txt
grep -q "heaviest flow:" /tmp/mv_readout.txt
grep -q "loopback-vs-remote:" /tmp/mv_readout.txt
python tools/profiler.py movement "$mv_dir"/events-*.jsonl --json \
  > /tmp/mv_readout.json
python - "$mv_dir" <<'PYEOF'
import glob, json, sys
m = json.load(open("/tmp/mv_readout.json"))
# denominator: the driver-registered per-reduce partition sizes
reg = 0
for path in glob.glob(sys.argv[1] + "/events-*.jsonl"):
    for ln in open(path):
        ln = ln.strip()
        if not ln:
            continue
        rec = json.loads(ln)
        if rec.get("event") == "stage.map.end" \
                and rec.get("partition_sizes"):
            reg += sum(rec["partition_sizes"])
assert reg > 0, "no registered partition sizes in the merged logs"
# the matrix's shuffle row (net -> host, payload units) must agree with
# the registered map-output bytes within 10% (15% headroom upward)
recv = m["matrix"].get("net->host", 0)
assert 0.9 * reg <= recv <= 1.15 * reg, (recv, reg)
by = m["by_link"]
assert by["tcp"] == 0, by
assert by["loopback"] > 0, by
assert m["flows"] and m["queries"], (len(m["flows"]), len(m["queries"]))
amp = [q for q in m["queries"] if q.get("amplification") is not None]
assert amp, "no query carries a movement amplification factor"
print(f"movement read-out gate ok: matrix shuffle row {recv}B vs "
      f"registered {reg}B ({recv / reg:.2f}x), tcp=0, "
      f"loopback={by['loopback']}B, amplification "
      f"{amp[-1]['amplification']}x")
PYEOF
rm -rf "$mv_dir"
# movement-plane unit/integration suite: ledger accounting, link
# classification, retry reclassification under injected faults, the
# 2-executor loopback/local split, and the chaos no-double-count invariant
JAX_PLATFORMS=cpu python -m pytest tests/test_movement.py -q

echo "== statistics plane: plan-history estimate-error gate =="
# q18 twice through a FRESH history dir: run 1 is a cold-start miss whose
# admission estimate comes from the static heuristic; run 2 must hit the
# plan-history store (estimate == run 1's observed device peak), cutting
# the estimate error at least in half WITHOUT changing results (a warm run
# pipelines fewer batches than a compile-stalled cold one, so its peak sits
# below the recorded one — the estimate stays conservative, not exact).
# The footprint floor is
# dropped to 64k because at SF 0.01 the default 16MB floor would dominate
# both runs' estimates and mask the history path entirely.
stats_dir=$(mktemp -d)
JAX_PLATFORMS=cpu SRT_STATS_DIR="$stats_dir" python - <<'PYEOF'
import jax
import os
import spark_rapids_tpu  # noqa: F401  (enables x64)
from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.runtime import eventlog, metrics

base = os.environ["SRT_STATS_DIR"]
paths = tpch.generate(0.01, "/tmp/tpch_ci_sf0.01")

def run(tag):
    spark = TpuSession({
        "spark.rapids.tpu.eventLog.dir": os.path.join(base, tag),
        "spark.rapids.tpu.stats.history.dir": os.path.join(base, "hist"),
        "spark.rapids.tpu.scheduler.footprint.floorBytes": "64k",
    })
    dfs = tpch.load(spark, paths, files_per_partition=4)
    # hash-repartition lineitem so q18's big aggregate runs behind a real
    # shuffle: per-reduce-partition sizes feed the skew table the read-out
    # gate asserts on (hash on l_orderkey is deliberately uneven)
    dfs["lineitem"] = dfs["lineitem"].repartition(4, "l_orderkey")
    out = tpch.q18(dfs).collect()
    return out, spark.last_query_metrics().stats

out1, st1 = run("run1")
out2, st2 = run("run2")
eventlog.shutdown()
assert st1["history_hit"] is False and st2["history_hit"] is True, (st1, st2)
e1, e2 = st1["estimate_error"], st2["estimate_error"]
# acceptance: run 2's absolute error at most half of run 1's (tiny epsilon
# for peak jitter between a cold and a compile-warm run)
assert e2 <= e1 / 2 + 1e-3, (e1, e2)
assert out1.to_pydict() == out2.to_pydict(), "history changed query results"
res = metrics.resilience_snapshot()
assert not any(res.values()), res
print(f"stats gate ok: estimate error run1={e1:.3f} -> run2={e2:.3f}, "
      f"history_hit={st2['history_hit']}, results identical, "
      f"resilience all-zero")
PYEOF
stats_log=$(ls "$stats_dir"/run2/events-*.jsonl | head -1)
# the plan.stats records must pass the event-log schema (validate_record
# runs inside the profiler's load), and the stats read-out must print the
# per-node ledger and name q18's skewed reduce partition
python tools/profiler.py stats "$stats_log" > /tmp/stats_readout.txt
grep -q "node ledger" /tmp/stats_readout.txt
grep -q "at partition" /tmp/stats_readout.txt
python tools/profiler.py stats "$stats_log" --json > /tmp/stats_readout.json
python -c '
import json
d = json.load(open("/tmp/stats_readout.json"))
assert d["violations"] == [], d["violations"][:5]
qs = [q for q in d["queries"] if q["stats"]]
assert qs and qs[-1]["stats"]["history_hit"] is True, "no history hit"
assert qs[-1]["shuffles"], "no shuffle skew rows for q18"
print("stats read-out gate ok:", len(qs), "queries with plan.stats,",
      len(qs[-1]["shuffles"]), "shuffle skew rows")
'
rm -rf "$stats_dir"

echo "== api coverage gate (0 missing vs reference GpuOverrides) =="
python tools/api_validation.py 0 0

echo "== config docs in sync =="
python -m spark_rapids_tpu.config
git diff --exit-code docs/configs.md || {
  echo "docs/configs.md out of date: run python -m spark_rapids_tpu.config"; exit 1; }

echo "== installable package (dist-jar analog) =="
# import + run a query from the INSTALLED package, outside the repo dir
instdir=$(mktemp -d)
# --no-build-isolation: the CI box has no egress; setuptools is preinstalled
pip install --quiet --no-build-isolation --target "$instdir" --no-deps .
(cd /tmp && PYTHONPATH="$instdir" JAX_PLATFORMS=cpu python - <<'PYEOF'
import jax
import spark_rapids_tpu, pyarrow as pa
assert "/repo/" not in spark_rapids_tpu.__file__, spark_rapids_tpu.__file__
from spark_rapids_tpu.session import TpuSession
spark = TpuSession()
spark.create_or_replace_temp_view(
    "t", spark.create_dataframe(pa.table({"k": [1, 2, 2], "v": [1.0, 2.0, 3.0]})))
out = spark.sql("select k, sum(v) s from t group by k order by k").collect()
assert out.to_pylist() == [{"k": 1, "s": 1.0}, {"k": 2, "s": 5.0}], out
print("installed-package query ok")
PYEOF
)
rm -rf "$instdir"

echo "CI OK"
