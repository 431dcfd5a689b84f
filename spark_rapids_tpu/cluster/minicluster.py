"""MiniCluster: driver + N executor OS processes running one query end-to-end.

Reference (SURVEY.md §1 L6, components #29-#33): on a real Spark cluster the
reference's plugin rides Spark's own scheduling — the driver's DAGScheduler
splits the plan at ShuffleDependency boundaries, executor JVMs run tasks, and
RapidsShuffleInternalManagerBase.scala:200 + the UCX transport move shuffle
blocks between executor processes (Plugin.scala:137-211 wires the executor
side up). Standalone, this module IS that cluster: a spawn-based executor
pool, a stage scheduler splitting the plan at explicit ExchangeNodes
(plan/distribute.py is the EnsureRequirements analog), and the existing
TcpTransport + ShuffleBlockStore as the inter-process data plane.

Execution model:
- the driver rewrites the logical plan with ensure_distribution(), then
  schedules each ExchangeNode bottom-up as a MAP STAGE: every map task
  executes one split of the exchange's child subtree on some executor,
  partitions rows with the exchange's partitioner, and parks the buckets in
  that executor's block store under a driver-assigned shuffle id;
- the consumed exchange is replaced by a RemoteSourceNode carrying every
  executor's block-server address; downstream tasks fetch their reduce
  partition from all peers over TCP (union of blocks = the partition);
- tasks ship with their RemoteSourceNodes PINNED to the task's reduce id, so
  the subtree is single-partition on the executor and stage-local planning
  (TpuOverrides) never inserts its own exchanges;
- the final (result) stage returns Arrow IPC bytes to the driver.

Fault tolerance — recovery proportional to what was lost (the Spark
task-retry / FetchFailed → lineage-recompute ladder, reference
RapidsShuffleIterator.scala:82,153):

- a **MapOutputTracker** on the driver records, per shuffle id, which
  executor hosts each map split's blocks, epoch-stamped: the epoch bumps
  whenever a shuffle's outputs are invalidated, and any task reply computed
  under a stale epoch is discarded and re-run (the reducer may have read a
  half-rebuilt partition);
- **task attempts**: a failed task (exception, injected fault, or a
  `cluster.task.timeoutSeconds` deadline) retries up to
  `cluster.task.maxFailures` times, preferring a different executor;
  per-executor failure strikes **blacklist** an executor from placement
  after `cluster.blacklist.maxTaskFailures`;
- **lineage-scoped recompute**: on executor death (broken channel, or the
  driver's poll of the heartbeat manager's expire_dead), the driver respawns
  the slot, consults the tracker for exactly the map splits that lived on
  the dead peer, re-runs only those under a bumped epoch, re-publishes
  addresses into every live RemoteSourceNode, and reuses every surviving
  stage output verbatim; the whole-query `_heal()` retry remains only as a
  final fallback once `cluster.stage.maxRecomputes` is exhausted;
- optional **speculative execution** (`cluster.speculation.enabled`):
  stragglers past `speculation.multiplier` × the median completed task time
  are duplicated on idle executors; the first completion wins (dedup keyed
  by `(shuffle_id, map_split)`) and the loser's blocks are dropped so
  results stay bit-identical.
"""

from __future__ import annotations

import collections
import itertools
import multiprocessing as mp
import os
import statistics
import time
import traceback

import pyarrow as pa

# NOTE: engine imports stay INSIDE functions — the spawn bootstrap imports
# this module in the executor child BEFORE _executor_main can select the jax
# platform, and importing the engine first would initialize the default
# (accelerator) backend in every executor.


# ---------------------------------------------------------------------------
# executor process
# ---------------------------------------------------------------------------

def _mesh_conf_raw(conf_settings: dict):
    """Parse the cluster.mesh knobs from the RAW settings dict — needed
    BEFORE any spark_rapids_tpu import (the config module pulls in jax,
    and the XLA device-count flag must be set first)."""
    pre = "spark.rapids.tpu.cluster.mesh."
    enabled = str(conf_settings.get(pre + "enabled", "")
                  ).strip().lower() in ("true", "1", "yes")
    try:
        n = int(conf_settings.get(pre + "devicesPerExecutor", 0) or 0)
    except (TypeError, ValueError):
        n = 0
    return enabled, n


def _executor_main(conn, executor_index: int, platform: str,
                   conf_settings: dict):
    """Executor entry (spawned): block server + task loop (the standalone
    Plugin.scala:137-211 executor-side bring-up analog)."""
    if platform:
        os.environ["JAX_PLATFORMS"] = platform
    mesh_on, mesh_n = _mesh_conf_raw(conf_settings)
    if mesh_on and platform == "cpu":
        # the local mesh needs >=2 devices; on the CPU platform they only
        # exist if the XLA host-device flag is set before jax initializes
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{mesh_n if mesh_n > 0 else 8}").strip()
    import jax
    if platform:
        jax.config.update("jax_platforms", platform)
    import cloudpickle
    import spark_rapids_tpu  # noqa: F401  (x64 etc.)
    from spark_rapids_tpu import config as CFG
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.exec.base import TaskContext
    from spark_rapids_tpu.plan.transitions import to_device_plan
    from spark_rapids_tpu.runtime import eventlog as EL
    from spark_rapids_tpu.runtime import faults as F
    from spark_rapids_tpu.runtime import tracing
    from spark_rapids_tpu.shuffle.manager import ShuffleBlockStore
    from spark_rapids_tpu.shuffle.transport import TcpTransport

    conf = RapidsConf(conf_settings)
    # arm the chaos injector in the executor too (exec_kill / oom / transport
    # sites fire where the work actually runs); the driver strips the spec
    # from RESPAWNED replacements so COUNT triggers cannot re-fire forever
    F.configure(conf.get(CFG.TEST_FAULTS), conf.get(CFG.TEST_FAULTS_SEED))
    # executor-side telemetry sinks: spans and event-log records land in
    # per-process files under the SAME directories the driver uses, merged
    # later by timestamp + the clock offset the driver measures below
    tdir = conf.get(CFG.TRACE_DIR)
    if tdir:
        tracing.configure_spans(tdir, process=f"executor-{executor_index}")
    edir = conf.get(CFG.EVENT_LOG_DIR)
    if edir:
        EL.configure(edir, max_bytes=conf.get(CFG.EVENT_LOG_MAX_BYTES),
                     keep=conf.get(CFG.EVENT_LOG_KEEP_FILES))
    # the movement ledger meters this process's own boundary crossings —
    # same knobs as the driver so merged per-process samples line up
    from spark_rapids_tpu.runtime import movement as MV
    MV.configure(
        sample_interval_bytes=conf.get(CFG.MOVEMENT_SAMPLE_INTERVAL),
        enabled=conf.get(CFG.MOVEMENT_ENABLED))
    # device + memory bring-up with the CLUSTER conf (the plugin.py:82
    # executor-side analog): without this the lazily-built DeviceManager
    # uses a default conf and out-of-core budgets (hbm.limitBytes,
    # spillStorageSize) silently do not apply on executors
    from spark_rapids_tpu.runtime.memory import DeviceManager
    DeviceManager.initialize(conf)
    store = ShuffleBlockStore.get()
    transport = TcpTransport(conf)
    # the reduce side short-circuits fetches addressed to THIS executor's
    # block server straight into the local store (cluster/remote.py) — the
    # read movement-aware placement schedules for
    from spark_rapids_tpu.cluster import remote as R
    R.set_local_address(("127.0.0.1", transport.port))
    # local mesh bring-up (unified mesh-cluster plane): report the ACTUAL
    # attached width on the handshake so the driver sizes mesh task groups
    # to what this process really has (mesh.attach / degraded re-plans)
    mesh_width = 0
    if mesh_on:
        try:
            from spark_rapids_tpu.distributed.mesh import LocalMesh
            mesh_width = LocalMesh.get(mesh_n).n
        except Exception:
            mesh_width = 0
    conn.send({"op": "ready", "port": transport.port, "pid": os.getpid(),
               "mesh": mesh_width})

    def run_mesh_map(task):
        """A MESH map task: up to mesh-width lanes (one map split each) run
        in one task; per partition wave, every lane's current batch gets
        its Spark-exact partition ids from ONE jitted shard_map dispatch on
        the local mesh, with the wave's per-partition row counts psum-ed
        over ICI (distributed/mesh.LocalMesh).

        TWO-LEVEL EXCHANGE (docs/cluster.md): when the driver shipped a
        `reduce_owned` set (the reduce partitions whose consumers will be
        placed on THIS executor) and the wave schema is fixed-width, the
        owned partitions' content moves lane→lane as `lax.all_to_all` over
        ICI (LocalMesh.exchange_wave) and the receiving lane writes the
        shards straight into the process-local block store under the SAME
        (map_split, seq) keys the per-batch path would use — so
        iter_union_blocks' canonical-key merge keeps bit-identity with the
        TCP plane by construction, and only cross-host partitions are
        sliced with the exact per-batch path and parked for the TCP fetch.
        String-keyed waves (counts is None) and variable-width schemas
        fall back to slice-and-park for every partition WITHOUT breaking
        the mesh group. Any failure of the mesh itself (bring-up, shrink,
        collective, exchange) surfaces as MeshDegradedError → the driver's
        degraded fallback; failures INSIDE a lane's subtree execution stay
        ordinary task failures and ride the attempt ladder."""
        import numpy as np
        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        from spark_rapids_tpu.columnar.vector import TpuColumnVector
        from spark_rapids_tpu.distributed.mesh import (LocalMesh,
                                                       MeshDegradedError)
        from spark_rapids_tpu.shuffle.partitioning import (
            slice_into_partitions)
        plan = task["plan"]
        lanes = task["mesh_lanes"]
        sid = task["shuffle_id"]
        part = task["partitioner"].bind(plan.output)
        owned = sorted(task.get("reduce_owned") or ())
        two_level = bool(owned) and LocalMesh.exchangeable_schema(plan.output)
        store.ensure_shuffle(sid)
        tracing.set_process_trace(task.get("trace"))
        try:
            # mesh_kill / mesh_hang / degrade chaos sites: INSIDE the
            # degrade guard, so exec_kill dies mid-collective with partial
            # blocks parked, hang hangs until the task deadline, and
            # error proves the transparent mesh→TCP fallback
            F.maybe_inject_any("cluster.mesh.begin")
            F.maybe_inject_any(f"cluster.mesh.begin.{executor_index}")
            lm = LocalMesh.get(mesh_n)
            if lm.n < len(lanes):
                raise MeshDegradedError(
                    f"mesh shrank: width {lm.n} < {len(lanes)} lanes")
        except MeshDegradedError:
            raise
        except Exception as e:
            raise MeshDegradedError(f"mesh bring-up failed: {e!r}") from e
        waves = rows_exchanged = ici_rows = 0
        with tracing.span("task.mesh_map", shuffle=sid,
                          lanes=len(lanes)), TaskContext():
            iters, seqs = [], []
            for lane in lanes:
                if lane["pin"] is not None:
                    lplan = _pin_sources(_clone_plan(plan), lane["pin"])
                    lsplit = 0
                else:
                    lplan = _clone_plan(plan)
                    lsplit = lane["split"]
                iters.append(to_device_plan(lplan, conf)
                             .execute_partition(lsplit))
                seqs.append(0)
            live = list(range(len(lanes)))
            while live:
                wave = []
                for li in list(live):
                    try:
                        wave.append((li, next(iters[li])))
                    except StopIteration:
                        live.remove(li)
                if not wave:
                    break
                try:
                    F.maybe_inject_any("cluster.mesh")
                    F.maybe_inject_any(f"cluster.mesh.{executor_index}")
                    pids_list, counts = lm.partition_wave(
                        [b for _, b in wave], part)
                except MeshDegradedError:
                    raise
                except Exception as e:
                    raise MeshDegradedError(
                        f"mesh collective failed: {e!r}") from e
                waves += 1
                if counts is not None:
                    rows_exchanged += int(counts.sum())
                # level 1: owned partitions' content rides ICI — routed
                # round-robin over the wave's live lanes; the dest lane
                # choice only balances ICI traffic (the block store is
                # process-local, so any lane's write serves the consumer
                # placed on this executor)
                dm = None
                if two_level and counts is not None:
                    dm = np.full((part.num_partitions,), -1, np.int32)
                    for i, rid in enumerate(owned):
                        dm[rid] = i % len(wave)
                    try:
                        F.maybe_inject_any("cluster.mesh.exchange")
                        F.maybe_inject_any(
                            f"cluster.mesh.exchange.{executor_index}")
                        rvals, rmasks, rpids, rcounts = lm.exchange_wave(
                            [b for _, b in wave], pids_list, dm,
                            part.num_partitions)
                    except MeshDegradedError:
                        raise
                    except Exception as e:
                        raise MeshDegradedError(
                            f"mesh exchange failed: {e!r}") from e
                # level 2: cross-host (and fallback) partitions slice with
                # the exact per-batch path and park for the TCP fetch
                for (li, b), pids in zip(wave, pids_list):
                    seqs[li] += 1
                    for pid, piece in slice_into_partitions(
                            b, pids, part.num_partitions):
                        if dm is not None and dm[pid] >= 0:
                            continue  # rode ICI in this wave
                        if piece.num_rows:
                            store.write_block(
                                sid, pid, piece,
                                seq=(lanes[li]["split"], seqs[li]))
                if dm is not None:
                    # receiving lanes park the ICI shards under the SOURCE
                    # lane's (map_split, seq) key — identical to what the
                    # per-batch path would have written for that wave
                    for d in range(len(wave)):
                        for s in range(len(wave)):
                            if int(rcounts[d][s]) == 0:
                                continue
                            src_schema = wave[s][1].schema or plan.output
                            cols = [TpuColumnVector(
                                        f.data_type, rvals[c][d][s],
                                        rmasks[c][d][s])
                                    for c, f in enumerate(src_schema)]
                            mini = ColumnarBatch(cols, int(rcounts[d][s]),
                                                 src_schema)
                            src_li = wave[s][0]
                            for pid, piece in slice_into_partitions(
                                    mini, rpids[d][s],
                                    part.num_partitions):
                                if piece.num_rows:
                                    store.write_block(
                                        sid, pid, piece,
                                        seq=(lanes[src_li]["split"],
                                             seqs[src_li]))
                                    ici_rows += piece.num_rows
        return {"sizes": store.partition_sizes(sid, part.num_partitions),
                "split_sizes": {
                    lane["split"]: store.split_partition_sizes(
                        sid, part.num_partitions, lane["split"])
                    for lane in lanes},
                "mesh": {"waves": waves, "lanes": len(lanes),
                         "rows_exchanged": rows_exchanged,
                         "ici_rows": ici_rows}}

    def run_map(task):
        if task.get("mesh_lanes") is not None:
            return run_mesh_map(task)
        plan = task["plan"]
        part = task["partitioner"].bind(plan.output)
        sid = task["shuffle_id"]
        # the map task's identity within the shuffle: pins block order per
        # reduce partition AND lets the driver drop exactly this task's
        # output (speculation losers, stale/failed attempts)
        map_split = task["map_split"]
        store.ensure_shuffle(sid)
        # the task's trace id pins the PROCESS (one task at a time here), so
        # pipeline worker threads and the shuffle fetch path inherit it
        tracing.set_process_trace(task.get("trace"))
        # task-START checkpoint (distinct site from the per-batch one so
        # batch-counted @SKIP triggers stay stable): lets exec_kill/hang
        # fire even for a task whose input produces zero batches
        F.maybe_inject_any("cluster.map.begin")
        F.maybe_inject_any(f"cluster.map.begin.{executor_index}")
        exec_root = to_device_plan(plan, conf)
        with tracing.span("task.map", shuffle=sid, split=map_split), \
                TaskContext():
            for split in task["splits"]:
                seq = 0
                for batch in exec_root.execute_partition(split):
                    # chaos checkpoint (any armed kind fires, like the
                    # pipeline queue sites): exec_kill dies mid-task with
                    # blocks partially written, error drives task-attempt
                    # retries, hang drives the task deadline
                    F.maybe_inject_any("cluster.map")
                    F.maybe_inject_any(f"cluster.map.{executor_index}")
                    seq += 1
                    for pid, piece in part.partition(batch, split):
                        if piece.num_rows:
                            # stable per-reduce-partition block order (same
                            # contract as the local exchange map writer)
                            store.write_block(sid, pid, piece,
                                              seq=(map_split, seq))
        # per-split map-output statistics ride every reply so the driver's
        # MapOutputTracker can place reducers where their bytes live
        return {"sizes": store.partition_sizes(sid, part.num_partitions),
                "split_sizes": {map_split: store.split_partition_sizes(
                    sid, part.num_partitions, map_split)}}

    def run_result(task):
        plan = task["plan"]
        tracing.set_process_trace(task.get("trace"))
        F.maybe_inject_any("cluster.result.begin")
        F.maybe_inject_any(f"cluster.result.begin.{executor_index}")
        exec_root = to_device_plan(plan, conf)
        tables = []
        with tracing.span("task.result", splits=len(task["splits"])), \
                TaskContext():
            for split in task["splits"]:
                for batch in exec_root.execute_partition(split):
                    F.maybe_inject_any("cluster.result")
                    F.maybe_inject_any(f"cluster.result.{executor_index}")
                    tables.append(batch.to_arrow())
        if not tables:
            out = plan.output.to_arrow().empty_table()
        else:
            out = pa.concat_tables(tables)
        sink = pa.BufferOutputStream()
        with pa.ipc.new_stream(sink, out.schema) as w:
            w.write_table(out)
        return {"ipc": sink.getvalue().to_pybytes()}

    while True:
        msg = conn.recv()
        op = msg["op"]
        if op == "stop":
            transport.shutdown()
            conn.send({"op": "bye"})
            break
        try:
            if op == "map":
                reply = run_map(cloudpickle.loads(msg["task"]))
                # task-completion flush: the driver's profiler merge reads
                # the LAST movement.sample per process, so every finished
                # task leaves a current ledger snapshot behind
                MV.maybe_emit(force=True)
            elif op == "result":
                reply = run_result(cloudpickle.loads(msg["task"]))
                MV.maybe_emit(force=True)
            elif op == "clock":
                # driver-side two-timestamp exchange: our wall clock, read
                # as close to the reply as the pipe protocol allows
                reply = {"t": time.time()}
            elif op == "clock_set":
                # the measured offset toward the driver's clock: stamped
                # into event-log records and span files so merged timelines
                # order correctly across processes
                EL.set_clock_offset(msg["offset"])
                reply = {}
            elif op == "ensure_shuffle":
                store.ensure_shuffle(msg["shuffle_id"])
                reply = {}
            elif op == "drop_shuffle":
                store.unregister_shuffle(msg["shuffle_id"])
                reply = {}
            elif op == "drop_map_output":
                reply = {"dropped": store.drop_map_output(
                    msg["shuffle_id"], msg["map_split"])}
            else:
                raise ValueError(f"unknown op {op}")
            reply.update({"op": "done", "ok": True})
        except BaseException as exc:  # noqa: BLE001 — shipped to the driver
            reply = {"op": "done", "ok": False,
                     "error": traceback.format_exc()}
            # typed marker: the driver treats a degraded mesh as a
            # transparent re-plan, NOT a task failure (no attempt strike)
            if type(exc).__name__ == "MeshDegradedError":
                reply["mesh_degraded"] = True
        finally:
            # the task's trace id must not bleed into the next task (or
            # into fetch serving between tasks)
            tracing.set_process_trace(None)
        conn.send(reply)


# ---------------------------------------------------------------------------
# driver-side plan plumbing
# ---------------------------------------------------------------------------

def _clone_plan(plan):
    import cloudpickle
    return cloudpickle.loads(cloudpickle.dumps(plan))


def _pin_sources(plan, reduce_id: int):
    """Deep-replace every RemoteSourceNode with a pinned copy."""
    from spark_rapids_tpu.plan import nodes as NN
    if isinstance(plan, NN.RemoteSourceNode):
        return plan.pinned(reduce_id)
    plan.children = [_pin_sources(c, reduce_id) for c in plan.children]
    return plan


def _collect_sources(plan, out):
    from spark_rapids_tpu.plan import nodes as NN
    if isinstance(plan, NN.RemoteSourceNode):
        out.append(plan)
    for c in plan.children:
        _collect_sources(c, out)
    return out


def _has_non_source_leaves(plan):
    from spark_rapids_tpu.plan import nodes as NN
    if not plan.children:
        return not isinstance(plan, NN.RemoteSourceNode)
    return any(_has_non_source_leaves(c) for c in plan.children)


class ExecutorLostError(RuntimeError):
    """Partial (lineage-scoped) recovery was exhausted or impossible: the
    driver heals the whole pool and retries the query — the final rung of
    the recovery ladder, not the first responder it used to be."""


class PlacementPolicy:
    """Deterministic, seedable round-robin task placement (replaces the old
    bare itertools.cycle): the seed rotates which executor receives the
    first task, so attempt/blacklist tests can pin which executor hosts
    which map split. `prefer_not` lets a retry avoid the executors that
    already failed the task when an alternative exists. `preferred` is the
    movement-aware override: when the caller already knows which executor
    holds the task's biggest input (MapOutputTracker byte accounting), that
    host wins WITHOUT advancing the round-robin cursor, so the rotation
    schedule of ordinary picks stays deterministic around it."""

    def __init__(self, n_executors: int, seed: int = 0):
        self.n = max(n_executors, 1)
        self._next = seed % self.n

    def pick(self, eligible, prefer_not=(), preferred=None):
        if (preferred is not None and preferred in eligible
                and preferred not in prefer_not):
            return preferred
        order = [(self._next + i) % self.n for i in range(self.n)]
        choices = [e for e in order
                   if e in eligible and e not in prefer_not] \
            or [e for e in order if e in eligible]
        if not choices:
            return None
        c = choices[0]
        self._next = (c + 1) % self.n
        return c


class _ShuffleState:
    __slots__ = ("shuffle_id", "subtree", "partitioner", "mode", "splits",
                 "hosts", "epoch", "recomputes", "split_sizes", "owners")

    def __init__(self, shuffle_id, subtree, partitioner, mode, splits):
        self.shuffle_id = shuffle_id
        self.subtree = subtree          # map-stage child plan (lineage)
        self.partitioner = partitioner
        self.mode = mode                # "pinned" | "plain" task shape
        self.splits = list(splits)
        self.hosts = {}                 # map_split -> executor index
        self.epoch = 0                  # bumped on every invalidation
        self.recomputes = 0             # partial recomputes consumed
        self.split_sizes = {}           # map_split -> [bytes per reduce id]
        # two-level exchange: reduce id -> owning executor (None = shuffle
        # runs single-level). Owned partitions' content rides ICI inside
        # the owner's mesh tasks and the partition's consumer is placed at
        # the owner, so those bytes are read via the local short-circuit
        self.owners = None


class MapOutputTracker:
    """Driver-side map-output registry (Spark MapOutputTrackerMaster
    analog): which executor hosts each map split's blocks, per shuffle,
    epoch-stamped so stale reads are detectable, plus enough lineage
    (subtree + partitioner + task shape) to re-run exactly the lost
    splits."""

    def __init__(self):
        self._shuffles: dict[int, _ShuffleState] = {}

    def register_shuffle(self, shuffle_id, subtree, partitioner, mode,
                         splits) -> _ShuffleState:
        st = _ShuffleState(shuffle_id, subtree, partitioner, mode, splits)
        self._shuffles[shuffle_id] = st
        return st

    def state(self, shuffle_id) -> _ShuffleState | None:
        return self._shuffles.get(shuffle_id)

    def sids(self) -> list:
        return sorted(self._shuffles)

    def epoch(self, shuffle_id) -> int:
        st = self._shuffles.get(shuffle_id)
        return st.epoch if st is not None else 0

    def epochs(self, shuffle_ids) -> dict:
        return {sid: self.epoch(sid) for sid in shuffle_ids}

    def register_map_output(self, shuffle_id, map_split, executor_idx,
                            sizes=None):
        """Record the split's host and (when the reply carried them) its
        per-reduce-partition byte sizes — the statistic movement-aware
        reduce placement reads. Re-registration after a partial recompute
        overwrites both, so the bytes always follow the live copy."""
        st = self._shuffles[shuffle_id]
        st.hosts[map_split] = executor_idx
        if sizes is not None:
            st.split_sizes[map_split] = list(sizes)

    def invalidate_splits(self, shuffle_id, splits) -> None:
        """Drop specific splits' outputs (degraded mesh task, partial
        attempt) and bump the shuffle's epoch so any in-flight reply that
        read the pre-drop layout is discarded and re-run."""
        st = self._shuffles.get(shuffle_id)
        if st is None:
            return
        st.epoch += 1
        for s in splits:
            st.hosts.pop(s, None)
            st.split_sizes.pop(s, None)

    def bytes_by_executor(self, shuffle_ids, reduce_id) -> dict:
        """executor -> map-output bytes it holds for `reduce_id` across
        `shuffle_ids` (Theseus-style movement statistic: the reduce task's
        cheapest host is the one already holding the most of its input)."""
        out: dict = {}
        for sid in shuffle_ids:
            st = self._shuffles.get(sid)
            if st is None:
                continue
            for split, ei in st.hosts.items():
                sizes = st.split_sizes.get(split)
                if sizes and 0 <= reduce_id < len(sizes):
                    out[ei] = out.get(ei, 0) + sizes[reduce_id]
        return out

    def executor_load(self, executor_idx) -> int:
        """Total shuffle bytes parked on one executor across every live
        shuffle — the spill-pressure proxy placement demotion checks."""
        total = 0
        for st in self._shuffles.values():
            for split, ei in st.hosts.items():
                if ei == executor_idx:
                    total += sum(st.split_sizes.get(split, ()))
        return total

    def on_executor_lost(self, executor_idx) -> list:
        """Invalidate every map split hosted on the dead executor; returns
        [(state, [lost splits])] in ascending shuffle-id (= dependency)
        order, with each affected shuffle's epoch bumped."""
        out = []
        for sid in sorted(self._shuffles):
            st = self._shuffles[sid]
            lost = sorted(s for s, h in st.hosts.items() if h == executor_idx)
            if lost:
                st.epoch += 1
                for s in lost:
                    del st.hosts[s]
                    st.split_sizes.pop(s, None)
                out.append((st, lost))
        return out

    def subtrees(self) -> list:
        return [st.subtree for st in self._shuffles.values()]


class _TaskSpec:
    __slots__ = ("idx", "op", "subtree", "pin", "split", "shuffle_id",
                 "partitioner", "read_sids", "attempts", "tried",
                 "speculated", "lanes")

    def __init__(self, idx, op, subtree, pin, split, shuffle_id=None,
                 partitioner=None, lanes=None):
        self.idx = idx
        self.op = op                    # "map" | "result"
        self.subtree = subtree
        self.pin = pin                  # reduce id to pin sources to, or None
        self.split = split              # map split id / subtree partition
        self.shuffle_id = shuffle_id
        self.partitioner = partitioner
        # mesh map task: [(split, pin_or_None)] — one lane per local mesh
        # device; None means the ordinary single-split task shape
        self.lanes = lanes
        self.read_sids = sorted({s.shuffle_id for s in
                                 _collect_sources(subtree, [])})
        self.attempts = 0
        self.tried: set = set()
        self.speculated = False

    def splits_covered(self) -> list:
        return ([s for s, _ in self.lanes] if self.lanes is not None
                else [self.split])


class _Running:
    __slots__ = ("spec", "t0", "epochs", "speculative", "gen")

    def __init__(self, spec, t0, epochs, speculative, gen):
        self.spec = spec
        self.t0 = t0
        self.epochs = epochs            # {sid: epoch} at dispatch time
        self.speculative = speculative
        self.gen = gen                  # executor incarnation at dispatch


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

class MiniCluster:
    """Driver for N executor processes; `collect(df)` runs the DataFrame's
    plan across them (DAGScheduler + cluster-manager stand-in)."""

    def __init__(self, n_executors: int = 2, conf=None, platform: str = "cpu",
                 max_attempts: int = 3):
        from spark_rapids_tpu import config as CFG
        from spark_rapids_tpu.config import RapidsConf
        from spark_rapids_tpu.shuffle.heartbeat import (
            RapidsShuffleHeartbeatManager)
        self.conf = conf or RapidsConf()
        self.n_executors = n_executors
        self.max_attempts = max_attempts
        self._platform = platform
        self._shuffle_ids = itertools.count(1000)
        self._conns = [None] * n_executors
        self._procs = [None] * n_executors
        self._gen = [0] * n_executors       # incarnation per slot
        self._exec_ids = [None] * n_executors
        self.addresses = [None] * n_executors
        self._hb = RapidsShuffleHeartbeatManager(
            timeout_s=self.conf.get(CFG.CLUSTER_HEARTBEAT_TIMEOUT))
        self._tracker = MapOutputTracker()
        self._current_root = None           # plan of the in-flight query
        self._exec_failures = [0] * n_executors
        self._blacklist: set = set()
        self._placement = PlacementPolicy(
            n_executors, self.conf.get(CFG.CLUSTER_PLACEMENT_SEED))
        self._task_max_failures = self.conf.get(CFG.CLUSTER_TASK_MAX_FAILURES)
        self._task_timeout_s = self.conf.get(CFG.CLUSTER_TASK_TIMEOUT)
        self._blacklist_max = self.conf.get(
            CFG.CLUSTER_BLACKLIST_MAX_TASK_FAILURES)
        self._stage_max_recomputes = self.conf.get(
            CFG.CLUSTER_STAGE_MAX_RECOMPUTES)
        self._speculation = self.conf.get(CFG.CLUSTER_SPECULATION_ENABLED)
        self._speculation_mult = self.conf.get(
            CFG.CLUSTER_SPECULATION_MULTIPLIER)
        # unified mesh-cluster plane state (docs/cluster.md): per-slot
        # attached mesh width from the spawn handshake, and whether the
        # slot's mesh is still trusted for mesh task groups
        self._mesh_enabled = self.conf.get(CFG.CLUSTER_MESH_ENABLED)
        self._two_level = self.conf.get(CFG.CLUSTER_MESH_TWO_LEVEL)
        self._mesh = [0] * n_executors
        self._mesh_ok = [False] * n_executors
        self._movement_aware = self.conf.get(
            CFG.CLUSTER_PLACEMENT_MOVEMENT_AWARE)
        self._max_loaded_bytes = self.conf.get(
            CFG.CLUSTER_PLACEMENT_MAX_LOADED_BYTES)
        self._spawn_retries = self.conf.get(CFG.CLUSTER_SPAWN_MAX_RETRIES)
        self.mesh_stats = {"mesh_tasks": 0, "waves": 0, "degraded": 0,
                           "ici_rows": 0}
        self.placement_stats = {"preferred": 0, "demoted": 0}
        for ei in range(n_executors):
            self._spawn_executor(ei)
        self.task_log: list = []        # (stage_op, executor_idx) per task
        self._after_stage_hook = None   # test fault-injection point

    # -- pool management ----------------------------------------------------
    def _spawn_executor(self, ei: int, arm_faults: bool = True):
        """Bring up slot `ei` with ONE bounded retry on a transient
        socket/pipe bring-up failure (cluster.spawn.maxRetries): a flaky
        handshake must not cost the slot — or, on the loss-recovery path,
        the whole query — before a second attempt was even made. Retries
        are visible as executor.spawn.retry events; they never charge the
        executor a blacklist strike (nothing ran yet)."""
        from spark_rapids_tpu.runtime import tracing
        last = None
        for attempt in range(self._spawn_retries + 1):
            try:
                return self._spawn_executor_once(ei, arm_faults)
            except RuntimeError as e:
                last = e
                if attempt < self._spawn_retries:
                    tracing.span_event("executor.spawn.retry", executor=ei,
                                       attempt=attempt + 1,
                                       error=str(e)[:200])
        raise last

    def _spawn_executor_once(self, ei: int, arm_faults: bool = True):
        from spark_rapids_tpu import config as CFG
        ctx = mp.get_context("spawn")
        parent, child = ctx.Pipe()
        settings = dict(self.conf.settings)
        if not arm_faults:
            # replacement executors come up clean: re-parsing a COUNT
            # trigger in the respawn would fire the same fault forever
            settings.pop(CFG.TEST_FAULTS.key, None)
        p = ctx.Process(target=_executor_main,
                        args=(child, ei, self._platform, settings),
                        daemon=True)
        p.start()
        # bounded handshake: a child that dies during bring-up must surface
        # as an error, not hang the driver in recv() forever
        if not parent.poll(120):
            p.kill()
            p.join(timeout=5)
            raise RuntimeError(f"executor {ei} never came up")
        try:
            hello = parent.recv()
        except (EOFError, OSError) as e:
            p.join(timeout=5)
            raise RuntimeError(f"executor {ei} died during bring-up") from e
        assert hello["op"] == "ready"
        # two-timestamp clock exchange riding the registration handshake
        # (the heartbeat register below is the same handshake's driver
        # half): executor_clock + offset ≈ driver_clock, error bounded by
        # half the pipe round-trip — the correction that lets executor
        # event-log records and span files merge onto the driver timeline
        from spark_rapids_tpu.runtime import tracing
        try:
            t0 = time.time()
            parent.send({"op": "clock"})
            clock = parent.recv()
            t1 = time.time()
            offset = tracing.estimate_clock_offset(t0, clock["t"], t1)
            parent.send({"op": "clock_set", "offset": offset})
            assert parent.recv().get("ok")
        except (EOFError, OSError) as e:
            p.kill()
            p.join(timeout=5)
            raise RuntimeError(
                f"executor {ei} died during clock handshake") from e
        self._conns[ei] = parent
        self._procs[ei] = p
        self.addresses[ei] = ("127.0.0.1", hello["port"])
        self._gen[ei] += 1
        old_eid = self._exec_ids[ei]
        if old_eid is not None:
            # a replaced incarnation must not fire a spurious expiry later
            self._hb.deregister(old_eid)
        eid = f"exec-{ei}-g{self._gen[ei]}"
        self._hb.register(eid, "127.0.0.1", hello["port"])
        self._exec_ids[ei] = eid
        self._exec_failures[ei] = 0
        self._blacklist.discard(ei)
        # mesh plane: the handshake reports the ACTUAL local mesh width
        # (0 = none); a respawned slot attaches a fresh, trusted mesh —
        # the dead incarnation's mesh generation died with it
        self._mesh[ei] = hello.get("mesh", 0) or 0
        self._mesh_ok[ei] = self._mesh[ei] >= 2
        if self._mesh[ei]:
            tracing.span_event("mesh.attach", executor=ei,
                               devices=self._mesh[ei],
                               generation=self._gen[ei])

    def _heal(self):
        """Restart the WHOLE pool — the LAST rung of the recovery ladder,
        reached only when lineage-scoped recovery is exhausted
        (cluster.stage.maxRecomputes) or no executor is placeable.
        Survivors may hold in-flight tasks whose replies would
        desynchronize the request/reply pipe protocol on retry; since the
        retry re-runs every stage anyway, clean processes are both simpler
        and correct (Spark's executor-replacement role)."""
        for ei, p in enumerate(self._procs):
            try:
                self._conns[ei].close()
            except OSError:
                pass
            if p is not None:
                if p.is_alive():
                    p.terminate()
                p.join(timeout=5)
                if p.is_alive():
                    p.kill()
                    p.join(timeout=5)
            self._spawn_executor(ei, arm_faults=False)
        self._tracker = MapOutputTracker()

    # -- liveness -----------------------------------------------------------
    def _poll_liveness(self) -> list:
        """Beat the heartbeat manager for every live executor process, then
        poll expire_dead (the driver-side failure detector the reference
        runs in RapidsShuffleHeartbeatManager); returns the slot indices
        the manager expired."""
        for ei, p in enumerate(self._procs):
            if p is not None and p.is_alive():
                try:
                    self._hb.heartbeat(self._exec_ids[ei])
                except KeyError:
                    pass
        expired = self._hb.expire_dead()
        slots = []
        by_eid = {eid: ei for ei, eid in enumerate(self._exec_ids)}
        for peer in expired:
            ei = by_eid.get(peer.executor_id)
            if ei is not None:
                slots.append(ei)
        return slots

    def check_liveness(self) -> list:
        """Public poll: expire dead executors via the heartbeat manager and
        run the same lineage-scoped recovery as a mid-task loss. Returns
        the recovered slot indices."""
        recovered = []
        for ei in self._poll_liveness():
            if self._procs[ei] is not None and not self._procs[ei].is_alive():
                self._handle_executor_loss(
                    ei, {}, collections.deque(), frozenset(),
                    reason="heartbeat.expired")
                recovered.append(ei)
        return recovered

    # -- loss recovery ------------------------------------------------------
    def _handle_executor_loss(self, ei, running, pending, busy,
                              reason="channel", depth=0, done=None,
                              total=None):
        """The lineage-scoped recovery path: respawn the slot, invalidate
        exactly the map splits the dead peer hosted, re-run only those
        under a bumped epoch, and re-publish addresses. In-flight work on
        other executors keeps running; its replies are discarded if the
        epoch moved underneath them. An in-flight MESH task on the dead
        executor — a participant lost inside the collective (mesh_kill) or
        hung in it past the task deadline (mesh_hang) — is NOT retried as
        a mesh task: its mesh generation is invalidated (mesh.detach) and
        its lanes re-plan onto the per-split TCP path under a bumped epoch
        (the degraded-mode fallback, counted in meshDegradedFallbacks)."""
        from spark_rapids_tpu.runtime import metrics as M
        from spark_rapids_tpu.runtime import tracing
        M.resilience_add(M.EXECUTORS_LOST)
        tracing.span_event("executor.lost", executor=ei,
                           generation=self._gen[ei], reason=reason)
        if self._mesh[ei]:
            tracing.span_event("mesh.detach", executor=ei,
                               generation=self._gen[ei], reason=reason)
        run = running.pop(ei, None)
        if run is not None and (done is None or run.spec.idx not in done):
            if run.spec.lanes is not None:
                self._degrade_mesh_spec(run.spec, ei, pending, total,
                                        reason=f"executor.lost:{reason}",
                                        executor_dead=True)
            else:
                pending.appendleft(run.spec)
        try:
            self._conns[ei].close()
        except OSError:
            pass
        p = self._procs[ei]
        if p is not None:
            if p.is_alive():
                p.kill()
            p.join(timeout=10)
        self._spawn_executor(ei, arm_faults=False)
        # the fresh block store must know every live shuffle id — a peer
        # with no blocks still serves (empty) metadata to reducers
        for sid in self._tracker.sids():
            self._conns[ei].send({"op": "ensure_shuffle", "shuffle_id": sid})
            reply = self._conns[ei].recv()
            assert reply.get("ok"), reply
        self._republish_addresses()
        lost = self._tracker.on_executor_lost(ei)
        for st, splits in lost:
            st.recomputes += 1
            if st.recomputes > self._stage_max_recomputes:
                raise ExecutorLostError(
                    f"shuffle {st.shuffle_id} exceeded "
                    f"cluster.stage.maxRecomputes="
                    f"{self._stage_max_recomputes}; healing the pool")
        for st, splits in lost:
            M.resilience_add(M.STAGE_PARTIAL_RECOMPUTES)
            M.resilience_add(M.MAP_TASKS_RECOMPUTED, len(splits))
            tracing.span_event("stage.recompute.partial",
                               shuffle=st.shuffle_id, epoch=st.epoch,
                               splits=len(splits),
                               total_splits=len(st.splits))
            specs = [self._make_map_spec(st, s, i)
                     for i, s in enumerate(splits)]
            # recompute runs on executors not busy with outer work (the
            # respawned slot is always idle, so progress is guaranteed)
            self._run_tasks(specs, busy=frozenset(busy) | set(running),
                            depth=depth + 1)

    def _republish_addresses(self):
        """Push the (possibly respawned) pool's addresses into every live
        RemoteSourceNode — the driver's plan and every tracked lineage
        subtree share node objects, so one walk re-points future task
        ships and recomputes at the new block servers."""
        roots = list(self._tracker.subtrees())
        if self._current_root is not None:
            roots.append(self._current_root)
        seen = set()
        for root in roots:
            for src in _collect_sources(root, []):
                if id(src) not in seen:
                    seen.add(id(src))
                    src.locations = [tuple(a) for a in self.addresses]

    def _stamp_epochs(self, plan):
        for src in _collect_sources(plan, []):
            src.epoch = self._tracker.epoch(src.shuffle_id)

    # -- task plumbing ------------------------------------------------------
    def _make_map_spec(self, st: _ShuffleState, split: int,
                       idx: int | None = None) -> _TaskSpec:
        return _TaskSpec(idx if idx is not None else split, "map",
                         st.subtree,
                         split if st.mode == "pinned" else None, split,
                         shuffle_id=st.shuffle_id,
                         partitioner=st.partitioner)

    def _build_task(self, spec: _TaskSpec, ei: int | None = None) -> dict:
        from spark_rapids_tpu.runtime import tracing
        if spec.lanes is not None:
            # mesh map task: ship the UNPINNED subtree once; the executor
            # pins a clone per lane (one lane per local mesh device)
            plan = _clone_plan(spec.subtree)
            self._stamp_epochs(plan)
            task = {"plan": plan, "splits": [],
                    "mesh_lanes": [{"split": s, "pin": p}
                                   for s, p in spec.lanes],
                    "shuffle_id": spec.shuffle_id,
                    "partitioner": spec.partitioner,
                    "trace": tracing.current_trace_id()}
            st = self._tracker.state(spec.shuffle_id)
            if ei is not None and st is not None and st.owners is not None:
                # two-level exchange: the reduce partitions THIS executor
                # owns ride ICI inside the task's waves; the rest slice
                # and park for the TCP fetch
                task["reduce_owned"] = [r for r, o in enumerate(st.owners)
                                        if o == ei]
            return task
        if spec.pin is not None:
            plan = _pin_sources(_clone_plan(spec.subtree), spec.pin)
            splits = [0]
        else:
            plan = spec.subtree
            splits = [spec.split]
        self._stamp_epochs(plan)
        task = {"plan": plan, "splits": splits,
                "trace": tracing.current_trace_id()}
        if spec.op == "map":
            task.update({"shuffle_id": spec.shuffle_id,
                         "partitioner": spec.partitioner,
                         "map_split": spec.split})
        return task

    def _drop_map_output(self, ei: int, spec: _TaskSpec, running, pending,
                         busy, depth=0, done=None):
        """Evict one map attempt's blocks from a LIVE executor (speculation
        loser, stale-epoch or failed attempt that may have written partial
        output); a dead executor's blocks died with its store. A mesh
        task's attempt drops every lane's split."""
        try:
            for s in spec.splits_covered():
                self._conns[ei].send({"op": "drop_map_output",
                                      "shuffle_id": spec.shuffle_id,
                                      "map_split": s})
                reply = self._conns[ei].recv()
                assert reply.get("ok"), reply
        except (BrokenPipeError, EOFError, OSError):
            self._handle_executor_loss(ei, running, pending, busy,
                                       depth=depth, done=done)

    def _degrade_mesh_spec(self, spec: _TaskSpec, ei, pending, total,
                           reason: str, executor_dead: bool,
                           running=None, busy=frozenset(), depth=0,
                           done=None):
        """Degraded-mode fallback (the robustness core of the unified
        plane): a mesh task that cannot run — or finish — on an executor's
        local mesh is transparently re-planned as SINGLE-split TCP tasks
        under a bumped map-output epoch, bit-identical to the healthy run.
        No task-attempt strike is charged: degradation is capacity loss,
        not task failure. When the executor survived (mesh shrank, chips
        unavailable, collective error) its partial blocks are evicted
        first and its mesh is distrusted for future groups; a dead
        executor's blocks died with its store and its RESPAWN attaches a
        fresh, trusted mesh."""
        from spark_rapids_tpu.runtime import metrics as M
        from spark_rapids_tpu.runtime import tracing
        splits = spec.splits_covered()
        M.resilience_add(M.MESH_DEGRADED_FALLBACKS)
        self.mesh_stats["degraded"] += 1
        tracing.span_event("mesh.degraded", executor=ei,
                           shuffle=spec.shuffle_id, splits=len(splits),
                           reason=reason)
        if not executor_dead and ei is not None and ei >= 0:
            if self._mesh_ok[ei]:
                self._mesh_ok[ei] = False
                tracing.span_event("mesh.detach", executor=ei,
                                   generation=self._gen[ei],
                                   reason="degraded")
            self._drop_map_output(ei, spec, running if running is not None
                                  else {}, pending, busy, depth=depth,
                                  done=done)
        # bump the epoch so an in-flight reply that read the pre-drop
        # layout is discarded, then re-plan each lane as its own TCP task
        self._tracker.invalidate_splits(spec.shuffle_id, splits)
        st = self._tracker.state(spec.shuffle_id)
        if total is not None:
            total.discard(spec.idx)
        for s in splits:
            nspec = self._make_map_spec(
                st, s, idx=("degraded", spec.shuffle_id, s, st.epoch))
            if total is not None:
                total.add(nspec.idx)
            pending.append(nspec)

    def _charge_failure(self, ei: int, spec: _TaskSpec, reason: str,
                        err: str = ""):
        from spark_rapids_tpu.runtime import metrics as M
        from spark_rapids_tpu.runtime import tracing
        spec.attempts += 1
        spec.tried.add(ei)
        M.resilience_add(M.TASK_ATTEMPTS)
        tracing.span_event("task.attempt", executor=ei, op=spec.op,
                           split=spec.split, shuffle=spec.shuffle_id,
                           attempt=spec.attempts, reason=reason,
                           error=err[-200:] if err else "")
        self._exec_failures[ei] += 1
        if (ei not in self._blacklist
                and self._exec_failures[ei] >= self._blacklist_max):
            self._blacklist.add(ei)
            M.resilience_add(M.EXECUTORS_BLACKLISTED)
            tracing.span_event("executor.blacklisted", executor=ei,
                               failures=self._exec_failures[ei])

    def _preferred_executor(self, spec: _TaskSpec, eligible):
        """Movement-aware placement: the executor already holding the most
        map-output bytes for this reduce partition (Theseus's
        movement-optimized scheduling — the read becomes a local
        block-store short-circuit instead of a TCP fetch). Spill-aware
        demotion: an executor parking more than placement.maxLoadedBytes
        of shuffle data is over its HBM/host budget proxy, and piling its
        reduce work on top would only force disk spills — demote to
        round-robin (placement.demoted)."""
        from spark_rapids_tpu.runtime import tracing
        by = self._tracker.bytes_by_executor(spec.read_sids, spec.pin)
        if not by:
            return None
        best = max(sorted(by), key=lambda e: by[e])
        if by[best] <= 0 or best not in eligible or best in spec.tried:
            return None
        load = self._tracker.executor_load(best)
        if load > self._max_loaded_bytes:
            self.placement_stats["demoted"] += 1
            tracing.span_event("placement.demoted", executor=best,
                               loaded_bytes=load,
                               budget=self._max_loaded_bytes,
                               reduce=spec.pin)
            return None
        self.placement_stats["preferred"] += 1
        return best

    def _owner_executor(self, spec: _TaskSpec, eligible):
        """Two-level placement: the executor OWNING the task's reduce
        partition(s) under the upstream shuffles' ownership assignment —
        the host whose mesh tasks already routed those partitions' content
        over ICI into its local store. Mesh consumer groups vote with
        every lane's pin; ties and unowned shuffles return None (fall back
        to byte-based preference / round-robin)."""
        pins = ([p for _, p in spec.lanes if p is not None]
                if spec.lanes is not None
                else [spec.pin] if spec.pin is not None else [])
        if not pins:
            return None
        votes: dict = {}
        for sid in spec.read_sids:
            st = self._tracker.state(sid)
            if st is None or st.owners is None:
                continue
            for p in pins:
                if 0 <= p < len(st.owners):
                    votes[st.owners[p]] = votes.get(st.owners[p], 0) + 1
        if not votes:
            return None
        best = max(sorted(votes), key=lambda e: votes[e])
        if best not in eligible or best in spec.tried:
            return None
        self.placement_stats["owner"] = \
            self.placement_stats.get("owner", 0) + 1
        return best

    # -- the scheduler loop -------------------------------------------------
    def _run_tasks(self, specs: list, busy=frozenset(), depth: int = 0
                   ) -> dict:
        """Run every spec to completion across the pool; returns
        {spec.idx: reply}. One in-flight task per executor (the Pipe is a
        simple duplex channel); handles attempts, blacklisting, deadlines,
        executor loss (with nested lineage recompute) and speculation."""
        import multiprocessing.connection as mpc

        from spark_rapids_tpu.runtime import metrics as M
        from spark_rapids_tpu.runtime import tracing
        if depth > 8:
            raise ExecutorLostError("recovery recursion exhausted")
        pending = collections.deque(specs)
        running: dict[int, _Running] = {}
        done: dict = {}
        durations: list = []
        # MUTABLE: a degraded mesh task swaps its group idx for per-split
        # idxs, so completion tracks whatever the plan degraded into
        total = {s.idx for s in specs}

        def dispatch(spec, speculative=False):
            import cloudpickle
            eligible = {ei for ei in range(self.n_executors)
                        if ei not in running and ei not in busy
                        and ei not in self._blacklist
                        and self._procs[ei] is not None
                        and self._procs[ei].is_alive()}
            preferred = None
            if spec.lanes is not None:
                # a mesh group may only land on a trusted mesh at least as
                # wide as the group; when NO placeable executor still has
                # one (all degraded/blacklisted), the group itself degrades
                capable = {ei for ei in range(self.n_executors)
                           if self._mesh_ok[ei]
                           and self._mesh[ei] >= len(spec.lanes)
                           and ei not in self._blacklist
                           and self._procs[ei] is not None
                           and self._procs[ei].is_alive()}
                if not capable:
                    return "degrade"
                eligible &= capable
                # two-level: a consumer mesh group prefers the executor
                # owning its lanes' reduce partitions — the owned bytes
                # are already in that executor's local store
                if self._movement_aware and spec.read_sids:
                    preferred = self._owner_executor(spec, eligible)
            elif (self._movement_aware and spec.pin is not None
                    and spec.read_sids):
                preferred = (self._owner_executor(spec, eligible)
                             or self._preferred_executor(spec, eligible))
            ei = self._placement.pick(eligible, prefer_not=spec.tried,
                                      preferred=preferred)
            if ei is None:
                return None
            task = self._build_task(spec, ei)
            epochs = self._tracker.epochs(spec.read_sids)
            try:
                self._conns[ei].send(
                    {"op": spec.op, "task": cloudpickle.dumps(task)})
            except (BrokenPipeError, OSError):
                self._handle_executor_loss(ei, running, pending, busy,
                                           depth=depth, done=done,
                                           total=total)
                return False
            running[ei] = _Running(spec, time.monotonic(), epochs,
                                   speculative, self._gen[ei])
            self.task_log.append(
                (spec.op if spec.lanes is None else "map.mesh", ei))
            if len(self.task_log) > 4096:   # observability ring, not a ledger
                del self.task_log[:-2048]
            return ei

        def handle_reply(ei, run, reply):
            spec = run.spec
            if not reply.get("ok"):
                err = reply.get("error") or ""
                if reply.get("mesh_degraded") and spec.lanes is not None:
                    # the executor is alive but its mesh is not (shrank,
                    # chips unavailable, collective failed): transparent
                    # re-plan onto the TCP path, no attempt strike
                    reason = (err.strip().splitlines() or ["mesh"])[-1]
                    self._degrade_mesh_spec(
                        spec, ei, pending, total, reason=reason[-160:],
                        executor_dead=False, running=running, busy=busy,
                        depth=depth, done=done)
                    return
                if "TransportError" in err:
                    dead = [k for k, p in enumerate(self._procs)
                            if p is not None and not p.is_alive()]
                    if dead:
                        # a fetch against a dead peer is not the task's
                        # fault (Spark: FetchFailed doesn't count against
                        # task attempts) — recover the peers, retry free
                        for k in dead:
                            self._handle_executor_loss(k, running, pending,
                                                       busy, depth=depth,
                                                       done=done)
                        if spec.op == "map":
                            self._drop_map_output(ei, spec, running, pending,
                                                  busy, depth=depth,
                                                  done=done)
                        if spec.idx not in done:
                            pending.appendleft(spec)
                        return
                # a real task failure: partial map output on a LIVE
                # executor must be evicted before the retry re-writes it
                if spec.op == "map":
                    self._drop_map_output(ei, spec, running, pending, busy,
                                          depth=depth, done=done)
                self._charge_failure(ei, spec, "failure", err)
                if spec.attempts >= self._task_max_failures:
                    raise RuntimeError(
                        f"task {spec.op}/{spec.split} failed "
                        f"{spec.attempts} times "
                        f"(cluster.task.maxFailures="
                        f"{self._task_max_failures}); last error:\n{err}")
                if spec.idx not in done:
                    pending.append(spec)
                return
            if spec.idx in done:
                # a duplicate (speculation) or re-run lost the race: the
                # winner's blocks are the only copy allowed to survive
                M.resilience_add(M.SPECULATION_LOST)
                tracing.span_event("speculation.lost", executor=ei,
                                   op=spec.op, split=spec.split,
                                   shuffle=spec.shuffle_id)
                if spec.op == "map":
                    self._drop_map_output(ei, spec, running, pending, busy,
                                          depth=depth, done=done)
                return
            if run.epochs != self._tracker.epochs(spec.read_sids):
                # computed against metadata that moved underneath it (a
                # peer died and its splits were rebuilt mid-flight): the
                # reply may have read a half-rebuilt partition — discard
                M.resilience_add(M.TASK_ATTEMPTS)
                tracing.span_event("task.attempt", executor=ei, op=spec.op,
                                   split=spec.split, shuffle=spec.shuffle_id,
                                   attempt=spec.attempts + 1,
                                   reason="stale_epoch")
                if spec.op == "map":
                    self._drop_map_output(ei, spec, running, pending, busy,
                                          depth=depth, done=done)
                pending.appendleft(spec)
                return
            done[spec.idx] = reply
            durations.append(time.monotonic() - run.t0)
            if spec.op == "map":
                sizes = reply.get("split_sizes") or {}
                for s in spec.splits_covered():
                    self._tracker.register_map_output(spec.shuffle_id, s,
                                                      ei, sizes.get(s))
                if spec.lanes is not None:
                    mesh = reply.get("mesh") or {}
                    self.mesh_stats["mesh_tasks"] += 1
                    self.mesh_stats["waves"] += mesh.get("waves", 0)
                    self.mesh_stats["ici_rows"] += mesh.get("ici_rows", 0)
            if run.speculative:
                M.resilience_add(M.SPECULATION_WON)
                tracing.span_event("speculation.won", executor=ei,
                                   op=spec.op, split=spec.split,
                                   shuffle=spec.shuffle_id)

        while not total.issubset(done.keys()) or running:
            # heartbeat-manager failure detection (expire_dead), polled by
            # the driver every scheduling round
            for ei in self._poll_liveness():
                if (self._procs[ei] is not None
                        and not self._procs[ei].is_alive()):
                    self._handle_executor_loss(ei, running, pending, busy,
                                               reason="heartbeat.expired",
                                               depth=depth, done=done,
                                               total=total)
            # a nested recovery may have respawned a slot under an outer
            # in-flight task: its reply can never arrive on the new pipe
            for ei, run in list(running.items()):
                if run.gen != self._gen[ei]:
                    del running[ei]
                    if run.spec.idx not in done:
                        pending.appendleft(run.spec)
            # fill idle executors (a False dispatch respawned the slot it
            # targeted, so retrying the same spec makes progress; a
            # "degrade" dispatch found NO placeable mesh executor left for
            # the group — it re-plans per-split and the loop continues)
            while pending:
                r = dispatch(pending[0])
                if r is None:
                    break               # no idle eligible executor
                if r is False:
                    continue
                if r == "degrade":
                    spec = pending.popleft()
                    self._degrade_mesh_spec(spec, -1, pending, total,
                                            reason="no_mesh_executor",
                                            executor_dead=True)
                    continue
                pending.popleft()
            if not running:
                if not pending and total.issubset(done.keys()):
                    break
                if pending:
                    raise ExecutorLostError(
                        f"no placeable executor for {len(pending)} pending "
                        f"task(s) (blacklisted={sorted(self._blacklist)})")
            conns = {self._conns[ei]: ei for ei in running}
            ready = mpc.wait(list(conns), timeout=0.05)
            now = time.monotonic()
            if not ready:
                # deadline scan: a task past cluster.task.timeoutSeconds is
                # on a hung executor — the pipe protocol cannot cancel a
                # task, so the executor is killed and replaced
                if self._task_timeout_s > 0:
                    for ei, run in list(running.items()):
                        if now - run.t0 > self._task_timeout_s:
                            self._charge_failure(ei, run.spec, "timeout")
                            if run.spec.attempts >= self._task_max_failures:
                                raise RuntimeError(
                                    f"task {run.spec.op}/{run.spec.split} "
                                    f"timed out {run.spec.attempts} times")
                            self._handle_executor_loss(ei, running, pending,
                                                       busy,
                                                       reason="task.timeout",
                                                       depth=depth,
                                                       done=done,
                                                       total=total)
                # speculation: duplicate stragglers on idle executors
                if (self._speculation and depth == 0 and not pending
                        and running and durations):
                    med = statistics.median(durations)
                    for ei, run in list(running.items()):
                        if (run.speculative or run.spec.speculated
                                or run.spec.idx in done
                                or run.spec.lanes is not None):
                            # mesh groups are never speculated: a duplicate
                            # group racing a straggler would double-write N
                            # lanes' blocks for one slow chip
                            continue
                        if now - run.t0 <= self._speculation_mult * med:
                            continue
                        run.spec.speculated = True
                        dispatch(run.spec, speculative=True)
                continue
            for conn in ready:
                ei = conns[conn]
                if ei not in running:
                    continue            # pool changed while iterating
                try:
                    reply = conn.recv()
                except (EOFError, OSError):
                    self._handle_executor_loss(ei, running, pending, busy,
                                               depth=depth, done=done,
                                               total=total)
                    continue
                run = running.pop(ei)
                handle_reply(ei, run, reply)
        return done

    # -- scheduling ---------------------------------------------------------
    def collect(self, df) -> pa.Table:
        last = None
        for attempt in range(self.max_attempts):
            try:
                return self._collect_once(df)
            except ExecutorLostError as e:
                # the FINAL fallback: lineage-scoped recovery was exhausted,
                # heal the pool and re-run all stages with fresh shuffle ids
                last = e
                self._heal()
        raise last

    def _collect_once(self, df) -> pa.Table:
        import uuid

        from spark_rapids_tpu.plan.distribute import (ensure_distribution,
                                                      stage_order)
        from spark_rapids_tpu.runtime import tracing
        plan = _clone_plan(df._plan)
        plan = ensure_distribution(plan, self.n_executors)
        self._tracker = MapOutputTracker()
        self._current_root = plan
        # one trace id for the whole distributed query: inherited from an
        # ambient session query when there is one, else minted here; every
        # task ships it (_build_task) so executor spans and their shuffle
        # fetches land on the same merged timeline
        trace_id = tracing.current_trace_id() or \
            f"cluster-{os.getpid():x}-{uuid.uuid4().hex[:8]}"
        try:
            with tracing.trace_context(trace_id), \
                    tracing.span("cluster.query",
                                 executors=self.n_executors):
                for exchange, parent, idx in stage_order(plan):
                    source = self._run_map_stage(exchange)
                    parent.children[idx] = source
                    if self._after_stage_hook is not None:
                        self._after_stage_hook(self)
                out = self._run_result_stage(plan)
        finally:
            self._current_root = None
        self._cleanup_shuffles(self._tracker.sids())
        # the finished query's lineage is dead weight: a loss between
        # queries should respawn the slot, not recompute dropped shuffles
        self._tracker = MapOutputTracker()
        return out

    def _broadcast_ensure_shuffle(self, sid: int):
        """Every executor must know the shuffle id — a peer with no map
        task for it still serves (empty) metadata requests from reducers.
        An executor lost mid-broadcast is recovered in place (the respawn
        path re-ensures every tracked shuffle, including this one)."""
        for ei in range(self.n_executors):
            for _ in range(2):
                try:
                    self._conns[ei].send({"op": "ensure_shuffle",
                                          "shuffle_id": sid})
                    reply = self._conns[ei].recv()
                    assert reply.get("ok"), reply
                    break
                except (BrokenPipeError, EOFError, OSError):
                    self._handle_executor_loss(
                        ei, {}, collections.deque(), frozenset())
            else:
                raise ExecutorLostError(
                    f"executor {ei} unreachable for ensure_shuffle")

    def _run_map_stage(self, exchange):
        from spark_rapids_tpu.plan import nodes as NN
        from spark_rapids_tpu.runtime import eventlog as EL
        from spark_rapids_tpu.runtime import metrics as M
        from spark_rapids_tpu.shuffle import partitioning as SP
        child = exchange.child
        if exchange.partitioning == "hash":
            part = SP.HashPartitioner(exchange.keys, exchange.num_out)
        elif exchange.partitioning == "single":
            part = SP.SinglePartitioner()
        elif exchange.partitioning == "roundrobin":
            part = SP.RoundRobinPartitioner(exchange.num_out)
        else:
            raise NotImplementedError(
                "range partitioning needs driver-side sampling (use "
                "sort with a single exchange in MiniCluster)")
        sid = next(self._shuffle_ids)
        mode, splits = self._stage_shape(child)
        st = self._tracker.register_shuffle(sid, child, part, mode, splits)
        # two-level exchange: assign every reduce partition an OWNING
        # executor up front (round-robin over placeable executors, so the
        # assignment is deterministic and balanced). Map tasks route owned
        # partitions' content over ICI; consumer placement below routes the
        # partition's reader to the owner, turning those bytes into local
        # short-circuit reads instead of loopback/TCP fetches
        if (self._two_level and self._mesh_group_width() >= 2
                and len(splits) >= 2
                and isinstance(part, SP.HashPartitioner)):
            placeable = [ei for ei in range(self.n_executors)
                         if ei not in self._blacklist
                         and self._procs[ei] is not None
                         and self._procs[ei].is_alive()]
            if placeable:
                st.owners = [placeable[r % len(placeable)]
                             for r in range(part.num_partitions)]
        self._broadcast_ensure_shuffle(sid)
        self._run_tasks(self._make_stage_specs(st))
        # stats plane: per-reduce-partition byte totals from the tracker's
        # split sizes, recorded into the ambient query's collector so the
        # shuffle-skew read-outs (plan.stats, profiler) cover mesh-plane map
        # stages too — not only the local exchange path
        if st.split_sizes:
            totals = [0] * part.num_partitions
            for split_sizes in st.split_sizes.values():
                for rid, b in enumerate(split_sizes[:part.num_partitions]):
                    totals[rid] += int(b)
            collector = M.current_collector()
            if collector is not None:
                collector.record_shuffle_sizes(None, sid, totals)
            if EL.enabled():
                # driver-side skew record: executors ran the map tasks, so
                # without this the DRIVER's log has no partition sizes and
                # the profiler's skew table goes blind on cluster runs
                EL.emit("stage.map.end", shuffle=sid,
                        partition_sizes=totals)
        return NN.RemoteSourceNode(sid, child.output, part.num_partitions,
                                   [tuple(a) for a in self.addresses],
                                   epoch=self._tracker.epoch(sid))

    def _mesh_group_width(self) -> int:
        """Lane width for mesh map tasks: the NARROWEST trusted mesh among
        placeable executors (groups must fit wherever they land); 0 when
        the mesh plane is off or no trusted mesh remains."""
        if not self._mesh_enabled:
            return 0
        widths = [self._mesh[ei] for ei in range(self.n_executors)
                  if self._mesh_ok[ei] and ei not in self._blacklist]
        return min(widths) if widths else 0

    def _make_stage_specs(self, st: _ShuffleState) -> list:
        """Task specs for one map stage. On the unified plane, a
        hash-partitioned stage's splits are grouped into mesh tasks of up
        to the local mesh width — one task drives M lanes on one
        executor's chips, with inter-executor movement still riding the
        TCP shuffle. Everything else (single/round-robin partitioners,
        mesh plane off or fully degraded) keeps the per-split shape."""
        from spark_rapids_tpu.shuffle import partitioning as SP
        width = self._mesh_group_width()
        if (width < 2 or len(st.splits) < 2
                or not isinstance(st.partitioner, SP.HashPartitioner)):
            return [self._make_map_spec(st, s, i)
                    for i, s in enumerate(st.splits)]
        splits = st.splits
        if st.mode == "pinned":
            # two-level: order a consumer stage's reduce-id splits by the
            # upstream ownership assignment, so each mesh group's lanes
            # share ONE owner and the whole group can be placed there
            owners = None
            for src in _collect_sources(st.subtree, []):
                up = self._tracker.state(src.shuffle_id)
                if up is not None and up.owners is not None:
                    owners = up.owners
                    break
            if owners is not None:
                splits = sorted(splits,
                                key=lambda s: (owners[s]
                                               if 0 <= s < len(owners)
                                               else -1, s))
        specs = []
        for gi in range(0, len(splits), width):
            group = splits[gi:gi + width]
            if len(group) == 1:
                specs.append(self._make_map_spec(st, group[0],
                                                 idx=("m", gi)))
            else:
                lanes = [(s, s if st.mode == "pinned" else None)
                         for s in group]
                specs.append(_TaskSpec(("m", gi), "map", st.subtree, None,
                                       group[0],
                                       shuffle_id=st.shuffle_id,
                                       partitioner=st.partitioner,
                                       lanes=lanes))
        return specs

    def _stage_shape(self, subtree):
        """Task shape covering every partition of `subtree`.
        Co-partitioned shuffle inputs → one pinned task per reduce id;
        everything else → one task per partition of the subtree (a UNION of
        a scan leaf with a shuffle source spreads its leaf splits and reduce
        partitions across executors instead of serializing in one task)."""
        sources = _collect_sources(subtree, [])
        if sources and not _has_non_source_leaves(subtree) and \
                len({s.n_parts for s in sources}) == 1:
            return "pinned", list(range(sources[0].n_parts))
        return "plain", list(range(subtree.num_partitions))

    def _run_result_stage(self, plan) -> pa.Table:
        from spark_rapids_tpu import types as T
        mode, splits = self._stage_shape(plan)
        specs = [_TaskSpec(i, "result", plan,
                           s if mode == "pinned" else None, s)
                 for i, s in enumerate(splits)]
        replies = self._run_tasks(specs)
        tables = []
        for i in range(len(specs)):
            t = pa.ipc.open_stream(replies[i]["ipc"]).read_all()
            if t.num_rows:
                tables.append(t)
        if not tables:
            # derive the empty-result schema from the plan's DECLARED
            # output instead of trusting the first (possibly schema-less)
            # empty reply: an all-empty multi-executor result must not
            # concat mismatched tables
            return pa.Table.from_arrays(
                [pa.array([], T.to_arrow_type(f.data_type))
                 for f in plan.output],
                names=[f.name for f in plan.output])
        return pa.concat_tables(tables)

    def _cleanup_shuffles(self, sids):
        """Best-effort: drop a finished query's shuffle blocks from every
        executor store (they are never read again; leaving them would grow
        executor memory query over query)."""
        for ei in range(self.n_executors):
            try:
                for sid in sids:
                    self._conns[ei].send({"op": "drop_shuffle",
                                          "shuffle_id": sid})
                    self._conns[ei].recv()
            except (BrokenPipeError, EOFError, OSError):
                pass

    def shutdown(self):
        for c in self._conns:
            if c is None:
                continue
            try:
                c.send({"op": "stop"})
                if c.poll(5):
                    c.recv()
            except (BrokenPipeError, EOFError, OSError):
                pass
        for p in self._procs:
            if p is None:
                continue
            p.join(timeout=10)
            if p.is_alive():
                p.terminate()
                p.join(timeout=5)
            if p.is_alive():
                # terminate() can be ignored by a hung child; escalate so
                # chaos tests never leak zombie processes
                p.kill()
                p.join(timeout=5)
        for c in self._conns:
            if c is None:
                continue
            try:
                c.close()
            except OSError:
                pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False
