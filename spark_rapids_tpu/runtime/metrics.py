"""Operator metrics — the GpuMetric analog.

Reference: GpuExec.scala:32-140: GpuMetric wraps SQLMetric with levels
ESSENTIAL/MODERATE/DEBUG gated by spark.rapids.sql.metrics.level; ~25 standard names
(NUM_OUTPUT_ROWS, OP_TIME, SEMAPHORE_WAIT_TIME, SPILL bytes per tier, …) and
makeSpillCallback feeding spill bytes back into the running operator's metrics."""

from __future__ import annotations

import bisect
import itertools
import os
import threading
import time
import uuid
from contextlib import contextmanager

ESSENTIAL = 0
MODERATE = 1
DEBUG = 2

_LEVELS = {"ESSENTIAL": ESSENTIAL, "MODERATE": MODERATE, "DEBUG": DEBUG}

# standard metric names (reference GpuExec.scala:42-67)
NUM_OUTPUT_ROWS = "numOutputRows"
NUM_OUTPUT_BATCHES = "numOutputBatches"
NUM_INPUT_ROWS = "numInputRows"
NUM_INPUT_BATCHES = "numInputBatches"
OP_TIME = "opTime"
TOTAL_TIME = "totalTime"
SEMAPHORE_WAIT_TIME = "semaphoreWaitTime"
PEAK_DEVICE_MEMORY = "peakDevMemory"
SPILL_AMOUNT = "spillData"
SPILL_AMOUNT_DISK = "spillDisk"
SPILL_AMOUNT_HOST = "spillHost"
BUILD_TIME = "buildTime"
JOIN_TIME = "joinTime"
SORT_TIME = "sortTime"
AGG_TIME = "computeAggTime"
CONCAT_TIME = "concatTime"
READ_FS_TIME = "readFsTime"
WRITE_TIME = "writeTime"
PARTITION_TIME = "partitionTime"
COLLECT_TIME = "collectTime"
NUM_PARTITIONS = "partitions"
# derived wall-clock attribution: time spent producing this node's output
# batches minus time spent inside child nodes on the same thread (the SQL
# UI's "op time" self-time column; maintained by TpuExec.wrap_output frames)
SELF_TIME = "selfTime"
# the build region's own self time (a nested node_frame inside the join's
# output frame: charged here, subtracted from the join's selfTime) — the
# profiler renders it as the "(build)" line item
BUILD_SELF_TIME = "buildSelfTime"
READAHEAD_STALL_TIME = "readaheadStallTime"
# pipeline queue edges (runtime/pipeline.py): per-edge metric names are
# suffixed "<name>:<edge>" (e.g. "queueWaitTime:scan.decode") so one exec
# can own several edges and the profiler can attribute stalls per edge
QUEUE_WAIT_TIME = "queueWaitTime"      # consumer blocked on an empty queue
QUEUE_FULL_TIME = "queueFullTime"      # producer blocked on a full queue
QUEUE_DEPTH_PEAK = "queueDepthPeak"    # high-water mark of queued batches

# resilience counters (reference: RmmRapidsRetryIterator retry/split counts
# surfaced through GpuMetric, RapidsShuffleIterator fetch-failure accounting)
NUM_OOM_RETRIES = "numOomRetries"
NUM_OOM_SPLIT_RETRIES = "numOomSplitRetries"
OOM_SPILL_BYTES = "oomRetrySpillBytes"
FETCH_RETRIES = "fetchRetries"
FETCH_FAILOVERS = "fetchFailovers"
FETCH_RECOMPUTES = "fetchRecomputes"
# cluster-scheduler recovery (cluster/minicluster.py): task re-attempts,
# executor deaths and blacklistings, lineage-scoped partial stage
# recomputes (map tasks re-run counted separately so chaos tests can prove
# recovery cost was proportional to the loss), and speculation outcomes
TASK_ATTEMPTS = "taskAttempts"
EXECUTORS_LOST = "executorsLost"
EXECUTORS_BLACKLISTED = "executorsBlacklisted"
STAGE_PARTIAL_RECOMPUTES = "stagePartialRecomputes"
MAP_TASKS_RECOMPUTED = "mapTasksRecomputed"
SPECULATION_WON = "speculationWon"
SPECULATION_LOST = "speculationLost"
# unified mesh-cluster plane (cluster/minicluster.py + distributed/mesh.py):
# a mesh map task that could not run (or finish) on its executor's local
# mesh and was transparently re-planned onto the per-split TCP-shuffle path
# under a bumped epoch. Zero in every healthy run — rides the no-faults
# all-zero gates like the rest of the recovery ladder
MESH_DEGRADED_FALLBACKS = "meshDegradedFallbacks"
# multi-tenant query lifecycle (runtime/scheduler.py): shed submissions,
# cancelled/deadlined queries and fair-share demotions of a victim query's
# device buffers during a peer's OOM recovery
QUERIES_SHED = "queriesShed"
QUERIES_CANCELLED = "queriesCancelled"
QUERY_DEMOTIONS = "queryDemotions"
# serving endpoint (runtime/endpoint.py): a client connection lost while its
# query was in flight (half-close, RST, or idle-timeout expiry) — the query
# was cancelled by the disconnect path
CLIENT_DISCONNECTS = "clientDisconnects"
# memory observability plane (runtime/memory.py): catalog buffers a finished
# query left behind, caught + reclaimed by the end-of-query leak detector.
# Riding the resilience registry makes leak-freedom a standing CI invariant:
# the no-faults phases of the chaos gates (tools/fleet_chaos.py) assert
# every counter here is zero
MEMORY_LEAKS = "memoryLeakedBuffers"
# serving fleet (runtime/fleet.py): a survivor's sweeper adopted a dead
# replica's expired lease — unlinked the membership record and reclaimed its
# orphaned shared-store write intents
FLEET_ADOPTIONS = "fleetAdoptions"
# fleet client (runtime/endpoint.py EndpointClient): a retryable failure
# rotated the client to the next replica in its address list
REPLICA_FAILOVERS = "replicaFailovers"
# streaming epochs (streaming/coordinator.py): a pending (begun,
# uncommitted) epoch re-run after a crash/kill, and a committed state
# snapshot that failed its journal checksum and was rebuilt from the
# consumed batch log. Both zero in every clean run — a no-faults stream
# never replays and never rebuilds
STREAM_EPOCH_REPLAYS = "streamEpochReplays"
STREAM_STATE_REBUILDS = "streamStateRebuilds"

RESILIENCE_METRICS = (NUM_OOM_RETRIES, NUM_OOM_SPLIT_RETRIES, OOM_SPILL_BYTES,
                      FETCH_RETRIES, FETCH_FAILOVERS, FETCH_RECOMPUTES,
                      TASK_ATTEMPTS, EXECUTORS_LOST, EXECUTORS_BLACKLISTED,
                      STAGE_PARTIAL_RECOMPUTES, MAP_TASKS_RECOMPUTED,
                      SPECULATION_WON, SPECULATION_LOST,
                      MESH_DEGRADED_FALLBACKS,
                      QUERIES_SHED, QUERIES_CANCELLED, QUERY_DEMOTIONS,
                      CLIENT_DISCONNECTS, MEMORY_LEAKS,
                      FLEET_ADOPTIONS, REPLICA_FAILOVERS,
                      STREAM_EPOCH_REPLAYS, STREAM_STATE_REBUILDS)


class GpuMetric:
    __slots__ = ("name", "level", "_value", "_lock", "_pending")

    def __init__(self, name: str, level: int = MODERATE):
        self.name = name
        self.level = level
        self._value = 0
        self._lock = threading.Lock()
        self._pending = []

    def add(self, v):
        with self._lock:
            self._value += int(v)

    def add_lazy(self, v):
        """Accumulate a possibly-device scalar WITHOUT forcing a host sync;
        pending scalars are folded into the value at read time (value())."""
        if isinstance(v, int):
            self.add(v)
            return
        with self._lock:
            self._pending.append(v)

    def set(self, v):
        with self._lock:
            self._value = int(v)

    @property
    def value(self):
        with self._lock:
            if self._pending:
                for v in self._pending:
                    self._value += int(v)
                self._pending = []
            return self._value

    @contextmanager
    def timed(self):
        """Time a region in nanoseconds (reference NvtxWithMetrics couples a trace
        range with a timing metric — see runtime/tracing.py for the range side)."""
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.add(time.perf_counter_ns() - t0)

    def __repr__(self):
        return f"GpuMetric({self.name}={self._value})"


class _NoopMetric(GpuMetric):
    """Stand-in for metrics above the configured level: all updates are dropped."""

    def add(self, v):
        pass

    def add_lazy(self, v):
        # must drop like add/set: appending device scalars to _pending on a
        # metric whose value is never read would pin them forever
        pass

    def set(self, v):
        pass


class MetricsRegistry:
    """Per-operator metric set filtered by the configured level."""

    def __init__(self, level_name: str = "MODERATE"):
        self.level = _LEVELS.get(level_name.upper(), MODERATE)
        self._metrics: dict[str, GpuMetric] = {}

    def metric(self, name: str, level: int = MODERATE) -> GpuMetric:
        if name not in self._metrics:
            cls = _NoopMetric if level > self.level else GpuMetric
            self._metrics[name] = cls(name, level)
        return self._metrics[name]

    def snapshot(self):
        return {n: m.value for n, m in self._metrics.items() if m.level <= self.level}


# -- process-wide resilience registry ----------------------------------------
# Retry/split/fetch-failover counts outlive any one operator's registry (a
# retry may span operator teardown), so they accumulate here; chaos tests
# (tests/test_retry_faults.py) and the STATS exposition
# (`srt_resilience_total`) read whole-query totals from this registry.

_global_registry: "MetricsRegistry | None" = None
_global_lock = threading.Lock()


def global_registry() -> MetricsRegistry:
    global _global_registry
    with _global_lock:
        if _global_registry is None:
            _global_registry = MetricsRegistry("DEBUG")
        return _global_registry


def reset_global_registry() -> None:
    global _global_registry
    with _global_lock:
        _global_registry = None


def resilience_snapshot() -> dict:
    """All resilience counters (zeros included)."""
    g = global_registry()
    return {name: g.metric(name).value for name in RESILIENCE_METRICS}


def resilience_add(name: str, v: int = 1) -> None:
    """Increment one resilience counter in the process-wide registry AND in
    the ambient query's own scoped registry. Concurrent queries made the old
    start/finish DELTA attribution wrong — a peer's retry landing inside
    another query's window leaked across query scopes; routing every
    increment through here pins it to the query whose thread did the work
    (worker threads re-enter their query's collector scope, so the ambient
    collector is the right owner even off the driving thread)."""
    global_registry().metric(name).add(v)
    c = current_collector()
    if c is not None:
        c._resilience_local.metric(name).add(v)


# -- process-wide gauges / counters / histograms ------------------------------
# The live serving-metrics plane (endpoint STATS frames, executor.health
# samples): gauges are last-write-wins instantaneous values (endpoint
# connection count, pipeline queue occupancy), counters are monotonic
# (deadline kills), and histograms are fixed-bucket distributions cheap
# enough to observe on every query completion.

_gauge_lock = threading.Lock()
_gauges: dict[str, float] = {}
_counters: dict[str, int] = {}


def set_gauge(name: str, value) -> None:
    with _gauge_lock:
        _gauges[name] = value


def add_gauge(name: str, delta) -> None:
    with _gauge_lock:
        _gauges[name] = _gauges.get(name, 0) + delta


def gauges_snapshot() -> dict:
    with _gauge_lock:
        return dict(_gauges)


def counter_add(name: str, v: int = 1) -> None:
    with _gauge_lock:
        _counters[name] = _counters.get(name, 0) + v


def counters_snapshot() -> dict:
    with _gauge_lock:
        return dict(_counters)


def reset_observability() -> None:
    """Test hook: clear gauges, counters, histograms and the movement
    ledger."""
    global _histograms
    with _gauge_lock:
        _gauges.clear()
        _counters.clear()
    with _hist_lock:
        _histograms = {}
    from spark_rapids_tpu.runtime import movement
    movement.reset()


# latency-shaped default bounds: 1ms .. 5min, roughly x2.5 per step —
# fine enough for p99 interpolation at interactive scales, coarse enough
# that one histogram is 18 ints
DEFAULT_HISTOGRAM_BOUNDS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0)


class Histogram:
    """Lock-cheap fixed-bucket histogram: observe() is one bisect over a
    static bound tuple plus four guarded int/float updates — cheap enough
    for per-query (not per-batch) call sites. Bucket i counts values
    v <= bounds[i]; the last bucket is the +inf overflow. min/max are
    tracked so percentile() can clamp interpolation to observed reality."""

    __slots__ = ("name", "bounds", "_counts", "_sum", "_count",
                 "_min", "_max", "_lock")

    def __init__(self, name: str, bounds=None):
        self.name = name
        self.bounds = tuple(sorted(bounds)) if bounds \
            else DEFAULT_HISTOGRAM_BOUNDS
        self._counts = [0] * (len(self.bounds) + 1)
        self._sum = 0.0
        self._count = 0
        self._min = None
        self._max = None
        self._lock = threading.Lock()

    def observe(self, v) -> None:
        v = float(v)
        i = bisect.bisect_left(self.bounds, v)
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1
            if self._min is None or v < self._min:
                self._min = v
            if self._max is None or v > self._max:
                self._max = v

    def snapshot(self) -> dict:
        with self._lock:
            return {"bounds": list(self.bounds),
                    "counts": list(self._counts),
                    "sum": self._sum, "count": self._count,
                    "min": self._min, "max": self._max}

    def percentile(self, q: float) -> float | None:
        """Linear-interpolated q-quantile (q in [0,1]) from the bucket
        cumulative counts, clamped to the observed [min, max]; None before
        any observation."""
        with self._lock:
            if not self._count:
                return None
            counts = list(self._counts)
            total, lo, hi = self._count, self._min, self._max
        target = q * total
        cum = 0.0
        for i, c in enumerate(counts):
            if cum + c >= target and c:
                b_lo = self.bounds[i - 1] if i > 0 else 0.0
                b_hi = self.bounds[i] if i < len(self.bounds) else hi
                frac = (target - cum) / c
                v = b_lo + (b_hi - b_lo) * frac
                return min(max(v, lo), hi)
            cum += c
        return hi


_hist_lock = threading.Lock()
_histograms: dict[str, Histogram] = {}


def histogram(name: str, bounds=None) -> Histogram:
    """Fetch-or-create the process-wide histogram `name` (shared across
    sessions, like the resilience registry)."""
    with _hist_lock:
        h = _histograms.get(name)
        if h is None:
            h = _histograms[name] = Histogram(name, bounds)
        return h


def histograms_snapshot() -> dict:
    with _hist_lock:
        items = list(_histograms.items())
    return {name: h.snapshot() for name, h in items}


def histogram_percentiles(name: str, qs=(0.5, 0.95, 0.99)) -> dict | None:
    with _hist_lock:
        h = _histograms.get(name)
    if h is None or not h._count:
        return None
    out = {f"p{int(q * 100)}": round(h.percentile(q), 6) for q in qs}
    out["count"] = h._count
    return out


# -- per-query compile/retrace accounting --------------------------------------
# runtime/fuse.py mirrors every XLA trace (compile) and program replay
# (dispatch) into the ambient query's collector, the same pattern as
# resilience_add: the process-global fuse counters stay authoritative for
# whole-process telemetry, while the per-query deltas establish the
# retrace denominator (ROADMAP item 1's zero-retrace gate reads these from
# last_query_metrics()).

def compile_add(kind: str, v: int = 1) -> None:
    c = current_collector()
    if c is not None:
        nid = current_node()
        with c._compile_lock:
            c._compile_local[kind] = c._compile_local.get(kind, 0) + v
            # per-node mirror: the innermost attribution frame on this thread
            # is the operator whose kernel compiled/dispatched, which makes
            # the fusion gate (dispatches per batch on a chain) measurable
            # per chain instead of per process
            if nid is not None:
                d = c._node_stats.setdefault(nid, {})
                d[kind] = d.get(kind, 0) + v


def stats_add(key: str, v, node: int | None = None) -> None:
    """Accumulate one observed-statistics counter into the ambient query's
    stats ledger, attributed to `node` (default: the innermost node_frame on
    this thread; no frame -> query-level). Always on — a dict update under a
    lock, the same cost class as the memory accounting — so the stats plane
    does not depend on the metrics level."""
    c = current_collector()
    if c is None:
        return
    nid = node if node is not None else current_node()
    with c._compile_lock:
        d = (c._node_stats.setdefault(nid, {}) if nid is not None
             else c._query_stats)
        d[key] = d.get(key, 0) + v


# -- query-scoped collection ---------------------------------------------------
# The SQL-UI analog: every exec node registers its MetricsRegistry with the
# query's collector at construction (TpuExec.__init__), so a finished query
# can render its plan tree annotated per node and attribute events
# (spill/oom/fetch) to plan-node ids. The collector is carried in a
# thread-local; pool-based schedulers re-enter it on worker threads via
# collector_context().

_collector_tls = threading.local()
_query_counter = itertools.count(1)


def current_collector() -> "QueryMetricsCollector | None":
    return getattr(_collector_tls, "collector", None)


def current_query_id() -> str | None:
    c = current_collector()
    return c.query_id if c is not None else None


@contextmanager
def collector_context(collector: "QueryMetricsCollector | None"):
    """Make `collector` the thread's current query scope (None allowed: a
    worker thread spawned outside any query keeps a clean scope)."""
    prev = getattr(_collector_tls, "collector", None)
    _collector_tls.collector = collector
    try:
        yield collector
    finally:
        _collector_tls.collector = prev


class _Frame:
    __slots__ = ("node_id", "child_ns")

    def __init__(self, node_id):
        self.node_id = node_id
        self.child_ns = 0


_frame_tls = threading.local()


def current_node() -> int | None:
    """Plan-node id of the innermost operator computing on this thread (the
    node-attribution stack maintained by node_frame) — events emitted while
    an operator runs land on its plan node."""
    stack = getattr(_frame_tls, "stack", None)
    return stack[-1].node_id if stack else None


@contextmanager
def node_frame(node_id, self_time_metric):
    """One attribution frame: wall time inside the frame, minus time spent in
    nested frames on the same thread, accumulates into `self_time_metric`
    (pass None to attribute events without charging time — e.g. while
    blocking on another thread's work that charges itself)."""
    stack = getattr(_frame_tls, "stack", None)
    if stack is None:
        stack = _frame_tls.stack = []
    f = _Frame(node_id)
    stack.append(f)
    t0 = time.perf_counter_ns()
    try:
        yield
    finally:
        dt = time.perf_counter_ns() - t0
        stack.pop()
        if self_time_metric is not None:
            self_time_metric.add(max(dt - f.child_ns, 0))
        if stack:
            stack[-1].child_ns += dt


class QueryMetricsCollector:
    """Per-query registry of plan-node metric sets (the SQLExecution /
    SQL-UI metrics-aggregation analog). Created by a DataFrame action,
    populated during plan conversion (exec construction) and execution,
    finished when the action returns; session.last_query_metrics() and
    DataFrame.explain(metrics=True) read it afterwards."""

    def __init__(self, description: str = ""):
        self.query_id = f"q{next(_query_counter):04d}-{os.getpid():x}-" \
                        f"{uuid.uuid4().hex[:8]}"
        self.description = description
        # cross-process trace id: defaults to the query id; the serving
        # endpoint/session may override it from the client's SUBMIT frame
        # (runtime/tracing.current_trace_id reads it through the ambient
        # collector so every worker thread inherits it)
        self.trace_id = self.query_id
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._nodes: dict[int, object] = {}   # node_id -> exec node
        self.root = None
        self._t0 = time.perf_counter()
        # query-scoped resilience counters: resilience_add() mirrors every
        # process-wide increment here, keyed by the worker thread's ambient
        # collector — correct under concurrent queries where the old
        # start/finish delta would count a peer's retries as this query's
        self._resilience_local = MetricsRegistry("DEBUG")
        # query-scoped compile/dispatch counters, mirrored by compile_add()
        # from runtime/fuse.py — the retrace denominator (a healthy repeat
        # query shows compiles == 0 here while dispatches == O(batches))
        self._compile_lock = threading.Lock()
        self._compile_local = {"compiles": 0, "dispatches": 0}
        # observed-statistics ledger (runtime/stats.py reads it): per-node
        # counters fed by stats_add/compile_add (output bytes, h2d/d2h
        # transfer bytes, per-node compiles/dispatches, input rows) plus
        # query-level counters for increments with no ambient node frame
        self._node_stats: dict[int, dict] = {}
        self._query_stats: dict = {}
        # per-shuffle reduce-partition byte sizes recorded by the map stage
        # (exchange/mesh), independent of the event log being enabled
        self._shuffle_stats: list[dict] = []
        # per-query mirror of the movement ledger (runtime/movement.py):
        # (edge, link) -> [bytes, payload_bytes, transfers] — the query.end
        # movement section reads this
        self._movement: dict = {}
        # admission footprint info ({estimate, static, history_hit,
        # fingerprint, ...}) set at submit; plan.stats payload set at finish
        self.footprint: dict | None = None
        self.stats: dict | None = None
        # cooperative cancellation (runtime/scheduler.py): the session's
        # action sets the query's CancelToken here so every thread that
        # re-enters this collector's scope can reach it
        self.cancel_token = None
        self.wall_s: float | None = None
        self._resilience: dict | None = None
        # per-query memory summary (peak device bytes + top allocation
        # sites), set by the action's memory epilogue
        # (session._finish_query_memory); None for host-only queries
        self.memory: dict | None = None

    # -- population (plan conversion + execution) -----------------------------
    def register(self, exec_node) -> int:
        with self._lock:
            nid = next(self._ids)
            self._nodes[nid] = exec_node
            return nid

    def set_root(self, root) -> None:
        self.root = root

    def finish(self) -> None:
        if self.wall_s is None:
            self.wall_s = time.perf_counter() - self._t0
            self._resilience = self.query_resilience()

    # -- read-out -------------------------------------------------------------
    def query_resilience(self) -> dict:
        """Resilience counters attributable to THIS query (zeros included).
        Accumulated directly in the query's scoped registry by
        resilience_add() — not a delta of the process-wide registry, which
        concurrent peers mutate inside this query's window."""
        if self._resilience is not None:
            return dict(self._resilience)
        return {name: self._resilience_local.metric(name).value
                for name in RESILIENCE_METRICS}

    def compile_metrics(self) -> dict:
        """XLA compiles (traces) and program dispatches attributable to THIS
        query (runtime/fuse.py mirrors them here via compile_add)."""
        with self._compile_lock:
            return dict(self._compile_local)

    def node_stats(self) -> dict:
        """{node_id: {stat: value}} snapshot of the observed-stats ledger."""
        with self._compile_lock:
            return {nid: dict(d) for nid, d in self._node_stats.items()}

    def query_stats(self) -> dict:
        with self._compile_lock:
            return dict(self._query_stats)

    def record_shuffle_sizes(self, node_id, shuffle_id, sizes) -> None:
        """Per-reduce-partition byte sizes observed at map-stage completion
        (the MapOutputTracker read-out); one entry per completed map stage."""
        with self._compile_lock:
            self._shuffle_stats.append({
                "node": node_id, "shuffle": int(shuffle_id),
                "partition_sizes": [int(s) for s in sizes]})

    def shuffle_stats(self) -> list:
        with self._compile_lock:
            return [dict(e) for e in self._shuffle_stats]

    def movement_stats(self) -> dict:
        """{(edge, link): {bytes, payload_bytes, transfers}} snapshot of
        this query's movement mirror (runtime/movement.py)."""
        with self._compile_lock:
            return {k: {"bytes": v[0], "payload_bytes": v[1],
                        "transfers": v[2]}
                    for k, v in self._movement.items()}

    def _walk(self, node, parent_id, depth, visit):
        """Duck-typed hybrid-tree walk (no imports of exec/plan here): device
        execs carry _node_id/metrics, HostBridgeNode carries tpu_exec, host
        PlanNodes carry children; DeviceBridgeExec's host subtree is walked
        as unregistered host nodes."""
        nid = getattr(node, "_node_id", None)
        if nid is not None or hasattr(node, "metrics"):
            visit(node, nid, parent_id, depth)
            parent_id = nid
        elif hasattr(node, "tpu_exec"):          # HostBridgeNode
            visit(node, None, parent_id, depth)
            self._walk(node.tpu_exec, parent_id, depth + 1, visit)
            return
        else:                                     # host PlanNode
            visit(node, None, parent_id, depth)
        for c in getattr(node, "children", []) or []:
            self._walk(c, parent_id, depth + 1, visit)
        host_node = getattr(node, "host_node", None)   # DeviceBridgeExec
        if host_node is not None:
            self._walk(host_node, parent_id, depth + 1, visit)

    def node_summaries(self) -> list:
        """[{id, name, args, parent, depth, metrics}] in plan-tree preorder
        (registered nodes that never made the executed tree are appended with
        parent None so nothing silently disappears)."""
        out, seen = [], set()

        def visit(node, nid, parent_id, depth):
            entry = {
                "id": nid,
                "name": type(node).__name__,
                "args": (node.args_string()
                         if hasattr(node, "args_string") else ""),
                "parent": parent_id,
                "depth": depth,
                "metrics": (node.metrics.snapshot()
                            if hasattr(node, "metrics") else {}),
            }
            out.append(entry)
            if nid is not None:
                seen.add(nid)

        if self.root is not None:
            self._walk(self.root, None, 0, visit)
        with self._lock:
            stragglers = [(nid, n) for nid, n in self._nodes.items()
                          if nid not in seen]
        for nid, n in sorted(stragglers):
            visit(n, nid, None, 0)
        return out

    def node_metrics(self) -> dict:
        """{node_id: metrics snapshot} for every registered node."""
        with self._lock:
            items = list(self._nodes.items())
        return {nid: n.metrics.snapshot() for nid, n in items
                if hasattr(n, "metrics")}

    def annotated_plan(self) -> str:
        """The explain tree annotated per node with its metric snapshot —
        the SQL-UI plan-with-metrics analog."""
        cm = self.compile_metrics()
        lines = [f"Query {self.query_id}"
                 + (f" [{self.description}]" if self.description else "")
                 + (f" wall={self.wall_s:.4f}s" if self.wall_s is not None
                    else " (running)")
                 + f" compiles={cm['compiles']} dispatches={cm['dispatches']}"]

        def fmt(mname, v):
            if mname.endswith(("Time", "time")) or mname == SELF_TIME:
                return f"{mname}={v / 1e6:.1f}ms"
            return f"{mname}={v}"

        def visit(node, nid, parent_id, depth):
            head = "  " * depth + "*" + type(node).__name__
            args = (node.args_string()
                    if hasattr(node, "args_string") else "")
            if args:
                head += " " + args
            if nid is not None:
                snap = node.metrics.snapshot()
                # zero metrics are noise — except the row count, which is
                # load-bearing even (especially) when it is zero
                ann = ", ".join(fmt(k, v) for k, v in sorted(snap.items())
                                if v or k == NUM_OUTPUT_ROWS)
                head += f"  [id={nid}" + (f", {ann}" if ann else "") + "]"
            lines.append(head)

        if self.root is not None:
            self._walk(self.root, None, 0, visit)
        else:
            lines.append("  (no executed plan recorded)")
        return "\n".join(lines)
