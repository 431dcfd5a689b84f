"""Arrow-over-TCP query-serving endpoint — the network front door.

The reference plugin is reachable because Spark itself is: external clients
hand SQL to a Thrift/Connect server and stream columnar results back. This
engine's multi-tenant core stopped at the Python API — PR 6 built admission
control, deadlines, cooperative cancellation and overload shedding
(runtime/scheduler.py) and made ``QueryRejectedError`` pickle-round-trippable
*for exactly this boundary*. This module is the remaining half of ROADMAP
item 2: a driver-side TCP server that accepts SQL submissions, routes them
through the scheduler, and streams Arrow-IPC result batches back, speaking
the shuffle transport's length-prefixed frame protocol
(shuffle/transport.py ``send_frame``/``recv_frame``) with CRC32C-stamped
payloads (runtime/checksum.py).

The robustness core is the failure surface, not the happy path:

- **Disconnect-driven cancellation.** Every active connection is watched
  for half-close/RST/idle-timeout while its query runs; a lost client fires
  the query's ``CancelToken`` (reason ``client_disconnect``) so the PR-6
  drain path frees buffers, semaphore permits and shuffle map outputs —
  a killed client costs the engine nothing beyond the work already done.
- **Backpressure.** Result batches flow through a byte-bounded
  :class:`_ResultStream` whose budget is capped by the shared host-prefetch
  budget (``endpoint.maxStreamBufferBytes`` ∧ free host spill headroom): a
  slow client stalls its own producer, never the heap or its neighbours.
- **Graceful drain.** :meth:`QueryEndpoint.shutdown` (the SIGTERM path via
  :meth:`install_signal_handlers`) stops accepting, sheds new submissions
  with retryable backoff-hinted ``QueryRejectedError``, gives in-flight
  queries ``endpoint.drain.graceSeconds`` to finish, then flips their
  tokens (reason ``drain``) — the hard-kill escalation — before closing.
- **Typed errors over the wire.** Server-side failures are pickled and
  re-raised typed at the client: ``QueryRejectedError`` (with its
  ``backoff_hint_s``), ``QueryCancelledError``/``QueryDeadlineError``,
  ``DeviceOomError``, ``TransportError``, ``SpillCorruptionError`` — so
  :meth:`EndpointClient.submit_with_retry` can honor the scheduler's own
  backoff hints instead of guessing.
- **Fleet membership + failover.** With ``fleet.dir`` set, the endpoint
  registers a lease-stamped membership record (runtime/fleet.py) naming its
  address and shared-store directories; its heartbeat doubles as the
  standby sweeper that adopts dead peers' leases. A fleet-registered
  replica converts a ``request_timeout`` kill into a retryable
  ``QueryRejectedError`` (reason ``replica_timeout``) — on a fleet, a
  wedged replica's queries belong on a surviving peer, so
  :class:`EndpointClient` (which accepts a comma-separated replica list)
  rotates instead of failing. Without a fleet the timeout stays a
  non-retryable typed cancellation, exactly as before.
- **Result cache.** With ``endpoint.resultCache.enabled``, fully-streamed
  results are recorded (runtime/result_cache.py) keyed by catalog epoch +
  plan signature + SQL digest; an identical re-submission replays the
  recorded CRC-stamped frames bit-identically WITHOUT touching scheduler
  admission — the hot set survives overload.
- **Chaos surface.** Fault sites ``endpoint.accept`` / ``endpoint.recv`` /
  ``endpoint.send`` (any armed kind fires, runtime/faults.py) and the
  ``endpoint.corrupt`` payload site (byte flip AFTER the CRC is stamped,
  so the client's verification must catch it) drive tools/endpoint_chaos.py
  and tests/test_endpoint.py.

Every transition is visible in the event log: ``endpoint.start`` /
``endpoint.stop``, ``client.connected`` / ``client.disconnected``,
``server.drain`` — alongside the scheduler's query lifecycle events.

Trust model: the error channel carries pickled exceptions, so the endpoint
binds loopback by default (``endpoint.host``) and belongs behind the same
trust boundary as the shuffle data plane — it is the driver's front door,
not an internet-facing gateway.
"""

from __future__ import annotations

import collections
import copy
import json
import pickle
import random
import select
import socket
import socketserver
import struct
import threading
import time
import uuid

import pyarrow as pa

from spark_rapids_tpu import config as CFG
from spark_rapids_tpu.runtime import blackbox as BB
from spark_rapids_tpu.runtime import faults as F
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import scheduler as SCHED
from spark_rapids_tpu.runtime import tracing
from spark_rapids_tpu.runtime.checksum import block_checksum
from spark_rapids_tpu.shuffle.transport import (TransportError,
                                                configure_socket,
                                                max_frame_bytes as
                                                _default_max_frame,
                                                recv_frame, send_frame)

# endpoint message ids — disjoint from the shuffle control plane's 1..5 so a
# client pointed at the wrong port fails loudly instead of half-parsing
MSG_SUBMIT = 16         # client→server: JSON request (sql + per-query knobs)
MSG_RESULT_BATCH = 17   # server→client: <Q crc> + Arrow-IPC stream payload
MSG_RESULT_END = 18     # server→client: JSON summary (query id, rows, ...)
MSG_QUERY_ERROR = 19    # server→client: pickled typed exception
MSG_PING = 20           # client→server: liveness probe
MSG_PONG = 21           # server→client: liveness reply
MSG_STATS = 22          # client→server: live serving-metrics snapshot probe
MSG_STATS_RESP = 23     # server→client: Prometheus-style text exposition
MSG_APPEND = 24         # client→server: <I hlen> + JSON header (source,
#                         batch, crc) + Arrow-IPC stream payload — one
#                         durable streaming-source batch (streaming/)
MSG_APPEND_ACK = 25     # server→client: JSON ack (duplicate flag, rows,
#                         catalog epoch, replica) — sent only after the
#                         batch is durable on disk

_CRC = struct.Struct("<Q")
_HDR = struct.Struct("<I")

# request knobs a client may set per submission — mapped onto the session
# conf keys the scheduler reads at submit time; everything else in the
# request JSON is rejected (the wire must not become a generic conf setter).
# 'trace' is NOT a conf key: it is the client's distributed trace id, handed
# to the query's collector so server-side spans merge with the client's own.
# 'journey'/'attempt' are likewise pure observability: the client-stamped
# journey id survives submit_with_retry's replica rotation, so each
# replica's query.journey record joins into one cross-replica timeline
_REQUEST_KNOBS = {
    "priority": (CFG.SCHEDULER_PRIORITY.key, int),
    "deadline_s": (CFG.SCHEDULER_QUERY_DEADLINE.key, float),
    "queue_timeout_s": (CFG.SCHEDULER_QUEUE_TIMEOUT.key, float),
}

_META_FIELDS = {"sql", "description", "trace", "journey", "attempt"}


def _table_to_ipc(tbl: pa.Table) -> bytes:
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, tbl.schema) as w:
        w.write_table(tbl)
    return sink.getvalue().to_pybytes()


def _ipc_to_table(data: bytes) -> pa.Table:
    return pa.ipc.open_stream(pa.BufferReader(data)).read_all()


def _pickle_error(exc: BaseException) -> bytes:
    try:
        return pickle.dumps(exc)
    except Exception:   # noqa: BLE001 — an unpicklable error still travels
        return pickle.dumps(RuntimeError(
            f"{type(exc).__name__}: {exc!r}"[:500]))


# ---------------------------------------------------------------------------
# live serving metrics (STATS frames)
# ---------------------------------------------------------------------------

def _hist_family(name: str):
    """Map a runtime/metrics histogram name to its Prometheus family +
    label string."""
    if name.startswith("query.latency.priority"):
        p = name[len("query.latency.priority"):]
        return "srt_query_latency_seconds", f'priority="{p}"'
    if name == "admission.wait":
        return "srt_admission_wait_seconds", ""
    # movement plane: per-transfer size / latency distributions
    if name == "movement.transfer.bytes":
        return "srt_movement_transfer_bytes", ""
    if name == "movement.transfer.latency":
        return "srt_movement_transfer_latency_seconds", ""
    safe = "".join(c if c.isalnum() else "_" for c in name)
    return f"srt_{safe}", ""


def render_stats(include_histograms: bool = True, endpoint=None) -> str:
    """Prometheus-style text snapshot of the live serving metrics: query
    lifecycle counters (admitted / shed / cancelled / deadline), the whole
    resilience registry, memory + queue gauges (HBM in use, spill tiers,
    admission queue depth, active queries, pipeline queue occupancy,
    endpoint connections) and the fixed-bucket latency histograms. An
    `endpoint` adds its fleet-membership and result-cache families."""
    from spark_rapids_tpu.runtime import eventlog as EL
    lines = []

    def fam(name, mtype):
        lines.append(f"# TYPE {name} {mtype}")

    sched = SCHED.QueryScheduler.get().stats()
    for key, metric in (("admitted", "srt_queries_admitted_total"),
                        ("shed", "srt_queries_shed_total"),
                        ("demotions", "srt_query_demotions_total")):
        fam(metric, "counter")
        lines.append(f"{metric} {sched[key]}")
    counters = M.counters_snapshot()
    fam("srt_queries_deadline_total", "counter")
    lines.append("srt_queries_deadline_total "
                 f"{counters.get('queries.deadline', 0)}")
    fam("srt_resilience_total", "counter")
    for k, v in sorted(M.resilience_snapshot().items()):
        lines.append(f'srt_resilience_total{{counter="{k}"}} {v}')

    fam("srt_scheduler_running", "gauge")
    lines.append(f"srt_scheduler_running {sched['running']}")
    fam("srt_scheduler_queue_depth", "gauge")
    lines.append(f"srt_scheduler_queue_depth {sched['queued']}")
    health = EL.health_payload()
    if health.get("device_initialized"):
        fam("srt_hbm_bytes", "gauge")
        for kind in ("budget", "used", "free"):
            lines.append(f'srt_hbm_bytes{{kind="{kind}"}} '
                         f'{health[f"hbm_{kind}_bytes"]}')
        fam("srt_spill_tier_bytes", "gauge")
        for tier, d in sorted(health["tiers"].items()):
            lines.append(f'srt_spill_tier_bytes{{tier="{tier}"}} '
                         f'{d["bytes"]}')
        # memory observability plane: process device high-water mark + live
        # device bytes per allocation site (who holds the HBM right now)
        fam("srt_hbm_watermark_bytes", "gauge")
        lines.append("srt_hbm_watermark_bytes "
                     f"{health.get('hbm_watermark_bytes', 0)}")
        mem_sites = health.get("memory_sites") or {}
        if mem_sites:
            fam("srt_memory_site_bytes", "gauge")
            for site, v in sorted(mem_sites.items()):
                lines.append(f'srt_memory_site_bytes{{site="{site}"}} {v}')
    fuse = health.get("fuse", {})
    fam("srt_fuse_total", "counter")
    for k in ("traces", "dispatches"):
        lines.append(f'srt_fuse_total{{kind="{k}"}} {fuse.get(k, 0)}')
    # stats plane: plan-shape history occupancy + submit-time hit counter
    gauges = M.gauges_snapshot()
    fam("srt_history_shapes", "gauge")
    lines.append(f"srt_history_shapes {gauges.get('history.shapes', 0)}")
    fam("srt_history_hit_total", "counter")
    lines.append(f"srt_history_hit_total {counters.get('history.hit', 0)}")
    fam("srt_gauge", "gauge")
    for k, v in sorted(gauges.items()):
        if k == "history.shapes":   # already exposed as its own family
            continue
        lines.append(f'srt_gauge{{name="{k}"}} {v}')
    # movement plane: cumulative bytes per (edge, link) from the ledger
    from spark_rapids_tpu.runtime import movement as MV
    flows = MV.edge_link_totals()
    if flows:
        fam("srt_movement_bytes", "gauge")
        for (edge, link), v in sorted(flows.items()):
            lines.append(f'srt_movement_bytes{{edge="{edge}",link="{link}"}} '
                         f'{v["bytes"]}')

    if endpoint is not None and endpoint.fleet is not None:
        fstats = endpoint.fleet.stats()
        fam("srt_fleet_live_members", "gauge")
        lines.append(f"srt_fleet_live_members {fstats['live_members']}")
        fam("srt_fleet_total", "counter")
        for k in ("heartbeats", "sweeps", "adoptions", "reclaimed_intents"):
            lines.append(f'srt_fleet_total{{event="{k}"}} {fstats[k]}')
    if endpoint is not None and endpoint.result_cache is not None:
        rstats = endpoint.result_cache.stats()
        fam("srt_result_cache_total", "counter")
        for k in ("hits", "misses", "inserts", "evictions", "stale_drops"):
            lines.append(f'srt_result_cache_total{{event="{k}"}} {rstats[k]}')
        fam("srt_result_cache_bytes", "gauge")
        lines.append(f"srt_result_cache_bytes {rstats['bytes']}")
        fam("srt_result_cache_entries", "gauge")
        lines.append(f"srt_result_cache_entries {rstats['entries']}")
    if endpoint is not None and endpoint.slo.target_s > 0:
        sstats = endpoint.slo.snapshot()
        fam("srt_slo_latency_target_seconds", "gauge")
        lines.append(f"srt_slo_latency_target_seconds {sstats['target_s']}")
        fam("srt_slo_total", "counter")
        for k in ("served", "breaches", "errors"):
            lines.append(f'srt_slo_total{{event="{k}"}} {sstats[k]}')

    if include_histograms:
        for name, snap in sorted(M.histograms_snapshot().items()):
            family, label = _hist_family(name)
            fam(family, "histogram")
            cum = 0
            for bound, count in zip(snap["bounds"], snap["counts"]):
                cum += count
                sep = "," if label else ""
                lines.append(f'{family}_bucket{{{label}{sep}le="{bound}"}} '
                             f"{cum}")
            sep = "," if label else ""
            lines.append(f'{family}_bucket{{{label}{sep}le="+Inf"}} '
                         f'{snap["count"]}')
            lab = f"{{{label}}}" if label else ""
            lines.append(f"{family}_sum{lab} {round(snap['sum'], 6)}")
            lines.append(f"{family}_count{lab} {snap['count']}")
    return "\n".join(lines) + "\n"


def parse_stats_text(text: str) -> dict:
    """Parse a render_stats() exposition back into
    ``{"counters": {series: value}, "gauges": {series: value}}`` keyed by
    the full series string (``name{labels}``). Histogram families are
    skipped — bucket counts do not sum meaningfully across label sets.
    The inverse half of the fleet-stats rollup: aggregate counters are the
    per-series SUM across replicas (gauges do not sum; they stay
    per-replica)."""
    out = {"counters": {}, "gauges": {}}
    types: dict[str, str] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) >= 4:
                types[parts[2]] = parts[3]
            continue
        if line.startswith("#"):
            continue
        series, _, value = line.rpartition(" ")
        if not series:
            continue
        name = series.split("{", 1)[0]
        kind = types.get(name)
        if kind not in ("counter", "gauge"):
            continue
        try:
            v = float(value)
        except ValueError:
            continue
        out["counters" if kind == "counter" else "gauges"][series] = v
    return out


def merge_fleet_stats(per_replica: dict) -> dict:
    """Merge ``{address: stats_text | Exception}`` into the fleet rollup:
    per-replica parsed counters/gauges (or the dial error) plus the
    fleet-aggregate counter families, where every aggregate counter equals
    the sum of the per-replica values — the invariant the ci fleet gate
    asserts."""
    replicas = {}
    aggregate: dict[str, float] = {}
    live = 0
    for addr, text in per_replica.items():
        if isinstance(text, BaseException):
            replicas[addr] = {"ok": False,
                              "error": f"{type(text).__name__}: {text}"}
            continue
        parsed = parse_stats_text(text)
        replicas[addr] = {"ok": True, "raw": text, **parsed}
        live += 1
        for series, v in parsed["counters"].items():
            aggregate[series] = aggregate.get(series, 0.0) + v
    return {"replicas": replicas, "aggregate": {"counters": aggregate},
            "live": live, "total": len(per_replica)}


def render_fleet_stats(fs: dict) -> str:
    """Human/CI-facing text of a merge_fleet_stats() rollup: one raw
    per-replica section per address, then the aggregate counter families
    (tpu_client.py fleet-stats prints this)."""
    lines = []
    for addr, rep in fs["replicas"].items():
        lines.append(f"== replica {addr} ==")
        if not rep["ok"]:
            lines.append(f"UNREACHABLE {rep['error']}")
        else:
            lines.append(rep["raw"].rstrip("\n"))
        lines.append("")
    lines.append(f"== fleet aggregate ({fs['live']}/{fs['total']} "
                 f"replicas) ==")
    for series, v in sorted(fs["aggregate"]["counters"].items()):
        out = int(v) if float(v).is_integer() else v
        lines.append(f"{series} {out}")
    return "\n".join(lines) + "\n"


class _SloTracker:
    """Per-replica serving-latency/availability accounting against
    ``endpoint.slo.latencyTargetSeconds``. A served/cached submission over
    the target is a breach; a failed submission (error/timeout/disconnect)
    counts against availability. Inert (every observe a no-op) when the
    target is <= 0."""

    def __init__(self, target_s: float):
        self.target_s = float(target_s)
        self._lock = threading.Lock()
        self.served = 0
        self.breaches = 0
        self.errors = 0

    def observe(self, wall_s: float | None, ok: bool) -> bool:
        """Record one finished submission; True when it breached the
        latency target (the caller emits the slo.breach event)."""
        if self.target_s <= 0:
            return False
        with self._lock:
            if not ok:
                self.errors += 1
                return False
            self.served += 1
            if wall_s is not None and wall_s > self.target_s:
                self.breaches += 1
                return True
            return False

    def snapshot(self) -> dict:
        with self._lock:
            finished = self.served + self.errors
            return {
                "target_s": self.target_s,
                "served": self.served,
                "breaches": self.breaches,
                "errors": self.errors,
                "availability": round(self.served / finished, 6)
                if finished else 1.0,
            }


def _unpickle_error(payload: bytes) -> BaseException:
    try:
        exc = pickle.loads(payload)
    except Exception as e:   # noqa: BLE001
        return TransportError(f"undecodable server error frame: {e!r}")
    if isinstance(exc, BaseException):
        return exc
    return TransportError(f"server error frame was not an exception: {exc!r}")


class _ResultStream:
    """Byte-bounded handoff between a query's executor thread and its client
    connection — the endpoint's backpressure edge. Same progress guarantee
    as the pipeline queues: one item is always accepted when empty, so a
    single result batch larger than the budget cannot deadlock the query.
    The producer's full-wait runs :func:`scheduler.check_cancel`, so a
    cancelled query (disconnect, drain, deadline) unblocks immediately."""

    def __init__(self, max_bytes: int):
        self._cond = threading.Condition()
        self._items: collections.deque = collections.deque()
        self._bytes = 0
        self.max_bytes = max(1, int(max_bytes))
        self._done = False
        self._summary = None
        self._error: BaseException | None = None
        self._closed = False

    def put(self, payload: bytes) -> bool:
        """Producer side; blocks while over budget. False = consumer gone
        (connection closed) — the producer must stop, not retry."""
        with self._cond:
            while (not self._closed and self._items
                   and self._bytes + len(payload) > self.max_bytes):
                SCHED.check_cancel()
                self._cond.wait(0.05)
            if self._closed:
                return False
            self._items.append(payload)
            self._bytes += len(payload)
            self._cond.notify_all()
            return True

    def finish(self, summary: dict) -> None:
        with self._cond:
            self._summary = summary
            self._done = True
            self._cond.notify_all()

    def fail(self, exc: BaseException) -> None:
        with self._cond:
            self._error = exc
            self._done = True
            self._cond.notify_all()

    def get(self, timeout: float):
        """Consumer side: ("batch", bytes) | ("error", exc) |
        ("end", summary) | None on timeout. Queued batches drain before a
        terminal item is surfaced (results already produced still ship)."""
        with self._cond:
            if not self._items and not self._done:
                self._cond.wait(timeout)
            if self._items:
                p = self._items.popleft()
                self._bytes -= len(p)
                self._cond.notify_all()
                return ("batch", p)
            if self._done:
                if self._error is not None:
                    return ("error", self._error)
                return ("end", self._summary)
            return None

    def close(self) -> None:
        """Consumer-side cancel: unblocks and stops the producer."""
        with self._cond:
            self._closed = True
            self._items.clear()
            self._bytes = 0
            self._cond.notify_all()


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

class _Handler(socketserver.BaseRequestHandler):
    def handle(self):
        endpoint: QueryEndpoint = self.server.owner   # type: ignore
        endpoint._handle_connection(self.request, self.client_address)


class QueryEndpoint:
    """The serving endpoint bound to one :class:`TpuSession` (whose temp
    views are the queryable catalog). Listening starts at construction;
    ``with QueryEndpoint(session) as ep: ...`` drains on exit."""

    def __init__(self, session, host: str | None = None,
                 port: int | None = None):
        from spark_rapids_tpu.runtime import eventlog as EL
        from spark_rapids_tpu.shuffle import transport as TR
        self.session = session
        conf = session.conf
        self.idle_timeout = conf.get(CFG.ENDPOINT_IDLE_TIMEOUT)
        self.request_timeout = conf.get(CFG.ENDPOINT_REQUEST_TIMEOUT)
        self.drain_grace = conf.get(CFG.ENDPOINT_DRAIN_GRACE)
        self.stream_buffer = conf.get(CFG.ENDPOINT_STREAM_BUFFER)
        self.stats_enabled = conf.get(CFG.ENDPOINT_STATS_ENABLED)
        self.stats_histograms = conf.get(CFG.ENDPOINT_STATS_HISTOGRAMS)
        self.slo = _SloTracker(conf.get(CFG.ENDPOINT_SLO_LATENCY_TARGET))
        TR.set_max_frame_bytes(conf.get(CFG.TRANSPORT_MAX_FRAME_BYTES))
        self._draining = False
        self._drain_deadline = None
        self._closing = False
        self._lock = threading.Lock()
        self._conns: set = set()
        self._active: dict = {}        # id(stream) -> {df, stream, query}
        self._next_worker = 0
        self.result_cache = None
        if conf.get(CFG.ENDPOINT_RESULT_CACHE_ENABLED):
            from spark_rapids_tpu.runtime.result_cache import ResultCache
            self.result_cache = ResultCache(
                conf.get(CFG.ENDPOINT_RESULT_CACHE_MAX_BYTES),
                conf.get(CFG.ENDPOINT_RESULT_CACHE_MAX_ENTRIES))

        class _Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True
        self._srv = _Server((host or conf.get(CFG.ENDPOINT_HOST),
                             port if port is not None
                             else conf.get(CFG.ENDPOINT_PORT)), _Handler)
        self._srv.owner = self
        self.host, self.port = self._srv.server_address[:2]
        self._thread = threading.Thread(target=self._srv.serve_forever,
                                        daemon=True, name="srt-endpoint")
        self._thread.start()
        # fleet membership: register this replica's lease once the port is
        # bound, recording the shared-store dirs a survivor must reclaim
        self.fleet = None
        fleet_dir = conf.get(CFG.FLEET_DIR)
        if fleet_dir:
            from spark_rapids_tpu.runtime.fleet import FleetDirectory
            stores = [conf.get(CFG.STAGE_CACHE_DIR)
                      if conf.stage_cache_enabled else None,
                      conf.get(CFG.STATS_HISTORY_DIR)]
            self.fleet = FleetDirectory(
                fleet_dir,
                lease_timeout_s=conf.get(CFG.FLEET_LEASE_TIMEOUT),
                heartbeat_interval_s=conf.get(CFG.FLEET_HEARTBEAT_INTERVAL))
            # the membership record names this replica's blackbox dump path
            # and lease timeout, so a survivor's fleet.adopt can point at
            # the victim's post-mortem and an observer (profiler.py fleet)
            # can judge liveness without knowing the fleet's config
            extra = {"lease_timeout_s": self.fleet.lease_timeout_s}
            if BB.dump_path():
                extra["blackbox"] = BB.dump_path()
            self.fleet.register(self.host, self.port, stores=stores,
                                extra=extra)
            # every heartbeat embeds this endpoint's health in the lease
            # record AND runs the stuck-query watchdog — the heartbeat
            # thread outlives a wedged connection thread, so deadline
            # enforcement and the blackbox dump survive a hung send
            self.fleet.set_health_provider(self._fleet_health)
        BB.set_inflight_provider(self._inflight_snapshot)
        EL.emit("endpoint.start", query=None, host=self.host, port=self.port)

    # -- connection lifecycle ------------------------------------------------
    def _handle_connection(self, sock, peer):
        from spark_rapids_tpu.runtime import eventlog as EL
        try:
            # chaos: an armed endpoint.accept fault kills the connection at
            # admission — the client observes connect-then-close and retries
            F.maybe_inject_any("endpoint.accept")
        except BaseException:   # noqa: BLE001 — any fault kind drops the conn
            return
        configure_socket(
            sock, timeout_s=self.idle_timeout if self.idle_timeout > 0
            else None)
        with self._lock:
            if self._closing:
                return
            self._conns.add(sock)
            M.set_gauge("endpoint.connections", len(self._conns))
        EL.emit("client.connected", query=None, peer=f"{peer[0]}:{peer[1]}")
        try:
            while not self._closing:
                try:
                    F.maybe_inject_any("endpoint.recv")
                    msg, payload = recv_frame(sock)
                except (TransportError, OSError, RuntimeError):
                    return   # idle timeout, client close, or any fault kind
                if msg == MSG_PING:
                    send_frame(sock, MSG_PONG, b"")
                    continue
                if msg == MSG_STATS:
                    if not self.stats_enabled:
                        self._send_error(sock, RuntimeError(
                            "endpoint.stats.enabled=false on this endpoint"))
                        return
                    send_frame(sock, MSG_STATS_RESP, render_stats(
                        self.stats_histograms, endpoint=self).encode("utf-8"))
                    continue
                if msg == MSG_APPEND:
                    if not self._serve_append(sock, payload):
                        return
                    continue
                if msg != MSG_SUBMIT:
                    self._send_error(sock, TransportError(
                        f"unexpected message {msg} (want SUBMIT)"))
                    return
                if not self._serve_query(sock, payload):
                    return
        except (OSError, RuntimeError):
            return   # connection-level failure: the conn dies, not the server
        finally:
            with self._lock:
                self._conns.discard(sock)
                M.set_gauge("endpoint.connections", len(self._conns))

    def _send_error(self, sock, exc) -> bool:
        try:
            send_frame(sock, MSG_QUERY_ERROR, _pickle_error(exc))
            return True
        except OSError:
            return False

    def _shed_draining(self, sock) -> bool:
        remaining = 0.0
        if self._drain_deadline is not None:
            remaining = max(0.0, self._drain_deadline - time.monotonic())
        hint = round(remaining + 1.0, 3)
        return self._send_error(sock, SCHED.QueryRejectedError(
            f"endpoint draining (shutdown in progress); retry another "
            f"replica after ~{hint}s", backoff_hint_s=hint,
            reason="draining"))

    def _serve_append(self, sock, payload) -> bool:
        """One streaming APPEND: CRC-verify, persist durably, bump the
        catalog epoch (local + fleet-shared), THEN ack — the ack is the
        durability receipt, so a client that saw it can stop retrying and
        a client that didn't can retry blindly (idempotent by (source,
        batch_id)). Returns False when the connection is dead."""
        try:
            (hlen,) = _HDR.unpack_from(payload, 0)
            hdr = json.loads(payload[_HDR.size:_HDR.size + hlen]
                             .decode("utf-8"))
            source, batch = hdr["source"], hdr["batch"]
            crc = int(hdr["crc"])
            body = payload[_HDR.size + hlen:]
        except BaseException as e:   # noqa: BLE001 — parse errors travel
            return self._send_error(sock, e)
        if self._draining:
            return self._shed_draining(sock)
        try:
            # on the SERVER session, never a request copy: the epoch bump
            # must land on the session the result-cache key reads
            ack = self.session.streaming_append(source, batch,
                                                ipc_body=body, crc=crc)
        except BaseException as e:   # noqa: BLE001 — typed errors travel
            return self._send_error(sock, e)
        ack["replica"] = self.replica_name
        try:
            send_frame(sock, MSG_APPEND_ACK,
                       json.dumps(ack).encode("utf-8"))
            return True
        except OSError:
            # the batch IS durable; the client that missed this ack will
            # retry into the duplicate path and get its receipt there
            return False

    def _request_session(self, req: dict):
        """Per-request session view: shares the server session's temp views
        and process switches, but carries its own conf with the request's
        scheduler knobs — concurrent requests must not mutate shared conf."""
        overrides = {}
        for field, (key, conv) in _REQUEST_KNOBS.items():
            if req.get(field) is not None:
                overrides[key] = conv(req[field])
        sess = copy.copy(self.session)
        if overrides:
            sess.conf = self.session.conf.copy_with(**overrides)
        return sess

    # -- one submission ------------------------------------------------------
    def _serve_query(self, sock, payload) -> bool:
        """Run one submission and stream its results; returns False when the
        connection is dead and the handler loop should exit."""
        # SUBMIT frame read to the END frame written: the request's whole
        # stay on this connection thread, and the parent of the worker
        # thread's ``query`` span
        with tracing.span("endpoint.request") as req_span:
            return self._serve_query_in(sock, payload, req_span)

    def _serve_query_in(self, sock, payload, req_span) -> bool:
        try:
            req = json.loads(payload.decode("utf-8"))
            sql = req["sql"]
            unknown = set(req) - set(_REQUEST_KNOBS) - _META_FIELDS
            if unknown:
                raise ValueError(f"unknown request fields {sorted(unknown)}")
            # the journey context exists from the first parsed byte, so
            # even a shed or plan-error submission leaves its timeline
            # record; an unstamped (legacy) client gets a server-minted id
            jctx = {"journey": str(req.get("journey") or
                                   "j-" + uuid.uuid4().hex[:12]),
                    "attempt": max(1, int(req.get("attempt") or 1)),
                    "t0": time.monotonic(), "done": False}
        except BaseException as e:   # noqa: BLE001 — parse errors travel
            return self._send_error(sock, e)
        if self._draining:
            self._journey_finish(jctx, "shed", reason="draining")
            return self._shed_draining(sock)
        try:
            sess = self._request_session(req)
            df = sess.sql(sql)
        except BaseException as e:   # noqa: BLE001 — plan errors travel
            self._journey_finish(jctx, "error", error=type(e).__name__)
            return self._send_error(sock, e)

        # result cache: a hit replays the recorded frames bit-identically
        # WITHOUT entering the scheduler — admission-exempt by design
        record = None
        if self.result_cache is not None:
            ckey = self._result_cache_key(sql, df)
            if ckey is not None:
                hit = self.result_cache.get(ckey)
                if hit is not None:
                    return self._stream_cached(sock, hit, jctx)
                record = {"key": ckey, "frames": [], "bytes": 0,
                          "over": False}

        from spark_rapids_tpu.runtime.memory import host_prefetch_budget
        stream = _ResultStream(host_prefetch_budget(self.stream_buffer))
        entry = {"df": df, "stream": stream, "sql": sql[:500],
                 "description": req.get("description", ""),
                 "jny": jctx, "t0": jctx["t0"], "timed_out": False}
        key = id(stream)
        with self._lock:
            raced_drain = self._draining   # raced shutdown(): shed, don't run
            if not raced_drain:
                self._active[key] = entry
                self._next_worker += 1
                wname = f"srt-endpoint-w{self._next_worker}"
        if raced_drain:
            self._journey_finish(jctx, "shed", reason="draining")
            return self._shed_draining(sock)
        worker = threading.Thread(target=self._run_query,
                                  args=(df, stream, req.get("trace"), record,
                                        req_span),
                                  daemon=True, name=wname)
        worker.start()
        try:
            return self._pump(sock, entry)
        finally:
            # leak guard on EVERY exit path (including a pump bug or an
            # unexpected fault class): the stream must be closed and a
            # still-running worker cancelled, or it would block forever on a
            # full stream nobody drains
            stream.close()
            if worker.is_alive():
                self._cancel_query(df, "connection_closed", wait_s=1.0)
            worker.join(timeout=60)
            with self._lock:
                self._active.pop(key, None)

    def _run_query(self, df, stream: _ResultStream, trace: str | None = None,
                   record: dict | None = None, req_span=tracing.NO_SPAN):
        """Worker thread: execute the action, pushing each result batch into
        the stream as a CRC-stamped Arrow-IPC payload. Partitions run in
        order on this one thread (batch order must be deterministic for the
        bit-identity contract); the pipelined executor still overlaps
        decode/compute/exchange inside each partition, and the stream's
        byte budget overlaps compute with the network send. A client-supplied
        `trace` id is handed to the query's collector so server-side spans
        land in the client's distributed trace. `record` collects the clean
        wire frames for the result cache (admitted only on success).
        `req_span` is the connection thread's ``endpoint.request`` span: the
        query's spans on this thread become its children, and it is told
        what the reply was made of."""
        from spark_rapids_tpu.exec.base import TaskContext, TpuExec
        from spark_rapids_tpu.runtime import pipeline as P
        if trace:
            tracing.set_pending_trace(str(trace))
        counts = {"rows": 0, "batches": 0, "bytes": 0}

        def sink(tbl: pa.Table):
            with tracing.span("endpoint.encode"):
                body = _table_to_ipc(tbl)
                crc = block_checksum(body)
            if record is not None and not record["over"]:
                # record BEFORE fault corruption — a chaos byte flip must
                # reach exactly one client, never be replayed from cache
                clean = _CRC.pack(crc) + body
                record["frames"].append(clean)
                record["bytes"] += len(clean)
                if record["bytes"] > self.result_cache.max_bytes:
                    record["over"] = True
                    record["frames"].clear()
            # chaos: flip a byte AFTER the CRC is stamped — the client's
            # verification must catch it and raise typed TransportError
            body = F.maybe_corrupt("endpoint.corrupt", body)
            with tracing.span("endpoint.send"):
                queued = stream.put(_CRC.pack(crc) + body)
            if not queued:
                SCHED.check_cancel()   # raises the token's typed error
                raise SCHED.QueryCancelledError(
                    "result stream closed by the connection")
            counts["rows"] += tbl.num_rows
            counts["batches"] += 1
            counts["bytes"] += _CRC.size + len(body)

        def run(hybrid):
            if isinstance(hybrid, TpuExec):
                pipe_on = P.enabled(hybrid.conf)
                for split in range(hybrid.num_partitions):
                    with TaskContext():
                        it = hybrid.execute_partition(split)
                        if pipe_on:
                            it = P.stage_iterator(
                                it, edge="collect", conf=hybrid.conf,
                                registry=hybrid.metrics,
                                node_id=hybrid._node_id, spillable=True)
                        for b in it:
                            sink(b.to_arrow())
                if counts["batches"] == 0:
                    sink(hybrid.output.to_arrow().empty_table())
            else:
                sink(hybrid.collect_host())
            return None

        try:
            with tracing.child_of(req_span.id):
                df._run_action(df._plan, run)
            req_span.set(**counts)
            qm = df._last_collector
            summary = {
                "query": qm.query_id, "trace": qm.trace_id,
                "rows": counts["rows"],
                "batches": counts["batches"],
                "wall_s": round(qm.wall_s, 4),
                # XLA compiles attributable to THIS attempt: the journey
                # plane's retrace count (a warm replica serves with 0)
                "traces": qm.compile_metrics().get("compiles", 0),
                "resilience": {k: v for k, v in
                               qm.query_resilience().items() if v},
            }
            stream.finish(summary)
            if record is not None and not record["over"]:
                self.result_cache.put(record["key"], record["frames"],
                                      summary)
        except BaseException as e:   # noqa: BLE001 — marshalled to the client
            stream.fail(e)

    def _result_cache_key(self, sql: str, df):
        """(catalog epoch, plan signature, sql digest) — or None for a plan
        the signature can't cover (never cache what can't be keyed)."""
        from spark_rapids_tpu.plan.fingerprint import plan_signature
        from spark_rapids_tpu.runtime.result_cache import ResultCache
        try:
            sig = plan_signature(df._plan)
        except Exception:   # noqa: BLE001 — unkeyable plan: run it, skip cache
            return None
        return ResultCache.key(self.session.catalog_epoch, sig, sql)

    def _stream_cached(self, sock, hit: dict, jctx: dict | None = None) -> bool:
        """Replay a cached result: the recorded frames bit-identically, then
        the recorded summary marked ``cached`` and re-stamped with THIS
        submission's journey (the recorded journey belongs to the
        submission that populated the cache)."""
        from spark_rapids_tpu.runtime import movement as MV
        try:
            egress_link = MV.classify_peer(sock.getpeername())
        except OSError:
            egress_link = "client"
        try:
            for frame in hit["frames"]:
                t0 = time.perf_counter()
                send_frame(sock, MSG_RESULT_BATCH, frame)
                MV.record("endpoint.egress", len(frame), link=egress_link,
                          site="endpoint.result",
                          seconds=time.perf_counter() - t0)
            summary = dict(hit["summary"])
            summary["cached"] = True
            if jctx is not None:
                summary["journey"] = jctx["journey"]
                summary["attempt"] = jctx["attempt"]
                summary["replica"] = self.replica_name
            send_frame(sock, MSG_RESULT_END,
                       json.dumps(summary).encode("utf-8"))
            self._journey_finish(jctx, "cached",
                                 query=hit["summary"].get("query"), traces=0)
            return True
        except OSError:
            self._journey_finish(jctx, "disconnect",
                                 query=hit["summary"].get("query"))
            return False

    def _cancel_query(self, df, reason: str, wait_s: float = 5.0) -> str | None:
        """Flip the query's CancelToken (waiting briefly for the collector to
        exist — the submit/disconnect race is microseconds wide); returns the
        query id when known."""
        deadline = time.monotonic() + wait_s
        while time.monotonic() < deadline:
            c = df._last_collector
            tok = getattr(c, "cancel_token", None) if c is not None else None
            if tok is not None:
                tok.cancel(reason)
                return c.query_id
            time.sleep(0.01)
        return None

    def _pump(self, sock, entry: dict) -> bool:
        """Connection-thread loop: watch the socket for disconnect while
        relaying stream items as frames. Returns False when the connection
        died (the handler loop must exit)."""
        df, stream, jctx = entry["df"], entry["stream"], entry["jny"]
        deadline = (entry["t0"] + self.request_timeout
                    if self.request_timeout > 0 else None)
        from spark_rapids_tpu.runtime import movement as MV
        try:
            egress_link = MV.classify_peer(sock.getpeername())
        except OSError:
            egress_link = "client"
        while True:
            # disconnect probe: the client sends nothing mid-query, so any
            # readability is a half-close (b""), an RST (OSError), or a
            # protocol violation — all treated as a lost client
            try:
                readable, _, _ = select.select([sock], [], [], 0)
            except (OSError, ValueError):
                readable = [sock]
            if readable:
                try:
                    data = sock.recv(1 << 16)
                except OSError:
                    data = b""
                # half-close (b""), RST (OSError) and mid-query traffic (a
                # protocol violation) all end the connection the same way
                return self._disconnected(df, stream, jctx,
                                          half_close=not data)
            if deadline is not None and not entry["timed_out"] \
                    and time.monotonic() > deadline:
                # entry-shared flag: the heartbeat watchdog (_sweep_stuck)
                # enforces the same deadline when THIS thread is wedged
                entry["timed_out"] = True
                self._cancel_query(df, "request_timeout")
                # deadline hard-kill: flush the flight recorder while the
                # in-flight registry still names the killed query
                BB.dump("deadline_kill")
            item = stream.get(timeout=0.05)
            if item is None:
                continue
            kind, val = item
            try:
                if kind == "batch":
                    F.maybe_inject_any("endpoint.send")
                    t0 = time.perf_counter()
                    send_frame(sock, MSG_RESULT_BATCH, val)
                    # movement ledger: Arrow IPC bytes leaving to the client
                    MV.record("endpoint.egress", len(val), link=egress_link,
                              site="endpoint.result",
                              seconds=time.perf_counter() - t0)
                elif kind == "end":
                    # echo the journey in the summary frame (a copy: the
                    # result cache must record the journey-free original)
                    val = dict(val)
                    if jctx is not None:
                        val["journey"] = jctx["journey"]
                        val["attempt"] = jctx["attempt"]
                        val["replica"] = self.replica_name
                    send_frame(sock, MSG_RESULT_END,
                               json.dumps(val).encode("utf-8"))
                    self._journey_finish(jctx, "served",
                                         query=val.get("query"),
                                         wall_s=val.get("wall_s"),
                                         traces=val.get("traces", 0))
                    return True
                else:   # error
                    exc = self._fleet_retryable(val, entry["timed_out"])
                    self._journey_error(jctx, exc, entry)
                    return self._send_error(sock, exc)
            except (OSError, RuntimeError) as e:
                # a dead client socket, or an injected endpoint.send fault
                # of any kind: the server-side write path died —
                # indistinguishable from a lost client
                return self._disconnected(
                    df, stream, jctx, send_fault=isinstance(e, RuntimeError))

    def _fleet_retryable(self, exc: BaseException,
                         timed_out: bool) -> BaseException:
        """On a fleet, a ``request_timeout`` kill means THIS replica wedged —
        the query belongs on a surviving peer, so the client gets a
        retryable rejection (reason ``replica_timeout``) its rotation
        re-routes. Without a fleet the non-retryable typed cancellation is
        unchanged (there is nowhere else to go)."""
        if (self.fleet is not None and timed_out
                and isinstance(exc, SCHED.QueryCancelledError)
                and getattr(exc, "reason", "") == "request_timeout"):
            return SCHED.QueryRejectedError(
                f"replica {self.fleet.replica_id} exceeded "
                f"requestTimeoutSeconds ({self.request_timeout}s); retry a "
                f"surviving replica", backoff_hint_s=0.05,
                query_id=getattr(exc, "query_id", None),
                reason="replica_timeout", replica=self.fleet.replica_id)
        return exc

    def _disconnected(self, df, stream: _ResultStream, jctx=None,
                      **detail) -> bool:
        from spark_rapids_tpu.runtime import eventlog as EL
        qid = self._cancel_query(df, "client_disconnect")
        M.resilience_add(M.CLIENT_DISCONNECTS)
        EL.emit("client.disconnected", query=qid, **detail)
        self._journey_finish(jctx, "disconnect", query=qid)
        stream.close()
        return False

    # -- journey plane -------------------------------------------------------
    @property
    def replica_name(self) -> str:
        """This replica's identity in journey records and summary frames:
        the fleet replica id when registered, host:port otherwise."""
        if self.fleet is not None and self.fleet.replica_id:
            return self.fleet.replica_id
        return f"{self.host}:{self.port}"

    def _journey_finish(self, jctx, outcome: str, *, query=None,
                        wall_s=None, **fields) -> None:
        """Emit the submission's terminal query.journey record exactly once
        — the connection thread and the heartbeat watchdog can race to
        close the same submission — and feed the SLO accounting (a shed is
        a redirect, not an availability loss)."""
        from spark_rapids_tpu.runtime import eventlog as EL
        if jctx is None:
            return
        with self._lock:
            if jctx["done"]:
                return
            jctx["done"] = True
        if wall_s is None:
            wall_s = time.monotonic() - jctx["t0"]
        wall_s = round(float(wall_s), 4)
        breach = False
        if outcome in ("served", "cached"):
            breach = self.slo.observe(wall_s, ok=True)
        elif outcome != "shed":
            self.slo.observe(wall_s, ok=False)
        extra = {k: v for k, v in fields.items() if v is not None}
        EL.emit("query.journey", query=query, journey=jctx["journey"],
                attempt=jctx["attempt"], replica=self.replica_name,
                outcome=outcome, wall_s=wall_s, **extra)
        if breach:
            EL.emit("slo.breach", query=query, journey=jctx["journey"],
                    attempt=jctx["attempt"], replica=self.replica_name,
                    wall_s=wall_s, target_s=self.slo.target_s)

    def _journey_error(self, jctx, exc: BaseException, entry: dict) -> None:
        """Close a submission's journey from its error path, classifying
        the outcome, and flush the flight recorder when the exception class
        is one the serving contract does not expect."""
        if isinstance(exc, SCHED.QueryRejectedError):
            outcome = ("replica_timeout"
                       if getattr(exc, "reason", "") == "replica_timeout"
                       else "shed")
        elif entry["timed_out"]:
            outcome = "timeout"
        else:
            outcome = "error"
        self._journey_finish(jctx, outcome,
                             query=getattr(exc, "query_id", None),
                             error=type(exc).__name__,
                             reason=getattr(exc, "reason", None))
        if outcome == "error" and not isinstance(
                exc, (SCHED.QueryCancelledError, TransportError)):
            BB.dump("endpoint_error")

    def _inflight_snapshot(self) -> list:
        """Blackbox dump detail: what this endpoint is serving right now —
        the record a survivor reads to explain a dead replica."""
        now = time.monotonic()
        with self._lock:
            entries = list(self._active.values())
        out = []
        for e in entries:
            c = e["df"]._last_collector
            jctx = e.get("jny") or {}
            out.append({
                "query": c.query_id if c is not None else None,
                "journey": jctx.get("journey"),
                "attempt": jctx.get("attempt"),
                "sql": e.get("sql", ""),
                "description": e.get("description", ""),
                "age_s": round(now - e.get("t0", now), 4),
                "timed_out": bool(e.get("timed_out")),
            })
        return out

    def _sweep_stuck(self) -> None:
        """Heartbeat-side deadline enforcement: the connection thread that
        normally enforces requestTimeoutSeconds can itself be wedged (a
        hung send), so every fleet heartbeat re-checks the age of each
        in-flight submission. A stuck one is cancelled, its journey closed
        (``replica_timeout`` on a fleet — the client re-routes), and the
        flight recorder dumped while this process can still write — the
        post-mortem a SIGKILL would otherwise erase."""
        limit = self.request_timeout
        if limit <= 0:
            return
        now = time.monotonic()
        with self._lock:
            stuck = [e for e in self._active.values()
                     if now - e["t0"] > limit and not e["timed_out"]]
            for e in stuck:
                e["timed_out"] = True
        for e in stuck:
            qid = self._cancel_query(e["df"], "request_timeout", wait_s=0.1)
            outcome = ("replica_timeout" if self.fleet is not None
                       else "timeout")
            self._journey_finish(e["jny"], outcome, query=qid, stuck=True)
        if stuck:
            BB.dump("stuck_query", min_interval_s=min(1.0, limit))

    def _fleet_health(self) -> dict:
        """Compact health summary embedded in this replica's lease record
        on every heartbeat — the per-replica row of the fleet roster
        (profiler.py fleet), preserved in the departed tombstone when a
        survivor adopts the lease. Doubles as the stuck-query watchdog's
        clock: the heartbeat thread outlives a wedged connection thread."""
        from spark_rapids_tpu.runtime import eventlog as EL
        self._sweep_stuck()
        h = EL.health_payload()
        out = {
            "active_queries": self.active_queries(),
            "hbm_watermark_bytes": int(h.get("hbm_watermark_bytes") or 0),
            "fuse": h.get("fuse", {}),
            "resilience": {k: v for k, v in
                           M.resilience_snapshot().items() if v},
        }
        if self.result_cache is not None:
            rs = self.result_cache.stats()
            out["result_cache"] = {"hits": rs["hits"],
                                   "misses": rs["misses"]}
        if self.slo.target_s > 0:
            out["slo"] = self.slo.snapshot()
        return out

    # -- drain / shutdown ----------------------------------------------------
    def active_queries(self) -> int:
        with self._lock:
            return len(self._active)

    def shutdown(self, grace_s: float | None = None) -> dict:
        """Graceful drain: stop accepting, shed new submissions (retryable,
        backoff-hinted), let in-flight queries finish within ``grace_s``
        (default ``endpoint.drain.graceSeconds``), then deadline-kill the
        stragglers via their CancelTokens — the hard-kill escalation — and
        close every connection. Idempotent; returns drain statistics."""
        from spark_rapids_tpu.runtime import eventlog as EL
        grace = self.drain_grace if grace_s is None else grace_s
        with self._lock:
            first = not self._draining
            self._draining = True
            if first:
                self._drain_deadline = time.monotonic() + max(0.0, grace)
            in_flight = len(self._active)
        if not first:
            return {"in_flight": in_flight, "cancelled": 0, "repeat": True}
        EL.emit("server.drain", query=None, phase="begin",
                in_flight=in_flight, grace_s=grace)
        # the listener stays up through the grace window: a client arriving
        # mid-drain gets the typed QueryRejectedError with a backoff hint
        # (retry another replica / later) instead of a blind refused connect
        while time.monotonic() < self._drain_deadline and self.active_queries():
            time.sleep(0.05)
        cancelled = 0
        with self._lock:
            stragglers = list(self._active.values())
        for entry in stragglers:
            if self._cancel_query(entry["df"], "drain", wait_s=0.5):
                cancelled += 1
        if cancelled:
            # drain hard-kill: in-flight queries are being force-cancelled;
            # leave the post-mortem before their state drains away
            BB.dump("drain_kill")
        # bounded wait for the cancelled queries to drain through their
        # cooperative checkpoints, then stop accepting and force the
        # remaining connections closed
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and self.active_queries():
            time.sleep(0.05)
        self._srv.shutdown()
        self._srv.server_close()
        self._closing = True
        with self._lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        self._thread.join(timeout=5)
        if self.fleet is not None:
            self.fleet.deregister()
        stats = {"in_flight": in_flight, "cancelled": cancelled,
                 "leaked": self.active_queries()}
        EL.emit("server.drain", query=None, phase="end", **stats)
        EL.emit("endpoint.stop", query=None, port=self.port)
        return stats

    def install_signal_handlers(self, grace_s: float | None = None) -> None:
        """SIGTERM → graceful drain (main thread only). The handler runs
        shutdown() on a helper thread so the signal frame returns
        immediately; the process exits once the drain completes and the
        caller's main loop observes ``draining``."""
        import signal

        def _on_term(signum, frame):
            threading.Thread(target=self.shutdown, args=(grace_s,),
                             daemon=True, name="srt-endpoint-drain").start()
        signal.signal(signal.SIGTERM, _on_term)

    @property
    def draining(self) -> bool:
        return self._draining

    def __enter__(self) -> "QueryEndpoint":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()


# ---------------------------------------------------------------------------
# client
# ---------------------------------------------------------------------------

def _parse_addresses(address) -> list:
    """Normalize every accepted address spec to [(host, port), ...]:
    one (host, port) tuple, one "host:port" string, a comma-separated
    "host:port,host:port" replica list, or a sequence of either."""
    def one(a):
        if isinstance(a, str):
            host, _, port = a.strip().rpartition(":")
            if not host:
                raise ValueError(f"address {a!r} needs host:port")
            return (host, int(port))
        return (a[0], int(a[1]))

    if isinstance(address, str):
        parts = [p for p in (s.strip() for s in address.split(",")) if p]
        if not parts:
            raise ValueError("empty endpoint address list")
        return [one(p) for p in parts]
    seq = list(address)
    if len(seq) == 2 and isinstance(seq[0], str) and isinstance(seq[1], int):
        return [(seq[0], seq[1])]   # the classic single (host, port) tuple
    if not seq:
        raise ValueError("empty endpoint address list")
    return [one(a) for a in seq]


class EndpointClient:
    """Remote submitter (tools/tpu_client.py is the CLI front). One
    connection per submission; closing the connection mid-stream is the
    cancellation protocol — the server cancels the query on disconnect.

    `address` may name a whole replica fleet — a comma-separated
    "host:port,host:port" list (or a sequence of addresses): plain submits
    use the current replica, and :meth:`submit_with_retry` rotates to the
    next one with jitter on any retryable failure (connection refused, a
    replica dying mid-stream, shed/drain/replica_timeout rejections), so
    failover needs no client code changes."""

    def __init__(self, address, *, timeout_s: float = 60.0,
                 max_frame_bytes: int | None = None):
        self.addresses = _parse_addresses(address)
        self._addr_idx = 0
        self.timeout_s = timeout_s
        self.max_frame = max_frame_bytes or _default_max_frame()
        self.last_summary: dict | None = None
        self.last_journey: str | None = None

    @property
    def address(self) -> tuple:
        """The replica currently targeted (rotation advances it)."""
        return self.addresses[self._addr_idx]

    def rotate(self) -> tuple:
        """Advance to the next replica in the list; returns the new target.
        Counts a replicaFailovers resilience event when there is more than
        one replica (rotation on a fleet IS the failover)."""
        if len(self.addresses) > 1:
            self._addr_idx = (self._addr_idx + 1) % len(self.addresses)
            M.resilience_add(M.REPLICA_FAILOVERS)
        return self.address

    def connect(self, address=None):
        addr = address if address is not None else self.address
        try:
            sock = socket.create_connection(addr, timeout=self.timeout_s)
        except OSError as e:
            # connection refused/reset IS retryable: the replica is gone,
            # the fleet may not be — rotation finds out
            raise TransportError(
                f"endpoint {addr} unreachable: {e}") from e
        configure_socket(sock, timeout_s=self.timeout_s)
        return sock

    def ping(self) -> bool:
        sock = self.connect()
        try:
            send_frame(sock, MSG_PING, b"")
            msg, _ = recv_frame(sock, max_bytes=self.max_frame)
            return msg == MSG_PONG
        except (TransportError, OSError):
            return False
        finally:
            sock.close()

    def stats(self, address=None) -> str:
        """Live serving-metrics snapshot (Prometheus-style text): admission
        counters, resilience registry, HBM/spill/queue gauges and latency
        histograms. `address` targets a specific replica (default: the
        currently-targeted one). Raises the server's typed error when STATS
        is disabled (endpoint.stats.enabled=false)."""
        addr = address if address is not None else self.address
        sock = self.connect(addr)
        try:
            send_frame(sock, MSG_STATS, b"")
            msg, payload = recv_frame(sock, max_bytes=self.max_frame)
            if msg == MSG_QUERY_ERROR:
                raise _unpickle_error(payload)
            if msg != MSG_STATS_RESP:
                raise TransportError(f"unexpected endpoint message {msg}")
            return payload.decode("utf-8")
        except OSError as e:
            raise TransportError(
                f"endpoint {addr} stats failed: {e}") from e
        finally:
            sock.close()

    def stats_all(self) -> dict:
        """Per-replica stats across the WHOLE replica list — never just the
        one replica the client happens to target. ``{"host:port": text |
        Exception}``; a dial failure is recorded, not raised, so one dead
        replica cannot hide the rest of the fleet."""
        out = {}
        for addr in self.addresses:
            key = f"{addr[0]}:{addr[1]}"
            try:
                out[key] = self.stats(addr)
            except Exception as e:   # noqa: BLE001 — typed server errors
                out[key] = e         # (stats disabled) report per-replica
        return out

    def fleet_stats(self) -> dict:
        """Fleet-wide stats rollup: dial every replica in the list, parse
        each Prometheus snapshot, and merge — per-replica counters/gauges
        (or the dial error) plus fleet-aggregate counter families where
        every aggregate equals the sum of per-replica values
        (tools/tpu_client.py fleet-stats renders this)."""
        return merge_fleet_stats(self.stats_all())

    def submit_iter(self, sql: str, *, priority: int | None = None,
                    deadline_s: float | None = None,
                    queue_timeout_s: float | None = None,
                    description: str = "", trace: str | None = None,
                    journey: str | None = None, attempt: int | None = None):
        """Generator of result tables, one per streamed Arrow-IPC batch;
        ``self.last_summary`` carries the MSG_RESULT_END stats afterwards.
        Abandoning the generator closes the connection, which cancels the
        query server-side. Raises the server's typed exception on failure
        and TransportError on any wire-level fault (CRC mismatch, short
        read, reset). Every submission is stamped with a journey id +
        attempt number (minted here when the caller has none):
        submit_with_retry reuses one journey across its replica rotation,
        so each replica's query.journey record joins one timeline."""
        if journey is None:
            journey = "j-" + uuid.uuid4().hex[:12]
        self.last_journey = journey
        req = {"sql": sql, "description": description,
               "priority": priority, "deadline_s": deadline_s,
               "queue_timeout_s": queue_timeout_s, "trace": trace,
               "journey": journey, "attempt": max(1, int(attempt or 1))}
        sock = self.connect()
        try:
            try:
                send_frame(sock, MSG_SUBMIT, json.dumps(
                    {k: v for k, v in req.items() if v is not None}
                ).encode("utf-8"))
                while True:
                    msg, payload = recv_frame(sock, max_bytes=self.max_frame)
                    if msg == MSG_RESULT_BATCH:
                        (crc,) = _CRC.unpack_from(payload, 0)
                        body = payload[_CRC.size:]
                        got = block_checksum(body)
                        if got != crc:
                            raise TransportError(
                                f"result batch checksum mismatch (sent "
                                f"{crc:#x}, got {got:#x}, {len(body)}B)")
                        yield _ipc_to_table(body)
                    elif msg == MSG_RESULT_END:
                        self.last_summary = json.loads(payload)
                        return
                    elif msg == MSG_QUERY_ERROR:
                        raise _unpickle_error(payload)
                    else:
                        raise TransportError(
                            f"unexpected endpoint message {msg}")
            except TransportError:
                raise
            except OSError as e:
                raise TransportError(
                    f"endpoint {self.address} connection failed: {e}") from e
        finally:
            sock.close()

    def submit(self, sql: str, **kw) -> pa.Table:
        """Submit and collect the whole result (a schema-bearing empty table
        for empty results)."""
        tables = list(self.submit_iter(sql, **kw))
        return pa.concat_tables(tables)

    def append(self, source: str, batch_id: str, tbl: pa.Table) -> dict:
        """Ship one streaming batch as a CRC-stamped Arrow-IPC APPEND
        frame; returns the server's ack (duplicate flag, rows, catalog
        epoch, replica). The ack means DURABLE — the server persisted the
        batch before replying. Raises the server's typed error, or a
        retryable TransportError on any wire-level fault."""
        from spark_rapids_tpu.streaming.source import table_to_ipc
        body = table_to_ipc(tbl)
        hdr = json.dumps({"source": source, "batch": batch_id,
                          "crc": block_checksum(body)}).encode("utf-8")
        sock = self.connect()
        try:
            try:
                send_frame(sock, MSG_APPEND,
                           _HDR.pack(len(hdr)) + hdr + body)
                msg, payload = recv_frame(sock, max_bytes=self.max_frame)
                if msg == MSG_QUERY_ERROR:
                    raise _unpickle_error(payload)
                if msg != MSG_APPEND_ACK:
                    raise TransportError(
                        f"unexpected endpoint message {msg} "
                        f"(want APPEND_ACK)")
                return json.loads(payload)
            except TransportError:
                raise
            except OSError as e:
                raise TransportError(
                    f"endpoint {self.address} append failed: {e}") from e
        finally:
            sock.close()

    def append_with_retry(self, source: str, batch_id: str, tbl: pa.Table,
                          *, max_attempts: int = 5,
                          backoff_cap_s: float = 10.0,
                          on_retry=None) -> dict:
        """APPEND under the same fleet rotation contract as
        submit_with_retry — safe to retry blindly because APPEND is
        idempotent by (source, batch_id): a replica that died AFTER
        persisting but BEFORE acking turns the retry into a ``duplicate``
        ack, never a double ingest. Retryable rejections (shed/drain)
        honor their backoff hint; transport faults back off exponentially;
        with a replica list every retryable failure rotates first."""
        attempt = 0
        while True:
            attempt += 1
            try:
                return self.append(source, batch_id, tbl)
            except SCHED.QueryRejectedError as e:
                if attempt >= max_attempts:
                    raise
                delay = min(max(0.05, e.backoff_hint_s), backoff_cap_s)
            except TransportError as e:
                if attempt >= max_attempts or not getattr(
                        e, "retryable", False):
                    raise
                delay = min(0.1 * (2 ** (attempt - 1)), backoff_cap_s)
            if len(self.addresses) > 1:
                self.rotate()
                delay *= 0.5 + random.random() * 0.5   # jittered rotation
            if on_retry is not None:
                on_retry(attempt, delay)
            time.sleep(delay)

    def submit_with_retry(self, sql: str, *, max_attempts: int = 5,
                          backoff_cap_s: float = 10.0, on_retry=None,
                          **kw) -> pa.Table:
        """Submit, honoring the serving contract: a retryable rejection
        (shed/drain/replica_timeout) sleeps its ``backoff_hint_s``; a
        transport fault (endpoint died mid-handshake or mid-stream, reset,
        connection refused) retries with jittered exponential backoff;
        non-retryable typed errors propagate immediately. With a replica
        list, every retryable failure first rotates to the next replica
        (jittered, so a killed replica's clients don't stampede one
        survivor) — failover is this loop, not new client code.

        One journey id spans every attempt, and when the caller passed no
        trace id the journey doubles as the trace — so a failed-over
        submission's server-side spans land in ONE distributed trace
        instead of orphaning attempt 1's spans under a per-attempt id."""
        journey = kw.pop("journey", None) or "j-" + uuid.uuid4().hex[:12]
        if kw.get("trace") is None:
            kw["trace"] = journey
        attempt = 0
        while True:
            attempt += 1
            try:
                return self.submit(sql, journey=journey, attempt=attempt,
                                   **kw)
            except SCHED.QueryRejectedError as e:
                if attempt >= max_attempts:
                    raise
                delay = min(max(0.05, e.backoff_hint_s), backoff_cap_s)
            except TransportError as e:
                if attempt >= max_attempts or not getattr(
                        e, "retryable", False):
                    raise
                delay = min(0.1 * (2 ** (attempt - 1)), backoff_cap_s)
            if len(self.addresses) > 1:
                self.rotate()
                delay *= 0.5 + random.random() * 0.5   # jittered rotation
            if on_retry is not None:
                on_retry(attempt, delay)
            time.sleep(delay)
