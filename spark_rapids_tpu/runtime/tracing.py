"""Spans — the NVTX analog: one primitive, three places a span lands.

Reference: NvtxWithMetrics.scala:42 couples an NVTX range with a timing metric;
ranges wrap every hot region (GpuSemaphore.scala:107, aggregate.scala:356) and are
viewed in Nsight. Here ``trace_range(name, metric, **counts)`` and
``span(name, **counts)`` are ONE code path (``_Span``), switched by
spark.rapids.tpu.sql.trace.enabled. When that is on a span

  (a) opens a ``jax.profiler.TraceAnnotation`` of the same name, so it lands
      in a profiler capture (``.xplane.pb``, Perfetto/XProf) on the device
      trace's own clock and idle device time can be put down to it;
  (b) appends one record to a bounded in-process buffer: name, span id,
      parent id (the span open on this thread, or the one handed over with
      ``current_span()`` / ``child_of()`` when work crosses to a pipeline,
      pool or endpoint worker thread), the query's trace id, thread name,
      start and end in ``time.perf_counter_ns()`` and the counts given at
      the boundary (``span(..., rows=n)`` or ``sp.set(rows=n)``).

A span that is a root, or that was opened with counts, carries those counts
and ``t0_ns`` (its ``perf_counter_ns`` start) as annotation metadata, so the
offset between this clock and the capture's is read from the capture itself.
The buffer is only read after the fact (``recorded()``, ``drain()``,
``summarize()``); nothing is written on the hot path. It holds the newest
``MAX_RECORDS`` spans; ``dropped()`` counts what fell off, and a reader that
needs whole queries treats any drop as "no reading". With tracing off every
span site costs one module-level check and the shared no-op ``NO_SPAN``.
With it on, each Python garbage collection is a ``gc`` span as well (a
``gc.callbacks`` hook that exists only while tracing is on), so a pause
is named where it lands and not charged to the work it interrupted.

  (c) Distributed spans (spark.rapids.tpu.trace.dir): the reference views
whole-cluster execution in Nsight because NVTX ranges from every process land
in one capture. Here each process appends the same records to its own JSONL
span file (``spans-<pid>-<stamp>.jsonl``) tagged with a per-query **trace id**
that propagates across every process boundary — the MiniCluster task protocol,
shuffle-transport frame headers, and the endpoint SUBMIT frame — so
``tools/profiler.py trace <dir>`` can merge them into one Chrome-trace
timeline (Perfetto) with per-process clock-offset correction
(runtime/eventlog.set_clock_offset, measured by the driver's two-timestamp
handshake exchange) and walk the critical path. The file sink works with or
without sql.trace.enabled.

Span record schema of the file sink (validate_span):
  name  str    range name (trace_range/span) or event name (span_event) or
               counter track name (counter)
  ph    "X"|"i"|"C"  complete span | zero-duration instant | counter sample
                     (args = {series: number}, the Chrome counter-track form
                     the memory plane uses for per-tier occupancy lanes)
  ts    float  wall-clock epoch seconds at span start (LOCAL clock)
  dur   float  seconds (ph == "X" only)
  pid   int    writing process
  proc  str    process label ("driver", "executor-N", ...)
  tid   str    thread name (pipeline edges appear as their srt-pipe-* lanes)
  trace str|None  the query's trace id (None for out-of-query spans)
  off   float  clock offset toward the driver (omitted when 0)
  args  dict   optional attributes (the span's counts)
  id    int    span id, unique in the process (ph == "X" only)
  parent int|None  id of the enclosing span (ph == "X" only)
"""

from __future__ import annotations

import collections
import contextlib
import datetime
import gc
import itertools
import json
import os
import threading
import time

from spark_rapids_tpu.runtime import eventlog as _eventlog
from spark_rapids_tpu.runtime import metrics as _metrics

_enabled = False   # sql.trace.enabled: annotations + in-memory records
_active = False    # _enabled or a span file is open: what a site checks

# zero-duration span events (oom.retry / oom.split / fetch.recompute …): a
# bounded in-memory ring that chaos tests and postmortems read regardless of
# whether the profiler is capturing; with tracing enabled each event also
# lands as a profiler annotation, and with the event log configured it is
# appended there too (runtime/eventlog.py)
_events: "collections.deque" = collections.deque(maxlen=512)


# ---------------------------------------------------------------------------
# trace context: which query's trace do spans on this thread belong to
# ---------------------------------------------------------------------------

_trace_tls = threading.local()
# per-process default (MiniCluster executors run one task at a time, so the
# task loop pins the whole process — including pipeline worker threads that
# never re-enter a collector scope — to the task's trace id)
_process_trace: "str | None" = None


def current_trace_id() -> "str | None":
    """The trace id spans on this thread are tagged with: an explicit
    thread-local trace_context() (transport server threads serving a remote
    fetch), else the ambient query collector's trace id (driver-side worker
    threads re-enter that scope), else the process default (executor task
    loops)."""
    tid = getattr(_trace_tls, "trace", None)
    if tid is not None:
        return tid
    c = _metrics.current_collector()
    if c is not None:
        return getattr(c, "trace_id", None) or c.query_id
    return _process_trace


@contextlib.contextmanager
def trace_context(trace_id: "str | None"):
    """Pin this thread's spans to `trace_id` (None = no-op passthrough to
    the ambient lookup)."""
    prev = getattr(_trace_tls, "trace", None)
    _trace_tls.trace = trace_id
    try:
        yield
    finally:
        _trace_tls.trace = prev


def set_process_trace(trace_id: "str | None") -> None:
    """Pin the whole PROCESS to `trace_id` (executor task loops: worker
    threads spawned by the pipelined executor inherit it without any
    collector plumbing)."""
    global _process_trace
    _process_trace = trace_id


# one-shot trace-id handoff into the next collector created on this thread
# (the endpoint worker thread sets the client's SUBMIT trace id here before
# running the action; session._run_action takes it)
def set_pending_trace(trace_id: "str | None") -> None:
    _trace_tls.pending = trace_id


def take_pending_trace() -> "str | None":
    t = getattr(_trace_tls, "pending", None)
    _trace_tls.pending = None
    return t


# executor-side event-log records fall back to the ambient trace id for
# their `query` tag (see eventlog.set_query_fallback) — registered at the
# bottom of this module once current_trace_id exists


def estimate_clock_offset(t_local_send: float, t_remote: float,
                          t_local_recv: float) -> float:
    """Two-timestamp offset estimate: assuming symmetric message latency,
    remote_clock + offset ≈ local_clock. Error is bounded by half the
    round-trip time."""
    return (t_local_send + t_local_recv) / 2.0 - t_remote


# ---------------------------------------------------------------------------
# span sink: per-process JSONL span files
# ---------------------------------------------------------------------------

class SpanWriter:
    """Append-only JSONL span sink, one file per process per configure."""

    def __init__(self, path: str, process: str):
        self.path = path
        self.process = process
        self._f = open(path, "a", encoding="utf-8")
        self._lock = threading.RLock()   # reentrant, as _records_lock is

    def write(self, rec: dict) -> None:
        line = json.dumps(rec, separators=(",", ":"), default=str)
        with self._lock:
            self._f.write(line + "\n")
            self._f.flush()

    def close(self) -> None:
        try:
            self._f.close()
        except OSError:
            pass


_span_writer: "SpanWriter | None" = None


def configure_spans(directory: str, process: "str | None" = None) -> str:
    """Open a span file under `directory` (created if missing) and make it
    this process's sink; returns the file path. `process` labels the
    Perfetto process lane ("driver", "executor-3", ...)."""
    global _span_writer
    os.makedirs(directory, exist_ok=True)
    # microsecond stamp: same collision guard as the event log's configure
    stamp = datetime.datetime.now().strftime("%Y%m%d-%H%M%S-%f")
    path = os.path.join(directory, f"spans-{os.getpid()}-{stamp}.jsonl")
    if _span_writer is not None:
        _span_writer.close()
    _span_writer = SpanWriter(path, process or f"pid{os.getpid()}")
    _set_active()
    return path


def spans_enabled() -> bool:
    return _span_writer is not None


def span_path() -> "str | None":
    w = _span_writer
    return w.path if w is not None else None


def shutdown_spans() -> None:
    global _span_writer
    if _span_writer is not None:
        _span_writer.close()
        _span_writer = None
    _set_active()


def _emit_span(name: str, ph: str, ts: float, dur: "float | None",
               attrs: "dict | None", ids: "tuple | None" = None) -> None:
    w = _span_writer
    if w is None:
        return
    rec = {"name": name, "ph": ph, "ts": ts, "pid": os.getpid(),
           "proc": w.process, "tid": threading.current_thread().name,
           "trace": current_trace_id()}
    if dur is not None:
        rec["dur"] = dur
    if ids is not None:
        rec["id"], rec["parent"] = ids
    off = _eventlog.clock_offset()
    if off:
        rec["off"] = off
    if attrs:
        rec["args"] = attrs
    w.write(rec)


# ---------------------------------------------------------------------------
# the span primitive
# ---------------------------------------------------------------------------

MAX_RECORDS = 1 << 18
_records: "collections.deque" = collections.deque(maxlen=MAX_RECORDS)
# reentrant: a collection that starts while this thread holds the lock
# closes its ``gc`` span inside it
_records_lock = threading.RLock()
_dropped = 0
_span_ids = itertools.count(1)


def _set_active() -> None:
    global _active
    _active = _enabled or _span_writer is not None


class _NoSpan:
    """What every span site gets while nothing listens: shared, falsy, and
    every method a no-op."""

    __slots__ = ()
    id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False

    def set(self, **counts) -> None:
        pass


NO_SPAN = _NoSpan()


class _Timed(_NoSpan):
    """trace_range with a metric while nothing listens: the timer alone."""

    __slots__ = ("_metric", "_t0")

    def __init__(self, metric):
        self._metric = metric

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self._metric.add(time.perf_counter_ns() - self._t0)
        return False


class _Span:
    """One open span; becomes its own record when it closes."""

    __slots__ = ("name", "id", "parent", "trace", "thread", "t0", "t1",
                 "counts", "_metric", "_outer", "_ann", "_ts")

    def __init__(self, name, metric, parent, counts):
        self.name, self._metric = name, metric
        self.parent, self.counts = parent, counts
        self.t1 = self._ann = None

    def __enter__(self):
        tls = _trace_tls
        self._outer = getattr(tls, "open", None)
        if self.parent is None:
            self.parent = self._outer
        self.id = tls.open = next(_span_ids)
        self.trace = current_trace_id()
        self.thread = threading.current_thread().name
        self._ts = time.time() if _span_writer is not None else 0.0
        self.t0 = time.perf_counter_ns()
        if _enabled:
            import jax
            if self.counts or self.parent is None:
                ann = jax.profiler.TraceAnnotation(
                    self.name, t0_ns=self.t0, **self.counts)
            else:
                ann = jax.profiler.TraceAnnotation(self.name)
            ann.__enter__()
            self._ann = ann
        return self

    def __exit__(self, *exc):
        global _dropped
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.t1 = time.perf_counter_ns()
        _trace_tls.open = self._outer
        if self._metric is not None:
            self._metric.add(self.t1 - self.t0)
        self._ann = self._metric = None    # the record keeps neither alive
        if _enabled:
            with _records_lock:
                if len(_records) == MAX_RECORDS:
                    _dropped += 1
                _records.append(self)
        if _span_writer is not None:
            _emit_span(self.name, "X", self._ts, (self.t1 - self.t0) / 1e9,
                       self.counts or None, (self.id, self.parent))
        return False

    def __bool__(self):
        return True

    def set(self, **counts) -> None:
        """Counts known only at the boundary (rows out, bytes, the path
        taken); the host must hold them already."""
        self.counts.update(counts)

    def as_dict(self) -> dict:
        return {"name": self.name, "id": self.id, "parent": self.parent,
                "trace": self.trace, "thread": self.thread, "t0": self.t0,
                "t1": self.t1, "counts": dict(self.counts)}


def span(name: str, *, parent: "int | None" = None, **counts):
    """A span with no metric: ``with span("scan.column") as sp: ...;
    sp.set(path="fused")``. ``parent`` overrides the span open on this
    thread (a span id from ``current_span()`` on the submitting thread)."""
    if not _active:
        return NO_SPAN
    return _Span(name, None, parent, counts)


def trace_range(name: str, metric=None, **counts):
    """NvtxWithMetrics analog: the same span, coupled to a timing metric
    that accumulates whether or not anything listens."""
    if not _active:
        return NO_SPAN if metric is None else _Timed(metric)
    return _Span(name, metric, None, counts)


def current_span() -> "int | None":
    """Id of the span open on this thread, to hand to a worker thread."""
    return getattr(_trace_tls, "open", None) if _active else None


class _ChildOf:
    __slots__ = ("_parent", "_outer")

    def __init__(self, parent):
        self._parent = parent

    def __enter__(self):
        self._outer = getattr(_trace_tls, "open", None)
        _trace_tls.open = self._parent
        return self

    def __exit__(self, *exc):
        _trace_tls.open = self._outer
        return False


def child_of(parent: "int | None"):
    """On a worker thread: spans opened inside are children of ``parent``
    (what ``current_span()`` returned on the thread that handed the work
    over). The companion of ``metrics.collector_context``."""
    return NO_SPAN if parent is None else _ChildOf(parent)


def recorded() -> list:
    """The buffered spans, oldest first, one dict each (``_Span.as_dict``).
    Read after the work, not during it."""
    with _records_lock:
        spans = list(_records)
    return [s.as_dict() for s in spans]


def dropped() -> int:
    """Spans that fell off the buffer since the last ``drain()``. A reader
    that needs whole queries gives no reading when this is not 0."""
    return _dropped


def drain() -> list:
    """``recorded()``, and the buffer and its drop count start anew."""
    global _dropped
    with _records_lock:
        spans = list(_records)
        _records.clear()
        _dropped = 0
    return [s.as_dict() for s in spans]


def summarize(spans: list) -> dict:
    """{name: {"count", "total_s", "self_s"}} over ``recorded()`` records.
    Self time is a span's duration minus its children's on the same thread
    (a child on another thread runs beside its parent, not inside it)."""
    by_id = {s["id"]: s for s in spans}
    child_ns: dict = {}
    for s in spans:
        p = by_id.get(s["parent"])
        if p is not None and p["thread"] == s["thread"]:
            child_ns[p["id"]] = child_ns.get(p["id"], 0) + s["t1"] - s["t0"]
    out: dict = {}
    for s in spans:
        d = s["t1"] - s["t0"]
        row = out.setdefault(s["name"],
                             {"count": 0, "total_s": 0.0, "self_s": 0.0})
        row["count"] += 1
        row["total_s"] += d / 1e9
        row["self_s"] += (d - child_ns.get(s["id"], 0)) / 1e9
    return out


def instant(name: str, **attrs) -> None:
    """Span-file-only zero-duration instant (no event-log or ring
    forwarding — for records whose analysis copy is already emitted
    elsewhere, e.g. spill-tier transitions next to the memory counter
    lanes). Free when no span sink is configured."""
    if _span_writer is not None:
        _emit_span(name, "i", time.time(), None, attrs or None)


def counter(name: str, values: dict) -> None:
    """Chrome counter-track sample (ph "C"): `values` maps series name to a
    number; Perfetto renders one stacked counter lane per (process, name).
    The memory plane emits its per-tier occupancy here so HBM/host/disk
    levels plot alongside the span lanes. Free when no sink is
    configured."""
    if _span_writer is not None:
        _emit_span(name, "C", time.time(), None, dict(values))


def validate_span(rec: dict) -> list:
    """Schema check for one parsed span record; returns violation strings
    (empty = valid). Shared by tools/profiler.py trace and the tests."""
    errs = []
    if not isinstance(rec.get("name"), str):
        errs.append("missing 'name'")
        return errs
    name = rec["name"]
    if rec.get("ph") not in ("X", "i", "C"):
        errs.append(f"{name}: ph must be 'X', 'i' or 'C'")
    if not isinstance(rec.get("ts"), (int, float)):
        errs.append(f"{name}: missing numeric 'ts'")
    if rec.get("ph") == "X" and not isinstance(rec.get("dur"), (int, float)):
        errs.append(f"{name}: X span without numeric 'dur'")
    if rec.get("ph") == "C" and not isinstance(rec.get("args"), dict):
        errs.append(f"{name}: C counter sample without an args series dict")
    if not isinstance(rec.get("pid"), int):
        errs.append(f"{name}: missing int 'pid'")
    if not isinstance(rec.get("tid"), str):
        errs.append(f"{name}: missing thread name 'tid'")
    return errs


# ---------------------------------------------------------------------------
# span events + ranges
# ---------------------------------------------------------------------------

def span_event(name: str, **attrs) -> None:
    # tag with the ambient query id so concurrent sessions/tests can filter
    # the process-global ring down to their own query (recent_events(query=))
    qid = _metrics.current_query_id()
    if qid is not None:
        attrs = dict(attrs, query=qid)
    _events.append((name, attrs))
    if _eventlog.enabled():
        _eventlog.emit(name, **attrs)
    if _span_writer is not None:
        _emit_span(name, "i", time.time(), None, attrs)
    if _enabled:
        import jax
        # label construction stays behind the enable check: formatting every
        # attr dict on a disabled path costs real time at batch granularity
        label = name + ("[" + ",".join(f"{k}={v}" for k, v in attrs.items())
                        + "]" if attrs else "")
        with jax.profiler.TraceAnnotation(label):
            pass


def recent_events(name: str | None = None, query: str | None = None) -> list:
    """Ring contents, optionally filtered by event name and/or the query id
    the event was tagged with (query=None returns every event regardless)."""
    evs = list(_events)
    if name is not None:
        evs = [e for e in evs if e[0] == name]
    if query is not None:
        evs = [e for e in evs if e[1].get("query") == query]
    return evs


def clear_events() -> None:
    _events.clear()


_gc_span = None    # the collection in progress: one runs at a time


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook while tracing is on: each Python collection is
    one ``gc`` span on the thread that runs it, a child of the span open
    there; counts ``generation``, ``collected``, ``uncollectable``."""
    global _gc_span
    if phase == "start":
        _gc_span = _Span("gc", None, None,
                         {"generation": info["generation"]}).__enter__()
    elif _gc_span is not None:
        sp, _gc_span = _gc_span, None
        sp.set(collected=info["collected"],
               uncollectable=info["uncollectable"])
        sp.__exit__(None, None, None)


def set_enabled(v: bool):
    """Tracing on or off; on, the ``gc`` hook is registered, off it is not."""
    global _enabled
    _enabled = bool(v)
    _set_active()
    hooked = _on_gc in gc.callbacks
    if _enabled and not hooked:
        gc.callbacks.append(_on_gc)
    elif not _enabled and hooked:
        gc.callbacks.remove(_on_gc)


_profiling = False
_profile_dir = None


def start_profile(outdir: str, **options) -> None:
    """Whole-session XProf capture (idempotent; stopped at interpreter
    exit — use stop_profile() to flush earlier in long-lived processes).
    Viewable in Perfetto/XProf — the Nsight-workflow analog. ``options``
    are attributes of ``jax.profiler.ProfileOptions``: on a chip pass
    ``python_tracer_level=0``, or the Python tracer's events swamp the
    capture."""
    global _profiling, _profile_dir
    if _profiling:
        if outdir != _profile_dir:
            import warnings
            warnings.warn(
                f"profiler already capturing to {_profile_dir}; "
                f"ignoring profile.dir={outdir}", stacklevel=2)
        return
    _profile_dir = outdir
    import atexit
    import jax
    if options:
        opts = jax.profiler.ProfileOptions()
        for k, v in options.items():
            setattr(opts, k, v)
        jax.profiler.start_trace(outdir, profiler_options=opts)
    else:
        jax.profiler.start_trace(outdir)
    _profiling = True

    atexit.register(stop_profile)


def stop_profile() -> None:
    """Flush and stop the capture (safe to call when not profiling). The
    atexit hook registered by start_profile is removed so repeated
    start/stop cycles don't stack handlers."""
    global _profiling
    if _profiling:
        import atexit
        import jax
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        _profiling = False
        atexit.unregister(stop_profile)


_eventlog.set_query_fallback(current_trace_id)
