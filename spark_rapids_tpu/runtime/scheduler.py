"""Multi-tenant query lifecycle — admission control, deadlines, cooperative
cancellation, and overload shedding.

The reference plugin leans on Spark's scheduler for this entire lifecycle:
queries queue in the fair scheduler, the driver admits them against executor
resources, and task cancellation propagates through TaskContext. This engine
is standalone, so this module IS that front door: a process-wide
:class:`QueryScheduler` multiplexes concurrent sessions onto the pipelined
executor with three guarantees.

**Admission control.** Every action declares an estimated device-memory
footprint (:func:`estimate_footprint` — scan bytes x a decode-expansion
factor, scaled by the plan's breaker count) and is admitted against the HBM
budget with ``scheduler.maxConcurrent`` concurrency and fair-share +
priority queues. Over-capacity submissions WAIT (fairness = strict
head-of-line on effective priority, where effective priority ages upward by
``scheduler.priority.agingSeconds`` of queue wait so low-priority tenants
cannot starve); a submission that would exceed ``scheduler.queue.maxDepth``
or waits past ``scheduler.queue.timeoutSeconds`` is SHED with a typed,
retryable :class:`QueryRejectedError` carrying a backoff hint — load
shedding at the front door instead of OOM cascades in the engine. The PR-2
OOM retry ladder makes mild over-admission recoverable, so one query is
always admitted when nothing is running (progress guarantee) even if its
estimate exceeds the budget.

**Cooperative cancellation + deadlines.** A :class:`CancelToken` rides the
query's metric collector (every pool/pipeline/broadcast thread already
re-enters that scope — the PR-3/PR-4 attribution pattern), so
:func:`check_cancel` is reachable from every blocking loop: pipeline queue
put/get waits, the scan readahead, semaphore acquisition, shuffle fetch
backoff sleeps, the exchange recompute ladder, the OOM retry ladder, and
every operator's per-batch ``wrap_output`` pull. ``session.cancel(qid)`` or
a ``scheduler.query.deadlineSeconds`` expiry flips the token; the whole
pipeline then drains through the PR-4 clean-cancellation machinery — queue
close callbacks unregister spillable batches, producers observe closed
queues and stop, TaskContext exits release semaphore permits — leaking
neither threads, nor device buffers, nor permits.

**Isolation under failure.** Catalog buffers are tagged with their owning
query; on a strict-budget OOM the retry ladder consults
:meth:`QueryScheduler.on_oom_retry`, which (a) re-checks admission — the
faulting query briefly waits for a peer to release when the scheduler is
over-committed — and (b) applies the fair-share degradation path: when the
faulting query is UNDER its fair share and a lower-priority peer is over
its own, the peer's spillable device buffers are demoted (spilled) instead
of the faulting query paying with splits — the victim chosen by (lowest
priority, most spillable device bytes).

Every transition is visible in the structured event log: query.queued /
query.admitted / query.shed / query.cancelled / query.deadline /
query.demoted, and tools/profiler.py renders an admission/lifecycle table
from them.
"""

from __future__ import annotations

import os
import threading
import time

from spark_rapids_tpu.runtime import metrics as M

# resilience counter names (registered in runtime/metrics.py)
QUERIES_SHED = M.QUERIES_SHED
QUERIES_CANCELLED = M.QUERIES_CANCELLED
QUERY_DEMOTIONS = M.QUERY_DEMOTIONS


# ---------------------------------------------------------------------------
# typed lifecycle errors
# ---------------------------------------------------------------------------

def _rebuild_rejected(msg, backoff_hint_s, query_id, reason, replica=None):
    return QueryRejectedError(msg, backoff_hint_s=backoff_hint_s,
                              query_id=query_id, reason=reason,
                              replica=replica)


class QueryRejectedError(RuntimeError):
    """The scheduler shed this submission (queue full, or queue wait past
    ``scheduler.queue.timeoutSeconds``). ``retryable`` marks it safe to
    resubmit; ``backoff_hint_s`` is the scheduler's estimate of when
    capacity frees up; ``replica`` names the fleet replica that shed (so a
    rotating client can record WHO rejected). Pickles losslessly so a
    serving endpoint can ship it back to a remote client with the hint
    intact."""

    retryable = True

    def __init__(self, msg: str, *, backoff_hint_s: float = 1.0,
                 query_id: str | None = None, reason: str = "shed",
                 replica: str | None = None):
        super().__init__(msg)
        self.backoff_hint_s = backoff_hint_s
        self.query_id = query_id
        self.reason = reason
        self.replica = replica

    def __reduce__(self):
        return (_rebuild_rejected, (str(self), self.backoff_hint_s,
                                    self.query_id, self.reason, self.replica))


def _rebuild_cancelled(cls, msg, query_id, reason):
    return cls(msg, query_id=query_id, reason=reason)


class QueryCancelledError(RuntimeError):
    """The query's CancelToken fired (session.cancel / a chaos ``cancel``
    fault). NOT retryable by the OOM ladder — cancellation must drain the
    pipeline, not re-run it. Pickles losslessly (subclass, query_id and
    reason preserved) so the serving endpoint can ship a drain/disconnect/
    deadline kill to a remote client typed."""

    retryable = False

    def __init__(self, msg: str, *, query_id: str | None = None,
                 reason: str = "cancelled"):
        super().__init__(msg)
        self.query_id = query_id
        self.reason = reason

    def __reduce__(self):
        return (_rebuild_cancelled, (type(self), str(self), self.query_id,
                                     self.reason))


class QueryDeadlineError(QueryCancelledError):
    """The query ran (or queued) past its deadline
    (``scheduler.query.deadlineSeconds``)."""

    def __init__(self, msg: str, *, query_id: str | None = None,
                 reason: str = "deadline"):
        super().__init__(msg, query_id=query_id, reason=reason)


# ---------------------------------------------------------------------------
# cancel token
# ---------------------------------------------------------------------------

class CancelToken:
    """Cooperative cancellation flag + optional deadline for one query.

    The token is carried on the query's QueryMetricsCollector, so every
    thread that re-enters the query's metric scope (pool tasks, pipeline
    stage workers, broadcast builds) can reach it via
    :func:`current_token` without extra plumbing. The deadline is evaluated
    lazily on every :meth:`check` — no watchdog thread."""

    __slots__ = ("query_id", "_event", "_reason", "_deadline")

    def __init__(self, query_id: str | None = None,
                 deadline_s: float | None = None):
        self.query_id = query_id
        self._event = threading.Event()
        self._reason = "cancelled"
        self._deadline = (time.monotonic() + deadline_s
                          if deadline_s and deadline_s > 0 else None)

    def cancel(self, reason: str = "cancelled") -> None:
        if not self._event.is_set():
            self._reason = reason
            self._event.set()

    @property
    def cancelled(self) -> bool:
        return (self._event.is_set()
                or (self._deadline is not None
                    and time.monotonic() >= self._deadline))

    @property
    def reason(self) -> str:
        return self._reason

    def remaining_s(self) -> float | None:
        """Seconds until the deadline (None = no deadline)."""
        if self._deadline is None:
            return None
        return self._deadline - time.monotonic()

    def check(self) -> None:
        """Raise the typed cancellation error if the token fired — the ONE
        call every cooperative blocking loop makes."""
        if self._event.is_set():
            cls = (QueryDeadlineError if self._reason == "deadline"
                   else QueryCancelledError)
            raise cls(f"query {self.query_id} {self._reason}",
                      query_id=self.query_id, reason=self._reason)
        if self._deadline is not None and time.monotonic() >= self._deadline:
            self.cancel("deadline")
            raise QueryDeadlineError(
                f"query {self.query_id} exceeded its deadline",
                query_id=self.query_id)


def current_token() -> CancelToken | None:
    """The ambient query's CancelToken (None outside any scheduled query)."""
    c = M.current_collector()
    return getattr(c, "cancel_token", None) if c is not None else None


def check_cancel() -> None:
    """Cooperative cancellation checkpoint: raises QueryCancelledError /
    QueryDeadlineError when the ambient query was cancelled. A thread-local
    read + None check when no token is armed — cheap enough for per-batch
    and per-wait-tick call sites."""
    tok = current_token()
    if tok is not None:
        tok.check()


# ---------------------------------------------------------------------------
# footprint estimation (admission input)
# ---------------------------------------------------------------------------

# defaults when no conf reaches the estimator; the knobs are
# scheduler.footprint.{decodeExpansion,floorBytes} (config.py). 3x is the
# round-number decode expansion that scans of TPC-H's Parquet showed
_DECODE_EXPANSION = 3.0
# every pipeline breaker (join build / agg / sort / exchange) holds an extra
# working set of roughly one batch stream alongside the scan
_BREAKER_FACTOR = 0.5
_MIN_FOOTPRINT = 16 << 20


def _static_footprint(plan, conf=None) -> int:
    """The cold-start heuristic: sum of on-disk scan bytes x decode
    expansion, scaled by (1 + 0.5 x breaker count) for
    join-build/agg/sort/exchange working sets, floored (a scanless plan
    still stages batches)."""
    from spark_rapids_tpu import config as CFG
    expansion = (conf.get(CFG.SCHEDULER_FOOTPRINT_DECODE_EXPANSION)
                 if conf is not None else _DECODE_EXPANSION)
    floor = (conf.get(CFG.SCHEDULER_FOOTPRINT_FLOOR)
             if conf is not None else _MIN_FOOTPRINT)
    scan_bytes = 0
    breakers = 0
    seen = set()
    stack = [plan]
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        name = type(node).__name__
        if name in ("JoinNode", "AggregateNode", "SortNode", "ExchangeNode",
                    "WindowNode"):
            breakers += 1
        parts = getattr(node, "partitions", None)
        if parts is not None and name == "FileScanNode":
            for p in parts:
                for path in getattr(p, "paths", ()):
                    try:
                        scan_bytes += os.path.getsize(path)
                    except OSError:
                        pass
        stack.extend(getattr(node, "children", []) or [])
    est = int(scan_bytes * expansion * (1 + _BREAKER_FACTOR * breakers))
    return max(est, int(floor))


def estimate_footprint_ex(plan, conf=None) -> dict:
    """Estimated device-memory footprint of one query plus its provenance:
    {estimate, static, history_hit, fingerprint, prior}. When the plan-shape
    history store (runtime/history.py) holds an observed peak for this
    plan's fingerprint, the observation IS the estimate (floored) — observed
    beats modeled; the static heuristic remains the cold-start fallback.
    The estimate feeds admission only — the strict HBM budget + OOM ladder
    remain the hard enforcement, so a wrong estimate degrades fairness,
    never safety."""
    from spark_rapids_tpu import config as CFG
    static = _static_footprint(plan, conf)
    out = {"estimate": static, "static": static, "history_hit": False,
           "fingerprint": None, "prior": None}
    try:
        from spark_rapids_tpu.plan.fingerprint import plan_fingerprint
        out["fingerprint"] = plan_fingerprint(plan)
    except Exception:   # noqa: BLE001 — fingerprint is advisory, never fatal
        return out
    enabled = conf is None or conf.get(CFG.STATS_HISTORY_ENABLED)
    if not enabled:
        return out
    from spark_rapids_tpu.runtime import history as H
    store = H.get()
    if store is None:
        return out
    try:
        prior = store.lookup(out["fingerprint"])
    except Exception:   # noqa: BLE001 — history is advisory, never fatal
        return out
    if prior is None:
        return out
    out["prior"] = prior
    peak = int(prior.get("peak_device_bytes") or 0)
    if peak > 0:
        floor = (conf.get(CFG.SCHEDULER_FOOTPRINT_FLOOR)
                 if conf is not None else _MIN_FOOTPRINT)
        out["estimate"] = max(peak, int(floor))
        out["history_hit"] = True
        M.counter_add("history.hit")
    return out


def estimate_footprint(plan, conf=None) -> int:
    """int facade over estimate_footprint_ex (existing call sites/tests)."""
    return estimate_footprint_ex(plan, conf)["estimate"]


# ---------------------------------------------------------------------------
# the scheduler
# ---------------------------------------------------------------------------

class _Ticket:
    __slots__ = ("query_id", "estimate", "priority", "token", "enqueue_t",
                 "admitted_t", "state", "description")

    def __init__(self, query_id, estimate, priority, token, description):
        self.query_id = query_id
        self.estimate = estimate
        self.priority = priority
        self.token = token
        self.enqueue_t = time.monotonic()
        self.admitted_t = None
        self.state = "queued"
        self.description = description


class QueryScheduler:
    """Process-wide admission controller (the driver-side scheduler of
    ROADMAP item 2). Like the other process-global switches (Pallas, trace,
    faults), structural knobs are only reconfigured by a session that sets
    them EXPLICITLY; per-query values (priority, deadline, queue timeout,
    estimate) come from the submitting session's conf at submit time."""

    _instance: "QueryScheduler | None" = None
    _ilock = threading.Lock()

    def __init__(self, max_concurrent: int = 4, queue_max_depth: int = 32,
                 aging_s: float = 10.0):
        self.max_concurrent = max(1, int(max_concurrent))
        self.queue_max_depth = max(0, int(queue_max_depth))
        self.aging_s = float(aging_s)
        self._cond = threading.Condition()
        self._running: dict[str, _Ticket] = {}
        self._waiting: list[_Ticket] = []
        # lifetime counters (scheduler-scope observability; per-query shed/
        # cancel counts also land in the resilience registry)
        self.admitted = 0
        self.shed = 0
        self.demotions = 0

    # -- singleton -----------------------------------------------------------
    @classmethod
    def get(cls) -> "QueryScheduler":
        with cls._ilock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        with cls._ilock:
            cls._instance = None

    def reconfigure(self, conf) -> None:
        """Apply a session's EXPLICIT scheduler.* structural settings
        (process-global, like the Pallas/trace/faults switches)."""
        from spark_rapids_tpu import config as C
        with self._cond:
            self.max_concurrent = max(1, conf.get(C.SCHEDULER_MAX_CONCURRENT))
            self.queue_max_depth = max(0, conf.get(C.SCHEDULER_QUEUE_MAX_DEPTH))
            self.aging_s = conf.get(C.SCHEDULER_PRIORITY_AGING)
            self._cond.notify_all()

    # -- internals (under self._cond) ---------------------------------------
    @staticmethod
    def _device_budget() -> int:
        from spark_rapids_tpu.runtime.memory import DeviceManager
        dm = DeviceManager._instance
        if dm is None:
            # admission must not force device initialization; a fresh process
            # admits on concurrency alone until the device comes up
            return 1 << 62
        return dm.catalog.device_budget

    def _eff_priority(self, t: _Ticket, now: float) -> float:
        if self.aging_s <= 0:
            return float(t.priority)
        return t.priority + (now - t.enqueue_t) / self.aging_s

    def _head(self, now: float) -> "_Ticket | None":
        if not self._waiting:
            return None
        return min(self._waiting,
                   key=lambda t: (-self._eff_priority(t, now), t.enqueue_t))

    def _admitted_bytes(self) -> int:
        return sum(t.estimate for t in self._running.values())

    def _admissible(self, t: _Ticket) -> bool:
        if len(self._running) >= self.max_concurrent:
            return False
        if not self._running:
            return True   # progress guarantee: an idle engine admits anything
        return self._admitted_bytes() + t.estimate <= self._device_budget()

    def _backoff_hint(self, t: _Ticket, now: float) -> float:
        """Retry-after estimate for a shed query: half the mean admitted
        runtime so far per queue position ahead, floored at 250ms — crude,
        but monotone in load, which is what a client backoff needs."""
        ahead = sum(1 for w in self._waiting
                    if self._eff_priority(w, now) >= self._eff_priority(t, now)
                    and w is not t)
        run_s = [now - r.admitted_t for r in self._running.values()
                 if r.admitted_t is not None]
        mean_run = (sum(run_s) / len(run_s)) if run_s else 1.0
        return round(max(0.25, 0.5 * mean_run * (1 + ahead)), 3)

    # -- submission lifecycle -------------------------------------------------
    def submit(self, query_id: str, estimate: int, *, priority: int = 0,
               token: CancelToken | None = None,
               timeout_s: float | None = None,
               description: str = "") -> _Ticket:
        """Block until admitted; raises QueryRejectedError when shed (queue
        full / wait past timeout_s) and QueryCancelledError /
        QueryDeadlineError when the token fires while queued."""
        from spark_rapids_tpu.runtime import eventlog as EL
        t = _Ticket(query_id, max(0, int(estimate)), int(priority), token,
                    description)
        queued_emitted = False
        with self._cond:
            if len(self._waiting) >= self.queue_max_depth > 0:
                self.shed += 1
                M.resilience_add(QUERIES_SHED)
                hint = self._backoff_hint(t, time.monotonic())
                EL.emit("query.shed", query=query_id, reason="queue_full",
                        queue_depth=len(self._waiting),
                        backoff_hint_s=hint)
                raise QueryRejectedError(
                    f"query {query_id} shed: admission queue full "
                    f"({len(self._waiting)} >= "
                    f"scheduler.queue.maxDepth={self.queue_max_depth}); "
                    f"retry after ~{hint}s",
                    backoff_hint_s=hint, query_id=query_id,
                    reason="queue_full")
            self._waiting.append(t)
            try:
                while True:
                    now = time.monotonic()
                    if self._head(now) is t and self._admissible(t):
                        self._waiting.remove(t)
                        self._running[query_id] = t
                        t.state = "running"
                        t.admitted_t = now
                        self.admitted += 1
                        break
                    if token is not None and token.cancelled:
                        self._waiting.remove(t)
                        self._cond.notify_all()
                        token.check()   # raises the typed error
                    waited = now - t.enqueue_t
                    if timeout_s is not None and 0 < timeout_s <= waited:
                        self._waiting.remove(t)
                        self._cond.notify_all()
                        self.shed += 1
                        M.resilience_add(QUERIES_SHED)
                        hint = self._backoff_hint(t, now)
                        EL.emit("query.shed", query=query_id,
                                reason="queue_timeout",
                                waited_s=round(waited, 4),
                                backoff_hint_s=hint)
                        raise QueryRejectedError(
                            f"query {query_id} shed after queueing "
                            f"{waited:.2f}s (scheduler.queue.timeoutSeconds="
                            f"{timeout_s}); retry after ~{hint}s",
                            backoff_hint_s=hint, query_id=query_id,
                            reason="queue_timeout")
                    if not queued_emitted:
                        queued_emitted = True
                        EL.emit("query.queued", query=query_id,
                                estimate_bytes=t.estimate,
                                priority=t.priority,
                                running=len(self._running),
                                queue_depth=len(self._waiting))
                    self._cond.wait(0.05)
            except BaseException:
                self._cond.notify_all()
                raise
            waited = time.monotonic() - t.enqueue_t
            running = len(self._running)
        # admission queue-wait distribution (STATS histograms, which
        # benchmark/run.py's admission_wait_ms reads): once per admitted query
        M.histogram("admission.wait").observe(waited)
        EL.emit("query.admitted", query=query_id,
                estimate_bytes=t.estimate, priority=t.priority,
                waited_s=round(waited, 4), running=running,
                description=description)
        return t

    def release(self, query_id: str) -> None:
        with self._cond:
            self._running.pop(query_id, None)
            self._cond.notify_all()

    def cancel(self, query_id: str, reason: str = "cancelled") -> bool:
        """Flip the query's CancelToken (running or still queued); the query
        observes it at its next cooperative checkpoint. Returns False for an
        unknown/finished query id."""
        with self._cond:
            t = self._running.get(query_id)
            if t is None:
                t = next((w for w in self._waiting
                          if w.query_id == query_id), None)
            if t is None or t.token is None:
                return False
            t.token.cancel(reason)
            self._cond.notify_all()
        return True

    def stats(self) -> dict:
        """Lifetime counters + instantaneous queue state for the serving
        STATS snapshot (runtime/endpoint.py): admitted/shed/demotions since
        process start, plus running and queued right now."""
        with self._cond:
            return {"admitted": self.admitted, "shed": self.shed,
                    "demotions": self.demotions,
                    "running": len(self._running),
                    "queued": len(self._waiting),
                    "max_concurrent": self.max_concurrent}

    def active_queries(self) -> list:
        """[{query, state, estimate_bytes, priority, waited_s|running_s}]
        for every queued or running query — the serving endpoint's ps."""
        now = time.monotonic()
        with self._cond:
            out = []
            for t in self._running.values():
                out.append({"query": t.query_id, "state": "running",
                            "estimate_bytes": t.estimate,
                            "priority": t.priority,
                            "description": t.description,
                            "running_s": round(now - (t.admitted_t or now), 4)})
            for t in self._waiting:
                out.append({"query": t.query_id, "state": "queued",
                            "estimate_bytes": t.estimate,
                            "priority": t.priority,
                            "description": t.description,
                            "waited_s": round(now - t.enqueue_t, 4)})
            return out

    # -- OOM escalation hooks (called from runtime/retry.py) ------------------
    def on_oom_retry(self, query_id: str | None = None) -> int:
        """The retry ladder hit a retryable device OOM. Two duties:

        1. **Fair-share demotion**: when the faulting query is at/under its
           fair share (budget / running count) and a peer is over its own,
           spill the victim's spillable device buffers — the peer pays with
           a (recoverable) unspill, not the under-share faulting query with
           splits. Victim = (lowest priority, most device bytes).
        2. **Admission re-check**: when admitted estimates exceed the
           budget (over-admission), briefly wait for a peer to release
           before retrying — bounded to 1s and token-interruptible, so it
           can improve the retry's odds but never deadlock.

        Returns bytes demoted (0 when no rebalance applied)."""
        qid = query_id if query_id is not None else M.current_query_id()
        if qid is None:
            return 0
        from spark_rapids_tpu.runtime import eventlog as EL
        from spark_rapids_tpu.runtime.memory import DeviceManager
        dm = DeviceManager._instance
        victim = None
        with self._cond:
            me = self._running.get(qid)
            if me is None or len(self._running) <= 1 or dm is None:
                return 0
            cat = dm.catalog
            usage = cat.query_device_bytes()
            share = cat.device_budget / max(1, len(self._running))
            if usage.get(qid, 0) <= share:
                over = [t for t in self._running.values()
                        if t.query_id != qid
                        and usage.get(t.query_id, 0) > share
                        and t.priority <= me.priority]
                if over:
                    victim = min(over, key=lambda t: (
                        t.priority, -usage.get(t.query_id, 0)))
        demoted = 0
        if victim is not None:
            demoted = dm.catalog.spill_query_device(victim.query_id)
            if demoted:
                self.demotions += 1
                M.resilience_add(QUERY_DEMOTIONS)
                EL.emit("query.demoted", query=victim.query_id,
                        faulting_query=qid, bytes=demoted)
        # admission re-check: over-committed estimates → wait briefly for a
        # peer to finish so the retry runs against a lighter device tier
        deadline = time.monotonic() + 1.0
        with self._cond:
            while (len(self._running) > 1
                   and self._admitted_bytes() > self._device_budget()
                   and time.monotonic() < deadline):
                me = self._running.get(qid)
                if me is not None and me.token is not None:
                    me.token.check()
                self._cond.wait(0.05)
        return demoted


def on_oom_retry() -> int:
    """Module-level hook for runtime/retry.py: no-op (0) when no scheduler
    instance exists yet — the ladder must not conjure one mid-OOM."""
    sched = QueryScheduler._instance
    if sched is None:
        return 0
    return sched.on_oom_retry()
