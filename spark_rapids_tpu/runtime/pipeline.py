"""Pipelined executor runtime — bounded, memory-budgeted producer/consumer
stages.

The reference engine gets stage overlap for free: CUDA kernel launches are
asynchronous on streams and UCX runs an async progress thread (SURVEY.md L0),
so its pull-based iterator chain still pipelines at the hardware level. Here
XLA dispatch is synchronous per program and host arrow decode shares the
query thread, so the engine was overhead-bound — parquet decode,
device compute and exchange serialization ran strictly sequentially
(an XLA:CPU profile). This module supplies the missing concurrency
EXPLICITLY: physical plans are cut into segments at the existing pipeline
breakers (scan, exchange map/reduce, join build, sort, final collect) and
each segment's batch loop runs on its own worker thread, connected by
:class:`BoundedBatchQueue` edges whose capacity is counted in BYTES as well
as batches. Queued device batches are registered as spillable with the
buffer catalog, so the task-scoped OOM ladder (runtime/retry.py) can steal
them under memory pressure exactly like any other on-deck batch.

Contracts:

- **Attribution** (the PR 3 pool-thread pattern, exec/base.py): the producer
  thread re-enters the creating query's metric scope, so operator frames
  executed there keep attributing self time to their plan nodes; the
  consumer's blocking waits ride a metric-less ``node_frame`` and are
  therefore SUBTRACTED from the consuming operator's selfTime (the producer
  charges its own work on its own thread — never both).
- **Observability**: every edge owns ``queueWaitTime:<edge>`` (consumer
  blocked on an empty queue), ``queueFullTime:<edge>`` (producer blocked on
  a full one) and ``queueDepthPeak:<edge>`` metrics on the consuming exec's
  registry, plus bounded ``pipeline.stall`` span events in the event log;
  tools/profiler.py aggregates both into a per-edge stall table.
- **Admission control**: a producer NEVER holds a TpuSemaphore permit while
  blocked on a full queue (the consumer may need that permit to drain it) —
  the permit is released before the wait and re-acquired by the operators'
  usual per-batch ``acquire_if_necessary`` calls.
- **Failure**: a producer-thread error (including injected faults from
  runtime/faults.py — the queue put/get hooks check the ``pipeline.put`` /
  ``pipeline.get`` sites) cancels the stage, drains and unregisters queued
  spillable batches, and re-raises the ORIGINAL exception at the consumer's
  position in the stream. Closing the consumer early (limit, downstream
  error) releases the producer instead of leaking it on a full queue.
"""

from __future__ import annotations

import collections
import threading
import time
import typing
import weakref

from spark_rapids_tpu import config as C
from spark_rapids_tpu.runtime import faults as F
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import tracing
from spark_rapids_tpu.runtime.scheduler import check_cancel as _check_cancel

# waits shorter than this are scheduling noise, not stalls; longer ones emit
# a pipeline.stall span event, capped per queue so a persistently starved
# edge cannot flood the event log
_STALL_EVENT_THRESHOLD_NS = 5_000_000
_STALL_EVENTS_PER_QUEUE = 32


def enabled(conf) -> bool:
    """Is the pipelined executor on (spark.rapids.tpu.pipeline.enabled)?"""
    return conf is not None and conf.get(C.PIPELINE_ENABLED)


def _size_of(item) -> int:
    """Bytes one queued item accounts for: arrow tables by nbytes, device
    batches by device footprint, spillable handles by registered size."""
    nb = getattr(item, "nbytes", None)
    if isinstance(nb, int):
        return nb
    if callable(nb):
        try:
            return int(nb())
        except Exception:
            return 0
    dm = getattr(item, "device_memory_size", None)
    if callable(dm):
        try:
            return int(dm())
        except Exception:
            return 0
    size = getattr(item, "size", None)
    return size if isinstance(size, int) else 0


class BoundedBatchQueue:
    """One pipeline edge: a bounded queue counted in items AND bytes.

    The byte budget has the same progress guarantee as the scan readahead it
    replaces: one oversized item is always accepted when the queue is empty,
    so a single huge batch can never deadlock the stage. ``close()`` is the
    consumer-side cancel — it unblocks the producer (put returns False) and
    drops queued items through a cleanup callback so spillable registrations
    never leak.
    """

    def __init__(self, edge: str, depth: int, max_bytes,
                 registry: "M.MetricsRegistry | None" = None,
                 stall_metric=None):
        self.edge = edge
        self.depth = max(1, int(depth))
        self.max_bytes = max_bytes  # None / inf = unbounded bytes
        self._cond = threading.Condition()
        self._items: collections.deque = collections.deque()
        self._bytes = 0
        self._done = False
        self._error: BaseException | None = None
        self._closed = False
        self.peak_bytes = 0
        self.peak_depth = 0
        self._stall_events_left = _STALL_EVENTS_PER_QUEUE
        if registry is not None:
            self._wait = registry.metric(f"{M.QUEUE_WAIT_TIME}:{edge}",
                                         M.MODERATE)
            self._full = registry.metric(f"{M.QUEUE_FULL_TIME}:{edge}",
                                         M.MODERATE)
            self._depth_gauge = registry.metric(
                f"{M.QUEUE_DEPTH_PEAK}:{edge}", M.MODERATE)
        else:
            self._wait = self._full = self._depth_gauge = None
        self._stall = stall_metric

    # -- producer side -------------------------------------------------------
    def put(self, item, nbytes: int | None = None) -> bool:
        """Enqueue one item; blocks while the queue is over depth or byte
        budget. Returns False when the consumer closed the stage (the
        producer must stop and discard `item`)."""
        F.maybe_inject_any(f"pipeline.put.{self.edge}")
        F.maybe_inject_any("pipeline.put")
        nb = _size_of(item) if nbytes is None else nbytes
        t0 = None
        with self._cond:
            while not self._closed and self._items and (
                    len(self._items) >= self.depth
                    or (self.max_bytes is not None
                        and self._bytes + nb > self.max_bytes)):
                # cooperative cancellation: a producer parked on a full edge
                # must observe session.cancel()/deadline expiry — the raise
                # propagates through produce()'s fail() path so the consumer
                # sees the SAME typed error (runtime/scheduler.py)
                _check_cancel()
                if t0 is None:
                    t0 = time.perf_counter_ns()
                    self._release_device_permit()
                self._cond.wait(0.05)
            if self._closed:
                return False
            self._items.append((item, nb))
            self._bytes += nb
            self.peak_bytes = max(self.peak_bytes, self._bytes)
            self.peak_depth = max(self.peak_depth, len(self._items))
            if self._depth_gauge is not None:
                self._depth_gauge.set(self.peak_depth)
            self._cond.notify_all()
        # live process-wide occupancy (the STATS endpoint's pipeline gauges);
        # outside the queue lock — the gauge has its own
        M.add_gauge("pipeline.queued.batches", 1)
        M.add_gauge("pipeline.queued.bytes", nb)
        if t0 is not None:
            dt = time.perf_counter_ns() - t0
            if self._full is not None:
                self._full.add(dt)
            self._maybe_stall_event("producer", dt)
        return True

    def finish(self) -> None:
        with self._cond:
            self._done = True
            self._cond.notify_all()

    def fail(self, exc: BaseException) -> None:
        """Producer error: queued items still drain in order, then the
        consumer's next get() re-raises `exc`."""
        with self._cond:
            self._error = exc
            self._done = True
            self._cond.notify_all()

    # -- consumer side -------------------------------------------------------
    def get(self):
        """('item', x) or ('done', None); re-raises the producer's error
        once every item queued before it was consumed."""
        F.maybe_inject_any(f"pipeline.get.{self.edge}")
        F.maybe_inject_any("pipeline.get")
        t0 = None
        err = None
        with self._cond:
            while (not self._items and not self._done and not self._closed):
                # symmetric to put(): a consumer starved on an empty queue
                # observes cancellation directly (its finally closes the
                # edge, which unblocks and stops the producer)
                _check_cancel()
                if t0 is None:
                    t0 = time.perf_counter_ns()
                    # symmetric to put(): a consumer blocked on an empty
                    # queue must not sit on a permit its producer needs
                    self._release_device_permit()
                self._cond.wait(0.05)
            if self._items:
                item, nb = self._items.popleft()
                self._bytes -= nb
                self._cond.notify_all()
                M.add_gauge("pipeline.queued.batches", -1)
                M.add_gauge("pipeline.queued.bytes", -nb)
                out = ("item", item)
            elif self._error is not None:
                err = self._error
                out = None
            else:
                out = ("done", None)
        if t0 is not None:
            dt = time.perf_counter_ns() - t0
            if self._wait is not None:
                self._wait.add(dt)
            if self._stall is not None:
                self._stall.add(dt)
            self._maybe_stall_event("consumer", dt)
        if out is None:
            raise err
        return out

    def close(self, cleanup=None) -> None:
        """Cancel the edge: producer puts start returning False and queued
        items are dropped through `cleanup` (idempotent)."""
        with self._cond:
            self._closed = True
            items = list(self._items)
            self._items.clear()
            self._bytes = 0
            self._cond.notify_all()
        for item, nb in items:
            M.add_gauge("pipeline.queued.batches", -1)
            M.add_gauge("pipeline.queued.bytes", -nb)
            if cleanup is not None:
                try:
                    cleanup(item)
                except Exception:   # noqa: BLE001 — cleanup must not mask
                    pass

    # -- internals -----------------------------------------------------------
    @staticmethod
    def _release_device_permit() -> None:
        # never block on a full queue holding a device permit: with
        # concurrentTpuTasks=N, N blocked producers would starve the very
        # consumers that must drain them (deadlock). Operators re-acquire
        # per batch via acquire_if_necessary, so dropping it here is safe.
        from spark_rapids_tpu.runtime.semaphore import TpuSemaphore
        TpuSemaphore.get().release_current()

    def _maybe_stall_event(self, side: str, dt_ns: int) -> None:
        if dt_ns < _STALL_EVENT_THRESHOLD_NS or self._stall_events_left <= 0:
            return
        self._stall_events_left -= 1
        tracing.span_event("pipeline.stall", edge=self.edge, side=side,
                           wait_ms=round(dt_ns / 1e6, 3))


def _spillable_ok(batch) -> bool:
    """Only plain fixed-layout device columns round-trip through the spill
    tiers; anything else (list vectors, host bridges) stays unregistered and
    is bounded by the queue's byte budget alone."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.vector import TpuColumnVector
    return (isinstance(batch, ColumnarBatch)
            and all(type(c) is TpuColumnVector for c in batch.columns))


def stage_iterator(gen, *, edge: str, conf=None, registry=None, node_id=None,
                   self_time_metric=None, stall_metric=None,
                   spillable: bool = False, depth: int | None = None,
                   max_bytes=None, _queue_cb=None) -> typing.Iterator:
    """Run `gen` on its own worker thread behind a BoundedBatchQueue and
    return an order-preserving iterator over its items.

    - `depth` / `max_bytes` default to pipeline.queueDepth /
      pipeline.maxQueueBytes (the byte cap additionally shrinks to the spill
      catalog's free host headroom — runtime/memory.host_prefetch_budget).
    - `spillable=True` registers device batches with the buffer catalog
      while queued (under the OOM split-retry ladder, so an over-budget
      registration spills others and may split the batch into pieces).
    - `node_id`/`self_time_metric`: plan-node attribution — producer work is
      charged there on the worker thread, consumer waits are subtracted from
      the enclosing operator frame.
    - `stall_metric`: extra metric accumulating consumer wait ns (the scan
      decode edge feeds readaheadStallTime through this).
    """
    from spark_rapids_tpu.exec.base import TaskContext

    if depth is None:
        depth = (conf.get(C.PIPELINE_QUEUE_DEPTH) if conf is not None
                 else C.PIPELINE_QUEUE_DEPTH.default)
    if max_bytes is None:
        cap = (conf.get(C.PIPELINE_MAX_QUEUE_BYTES) if conf is not None
               else C.PIPELINE_MAX_QUEUE_BYTES.default)
        from spark_rapids_tpu.runtime.memory import host_prefetch_budget
        max_bytes = host_prefetch_budget(cap)
    q = BoundedBatchQueue(edge, depth, max_bytes, registry=registry,
                          stall_metric=stall_metric)
    if _queue_cb is not None:
        _queue_cb(q)
    collector = M.current_collector()
    parent_span = tracing.current_span()
    frame_producer = node_id is not None or self_time_metric is not None

    def produce():
        from spark_rapids_tpu.runtime import memory as mem
        from spark_rapids_tpu.runtime import retry as R
        it = iter(gen)
        try:
            # one span per segment run: the srt-pipe-<edge> thread becomes
            # its own lane in the merged Perfetto timeline (trace id via the
            # re-entered collector scope, or the executor's process trace;
            # parent = the span open where the segment was set up)
            with M.collector_context(collector), TaskContext(), \
                    tracing.span(f"pipeline.{edge}", parent=parent_span):
                while True:
                    # segment batch loops are the issue's canonical
                    # cancellation points: one check per produced item
                    _check_cancel()
                    if frame_producer:
                        with M.node_frame(node_id, self_time_metric):
                            try:
                                item = next(it)
                            except StopIteration:
                                break
                    else:
                        try:
                            item = next(it)
                        except StopIteration:
                            break
                    if spillable and _spillable_ok(item):
                        ok = True
                        # heap-profiler attribution: queued device batches
                        # are held by the queue edge, not the producing
                        # operator (which already closed its frame)
                        with mem.alloc_site("pipeline.queue"):
                            sbs = R.register_with_retry(
                                item, mem.ACTIVE_ON_DECK_PRIORITY, conf=conf)
                        for sb in sbs:
                            if ok:
                                ok = q.put(sb, sb.size)
                            if not ok:
                                sb.close()
                        if not ok:
                            return
                    elif not q.put(item):
                        return
                q.finish()
        except BaseException as e:   # noqa: BLE001 — re-raised at consumer
            q.fail(e)
        finally:
            # run the source generator's finalizers ON THIS THREAD even when
            # the consumer cancelled mid-stream (shuffle read accounting,
            # nested stage teardown, spillable closes all live in them)
            close = getattr(it, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:   # noqa: BLE001
                    pass

    t = threading.Thread(target=produce, daemon=True,
                         name=f"srt-pipe-{edge}")

    def consume():
        from spark_rapids_tpu.runtime.memory import SpillableColumnarBatch
        try:
            while True:
                # metric-less frame: the wait is charged by the producer's
                # own frames on its thread; the enclosing operator frame
                # subtracts this dt from its selfTime
                with M.node_frame(node_id, None):
                    kind, item = q.get()
                if kind == "done":
                    return
                if isinstance(item, SpillableColumnarBatch):
                    batch = item.get_batch()
                    item.close()
                    yield batch
                else:
                    yield item
        finally:
            q.close(_cleanup_item)

    out = consume()
    # a consumer that is never started (abandoned before the first next())
    # skips its finally block entirely — the GC finalizer still cancels the
    # queue so the producer can never idle forever against a full edge
    weakref.finalize(out, q.close, _cleanup_item)
    t.start()
    return out


def _cleanup_item(item) -> None:
    close = getattr(item, "close", None)
    if close is not None:
        close()
