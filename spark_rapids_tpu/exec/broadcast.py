"""BroadcastExchangeExec — standalone broadcast exchange operator.

Reference: GpuBroadcastExchangeExecBase (org/.../execution/
GpuBroadcastExchangeExec.scala:237) materializes the build side ONCE on its
own broadcast thread pool with a timeout, serializes the contiguous table,
and every consumer (broadcast hash join, nested-loop join, AQE reuse) reads
the same relation; GpuBroadcastToCpuExec bridges the relation back to the
host. Here the relation is a SpillableColumnarBatch (HBM-resident,
spillable under pressure) built by a daemon worker; `broadcast()` blocks
consumers on the shared future with `spark.sql.broadcastTimeout` semantics,
and `execute_partition` is the host-bridge path (one single-partition
stream of the relation).
"""

from __future__ import annotations

import concurrent.futures
import threading

from spark_rapids_tpu import config as CFG
from spark_rapids_tpu.exec.base import TaskContext, TpuExec
from spark_rapids_tpu.exec.coalesce import concat_all
from spark_rapids_tpu.runtime import faults as F
from spark_rapids_tpu.runtime import memory as mem
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import retry as R
from spark_rapids_tpu.runtime import tracing
from spark_rapids_tpu.runtime.tracing import trace_range

class BroadcastTimeout(RuntimeError):
    pass


def _spawn_build(fn) -> concurrent.futures.Future:
    """One dedicated daemon thread per broadcast build (like Spark's
    relation-future threads). A bounded shared pool would deadlock when a
    build side itself contains broadcast joins: outer builds could occupy
    every worker while blocking on inner builds stuck in the queue."""
    fut: concurrent.futures.Future = concurrent.futures.Future()

    def run():
        if not fut.set_running_or_notify_cancel():
            return
        try:
            fut.set_result(fn())
        except BaseException as e:  # noqa: BLE001 — future carries it
            fut.set_exception(e)

    threading.Thread(target=run, name="tpu-broadcast", daemon=True).start()
    return fut


class BroadcastExchangeExec(TpuExec):
    """Materialize the child once as a shared, spillable device relation."""

    def __init__(self, child: TpuExec, conf=None):
        super().__init__(child, conf=conf)
        self._build_time = self.metrics.metric(M.BUILD_TIME, M.ESSENTIAL)
        self._lock = threading.Lock()
        self._future: concurrent.futures.Future | None = None
        t = float(self.conf.get(CFG.BROADCAST_TIMEOUT))
        self._timeout = t if t > 0 else None  # <=0 waits forever
        self._max_bytes = self.conf.get(CFG.BROADCAST_MAX_TABLE_BYTES)

    @property
    def output(self):
        return self.child.output

    @property
    def num_partitions(self) -> int:
        return 1

    def _materialize(self) -> mem.SpillableColumnarBatch:
        # "joins.build" fault scope: in the default (non-mesh) plan every
        # equi-join builds through this exchange, so join-build OOM chaos
        # specs target the broadcast materialization; the coalesce layer's
        # registration retry splits over-budget input batches, and the final
        # single-batch registration gets a spill-only retry
        with trace_range("BroadcastExchange.build", self._build_time), \
                F.scope("joins.build"):
            from spark_rapids_tpu.columnar.batch import on_one_device

            def child_batches():
                for split in range(self.child.num_partitions):
                    with TaskContext():
                        yield from self.child.execute_partition(split)

            # partitions of a mesh exchange lie one a chip: the relation is
            # built on the first batch's (nothing to move on one device)
            batches = list(on_one_device(child_batches()))
            batch = concat_all(iter(batches), self.child.output,
                               conf=self.conf)
            size = batch.device_memory_size()
            if self._max_bytes and size > self._max_bytes:
                raise RuntimeError(
                    f"broadcast table {size} bytes exceeds "
                    f"{CFG.BROADCAST_MAX_TABLE_BYTES.key}={self._max_bytes} "
                    "(reference maxBroadcastTableSize guard)")
            return R.call_with_retry(
                lambda: mem.SpillableColumnarBatch(
                    batch, mem.ACTIVE_BATCHING_PRIORITY),
                scope="joins.build")

    def broadcast(self) -> mem.SpillableColumnarBatch:
        """The shared relation; first caller schedules the build, everyone
        blocks on the same future (reference executeBroadcast + relation
        future with broadcastTimeout)."""
        with self._lock:
            if self._future is None:
                # the build thread must re-enter the caller's query scope
                # (metrics/events attribution) and charge its wall time to
                # this node's selfTime — consumers only ever BLOCK on the
                # future, so the build is otherwise invisible to the
                # per-thread attribution frames
                collector = M.current_collector()
                parent_span = tracing.current_span()

                def build():
                    with M.collector_context(collector), \
                            M.node_frame(self._node_id,
                                         self.metrics.metric(
                                             M.BUILD_SELF_TIME,
                                             M.ESSENTIAL)), \
                            tracing.child_of(parent_span):
                        return self._materialize()

                self._future = _spawn_build(build)
            fut = self._future
        from spark_rapids_tpu.runtime.scheduler import check_cancel
        import time as _time
        deadline = (_time.monotonic() + self._timeout
                    if self._timeout is not None else None)
        # metric=None frame: the build thread charges itself; the
        # consumer's blocked wait must not double-count in its own frame.
        # The wait polls so a cancelled/deadlined query drains instead of
        # camping on a peer-started build for broadcastTimeout seconds
        with M.node_frame(self._node_id, None), \
                tracing.span("broadcast.wait"):
            while True:
                check_cancel()
                try:
                    return fut.result(timeout=0.05)
                except concurrent.futures.TimeoutError:
                    if (deadline is not None
                            and _time.monotonic() >= deadline):
                        raise BroadcastTimeout(
                            f"broadcast of {self.child.args_string()!s} did "
                            f"not finish within {self._timeout}s") from None

    def release(self) -> None:
        """Close the relation (called by the last consumer). If the build is
        still running (consumers timed out), a done-callback closes the
        relation when it lands instead of orphaning it in HBM."""
        with self._lock:
            fut, self._future = self._future, None
        if fut is None:
            return

        def close_result(f: concurrent.futures.Future):
            if f.exception() is None:
                f.result().close()

        fut.add_done_callback(close_result)

    def abort_query(self):
        """Query-death cleanup (session._run_action's exec sweep): the
        shared-broadcast reader countdown only counts readers whose
        generators STARTED — a cancelled query can abandon a stream
        partition's iterator unstarted, leaving the countdown short and the
        relation orphaned in HBM. release() is idempotent, so the sweep and
        a late last-reader countdown cannot double-close."""
        self.release()

    def execute_partition(self, split: int):
        # host-bridge / reuse path (GpuBroadcastToCpuExec analog): stream the
        # relation as a normal single-partition exec without taking ownership.
        # The batch is materialized BEFORE yielding: once device arrays are
        # referenced they outlive a concurrent release() by the last join
        # consumer; if that release closes the relation mid-acquire (spill
        # file unlinked / use-after-close), rebuild via a fresh broadcast().
        def it():
            batch = None
            for attempt in range(3):
                sb = self.broadcast()
                try:
                    batch = sb.get_batch()
                    break
                except mem.BufferClosedError:
                    if attempt == 2:
                        raise
            yield batch
        return self.wrap_output(it())

    def args_string(self):
        return f"timeout={self._timeout}s"
