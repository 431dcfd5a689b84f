"""TpuExec — base of the physical operator layer.

Reference: GpuExec.scala:40,281: base trait for all columnar operators, carrying the
metric registry, coalesce-goal declarations, and doExecuteColumnar. Here an exec is a
tree node with `execute_partition(split) -> Iterator[ColumnarBatch]`; a lightweight
local task scheduler (the stand-in for Spark's task execution — the reference
delegates scheduling to Spark itself, SURVEY.md §1) drives partitions through thread
pool tasks gated by the TpuSemaphore."""

from __future__ import annotations

import itertools
import threading
import typing

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime.semaphore import TpuSemaphore
from spark_rapids_tpu.runtime import tracing

_task_counter = itertools.count(1)
_task_local = threading.local()


def current_task_id() -> int:
    tid = getattr(_task_local, "task_id", None)
    if tid is None:
        tid = next(_task_counter)
        _task_local.task_id = tid
    return tid


class TaskContext:
    """Per-task scope: semaphore auto-release on completion (reference
    GpuSemaphore task-completion listener, GpuSemaphore.scala:58)."""

    def __init__(self):
        self.task_id = next(_task_counter)
        self._outer = None

    def __enter__(self):
        # save/restore the enclosing task id so inline nested tasks (e.g. a map
        # stage run on the calling thread) don't orphan the outer task's permit
        self._outer = getattr(_task_local, "task_id", None)
        _task_local.task_id = self.task_id
        return self

    def __exit__(self, *exc):
        TpuSemaphore.get().release_if_necessary(self.task_id)
        _task_local.task_id = self._outer
        return False


class TpuExec:
    """Base physical operator."""

    def __init__(self, *children: "TpuExec", conf: RapidsConf | None = None):
        self.children = list(children)
        self.conf = conf or (children[0].conf if children else RapidsConf())
        self.metrics = M.MetricsRegistry(self.conf.metrics_level)
        self._out_rows = self.metrics.metric(M.NUM_OUTPUT_ROWS, M.ESSENTIAL)
        self._out_batches = self.metrics.metric(M.NUM_OUTPUT_BATCHES, M.MODERATE)
        self._op_time = self.metrics.metric(M.OP_TIME, M.MODERATE)
        self._self_time = self.metrics.metric(M.SELF_TIME, M.ESSENTIAL)
        # query-scoped observability (SQL-UI analog): conversion runs inside
        # the action's QueryMetricsCollector scope, so every exec registers
        # its registry under a plan-node id at construction
        collector = M.current_collector()
        self._node_id = (collector.register(self)
                         if collector is not None else None)

    @property
    def child(self) -> "TpuExec":
        return self.children[0]

    @property
    def output(self) -> T.StructType:
        raise NotImplementedError

    @property
    def num_partitions(self) -> int:
        return self.children[0].num_partitions if self.children else 1

    def execute_partition(self, split: int) -> typing.Iterator[ColumnarBatch]:
        raise NotImplementedError

    # -- driver-side helpers -------------------------------------------------
    def execute_collect(self):
        """Run all partitions (threaded local scheduler) and collect to one arrow
        table — the test/driver path (Spark collect())."""
        import pyarrow as pa
        from concurrent.futures import ThreadPoolExecutor
        from spark_rapids_tpu.config import NUM_LOCAL_TASKS
        from spark_rapids_tpu.runtime import pipeline as P
        nthreads = max(1, min(self.conf.get(NUM_LOCAL_TASKS), self.num_partitions))
        collector = M.current_collector()
        parent_span = tracing.current_span()
        pipe_on = P.enabled(self.conf)

        def to_arrow(b):
            # one span a result batch: the device-to-host reads of its
            # columns and their Arrow arrays
            with tracing.span("collect.to_arrow", columns=b.num_cols) as sp:
                t = b.to_arrow()
                if sp:
                    sp.set(rows=t.num_rows, bytes=b.device_memory_size())
            return t

        def run(split):
            # re-enter the driving action's query scope on the pool thread so
            # metrics/events fired by operators attribute to this query
            with M.collector_context(collector), TaskContext(), \
                    tracing.child_of(parent_span):
                it = self.execute_partition(split)
                if pipe_on:
                    # final-collect pipeline segment: upstream compute runs
                    # on the stage's worker thread while this thread does the
                    # D2H arrow conversion of the previous batch
                    it = P.stage_iterator(
                        it, edge="collect", conf=self.conf,
                        registry=self.metrics, node_id=self._node_id,
                        spillable=True)
                return [to_arrow(b) for b in it]

        if self.num_partitions == 1:
            parts = [run(0)]
        else:
            with ThreadPoolExecutor(max_workers=nthreads) as pool:
                parts = list(pool.map(run, range(self.num_partitions)))
        tables = [t for p in parts for t in p]
        if not tables:
            return self.output.to_arrow().empty_table()
        return pa.concat_tables(tables)

    def wrap_output(self, it):
        """Instrument an output iterator with row/batch metrics and one
        self-time attribution frame per batch pull: time spent producing a
        batch, minus time charged by nested operator frames on this thread,
        lands in this node's selfTime (the SQL-UI op-time analog). Row counts
        accumulate LAZILY (device scalars fold in at metric read time) — a
        per-batch host sync here would serialize every operator on the
        accelerator round-trip."""
        from spark_rapids_tpu.runtime import eventlog as EL
        from spark_rapids_tpu.runtime.scheduler import check_cancel
        it = iter(it)
        while True:
            # cooperative cancellation checkpoint on EVERY operator's batch
            # pull (runtime/scheduler.py): session.cancel()/deadline expiry
            # drains the whole operator chain one batch later, no matter
            # which segment a thread is computing in
            check_cancel()
            with M.node_frame(self._node_id, self._self_time):
                try:
                    b = next(it)
                except StopIteration:
                    return
            self._out_batches.add(1)
            self._out_rows.add_lazy(b.lazy_num_rows)
            # stats plane: observed output bytes per node (array metadata
            # only — device_memory_size never syncs the device)
            M.stats_add("outputBytes", b.device_memory_size(),
                        node=self._node_id)
            if EL.enabled():
                # batch lifecycle event; never force a device sync for the
                # row count — a still-lazy count is logged as null
                n = b.lazy_num_rows
                EL.emit("batch", node=self._node_id,
                        rows=n if isinstance(n, int) else None)
            yield b

    def tree_string(self, indent=0):
        s = "  " * indent + "*" + type(self).__name__ + " " + self.args_string() + "\n"
        for c in self.children:
            s += c.tree_string(indent + 1)
        return s

    def args_string(self):
        return ""

    def __repr__(self):
        return self.tree_string().rstrip()


def acquire_semaphore(metrics: M.MetricsRegistry):
    TpuSemaphore.get().acquire_if_necessary(
        current_task_id(), metrics.metric(M.SEMAPHORE_WAIT_TIME, M.MODERATE))
