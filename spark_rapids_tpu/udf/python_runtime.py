"""Python UDF runtime: worker-process execution with Arrow exchange.

Reference (SURVEY.md #40): GpuArrowEvalPythonExec ships device batches to
separate Python worker processes over Arrow IPC (BatchQueue:187,
GpuArrowPythonRunner:336, python/rapids daemon/worker), throttled by
PythonWorkerSemaphore (separate from the device semaphore). Here the workers are
a process pool fed cloudpickled functions and Arrow IPC payloads; device batches
hop D2H → worker → H2D with a bounded prefetch pipeline standing in for the
BatchQueue."""

from __future__ import annotations

import concurrent.futures as futures
import io
import threading

import pyarrow as pa

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.base import TpuExec, acquire_semaphore
from spark_rapids_tpu.expr.core import Expression
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import tracing
from spark_rapids_tpu.runtime.tracing import trace_range


def _worker_eval(payload: bytes, ipc: bytes, vectorized: bool,
                 ret_arrow: bytes) -> bytes:
    """Runs inside a worker process: unpickle fn, eval over the arrow batch."""
    import cloudpickle
    import pyarrow as pa_w
    fn = cloudpickle.loads(payload)
    tbl = pa_w.ipc.open_stream(ipc).read_all()
    cols = [tbl.column(i).to_pandas() for i in range(tbl.num_columns)]
    ret_type = pa_w.ipc.open_stream(ret_arrow).read_all().schema.field(0).type
    if vectorized:
        out = fn(*cols)
        arr = pa_w.Array.from_pandas(out, type=ret_type)
    else:
        # scalar UDF: one python call per row; nulls arrive as None and the
        # function decides (Spark scalar-UDF semantics)
        lists = [tbl.column(i).to_pylist() for i in range(tbl.num_columns)]
        vals = [fn(*args) for args in zip(*lists)] if lists else []
        arr = pa_w.array(vals, type=ret_type)
    sink = pa_w.BufferOutputStream()
    out_t = pa_w.table({"r": arr})
    with pa_w.ipc.new_stream(sink, out_t.schema) as w:
        w.write_table(out_t)
    return sink.getvalue().to_pybytes()


class PythonWorkerSemaphore:
    """Bound concurrent python workers (reference PythonWorkerSemaphore.scala:41
    — deliberately separate from the device semaphore)."""

    _sem = threading.Semaphore(4)

    @classmethod
    def initialize(cls, n: int):
        cls._sem = threading.Semaphore(n)


class PythonWorkerPool:
    _instance = None
    _lock = threading.Lock()

    def __init__(self, max_workers: int = 4):
        import multiprocessing as mp
        # spawn, never fork: the parent runs multithreaded JAX, and forking
        # a threaded process intermittently dies with "Fatal Python error"
        # (the reference sidesteps this the same way — its python workers
        # are daemon-spawned fresh interpreters, python/rapids/daemon.py)
        self.pool = futures.ProcessPoolExecutor(
            max_workers=max_workers, mp_context=mp.get_context("spawn"))

    @classmethod
    def get(cls) -> "PythonWorkerPool":
        with cls._lock:
            if cls._instance is None:
                cls._instance = PythonWorkerPool()
            return cls._instance

    @classmethod
    def shutdown(cls):
        with cls._lock:
            if cls._instance is not None:
                cls._instance.pool.shutdown(wait=False)
                cls._instance = None


def _to_ipc(tbl: pa.Table) -> bytes:
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, tbl.schema) as w:
        w.write_table(tbl)
    return sink.getvalue().to_pybytes()


def _ret_schema_ipc(ret_type: T.DataType) -> bytes:
    t = pa.table({"r": pa.array([], T.to_arrow_type(ret_type))})
    return _to_ipc(t)


class PythonUDF(Expression):
    """A UDF that could not be compiled to device expressions; the planner tags
    its exec host-side, and host evaluation runs through the worker pool
    (reference GpuUserDefinedFunction fallback contract)."""

    def __init__(self, fn, children: list, return_type: T.DataType,
                 vectorized: bool = False):
        self.fn = fn
        self.children = list(children)
        self.return_type = return_type
        self.vectorized = vectorized

    @property
    def dtype(self):
        return self.return_type

    @property
    def nullable(self):
        return True

    def with_children(self, children):
        return PythonUDF(self.fn, children, self.return_type, self.vectorized)

    def eval(self, ctx):
        raise RuntimeError("PythonUDF cannot run inside a device kernel; the "
                           "planner must route it through ArrowEvalPythonExec")

    def eval_arrow(self, tbl: pa.Table) -> pa.Array:
        """Evaluate over a host arrow table of the child columns."""
        import cloudpickle
        payload = cloudpickle.dumps(self.fn)
        with PythonWorkerSemaphore._sem:
            fut = PythonWorkerPool.get().pool.submit(
                _worker_eval, payload, _to_ipc(tbl), self.vectorized,
                _ret_schema_ipc(self.return_type))
            out_ipc = fut.result()
        return pa.ipc.open_stream(out_ipc).read_all().column(0)

    def __repr__(self):
        name = getattr(self.fn, "__name__", "fn")
        return f"python_udf:{name}({', '.join(map(repr, self.children))})"


class ArrowEvalPythonExec(TpuExec):
    """Device exec evaluating PythonUDF projections: D2H → worker → H2D with a
    bounded prefetch pipeline (reference GpuArrowEvalPythonExec + BatchQueue)."""

    def __init__(self, project_list: list, child: TpuExec, conf=None,
                 prefetch: int = 2):
        from spark_rapids_tpu.expr.core import bind_references
        super().__init__(child, conf=conf)
        self.project_list = [bind_references(e, child.output)
                             for e in project_list]
        self.prefetch = prefetch
        self._udf_time = self.metrics.metric(M.OP_TIME, M.MODERATE)

    @property
    def output(self):
        from spark_rapids_tpu.expr.core import (Alias, AttributeReference,
                                                BoundReference)
        fields = []
        for i, e in enumerate(self.project_list):
            name = (e.name if isinstance(e, (Alias, AttributeReference,
                                             BoundReference)) else f"c{i}")
            fields.append(T.StructField(name, e.dtype, e.nullable))
        return T.StructType(fields)

    def execute_partition(self, split):
        from spark_rapids_tpu.expr.core import Alias, EvalContext

        def eval_batch(batch):
            with trace_range("ArrowEvalPython", self._udf_time):
                host = batch.to_arrow()
                cols = {}
                for i, e in enumerate(self.project_list):
                    inner = e.child if isinstance(e, Alias) else e
                    fname = self.output.fields[i].name
                    if isinstance(inner, PythonUDF):
                        child_tbl = pa.Table.from_arrays(
                            [_host_eval_col(c, host)
                             for c in inner.children],
                            names=[f"a{j}"
                                   for j in range(len(inner.children))])
                        cols[fname] = inner.eval_arrow(child_tbl)
                    else:
                        cols[fname] = _host_eval_col(inner, host)
                out = pa.table(cols)
                return ColumnarBatch.from_arrow(out, self.output)

        def it():
            # prefetch threads re-enter the query scope so any event they
            # fire (spill during H2D, etc.) attributes to this query/node
            collector = M.current_collector()
            parent_span = tracing.current_span()

            def eval_in_scope(batch):
                with M.collector_context(collector), \
                        M.node_frame(self._node_id, None), \
                        tracing.child_of(parent_span):
                    return eval_batch(batch)

            pending = []
            pool = futures.ThreadPoolExecutor(max_workers=self.prefetch)
            try:
                for batch in self.child.execute_partition(split):
                    acquire_semaphore(self.metrics)
                    pending.append(pool.submit(eval_in_scope, batch))
                    while len(pending) > self.prefetch:
                        yield pending.pop(0).result()
                for f in pending:
                    yield f.result()
            finally:
                pool.shutdown(wait=False)
        return self.wrap_output(it())


def _host_eval_col(expr, tbl: pa.Table) -> pa.Array:
    from spark_rapids_tpu.plan.host_eval import eval_host
    hc = eval_host(expr, tbl)
    return pa.array(hc.data, T.to_arrow_type(hc.dtype))
