"""Basic physical operators: project / filter / range / union / limits.

Reference: basicPhysicalOperators.scala (GpuProjectExec:83, GpuFilterExec:181,
GpuRangeExec:239, GpuUnionExec:370) and limit.scala. The filter keeps the surviving
row count as a device scalar (no host sync between chained operators — see
ops/filtering.py), which is the TPU-first departure from cudf's eager compaction."""

from __future__ import annotations

import typing

import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.vector import TpuColumnVector, bucket_capacity
from spark_rapids_tpu.exec.base import TpuExec, acquire_semaphore
from spark_rapids_tpu.expr.core import EvalContext, Expression, bind_references
from spark_rapids_tpu.ops.filtering import selection_mask, compact_cols
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime.tracing import trace_range


class ProjectExec(TpuExec):
    def __init__(self, project_list: list, child: TpuExec, conf=None):
        super().__init__(child, conf=conf)
        self.project_list = [bind_references(e, child.output) for e in project_list]

    @property
    def output(self):
        return T.StructType([
            T.StructField(e.name, e.dtype, e.nullable) for e in self.project_list])

    def execute_partition(self, split):
        from spark_rapids_tpu.expr.core import Col
        from spark_rapids_tpu.expr.misc import (CONTEXT_SENSITIVE,
                                                MonotonicallyIncreasingID,
                                                Rand)
        from spark_rapids_tpu.runtime import fuse
        positional = any(
            e.collect(lambda x: isinstance(
                x, (MonotonicallyIncreasingID, Rand)))
            for e in self.project_list)
        ctx_sensitive = any(
            e.collect(lambda x: isinstance(x, CONTEXT_SENSITIVE))
            for e in self.project_list)
        exprs = self.project_list
        key = ("project", fuse.schema_key(self.child.output),
               tuple(fuse.expr_key(e) for e in exprs))

        def build():
            def kernel(cols, num_rows):
                ctx = EvalContext(cols, num_rows, cols[0].values.shape[0])
                return [e.eval(ctx) for e in exprs]
            return kernel

        def it():
            offset = 0
            for batch in self.child.execute_partition(split):
                acquire_semaphore(self.metrics)
                with trace_range("ProjectExec", self._op_time):
                    if ctx_sensitive or not batch.columns:
                        ctx = EvalContext.from_batch(batch, split, offset)
                        out = [e.eval(ctx) for e in exprs]
                    else:
                        in_cols = [Col.from_vector(c) for c in batch.columns]
                        nr = jnp.asarray(batch.lazy_num_rows, jnp.int32)
                        ctx = EvalContext.from_batch(batch, split, offset)
                        out = fuse.call_fused(
                            key, "ProjectExec", build, (in_cols, nr),
                            lambda: [e.eval(ctx) for e in exprs])
                    cols = [c.to_vector() for c in out]
                yield ColumnarBatch(cols, batch.lazy_num_rows, self.output,
                                    metadata=batch.metadata)
                if positional:  # host sync only when an expr needs positions
                    offset += int(batch.num_rows)
        return self.wrap_output(it())

    def args_string(self):
        return str(self.project_list)


class FilterExec(TpuExec):
    def __init__(self, condition: Expression, child: TpuExec, conf=None):
        super().__init__(child, conf=conf)
        self.condition = bind_references(condition, child.output)

    @property
    def output(self):
        return self.child.output

    def execute_partition(self, split):
        from spark_rapids_tpu.expr.core import Col
        from spark_rapids_tpu.expr.misc import CONTEXT_SENSITIVE
        from spark_rapids_tpu.runtime import fuse
        cond = self.condition
        ctx_sensitive = bool(
            cond.collect(lambda x: isinstance(x, CONTEXT_SENSITIVE)))
        key = ("filter", fuse.schema_key(self.child.output),
               fuse.expr_key(cond))

        def build():
            def kernel(cols, num_rows):
                cap = cols[0].values.shape[0]
                ctx = EvalContext(cols, num_rows, cap)
                pred = cond.eval(ctx)
                keep = selection_mask(pred, num_rows, cap)
                return compact_cols(ctx.cols, keep)
            return kernel

        def eager(batch):
            ctx = EvalContext.from_batch(batch, split)
            pred = cond.eval(ctx)
            keep = selection_mask(pred, ctx.num_rows, ctx.capacity)
            return compact_cols(ctx.cols, keep)

        fusion = self.conf.stage_fusion_enabled

        def it():
            for batch in self.child.execute_partition(split):
                acquire_semaphore(self.metrics)
                with trace_range("FilterExec", self._op_time):
                    if ctx_sensitive or not batch.columns:
                        new_cols, count = eager(batch)
                    else:
                        in_cols = [Col.from_vector(c) for c in batch.columns]
                        nr = jnp.asarray(batch.lazy_num_rows, jnp.int32)
                        new_cols, count = fuse.call_fused(
                            key, "FilterExec", build, (in_cols, nr),
                            lambda: eager(batch))
                        if fusion and new_cols:
                            # selective filters re-land at a right-sized
                            # capacity so downstream programs stop paying the
                            # stale one (ops/filtering.maybe_host_resize)
                            from spark_rapids_tpu.ops.filtering import \
                                maybe_host_resize
                            resized = maybe_host_resize(new_cols, count)
                            if resized is not None:
                                new_cols, count = resized
                yield ColumnarBatch([c.to_vector() for c in new_cols], count,
                                    self.output, metadata=batch.metadata)
        return self.wrap_output(it())

    def args_string(self):
        return repr(self.condition)


class RangeExec(TpuExec):
    """range(start, end, step) — generates LongType rows on device
    (reference GpuRangeExec:239)."""

    def __init__(self, start: int, end: int, step: int = 1, num_slices: int = 1,
                 conf=None, max_rows_per_batch: int = 1 << 20):
        super().__init__(conf=conf)
        self.start, self.end, self.step = start, end, step
        self.num_slices = num_slices
        self.max_rows_per_batch = max_rows_per_batch

    @property
    def output(self):
        return T.StructType([T.StructField("id", T.LONG, False)])

    @property
    def num_partitions(self):
        return self.num_slices

    def execute_partition(self, split):
        total = max(0, -(-(self.end - self.start) // self.step))
        per = -(-total // self.num_slices)
        lo = split * per
        hi = min(total, (split + 1) * per)

        def it():
            i = lo
            while i < hi:
                n = min(self.max_rows_per_batch, hi - i)
                acquire_semaphore(self.metrics)
                cap = bucket_capacity(n)
                vals = (self.start
                        + (jnp.arange(cap, dtype=jnp.int64) + i) * self.step)
                col = TpuColumnVector(
                    T.LONG, vals, jnp.arange(cap) < n)
                yield ColumnarBatch([col], n, self.output)
                i += n
        return self.wrap_output(it())

    def args_string(self):
        return f"({self.start}, {self.end}, {self.step})"


class UnionExec(TpuExec):
    """Concatenation of children partitions (reference GpuUnionExec:370)."""

    def __init__(self, *children, conf=None):
        super().__init__(*children, conf=conf)

    @property
    def output(self):
        return self.children[0].output

    @property
    def num_partitions(self):
        return sum(c.num_partitions for c in self.children)

    def execute_partition(self, split):
        for c in self.children:
            if split < c.num_partitions:
                return self.wrap_output(c.execute_partition(split))
            split -= c.num_partitions
        raise IndexError(split)


class LocalLimitExec(TpuExec):
    """Per-partition limit (reference limit.scala GpuLocalLimitExec)."""

    def __init__(self, limit: int, child, conf=None):
        super().__init__(child, conf=conf)
        self.limit = limit

    @property
    def output(self):
        return self.child.output

    def execute_partition(self, split):
        def it():
            remaining = self.limit
            for batch in self.child.execute_partition(split):
                if remaining <= 0:
                    break
                n = batch.num_rows  # host sync at the limit boundary
                if n <= remaining:
                    remaining -= n
                    yield batch
                else:
                    live = jnp.arange(batch.capacity) < remaining
                    cols = [TpuColumnVector(c.dtype,
                                            jnp.where(live, c.data,
                                                      c.dtype.default_value()),
                                            c.validity & live, c.dictionary)
                            for c in batch.columns]
                    yield ColumnarBatch(cols, remaining, batch.schema,
                                        metadata=batch.metadata)
                    remaining = 0
        return self.wrap_output(it())

    def args_string(self):
        return str(self.limit)


class GlobalLimitExec(LocalLimitExec):
    """Whole-plan limit; requires a single partition upstream (Spark plans the same
    way: GlobalLimit over a single-partition exchange)."""

    def __init__(self, limit: int, child, conf=None):
        assert child.num_partitions == 1, \
            "GlobalLimitExec requires a single-partition child (insert a " \
            "SinglePartitioner exchange first, as Spark's planner does)"
        super().__init__(limit, child, conf=conf)

    @property
    def num_partitions(self):
        return 1


class ArrowScanExec(TpuExec):
    """Leaf: scan host Arrow tables (one per partition) onto the device — the test
    data source and the HostColumnarToGpu analog."""

    def __init__(self, tables: list, schema: T.StructType | None = None, conf=None,
                 batch_rows: int | None = None):
        super().__init__(conf=conf)
        self.tables = tables
        import pyarrow as pa
        self._schema = schema or T.StructType.from_arrow(tables[0].schema)
        self.batch_rows = batch_rows

    @property
    def output(self):
        return self._schema

    @property
    def num_partitions(self):
        return len(self.tables)

    def execute_partition(self, split):
        def it():
            t = self.tables[split]
            step = self.batch_rows or max(1, t.num_rows)
            for off in range(0, max(t.num_rows, 1), step):
                sl = t.slice(off, step)
                if t.num_rows == 0 and off > 0:
                    break
                acquire_semaphore(self.metrics)
                yield ColumnarBatch.from_arrow(sl, self._schema)
        return self.wrap_output(it())
