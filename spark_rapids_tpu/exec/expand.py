"""Expand exec — each input row emits one row per projection (rollup/cube/
grouping-sets building block).

Reference: GpuExpandExec.scala (194 LoC): evaluates k projections per batch and
interleaves them. TPU-native: evaluate all k projections at the padded capacity,
stack to (cap, k) and reshape row-major — one fused XLA program, and the
interleaved layout (r0p0, r0p1, …) matches Spark's output order exactly."""

from __future__ import annotations

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.vector import bucket_capacity
from spark_rapids_tpu.exec.base import TpuExec, acquire_semaphore
from spark_rapids_tpu.expr.core import Col, EvalContext, bind_references
from spark_rapids_tpu.ops.filtering import slice_to_capacity
from spark_rapids_tpu.ops.strings import align_many
from spark_rapids_tpu.runtime.tracing import trace_range


class ExpandExec(TpuExec):
    def __init__(self, projections: list, out_schema: T.StructType,
                 child: TpuExec, conf=None):
        super().__init__(child, conf=conf)
        self.projections = [[bind_references(e, child.output) for e in proj]
                            for proj in projections]
        k = len(self.projections)
        assert k >= 1 and all(len(p) == len(out_schema) for p in self.projections)
        self._out = out_schema

    @property
    def output(self):
        return self._out

    def execute_partition(self, split):
        k = len(self.projections)

        def it():
            for batch in self.child.execute_partition(split):
                acquire_semaphore(self.metrics)
                with trace_range("ExpandExec", self._op_time) as sp:
                    out = self._expand(batch, k)
                    if sp:
                        counts = {"projections": k,
                                  "capacity": batch.capacity,
                                  "capacity_out": out.capacity}
                        if isinstance(batch.lazy_num_rows, int):
                            counts.update(rows=batch.lazy_num_rows,
                                          rows_out=batch.lazy_num_rows * k)
                        sp.set(**counts)
                yield out
        return self.wrap_output(it())

    def _expand(self, batch: ColumnarBatch, k: int) -> ColumnarBatch:
        from spark_rapids_tpu.expr.misc import CONTEXT_SENSITIVE
        from spark_rapids_tpu.runtime import fuse
        n_rows = batch.lazy_num_rows
        out_rows = n_rows * k
        # static output capacity: the host-known bucket when the row count is
        # known, else the padded worst case — either way a STATIC shape, so
        # the whole expand (k evals + interleave + re-bucket) traces as one
        # fused program keyed on it
        target = bucket_capacity(out_rows if isinstance(out_rows, int)
                                 else batch.capacity * k)
        ctx_sensitive = any(
            e.collect(lambda x: isinstance(x, CONTEXT_SENSITIVE))
            for proj in self.projections for e in proj)
        if batch.columns and not ctx_sensitive:
            key = ("expand", fuse.schema_key(self.child.output),
                   tuple(tuple(fuse.expr_key(e) for e in proj)
                         for proj in self.projections), target)

            def build():
                def kernel(cols, num_rows):
                    ctx = EvalContext(cols, num_rows,
                                      cols[0].values.shape[0])
                    return self._expand_kernel(ctx, k, target)
                return kernel

            in_cols = [Col.from_vector(c) for c in batch.columns]
            nr = jnp.asarray(n_rows, jnp.int32)
            out_cols = fuse.call_fused(
                key, "ExpandExec", build, (in_cols, nr),
                lambda: self._expand_kernel(EvalContext.from_batch(batch),
                                            k, target))
        else:
            out_cols = self._expand_kernel(EvalContext.from_batch(batch),
                                           k, target)
        return ColumnarBatch([c.to_vector() for c in out_cols], out_rows,
                             self._out)

    def _expand_kernel(self, ctx: EvalContext, k: int, target: int):
        """Pure per-batch expand body (traceable): k projection evals, the
        row-major interleave, and the re-land at the static `target`
        capacity (downstream kernels assume power-of-two buckets)."""
        cap = ctx.capacity
        per_proj = [[e.eval(ctx) for e in proj] for proj in self.projections]
        out_rows = ctx.num_rows * k
        out_cap = cap * k
        out_cols = []
        for ci, field in enumerate(self._out):
            cols = [per_proj[p][ci] for p in range(k)]
            if any(c.is_string for c in cols):
                cols = align_many(cols)  # shared dictionary across projections
            vals = jnp.stack([c.values for c in cols], axis=1).reshape(out_cap)
            valid = jnp.stack([c.validity for c in cols],
                              axis=1).reshape(out_cap)
            live = jnp.arange(out_cap, dtype=jnp.int64) < out_rows
            out_cols.append(Col(vals, valid & live, field.data_type,
                                cols[0].dictionary))
        if target != out_cap:
            out_cols = slice_to_capacity(out_cols, None, target)
        return out_cols

    def args_string(self):
        return f"{len(self.projections)} projections"
