"""Sort exec (reference GpuSortExec.scala:56). Batches within a partition are
concatenated then sorted in one fused XLA program; SortOrder carries Spark's
ASC/DESC + NULLS FIRST/LAST semantics (ops/sorting.py)."""

from __future__ import annotations

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.base import TpuExec, acquire_semaphore
from spark_rapids_tpu.expr.core import EvalContext, bind_references
from spark_rapids_tpu.ops.filtering import gather_cols
from spark_rapids_tpu.ops.sorting import SortOrder, sort_permutation
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime.tracing import trace_range

import jax
import jax.numpy as jnp


class SortExec(TpuExec):
    def __init__(self, sort_exprs: list, orders: list, child: TpuExec,
                 global_sort: bool = False, conf=None):
        """sort_exprs: expressions producing sort keys; orders: list[SortOrder].
        global_sort gathers every partition first (a total order, as Spark gets
        from range-partition + per-partition sort; out-of-core merge is the
        RangePartitioner path in the exchange layer)."""
        if global_sort and child.num_partitions > 1:
            child = _GatherAllExec(child, conf=conf)
        super().__init__(child, conf=conf)
        self.sort_exprs = [bind_references(e, child.output) for e in sort_exprs]
        self.orders = list(orders)
        self.global_sort = global_sort
        self._sort_time = self.metrics.metric(M.SORT_TIME, M.MODERATE)

    @property
    def output(self):
        return self.child.output

    def execute_partition(self, split):
        def it():
            # single-batch goal via the coalesce layer (reference
            # GpuSortExec + RequireSingleBatch): inputs accumulate in the
            # SPILL STORE — under HBM pressure earlier batches move to
            # host/disk instead of OOMing — with leak-safe close on error
            from spark_rapids_tpu.exec.coalesce import concat_all
            from spark_rapids_tpu.runtime import pipeline as P
            from spark_rapids_tpu.runtime import retry as R
            src = self.child.execute_partition(split)
            if P.enabled(self.conf):
                # sort-segment boundary: the input subtree produces on the
                # stage's worker thread while this thread accumulates the
                # single-batch goal in the spill store
                src = P.stage_iterator(
                    src, edge="sort.input", conf=self.conf,
                    registry=self.metrics,
                    node_id=getattr(self.child, "_node_id", None),
                    spillable=True)
            batch = concat_all(src, self.child.output, conf=self.conf)
            if batch.num_rows == 0:
                return
            acquire_semaphore(self.metrics)

            def run_sort():
                with trace_range("SortExec", self._sort_time):
                    from spark_rapids_tpu.expr.core import Col
                    from spark_rapids_tpu.expr.misc import CONTEXT_SENSITIVE
                    from spark_rapids_tpu.runtime import fuse
                    exprs, orders = self.sort_exprs, self.orders
                    ctx_sensitive = any(
                        e.collect(lambda x: isinstance(x, CONTEXT_SENSITIVE))
                        for e in exprs)

                    def kernel(cols, num_rows):
                        cap = cols[0].values.shape[0]
                        ctx = EvalContext(cols, num_rows, cap)
                        with jax.named_scope("sort_keys"):
                            key_cols = [e.eval(ctx) for e in exprs]
                        perm = sort_permutation(key_cols, orders, num_rows, cap)
                        live = jnp.arange(cap, dtype=jnp.int32) < num_rows
                        return gather_cols(ctx.cols, perm, live)

                    if ctx_sensitive or not batch.columns:
                        ctx = EvalContext.from_batch(batch, split)
                        key_cols = [e.eval(ctx) for e in exprs]
                        perm = sort_permutation(key_cols, orders, ctx.num_rows,
                                                ctx.capacity)
                        live = (jnp.arange(ctx.capacity, dtype=jnp.int32)
                                < ctx.num_rows)
                        return gather_cols(ctx.cols, perm, live)
                    key = ("sort", fuse.schema_key(self.child.output),
                           tuple(fuse.expr_key(e) for e in exprs),
                           tuple(repr(o) for o in orders))
                    in_cols = [Col.from_vector(c) for c in batch.columns]
                    nr = jnp.asarray(batch.lazy_num_rows, jnp.int32)
                    return fuse.call_fused(key, "SortExec", lambda: kernel,
                                           (in_cols, nr),
                                           lambda: kernel(in_cols, nr))

            # the total sort needs the whole batch (its inputs already sit
            # spill-protected in the catalog while accumulating) — an OOM
            # here gets spill-only retries (withRetryNoSplit)
            cols = R.call_with_retry(run_sort, scope="sort.sort")
            yield ColumnarBatch([c.to_vector() for c in cols],
                                batch.lazy_num_rows, self.output)
        return self.wrap_output(it())

    def args_string(self):
        return str(list(zip(self.sort_exprs, self.orders)))


class TakeOrderedAndProjectExec(TpuExec):
    """limit + sort + project (reference GpuTakeOrderedAndProjectExec, limit.scala).
    Sorts each partition, takes the first `limit` rows, then the driver merges."""

    def __init__(self, limit: int, sort_exprs, orders, project_list, child, conf=None):
        super().__init__(child, conf=conf)
        self.limit = limit
        self.sort_exprs = sort_exprs
        self.orders = orders
        self.project_list = project_list

    @property
    def output(self):
        from spark_rapids_tpu.exec.basic import ProjectExec
        if self.project_list:
            tmp = ProjectExec(self.project_list, self.child, conf=self.conf)
            return tmp.output
        return self.child.output

    @property
    def num_partitions(self):
        return 1

    def execute_partition(self, split):
        from spark_rapids_tpu.exec.basic import LocalLimitExec, ProjectExec
        inner = SortExec(self.sort_exprs, self.orders, _GatherAllExec(self.child),
                         conf=self.conf)
        plan: TpuExec = LocalLimitExec(self.limit, inner, conf=self.conf)
        if self.project_list:
            plan = ProjectExec(self.project_list, plan, conf=self.conf)
        return self.wrap_output(plan.execute_partition(0))


class _GatherAllExec(TpuExec):
    """Pulls every child partition into one (driver-side single partition)."""

    def __init__(self, child, conf=None):
        super().__init__(child, conf=conf)

    @property
    def output(self):
        return self.child.output

    @property
    def num_partitions(self):
        return 1

    def execute_partition(self, split):
        # partitions of a mesh exchange lie one a chip: what is gathered goes
        # to the chip of the first batch, once (nothing on one device)
        from spark_rapids_tpu.columnar.batch import on_one_device
        return on_one_device(
            b for p in range(self.child.num_partitions)
            for b in self.child.execute_partition(p))
