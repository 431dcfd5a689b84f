"""Window exec — sort once, segmented scans for every frame (one XLA program).

Reference: GpuWindowExec.scala:92 + GpuWindowExpression.scala (windowAggregation:
847). Each task concatenates its input, sorts by (partition keys, order keys),
derives partition/tie boundaries, then computes all window expressions with the
kernels in ops/windowing.py. The planner (conv_window) guarantees rows of one
window partition land in one task (hash exchange on partition_by)."""

from __future__ import annotations

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.base import TpuExec, acquire_semaphore
from spark_rapids_tpu.expr.core import (Alias, Col, EvalContext, bind_references)
from spark_rapids_tpu.expr.aggregates import (AggregateFunction, Average, Count,
                                              Max, Min, Sum)
from spark_rapids_tpu.expr.windows import (DenseRank, Lag, Lead, Rank, RowNumber,
                                           WindowExpression)
from spark_rapids_tpu.ops import windowing as W
from spark_rapids_tpu.ops.concat import concat_batches
from spark_rapids_tpu.ops.filtering import gather_cols
from spark_rapids_tpu.ops.sorting import (SortOrder, fold_keys, sort_folded,
                                          sort_permutation, unfold_keys,
                                          unfolded_operands)
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime.tracing import trace_range


def _unalias(e):
    return e.child if isinstance(e, Alias) else e


def supported_window_expr(we: WindowExpression) -> str | None:
    """Reason string when unsupported (used by the planner tag), else None."""
    f = we.func
    frame = we.spec.frame
    if isinstance(f, (Lead, Lag)):
        try:
            is_string = isinstance(f.children[0].dtype, T.StringType)
        except Exception:
            is_string = False
        if is_string and f.default is not None:
            return ("lead/lag over strings with a non-null default not "
                    "supported on device (default is not a dictionary code)")
        return None
    if isinstance(f, (RowNumber, Rank, DenseRank)):
        return None
    if isinstance(f, (Sum, Count, Min, Max, Average)):
        if frame.is_unbounded_to_current or frame.is_unbounded_both:
            return None
        if frame.frame_type == "rows":
            return None
        # bounded RANGE frame: Spark requires exactly one order key, and the
        # device search needs it numeric (int/long/float/double/date/decimal)
        ob = we.spec.order_by
        if len(ob) != 1:
            return ("bounded range frame needs exactly one order key, "
                    f"got {len(ob)}")
        okey_dt = ob[0][0].dtype
        if not (okey_dt.is_numeric or isinstance(okey_dt, (T.DateType,
                                                           T.TimestampType))):
            return f"range frame over non-numeric order key {okey_dt}"
        return None
    return f"window function {type(f).__name__} not supported"


class WindowExec(TpuExec):
    def __init__(self, window_exprs: list, child: TpuExec, conf=None):
        """window_exprs: Alias(WindowExpression) list; all must share one spec's
        partition/order for this exec (the planner groups them; reference
        GpuWindowExec partitions its expressions the same way)."""
        super().__init__(child, conf=conf)
        self.window_exprs = [bind_references(e, child.output)
                             for e in window_exprs]
        specs = {repr((_unalias(e).spec.partition_by,
                       _unalias(e).spec.order_by))
                 for e in self.window_exprs}
        assert len(specs) == 1, "one WindowExec handles one partition/order spec"
        self._win_time = self.metrics.metric(M.OP_TIME, M.MODERATE)

    @property
    def output(self):
        fields = list(self.child.output.fields)
        for i, e in enumerate(self.window_exprs):
            name = e.name if isinstance(e, Alias) else f"win{i}"
            fields.append(T.StructField(name, e.dtype, e.nullable))
        return T.StructType(fields)

    def execute_partition(self, split):
        def it():
            batches = list(self.child.execute_partition(split))
            if not batches:
                return
            acquire_semaphore(self.metrics)
            with trace_range("WindowExec", self._win_time) as sp:
                batch = concat_batches(batches)
                out = self._compute(batch, sp)
            yield out
        return self.wrap_output(it())

    def _compute(self, batch: ColumnarBatch, sp) -> ColumnarBatch:
        """One partition through ONE fused program (``jit_srt_WindowExec`` in
        a device trace), keyed by schema and expressions; jit keys it by
        capacity. The eager body is what ``call_fused`` falls back to."""
        from spark_rapids_tpu.expr.misc import CONTEXT_SENSITIVE
        from spark_rapids_tpu.runtime import fuse
        ctx_sensitive = any(
            e.collect(lambda x: isinstance(x, CONTEXT_SENSITIVE))
            for e in self.window_exprs)
        in_cols = [Col.from_vector(c) for c in batch.columns]
        nr = jnp.asarray(batch.lazy_num_rows, jnp.int32)
        if in_cols and not ctx_sensitive:
            n_words = self._sort_words(batch, in_cols, nr)
            key = ("window", fuse.schema_key(self.child.output),
                   tuple(fuse.expr_key(e) for e in self.window_exprs),
                   n_words)

            def build():
                return lambda cols, num_rows: self._window_kernel(
                    cols, num_rows, n_words)

            out_cols, facts = fuse.call_fused(
                key, "WindowExec", build, (in_cols, nr),
                lambda: self._window_kernel(in_cols, nr))
        else:
            ctx = EvalContext.from_batch(batch)
            out_cols, facts = self._window_kernel(ctx.cols, ctx.num_rows)
        if sp:
            counts = dict(facts.items, capacity=batch.capacity)
            if isinstance(batch.lazy_num_rows, int):
                counts["rows"] = batch.lazy_num_rows
            sp.set(**counts)
        return ColumnarBatch([c.to_vector() for c in out_cols],
                             batch.lazy_num_rows, self.output)

    def _sort_words(self, batch, in_cols, nr):
        """The sort operands the partition's keys fold into BY WHAT THEY HOLD
        (ops/sorting.fold_keys, words_for), from one reduction program and
        one host read a partition; None where the keys' types already say
        (no read), where a key cannot fold (a float) and below 2^17 slots
        (any sort is cheap there). A sum to rank by is an int64 that holds
        forty bits: folded with its partition key it sorts as three int32
        operands, unfolded as six mixed ones, and the chip's compiler takes
        its time by them (34 s against 167 for a described v5e)."""
        from spark_rapids_tpu.ops.sorting import ranged_key, words_for
        from spark_rapids_tpu.runtime import fuse, tracing
        spec0 = _unalias(self.window_exprs[0]).spec
        exprs = list(spec0.partition_by) + [e for e, _, _ in spec0.order_by]
        wide = False
        for e in exprs:
            if isinstance(e.dtype, (T.StringType, T.BooleanType)):
                continue
            if not ranged_key(e.dtype):
                return None
            wide = wide or jnp.iinfo(e.dtype.jnp_dtype).bits > 32
        if not wide or batch.capacity < (1 << 17):
            return None
        orders = self._orders(spec0)
        skey = ("window_key_stats", fuse.schema_key(self.child.output),
                tuple(fuse.expr_key(e) for e in exprs),
                tuple(repr(o) for o in orders))

        def build():
            def kernel(cols, num_rows):
                cap = cols[0].values.shape[0]
                ctx = EvalContext(cols, num_rows, cap)
                folded = fold_keys([e.eval(ctx) for e in exprs], orders,
                                   num_rows, cap, n_words=1)
                return jnp.asarray(0 if folded is None else folded.need_bits,
                                   jnp.int32)
            return kernel

        with tracing.span("sync.key_stats") as sp:
            need = int(fuse.call_fused(
                skey, "WindowExec.key_stats", build, (in_cols, nr),
                lambda: build()(in_cols, nr)))
            sp.set(rows=need, capacity=batch.capacity)
        return words_for(need) if need else None

    @staticmethod
    def _orders(spec0):
        return ([SortOrder() for _ in spec0.partition_by]
                + [SortOrder(asc, nf) for (_, asc, nf) in spec0.order_by])

    def _window_kernel(self, cols, num_rows, n_words=None):
        """Pure per-partition body (traceable): (output columns, fuse.Facts
        of the expressions and the sort's operands). ``n_words``: see
        _sort_words; the sorted keys are then read back out of the sort's
        operands (ops/sorting.unfold_keys), not gathered a key."""
        from spark_rapids_tpu.runtime.fuse import Facts
        cap = cols[0].values.shape[0] if cols else 0
        ctx = EvalContext(cols, num_rows, cap)
        spec0 = _unalias(self.window_exprs[0]).spec
        part_cols = [e.eval(ctx) for e in spec0.partition_by]
        order_cols = [e.eval(ctx) for (e, _, _) in spec0.order_by]
        keys, orders = part_cols + order_cols, self._orders(spec0)
        live = jnp.arange(cap, dtype=jnp.int32) < num_rows
        folded = fold_keys(keys, orders, num_rows, cap, n_words)
        if folded is not None:
            operands = len(folded.words)
            perm, words = sort_folded(folded)
            sorted_keys = unfold_keys(folded, words, keys, orders, live)
        else:
            operands = unfolded_operands(keys)
            perm = sort_permutation(keys, orders, num_rows, cap)
            sorted_keys = gather_cols(keys, perm, live)
        sorted_in = gather_cols(ctx.cols, perm, live)
        sorted_part = sorted_keys[:len(part_cols)]
        sorted_order = sorted_keys[len(part_cols):]

        part_boundary = self._boundaries(sorted_part, cap)
        order_boundary = part_boundary | self._boundaries(sorted_order, cap) \
            if sorted_order else part_boundary
        seg_ids = W.cumsum(part_boundary.astype(jnp.int32)) - 1

        sctx = EvalContext(sorted_in, num_rows, cap)
        bounds_memo = {}  # per-batch: partitions run concurrently in threads
        out_cols = list(sorted_in)
        for e in self.window_exprs:
            we = _unalias(e)
            out_cols.append(self._eval_window(
                we, sctx, part_boundary, order_boundary, seg_ids, cap, live,
                sorted_order, bounds_memo))
        return out_cols, Facts(exprs=len(self.window_exprs),
                               sort_operands=operands)

    @staticmethod
    def _boundaries(cols, cap) -> jnp.ndarray:
        """True where any key differs from the previous row (first row = True)."""
        b = jnp.zeros((cap,), jnp.bool_).at[0].set(True)
        for c in cols:
            prev_vals = jnp.roll(c.values, 1)
            prev_valid = jnp.roll(c.validity, 1)
            if isinstance(c.dtype, T.FractionalType):
                both_nan = jnp.isnan(c.values) & jnp.isnan(prev_vals)
                differs = ~both_nan & ~(c.values == prev_vals)
            else:
                differs = c.values != prev_vals
            b = b | differs | (c.validity != prev_valid)
        return b.at[0].set(True)

    def _eval_window(self, we, sctx, part_b, order_b, seg_ids, cap, live,
                     sorted_order, bounds_memo):
        f = we.func
        frame = we.spec.frame
        if isinstance(f, RowNumber):
            return Col(W.row_number(part_b, cap), live, T.INT)
        if isinstance(f, DenseRank):
            return Col(W.dense_rank(order_b, part_b), live, T.INT)
        if isinstance(f, Rank):
            return Col(W.rank(order_b, part_b, cap), live, T.INT)
        if isinstance(f, (Lead, Lag)):
            c = f.children[0].eval(sctx)
            off = f.offset if isinstance(f, Lead) else -f.offset
            if f.default is None:
                fill, fill_valid = jnp.asarray(
                    c.dtype.default_value(), c.values.dtype), False
            else:
                fill = jnp.asarray(f.default, c.values.dtype)
                fill_valid = True
            vals, valid = W.shift_within_partition(
                c.values, c.validity, seg_ids, off, cap, fill, fill_valid)
            return Col(vals, valid & live, c.dtype, c.dictionary)
        assert isinstance(f, AggregateFunction), f
        return self._eval_agg_window(f, we, sctx, part_b, order_b, seg_ids,
                                     cap, live, sorted_order, bounds_memo)

    def _frame_lo_hi(self, we, part_b, order_b, seg_ids, cap, sorted_order,
                     bounds_memo):
        """Per-row inclusive [lo, hi] index bounds of the frame. Every frame
        shape reduces to this; aggregates then answer range queries
        (prefix-sum differences / sparse-table gathers, ops/windowing.py).
        Memoized per batch: all expressions share one partition/order spec and
        usually repeat frames, and the range search is the priciest step."""
        frame = we.spec.frame
        cached = bounds_memo.get(frame)
        if cached is not None:
            return cached
        idx = jnp.arange(cap, dtype=jnp.int32)
        pstart = W.seg_starts(part_b)
        pend = self._partition_ends(part_b, cap)
        if frame.is_unbounded_both:
            lo, hi = pstart, pend
        elif frame.frame_type == "rows":
            if frame.is_unbounded_to_current:
                lo, hi = pstart, idx
            else:
                lo = pstart if frame.preceding is None else \
                    jnp.maximum(idx - frame.preceding, pstart)
                hi = pend if frame.following is None else \
                    jnp.minimum(idx + frame.following, pend)
        elif frame.is_unbounded_to_current:
            lo, hi = pstart, W.tie_group_ends(order_b, part_b)
        else:
            (_okey, asc, _nf) = we.spec.order_by[0]
            oc = sorted_order[0]
            lo, hi = W.range_frame_bounds(
                oc.values, oc.validity, seg_ids, asc,
                frame.preceding, frame.following, pstart, pend)
        bounds_memo[frame] = (lo, hi)
        return lo, hi

    @staticmethod
    def _range_sum(values, lo, hi):
        """Sum over [lo, hi] via one global inclusive cumsum (lo/hi never cross
        a partition, so cross-partition prefix mass cancels in the diff)."""
        cs = jnp.cumsum(values, axis=0)
        return cs[hi] - jnp.where(lo > 0, cs[jnp.maximum(lo - 1, 0)],
                                  jnp.zeros_like(cs[0]))

    def _eval_agg_window(self, f, we, sctx, part_b, order_b, seg_ids, cap,
                         live, sorted_order, bounds_memo):
        dict_ = None
        if isinstance(f, Count) and not f.children:
            vals = jnp.ones((cap,), jnp.int64)
            valid = live
            dtype = T.LONG
        else:
            c = f.children[0].eval(sctx)
            vals, valid, dtype = c.values, c.validity & live, c.dtype
            dict_ = c.dictionary
        if isinstance(f, (Min, Max)) and vals.dtype == jnp.bool_:
            vals = vals.astype(jnp.int8)  # iinfo sentinels need an int carrier

        out_dtype = f.dtype
        lo, hi = self._frame_lo_hi(we, part_b, order_b, seg_ids, cap,
                                   sorted_order, bounds_memo)
        nonempty = hi >= lo
        lo_q = jnp.where(nonempty, lo, 0)
        hi_q = jnp.where(nonempty, hi, 0)

        cnt_w = jnp.where(
            nonempty, self._range_sum(valid.astype(jnp.int64), lo_q, hi_q), 0)
        if isinstance(f, (Sum, Average, Count)):
            acc_dt = (jnp.float64 if isinstance(dtype, T.FractionalType)
                      else jnp.int64)
            data = jnp.where(valid, vals, jnp.zeros_like(vals)).astype(acc_dt)
            sum_w = self._range_sum(data, lo_q, hi_q)
            return self._finish(f, sum_w, cnt_w, None, out_dtype, live, None)

        # min/max: sparse-table range queries; Spark orders NaN as the LARGEST
        # value — min ignores NaN unless the frame is all-NaN, max returns NaN
        # as soon as the frame contains one
        if isinstance(dtype, T.FractionalType):
            nan = jnp.isnan(vals)
            nan_w = self._range_sum((valid & nan).astype(jnp.int32), lo_q, hi_q)
            nonnan_w = self._range_sum((valid & ~nan).astype(jnp.int32),
                                       lo_q, hi_q)
            eff_valid = valid & ~nan
            sent = jnp.asarray(jnp.inf if isinstance(f, Min) else -jnp.inf,
                               vals.dtype)
        else:
            nan_w = None
            eff_valid = valid
            info = jnp.iinfo(vals.dtype)
            sent = jnp.asarray(info.max if isinstance(f, Min) else info.min,
                               vals.dtype)
        combine = jnp.minimum if isinstance(f, Min) else jnp.maximum
        table = W.sparse_table(jnp.where(eff_valid, vals, sent), combine, sent)
        mm_w = W.range_query(table, combine, lo_q, hi_q)
        if nan_w is not None:
            if isinstance(f, Min):  # all-NaN frame → NaN
                mm_w = jnp.where((nonnan_w == 0) & (nan_w > 0), jnp.nan, mm_w)
            else:                   # any NaN in frame → NaN
                mm_w = jnp.where(nan_w > 0, jnp.nan, mm_w)
        return self._finish(f, None, cnt_w, mm_w, out_dtype, live, dict_)

    @staticmethod
    def _partition_ends(part_b, cap):
        idx = jnp.arange(cap, dtype=jnp.int32)
        next_b = jnp.concatenate([part_b[1:], jnp.ones((1,), jnp.bool_)])
        rev = lambda x: jnp.flip(x, 0)
        return rev(W.seg_cummax(rev(jnp.where(next_b, idx, 0)), rev(next_b)))

    @staticmethod
    def _finish(f, sum_w, cnt_w, mm_w, out_dtype, live, dict_):
        if isinstance(f, Count):
            return Col(cnt_w.astype(jnp.int64), live, T.LONG)
        if isinstance(f, Average):
            vals = sum_w.astype(jnp.float64) / jnp.maximum(cnt_w, 1)
            return Col(vals, (cnt_w > 0) & live, T.DOUBLE)
        if isinstance(f, Sum):
            dt = out_dtype.jnp_dtype
            return Col(sum_w.astype(dt), (cnt_w > 0) & live, out_dtype)
        # min/max: restore the value dtype (bool scans ran on an int8 carrier;
        # string scans ran on dictionary codes — the sorted dictionary rides
        # along so codes stay decodable, like expr/aggregates.py Min/Max)
        if isinstance(out_dtype, T.BooleanType):
            mm_w = mm_w.astype(jnp.bool_)
        return Col(mm_w, (cnt_w > 0) & live, out_dtype, dict_)

    def args_string(self):
        return str(self.window_exprs)
