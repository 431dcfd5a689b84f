"""Hash aggregate exec — Spark's two-phase aggregation on TPU.

Reference: aggregate.scala GpuHashAggregateExec:240 with the update→concat→merge loop
at 282-420 and computeAggregate:706: batches are aggregated incrementally (update
aggregation per batch, then merge-aggregation of partials) so memory stays bounded;
modes Partial/Final/Complete mirror Spark's AggregateMode.

TPU-native realization (see ops/grouping.py): each batch goes through one fused XLA
program — sort by keys, segment-reduce, compact one row per group. Partial results
accumulate; when more than one partial batch exists they are concatenated and
merge-aggregated (the same incremental loop as the reference). The group count stays
a device scalar until a downstream sync."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.base import TpuExec, acquire_semaphore
from spark_rapids_tpu.expr.core import Alias, Col, EvalContext, bind_references
from spark_rapids_tpu.expr.aggregates import AggregateFunction
from spark_rapids_tpu.ops import grouping as G
from spark_rapids_tpu.ops.concat import concat_batches
from spark_rapids_tpu.ops.filtering import compact_cols, gather_cols
from spark_rapids_tpu.ops.sorting import held_bits, words_for
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import retry as R
from spark_rapids_tpu.runtime import tracing
from spark_rapids_tpu.runtime.tracing import trace_range

PARTIAL = "partial"
FINAL = "final"
COMPLETE = "complete"

# smallest batch capacity the group-by chain will fuse (see _chain_step)
_CHAIN_MIN_CAPACITY = 1024

# the partial→merge contract per aggregate op: which op folds two PARTIAL
# states of the named op into one (sums and counts re-SUM; min/max are
# idempotent under themselves). This is the same algebra the FINAL-mode
# merge below implements batch-to-batch; streaming/coordinator.py reuses it
# epoch-to-epoch — incremental streaming state IS a parked partial batch,
# and any consumer that parks partials across queries must merge with
# exactly these ops or double-count
AGG_MERGE_OPS = {"sum": "sum", "count": "sum", "min": "min", "max": "max"}


def _sorted_and_valid(key: Col, num_rows, capacity: int):
    """1 where the live rows of ``key`` are all valid and nondecreasing,
    else 0 (int32): the condition of the sorted-input group-by."""
    vals = key.values
    live = jnp.arange(capacity, dtype=jnp.int32) < num_rows
    ok = jnp.all(key.validity | ~live) & jnp.all(
        jnp.where(live[1:], vals[1:] >= vals[:-1], True))
    return ok.astype(jnp.int32)


def _agg_fn(e) -> AggregateFunction:
    f = e.child if isinstance(e, Alias) else e
    assert isinstance(f, AggregateFunction), f
    return f


class HashAggregateExec(TpuExec):
    """group_exprs: grouping expressions; agg_exprs: Alias(AggregateFunction).

    mode=COMPLETE: update + evaluate in one exec (single stage);
    mode=PARTIAL: emits keys + state columns (pre-shuffle);
    mode=FINAL: child output is PARTIAL layout; merges states and evaluates.
    """

    def __init__(self, group_exprs: list, agg_exprs: list, child: TpuExec,
                 mode: str = COMPLETE, conf=None, prefilter=None,
                 preproject=None, prefilter_on_projected: bool = False):
        super().__init__(child, conf=conf)
        self.mode = mode
        # whole-stage fusion (planner hoists child Filter/Project execs):
        # `preproject` exprs re-derive the aggregation input inside the
        # kernel; `prefilter` masks rows there (dense path) or compacts
        # in-program (segment path) — no separate dispatches, no full-width
        # intermediate batches. The reference gets this from whole-stage
        # codegen feeding GpuHashAggregateExec; the fuse layer plays that
        # role here. With preproject set, group/agg exprs must arrive BOUND
        # against the hoisted project's output (the planner's logical nodes
        # bind eagerly, so this holds by construction).
        self.preproject = list(preproject) if preproject is not None else None
        self.prefilter_on_projected = prefilter_on_projected
        if mode == FINAL:
            # keys are the first child columns; aggs reference state columns
            self.group_exprs = [bind_references(e, child.output)
                                for e in group_exprs]
            self.agg_exprs = list(agg_exprs)
        elif self.preproject is not None:
            self.group_exprs = list(group_exprs)
            self.agg_exprs = list(agg_exprs)
        else:
            self.group_exprs = [bind_references(e, child.output)
                                for e in group_exprs]
            self.agg_exprs = [bind_references(e, child.output) for e in agg_exprs]
        bind_to = child.output if not prefilter_on_projected else None
        self.prefilter = (prefilter if prefilter is None or bind_to is None
                          else bind_references(prefilter, bind_to))
        # HAVING fusion: a Filter directly ABOVE this aggregate folded into
        # the finalize kernel (fuse_having, planner-only). Evaluated against
        # self.output after f.evaluate; surviving groups compact in the same
        # program (or via the host-indexed epilogue) — the separate FilterExec
        # dispatch and its full-width capacity disappear.
        self.postfilter = None
        self._agg_time = self.metrics.metric(M.AGG_TIME, M.MODERATE)
        self._concat_time = self.metrics.metric(M.CONCAT_TIME, M.MODERATE)
        # observed input cardinality (stats plane): with output rows this
        # gives the aggregation's reduction factor per node
        self._in_rows = self.metrics.metric(M.NUM_INPUT_ROWS, M.ESSENTIAL)

    @property
    def output(self):
        fields = [T.StructField(e.name, e.dtype, True) for e in self.group_exprs]
        if self.mode == PARTIAL:
            for e in self.agg_exprs:
                f = _agg_fn(e)
                for i, st in enumerate(f.state_types):
                    fields.append(T.StructField(f"{e.name}#state{i}", st, True))
        else:
            for e in self.agg_exprs:
                fields.append(T.StructField(e.name, _agg_fn(e).dtype, True))
        return T.StructType(fields)

    def fuse_having(self, condition):
        """Fold a HAVING predicate into finalization (plan/overrides
        conv_filter). The condition must reference only this aggregate's
        OUTPUT columns; COMPLETE/FINAL modes only (PARTIAL output is
        state-typed and the filter must see evaluated aggregates)."""
        assert self.mode != PARTIAL
        from spark_rapids_tpu.expr import predicates as P
        cond = bind_references(condition, self.output)
        self.postfilter = (cond if self.postfilter is None
                           else P.And(self.postfilter, cond))

    def _partial_schema(self):
        fields = [T.StructField(e.name, e.dtype, True) for e in self.group_exprs]
        for e in self.agg_exprs:
            f = _agg_fn(e)
            for i, st in enumerate(f.state_types):
                fields.append(T.StructField(f"{e.name}#state{i}", st, True))
        return T.StructType(fields)

    # ------------------------------------------------------------------
    def _aggregate_batch(self, batch: ColumnarBatch, merge: bool,
                         sp=tracing.NO_SPAN):
        """One fused update-or-merge aggregation, jit-compiled per shape bucket
        (runtime/fuse.py). In merge mode the batch is in keys+state layout; in
        update mode it is raw child output. Returns (a batch in keys+state
        layout with one row per group, the words the keys fold into by what
        they hold, or None, and whether the key-stats probe proved the input
        sorted: what the chain predicts by). ``sp``: the caller's span, for
        what the grouping did."""
        from spark_rapids_tpu.columnar.encoded import (EncodedColumnVector,
                                                       densify_cols)
        from spark_rapids_tpu.expr.core import Col
        from spark_rapids_tpu.expr.misc import CONTEXT_SENSITIVE
        from spark_rapids_tpu.runtime import fuse
        pre = self.prefilter if not merge else None
        prep = self.preproject if not merge else None
        ctx_sensitive = any(
            e.collect(lambda x: isinstance(x, CONTEXT_SENSITIVE))
            for e in (*self.group_exprs, *self.agg_exprs,
                      *([pre] if pre is not None else []),
                      *(prep or [])))
        n_words, need, presorted = None, 0, False
        if batch.columns and not ctx_sensitive:
            # scan-side chain: still-encoded scan columns enter the kernel AS
            # ENCODED PAGES and expand inside this fused program (late
            # materialization) — the standalone decode dispatch and its dense
            # H2D column never exist. from_vector on anything else (including
            # an already-forced encoded vector) yields the usual dense Col.
            use_enc = not merge and self.conf.scan_fusion_enabled
            in_cols = []
            for c in batch.columns:
                enc = (c.encoded if use_enc
                       and isinstance(c, EncodedColumnVector) else None)
                in_cols.append(enc if enc is not None else Col.from_vector(c))
            nr = jnp.asarray(batch.lazy_num_rows, jnp.int32)
            n_words, need, presorted = self._key_stats(batch, in_cols, nr,
                                                       merge)
            # a presorted batch sorts nothing: its program folds no key
            words = None if presorted else n_words
            key = ("agg", merge, fuse.schema_key(
                self._partial_schema() if merge else self.child.output),
                tuple(fuse.expr_key(e) for e in self.group_exprs),
                tuple(fuse.expr_key(e) for e in self.agg_exprs),
                fuse.expr_key(pre) if pre is not None else None,
                tuple(fuse.expr_key(e) for e in prep) if prep is not None
                else None, self.prefilter_on_projected, words, presorted)

            def build():
                def kernel(cols, num_rows):
                    cols = densify_cols(cols)
                    ctx = EvalContext(cols, num_rows, cols[0].values.shape[0])
                    return self._agg_kernel(ctx, merge, n_words=words,
                                            presorted=presorted)
                return kernel

            compacted, n_groups, _need, facts = fuse.call_fused(
                key, "HashAggregateExec", build, (in_cols, nr),
                lambda: self._agg_kernel(EvalContext.from_batch(batch), merge))
            # stage-boundary right-sizing: a high-reduction aggregation at a
            # big capacity stops dragging that capacity into downstream
            # programs (merge/finalize/join build) — one count sync, one tiny
            # slice program (ops/filtering.maybe_host_resize)
            if compacted and self.conf.stage_fusion_enabled:
                from spark_rapids_tpu.ops.filtering import maybe_host_resize
                resized = maybe_host_resize(compacted, n_groups)
                if resized is not None:
                    compacted, n_groups = resized
        else:
            compacted, n_groups, _need, facts = self._agg_kernel(
                EvalContext.from_batch(batch), merge)
        if sp:
            self._count_grouping(sp, facts, 0 if presorted else need,
                                 batch.lazy_num_rows, batch.capacity,
                                 n_groups)
            sp.set(presorted=int(presorted))
        cols = [c.to_vector() for c in compacted]
        return (ColumnarBatch(cols, n_groups, self._partial_schema()),
                n_words, presorted)

    @staticmethod
    def _count_grouping(sp, facts, need_bits, rows, capacity, groups):
        """What the grouping did, on the caller's span: ``path`` (``dense`` /
        ``sort`` / ``global``), ``keys``, ``sort_operands``, ``packed_bits``
        (the bits the folded keys and the row index take), and the rows the
        host already holds (no read is made for a count)."""
        counts = dict(facts.items, capacity=capacity)
        if need_bits:
            counts["packed_bits"] = int(need_bits)
        if isinstance(rows, int):
            counts["rows"] = rows
        if isinstance(groups, int):
            counts["groups"] = groups
        sp.set(**counts)

    def _chain_step(self, acc: ColumnarBatch, batch: ColumnarBatch,
                    A: int, pred_P: int, n_words=None, sp=tracing.NO_SPAN,
                    presorted: bool = False):
        """One fused update→concat→merge step of the group-by chain: aggregate
        the incoming batch, pad-concat the partial onto the accumulated
        partials, and merge-aggregate — ONE program per batch, like
        exec/joins.py chains probes. The unchained loop pays three host syncs
        per batch (key-stats probe, concat's num_rows, right-sizing count);
        the chain pays exactly one (the status readback below) and its output
        capacity is PREDICTED from the caller's host-side group counts
        (``bucket_capacity(A + pred_P)``), so no device count ever gates a
        shape. The update, concat, and merge bodies are the SAME traced
        functions the unchained path runs (``_agg_kernel``, ``concat_cols``),
        and the result is accepted only when the predicted concat bucket
        matches the one the unchained loop would have used — chained-vs-
        unchained results are bit-identical; on any non-chainable shape or
        mispredict the caller redoes the batch unchained (degraded, never
        wrong).

        ``n_words``: the words the group sorts fold their keys into by what
        the keys hold, PREDICTED from the last unchained batch; both sorts
        say what they needed in the status the step reads anyway, and a key
        that outgrew the prediction rejects the step like a capacity
        mispredict does (ops/sorting.fold_keys).

        ``presorted``: the last unchained batch's key-stats probe proved its
        input sorted. The update and the merge then sort nothing (the
        sorted-input group-by of ``_agg_kernel``), and the program checks
        what that assumes: the concat's keys, the accumulator's and then the
        update's, are all valid and nondecreasing over the live rows. The
        update's groups are the batch's runs of equal keys, so they come out
        nondecreasing only where the batch was sorted, and the merge needs
        the accumulator's last key at or below the update's first. A fourth
        status word says so; where it does not hold the step is rejected
        like a mispredict. Every sort breaks ties on the row index, so over
        sorted input the sorting strategies give the same order and the same
        sums as this one.

        Returns ``(accepted, merged_batch, merged_groups, update_groups,
        words the keys needed)`` or None when the shape cannot chain at all.
        """
        from spark_rapids_tpu.columnar.encoded import (EncodedColumnVector,
                                                       densify_cols)
        from spark_rapids_tpu.columnar.vector import bucket_capacity
        from spark_rapids_tpu.expr.misc import CONTEXT_SENSITIVE
        from spark_rapids_tpu.ops.concat import concat_cols
        from spark_rapids_tpu.runtime import fuse
        import numpy as np
        if not (batch.columns and acc.columns):
            return None
        presorted = presorted and len(self.group_exprs) == 1
        # chaining only pays when its one-off trace+compile can amortize over
        # real batches: the syncs it removes cost microseconds, the fused
        # program costs seconds to compile, and a cluster executor compiling
        # it mid-task under an armed task deadline can be killed for it —
        # tiny batches (toy partitions, interactive map tasks) go unchained
        if batch.capacity < _CHAIN_MIN_CAPACITY:
            return None
        pre = self.prefilter
        prep = self.preproject
        ctx_sensitive = any(
            e.collect(lambda x: isinstance(x, CONTEXT_SENSITIVE))
            for e in (*self.group_exprs, *self.agg_exprs,
                      *([pre] if pre is not None else []),
                      *(prep or [])))
        if ctx_sensitive:
            return None
        acc_cap = acc.capacity
        bcap = batch.capacity
        Cc = bucket_capacity(max(A + pred_P, 1))
        use_enc = self.conf.scan_fusion_enabled
        in_cols = []
        for c in batch.columns:
            enc = (c.encoded if use_enc
                   and isinstance(c, EncodedColumnVector) else None)
            in_cols.append(enc if enc is not None else Col.from_vector(c))
        acc_cols = [Col.from_vector(c) for c in acc.columns]
        if presorted:
            n_words = None   # nothing is sorted, so no key is folded
        key = ("agg_chain", fuse.schema_key(self.child.output),
               fuse.schema_key(self._partial_schema()), acc_cap, bcap, Cc,
               tuple(fuse.expr_key(e) for e in self.group_exprs),
               tuple(fuse.expr_key(e) for e in self.agg_exprs),
               fuse.expr_key(pre) if pre is not None else None,
               tuple(fuse.expr_key(e) for e in prep) if prep is not None
               else None, self.prefilter_on_projected, n_words) + (
                   ("presorted",) if presorted else ())

        def build():
            def kernel(a_cols, b_cols, acc_n, nr):
                b_cols = densify_cols(b_cols)
                uctx = EvalContext(b_cols, nr, bcap)
                # no key-stats probe: the presorted verdict is the last
                # unchained batch's, checked in the status; skipping it is
                # value-neutral (every sort embeds the row index, so all
                # strategies produce the same total order)
                upd_cols, upd_n, need_u, _ = self._agg_kernel(
                    uctx, merge=False, n_words=n_words, presorted=presorted)
                counts_v = jnp.stack([acc_n, upd_n.astype(jnp.int32)])
                per_col = [[a, u] for a, u in zip(a_cols, upd_cols)]
                with jax.named_scope("concat"):
                    cat = concat_cols(per_col, counts_v, Cc, (acc_cap, bcap))
                mctx = EvalContext(cat, acc_n + upd_n, Cc)
                mg_cols, mg_n, need_m, facts = self._agg_kernel(
                    mctx, merge=True, n_words=n_words, presorted=presorted)
                status = [jnp.asarray(mg_n, jnp.int32),
                          jnp.asarray(upd_n, jnp.int32),
                          jnp.maximum(need_u, need_m).astype(jnp.int32)]
                if presorted:
                    status.append(_sorted_and_valid(cat[0], acc_n + upd_n,
                                                    Cc))
                return mg_cols, jnp.stack(status), facts
            return kernel

        acc_n_t = jnp.asarray(acc.lazy_num_rows, jnp.int32)
        nr_t = jnp.asarray(batch.lazy_num_rows, jnp.int32)
        out = fuse.call_fused(key, "HashAggregateExec.chain", build,
                              (acc_cols, in_cols, acc_n_t, nr_t),
                              lambda: None)
        if out is None:
            return None   # uncacheable key or trace fallback → go unchained
        mg_cols, status, facts = out
        with tracing.span("sync.status") as sync:
            st = np.asarray(status)   # the ONE host sync of the chained step
            mg_n, upd_n, need = int(st[0]), int(st[1]), int(st[2])
            sync.set(rows=mg_n, capacity=Cc)
        if sp:
            self._count_grouping(sp, facts, need, batch.lazy_num_rows, Cc,
                                 mg_n)
        # accept only when the concat ran at the bucket the unchained loop's
        # concat_batches would have picked (bucket of the TRUE total): the
        # merge's f64 reduction order is capacity-sensitive, so an equal
        # bucket is exactly the bit-identity condition
        in_order = not presorted or bool(st[3])
        accepted = (bucket_capacity(max(A + upd_n, 1)) == Cc
                    and need <= (held_bits(n_words) if n_words else 0)
                    and in_order)
        if not in_order:
            upd_n = pred_P   # it counted the batch's runs, not its groups
        if sp:
            sp.set(accepted=int(accepted), presorted=int(presorted))
        if accepted and self.conf.stage_fusion_enabled:
            # same stage-boundary right-sizing the unchained merge applies —
            # mg_n is already a host int, so this syncs nothing extra, and
            # the accumulator lands at ITS bucket whenever that is smaller:
            # the steps after it then share one program (the key holds the
            # accumulator's capacity; q67's third step compiled two more
            # two-operand sorts for an accumulator left at 2 Mi)
            from spark_rapids_tpu.ops.filtering import maybe_host_resize
            resized = maybe_host_resize(mg_cols, mg_n, min_shrink=2)
            if resized is not None:
                mg_cols, mg_n = resized
        merged = ColumnarBatch([c.to_vector() for c in mg_cols], mg_n,
                               self._partial_schema())
        return (accepted, merged, mg_n, upd_n,
                words_for(need) if need else None)

    def _key_stats(self, batch, in_cols, nr, merge: bool):
        """(n_words, need_bits, presorted) for the sort-path group-by: one
        cheap reduction program + ONE host read a batch say how many sort
        operands the keys fold into BY WHAT THEY HOLD (ops/sorting.fold_keys:
        an int64 key that holds a million values takes 20 bits, not an
        operand pair of the comparator sort), which is static in the
        aggregate's program; the ranges themselves stay traced there, so one
        program serves every batch that needs as many words. For a single
        key the same probe also checks whether the live rows already ARRIVE
        key-sorted with no nulls (clustered fact tables — TPC-H lineitem is
        physically ordered by l_orderkey): then the sort vanishes entirely
        and the segment path runs over the input order (the sorted-input
        group-by; `presorted` wins over the fold). Gated to big capacities
        (below, any sort is cheap) and key sets that hold an integer whose
        type alone says too little: a 64-bit key, or one among several.
        Several keys take dense inputs only (the probe would expand encoded
        columns a second time); a single key is probed over a scan's
        still-encoded pages too, since that is the key that arrives sorted,
        and only its own column expands (the probe returns nothing else).
        Every other key set folds by its types and dictionaries with no read
        (n_words None), or cannot fold at all."""
        from spark_rapids_tpu.columnar.encoded import EncodedCol, densify_cols
        from spark_rapids_tpu.ops.sorting import (SortOrder, fold_keys,
                                                  ranged_key)
        from spark_rapids_tpu.runtime import fuse
        no = (None, 0, False)
        if not self.group_exprs or batch.capacity < (1 << 17):
            return no
        ranged = False
        for e in self.group_exprs:
            try:
                kdt = e.dtype
            except Exception:  # noqa: BLE001 — unresolvable dtype: no fold
                return no
            if isinstance(kdt, (T.StringType, T.BooleanType)):
                continue
            if not ranged_key(kdt):
                return no
            ranged = ranged or len(self.group_exprs) > 1 or \
                jnp.iinfo(kdt.jnp_dtype).bits > 32
        if not ranged:
            return no
        single = len(self.group_exprs) == 1
        if not single and any(isinstance(c, EncodedCol) for c in in_cols):
            return no
        prep = self.preproject if not merge else None
        skey = ("agg_key_stats", merge, fuse.schema_key(
            self._partial_schema() if merge else self.child.output),
            tuple(fuse.expr_key(e) for e in self.group_exprs),
            tuple(fuse.expr_key(e) for e in prep) if prep is not None
            else None)

        def build():
            def kernel(cols, num_rows):
                cols = densify_cols(cols)
                cap_ = cols[0].values.shape[0]
                ctx = EvalContext(cols, num_rows, cap_)
                if prep is not None:
                    ctx = EvalContext([e.eval(ctx) for e in prep], num_rows,
                                      cap_)
                keys = (ctx.cols[:len(self.group_exprs)] if merge
                        else [e.eval(ctx) for e in self.group_exprs])
                folded = fold_keys(keys, [SortOrder() for _ in keys],
                                   num_rows, cap_, n_words=1)
                need = 0 if folded is None else folded.need_bits
                # sorted = every live row valid AND values nondecreasing
                # over the live prefix (all-valid means validity boundaries
                # cannot reorder groups, so input order == sorted group
                # order)
                is_sorted = (_sorted_and_valid(keys[0], num_rows, cap_)
                             if single else 0)
                return jnp.stack([jnp.asarray(need, jnp.int32),
                                  jnp.asarray(is_sorted, jnp.int32)])
            return kernel

        with tracing.span("sync.key_stats") as sp:
            need, is_sorted = (int(x) for x in fuse.call_fused(
                skey, "HashAggregateExec.key_stats", build, (in_cols, nr),
                lambda: build()(in_cols, nr)))
            sp.set(rows=need, capacity=batch.capacity)
        presorted = bool(is_sorted) and self.conf.stage_fusion_enabled
        if not need:
            return None, 0, presorted
        # the words stay with a presorted verdict: a later batch that turns
        # out unsorted then folds its key instead of the unfolded sort
        return words_for(need), need, presorted

    def _agg_kernel(self, ctx: EvalContext, merge: bool, n_words=None,
                    presorted: bool = False):
        """Pure per-batch aggregation body (traceable): (columns, groups,
        the bits the folded keys needed, fuse.Facts of the path taken).
        `n_words`: see _key_stats. `presorted` asserts that probe PROVED the
        single key column arrives sorted and null-free: the segment sort AND
        every row gather collapse to identity."""
        cap = ctx.capacity
        keep = None

        def eval_keep(c):
            from spark_rapids_tpu.ops.filtering import selection_mask
            return selection_mask(self.prefilter.eval(c), c.num_rows, cap)

        # the operators the planner fused into this program keep their own
        # names in its op metadata (srt_HashAggregateExec/FilterExec/...)
        if not merge:
            if self.prefilter is not None and not self.prefilter_on_projected:
                with jax.named_scope("FilterExec"):
                    keep = eval_keep(ctx)
            if self.preproject is not None:
                with jax.named_scope("ProjectExec"):
                    cols = [e.eval(ctx) for e in self.preproject]
                ctx = EvalContext(cols, ctx.num_rows, cap)
            if self.prefilter is not None and self.prefilter_on_projected:
                with jax.named_scope("FilterExec"):
                    keep = eval_keep(ctx)
        with jax.named_scope("HashAggregate.merge" if merge
                             else "HashAggregate.update"):
            return self._agg_groups(ctx, keep, merge, n_words, presorted)

    def _agg_groups(self, ctx: EvalContext, keep, merge: bool, n_words,
                    presorted: bool):
        """_agg_kernel after its fused-in filter and projection: group the
        rows (dense codes, or sort + segments) and reduce each aggregate."""
        from spark_rapids_tpu.ops.filtering import front_perm
        from spark_rapids_tpu.ops.sorting import SortOrder, unfold_keys
        from spark_rapids_tpu.runtime.fuse import Facts
        cap = ctx.capacity
        nkeys = len(self.group_exprs)
        gs, need = None, jnp.zeros((), jnp.int32)
        if nkeys:
            if merge:
                key_cols = [ctx.cols[i] for i in range(nkeys)]
            else:
                key_cols = [e.eval(ctx) for e in self.group_exprs]
            dense = self._agg_dense(ctx, merge, key_cols, live_mask=keep)
            if dense is not None:
                return (*dense, need,
                        Facts(path="dense", keys=nkeys, sort_operands=0))
            if keep is not None:
                # segment path sorts by key — masked rows must become padding,
                # so compact first (still inside this one fused program)
                new_cols, cnt = compact_cols(ctx.cols, keep)
                ctx = EvalContext(new_cols, cnt, cap)
                key_cols = [e.eval(ctx) for e in self.group_exprs]
                keep = None
            presorted = presorted and len(key_cols) == 1
            gs = G.sorted_groups(key_cols, ctx.num_rows, cap, n_words=n_words,
                                 presorted=presorted)
            perm, seg_ids, boundary, live = gs[:4]
            if gs.folded is not None and n_words is not None:
                need = jnp.asarray(gs.folded.need_bits, jnp.int32)
        else:
            if keep is not None:
                # segment kernels need contiguous runs — masked rows mid-run
                # would split segment 0; compact inside this same program
                new_cols, cnt = compact_cols(ctx.cols, keep)
                ctx = EvalContext(new_cols, cnt, cap)
                keep = None
            live = jnp.arange(cap) < ctx.num_rows
            perm = jnp.arange(cap, dtype=jnp.int32)
            seg_ids = jnp.where(live, 0, cap - 1).astype(jnp.int32)
            # global agg: always one output row, even on empty input (Spark)
            boundary = jnp.arange(cap, dtype=jnp.int32) == 0
        segctx = G.segment_structure(seg_ids, cap)

        # aggregate states are PER-ROW (row i = aggregate of its whole
        # segment, ops/grouping.py) — one compaction pulls boundary rows of
        # keys and states together
        state_cols = []
        off = nkeys
        for e in self.agg_exprs:
            f = _agg_fn(e)
            nstates = len(f.state_types)
            if merge:
                ins = [ctx.cols[off + i] for i in range(nstates)]
                ins = ([Col(c.values, c.validity & live, c.dtype,
                            c.dictionary) for c in ins]
                       if presorted else gather_cols(ins, perm, live))
                outs = f.merge(ins, segctx)
            else:
                if f.child is None:
                    in_col = Col(jnp.zeros((cap,), jnp.int8), live, T.BYTE)
                else:
                    in_col = f.child.eval(ctx)
                in_sorted = (Col(in_col.values, in_col.validity & live,
                                 in_col.dtype, in_col.dictionary)
                             if presorted else
                             gather_cols([in_col], perm, live)[0])
                outs = f.update(in_sorted, segctx)
            off += nstates
            state_cols.extend(outs)
        if gs is None:
            return (*compact_cols(state_cols, boundary), need,
                    Facts(path="global", keys=0, sort_operands=0))
        facts = {"path": "sort", "keys": nkeys, "sort_operands": gs.operands}
        if gs.folded is None:
            out, count = compact_cols(list(gs.sorted_keys) + state_cols,
                                      boundary)
            return out, count, need, Facts(**facts)
        if n_words is None:
            facts["packed_bits"] = int(gs.folded.need_bits)
        # the keys leave as they came: in the sort's own operands, moved to
        # the front once and unfolded there, not a gather a key and validity
        front, count = front_perm(boundary)
        live_out = jnp.arange(cap, dtype=jnp.int32) < count
        keys_out = unfold_keys(gs.folded, [w[front] for w in gs.words],
                               key_cols, [SortOrder() for _ in key_cols],
                               live_out)
        return (keys_out + gather_cols(state_cols, front, live_out), count,
                need, Facts(**facts))

    def _agg_dense(self, ctx: EvalContext, merge: bool, key_cols,
                   live_mask=None):
        """Sort-free small-domain aggregation: keys with statically-known
        compact domains (dict strings / bools) and sum-shaped aggregates
        (Sum/Count/Average) reduce straight into D per-group buckets —
        scatter-add on CPU, D masked reductions in one pass on TPU (cudf's
        hash groupby plays this role in the reference,
        aggregate.scala:706). The sorted segment path (q1: ~18 ms sort +
        ~12 ms/column tree per batch) drops to ~1 ms/column.

        Returns (cols, n_groups) or None when ineligible."""
        import jax
        from spark_rapids_tpu.columnar.vector import bucket_capacity
        from spark_rapids_tpu.expr.aggregates import Average, Count, Sum

        on_tpu = jax.devices()[0].platform == "tpu"
        fns = [_agg_fn(e) for e in self.agg_exprs]
        if not all(isinstance(f, (Sum, Count, Average)) for f in fns):
            return None
        # TPU domain bound: the masked reduction does cap x D work, so
        # D stays small; count-only aggregations (incl. DISTINCT dedup,
        # which has no aggregates) ride the blocked Pallas one-hot kernel
        # in the non-merge phase and stretch to medium domains — only when
        # that kernel is routed (pallas_kernels.KERNELS), else the jnp
        # fallback would materialize the very (cap, D) blowup the 128
        # bound exists to prevent
        count_only = all(isinstance(f, Count) for f in fns)
        if on_tpu:
            from spark_rapids_tpu.ops import pallas_kernels as PK
            # mirror dense_group_sum's f32-exactness cap guard: a batch at
            # or above 2^24 rows would fall through to the jnp one-hot,
            # materializing the (cap, D) blowup the 128 bound prevents
            max_dom = (1024 if count_only and not merge
                       and ctx.capacity < (1 << 24)
                       and PK.should_use("onehot") else 128)
        else:
            max_dom = 4096
        ks = G.compact_key_codes(key_cols, max_domain=max_dom)
        if ks is None:
            return None
        if on_tpu and any(
                not jnp.issubdtype(jnp.dtype(st.jnp_dtype), jnp.floating)
                for f in fns if isinstance(f, (Sum, Average))
                for st in f.state_types[:1]):
            return None   # int64 matmul is not an MXU op
        codes, strides = ks
        D = 1
        for d in strides:
            D *= d
        cap = ctx.capacity
        live = jnp.arange(cap, dtype=jnp.int32) < ctx.num_rows
        if live_mask is not None:
            live = live & live_mask    # fused prefilter (see _agg_kernel)
        codes = jnp.where(live, codes, jnp.int32(D))   # pad bucket, dropped

        # memoized child eval + count images: aggregates sharing a child
        # (sum(x) + avg(x) + count(x)) then feed IDENTICAL arrays to gsum,
        # so the CPU resolve pass dedups their stacked rows by identity
        from spark_rapids_tpu.runtime import fuse as _fuse
        _child_memo: dict = {}
        _cnt_memo: dict = {}

        def eval_child(e):
            k = _fuse.expr_key(e)
            if k not in _child_memo:
                _child_memo[k] = e.eval(ctx)
            return _child_memo[k]

        def cnt_vals(col):
            a = _cnt_memo.get(id(col))
            if a is None:
                a = col.validity.astype(jnp.int64)
                _cnt_memo[id(col)] = a
            return a

        def _state_cols(gsum):
            """One walk over the aggregate list through `gsum`; the CPU path
            runs it twice (record, then replay) so every f64-safe reduction
            lands in one stacked masked-matvec pass
            (G.resolve_dense_group_sums)."""
            rows_per = gsum(jnp.ones((cap,), jnp.int32),
                            jnp.ones((cap,), jnp.bool_), jnp.int32,
                            count_like=True)
            state_cols = []   # (D,)-length states, padded to D_cap below
            off = len(key_cols)
            for e, f in zip(self.agg_exprs, fns):
                nstates = len(f.state_types)
                if merge:
                    ins = [ctx.cols[off + i] for i in range(nstates)]
                elif f.child is None:
                    ins = [Col(jnp.zeros((cap,), jnp.int8), live, T.BYTE)]
                else:
                    ins = [eval_child(f.child)]
                off += nstates
                if isinstance(f, Count):
                    s = gsum(cnt_vals(ins[0])
                             if not merge else ins[0].values,
                             ins[0].validity, jnp.int64,
                             count_like=not merge)   # update inputs are 0/1
                    state_cols.append(Col(s, jnp.ones_like(s, jnp.bool_),
                                          T.LONG))
                    continue
                sum_t = f.state_types[0]
                acc = sum_t.jnp_dtype
                s = gsum(ins[0].values, ins[0].validity, acc)
                cnt = gsum(cnt_vals(ins[0]), ins[0].validity,
                           jnp.int64, count_like=True)   # validity is 0/1
                state_cols.append(Col(s, cnt > 0, sum_t))
                if isinstance(f, Average):
                    if merge:
                        c2 = gsum(ins[1].values, ins[1].validity, jnp.int64)
                    else:
                        c2 = cnt
                    state_cols.append(Col(c2, jnp.ones_like(c2, jnp.bool_),
                                          T.LONG))
            return rows_per, state_cols

        if on_tpu:
            def gsum(vals, mask, acc_dtype, count_like=False):
                return G.dense_group_sum(vals.astype(acc_dtype), mask & live,
                                         codes, D, on_tpu,
                                         count_like=count_like)
            rows_per, state_cols = _state_cols(gsum)
        else:
            # CPU: XLA's scatter-add costs ~50 ms per column at 1M rows
            # (numpy bincount: ~6 ms); batching every f64-safe reduction of
            # the batch into one shared-one-hot GEMM amortizes the one-hot
            # materialization and runs ~6x faster for q1-shaped aggregates.
            # Record pass enumerates the requests (outputs discarded),
            # replay pass rebuilds the states from the batched results.
            reqs = []

            def record(vals, mask, acc_dtype, count_like=False):
                reqs.append((vals, mask, acc_dtype, count_like))
                return jnp.zeros((D,), acc_dtype)
            _state_cols(record)
            results = G.resolve_dense_group_sums(reqs, codes, D, live)
            it = iter(results)

            def replay(vals, mask, acc_dtype, count_like=False):
                return next(it)
            rows_per, state_cols = _state_cols(replay)

        # decode bucket index -> key columns (inverse of the stride mix)
        D_cap = bucket_capacity(D)
        bidx = jnp.arange(D_cap, dtype=jnp.int32)
        key_out = []
        for ki, (c, d) in enumerate(zip(key_cols, strides)):
            tail = 1
            for d2 in strides[ki + 1:]:
                tail *= d2
            part = (bidx // tail) % jnp.int32(d)
            valid = (part != d - 1) & (bidx < D)
            if c.is_string:
                key_out.append(Col(jnp.where(valid, part, 0), valid,
                                   T.STRING, c.dictionary))
            else:   # boolean
                key_out.append(Col(jnp.where(valid, part == 1, False),
                                   valid, T.BOOLEAN))
        present = jnp.zeros((D_cap,), jnp.bool_).at[:D].set(rows_per > 0)

        def pad(col):
            v = jnp.zeros((D_cap,), col.values.dtype).at[:D].set(col.values)
            m = jnp.zeros((D_cap,), jnp.bool_).at[:D].set(col.validity)
            return Col(v, m & present, col.dtype, col.dictionary)

        out = key_out + [pad(c) for c in state_cols]
        return compact_cols(out, present)

    def _finalize(self, partial: ColumnarBatch) -> ColumnarBatch:
        from spark_rapids_tpu.expr.core import Col
        from spark_rapids_tpu.ops.filtering import (fused_compact_cols,
                                                    host_compact_cols,
                                                    selection_mask)
        from spark_rapids_tpu.runtime import fuse

        def body(ctx):
            nkeys = len(self.group_exprs)
            out = [ctx.cols[i] for i in range(nkeys)]
            off = nkeys
            for e in self.agg_exprs:
                f = _agg_fn(e)
                states = [ctx.cols[off + i] for i in range(len(f.state_types))]
                off += len(f.state_types)
                out.append(f.evaluate(states))
            if self.postfilter is None:
                return out, None
            # fused HAVING: the predicate sees the EVALUATED output columns;
            # the keep mask leaves the kernel so the epilogue can choose the
            # host-indexed compaction (right-sized capacity) over the
            # in-program one
            octx = EvalContext(out, ctx.num_rows, ctx.capacity)
            keep = selection_mask(self.postfilter.eval(octx), octx.num_rows,
                                  octx.capacity)
            return out, keep

        if partial.columns:
            key = ("agg_final", fuse.schema_key(self._partial_schema()),
                   tuple(fuse.expr_key(e) for e in self.group_exprs),
                   tuple(fuse.expr_key(e) for e in self.agg_exprs),
                   fuse.expr_key(self.postfilter)
                   if self.postfilter is not None else None)

            def build():
                def kernel(cols, num_rows):
                    return body(EvalContext(cols, num_rows,
                                            cols[0].values.shape[0]))
                return kernel

            in_cols = [Col.from_vector(c) for c in partial.columns]
            nr = jnp.asarray(partial.lazy_num_rows, jnp.int32)
            out, keep = fuse.call_fused(
                key, "HashAggregateExec.finalize", build, (in_cols, nr),
                lambda: body(EvalContext.from_batch(partial)))
        else:
            out, keep = body(EvalContext.from_batch(partial))
        num_rows = partial.lazy_num_rows
        if keep is not None:
            res = host_compact_cols(out, keep)
            if res is None:
                res = fused_compact_cols(out, keep)
            out, num_rows = res
        return ColumnarBatch([c.to_vector() for c in out], num_rows,
                             self.output)

    def execute_partition(self, split):
        def it():
            merge_input = self.mode == FINAL

            def agg_one(b, merge=merge_input):
                nonlocal n_words, sorted_in
                with trace_range("HashAggregate.agg", self._agg_time) as sp:
                    out, n_words, sorted_in = self._aggregate_batch(b, merge,
                                                                    sp)
                    return out

            acc = None
            # group-by chain (host-side predictors): A = accumulated group
            # count, pred_P = predicted partial-group count of the next batch
            # (last observed), n_words = the words the last group sort folded
            # its keys into, sorted_in = the last unchained batch's probe
            # proved its input sorted, and its merge (if one ran) its concat,
            # seen_rows = the rows of the batch pred_P counted (a host int, or
            # None). All plain values maintained WITHOUT extra syncs on
            # chained iterations.
            chain_ok = (not merge_input and bool(self.group_exprs)
                        and self.conf.groupby_chain_enabled)
            A = pred_P = 0
            n_words = seen_rows = None
            sorted_in = False
            for batch in self.child.execute_partition(split):
                self._in_rows.add_lazy(batch.lazy_num_rows)
                # acquire only once data is ready for device work — acquiring before
                # pulling the child would hold a permit across a blocking shuffle map
                # stage and deadlock the semaphore (reference RapidsShuffleIterator
                # acquires on data arrival, RapidsShuffleIterator.scala:300)
                acquire_semaphore(self.metrics)
                needed = None
                rows = batch.lazy_num_rows
                rows = rows if isinstance(rows, int) else None
                if acc is not None and chain_ok:
                    P = pred_P
                    if sorted_in and rows and seen_rows:
                        # a sorted stream's groups follow its rows: the last
                        # batch's count scaled to this one's rows (a file's
                        # batches alternate 1 Mi rows and its remainder)
                        P = -(-pred_P * rows // seen_rows)

                    def chain_step(a=acc, b=batch, A=A, P=P, w=n_words,
                                   srt=sorted_in):
                        with trace_range("HashAggregate.chain",
                                         self._agg_time) as sp:
                            return self._chain_step(a, b, A, P, w, sp, srt)
                    try:
                        res = R.call_with_retry(chain_step, scope="agg.chain")
                    except R.DeviceOomError:
                        res = None   # fall back to the splittable update loop
                    if res is not None:
                        accepted, merged, mg_n, upd_n, needed = res
                        seen_rows = rows
                        if accepted:
                            acc, A, pred_P = merged, mg_n, upd_n
                            continue
                        # capacity or key-width mispredict: DISCARD the
                        # chained result and redo this batch unchained — never
                        # accept a result whose concat bucket differs from the
                        # unchained one, or whose keys outgrew their words
                        # (degraded, never wrong). What was observed still
                        # improves the next prediction.
                        pred_P = upd_n
                # per-batch update aggregation under the OOM ladder: a split
                # aggregates the halves into two partials, which the merge
                # loop below folds together — exactly the semantics of
                # batches arriving pre-split (reference withRetry around the
                # update aggregation, aggregate.scala:282-420)
                for partial in R.with_retry([batch], agg_one, conf=self.conf,
                                            scope="agg.update"):
                    if acc is None:
                        acc = partial
                        continue

                    # incremental concat+merge loop (reference aggregate.scala:388)
                    def merge_acc(a=acc, p=partial):
                        nonlocal sorted_in
                        with trace_range("HashAggregate.concat",
                                         self._concat_time):
                            both = concat_batches([a, p])
                        with trace_range("HashAggregate.merge",
                                         self._agg_time) as sp:
                            out, _, merged_sorted = self._aggregate_batch(
                                both, True, sp)
                            sorted_in = sorted_in and merged_sorted
                            return out

                    # the merge needs BOTH partials at once — unsplittable,
                    # so spill-only retry (withRetryNoSplit)
                    acc = R.call_with_retry(merge_acc, scope="agg.merge")
                if needed and n_words:
                    # the merge's keys span the accumulated batches' ranges
                    n_words = max(n_words, needed)
                if chain_ok and acc is not None:
                    # refresh predictors after an unchained batch (first batch
                    # or chain fallback): one count sync — the unchained loop
                    # already syncs counts per merge, so this adds none on the
                    # steady path and the chain adds exactly one per step
                    A = acc.num_rows
                    if not pred_P:
                        pred_P, seen_rows = A, rows
            if acc is None:
                if self.group_exprs:
                    return  # grouped agg over empty input → no rows (Spark)
                acquire_semaphore(self.metrics)
                empty = ColumnarBatch.empty(
                    self._partial_schema() if merge_input else self.child.output)
                acc = self._aggregate_batch(empty, merge_input)[0]
            if self.mode == PARTIAL:
                yield acc
            else:
                yield self._finalize(acc)
        return self.wrap_output(it())

    def args_string(self):
        return f"keys={self.group_exprs} aggs={self.agg_exprs} mode={self.mode}"
