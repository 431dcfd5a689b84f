"""TpuSession + DataFrame — the user entry point.

Reference analogy: the reference is a plugin inside Spark — users keep the Spark
session/DataFrame API and the plugin rewrites plans underneath
(Plugin.scala:45-70, SURVEY.md #1). This framework is standalone, so it ships the
session facade itself: a DataFrame builds a CPU plan (plan/nodes.py); every
action runs it through TpuOverrides and executes the hybrid plan, exactly the
flow Spark would drive. `spark.rapids.tpu.*` conf keys keep their reference
meanings (config.py).

    from spark_rapids_tpu.session import TpuSession
    import spark_rapids_tpu.functions as F

    spark = TpuSession({"spark.rapids.tpu.sql.explain": "NONE"})
    df = spark.read_parquet("/data/sales")
    out = (df.filter(F.col("price") > 0)
             .group_by("region").agg(F.sum("price").alias("total"))
             .collect())
"""

from __future__ import annotations

import typing

import pyarrow as pa

from spark_rapids_tpu import types as T
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.expr import core as E
from spark_rapids_tpu.expr.aggregates import AggregateFunction
from spark_rapids_tpu.plan import nodes as NN
from spark_rapids_tpu.plan.overrides import TpuOverrides
from spark_rapids_tpu.plan.transitions import execute_hybrid


def _plan_counts(hybrid) -> dict:
    """What the ``query.plan`` span says of the plan it made: operators,
    fused stages, and operators that stayed on the host. Walked only while
    tracing is on."""
    from spark_rapids_tpu.exec.base import TpuExec
    from spark_rapids_tpu.plan.stages import assign_stages
    from spark_rapids_tpu.plan.transitions import (DeviceBridgeExec,
                                                   HostBridgeNode)
    operators = fallback = stages = 0
    todo = [(hybrid, False)]
    while todo:
        node, under_device = todo.pop()
        if isinstance(node, DeviceBridgeExec):
            todo.append((node.host_node, False))
            continue
        if isinstance(node, HostBridgeNode):
            todo.append((node.tpu_exec, False))
            continue
        operators += 1
        on_device = isinstance(node, TpuExec)
        if not on_device:
            fallback += 1
        elif not under_device:   # the root of a device subtree
            stages += len(set(assign_stages(node).values()))
        todo.extend((c, on_device) for c in node.children)
    return {"operators": operators, "stages": stages, "fallback": fallback}


def _abort_execs(collector) -> None:
    """Query-death sweep: give every exec registered with the dead query's
    collector its `abort_query()` cleanup hook (shuffle exchanges free map
    outputs whose read-completion countdown can never finish — a cancelled
    or failed query's unvisited reduce splits have no reader to account
    them). Hooks must never mask the original error."""
    with collector._lock:
        nodes = list(collector._nodes.values())
    for node in nodes:
        hook = getattr(node, "abort_query", None)
        if hook is not None:
            try:
                hook()
            except Exception:   # noqa: BLE001 — cleanup must not mask
                pass


def _finish_query_memory(collector, conf, leak_check: bool = True):
    """Memory-plane epilogue of one action (runtime/memory.py): pop the
    query's allocation-site accounting into ``collector.memory`` (peak +
    per-site breakdown — the query.end event embeds it), run
    the end-of-query leak detector (event + resilience counter + reclaim)
    and emit a full heap snapshot into the event log. Idempotent per
    collector (success and error paths both call it; first wins) and a
    no-op when the device was never initialized (host-only plans).

    ``leak_check=False`` on the cancel/error paths: those drains are
    COOPERATIVE — worker threads may legitimately still be closing their
    buffers when the exception propagates, so a scan here would race them
    (PR-6's polling leak checks own those paths). Only a cleanly drained
    action can assert "still tagged == leaked". Returns the leak info
    dict, or None when clean/skipped."""
    from spark_rapids_tpu import config as CFG
    from spark_rapids_tpu.runtime import eventlog as EL
    from spark_rapids_tpu.runtime.memory import DeviceManager
    if getattr(collector, "_memory_done", False):
        return None
    collector._memory_done = True
    dm = DeviceManager._instance
    if dm is None:
        return None
    summary, leak = dm.catalog.finish_query(
        collector.query_id,
        leak_check=leak_check and conf.get(CFG.MEMORY_LEAK_CHECK))
    collector.memory = summary
    if EL.enabled():
        snap = dm.catalog.heap_snapshot()
        snap["sites"] = snap["sites"][:conf.get(CFG.MEMORY_PROFILE_TOPK)]
        EL.emit("memory.snapshot", query=collector.query_id, **snap)
    return leak


def _to_expr(c) -> E.Expression:
    if isinstance(c, E.Expression):
        return c
    if isinstance(c, str):
        return E.col(c)
    return E.lit(c)


class DataFrame:
    def __init__(self, plan: NN.PlanNode, session: "TpuSession"):
        self._plan = plan
        self.session = session
        self._last_collector = None   # QueryMetricsCollector of the last action

    # -- transformations (lazy: build plan nodes) ----------------------------
    def select(self, *cols) -> "DataFrame":
        return DataFrame(NN.ProjectNode([_to_expr(c) for c in cols],
                                        self._plan), self.session)

    def with_column(self, name: str, expr) -> "DataFrame":
        keep = [E.col(f.name) for f in self._plan.output
                if f.name != name]
        return DataFrame(NN.ProjectNode(
            keep + [E.Alias(_to_expr(expr), name)], self._plan), self.session)

    def filter(self, condition) -> "DataFrame":
        return DataFrame(NN.FilterNode(_to_expr(condition), self._plan),
                         self.session)

    where = filter

    def group_by(self, *keys) -> "GroupedData":
        return GroupedData([_to_expr(k) for k in keys], self)

    def rollup(self, *keys) -> "RollupData":
        """df.rollup(a, b).agg(...) — hierarchical subtotals via Expand with
        a grouping-id column, Spark's own lowering (the SQL front-end's
        GROUP BY ROLLUP takes the same path; reference GpuExpandExec role)."""
        return RollupData([_to_expr(k) for k in keys], self)

    def agg(self, *aggs) -> "DataFrame":
        return GroupedData([], self).agg(*aggs)

    def join(self, other: "DataFrame", on=None, how: str = "inner",
             condition=None) -> "DataFrame":
        jt = {"left_outer": "left", "right_outer": "right",
              "full_outer": "full", "outer": "full",
              "left_semi": "leftsemi", "semi": "leftsemi",
              "left_anti": "leftanti", "anti": "leftanti"}.get(how, how)
        if on is None:
            lk, rk = [], []
        else:
            names = [on] if isinstance(on, str) else list(on)
            lk = [E.col(n) for n in names]
            rk = [E.col(n) for n in names]
        jn = NN.JoinNode(self._plan, other._plan, lk, rk, jt, condition)
        if on is None or jt in ("leftsemi", "leftanti"):
            return DataFrame(jn, self.session)
        # USING join: one key column per name, Spark semantics — left key for
        # inner/left, right key for right, coalesce(left, right) for full;
        # the right-side duplicate is dropped
        from spark_rapids_tpu.expr.nullexprs import Coalesce
        lout, rout = self._plan.output, other._plan.output
        nl = len(lout.fields)
        proj = []
        for n in names:
            li, ri = lout.index_of(n), rout.index_of(n)
            lref = E.BoundReference(li, lout.fields[li].data_type)
            rref = E.BoundReference(nl + ri, rout.fields[ri].data_type)
            key = (rref if jt == "right"
                   else Coalesce(lref, rref) if jt == "full" else lref)
            proj.append(E.Alias(key, n))
        for i, f in enumerate(lout.fields):
            if f.name not in names:
                proj.append(E.Alias(E.BoundReference(i, f.data_type), f.name))
        for i, f in enumerate(rout.fields):
            if f.name not in names:
                proj.append(E.Alias(E.BoundReference(nl + i, f.data_type),
                                    f.name))
        return DataFrame(NN.ProjectNode(proj, jn), self.session)

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(NN.UnionNode(self._plan, other._plan), self.session)

    def sort(self, *cols, ascending=True) -> "DataFrame":
        ascs = (ascending if isinstance(ascending, (list, tuple))
                else [ascending] * len(cols))
        # Spark default: nulls first when ascending, last when descending
        sort_exprs = [(_to_expr(c), bool(a), bool(a))
                      for c, a in zip(cols, ascs)]
        return DataFrame(NN.SortNode(sort_exprs, self._plan), self.session)

    order_by = sort

    def sort_within_partitions(self, *cols, ascending=True) -> "DataFrame":
        """Per-partition sort without a global exchange (Spark
        sortWithinPartitions)."""
        ascs = (ascending if isinstance(ascending, (list, tuple))
                else [ascending] * len(cols))
        sort_exprs = [(_to_expr(c), bool(a), bool(a))
                      for c, a in zip(cols, ascs)]
        return DataFrame(NN.SortNode(sort_exprs, self._plan,
                                     global_sort=False), self.session)

    def distinct(self) -> "DataFrame":
        """Spark distinct(): group by every column (device group-by kernel)."""
        keys = [E.col(f.name) for f in self._plan.output]
        return DataFrame(NN.AggregateNode(keys, [], self._plan), self.session)

    drop_duplicates = distinct

    def drop(self, *names) -> "DataFrame":
        drop_set = set(names)
        keep = [E.col(f.name) for f in self._plan.output
                if f.name not in drop_set]
        return DataFrame(NN.ProjectNode(keep, self._plan), self.session)

    def with_column_renamed(self, old: str, new: str) -> "DataFrame":
        proj = [E.Alias(E.col(f.name), new) if f.name == old
                else E.col(f.name) for f in self._plan.output]
        return DataFrame(NN.ProjectNode(proj, self._plan), self.session)

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(NN.LimitNode(n, self._plan, global_limit=True),
                         self.session)

    def repartition(self, n: int, *keys) -> "DataFrame":
        if keys:
            return DataFrame(NN.ExchangeNode(
                self._plan, "hash", n, keys=[_to_expr(k) for k in keys]),
                self.session)
        return DataFrame(NN.ExchangeNode(self._plan, "roundrobin", n),
                         self.session)

    def window(self, window_exprs: list) -> "DataFrame":
        return DataFrame(NN.WindowNode(window_exprs, self._plan), self.session)

    def map_in_pandas(self, fn, schema) -> "DataFrame":
        """df.mapInPandas(fn, schema): fn(iterator[pandas.DataFrame]) ->
        iterator[pandas.DataFrame] over each partition (reference
        GpuMapInPandasExec)."""
        return DataFrame(NN.MapInPandasNode(fn, _to_schema(schema),
                                            self._plan), self.session)

    def explode(self, column: str, outer: bool = False,
                pos: bool = False) -> "DataFrame":
        """explode/posexplode an array column into one row per element
        (GpuGenerateExec analog; device path is one gather program)."""
        f = self._plan.output[column]
        if not isinstance(f.data_type, T.ArrayType):
            raise TypeError(
                f"explode: column '{column}' is {f.data_type}, not an array")
        return DataFrame(NN.GenerateNode(
            column, self._plan, outer=outer,
            element_type=f.data_type.element_type, pos=pos), self.session)

    def cache(self, serializer: str | None = None) -> "DataFrame":
        """Materialize-once cache (reference ParquetCachedBatchSerializer /
        the device spill-store cache; conf spark.rapids.tpu.sql.cache.serializer)."""
        from spark_rapids_tpu import config as CFG
        from spark_rapids_tpu.plan.cache import CacheNode
        ser = serializer or self.session.conf.get(CFG.CACHE_SERIALIZER)
        return DataFrame(CacheNode(self._plan, ser, self.session), self.session)

    def unpersist(self) -> "DataFrame":
        from spark_rapids_tpu.plan.cache import CacheNode
        if isinstance(self._plan, CacheNode):
            self._plan.unpersist()
            return DataFrame(self._plan.child, self.session)
        return self

    # -- metadata ------------------------------------------------------------
    @property
    def schema(self) -> T.StructType:
        return self._plan.output

    @property
    def columns(self) -> list:
        return [f.name for f in self._plan.output]

    def explain(self, all_nodes: bool = True, metrics: bool = False,
                stats: bool = False, fused: bool = False) -> str:
        from spark_rapids_tpu.plan.overrides import explain_plan
        if fused:
            # whole-stage view: the exec tree with Spark's `*(k)` stage
            # markers plus a per-stage summary of members and fused-in
            # operators; after an action the last collector's tree is reused
            # so per-node dispatch counts ride along
            from spark_rapids_tpu.plan.overrides import TpuOverrides
            from spark_rapids_tpu.plan.stages import explain_fused
            c = self._last_collector
            if c is not None and c.root is not None:
                return explain_fused(c.root, c)
            return explain_fused(
                TpuOverrides(self.session.conf).apply(self._plan))
        if metrics or stats:
            # SQL-UI analog: the executed plan tree annotated per node with
            # its metric snapshot — requires a completed action on this frame
            c = self._last_collector
            if c is None:
                return ("<no completed action on this DataFrame — run "
                        "collect()/count()/write first for "
                        f"explain({'stats' if stats else 'metrics'}=True)>\n"
                        + explain_plan(self._plan, self.session.conf,
                                       all_nodes))
            if stats:
                # stats plane: observed vs estimated rows per node plus the
                # per-node dispatch/transfer ledger and shuffle skew
                from spark_rapids_tpu.runtime import stats as STATS
                return STATS.annotated_stats_plan(c)
            return c.annotated_plan()
        return explain_plan(self._plan, self.session.conf, all_nodes)

    # -- actions -------------------------------------------------------------
    def _run_action(self, plan, run):
        """Execute one action under a fresh QueryMetricsCollector: plan
        conversion registers every exec node with it, `run(hybrid)` executes,
        and the finished collector (annotated plan, per-node metrics,
        query-scoped resilience deltas) lands on the DataFrame and the
        session for explain(metrics=True) / last_query_metrics(). Query
        lifecycle is mirrored to the structured event log when configured.

        Multi-tenant lifecycle (runtime/scheduler.py): the action is
        ADMITTED against the process-wide QueryScheduler before it executes
        (declared footprint from scan stats + plan shape), carries a
        CancelToken (+ optional scheduler.query.deadlineSeconds deadline)
        on its collector so session.cancel(query_id) reaches every worker
        thread, and releases its admission slot on every exit path. A shed
        submission raises QueryRejectedError (retryable, backoff hint); a
        cancellation/deadline classifies as query.cancelled/query.deadline
        in the event log, not query.error."""
        from spark_rapids_tpu import config as CFG
        from spark_rapids_tpu.runtime import eventlog as EL
        from spark_rapids_tpu.runtime import metrics as M
        from spark_rapids_tpu.runtime import scheduler as SCHED
        from spark_rapids_tpu.runtime import movement as MV
        from spark_rapids_tpu.runtime import tracing
        conf = self.session.conf
        MV.configure(
            sample_interval_bytes=conf.get(CFG.MOVEMENT_SAMPLE_INTERVAL),
            enabled=conf.get(CFG.MOVEMENT_ENABLED))
        collector = M.QueryMetricsCollector(description=type(plan).__name__)
        # cross-process trace id: a pending handoff (endpoint SUBMIT frame)
        # wins, then an explicit session override, else the query id — every
        # span this query emits, in every process it touches, carries it
        collector.trace_id = (tracing.take_pending_trace()
                              or conf.get(CFG.TRACE_ID_OVERRIDE)
                              or collector.query_id)
        deadline_s = conf.get(CFG.SCHEDULER_QUERY_DEADLINE)
        token = SCHED.CancelToken(
            collector.query_id,
            deadline_s=deadline_s if deadline_s > 0 else None)
        collector.cancel_token = token
        self._last_collector = collector
        self.session._last_collector = collector
        sched = SCHED.QueryScheduler.get()
        priority = conf.get(CFG.SCHEDULER_PRIORITY)

        def observe_latency():
            # end-to-end latency histogram per priority class (admission
            # wait included) — the serving tier's STATS/percentile source
            if collector.wall_s is not None:
                M.histogram(f"query.latency.priority{priority}").observe(
                    collector.wall_s)
        admitted = False
        with M.collector_context(collector), \
                tracing.span("query", query=collector.query_id) as query_span:
            # query.plan: overrides, stage split and footprint estimate, up
            # to the scheduler's door — the part of collect() that no clock
            # outside the program can see
            with tracing.span("query.plan") as plan_span:
                hybrid = TpuOverrides(conf).apply(plan)
                collector.set_root(hybrid)
                if EL.enabled():
                    from spark_rapids_tpu.plan.stages import \
                        emit_stage_events
                    emit_stage_events(hybrid, collector.query_id)
                # admission footprint: per-shape observed history when the
                # store has seen this plan's fingerprint, else the static
                # scan-bytes heuristic (stats plane; provenance kept on the
                # collector for plan.stats / explain(stats=True))
                collector.footprint = SCHED.estimate_footprint_ex(plan, conf)
                if plan_span:
                    plan_span.set(**_plan_counts(hybrid))
            try:
                queue_timeout = conf.get(CFG.SCHEDULER_QUEUE_TIMEOUT)
                with tracing.span("query.admission"):
                    sched.submit(
                        collector.query_id,
                        collector.footprint["estimate"],
                        priority=priority,
                        token=token,
                        timeout_s=queue_timeout if queue_timeout > 0
                        else None,
                        description=collector.description)
                admitted = True
                EL.emit("query.start", query=collector.query_id,
                        description=collector.description)
                out = run(hybrid)
                if query_span and hasattr(out, "num_rows"):
                    query_span.set(rows=out.num_rows)
                # end-of-query leak detection (memory observability plane):
                # the action has drained, so any device bytes still tagged
                # to this query are a leak — event + counter + reclaim,
                # escalated to a hard failure under memory.leak.strict
                leak = _finish_query_memory(collector, conf)
                if leak is not None and conf.get(CFG.MEMORY_LEAK_STRICT):
                    from spark_rapids_tpu.runtime.memory import \
                        MemoryLeakError
                    raise MemoryLeakError(
                        f"query {collector.query_id} leaked "
                        f"{leak['bytes']}B in {leak['buffers']} buffer(s): "
                        f"{leak['sites']}")
            except SCHED.QueryCancelledError as e:
                M.resilience_add(M.QUERIES_CANCELLED)
                if isinstance(e, SCHED.QueryDeadlineError):
                    M.counter_add("queries.deadline")
                collector.finish()
                observe_latency()
                _abort_execs(collector)
                _finish_query_memory(collector, conf, leak_check=False)
                EL.emit("query.deadline" if isinstance(
                            e, SCHED.QueryDeadlineError)
                        else "query.cancelled",
                        query=collector.query_id, reason=e.reason,
                        admitted=admitted, wall_s=collector.wall_s)
                raise
            except SCHED.QueryRejectedError:
                collector.finish()   # query.shed already emitted by submit()
                _finish_query_memory(collector, conf, leak_check=False)
                raise
            except BaseException as e:
                collector.finish()
                _abort_execs(collector)
                _finish_query_memory(collector, conf, leak_check=False)
                EL.emit("query.error", query=collector.query_id,
                        error=repr(e)[:200], wall_s=collector.wall_s)
                raise
            finally:
                if admitted:
                    sched.release(collector.query_id)
        collector.finish()
        observe_latency()
        # stats epilogue: build the per-node observed-stats payload, fold
        # this run into the plan-shape history store, publish the
        # estimate-error histogram (never raises)
        from spark_rapids_tpu.runtime import stats as STATS
        stats_payload = STATS.finish_query(collector, conf)
        compile_m = collector.compile_metrics()
        EL.emit("query.end", query=collector.query_id,
                description=collector.description,
                wall_s=collector.wall_s,
                compiles=compile_m["compiles"],
                dispatches=compile_m["dispatches"],
                resilience=collector.query_resilience(),
                memory=collector.memory,
                estimate_bytes=stats_payload.get("estimate_bytes"),
                history_hit=stats_payload.get("history_hit"),
                estimate_error=stats_payload.get("estimate_error"),
                nodes=collector.node_summaries(),
                # movement plane: this query's boundary-crossing bytes by
                # (edge, link) + amplification vs the result's Arrow size
                movement=MV.query_summary(
                    collector, result_bytes=getattr(out, "nbytes", None)))
        if EL.enabled():
            EL.emit("plan.stats", query=collector.query_id, **stats_payload)
        # flush the process ledger snapshot so short queries still leave a
        # movement.sample for the profiler even below the sample interval
        MV.maybe_emit(force=True)
        return out

    def collect(self) -> pa.Table:
        return self._run_action(self._plan, execute_hybrid)

    def collect_host(self) -> pa.Table:
        """CPU-only execution (the withCpuSparkSession analog for tests)."""
        return self._plan.collect_host()

    def collect_row_buffer(self):
        """Packed binary row collection (reference GpuColumnarToRowExec +
        CudfUnsafeRow, SURVEY.md #9). Fixed-width schemas return
        (rows int64[n, words], schema); schemas with strings return the
        UnsafeRow-style variable layout ((words, row_offsets), schema) —
        see columnar/rows.py pack_arrow_var."""
        from spark_rapids_tpu.columnar import rows as R
        schema = self._plan.output
        # host-only pack: collect() already materialized host arrow
        if R.is_fixed_width(schema):
            return R.pack_arrow(self.collect(), schema), schema
        if R.is_packable(schema):
            return R.pack_arrow_var(self.collect(), schema), schema
        raise NotImplementedError(
            f"nested types in {schema}: use collect()")

    def count(self) -> int:
        from spark_rapids_tpu.expr.aggregates import Count
        agg = NN.AggregateNode([], [E.Alias(Count(None), "count")], self._plan)
        out = self._run_action(agg, execute_hybrid)
        return out.column("count")[0].as_py()

    def to_pandas(self):
        return self.collect().to_pandas()

    def write_parquet(self, path: str, partition_by=None, mode="error"):
        return self._write(path, "parquet", partition_by, mode)

    def write_orc(self, path: str, partition_by=None, mode="error"):
        return self._write(path, "orc", partition_by, mode)

    def write_csv(self, path: str, mode="error"):
        return self._write(path, "csv", None, mode)

    def _write(self, path, fmt, partition_by, mode):
        from spark_rapids_tpu.io.writer import write_columnar
        return self._run_action(
            self._plan,
            lambda hybrid: write_columnar(hybrid, path, fmt,
                                          partition_by=partition_by,
                                          mode=mode, conf=self.session.conf))


class GroupedData:
    def __init__(self, keys: list, df: DataFrame):
        self.keys = keys
        self.df = df

    def _key_names(self) -> list:
        names = []
        for k in self.keys:
            if isinstance(k, (E.AttributeReference, E.Alias)):
                names.append(k.name)
            else:
                raise ValueError(
                    "pandas grouped operations need plain column keys, got "
                    f"{k!r}")
        return names

    def agg(self, *aggs) -> DataFrame:
        from spark_rapids_tpu.udf.pandas_exec import PandasAggUDF
        named = []
        pandas_udfs = []
        for i, a in enumerate(aggs):
            e = _to_expr(a)
            inner = e.child if isinstance(e, E.Alias) else e
            if isinstance(inner, PandasAggUDF):
                name = e.name if isinstance(e, E.Alias) else f"udf{i}"
                pandas_udfs.append((inner.fn, list(inner.input_cols), name,
                                    inner.return_type))
                continue
            assert isinstance(inner, AggregateFunction), \
                f"agg() requires aggregate expressions, got {e!r}"
            named.append(e)
        if pandas_udfs:
            if named:
                raise ValueError(
                    "cannot mix pandas aggregate UDFs with builtin "
                    "aggregates in one agg() (Spark AggregateInPandas "
                    "restriction)")
            return DataFrame(NN.AggregateInPandasNode(
                self._key_names(), pandas_udfs, self.df._plan),
                self.df.session)
        return DataFrame(NN.AggregateNode(self.keys, named, self.df._plan),
                         self.df.session)

    def apply_in_pandas(self, fn, schema) -> DataFrame:
        """groupBy(keys).applyInPandas(fn, schema): fn(pandas.DataFrame) ->
        pandas.DataFrame per group (keys included in the group frame)."""
        return DataFrame(NN.GroupedMapInPandasNode(
            self._key_names(), fn, _to_schema(schema), self.df._plan),
            self.df.session)

    def cogroup(self, other: "GroupedData") -> "CoGroupedData":
        """cogroup(df1.groupBy(k), df2.groupBy(k)) — Spark's cogroup."""
        return CoGroupedData(self, other)

    def count(self) -> DataFrame:
        from spark_rapids_tpu.expr.aggregates import Count
        return self.agg(E.Alias(Count(None), "count"))

    def pivot(self, pivot_col, values: list) -> "PivotedGroupedData":
        """df.group_by(k).pivot(p, values).agg(f(v)) — Spark's pivot.
        Lowered by If-guard expansion (one guarded aggregate per pivot
        value), which keeps every aggregate on the DEVICE kernels; the
        PivotFirst expression (expr/aggregates.py) is the reference-shaped
        host form for plans that carry it directly."""
        return PivotedGroupedData(self.keys, self.df, _to_expr(pivot_col),
                                  list(values))


class RollupData:
    """GROUP BY ROLLUP over plain columns (Expand + grouping-id, like the
    SQL lowering sql/lower.py _expand_rollup)."""

    def __init__(self, keys: list, df: DataFrame):
        for k in keys:
            if not isinstance(k, (E.AttributeReference, E.BoundReference)):
                raise ValueError("rollup supports plain columns only")
        self.keys = [E.bind_references(k, df._plan.output) for k in keys]
        self.df = df

    def agg(self, *aggs) -> DataFrame:
        named = []
        for a in aggs:
            e = _to_expr(a)
            inner = e.child if isinstance(e, E.Alias) else e
            if not isinstance(inner, AggregateFunction):
                raise ValueError(
                    f"rollup().agg() requires aggregate expressions, got {e!r}"
                    " (pandas aggregate UDFs are not supported under rollup)")
            named.append(e)
        expand, group_refs, gid_ref = NN.build_rollup_expand(
            self.df._plan, self.keys)
        group_named = [E.Alias(r, r.name) for r in group_refs]
        agg_node = NN.AggregateNode(group_named + [E.Alias(gid_ref, "_gid")],
                                    named, expand)
        # drop the grouping-id column from the visible output — POSITIONALLY
        # (an agg alias may collide with a key name)
        gid_pos = len(group_refs)
        keep = [E.Alias(E.BoundReference(i, f.data_type, f.nullable, f.name),
                        f.name)
                for i, f in enumerate(agg_node.output) if i != gid_pos]
        return DataFrame(NN.ProjectNode(keep, agg_node), self.df.session)


def _to_schema(schema) -> T.StructType:
    if isinstance(schema, T.StructType):
        return schema
    return T.StructType([T.StructField(n, dt, True) for n, dt in schema])


class CoGroupedData:
    """Pair of grouped frames for cogrouped applyInPandas (Spark
    PandasCogroupedOps)."""

    def __init__(self, left: GroupedData, right: GroupedData):
        if len(left.keys) != len(right.keys):
            raise ValueError("cogroup requires equal-arity grouping keys")
        self.left = left
        self.right = right

    def apply_in_pandas(self, fn, schema) -> DataFrame:
        """fn(left_group_df, right_group_df) -> pandas.DataFrame per key
        present on either side (the absent side gets an empty frame)."""
        return DataFrame(NN.CoGroupedMapInPandasNode(
            self.left._key_names(), self.right._key_names(), fn,
            _to_schema(schema), self.left.df._plan, self.right.df._plan),
            self.left.df.session)


class PivotedGroupedData:
    def __init__(self, keys: list, df: DataFrame, pivot_expr, values: list):
        self.keys = keys
        self.df = df
        self.pivot_expr = pivot_expr
        self.values = values

    def agg(self, *aggs) -> DataFrame:
        from spark_rapids_tpu.expr.aggregates import Count, First, Last
        from spark_rapids_tpu.expr.conditional import If
        named = []
        for a in aggs:
            e = _to_expr(a)
            inner = e.child if isinstance(e, E.Alias) else e
            assert isinstance(inner, AggregateFunction), \
                f"agg() requires aggregate expressions, got {e!r}"
            base_name = e.name if isinstance(e, E.Alias) else None
            for pv in self.values:
                child = inner.children[0] if inner.children else None
                if child is None:
                    # count(*) counts only the pivot value's rows (Spark
                    # lowers pivot by grouping on the pivot column)
                    guarded = Count(If(E.Literal(pv) == self.pivot_expr,
                                       E.Literal(1), E.Literal(None, T.INT)))
                else:
                    guard = If(E.Literal(pv) == self.pivot_expr, child,
                               E.Literal(None, child.dtype))
                    if isinstance(inner, (First, Last)):
                        # non-matching rows become nulls; they must not win
                        guarded = type(inner)(guard, ignore_nulls=True)
                    else:
                        guarded = inner.with_children([guard])
                col_name = (f"{pv}" if len(aggs) == 1 and base_name is None
                            else f"{pv}_{base_name or type(inner).__name__.lower()}")
                named.append(E.Alias(guarded, col_name))
        return DataFrame(NN.AggregateNode(self.keys, named, self.df._plan),
                         self.df.session)


class UDFRegistration:
    """Named-UDF registry (reference RapidsUDF + GpuUserDefinedFunction.scala:73
    + hiveUDFs.scala: a user function that SHIPS its own device implementation
    is routed to it by the planner; otherwise the usual ladder applies —
    bytecode-compile to device expressions, else the python worker pool).

        spark.udf.register("my_fn", fn=slow_row_fn, return_type=T.DOUBLE,
                           device_fn=lambda v: v * 2.0)
        spark.sql("select my_fn(x) from t")        # runs the jax impl, fused
    """

    def __init__(self, session: "TpuSession"):
        self._session = session
        self._fns: dict = {}

    def register(self, name: str, fn=None, return_type: T.DataType | None = None,
                 device_fn=None, null_aware: bool = False):
        if fn is None and device_fn is None:
            raise ValueError("register() needs fn and/or device_fn")
        self._fns[name] = (fn, return_type, device_fn, null_aware)

        def call(*cols):
            return self.build(name, [_to_expr(c) for c in cols])
        return call

    def __contains__(self, name: str) -> bool:
        return name in self._fns

    def build(self, name: str, args: list) -> E.Expression:
        """Expression for a registered UDF call: device impl > compiled
        bytecode > python worker (the reference's replacement-else-fallback
        contract)."""
        fn, return_type, device_fn, null_aware = self._fns[name]
        if device_fn is not None:
            from spark_rapids_tpu.udf.device_udf import JaxUDF
            if return_type is None:
                raise ValueError(f"UDF {name}: device_fn needs return_type")
            return JaxUDF(device_fn, args, return_type, null_aware, name=name)
        from spark_rapids_tpu.udf.compiler import compile_udf
        compiled = compile_udf(fn, args)
        if compiled is not None:
            return compiled
        from spark_rapids_tpu.udf.python_runtime import PythonUDF
        if return_type is None:
            raise ValueError(
                f"UDF {name} could not be compiled to device expressions; "
                "the python-worker fallback needs an explicit return_type")
        return PythonUDF(fn, args, return_type)


class TpuSession:
    """The SparkSession stand-in; owns the conf and the read API
    (reference RapidsDriverPlugin/SQLExecPlugin wiring, Plugin.scala:45-70)."""

    def __init__(self, conf: dict | RapidsConf | None = None):
        self.conf = (conf if isinstance(conf, RapidsConf)
                     else RapidsConf(conf or {}))
        self._views: dict = {}   # temp-view catalog for session.sql()
        # streaming sources (streaming/source.py): resolved to a FRESH
        # DataFrame on every sql() call — a file-scan plan freezes its file
        # list at construction, and a stream's whole point is that the
        # list grows
        self._stream_sources: dict = {}
        # bumped on every view (re)registration; the endpoint result cache
        # keys on it so results computed against a replaced catalog can
        # never be served again
        self._catalog_epoch = 0
        self.udf = UDFRegistration(self)
        from spark_rapids_tpu import config as CFG
        # plugin bootstrap: config fixup/version check once per process;
        # eager device acquisition when conf'd (reference Plugin.scala flow)
        from spark_rapids_tpu import plugin as PL
        PL.bootstrap(self.conf)
        # tracing (NVTX analog): profiler annotations around hot regions,
        # optional whole-session XProf capture (reference nvtx_profiling.md)
        from spark_rapids_tpu.runtime import tracing
        # process-global: only an EXPLICIT setting touches it, so a default
        # session never clobbers another's choice
        if CFG.TRACE_ENABLED.key in self.conf.settings:
            tracing.set_enabled(self.conf.get(CFG.TRACE_ENABLED))
        pdir = self.conf.get(CFG.PROFILE_DIR)
        if pdir:
            tracing.start_profile(pdir)
        # distributed span plane (trace.dir): per-process JSONL span files
        # merged by tools/profiler.py trace — process-global like the
        # switches above; only an EXPLICIT setting opens (or closes, when
        # set empty) the sink. MiniCluster executors open their own from
        # the same conf key (cluster/minicluster._executor_main)
        if CFG.TRACE_DIR.key in self.conf.settings:
            tdir = self.conf.get(CFG.TRACE_DIR)
            if tdir:
                tracing.configure_spans(tdir, process="driver")
            else:
                tracing.shutdown_spans()
        # deterministic fault injection (chaos testing, runtime/faults.py):
        # process-global like the switches above — only an EXPLICIT setting
        # arms or re-seeds the injector
        if CFG.TEST_FAULTS.key in self.conf.settings:
            from spark_rapids_tpu.runtime import faults
            faults.configure(self.conf.get(CFG.TEST_FAULTS),
                             self.conf.get(CFG.TEST_FAULTS_SEED))
        # structured event log (Spark event-log analog, runtime/eventlog.py):
        # process-global like the switches above — only an EXPLICIT setting
        # opens (or closes, when set empty) the sink
        if CFG.EVENT_LOG_DIR.key in self.conf.settings:
            from spark_rapids_tpu.runtime import eventlog
            elog_dir = self.conf.get(CFG.EVENT_LOG_DIR)
            if elog_dir:
                eventlog.configure(
                    elog_dir, self.conf.get(CFG.EVENT_LOG_HEALTH_INTERVAL),
                    max_bytes=self.conf.get(CFG.EVENT_LOG_MAX_BYTES),
                    keep=self.conf.get(CFG.EVENT_LOG_KEEP_FILES))
            else:
                eventlog.shutdown()
        # black-box flight recorder (runtime/blackbox.py): the in-memory
        # ring runs at its default bound with no configuration; the dump
        # directory follows eventLog.dir, and an EXPLICIT maxEvents setting
        # resizes (0 disables) the process-global ring
        if any(k.key in self.conf.settings for k in (
                CFG.FLIGHT_RECORDER_MAX_EVENTS, CFG.EVENT_LOG_DIR)):
            from spark_rapids_tpu.runtime import blackbox
            blackbox.configure(
                max_events=self.conf.get(CFG.FLIGHT_RECORDER_MAX_EVENTS)
                if CFG.FLIGHT_RECORDER_MAX_EVENTS.key in self.conf.settings
                else None,
                directory=self.conf.get(CFG.EVENT_LOG_DIR) or None)
        # memory observability plane (runtime/memory.py): watermark sample
        # granularity + site top-K are process-global like the switches
        # above — only an EXPLICIT setting pushes them onto the (lazily
        # constructed) buffer catalog
        if any(k.key in self.conf.settings for k in (
                CFG.MEMORY_WATERMARK_INTERVAL, CFG.MEMORY_PROFILE_TOPK)):
            from spark_rapids_tpu.runtime import memory as MEM
            MEM.set_profile_options(
                self.conf.get(CFG.MEMORY_WATERMARK_INTERVAL),
                self.conf.get(CFG.MEMORY_PROFILE_TOPK))
        # plan-shape history store (stats plane, runtime/history.py):
        # process-global like the switches above — only an EXPLICIT setting
        # opens (or closes, when set empty) the store
        if any(k.key in self.conf.settings for k in (
                CFG.STATS_HISTORY_DIR, CFG.STATS_HISTORY_MAX_SHAPES)):
            from spark_rapids_tpu.runtime import history as HIST
            hdir = self.conf.get(CFG.STATS_HISTORY_DIR)
            if hdir:
                HIST.configure(hdir,
                               self.conf.get(CFG.STATS_HISTORY_MAX_SHAPES))
            else:
                HIST.shutdown()
        # persistent compiled-stage cache (runtime/stage_cache.py):
        # process-global like the switches above — only an EXPLICIT setting
        # opens (or closes, when disabled or the dir is empty) the store
        if any(k.key in self.conf.settings for k in (
                CFG.STAGE_CACHE_ENABLED, CFG.STAGE_CACHE_DIR,
                CFG.STAGE_CACHE_MAX_BYTES)):
            from spark_rapids_tpu.runtime import stage_cache
            sc_dir = self.conf.get(CFG.STAGE_CACHE_DIR)
            if self.conf.stage_cache_enabled and sc_dir:
                stage_cache.configure(
                    sc_dir, self.conf.get(CFG.STAGE_CACHE_MAX_BYTES))
            else:
                stage_cache.shutdown()
        # multi-tenant query scheduler (runtime/scheduler.py): STRUCTURAL
        # knobs (concurrency, queue depth, aging) are process-global like
        # the switches above — only an EXPLICIT setting reconfigures the
        # shared instance; per-query values (priority, deadline, queue
        # timeout, footprint estimate) are read from this session's conf at
        # every submission
        if any(k.key in self.conf.settings for k in (
                CFG.SCHEDULER_MAX_CONCURRENT, CFG.SCHEDULER_QUEUE_MAX_DEPTH,
                CFG.SCHEDULER_PRIORITY_AGING)):
            from spark_rapids_tpu.runtime.scheduler import QueryScheduler
            QueryScheduler.get().reconfigure(self.conf)
        self._last_collector = None

    def last_query_metrics(self):
        """QueryMetricsCollector of the most recently completed action on
        this session (None before any action): per-node metric snapshots,
        the annotated plan, wall time and query-scoped resilience deltas."""
        return self._last_collector

    def heap_snapshot(self) -> dict:
        """Live allocation-site heap snapshot of the process-wide buffer
        catalog (runtime/memory.py): per-site tier occupancy, plan nodes,
        owning queries, process-lifetime peak/cumulative traffic, plus the
        device high-water mark — the programmatic face of
        ``tools/profiler.py memory`` and the STATS memory gauges."""
        from spark_rapids_tpu.runtime.memory import DeviceManager
        return DeviceManager.get().catalog.heap_snapshot()

    # -- multi-tenant lifecycle (runtime/scheduler.py) -----------------------
    def cancel(self, query_id: str, reason: str = "cancelled") -> bool:
        """Cooperatively cancel a running OR queued query by id (ids come
        from active_queries(), or last_query_metrics().query_id on the
        submitting thread). The query observes the token at its next
        checkpoint — pipeline queue waits, per-batch operator pulls, fetch
        backoffs, the OOM retry ladder — and drains without leaking
        threads, device buffers, or semaphore permits. Returns False for
        an unknown/already-finished id."""
        from spark_rapids_tpu.runtime.scheduler import QueryScheduler
        return QueryScheduler.get().cancel(query_id, reason)

    def active_queries(self) -> list:
        """Every queued or running query on the process-wide scheduler:
        [{query, state, estimate_bytes, priority, waited_s|running_s,
        description}] — the serving endpoint's `ps`."""
        from spark_rapids_tpu.runtime.scheduler import QueryScheduler
        return QueryScheduler.get().active_queries()

    def serve(self, host: str | None = None, port: int | None = None):
        """Start the Arrow-over-TCP query endpoint on this session
        (runtime/endpoint.py): remote clients submit SQL over this
        session's temp views and stream Arrow-IPC result batches back,
        routed through the multi-tenant scheduler (admission, priority,
        deadline, shedding). Listening starts immediately; call
        ``.shutdown()`` (or use as a context manager) for a graceful
        drain. host/port default to endpoint.host / endpoint.port."""
        from spark_rapids_tpu.runtime.endpoint import QueryEndpoint
        return QueryEndpoint(self, host=host, port=port)

    # -- data sources --------------------------------------------------------
    def read_parquet(self, path, pushed_filter=None,
                     files_per_partition: int = 1) -> DataFrame:
        from spark_rapids_tpu import config as CFG
        from spark_rapids_tpu.io.filescan import FileScanNode, rewrite_scan_path
        # node-level default so host-fallback scans honor the conf too; the
        # device exec re-applies its conf value per execution
        opts = {"rebase_mode": self.conf.get(CFG.PARQUET_REBASE_MODE)}
        path = rewrite_scan_path(path, self.conf)
        return DataFrame(FileScanNode(path, "parquet",
                                      pushed_filter=pushed_filter,
                                      files_per_partition=files_per_partition,
                                      options=opts),
                         self)

    def read_orc(self, path, **kw) -> DataFrame:
        from spark_rapids_tpu.io.filescan import FileScanNode, rewrite_scan_path
        return DataFrame(FileScanNode(rewrite_scan_path(path, self.conf),
                                      "orc", **kw), self)

    def read_csv(self, path, schema: T.StructType | None = None,
                 header: bool = True, delimiter: str = ",") -> DataFrame:
        from spark_rapids_tpu.io.filescan import FileScanNode, rewrite_scan_path
        return DataFrame(FileScanNode(
            rewrite_scan_path(path, self.conf), "csv", schema=schema,
            options={"header": header, "delimiter": delimiter,
                     "schema": schema}), self)

    def create_dataframe_from_rows(self, rows, schema,
                                   num_partitions: int = 1,
                                   offsets=None) -> DataFrame:
        """Packed binary row buffer → DataFrame without per-row conversion
        (reference GpuRowToColumnarExec's codegen'd fast path). Pass
        `offsets` for the variable-width layout from pack_arrow_var; a
        (words, offsets) tuple in `rows` also works."""
        from spark_rapids_tpu.columnar import rows as R
        import numpy as np
        if offsets is None and isinstance(rows, tuple) and len(rows) == 2:
            rows, offsets = rows
        if offsets is not None:
            tbl = R.unpack_rows_arrow_var(np.asarray(rows),
                                          np.asarray(offsets), schema)
            return self.create_dataframe(tbl, num_partitions)
        rows = np.asarray(rows)
        n = rows.shape[0]
        per = -(-n // max(1, num_partitions)) if n else 1
        parts = []
        for i in range(max(1, num_partitions)):
            chunk = rows[i * per:(i + 1) * per]
            if chunk.shape[0] == 0 and i > 0:
                break
            parts.append(R.unpack_rows_arrow(chunk, schema))
        return DataFrame(NN.ScanNode(parts, schema), self)

    def create_dataframe(self, data, num_partitions: int = 1) -> DataFrame:
        """From a pyarrow table / pandas DataFrame / dict of columns."""
        if not isinstance(data, pa.Table):
            data = pa.table(data) if isinstance(data, dict) else \
                pa.Table.from_pandas(data)
        per = -(-data.num_rows // max(1, num_partitions))
        parts = ([data.slice(i * per, per) for i in range(num_partitions)]
                 if num_partitions > 1 else [data])
        return DataFrame(NN.ScanNode(parts), self)

    def range(self, start: int, end: int | None = None, step: int = 1,
              num_slices: int = 1) -> DataFrame:
        if end is None:
            start, end = 0, start
        return DataFrame(NN.RangeNode(start, end, step, num_slices), self)

    # -- SQL -----------------------------------------------------------------
    def create_or_replace_temp_view(self, name: str, df: DataFrame) -> None:
        """Register `df` under `name` for session.sql() (SparkSession
        createOrReplaceTempView analog). Bumps the catalog epoch, which
        invalidates every endpoint result-cache entry."""
        self._views[name] = df
        self._catalog_epoch += 1

    createOrReplaceTempView = create_or_replace_temp_view

    @property
    def catalog_epoch(self) -> int:
        """Monotonic catalog-staleness counter (the result-cache key): the
        local view-registration counter, plus — when this session belongs
        to a fleet — the shared fleet-wide counter, so a streaming APPEND
        processed by a PEER replica still invalidates this replica's
        cached results (the peer bumps the shared counter; this property
        folds it in on the next cache-key computation)."""
        epoch = self._catalog_epoch
        from spark_rapids_tpu import config as CFG
        fleet_dir = self.conf.get(CFG.FLEET_DIR)
        if fleet_dir:
            from spark_rapids_tpu.runtime import fleet as FL
            epoch += FL.shared_catalog_epoch(fleet_dir)
        return epoch

    # -- streaming ------------------------------------------------------------
    def create_stream_source(self, name: str, directory: str, schema=None):
        """Register a micro-batch streaming source (streaming/source.py):
        a durable batch log fed by directory tail and/or endpoint APPEND
        frames, queryable under `name` in session.sql() — re-resolved to a
        fresh scan on every sql() call, so queries always see every batch
        durable at plan time. `schema` (pyarrow) makes the empty source
        queryable and gates appends; omitted, it is adopted from the first
        batch."""
        from spark_rapids_tpu.streaming.source import StreamingSource
        src = StreamingSource(name, directory, schema=schema)
        self._stream_sources[name] = src
        self._catalog_epoch += 1
        return src

    def streaming_append(self, source: str, batch_id: str, table=None, *,
                         ipc_body: bytes | None = None,
                         crc: int | None = None) -> dict:
        """Durably append one batch to a registered stream source —
        idempotent by (source, batch_id). A FRESH append bumps the catalog
        epoch (and the fleet-shared epoch when fleet.dir is set), so no
        result cache in the fleet can serve a pre-append frame; a
        duplicate bumps nothing. Returns the APPEND ack fields."""
        src = self._stream_sources.get(source)
        if src is None:
            raise ValueError(f"unknown stream source {source!r} "
                             f"(create_stream_source first)")
        if ipc_body is not None:
            table, fresh = src.append_ipc(batch_id, ipc_body,
                                          int(crc or 0))
        else:
            fresh = src.append_table(batch_id, table)
        if fresh:
            self._catalog_epoch += 1
            from spark_rapids_tpu import config as CFG
            fleet_dir = self.conf.get(CFG.FLEET_DIR)
            if fleet_dir:
                from spark_rapids_tpu.runtime import fleet as FL
                FL.bump_shared_catalog_epoch(fleet_dir)
        return {"source": source, "batch": batch_id,
                "duplicate": not fresh, "rows": table.num_rows,
                "epoch": self.catalog_epoch}

    def _refresh_stream_views(self) -> None:
        """Re-resolve every stream source to a fresh DataFrame before SQL
        lowering (no epoch bump — freshness is data arriving, staleness is
        keyed by the APPEND-time bumps). A source that is still empty with
        no declared schema is skipped; querying it stays an unknown-view
        error until its first batch lands."""
        for name, src in self._stream_sources.items():
            try:
                self._views[name] = src.dataframe(self)
            except ValueError:
                self._views.pop(name, None)

    def sql(self, text: str) -> DataFrame:
        """Run a SQL query over the registered temp views (the reference's
        entire surface is SQL text — qa_nightly_sql.py; see sql/)."""
        from spark_rapids_tpu.runtime import tracing
        from spark_rapids_tpu.sql import lower_sql
        with tracing.span("sql.parse"):
            if self._stream_sources:
                self._refresh_stream_views()
            return DataFrame(lower_sql(text, self._views, self), self)
