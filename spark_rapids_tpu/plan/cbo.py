"""Cost-based optimizer — dual host/device cost model.

Reference (SURVEY.md #13): CostBasedOptimizer.scala:52 builds a CpuCostModel
and a GpuCostModel, walks the tagged meta tree, costs each contiguous
device-capable section on both sides (including row↔columnar transition
costs at the section boundary), and reverts sections where acceleration
would not pay (`costPreventsRunningOnGpu`).

TPU translation of the cost terms:
  host cost    = Σ rows(op) · weight(op) · host.rowCost
  device cost  = Σ [dispatchCost + rows(op) · weight(op) · tpu.rowCost]
                 + boundary_rows · transferRowCost      (H2D at leaves,
                                                          D2H at the root)
The fixed per-operator dispatch term models what dominates on TPU for small
inputs: jit dispatch latency, the analog of the reference's
per-exec coefficient tables. `optimizer.minRows` remains as a hard floor
(cheaper than costing when the answer is obvious).
"""

from __future__ import annotations

from spark_rapids_tpu import config as CFG
from spark_rapids_tpu.plan import nodes as NN


def estimate_rows(node, _memo: dict | None = None) -> int:
    """Static cardinality estimate (the cost models' shared row-count term).
    Memoized per optimize() pass — parquet estimates open footers."""
    if _memo is None:
        _memo = {}
    key = id(node)
    if key in _memo:
        return _memo[key]
    rows = _estimate_rows(node, _memo)
    _memo[key] = rows
    return rows


def _estimate_rows(node, memo) -> int:
    from spark_rapids_tpu.io.filescan import FileScanNode
    from spark_rapids_tpu.plan.cache import CacheNode

    def est(n):
        return estimate_rows(n, memo)

    if isinstance(node, NN.ScanNode):
        return sum(t.num_rows for t in node.partitions)
    if isinstance(node, FileScanNode):
        # cached on the node: scans persist across planning passes (the
        # build-side chooser and optimize() both ask), and re-opening every
        # parquet footer per pass scales with file count. Keyed on file
        # mtimes so a retained plan over files that grew/shrank (or a
        # pruning-pass shallow clone of a stale node) re-estimates.
        import os

        def _mt(p):
            try:
                return os.path.getmtime(p)
            except OSError:
                return 0.0
        fp = tuple((p, _mt(p))
                   for part in node.partitions for p in part.paths)
        if (getattr(node, "_est_rows", None) is not None
                and getattr(node, "_est_rows_fp", None) == fp):
            return node._est_rows
        total = 0
        for part in node.partitions:
            for p in part.paths:
                try:
                    if node.fmt == "parquet":
                        import pyarrow.parquet as pq
                        total += pq.ParquetFile(p).metadata.num_rows
                    else:
                        total += max(1, os.path.getsize(p) // 64)
                except Exception:
                    total += 1 << 20  # unknown: assume big (stay on device)
        node._est_rows, node._est_rows_fp = total, fp
        return total
    if isinstance(node, NN.RangeNode):
        return max(0, -(-(node.end - node.start) // node.step))
    if isinstance(node, NN.FilterNode):
        return max(1, est(node.child) // 2)   # selectivity 0.5
    if isinstance(node, NN.AggregateNode):
        return max(1, est(node.child) // 10)  # grouping factor
    if isinstance(node, NN.JoinNode):
        return max(est(node.left), est(node.right))
    if isinstance(node, NN.LimitNode):
        return min(node.n, est(node.child))
    if isinstance(node, NN.UnionNode):
        return sum(est(c) for c in node.children)
    if isinstance(node, NN.GenerateNode):
        return est(node.child) * 4             # explode fan-out guess
    if isinstance(node, CacheNode):
        return est(node.child)
    if node.children:
        return max(est(c) for c in node.children)
    return 1 << 20


# relative per-row operator weights (the reference keys its coefficient
# table by exec class the same way)
_OP_WEIGHTS = (
    (NN.SortNode, 6.0),
    (NN.JoinNode, 5.0),
    (NN.WindowNode, 5.0),
    (NN.AggregateNode, 3.0),
    (NN.ExchangeNode, 2.0),
    (NN.GenerateNode, 2.0),
    (NN.ExpandNode, 2.0),
)


def _op_weight(node) -> float:
    for cls, w in _OP_WEIGHTS:
        if isinstance(node, cls):
            return w
    return 1.0


class _CostModel:
    """One side of the dual model: per-op cost from shared cardinality."""

    def __init__(self, row_cost: float, dispatch_cost: float = 0.0):
        self.row_cost = row_cost
        self.dispatch_cost = dispatch_cost

    def op_cost(self, node, rows: int) -> float:
        return self.dispatch_cost + rows * _op_weight(node) * self.row_cost


def optimize(meta) -> None:
    """Walk the tagged meta tree; revert device sections the dual cost model
    says are unprofitable (reference CostBasedOptimizer.optimize, called
    between tagging and conversion)."""
    conf = meta.conf
    if not conf.get(CFG.OPTIMIZER_ENABLED):
        return
    host = _CostModel(conf.get(CFG.OPTIMIZER_HOST_ROW_COST))
    tpu = _CostModel(conf.get(CFG.OPTIMIZER_TPU_ROW_COST),
                     conf.get(CFG.OPTIMIZER_TPU_DISPATCH_COST))
    xfer = conf.get(CFG.OPTIMIZER_TRANSFER_ROW_COST)
    memo = {}
    # pass 1 — hard floor, PER NODE: a tiny operator (a global limit, a
    # low-cardinality root) never pays for dispatch, but pinning it must not
    # drag a large upstream scan off the device with it
    _apply_min_rows(meta, conf.get(CFG.OPTIMIZER_MIN_ROWS), memo)
    # pass 2 — dual cost comparison over the remaining device sections
    _optimize_sections(meta, host, tpu, xfer, memo, parent_on_tpu=False)


def _apply_min_rows(meta, min_rows: int, memo: dict) -> None:
    from spark_rapids_tpu.plan.cache import CacheNode
    node = getattr(meta, "node", None)
    if (node is not None and meta.can_run_on_tpu
            and not isinstance(node, CacheNode)):
        rows = estimate_rows(node, memo)
        if rows < min_rows:
            meta.will_not_work(
                f"cost model: ~{rows} rows < optimizer.minRows={min_rows};"
                " transfer+dispatch overhead exceeds device speedup")
    for m in _plan_metas(meta):
        _apply_min_rows(m, min_rows, memo)


def _plan_metas(meta):
    """Child metas that wrap plan nodes (expression metas are costed with
    their operator, not separately)."""
    return [m for m in meta.child_metas if hasattr(m, "node")]


def _section(meta, memo):
    """Collect the maximal contiguous device-capable subtree rooted at
    `meta`: (section metas, host-boundary metas below it)."""
    nodes, fringe = [meta], []
    for m in _plan_metas(meta):
        if m.can_run_on_tpu:
            sub_nodes, sub_fringe = _section(m, memo)
            nodes.extend(sub_nodes)
            fringe.extend(sub_fringe)
        else:
            fringe.append(m)
    return nodes, fringe


def _optimize_sections(meta, host, tpu, xfer, memo, parent_on_tpu):
    from spark_rapids_tpu.plan.cache import CacheNode
    node = getattr(meta, "node", None)
    on_tpu = node is not None and meta.can_run_on_tpu
    if on_tpu and not parent_on_tpu and not isinstance(node, CacheNode):
        section, fringe = _section(meta, memo)
        # a cache inside the section may hold device-materialized batches;
        # reverting would re-execute its child — never profitable
        if not any(isinstance(m.node, CacheNode) for m in section):
            host_cost = tpu_cost = 0.0
            for m in section:
                rows = estimate_rows(m.node, memo)
                host_cost += host.op_cost(m.node, rows)
                tpu_cost += tpu.op_cost(m.node, rows)
            # transitions: H2D for every host child feeding the section,
            # D2H for the section's result
            boundary = estimate_rows(meta.node, memo)
            for m in fringe:
                boundary += estimate_rows(m.node, memo)
            tpu_cost += boundary * xfer
            if tpu_cost >= host_cost:
                why = (f"cost model: device {tpu_cost * 1e3:.2f}ms >= "
                       f"host {host_cost * 1e3:.2f}ms over "
                       f"{len(section)}-op section")
                for m in section:
                    m.will_not_work(why)
                on_tpu = False
    for m in _plan_metas(meta):
        _optimize_sections(m, host, tpu, xfer, memo, on_tpu)
