"""Whole-stage structure over the physical exec tree.

Reference contrast: Spark marks codegen-fused regions in explain() output with
`*(k)` stage prefixes (WholeStageCodegenExec). Here the analogous unit is a
maximal contiguous region of DEVICE operators between pipeline breakers
(exchanges, host materializations, scans): every operator inside one region
replays fused per-batch XLA programs (runtime/fuse.py) and several collapse
entirely into a neighbor's kernel (aggregate pre/post hoists, join stream
hoists). This module is the planner/read-out side of that story:

- `compose_prestage` folds an arbitrary-depth stack of context-free
  Filter/Project execs into (prefilter, preproject) terms an aggregate's
  kernel evaluates inline (plan/overrides.conv_aggregate);
- `assign_stages` / `describe_stages` compute the stage regions and which
  logical operators each physical node absorbed;
- `explain_fused` renders the `*(k)`-annotated tree plus a per-stage summary
  (members, fused-in operators, per-node dispatch counts when a finished
  query's collector is supplied);
- `emit_stage_events` mirrors the stage structure to the structured event
  log (`stage.fused`, one record per stage) so offline tooling can join
  stages with the per-node dispatch ledger.
"""

from __future__ import annotations

# Pipeline breakers: operators that materialize, reshuffle or leave the
# device — a fused per-batch program cannot span them. Matched by class NAME
# so this module needs no exec imports (several would cycle).
BOUNDARY_EXECS = frozenset({
    "ShuffleExchangeExec", "MeshExchangeExec", "AdaptiveShuffleReaderExec",
    "_GatherAllExec", "ArrowScanExec", "RangeExec", "ArrowEvalPythonExec",
    "CacheExec", "CoalesceExec", "HostFallbackExec",
})


def compose_prestage(child, max_depth: int = 8):
    """Fold the stack of context-free Filter/Project execs under an
    aggregate into `(prefilter, preproject, base_child)`.

    Predicates AND-compose; every expression is rebased onto the BASE
    child's output by substituting each BoundReference with the projection
    term it names (Alias unwrapped — a naming shell, not a value node), so
    the consumer evaluates the whole stack inside one kernel with
    `prefilter_on_projected=False` semantics: the filter masks RAW rows,
    the projection re-derives its columns on whatever survives. Returns
    `(None, None, child)` when nothing composable; `max_depth` bounds the
    rebase blowup on pathological towers (beyond it the remaining execs
    simply keep their own fused programs)."""
    from spark_rapids_tpu.exec import basic as XB
    from spark_rapids_tpu.expr import core as E
    from spark_rapids_tpu.expr import predicates as P
    from spark_rapids_tpu.expr.misc import is_context_free

    stack = []
    cur = child
    while len(stack) < max_depth:
        if isinstance(cur, XB.FilterExec) and is_context_free(cur.condition):
            stack.append(cur)
        elif (isinstance(cur, XB.ProjectExec)
                and is_context_free(*cur.project_list)):
            stack.append(cur)
        else:
            break
        cur = cur.children[0]
    if not stack:
        return None, None, child

    def rebase(e, terms):
        if terms is None:
            return e
        plist = [t.child if isinstance(t, E.Alias) else t for t in terms]
        return e.transform(lambda x: plist[x.ordinal]
                           if isinstance(x, E.BoundReference) else x)

    terms = None   # projection exprs in base terms (None = identity)
    cond = None
    for node in reversed(stack):   # bottom-up: closest to the base first
        if isinstance(node, XB.FilterExec):
            c = rebase(node.condition, terms)
            cond = c if cond is None else P.And(cond, c)
        else:
            terms = [rebase(t, terms) for t in node.project_list]
    return cond, terms, cur


def fused_members(node) -> list:
    """Human-readable list of the logical operators this physical node
    absorbed (aggregate pre/post hoists, join stream hoists) — duck-typed on
    the hoist attributes so new hosts join the read-out for free."""
    out = []
    pf = getattr(node, "postfilter", None)
    if pf is not None:
        out.append(f"Filter[HAVING] {pf!r}")
    pre = getattr(node, "prefilter", None)
    if pre is not None:
        out.append(f"Filter {pre!r}")
    prj = getattr(node, "preproject", None)
    if prj is not None:
        out.append(f"Project {prj!r}")
    spf = getattr(node, "stream_prefilter", None)
    if spf is not None:
        out.append(f"Filter[stream] {spf!r}")
    spp = getattr(node, "stream_preproject", None)
    if spp is not None:
        out.append(f"Project[stream] {spp!r}")
    for h in getattr(node, "hops", None) or []:
        out.append(f"BroadcastHashJoin[{h.join_type}] "
                   f"lk={h.left_keys!r} rk={h.right_keys!r}")
        hpf = getattr(h, "stream_prefilter", None)
        if hpf is not None:
            out.append(f"Filter[stream] {hpf!r}")
        hpp = getattr(h, "stream_preproject", None)
        if hpp is not None:
            out.append(f"Project[stream] {hpp!r}")
    return out


def _stream_child_index(node) -> int | None:
    """For joins the fused per-batch pipeline continues into the STREAM side
    only — the build side materializes (concat_all) and starts a new stage."""
    sci = getattr(node, "stream_child_index", None)
    if sci is not None:
        return sci
    sil = getattr(node, "stream_is_left", None)
    if sil is None or len(node.children) != 2:
        return None
    return 0 if sil else 1


def assign_stages(root) -> dict:
    """{id(node): stage_number} for every exec in a fused stage; boundary
    execs carry no stage. Numbering is preorder, 1-based (Spark's `*(k)`)."""
    stages: dict = {}
    counter = [0]

    def visit(node, parent_stage):
        name = type(node).__name__
        if name in BOUNDARY_EXECS:
            my = None
        elif parent_stage is not None:
            my = parent_stage
        else:
            counter[0] += 1
            my = counter[0]
        if my is not None:
            stages[id(node)] = my
        si = _stream_child_index(node)
        for i, c in enumerate(node.children):
            # join build side / boundary children start fresh stages
            child_stage = my if (my is not None
                                 and (si is None or i == si)) else None
            visit(c, child_stage)

    visit(root, None)
    return stages


def describe_stages(root) -> list:
    """Per-stage summary in stage order: members (preorder class names with
    node ids) and the logical operators fused into each member."""
    stages = assign_stages(root)
    by_stage: dict = {}

    def visit(node):
        k = stages.get(id(node))
        if k is not None:
            ent = by_stage.setdefault(
                k, {"stage": k, "members": [], "fused": []})
            ent["members"].append({
                "name": type(node).__name__,
                "node": getattr(node, "_node_id", None),
            })
            ent["fused"].extend(fused_members(node))
        for c in node.children:
            visit(c)

    visit(root)
    return [by_stage[k] for k in sorted(by_stage)]


def render_tree(root) -> str:
    """The exec tree with Spark's WholeStageCodegen notation: stage members
    render as `*(k) Name`, boundary execs plain."""
    stages = assign_stages(root)
    lines = []

    def visit(node, indent):
        k = stages.get(id(node))
        mark = f"*({k}) " if k is not None else ""
        args = node.args_string()
        lines.append("  " * indent + mark + type(node).__name__
                     + (" " + args if args else ""))
        for c in node.children:
            visit(c, indent + 1)

    visit(root, 0)
    return "\n".join(lines) + "\n"


def explain_fused(root, collector=None) -> str:
    """`explain(fused=True)` body: the stage-annotated tree plus one summary
    block per stage naming its members, the logical operators fused into
    them, and (when a finished query's collector is supplied) each member's
    dispatch and batch counts — dispatches/batch is the fusion win metric."""
    out = [render_tree(root)]
    per_node: dict = {}
    if collector is not None:
        from spark_rapids_tpu.runtime import stats as STATS
        for e in STATS.node_table(collector):
            if e["id"] is not None:
                per_node[e["id"]] = e
    out.append("== Fused stages ==")
    for ent in describe_stages(root):
        names = []
        for m in ent["members"]:
            label = m["name"]
            e = per_node.get(m["node"])
            if e is not None and e.get("dispatches") is not None:
                label += (f" [dispatches={e['dispatches']}"
                          + (f" batches={e['batches']}"
                             if e.get("batches") else "") + "]")
            names.append(label)
        out.append(f"Stage {ent['stage']}: " + ", ".join(names))
        for f in ent["fused"]:
            out.append(f"    fused: {f}")
    return "\n".join(out) + "\n"


def emit_stage_events(root, query_id) -> None:
    """One `stage.fused` event-log record per stage (query-scoped): the
    offline join key between the stage structure and the per-node dispatch
    ledger in `plan.stats`."""
    from spark_rapids_tpu.runtime import eventlog as EL
    if not EL.enabled():
        return
    for ent in describe_stages(root):
        EL.emit("stage.fused", query=query_id, stage=ent["stage"],
                members=[m["name"] for m in ent["members"]],
                nodes=[m["node"] for m in ent["members"]],
                fused=ent["fused"])
