"""What the readers of the program's spans share: the window's queries,
each with the spans it made, from ``spark_rapids_tpu.runtime.tracing``'s
in-memory buffer (filled while ``spark.rapids.tpu.sql.trace.enabled`` is on,
as the configuration's ``trace_conf`` has it in a traced run).

A query of the window is a span named ``query`` with no ``query`` above it;
what it made are its descendants, on whatever thread. The window's queries
are the last ``len(ctx["done"])`` of them, paired with ``ctx["done"]`` in
order of completion; the pairing stands only if one offset between the
program's clock and the harness's puts every root inside its harness
interval. Where the program has no buffer (a commit before it had one),
where the buffer dropped a span, or where the roots do not match, there is
no reading: ``window_queries`` returns None and so does every reader.
"""

SLACK_S = 0.005


def _recorded():
    try:
        from spark_rapids_tpu.runtime import tracing
    except ImportError:
        return None
    if not hasattr(tracing, "recorded") or tracing.dropped():
        return None
    return tracing.recorded()


def match_queries(spans: list, done: list):
    """[{"root", "spans", "request", "parse"}] a query of ``done``, or None."""
    by_id = {s["id"]: s for s in spans}
    kids: dict = {}
    for s in spans:
        kids.setdefault(s["parent"], []).append(s)

    def under_a_query(s):
        while s["parent"] in by_id:
            s = by_id[s["parent"]]
            if s["name"] == "query":
                return True
        return False

    roots = sorted((s for s in spans
                    if s["name"] == "query" and not under_a_query(s)),
                   key=lambda s: s["t1"])
    if not done or len(roots) < len(done):
        return None
    roots = roots[-len(done):]
    ends = sorted(done, key=lambda d: d["end"])
    # one offset (harness clock = program clock - offset) has to hold them all
    latest = max(r["t1"] / 1e9 - d["end"] for r, d in zip(roots, ends))
    earliest = min(r["t0"] / 1e9 - d["start"] for r, d in zip(roots, ends))
    if latest > earliest + SLACK_S:
        return None
    out = []
    for root in roots:
        made, todo = [], [root]
        while todo:
            s = todo.pop()
            made.append(s)
            todo.extend(kids.get(s["id"], ()))
        request = by_id.get(root["parent"])
        if request is not None and request["name"] != "endpoint.request":
            request = None
        if request is not None:
            parses = [s for s in kids.get(request["id"], ())
                      if s["name"] == "sql.parse"]
        else:   # the text was parsed on this thread just before
            parses = [s for s in kids.get(None, ())
                      if s["name"] == "sql.parse"
                      and s["thread"] == root["thread"]
                      and s["t1"] <= root["t0"]]
        parse = max(parses, key=lambda s: s["t1"]) if parses else None
        out.append({"root": root, "spans": made, "request": request,
                    "parse": parse})
    return out


def window_queries(ctx):
    if "_window_queries" not in ctx:
        spans = _recorded()
        ctx["_window_queries"] = (None if spans is None
                                  else match_queries(spans, ctx["done"]))
    return ctx["_window_queries"]


def seconds(spans, *names) -> float:
    """Summed durations of the spans called one of ``names``; a name that
    ends in a dot is a prefix (``sync.``)."""
    return sum(s["t1"] - s["t0"] for s in spans
               if any(s["name"] == n or (n.endswith(".")
                                         and s["name"].startswith(n))
                      for n in names)) / 1e9


def mean_seconds_a_query(ctx, *names):
    """Mean over the window's queries of ``seconds(..)``; None where no
    query of the window has such a span."""
    queries = window_queries(ctx)
    if not queries:
        return None
    if not any(seconds(q["spans"], *names) for q in queries):
        return None
    return sum(seconds(q["spans"], *names) for q in queries) / len(queries)
