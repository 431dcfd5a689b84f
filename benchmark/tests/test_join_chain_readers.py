"""The two readers of the fused join chain (PR 36) on a hand-made ``ctx``:
a program list as a commit that runs a mispredicted batch twice leaves it,
one as a commit that cuts the first run's output leaves it, and traces and
span lists that hold no chain, which give no reading."""

import pytest

from benchmark.metrics_per_layer import (_spans, join_chain_device_s_per_query,
                                         join_chain_runs_per_batch)

S = 1_000_000_000
T0 = 9000 * S
BATCHES = 4


def span(name, sid, parent, t0, t1, **counts):
    return {"name": name, "id": sid, "parent": parent, "trace": None,
            "thread": "client-0", "t0": T0 + int(t0 * S),
            "t1": T0 + int(t1 * S), "counts": counts}


def chain_spans(landed=None):
    """The warm-up query and two of the window, 10 s behind the harness,
    each with a probe span a stream batch."""
    out = []
    for root, t in ((1, 0.0), (20, 20.0), (40, 24.0)):
        out.append(span("query", root, None, t, t + 4.0))
        for b in range(BATCHES):
            counts = {"modes": "dense+dense"}
            if landed is not None:
                counts.update(landed=landed[b], capacity_pred=64,
                              capacity_out=32)
            out += [span("HashJoinChain.probe", root + 1 + 2 * b, root,
                         t + b, t + b + 0.9, **counts),
                    span("sync.count", root + 2 + 2 * b, root + 1 + 2 * b,
                         t + b + 0.5, t + b + 0.9, rows=20, capacity=64)]
    return out


OTHERS = [("jit_srt_HashJoin_probe", 9.0), ("jit_srt_HashJoinChainless", 9.0),
          ("jit_srt_ParquetScan_decode", 9.0), ("jit_gather", 9.0)]
# a mispredicted batch runs the chain again: two runs a batch
PARENT_RUNS = OTHERS + [("jit_srt_HashJoinChain_probe", 0.5),
                        ("jit_srt_HashJoinChain_probe", 0.25)] * BATCHES
# one run a batch, two of them cut by the landing program
CHANGE_RUNS = OTHERS + [("jit_srt_HashJoinChain_probe", 0.5)] * BATCHES \
    + [("jit_srt_HashJoinChain_land", 0.125)] * 2


def ctx_of(monkeypatch, runs, spans, **over):
    monkeypatch.setattr(_spans, "_recorded", lambda: spans)
    ctx = {"cell": {"chips": 1}, "_program_runs": runs,
           "traced_span": (10.0, 14.0),
           "done": [{"client": 0, "query": "q5", "start": 10.0, "end": 14.0},
                    {"client": 0, "query": "q5", "start": 14.0,
                     "end": 18.0}]}
    ctx.update(over)
    return ctx


@pytest.mark.parametrize("runs, spans, seconds, runs_a_batch", [
    pytest.param(PARENT_RUNS, chain_spans(), 3.0, 2.0, id="parent"),
    pytest.param(CHANGE_RUNS, chain_spans(["sliced", "sliced", "hit", "hit"]),
                 2.25, 1.0, id="change"),
    # one batch of the four runs again
    pytest.param(CHANGE_RUNS + [("srt_HashJoinChain_probe", 0.75)],
                 chain_spans(["sliced", "rerun", "hit", "hit"]), 3.0, 1.25,
                 id="one_rerun")])
def test_chain_readers_by_hand(monkeypatch, runs, spans, seconds,
                               runs_a_batch):
    ctx = ctx_of(monkeypatch, runs, spans)
    assert join_chain_device_s_per_query.read(ctx) == pytest.approx(seconds)
    assert join_chain_runs_per_batch.read(ctx) == pytest.approx(runs_a_batch)


def test_half_a_query_in_the_span(monkeypatch):
    """The seconds are those inside the span over the part of a query it
    holds; the runs a batch take the query's spans whole."""
    ctx = ctx_of(monkeypatch, CHANGE_RUNS,
                 chain_spans(["sliced", "sliced", "hit", "hit"]),
                 traced_span=(12.0, 14.0))
    assert join_chain_device_s_per_query.read(ctx) == pytest.approx(4.5)
    assert join_chain_runs_per_batch.read(ctx) == pytest.approx(1.0)


@pytest.mark.parametrize("why", ["no_trace", "no_chain_program",
                                 "no_chain_span", "no_span_buffer",
                                 "no_traced_span"])
def test_no_reading_is_not_zero(monkeypatch, why):
    """A run without a trace, a query that stacks no broadcast joins (q1,
    q3), a commit without spans: None, never 0, and no reader raises."""
    runs, spans, over = CHANGE_RUNS, chain_spans(), {}
    if why == "no_trace":
        runs = None
    elif why == "no_chain_program":
        runs = OTHERS
    elif why == "no_chain_span":
        spans = [s for s in spans if s["name"] != "HashJoinChain.probe"]
    elif why == "no_span_buffer":
        spans = None
    elif why == "no_traced_span":
        over["traced_span"] = None
    ctx = ctx_of(monkeypatch, runs, spans, **over)
    silent = {"no_trace": {join_chain_device_s_per_query,
                           join_chain_runs_per_batch},
              "no_chain_program": {join_chain_device_s_per_query,
                                   join_chain_runs_per_batch},
              "no_chain_span": {join_chain_runs_per_batch},
              "no_span_buffer": {join_chain_runs_per_batch},
              "no_traced_span": {join_chain_device_s_per_query,
                                 join_chain_runs_per_batch}}[why]
    for reader in (join_chain_device_s_per_query, join_chain_runs_per_batch):
        value = reader.read(ctx)
        assert (value is None) == (reader in silent), (reader.__name__, value)


def test_the_entries_and_their_cells():
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, unit in (("join_chain_device_s_per_query", "s/query"),
                       ("join_chain_runs_per_batch", "1/batch")):
        m = entries[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            unit, "lower", "device_trace", "input_rows_per_s")
        assert m["layer"] == "exec operators and dispatch"
        assert m["workloads"] == ["tpch_sf1_batch.q5", "tpcds_sf1_batch.q67"]
