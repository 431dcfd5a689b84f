"""The cell ``tpch_sf1_batch.q18``: its four readers on a hand-made ``ctx``
whose answers are known by hand, the bytes of ``q18_bytes.py`` and the
reference on data counted by hand, the generator's ``c_name``, the float32
control, and whole rehearsal runs on the CPU with one answer altered, each of
which has to come out as not correct."""

import copy

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark import compare, control, q18_bytes, run
from benchmark.datagen import tpch, tpch_q18
from benchmark.metrics_per_layer import (
    _orderkey, _spans, agg_chain_rejects_per_query, agg_presorted_batches_pct,
    orderkey_agg_device_s_per_query, orderkey_agg_hbm_roofline)
from benchmark.reference import q18 as ref18

CELL = "tpch_sf1_batch.q18"
SCALE = 0.05          # three orders pass 300 at this seed: rows to alter
SEED = 2**31 + 18
S = 1_000_000_000
T0 = 9000 * S
PEAKS = {"hbm_bytes_per_s": 1000.0}


def span(name, sid, parent, t0, t1, **counts):
    return {"name": name, "id": sid, "parent": parent, "trace": None,
            "thread": "client-0", "t0": T0 + int(t0 * S),
            "t1": T0 + int(t1 * S), "counts": counts}


def q18_spans(accepted=(1, 1, 0), presorted=(1, 1, 1), counted=True):
    """The warm-up query and two of the window, 10 s behind the harness:
    the orderkey aggregate's first batch and three chained steps, then the
    five-key group-by's batch and a chain step too small to run."""
    out = []
    for sid, t in ((1, 0.0), (20, 20.0), (40, 24.0)):
        out.append(span("query", sid, None, t, t + 4.0))
        one = dict(path="sort", keys=1, sort_operands=0, capacity=64)
        first = dict(one, presorted=1) if counted else one
        out.append(span("HashAggregate.agg", sid + 1, sid, t + 0.1, t + 0.2,
                        **first))
        for i, (a, p) in enumerate(zip(accepted, presorted)):
            counts = dict(one, accepted=a, presorted=p) if counted else one
            out.append(span("HashAggregate.chain", sid + 2 + i, sid,
                            t + 0.3 + i / 10, t + 0.35 + i / 10, **counts))
        five = dict(path="sort", keys=5, sort_operands=13, capacity=16)
        out += [span("HashAggregate.agg", sid + 8, sid, t + 1.0, t + 1.1,
                     **(dict(five, presorted=0) if counted else five)),
                span("HashAggregate.chain", sid + 9, sid, t + 1.2, t + 1.3),
                span("HashAggregate.merge", sid + 10, sid, t + 1.4, t + 1.5,
                     **(dict(one, presorted=1) if counted else one))]
    return out


RUNS = [("jit_srt_HashAggregateExec", 0.25),
        ("jit_srt_HashAggregateExec_chain", 0.1),
        ("jit_srt_HashAggregateExec_key_stats", 0.05),
        ("jit_srt_HashAggregateExec_finalize", 0.1),
        ("jit_srt_HashJoin_probe", 9.0), ("jit_srt_SortExec", 9.0),
        ("jit_srt_ParquetScan_decode", 9.0), ("jit_gather", 9.0)]
BYTES = {"bytes": 500, "rows": 20, "groups": 5}


def ctx_of(monkeypatch, runs=RUNS, spans=None, moved=BYTES, **over):
    monkeypatch.setattr(_spans, "_recorded",
                        lambda: q18_spans() if spans is None else spans)
    ctx = {"cell": {"chips": 1}, "peaks": PEAKS, "_program_runs": runs,
           "_q18_bytes": moved, "traced_span": (10.0, 14.0),
           "queries": {"q18": {"input_rows": 1}},
           "done": [{"client": 0, "query": "q18", "start": 10.0, "end": 14.0},
                    {"client": 0, "query": "q18", "start": 14.0,
                     "end": 18.0}]}
    ctx.update(over)
    return ctx


def test_q18_readers_by_hand(monkeypatch):
    ctx = ctx_of(monkeypatch)
    assert orderkey_agg_device_s_per_query.read(ctx) == pytest.approx(0.5)
    # 500 B over 1000 B/s = 0.5 s least, over 0.5 s of programs
    assert orderkey_agg_hbm_roofline.read(ctx) == pytest.approx(100.0)
    # a query: the orderkey aggregate's agg and three chain steps, all
    # presorted; the five-key agg and the chain that never ran are not its
    assert agg_presorted_batches_pct.read(ctx) == pytest.approx(100.0)
    # one step of each of the two window queries thrown away
    assert agg_chain_rejects_per_query.read(ctx) == pytest.approx(1.0)


def test_a_step_that_sorted_and_none_rejected(monkeypatch):
    ctx = ctx_of(monkeypatch, spans=q18_spans(accepted=(1, 1, 1),
                                              presorted=(1, 0, 0)))
    assert agg_presorted_batches_pct.read(ctx) == pytest.approx(50.0)
    assert agg_chain_rejects_per_query.read(ctx) == 0.0


def test_half_a_query_in_the_span(monkeypatch):
    ctx = ctx_of(monkeypatch, traced_span=(12.0, 14.0))
    assert orderkey_agg_device_s_per_query.read(ctx) == pytest.approx(1.0)
    assert orderkey_agg_hbm_roofline.read(ctx) == pytest.approx(50.0)


@pytest.mark.parametrize("why", ["no_trace", "no_span_buffer", "parent",
                                 "no_data", "no_traced_span"])
def test_no_reading_is_not_zero(monkeypatch, why):
    """A commit whose spans count neither ``accepted`` nor ``presorted``
    (the parent), a run without a trace, data that is not found: the readers
    return None, never 0, and none raises."""
    runs, spans, over = RUNS, None, {}
    if why == "no_trace":
        runs = None
    elif why == "no_span_buffer":
        spans = []
    elif why == "parent":
        spans = q18_spans(counted=False)
    elif why == "no_data":
        over["moved"] = None
    elif why == "no_traced_span":
        over["traced_span"] = None
    ctx = ctx_of(monkeypatch, runs, spans, **over)
    silent = {"no_trace": {orderkey_agg_device_s_per_query,
                           orderkey_agg_hbm_roofline},
              "no_span_buffer": {agg_presorted_batches_pct,
                                 agg_chain_rejects_per_query},
              "parent": {agg_presorted_batches_pct,
                         agg_chain_rejects_per_query},
              "no_data": {orderkey_agg_hbm_roofline},
              "no_traced_span": {orderkey_agg_device_s_per_query,
                                 orderkey_agg_hbm_roofline}}[why]
    for reader in (orderkey_agg_device_s_per_query, orderkey_agg_hbm_roofline,
                   agg_presorted_batches_pct, agg_chain_rejects_per_query):
        value = reader.read(ctx)
        assert (value is None) == (reader in silent), (reader.__name__, value)


def test_the_program_names_the_readers_match():
    for name in ("jit_srt_HashAggregateExec", "srt_HashAggregateExec_chain",
                 "jit_srt_HashAggregateExec_key_stats",
                 "jit_srt_HashAggregateExec_finalize"):
        assert _orderkey.AGG.match(name)
    for name in ("jit_srt_SortExec", "jit_gather", "jit_srt_HashJoin_probe",
                 "jit_srt_ExpandExec"):
        assert not _orderkey.AGG.match(name)


# -- the bytes, the generator and the reference, on data counted by hand -----

def test_orderkey_bytes_by_hand(tmp_path):
    pq.write_table(pa.table({"l_orderkey": pa.array([1, 1, 2, 5, 5, 5]),
                             "l_quantity": pa.array([1.0] * 6)}),
                   str(tmp_path / "part-0000.parquet"))
    moved = q18_bytes.orderkey_bytes(str(tmp_path))
    assert moved == {"bytes": 6 * 16 + 3 * 16, "rows": 6, "groups": 3}


def test_c_name_is_derived_and_nothing_else_moves():
    files = {"lineitem": 2, "orders": 2, "customer": 1}
    plain = tpch.tables(0.001, SEED, files)
    named = tpch_q18.tables(0.001, SEED, files)
    for t in files:
        for c in plain[t][0].column_names:
            assert plain[t][0].column(c).equals(named[t][0].column(c)), c
    names = named["customer"][0].column("c_name").to_pylist()
    assert names[:2] == ["Customer#000000001", "Customer#000000002"]
    assert len(names) == 150


def tiny_tables():
    """Four orders: 2 and 3 pass 300, order 3 the dearer; order 1 does not."""
    return {
        "customer": {"c_custkey": np.array([7, 8]),
                     "c_name": np.array(["Customer#000000007",
                                         "Customer#000000008"], object)},
        "orders": {"o_orderkey": np.array([3, 1, 2, 4]),
                   "o_custkey": np.array([8, 7, 7, 8]),
                   "o_orderdate": np.array([9000, 9001, 9002, 9003]),
                   "o_totalprice": np.array([500.25, 900.0, 100.5, 50.0])},
        "lineitem": {"l_orderkey": np.array([1, 2, 2, 2, 2, 2, 2, 2, 3, 3,
                                             3, 3, 3, 3, 3, 4]),
                     "l_quantity": np.array([50.0] + [43.0] * 7
                                            + [50.0] * 6 + [1.0] + [49.0])}}


def test_reference_on_data_counted_by_hand():
    rows = ref18.reference(tiny_tables())
    assert rows == [
        {"c_name": "Customer#000000008", "c_custkey": 8, "o_orderkey": 3,
         "o_orderdate": 9000, "o_totalprice": 500.25, "sum": 301.0},
        {"c_name": "Customer#000000007", "c_custkey": 7, "o_orderkey": 2,
         "o_orderdate": 9002, "o_totalprice": 100.5, "sum": 301.0}]


# -- the comparison, through whole rehearsal runs -----------------------------

@pytest.fixture
def workdir(tmp_path_factory):
    return str(tmp_path_factory.getbasetemp() / "benchmark_work")


def _sum_off_by_one(rows):
    rows[-1]["sum"] += 1
    return rows


def _drop_row(rows):
    return rows[:-1]


def _swap_rows(rows):
    rows[0], rows[1] = rows[1], rows[0]
    return rows


def _name_changed(rows):
    rows[0]["c_name"] = rows[0]["c_name"].replace("#0", "#9", 1)
    return rows


FAULTS = {"sum_off_by_one": _sum_off_by_one, "row_dropped": _drop_row,
          "rows_swapped": _swap_rows, "name_changed": _name_changed}


def test_sound_run_is_correct(workdir):
    r = run.run_cell(CELL, SEED, 1.0, False, rehearsal=True, scale=SCALE,
                     workdir=workdir)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 2, r
    assert r["compared"]["exact_mismatch"] == [0, 0]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_altered_answer_is_not_correct(fault, workdir, monkeypatch):
    sound = run.SqlEntry.run
    calls = {"n": 0}

    def broken(self, query):
        rows, spans = sound(self, query)
        calls["n"] += 1
        # the warm-up is the first call; break one answer of the window only
        if calls["n"] == 2:
            assert len(rows) >= 2
            rows = FAULTS[fault](copy.deepcopy(rows))
        return rows, spans
    monkeypatch.setattr(run.SqlEntry, "run", broken)
    r = run.run_cell(CELL, SEED, 1.0, False, rehearsal=True, scale=SCALE,
                     workdir=workdir)
    assert r["attempted"] >= 2 and not r["correct"], r
    if fault == "row_dropped":
        assert r["compared"]["rows_off"][0] == 1
    elif fault == "sum_off_by_one":
        assert r["compared"]["float_gap"][0] > 1e-9
    else:
        assert r["compared"]["exact_mismatch"][0] >= 1


def test_float32_control_is_not_correct_by_the_float_gap(workdir):
    refs, controls, limit = control.control_rows(CELL, SEED, SCALE, workdir)
    correct, compared = compare.compare_all(list(controls.items()), refs,
                                            limit, 0)
    # o_totalprice in float32: some 1e-8 off, where the limit is 1e-9; the
    # rows, keys, names and sums of quantity stay exact
    assert not correct and compared["float_gap"][0] > 1e-9, compared
    assert compared["exact_mismatch"][0] == 0
    assert compared["rows_off"][0] == 0
    assert compare.compare_all(list(refs.items()), refs, limit, 0)[0]
