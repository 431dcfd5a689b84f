"""The cell ``tpcds_sf1_batch.q67`` (PR 35): its five readers on a hand-made
``ctx``, program runs and span list whose answers are known by hand; the
bytes of ``rollup_bytes.py`` on a data set small enough to count by hand; the
reference against a dictionary-loop oracle; and a whole rehearsal run on the
CPU with one answer altered, which has to come out as not correct."""

import copy

import numpy as np
import pytest

from benchmark import compare, control, rollup_bytes, run
from benchmark.metrics_per_layer import (
    _rollup, _spans, agg_sort_operands, rollup_agg_device_s_per_query,
    rollup_agg_hbm_roofline, window_device_s_per_query, window_hbm_roofline)
from benchmark.reference import q67 as ref67

CELL = "tpcds_sf1_batch.q67"
SCALE = 0.01
SEED = 2**31 + 67
S = 1_000_000_000
T0 = 9000 * S
PEAKS = {"hbm_bytes_per_s": 1000.0}
BYTES = {"rollup": 400, "window": 50, "joined_rows": 5, "groups": 3}


def span(name, sid, parent, t0, t1, **counts):
    return {"name": name, "id": sid, "parent": parent, "trace": None,
            "thread": "client-0", "t0": T0 + int(t0 * S),
            "t1": T0 + int(t1 * S), "counts": counts}


def q67_spans():
    """The warm-up query and two of the window, 10 s behind the harness."""
    out = []
    for sid, t in ((1, 0.0), (10, 20.0), (30, 24.0)):
        out += [span("query", sid, None, t, t + 4.0),
                span("ExpandExec", sid + 1, sid, t + 0.1, t + 0.2, rows=5,
                     rows_out=45, projections=9, capacity=8,
                     capacity_out=64),
                span("HashAggregate.agg", sid + 2, sid, t + 0.3, t + 1.0,
                     path="sort", keys=9, sort_operands=2, packed_bits=87,
                     rows=45, capacity=64, groups=3),
                span("HashAggregate.merge", sid + 3, sid, t + 1.0, t + 1.5,
                     path="sort", keys=9, sort_operands=sid % 7,
                     capacity=64),
                span("HashAggregate.agg", sid + 4, sid, t + 1.5, t + 1.6,
                     path="dense", keys=1, sort_operands=0, capacity=8),
                span("WindowExec", sid + 5, sid, t + 2.0, t + 3.0, rows=3,
                     capacity=4, exprs=1, sort_operands=6)]
    return out


RUNS = [("jit_srt_ExpandExec", 0.05), ("jit_srt_HashAggregateExec", 0.25),
        ("jit_srt_HashAggregateExec_chain", 0.05),
        ("jit_srt_HashAggregateExec_finalize", 0.05),
        ("jit_srt_WindowExec", 0.1), ("jit_srt_SortExec", 9.0),
        ("jit_srt_HashJoin_probe", 9.0), ("jit_gather", 9.0)]
# a commit whose window runs as eager programs (the parent)
EAGER_WINDOW = [r for r in RUNS if r[0] != "jit_srt_WindowExec"]


def ctx_of(monkeypatch, runs, spans=None, moved=BYTES, **over):
    monkeypatch.setattr(_spans, "_recorded",
                        lambda: q67_spans() if spans is None else spans)
    ctx = {"cell": {"chips": 1}, "peaks": PEAKS, "_program_runs": runs,
           "_rollup_bytes": moved, "traced_span": (10.0, 14.0),
           "queries": {"q67": {"input_rows": 1}},
           "done": [{"client": 0, "query": "q67", "start": 10.0, "end": 14.0},
                    {"client": 0, "query": "q67", "start": 14.0,
                     "end": 18.0}]}
    ctx.update(over)
    return ctx


def test_q67_readers_by_hand(monkeypatch):
    ctx = ctx_of(monkeypatch, RUNS)
    # the traced query is the span tree at 20 s: its merge counted 10 % 7
    assert agg_sort_operands.read(ctx) == 3
    assert rollup_agg_device_s_per_query.read(ctx) == pytest.approx(0.4)
    assert window_device_s_per_query.read(ctx) == pytest.approx(0.1)
    # 400 B over 1000 B/s = 0.4 s least, over 0.4 s of programs
    assert rollup_agg_hbm_roofline.read(ctx) == pytest.approx(100.0)
    assert window_hbm_roofline.read(ctx) == pytest.approx(50.0)


def test_half_a_query_in_the_span(monkeypatch):
    ctx = ctx_of(monkeypatch, RUNS, traced_span=(12.0, 14.0))
    assert rollup_agg_device_s_per_query.read(ctx) == pytest.approx(0.8)
    assert rollup_agg_hbm_roofline.read(ctx) == pytest.approx(50.0)


@pytest.mark.parametrize("why", ["no_trace", "no_span_buffer", "parent",
                                 "no_data", "no_traced_span"])
def test_no_reading_is_not_zero(monkeypatch, why):
    """A commit whose window runs eagerly and whose aggregate counts no
    operands (the parent), a run without a trace, data that is not found:
    the readers return None, never 0, and none raises."""
    runs, spans, over = RUNS, None, {}
    if why == "no_trace":
        runs = None
    elif why == "no_span_buffer":
        spans = []
    elif why == "parent":
        runs = EAGER_WINDOW
        spans = [dict(s, counts={}) for s in q67_spans()]
    elif why == "no_data":
        over["moved"] = None
    elif why == "no_traced_span":
        over["traced_span"] = None
    ctx = ctx_of(monkeypatch, runs, spans, **over)
    silent = {"no_trace": {rollup_agg_device_s_per_query,
                           window_device_s_per_query, rollup_agg_hbm_roofline,
                           window_hbm_roofline},
              "no_span_buffer": {agg_sort_operands},
              "parent": {agg_sort_operands, window_device_s_per_query,
                         window_hbm_roofline},
              "no_data": {rollup_agg_hbm_roofline, window_hbm_roofline},
              "no_traced_span": {agg_sort_operands,
                                 rollup_agg_device_s_per_query,
                                 window_device_s_per_query,
                                 rollup_agg_hbm_roofline,
                                 window_hbm_roofline}}[why]
    for reader in (agg_sort_operands, rollup_agg_device_s_per_query,
                   window_device_s_per_query, rollup_agg_hbm_roofline,
                   window_hbm_roofline):
        value = reader.read(ctx)
        assert (value is None) == (reader in silent), (reader.__name__, value)


def test_the_program_names_the_readers_match():
    for name in ("jit_srt_ExpandExec", "srt_HashAggregateExec_chain",
                 "jit_srt_HashAggregateExec_key_stats"):
        assert _rollup.ROLLUP_AGG.match(name)
    assert _rollup.WINDOW.match("jit_srt_WindowExec")
    for name in ("jit_srt_SortExec", "jit_gather", "jit_srt_HashJoin_probe"):
        assert not _rollup.ROLLUP_AGG.match(name)
        assert not _rollup.WINDOW.match(name)


# -- the bytes and the reference, on data counted by hand --------------------

def tiny_tables():
    """Two items, one store, three sales in 2000 and one outside."""
    d0 = 2_415_022
    days = np.arange(d0, d0 + 73_049)
    month_seq = np.full(len(days), 0)
    y2000 = 36_523       # 2000-01-01 is this many days after 1900-01-02
    month_seq[y2000:y2000 + 31] = 1200
    return {
        "date_dim": {"d_date_sk": days, "d_month_seq": month_seq,
                     "d_year": np.full(len(days), 2000),
                     "d_qoy": np.full(len(days), 1),
                     "d_moy": np.full(len(days), 1)},
        "item": {"i_item_sk": np.array([1, 2]),
                 "i_category": np.array(["Ab", "Ab"], object),
                 "i_class": np.array(["c", "c"], object),
                 "i_brand": np.array(["bb", "bbb"], object),
                 "i_product_name": np.array(["x", "yy"], object)},
        "store": {"s_store_sk": np.array([1]),
                  "s_store_id": np.array(["S"], object)},
        "store_sales": {
            "ss_sold_date_sk": np.array([d0 + y2000, d0 + y2000 + 1,
                                         d0 + y2000 + 2, d0 + 5]),
            "ss_item_sk": np.array([1, 1, 2, 2]),
            "ss_store_sk": np.array([1, 1, 1, 1]),
            "ss_quantity": np.array([2, 3, 1, 50], np.int32),
            "ss_sales_price": np.array([100, 10, 7, 999])}}


def test_rollup_bytes_by_hand():
    moved = rollup_bytes.step_bytes(tiny_tables())
    assert moved["joined_rows"] == 3
    # groups: 2 a level for the six levels that hold the brand or more, one
    # for (category, class), (category), ()
    assert moved["groups"] == 2 * 6 + 3
    # a joined row: Ab c bb x + three ints + S + the product = 2+1+2+1+12+1+8
    rows = 2 * 27 + (2 + 1 + 3 + 2 + 12 + 1 + 8)
    # groups: item 1's keys are 27 - 8 wide at the full level, item 2's 21
    full = [19, 21]
    cut = [[w - 1 for w in full], [w - 5 for w in full],
           [w - 9 for w in full], [w - 13 for w in full],
           [5, 6], [3], [2], [0]]
    groups = sum(full) + sum(map(sum, cut)) + 8 * 15
    assert moved["rollup"] == 9 * rows + groups
    assert moved["window"] == 2 * groups + 4 * 15


def test_reference_on_data_counted_by_hand():
    rows = ref67.reference(tiny_tables())
    assert len(rows) == 15
    # NULLs first: the grand total, then the category's, and so on down
    assert rows[0] == {"i_category": "<null>", "i_class": "<null>",
                       "i_brand": "<null>", "i_product_name": "<null>",
                       "d_year": -1, "d_qoy": -1, "d_moy": -1,
                       "s_store_id": "<null>", "sumsales": 237, "rk": 1}
    assert [r["sumsales"] for r in rows[:3]] == [237, 237, 237]
    in_category = [r for r in rows if r["i_category"] == "Ab"]
    # (Ab), (Ab, c) tie at 237; item 1's six rows tie at 230; item 2's at 7
    assert sorted(r["rk"] for r in in_category) == [1, 1] + [3] * 6 + [9] * 6


def test_reference_agrees_with_a_dictionary_loop():
    """The reference's packed-key grouping sets and ranks against sums kept
    in a dictionary by the tuple of keys, on generated data."""
    import tempfile
    from benchmark.datagen import tpcds
    config = run.find_cell(CELL)[2]
    with tempfile.TemporaryDirectory() as d:
        paths = tpcds.generate(0.002, SEED, config["tables"], d)
        tb = run.read_tables(paths, ref67.COLUMNS)
    codes, domains, price, quantity = ref67.joined_keys(tb)
    sums = {}
    for i in range(len(price)):
        full = tuple(str(domains[k][codes[k][i]]) if c in ref67.STRINGS
                     else int(domains[k][codes[k][i]])
                     for k, c in enumerate(ref67.KEYS))
        for level in range(9):
            key = full[:level] + (None,) * (8 - level)
            sums[key] = sums.get(key, 0) + int(price[i]) * int(quantity[i])
    want = []
    for key, total in sums.items():
        rk = 1 + sum(1 for k, t in sums.items()
                     if k[0] == key[0] and t > total)
        if rk <= 100:
            want.append(key + (total, rk))
    want.sort(key=lambda r: tuple((v is not None, v) for v in r))
    got = ref67.reference(tb)
    assert len(got) == 100
    for g, w in zip(got, want):
        shown = tuple(("<null>" if c in ref67.STRINGS else -1)
                      if v is None else v for c, v in zip(ref67.KEYS, w))
        assert tuple(g.values()) == shown + w[8:]


# -- the comparison, through a whole rehearsal run ---------------------------

@pytest.fixture
def workdir(tmp_path_factory):
    return str(tmp_path_factory.getbasetemp() / "benchmark_work")


def _rank_off_by_one(rows):
    rows[5]["rk"] += 1
    return rows


def _sum_off_by_a_hundredth(rows):
    rows[-1]["sumsales"] += 1
    return rows


def _drop_row(rows):
    return rows[:-1]


def _null_where_a_value_belongs(rows):
    rows[-1]["i_category"] = None
    return rows


def _value_where_a_null_belongs(rows):
    assert rows[0]["i_category"] == "<null>"
    rows[0]["i_category"] = "Books"
    return rows


FAULTS = {"rank_off_by_one": _rank_off_by_one,
          "sum_off_by_a_hundredth": _sum_off_by_a_hundredth,
          "row_dropped": _drop_row,
          "null_where_a_value_belongs": _null_where_a_value_belongs,
          "value_where_a_null_belongs": _value_where_a_null_belongs}


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
            rows = FAULTS[fault](copy.deepcopy(rows))
        return rows, spans
    monkeypatch.setattr(run.SqlEntry, "run", broken)
    r = run.run_cell(CELL, SEED, 1.0, False, rehearsal=True, scale=SCALE,
                     workdir=workdir)
    assert r["attempted"] >= 2 and not r["correct"], r
    if fault == "row_dropped":
        assert r["compared"]["rows_off"][0] == 1
    else:
        assert r["compared"]["exact_mismatch"][0] >= 1


def test_float32_control_is_not_correct_by_exact_mismatch(workdir):
    refs, controls, limit = control.control_rows(CELL, SEED, SCALE, workdir)
    correct, compared = compare.compare_all(list(controls.items()), refs,
                                            limit, 0)
    assert not correct and compared["exact_mismatch"][0] > 0, compared
    # no float is compared in this cell: it is the exact sums that catch it
    assert compared["float_gap"][0] == 0.0
    assert compare.compare_all(list(refs.items()), refs, limit, 0)[0]
