"""Window exec tests — host-oracle equivalence across frames and functions
(reference WindowFunctionSuite / window_function_test.py patterns, SURVEY.md §4)."""

import numpy as np
import pyarrow as pa
import pytest

from conftest import make_table

from spark_rapids_tpu import types as T
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.expr.core import Alias, col, lit
from spark_rapids_tpu.expr.aggregates import Average, Count, Max, Min, Sum
from spark_rapids_tpu.expr.windows import (
    DEFAULT_FRAME, FULL_FRAME, DenseRank, Lag, Lead, Rank, RowNumber,
    WindowExpression, WindowFrame, WindowSpec,
)
from spark_rapids_tpu.plan import ScanNode, TpuOverrides, WindowNode, explain_plan
from spark_rapids_tpu.plan.transitions import execute_hybrid
from spark_rapids_tpu.exec.base import TpuExec
from test_plan import norm, split_table


def win_table(n=400, seed=11):
    """Order key is UNIQUE: ROWS-frame results over order-key ties depend on the
    physical tie order, which legitimately differs between the host path and the
    post-exchange device path (Spark is equally nondeterministic there). Tie
    semantics (RANGE frames, rank vs dense_rank) are covered by the deterministic
    single-partition tests below."""
    r = np.random.default_rng(seed)
    grp = r.integers(0, 8, n)
    ordv = r.permutation(n)
    vals = r.normal(0, 10, n)
    vmask = r.random(n) < 0.1
    return pa.table({
        "g": pa.array([int(v) for v in grp], pa.int64()),
        "o": pa.array([int(v) for v in ordv], pa.int32()),
        "v": pa.array([None if m else float(v) for v, m in zip(vals, vmask)],
                      pa.float64()),
    })


def spec(order=True, frame=DEFAULT_FRAME):
    return WindowSpec(
        (col("g"),),
        ((col("o"), True, True),) if order else (),
        frame)


def check(node, approx=True):
    host = node.collect_host()
    hybrid = TpuOverrides(RapidsConf()).apply(node)
    dev = execute_hybrid(hybrid)
    assert norm(host) == norm(dev) if not approx else True
    if approx:
        h, d = norm(host), norm(dev)
        assert len(h) == len(d)
        import math
        for hr, dr in zip(h, d):
            for hv, dv in zip(hr, dr):
                if isinstance(hv, float) and isinstance(dv, float):
                    if math.isnan(hv):
                        assert math.isnan(dv), (hr, dr)
                    else:
                        assert dv == pytest.approx(hv, rel=1e-9, abs=1e-9), (hr, dr)
                else:
                    assert hv == dv, (hr, dr)
    return hybrid


def test_ranking_functions():
    t = win_table()
    node = WindowNode([
        Alias(WindowExpression(RowNumber(), spec()), "rn"),
        Alias(WindowExpression(Rank(), spec()), "rk"),
        Alias(WindowExpression(DenseRank(), spec()), "dr"),
    ], ScanNode(split_table(t, 3)))
    hybrid = check(node)
    assert isinstance(hybrid, TpuExec)


def test_cumulative_and_range_aggregates():
    t = win_table()
    node = WindowNode([
        Alias(WindowExpression(Sum(col("v")), spec()), "cum_sum_range"),
        Alias(WindowExpression(Count(col("v")),
                               spec(frame=WindowFrame("rows", None, 0))),
              "cum_cnt_rows"),
        Alias(WindowExpression(Min(col("v")), spec()), "cum_min"),
        Alias(WindowExpression(Max(col("v")), spec()), "cum_max"),
    ], ScanNode(split_table(t, 2)))
    check(node)


def test_full_partition_frame():
    t = win_table()
    node = WindowNode([
        Alias(WindowExpression(Sum(col("v")), spec(frame=FULL_FRAME)), "tot"),
        Alias(WindowExpression(Average(col("v")), spec(frame=FULL_FRAME)), "avg"),
        Alias(WindowExpression(Count(None), spec(frame=FULL_FRAME)), "n"),
    ], ScanNode(split_table(t, 2)))
    check(node)


def test_sliding_rows_frame():
    t = win_table()
    node = WindowNode([
        Alias(WindowExpression(Sum(col("v")),
                               spec(frame=WindowFrame("rows", 2, 2))), "s5"),
        Alias(WindowExpression(Average(col("v")),
                               spec(frame=WindowFrame("rows", 3, 0))), "a4"),
        Alias(WindowExpression(Count(col("v")),
                               spec(frame=WindowFrame("rows", 0, 2))), "c3"),
    ], ScanNode(split_table(t, 2)))
    check(node)


def test_lead_lag():
    t = win_table()
    node = WindowNode([
        Alias(WindowExpression(Lead(col("v"), 2), spec()), "ld"),
        Alias(WindowExpression(Lag(col("v"), 1), spec()), "lg"),
        Alias(WindowExpression(Lag(col("o"), 3, default=-1), spec()), "lgd"),
    ], ScanNode(split_table(t, 2)))
    check(node)


def test_nan_min_max_window():
    t = pa.table({
        "g": pa.array([1, 1, 1, 2, 2], pa.int64()),
        "o": pa.array([1, 2, 3, 1, 2], pa.int32()),
        "v": pa.array([1.0, float("nan"), 2.0, float("nan"), float("nan")],
                      pa.float64()),
    })
    node = WindowNode([
        Alias(WindowExpression(Max(col("v")), spec(frame=FULL_FRAME)), "mx"),
        Alias(WindowExpression(Min(col("v")), spec(frame=FULL_FRAME)), "mn"),
    ], ScanNode([t]))
    host = node.collect_host()
    dev = execute_hybrid(TpuOverrides(RapidsConf()).apply(node))
    import math
    # group 1: max=NaN (NaN largest), min=1.0; group 2: all NaN → both NaN
    for out in (host, dev):
        rows = {g: (mx, mn) for g, mx, mn in zip(
            out["g"].to_pylist(), out["mx"].to_pylist(), out["mn"].to_pylist())}
        assert math.isnan(rows[1][0]) and rows[1][1] == 1.0
        assert math.isnan(rows[2][0]) and math.isnan(rows[2][1])


def test_sliding_min_max_on_device():
    """Sliding rows min/max runs on device (sparse-table range queries,
    ops/windowing.py — VERDICT r1 item #4)."""
    t = win_table(200)
    node = WindowNode([
        Alias(WindowExpression(Min(col("v")),
                               spec(frame=WindowFrame("rows", 2, 2))), "m"),
        Alias(WindowExpression(Max(col("v")),
                               spec(frame=WindowFrame("rows", 3, 1))), "x"),
        Alias(WindowExpression(Min(col("o")),
                               spec(frame=WindowFrame("rows", 0, 4))), "mi"),
        Alias(WindowExpression(Max(col("v")),
                               spec(frame=WindowFrame("rows", 2, None))), "xu"),
    ], ScanNode(split_table(t, 2)))
    hybrid = check(node)
    assert isinstance(hybrid, TpuExec), explain_plan(node)


def test_sliding_min_max_nan_and_empty_frames():
    t = pa.table({
        "g": pa.array([1, 1, 1, 1, 1], pa.int64()),
        "o": pa.array([1, 2, 3, 4, 5], pa.int32()),
        "v": pa.array([1.0, float("nan"), None, 4.0, 2.0], pa.float64()),
    })
    node = WindowNode([
        Alias(WindowExpression(Max(col("v")),
                               spec(frame=WindowFrame("rows", 1, 1))), "mx"),
        Alias(WindowExpression(Min(col("v")),
                               spec(frame=WindowFrame("rows", 1, 1))), "mn"),
    ], ScanNode([t]))
    hybrid = check(node)
    assert isinstance(hybrid, TpuExec), explain_plan(node)


def test_range_frame_bounded_int_key():
    """RANGE BETWEEN k PRECEDING AND k FOLLOWING over an int order key, asc and
    desc, with nulls in the VALUE column (VERDICT r1 item #4)."""
    r = np.random.default_rng(5)
    n = 300
    t = pa.table({
        "g": pa.array([int(v) for v in r.integers(0, 6, n)], pa.int64()),
        "o": pa.array([int(v) for v in r.integers(0, 40, n)], pa.int32()),
        "v": pa.array([None if m < 0.1 else float(x) for x, m in
                       zip(r.normal(0, 10, n), r.random(n))], pa.float64()),
    })
    for asc in (True, False):
        sp = WindowSpec((col("g"),), ((col("o"), asc, True),),
                        WindowFrame("range", 3, 5))
        node = WindowNode([
            Alias(WindowExpression(Sum(col("v")), sp), "s"),
            Alias(WindowExpression(Count(col("v")), sp), "c"),
            Alias(WindowExpression(Min(col("v")), sp), "mn"),
            Alias(WindowExpression(Max(col("v")), sp), "mx"),
            Alias(WindowExpression(Average(col("v")), sp), "av"),
        ], ScanNode(split_table(t, 2)))
        hybrid = check(node)
        assert isinstance(hybrid, TpuExec), explain_plan(node)


def test_range_frame_null_order_keys():
    """Null order values form their own peer group on bounded sides (Spark
    RangeBoundOrdering: null±offset compares equal only to nulls)."""
    t = pa.table({
        "g": pa.array([1, 1, 1, 1, 1, 2, 2], pa.int64()),
        "o": pa.array([None, None, 1, 3, 9, None, 5], pa.int32()),
        "v": pa.array([1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0], pa.float64()),
    })
    for nf in (True, False):
        sp = WindowSpec((col("g"),), ((col("o"), True, nf),),
                        WindowFrame("range", 2, 2))
        node = WindowNode([
            Alias(WindowExpression(Sum(col("v")), sp), "s"),
            Alias(WindowExpression(Count(col("v")), sp), "c"),
        ], ScanNode([t]))
        hybrid = check(node)
        assert isinstance(hybrid, TpuExec), explain_plan(node)


def test_range_frame_one_sided_and_unbounded():
    r = np.random.default_rng(9)
    n = 120
    t = pa.table({
        "g": pa.array([int(v) for v in r.integers(0, 4, n)], pa.int64()),
        "o": pa.array([int(v) for v in r.integers(0, 30, n)], pa.int32()),
        "v": pa.array([float(x) for x in r.normal(0, 3, n)], pa.float64()),
    })
    sp1 = WindowSpec((col("g"),), ((col("o"), True, True),),
                     WindowFrame("range", None, 4))   # unbounded → +4
    sp2 = WindowSpec((col("g"),), ((col("o"), True, True),),
                     WindowFrame("range", 2, None))   # -2 → unbounded
    sp3 = WindowSpec((col("g"),), ((col("o"), True, True),),
                     WindowFrame("range", 0, 0))      # peers only
    node = WindowNode([
        Alias(WindowExpression(Sum(col("v")), sp1), "s1"),
        Alias(WindowExpression(Sum(col("v")), sp2), "s2"),
        Alias(WindowExpression(Sum(col("v")), sp3), "s3"),
    ], ScanNode(split_table(t, 3)))
    hybrid = check(node)
    assert isinstance(hybrid, TpuExec), explain_plan(node)


def test_range_frame_float_key_with_nan():
    t = pa.table({
        "g": pa.array([1, 1, 1, 1, 1], pa.int64()),
        "o": pa.array([1.0, 2.5, float("nan"), float("nan"), 9.0],
                      pa.float64()),
        "v": pa.array([1.0, 2.0, 4.0, 8.0, 16.0], pa.float64()),
    })
    sp = WindowSpec((col("g"),), ((col("o"), True, True),),
                    WindowFrame("range", 2, 2))
    node = WindowNode([
        Alias(WindowExpression(Sum(col("v")), sp), "s"),
    ], ScanNode([t]))
    hybrid = check(node)
    assert isinstance(hybrid, TpuExec), explain_plan(node)


def test_range_frame_multi_order_key_falls_back():
    t = win_table(40)
    sp = WindowSpec((col("g"),),
                    ((col("o"), True, True), (col("v"), True, True)),
                    WindowFrame("range", 1, 1))
    node = WindowNode([
        Alias(WindowExpression(Sum(col("v")), sp), "s"),
    ], ScanNode([t]))
    txt = explain_plan(node)
    assert "one order key" in txt


def test_window_no_order_by_full_frame():
    t = win_table(100)
    node = WindowNode([
        Alias(WindowExpression(Sum(col("v")), spec(order=False,
                                                   frame=FULL_FRAME)), "s"),
    ], ScanNode(split_table(t, 2)))
    check(node)


def test_range_frame_ties_deterministic():
    """RANGE unbounded→current includes the whole tie group; single partition so
    tie order is deterministic for the rank functions too."""
    t = pa.table({
        "g": pa.array([1, 1, 1, 1, 2], pa.int64()),
        "o": pa.array([1, 1, 2, 2, 1], pa.int32()),
        "v": pa.array([10.0, 20.0, 30.0, 40.0, 5.0], pa.float64()),
    })
    node = WindowNode([
        Alias(WindowExpression(Sum(col("v")), spec()), "s"),
        Alias(WindowExpression(Rank(), spec()), "rk"),
        Alias(WindowExpression(DenseRank(), spec()), "dr"),
    ], ScanNode([t]))
    host = node.collect_host()
    dev = execute_hybrid(TpuOverrides(RapidsConf()).apply(node))
    for out in (host, dev):
        rows = sorted(zip(out["g"].to_pylist(), out["o"].to_pylist(),
                          out["v"].to_pylist(), out["s"].to_pylist(),
                          out["rk"].to_pylist(), out["dr"].to_pylist()))
        # RANGE sum includes ties: both o=1 rows see 30; both o=2 rows see 100
        assert rows == [
            (1, 1, 10.0, 30.0, 1, 1), (1, 1, 20.0, 30.0, 1, 1),
            (1, 2, 30.0, 100.0, 3, 2), (1, 2, 40.0, 100.0, 3, 2),
            (2, 1, 5.0, 5.0, 1, 1)]


def test_window_min_max_bool_and_string():
    t = pa.table({
        "g": pa.array([1, 1, 2, 2], pa.int64()),
        "o": pa.array([1, 2, 1, 2], pa.int32()),
        "b": pa.array([True, False, None, True]),
        "s": pa.array(["pear", "apple", "kiwi", None]),
    })
    node = WindowNode([
        Alias(WindowExpression(Min(col("b")), spec(frame=FULL_FRAME)), "bmin"),
        Alias(WindowExpression(Max(col("s")), spec(frame=FULL_FRAME)), "smax"),
        Alias(WindowExpression(Min(col("s")), spec(frame=FULL_FRAME)), "smin"),
    ], ScanNode([t]))
    host = node.collect_host()
    dev = execute_hybrid(TpuOverrides(RapidsConf()).apply(node))
    for out in (host, dev):
        rows = sorted(zip(out["g"].to_pylist(), out["bmin"].to_pylist(),
                          out["smax"].to_pylist(), out["smin"].to_pylist()))
        assert rows == [(1, False, "pear", "apple"), (1, False, "pear", "apple"),
                        (2, True, "kiwi", "kiwi"), (2, True, "kiwi", "kiwi")]


def test_lead_string_default_falls_back():
    t = win_table(30)
    st = pa.table({"g": t.column("g"), "o": t.column("o"),
                   "s": pa.array([f"v{i%5}" for i in range(30)])})
    node = WindowNode([
        Alias(WindowExpression(Lead(col("s"), 1, default="zzz"), spec()), "ld"),
    ], ScanNode([st]))
    txt = explain_plan(node)
    assert "non-null default" in txt
    out = execute_hybrid(TpuOverrides(RapidsConf()).apply(node))  # host path
    assert out.num_rows == 30


@pytest.mark.parametrize("n", [1000, 4096, 5000, 1 << 14])
@pytest.mark.parametrize("dtype", ["int32", "int64", "bool", "float64"])
def test_two_level_scans_match_the_native_ones(n, dtype):
    """ops/windowing.cumsum/cummax/cummin_reverse take a two-level blocked
    form on long arrays (the chip's compiler needs it); the result is the
    native scan's, in dtype and in every element."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import windowing as W
    rng = np.random.default_rng(n)
    x = {"bool": lambda: rng.random(n) < 0.3,
         "float64": lambda: rng.uniform(-5, 5, n)}.get(
        dtype, lambda: rng.integers(-50, 1000, n).astype(dtype))()
    d = jnp.asarray(x)
    got, want = W.cumsum(d), jnp.cumsum(d, axis=0)
    assert got.dtype == want.dtype and np.array_equal(got, want)
    if dtype != "bool":
        assert np.array_equal(W.cummax(d), jax.lax.cummax(d))
        assert np.array_equal(W.cummin_reverse(d),
                              jax.lax.cummin(d, reverse=True))


def _float_segment_sum(cap, seg_ids, valid, data):
    """(per-row sum, per-row count) of ops/grouping.segment_sum over sorted
    ``seg_ids``, in one program."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import grouping as G

    def run(v, m, sid):
        return G.segment_sum(v, m, G.segment_structure(sid, cap))
    s, n = jax.jit(run)(jnp.asarray(data), jnp.asarray(valid),
                        jnp.asarray(seg_ids))
    return np.asarray(s), np.asarray(n)


def _segments(cap, layout, rng):
    """(sorted seg_ids, valid): ``padded`` leaves the last tenth of the rows
    dead on seg_id cap - 1, as sorted_groups pads a batch."""
    valid = np.ones(cap, bool)
    if layout == "singletons":
        return np.arange(cap, dtype=np.int32), valid
    if layout == "one":
        return np.zeros(cap, np.int32), valid
    boundary = rng.random(cap) < 0.01
    boundary[0] = True
    seg_ids = np.cumsum(boundary).astype(np.int32) - 1
    if layout == "padded":
        live = cap - cap // 10
        seg_ids[live:] = cap - 1
        valid[live:] = False
    return seg_ids, valid


@pytest.mark.parametrize("layout", ["random", "singletons", "one", "padded"])
@pytest.mark.parametrize("cap", [256, 5000, 1 << 14, 1 << 20])
def test_float_segment_sum_matches_segment_totals(cap, layout):
    """A float column's segment sum (a segmented scan bounded by each row's
    segment, its end carried back) gives every row its own segment's total:
    within 1e-12 of np.bincount, and integral float64 values (TPC-H
    quantities) summed exactly; dead padding rows add nothing."""
    rng = np.random.default_rng(cap)
    seg_ids, valid = _segments(cap, layout, rng)
    live = np.where(valid, 1.0, 0.0)
    nseg = int(seg_ids.max()) + 1
    data = rng.uniform(0, 1e5, cap)
    got, count = _float_segment_sum(cap, seg_ids, valid, data)
    want = np.bincount(seg_ids, weights=data * live, minlength=nseg)[seg_ids]
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert np.array_equal(
        count, np.bincount(seg_ids, weights=live, minlength=nseg)[seg_ids])
    qty = rng.integers(1, 51, cap).astype(np.float64)
    got, _ = _float_segment_sum(cap, seg_ids, valid, qty)
    assert np.array_equal(
        got, np.bincount(seg_ids, weights=qty * live, minlength=nseg)[seg_ids])


@pytest.mark.parametrize("cap", [256, 5000, 1 << 14, 1 << 20])
def test_float_segment_sum_keeps_nan_and_inf_in_their_segment(cap):
    """A NaN, a +inf, and a +inf beside a -inf make their own segments'
    totals NaN, inf and NaN, and never reach a neighbour's, which stays
    exact."""
    rng = np.random.default_rng(cap + 1)
    seg_ids = (np.arange(cap) // 8).astype(np.int32)
    data = rng.integers(1, 51, cap).astype(np.float64)
    mid = int(seg_ids[cap // 2])
    rows = [np.flatnonzero(seg_ids == mid + d) for d in (-1, 0, 1)]
    data[rows[0][3]] = np.nan
    data[rows[1][0]] = np.inf
    data[rows[2][1]], data[rows[2][6]] = np.inf, -np.inf
    got, _ = _float_segment_sum(cap, seg_ids, np.ones(cap, bool), data)
    assert np.isnan(got[rows[0]]).all()
    assert np.isposinf(got[rows[1]]).all()
    assert np.isnan(got[rows[2]]).all()
    finite = np.abs(seg_ids - mid) > 1
    assert np.array_equal(
        got[finite], np.bincount(seg_ids, weights=data)[seg_ids][finite])


# -- the fused program (PR 35) -------------------------------------------------

def rollup_table(n=600, seed=67):
    """q67's window input in small: a string partition key with NULLs (the
    rolled-up category), an int64 order key past 32 bits with ties."""
    r = np.random.default_rng(seed)
    cat = r.integers(0, 5, n)
    sums = (1 << 33) + r.integers(0, 40, n)
    return pa.table({
        "g": pa.array([None if c == 4 else f"cat{c}" for c in cat]),
        "o": pa.array(sums, pa.int64()),
        "v": pa.array(r.integers(0, 1000, n), pa.int64()),
    })


def _by_sums_desc(frame=DEFAULT_FRAME):
    return WindowSpec((col("g"),), ((col("o"), False, False),), frame)


@pytest.mark.parametrize("name,func", [
    ("rank", Rank), ("dense_rank", DenseRank),
    ("row_number", RowNumber), ("sum", lambda: Sum(col("v"))),
    ("avg", lambda: Average(col("v")))])
def test_fused_window_program_matches_host_window(name, func):
    """WindowExec's partition goes through ONE fused program
    (`srt_WindowExec`), not the eager body it falls back to, and agrees with
    plan/host_window.py; ties in the order key get one rank, and a RANGE
    frame sums over them (row_number over ties depends on the physical
    order, so it runs over the order key made unique)."""
    from spark_rapids_tpu.runtime import fuse
    t = rollup_table()
    if name == "row_number":
        t = t.set_column(1, "o", pa.array(
            (1 << 33) + np.random.default_rng(1).permutation(t.num_rows),
            pa.int64()))
    node = WindowNode([Alias(WindowExpression(func(), _by_sums_desc()), name)],
                      ScanNode([t]))
    before = fuse.stage_metrics()["dispatches"]
    hybrid = check(node, approx=name == "avg")
    assert isinstance(hybrid, TpuExec)
    kernels = [k for key, k in fuse._kernels.items() if key[0] == "window"]
    assert kernels and all(k is not fuse._EAGER for k in kernels)
    assert fuse.stage_metrics()["dispatches"] > before


def test_window_sort_folds_a_wide_order_key_by_what_it_holds():
    """From 2^17 slots on, one key-stats read a partition folds the sort's
    keys by the ranges they hold: a string and an int64 past 32 bits (here
    5 bits of span) sort as ONE int64 operand (six unfolded), and rank() and
    a running RANGE sum agree with pandas."""
    from spark_rapids_tpu.runtime import tracing
    t = rollup_table(n=140_000)
    node = WindowNode([Alias(WindowExpression(Rank(), _by_sums_desc()), "rk"),
                       Alias(WindowExpression(Sum(col("v")), _by_sums_desc()),
                             "running")], ScanNode([t]))
    tracing.drain()
    tracing.set_enabled(True)
    try:
        got = execute_hybrid(TpuOverrides(RapidsConf()).apply(node))
        spans = tracing.drain()
    finally:
        tracing.set_enabled(False)
    (counts,) = [s["counts"] for s in spans if s["name"] == "WindowExec"]
    assert counts["rows"] == 140_000 and counts["capacity"] == 1 << 18
    assert counts["exprs"] == 2 and counts["sort_operands"] == 1
    df = t.to_pandas()
    df["g"] = df["g"].fillna("<null>")
    by = df.groupby("g")
    df["rk"] = by["o"].rank(method="min", ascending=False).astype(int)
    tie_sums = df.groupby(["g", "o"])["v"].sum().sort_index(
        ascending=[True, False]).groupby(level=0).cumsum()
    df["running"] = [tie_sums[(g, o)] for g, o in zip(df["g"], df["o"])]
    want = sorted(zip(df["g"], df["o"], df["v"], df["rk"], df["running"]))
    have = got.to_pandas()
    have["g"] = have["g"].fillna("<null>")
    assert sorted(zip(have["g"], have["o"], have["v"], have["rk"],
                      have["running"])) == want
