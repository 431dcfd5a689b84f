"""Join tests — CPU-reference equivalence over all join types with nulls, NaNs,
duplicate keys, and string keys (reference: JoinsSuite / HashJoinSuite patterns,
SURVEY.md §4 ring 2)."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.exec.basic import ArrowScanExec
from spark_rapids_tpu.exec.joins import (BroadcastHashJoinExec, CartesianProductExec,
                                         HashJoinExec, NestedLoopJoinExec)
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.expr.predicates import GreaterThan

from test_partitioning import same_multiset


def left_table(n=200, seed=3):
    r = np.random.default_rng(seed)
    keys = [None if m else int(v) for v, m in
            zip(r.integers(0, 40, n), r.random(n) < 0.1)]
    return pa.table({"lk": pa.array(keys, type=pa.int64()),
                     "lv": pa.array(np.arange(n), type=pa.int32()),
                     "ls": pa.array([["x", "y", "z", None][i % 4] for i in range(n)])})


def right_table(n=120, seed=9):
    r = np.random.default_rng(seed)
    keys = [None if m else int(v) for v, m in
            zip(r.integers(0, 40, n), r.random(n) < 0.1)]
    return pa.table({"rk": pa.array(keys, type=pa.int64()),
                     "rv": pa.array(np.arange(n) * 10, type=pa.int32())})


def host_join(lt, rt, lkey, rkey, how):
    """Plain-python reference join with Spark semantics (null keys never match)."""
    lrows = lt.to_pylist()
    rrows = rt.to_pylist()
    out = []
    rmatched = [False] * len(rrows)
    for lr in lrows:
        k = lr[lkey]
        matches = [j for j, rr in enumerate(rrows)
                   if k is not None and rr[rkey] == k]
        for j in matches:
            rmatched[j] = True
        if how in ("inner",):
            out += [{**lr, **rrows[j]} for j in matches]
        elif how in ("leftouter", "fullouter"):
            if matches:
                out += [{**lr, **rrows[j]} for j in matches]
            else:
                out.append({**lr, **{c: None for c in rt.column_names}})
        elif how == "leftsemi":
            if matches:
                out.append(dict(lr))
        elif how == "leftanti":
            if not matches:
                out.append(dict(lr))
    if how == "fullouter":
        for j, rr in enumerate(rrows):
            if not rmatched[j]:
                out.append({**{c: None for c in lt.column_names}, **rr})
    if how == "rightouter":
        return host_join(rt, lt, rkey, lkey, "leftouter")
    cols = (lt.column_names + rt.column_names if how not in ("leftsemi", "leftanti")
            else lt.column_names)
    if how == "rightouter":
        cols = lt.column_names + rt.column_names
    return pa.table({c: pa.array([row.get(c) for row in out],
                                 type=(lt.schema.field(c).type if c in lt.column_names
                                       else rt.schema.field(c).type))
                     for c in cols})


def run_join(how, lt=None, rt=None, **kw):
    lt = left_table() if lt is None else lt
    rt = right_table() if rt is None else rt
    conf = RapidsConf()
    lscan = ArrowScanExec([lt], conf=conf)
    rscan = ArrowScanExec([rt], conf=conf)
    j = HashJoinExec(how, [col("lk")], [col("rk")], lscan, rscan, **kw)
    return j.execute_collect()


@pytest.mark.parametrize("how", ["inner", "leftouter", "rightouter", "fullouter",
                                 "leftsemi", "leftanti"])
def test_hash_join_types_match_host(how):
    lt, rt = left_table(), right_table()
    got = run_join(how)
    want = host_join(lt, rt, "lk", "rk", how)
    if how == "rightouter":
        # host reference emits columns right-first; reorder to left++right
        want = want.select(got.column_names)
    assert got.num_rows == want.num_rows, f"{how}: {got.num_rows} != {want.num_rows}"
    assert same_multiset(got, want), how


def test_inner_join_build_side_left():
    lt, rt = left_table(), right_table()
    got = run_join("inner", build_side="left")
    want = host_join(lt, rt, "lk", "rk", "inner")
    assert same_multiset(got, want)


def test_inner_join_with_condition():
    lt, rt = left_table(), right_table()
    got = run_join("inner", condition=GreaterThan(col("lv"), col("rv")))
    rows = host_join(lt, rt, "lk", "rk", "inner").to_pylist()
    want_rows = [r for r in rows if r["lv"] is not None and r["rv"] is not None
                 and r["lv"] > r["rv"]]
    assert got.num_rows == len(want_rows)


def test_string_key_join():
    lt = pa.table({"lk": pa.array(["a", "b", None, "c", "a", ""]),
                   "lv": pa.array(range(6), type=pa.int32())})
    rt = pa.table({"rk": pa.array(["a", None, "", "d"]),
                   "rv": pa.array(range(4), type=pa.int32())})
    conf = RapidsConf()
    j = HashJoinExec("inner", [col("lk")], [col("rk")],
                     ArrowScanExec([lt], conf=conf), ArrowScanExec([rt], conf=conf))
    got = j.execute_collect()
    want = pa.table({"lk": pa.array(["a", "a", ""]),
                     "lv": pa.array([0, 4, 5], type=pa.int32()),
                     "rk": pa.array(["a", "a", ""]),
                     "rv": pa.array([0, 0, 2], type=pa.int32())})
    assert same_multiset(got, want)


def test_multi_key_join_with_nan():
    lt = pa.table({"lk": pa.array([1.0, float("nan"), 2.0, None, -0.0]),
                   "lv": pa.array(range(5), type=pa.int32())})
    rt = pa.table({"rk": pa.array([float("nan"), 1.0, 0.0]),
                   "rv": pa.array(range(3), type=pa.int32())})
    conf = RapidsConf()
    j = HashJoinExec("inner", [col("lk")], [col("rk")],
                     ArrowScanExec([lt], conf=conf), ArrowScanExec([rt], conf=conf))
    got = j.execute_collect()
    # Spark: NaN == NaN in join keys; -0.0 == 0.0; null never matches
    lvs = sorted(got["lv"].to_pylist())
    assert lvs == [0, 1, 4]


def test_broadcast_hash_join_multi_partition_stream():
    lt = left_table(300)
    tables = [lt.slice(0, 100), lt.slice(100, 100), lt.slice(200, 100)]
    rt = right_table()
    conf = RapidsConf()
    j = BroadcastHashJoinExec("leftouter", [col("lk")], [col("rk")],
                              ArrowScanExec(tables, conf=conf),
                              ArrowScanExec([rt], conf=conf))
    got = j.execute_collect()
    want = host_join(lt, rt, "lk", "rk", "leftouter")
    assert same_multiset(got, want)


def test_nested_loop_cross_and_condition():
    lt = pa.table({"a": pa.array([1, 2, 3], type=pa.int64())})
    rt = pa.table({"b": pa.array([10, 2, 30, 1], type=pa.int64())})
    conf = RapidsConf()
    cross = CartesianProductExec(ArrowScanExec([lt], conf=conf),
                                 ArrowScanExec([rt], conf=conf))
    assert cross.execute_collect().num_rows == 12
    nl = NestedLoopJoinExec("inner", ArrowScanExec([lt], conf=conf),
                            ArrowScanExec([rt], conf=conf),
                            condition=GreaterThan(col("a"), col("b")))
    got = nl.execute_collect()
    pairs = sorted(zip(got["a"].to_pylist(), got["b"].to_pylist()))
    assert pairs == [(2, 1), (3, 1), (3, 2)]


def test_nested_loop_left_outer_with_condition():
    lt = pa.table({"a": pa.array([1, 5, 7], type=pa.int64())})
    rt = pa.table({"b": pa.array([6, 6], type=pa.int64())})
    conf = RapidsConf()
    nl = NestedLoopJoinExec("leftouter", ArrowScanExec([lt], conf=conf),
                            ArrowScanExec([rt], conf=conf),
                            condition=GreaterThan(col("a"), col("b")))
    got = nl.execute_collect()
    rows = sorted(zip(got["a"].to_pylist(), got["b"].to_pylist()))
    assert rows == [(1, None), (5, None), (7, 6), (7, 6)]


def test_nested_loop_semi_anti():
    lt = pa.table({"a": pa.array([1, 5, 7], type=pa.int64())})
    rt = pa.table({"b": pa.array([6, 6], type=pa.int64())})
    conf = RapidsConf()
    semi = NestedLoopJoinExec("leftsemi", ArrowScanExec([lt], conf=conf),
                              ArrowScanExec([rt], conf=conf),
                              condition=GreaterThan(col("a"), col("b")))
    assert semi.execute_collect()["a"].to_pylist() == [7]
    anti = NestedLoopJoinExec("leftanti", ArrowScanExec([lt], conf=conf),
                              ArrowScanExec([rt], conf=conf),
                              condition=GreaterThan(col("a"), col("b")))
    assert sorted(anti.execute_collect()["a"].to_pylist()) == [1, 5]


def test_join_empty_build_side():
    lt = left_table(50)
    rt = right_table(0)
    got = run_join("leftouter", lt=lt, rt=rt)
    assert got.num_rows == 50
    assert got["rv"].null_count == 50
    got_inner = run_join("inner", lt=lt, rt=rt)
    assert got_inner.num_rows == 0


def test_broadcast_full_outer_multi_partition_stream():
    """Regression: unmatched build rows must be emitted exactly once globally, not
    once per stream partition (matched flags merge across partitions)."""
    lt = left_table(300)
    tables = [lt.slice(0, 100), lt.slice(100, 100), lt.slice(200, 100)]
    rt = right_table()
    conf = RapidsConf()
    j = BroadcastHashJoinExec("fullouter", [col("lk")], [col("rk")],
                              ArrowScanExec(tables, conf=conf),
                              ArrowScanExec([rt], conf=conf))
    got = j.execute_collect()
    want = host_join(lt, rt, "lk", "rk", "fullouter")
    assert same_multiset(got, want)


def test_nested_loop_full_outer_multi_partition_left():
    lt = pa.table({"a": pa.array([1, 5, 7, 9], type=pa.int64())})
    tables = [lt.slice(0, 2), lt.slice(2, 2)]
    rt = pa.table({"b": pa.array([6, 6, 100], type=pa.int64())})
    conf = RapidsConf()
    nl = NestedLoopJoinExec("fullouter", ArrowScanExec(tables, conf=conf),
                            ArrowScanExec([rt], conf=conf),
                            condition=GreaterThan(col("a"), col("b")))
    got = nl.execute_collect()
    rows = sorted(zip(got["a"].to_pylist(), got["b"].to_pylist()),
                  key=lambda p: (p[0] is None, p[0] or 0, p[1] is None, p[1] or 0))
    # pairs where a > b: (7,6)x2, (9,6)x2; unmatched left: 1, 5; unmatched right:
    # 100 exactly once (6s both matched)
    assert rows == [(1, None), (5, None), (7, 6), (7, 6), (9, 6), (9, 6),
                    (None, 100)]


def test_hash_join_rejects_cross():
    lt = left_table(10)
    rt = right_table(10)
    conf = RapidsConf()
    with pytest.raises(ValueError):
        HashJoinExec("cross", [], [], ArrowScanExec([lt], conf=conf),
                     ArrowScanExec([rt], conf=conf))


def test_broadcast_exchange_exec_standalone():
    """Standalone BroadcastExchangeExec (reference GpuBroadcastExchangeExecBase):
    plan-visible node, one shared materialization, host-bridge stream path."""
    import pyarrow as pa
    from spark_rapids_tpu.exec.broadcast import BroadcastExchangeExec
    tbl = pa.table({"k": pa.array([1, 2, 3], pa.int64())})
    scan = ArrowScanExec([tbl])
    bx = BroadcastExchangeExec(scan)
    assert bx.num_partitions == 1
    sb1 = bx.broadcast()
    sb2 = bx.broadcast()
    assert sb1 is sb2  # single shared relation
    # host-bridge path streams the same relation
    out = list(bx.execute_partition(0))
    assert out[0].num_rows == 3
    assert "BroadcastExchangeExec" in bx.tree_string()
    bx.release()


def test_broadcast_join_rides_exchange():
    from spark_rapids_tpu.session import TpuSession
    spark = TpuSession()
    left = spark.create_dataframe({"k": pa.array([1, 2], pa.int64()),
                                   "a": pa.array([10, 20], pa.int64())})
    right = spark.create_dataframe({"k": pa.array([2, 3], pa.int64()),
                                    "b": pa.array([7, 8], pa.int64())})
    out = left.join(right, on="k").collect()
    assert out.num_rows == 1
    assert out["a"].to_pylist() == [20] and out["b"].to_pylist() == [7]


@pytest.mark.parametrize("how", ["inner", "leftouter", "fullouter", "leftsemi",
                                 "leftanti"])
def test_mixed_width_key_join(how):
    """int64 stream key vs int32 build key must NOT wrap on the fast path
    (advisor r3 high): 2**32+5 is not equal to 5."""
    lt = pa.table({"lk": pa.array([2**32 + 5, 5, -1, None, 2**31 + 7],
                                  type=pa.int64()),
                   "lv": pa.array(range(5), type=pa.int32())})
    rt = pa.table({"rk": pa.array([5, 7, -1], type=pa.int32()),
                   "rv": pa.array(range(3), type=pa.int32())})
    conf = RapidsConf()
    j = HashJoinExec(how, [col("lk")], [col("rk")],
                     ArrowScanExec([lt], conf=conf), ArrowScanExec([rt], conf=conf))
    got = j.execute_collect()
    rt64 = pa.table({"rk": rt["rk"].cast(pa.int64()), "rv": rt["rv"]})
    want = host_join(lt, rt64, "lk", "rk", how)
    assert got.num_rows == want.num_rows, (how, got.to_pylist(), want.to_pylist())
    if how in ("inner", "leftsemi", "leftanti"):
        assert sorted(got["lv"].to_pylist()) == sorted(want["lv"].to_pylist()), how


def test_mixed_width_key_join_wide_build():
    """int32 stream key vs int64 build key (widening direction) stays correct."""
    lt = pa.table({"lk": pa.array([5, -1, 3], type=pa.int32()),
                   "lv": pa.array(range(3), type=pa.int32())})
    rt = pa.table({"rk": pa.array([2**32 + 5, 5, -1], type=pa.int64()),
                   "rv": pa.array(range(3), type=pa.int32())})
    conf = RapidsConf()
    j = HashJoinExec("inner", [col("lk")], [col("rk")],
                     ArrowScanExec([lt], conf=conf), ArrowScanExec([rt], conf=conf))
    got = j.execute_collect()
    assert sorted(zip(got["lv"].to_pylist(), got["rv"].to_pylist())) == [(0, 1), (1, 2)]


@pytest.mark.parametrize("how", ["inner", "leftouter"])
def test_dtype_max_key_fast_path(how):
    """A legitimate dtype-max key must keep matching on the packed fast path
    (the ineligible-row sentinel is vmax+1 — kept in int64 so it can never
    wrap into/collide with a real key)."""
    import numpy as np
    m32 = np.iinfo(np.int32).max
    lt = pa.table({"lk": pa.array([m32, m32 - 1, 5, None], pa.int32()),
                   "lv": pa.array(range(4), type=pa.int32())})
    rt = pa.table({"rk": pa.array([m32, m32, 7], pa.int32()),
                   "rv": pa.array(range(3), type=pa.int32())})
    conf = RapidsConf()
    j = HashJoinExec(how, [col("lk")], [col("rk")],
                     ArrowScanExec([lt], conf=conf),
                     ArrowScanExec([rt], conf=conf))
    got = j.execute_collect()
    want = host_join(lt, rt, "lk", "rk", how)
    assert got.num_rows == want.num_rows, (got.to_pylist(), want.to_pylist())
    assert sorted(got["lv"].to_pylist()) == sorted(want["lv"].to_pylist())
    # and with int64 keys at the int64 max (packed path must refuse/stay safe)
    m64 = np.iinfo(np.int64).max
    lt64 = pa.table({"lk": pa.array([m64, 5], pa.int64()),
                     "lv": pa.array([0, 1], pa.int32())})
    rt64 = pa.table({"rk": pa.array([m64], pa.int64()),
                     "rv": pa.array([9], pa.int32())})
    j2 = HashJoinExec(how, [col("lk")], [col("rk")],
                      ArrowScanExec([lt64], conf=conf),
                      ArrowScanExec([rt64], conf=conf))
    got2 = j2.execute_collect()
    want2 = host_join(lt64, rt64, "lk", "rk", how)
    assert got2.num_rows == want2.num_rows


@pytest.mark.parametrize("dtypes", [("int64", "int64"), ("int64", "int32"),
                                    ("bool", "int64", "int32"),
                                    ("int64", "float64")])
def test_join_ranks_are_tuple_equality(dtypes):
    """ops/joining.join_ranks: rank equality == key-tuple equality across
    both sides and rank order == tuple order (integer-backed keys are ranked
    by one-operand sorts, a fractional key keeps the comparator sort);
    null-keyed and padding rows get sentinels that never match."""
    import jax.numpy as jnp
    from spark_rapids_tpu.expr.core import Col
    from spark_rapids_tpu.ops import joining as J
    r = np.random.default_rng(len(dtypes))
    bcap, scap, nb, ns = 64, 128, 50, 100
    sp = {"int64": T.LONG, "int32": T.INT, "bool": T.BOOLEAN,
          "float64": T.DOUBLE}

    def side(cap):
        cols, tuples = [], []
        for dt in dtypes:
            if dt == "bool":
                v = r.random(cap) < 0.5
            elif dt == "int64":   # extremes: not packable beside a row index
                v = r.choice(np.array([-2**63, -7, 0, 3, 2**63 - 1]), cap)
            else:
                v = r.integers(-2, 3, cap).astype(dt)
            valid = r.random(cap) > 0.1
            cols.append(Col(jnp.asarray(v), jnp.asarray(valid), sp[dt]))
            tuples.append([x if ok else None
                           for x, ok in zip(v.tolist(), valid.tolist())])
        return cols, list(zip(*tuples))

    (b, bt), (s, st) = side(bcap), side(scap)
    br, sr = (np.asarray(x) for x in J.join_ranks(b, nb, bcap, s, ns, scap))
    rows = ([(t, int(k), i < nb) for i, (t, k) in enumerate(zip(bt, br))]
            + [(t, int(k), i < ns) for i, (t, k) in enumerate(zip(st, sr))])
    assert all(k == J._PAD_RANK for _, k, live in rows if not live)
    live = [(t, k) for t, k, ok in rows if ok]
    assert all((k < 0) == (None in t) for t, k in live)
    keyed = [(t, k) for t, k in live if None not in t]
    for t1, k1 in keyed:
        for t2, k2 in keyed:
            assert (k1 == k2) == (t1 == t2) and (k1 < k2) == (t1 < t2)


# -- probe modes of the sorted-build path -------------------------------------

@pytest.fixture
def spans():
    from spark_rapids_tpu.runtime import tracing
    tracing.drain()
    tracing.set_enabled(True)
    yield tracing
    tracing.set_enabled(False)
    tracing.drain()


NO_KEY = "dense_every_build_key_null"
I16, I32, I64 = np.iinfo(np.int16), np.iinfo(np.int32), np.iinfo(np.int64)


def _keys(vals, nulls, typ):
    # the value stays in the buffer beneath a null: a probe for it must miss
    return pa.array(np.asarray(vals, dtype=typ.to_pandas_dtype()),
                    mask=np.asarray(nulls), type=typ)


def _mode_case(name):
    """(build table, stream table, key names, the mode the build must get).
    Every stream holds null keys, keys outside [vmin, vmax], the extremes of
    its dtype and keys that match; every build holds null keys."""
    r = np.random.default_rng(sum(map(ord, name)))
    nb, ns = 300, 700
    if name in MULTI_KEY_CASES:
        return _multi_key_case(name, r, nb, ns)
    btype = stype = pa.int64()
    b_nulls = 0.1
    if name == "dense_negative_far_from_zero":
        bk = -7_000_000_000 + r.permutation(900)[:nb]
    elif name == "dense_int32_build_int64_stream":
        # the build ends at its dtype's maximum; 2**32 + key must not match
        bk = I32.max - r.permutation(900)[:nb]
        btype = pa.int32()
    elif name == "dense_int64_max":
        # packed relative to its least key, the greatest cannot overflow
        bk = I64.max - r.permutation(900)[:nb]
    elif name == "one_over_the_budget":
        bk = -5 + r.permutation(900)[:nb] * (1 << 33)
    elif name == "one_int32_build_sparse":
        # unique over most of its dtype's range: no table fits the budget
        bk = I32.min // 2 + r.permutation(900)[:nb] * (1 << 21)
        btype = pa.int32()
    elif name == "dense_int16_build_sparse":
        # the whole of a 16-bit domain is under the budget: always a table
        bk = I16.min + r.permutation(900)[:nb] * 72
        btype = pa.int16()
    elif name == "one_null_keys_probed_by_value":
        # a third of the build is null, the rows of its capacity past
        # n_build are padding; the stream asks for every value beneath
        bk = r.permutation(900)[:nb] * (1 << 25)
        b_nulls = 0.3
    elif name == NO_KEY:
        # rows and no key: nothing matches, whatever lies beneath
        bk = 40 + r.permutation(900)[:nb]
        b_nulls = 2.0
    else:
        assert name == "two_duplicate_keys", name
        bk = 40 + r.integers(0, 90, nb)
    far = [I64.min, I64.max, I64.min + 1, int(bk.min()) - 1, 0, -1,
           int(bk.max() % (1 << 32)) + (1 << 32)]
    far += [int(bk.max()) + 1] if int(bk.max()) < I64.max else []
    sk = np.concatenate([bk, r.choice(bk, ns - nb - len(far)),
                         np.asarray(far, dtype=np.int64)])
    bt = {"rk": _keys(bk, r.random(nb) < b_nulls, btype),
          "rv": pa.array(np.arange(nb), type=pa.int32())}
    st = {"lk": _keys(sk, r.random(ns) < 0.1, stype),
          "lv": pa.array(np.arange(ns), type=pa.int32())}
    return pa.table(bt), pa.table(st), ["lk"], ["rk"], name.split("_")[0]


SINGLE_KEY_CASES = [
    "dense_negative_far_from_zero", "dense_int32_build_int64_stream",
    "dense_int64_max", "one_over_the_budget", "one_int32_build_sparse",
    "dense_int16_build_sparse", "one_null_keys_probed_by_value",
    NO_KEY, "two_duplicate_keys"]

# name: (build key types, stream key types, how many values a key takes,
# the step between them, each key's least value, whether tuples repeat).
# The packed domain is the product of the keys' ranges.
DATE = pa.date32()
MULTI_KEY_CASES = {
    # the second build key ends at its dtype's maximum under a wider stream
    "dense_two_keys": ([pa.int64(), pa.int32()], [pa.int64(), pa.int64()],
                       (60, 25), (1, 1), (-7_000_000_000, I32.max - 24),
                       False),
    # 59 * 2**20 * 25 slots: no table fits the budget
    "one_two_keys_over_the_budget": (
        [pa.int64()] * 2, [pa.int64()] * 2, (60, 25), (1 << 20, 1),
        (-5, 40), False),
    "two_duplicate_tuples": ([pa.int64()] * 2, [pa.int64()] * 2, (60, 3),
                             (1, 1), (-50, 0), True),
    # bigint + int + date, as TPC-H Q5's keys are
    "dense_three_keys_mixed_widths": (
        [pa.int64(), pa.int32(), DATE], [pa.int64(), pa.int32(), DATE],
        (20, 10, 8), (1, 1, 1), (10_000_000_000, -5, 10_000), False),
    # each range fits, their product (2**46 x 2**35) does not
    "rank_domain_product_past_2_62": (
        [pa.int64()] * 2, [pa.int64()] * 2, (60, 25), (1 << 40, 1 << 30),
        (-(1 << 44), 7), False),
    # a string is ranked under the two sides' united dictionary
    "rank_string_beside_int": ([pa.int64(), pa.string()],
                               [pa.int64(), pa.string()], (60, 25), (1, 1),
                               (3, 0), False),
}
MODE_CASES = SINGLE_KEY_CASES + list(MULTI_KEY_CASES)


def _key_column(vals, nulls, typ):
    if typ == pa.string():
        return pa.array([None if m else f"s{v:03d}"
                         for v, m in zip(vals, nulls)], type=typ)
    if typ == DATE:
        return _keys(vals, nulls, pa.int32()).cast(DATE)
    return _keys(vals, nulls, typ)


def _multi_key_case(name, r, nb, ns):
    """Several keys a side. The build has nulls in each key alone; the
    stream has them too, and beside the tuples that match: tuples outside
    ONE key's range whose unguarded packed value is a build tuple's, the
    tuples that would pack to the sentinel of the rows without a key and to
    -1, and each key at the extremes of its stream dtype."""
    btypes, stypes, radix, step, base, repeat = MULTI_KEY_CASES[name]
    n = len(radix)
    ids = (r.integers(0, np.prod(radix), nb) if repeat
           else r.permutation(np.prod(radix))[:nb])
    digits = np.stack(np.unravel_index(ids, radix), axis=1)
    bk = digits * np.asarray(step) + np.asarray(base)          # (nb, n)
    b_null = r.random((nb, n)) < 0.05
    keyed = bk[~b_null.any(axis=1)]
    vmin, vmax = keyed.min(axis=0), keyed.max(axis=0)
    rng = vmax - vmin + 1
    far = []
    for j in range(n):
        if stypes[j] == pa.string():
            continue
        # a date's extremes are datetime.date's (years 1 and 9999)
        least, most = ((-719_162, 2_932_896) if stypes[j] == DATE else
                       (I64.min, I64.max) if stypes[j] == pa.int64() else
                       (I32.min, I32.max))
        for t in keyed[:12]:
            lo, hi = t.copy(), t.copy()
            lo[j], hi[j] = least, most
            far += [lo, hi]
            if stypes[j] != btypes[j]:      # the build dtype would wrap it
                wide = t.copy()
                wide[j] += 1 << 32
                far.append(wide)
            if j > 0 and stypes[j - 1] != pa.string():
                up, down = t.copy(), t.copy()
                up[j - 1], up[j] = t[j - 1] - 1, t[j] + rng[j]
                down[j - 1], down[j] = t[j - 1] + 1, t[j] - rng[j]
                far += [up, down]
    if stypes[0] != pa.string():
        far += [np.concatenate([[vmax[0] + 1], vmin[1:]]),   # the sentinel
                np.concatenate([[vmin[0] - 1], vmax[1:]])]   # -1
    far = np.asarray(far, dtype=np.int64).reshape(-1, n)
    sk = np.concatenate([bk, bk[r.integers(0, nb, ns - nb - len(far))], far])
    s_null = r.random((ns, n)) < 0.05
    s_null[-len(far):] = False
    rkeys = [f"rk{j}" for j in range(n)]
    lkeys = [f"lk{j}" for j in range(n)]
    bt = {c: _key_column(bk[:, j], b_null[:, j], btypes[j])
          for j, c in enumerate(rkeys)}
    st = {c: _key_column(sk[:, j], s_null[:, j], stypes[j])
          for j, c in enumerate(lkeys)}
    bt["rv"] = pa.array(np.arange(nb), type=pa.int32())
    st["lv"] = pa.array(np.arange(ns), type=pa.int32())
    return pa.table(bt), pa.table(st), lkeys, rkeys, name.split("_")[0]


def _key_domain(bt, rkeys):
    """The product of the keys' ranges over the build rows that have every
    key: what `HashJoin.build_prep` counts as `domain`."""
    cols = [bt[c].cast(pa.int64()) if bt.schema.field(c).type != DATE
            else bt[c].cast(pa.int32()).cast(pa.int64()) for c in rkeys]
    rows = [t for t in zip(*(c.to_pylist() for c in cols)) if None not in t]
    return int(np.prod([max(k) - min(k) + 1 for k in zip(*rows)] or [0],
                       dtype=object))


def ref_join_rows(st, bt, lkeys, rkeys, how):
    """NumPy/plain-Python reference with Spark's semantics (a null key
    matches nothing), as sorted row tuples. `st` streams and stands left;
    a full outer adds the build rows no stream row matched."""
    by_key = {}
    for i, b in enumerate(bt.to_pylist()):
        k = tuple(b[c] for c in rkeys)
        if None not in k:
            by_key.setdefault(k, []).append((i, b))
    out, matched = [], set()
    for s in st.to_pylist():
        hits = by_key.get(tuple(s[c] for c in lkeys), [])
        matched.update(i for i, _ in hits)
        if how == "inner" or (how in ("leftouter", "fullouter") and hits):
            out += [{**s, **b} for _, b in hits]
        elif how in ("leftouter", "fullouter"):
            out.append({**s, **{c: None for c in bt.column_names}})
        elif (how == "leftsemi") == bool(hits):
            out.append(s)
    if how == "fullouter":
        out += [{**{c: None for c in st.column_names}, **b}
                for i, b in enumerate(bt.to_pylist()) if i not in matched]
    return _sorted_rows(out)


def _sorted_rows(rows):
    return sorted((tuple(r.values()) for r in rows),
                  key=lambda t: [(v is None, v or 0) for v in t])


def _span_counts(tracing, name):
    return [s["counts"] for s in tracing.recorded() if s["name"] == name]


@pytest.mark.parametrize("how", ["inner", "leftouter", "leftsemi", "leftanti"])
@pytest.mark.parametrize("case", MODE_CASES)
def test_probe_mode_is_chosen_from_the_build_and_matches_numpy(
        case, how, spans):
    bt, st, lkeys, rkeys, mode = _mode_case(case)
    conf = RapidsConf()
    j = HashJoinExec(how, [col(c) for c in lkeys], [col(c) for c in rkeys],
                     ArrowScanExec([st], conf=conf),
                     ArrowScanExec([bt], conf=conf))
    got = _sorted_rows(j.execute_collect().to_pylist())
    assert got == ref_join_rows(st, bt, lkeys, rkeys, how)
    assert len(got) > 0 or (case == NO_KEY and how in ("inner", "leftsemi"))
    (prep,) = _span_counts(spans, "HashJoin.build_prep")
    assert prep["mode"] == mode and prep["keys"] == len(rkeys), prep
    assert {p["mode"] for p in _span_counts(spans, "HashJoin.probe")} == {mode}
    if case in MULTI_KEY_CASES and case != "rank_string_beside_int":
        assert prep["domain"] == _key_domain(bt, rkeys)
        assert mode != "rank" or prep["domain"] > 1 << 62
    if mode == "dense":
        assert prep["table_slots"] >= prep["domain"]
        assert (prep["domain"] > 0) == (case != NO_KEY)
    else:
        assert prep["table_slots"] == 0
        # over the budget the code has: max(4 x capacity, 4 Mi)
        assert mode != "one" or prep["domain"] > max(4 * prep["capacity"],
                                                     1 << 22)


def _chain_over(st, bt, conf):
    """`st` joined to `bt` on lk = rk, then to a small second build on
    lv = k2, as the planner stacks broadcast joins."""
    from spark_rapids_tpu.exec.joins import maybe_chain
    b2 = pa.table({"k2": pa.array(np.arange(0, 700, 2), type=pa.int32()),
                   "w": pa.array(np.arange(350) * 3, type=pa.int64())})
    inner = BroadcastHashJoinExec("inner", [col("lk")], [col("rk")],
                                  ArrowScanExec([st], conf=conf),
                                  ArrowScanExec([bt], conf=conf))
    outer = BroadcastHashJoinExec("inner", [col("lv")], [col("k2")], inner,
                                  ArrowScanExec([b2], conf=conf))
    return maybe_chain(outer, conf), b2


def _chain_reference(st, bt, b2):
    first = pa.Table.from_pylist(
        [dict(zip(st.column_names + bt.column_names, row))
         for row in ref_join_rows(st, bt, ["lk"], ["rk"], "inner")],
        schema=pa.schema(list(st.schema) + list(bt.schema)))
    return ref_join_rows(first, b2, ["lv"], ["k2"], "inner")


@pytest.mark.parametrize("case", SINGLE_KEY_CASES)   # the chain's hops
def test_probe_modes_through_the_join_chain(case, spans):
    from spark_rapids_tpu.exec.joins import BroadcastHashJoinChainExec
    bt, st, lkeys, rkeys, mode = _mode_case(case)
    chain, b2 = _chain_over(st, bt, RapidsConf())
    assert isinstance(chain, BroadcastHashJoinChainExec)
    got = _sorted_rows(chain.execute_collect().to_pylist())
    assert got == _chain_reference(st, bt, b2)
    assert len(got) > 0 or case == NO_KEY
    assert [p["mode"] for p in _span_counts(spans, "HashJoin.build_prep")] \
        == [mode, "dense"]
    fused = _span_counts(spans, "HashJoinChain.probe")
    if mode == "two":       # duplicate keys: the hops run one by one
        assert not fused
        assert [p["mode"] for p in _span_counts(spans, "HashJoin.probe")] \
            == ["two", "dense"]
    else:
        assert {p["modes"] for p in fused} == {mode + "+dense"}


class _BatchesExec(ArrowScanExec):
    """One partition that hands out each table as a batch of its own, so a
    stream's capacities can be chosen batch by batch."""

    num_partitions = 1

    def execute_partition(self, split):
        return self.wrap_output(
            ColumnarBatch.from_arrow(t, self._schema) for t in self.tables)


def _stream_batch(rows, survivors, first):
    """`rows` stream rows of which `survivors` pass both hops of
    `_landing_stack`; the rest miss the first build, every third of them
    with a NULL key."""
    i = first + np.arange(rows)
    keep = np.arange(rows) % max(rows // max(survivors, 1), 1) == 0
    keep &= np.cumsum(keep) <= survivors
    assert keep.sum() == survivors
    return pa.table({
        "lk": pa.array(np.where(keep, i % 500, 900 + i % 50), pa.int64(),
                       mask=~keep & (i % 3 == 0)),
        "lv": pa.array(2 * (i % 300), pa.int32()),
        "lf": pa.array(i / 7.0, pa.float64(), mask=i % 5 == 0),
        "ls": pa.array([["x", "y", None, "z"][j % 4] for j in i])})


def _landing_stack(batches, conf, hops=2, chained=False):
    """Two stacked inner broadcast joins over a stream of `batches`. With
    `hops=3` a third joins on `rg`, a column of the first hop's build,
    under a Project hoisted into its probe (as `plan/overrides.py` hoists
    one) that reorders the columns, drops the first build's `rv` and
    renames the second build's `w`. `chained`: each join goes through
    `maybe_chain` as the planner builds it, bottom-up."""
    from spark_rapids_tpu.exec.basic import ProjectExec
    from spark_rapids_tpu.exec.joins import maybe_chain
    plan = (lambda j: maybe_chain(j, conf)) if chained else (lambda j: j)
    bt = {"rk": pa.array(np.arange(500), pa.int64()),
          "rv": pa.array(np.arange(500) * 1.5, pa.float64())}
    if hops == 3:
        bt["rg"] = pa.array(np.arange(500) % 40, pa.int32())
    b2 = pa.table({"k2": pa.array(np.arange(0, 700, 2), pa.int32()),
                   "w": pa.array([None if k % 7 == 0 else f"w{k % 11}"
                                  for k in range(350)])})
    inner = plan(BroadcastHashJoinExec(
        "inner", [col("lk")], [col("rk")], _BatchesExec(batches, conf=conf),
        ArrowScanExec([pa.table(bt)], conf=conf)))
    mid = plan(BroadcastHashJoinExec("inner", [col("lv")], [col("k2")], inner,
                                     ArrowScanExec([b2], conf=conf)))
    if hops == 2:
        return mid
    b3 = pa.table({"g3": pa.array(np.arange(40), pa.int32()),
                   "gv": pa.array([None if g % 6 == 0 else g * 1.25
                                   for g in range(40)], pa.float64())})
    proj = ProjectExec([col("w").alias("w_name"), col("lk"), col("lf"),
                        col("ls"), col("lv"), col("rk"), col("rg"),
                        col("k2")], mid, conf=conf)
    return plan(BroadcastHashJoinExec(
        "inner", [col("rg")], [col("g3")], mid, ArrowScanExec([b3], conf=conf),
        stream_preproject=proj.project_list, stream_schema=proj.output))


# (stream rows, survivors) a batch; how each output lands at its bucket
# (None: no survivor, no output); runs of the chain program; hops
LANDING_CASES = {
    # two capacities, two buckets, in turn: one prediction a capacity
    "alternating_capacities": (
        [(64, 20), (30, 5), (60, 17), (32, 8), (64, 30), (20, 6)],
        ["sliced", "sliced", "hit", "hit", "hit", "hit"], 6, 2),
    "shrinking": ([(64, 40), (64, 10), (64, 3)],
                  ["hit", "sliced", "sliced"], 3, 2),
    "growing": ([(64, 3), (64, 10), (64, 40)],
                ["sliced", "rerun", "rerun"], 5, 2),
    "no_survivor_between": ([(64, 10), (64, 0), (64, 12)],
                            ["sliced", None, "hit"], 3, 2),
    # the largest bucket seen stands, not the last
    "smaller_then_larger_again": ([(64, 20), (64, 5), (64, 20)],
                                  ["sliced", "sliced", "hit"], 3, 2),
    "three_hops_alternating_capacities": (
        [(64, 20), (30, 5), (60, 17), (32, 8), (64, 30), (20, 6)],
        ["sliced", "sliced", "hit", "hit", "hit", "hit"], 6, 3),
    "three_hops_every_landing": (
        [(64, 20), (64, 5), (64, 40), (64, 30), (64, 40)],
        ["sliced", "sliced", "rerun", "sliced", "hit"], 6, 3),
}

# hops -> (deferred_cols, hop_cols): the build columns gathered after the
# compaction, and those gathered at their hop because a later hop reads
# them. Three hops: `rg` is the third hop's key; `rv` is dropped, so it is
# gathered nowhere.
LANDING_PLACES = {2: (4, 0), 3: (5, 1)}


@pytest.mark.parametrize("case", LANDING_CASES)
def test_the_chains_output_lands_at_its_bucket(case, spans, monkeypatch):
    """A predicted output bucket that was too large costs a slice of the
    first run's output, one that was too small a second run; either way the
    batches are the unfused stack's bit for bit, padding included, with the
    build columns gathered behind the compaction."""
    from spark_rapids_tpu.columnar.vector import bucket_capacity
    from spark_rapids_tpu.exec.joins import BroadcastHashJoinChainExec
    from spark_rapids_tpu.runtime import fuse
    shape, landed, chain_runs, hops = LANDING_CASES[case]
    batches, first = [], 0
    for rows, kept in shape:
        batches.append(_stream_batch(rows, kept, first))
        first += rows
    conf = RapidsConf()
    want = [b for b in _landing_stack(batches, conf, hops).execute_partition(0)
            if b.num_rows]
    spans.drain()
    calls = []
    call_fused = fuse.call_fused
    monkeypatch.setattr(
        fuse, "call_fused",
        lambda key, name, *a: calls.append(name) or call_fused(key, name, *a))
    chain = _landing_stack(batches, conf, hops, chained=True)
    assert isinstance(chain, BroadcastHashJoinChainExec)
    assert len(chain.hops) == hops
    got = list(chain.execute_partition(0))

    probes = _span_counts(spans, "HashJoinChain.probe")
    assert [p.get("landed") for p in probes] == landed
    assert {(p["deferred_cols"], p["hop_cols"]) for p in probes} == \
        {LANDING_PLACES[hops]}
    assert calls.count("HashJoinChain.probe") == chain_runs
    assert calls.count("HashJoinChain.land") == landed.count("sliced")
    survivors = [n for _, n in shape]
    assert [p["capacity_out"] for p in probes] == \
        [bucket_capacity(n) if n else 0 for n in survivors]
    for p in probes:
        # a hit ran at its bucket, a slice above it, a rerun below it
        assert {"hit": p["capacity_pred"] == p["capacity_out"],
                "sliced": p["capacity_pred"] > p["capacity_out"],
                "rerun": p["capacity_pred"] < p["capacity_out"],
                None: p["capacity_out"] == 0}[p.get("landed")], p
    # the read of the count stands inside the probe, at the run's capacity
    by_id = {s["id"]: s for s in spans.recorded()}
    assert [(by_id[s["parent"]]["name"], s["counts"]["capacity"],
             s["counts"]["rows"])
            for s in spans.recorded() if s["name"] == "sync.count"] == \
        [("HashJoinChain.probe", p["capacity_pred"], n)
         for p, n in zip(probes, survivors)]

    assert [b.num_rows for b in got] == [n for n in survivors if n]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.capacity == w.capacity == bucket_capacity(g.num_rows)
        assert g.num_rows == w.num_rows
        for gc, wc in zip(g.columns, w.columns):
            assert gc.dtype == wc.dtype
            np.testing.assert_array_equal(np.asarray(gc.validity),
                                          np.asarray(wc.validity))
            # every slot, the padding too, and -0.0 / NaN by their bits
            assert np.asarray(gc.data).tobytes() == \
                np.asarray(wc.data).tobytes()
        assert g.to_arrow().equals(w.to_arrow())


def _ranged_tables(vmin, n_build, step, second=None):
    """A unique build of `n_build` keys from `vmin` in steps of `step`, and a
    stream over and around them; with `second` = (vmin, range) a second key
    beside each, of its own range."""
    bk = vmin + np.arange(n_build) * step
    sk = np.concatenate([bk[::2], bk[:50] - 1, [vmin - 9, int(bk[-1]) + 9]])
    bt = {"rk": pa.array(bk, type=pa.int64())}
    st = {"lk": pa.array(sk, type=pa.int64())}
    if second is not None:
        vmin2, range2 = second
        bt["rk2"] = pa.array(vmin2 + np.arange(n_build) % range2,
                             type=pa.int32())
        # the rows whose first key matches match in the second too; the
        # rest lie one past its range
        lk2 = np.full(len(sk), vmin2 + range2)
        lk2[:len(bk[::2])] = vmin2 + np.arange(n_build)[::2] % range2
        st["lk2"] = pa.array(lk2, type=pa.int32())
    bt["rv"] = pa.array(np.arange(n_build), type=pa.int32())
    st["lv"] = pa.array(np.arange(len(sk)), type=pa.int32())
    return pa.table(bt), pa.table(st)


@pytest.mark.parametrize("through", ["join", "chain", "join_two_keys"])
def test_a_builds_key_range_shapes_no_program(through, spans):
    """Two builds of one capacity whose key ranges (each key's vmin and
    range, so the strides too) differ inside one bucket of table slots run
    ONE set of compiled programs: the second traces nothing and adds no
    kernel."""
    from spark_rapids_tpu.runtime import fuse

    def run(vmin, n_build, step, second):
        two = through == "join_two_keys"
        bt, st = _ranged_tables(vmin, n_build, step, second if two else None)
        lkeys, rkeys = (["lk", "lk2"], ["rk", "rk2"]) if two \
            else (["lk"], ["rk"])
        conf = RapidsConf()
        if through == "chain":
            node, b2 = _chain_over(st, bt, conf)
            want = _chain_reference(st, bt, b2)
        else:
            node = HashJoinExec("inner", [col(c) for c in lkeys],
                                [col(c) for c in rkeys],
                                ArrowScanExec([st], conf=conf),
                                ArrowScanExec([bt], conf=conf))
            want = ref_join_rows(st, bt, lkeys, rkeys, "inner")
        assert _sorted_rows(node.execute_collect().to_pylist()) == want
        assert len(want) > 30

    # domain 1198 (x 3): a table of 2048 (4096) slots
    run(1000, 400, 3, (7, 3))
    traces = fuse.stage_metrics()["traces"]
    with fuse._lock:
        kernels = len(fuse._kernels)
    # domain 1946 (x 2): the same bucket
    run(-123_456_789, 390, 5, (-40, 2))
    assert fuse.stage_metrics()["traces"] == traces
    with fuse._lock:
        assert len(fuse._kernels) == kernels
    assert {p["mode"] for p in _span_counts(spans, "HashJoin.build_prep")} \
        == {"dense"}


@pytest.mark.parametrize("case", ["dense_two_keys", "two_duplicate_tuples",
                                  "dense_three_keys_mixed_widths"])
@pytest.mark.parametrize("how", ["fullouter", "rightouter"])
def test_outer_joins_over_several_keys(how, case, spans):
    """A full outer reads which build rows matched from the packed build
    keys (mode `two` whatever the build, and a read of the mask a batch); a
    right outer streams its right side against a build of the left."""
    bt, st, lkeys, rkeys, mode = _mode_case(case)
    conf = RapidsConf()
    if how == "fullouter":
        left, right, lk, rk = st, bt, lkeys, rkeys
        want = ref_join_rows(st, bt, lkeys, rkeys, "fullouter")
        mode = "two"
    else:       # the left side builds: rows come build columns first
        left, right, lk, rk = bt, st, rkeys, lkeys
        want = _sorted_rows(
            dict(zip(bt.column_names + st.column_names,
                     row[st.num_columns:] + row[:st.num_columns]))
            for row in ref_join_rows(st, bt, lkeys, rkeys, "leftouter"))
    # the full outer over two stream partitions of one broadcast build: the
    # masks of both are merged before the unmatched build rows go out
    parts = ([left.slice(0, 350), left.slice(350)] if how == "fullouter"
             else [left])
    j = BroadcastHashJoinExec(
        how, [col(c) for c in lk], [col(c) for c in rk],
        ArrowScanExec(parts, conf=conf), ArrowScanExec([right], conf=conf))
    assert _sorted_rows(j.execute_collect().to_pylist()) == want
    for prep in _span_counts(spans, "HashJoin.build_prep"):   # a partition
        assert prep["mode"] == mode and prep["keys"] == len(rkeys), prep
        assert prep["domain"] == _key_domain(bt, rkeys)
    reads = _span_counts(spans, "sync.matched")
    assert len(reads) == (2 if how == "fullouter" else 0)


@pytest.mark.parametrize("how", ["inner", "leftouter", "fullouter",
                                 "leftanti"])
def test_an_empty_build_of_two_keys(how, spans):
    bt, st, lkeys, rkeys, _ = _mode_case("dense_two_keys")
    bt = bt.slice(0, 0)
    conf = RapidsConf()
    j = HashJoinExec(how, [col(c) for c in lkeys], [col(c) for c in rkeys],
                     ArrowScanExec([st], conf=conf),
                     ArrowScanExec([bt], conf=conf))
    got = _sorted_rows(j.execute_collect().to_pylist())
    assert got == ref_join_rows(st, bt, lkeys, rkeys, how)
    assert len(got) == (0 if how == "inner" else st.num_rows)
    (prep,) = _span_counts(spans, "HashJoin.build_prep")
    assert (prep["keys"], prep["rows"], prep["domain"]) == (2, 0, 0), prep
    assert prep["mode"] == ("two" if how == "fullouter" else "dense")


def test_a_hoisted_filter_masks_the_rank_path_too(spans):
    """The planner hoists an inner join's stream filter into the probe
    whatever the build turns out to hold; a single key whose range cannot
    be packed takes the rank path, which has to apply the filter as the
    packed path does."""
    from spark_rapids_tpu.session import TpuSession
    spark = TpuSession()
    lk = [I64.min, 5, 5, 7, I64.max, None, 5] * 30
    left = spark.create_dataframe({"k": pa.array(lk, pa.int64()),
                                   "a": pa.array(range(210), pa.int64())})
    right = spark.create_dataframe({"k": pa.array([I64.min, 5, I64.max],
                                                  pa.int64()),
                                    "b": pa.array([1, 2, 3], pa.int64())})
    df = left.filter(col("a") > 100).join(right, on="k")
    assert "Filter[stream]" in df.explain(fused=True)
    out = df.collect()
    b_of = {I64.min: 1, 5: 2, I64.max: 3}
    want = sorted((k, a, b_of[k]) for a, k in enumerate(lk)
                  if a > 100 and k in b_of)
    assert sorted(zip(*(out[c].to_pylist() for c in "kab"))) == want
    (prep,) = _span_counts(spans, "HashJoin.build_prep")
    assert prep["mode"] == "rank" and prep["domain"] == 1 << 64, prep
