"""The group-by chain keeps the sorted-input verdict of the key-stats probe:
a single integer key that arrives sorted (TPC-H lineitem by l_orderkey) is
grouped over its input order in the chained update and merge too, and the
program checks in its status that the concat stayed sorted. Q18's text
through ``session.sql`` against the NumPy reference, chained against
unchained bit for bit, the two ways a step is rejected (a batch out of order,
a batch below the accumulator), and the cells whose chain never sees a
sorted key: every step accepted, at the dispatches they took before."""

import datetime
import gc
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark.datagen.tpch_q18 import c_name
from benchmark.reference import q18 as ref18
from spark_rapids_tpu import functions as F
from spark_rapids_tpu.runtime import stats as STATS
from spark_rapids_tpu.runtime import tracing
from spark_rapids_tpu.session import TpuSession

CHAIN = "spark.rapids.tpu.sql.stageFusion.groupBy.chain.enabled"
Q18 = open(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "queries", "q18.sql")).read()
# a batch of lineitem: capacity 2^17, where the key-stats probe runs
ORDERS_A_PART = 25_000


@pytest.fixture
def traced():
    automatic = gc.isenabled()
    gc.disable()
    tracing.drain()
    tracing.set_enabled(True)
    yield
    tracing.set_enabled(False)
    tracing.drain()
    if automatic:
        gc.enable()


def _lineitem(rng, first, n_orders):
    """Orders ``first``.. with 1 to 7 lines each, in key order, quantities
    1 to 50 as TPC-H draws them: bit-packed dictionary pages, which the
    scan can hand over still encoded."""
    keys = np.arange(first, first + n_orders, dtype=np.int64)
    nlines = rng.integers(1, 8, n_orders)
    lk = np.repeat(keys, nlines)
    qty = rng.integers(1, 51, len(lk)).astype(np.float64)
    return pa.table({"l_orderkey": pa.array(lk),
                     "l_quantity": pa.array(qty)})


def _write(d, name, parts):
    os.makedirs(d / name)
    for i, t in enumerate(parts):
        pq.write_table(t, str(d / name / f"part-{i:04d}.parquet"))
    return str(d / name)


def _tables(tmp_path, rng, parts=4, arrange=None):
    """lineitem in ``parts`` files of ORDERS_A_PART orders each, orders and
    customer to join them to. ``arrange(list of lineitem parts)`` returns
    the parts as they are written, in file order."""
    n_orders = parts * ORDERS_A_PART
    li = [_lineitem(rng, 1 + i * ORDERS_A_PART, ORDERS_A_PART)
          for i in range(parts)]
    if arrange is not None:
        li = arrange(li)
    n_cust = 2000
    okeys = np.arange(1, n_orders + 1, dtype=np.int64)
    ckeys = np.arange(1, n_cust + 1, dtype=np.int64)
    orders = pa.table({
        "o_orderkey": pa.array(okeys),
        "o_custkey": pa.array(rng.integers(1, n_cust + 1, n_orders)),
        "o_orderdate": pa.array(rng.integers(8000, 10000, n_orders)
                                .astype(np.int32), pa.int32())
        .cast(pa.date32()),
        "o_totalprice": pa.array(np.round(
            857.71 + (okeys * 9973 % 45000000) / 100.0, 2)),
    })
    customer = pa.table({"c_custkey": pa.array(ckeys),
                         "c_name": c_name(ckeys)})
    return {"lineitem": _write(tmp_path, "lineitem", li),
            "orders": _write(tmp_path, "orders", [orders]),
            "customer": _write(tmp_path, "customer", [customer])}


# the scan hands the aggregate its columns as still-encoded pages, as it
# does on the chip (the CPU's default decodes them on the host)
ENCODED = {"spark.rapids.tpu.sql.format.parquet.reader.type": "COALESCING",
           "spark.rapids.tpu.pipeline.enabled": True,
           "spark.rapids.tpu.sql.parquet.deviceDecode.enabled": True,
           "spark.rapids.tpu.sql.parquet.encodedUpload.enabled": True,
           "spark.rapids.tpu.sql.stageFusion.scan.enabled": True}


def _session(paths, chain=True, conf=None):
    spark = TpuSession({CHAIN: chain, **(conf or {})})
    for t, p in paths.items():
        spark.create_or_replace_temp_view(
            t, spark.read_parquet(p, files_per_partition=8))
    return spark


def _orderkey_sums(paths, chain=True):
    """The subquery's aggregate alone: (rows sorted by key, its spans)."""
    tracing.drain()
    spark = TpuSession({CHAIN: chain})
    df = (spark.read_parquet(paths["lineitem"], files_per_partition=8)
          .group_by("l_orderkey")
          .agg(F.sum(F.col("l_quantity")).alias("s")))
    rows = sorted((r["l_orderkey"], r["s"])
                  for r in df.collect().to_pylist())
    return rows, [s for s in tracing.drain()
                  if s["name"].startswith("HashAggregate.")]


def _reference_sums(paths):
    t = pq.read_table(paths["lineitem"])
    k = t.column("l_orderkey").to_numpy()
    q = t.column("l_quantity").to_numpy()
    keys, inv = np.unique(k, return_inverse=True)
    sums = np.zeros(len(keys))
    np.add.at(sums, inv, q)
    return sorted(zip(keys.tolist(), sums.tolist()))


def _read(paths, columns):
    out = {}
    for table, cols in columns.items():
        t = pq.read_table(paths[table], columns=cols)
        out[table] = {}
        for c in t.column_names:
            col = t.column(c)
            if pa.types.is_date32(col.type):
                col = col.cast(pa.int32())
            out[table][c] = col.to_numpy(zero_copy_only=False)
    return out


def _plain(rows):
    """Dates as days since 1970, as the reference gives them."""
    epoch = datetime.date(1970, 1, 1)
    return [{k: (v - epoch).days if isinstance(v, datetime.date) else v
             for k, v in r.items()} for r in rows]


def _chain_counts(spans):
    return [(s["counts"].get("accepted"), s["counts"].get("presorted"))
            for s in spans if s["name"] == "HashAggregate.chain"]


@pytest.mark.parametrize("conf", [None, ENCODED], ids=["dense", "encoded"])
def test_q18_text_matches_the_reference_with_a_presorted_chain(
        tmp_path, traced, conf):
    paths = _tables(tmp_path, np.random.default_rng(18))
    spark = _session(paths, conf=conf)
    got = _plain(spark.sql(Q18).collect().to_pylist())
    spans = [s for s in tracing.drain()
             if s["name"].startswith("HashAggregate.")]
    want = ref18.reference(_read(paths, ref18.COLUMNS))
    assert want and got == want
    # the subquery's aggregate: probed sorted, then three chained steps
    # that sorted nothing and were all accepted
    assert _chain_counts(spans).count((1, 1)) == 3
    assert (0, 1) not in _chain_counts(spans)
    assert any(s["name"] == "HashAggregate.agg"
               and s["counts"].get("presorted") == 1 for s in spans)


def test_presorted_chain_is_bit_identical_to_unchained(tmp_path, traced):
    paths = _tables(tmp_path, np.random.default_rng(19), parts=5)
    chained, spans = _orderkey_sums(paths, chain=True)
    unchained, plain_spans = _orderkey_sums(paths, chain=False)
    assert chained == unchained == _reference_sums(paths)
    assert _chain_counts(spans) == [(1, 1)] * 4
    assert not _chain_counts(plain_spans)
    (agg,) = [s for s in spans if s["name"] == "HashAggregate.agg"]
    assert agg["counts"]["presorted"] == 1
    assert agg["counts"]["sort_operands"] == 0


def _shuffle_third(parts):
    rng = np.random.default_rng(3)
    t = parts[2]
    parts[2] = t.take(pa.array(rng.permutation(t.num_rows)))
    return parts


def _swap_second_and_third(parts):
    parts[1], parts[2] = parts[2], parts[1]
    return parts


@pytest.mark.parametrize("arrange", [
    _shuffle_third,           # the batch itself is out of order
    _swap_second_and_third,   # its first key below the accumulator's last
])
def test_a_step_off_its_order_is_rejected_and_redone(tmp_path, traced,
                                                     arrange):
    paths = _tables(tmp_path, np.random.default_rng(20), arrange=arrange)
    chained, spans = _orderkey_sums(paths, chain=True)
    unchained, _ = _orderkey_sums(paths, chain=False)
    assert chained == unchained == _reference_sums(paths)
    steps = _chain_counts(spans)
    # the step that finds its batch off the order is rejected and the batch
    # redone unchained, whose probe (or merge) no longer proves the stream
    # sorted: the step after it runs the sorting program, accepted
    assert steps == [(1, 1), (0, 1), (1, 0)]


# -- the cells whose chain never sees a sorted key -----------------------------

N = 110_000


def _fact(rng):
    return pa.table({
        "flag": pa.array(np.array(["A", "N", "R"])[rng.integers(0, 3, N)]),
        "status": pa.array(np.array(["F", "O"])[rng.integers(0, 2, N)]),
        "okey": pa.array(rng.integers(1, 40_000, N).astype(np.int64)),
        "odate": pa.array(rng.integers(8000, 8100, N).astype(np.int32)),
        "prio": pa.array(np.zeros(N, np.int32)),
        "price": pa.array(np.round(rng.uniform(900, 105000, N), 2)),
        "disc": pa.array(rng.integers(0, 11, N) * 0.01),
    })


def _agg_dispatches_and_chain(path, keys, chain):
    """(rows, the aggregate's dispatches in a second run, its spans)."""
    tracing.drain()
    spark = TpuSession({CHAIN: chain})
    df = (spark.read_parquet(path, files_per_partition=4)
          .group_by(*keys)
          .agg(F.sum(F.col("price") * (1 - F.col("disc"))).alias("rev"),
               F.count(None).alias("n")))
    df.collect()
    spans = [s for s in tracing.drain()
             if s["name"].startswith("HashAggregate.")]
    rows = sorted(tuple(r.values()) for r in df.collect().to_pylist())
    tbl = STATS.node_table(df._last_collector)
    dispatches = sum(e["dispatches"] or 0 for e in tbl
                     if e["name"] == "HashAggregateExec")
    return rows, dispatches, spans


@pytest.mark.parametrize("shape,keys,dispatches", [
    # the dispatches each shape took before the chain kept a sorted verdict
    ("q1", ("flag", "status"), 5),            # the dense path
    ("q3", ("okey", "odate", "prio"), 6),     # a folded multi-key sort
])
def test_unsorted_keys_chain_as_before(tmp_path, traced, shape, keys,
                                       dispatches):
    rng = np.random.default_rng(21)
    path = _write(tmp_path, "fact", [_fact(rng) for _ in range(4)])
    chained, n, spans = _agg_dispatches_and_chain(path, keys, True)
    unchained, _, _ = _agg_dispatches_and_chain(path, keys, False)
    assert chained == unchained
    assert _chain_counts(spans) == [(1, 0)] * 3
    assert all(s["counts"].get("presorted", 0) == 0 for s in spans)
    assert n == dispatches


# -- float sums on the sort path ----------------------------------------------

def _float_groups(rng, presorted):
    """One batch at capacity 2^17 (the key-stats probe's gate): an int64 key
    with about four rows a key, a float64 value with nulls, one key whose
    values are all null and one whose values hold a NaN. Shuffled, the keys
    hold nulls too (a null key would break the sorted verdict)."""
    n = 120_000
    keys = np.sort(rng.integers(1 << 33, (1 << 33) + n // 4, n))
    vals = rng.uniform(-1e4, 1e5, n)
    vals[np.flatnonzero(keys == keys[n // 3])[-1]] = np.nan
    null_val = (rng.random(n) < 0.05) | (keys == keys[n // 2])
    key_valid = np.ones(n, bool)
    if not presorted:
        perm = rng.permutation(n)
        keys, vals, null_val = keys[perm], vals[perm], null_val[perm]
        key_valid = rng.random(n) >= 0.01
    return pa.table({"k": pa.array(keys, mask=~key_valid),
                     "v": pa.array(vals, mask=null_val)})


@pytest.mark.parametrize("presorted", [True, False],
                         ids=["presorted", "unsorted"])
def test_float_sum_and_avg_on_the_sort_path_match_pyarrow(traced, presorted):
    """A float64 sum and avg by an int64 key through the sort-path group-by,
    over input the probe proves sorted and over shuffled input, agree with
    pyarrow's group_by: a group of null values sums to null, a NaN stays in
    its own group, every other group within 1e-12."""
    t = _float_groups(np.random.default_rng(41), presorted)
    got = (TpuSession().create_dataframe(t).group_by("k")
           .agg(F.sum(F.col("v")).alias("s"), F.avg(F.col("v")).alias("a"))
           .collect())
    (agg,) = [s for s in tracing.drain() if s["name"] == "HashAggregate.agg"]
    assert agg["counts"]["path"] == "sort"
    assert agg["counts"]["capacity"] >= 4096
    assert agg["counts"].get("presorted", 0) == int(presorted)
    want = t.group_by("k").aggregate([("v", "sum"), ("v", "mean")])
    want = {k: (s, a) for k, s, a in zip(*(want.column(c).to_pylist()
                                           for c in ("k", "v_sum", "v_mean")))}
    got = {r["k"]: (r["s"], r["a"]) for r in got.to_pylist()}
    assert got.keys() == want.keys()
    nan = [k for k, (s, _) in want.items() if s is not None and s != s]
    null = [k for k, (s, _) in want.items() if s is None]
    assert len(nan) == 1 and null and (None in want) != presorted
    for k, (s, a) in want.items():
        if s is None or s != s:
            assert got[k][0] is None if s is None else got[k][0] != got[k][0]
            assert got[k][1] is None if a is None else got[k][1] != got[k][1]
        else:
            np.testing.assert_allclose(got[k], (s, a), rtol=1e-12, atol=0)
