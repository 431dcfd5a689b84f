"""TPC-H q1/q3/q5 end-to-end through the session API vs independent NumPy
oracles (reference mortgage-app role)."""

import pytest

from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.session import TpuSession


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    d = tmp_path_factory.mktemp("tpch")
    paths = tpch.generate(0.005, str(d))
    spark = TpuSession()
    return tpch.load(spark, paths), tpch.load_np(paths)


def test_q1(data):
    dfs, tb = data
    got = tpch.q1(dfs).collect().to_pylist()
    exp = tpch.np_q1(tb)
    assert len(got) == len(exp) == 4
    for g_, e in zip(got, exp):
        g = list(g_.values())
        assert g[0] == e[0] and g[1] == e[1]
        for a, b in zip(g[2:], e[2:]):
            assert a == pytest.approx(b, rel=1e-9)


def test_q3(data):
    dfs, tb = data
    got = tpch.q3(dfs).collect().to_pylist()
    exp = tpch.np_q3(tb)
    assert len(got) == len(exp)
    for g, (k, d, p, rev) in zip(got, exp):
        assert g["l_orderkey"] == k
        assert g["o_shippriority"] == p
        assert g["revenue"] == pytest.approx(rev, rel=1e-9)


def test_q5(data):
    dfs, tb = data
    got = tpch.q5(dfs).collect().to_pylist()
    exp = tpch.np_q5(tb)
    assert len(got) == len(exp)
    for g, (n, v) in zip(got, exp):
        assert g["n_name"] == n
        assert g["revenue"] == pytest.approx(v, rel=1e-9)


def test_q18(data):
    import datetime
    dfs, tb = data
    got = tpch.q18(dfs).collect().to_pylist()
    exp = tpch.np_q18(tb)
    assert len(got) == len(exp)   # may be empty at tiny SF — both sides
    epoch = datetime.date(1970, 1, 1)
    for g, (c, o, d, t, s) in zip(got, exp):
        assert g["c_custkey"] == c and g["o_orderkey"] == o
        gd = g["o_orderdate"]
        if isinstance(gd, datetime.date):
            gd = (gd - epoch).days
        assert gd == d
        assert g["o_totalprice"] == pytest.approx(t, rel=1e-9)
        assert g["sum_qty"] == pytest.approx(s, rel=1e-9)
