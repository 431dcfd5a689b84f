"""TPC-DS subset end-to-end through the session API vs independent NumPy
oracles (reference qa_nightly_select_test role)."""

import pytest

from spark_rapids_tpu.benchmarks import tpcds
from spark_rapids_tpu.session import TpuSession


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    d = tmp_path_factory.mktemp("tpcds")
    paths = tpcds.generate(0.012, str(d))
    spark = TpuSession()
    return tpcds.load(spark, paths), tpcds.load_np(paths)


def _rows(df):
    return [tuple(r.values()) for r in df.collect().to_pylist()]


def _check(got, exp, float_cols):
    tpcds.check_rows(got, exp, float_cols)


# breadth: window-heavy (q53/q63/q89/q98), decimal-heavy (q48/q79 over
# decimal(7,2) ss_net_profit — exact, no float slot), conditional aggregation
# (q43), multi-count cross join (q88/q96), ticket/basket shapes
# (q34/q73/q46/q68/q79), avg-subquery joins (q6/q65), state rollup base (q27);
# float-tolerance columns come from the shared tpcds.FLOAT_COLS table
@pytest.mark.parametrize("name", sorted(tpcds.FLOAT_COLS))
def test_tpcds_query_matches_oracle(data, name):
    dfs, tb = data
    got = _rows(tpcds.QUERIES[name](dfs))
    exp = [tuple(r) for r in tpcds.NP_QUERIES[name](tb)]
    assert exp, "vacuous test: oracle returned no rows"
    _check(got, exp, tpcds.FLOAT_COLS[name])


def test_tpcds_q3_over_mesh(tmp_path):
    """Config-3's defining property: the subset also runs with exchanges as
    all_to_all collectives over the virtual 8-device mesh."""
    paths = tpcds.generate(0.003, str(tmp_path))
    mesh = TpuSession({"spark.rapids.tpu.mesh.enabled": "true",
                       "spark.rapids.tpu.mesh.devices": "8"})
    dfs = tpcds.load(mesh, paths)
    got = _rows(tpcds.q3(dfs))
    exp = [tuple(r) for r in tpcds.np_q3(tpcds.load_np(paths))]
    assert exp, "vacuous test: oracle returned no rows"
    _check(got, exp, {3})
