"""The yardstick's own arithmetic: trace reduction, byte counts, the files
that BENCHMARK.json names, and the refusal to run without a chip."""

import os
import subprocess
import sys

import pytest

from benchmark import query_bytes, run, trace_reduce
from benchmark.datagen import tpch


def test_trace_reduce_selftest():
    trace_reduce._selftest()


def test_q1_asks_for_about_230_MB_at_sf1(tmp_path):
    config = run.find_cell("tpch_sf1_batch.q1")[2]
    paths = tpch.generate(1, 2**31 + 5, config["tables"], str(tmp_path))
    footers = query_bytes.table_footers(paths)
    q1 = run.load_query("q1")
    rows = query_bytes.input_rows(q1["text"], footers)
    assert 5_990_000 < rows < 6_010_000, rows
    # 4 float64, 1 date32 and 2 one-character strings a row: 38 bytes
    assert query_bytes.column_bytes(q1["text"], footers) == rows * 38
    q5 = run.load_query("q5")
    assert query_bytes.input_rows(q5["text"], footers) == \
        rows + 1_500_000 + 150_000 + 10_000 + 25 + 5
    # the same seed gives the same data, another seed other data
    again = tpch.tables(0.001, 7, {"lineitem": 1})["lineitem"][0]
    assert again.equals(tpch.tables(0.001, 7, {"lineitem": 1})["lineitem"][0])
    assert not again.equals(tpch.tables(0.001, 8, {"lineitem": 1})["lineitem"][0])


def test_generator_writes_the_raw_draws_anew_each_time(tmp_path):
    import numpy as np
    import pyarrow.parquet as pq
    files = {"lineitem": 2, "nation": 1}
    paths = tpch.generate(0.001, 7, files, str(tmp_path))
    first = pq.read_table(paths["lineitem"])
    # the values are the draws themselves: tax from the same place of the
    # same stream as the program's generator takes it, nothing rewritten
    rng = np.random.default_rng(7)
    n_orders, n_cust, n_supp = 1500, 150, 10
    rng.integers(0, 5, n_cust), rng.integers(0, 25, n_cust)
    rng.integers(0, 25, n_supp)
    rng.integers(tpch.START, tpch.END - 150, n_orders)
    rng.integers(1, n_cust + 1, n_orders)
    n_li = int(rng.integers(1, 8, n_orders).sum())
    assert first.num_rows == n_li
    rng.integers(1, 122, n_li), rng.integers(1, 31, n_li), rng.random(n_li)
    rng.integers(1, n_supp + 1, n_li), rng.integers(1, 51, n_li)
    rng.uniform(900.0, 105000.0, n_li), rng.integers(0, 11, n_li)
    assert (first.column("l_tax").to_numpy()
            == np.round(rng.integers(0, 9, n_li) * 0.01, 2)).all()
    # another seed takes the place of the first: one data set on disk
    again = tpch.generate(0.001, 8, files, str(tmp_path))
    assert again == paths and os.listdir(str(tmp_path)) == ["tpch"]
    assert not pq.read_table(paths["lineitem"]).equals(first)


def test_every_name_in_benchmark_json_has_its_files():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    for w in bench["workloads"]:
        _cell, entry, config, traffic, _ = run.find_cell(w["name"])
        assert config["name"] == entry["name"] == w["config"]
        assert sorted(config["reduced"]) == sorted(entry["reduced"])
        # every departure from the source is said in words, once
        assert set(config["reduced_detail"]) == set(config["reduced"])
        for q in traffic["queries"]:
            assert run.load_query(q)["text"]
            assert os.path.exists(os.path.join(run.HERE, "reference", q + ".py"))
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            assert os.path.exists(os.path.join(
                run.HERE, "metrics_" + kind, m["name"] + ".py")), m["name"]


def test_open_loop_is_refused_with_a_reason(monkeypatch):
    real = run.load_json

    def fake(*parts):
        got = real(*parts)
        if parts[-1] == "q1.json":
            got = dict(got, loop="open", rate_per_s=1.0)
        return got
    monkeypatch.setattr(run, "load_json", fake)
    with pytest.raises(SystemExit, match="only the closed loop"):
        run.find_cell("tpch_sf1_batch.q1")


def test_run_exits_2_and_prints_no_result_without_a_chip():
    p = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         "tpch_sf1_batch.q1", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 2, (p.returncode, p.stderr[-500:])
    assert p.stdout.strip() == "", p.stdout
