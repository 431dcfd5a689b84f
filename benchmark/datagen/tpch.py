"""TPC-H table subset as Parquet, made from a seed.

Copied from spark_rapids_tpu/benchmarks/tpch.py ``generate`` and
benchmarks/common.py ``write_partitioned`` at commit 566b502, with two
changes: the rng takes the run's ``seed`` (the original fixes 20260729), and
each file's key-value metadata records the decoded bytes of every column
(``benchmark.decoded_bytes``), which the roofline's byte count reads back.
The copy imports nothing of the program: later PRs may change the program's
generator and may not change the yardstick.

Keys are dense (1..n) and uniform, money and quantity are float64, and only
the columns that Q1/Q3/Q5/Q18 read are made: see the configuration files'
``reduced`` and ``assumed``. Every value is the raw draw from the seed.
"""

from __future__ import annotations

import datetime
import json
import os
import shutil

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

from benchmark.query_bytes import DECODED_KEY

EPOCH = datetime.date(1970, 1, 1)


def days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - EPOCH).days


START = days(1992, 1, 1)
END = days(1998, 8, 2)

NATIONS = ["ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
           "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ", "JAPAN",
           "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU", "CHINA",
           "ROMANIA", "SAUDI ARABIA", "VIETNAM", "RUSSIA", "UNITED KINGDOM",
           "UNITED STATES"]
NATION_REGION = [0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2, 3,
                 4, 2, 3, 3, 1]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]


def decoded_bytes(column: pa.ChunkedArray) -> int:
    """Bytes of a column as a kernel would hold it: values times width, and
    for strings the characters alone (no offsets)."""
    t = column.type
    if pa.types.is_string(t) or pa.types.is_binary(t):
        return int(pc.sum(pc.binary_length(column)).as_py() or 0)
    return len(column) * t.bit_width // 8


def _write(outdir: str, name: str, table: pa.Table, nfiles: int) -> None:
    d = os.path.join(outdir, name)
    os.makedirs(d)
    per = max((table.num_rows + nfiles - 1) // nfiles, 1)
    for i in range(nfiles):
        sl = table.slice(i * per, per).combine_chunks()
        if sl.num_rows == 0 and i > 0:
            break
        sizes = {c: decoded_bytes(sl.column(c)) for c in sl.column_names}
        sl = sl.replace_schema_metadata({DECODED_KEY: json.dumps(sizes)})
        pq.write_table(sl, os.path.join(d, f"part-{i:04d}.parquet"))


def tables(sf: float, seed: int, files: dict) -> dict:
    """{table: (pa.Table, files)} for the tables ``files`` names."""
    rng = np.random.default_rng(seed)
    n_orders = int(1_500_000 * sf)
    n_cust = max(int(150_000 * sf), 1)
    n_supp = max(int(10_000 * sf), 1)
    out = {}
    out["customer"] = pa.table({
        "c_custkey": pa.array(np.arange(1, n_cust + 1, dtype=np.int64)),
        "c_mktsegment": pa.array(
            np.array(SEGMENTS)[rng.integers(0, 5, n_cust)]),
        "c_nationkey": pa.array(
            rng.integers(0, 25, n_cust).astype(np.int32)),
    })
    out["supplier"] = pa.table({
        "s_suppkey": pa.array(np.arange(1, n_supp + 1, dtype=np.int64)),
        "s_nationkey": pa.array(
            rng.integers(0, 25, n_supp).astype(np.int32)),
    })
    out["nation"] = pa.table({
        "n_nationkey": pa.array(np.arange(25, dtype=np.int32)),
        "n_name": pa.array(NATIONS),
        "n_regionkey": pa.array(np.array(NATION_REGION, dtype=np.int32)),
    })
    out["region"] = pa.table({
        "r_regionkey": pa.array(np.arange(5, dtype=np.int32)),
        "r_name": pa.array(REGIONS),
    })
    o_orderkey = np.arange(1, n_orders + 1, dtype=np.int64)
    o_orderdate = rng.integers(START, END - 150, n_orders).astype(np.int32)
    out["orders"] = pa.table({
        "o_orderkey": pa.array(o_orderkey),
        "o_custkey": pa.array(
            rng.integers(1, n_cust + 1, n_orders).astype(np.int64)),
        "o_orderdate": pa.array(o_orderdate, pa.int32()).cast(pa.date32()),
        "o_shippriority": pa.array(np.zeros(n_orders, dtype=np.int32)),
        # derived from the key, not drawn (q18 reads it)
        "o_totalprice": pa.array(np.round(
            857.71 + (o_orderkey * 9973 % 45000000) / 100.0, 2)),
    })
    # lineitem: 1..7 lines an order, mean 4
    nlines = rng.integers(1, 8, n_orders)
    l_orderkey = np.repeat(o_orderkey, nlines)
    l_orderdate = np.repeat(o_orderdate, nlines)
    n_li = len(l_orderkey)
    l_shipdate = (l_orderdate + rng.integers(1, 122, n_li)).astype(np.int32)
    l_receiptdate = (l_shipdate + rng.integers(1, 31, n_li)).astype(np.int32)
    cutoff = days(1995, 6, 17)
    returnflag = np.where(l_receiptdate <= cutoff,
                          np.where(rng.random(n_li) < 0.5, "R", "A"), "N")
    linestatus = np.where(l_shipdate > cutoff, "O", "F")
    out["lineitem"] = pa.table({
        "l_orderkey": pa.array(l_orderkey),
        "l_suppkey": pa.array(
            rng.integers(1, n_supp + 1, n_li).astype(np.int64)),
        "l_quantity": pa.array(
            rng.integers(1, 51, n_li).astype(np.float64)),
        "l_extendedprice": pa.array(
            np.round(rng.uniform(900.0, 105000.0, n_li), 2)),
        "l_discount": pa.array(
            np.round(rng.integers(0, 11, n_li) * 0.01, 2)),
        "l_tax": pa.array(
            np.round(rng.integers(0, 9, n_li) * 0.01, 2)),
        "l_returnflag": pa.array(returnflag),
        "l_linestatus": pa.array(linestatus),
        "l_shipdate": pa.array(l_shipdate, pa.int32()).cast(pa.date32()),
    })
    unknown = set(files) - set(out)
    if unknown:
        raise ValueError(f"the generator makes no table {sorted(unknown)}")
    return {name: (out[name], int(n)) for name, n in files.items()}


def generate(sf: float, seed: int, files: dict, workdir: str) -> dict:
    """Write the tables anew under ``workdir/tpch/``, in place of whatever
    data set is there, and return {table: directory}."""
    root = os.path.join(workdir, "tpch")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    for name, (table, nfiles) in tables(sf, seed, files).items():
        _write(root, name, table, nfiles)
    return {name: os.path.join(root, name) for name in files}
