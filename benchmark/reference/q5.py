"""TPC-H Q5 in plain NumPy. Copied from spark_rapids_tpu/benchmarks/tpch.py
``np_q5`` at commit 566b502; rows come back keyed by the text's output
columns. ``dtype``: see reference/q1.py."""

import numpy as np

from benchmark.datagen.tpch import days

COLUMNS = {"customer": ["c_custkey", "c_nationkey"],
           "orders": ["o_custkey", "o_orderdate", "o_orderkey"],
           "lineitem": ["l_discount", "l_extendedprice", "l_orderkey",
                        "l_suppkey"],
           "supplier": ["s_nationkey", "s_suppkey"],
           "nation": ["n_name", "n_nationkey", "n_regionkey"],
           "region": ["r_name", "r_regionkey"]}


def reference(tb, dtype=np.float64):
    date0, date1 = days(1994, 1, 1), days(1995, 1, 1)
    region, nation = tb["region"], tb["nation"]
    asia = region["r_regionkey"][region["r_name"] == "ASIA"]
    nmask = np.isin(nation["n_regionkey"], asia)
    nkeys = nation["n_nationkey"][nmask]
    nnames = nation["n_name"][nmask]
    supp = tb["supplier"]
    smask = np.isin(supp["s_nationkey"], nkeys)
    # supplier key -> nation (dense s_suppkey 1..n)
    s_nation = np.full(int(supp["s_suppkey"].max()) + 1, -1, dtype=np.int64)
    s_nation[supp["s_suppkey"][smask]] = supp["s_nationkey"][smask]
    cust = tb["customer"]
    c_nation = np.full(int(cust["c_custkey"].max()) + 1, -2, dtype=np.int64)
    c_nation[cust["c_custkey"]] = cust["c_nationkey"]
    orders = tb["orders"]
    om = (orders["o_orderdate"] >= date0) & (orders["o_orderdate"] < date1)
    o_cnation = np.full(int(orders["o_orderkey"].max()) + 1, -3,
                        dtype=np.int64)
    o_cnation[orders["o_orderkey"][om]] = c_nation[orders["o_custkey"][om]]
    li = tb["lineitem"]
    lsn = s_nation[li["l_suppkey"]]
    lcn = o_cnation[li["l_orderkey"]]
    keep = (lsn >= 0) & (lsn == lcn)
    vol = (li["l_extendedprice"][keep].astype(dtype)
           * (dtype(1.0) - li["l_discount"][keep].astype(dtype)))
    nat = lsn[keep]
    name_of = {int(k): str(n) for k, n in zip(nkeys, nnames)}
    out = {name_of[int(k)]: float(vol[nat == k].sum()) for k in np.unique(nat)}
    return [{"n_name": n, "revenue": v}
            for n, v in sorted(out.items(), key=lambda kv: -kv[1])]
