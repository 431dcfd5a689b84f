"""TPC-H Q3 in plain NumPy. Copied from spark_rapids_tpu/benchmarks/tpch.py
``np_q3`` at commit 566b502; rows come back keyed by the text's output
columns. ``dtype``: see reference/q1.py."""

import numpy as np

from benchmark.datagen.tpch import days

COLUMNS = {"customer": ["c_custkey", "c_mktsegment"],
           "orders": ["o_custkey", "o_orderdate", "o_orderkey",
                      "o_shippriority"],
           "lineitem": ["l_discount", "l_extendedprice", "l_orderkey",
                        "l_shipdate"]}


def reference(tb, dtype=np.float64):
    cust, orders, li = tb["customer"], tb["orders"], tb["lineitem"]
    date = days(1995, 3, 15)
    ck = cust["c_custkey"][cust["c_mktsegment"] == "BUILDING"]
    om = (orders["o_orderdate"] < date) & np.isin(orders["o_custkey"], ck)
    okeys = orders["o_orderkey"][om]
    odate = orders["o_orderdate"][om]
    oprio = orders["o_shippriority"][om]
    lm = (li["l_shipdate"] > date) & np.isin(li["l_orderkey"], okeys)
    lkey = li["l_orderkey"][lm]
    vol = (li["l_extendedprice"][lm].astype(dtype)
           * (dtype(1.0) - li["l_discount"][lm].astype(dtype)))
    order = np.argsort(lkey, kind="stable")
    lkey, vol = lkey[order], vol[order]
    uk, start = np.unique(lkey, return_index=True)
    rev = np.add.reduceat(vol, start)
    osort = np.argsort(okeys, kind="stable")
    pos = osort[np.searchsorted(okeys, uk, sorter=osort)]
    rows = sorted(zip(uk, odate[pos], oprio[pos], rev),
                  key=lambda r: (-r[3], r[1], r[0]))[:10]
    return [{"l_orderkey": int(k), "revenue": float(r),
             "o_orderdate": int(d), "o_shippriority": int(p)}
            for k, d, p, r in rows]
