"""TPC-H Q1 in plain NumPy. Copied from spark_rapids_tpu/benchmarks/tpch.py
``np_q1`` at commit 566b502; rows come back keyed by the text's output
columns. ``dtype`` is the type the money arithmetic runs in: float64 is the
reference, float32 the control that has to come out as not correct."""

import numpy as np

from benchmark.datagen.tpch import days

COLUMNS = {"lineitem": ["l_discount", "l_extendedprice", "l_linestatus",
                        "l_quantity", "l_returnflag", "l_shipdate", "l_tax"]}


def reference(tb, dtype=np.float64):
    li = tb["lineitem"]
    keep = li["l_shipdate"] <= days(1998, 9, 2)
    rf, ls = li["l_returnflag"][keep], li["l_linestatus"][keep]
    qty = li["l_quantity"][keep].astype(dtype)
    price = li["l_extendedprice"][keep].astype(dtype)
    disc = li["l_discount"][keep].astype(dtype)
    tax = li["l_tax"][keep].astype(dtype)
    one = dtype(1.0)
    disc_price = price * (one - disc)
    charge = disc_price * (one + tax)
    key = np.char.add(rf.astype("U1"), ls.astype("U1"))
    order = np.argsort(key, kind="stable")
    key, qty, price, disc, disc_price, charge = (
        a[order] for a in (key, qty, price, disc, disc_price, charge))
    uniq, start = np.unique(key, return_index=True)
    rows = []
    for g, s in enumerate(start):
        e = start[g + 1] if g + 1 < len(start) else len(key)
        n = int(e - s)
        rows.append({
            "l_returnflag": str(uniq[g][0]), "l_linestatus": str(uniq[g][1]),
            "sum_qty": float(qty[s:e].sum()),
            "sum_base_price": float(price[s:e].sum()),
            "sum_disc_price": float(disc_price[s:e].sum()),
            "sum_charge": float(charge[s:e].sum()),
            "avg_qty": float(qty[s:e].sum() / dtype(n)),
            "avg_price": float(price[s:e].sum() / dtype(n)),
            "avg_disc": float(disc[s:e].sum() / dtype(n)),
            "count_order": n})
    return rows
