"""TPC-H Q18 in plain NumPy; rows come back keyed by the text's output
columns (the unaliased ``sum(l_quantity)`` is ``sum``, as the program names
it). ``dtype``: see reference/q1.py.

o_totalprice is derived from the key by ``datagen/tpch.py`` and no two
orders share one, so ``order by o_totalprice desc, o_orderdate`` leaves no
tie for the limit to cut through."""

import numpy as np

COLUMNS = {"customer": ["c_custkey", "c_name"],
           "orders": ["o_custkey", "o_orderdate", "o_orderkey",
                      "o_totalprice"],
           "lineitem": ["l_orderkey", "l_quantity"]}


def _lookup(keys, wanted):
    """Positions in ``keys`` (unique) of each of ``wanted``, all present."""
    order = np.argsort(keys, kind="stable")
    pos = order[np.searchsorted(keys, wanted, sorter=order)]
    assert (keys[pos] == wanted).all()
    return pos


def reference(tb, dtype=np.float64):
    cust, orders, li = tb["customer"], tb["orders"], tb["lineitem"]
    # the subquery: l_orderkey whose lines sum to more than 300
    keys, inverse = np.unique(li["l_orderkey"], return_inverse=True)
    sums = np.zeros(len(keys), dtype)
    np.add.at(sums, inverse, li["l_quantity"].astype(dtype))
    big = sums > dtype(300)
    big_keys, big_sums = keys[big], sums[big]
    # orders IN the subquery, joined to customer and to their lines: a group
    # a qualifying order, summing the same lines the subquery summed
    opos = _lookup(orders["o_orderkey"], big_keys)
    custkey = orders["o_custkey"][opos]
    cpos = _lookup(cust["c_custkey"], custkey)
    price = orders["o_totalprice"][opos].astype(dtype)
    date = orders["o_orderdate"][opos]
    rows = sorted(range(len(big_keys)),
                  key=lambda i: (-price[i], date[i]))[:100]
    return [{"c_name": str(cust["c_name"][cpos[i]]),
             "c_custkey": int(custkey[i]),
             "o_orderkey": int(big_keys[i]),
             "o_orderdate": int(date[i]),
             "o_totalprice": float(price[i]),
             "sum": float(big_sums[i])} for i in rows]
