"""TPC-DS query 67 in plain NumPy, over the Parquet files read through pyarrow.
Imports nothing of the program. Rows come back keyed by the text's output
columns, in the text's order, the first 100.

Departures from the specification, each stated:
- money is int64 hundredths (the unscaled DECIMAL(7,2) of the data), so
  ``sumsales`` reads in hundredths and ``rk`` is exact; ``dtype`` is the
  control's way in: ``np.float32`` computes products and sums in float32.
- the three joins are lookups by dense key (no foreign key is NULL in the
  generated data), so the inner joins drop no row but by the date filter.
- ORDER BY is Spark's: ascending, NULLs first, strings by code point.
- NULL is rendered as ``queries/q67.py`` renders it: ``"<null>"`` for the
  five string keys, -1 for ``d_year`` / ``d_qoy`` / ``d_moy``, after the
  order and the limit. ``compare.py`` counts every NULL as a mismatch.
- ``rank()`` is 1 + the number of rows of the ``i_category`` partition with
  a strictly greater ``sumsales``; the rolled-up NULL category is a
  partition of its own.
"""

import numpy as np

COLUMNS = {"store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
                           "ss_quantity", "ss_sales_price"],
           "date_dim": ["d_date_sk", "d_month_seq", "d_year", "d_qoy",
                        "d_moy"],
           "store": ["s_store_sk", "s_store_id"],
           "item": ["i_item_sk", "i_category", "i_class", "i_brand",
                    "i_product_name"]}

KEYS = ["i_category", "i_class", "i_brand", "i_product_name", "d_year",
        "d_qoy", "d_moy", "s_store_id"]
STRINGS = {"i_category", "i_class", "i_brand", "i_product_name", "s_store_id"}
DMS = 1200
NULL_STRING, NULL_INT = "<null>", -1


def _dense(keys, values):
    """values by key, as an array indexed by key - keys.min()."""
    out = np.zeros(int(keys.max() - keys.min()) + 1, dtype=values.dtype)
    out[keys - keys.min()] = values
    return out


def joined_keys(tb):
    """(codes, domains, weights): for each row of store_sales that survives
    the date filter and the three joins, the eight keys as codes into their
    sorted distinct values, those values, and price and quantity."""
    ss, dd, st, it = (tb[t] for t in ("store_sales", "date_dim", "store",
                                      "item"))
    d0, i0, s0 = (int(k.min()) for k in (dd["d_date_sk"], it["i_item_sk"],
                                          st["s_store_sk"]))
    in_year = _dense(dd["d_date_sk"], (dd["d_month_seq"] >= DMS)
                     & (dd["d_month_seq"] <= DMS + 11))
    keep = in_year[ss["ss_sold_date_sk"] - d0]
    date = ss["ss_sold_date_sk"][keep] - d0
    item = ss["ss_item_sk"][keep] - i0
    store = ss["ss_store_sk"][keep] - s0
    by_row = {"d_year": _dense(dd["d_date_sk"], dd["d_year"])[date],
              "d_qoy": _dense(dd["d_date_sk"], dd["d_qoy"])[date],
              "d_moy": _dense(dd["d_date_sk"], dd["d_moy"])[date]}
    for c in ("i_category", "i_class", "i_brand", "i_product_name"):
        by_row[c] = (it["i_item_sk"] - i0, it[c], item)
    by_row["s_store_id"] = (st["s_store_sk"] - s0, st["s_store_id"], store)
    codes, domains = [], []
    for c in KEYS:
        if c in STRINGS:
            keys, values, rows = by_row[c]
            domain, code = np.unique(values.astype(str), return_inverse=True)
            codes.append(_dense(keys, code)[rows])
        else:
            domain, code = np.unique(by_row[c], return_inverse=True)
            codes.append(code)
        domains.append(domain)
    return (codes, domains, ss["ss_sales_price"][keep],
            ss["ss_quantity"][keep])


def rollup_sums(codes, domains, sales):
    """The nine prefix grouping sets: (codes (n, 8) with -1 where a key is
    rolled up, sums (n,)), the levels one after another."""
    all_codes, all_sums = [], []
    for level in range(len(codes), -1, -1):
        packed = np.zeros(len(sales), dtype=np.int64)
        for code, domain in zip(codes[:level], domains[:level]):
            packed = packed * len(domain) + code
        order = np.argsort(packed, kind="stable")
        groups, start = np.unique(packed[order], return_index=True)
        all_sums.append(np.add.reduceat(sales[order], start))
        cols = np.full((len(groups), len(codes)), -1, dtype=np.int64)
        for k in range(level - 1, -1, -1):
            groups, cols[:, k] = np.divmod(groups, len(domains[k]))
        all_codes.append(cols)
    return np.concatenate(all_codes), np.concatenate(all_sums)


def ranks(partition, sums):
    """rank() over (partition by ``partition`` order by ``sums`` desc)."""
    rk = np.zeros(len(sums), dtype=np.int64)
    for p in np.unique(partition):
        rows = np.flatnonzero(partition == p)
        ascending = np.sort(sums[rows])
        rk[rows] = 1 + len(rows) - np.searchsorted(ascending, sums[rows],
                                                   side="right")
    return rk


def reference(tb, dtype=np.int64):
    codes, domains, price, quantity = joined_keys(tb)
    sales = price.astype(dtype) * quantity.astype(dtype)
    key_codes, sums = rollup_sums(codes, domains, sales)
    rk = ranks(key_codes[:, 0], sums)
    top = np.flatnonzero(rk <= 100)
    # codes order as their values do, and -1 (NULL) sorts first
    order = np.lexsort([rk[top], sums[top]]
                       + [key_codes[top, k] for k in range(7, -1, -1)])
    rows = []
    for r in top[order][:100]:
        row = {}
        for k, c in enumerate(KEYS):
            code = key_codes[r, k]
            if c in STRINGS:
                row[c] = NULL_STRING if code < 0 else str(domains[k][code])
            else:
                row[c] = NULL_INT if code < 0 else int(domains[k][code])
        row["sumsales"] = sums[r].item()
        row["rk"] = int(rk[r])
        rows.append(row)
    return rows
