"""The four TPC-DS tables that query 67 names, as Parquet, made from a seed.

Row counts and key ranges are the specification's (v3.2) at SF 1:
store_sales 2,880,404, item 18,000, store 12, date_dim 73,049 (1900-01-02 to
2100-01-01, ``d_date_sk`` from 2,415,022, ``d_month_seq`` the months since
1900-01, so 1200 to 1211 is the year 2000); sales are dated 1998 to 2002. At
a fractional scale (the rehearsal's 0.01) the fact table and item shrink,
date_dim and store do not. Only the columns that the text names are made.

Money is int64 hundredths, the unscaled value of the specification's
DECIMAL(7,2): products and sums are then exact on every backend, as the
source's are. No foreign key is NULL, items and stores are drawn uniformly,
and the rows of store_sales are in no order: see the configuration's
``reduced`` and ``assumed``. Every value is the raw draw from the seed.

Imports nothing of the program; the file writer is the TPC-H generator's, so
every file records ``benchmark.decoded_bytes``.
"""

from __future__ import annotations

import datetime
import os
import shutil

import numpy as np
import pyarrow as pa

from benchmark.datagen.tpch import _write

FIRST_DATE = datetime.date(1900, 1, 2)
N_DATES = 73_049                  # to 2100-01-01
FIRST_DATE_SK = 2_415_022         # the Julian day number of FIRST_DATE
SALES_FROM = datetime.date(1998, 1, 1)
SALES_TO = datetime.date(2002, 12, 31)

CATEGORIES = ["Books", "Children", "Electronics", "Home", "Jewelry", "Men",
              "Music", "Shoes", "Sports", "Women"]
CLASSES_PER_CATEGORY = 10         # 100 classes
BRANDS_PER_CATEGORY = 100         # 1,000 brands
# dsdgen writes an item's number in these syllables, a decimal digit each
SYLLABLES = ["bar", "ought", "able", "pri", "pres", "ese", "anti", "cally",
             "ation", "eing"]


def product_name(k: int) -> str:
    """dsdgen's i_product_name: the key's decimal digits as syllables, the
    lowest first; unique a key."""
    out = ""
    while True:
        out += SYLLABLES[k % 10]
        k //= 10
        if k == 0:
            return out


def business_key(k: int) -> str:
    """dsdgen's 16-character business key (s_store_id): the key in base 16
    as the letters A to P, the lowest digit first, padded with A."""
    out = ""
    for _ in range(8):
        out += chr(ord("A") + k % 16)
        k //= 16
    return "AAAAAAAA" + out


def date_dim() -> pa.Table:
    ordinal = FIRST_DATE.toordinal() + np.arange(N_DATES)
    dates = [datetime.date.fromordinal(int(o)) for o in ordinal]
    year = np.array([d.year for d in dates], dtype=np.int32)
    moy = np.array([d.month for d in dates], dtype=np.int32)
    return pa.table({
        "d_date_sk": pa.array(FIRST_DATE_SK + np.arange(N_DATES,
                                                        dtype=np.int64)),
        "d_month_seq": pa.array(((year - 1900) * 12 + moy - 1)
                                .astype(np.int32)),
        "d_year": pa.array(year),
        "d_moy": pa.array(moy),
        "d_qoy": pa.array(((moy - 1) // 3 + 1).astype(np.int32)),
    })


def tables(sf: float, seed: int, files: dict) -> dict:
    """{table: (pa.Table, files)} for the tables ``files`` names."""
    rng = np.random.default_rng(seed)
    n_sales = max(int(2_880_404 * sf), 1)
    n_item = max(int(18_000 * sf), 1)
    n_store = 12
    out = {"date_dim": date_dim()}
    out["store"] = pa.table({
        "s_store_sk": pa.array(np.arange(1, n_store + 1, dtype=np.int64)),
        "s_store_id": pa.array([business_key(k)
                                for k in range(1, n_store + 1)]),
    })
    category = rng.integers(0, len(CATEGORIES), n_item)
    klass = rng.integers(0, CLASSES_PER_CATEGORY, n_item)
    brand = rng.integers(0, BRANDS_PER_CATEGORY, n_item)
    names = np.array([c.lower() for c in CATEGORIES])[category]
    out["item"] = pa.table({
        "i_item_sk": pa.array(np.arange(1, n_item + 1, dtype=np.int64)),
        "i_category": pa.array(np.array(CATEGORIES)[category]),
        "i_class": pa.array([f"{n} class {c}"
                             for n, c in zip(names, klass)]),
        "i_brand": pa.array([f"{n} brand #{b:02d}"
                             for n, b in zip(names, brand)]),
        "i_product_name": pa.array([product_name(k)
                                    for k in range(1, n_item + 1)]),
    })
    first = FIRST_DATE_SK + (SALES_FROM - FIRST_DATE).days
    last = FIRST_DATE_SK + (SALES_TO - FIRST_DATE).days
    out["store_sales"] = pa.table({
        "ss_sold_date_sk": pa.array(
            rng.integers(first, last + 1, n_sales).astype(np.int64)),
        "ss_item_sk": pa.array(
            rng.integers(1, n_item + 1, n_sales).astype(np.int64)),
        "ss_store_sk": pa.array(
            rng.integers(1, n_store + 1, n_sales).astype(np.int64)),
        "ss_quantity": pa.array(
            rng.integers(1, 101, n_sales).astype(np.int32)),
        # DECIMAL(7,2) as its unscaled value: 0.00 to 200.00
        "ss_sales_price": pa.array(
            rng.integers(0, 20_001, n_sales).astype(np.int64)),
    })
    unknown = set(files) - set(out)
    if unknown:
        raise ValueError(f"the generator makes no table {sorted(unknown)}")
    return {name: (out[name], int(n)) for name, n in files.items()}


def generate(sf: float, seed: int, files: dict, workdir: str) -> dict:
    """Write the tables anew under ``workdir/tpcds/``, in place of whatever
    data set is there, and return {table: directory}."""
    root = os.path.join(workdir, "tpcds")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    for name, (table, nfiles) in tables(sf, seed, files).items():
        _write(root, name, table, nfiles)
    return {name: os.path.join(root, name) for name in files}
