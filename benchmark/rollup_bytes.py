"""Bytes that query 67's ROLLUP aggregate and its window must move, from the
data alone: the same work whatever implements it.

Worked out in NumPy over the Parquet files (the reference's own join and
grouping sets, ``benchmark/reference/q67.py``), never from a span:

- ``rollup``: the rows of store_sales that survive the date filter and the
  three joins, times the 9 grouping sets, times the decoded width of a row
  (the eight keys, strings by their characters, and the 8-byte product),
  each read once; plus every group written once (its keys that are not
  rolled up, and the 8-byte sum).
- ``window``: every group read once and written once with its 4-byte rank.

The files are looked for where ``run.py`` has them made (``<workdir>/tpcds``,
the workdir being ``benchmark_work`` or a directory below it) and taken only
if their footers count the rows that the run counted for the text.
"""

import glob
import os

import numpy as np

from benchmark import query_bytes
from benchmark.reference import q67

_WORK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark_work")
INT_WIDTH, SUM_WIDTH, RANK_WIDTH = 4, 8, 4
GROUPING_SETS = len(q67.KEYS) + 1


def key_widths(domains) -> list:
    """Decoded bytes of each value of each key's domain."""
    return [np.char.str_len(d) if d.dtype.kind == "U"
            else np.full(len(d), INT_WIDTH) for d in domains]


def step_bytes(tb) -> dict:
    """{"rollup": bytes, "window": bytes, "joined_rows": n, "groups": n}."""
    codes, domains, price, quantity = q67.joined_keys(tb)
    widths = key_widths(domains)
    row_bytes = sum(int(w[c].sum()) for w, c in zip(widths, codes))
    row_bytes += SUM_WIDTH * len(price)
    key_codes, _sums = q67.rollup_sums(
        codes, domains, price.astype(np.int64) * quantity)
    group_bytes = SUM_WIDTH * len(key_codes)
    for k, w in enumerate(widths):
        present = key_codes[:, k] >= 0
        group_bytes += int(w[key_codes[present, k]].sum())
    return {"rollup": GROUPING_SETS * row_bytes + group_bytes,
            "window": 2 * group_bytes + RANK_WIDTH * len(key_codes),
            "joined_rows": len(price), "groups": len(key_codes)}


def data_paths(ctx):
    """{table: directory} of the data this run's query read, or None."""
    tables = ctx["config"]["tables"]
    asked = {q["input_rows"] for q in ctx["queries"].values()}
    for root in [os.path.join(_WORK, "tpcds")] + sorted(
            glob.glob(os.path.join(_WORK, "*", "tpcds"))):
        paths = {t: os.path.join(root, t) for t in tables}
        if not all(os.path.isdir(p) for p in paths.values()):
            continue
        footers = query_bytes.table_footers(paths)
        if {query_bytes.input_rows(q["text"], footers)
                for q in ctx["queries"].values()} == asked:
            return paths
    return None


def for_run(ctx):
    """``step_bytes`` of the run's data, worked out once a run; None where
    the data is not found."""
    if "_rollup_bytes" not in ctx:
        from benchmark import run
        paths = data_paths(ctx)
        ctx["_rollup_bytes"] = None if paths is None else step_bytes(
            run.read_tables(paths, q67.COLUMNS))
    return ctx["_rollup_bytes"]
