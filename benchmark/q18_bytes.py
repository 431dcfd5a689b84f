"""Bytes that Q18's aggregate by ``l_orderkey`` must move, from the data
alone: the same work whatever implements it.

Worked out in NumPy over the Parquet files, never from a span: the decoded
``l_orderkey`` (int64) and ``l_quantity`` (float64) of every lineitem row
read once, and every ``l_orderkey`` group written once with its sum. At
SF 1 that is about 6.0 M x 16 B + 1.5 M x 16 B, some 120 MB.

The files are looked for where ``run.py`` has them made (``<workdir>/tpch``,
the workdir being ``benchmark_work`` or a directory below it) and taken only
if their footers count the rows that the run counted for the text.
"""

import glob
import os

import numpy as np
import pyarrow.parquet as pq

from benchmark import query_bytes

_WORK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark_work")
KEY_WIDTH, SUM_WIDTH = 8, 8
QUANTITY_WIDTH = 8


def orderkey_bytes(lineitem_dir: str) -> dict:
    """{"bytes": read + written, "rows": lineitem rows, "groups": n}."""
    keys = pq.read_table(lineitem_dir, columns=["l_orderkey"]).column(
        "l_orderkey").to_numpy()
    groups = len(np.unique(keys))
    return {"bytes": len(keys) * (KEY_WIDTH + QUANTITY_WIDTH)
            + groups * (KEY_WIDTH + SUM_WIDTH),
            "rows": len(keys), "groups": groups}


def data_paths(ctx):
    """{table: directory} of the data this run's query read, or None."""
    tables = ctx["config"]["tables"]
    asked = {q["input_rows"] for q in ctx["queries"].values()}
    for root in [os.path.join(_WORK, "tpch")] + sorted(
            glob.glob(os.path.join(_WORK, "*", "tpch"))):
        paths = {t: os.path.join(root, t) for t in tables}
        if not all(os.path.isdir(p) for p in paths.values()):
            continue
        footers = query_bytes.table_footers(paths)
        if {query_bytes.input_rows(q["text"], footers)
                for q in ctx["queries"].values()} == asked:
            return paths
    return None


def for_run(ctx):
    """``orderkey_bytes`` of the run's data, worked out once a run; None
    where the data is not found."""
    if "_q18_bytes" not in ctx:
        paths = data_paths(ctx)
        ctx["_q18_bytes"] = (None if paths is None
                             else orderkey_bytes(paths["lineitem"]))
    return ctx["_q18_bytes"]
