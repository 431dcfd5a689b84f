"""Rows and bytes that a query's text asks for, from the text and the data
alone: the same work whatever implements it.

A table is named by the text when its name stands in it as a word, and a
column likewise. Rows are the rows of the named tables (Parquet footers).
Bytes are the decoded bytes of the named columns: values times the width of
the physical type, and for strings the characters, which the generator
records in each file's key-value metadata.
"""

from __future__ import annotations

import glob
import json
import os
import re

import pyarrow.parquet as pq

_WIDTH = {"BOOLEAN": 1, "INT32": 4, "INT64": 8, "INT96": 12, "FLOAT": 4,
          "DOUBLE": 8}
DECODED_KEY = b"benchmark.decoded_bytes"


def words(text: str) -> set:
    return set(re.findall(r"[A-Za-z_][A-Za-z0-9_]*", text.lower()))


def table_footers(paths: dict) -> dict:
    """{table: [FileMetaData]} of every Parquet part of every table."""
    return {t: [pq.read_metadata(f)
                for f in sorted(glob.glob(os.path.join(d, "*.parquet")))]
            for t, d in paths.items()}


def named_tables(text: str, footers: dict) -> list:
    w = words(text)
    return [t for t in footers if t.lower() in w]


def input_rows(text: str, footers: dict) -> int:
    return sum(md.num_rows for t in named_tables(text, footers)
               for md in footers[t])


def column_bytes(text: str, footers: dict) -> int:
    w = words(text)
    total = 0
    for t in named_tables(text, footers):
        for md in footers[t]:
            recorded = json.loads((md.metadata or {}).get(DECODED_KEY, "{}"))
            for rg in range(md.num_row_groups):
                group = md.row_group(rg)
                for c in range(group.num_columns):
                    col = group.column(c)
                    if col.path_in_schema.lower() not in w:
                        continue
                    if col.physical_type in _WIDTH:
                        total += col.num_values * _WIDTH[col.physical_type]
                    elif rg == 0:
                        # recorded once a file, for all its row groups
                        if col.path_in_schema not in recorded:
                            raise ValueError(
                                f"{t}.{col.path_in_schema}: a "
                                f"{col.physical_type} column with no "
                                "decoded size in the file's metadata")
                        total += recorded[col.path_in_schema]
    return total
