"""TPC-H tables as ``datagen/tpch.py`` makes them, with ``c_name``, which
Q18 returns and groups by.

``tables`` of ``datagen/tpch.py`` runs with the same seed, so every column it
makes is byte-identical; ``c_name`` is derived from the key and draws nothing:
``Customer#`` and ``c_custkey`` in nine digits, zero-padded (TPC-H clause
4.2.3). Imports nothing of the program.
"""

from __future__ import annotations

import os
import shutil

import numpy as np
import pyarrow as pa

from benchmark.datagen import tpch


def c_name(custkey: np.ndarray) -> pa.Array:
    digits = np.char.zfill(custkey.astype(str), 9)
    return pa.array(np.char.add("Customer#", digits))


def tables(sf: float, seed: int, files: dict) -> dict:
    out = tpch.tables(sf, seed, files)
    if "customer" in out:
        table, n = out["customer"]
        keys = table.column("c_custkey").to_numpy()
        out["customer"] = (table.append_column("c_name", c_name(keys)), n)
    return out


def generate(sf: float, seed: int, files: dict, workdir: str) -> dict:
    """Write the tables anew under ``workdir/tpch/``, in place of whatever
    data set is there, and return {table: directory}."""
    root = os.path.join(workdir, "tpch")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    for name, (table, nfiles) in tables(sf, seed, files).items():
        tpch._write(root, name, table, nfiles)
    return {name: os.path.join(root, name) for name in files}
