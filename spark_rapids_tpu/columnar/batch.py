"""ColumnarBatch — a set of device columns plus a row count.

Reference: Spark's ColumnarBatch wrapped by GpuColumnVector.from(Table)
(GpuColumnVector.java). TPU twist: ``num_rows`` may be a *device scalar* while a fused
XLA stage is in flight (e.g. a filter's surviving-row count), and is only synced to a
host int at stage boundaries — cudf syncs after every kernel, we sync once per stage.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.vector import TpuColumnVector, bucket_capacity
from spark_rapids_tpu.runtime import tracing


class ColumnarBatch:
    __slots__ = ("columns", "_num_rows", "schema", "metadata")

    def __init__(self, columns, num_rows, schema: T.StructType | None = None,
                 metadata: dict | None = None):
        self.columns = list(columns)
        self._num_rows = num_rows
        self.schema = schema
        # scan provenance (input file path/offsets) for the metadata
        # expressions (input_file_name family); None off the scan path
        self.metadata = metadata
        if self.columns:
            cap = self.columns[0].capacity
            assert all(c.capacity == cap for c in self.columns), \
                "all columns in a batch must share one padded capacity"

    @property
    def num_rows(self) -> int:
        """Host row count; forces a device sync if the count is still a device scalar."""
        if not isinstance(self._num_rows, int):
            with tracing.span("sync.count") as sp:
                self._num_rows = int(self._num_rows)
                if sp and self.columns:
                    sp.set(rows=self._num_rows,
                           capacity=self.columns[0].capacity)
        return self._num_rows

    @property
    def lazy_num_rows(self):
        """Row count without forcing a sync (may be a jax scalar)."""
        return self._num_rows

    @property
    def num_cols(self) -> int:
        return len(self.columns)

    @property
    def capacity(self) -> int:
        return self.columns[0].capacity if self.columns else bucket_capacity(self.num_rows)

    def column(self, i: int) -> TpuColumnVector:
        return self.columns[i]

    def device_memory_size(self) -> int:
        return sum(c.device_memory_size() for c in self.columns)

    def with_columns(self, columns, schema=None):
        return ColumnarBatch(columns, self._num_rows, schema or self.schema,
                             metadata=self.metadata)

    # -- host interop -------------------------------------------------------
    def to_arrow(self, site: str = "batch.to_arrow"):
        import pyarrow as pa
        from spark_rapids_tpu.runtime import movement as _MV
        n = self.num_rows
        names = (self.schema.names if self.schema is not None
                 else [f"c{i}" for i in range(self.num_cols)])
        # device bytes crossing to the host at this boundary: one call
        # feeds the per-node stats ledger (d2hBytes) AND the movement
        # ledger's d2h/pcie edge (runtime/movement.py)
        _MV.record_d2h(self.device_memory_size(), site=site)
        # from_arrays, not a dict: Spark allows duplicate output column names
        return pa.Table.from_arrays(
            [col.to_arrow(n) for col in self.columns], names=list(names))

    @staticmethod
    def from_arrow(table, schema: T.StructType | None = None) -> "ColumnarBatch":
        from spark_rapids_tpu.columnar import arrow as ai
        from spark_rapids_tpu.runtime import movement as _MV
        batch = ai.table_to_device(table, schema=schema)
        _MV.record_h2d(batch.device_memory_size())
        return batch

    @staticmethod
    def empty(schema: T.StructType) -> "ColumnarBatch":
        cap = bucket_capacity(0)
        cols = [TpuColumnVector.all_null(f.data_type, cap) for f in schema]
        return ColumnarBatch(cols, 0, schema)

    def __repr__(self):
        n = self._num_rows if isinstance(self._num_rows, int) else "<device>"
        return f"ColumnarBatch(rows={n}, cols={self.num_cols}, cap={self.capacity})"


# -- placement (a mesh partition lives on its own chip) ------------------------

def _column_arrays(col):
    """Every device array of a column, nested vectors included."""
    flat = getattr(col, "flat", None)
    out = [] if flat is None else _column_arrays(flat)
    return out + [a for a in (col.data, col.validity,
                              *(col._dict_device or ()))
                  if hasattr(a, "devices")]


def batch_devices(batch: ColumnarBatch) -> set:
    """The devices that hold the batch's columns: one for a batch of a mesh
    partition, every device of the mesh for one that XLA left replicated."""
    out: set = set()
    for col in batch.columns:
        for a in _column_arrays(col):
            out |= a.devices()
    return out


def batch_device(batch: ColumnarBatch):
    """The device of the batch's first column (None for a batch of none):
    where a batch of one mesh partition lies."""
    for col in batch.columns:
        for a in _column_arrays(col):
            return next(iter(a.devices()))
    return None


def batch_to_device(batch: ColumnarBatch, device) -> ColumnarBatch:
    """`batch` with every array on `device`: the explicit move where
    partitions of several chips come together (a gather to one partition, a
    broadcast build). A batch that is there already is returned as it is, so
    on one device this is nothing."""
    import copy
    import jax
    if device is None or batch_devices(batch) <= {device}:
        return batch

    def move_col(col):
        moved = copy.copy(col)
        moved.data = jax.device_put(col.data, device)
        moved.validity = jax.device_put(col.validity, device)
        moved._dict_device = None   # packed again on `device` when asked for
        if getattr(col, "flat", None) is not None:
            moved.flat = move_col(col.flat)
        return moved

    n = batch.lazy_num_rows
    if hasattr(n, "devices"):
        n = jax.device_put(n, device)
    return ColumnarBatch([move_col(c) for c in batch.columns], n,
                         batch.schema, metadata=batch.metadata)


def on_one_device(batches):
    """The batches as they come, each on the device of the first: what
    gathers the partitions of a mesh exchange (one a chip) into one."""
    target = None
    for b in batches:
        if target is None:
            target = batch_device(b)
        yield batch_to_device(b, target)
