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
    def to_arrow(self):
        import pyarrow as pa
        from spark_rapids_tpu.runtime import movement as _MV
        n = self.num_rows
        names = (self.schema.names if self.schema is not None
                 else [f"c{i}" for i in range(self.num_cols)])
        # device bytes crossing to the host at this boundary: one call
        # feeds the per-node stats ledger (d2hBytes) AND the movement
        # ledger's d2h/pcie edge (runtime/movement.py)
        _MV.record_d2h(self.device_memory_size())
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
