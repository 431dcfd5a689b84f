"""Generate (explode) exec — device expansion of list columns.

Reference: GpuGenerateExec.scala (explode/posexplode over cudf LIST columns,
493 LoC). TPU-native design: the list column arrives from the arrow bridge as
a ListVector (flat padded element vector on device + host row offsets,
columnar/vector.py); the exec computes the explode mapping as ONE jitted
gather program — per-output-row source indices come from a searchsorted over
the cumulative length prefix, so the MXU-facing data path never sees variable
shapes. Output capacity is the bucketed total element count (host-known from
offsets metadata, no device sync).

explode_outer keeps null/empty-list rows as one output row with a null
element (effective length max(len, 1); the element slot is invalid when the
position is past the true length).
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.vector import (ListVector, TpuColumnVector,
                                              bucket_capacity)
from spark_rapids_tpu.exec.base import TpuExec, acquire_semaphore
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime.tracing import trace_range
from spark_rapids_tpu.ops.windowing import cumsum


class GenerateExec(TpuExec):
    def __init__(self, generator_col: str, child: TpuExec, outer: bool = False,
                 element_type: T.DataType | None = None, pos: bool = False,
                 conf=None):
        super().__init__(child, conf=conf)
        self.generator_col = generator_col
        self.outer = outer
        self.pos = pos  # posexplode: also emit the element position
        self.element_type = element_type or T.LONG

    @property
    def output(self):
        fields = [f for f in self.child.output
                  if f.name != self.generator_col]
        if self.pos:
            fields.append(T.StructField("pos", T.INT, self.outer))
        fields.append(T.StructField("col", self.element_type, True))
        return T.StructType(fields)

    def execute_partition(self, split):
        def it():
            for batch in self.child.execute_partition(split):
                acquire_semaphore(self.metrics)
                with trace_range("GenerateExec", self._op_time):
                    out = self._generate(batch)
                if out is not None:
                    yield out
        return self.wrap_output(it())

    def _generate(self, batch: ColumnarBatch) -> ColumnarBatch | None:
        names = batch.schema.names
        gi = names.index(self.generator_col)
        lv = batch.columns[gi]
        assert isinstance(lv, ListVector), \
            "planner must feed GenerateExec a bridge-produced list column"
        n = batch.num_rows
        lengths = np.diff(lv.offsets)[:n]
        # outer: null and empty lists still emit one (null-element) row
        eff = np.maximum(lengths, 1) if self.outer else lengths
        total = int(eff.sum())
        if total == 0:
            return None
        out_cap = bucket_capacity(total)

        # device mapping: out position -> (source row, element index)
        eff_d = jnp.zeros((batch.capacity,), jnp.int32).at[:n].set(
            jnp.asarray(eff.astype(np.int32)))
        cum = cumsum(eff_d)
        pos = jnp.arange(out_cap, dtype=jnp.int32)
        src = jnp.searchsorted(cum, pos, side="right").astype(jnp.int32)
        src_c = jnp.clip(src, 0, batch.capacity - 1)
        base = jnp.where(src_c > 0, cum[jnp.maximum(src_c - 1, 0)], 0)
        elem_idx = pos - base
        live = pos < total

        # element column: gather from the flat vector
        off_d = jnp.asarray(lv.offsets[:n].astype(np.int64))
        off_pad = jnp.zeros((batch.capacity,), jnp.int64).at[:n].set(off_d)
        flat_pos = off_pad[src_c] + elem_idx
        flat_cap = lv.flat.capacity
        flat_pos_c = jnp.clip(flat_pos, 0, flat_cap - 1)
        real_elem = elem_idx < lv.data[src_c]  # past-length slots (outer pad)
        evals = lv.flat.data[flat_pos_c]
        evalid = lv.flat.validity[flat_pos_c] & real_elem & live
        evals = jnp.where(evalid, evals, jnp.asarray(
            lv.element_dtype.default_value(), evals.dtype))

        out_cols = []
        for name, col in zip(names, batch.columns):
            if name == self.generator_col:
                continue
            vals = col.data[src_c]
            valid = col.validity[src_c] & live
            out_cols.append(TpuColumnVector(col.dtype, vals, valid,
                                            col.dictionary))
        if self.pos:
            # posexplode_outer pads null/empty rows with a NULL position
            pos_valid = real_elem & live
            out_cols.append(TpuColumnVector(
                T.INT, jnp.where(pos_valid, elem_idx, 0), pos_valid))
        out_cols.append(TpuColumnVector(self.element_type, evals, evalid,
                                        lv.flat.dictionary))
        return ColumnarBatch(out_cols, total, self.output)

    def args_string(self):
        kind = "posexplode" if self.pos else "explode"
        return f"{kind}({self.generator_col}), outer={self.outer}"
