"""Device-side partitioning — hash / range / round-robin / single.

Reference (SURVEY.md component #28): GpuHashPartitioning.scala (cudf murmur3 matching
Spark's Murmur3Hash with seed 42), GpuRangePartitioner.scala (host reservoir sample +
sort to pick bounds), GpuRoundRobinPartitioning.scala, GpuSinglePartitioning.scala,
GpuPartitioning.scala:169 (slice device batch into contiguous per-partition pieces).

TPU shape: partition ids are computed on device in one fused program, rows are
stable-sorted by partition id (one XLA sort), and per-partition counts come back in a
single device→host sync at the exchange boundary — the same one sync the reference
needs to build its slice offsets.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.vector import TpuColumnVector, bucket_capacity
from spark_rapids_tpu.expr.core import Col, EvalContext, bind_references
from spark_rapids_tpu.ops import hashing as H
from spark_rapids_tpu.ops.filtering import gather_cols
from spark_rapids_tpu.ops.sorting import SortOrder, _key_arrays

SPARK_HASH_SEED = 42  # HashPartitioning's Murmur3Hash seed


def murmur3_row_hash(cols: list[Col], capacity: int, seed: int = SPARK_HASH_SEED,
                     dict_words: dict | None = None):
    """Per-row Spark Murmur3Hash over `cols`, chaining each column's hash into the
    next column's seed; null cells leave the running hash unchanged (Spark
    HashExpression.eval semantics, mirrored by the reference's cudf murmur3)."""
    h = jnp.full((capacity,), jnp.int32(seed))
    for ci, c in enumerate(cols):
        dt = c.dtype
        if isinstance(dt, T.StringType):
            words, lens = dict_words[ci]
            row_words = words[c.values]      # (capacity, W)
            row_lens = lens[c.values]
            nh = H.hash_string_words(row_words, row_lens, h)
        elif isinstance(dt, (T.LongType, T.TimestampType)):
            nh = H.hash_long(c.values, h)
        elif isinstance(dt, T.DecimalType):
            nh = H.hash_long(c.values.astype(jnp.int64), h)
        elif isinstance(dt, T.DoubleType):
            nh = H.hash_double(c.values, h)
        elif isinstance(dt, T.FloatType):
            nh = H.hash_float(c.values, h)
        elif isinstance(dt, T.BooleanType):
            nh = H.hash_int(c.values.astype(jnp.int32), h)
        else:  # byte/short/int/date widen to int32
            nh = H.hash_int(c.values.astype(jnp.int32), h)
        h = jnp.where(c.validity, nh, h)
    return h


def range_part_ids(keys: list[Col], bounds: list[Col], orders, capacity: int):
    """Partition id per row given `n-1` sorted bound rows: number of bounds the
    row compares strictly greater than (lexicographic, Spark null/NaN ordering
    via _key_arrays). Shared by the host RangePartitioner and the mesh exchange
    (the mesh path passes keys/bounds already in one global dictionary space)."""
    keys = list(keys)
    bounds = list(bounds)
    # align string dictionaries between keys and bounds so codes compare
    for i, (k, b) in enumerate(zip(keys, bounds)):
        if k.is_string and k.dictionary is not b.dictionary:
            from spark_rapids_tpu.ops.strings import union_dictionaries
            k2, b2 = union_dictionaries(k, b)
            keys[i], bounds[i] = k2, b2
    nb = bounds[0].values.shape[0]
    row_keys = [ka for k, o in zip(keys, orders)
                for ka in _key_arrays(k, o)]
    bound_keys = [ka for b, o in zip(bounds, orders)
                  for ka in _key_arrays(b, o)]
    ids = jnp.zeros((capacity,), jnp.int32)
    for j in range(nb):
        gt = jnp.zeros((capacity,), jnp.bool_)
        tie = jnp.ones((capacity,), jnp.bool_)
        for rk, bk in zip(row_keys, bound_keys):
            bj = bk[j]
            gt = gt | (tie & (rk > bj))
            tie = tie & (rk == bj)
        ids = ids + gt.astype(jnp.int32)
    return ids


def slice_into_partitions(batch: ColumnarBatch, part_ids, num_partitions: int):
    """Stable-sort rows by partition id and slice into per-partition batches.
    Returns list[(part, ColumnarBatch)] for non-empty partitions
    (reference GpuPartitioning.sliceInternalOnGpu)."""
    cap = batch.capacity
    n = batch.num_rows
    live = jnp.arange(cap, dtype=jnp.int32) < n
    ids = jnp.where(live, part_ids.astype(jnp.int32), jnp.int32(num_partitions))
    # radix-rank kernel when routed, stable argsort otherwise; padding rows
    # sink to the end via the sentinel id either way
    from spark_rapids_tpu.ops.sorting import partition_permutation
    perm = partition_permutation(part_ids, num_partitions, n, cap)
    cols = [Col.from_vector(c) for c in batch.columns]
    sorted_cols = gather_cols(cols, perm, live[perm])
    counts = np.asarray(jnp.bincount(ids, length=num_partitions + 1))[:num_partitions]
    offsets = np.concatenate([[0], np.cumsum(counts)])
    out = []
    for p in range(num_partitions):
        cnt = int(counts[p])
        if cnt == 0:
            continue
        lo = int(offsets[p])
        pcap = bucket_capacity(cnt)
        pcols = []
        for c in sorted_cols:
            vals = c.values[lo:lo + pcap]
            valid = c.validity[lo:lo + pcap]
            default = jnp.asarray(c.dtype.default_value(), dtype=vals.dtype)
            if vals.shape[0] < pcap:  # partition tail ran past the padded capacity
                pad = pcap - vals.shape[0]
                vals = jnp.concatenate([vals, jnp.full((pad,), default)])
                valid = jnp.concatenate([valid, jnp.zeros((pad,), jnp.bool_)])
            idx = jnp.arange(pcap) < cnt
            valid = valid & idx
            pcols.append(TpuColumnVector(c.dtype, jnp.where(valid, vals, default),
                                         valid, c.dictionary))
        out.append((p, ColumnarBatch(pcols, cnt, batch.schema)))
    return out


class Partitioner:
    """Base: `partition(batch, split) -> list[(part_id, ColumnarBatch)]`."""

    num_partitions: int

    def bind(self, schema):
        return self

    def partition(self, batch: ColumnarBatch, split: int = 0):
        raise NotImplementedError


class SinglePartitioner(Partitioner):
    """Reference GpuSinglePartitioning.scala."""

    num_partitions = 1

    def partition(self, batch, split=0):
        return [(0, batch)] if batch.num_rows else []


class HashPartitioner(Partitioner):
    """Reference GpuHashPartitioning.scala — bit-exact with Spark's
    HashPartitioning(pmod(murmur3(keys, 42), n))."""

    def __init__(self, key_exprs: list, num_partitions: int):
        self.key_exprs = list(key_exprs)
        self.num_partitions = num_partitions

    def bind(self, schema):
        self.key_exprs = [bind_references(e, schema) for e in self.key_exprs]
        return self

    def part_ids(self, batch: ColumnarBatch):
        from spark_rapids_tpu.expr.core import BoundReference
        ctx = EvalContext.from_batch(batch)
        keys = [e.eval(ctx) for e in self.key_exprs]
        dict_words = {}
        for i, (e, k) in enumerate(zip(self.key_exprs, keys)):
            if not k.is_string:
                continue
            if isinstance(e, BoundReference):
                # reuse the batch vector's cached dictionary packing instead of
                # repacking the dictionary for every batch
                dict_words[i] = batch.column(e.ordinal).dictionary_words()
            else:
                dict_words[i] = k.to_vector().dictionary_words()
        h = murmur3_row_hash(keys, batch.capacity, dict_words=dict_words)
        return H.pmod(h, self.num_partitions)

    def partition(self, batch, split=0):
        return slice_into_partitions(batch, self.part_ids(batch), self.num_partitions)


class RoundRobinPartitioner(Partitioner):
    """Reference GpuRoundRobinPartitioning.scala: rows dealt onto partitions in order,
    starting at a position derived from the input split so outputs stay balanced."""

    def __init__(self, num_partitions: int):
        self.num_partitions = num_partitions

    def partition(self, batch, split=0):
        cap = batch.capacity
        start = split % self.num_partitions
        ids = (jnp.arange(cap, dtype=jnp.int32) + start) % self.num_partitions
        return slice_into_partitions(batch, ids, self.num_partitions)


class RangePartitioner(Partitioner):
    """Reference GpuRangePartitioner.scala + GpuRangePartitioning.scala: sample rows
    (reservoir, host), sort the sample to choose `n-1` bounds, then place each row by
    lexicographic comparison against the bounds on device."""

    def __init__(self, sort_exprs: list, orders: list, num_partitions: int):
        self.sort_exprs = list(sort_exprs)
        self.orders = list(orders)
        self.num_partitions = num_partitions
        self._bounds: list[ColumnarBatch] | None = None

    def bind(self, schema):
        self.sort_exprs = [bind_references(e, schema) for e in self.sort_exprs]
        return self

    def set_bounds_from_sample(self, sample_batches: list[ColumnarBatch]):
        """Compute bounds from sampled batches (driver-side, reference
        GpuRangePartitioner.createRangeBounds)."""
        from spark_rapids_tpu.ops.concat import concat_batches
        from spark_rapids_tpu.ops.sorting import sort_permutation
        sample = concat_batches(sample_batches)
        ctx = EvalContext.from_batch(sample)
        keys = [e.eval(ctx) for e in self.sort_exprs]
        perm = sort_permutation(keys, self.orders, sample.num_rows, sample.capacity)
        n = sample.num_rows
        live = jnp.arange(sample.capacity, dtype=jnp.int32) < n
        skeys = gather_cols(keys, perm, live[perm])
        # n-1 evenly spaced bound rows
        nb = self.num_partitions - 1
        if n == 0 or nb == 0:
            self._bounds = None
            return
        pos = np.minimum(((np.arange(1, nb + 1) * n) // self.num_partitions),
                         max(n - 1, 0)).astype(np.int32)
        self._bounds = [
            Col(c.values[jnp.asarray(pos)], c.validity[jnp.asarray(pos)], c.dtype,
                c.dictionary) for c in skeys]

    def part_ids(self, batch: ColumnarBatch):
        if self._bounds is None:
            return jnp.zeros((batch.capacity,), jnp.int32)
        ctx = EvalContext.from_batch(batch)
        keys = [e.eval(ctx) for e in self.sort_exprs]
        return range_part_ids(keys, self._bounds, self.orders, batch.capacity)

    def partition(self, batch, split=0):
        return slice_into_partitions(batch, self.part_ids(batch), self.num_partitions)
