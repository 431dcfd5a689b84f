"""Multi-key sort with Spark ordering semantics.

Reference: GpuSortExec.scala:56 + SortUtils.scala over cudf Table.orderBy. Spark
ordering rules implemented here (the reference encodes the same in cudf flags):
- per-key ASC/DESC with explicit NULLS FIRST/LAST;
- floats: NaN is greater than every value (incl. +inf), NaN == NaN, -0.0 == 0.0;
- strings sort by dictionary code (dictionary is sorted, so code order == UTF-8
  lexicographic — actually python str order; matches Spark's UTF8String binary order
  for the ASCII range).

TPU-first notes: lax.sort is a single fused XLA sort over multiple key operands; no
f64→i64 bitcast (unsupported under the TPU x64 rewrite), so float keys stay float with
NaN lifted into a separate int8 key; padding rows carry a leading pad-rank key so they
always sink to the end regardless of key direction.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.core import Col


@dataclasses.dataclass(frozen=True)
class SortOrder:
    ascending: bool = True
    nulls_first: bool = None  # default: first when asc, last when desc (Spark)

    @property
    def resolved_nulls_first(self):
        return self.ascending if self.nulls_first is None else self.nulls_first


def _key_arrays(c: Col, order: SortOrder):
    """Key operands for one sort column, in significance order."""
    keys = []
    nf = order.resolved_nulls_first
    null_rank = jnp.where(c.validity, jnp.int8(1 if nf else 0),
                          jnp.int8(0 if nf else 1))
    keys.append(null_rank)
    vals = c.values
    if isinstance(c.dtype, T.FractionalType):
        nan = jnp.isnan(vals)
        # NaN largest: rank 1 after all finite for asc; first (rank 0) for desc
        nan_rank = jnp.where(nan, jnp.int8(1), jnp.int8(0))
        if not order.ascending:
            nan_rank = jnp.int8(1) - nan_rank
        keys.append(nan_rank)
        vals = jnp.where(nan, jnp.zeros_like(vals), vals)
        vals = jnp.where(vals == 0, jnp.zeros_like(vals), vals)  # -0.0 → 0.0
        if not order.ascending:
            vals = -vals
    elif isinstance(c.dtype, T.BooleanType):
        v8 = vals.astype(jnp.int8)
        vals = v8 if order.ascending else (jnp.int8(1) - v8)
    else:
        if not order.ascending:
            vals = ~vals  # order-reversing, overflow-free for ints
    keys.append(vals)
    return keys


def _key_bits(c: Col) -> int | None:
    """Static bit-width of one key column's order-preserving unsigned image,
    or None if it cannot be packed (wide ints, floats)."""
    if c.is_string and c.dictionary is not None:
        d = max(len(c.dictionary), 1)
        return max(d - 1, 1).bit_length()
    if isinstance(c.dtype, T.BooleanType):
        return 1
    if isinstance(c.dtype, T.IntegralType) or isinstance(c.dtype, T.DateType):
        w = jnp.iinfo(c.values.dtype).bits
        return w + 1 if w <= 32 else None  # +1: bias to unsigned
    return None


@jax.named_scope("packed_key")
def _packed_key(key_cols, orders, num_rows, capacity: int,
                range_hint=None):
    """Pack (pad-rank, per-key null-rank + value image, row index) into ONE
    int64 sort operand. lax.sort cost grows steeply with operand count
    (~4x from 1 to 4 operands at 256k rows on both CPU and TPU backends), so
    a single packed operand with the row index in the low bits — uniqueness
    makes stability free — is the fast path whenever the static widths fit.
    Returns None when the keys cannot be packed order-faithfully.

    `range_hint=(vmin, vmax_minus_vmin_fits)` (single int key only) lets a
    caller that already paid a range reduction + host sync (the join-build
    pattern, exec/aggregate.py) pack a statically-too-wide int64 key as
    `value - vmin`: vmin rides in as a TRACED scalar so one compiled
    program serves every in-range batch."""
    iota_bits = max((capacity - 1).bit_length(), 1)
    if (range_hint is not None and len(key_cols) == 1
            and isinstance(key_cols[0].dtype,
                           (T.IntegralType, T.DateType, T.TimestampType))
            and not isinstance(key_cols[0].dtype, T.BooleanType)):
        vmin, fits = range_hint
        if fits:
            c, o = key_cols[0], orders[0]
            w = 62 - iota_bits - 1      # value bits left beside the ranks
            nf = o.resolved_nulls_first
            acc = (jnp.arange(capacity, dtype=jnp.int32)
                   >= num_rows).astype(jnp.int64)
            null_rank = jnp.where(c.validity, jnp.int64(1 if nf else 0),
                                  jnp.int64(0 if nf else 1))
            acc = (acc << 1) | null_rank
            u = c.values.astype(jnp.int64) - vmin
            u = jnp.clip(u, 0, (1 << w) - 1)
            u = jnp.where(c.validity, u, 0)
            if not o.ascending:
                u = ((1 << w) - 1) - u
            acc = (acc << w) | u
            return ((acc << iota_bits)
                    | jnp.arange(capacity, dtype=jnp.int64)), iota_bits
    total = 1 + iota_bits  # pad rank + tiebreaker
    widths = []
    for c in key_cols:
        w = _key_bits(c)
        if w is None:
            return None
        widths.append(w)
        total += 1 + w  # null rank + value image
    if total > 63:
        return None
    acc = (jnp.arange(capacity, dtype=jnp.int32) >= num_rows).astype(jnp.int64)
    for c, o, w in zip(key_cols, orders, widths):
        nf = o.resolved_nulls_first
        # nulls-first → nulls rank 0 (before valid rows), else after
        null_rank = jnp.where(c.validity, jnp.int64(1 if nf else 0),
                              jnp.int64(0 if nf else 1))
        acc = (acc << 1) | null_rank
        if isinstance(c.dtype, T.BooleanType):
            u = c.values.astype(jnp.int64)
        elif c.is_string:
            u = c.values.astype(jnp.int64)
        else:
            u = c.values.astype(jnp.int64) + (1 << (w - 1))
        u = jnp.clip(u, 0, (1 << w) - 1)
        u = jnp.where(c.validity, u, 0)
        if not o.ascending:
            u = ((1 << w) - 1) - u
        acc = (acc << w) | u
    return (acc << iota_bits) | jnp.arange(capacity, dtype=jnp.int64), iota_bits


@jax.named_scope("wide_single_key")
def _wide_single_key(key_cols, orders, num_rows, capacity: int):
    """Single int key too wide for the packed operand (int64/timestamp):
    TWO int64 operands instead of the 4-operand stable comparator sort
    (~2.6x cheaper at 1M rows). Operand 1 is the order image with null/pad
    rows forced to the extremes; operand 2 carries (rank, row-index) so
    rank ties between a real extreme value, a null, and padding resolve
    correctly and the unique index makes stability free."""
    if len(key_cols) != 1:
        return None
    c, o = key_cols[0], orders[0]
    if (not isinstance(c.dtype, (T.IntegralType, T.DateType,
                                 T.TimestampType))
            or isinstance(c.dtype, T.BooleanType)):
        return None
    if _key_bits(c) is not None:
        return None   # narrow enough for the packed path
    big = jnp.iinfo(jnp.int64).max
    small = jnp.iinfo(jnp.int64).min
    v = c.values.astype(jnp.int64)
    if not o.ascending:
        v = ~v        # order-reversing, overflow-free
    nf = o.resolved_nulls_first
    v = jnp.where(c.validity, v, small if nf else big)
    live = jnp.arange(capacity, dtype=jnp.int32) < num_rows
    v = jnp.where(live, v, big)
    # rank: valid 1; nulls 0 (first) or 2 (last); padding 3 — dominates
    # operand-1 ties against real extreme values
    rank = jnp.where(c.validity, jnp.int64(1),
                     jnp.int64(0 if nf else 2))
    rank = jnp.where(live, rank, jnp.int64(3))
    iota_bits = max((capacity - 1).bit_length(), 1)
    op2 = (rank << iota_bits) | jnp.arange(capacity, dtype=jnp.int64)
    _, s2 = lax.sort((v, op2), num_keys=2, is_stable=False)
    return (s2 & ((1 << iota_bits) - 1)).astype(jnp.int32)


@jax.named_scope("sort_permutation")
def sort_permutation(key_cols, orders, num_rows, capacity: int,
                     range_hint=None):
    """Stable permutation sorting live rows by keys; padding sinks to the end."""
    packed = _packed_key(key_cols, orders, num_rows, capacity,
                         range_hint=range_hint)
    if packed is not None:
        key, iota_bits = packed
        (s,) = lax.sort((key,), num_keys=1, is_stable=False)
        return (s & ((1 << iota_bits) - 1)).astype(jnp.int32)
    wide = _wide_single_key(key_cols, orders, num_rows, capacity)
    if wide is not None:
        return wide
    pad_rank = (jnp.arange(capacity, dtype=jnp.int32) >= num_rows).astype(jnp.int8)
    operands = [pad_rank]
    for c, o in zip(key_cols, orders):
        operands.extend(_key_arrays(c, o))
    iota = jnp.arange(capacity, dtype=jnp.int32)
    # the row index as last key = the stable order, without the second
    # index operand a stable sort would add
    res = lax.sort(tuple(operands) + (iota,), num_keys=len(operands) + 1,
                   is_stable=False)
    return res[-1]


@jax.named_scope("sort_cols")
def sort_cols(cols, key_indices, orders, num_rows, capacity):
    from spark_rapids_tpu.ops.filtering import gather_cols
    perm = sort_permutation([cols[i] for i in key_indices], orders, num_rows, capacity)
    live = jnp.arange(capacity, dtype=jnp.int32) < num_rows
    return gather_cols(cols, perm, live)


@jax.named_scope("partition_permutation")
def partition_permutation(part_ids, num_partitions: int, num_rows,
                          capacity: int):
    """Stable permutation grouping live rows by partition id with padding
    sunk to the end — the exchange partition step. Ids are a tiny dense
    domain, so a comparator sort is overkill: when the radix kernel is routed
    the Pallas counting-rank kernel (pallas_kernels.radix_partition_permutation)
    produces the permutation from one-hot cumsums; otherwise the stable
    argsort stands in."""
    from spark_rapids_tpu.ops import pallas_kernels as PK
    live = jnp.arange(capacity, dtype=jnp.int32) < num_rows
    ids = jnp.where(live, part_ids.astype(jnp.int32),
                    jnp.int32(num_partitions))
    if (num_partitions + 1 <= PK.RADIX_MAX_PARTS
            and PK.should_use("radix")):
        return PK.radix_partition_permutation(ids, num_partitions + 1)
    return jnp.argsort(ids, stable=True)
