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
import typing

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


FIELD_BITS = 62   # the widest value image one key's field may hold


def words_for(need_bits: int) -> int:
    """Sort operands that hold ``need_bits``: one int64 (63 bits under the
    sign) while that does, 31-bit int32 words beyond. The chip's compiler
    takes its time by 32-bit operands (timed for a described v5e at 2 Mi
    rows: one int64 16 s, three int32 34 s, two int64 61 s, the six mixed
    operands of a two-key comparator sort 167 s), and the sort moves fewer
    bytes a row."""
    return 1 if need_bits <= 63 else -(-need_bits // 31)


def held_bits(n_words: int) -> int:
    """Bits that ``n_words`` operands hold (the inverse of ``words_for``)."""
    return 63 if n_words == 1 else 31 * n_words


class Folded(typing.NamedTuple):
    """Sort keys folded into integer words (ops/sorting.fold_keys)."""
    words: list          # most significant first; the row index ends the last
    offsets: list        # bit offset of each key's field from the low end
    widths: list         # value bits of each key's field (a null bit on top)
    vmins: list          # what each key's values are counted from
    iota_bits: int
    need_bits: object    # bits the fields take in all; traced when observed


def _bit_length(x):
    """Bits of a non-negative int64 scalar (no clz on an emulated 64-bit)."""
    return jnp.sum((x >> jnp.arange(63, dtype=jnp.int64)) != 0,
                   dtype=jnp.int32)


def _static_domain(c: Col):
    """(vmin, value bits) of a key whose domain its type or dictionary
    states, or None (wide ints, floats, strings without a dictionary)."""
    if c.is_string:
        if c.dictionary is None:
            return None
        return 0, max(len(c.dictionary) - 1, 0).bit_length()
    if isinstance(c.dtype, T.BooleanType):
        return 0, 1
    if isinstance(c.dtype, (T.IntegralType, T.DateType)):
        w = jnp.iinfo(c.values.dtype).bits
        return (-(1 << (w - 1)), w) if w <= 32 else None
    return None


def ranged_key(dtype) -> bool:
    """An integer key: folded by the range its rows hold where a caller
    observes it (``fold_keys(n_words=k)``), by its type's width otherwise."""
    return (not isinstance(dtype, T.BooleanType) and isinstance(
        dtype, (T.IntegralType, T.DateType, T.TimestampType)))


def _observed_domain(c: Col, live):
    """(vmin, value bits, poison) of an integer key by what its live rows
    hold; ``poison`` is a count of bits no word holds where the span passes
    2^62 and cannot be one field (it goes into ``need_bits``)."""
    v = c.values.astype(jnp.int64)
    seen = c.validity & live
    vmin = jnp.min(jnp.where(seen, v, jnp.iinfo(jnp.int64).max))
    vmax = jnp.max(jnp.where(seen, v, jnp.iinfo(jnp.int64).min))
    vmin = jnp.where(vmax >= vmin, vmin, 0)
    span = jnp.where(vmax >= vmin, vmax - vmin, 0)   # wraps negative if wide
    wide = (span < 0) | (span >= (1 << FIELD_BITS))
    bits = jnp.where(wide, jnp.int32(FIELD_BITS), _bit_length(span))
    return vmin, bits, jnp.where(wide, jnp.int32(1 << 12), jnp.int32(0))


def _word_shift(at: int, word_bits: int, offset):
    """How far right a field that starts ``offset`` bits from the low end
    lies of word ``at`` (counted from the low end); negative: to its left."""
    return at * word_bits - offset


@jax.named_scope("fold_keys")
def fold_keys(key_cols, orders, num_rows, capacity: int, n_words=None):
    """Fold (pad rank, a null rank and a value image a key, the row index)
    into as few sort operands as they need (``words_for``), the row index
    in the low bits of the last: uniqueness makes stability free, and
    lax.sort's cost and the chip compiler's time go by operand count. One
    algorithm for every key set; a single key is the case n = 1.

    ``n_words=None``: every key's field is as wide as its type or its
    dictionary states, and the number of words follows (None when a key
    states no domain: an int64, a float). ``n_words=k``: integer keys are
    counted from the least value their live rows hold and take the bits of
    the span they hold, so a statically wide key that holds little folds
    too; fields are then placed by traced offsets, ONE compiled program
    serves every range, and ``need_bits`` says whether the k words held
    them (the caller compares it with ``held_bits(k)`` and falls back: a
    key that outgrew its word must never miscompute)."""
    iota_bits = max((capacity - 1).bit_length(), 1)
    live = jnp.arange(capacity, dtype=jnp.int32) < num_rows
    observed = n_words is not None
    domains, poison = [], 0
    for c in key_cols:
        if observed and ranged_key(c.dtype):
            vmin, bits, wide = _observed_domain(c, live)
            poison = poison + wide
        elif _static_domain(c) is not None:
            vmin, bits = _static_domain(c)
        else:
            return None
        domains.append((vmin, bits))
    # fields from the low end: the row index, then the keys last to first
    offsets, off = [], iota_bits
    for _vmin, bits in reversed(domains):
        offsets.append(off)
        off = off + bits + 1
    offsets.reverse()
    pad_at = off                                     # the pad rank on top
    need_bits = off + 1 + poison
    if not observed:
        n_words = words_for(need_bits)
    word_bits = held_bits(n_words) // n_words
    dtype = jnp.int64 if n_words == 1 else jnp.int32
    mask = (1 << word_bits) - 1
    words = [jnp.zeros((capacity,), jnp.int64) for _ in range(n_words)]

    def place(field, offset):
        """OR ``field`` (an int64 under 2^63) in at ``offset`` bits from the
        low end, across as many words as it spans."""
        for j in range(n_words):
            d = _word_shift(n_words - 1 - j, word_bits, offset)
            up = jnp.clip(-d, 0, word_bits)
            words[j] = words[j] | jnp.where(
                d >= 0, (field >> jnp.clip(d, 0, 63)) & mask,
                jnp.where(-d < word_bits,
                          (field & (mask >> up)) << up, 0))

    place(jnp.arange(capacity, dtype=jnp.int64), 0)
    for c, o, (vmin, bits), offset in zip(key_cols, orders, domains,
                                          offsets):
        top = (jnp.int64(1) << bits) - 1
        u = jnp.clip(c.values.astype(jnp.int64) - vmin, 0, top)
        u = jnp.where(c.validity, u, 0)
        if not o.ascending:
            u = top - u
        nf = o.resolved_nulls_first
        null_rank = jnp.where(c.validity, jnp.int64(1 if nf else 0),
                              jnp.int64(0 if nf else 1))
        place((null_rank << bits) | u, offset)
    place((~live).astype(jnp.int64), pad_at)
    return Folded([w.astype(dtype) for w in words], offsets,
                  [b for _v, b in domains], [v for v, _b in domains],
                  iota_bits, need_bits)


def sort_folded(folded: Folded):
    """(permutation, the sorted words) of folded keys."""
    out = lax.sort(tuple(folded.words), num_keys=len(folded.words),
                   is_stable=False)
    perm = (out[-1] & ((1 << folded.iota_bits) - 1)).astype(jnp.int32)
    return perm, list(out)


def unfold_keys(folded: Folded, words, key_cols, orders, live):
    """The key columns back out of ``words`` (sorted, or gathered, as the
    caller left them): what a gather a key and a validity would fetch, read
    from the operands the sort already moved."""
    n = len(words)
    word_bits = held_bits(n) // n
    out = []
    for c, o, off, bits, vmin in zip(key_cols, orders, folded.offsets,
                                     folded.widths, folded.vmins):
        field = 0
        for j, w in enumerate(words):
            d = _word_shift(n - 1 - j, word_bits, off)
            w = w.astype(jnp.int64)
            field = field | jnp.where(
                d >= 0, jnp.where(d < 63, w << jnp.clip(d, 0, 62), 0),
                w >> jnp.clip(-d, 0, 62))
        top = (jnp.int64(1) << bits) - 1
        u = field & top
        null_rank = (field >> bits) & 1
        valid = (null_rank == (1 if o.resolved_nulls_first else 0)) & live
        if not o.ascending:
            u = top - u
        vals = (u + vmin).astype(c.values.dtype)
        default = jnp.asarray(c.dtype.default_value(), dtype=vals.dtype)
        out.append(Col(jnp.where(valid, vals, default), valid, c.dtype,
                       c.dictionary))
    return out


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
    if _static_domain(c) is not None:
        return None   # narrow enough to fold
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


def unfolded_operands(key_cols) -> int:
    """Operands of the sort ``sort_permutation`` runs for keys that do not
    fold (what the chip's compiler takes its time by): two for a single
    wide integer, else a rank and a value a key (a NaN rank more for a
    float) between the pad rank and the row index."""
    if len(key_cols) == 1 and ranged_key(key_cols[0].dtype):
        return 2
    return 2 + sum(3 if isinstance(c.dtype, T.FractionalType) else 2
                   for c in key_cols)


@jax.named_scope("sort_permutation")
def sort_permutation(key_cols, orders, num_rows, capacity: int):
    """Stable permutation sorting live rows by keys; padding sinks to the end."""
    folded = fold_keys(key_cols, orders, num_rows, capacity)
    if folded is not None:
        return sort_folded(folded)[0]
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
