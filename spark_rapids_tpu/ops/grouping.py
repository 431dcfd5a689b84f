"""Sort-based group-by — the cudf groupby analog under XLA's static-shape regime.

Reference: GpuHashAggregateExec (aggregate.scala:240) calls cudf hash groupby, whose
output size is data-dependent. XLA cannot produce data-dependent shapes, so the
TPU-native design is a FUSED sort-based pipeline within the padded capacity:

    sort rows by keys → flag group boundaries → segment-reduce values
    → compact one row per group to the front → group count as a device scalar

Everything is one XLA program; the number of groups never exceeds the number of
live rows, so the input capacity bounds the output. Null keys form their own
group (Spark GROUP BY semantics); null aggregation semantics (sum ignores nulls,
null iff no non-null input, NaN handling in min/max) live in expr/aggregates.py
which drives these primitives.

Segment reductions are SCAN-based, never scatter-based: TPU scatters at large
segment counts are catastrophically slow (measured: jax.ops.segment_sum with
4M segments does not finish in minutes on v5e, while the whole sort is ~7 ms).
Integer sums and counts difference one global cumsum at segment edges (exact
even across wrap). Float sums take a segmented doubling scan bounded by each
row's segment start, and carry the value at the segment end back over the
segment the same way (_seg_total): full-width passes that fuse, with no
gather, since a dynamic float64 gather costs the chip tens of ms a 1 Mi rows,
and no prefix differencing, whose error is ulp(prefix), not ulp(total) (at a
revenue prefix of 1e11, 1e-5 against totals of 1e5). min/max/first/last
re-sort (seg_id, value) pairs so the extreme lands on a segment edge. Results
are PER-ROW (row i holds the aggregate of row i's whole segment), so callers
compact boundary rows to get one row per group.
"""

from __future__ import annotations

import typing

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.core import Col
from spark_rapids_tpu.ops import windowing as W
from spark_rapids_tpu.ops.sorting import (SortOrder, fold_keys, sort_folded,
                                          sort_permutation, unfold_keys,
                                          unfolded_operands)
from spark_rapids_tpu.ops.filtering import gather_cols, compact_cols


class SegCtx(typing.NamedTuple):
    """Shared segment structure for one sorted group-by batch."""
    seg_ids: jnp.ndarray    # group index per sorted row (pad → capacity-1)
    boundary: jnp.ndarray   # True at the first row of each segment
    seg_start: jnp.ndarray  # index of the first row of the row's segment
    seg_end: jnp.ndarray    # index of the last row of the row's segment
    capacity: int


@jax.named_scope("compact_key_codes")
def compact_key_codes(key_cols, max_domain: int = 1 << 20):
    """(codes int32, strides) for keys whose domains are STATICALLY known
    (dictionary-coded strings, booleans); nulls get each key's top code
    (Spark groups nulls together). None when unknown/overflowing."""
    if not key_cols:
        return None
    strides = []
    K = 1
    for c in key_cols:
        if c.is_string and c.dictionary is not None:
            d = len(c.dictionary) + 1
        elif isinstance(c.dtype, T.BooleanType):
            d = 3
        else:
            return None
        strides.append(d)
        K *= d
        if K > max_domain:
            return None
    combined = None
    for c, d in zip(key_cols, strides):
        code = c.values.astype(jnp.int32)
        code = jnp.where(c.validity, code, jnp.int32(d - 1))
        combined = code if combined is None else combined * d + code
    return combined, strides


@jax.named_scope("dense_group_sum")
def dense_group_sum(vals, mask, codes, n_domain: int, use_matmul: bool,
                    count_like: bool = False):
    """(n_domain,) per-group totals of `vals` over UNSORTED small-domain
    codes — no sort, no segment structure. CPU: D-bucket scatter-add. TPU
    (`use_matmul`): a cap-length scatter would serialize there, so the
    totals are D masked reductions in one fused pass over the rows.

    `count_like` marks 0/1-valued inputs (histograms, per-batch count
    updates): those are EXACT in f32 below 2^24 rows, so on TPU they ride
    the blocked Pallas one-hot kernel (pallas_kernels.onehot_sum_f32) which
    never materializes the (cap, D) one-hot in HBM — the medium-domain
    MXU-shaped path. Everything else sums in its own dtype: int64 stays
    exact, f64 keeps the chip's emulated-f64 adds. (The f64 one-hot MATMUL
    this replaces was measured on a v5e: XLA splits an f64 dot into f32
    pieces, which was 1e-15 for f64 values but NOT exact for int64 sums
    routed through it, 10-160x slower at 1 Mi rows, and several seconds
    more to compile per aggregate.)"""
    v = jnp.where(mask, vals, jnp.zeros_like(vals))
    if use_matmul:
        if count_like and v.shape[0] < (1 << 24):
            # the f32 2^24 exactness bound: a batch cap at/above it could
            # put >2^24 ones in one bucket — exact path below instead
            from spark_rapids_tpu.ops import pallas_kernels as PK
            if PK.should_use("onehot"):
                out = PK.onehot_sum_f32(v.astype(jnp.float32), codes,
                                        n_domain)
                return out.astype(v.dtype)
        hit = codes[:, None] == jnp.arange(n_domain, dtype=jnp.int32)[None, :]
        return jnp.sum(jnp.where(hit, v[:, None], jnp.zeros((), v.dtype)),
                       axis=0)
    out = jnp.zeros((n_domain + 1,), v.dtype)
    return out.at[jnp.clip(codes, 0, n_domain)].add(v,
                                                    mode="drop")[:n_domain]


_STACK_MAX_DOMAIN = 64   # per-domain masked matvecs unroll D times


@jax.named_scope("resolve_dense_group_sums")
def resolve_dense_group_sums(reqs, codes, n_domain: int, live):
    """CPU batch executor for a batch's dense_group_sum requests
    (`reqs` = [(vals, mask, acc_dtype, count_like), ...]) → results in
    request order. At small domains, requests whose accumulator is
    f64-exact — float sums (native f64) and count-likes (0/1 inputs: any
    count ≤ capacity is exact in a 53-bit mantissa) — stack into one
    (A, cap) f64 matrix reduced by D masked matvecs (V @ (codes == d)):
    XLA:CPU's scatter-add costs ~50 ms per column at 1M rows, the shared
    masked reduction ~6 ms — and unlike a materialized (cap, D) one-hot
    GEMM it never allocates O(cap*D). Wide integer value sums and big
    domains keep the exact per-column scatter path."""
    outs: list = [None] * len(reqs)
    stack = [i for i, (v, m, acc, cl) in enumerate(reqs)
             if cl or jnp.issubdtype(jnp.dtype(acc), jnp.floating)]
    if len(stack) >= 2 and n_domain <= _STACK_MAX_DOMAIN:
        # identity-dedup: sum(x)/avg(x)/count(x) share memoized input arrays
        # (exec/aggregate.py eval_child), so equal requests reduce once
        row_of: dict = {}
        rows = []
        for i in stack:
            v, m, _, _ = reqs[i]
            kk = (id(v), id(m))
            if kk not in row_of:
                row_of[kk] = len(rows)
                rows.append(jnp.where(m & live, v.astype(jnp.float64), 0.0))
        V = jnp.stack(rows)
        sums = jnp.stack(
            [V @ (codes == d).astype(jnp.float64)
             for d in range(n_domain)], axis=1)   # (A, D)
        for i in stack:
            v, m, acc, _ = reqs[i]
            outs[i] = sums[row_of[(id(v), id(m))]].astype(acc)
    for i, (v, m, acc, cl) in enumerate(reqs):
        if outs[i] is None:
            outs[i] = dense_group_sum(v.astype(acc), m & live, codes,
                                      n_domain, False, count_like=cl)
    return outs


class GroupSort(typing.NamedTuple):
    """Rows sorted by their group keys (ops/grouping.sorted_groups)."""
    perm: jnp.ndarray       # the sorting permutation
    seg_ids: jnp.ndarray    # group index a sorted row (pad -> capacity-1)
    boundary: jnp.ndarray   # True at the first row of each group
    live: jnp.ndarray
    sorted_keys: list       # the key columns in sorted order
    folded: object          # sorting.Folded, or None (presorted, variadic)
    words: list             # the sorted words of ``folded``
    operands: int           # what the sort took (0: presorted)


@jax.named_scope("group_segments")
def sorted_groups(key_cols, num_rows, capacity: int, n_words=None,
                  presorted: bool = False) -> GroupSort:
    """Sort by keys and compute segment structure.

    The keys are folded into as few sort operands as they need
    (ops/sorting.fold_keys; ``n_words`` as there: by what the keys hold, for
    a caller that checks ``folded.need_bits``), and the sorted key columns
    and the group boundaries are read back out of the sorted operands: no
    gather a key and no compare a key. Keys that cannot be folded (a float,
    an int64 with no observed range) take ``sort_permutation``'s wider
    sorts and a gather a key.
    `presorted=True` asserts the caller PROVED the live rows already arrive
    key-sorted (exec/aggregate's per-batch key-stats probe): the sort and the
    key gather vanish — equal keys are contiguous by hypothesis, so segment
    detection runs directly over the input order (the sorted-input group-by,
    Spark's sort-aware aggregate analog).
    """
    live = jnp.arange(capacity, dtype=jnp.int32) < num_rows
    orders = [SortOrder() for _ in key_cols]
    folded = words = None
    if presorted:
        operands = 0
        perm = jnp.arange(capacity, dtype=jnp.int32)
        sorted_keys = [Col(c.values, c.validity & live, c.dtype, c.dictionary)
                       for c in key_cols]
    else:
        folded = fold_keys(key_cols, orders, num_rows, capacity, n_words)
    if folded is not None:
        operands = len(folded.words)
        perm, words = sort_folded(folded)
        sorted_keys = unfold_keys(folded, words, key_cols, orders, live)
        # everything above the row index is key: one compare a word
        neq = (words[-1] >> folded.iota_bits) != (
            jnp.roll(words[-1], 1) >> folded.iota_bits)
        for w in words[:-1]:
            neq = neq | (w != jnp.roll(w, 1))
    else:
        if not presorted:
            operands = unfolded_operands(key_cols)
            perm = sort_permutation(key_cols, orders, num_rows, capacity)
            sorted_keys = gather_cols(key_cols, perm, live)
        neq = jnp.zeros((capacity,), jnp.bool_)
        for c in sorted_keys:
            prev_vals = jnp.roll(c.values, 1)
            prev_valid = jnp.roll(c.validity, 1)
            if isinstance(c.dtype, T.FractionalType):
                # NaN == NaN for grouping (Spark), -0.0 == 0.0 (canonicalized already)
                a, b = c.values, prev_vals
                both_nan = jnp.isnan(a) & jnp.isnan(b)
                differs = ~both_nan & ~(a == b)
            else:
                differs = c.values != prev_vals
            neq = neq | differs | (c.validity != prev_valid)
    first_live = jnp.arange(capacity) == 0
    boundary = (first_live | neq) & live
    seg_ids = W.cumsum(boundary.astype(jnp.int32)) - 1
    seg_ids = jnp.where(live, seg_ids, capacity - 1)
    seg_ids = jnp.clip(seg_ids, 0, capacity - 1)
    return GroupSort(perm, seg_ids, boundary, live, sorted_keys, folded,
                     words, operands)


def group_segments(key_cols, num_rows, capacity: int):
    """(perm, seg_ids, boundary, live) of ``sorted_groups``."""
    return sorted_groups(key_cols, num_rows, capacity)[:4]


@jax.named_scope("segment_structure")
def segment_structure(seg_ids, capacity: int) -> SegCtx:
    """Per-row segment start/end from sorted seg_ids (two NATIVE cumulative
    ops — see windowing.seg_starts/seg_ends — shared by every aggregate in
    the batch)."""
    idx = jnp.arange(capacity, dtype=jnp.int32)
    prev = jnp.roll(seg_ids, 1)
    boundary = (idx == 0) | (seg_ids != prev)
    seg_start = W.seg_starts(boundary)
    seg_end = W.seg_ends(boundary)
    return SegCtx(seg_ids, boundary, seg_start, seg_end, capacity)


def _edge_sum(data, ctx: SegCtx):
    """Per-row segment total of `data` via one global cumsum differenced at the
    row's segment edges. Exact for ints (wrap cancels); f64 error ~ulp(prefix)."""
    cs = W.cumsum(data)
    csz = jnp.concatenate([jnp.zeros((1,), cs.dtype), cs])
    return csz[ctx.seg_end + 1] - csz[ctx.seg_start]


def _seg_total(data, ctx: SegCtx):
    """Per-row total of a float column's segment, with no gather: an
    inclusive sum scan bounded by ctx.seg_start, then the value at
    ctx.seg_end carried back over the segment. Each is log2(capacity)
    full-width passes of roll, compare and select (windowing's doubling
    scan), which fuse; a dynamic gather of float64 is what the chip pays
    for (on a v5e at 1 Mi rows, the scan read at seg_end by a gather took
    35 ms, the two scans 1.5 ms). A
    row's sum adds disjoint blocks of its own segment only, in a tree of
    depth log2(capacity), so a total never cancels against a foreign
    prefix, NaN and infinities stay in their segment, and integral totals
    below 2^53 are exact. Every row of a segment holds the same value."""
    scan = W._doubling_scan(data, lambda i, s: (i - s) >= ctx.seg_start,
                            jnp.add)
    return W._doubling_scan(scan, lambda i, s: (i + s) <= ctx.seg_end,
                            lambda later, _: later, reverse=True)


def _seg_extreme(data, ctx: SegCtx, largest: bool):
    """Per-segment min/max by re-sorting (seg_id, value) pairs — seg_ids are
    already sorted, so the 2-key native sort only reorders within segments and
    the extreme lands on the segment's first/last row. One native sort
    (~log n comparator passes fused by XLA) instead of a log-step doubling
    scan over full-width data."""
    # every operand is a key, so stability buys nothing (and its extra
    # index operand costs the chip's compiler tens of seconds)
    _, sorted_vals = jax.lax.sort([ctx.seg_ids, data], num_keys=2,
                                  is_stable=False)
    pos = ctx.seg_end if largest else ctx.seg_start
    return sorted_vals[pos]


@jax.named_scope("segment_count")
def segment_count(validity, ctx: SegCtx):
    """Per-row count of valid rows in the row's segment."""
    return _edge_sum(validity.astype(jnp.int64), ctx)


@jax.named_scope("segment_sum")
def segment_sum(values, validity, ctx: SegCtx):
    data = jnp.where(validity, values, jnp.zeros_like(values))
    if jnp.issubdtype(data.dtype, jnp.floating):
        # floats: a segmented scan, no gather and no prefix differencing
        # (_seg_total); a global prefix's ulp would swamp a small total
        s = _seg_total(data, ctx)
    else:
        s = _edge_sum(data, ctx)  # ints: exact even across wrap
    return s, segment_count(validity, ctx)


@jax.named_scope("segment_min")
def segment_min(values, validity, ctx: SegCtx, dtype: T.DataType):
    if isinstance(dtype, T.FractionalType):
        sentinel = jnp.asarray(jnp.inf, values.dtype)
        nan = jnp.isnan(values)
        data = jnp.where(validity & ~nan, values, sentinel)
        m = _seg_extreme(data, ctx, largest=False)
        # all-NaN group: min is NaN (Spark: NaN is largest; min picks non-NaN if any)
        has_non_nan = _edge_sum((validity & ~nan).astype(jnp.int32), ctx)
        has_nan = _edge_sum((validity & nan).astype(jnp.int32), ctx)
        return jnp.where((has_non_nan == 0) & (has_nan > 0), jnp.nan, m)
    if values.dtype == jnp.bool_:
        data = jnp.where(validity, values, True).astype(jnp.int8)
        return _seg_extreme(data, ctx, largest=False).astype(jnp.bool_)
    info = jnp.iinfo(values.dtype)
    data = jnp.where(validity, values, jnp.asarray(info.max, values.dtype))
    return _seg_extreme(data, ctx, largest=False)


@jax.named_scope("segment_max")
def segment_max(values, validity, ctx: SegCtx, dtype: T.DataType):
    if isinstance(dtype, T.FractionalType):
        nan = jnp.isnan(values)
        sentinel = jnp.asarray(-jnp.inf, values.dtype)
        data = jnp.where(validity & ~nan, values, sentinel)
        m = _seg_extreme(data, ctx, largest=True)
        has_nan = _edge_sum((validity & nan).astype(jnp.int32), ctx)
        # any NaN in group → max is NaN (NaN is largest)
        return jnp.where(has_nan > 0, jnp.nan, m)
    if values.dtype == jnp.bool_:
        data = jnp.where(validity, values, False).astype(jnp.int8)
        return _seg_extreme(data, ctx, largest=True).astype(jnp.bool_)
    info = jnp.iinfo(values.dtype)
    data = jnp.where(validity, values, jnp.asarray(info.min, values.dtype))
    return _seg_extreme(data, ctx, largest=True)


@jax.named_scope("segment_first")
def segment_first(values, validity, ctx: SegCtx, ignore_nulls: bool):
    """First (by sorted order) value per group; Spark First(ignoreNulls)."""
    idx = jnp.arange(ctx.capacity, dtype=jnp.int32)
    big = jnp.int32(ctx.capacity)
    eligible = validity if ignore_nulls else jnp.ones_like(validity)
    cand = jnp.where(eligible, idx, big)
    pos = _seg_extreme(cand, ctx, largest=False)
    pos_clamped = jnp.clip(pos, 0, ctx.capacity - 1)
    vals = values[pos_clamped]
    valid = (pos < big) & validity[pos_clamped]
    return vals, valid


@jax.named_scope("segment_last")
def segment_last(values, validity, ctx: SegCtx, ignore_nulls: bool):
    """Last (by sorted order) value per group; Spark Last(ignoreNulls)."""
    idx = jnp.arange(ctx.capacity, dtype=jnp.int32)
    small = jnp.int32(-1)
    eligible = validity if ignore_nulls else jnp.ones_like(validity)
    cand = jnp.where(eligible, idx, small)
    pos = _seg_extreme(cand, ctx, largest=True)
    pos_clamped = jnp.clip(pos, 0, ctx.capacity - 1)
    vals = values[pos_clamped]
    valid = (pos > small) & validity[pos_clamped]
    return vals, valid
