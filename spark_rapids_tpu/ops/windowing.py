"""Window kernels — segmented scans over sorted partitions, all inside one XLA
program.

Reference: cudf rolling/window aggregation driven by GpuWindowExpression
(`.overWindow`:295, `windowAggregation`:847). cudf materializes per-row gather
windows; the TPU-native design instead sorts once and computes SEGMENTED SCANS.

Implementation note: jax.lax.associative_scan with a tuple carrier compiles
pathologically on the TPU toolchain here, so scans use (a) the native cumsum for
sums and (b) explicit Hillis-Steele log-step doubling (12 static steps at 4k
capacity: roll + where, all plain XLA ops) for max/min — O(n log n) work, tiny
programs, no data-dependent shapes:

  - unbounded-preceding → current (ROWS): segmented inclusive scan
  - RANGE ...→ current with ties: gather the scan value at each tie-group end
  - unbounded both: segment totals broadcast
  - sliding ROWS [p, f]: prefix-sum differences (sum/count/avg)
  - ranking: positions vs segment starts / tie-group starts
  - lead/lag: shifted gathers masked by partition membership
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T


def _doubling_scan(values, mask_fn, combine, reverse: bool = False):
    """Inclusive scan by log-step doubling: out[i] = combine over the allowed
    prefix. mask_fn(idx, s) says whether out[i-s] may fold into out[i]
    (`reverse`: over the allowed suffix, whether out[i+s] may)."""
    cap = values.shape[0]
    idx = jnp.arange(cap, dtype=jnp.int32)
    out = values
    s = 1
    while s < cap:
        # out[i-s] (out[i+s]); rows it wraps to are masked off below
        prev = jnp.roll(out, -s if reverse else s)
        out = jnp.where(mask_fn(idx, s), combine(prev, out), out)
        s <<= 1
    return out


_SCAN_BLOCK = 1024


def _blocked_scan(values, scan, combine, identity, reverse: bool = False):
    """Exact inclusive scan of a long 1-D array in two levels: `scan` inside
    rows of _SCAN_BLOCK, then across the row totals, then `combine` each row
    with the total of the rows before it (after it, for a reverse scan). XLA
    lowers a cumulative op to a reduce-window as wide as the array; at 1 Mi
    rows the chip's compiler takes 20-50 s over one of those and under a
    second over this form, and the result is the same (integer sums wrap
    identically, max/min are exact in any dtype). Float sums are not routed
    here: their rounding would depend on the block size."""
    n = values.shape[0]
    if values.ndim != 1 or n < 4 * _SCAN_BLOCK or n % _SCAN_BLOCK:
        return scan(values, 0)
    inner = scan(values.reshape(n // _SCAN_BLOCK, _SCAN_BLOCK), 1)
    totals = scan(inner[:, 0] if reverse else inner[:, -1], 0)
    edge = jnp.full((1,), identity, totals.dtype)
    carried = (jnp.concatenate([totals[1:], edge]) if reverse
               else jnp.concatenate([edge, totals[:-1]]))
    return combine(inner, carried[:, None]).reshape(n)


def cumsum(values):
    """jnp.cumsum along axis 0; long integer arrays take the two-level form
    (see _blocked_scan)."""
    if jnp.issubdtype(values.dtype, jnp.floating):
        return jnp.cumsum(values, axis=0)
    return _blocked_scan(values, jnp.cumsum, jnp.add, 0)


def _extreme(dtype, largest: bool):
    info = (jnp.finfo if jnp.issubdtype(dtype, jnp.floating)
            else jnp.iinfo)(dtype)
    return info.max if largest else info.min


def cummax(values):
    return _blocked_scan(values, lambda v, ax: jax.lax.cummax(v, axis=ax),
                         jnp.maximum, _extreme(values.dtype, False))


def cummin_reverse(values):
    return _blocked_scan(
        values, lambda v, ax: jax.lax.cummin(v, axis=ax, reverse=True),
        jnp.minimum, _extreme(values.dtype, True), reverse=True)


def seg_starts(boundary):
    """Index of the segment start for every row: the most recent boundary at
    or before the row. Marked indices are prefix-monotone (earlier segments
    start earlier), so one NATIVE global cummax is exact — no cross-segment
    contamination and ~30x cheaper than the log-step doubling scan."""
    idx = jnp.arange(boundary.shape[0], dtype=jnp.int32)
    marked = jnp.where(boundary, idx, jnp.int32(0))
    return cummax(marked)


def seg_ends(boundary):
    """Index of the segment end for every row: the next boundary (exclusive)
    minus one. Suffix-monotone, so one native reversed cummin is exact."""
    cap = boundary.shape[0]
    idx = jnp.arange(cap, dtype=jnp.int32)
    next_b = jnp.concatenate([boundary[1:], jnp.ones((1,), jnp.bool_)])
    marked = jnp.where(next_b, idx, jnp.int32(2**31 - 1))
    return cummin_reverse(marked)


def segmented_scan(values, boundary, combine):
    """Inclusive scan of `values` restarting where boundary=True."""
    start = seg_starts(boundary)
    return _doubling_scan(values, lambda i, s: (i - s) >= start, combine)


def seg_cumsum(values, boundary):
    """Segmented cumulative sum via ONE native cumsum + per-segment rebase
    (cheaper than doubling for the common sum/count scans)."""
    cs = cumsum(values)
    start = seg_starts(boundary)
    base = jnp.where(start > 0, cs[jnp.maximum(start - 1, 0)],
                     jnp.zeros_like(cs[0]))
    return cs - base


def seg_cummax(values, boundary):
    return segmented_scan(values, boundary, jnp.maximum)


def tie_group_ends(order_boundary, part_boundary):
    """For RANGE frames: last index of each row's order-key tie group within its
    partition (rows with equal order keys share the frame end — Spark RANGE
    CURRENT ROW includes ties)."""
    n = order_boundary.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    rev = lambda x: jnp.flip(x, 0)
    # a tie group ends where the NEXT row starts a new tie group (or at n-1)
    next_is_boundary = jnp.concatenate(
        [order_boundary[1:], jnp.ones((1,), jnp.bool_)])
    end_idx = jnp.where(next_is_boundary, idx, jnp.int32(0))
    # propagate each end backwards across its tie group: reversed segmented scan
    ends = rev(seg_cummax(rev(end_idx), rev(next_is_boundary)))
    return ends


def row_number(part_boundary, capacity):
    idx = jnp.arange(capacity, dtype=jnp.int32)
    return idx - seg_starts(part_boundary) + 1


def dense_rank(order_boundary, part_boundary):
    newgrp = order_boundary & ~part_boundary
    return seg_cumsum(newgrp.astype(jnp.int32), part_boundary) + 1


def rank(order_boundary, part_boundary, capacity):
    idx = jnp.arange(capacity, dtype=jnp.int32)
    start = seg_starts(part_boundary)
    tie_start = seg_cummax(jnp.where(order_boundary, idx, jnp.int32(0)),
                           part_boundary)
    return tie_start - start + 1


def shift_within_partition(values, validity, seg_ids, offset: int, capacity: int,
                           fill_value, fill_valid):
    """lead (offset>0) / lag (offset<0) with partition-membership masking."""
    idx = jnp.arange(capacity, dtype=jnp.int32)
    src = idx + offset
    in_range = (src >= 0) & (src < capacity)
    src_c = jnp.clip(src, 0, capacity - 1)
    same_part = in_range & (seg_ids[src_c] == seg_ids)
    vals = jnp.where(same_part, values[src_c], fill_value)
    valid = jnp.where(same_part, validity[src_c], fill_valid)
    return vals, valid


# ---- variable-bound frames: [lo, hi] per row ------------------------------
#
# Sliding min/max and bounded RANGE frames reduce every frame shape to an
# inclusive per-row index window [lo, hi]. min/max answer range queries with a
# sparse table (log-levels of power-of-2 span minima — the TPU-native stand-in
# for cudf's per-row rolling gather, reference GpuWindowExpression.scala:847);
# sums/counts difference one global cumsum. All static shapes, O(n log n).

def sparse_table(values, combine, sentinel):
    """(L, n) table: t[k][i] = combine over values[i : i+2^k] (clamped).
    Entries whose span crosses n are padded with `sentinel`; queries built by
    `range_query` never read a padded slot for in-bounds [lo, hi]."""
    n = values.shape[0]
    levels = [values]
    k = 0
    while (1 << (k + 1)) <= n:
        prev = levels[-1]
        s = 1 << k
        shifted = jnp.concatenate(
            [prev[s:], jnp.full((s,), sentinel, prev.dtype)])
        levels.append(combine(prev, shifted))
        k += 1
    return jnp.stack(levels)


def range_query(table, combine, lo, hi):
    """combine over [lo, hi] inclusive per row (requires hi >= lo; callers mask
    empty frames separately). Two overlapping power-of-2 spans."""
    L = table.shape[0]
    w = hi - lo + 1
    k = jnp.zeros_like(w)
    for j in range(1, L):
        k = k + (w >= (1 << j)).astype(k.dtype)
    span = jnp.left_shift(jnp.ones_like(k), k)
    a = table[k, lo]
    b = table[k, hi - span + 1]
    return combine(a, b)


def searchsorted_lex(seg, rank, val, q_seg, q_rank, q_val, side: str):
    """Vectorized first index j with (seg[j], rank[j], val[j]) >= (or > for
    side='right') the per-row query triple, by branchless binary search —
    log2(n) rounds of gathers, no data-dependent control flow. The arrays must
    be lexicographically sorted (they are: rows sort by partition, then
    null-rank, then order value)."""
    n = seg.shape[0]
    lo = jnp.zeros_like(q_seg, shape=q_seg.shape).astype(jnp.int32)
    hi = jnp.full(q_seg.shape, n, jnp.int32)
    steps = max(1, n.bit_length())
    for _ in range(steps):
        mid = (lo + hi) >> 1
        m = jnp.clip(mid, 0, n - 1)
        sj, rj, vj = seg[m], rank[m], val[m]
        if side == "left":
            vcmp = vj >= q_val
        else:
            vcmp = vj > q_val
        ge = (sj > q_seg) | ((sj == q_seg) &
                             ((rj > q_rank) | ((rj == q_rank) & vcmp)))
        ge = ge & (mid < n)
        hi = jnp.where(ge, mid, hi)
        lo = jnp.where(ge, lo, jnp.minimum(mid + 1, n))
    return lo


def range_frame_bounds(order_col_values, order_validity, seg_ids, ascending,
                       preceding, following, pstart, pend):
    """Per-row [lo, hi] for a bounded RANGE frame over ONE numeric order key.

    Sort-space transform: desc negates (bitwise-not for ints so INT_MIN is
    safe), so the search is always ascending. Rows sort (within a partition)
    as null-first-group < values < NaN-group < null-last-group — encoded in a
    rank lane so null/NaN current rows resolve to their PEER GROUP on bounded
    sides (Spark RangeBoundOrdering: null±offset is null, which compares equal
    to nulls only; NaN is its own largest peer class).
    """
    v = order_col_values
    is_float = jnp.issubdtype(v.dtype, jnp.floating)
    if is_float:
        s = jnp.where(jnp.isnan(v), jnp.float64(0), v.astype(jnp.float64))
        s = s if ascending else -s
        nan_rank_pos = jnp.isnan(v)
        q_lo_sent = jnp.float64(-jnp.inf)
        q_hi_sent = jnp.float64(jnp.inf)
        pre = None if preceding is None else jnp.float64(preceding)
        fol = None if following is None else jnp.float64(following)
    else:
        s = v.astype(jnp.int64)
        s = s if ascending else ~s
        nan_rank_pos = jnp.zeros(v.shape, jnp.bool_)
        q_lo_sent = jnp.int64(jnp.iinfo(jnp.int64).min)
        q_hi_sent = jnp.int64(jnp.iinfo(jnp.int64).max)
        pre = None if preceding is None else jnp.int64(preceding)
        fol = None if following is None else jnp.int64(following)

    # rank within partition: nulls keep their sorted side, NaN sorts as the
    # largest value class (asc) / smallest (desc negation puts it first, but
    # the sort itself put NaN where 'NaN is largest' dictates — derive the
    # rank from the OBSERVED layout by giving NaN the rank matching direction)
    nan_rank = jnp.int32(2) if ascending else jnp.int32(-1)
    rank = jnp.where(order_validity,
                     jnp.where(nan_rank_pos, nan_rank, jnp.int32(1)),
                     jnp.int32(0))
    # null rows sort first or last depending on nulls_first: infer from layout
    # (a null row at pstart ⇒ nulls-first). Both cases keep nulls one block.
    null_first_here = ~order_validity[pstart]
    rank = jnp.where(order_validity, rank,
                     jnp.where(null_first_here, jnp.int32(-2), jnp.int32(3)))

    s = jnp.where(order_validity & ~nan_rank_pos, s,
                  jnp.zeros_like(s))  # peers distinguished by rank lane only
    own_rank = rank
    peer_only = ~order_validity | nan_rank_pos

    if pre is None:
        lo = pstart
    else:
        q_val = jnp.where(peer_only, q_lo_sent, s - pre)
        lo = searchsorted_lex(seg_ids, rank, s, seg_ids, own_rank, q_val,
                              side="left")
    if fol is None:
        hi = pend
    else:
        q_val = jnp.where(peer_only, q_hi_sent, s + fol)
        hi = searchsorted_lex(seg_ids, rank, s, seg_ids, own_rank, q_val,
                              side="right") - 1
    return jnp.maximum(lo, pstart).astype(jnp.int32), \
        jnp.minimum(hi, pend).astype(jnp.int32)
