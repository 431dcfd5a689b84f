"""Equi-join gather-map kernels under XLA's static-shape regime.

Reference (SURVEY.md component #16): GpuHashJoin.scala:289 calls cudf
`innerJoinGatherMaps` / `leftJoinGatherMaps` etc — hash-table probes producing
data-dependent-size gather maps, iterated out-of-core by JoinGatherer.scala.

TPU-native design for the general case (keys that are no integers, keys that read
the batch's context, integer keys whose domain cannot be packed into 62 bits:
sorts and searches are XLA-native, and rank equality is collision-free). Integer
keys, one or several, do not come here: exec/joins.py packs the tuple into one
int64, sorts that build once and probes it by direct address where the key domain
is compact (one gather a stream row; on a v5e 8.5 ms a 1 Mi-row batch where the
`searchsorted` below costs 240 to 480 ms, PERF.md section 6, PR 30), by
`searchsorted` where it is not:

1. **Dense ranks**: concatenate build+stream key rows and run ONE fused multi-key sort
   (ops.grouping.group_segments); equal key tuples — with Spark's NaN==NaN and
   null-grouping semantics — get equal dense ranks. Rank equality IS key-tuple
   equality (collision-free, unlike hashing).
2. **Range probe**: sort build ranks once; per stream row `searchsorted` left/right
   gives its contiguous match range [lo, hi) — the "gather map" is implicit.
3. **Bounded expansion**: pair j maps to stream row i = searchsorted(cumsum(counts), j)
   and build slot lo[i] + (j - start[i]); expansion is chunked to a fixed output
   capacity so one compiled program serves any join size (the JoinGatherer analog).

Join-type semantics (Spark):
- nulls in keys never match (EqualTo); NaN matches NaN; -0.0 == 0.0 (canonicalized);
- LeftOuter emits unmatched stream rows null-extended; FullOuter additionally emits
  unmatched build rows (computed by the symmetric probe, no scatter);
- LeftSemi emits each matching stream row once; LeftAnti the non-matching ones.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.core import Col
from spark_rapids_tpu.ops.grouping import group_segments
from spark_rapids_tpu.ops.windowing import cumsum

INNER = "inner"
LEFT_OUTER = "leftouter"
RIGHT_OUTER = "rightouter"
FULL_OUTER = "fullouter"
LEFT_SEMI = "leftsemi"
LEFT_ANTI = "leftanti"
CROSS = "cross"

# plain ints (weak-typed under jnp ops): creating jnp scalars at import time
# would initialize the default jax backend before a process has a chance to
# select its platform (MiniCluster executors force CPU after import)
_BUILD_NULL_RANK = -2
_STREAM_NULL_RANK = -1
_PAD_RANK = 2**31 - 1


def _concat_key_cols(build_keys, stream_keys):
    out = []
    for b, s in zip(build_keys, stream_keys):
        vals = jnp.concatenate([b.values, s.values])
        valid = jnp.concatenate([b.validity, s.validity])
        out.append(Col(vals, valid, b.dtype, b.dictionary))
    return out


def _order_rank(v):
    """int32 rank of every element of a 1-D integer array: equal values get
    equal ranks and order is kept (the position of the value's first
    occurrence in sorted order). One single-operand sort and one
    searchsorted."""
    return jnp.searchsorted(jax.lax.sort(v, is_stable=False), v,
                            side="left").astype(jnp.int32)


@jax.named_scope("tuple_ranks")
def _tuple_ranks(key_cols, total_cap: int):
    """int32 ranks over the rows of integer-backed key columns such that rank
    equality == key-tuple equality and rank order == tuple order, or None
    when a column is not integer-backed (floats keep their NaN/-0.0 grouping
    rules on the comparator path). Each column is ranked alone, then the running rank and the next
    column's rank are packed into one int64 (both are below total_cap, so
    two of them always fit) and ranked again. Every sort has ONE operand:
    the chip's compiler takes 140 s over the 6-operand comparator sort of
    two int64 keys at 384 Ki rows and 13 s over a one-operand int64 sort,
    and no permutation is scattered back."""
    if not all(c.values.ndim == 1
               and (c.values.dtype == jnp.bool_
                    or jnp.issubdtype(c.values.dtype, jnp.integer))
               for c in key_cols):
        return None
    bits = max((total_cap - 1).bit_length(), 1)
    acc = None
    for c in key_cols:
        v = (c.values.astype(jnp.int8) if c.values.dtype == jnp.bool_
             else c.values)
        r = _order_rank(jnp.where(c.validity, v, jnp.zeros_like(v)))
        acc = r if acc is None else _order_rank(
            (acc.astype(jnp.int64) << bits) | r.astype(jnp.int64))
    return acc


@jax.named_scope("join_ranks")
def join_ranks(build_keys, n_build, build_cap, stream_keys, n_stream, stream_cap):
    """Ranks for both sides such that rank equality == key-tuple equality.
    Null-keyed rows get side-specific sentinel ranks so they never match; padding
    gets +inf rank. Returns (build_ranks, stream_ranks) int32 arrays."""
    total_cap = build_cap + stream_cap
    both = _concat_key_cols(build_keys, stream_keys)
    # live across the concatenated array: build rows [0,n_build), stream rows
    # [build_cap, build_cap+n_stream)
    idx = jnp.arange(total_cap, dtype=jnp.int32)
    live = jnp.where(idx < build_cap, idx < n_build, (idx - build_cap) < n_stream)
    ranks = _tuple_ranks(both, total_cap)
    if ranks is None:
        # group_segments sorts with padding sunk by its own live test (arange
        # < num_rows); all rows are sorted and liveness is handled via rank
        # sentinels below
        perm, seg_ids, boundary, _ = group_segments(
            both, jnp.int32(total_cap), total_cap)
        ranks = jnp.zeros((total_cap,), jnp.int32).at[perm].set(seg_ids)
    any_null = jnp.zeros((total_cap,), jnp.bool_)
    for c in both:
        any_null = any_null | ~c.validity
    is_build = idx < build_cap
    ranks = jnp.where(any_null, jnp.where(is_build, _BUILD_NULL_RANK,
                                          _STREAM_NULL_RANK), ranks)
    ranks = jnp.where(live, ranks, _PAD_RANK)
    return ranks[:build_cap], ranks[build_cap:]


@jax.named_scope("probe")
def probe(build_ranks, stream_ranks):
    """Sorted-build probe. Returns (build_perm, lo, hi) with lo/hi per stream row."""
    build_perm = jnp.argsort(build_ranks, stable=True)
    sorted_build = build_ranks[build_perm]
    lo = jnp.searchsorted(sorted_build, stream_ranks, side="left")
    hi = jnp.searchsorted(sorted_build, stream_ranks, side="right")
    # null/pad sentinels never match: stream sentinel ranks are negative/huge and
    # distinct from build sentinels, but guard explicitly for safety
    bad = (stream_ranks == _STREAM_NULL_RANK) | (stream_ranks == _PAD_RANK)
    hi = jnp.where(bad, lo, hi)
    return build_perm, lo, hi


@jax.named_scope("pair_counts")
def pair_counts(lo, hi, n_stream, stream_cap, join_type):
    """Per-stream-row emitted pair count for the join type."""
    live = jnp.arange(stream_cap, dtype=jnp.int32) < n_stream
    matches = (hi - lo).astype(jnp.int32)
    if join_type in (INNER,):
        counts = matches
    elif join_type in (LEFT_OUTER, FULL_OUTER):
        counts = jnp.maximum(matches, 1)
    elif join_type == LEFT_SEMI:
        counts = jnp.minimum(matches, 1)
    elif join_type == LEFT_ANTI:
        counts = (matches == 0).astype(jnp.int32)
    else:
        raise ValueError(f"unsupported join type for pair_counts: {join_type}")
    return jnp.where(live, counts, 0)


@jax.named_scope("expand_pairs")
def expand_pairs(build_perm, lo, hi, counts, start_pair: int, out_cap: int):
    """Materialize pairs [start_pair, start_pair+out_cap) as
    (stream_idx, build_idx, build_matched, pair_live).

    build_matched=False marks null-extension slots of outer joins. One compiled
    program serves every chunk (static out_cap) — the JoinGatherer iteration."""
    offsets = cumsum(counts)  # inclusive
    total = offsets[-1]
    j = jnp.arange(out_cap, dtype=jnp.int32) + jnp.int32(start_pair)
    stream_idx = jnp.searchsorted(offsets, j, side="right").astype(jnp.int32)
    stream_idx_c = jnp.clip(stream_idx, 0, counts.shape[0] - 1)
    starts = offsets - counts
    within = j - starts[stream_idx_c]
    n_matches = (hi - lo)[stream_idx_c]
    build_matched = within < n_matches
    b_pos = jnp.clip(lo[stream_idx_c] + jnp.minimum(within, n_matches - 1), 0,
                     build_perm.shape[0] - 1)
    build_idx = build_perm[b_pos]
    pair_live = j < total
    return stream_idx_c, build_idx, build_matched & pair_live, pair_live


@jax.named_scope("total_pairs")
def total_pairs(counts):
    return jnp.sum(counts)
