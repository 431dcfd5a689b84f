"""Filter/compaction and gather kernels.

Reference: cudf apply_boolean_mask via GpuFilterExec (basicPhysicalOperators.scala:181).
cudf compacts to a new smaller column; XLA needs static shapes, so we compact IN PLACE
within the padded capacity: surviving rows are moved to the front (stable), the live
row count becomes a device scalar, and the tail is marked invalid. The whole thing is
a fused sort-by-flag — no host sync, so filters chain inside one XLA program."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu.expr.core import Col
from spark_rapids_tpu.ops.windowing import cumsum
from spark_rapids_tpu.runtime import tracing


@jax.named_scope("selection_mask")
def selection_mask(pred: Col, num_rows, capacity: int):
    """Rows kept by a filter: predicate true AND valid AND a live (non-pad) row."""
    live = jnp.arange(capacity) < num_rows
    return pred.values & pred.validity & live


@jax.named_scope("front_perm")
def front_perm(keep_mask):
    """The front-compaction permutation of a keep mask: (perm, count), with
    perm[j] the j-th kept row for j < count (stable) and the dropped rows
    behind them, for the caller to mask.

    One prefix sum and ONE scatter on every backend: row j writes its own
    index to the slot it moves to, a kept row to `running - 1` and a dropped
    one behind the last kept (`count +` the dropped rows ahead of it), so the
    destinations are a full permutation: in bounds and distinct, as the
    scatter is told. Timed alone on a TPU v5e (PERF.md section 6, PR 32;
    1 Mi / 4 Mi rows, any keep share): 6.1 / 26.5 ms, against 159 / 694 ms
    for `searchsorted(running, j + 1)`, the 19-step loop this replaced on
    the chip; 6.2 / 26.5 ms with the dropped rows scattered out of bounds
    (`mode="drop"`), 10.5 / 39 ms for a scatter-min into slot `running`
    (sorted destinations). XLA:CPU: a scatter ~50 ms, a gather ~8 ms."""
    capacity = keep_mask.shape[0]
    running = cumsum(keep_mask.astype(jnp.int32))
    count = running[-1]
    j = jnp.arange(capacity, dtype=jnp.int32)
    dest = jnp.where(keep_mask, running - 1, count + j - running)
    perm = jnp.zeros((capacity,), jnp.int32).at[dest].set(
        j, unique_indices=True, mode="promise_in_bounds")
    return perm, count


def compact_cols(cols, keep_mask):
    """Stable-move surviving rows to the front. Returns (new_cols, new_count).

    One permutation (`front_perm`), then a gather a column through it; slots
    at and past the count read the dtype's default with validity false, which
    `maybe_host_resize` and the chain's `slice_to_capacity` rely on."""
    out, _, count = compact_cols_to(cols, keep_mask, keep_mask.shape[0])
    return out, count


@jax.named_scope("compact_cols")
def compact_cols_to(cols, keep_mask, cap: int, rows=()):
    """`compact_cols` landed at `cap` slots: the gathers run through the
    first `cap` slots of the permutation, so each costs `cap` indices, not
    the mask's capacity (a gather costs by its index count). Survivors past
    `cap` are dropped; `count` is taken over the whole mask, so a caller
    that predicted `cap` sees when it was too small. Only the join chain
    passes a `cap` below the mask's capacity: no other caller knows its
    output bucket inside its program.

    `rows` are int32 index arrays into another table (a join's build rows),
    moved with the kept rows and given no validity: the caller gathers
    through them with the same `arange(cap) < count`.
    Returns (new_cols, new_rows, count)."""
    perm, count = front_perm(keep_mask)
    perm = perm[:cap]
    live = jnp.arange(cap, dtype=jnp.int32) < count
    return gather_cols(cols, perm, live), [r[perm] for r in rows], count


@jax.named_scope("gather_cols")
def gather_cols(cols, indices, valid_out):
    """Gather rows by index (join/sort output). valid_out masks output
    slots. Each column costs two gathers (values, validity) of
    `len(indices)` rows, whatever the source's length: a caller that knows
    its output is short gathers at that length (`compact_cols_to`)."""
    out = []
    for c in cols:
        vals = c.values[indices]
        validity = c.validity[indices] & valid_out
        default = jnp.asarray(c.dtype.default_value(), dtype=vals.dtype)
        out.append(Col(jnp.where(validity, vals, default), validity, c.dtype,
                       c.dictionary))
    return out


def host_compact_cols(cols, keep_mask, min_shrink: int = 4):
    """Host-indexed stage-boundary compaction: sync the keep mask, gather the
    survivors into a RIGHT-SIZED capacity bucket.

    The in-program `compact_cols` pays a capacity-wide scatter + per-column
    gathers (~53 ms at 1M rows on XLA:CPU) and keeps the output at the INPUT
    capacity — a high-reduction stage (HAVING over a group-by, a selective
    filter) then drags that stale capacity through every downstream operator.
    One host round-trip (mask sync + np.nonzero, ~1 ms at 1M rows) instead
    yields the survivor indices, and a tiny gather program lands the output
    at bucket_capacity(count): the 3-row result of a 1M-capacity stage flows
    on at capacity 8 (measured ~50x on the compaction itself, and every
    downstream per-batch program shrinks with it).

    Returns (new_cols, count) or None when the output would not shrink by at
    least `min_shrink` (caller falls back to the in-program compact — for
    low-reduction stages the device path is the right one, and the sync
    would only serialize the pipeline)."""
    import numpy as np
    from spark_rapids_tpu.columnar.vector import bucket_capacity
    from spark_rapids_tpu.runtime import fuse

    with tracing.span("sync.count") as sp:
        keep = np.asarray(keep_mask)
        capacity = int(keep.shape[0])
        idx = np.nonzero(keep)[0]
        count = int(idx.size)
        sp.set(rows=count, capacity=capacity)
    out_cap = bucket_capacity(count)
    if out_cap * min_shrink > capacity:
        return None
    pad = np.zeros(out_cap, dtype=np.int32)
    pad[:count] = idx.astype(np.int32)
    idx_dev = jnp.asarray(pad)
    n_t = jnp.asarray(count, jnp.int32)
    key = ("host_compact", capacity, out_cap,
           tuple((c.dtype, str(c.values.dtype)) for c in cols))

    def build():
        def kernel(cols, indices, n):
            valid_out = jnp.arange(out_cap, dtype=jnp.int32) < n
            return gather_cols(cols, indices, valid_out)
        return kernel

    out = fuse.call_fused(key, "host_compact", build, (cols, idx_dev, n_t),
                          lambda: build()(cols, idx_dev, n_t))
    return out, count


def maybe_host_resize(cols, count, min_shrink: int = 4):
    """Re-land FRONT-COMPACTED columns (survivors first, tail invalid — the
    compact_cols output contract) at bucket_capacity(count): one host sync of
    the live count, then a tiny fused slice program. Returns (cols, n) with a
    HOST int count, or None when the input capacity is small or the shrink is
    under `min_shrink` (the sync would serialize the pipeline for nothing).

    This is the stage-boundary half of the host-compaction design: a
    high-reduction operator output stops dragging
    its stale input capacity through every downstream per-batch program."""
    from spark_rapids_tpu.columnar.vector import bucket_capacity
    from spark_rapids_tpu.runtime import fuse

    capacity = int(cols[0].values.shape[0])
    if capacity < (1 << 16):
        return None
    if isinstance(count, int):
        n = count
    else:
        with tracing.span("sync.count") as sp:
            n = int(count)
            sp.set(rows=n, capacity=capacity)
    out_cap = bucket_capacity(n)
    if out_cap * min_shrink > capacity:
        return None
    key = ("cap_slice", capacity, out_cap,
           tuple((c.dtype, str(c.values.dtype)) for c in cols))

    def build():
        def kernel(cols):
            return slice_to_capacity(cols, None, out_cap)
        return kernel

    out = fuse.call_fused(key, "cap_slice", build, (cols,),
                          lambda: slice_to_capacity(cols, n, out_cap))
    return out, n


def fused_compact_cols(cols, keep_mask):
    """compact_cols as its own fused program (device fallback for epilogues
    whose host-compaction path declined — see host_compact_cols)."""
    from spark_rapids_tpu.runtime import fuse
    capacity = int(keep_mask.shape[0])
    key = ("mask_compact", capacity,
           tuple((c.dtype, str(c.values.dtype)) for c in cols))

    def build():
        def kernel(cols, keep):
            return compact_cols(cols, keep)
        return kernel

    return fuse.call_fused(key, "mask_compact", build, (cols, keep_mask),
                           lambda: compact_cols(cols, keep_mask))


def slice_to_capacity(cols, count, new_capacity: int):
    """Shrink/grow the padded capacity (host-known count required)."""
    out = []
    for c in cols:
        if new_capacity <= c.values.shape[0]:
            vals = c.values[:new_capacity]
            validity = c.validity[:new_capacity]
        else:
            pad = new_capacity - c.values.shape[0]
            default = jnp.asarray(c.dtype.default_value(), dtype=c.values.dtype)
            vals = jnp.concatenate([c.values, jnp.full((pad,), default)])
            validity = jnp.concatenate([c.validity, jnp.zeros((pad,), jnp.bool_)])
        out.append(Col(vals, validity, c.dtype, c.dictionary))
    return out
