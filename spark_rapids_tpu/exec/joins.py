"""Join physical operators — shuffled-hash, broadcast-hash, nested-loop, cartesian.

Reference (SURVEY.md component #16): GpuHashJoin.scala:386 (`HashJoinIterator`:179
streams probe batches against a spillable built table), JoinGatherer.scala (bounded
gather-map iteration), GpuShuffledHashJoinBase.scala:97, shim GpuBroadcastHashJoinExec,
GpuBroadcastNestedLoopJoinExec.scala, GpuCartesianProductExec.scala.

The kernel side (ops/joining.py) replaces cudf's hash-table gather maps with a fused
rank-sort + searchsorted probe; this layer owns build-side materialization (single
spillable batch, like the reference's LazySpillableColumnarBatch build side), the
streamed probe loop, chunked output expansion, residual condition filtering, and
full-outer unmatched-build tracking across the whole stream.

Join type support matrix mirrors the reference (GpuHashJoin.tagJoin): equi-joins for
inner/left/right/full/semi/anti; residual conditions on inner only (the reference
falls conditional outer joins back to CPU / nested-loop); nested-loop handles cross
and conditional inner plus outer/semi/anti against a broadcast build side.
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (ColumnarBatch, batch_device,
                                             batch_to_device)
from spark_rapids_tpu.columnar.vector import TpuColumnVector, bucket_capacity
from spark_rapids_tpu.exec.base import TpuExec, TaskContext, acquire_semaphore
from spark_rapids_tpu.exec.coalesce import concat_all
from spark_rapids_tpu.expr.core import (Alias, BoundReference, Col,
                                        EvalContext, Expression,
                                        bind_references)
from spark_rapids_tpu.ops import joining as J
from spark_rapids_tpu.ops.filtering import (
    gather_cols, selection_mask, compact_cols, compact_cols_to,
    slice_to_capacity)
from spark_rapids_tpu.ops.strings import union_dictionaries
from spark_rapids_tpu.runtime import faults as F
from spark_rapids_tpu.runtime import memory as mem
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import retry as R
from spark_rapids_tpu.runtime import tracing
from spark_rapids_tpu.runtime.tracing import trace_range

# max pairs expanded per output chunk (the JoinGatherer row-target analog)
_MAX_CHUNK_ROWS = 1 << 20


def _align_string_keys(build_keys, stream_keys):
    out_b, out_s = [], []
    for b, s in zip(build_keys, stream_keys):
        if b.is_string:
            b, s = union_dictionaries(b, s)
        out_b.append(b)
        out_s.append(s)
    return out_b, out_s


def _null_extended(cols, idx, valid):
    """Gather `cols` rows by idx where valid, null otherwise (outer join side)."""
    return gather_cols(cols, idx, valid)


def _emit_pairs(join_type, stream_is_left, condition, preproject,
                stream_batch, build_batch, build_perm, lo, hi, counts, total,
                out_schema):
    """Pair-expansion emit shared by HashJoinExec and the join-chain fallback:
    expand in chunks (one fused program per chunk capacity), yield batches."""
    from spark_rapids_tpu.runtime import fuse
    with tracing.span("sync.count") as sp:
        total = int(total)
        sp.set(pairs=total)
    semi_anti = join_type in (J.LEFT_SEMI, J.LEFT_ANTI)
    cond = condition
    cond_key = fuse.expr_key(cond) if cond is not None else None
    out_key = fuse.schema_key(out_schema)
    pos = 0
    while pos < total:
        out_cap = bucket_capacity(min(total - pos, _MAX_CHUNK_ROWS))

        def kernel(build_perm, lo, hi, counts, s_in, b_in, start, n_out,
                   _cap=out_cap):
            s_idx, b_idx, b_matched, live = J.expand_pairs(
                build_perm, lo, hi, counts, start, _cap)
            s_cols = gather_cols(s_in, s_idx, live)
            if preproject is not None:
                with jax.named_scope("ProjectExec"):
                    pctx = EvalContext(s_cols, n_out, _cap)
                    s_cols = [e.eval(pctx) for e in preproject]
            if semi_anti:
                cols = s_cols
            else:
                b_cols = _null_extended(b_in, b_idx, b_matched)
                cols = (s_cols + b_cols) if stream_is_left else (b_cols + s_cols)
            if cond is not None:
                with jax.named_scope("condition"):
                    ctx = EvalContext(cols, n_out, _cap)
                    pred = cond.eval(ctx)
                    keep = pred.values & pred.validity & live
                    return compact_cols(cols, keep)
            return cols, None

        key = ("join_emit", semi_anti, stream_is_left, out_cap,
               cond_key, out_key,
               tuple(fuse.expr_key(e) for e in preproject)
               if preproject is not None else None)
        s_in = [Col.from_vector(c) for c in stream_batch.columns]
        b_in = ([] if semi_anti else
                [Col.from_vector(c) for c in build_batch.columns])
        start = jnp.asarray(pos, jnp.int32)
        n_out_t = jnp.asarray(min(total - pos, out_cap), jnp.int32)
        args = (build_perm, lo, hi, counts, s_in, b_in, start, n_out_t)
        cols, count = fuse.call_fused(key, "HashJoin.emit",
                                      lambda: kernel, args,
                                      lambda: kernel(*args))
        n_out = min(total - pos, out_cap) if count is None else count
        yield ColumnarBatch([c.to_vector() for c in cols], n_out, out_schema)
        pos += out_cap


def _dense_table(sorted_keys, n_valid, *, slots: int):
    """The direct-address table of a sorted unique build: slot `packed key`
    holds the key's position in the sorted build, the rest -1. One scatter a
    build; the sorted distinct keys give ascending distinct slots, which XLA
    is told, and the tail past `n_valid` continues beyond the table and is
    dropped."""
    i = jnp.arange(sorted_keys.shape[0], dtype=jnp.int32)
    slot = jnp.where(i < n_valid, sorted_keys.astype(jnp.int32), slots + i)
    return jnp.full((slots,), -1, jnp.int32).at[slot].set(
        i, mode="drop", indices_are_sorted=True, unique_indices=True)


def _int_backed(dtype) -> bool:
    """Orderable fixed-point key: comparisons over raw device values ARE key
    comparisons (unlike string codes, which are only comparable under one
    shared dictionary, or floats, which need NaN totalization)."""
    return isinstance(dtype, (T.IntegralType, T.BooleanType, T.DateType,
                              T.TimestampType, T.DecimalType))


def _pack_keys(keys, lims):
    """Integer key columns -> `(packed, ok)`: the tuple as ONE int64,
    `(..(k_0 - vmin_0) * range_1 + (k_1 - vmin_1)..) * range_n + ..`, a
    bijection from the build's key domain `prod [vmin_i, vmax_i]` onto
    `[0, domain)` that keeps the tuples' order (the first key is the most
    significant), and whether a row has every key valid and inside its
    range. `lims` is an int64 operand, `lims[0]` the vmin and `lims[1]` the
    vmax of each key: the ranges shape no program, and a single key is
    `k - vmin` with no multiply (the compiler folds `0 * range`). Every key
    widens to int64 (never the stream down to the build: that wraps values
    and fabricates matches), and each range test compares and never
    subtracts first, so no key wraps into the domain and no tuple outside
    one key's range aliases a packed value inside it: such a row packs to
    some in-domain value, safe as an index, under `ok` False."""
    packed = jnp.zeros(keys[0].values.shape, jnp.int64)
    ok = jnp.ones(keys[0].values.shape, jnp.bool_)
    for i, k in enumerate(keys):
        v = k.values.astype(jnp.int64)    # bool as 0 / 1
        vmin, vmax = lims[0, i], lims[1, i]
        inside = (v >= vmin) & (v <= vmax)
        ok = ok & inside & k.validity
        packed = packed * (vmax - vmin + 1) + jnp.where(inside, v - vmin, 0)
    return packed, ok


class _ProbeArgs(NamedTuple):
    """Traced operands of a packed-key probe (`_JoinCore.chain_args`)."""
    sorted_build: jax.Array     # the build's packed keys, ascending
    n_valid: jax.Array          # how many of them are keys (the rest: tail)
    perm: jax.Array             # sorted position -> build row
    lims: jax.Array             # each key's vmin and vmax (`_pack_keys`)
    table: tuple                # (the dense table,) in mode `dense`, else ()


class _JoinCore:
    """Shared probe machinery over one materialized build batch.

    Joins whose keys are all fixed-point (one key or several) take a FAST
    path: the key tuple is packed into one int64 (`_pack_keys`), the build
    side is sorted ONCE by it (rows without a key sent past the valid
    count), and each stream batch probes it by direct address or by
    searchsorted (`_prep_fast_build` picks the mode from the build) — no
    per-batch re-sort of build+stream. The rank path (ops/joining.py, a
    multi-key sort over both sides per stream batch) keeps what cannot be
    packed: a key that is no integer, a key that reads the batch's context,
    a build whose key domain passes 2^62."""

    def __init__(self, build_batch: ColumnarBatch, build_key_exprs,
                 stream_key_exprs, join_type: str, stream_prefilter=None):
        from spark_rapids_tpu.runtime import fuse
        self.build_batch = build_batch
        self.build_key_exprs = build_key_exprs
        self.stream_key_exprs = stream_key_exprs
        self.join_type = join_type
        # hoisted stream-side filter (inner joins only — the planner
        # guarantees that): the predicate masks probe rows in-kernel, so
        # filtered rows emit zero pairs without a separate FilterExec
        # dispatch + compaction (whole-stage-codegen role)
        self.stream_prefilter = stream_prefilter
        from spark_rapids_tpu.expr.misc import CONTEXT_SENSITIVE
        bctx = EvalContext.from_batch(build_batch)
        self.build_keys_raw = [e.eval(bctx) for e in build_key_exprs]
        self.n_build = build_batch.num_rows
        self.build_cap = build_batch.capacity
        # stream keys reading per-batch context (input_file_name family etc.)
        # cannot be baked into a shared compiled program
        self.ctx_sensitive = any(
            e.collect(lambda x: isinstance(x, CONTEXT_SENSITIVE))
            for e in (*stream_key_exprs,
                      *([stream_prefilter] if stream_prefilter is not None
                        else [])))
        self._stream_key_key = (tuple(
            fuse.expr_key(e) for e in stream_key_exprs),
            fuse.expr_key(stream_prefilter)
            if stream_prefilter is not None else None)
        # matched-build tracking for full outer (host accumulation across stream)
        self.build_matched_acc = (np.zeros(self.build_cap, dtype=bool)
                                  if join_type == J.FULL_OUTER else None)
        self.fast = all(_int_backed(b.dtype) and _int_backed(s.dtype)
                        for b, s in zip(self.build_keys_raw, stream_key_exprs))
        # the hoisting planner rule guarantees these; the eager probe path
        # does not evaluate the prefilter, the rank path only for an inner
        assert stream_prefilter is None or (join_type == J.INNER
                                            and not self.ctx_sensitive)
        self._probe_mode = "rank"
        self.domain = self.table_slots = 0    # of the fast path's key domain
        with tracing.span("HashJoin.build_prep") as sp:
            if self.fast:
                self._prep_fast_build()
            sp.set(mode=self.mode, keys=len(self.build_keys_raw),
                   rows=self.n_build, capacity=self.build_cap,
                   domain=self.domain, table_slots=self.table_slots)

    @property
    def mode(self) -> str:
        """How a stream batch finds its build rows: `dense` / `one` / `two`
        over the build sorted once by its packed key, `rank` for keys that
        are no integers, a key domain too wide to pack, or keys that read
        the batch's context."""
        return "rank" if self.ctx_sensitive else self._probe_mode

    def _prep_fast_build(self):
        """Pack the integer build keys into one and sort it once. Everything
        is read from the build batch (one cheap reduction + host sync per
        build, like the reference's one-time build-table materialization):

        - each key's [vmin, vmax] over the rows that have every key gives
          the domain, the product of the ranges. A domain
          that fits beside a row index in 62 bits is packed, and sorted by
          ONE-operand int64 sort of (packed key << idx_bits | row_idx), ~8x
          cheaper than a 3-operand comparator sort (an XLA:CPU
          measurement); a wider one leaves the build on the rank path.
        - afterwards, uniqueness + compact domain decide the probe mode, on
          every backend: dense direct-address rank table (one gather per
          stream row), unique single-searchsorted, or the general
          two-searchsorted. Timed on a v5e at SF 1 shapes (PERF.md section 6,
          PR 30): the table's scatter 0.7 to 1.9 ms a build, a dense lookup
          of 1 Mi stream keys 8.5 ms, the searchsorted it replaces 240 to
          480 ms."""
        from spark_rapids_tpu.runtime import fuse
        keys = self.build_keys_raw
        cap = self.build_cap
        idx_bits = max(int(cap - 1).bit_length(), 1)

        def eligible_rows(keys, n_build):
            eligible = jnp.arange(cap, dtype=jnp.int32) < n_build
            for k in keys:
                eligible = eligible & k.validity
            return eligible

        def stats(keys, n_build):
            eligible = eligible_rows(keys, n_build)
            i64 = jnp.iinfo(jnp.int64)
            vals = [k.values.astype(jnp.int64) for k in keys]
            lims = jnp.stack([
                jnp.stack([jnp.min(jnp.where(eligible, v, i64.max))
                           for v in vals]),
                jnp.stack([jnp.max(jnp.where(eligible, v, i64.min))
                           for v in vals])])
            return lims, jnp.sum(eligible, dtype=jnp.int32)

        dtypes = tuple(k.dtype for k in keys)
        n_build_t = jnp.asarray(self.n_build, jnp.int32)
        lims_t, n_valid = fuse.call_fused(
            ("join_build_stats", dtypes, cap), "HashJoin.build_stats",
            lambda: stats, (keys, n_build_t), lambda: stats(keys, n_build_t))
        lims = np.asarray(lims_t).tolist()    # one host sync per build
        self.domain = domain = math.prod(
            max(hi - lo + 1, 0) for lo, hi in zip(*lims))
        # the packed key and the ineligible rows' tail (`domain`, above every
        # real key) sit beside a row index in one non-negative int64
        if domain >= 1 << (62 - idx_bits):
            return                                       # mode `rank`
        # vmin and vmax are operands: the key ranges of the data shape no
        # program
        self._lims = lims_t

        def prep(keys, n_build, n_valid, lims, tail):
            rel = jnp.where(eligible_rows(keys, n_build),
                            _pack_keys(keys, lims)[0], tail)
            packed = (rel << idx_bits) | jnp.arange(cap, dtype=jnp.int64)
            # one operand of distinct values: stability buys nothing
            s = jax.lax.sort(packed, is_stable=False)
            perm = (s & ((1 << idx_bits) - 1)).astype(jnp.int32)
            sorted_keys = s >> idx_bits
            same = sorted_keys[1:] == sorted_keys[:-1]
            in_valid = (jnp.arange(cap - 1, dtype=jnp.int32) + 1) < n_valid
            return sorted_keys, perm, ~jnp.any(same & in_valid)

        args = (keys, n_build_t, n_valid, lims_t, np.int64(domain))
        self._sorted_build, self._build_perm, uniq_t = fuse.call_fused(
            ("join_build_pack", dtypes, cap), "HashJoin.build_prep",
            lambda: prep, args, lambda: prep(*args))
        self._n_valid = n_valid
        # probe-mode choice, from what the build shows (uniqueness, key
        # domain, capacity) and on every backend — static per compiled probe
        # kernel. Different needs, not knobs: "two" for duplicate keys, "one"
        # for a unique build whose domain is over the budget, "dense" below it
        unique = bool(uniq_t) if self.n_build > 0 else True
        self._probe_mode = "two"
        self._dense_table = ()
        if unique and self.build_matched_acc is None:
            self._probe_mode = "one"
            # the direct-address table covers the domain, its length rounded
            # up to a bucket so the programs that take it are shaped by the
            # bucket and not by the data; the budget is a power of two, so a
            # domain under it has its bucket under it too
            slots = bucket_capacity(domain)
            # slot numbers (the dropped tail's too) are int32
            if domain <= max(4 * cap, 1 << 22) and slots + cap < (1 << 31):
                # direct-address rank table: ONE scatter a build, one gather
                # a probe row where "one" pays a log2(capacity)+1-step
                # searchsorted loop over 64-bit halves a stream batch
                self._probe_mode = "dense"
                self.table_slots = slots
                mktable = functools.partial(_dense_table, slots=slots)
                targs = (self._sorted_build, n_valid)
                self._dense_table = (fuse.call_fused(
                    ("join_dense_table", slots), "HashJoin.dense_table",
                    lambda: mktable, targs, lambda: mktable(*targs)),)

    def probe_batch(self, stream_batch: ColumnarBatch):
        from spark_rapids_tpu.runtime import fuse
        # from the stream (preserved) side's perspective, right/full outer are a
        # left outer over the swapped/streamed input
        jt = (J.LEFT_OUTER if self.join_type in (J.FULL_OUTER, J.RIGHT_OUTER)
              else self.join_type)
        track_matched = self.build_matched_acc is not None
        stream_key_exprs = self.stream_key_exprs
        stream_prefilter = self.stream_prefilter
        if self.ctx_sensitive:
            return self._probe_batch_eager(stream_batch, jt, track_matched)
        if self._probe_mode != "rank":
            return self._probe_batch_fast(stream_batch, jt, track_matched)

        def kernel(build_keys_raw, n_build, stream_cols, n_stream):
            scap = stream_cols[0].values.shape[0]
            sctx = EvalContext(stream_cols, n_stream, scap)
            stream_keys = [e.eval(sctx) for e in stream_key_exprs]
            build_keys, stream_keys = _align_string_keys(build_keys_raw,
                                                         stream_keys)
            b_ranks, s_ranks = J.join_ranks(
                build_keys, n_build, build_keys[0].values.shape[0],
                stream_keys, n_stream, scap)
            build_perm, lo, hi = J.probe(b_ranks, s_ranks)
            if stream_prefilter is not None:    # inner: no pair, no row
                hi = jnp.where(selection_mask(stream_prefilter.eval(sctx),
                                              n_stream, scap), hi, lo)
            counts = J.pair_counts(lo, hi, n_stream, scap, jt)
            total = J.total_pairs(counts)
            if track_matched:
                # symmetric probe: which build rows matched this stream batch
                _, blo, bhi = J.probe(s_ranks, b_ranks)
                return build_perm, lo, hi, counts, total, (bhi - blo) > 0
            return build_perm, lo, hi, counts, total, None

        key = ("join_probe", jt, track_matched, self._stream_key_key,
               fuse.schema_key(stream_batch.schema)
               if stream_batch.schema else None)
        stream_cols = [Col.from_vector(c) for c in stream_batch.columns]
        n_build = jnp.asarray(self.n_build, jnp.int32)
        n_stream = jnp.asarray(stream_batch.lazy_num_rows, jnp.int32)
        build_perm, lo, hi, counts, total, matched = fuse.call_fused(
            key, "HashJoin.probe", lambda: kernel,
            (self.build_keys_raw, n_build, stream_cols, n_stream),
            lambda: kernel(self.build_keys_raw, n_build, stream_cols,
                           n_stream))
        if track_matched:
            self._sync_matched(matched)
        return build_perm, lo, hi, counts, total

    def _sync_matched(self, matched) -> None:
        """Fold one stream batch's matched-build-rows mask into the host
        accumulator: a blocking device-to-host read per batch."""
        with tracing.span("sync.matched") as sp:
            self.build_matched_acc |= np.asarray(matched)
            sp.set(rows=self.n_build, capacity=int(matched.shape[0]))

    def _probe_batch_eager(self, stream_batch, jt, track_matched):
        """Context-sensitive stream keys: evaluate with the batch's full
        context (scan provenance etc.) — never through a shared compiled
        program."""
        sctx = EvalContext.from_batch(stream_batch)
        stream_keys = [e.eval(sctx) for e in self.stream_key_exprs]
        build_keys, stream_keys = _align_string_keys(self.build_keys_raw,
                                                     stream_keys)
        b_ranks, s_ranks = J.join_ranks(
            build_keys, self.n_build, self.build_cap,
            stream_keys, stream_batch.lazy_num_rows, stream_batch.capacity)
        build_perm, lo, hi = J.probe(b_ranks, s_ranks)
        counts = J.pair_counts(lo, hi, stream_batch.lazy_num_rows,
                               stream_batch.capacity, jt)
        total = J.total_pairs(counts)
        if track_matched:
            _, blo, bhi = J.probe(s_ranks, b_ranks)
            self._sync_matched((bhi - blo) > 0)
        return build_perm, lo, hi, counts, total

    def _probe_batch_fast(self, stream_batch, jt, track_matched):
        """Pre-sorted-build probe over the packed key. Modes (chosen at
        build, static per compiled kernel): "dense" = O(1) direct-address
        rank-table gather (unique keys, compact domain); "one" = single
        searchsorted + equality (unique keys); "two" = general left+right
        searchsorted."""
        from spark_rapids_tpu.runtime import fuse
        stream_key_exprs = self.stream_key_exprs
        mode = self._probe_mode
        stream_prefilter = self.stream_prefilter
        find = self._find()

        def kernel(cargs, n_build, build_keys_raw, stream_cols, n_stream):
            scap = stream_cols[0].values.shape[0]
            sctx = EvalContext(stream_cols, n_stream, scap)
            packed, ok = _pack_keys([e.eval(sctx) for e in stream_key_exprs],
                                    cargs.lims)
            if stream_prefilter is not None:
                ok = ok & selection_mask(stream_prefilter.eval(sctx),
                                         n_stream, scap)
            else:
                ok = ok & (jnp.arange(scap, dtype=jnp.int32) < n_stream)
            lo, hi = find(cargs, packed, ok)
            counts = J.pair_counts(lo, hi, n_stream, scap, jt)
            total = J.total_pairs(counts)
            if track_matched:
                # which eligible build rows matched: probe the sorted stream
                # by the build's packed keys (under the packed budget, so
                # the sentinel of a row without a key is above them all)
                s_sorted = jax.lax.sort(
                    jnp.where(ok, packed, jnp.iinfo(jnp.int64).max),
                    is_stable=False)
                ns = jnp.sum(ok, dtype=jnp.int32)
                bvals, b_ok = _pack_keys(build_keys_raw, cargs.lims)
                blo = jnp.minimum(
                    jnp.searchsorted(s_sorted, bvals, side="left"), ns)
                bhi = jnp.minimum(
                    jnp.searchsorted(s_sorted, bvals, side="right"), ns)
                b_ok = b_ok & (jnp.arange(bvals.shape[0], dtype=jnp.int32)
                               < n_build)
                return lo, hi, counts, total, (bhi > blo) & b_ok
            return lo, hi, counts, total, None

        # the key ranges and the dense table's length are operands: a
        # build's key domain shapes no program (jit specialises on the
        # table's bucket)
        key = ("join_probe_fast", jt, track_matched, mode,
               self._stream_key_key,
               fuse.schema_key(stream_batch.schema)
               if stream_batch.schema else None)
        stream_cols = [Col.from_vector(c) for c in stream_batch.columns]
        n_stream = jnp.asarray(stream_batch.lazy_num_rows, jnp.int32)
        args = (self.chain_args(), jnp.asarray(self.n_build, jnp.int32),
                self.build_keys_raw, stream_cols, n_stream)
        lo, hi, counts, total, matched = fuse.call_fused(
            key, "HashJoin.probe", lambda: kernel, args,
            lambda: kernel(*args))
        if track_matched:
            self._sync_matched(matched)
        return self._build_perm, lo, hi, counts, total

    def _find(self):
        """Traceable `(chain_args, packed stream key, ok) -> (lo, hi)`: the
        range of sorted-build positions a stream row matches, empty where
        `ok` (every key valid and in the build's domain, the row live) is
        False. One body a mode, for the probe kernel and the chain."""
        mode = self._probe_mode

        def find(cargs, packed, ok):
            sorted_build, n_valid = cargs.sorted_build, cargs.n_valid
            if mode == "dense":
                # one gather a stream row; -1 where the build has no such key
                lo = cargs.table[0][packed.astype(jnp.int32)]
                found = ok & (lo >= 0)
                # the position depends on the table's gather alone, not on
                # the mask: with a select between them the chip's compiler
                # left the chain's position->row table in HBM and its gather
                # took 36 ms a 1 Mi rows for 9 (PERF.md section 6, PR 34)
                lo = jnp.maximum(lo, 0)
            else:
                lo = jnp.minimum(
                    jnp.searchsorted(sorted_build, packed, side="left"),
                    n_valid).astype(jnp.int32)
                if mode == "two":
                    hi = jnp.minimum(
                        jnp.searchsorted(sorted_build, packed, side="right"),
                        n_valid).astype(jnp.int32)
                    return lo, jnp.where(ok, hi, lo)
                at = sorted_build[jnp.clip(lo, 0, sorted_build.shape[0] - 1)]
                found = ok & (at == packed) & (lo < n_valid)
            return lo, jnp.where(found, lo + 1, lo)

        return find

    # -- whole-stage join-chain surface (BroadcastHashJoinChainExec) ---------

    def chain_capable(self) -> bool:
        """True when this core's probe matches AT MOST ONE build row per
        stream row through a shared compiled program — the property that lets
        a stack of joins fuse into one static-shape per-batch kernel (output
        rows <= stream rows, so stream capacity bounds every hop)."""
        return self.mode in ("dense", "one")

    def chain_static(self):
        """Kernel-key part: everything `chain_lookup` bakes into the trace
        (the key ranges and the dense table's length are operands)."""
        return self._probe_mode

    def chain_args(self):
        """Traced operands of the probe: the sorted packed build, its valid
        count, the position->row permutation, the keys' ranges (`_pack_keys`'s
        operand) and the dense table, empty outside mode `dense`."""
        return _ProbeArgs(self._sorted_build, self._n_valid,
                          self._build_perm, self._lims, self._dense_table)

    def chain_lookup(self):
        """Traceable single-match probe `(chain_args, stream key cols, live)
        -> (build_row, hit)`: the unique-match modes of `_find`, with the
        position->row mapping through `_build_perm` folded in (expand_pairs
        does that mapping on the unfused path). `hit` holds every key's
        validity and the domain test."""
        find = self._find()

        def lookup(cargs, keys, live):
            packed, ok = _pack_keys(keys, cargs.lims)
            lo, hi = find(cargs, packed, ok & live)
            hit = hi > lo
            row = cargs.perm[jnp.clip(lo, 0, cargs.perm.shape[0] - 1)]
            return jnp.where(hit, row, 0).astype(jnp.int32), hit

        return lookup

    def unmatched_build_indices(self):
        assert self.build_matched_acc is not None
        live = np.arange(self.build_cap) < self.n_build
        return np.nonzero(live & ~self.build_matched_acc)[0]

    # Retryable (reference trait behind withRestoreOnRetry): the matched-row
    # accumulator is the core's only cross-batch mutable state — a probe
    # attempt that OOMs after updating it must roll back before the split
    # pieces re-probe
    def checkpoint(self):
        self._matched_ckpt = (None if self.build_matched_acc is None
                              else self.build_matched_acc.copy())

    def restore(self):
        if getattr(self, "_matched_ckpt", None) is not None:
            self.build_matched_acc = self._matched_ckpt.copy()


class HashJoinExec(TpuExec):
    """Equi-join with a materialized build side (reference GpuShuffledHashJoinBase:97;
    children are co-partitioned by upstream exchanges)."""

    def __init__(self, join_type: str, left_keys, right_keys,
                 left: TpuExec, right: TpuExec, condition: Expression | None = None,
                 build_side: str = "right", conf=None, stream_prefilter=None,
                 stream_preproject=None, stream_schema=None):
        super().__init__(left, right, conf=conf)
        # whole-stage hoists (planner-controlled, inner single-int-key joins
        # only): `stream_prefilter` masks probe rows against the RAW stream
        # child; `stream_preproject` re-derives the hoisted projection on
        # post-join gathered rows in the emit kernel; `stream_schema` is the
        # hoisted projection's output schema (the join's stream-side
        # contribution, since the raw child is now wider)
        self.stream_prefilter = stream_prefilter
        self.stream_preproject = (list(stream_preproject)
                                  if stream_preproject is not None else None)
        self._stream_schema = stream_schema
        jt = join_type.lower().replace("_", "")
        self.join_type = jt
        if jt not in (J.INNER, J.LEFT_OUTER, J.RIGHT_OUTER, J.FULL_OUTER,
                      J.LEFT_SEMI, J.LEFT_ANTI):
            # CROSS must go through NestedLoopJoinExec: the hash-probe kernel has
            # no all-pairs mode, so accepting it here would only fail at run time
            raise ValueError(f"unsupported join type {join_type}")
        if condition is not None and jt not in (J.INNER, J.CROSS):
            # reference: conditional outer joins are not supported by GpuHashJoin
            # (GpuHashJoin.tagJoin) — the planner must fall back / use nested loop
            raise ValueError("residual join conditions only supported for inner joins")
        self.left_keys = [bind_references(k, left.output) for k in left_keys]
        self.right_keys = [bind_references(k, right.output) for k in right_keys]
        # which side streams: the preserved side streams; the other side builds
        if jt == J.RIGHT_OUTER:
            self.stream_is_left = False
        elif jt == J.INNER and build_side == "left":
            self.stream_is_left = False
        else:
            self.stream_is_left = True
        self.condition = (bind_references(condition, self.output)
                          if condition is not None else None)
        self._build_time = self.metrics.metric(M.BUILD_TIME, M.MODERATE)
        self._join_time = self.metrics.metric(M.JOIN_TIME, M.MODERATE)

    @property
    def output(self) -> T.StructType:
        lf, rf = list(self.children[0].output), list(self.children[1].output)
        if self._stream_schema is not None:
            if self.stream_is_left:
                lf = list(self._stream_schema)
            else:
                rf = list(self._stream_schema)
        if self.join_type in (J.LEFT_SEMI, J.LEFT_ANTI):
            return T.StructType(lf)
        # outer joins make the non-preserved side nullable
        if self.join_type in (J.LEFT_OUTER, J.FULL_OUTER):
            rf = [T.StructField(f.name, f.data_type, True) for f in rf]
        if self.join_type in (J.RIGHT_OUTER, J.FULL_OUTER):
            lf = [T.StructField(f.name, f.data_type, True) for f in lf]
        return T.StructType(lf + rf)

    @property
    def num_partitions(self):
        return (self.children[0] if self.stream_is_left else self.children[1]).num_partitions

    def _emit(self, stream_batch, build_batch, core, build_perm, lo, hi, counts,
              total, out_schema):
        """Expand pairs in chunks (one fused program per chunk capacity) and
        yield output batches."""
        yield from _emit_pairs(
            self.join_type, self.stream_is_left, self.condition,
            self.stream_preproject, stream_batch, build_batch, build_perm,
            lo, hi, counts, total, out_schema)

    def _probe_stream(self, core, sb, stream_child, split, out_schema):
        """Probe+emit loop shared by the shuffled and broadcast variants,
        under the task-scoped OOM ladder: each stream batch probes inside
        with_retry (an OOM spills, splits the stream batch and re-probes the
        halves — the reference withRetry over the stream iterator) with the
        matched-row accumulator checkpointed per attempt."""
        def probe(b):
            with trace_range("HashJoin.probe", self._join_time,
                             mode=core.mode), \
                    R.with_restore_on_retry(core):
                return b, core.probe_batch(b)

        # observed stream-side input cardinality (stats plane): out rows /
        # probe rows is the join's selectivity read-out
        in_rows = self.metrics.metric(M.NUM_INPUT_ROWS, M.ESSENTIAL)
        for stream_batch in stream_child.execute_partition(split):
            in_rows.add_lazy(stream_batch.lazy_num_rows)
            acquire_semaphore(self.metrics)
            for piece, (build_perm, lo, hi, counts, total) in R.with_retry(
                    [stream_batch], probe, conf=self.conf,
                    scope="joins.gather"):
                yield from self._emit(piece, sb.get_batch(), core,
                                      build_perm, lo, hi, counts, total,
                                      out_schema)

    def execute_partition(self, split):
        def it():
            build_child = self.children[1] if self.stream_is_left else self.children[0]
            stream_child = self.children[0] if self.stream_is_left else self.children[1]
            # nested attribution frame: the build's own work (concat +
            # spillable registration, minus child pulls) lands in
            # buildSelfTime and is subtracted from this join's selfTime, so
            # the profiler can render the build as a distinct line item
            # without double counting (buildTime stays the INCLUSIVE timer)
            with contextlib.ExitStack() as held:
                with trace_range("HashJoin.build", self._build_time):
                    with M.node_frame(self._node_id,
                                      self.metrics.metric(M.BUILD_SELF_TIME,
                                                          M.MODERATE)), \
                            F.scope("joins.build"):
                        from spark_rapids_tpu.runtime import pipeline as P
                        build_it = build_child.execute_partition(split)
                        if P.enabled(self.conf):
                            # build-segment boundary: the build subtree (scan
                            # + upstream operators) produces on the stage's
                            # worker thread while this thread
                            # registers/concats
                            build_it = P.stage_iterator(
                                build_it, edge="join.build", conf=self.conf,
                                registry=self.metrics,
                                node_id=getattr(build_child, "_node_id",
                                                None),
                                spillable=True)
                        build_batch = concat_all(build_it, build_child.output,
                                                 conf=self.conf)
                        # hold the built table spillable while we stream
                        # (reference LazySpillableColumnarBatch,
                        # GpuHashJoin.scala:200); the single-batch
                        # registration cannot split — spill-only retry
                        sb = held.enter_context(R.call_with_retry(
                            lambda: mem.SpillableColumnarBatch(
                                build_batch, mem.ACTIVE_BATCHING_PRIORITY),
                            scope="joins.build"))
                    # the sorted build and its table belong to the build's
                    # span (`HashJoin.build_prep` is its child), not to its
                    # fault scope or its self-time frame
                    bk = self.left_keys if not self.stream_is_left else self.right_keys
                    sk = self.right_keys if not self.stream_is_left else self.left_keys
                    core = _JoinCore(sb.get_batch(), bk, sk, self.join_type,
                                     stream_prefilter=self.stream_prefilter)
                out_schema = self.output
                yield from self._probe_stream(core, sb, stream_child, split,
                                              out_schema)
                if self.join_type == J.FULL_OUTER:
                    yield from self._emit_unmatched_build(core, sb.get_batch(),
                                                          out_schema)
        return self.wrap_output(it())

    def _emit_unmatched_build(self, core, build_batch, out_schema):
        idxs = core.unmatched_build_indices()
        if len(idxs) == 0:
            return
        n = len(idxs)
        cap = bucket_capacity(n)
        idx_dev = jnp.zeros((cap,), jnp.int32).at[:n].set(jnp.asarray(idxs, jnp.int32))
        live = jnp.arange(cap) < n
        b_cols = gather_cols([Col.from_vector(c) for c in build_batch.columns],
                             idx_dev, live)
        stream_child = self.children[0] if self.stream_is_left else self.children[1]
        s_cols = [Col(jnp.full((cap,), f.data_type.default_value(),
                               dtype=f.data_type.jnp_dtype),
                      jnp.zeros((cap,), jnp.bool_), f.data_type)
                  for f in stream_child.output]
        cols = (s_cols + b_cols) if self.stream_is_left else (b_cols + s_cols)
        yield ColumnarBatch([c.to_vector() for c in cols], n, out_schema)

    def args_string(self):
        return (f"{self.join_type} lk={self.left_keys} rk={self.right_keys}"
                + (f" cond={self.condition}" if self.condition is not None else ""))


class _SharedBroadcast:
    """Per-join consumer state over a BroadcastExchangeExec relation: a
    reader countdown (the LAST stream partition releases the relation) and a
    globally-merged matched-row accumulator so full-outer unmatched-build
    rows are emitted exactly once (reference GpuBroadcastExchangeExec + the
    shared gatherer state in GpuBroadcastNestedLoopJoinExec)."""

    def __init__(self, exchange, n_readers: int):
        from spark_rapids_tpu.exec.broadcast import BroadcastExchangeExec
        assert isinstance(exchange, BroadcastExchangeExec), exchange
        self.exchange = exchange
        self._lock = threading.Lock()
        self._readers_left = n_readers
        self.matched_acc: np.ndarray | None = None

    def get(self) -> mem.SpillableColumnarBatch:
        return self.exchange.broadcast()

    def merge_matched(self, local: np.ndarray) -> None:
        with self._lock:
            if self.matched_acc is None:
                self.matched_acc = np.zeros_like(local)
            np.logical_or(self.matched_acc, local, out=self.matched_acc)

    def finish(self) -> bool:
        """Count down one reader; True for the last one (who must close())."""
        with self._lock:
            self._readers_left -= 1
            return self._readers_left == 0

    def close(self) -> None:
        self.exchange.release()

    def reader(self):
        """Per-reader idempotent countdown handle: `finish_once()` counts
        this reader down at most once, True for the last reader overall.
        Consumers call it on the NORMAL path (to emit full-outer unmatched
        rows before closing) AND from a finally (so a stream partition
        abandoned mid-iteration — downstream limit, error, cooperative
        cancellation draining the pipeline — still releases the broadcast
        relation instead of leaking it in HBM)."""
        shared = self

        class _Reader:
            __slots__ = ("_counted",)

            def __init__(self):
                self._counted = False

            def finish_once(self) -> bool:
                if self._counted:
                    return False
                self._counted = True
                return shared.finish()

        return _Reader()


class BroadcastHashJoinExec(HashJoinExec):
    """Build side is broadcast (materialized once, shared across stream partitions)
    — reference shim GpuBroadcastHashJoinExec + GpuBroadcastExchangeExec."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        from spark_rapids_tpu.exec.broadcast import BroadcastExchangeExec
        bi = 1 if self.stream_is_left else 0
        exchange = BroadcastExchangeExec(self.children[bi], conf=self.conf)
        self.children[bi] = exchange  # plan-visible broadcast exchange node
        self._shared = _SharedBroadcast(exchange, self.num_partitions)

    def execute_partition(self, split):
        def it():
            reader = self._shared.reader()
            try:
                stream_child = self.children[0] if self.stream_is_left else self.children[1]
                bk = self.left_keys if not self.stream_is_left else self.right_keys
                sk = self.right_keys if not self.stream_is_left else self.left_keys
                with trace_range("BroadcastHashJoin.build", self._build_time):
                    sb = self._shared.get()
                    core = _JoinCore(sb.get_batch(), bk, sk, self.join_type,
                                     stream_prefilter=self.stream_prefilter)
                out_schema = self.output
                yield from self._probe_stream(core, sb, stream_child, split,
                                              out_schema)
                if core.build_matched_acc is not None:
                    self._shared.merge_matched(core.build_matched_acc)
                if reader.finish_once():
                    if self.join_type == J.FULL_OUTER:
                        core.build_matched_acc = self._shared.matched_acc
                        yield from self._emit_unmatched_build(
                            core, sb.get_batch(), out_schema)
                    self._shared.close()
            finally:
                # abandoned mid-stream (limit / error / cancellation): still
                # count this reader down so the LAST one out releases the
                # broadcast relation instead of leaking it in HBM
                if reader.finish_once():
                    self._shared.close()
        return self.wrap_output(it())


def _chainable(node) -> bool:
    """A broadcast hash join the chain fuser may absorb: inner, single
    int-backed equi key, no residual condition, every hoisted term
    context-free — the static half of the contract (`_JoinCore.chain_capable`
    checks the build-content half at run time)."""
    from spark_rapids_tpu.expr.misc import is_context_free
    return (type(node) is BroadcastHashJoinExec
            and node.join_type == J.INNER and node.condition is None
            and len(node.left_keys) == 1
            and _int_backed(node.left_keys[0].dtype)
            and _int_backed(node.right_keys[0].dtype)
            and is_context_free(*node.left_keys, *node.right_keys)
            and (node.stream_prefilter is None
                 or is_context_free(node.stream_prefilter))
            and (node.stream_preproject is None
                 or is_context_free(*node.stream_preproject)))


def _plain_ref(e):
    """The ordinal a projection term passes through unchanged (a column
    reference, aliased or not), else None."""
    while isinstance(e, Alias):
        e = e.child
    return e.ordinal if isinstance(e, BoundReference) else None


def _chain_plan(specs, n_stream, widths):
    """Where the fused chain gathers each build column: (at_hop, late).

    `at_hop` is the set of (hop, column) that a later hop reads, through its
    key, its prefilter, or a preproject term other than a plain reference:
    those are gathered at their hop, over the stream's capacity. `late`
    lists, in output order, the (hop, column) that only reach the output:
    gathered once after the compaction, at the output bucket. A column that
    a preproject drops is in neither and is never gathered."""
    cur = [None] * n_stream            # None: a stream or computed column
    at_hop = set()
    for hop, ((sk, pf, pp, sil), width) in enumerate(zip(specs, widths)):
        reads = [*sk, *([] if pf is None else [pf]),
                 *(e for e in pp or () if _plain_ref(e) is None)]
        at_hop.update(cur[r.ordinal] for e in reads for r in e.collect(
            lambda x: isinstance(x, BoundReference))
            if cur[r.ordinal] is not None)
        s = cur if pp is None else [
            None if _plain_ref(e) is None else cur[_plain_ref(e)]
            for e in pp]
        b = [(hop, j) for j in range(width)]
        cur = (s + b) if sil else (b + s)
    late = [c for c in dict.fromkeys(cur) if c is not None and c not in at_hop]
    return at_hop, late


def maybe_chain(join, conf=None):
    """Collapse `BHJ(stream=BHJ(...))` stacks into one
    BroadcastHashJoinChainExec (planner hook, bottom-up: the stream child is
    already chained if it could be). Returns `join` unchanged when the stack
    doesn't qualify."""
    if not _chainable(join):
        return join
    si = 0 if join.stream_is_left else 1
    stream = join.children[si]
    if isinstance(stream, BroadcastHashJoinChainExec):
        return BroadcastHashJoinChainExec(stream.children[0],
                                          stream.hops + [join], conf=conf)
    if _chainable(stream):
        si2 = 0 if stream.stream_is_left else 1
        return BroadcastHashJoinChainExec(stream.children[si2],
                                          [stream, join], conf=conf)
    return join


class BroadcastHashJoinChainExec(TpuExec):
    """A stack of inner single-int-key broadcast hash joins probed by ONE
    fused per-batch kernel — the whole-stage-codegen analog for q18's shape
    (probe chains between exchanges collapse into a single XLA program).

    Each absorbed join ("hop") keeps its BroadcastExchangeExec child in the
    plan tree; this node takes over the probe side. When every hop's build
    turns out unique-keyed at run time (`_JoinCore.chain_capable`: dense /
    one probe modes), a stream row matches at most one build
    row per hop, so stream capacity statically bounds every intermediate —
    probe -> probe -> compact -> gather runs as one dispatch per batch
    instead of (project + probe + emit) per hop.

    What runs at the stream's capacity: each hop's lookup (two gathers: the
    table, then the position->row permutation) and the gathers of the build
    columns a later hop reads (`_chain_plan`). A hop otherwise carries only
    its build row. What runs at the output bucket: the compaction's gathers
    of the carried columns and build rows, through the first `cap` slots of
    its permutation (`compact_cols_to`), then one gather a remaining build
    column through its hop's compacted row. The output lands at a
    PREDICTED capacity bucket, kept per stream-batch capacity: the largest
    survivor bucket a batch of that capacity has needed in this partition
    (the stream's own capacity until one has been seen). A batch that
    needs the predicted bucket pays exactly one dispatch; one that needs a
    smaller bucket pays one more small program that cuts the compacted
    output to it (`HashJoinChain.land`); only one that needs a LARGER bucket
    lost rows to the cut inside the program and runs the chain again.
    Non-unique / context-sensitive builds degrade per batch
    to the classic sequential probe+emit path — degraded, never wrong."""

    stream_child_index = 0   # the fused pipeline continues into children[0]

    def __init__(self, stream, hops, conf=None):
        super().__init__(
            stream,
            *[h.children[1 if h.stream_is_left else 0] for h in hops],
            conf=conf)
        self.hops = list(hops)
        self._build_time = self.metrics.metric(M.BUILD_TIME, M.MODERATE)
        self._join_time = self.metrics.metric(M.JOIN_TIME, M.MODERATE)

    @property
    def output(self) -> T.StructType:
        return self.hops[-1].output

    @property
    def num_partitions(self):
        return self.children[0].num_partitions

    def execute_partition(self, split):
        def it():
            readers = [(h, h._shared.reader()) for h in self.hops]
            try:
                with trace_range("BroadcastHashJoin.build", self._build_time):
                    # outermost hop first: the nested (unfused) iterators
                    # materialize the outer join's build before pulling the
                    # stream triggers the inner one — keep that order so
                    # chaos schedules and memory watermarks line up
                    sbs = [None] * len(self.hops)
                    for i in reversed(range(len(self.hops))):
                        sbs[i] = self.hops[i]._shared.get()
                    cores = []
                    for h, sb in zip(self.hops, sbs):
                        bk = (h.left_keys if not h.stream_is_left
                              else h.right_keys)
                        sk = (h.right_keys if not h.stream_is_left
                              else h.left_keys)
                        cores.append(_JoinCore(
                            sb.get_batch(), bk, sk, h.join_type,
                            stream_prefilter=h.stream_prefilter))
                fused_ok = all(c.chain_capable() for c in cores)
                out_schema = self.output
                in_rows = self.metrics.metric(M.NUM_INPUT_ROWS, M.ESSENTIAL)
                # stream-batch capacity -> the largest output bucket a batch
                # of that capacity has needed: too large costs a slice, too
                # small a second run of the chain
                pred_cap = {}

                modes = "+".join(c.mode for c in cores)    # a hop each

                def probe(b):
                    with trace_range("HashJoinChain.probe", self._join_time,
                                     modes=modes) as sp:
                        return self._fused_probe(b, cores, sbs, pred_cap,
                                                 out_schema, sp)

                for stream_batch in self.children[0].execute_partition(split):
                    in_rows.add_lazy(stream_batch.lazy_num_rows)
                    acquire_semaphore(self.metrics)
                    if fused_ok:
                        for out in R.with_retry([stream_batch], probe,
                                                conf=self.conf,
                                                scope="joins.gather"):
                            if out is not None:
                                yield out
                    else:
                        yield from self._fallback(stream_batch, cores, sbs)
            finally:
                # abandoned mid-stream (limit / error / cancellation): still
                # count each reader down so the LAST one out releases its
                # broadcast relation instead of leaking it in HBM
                for h, r in readers:
                    if r.finish_once():
                        h._shared.close()
        return self.wrap_output(it())

    def _fused_probe(self, stream_batch, cores, sbs, pred_cap, out_schema,
                     probe_span):
        """One fused program per (stream shape, output bucket): every hop's
        key eval + prefilter + unique-match lookup + stream preproject, then
        a single front-compaction at the predicted output bucket and the
        build columns' gathers behind it. Returns the output batch or None
        (no survivors). `probe_span` counts how the output landed at its
        bucket, and which build columns were gathered where."""
        from spark_rapids_tpu.runtime import fuse
        scap = stream_batch.capacity
        specs = [(c.stream_key_exprs, c.stream_prefilter,
                  h.stream_preproject, h.stream_is_left)
                 for h, c in zip(self.hops, cores)]
        spec_key = tuple(
            (tuple(fuse.expr_key(e) for e in sk),
             fuse.expr_key(pf) if pf is not None else None,
             tuple(fuse.expr_key(e) for e in pp) if pp is not None else None,
             sil)
            for sk, pf, pp, sil in specs)
        statics = tuple(c.chain_static() for c in cores)
        stream_cols = [Col.from_vector(c) for c in stream_batch.columns]
        n_stream = jnp.asarray(stream_batch.lazy_num_rows, jnp.int32)
        hop_args = tuple(
            (c.chain_args(), [Col.from_vector(x)
                              for x in sb.get_batch().columns])
            for c, sb in zip(cores, sbs))
        at_hop, late = _chain_plan(specs, len(stream_cols),
                                   [len(b) for _, b in hop_args])
        probe_span.set(deferred_cols=len(late), hop_cols=len(at_hop))

        def run(cap):
            key = ("join_chain", cap, statics, spec_key,
                   fuse.schema_key(stream_batch.schema)
                   if stream_batch.schema else None)

            def build():
                lookups = [c.chain_lookup() for c in cores]

                def kernel(stream_cols, n_stream, hop_args):
                    cap_in = stream_cols[0].values.shape[0]
                    live = jnp.arange(cap_in, dtype=jnp.int32) < n_stream
                    at_hop, late = _chain_plan(
                        specs, len(stream_cols), [len(b) for _, b in hop_args])
                    # a carried column is a Col, or (hop, column) for a build
                    # column not gathered yet
                    cur = list(stream_cols)
                    rows = []
                    for hop, (lk, (cargs, b_cols), spec) in enumerate(
                            zip(lookups, hop_args, specs)):
                        sk_exprs, prefilter, preproject, sil = spec
                        ctx = EvalContext(cur, n_stream, cap_in)
                        # each hop, and the filter and projection fused into
                        # it, under its own name in the op metadata
                        with jax.named_scope(f"hop{hop}"):
                            if prefilter is not None:
                                with jax.named_scope("FilterExec"):
                                    p = prefilter.eval(ctx)
                                    live = live & p.values & p.validity
                            with jax.named_scope("lookup"):
                                row, hit = lk(
                                    cargs, [e.eval(ctx) for e in sk_exprs],
                                    live)
                            rows.append(row)
                            now = [j for j in range(len(b_cols))
                                   if (hop, j) in at_hop]
                            got = dict(zip(now, gather_cols(
                                [b_cols[j] for j in now], row, hit)))
                            bg = [got.get(j, (hop, j))
                                  for j in range(len(b_cols))]
                            if preproject is not None:
                                # a plain reference hands a build column not
                                # gathered yet on as it is
                                with jax.named_scope("ProjectExec"):
                                    s_cols = [e.eval(ctx) for e in preproject]
                            else:
                                s_cols = cur
                        cur = (s_cols + bg) if sil else (bg + s_cols)
                        live = hit
                    # every gather from here on runs at the output bucket
                    hops = sorted({h for h, _ in late})
                    carried, rows_out, count = compact_cols_to(
                        [c for c in cur if isinstance(c, Col)], live, cap,
                        [rows[h] for h in hops])
                    valid = jnp.arange(cap, dtype=jnp.int32) < count
                    with jax.named_scope("deferred"):
                        at = dict(zip(hops, rows_out))
                        built = {(h, j): gather_cols([hop_args[h][1][j]],
                                                     at[h], valid)[0]
                                 for h, j in late}
                    carried = iter(carried)
                    return [next(carried) if isinstance(c, Col) else built[c]
                            for c in cur], count

                return kernel

            args = (stream_cols, n_stream, hop_args)
            return fuse.call_fused(key, "HashJoinChain.probe", build, args,
                                   lambda: build()(*args))

        def land(cols, cap, tgt):
            # survivors lie at the front and the rest reads defaults
            # (compact_cols_to), so the first `tgt` slots of a run at a larger
            # capacity are the bits a run at `tgt` returns
            key = ("join_chain_land", cap, tgt, fuse.schema_key(out_schema))
            return fuse.call_fused(
                key, "HashJoinChain.land",
                lambda: lambda cols: slice_to_capacity(cols, None, tgt),
                (cols,), lambda: slice_to_capacity(cols, None, tgt))

        cap = min(pred_cap.get(scap, scap), scap)
        cols, count = run(cap)
        with tracing.span("sync.count") as sp:
            # one host sync per batch (the emit-total analog)
            count = int(count)
            sp.set(rows=count, capacity=cap)
        if count == 0:      # nothing to land, and nothing learned
            probe_span.set(capacity_pred=cap, capacity_out=0)
            return None
        # output capacity must be bucket_capacity(count) EXACTLY — the
        # unfused emit's chunk capacity — or downstream float reductions see
        # a different XLA tree shape and bit-identity breaks. A prediction
        # that was too large is cut to the bucket, one that was too small
        # cut survivors off inside the program: only that runs again.
        tgt = bucket_capacity(count)
        pred_cap[scap] = max(pred_cap.get(scap, 0), tgt)
        landed = "hit"
        if tgt > cap:
            cols, _ = run(tgt)
            landed = "rerun"
        elif tgt < cap:
            cols = land(cols, cap, tgt)
            landed = "sliced"
        probe_span.set(landed=landed, capacity_pred=cap, capacity_out=tgt)
        return ColumnarBatch([c.to_vector() for c in cols], count, out_schema)

    def _fallback(self, stream_batch, cores, sbs):
        """Non-unique or context-sensitive build on some hop: probe + emit
        each hop sequentially (exactly the unfused two-node behavior)."""
        batches = [stream_batch]
        for h, core, sb in zip(self.hops, cores, sbs):
            schema = h.output

            def probe(b):
                with trace_range("HashJoin.probe", self._join_time,
                                 mode=core.mode), \
                        R.with_restore_on_retry(core):
                    return b, core.probe_batch(b)

            nxt = []
            for b in batches:
                for piece, (perm, lo, hi, counts, total) in R.with_retry(
                        [b], probe, conf=self.conf, scope="joins.gather"):
                    nxt.extend(_emit_pairs(
                        h.join_type, h.stream_is_left, None,
                        h.stream_preproject, piece, sb.get_batch(), perm,
                        lo, hi, counts, total, schema))
            batches = nxt
        return batches

    def args_string(self):
        return " -> ".join(
            f"{h.join_type} lk={h.left_keys} rk={h.right_keys}"
            for h in self.hops)


class NestedLoopJoinExec(TpuExec):
    """All-pairs join with optional condition (reference
    GpuBroadcastNestedLoopJoinExec.scala — build side broadcast, every pair
    evaluated; supports cross/inner plus outer/semi/anti)."""

    def __init__(self, join_type: str, left: TpuExec, right: TpuExec,
                 condition: Expression | None = None, conf=None):
        super().__init__(left, right, conf=conf)
        jt = join_type.lower().replace("_", "")
        self.join_type = J.INNER if jt == J.CROSS else jt
        if self.join_type == J.RIGHT_OUTER:
            raise ValueError("right outer nested-loop join: swap the inputs and "
                             "plan a left outer (the planner mirrors the reference's "
                             "build-side rules)")
        self.condition = (bind_references(condition, self._pair_schema())
                          if condition is not None else None)
        self._join_time = self.metrics.metric(M.JOIN_TIME, M.MODERATE)
        from spark_rapids_tpu.exec.broadcast import BroadcastExchangeExec
        exchange = BroadcastExchangeExec(self.children[1], conf=self.conf)
        self.children[1] = exchange  # plan-visible broadcast exchange node
        self._shared = _SharedBroadcast(exchange, self.num_partitions)

    def _pair_schema(self):
        return T.StructType(list(self.children[0].output) +
                            list(self.children[1].output))

    @property
    def output(self):
        lf, rf = list(self.children[0].output), list(self.children[1].output)
        if self.join_type in (J.LEFT_SEMI, J.LEFT_ANTI):
            return T.StructType(lf)
        if self.join_type in (J.LEFT_OUTER, J.FULL_OUTER):
            rf = [T.StructField(f.name, f.data_type, True) for f in rf]
        if self.join_type in (J.RIGHT_OUTER, J.FULL_OUTER):
            lf = [T.StructField(f.name, f.data_type, True) for f in lf]
        return T.StructType(lf + rf)

    @property
    def num_partitions(self):
        return self.children[0].num_partitions

    def execute_partition(self, split):
        def it():
            reader = self._shared.reader()
            try:
                sb = self._shared.get()
                build = sb.get_batch()
                n_build = build.num_rows
                out_schema = self.output
                pair_schema = self._pair_schema()
                right_matched_acc = (np.zeros(build.capacity, dtype=bool)
                                     if self.join_type == J.FULL_OUTER else None)
                for lb in self.children[0].execute_partition(split):
                    acquire_semaphore(self.metrics)
                    # a stream partition of a mesh exchange lies on its own
                    # chip: the relation goes there (nothing on one device)
                    build = batch_to_device(build, batch_device(lb))
                    # the range closes before each batch goes downstream: a
                    # span left open across a yield would adopt the consumer
                    pairs = self._join_batch(lb, build, n_build, out_schema,
                                             pair_schema, right_matched_acc)
                    while True:
                        with trace_range("NestedLoopJoin", self._join_time):
                            out = next(pairs, None)
                        if out is None:
                            break
                        yield out
                if right_matched_acc is not None:
                    self._shared.merge_matched(right_matched_acc)
                if reader.finish_once():
                    if self.join_type == J.FULL_OUTER:
                        yield from self._unmatched_right(
                            build, n_build, self._shared.matched_acc, out_schema)
                    self._shared.close()
            finally:
                # same contract as BroadcastHashJoinExec: an abandoned
                # reader still counts down; the last one out releases
                if reader.finish_once():
                    self._shared.close()
        return self.wrap_output(it())

    def _join_batch(self, lb, build, n_build, out_schema, pair_schema, matched_acc):
        n_left = lb.num_rows
        lcols = [Col.from_vector(c) for c in lb.columns]
        rcols = [Col.from_vector(c) for c in build.columns]
        total = n_left * n_build
        left_match = np.zeros(lb.capacity, dtype=bool)
        jt = self.join_type
        # inner/outer pair chunks stream out as soon as each is produced so only
        # one expansion chunk is live at a time; semi/anti only need match flags
        emit_pairs = jt in (J.INNER, J.LEFT_OUTER, J.FULL_OUTER)
        pos = 0
        while pos < total:
            out_cap = bucket_capacity(min(total - pos, _MAX_CHUNK_ROWS))
            j = jnp.arange(out_cap, dtype=jnp.int32) + jnp.int32(pos)
            li = jnp.clip(j // max(n_build, 1), 0, lb.capacity - 1)
            ri = jnp.clip(j % max(n_build, 1), 0, build.capacity - 1)
            live = j < total
            lg = gather_cols(lcols, li, live)
            rg = gather_cols(rcols, ri, live)
            n_out = min(total - pos, out_cap)
            batch = ColumnarBatch([c.to_vector() for c in lg + rg], n_out, pair_schema)
            if self.condition is not None:
                ctx = EvalContext.from_batch(batch)
                pred = self.condition.eval(ctx)
                keep = selection_mask(pred, batch.lazy_num_rows, batch.capacity)
                # track which left/right rows matched (for outer/semi/anti)
                keep_h = np.asarray(keep)
                li_h, ri_h = np.asarray(li), np.asarray(ri)
                np.logical_or.at(left_match, li_h[keep_h], True)
                if matched_acc is not None:
                    np.logical_or.at(matched_acc, ri_h[keep_h], True)
                cols, count = compact_cols([Col.from_vector(c) for c in batch.columns],
                                           keep)
                batch = ColumnarBatch([c.to_vector() for c in cols], int(count),
                                      pair_schema)
            else:
                left_match[np.asarray(li[:n_out])] = True if n_build > 0 else False
                if matched_acc is not None and n_left > 0:
                    matched_acc[:n_build] = True
            pos += out_cap
            if emit_pairs and batch.num_rows:
                yield batch
        if jt in (J.LEFT_OUTER, J.FULL_OUTER):
            yield from self._unmatched_left(lb, lcols, left_match, out_schema)
        elif jt in (J.LEFT_SEMI, J.LEFT_ANTI):
            want = left_match if jt == J.LEFT_SEMI else ~left_match
            if self.condition is None and jt == J.LEFT_SEMI and n_build == 0:
                want = np.zeros_like(left_match)
            if self.condition is None and jt == J.LEFT_ANTI:
                want = (~left_match if n_build > 0 else
                        np.ones_like(left_match))
            keep = jnp.asarray(want) & (jnp.arange(lb.capacity) < n_left)
            cols, count = compact_cols(lcols, keep)
            if int(count):
                yield ColumnarBatch([c.to_vector() for c in cols], int(count),
                                    out_schema)

    def _unmatched_left(self, lb, lcols, left_match, out_schema):
        live = np.arange(lb.capacity) < lb.num_rows
        idxs = np.nonzero(live & ~left_match)[0]
        if len(idxs) == 0:
            return
        n = len(idxs)
        cap = bucket_capacity(n)
        idx_dev = jnp.zeros((cap,), jnp.int32).at[:n].set(jnp.asarray(idxs, jnp.int32))
        lg = gather_cols(lcols, idx_dev, jnp.arange(cap) < n)
        rnull = [Col(jnp.full((cap,), f.data_type.default_value(),
                              dtype=f.data_type.jnp_dtype),
                     jnp.zeros((cap,), jnp.bool_), f.data_type)
                 for f in self.children[1].output]
        yield ColumnarBatch([c.to_vector() for c in lg + rnull], n, out_schema)

    def _unmatched_right(self, build, n_build, matched_acc, out_schema):
        live = np.arange(build.capacity) < n_build
        idxs = np.nonzero(live & ~matched_acc)[0]
        if len(idxs) == 0:
            return
        n = len(idxs)
        cap = bucket_capacity(n)
        idx_dev = jnp.zeros((cap,), jnp.int32).at[:n].set(jnp.asarray(idxs, jnp.int32))
        rg = gather_cols([Col.from_vector(c) for c in build.columns], idx_dev,
                         jnp.arange(cap) < n)
        lnull = [Col(jnp.full((cap,), f.data_type.default_value(),
                              dtype=f.data_type.jnp_dtype),
                     jnp.zeros((cap,), jnp.bool_), f.data_type)
                 for f in self.children[0].output]
        yield ColumnarBatch([c.to_vector() for c in lnull + rg], n, out_schema)

    def args_string(self):
        return f"{self.join_type}" + (f" cond={self.condition}"
                                      if self.condition is not None else "")


class CartesianProductExec(NestedLoopJoinExec):
    """Reference GpuCartesianProductExec.scala — cross product of all partitions."""

    def __init__(self, left, right, condition=None, conf=None):
        super().__init__(J.CROSS, left, right, condition=condition, conf=conf)
