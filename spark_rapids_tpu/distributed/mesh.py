"""MeshExecutor — SPMD aggregate execution over a TPU mesh in ONE jit.

Reference analogy: a Spark stage = map tasks → UCX shuffle → reduce tasks
(RapidsShuffleInternalManagerBase + GpuShuffleExchangeExec). On a TPU slice the
idiomatic equivalent is a single compiled SPMD program: every chip holds one
data shard; the "shuffle" is an XLA all_to_all over ICI inside the same program
(no host hops, no per-block RPC). This module generalizes
__graft_entry__.dryrun_multichip into a product executor:

    shard-local: filter → project keys/values → sort-based partial aggregate
    exchange:    hash-partition partial rows → lax.all_to_all over axis "data"
    shard-local: merge-aggregate received partials → evaluate finals

Strings participate via a mesh-global dictionary built on host at ingest (codes
are ints on device). The exchange hash is mesh-internal (chained murmur3 over
key carriers) — it only balances partials, it is NOT the Spark-compatible
partitioning (that lives in shuffle/partitioning.py for the Spark shuffle path).

Scaling note: per-shard capacity is static, so compile once and stream any
number of row-chunks through; DCN-spanning jobs compose this with the TCP
transport between slices (SURVEY.md §5 distributed backend mapping)."""

from __future__ import annotations

import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.vector import bucket_capacity
from spark_rapids_tpu.expr.core import Alias, Col, EvalContext, bind_references
from spark_rapids_tpu.expr.aggregates import AggregateFunction
from spark_rapids_tpu.ops import grouping as G
from spark_rapids_tpu.ops import hashing as H
from spark_rapids_tpu.ops.filtering import compact_cols, gather_cols, selection_mask


def _unalias(e):
    return e.child if isinstance(e, Alias) else e


def _mesh_hash(cols, capacity: int):
    """Deterministic per-row hash for the internal exchange (chained murmur3
    over value carriers; string codes hash as ints — mesh-internal only)."""
    h = jnp.full((capacity,), jnp.int32(42))
    for c in cols:
        if c.values.dtype == jnp.int64:
            nh = H.hash_long(c.values, h)
        elif c.values.dtype == jnp.float64:
            nh = H.hash_double(c.values, h)
        else:
            nh = H.hash_int(c.values.astype(jnp.int32), h)
        h = jnp.where(c.validity, nh, h)
    return h


def encode_shards(tables, schema: T.StructType, n: int):
    """Host-side mesh ingest shared by MeshExecutor and MeshExchangeExec: pad
    each shard to one common capacity; string columns are re-coded against a
    mesh-GLOBAL sorted dictionary (codes then compare/exchange as ints on
    device, and code order == lexicographic order). Returns
    (shards [(cols, n_rows)] * n, cap, global_dicts {ordinal: pa.Array})."""
    import pyarrow as pa
    from spark_rapids_tpu.columnar.arrow import table_to_device
    from spark_rapids_tpu.ops.filtering import slice_to_capacity
    if len(tables) > n:
        raise ValueError(
            f"{len(tables)} input shards > {n} mesh devices; "
            "merge shards before calling the mesh executor")
    cap = bucket_capacity(max((t.num_rows for t in tables), default=1))
    global_dicts = {}
    for i, f in enumerate(schema):
        if isinstance(f.data_type, T.StringType):
            union = pa.concat_arrays(
                [t.column(i).combine_chunks().cast(pa.string()).unique()
                 for t in tables]).unique().sort()
            global_dicts[i] = union
    shards = []
    for t in tables:
        batch = table_to_device(t, schema=schema)
        cols = []
        for i, cv in enumerate(batch.columns):
            c = Col.from_vector(cv)
            if i in global_dicts and c.dictionary is not None:
                remap = {v: j for j, v in
                         enumerate(global_dicts[i].to_pylist())}
                m = np.array([remap[v] for v in
                              c.dictionary.to_pylist()] or [0], np.int32)
                c = Col(jnp.asarray(m)[c.values], c.validity, c.dtype,
                        global_dicts[i])
            cols.append(c)
        # re-pad to the common mesh capacity
        cols = slice_to_capacity(cols, t.num_rows, cap)
        shards.append((cols, t.num_rows))
    while len(shards) < n:  # fewer shards than chips: empty pads
        cols = [Col(jnp.full((cap,), f.data_type.default_value(),
                             dtype=f.data_type.jnp_dtype),
                    jnp.zeros((cap,), jnp.bool_), f.data_type,
                    global_dicts.get(i))
                for i, f in enumerate(schema)]
        shards.append((cols, 0))
    return shards, cap, global_dicts


def put_stacked_shards(mesh: Mesh, shards):
    """device_put every field of `shards` ([(cols, n_rows)] with one entry
    per mesh device) stacked over the mesh's "data" axis. Returns
    (vals, masks, nrows) ready to feed a shard_map program — the ingest
    step shared by MeshExecutor.aggregate, MeshExchangeExec._run_exchange
    and LocalMesh.partition_wave."""
    sharding = NamedSharding(mesh, P("data", None))
    vals, masks = [], []
    for ci in range(len(shards[0][0])):
        vals.append(jax.device_put(
            jnp.stack([s[0][ci].values for s in shards]), sharding))
        masks.append(jax.device_put(
            jnp.stack([s[0][ci].validity for s in shards]), sharding))
    nrows = jax.device_put(
        jnp.asarray([s[1] for s in shards], jnp.int32),
        NamedSharding(mesh, P("data")))
    return vals, masks, nrows


class MeshDegradedError(RuntimeError):
    """The executor's local mesh is unavailable (fewer than 2 devices),
    narrower than the task group being dispatched (mesh shrank), or failed
    inside its collective region. The cluster driver treats a reply
    carrying this as DEGRADATION, not task failure: the mesh task's splits
    are transparently re-planned onto the per-split TCP-shuffle path under
    a bumped map-output epoch — no task-attempt strike, bit-identical
    results (cluster/minicluster.py)."""


class LocalMesh:
    """One MiniCluster executor's device mesh — the intra-process half of
    the unified mesh-cluster plane (ROADMAP item 4: N processes x M chips).

    A mesh map task carries up to `n` lanes (one map split each); per
    partition wave, the Spark-exact murmur3 partition ids of EVERY lane's
    current batch are computed in ONE jitted shard_map dispatch (lane =
    shard), and the wave's per-reduce-partition row counts are all-reduced
    over ICI with `lax.psum` — the map-output-statistics exchange.

    TWO-LEVEL EXCHANGE (docs/cluster.md): block content for reduce
    partitions OWNED by this host (the driver's ownership assignment, i.e.
    the partitions whose consumer will be placed here) rides
    `exchange_wave` — every fixed-width column moves lane→lane with ONE
    `lax.all_to_all` per carrier over ICI, and the receiving lane writes
    the shards into the local block store under the SAME (map_split, seq)
    keys the per-batch path would have used, so `iter_union_blocks`'
    canonical-key merge keeps bit-identity with the TCP plane by
    construction. Only partitions owned by OTHER hosts are sliced with the
    per-batch path (shuffle.partitioning.slice_into_partitions) and parked
    for the TCP fetch. Waves whose schema carries variable-width columns
    (strings, lists, maps, structs) fall back to slice-and-park for the
    whole wave without breaking the mesh group, and any failure inside the
    collective degrades the task to per-split TCP execution — which is
    what makes the transparent mesh→TCP degraded fallback sound."""

    _instance: "LocalMesh | None" = None
    _ilock = threading.Lock()

    def __init__(self, n_devices: int = 0):
        devs = jax.devices()
        n = len(devs) if n_devices <= 0 else min(n_devices, len(devs))
        if n < 2:
            raise MeshDegradedError(
                f"local mesh unavailable: {len(devs)} visible device(s), "
                f"{n_devices} requested")
        self.n = n
        self.mesh = Mesh(np.array(devs[:n]), ("data",))
        self._steps: dict = {}

    @classmethod
    def get(cls, n_devices: int = 0) -> "LocalMesh":
        with cls._ilock:
            if cls._instance is None:
                cls._instance = LocalMesh(n_devices)
            return cls._instance

    @classmethod
    def reset(cls):
        with cls._ilock:
            cls._instance = None

    def _pid_step(self, dtypes, cap: int, n_out: int):
        """Jitted shard_map program keyed by (key dtypes, capacity, reduce
        fan-out): per shard, murmur3 partition ids masked to a sentinel on
        padding rows, plus the psum-reduced live row count per partition."""
        key = (tuple(type(dt).__name__ for dt in dtypes), cap, n_out)
        step = self._steps.get(key)
        if step is not None:
            return step
        from spark_rapids_tpu.ops import hashing as H
        from spark_rapids_tpu.shuffle.partitioning import murmur3_row_hash
        nk = len(dtypes)

        def shard_step(*flat):
            vals = flat[:nk]
            masks = flat[nk:2 * nk]
            n_rows = flat[2 * nk][0]
            cols = [Col(v[0], m[0], dt)
                    for v, m, dt in zip(vals, masks, dtypes)]
            h = murmur3_row_hash(cols, cap)
            pids = H.pmod(h, n_out)
            live = jnp.arange(cap, dtype=jnp.int32) < n_rows
            pids = jnp.where(live, pids, jnp.int32(n_out))
            counts = jnp.bincount(pids, length=n_out + 1)[:n_out]
            return pids[None], jax.lax.psum(counts, "data")

        spec = P("data", None)
        step = jax.jit(jax.shard_map(
            shard_step, mesh=self.mesh,
            in_specs=tuple([spec] * (2 * nk) + [P("data")]),
            out_specs=(spec, P())))
        self._steps[key] = step
        return step

    @staticmethod
    def _pad_col(col: Col, cap: int) -> Col:
        n = col.values.shape[0]
        if n >= cap:
            return col
        default = jnp.asarray(col.dtype.default_value(),
                              dtype=col.values.dtype)
        return Col(jnp.concatenate([col.values,
                                    jnp.full((cap - n,), default)]),
                   jnp.concatenate([col.validity,
                                    jnp.zeros((cap - n,), jnp.bool_)]),
                   col.dtype)

    def partition_wave(self, batches: list, partitioner):
        """One wave: `batches` holds each live lane's current batch (≤ n).
        Returns ([pids per batch, each sliced to that batch's capacity],
        wave_counts) where wave_counts is the psum-reduced live-row count
        per reduce partition (None on the per-batch string fallback).
        Lanes whose keys include string columns fall back to the per-batch
        pid path: per-lane dictionaries cannot be trace-time constants of
        one stacked program (docs/cluster.md)."""
        if len(batches) > self.n:
            raise MeshDegradedError(
                f"mesh shrank: {self.n} device(s) < {len(batches)} lanes")
        n_out = partitioner.num_partitions
        keys_per_lane = []
        for b in batches:
            ctx = EvalContext.from_batch(b)
            keys_per_lane.append([e.eval(ctx)
                                  for e in partitioner.key_exprs])
        if any(k.is_string for k in keys_per_lane[0]):
            return [partitioner.part_ids(b) for b in batches], None
        cap = max(b.capacity for b in batches)
        dtypes = [k.dtype for k in keys_per_lane[0]]
        shards = [([self._pad_col(k, cap) for k in keys], b.num_rows)
                  for keys, b in zip(keys_per_lane, batches)]
        while len(shards) < self.n:    # idle lanes: empty pad shards
            shards.append((
                [Col(jnp.full((cap,), dt.default_value(),
                              dtype=dt.jnp_dtype),
                     jnp.zeros((cap,), jnp.bool_), dt) for dt in dtypes],
                0))
        vals, masks, nrows = put_stacked_shards(self.mesh, shards)
        pids, counts = self._pid_step(dtypes, cap, n_out)(
            *vals, *masks, nrows)
        counts = np.asarray(counts)
        # movement ledger, ICI edge: the program's only collective is the
        # psum of per-partition live-row counts — metered as the ACTUAL
        # per-lane operand bytes (every device contributes one n_out count
        # vector of the psum operand's real dtype)
        from spark_rapids_tpu.runtime import movement as MV
        op_bytes = int(counts.dtype.itemsize) * n_out * self.n
        MV.record("ici.collective", op_bytes, link="ici",
                  site="mesh.partition_wave", payload_bytes=op_bytes)
        return ([pids[d][:b.capacity] for d, b in enumerate(batches)],
                counts)

    # -- two-level content exchange -----------------------------------------
    @staticmethod
    def exchangeable_schema(schema) -> bool:
        """Whether a batch schema can ride the ICI content exchange: every
        column must be fixed-width on device. Variable-width carriers
        (strings with per-batch dictionaries, lists, maps, structs) fall
        back to the per-batch slice-and-park path for the whole wave."""
        return all(not isinstance(f.data_type,
                                  (T.StringType, T.ArrayType, T.MapType,
                                   T.StructDataType, T.NullType))
                   for f in schema)

    def _exchange_step(self, dtypes, cap: int, cap_ex: int, n_out: int):
        """Jitted shard_map program keyed by (column dtypes, input
        capacity, exchange-block capacity, fan-out): per lane, rows whose
        reduce partition is owned by THIS host are compacted per
        destination lane and every column carrier (values, validity, pid)
        moves lane→lane with one `lax.all_to_all` over ICI. Returns the
        received shards still stacked per (dest lane, source lane) with
        the received pids sentinel-masked past each source's live count."""
        key = ("exchange", tuple(type(dt).__name__ for dt in dtypes),
               cap, cap_ex, n_out)
        step = self._steps.get(key)
        if step is not None:
            return step
        from spark_rapids_tpu.ops.filtering import compact_cols
        nc = len(dtypes)
        n_dev = self.n

        def shard_step(*flat):
            vals = flat[:nc]
            masks = flat[nc:2 * nc]
            pids = flat[2 * nc][0]          # (cap,) sentinel n_out on pads
            dest_map = flat[2 * nc + 1]     # (n_out+1,) lane or -1
            dest = dest_map[pids]
            cols = [Col(v[0], m[0], dt)
                    for v, m, dt in zip(vals, masks, dtypes)]
            idcol = Col(pids, jnp.ones((cap,), jnp.bool_), T.IntegerType())
            sv, sm, sp, sn = [], [], [], []
            for d in range(n_dev):
                keep = dest == jnp.int32(d)
                cc, cn = compact_cols(cols + [idcol], keep)
                sv.append([c.values[:cap_ex] for c in cc[:-1]])
                sm.append([c.validity[:cap_ex] for c in cc[:-1]])
                sp.append(cc[-1].values[:cap_ex])
                sn.append(jnp.minimum(cn, jnp.int32(cap_ex)))
            stacked_v = [jnp.stack([sv[d][c] for d in range(n_dev)])
                         for c in range(nc)]
            stacked_m = [jnp.stack([sm[d][c] for d in range(n_dev)])
                         for c in range(nc)]
            spids = jnp.stack(sp)
            scnt = jnp.stack(sn).astype(jnp.int32)
            rv = [jax.lax.all_to_all(a, "data", 0, 0) for a in stacked_v]
            rm = [jax.lax.all_to_all(a, "data", 0, 0) for a in stacked_m]
            rp = jax.lax.all_to_all(spids, "data", 0, 0)
            rn = jax.lax.all_to_all(scnt, "data", 0, 0)
            # sentinel-mask the received pids past each source's live count
            # so the host-side per-pid slicing sinks padding rows
            live = jnp.arange(cap_ex, dtype=jnp.int32)[None, :] < rn[:, None]
            rp = jnp.where(live, rp, jnp.int32(n_out))
            return (tuple(v[None] for v in rv) + tuple(m[None] for m in rm)
                    + (rp[None], rn[None]))

        spec = P("data", None)
        step = jax.jit(jax.shard_map(
            shard_step, mesh=self.mesh,
            in_specs=tuple([spec] * (2 * nc) + [spec, P()]),
            out_specs=tuple([P("data", None, None)] * (2 * nc + 1)
                            + [spec])))
        self._steps[key] = step
        return step

    def exchange_wave(self, batches: list, pids_list: list, dest_map,
                      n_out: int):
        """Move one wave's intra-host reduce-partition CONTENT over ICI:
        `dest_map` maps pid → receiving lane for partitions owned by this
        host (-1 for cross-host pids, which stay on the slice-and-park
        path). Returns (recv_vals, recv_masks, recv_pids, recv_counts)
        where recv_vals[c][dest][src] is source lane `src`'s rows for the
        partitions assigned to lane `dest`, in source batch order — the
        receiving lane reconstructs per-(map_split, pid) blocks from them
        bit-identically to the per-batch path. The movement ledger meters
        the ACTUAL per-lane all_to_all operand bytes on the ici edge, with
        the live-row content bytes as the payload unit."""
        if len(batches) > self.n:
            raise MeshDegradedError(
                f"mesh shrank: {self.n} device(s) < {len(batches)} lanes")
        cap = max(b.capacity for b in batches)
        cols_per_lane = [[Col.from_vector(c) for c in b.columns]
                         for b in batches]
        dtypes = [c.dtype for c in cols_per_lane[0]]
        # dest_map indexed by pid; slot n_out is the pad-row sentinel and
        # always routes off-mesh (-1)
        dm = np.full((n_out + 1,), -1, np.int32)
        dm[:n_out] = np.asarray(dest_map, np.int32)[:n_out]
        # host-side per-(lane, dest) live-row counts size the exchange
        # block (one d2h sync of the wave's pid vectors, same sync the
        # per-batch slice path pays for its bincount)
        row_bytes = sum(np.dtype(dt.jnp_dtype).itemsize + 1
                        for dt in dtypes) + 4
        live_rows = 0
        max_cnt = 1
        for b, pids in zip(batches, pids_list):
            p = np.asarray(pids)[:b.num_rows]
            d = dm[p]
            d = d[d >= 0]
            live_rows += int(d.size)
            if d.size:
                max_cnt = max(max_cnt, int(np.bincount(d).max()))
        cap_ex = min(bucket_capacity(max_cnt), cap)
        shards = []
        pid_rows = []
        for b, cols, pids in zip(batches, cols_per_lane, pids_list):
            shards.append(([self._pad_col(c, cap) for c in cols],
                           b.num_rows))
            p = jnp.asarray(pids, jnp.int32)
            if p.shape[0] < cap:
                p = jnp.concatenate(
                    [p, jnp.full((cap - p.shape[0],), jnp.int32(n_out))])
            pid_rows.append(p)
        while len(shards) < self.n:    # idle lanes: empty pad shards
            shards.append((
                [Col(jnp.full((cap,), dt.default_value(),
                              dtype=dt.jnp_dtype),
                     jnp.zeros((cap,), jnp.bool_), dt) for dt in dtypes],
                0))
            pid_rows.append(jnp.full((cap,), jnp.int32(n_out)))
        vals, masks, _nrows = put_stacked_shards(self.mesh, shards)
        sharding = NamedSharding(self.mesh, P("data", None))
        pids_stacked = jax.device_put(jnp.stack(pid_rows), sharding)
        dm_dev = jax.device_put(jnp.asarray(dm),
                                NamedSharding(self.mesh, P()))
        step = self._exchange_step(dtypes, cap, cap_ex, n_out)
        out = step(*vals, *masks, pids_stacked, dm_dev)
        nc = len(dtypes)
        rv, rm = list(out[:nc]), list(out[nc:2 * nc])
        rp, rn = out[2 * nc], out[2 * nc + 1]
        rn = np.asarray(rn)             # sync: collective errors surface HERE
        # movement ledger, ICI edge: the REAL all_to_all operand bytes
        # (per-lane (n, cap_ex) carriers for every value/validity/pid
        # column plus the count vector, summed over lanes), dual-unit with
        # the wave's live-row content bytes as the payload column
        from spark_rapids_tpu.runtime import movement as MV
        per_lane = (sum(self.n * cap_ex * np.dtype(dt.jnp_dtype).itemsize
                        for dt in dtypes)
                    + nc * self.n * cap_ex          # validity carriers
                    + self.n * cap_ex * 4           # pid carrier
                    + self.n * 4)                   # count vector
        MV.record("ici.collective", per_lane * self.n, link="ici",
                  site="mesh.exchange_wave",
                  payload_bytes=live_rows * row_bytes)
        return rv, rm, rp, rn


class MeshExecutor:
    """Compile + run grouped aggregation across an n-device mesh."""

    def __init__(self, n_devices: int | None = None, devices=None):
        devs = (list(devices) if devices is not None
                else jax.devices()[:n_devices or len(jax.devices())])
        self.n = len(devs)
        self.mesh = Mesh(np.array(devs), ("data",))

    # -- host-side ingest ----------------------------------------------------
    def _encode_shards(self, tables, schema: T.StructType):
        return encode_shards(tables, schema, self.n)

    # -- the SPMD program ----------------------------------------------------
    def _build_step(self, schema, group_exprs, agg_exprs, filter_expr, cap):
        n_dev = self.n
        group_b = [bind_references(e, schema) for e in group_exprs]
        aggs = [(_unalias(bind_references(e, schema))) for e in agg_exprs]
        assert all(isinstance(a, AggregateFunction) for a in aggs)
        filt_b = (bind_references(filter_expr, schema)
                  if filter_expr is not None else None)
        state_counts = [len(a.state_types) for a in aggs]

        def local_partial(cols, n_rows):
            ctx = EvalContext(cols, n_rows, cap)
            if filt_b is not None:
                pred = filt_b.eval(ctx)
                keep = selection_mask(pred, n_rows, cap)
                cols, n_rows = compact_cols(cols, keep)
                ctx = EvalContext(cols, n_rows, cap)
            keys = [e.eval(ctx) for e in group_b]
            perm, seg_ids, boundary, live = G.group_segments(keys, n_rows, cap)
            skeys = gather_cols(keys, perm, live)
            segctx = G.segment_structure(seg_ids, cap)
            states = []
            for a in aggs:
                in_col = (gather_cols([a.child.eval(ctx)], perm, live)[0]
                          if a.children else
                          Col(jnp.zeros((cap,), jnp.int8), live, T.NULL))
                states.extend(a.update(in_col, segctx))  # per-row states
            out, n_groups = compact_cols(skeys + states, boundary)
            return out, n_groups

        def shard_step(*flat):
            nk = len(group_b)
            n_state = sum(state_counts)
            n_cols = len(schema.fields)
            vals = flat[:n_cols]
            vlds = flat[n_cols:2 * n_cols]
            n_rows = flat[2 * n_cols][0]
            cols = [Col(v[0], m[0], f.data_type)
                    for v, m, f in zip(vals, vlds, schema.fields)]

            partial, n_groups = local_partial(cols, n_rows)

            # exchange: hash-partition partial rows over the mesh
            pids = H.pmod(_mesh_hash(partial[:nk], cap), n_dev)
            live = jnp.arange(cap, dtype=jnp.int32) < n_groups
            sends_v, sends_m, sends_n = [], [], []
            for p in range(n_dev):
                mask = live & (pids == p)
                pc, pn = compact_cols(partial, mask)
                sends_v.append([c.values for c in pc])
                sends_m.append([c.validity for c in pc])
                sends_n.append(pn)
            ncols_p = nk + n_state
            stacked_v = [jnp.stack([sends_v[p][c] for p in range(n_dev)])
                         for c in range(ncols_p)]
            stacked_m = [jnp.stack([sends_m[p][c] for p in range(n_dev)])
                         for c in range(ncols_p)]
            sn = jnp.stack(sends_n)
            recv_v = [jax.lax.all_to_all(a, "data", 0, 0) for a in stacked_v]
            recv_m = [jax.lax.all_to_all(a, "data", 0, 0) for a in stacked_m]
            rn = jax.lax.all_to_all(sn, "data", 0, 0)

            # merge received partials
            mcap = n_dev * cap
            slot = jnp.arange(mcap, dtype=jnp.int32) % cap
            rlive = slot < jnp.repeat(rn, cap)
            rcols = []
            src = partial  # dtype templates
            for c in range(ncols_p):
                v = recv_v[c].reshape(mcap)
                m = recv_m[c].reshape(mcap) & rlive
                proto = src[c]
                default = jnp.asarray(proto.dtype.default_value(),
                                      dtype=v.dtype)
                rcols.append(Col(jnp.where(m, v, default), m, proto.dtype,
                                 proto.dictionary))
            # key validity defines row presence only together with rlive;
            # null-keyed rows are real rows — track presence separately
            present = rlive
            (packed, m_rows) = compact_cols(
                rcols + [Col(jnp.zeros((mcap,), jnp.int8), present, T.NULL)],
                present)
            packed = packed[:-1]
            keys2 = packed[:nk]
            perm, seg_ids, boundary, live2 = G.group_segments(
                keys2, m_rows, mcap)
            skeys2 = gather_cols(keys2, perm, live2)
            segctx2 = G.segment_structure(seg_ids, mcap)
            out_states = []
            si = nk
            for a, nst in zip(aggs, state_counts):
                sts = gather_cols(packed[si:si + nst], perm, live2)
                out_states.extend(a.merge(sts, segctx2))  # per-row states
                si += nst
            out, out_groups = compact_cols(skeys2 + out_states, boundary)

            # finals
            finals = out[:nk]
            si = nk
            for a, nst in zip(aggs, state_counts):
                finals.append(a.evaluate(out[si:si + nst]))
                si += nst
            ret_v = tuple(c.values[None] for c in finals)
            ret_m = tuple(c.validity[None] for c in finals)
            return ret_v + ret_m + (out_groups[None],)

        spec2 = P("data", None)
        n_out = len(group_b) + len(aggs)
        n_in = len(schema.fields)
        step = jax.jit(jax.shard_map(
            shard_step, mesh=self.mesh,
            in_specs=tuple([spec2] * (2 * n_in) + [P("data")]),
            out_specs=tuple([spec2] * (2 * n_out) + [P("data")])))
        return step

    # -- public API ----------------------------------------------------------
    def aggregate(self, tables: list, group_exprs: list, agg_exprs: list,
                  filter_expr=None, schema: T.StructType | None = None):
        """tables: one pyarrow Table per shard (≤ n_devices). Returns one
        pyarrow Table of grouped results."""
        import pyarrow as pa
        if schema is None:
            schema = T.StructType.from_arrow(tables[0].schema)
        shards, cap, _dicts = self._encode_shards(tables, schema)
        step = self._build_step(schema, group_exprs, agg_exprs, filter_expr,
                                cap)
        vals, masks, nrows = put_stacked_shards(self.mesh, shards)
        group_b = [bind_references(e, schema) for e in group_exprs]
        aggs = [_unalias(bind_references(e, schema)) for e in agg_exprs]
        # movement ledger, ICI edge: the exchange inside the program is one
        # lax.all_to_all per partial-aggregate carrier — metered as the
        # ACTUAL operand bytes: every device contributes a (n_dev, cap)
        # values + validity pair per key/state column plus its per-dest
        # count vector (the partials ride at full capacity; the live-row
        # subset is not knowable host-side without a d2h sync)
        from spark_rapids_tpu.runtime import movement as MV
        part_dtypes = ([g.dtype for g in group_b]
                       + [st for a in aggs for st in a.state_types])
        n = self.n
        op_bytes = n * n * cap * sum(
            np.dtype(dt.jnp_dtype).itemsize + 1 for dt in part_dtypes)
        op_bytes += n * n * 4  # per-dest count vectors
        MV.record("ici.collective", op_bytes, link="ici",
                  site="mesh.aggregate", payload_bytes=op_bytes)
        out = step(*vals, *masks, nrows)

        n_out = len(group_b) + len(aggs)
        out_v, out_m, groups = out[:n_out], out[n_out:2 * n_out], out[-1]
        counts = np.asarray(groups)

        names = []
        dtypes = []
        for i, e in enumerate(group_exprs):
            names.append(e.name if isinstance(e, Alias) else
                         getattr(e, "name", f"k{i}"))
            dtypes.append(group_b[i].dtype)
        for i, e in enumerate(agg_exprs):
            names.append(e.name if isinstance(e, Alias) else f"agg{i}")
            dtypes.append(aggs[i].dtype)

        # keep per-key dictionaries for decode
        key_dicts = [shards[0][0][_key_ordinal(group_b[i], schema)].dictionary
                     if isinstance(dtypes[i], T.StringType) else None
                     for i in range(len(group_b))] + [None] * len(aggs)

        rows = {n: [] for n in names}
        for d in range(len(counts)):
            n_g = int(counts[d])
            if n_g == 0:
                continue
            for ci, name in enumerate(names):
                v = np.asarray(out_v[ci][d][:n_g])
                m = np.asarray(out_m[ci][d][:n_g])
                dt = dtypes[ci]
                for j in range(n_g):
                    if not m[j]:
                        rows[name].append(None)
                    elif key_dicts[ci] is not None:
                        rows[name].append(
                            key_dicts[ci][int(v[j])].as_py())
                    else:
                        rows[name].append(_pyval(v[j], dt))
        return pa.table({n: pa.array(rows[n], T.to_arrow_type(dt))
                         for n, dt in zip(names, dtypes)})


def _key_ordinal(expr, schema) -> int:
    from spark_rapids_tpu.expr.core import BoundReference
    if isinstance(expr, BoundReference):
        return expr.ordinal
    return 0


def _pyval(v, dt: T.DataType):
    if isinstance(dt, T.BooleanType):
        return bool(v)
    if isinstance(dt, (T.FloatType, T.DoubleType)):
        return float(v)
    return int(v)
