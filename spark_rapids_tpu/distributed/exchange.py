"""MeshExchangeExec — the shuffle exchange as ONE jitted SPMD program over ICI.

Reference mapping: in the reference the exchange IS the distributed engine —
GpuShuffleExchangeExec.scala:80-167 partitions batches on device and the UCX
transport (shuffle-plugin, UCXShuffleTransport.scala) moves blocks peer-to-peer;
joins (GpuShuffledHashJoinBase.scala:97) and sorts ride co-partitioned exchanges.

On a TPU slice the idiomatic data plane is not peer-to-peer RPC but an XLA
`all_to_all` collective over the mesh ("data" axis, ICI links): every device
computes Spark-exact partition ids for its rows, compacts rows per destination,
and one collective moves every row-group in a single step. This exec keeps
ShuffleExchangeExec's external contract (child partitions in, one output
partition per device out) so HashJoinExec / HashAggregateExec / SortExec
compose with it unchanged: the planner routes exchanges here when
`spark.rapids.tpu.mesh.enabled` is set.

What it does today, stage by stage (each is a span under the query's root):

  MeshExchange.map         the child's partitions are drained on a thread pool
                           and brought to the HOST as Arrow tables (one D2H of
                           every live row), their rows dealt evenly onto the
                           shards whatever the child's partitions were.
  MeshExchange.ingest      `encode_shards` uploads each shard again, pads it to
                           one capacity and re-codes strings against a
                           mesh-global dictionary; `put_stacked_shards` lays
                           the stack over the mesh.
  MeshExchange.collective  the SPMD program: partition ids, compaction by
                           destination, `all_to_all`, re-pack. "No host hop"
                           holds for this stage alone. The program is built
                           once a shape (mesh, column types, capacity,
                           partitioner, key expressions, dictionaries, bounds'
                           shape) in runtime/fuse.py's kernel table and is
                           named `jit_srt_MeshExchange_<hash|range|roundrobin>`
                           in a device trace; range bounds are operands.
  sync.count               ONE read of the four received-row counts.

Reduce partition d is then built from device d's own shard of the result
(`addressable_shards`), cut to its bucket by `jit_srt_MeshExchange_slice` ON
device d: its columns are single-device arrays committed to that device, so
whatever consumes the partition runs there, four partition tasks on four
chips at once. What brings partitions of several chips together moves them
explicitly (`columnar.batch.batch_to_device`: `_GatherAllExec`, the broadcast
build); `collect()` reads each where it lies.

The map side's host round trip is measured (`d2h_bytes`, `h2d_bytes`, the
movement ledger's sites `mesh.exchange.map` / `mesh.exchange.ingest`), not yet
mended: keeping scan partition p on device p % n and assembling the global
array from single-device arrays is ROADMAP Queue 1's next item.

Supported partitionings: hash (Spark murmur3, bit-exact — strings hash their
UTF-8 bytes via the mesh-global dictionary so both join sides agree), range
(host-sampled bounds compared in mesh-global code space; global dictionaries
are sorted, so code order == lexicographic order), and round-robin
(axis_index-offset deal)."""

from __future__ import annotations

import threading

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.vector import TpuColumnVector, bucket_capacity
from spark_rapids_tpu.distributed.mesh import encode_shards, put_stacked_shards
from spark_rapids_tpu.exec.base import TpuExec, TaskContext
from spark_rapids_tpu.expr.core import Col, EvalContext
from spark_rapids_tpu.ops import hashing as H
from spark_rapids_tpu.ops.filtering import compact_cols
from spark_rapids_tpu.ops.hashing import pack_utf8_words
from spark_rapids_tpu.runtime import fuse, tracing
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import movement as MV
from spark_rapids_tpu.shuffle.partitioning import (
    HashPartitioner, Partitioner, RangePartitioner, RoundRobinPartitioner,
    murmur3_row_hash, range_part_ids)


def mesh_devices(conf) -> list:
    """Devices forming the execution mesh per conf (0 = all visible)."""
    want = conf.get(C.MESH_DEVICES)
    devs = jax.devices()
    return list(devs if want <= 0 else devs[:want])


def _string_dict_words(col: Col):
    """(words, lens) device packing of a Col's dictionary (trace-time constant:
    the dictionary is static metadata, only the codes are traced)."""
    strs = col.dictionary.to_pylist() if col.dictionary is not None else []
    words, lens = pack_utf8_words(strs)
    if words.shape[0] == 0:
        words = np.zeros((1, 1), dtype=np.int32)
        lens = np.zeros(1, dtype=np.int32)
    return jnp.asarray(words), jnp.asarray(lens)


def row_exchange(cols, n_rows, pids, n_dev: int, cap: int):
    """The generic ICI row exchange, called inside shard_map: compact this
    shard's rows per destination device, all_to_all the stacked groups over the
    "data" axis, and re-pack received rows to the front. Returns
    (merged_cols with (n_dev*cap,) arrays, m_rows device scalar)."""
    live = jnp.arange(cap, dtype=jnp.int32) < n_rows
    sends_v, sends_m, sends_n = [], [], []
    for p in range(n_dev):
        mask = live & (pids == p)
        pc, pn = compact_cols(cols, mask)
        sends_v.append([c.values for c in pc])
        sends_m.append([c.validity for c in pc])
        sends_n.append(pn)
    ncols = len(cols)
    stacked_v = [jnp.stack([sends_v[p][c] for p in range(n_dev)])
                 for c in range(ncols)]
    stacked_m = [jnp.stack([sends_m[p][c] for p in range(n_dev)])
                 for c in range(ncols)]
    sn = jnp.stack(sends_n)
    recv_v = [jax.lax.all_to_all(a, "data", 0, 0) for a in stacked_v]
    recv_m = [jax.lax.all_to_all(a, "data", 0, 0) for a in stacked_m]
    rn = jax.lax.all_to_all(sn, "data", 0, 0)

    mcap = n_dev * cap
    slot = jnp.arange(mcap, dtype=jnp.int32) % cap
    rlive = slot < jnp.repeat(rn, cap)
    rcols = []
    for c in range(ncols):
        v = recv_v[c].reshape(mcap)
        m = recv_m[c].reshape(mcap)
        proto = cols[c]
        default = jnp.asarray(proto.dtype.default_value(), dtype=v.dtype)
        rcols.append(Col(jnp.where(m & rlive, v, default), m & rlive,
                         proto.dtype, proto.dictionary))
    # pack present rows (null-valued rows included — presence is rlive, not
    # value validity) to the front
    merged, m_rows = compact_cols(rcols, rlive)
    return merged, m_rows


def row_bytes(schema) -> int:
    """Bytes one row of `schema` crosses a link with: every column's value
    (strings as their int32 code) and its validity byte."""
    return sum(np.dtype(f.data_type.jnp_dtype).itemsize + 1
               for f in schema.fields)


def _pids_fn(part: Partitioner, n: int, cap: int):
    """(name, fn(cols, n_rows, bounds) -> pids) of a bound partitioner, run
    inside shard_map. Closes over the partitioner's expressions and the
    bounds' types only: the bounds' VALUES are operands of the program."""
    if isinstance(part, HashPartitioner):
        key_exprs = list(part.key_exprs)

        def hash_pids(cols, n_rows, bounds):
            ctx = EvalContext(cols, n_rows, cap)
            keys = [e.eval(ctx) for e in key_exprs]
            dict_words = {i: _string_dict_words(k)
                          for i, k in enumerate(keys) if k.is_string}
            h = murmur3_row_hash(keys, cap, dict_words=dict_words)
            return H.pmod(h, n)
        return "hash", hash_pids
    if isinstance(part, RangePartitioner):
        sort_exprs, orders = list(part.sort_exprs), list(part.orders)

        def range_pids(cols, n_rows, bounds):
            if not bounds:
                return jnp.zeros((cap,), jnp.int32)
            ctx = EvalContext(cols, n_rows, cap)
            keys = [e.eval(ctx) for e in sort_exprs]
            return range_part_ids(keys, bounds, orders, cap)
        return "range", range_pids
    if isinstance(part, RoundRobinPartitioner):
        def rr_pids(cols, n_rows, bounds):
            start = jax.lax.axis_index("data").astype(jnp.int32)
            return (jnp.arange(cap, dtype=jnp.int32) + start) % n
        return "roundrobin", rr_pids
    raise ValueError(
        f"mesh exchange does not support {type(part).__name__}")


def _partitioner_key(part: Partitioner) -> tuple:
    """Everything of a bound partitioner that the traced program depends on."""
    if isinstance(part, HashPartitioner):
        return ("hash", tuple(fuse.expr_key(e) for e in part.key_exprs))
    if isinstance(part, RangePartitioner):
        return ("range", tuple(fuse.expr_key(e) for e in part.sort_exprs),
                tuple((o.ascending, o.nulls_first) for o in part.orders),
                tuple((b.dtype, b.values.shape[0], _dict_ref(b.dictionary))
                      for b in part._bounds or ()))
    return (type(part).__name__,)


def _dict_ref(dictionary):
    return None if dictionary is None else fuse.DictRef(dictionary)


def exchange_step(mesh: Mesh, schema, cap: int, part: Partitioner, dicts):
    """The SPMD exchange program for one shape, from runtime/fuse.py's kernel
    table: every later exchange and query of the session with the same mesh,
    column types, capacity, partitioner, key expressions, dictionaries and
    bounds' shape replays it. Called as step(*vals, *masks, nrows, *bounds)
    with the range bounds' (values, validity) replicated."""
    fields = list(schema.fields)
    n_dev, n_cols = mesh.size, len(fields)
    kind, pids_fn = _pids_fn(part, n_dev, cap)
    bound_meta = [(b.dtype, b.dictionary)
                  for b in getattr(part, "_bounds", None) or ()]
    key = ("MeshExchange", mesh, tuple((f.data_type, f.nullable)
                                       for f in fields), cap,
           _partitioner_key(part),
           tuple(sorted((ci, fuse.DictRef(d)) for ci, d in dicts.items())))

    def build():
        def shard_step(*flat):
            vals = flat[:n_cols]
            masks = flat[n_cols:2 * n_cols]
            n_rows = flat[2 * n_cols][0]
            bvals = flat[2 * n_cols + 1:]
            # re-attach the mesh-global dictionaries (static metadata): string
            # keys must hash/compare their actual UTF-8 bytes, not bare codes
            cols = [Col(v[0], m[0], f.data_type, dicts.get(ci))
                    for ci, (v, m, f) in enumerate(zip(vals, masks, fields))]
            bounds = [Col(bvals[2 * i], bvals[2 * i + 1], dt, dictionary)
                      for i, (dt, dictionary) in enumerate(bound_meta)]
            pids = pids_fn(cols, n_rows, bounds)
            merged, m_rows = row_exchange(cols, n_rows, pids, n_dev, cap)
            return (tuple(c.values[None] for c in merged)
                    + tuple(c.validity[None] for c in merged)
                    + (m_rows[None],))

        spec = P("data", None)
        return jax.shard_map(
            shard_step, mesh=mesh,
            in_specs=tuple([spec] * (2 * n_cols) + [P("data")]
                           + [P()] * (2 * len(bound_meta))),
            out_specs=tuple([spec] * (2 * n_cols) + [P("data")]))

    name = "MeshExchange." + kind
    if not fuse.key_is_cacheable(key):
        # a key expression with no stable content key (fuse.UNKEYABLE): the
        # program is this exchange's own, as every one was before the table
        return fuse.BatchKernel(build(), name)
    return fuse.get_kernel(key, name, build)


def _slice_kernel(pcap: int):
    """Partition d's batch out of device d's shard of the exchange's result,
    cut to its bucket ON that device: `(1, n_dev*cap)` pieces in, `(pcap,)`
    columns out, validity cleared past the `n` rows that arrived."""
    def build():
        def cut(vals, masks, n):
            live = jnp.arange(pcap, dtype=jnp.int32) < n
            return (tuple(v[0, :pcap] for v in vals),
                    tuple(m[0, :pcap] & live for m in masks))
        return cut
    return fuse.get_kernel(("MeshExchange.slice", pcap),
                           "MeshExchange.slice", build)


class MeshExchangeExec(TpuExec):
    """Mesh-backed drop-in for ShuffleExchangeExec: num_partitions == number of
    mesh devices; reduce partition d is whatever the all_to_all delivered to
    device d, and lives there."""

    def __init__(self, partitioner: Partitioner, child: TpuExec, conf=None,
                 devices=None):
        super().__init__(child, conf=conf)
        devs = devices if devices is not None else mesh_devices(self.conf)
        self.n = len(devs)
        if partitioner.num_partitions != self.n:
            raise ValueError(
                f"mesh exchange needs num_partitions == n_devices "
                f"({partitioner.num_partitions} != {self.n})")
        self.mesh = Mesh(np.array(devs), ("data",))
        self.partitioner = partitioner.bind(child.output)
        self._done = threading.Event()
        self._lock = threading.Lock()
        self._shard_out: list | None = None
        self._error = None
        self._partition_time = self.metrics.metric(M.PARTITION_TIME, M.MODERATE)

    @property
    def output(self):
        return self.child.output

    @property
    def num_partitions(self):
        return self.n

    # -- execution -------------------------------------------------------------
    def _collect_shard_tables(self):
        """Drain child partitions to the host (thread-pool map side, same as
        ShuffleExchangeExec) and deal their rows evenly onto the mesh devices:
        the shards' common capacity is then a quarter of the rows, not the
        largest child partition (a scan that packs its files into one
        partition would put every row in shard 0 and pad the other three to
        its size). Returns (one Arrow table a shard, device bytes that came
        down)."""
        import pyarrow as pa
        from concurrent.futures import ThreadPoolExecutor
        nparts = self.child.num_partitions
        drained: list[list] = [[] for _ in range(nparts)]
        d2h = [0] * nparts
        collector = M.current_collector()
        parent_span = tracing.current_span()

        def map_task(split):
            # pool thread: re-enter the query's scope and the span that
            # started the map stage (as exec/exchange.py's map_task does)
            with M.collector_context(collector), TaskContext(), \
                    tracing.child_of(parent_span):
                for b in self.child.execute_partition(split):
                    if b.num_rows:
                        d2h[split] += b.device_memory_size()
                        drained[split].append(
                            b.to_arrow(site="mesh.exchange.map"))

        nthreads = max(1, min(self.conf.get(C.NUM_LOCAL_TASKS), nparts))
        if nparts == 1:
            map_task(0)
        else:
            with ThreadPoolExecutor(max_workers=nthreads) as pool:
                list(pool.map(map_task, range(nparts)))
        whole = pa.concat_tables(
            [t for ts in drained for t in ts] or [self._empty_table()])
        per = -(-whole.num_rows // self.n)
        return [whole.slice(d * per, per) for d in range(self.n)], sum(d2h)

    def _empty_table(self):
        import pyarrow as pa
        return pa.table({f.name: pa.array([], T.to_arrow_type(f.data_type))
                         for f in self.output})

    def _run_exchange(self):
        schema = self.output
        n_out, rb = len(schema.fields), row_bytes(schema)
        with tracing.span("MeshExchange.map",
                          partitions=self.child.num_partitions) as sp:
            tables, d2h = self._collect_shard_tables()
            rows_in = sum(t.num_rows for t in tables)
            sp.set(rows=rows_in, d2h_bytes=d2h)
        with tracing.span("MeshExchange.ingest") as sp:
            shards, cap, global_dicts = encode_shards(tables, schema, self.n)
            vals, masks, nrows = put_stacked_shards(self.mesh, shards)
            # each shard goes up at its own bucket and is padded to the
            # common capacity on the device
            h2d = sum(bucket_capacity(t.num_rows) for t in tables) * rb
            MV.record_h2d(h2d, site="mesh.exchange.ingest")
            sp.set(rows=rows_in, h2d_bytes=h2d, capacity=cap)
        bounds = []
        if isinstance(self.partitioner, RangePartitioner):
            # bounds from a host-side sample of the ENCODED shards so string
            # bounds live in the mesh-global (sorted) dictionary space
            sample = [ColumnarBatch([c.to_vector() for c in cols], nr, schema)
                      for cols, nr in shards if nr > 0]
            if sample:
                self.partitioner.set_bounds_from_sample(sample)
            bounds = [a for b in self.partitioner._bounds or ()
                      for a in (b.values, b.validity)]

        step = exchange_step(self.mesh, schema, cap, self.partitioner,
                             global_dicts)
        # what the all_to_all is handed on every device: a (n, cap) block a
        # column, values and validity, padding included
        operand = self.n * self.n * cap * rb
        with tracing.trace_range(
                "MeshExchange.collective", self._partition_time,
                rows=rows_in, capacity=cap, columns=n_out, row_bytes=rb,
                devices=self.n, operand_bytes=operand,
                partitioner=step.name.rpartition(".")[2]):
            out = step(*vals, *masks, nrows, *bounds)
        MV.record("ici.collective", operand, link="ici",
                  site="mesh.exchange", payload_bytes=rows_in * rb)

        out_v, out_m, m_rows = out[:n_out], out[n_out:2 * n_out], out[-1]
        with tracing.span("sync.count") as sp:
            counts = np.asarray(m_rows)  # ONE host sync at the stage boundary
            sp.set(rows=int(counts.sum()), capacity=self.n * self.n * cap)
        # partition d from device d's own piece of every result array: the
        # batch is committed to that device and its consumers run there
        pieces = [{s.device: s.data for s in a.addressable_shards}
                  for a in (*out_v, *out_m)]
        batches = []
        for d, dev in enumerate(self.mesh.devices.flat):
            n = int(counts[d])
            pcap = min(bucket_capacity(max(n, 1)), self.n * cap)
            cut_v, cut_m = _slice_kernel(pcap)(
                tuple(p[dev] for p in pieces[:n_out]),
                tuple(p[dev] for p in pieces[n_out:]), n)
            batches.append(ColumnarBatch(
                [TpuColumnVector(f.data_type, v, m, global_dicts.get(ci))
                 for ci, (f, v, m) in enumerate(
                     zip(schema.fields, cut_v, cut_m))], n, schema))
        self._shard_out = batches

    def _ensure_exchange(self):
        if not self._done.is_set():
            with self._lock:
                if not self._done.is_set():
                    try:
                        self._run_exchange()
                    except BaseException as e:
                        self._error = e
                    finally:
                        self._done.set()
        if self._error is not None:
            raise RuntimeError("mesh exchange failed") from self._error

    def execute_partition(self, split):
        # release this task's permit before blocking on the collective map
        # stage (same deadlock-avoidance as ShuffleExchangeExec)
        from spark_rapids_tpu.exec.base import current_task_id
        from spark_rapids_tpu.runtime.semaphore import TpuSemaphore
        TpuSemaphore.get().release_if_necessary(current_task_id())
        self._ensure_exchange()

        def it():
            b = self._shard_out[split]
            if b.num_rows:
                yield b
        return self.wrap_output(it())

    def args_string(self):
        return (f"{type(self.partitioner).__name__}({self.n}) "
                f"mesh={self.n}dev")
