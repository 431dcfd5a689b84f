"""MeshExchangeExec — the shuffle exchange as ONE jitted SPMD program over ICI.

Reference mapping: in the reference the exchange IS the distributed engine —
GpuShuffleExchangeExec.scala:80-167 partitions batches on device and the UCX
transport (shuffle-plugin, UCXShuffleTransport.scala) moves blocks peer-to-peer;
joins (GpuShuffledHashJoinBase.scala:97) and sorts ride co-partitioned exchanges.

On a TPU slice the idiomatic data plane is not peer-to-peer RPC but an XLA
`all_to_all` collective over the mesh ("data" axis, ICI links): every device
computes Spark-exact partition ids for its rows, compacts rows per destination,
and one collective moves every row-group in a single step — no host hops. This
exec keeps ShuffleExchangeExec's external contract (child partitions in, one
output partition per device out) so HashJoinExec / HashAggregateExec / SortExec
compose with it unchanged: the planner routes exchanges here when
`spark.rapids.tpu.mesh.enabled` is set.

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
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

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
from spark_rapids_tpu.runtime import metrics as M
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


class MeshExchangeExec(TpuExec):
    """Mesh-backed drop-in for ShuffleExchangeExec: num_partitions == number of
    mesh devices; reduce partition d is whatever the all_to_all delivered to
    device d."""

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

    # -- partition-id programs (run inside shard_map, trace-time specialized) --
    def _pids_fn(self, cap: int):
        part = self.partitioner
        if isinstance(part, HashPartitioner):
            key_exprs = part.key_exprs
            n = self.n

            def hash_pids(cols, n_rows):
                ctx = EvalContext(cols, n_rows, cap)
                keys = [e.eval(ctx) for e in key_exprs]
                dict_words = {i: _string_dict_words(k)
                              for i, k in enumerate(keys) if k.is_string}
                h = murmur3_row_hash(keys, cap, dict_words=dict_words)
                return H.pmod(h, n)
            return hash_pids
        if isinstance(part, RangePartitioner):
            sort_exprs, orders, bounds = part.sort_exprs, part.orders, part._bounds

            def range_pids(cols, n_rows):
                if bounds is None:
                    return jnp.zeros((cap,), jnp.int32)
                ctx = EvalContext(cols, n_rows, cap)
                keys = [e.eval(ctx) for e in sort_exprs]
                return range_part_ids(keys, bounds, orders, cap)
            return range_pids
        if isinstance(part, RoundRobinPartitioner):
            n = self.n

            def rr_pids(cols, n_rows):
                start = jax.lax.axis_index("data").astype(jnp.int32)
                return (jnp.arange(cap, dtype=jnp.int32) + start) % n
            return rr_pids
        raise ValueError(
            f"mesh exchange does not support {type(part).__name__}")

    # -- the SPMD exchange program --------------------------------------------
    def _build_program(self, schema, cap, dicts):
        n_dev = self.n
        n_cols = len(schema.fields)
        pids_fn = self._pids_fn(cap)

        def shard_step(*flat):
            vals = flat[:n_cols]
            masks = flat[n_cols:2 * n_cols]
            n_rows = flat[2 * n_cols][0]
            # re-attach the mesh-global dictionaries (static metadata): string
            # keys must hash/compare their actual UTF-8 bytes, not bare codes
            cols = [Col(v[0], m[0], f.data_type, dicts.get(ci))
                    for ci, (v, m, f) in enumerate(
                        zip(vals, masks, schema.fields))]
            pids = pids_fn(cols, n_rows)
            merged, m_rows = row_exchange(cols, n_rows, pids, n_dev, cap)
            return (tuple(c.values[None] for c in merged)
                    + tuple(c.validity[None] for c in merged)
                    + (m_rows[None],))

        spec = P("data", None)
        return jax.jit(jax.shard_map(
            shard_step, mesh=self.mesh,
            in_specs=tuple([spec] * (2 * n_cols) + [P("data")]),
            out_specs=tuple([spec] * (2 * n_cols) + [P("data")])))

    # -- execution -------------------------------------------------------------
    def _collect_shard_tables(self):
        """Drain child partitions on host (thread-pool map side, same as
        ShuffleExchangeExec), dealing them round-robin onto the mesh devices."""
        import pyarrow as pa
        from concurrent.futures import ThreadPoolExecutor
        per_dev: list[list] = [[] for _ in range(self.n)]
        lock = threading.Lock()

        def map_task(split):
            with TaskContext():
                got = [b.to_arrow() for b in self.child.execute_partition(split)
                       if b.num_rows]
            with lock:
                per_dev[split % self.n].extend(got)

        nparts = self.child.num_partitions
        nthreads = max(1, min(self.conf.get(C.NUM_LOCAL_TASKS), nparts))
        if nparts == 1:
            map_task(0)
        else:
            with ThreadPoolExecutor(max_workers=nthreads) as pool:
                list(pool.map(map_task, range(nparts)))
        empty = self._empty_table()
        return [pa.concat_tables(ts) if ts else empty for ts in per_dev]

    def _empty_table(self):
        import pyarrow as pa
        return pa.table({f.name: pa.array([], T.to_arrow_type(f.data_type))
                         for f in self.output})

    def _run_exchange(self):
        schema = self.output
        tables = self._collect_shard_tables()
        shards, cap, global_dicts = encode_shards(tables, schema, self.n)
        if isinstance(self.partitioner, RangePartitioner):
            # bounds from a host-side sample of the ENCODED shards so string
            # bounds live in the mesh-global (sorted) dictionary space
            sample = [ColumnarBatch([c.to_vector() for c in cols], nr, schema)
                      for cols, nr in shards if nr > 0]
            if sample:
                self.partitioner.set_bounds_from_sample(sample)

        with self._partition_time.timed():
            step = self._build_program(schema, cap, global_dicts)
            vals, masks, nrows = put_stacked_shards(self.mesh, shards)
            out = step(*vals, *masks, nrows)

        n_out = len(schema.fields)
        out_v, out_m, m_rows = out[:n_out], out[n_out:2 * n_out], out[-1]
        counts = np.asarray(m_rows)  # ONE host sync at the stage boundary
        dicts = global_dicts
        batches = []
        for d in range(self.n):
            n = int(counts[d])
            pcap = min(bucket_capacity(max(n, 1)), self.n * cap)
            cvs = []
            for ci, f in enumerate(schema.fields):
                v = out_v[ci][d][:pcap]
                m = out_m[ci][d][:pcap] & (jnp.arange(pcap) < n)
                cvs.append(TpuColumnVector(f.data_type, v, m, dicts.get(ci)))
            batches.append(ColumnarBatch(cvs, n, schema))
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
