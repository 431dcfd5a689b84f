"""DataFrame caching — materialize-once plan nodes.

Reference (SURVEY.md #42): ParquetCachedBatchSerializer caches dataframes as
GPU-written parquet blobs with a CPU fallback path. Two tiers here, selected by
conf `spark.rapids.tpu.sql.cache.serializer`:
  - "device": a partition's batches are coalesced toward the engine's target
    batch size (`spark.rapids.tpu.sql.batchSizeBytes`, the goal of every
    coalesce: exec/coalesce.py) and kept as SpillableColumnarBatches in the
    spill hierarchy (evictable HBM→host→disk) — the fast path. A partition
    under the target is ONE batch at its row count's bucket: on a v5e a
    query's host pays about 6 ms a further batch it is handed (a dispatch
    and the aggregate chain's blocking status read), more than the device
    pays for the 28 % of padding (PERF.md, PR 33: 66.6 M against 52.7 M
    input rows/s for Q1 over SF 1's lineitem as one 8 Mi batch against the
    scan's own eight); a larger
    partition is several batches, none past the target. A scan column whose
    decode was deferred into its consumer is expanded once, here. A read
    hands the resident arrays out uncopied; a batch that was demoted comes
    back to HBM on its next read and stays there;
  - "parquet": partitions are written once as parquet blobs in a temp dir and
    re-read on use — survives device memory pressure entirely, byte-compatible
    with external readers (the reference's actual design).

Spans (runtime/tracing.py, docs/observability.md): ``cache.materialize`` once
a node, ``CachedScan.read`` a cached batch handed to a query; a batch that
had to come back meters its upload at movement site ``cache.unspill``."""

from __future__ import annotations

import os
import shutil
import tempfile
import threading

import pyarrow as pa
import pyarrow.parquet as pq

from spark_rapids_tpu.plan.nodes import PlanNode
from spark_rapids_tpu.runtime import tracing as TR


def _dense(batch):
    """The batch with every scan column whose decode was deferred into its
    consumer expanded, once: what the cache keeps is dense values and
    dictionary codes, never an encoded page a query would decode again."""
    from spark_rapids_tpu.columnar.encoded import EncodedColumnVector
    from spark_rapids_tpu.columnar.vector import TpuColumnVector
    if not any(isinstance(c, EncodedColumnVector) for c in batch.columns):
        return batch
    return batch.with_columns(
        [TpuColumnVector(c.dtype, c.data, c.validity, c.dictionary)
         if isinstance(c, EncodedColumnVector) else c for c in batch.columns])


class CacheNode(PlanNode):
    def __init__(self, child: PlanNode, serializer: str = "device",
                 session=None):
        super().__init__(child)
        assert serializer in ("device", "parquet")
        self.serializer = serializer
        self.session = session
        self._n_parts = child.num_partitions  # pinned: survives child mutation
        self._lock = threading.Lock()
        self._host_tables: list | None = None
        self._device_batches: list | None = None
        self._parquet_dir: str | None = None

    @property
    def output(self):
        return self.child.output

    @property
    def num_partitions(self):
        return self._n_parts

    # -- materialization ----------------------------------------------------
    def _materialize_host(self):
        with self._lock:
            if self._host_tables is None:
                self._host_tables = [self.child.execute_host(i)
                                     for i in range(self._n_parts)]
        return self._host_tables

    def materialize_device(self, conf):
        """Run the DEVICE plan for the child once; cache per-partition results.
        Returns the number of cached device partitions — the DEVICE plan's
        partitioning (e.g. an aggregate's post-exchange layout), which may
        differ from the host interpreter's (called by CachedScanExec)."""
        from spark_rapids_tpu.exec.base import TaskContext
        from spark_rapids_tpu.exec.coalesce import (TargetSize,
                                                    coalesce_iterator)
        from spark_rapids_tpu.plan.transitions import to_device_plan
        from spark_rapids_tpu.runtime import memory as mem
        with self._lock:
            if self.serializer == "parquet":
                if self._parquet_dir is None:
                    with TR.span("cache.materialize", tier="parquet") as sp:
                        self._write_parquet(conf, sp)
                return len(os.listdir(self._parquet_dir))
            if self._device_batches is not None:
                return len(self._device_batches)
            with TR.span("cache.materialize", tier="device") as sp:
                hybrid = to_device_plan(self.child, conf)
                goal = TargetSize(conf.batch_size_bytes)
                out = []
                for split in range(hybrid.num_partitions):
                    part = []
                    with TaskContext():
                        produced = map(_dense,
                                       hybrid.execute_partition(split))
                        for batch in coalesce_iterator(produced, goal,
                                                       conf=conf):
                            # retained: cache batches OUTLIVE the materializing
                            # query on purpose (until unpersist), so the
                            # end-of-query leak detector must not flag them;
                            # the query tag stays for fair-share demotion
                            # accounting
                            with mem.alloc_site("cache.device", retained=True):
                                part.append(mem.SpillableColumnarBatch(batch))
                    out.append(part)
                self._device_batches = out
                if sp:
                    held = [sb for part in out for sb in part]
                    sp.set(rows=sum(sb.num_rows for sb in held),
                           partitions=len(out), batches=len(held),
                           capacity=sum(sb.capacity for sb in held),
                           columns=len(self.output.fields),
                           bytes=sum(sb.size for sb in held))
            return len(out)

    def _write_parquet(self, conf, sp):
        from spark_rapids_tpu.exec.base import TaskContext
        from spark_rapids_tpu.plan.transitions import to_device_plan
        d = tempfile.mkdtemp(prefix="tpu-cache-")
        hybrid = to_device_plan(self.child, conf)
        rows = batches = nbytes = 0
        for split in range(hybrid.num_partitions):
            with TaskContext():
                tables = [b.to_arrow()
                          for b in hybrid.execute_partition(split)]
            tbl = (pa.concat_tables(tables) if tables else self._empty())
            path = os.path.join(d, f"part-{split:05d}.parquet")
            pq.write_table(tbl, path)
            rows, batches = rows + tbl.num_rows, batches + len(tables)
            nbytes += os.path.getsize(path)
        self._parquet_dir = d
        sp.set(rows=rows, partitions=hybrid.num_partitions, batches=batches,
               capacity=rows, columns=len(self.output.fields), bytes=nbytes)

    def read_batches(self, split: int):
        """Device-side read of a cached partition, a batch at a time."""
        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        from spark_rapids_tpu.runtime import memory as mem
        from spark_rapids_tpu.runtime import movement as MV
        if self.serializer == "parquet":
            with TR.span("CachedScan.read", tier="parquet") as sp:
                path = os.path.join(self._parquet_dir,
                                    f"part-{split:05d}.parquet")
                batch = ColumnarBatch.from_arrow(pq.read_table(path),
                                                 self.output)
                sp.set(rows=batch.num_rows, capacity=batch.capacity,
                       bytes=os.path.getsize(path))
            yield batch
            return
        for sb in self._device_batches[split]:
            with TR.span("CachedScan.read") as sp:
                # unspill: a demoted batch of a hot table comes back to stay
                batch, tier = sb.acquire(unspill=True)
                if tier != mem.TierEnum.DEVICE:
                    MV.record_h2d(sb.size, site="cache.unspill")
                sp.set(rows=sb.num_rows, capacity=sb.capacity, bytes=sb.size,
                       tier=tier.lower())
            yield batch

    def execute_host(self, split):
        return self._materialize_host()[split]

    def unpersist(self):
        with self._lock:
            for part in self._device_batches or ():
                for sb in part:
                    sb.close()
            self._device_batches = None
            self._host_tables = None
            if self._parquet_dir:
                shutil.rmtree(self._parquet_dir, ignore_errors=True)
                self._parquet_dir = None

    def name(self):
        return f"Cache[{self.serializer}]"


class CachedScanExec:
    """Leaf device exec over a CacheNode (imports deferred to avoid plan↔exec
    import cycles at module load)."""

    def __new__(cls, node: CacheNode, conf=None):
        from spark_rapids_tpu.exec.base import TpuExec, acquire_semaphore

        class _Exec(TpuExec):
            def __init__(self, node, conf):
                super().__init__(conf=conf)
                self.node = node

            @property
            def output(self):
                return self.node.output

            @property
            def num_partitions(self):
                # the DEVICE cache layout, not the host interpreter's; forces
                # materialization at planning time (once)
                return self.node.materialize_device(self.conf)

            def execute_partition(self, split):
                def it():
                    self.node.materialize_device(self.conf)
                    for i, batch in enumerate(self.node.read_batches(split)):
                        if i == 0:
                            acquire_semaphore(self.metrics)
                        yield batch
                return self.wrap_output(it())

            def args_string(self):
                return self.node.name()

        return _Exec(node, conf)
