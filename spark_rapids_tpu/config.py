"""Typed configuration registry — the RapidsConf analog.

Reference: sql-plugin/src/main/scala/com/nvidia/spark/rapids/RapidsConf.scala:30,116,288
(110 typed `spark.rapids.*` entries with docs/defaults/internal flags, byte-unit parsing,
and markdown doc generation via `main`, RapidsConf.scala:1259). Same design here under the
`spark.rapids.tpu.*` namespace: a ConfBuilder DSL registers ConfEntry objects; RapidsConf
wraps a plain dict of overrides and resolves typed values; `python -m
spark_rapids_tpu.config` regenerates docs/configs.md.
"""

from __future__ import annotations

import dataclasses
import re
import typing

_REGISTERED: "dict[str, ConfEntry]" = {}

_BYTE_SUFFIXES = {
    "b": 1, "k": 1 << 10, "kb": 1 << 10, "m": 1 << 20, "mb": 1 << 20,
    "g": 1 << 30, "gb": 1 << 30, "t": 1 << 40, "tb": 1 << 40,
}


def parse_bytes(v) -> int:
    """Parse '512m', '4g', plain ints — Spark byte-unit strings
    (reference RapidsConf.scala byteConf entries)."""
    if isinstance(v, (int, float)):
        return int(v)
    m = re.fullmatch(r"\s*(\d+)\s*([a-zA-Z]*)\s*", str(v))
    if not m:
        raise ValueError(f"cannot parse byte value {v!r}")
    n, suf = int(m.group(1)), m.group(2).lower()
    if suf and suf not in _BYTE_SUFFIXES:
        raise ValueError(f"unknown byte suffix {suf!r} in {v!r}")
    return n * _BYTE_SUFFIXES.get(suf, 1)


def _parse_bool(v) -> bool:
    if isinstance(v, bool):
        return v
    return str(v).strip().lower() in ("true", "1", "yes")


@dataclasses.dataclass(frozen=True)
class ConfEntry:
    key: str
    doc: str
    default: typing.Any
    conv: typing.Callable
    internal: bool = False

    def get(self, settings: dict):
        if self.key in settings:
            return self.conv(settings[self.key])
        return self.default


class ConfBuilder:
    """`conf("spark.rapids.tpu.x").doc(...).boolean_conf(default)` DSL
    (reference RapidsConf.scala:288 ConfBuilder)."""

    def __init__(self, key: str):
        self._key = key
        self._doc = ""
        self._internal = False

    def doc(self, d: str) -> "ConfBuilder":
        self._doc = d
        return self

    def internal(self) -> "ConfBuilder":
        self._internal = True
        return self

    def _register(self, default, conv) -> ConfEntry:
        e = ConfEntry(self._key, self._doc, default, conv, self._internal)
        if e.key in _REGISTERED:
            raise ValueError(f"duplicate conf key {e.key}")
        _REGISTERED[e.key] = e
        return e

    def boolean_conf(self, default: bool) -> ConfEntry:
        return self._register(default, _parse_bool)

    def integer_conf(self, default: int) -> ConfEntry:
        return self._register(default, int)

    def double_conf(self, default: float) -> ConfEntry:
        return self._register(default, float)

    def string_conf(self, default) -> ConfEntry:
        return self._register(default, lambda v: v if v is None else str(v))

    def bytes_conf(self, default) -> ConfEntry:
        return self._register(parse_bytes(default), parse_bytes)


def conf(key: str) -> ConfBuilder:
    return ConfBuilder(key)


# ---------------------------------------------------------------------------
# Registry — mirrors the reference's main knobs (RapidsConf.scala:301-1139)
# ---------------------------------------------------------------------------

SQL_ENABLED = conf("spark.rapids.tpu.sql.enabled").doc(
    "Enable TPU acceleration of SQL operators; when false every plan stays on CPU "
    "(reference spark.rapids.sql.enabled)").boolean_conf(True)

EXPLAIN = conf("spark.rapids.tpu.sql.explain").doc(
    "NONE | ALL | NOT_ON_TPU — log why operators will / will not run on the TPU "
    "(reference spark.rapids.sql.explain)").string_conf("NONE")

BATCH_SIZE_BYTES = conf("spark.rapids.tpu.sql.batchSizeBytes").doc(
    "Target size of output batches from coalescing and readers "
    "(reference spark.rapids.sql.batchSizeBytes, RapidsConf.scala:411)"
).bytes_conf("512m")

MAX_READER_BATCH_SIZE_ROWS = conf("spark.rapids.tpu.sql.reader.batchSizeRows").doc(
    "Soft cap on rows per reader batch (reference reader.batchSizeRows)"
).integer_conf(2147483647)

MAX_READER_BATCH_SIZE_BYTES = conf("spark.rapids.tpu.sql.reader.batchSizeBytes").doc(
    "Soft cap on bytes per reader batch (reference reader.batchSizeBytes)"
).bytes_conf("512m")

CONCURRENT_TPU_TASKS = conf("spark.rapids.tpu.sql.concurrentTpuTasks").doc(
    "Tasks admitted to the TPU concurrently via the semaphore "
    "(reference spark.rapids.sql.concurrentGpuTasks, RapidsConf.scala:398)"
).integer_conf(2)

DEVICE_ORDINAL = conf("spark.rapids.tpu.device.ordinal").doc(
    "Which visible accelerator device this process acquires (reference: one "
    "GPU per executor, GpuDeviceManager.scala:103)").integer_conf(0)

DEVICE_EAGER_INIT = conf("spark.rapids.tpu.device.eagerInit").doc(
    "Acquire and warm up the device at session creation instead of first "
    "use — fails fast on a dead backend like the reference's executor "
    "plugin (Plugin.scala:210 crash-fast)").boolean_conf(False)

DEVICE_MEMORY_FRACTION = conf("spark.rapids.tpu.memory.hbm.allocFraction").doc(
    "Fraction of HBM the pool budget may use "
    "(reference spark.rapids.memory.gpu.allocFraction)").double_conf(0.9)

DEVICE_MEMORY_LIMIT = conf("spark.rapids.tpu.memory.hbm.limitBytes").doc(
    "Absolute HBM budget override; 0 = derive from allocFraction").bytes_conf(0)

HOST_SPILL_STORAGE_SIZE = conf("spark.rapids.tpu.memory.host.spillStorageSize").doc(
    "Bytes of host memory used for spilled device buffers before disk "
    "(reference spark.rapids.memory.host.spillStorageSize)").bytes_conf("1g")

SPILL_DIRS = conf("spark.rapids.tpu.memory.spill.dirs").doc(
    "Comma-separated local dirs for the disk spill tier "
    "(reference uses Spark local dirs, RapidsDiskStore.scala)").string_conf(None)

DIRECT_SPILL_ENABLED = conf(
    "spark.rapids.tpu.memory.direct.storage.spill.enabled").doc(
    "Spill the disk tier through the batched aligned direct-I/O store "
    "(O_DIRECT; the GDS analog — reference "
    "spark.rapids.memory.gpu.direct.storage.spill.enabled, RapidsGdsStore)"
).boolean_conf(False)

DIRECT_SPILL_BATCH_BYTES = conf(
    "spark.rapids.tpu.memory.direct.storage.spill.batchWriteBufferSize").doc(
    "Size at which a direct-spill batch file rotates (reference GDS "
    "batchWriteBufferSize)").bytes_conf("64m")

STRICT_DEVICE_BUDGET = conf("spark.rapids.tpu.memory.hbm.strictBudget").doc(
    "When a registration cannot spill the device tier back under the HBM "
    "budget, raise a retryable DeviceOomError (the DeviceMemoryEventHandler "
    "OOM analog) so the task-scoped retry framework (runtime/retry.py) can "
    "spill, split the input batch and re-run. false restores the legacy "
    "lenient accounting that silently left the device tier over budget"
).boolean_conf(True)

RETRY_MAX_SPLITS = conf("spark.rapids.tpu.memory.retry.maxSplits").doc(
    "Times one input batch may be split in half by OOM split-and-retry "
    "before the error is re-raised (reference RmmRapidsRetryIterator's "
    "splitSpillableInHalfByRows ladder)").integer_conf(8)

RETRY_SPLIT_FLOOR_BYTES = conf(
    "spark.rapids.tpu.memory.retry.splitFloorBytes").doc(
    "Split-and-retry never produces a batch smaller than this (nor below 2 "
    "rows); at the floor one spill-only retry runs and then the OOM "
    "propagates").bytes_conf("64k")

TEST_FAULTS = conf("spark.rapids.tpu.test.faults").doc(
    "Deterministic fault-injection spec 'kind:site:trigger,...' — kinds "
    "oom / splitoom / transport / error / exec_kill / hang / cancel / "
    "slow / corrupt / leak / disk_full; trigger COUNT, COUNT@SKIP or "
    "pPROB; e.g. 'oom:joins.build:2,transport:fetch:1,"
    "cancel:pipeline.put.scan.decode:1' (grammar + site list in "
    "runtime/faults.py; pipeline.put/get sites fire whatever kind is "
    "armed). Chaos testing only — never set in production; "
    "empty disables").string_conf(None)

TEST_FAULTS_SEED = conf("spark.rapids.tpu.test.faults.seed").doc(
    "Seed for probabilistic (pPROB) fault triggers; each (kind, site) "
    "entry draws from its own stream seeded by (seed, kind, site), so one "
    "seed yields one deterministic schedule per site even under the "
    "pipeline's worker-thread interleavings").integer_conf(0)

UNSPILL_ENABLED = conf("spark.rapids.tpu.memory.hbm.unspill.enabled").doc(
    "Re-promote spilled buffers back to HBM on access "
    "(reference spark.rapids.memory.gpu.unspill.enabled)").boolean_conf(False)

# NOTE: the reference's RMM pooling conf (spark.rapids.memory.gpu.pool,
# GpuDeviceManager.scala:204) has no TPU analog to toggle: XLA owns the HBM
# arena (BFC allocator) and the engine's power-of-two capacity bucketing
# (columnar/vector.py:bucket_capacity) is the pooling strategy — it is not
# optional, so no conf is registered for it.

STABLE_SORT = conf("spark.rapids.tpu.sql.stableSort.enabled").doc(
    "Force stable device sorts (reference spark.rapids.sql.stableSort.enabled)"
).boolean_conf(False)

HAS_NANS = conf("spark.rapids.tpu.sql.hasNans").doc(
    "Assume floating point columns may hold NaNs, enabling Spark-exact NaN ordering "
    "and equality (reference spark.rapids.sql.hasNans)").boolean_conf(True)

IMPROVED_FLOAT_OPS = conf("spark.rapids.tpu.sql.improvedFloatOps.enabled").doc(
    "Allow float aggregations whose ordering differs from CPU Spark "
    "(reference spark.rapids.sql.variableFloatAgg.enabled)").boolean_conf(True)

ENABLE_CAST_STRING_TO_FLOAT = conf("spark.rapids.tpu.sql.castStringToFloat.enabled").doc(
    "Enable string→float casts which can differ in rounding from CPU "
    "(reference spark.rapids.sql.castStringToFloat.enabled)").boolean_conf(False)

DECIMAL_ENABLED = conf("spark.rapids.tpu.sql.decimalType.enabled").doc(
    "Enable decimal(<=18) device execution (reference decimalType.enabled)"
).boolean_conf(True)

SHUFFLE_MANAGER_ENABLED = conf("spark.rapids.tpu.shuffle.enabled").doc(
    "Use the catalog-backed accelerated shuffle instead of the serializing fallback "
    "(reference RapidsShuffleManager wiring)").boolean_conf(True)

SHUFFLE_TRANSPORT_CLASS = conf("spark.rapids.tpu.shuffle.transport.class").doc(
    "Transport implementation classname for the P2P shuffle data plane "
    "(reference spark.rapids.shuffle.transport.class, RapidsConf.scala:925)"
).string_conf("spark_rapids_tpu.shuffle.transport.LocalTransport")

SHUFFLE_COMPRESSION_CODEC = conf("spark.rapids.tpu.shuffle.compression.codec").doc(
    "none | lz4 | copy — codec for shuffle buffers (reference "
    "spark.rapids.shuffle.compression.codec over nvcomp; here a native C++ LZ4)"
).string_conf("lz4")

SHUFFLE_COMPRESSION_TCP_ONLY = conf(
    "spark.rapids.tpu.shuffle.compression.tcpOnly").doc(
    "Compress shuffle frames only for peers whose link classifies as "
    "genuinely tcp (cross-host): loopback/local/ici stay uncompressed — "
    "spending CPU to shrink bytes that never cross a real wire loses. The "
    "movement ledger's wire-vs-payload dual units make the ratio visible "
    "per link class. false compresses every serialized transfer whenever "
    "the codec is active").boolean_conf(True)

SHUFFLE_MAX_INFLIGHT_BYTES = conf(
    "spark.rapids.tpu.shuffle.maxBytesInFlight").doc(
    "Throttle on concurrently fetched shuffle bytes "
    "(reference UCXShuffleTransport.scala:51-56)").bytes_conf("128m")

SHUFFLE_BOUNCE_BUFFER_SIZE = conf("spark.rapids.tpu.shuffle.bounceBuffers.size").doc(
    "Size of each staging (bounce) buffer used to window large transfers "
    "(reference spark.rapids.shuffle.bounceBuffers.size, 4 MB default)").bytes_conf("4m")

SHUFFLE_FETCH_MAX_RETRIES = conf("spark.rapids.tpu.shuffle.fetch.maxRetries").doc(
    "Fetch failures tolerated per reduce partition before the query fails; "
    "each failure invalidates the map outputs and recomputes them (reference "
    "TransferError -> FetchFailedException -> stage retry, "
    "RapidsShuffleIterator.scala:82)").integer_conf(2)

METRICS_LEVEL = conf("spark.rapids.tpu.sql.metrics.level").doc(
    "ESSENTIAL | MODERATE | DEBUG (reference spark.rapids.sql.metrics.level, "
    "RapidsConf.scala:465)").string_conf("MODERATE")

TRACE_ENABLED = conf("spark.rapids.tpu.sql.trace.enabled").doc(
    "Record spans (runtime/tracing.py; reference NVTX ranges, "
    "NvtxWithMetrics.scala): every trace_range/span region opens a "
    "jax.profiler trace annotation of its name, so a profiler capture shows "
    "it on the device trace's clock, and appends one record (name, id, "
    "parent, trace id, thread, perf_counter_ns start and end, counts) to a "
    "bounded in-process buffer read afterwards with tracing.recorded() / "
    "drain() / summarize(); docs/observability.md lists the spans. Off, a "
    "span site costs one check").boolean_conf(False)

CPU_FALLBACK_ENABLED = conf("spark.rapids.tpu.sql.cpuFallback.enabled").doc(
    "Allow untagged operators to run via the host (pyarrow) fallback engine rather "
    "than fail (the reference always retains Spark CPU execution)").boolean_conf(True)

TEST_ENABLED = conf("spark.rapids.tpu.sql.test.enabled").doc(
    "Fail if an operator unexpectedly falls back to CPU "
    "(reference spark.rapids.sql.test.enabled, RapidsConf.scala:854)").internal(
).boolean_conf(False)

TEST_ALLOWED_NON_TPU = conf("spark.rapids.tpu.sql.test.allowedNonTpu").doc(
    "Comma-separated operator class names allowed on CPU when test.enabled "
    "(reference test.allowedNonGpu)").internal().string_conf("")

ENABLE_WHOLE_STAGE_FUSION = conf("spark.rapids.tpu.sql.stageFusion.enabled").doc(
    "Trace adjacent project/filter/aggregate operators into a single XLA program. "
    "TPU-first optimization with no reference analog (cudf launches one kernel per op)"
).boolean_conf(True)

ENABLE_SCAN_FUSION = conf("spark.rapids.tpu.sql.stageFusion.scan.enabled").doc(
    "Fuse the parquet page-decode prologue (bit-unpack + dictionary gather + "
    "null spread) into the consuming aggregate's per-batch program, so a scan "
    "stage runs decode->project->filter->partial-agg as one XLA dispatch over "
    "ENCODED page bytes; batches no consumer can absorb decode standalone "
    "through the same fused kernel (degraded, never wrong). Requires "
    "stageFusion.enabled").boolean_conf(True)

ENABLE_GROUPBY_CHAIN = conf(
    "spark.rapids.tpu.sql.stageFusion.groupBy.chain.enabled").doc(
    "Chain the aggregation's per-batch update->concat->merge loop into one "
    "fused program per input batch with predictive output capacity (the "
    "broadcast-join probe-chain discipline): one host sync per batch instead "
    "of the per-batch key-stats / concat-count / right-sizing syncs. A "
    "mispredicted capacity discards the chained result and reruns the "
    "unchained path for that batch. Batches below a small capacity floor "
    "(1024) go unchained: the fused program's one-off compile cannot "
    "amortize over toy batches and would count against an armed cluster "
    "task deadline. Requires stageFusion.enabled"
).boolean_conf(True)

STAGE_CACHE_ENABLED = conf("spark.rapids.tpu.sql.stage.cache.enabled").doc(
    "Persist compiled stage executables (serialized XLA programs) to disk and "
    "reload them in later sessions, skipping tracing and compilation entirely "
    "on warm starts. Requires stage.cache.dir. Entries are keyed by backend "
    "platform + jax/package versions + kernel semantics + argument signature; "
    "corrupt or stale entries degrade to a retrace with a warning"
).boolean_conf(False)

STAGE_CACHE_DIR = conf("spark.rapids.tpu.sql.stage.cache.dir").doc(
    "Directory for the persistent compiled-stage cache (created on demand). "
    "Safe to share across sessions of the same build; entries from other "
    "backends/versions are ignored").string_conf("")

STAGE_CACHE_MAX_BYTES = conf("spark.rapids.tpu.sql.stage.cache.maxBytes").doc(
    "On-disk size budget for the compiled-stage cache; least-recently-used "
    "entries are pruned past it").bytes_conf("256m")

PARQUET_READER_TYPE = conf("spark.rapids.tpu.sql.format.parquet.reader.type").doc(
    "PERFILE | MULTITHREADED | COALESCING (reference GpuParquetScan.scala:317,426 "
    "reader strategies)").string_conf("MULTITHREADED")

MULTITHREADED_READ_NUM_THREADS = conf(
    "spark.rapids.tpu.sql.format.parquet.multiThreadedRead.numThreads").doc(
    "Thread pool size for the multithreaded reader (reference "
    "multiThreadedRead.numThreads)").integer_conf(20)

PARQUET_WRITER_TYPE = conf("spark.rapids.tpu.sql.format.parquet.writer.type").doc(
    "NATIVE encodes Parquet pages from device columns (stats + null "
    "compaction on device, thrift framing on host — reference "
    "ColumnarOutputWriter.scala device-buffer write); ARROW round-trips "
    "through host pyarrow. NATIVE falls back to ARROW for unsupported "
    "schemas (lists, decimal>18) and partitioned writes.").string_conf("NATIVE")

ORC_WRITER_TYPE = conf("spark.rapids.tpu.sql.format.orc.writer.type").doc(
    "NATIVE encodes ORC stripes from device columns (null compaction + "
    "stats on device, RLEv2/protobuf framing on host — reference "
    "GpuOrcFileFormat.scala device-buffer write); ARROW round-trips "
    "through host pyarrow. NATIVE falls back to ARROW for unsupported "
    "schemas (lists, decimal>18) and partitioned writes.").string_conf("NATIVE")

CSV_WRITER_TYPE = conf("spark.rapids.tpu.sql.format.csv.writer.type").doc(
    "NATIVE formats CSV from device buffers (one transfer per column, "
    "vectorized host text, no arrow round-trip); ARROW uses host pyarrow. "
    "NATIVE falls back to ARROW for unsupported schemas and partitioned "
    "writes; float/timestamp formatting differences are documented in "
    "io/csv_write_native.py.").string_conf("NATIVE")

CSV_ENABLED = conf("spark.rapids.tpu.sql.format.csv.enabled").doc(
    "Enable accelerated CSV reading (reference spark.rapids.sql.format.csv.enabled)"
).boolean_conf(True)

ORC_ENABLED = conf("spark.rapids.tpu.sql.format.orc.enabled").doc(
    "Enable accelerated ORC reading (reference spark.rapids.sql.format.orc.enabled)"
).boolean_conf(True)

NUM_LOCAL_TASKS = conf("spark.rapids.tpu.sql.localScheduler.numThreads").doc(
    "Partition-task threads in the local scheduler (stands in for Spark executor "
    "task slots; the reference delegates scheduling to Spark)").integer_conf(4)

MESH_ENABLED = conf("spark.rapids.tpu.mesh.enabled").doc(
    "Run shuffle exchanges as SPMD all_to_all collectives over a "
    "jax.sharding.Mesh (the ICI data plane; stands in for the reference's "
    "UCX RapidsShuffleManager, shuffle-plugin UCXShuffleTransport.scala). "
    "Joins, two-phase aggregates and global sorts then ride co-partitioned "
    "mesh exchanges").boolean_conf(False)

MESH_DEVICES = conf("spark.rapids.tpu.mesh.devices").doc(
    "Number of mesh devices for collective exchanges; 0 uses every visible "
    "device").integer_conf(0)

UDF_COMPILER_ENABLED = conf("spark.rapids.tpu.sql.udfCompiler.enabled").doc(
    "Compile Python UDF bytecode into device expressions "
    "(reference udf-compiler translates Scala bytecode → Catalyst)").boolean_conf(True)

CACHE_SERIALIZER = conf("spark.rapids.tpu.sql.cache.serializer").doc(
    "DataFrame cache tier: 'device' (spillable HBM batches) or 'parquet' "
    "(blob files; reference ParquetCachedBatchSerializer)").string_conf("device")

OPTIMIZER_ENABLED = conf("spark.rapids.tpu.sql.optimizer.enabled").doc(
    "Cost-based rejection of unprofitable device sections "
    "(reference spark.rapids.sql.optimizer.enabled, CostBasedOptimizer.scala:52)"
).boolean_conf(False)

OPTIMIZER_MIN_ROWS = conf("spark.rapids.tpu.sql.optimizer.minRows").doc(
    "Estimated row count below which a plan stays on the host when the "
    "optimizer is enabled (transfer+launch overhead dominates tiny inputs)"
).integer_conf(4096)

OPTIMIZER_HOST_ROW_COST = conf("spark.rapids.tpu.sql.optimizer.host.rowCost").doc(
    "Dual cost model: seconds per row·weight for host execution "
    "(reference spark.rapids.sql.optimizer.cpu.exec.*, CostBasedOptimizer.scala)"
).double_conf(60e-9)

OPTIMIZER_TPU_ROW_COST = conf("spark.rapids.tpu.sql.optimizer.tpu.rowCost").doc(
    "Dual cost model: seconds per row·weight for device execution "
    "(reference spark.rapids.sql.optimizer.gpu.exec.*)").double_conf(1.5e-9)

OPTIMIZER_TPU_DISPATCH_COST = conf(
    "spark.rapids.tpu.sql.optimizer.tpu.dispatchCost").doc(
    "Dual cost model: fixed seconds per device operator dispatch (one jit "
    "call)").double_conf(2e-3)

OPTIMIZER_TRANSFER_ROW_COST = conf(
    "spark.rapids.tpu.sql.optimizer.transferRowCost").doc(
    "Dual cost model: seconds per row crossing a host↔device boundary "
    "(the reference's transitionCost per-byte analog)").double_conf(8e-9)

ADAPTIVE_COALESCE_ENABLED = conf(
    "spark.rapids.tpu.sql.adaptive.coalescePartitions.enabled").doc(
    "After a shuffle map stage materializes, merge contiguous small reduce "
    "partitions into advisory-sized reader partitions (AQE; reference "
    "GpuCustomShuffleReaderExec + Spark CoalesceShufflePartitions)"
).boolean_conf(True)

ADVISORY_PARTITION_BYTES = conf(
    "spark.rapids.tpu.sql.adaptive.advisoryPartitionSizeInBytes").doc(
    "Target size of a coalesced post-shuffle partition "
    "(Spark spark.sql.adaptive.advisoryPartitionSizeInBytes)").bytes_conf("64m")

ORC_DEVICE_DECODE = conf("spark.rapids.tpu.sql.orc.deviceDecode.enabled").doc(
    "Decode in-scope ORC stripes on device (protobuf/RLEv2 run headers on "
    "host, packed bits unpacked on device — io/orc_native.py); out-of-scope "
    "files or columns fall back to the arrow host reader (reference "
    "GpuOrcScan hands stripes to libcudf)").boolean_conf(True)

CSV_DEVICE_DECODE = conf("spark.rapids.tpu.sql.csv.deviceDecode.enabled").doc(
    "Parse in-scope CSV files on device (host boundary scan + device digit "
    "kernels, io/csv_native.py); out-of-scope files use the arrow host "
    "reader (reference decodes CSV via cudf, GpuBatchScanExec)"
).boolean_conf(True)

CSV_READ_FLOATS = conf("spark.rapids.tpu.sql.csv.read.float.enabled").doc(
    "Allow float/double CSV columns on the device parse path; the final "
    "power-of-ten division can differ from Spark's strtod by 1 ulp "
    "(reference spark.rapids.sql.csv.read.float.enabled, same default)"
).boolean_conf(False)

SCAN_READAHEAD_DEPTH = conf("spark.rapids.tpu.sql.scan.readahead.depth").doc(
    "Decoded host batches a file scan prefetches ahead of device compute on "
    "a background thread (0 disables): host parquet/orc/csv decode of batch "
    "N+1 overlaps device compute of batch N for every reader strategy "
    "(reference MultiFileCloudParquetPartitionReader's prefetch role, "
    "GpuParquetScan.scala:1377, generalized past the MULTITHREADED reader)"
).integer_conf(2)

SCAN_READAHEAD_MAX_BUFFER = conf(
    "spark.rapids.tpu.sql.scan.readahead.maxBufferBytes").doc(
    "Byte cap on host tables buffered by the scan readahead queue; the "
    "effective budget also shrinks to the spill catalog's free host "
    "headroom (runtime/memory.scan_readahead_budget) so prefetch never "
    "competes with host spill storage").bytes_conf("256m")

PIPELINE_ENABLED = conf("spark.rapids.tpu.pipeline.enabled").doc(
    "Run each plan segment's batch loop on its own worker thread at the "
    "pipeline breakers (scan, exchange map/reduce, join build, sort, final "
    "collect), connected by bounded byte-budgeted queues, so host decode, "
    "device compute and exchange I/O overlap (runtime/pipeline.py; the "
    "reference gets this overlap from CUDA streams + UCX's async progress "
    "thread). Results are bit-identical either way").boolean_conf(True)

PIPELINE_QUEUE_DEPTH = conf("spark.rapids.tpu.pipeline.queueDepth").doc(
    "Batches one pipeline queue edge may hold ahead of its consumer; 2 is "
    "classic double buffering (batch N resident while N+1 decodes/uploads)"
).integer_conf(2)

PIPELINE_MAX_QUEUE_BYTES = conf("spark.rapids.tpu.pipeline.maxQueueBytes").doc(
    "Byte cap per pipeline queue edge; the effective budget also shrinks "
    "to the spill catalog's free host headroom "
    "(runtime/memory.host_prefetch_budget) and queued device batches are "
    "registered as spillable so the OOM-retry ladder can steal them"
).bytes_conf("256m")

BROADCAST_TIMEOUT = conf("spark.rapids.tpu.sql.broadcast.timeout").doc(
    "Seconds a consumer waits for the broadcast relation to materialize; "
    "<=0 waits forever (Spark spark.sql.broadcastTimeout; reference "
    "GpuBroadcastExchangeExec relation future)").double_conf(300.0)

BROADCAST_MAX_TABLE_BYTES = conf("spark.rapids.tpu.sql.broadcast.maxTableBytes"
                                 ).doc(
    "Fail a broadcast whose materialized relation exceeds this size "
    "(reference maxBroadcastTableSize guard); 0 disables").bytes_conf("8g")

CLUSTER_TASK_MAX_FAILURES = conf("spark.rapids.tpu.cluster.task.maxFailures").doc(
    "Attempts one MiniCluster task gets before the query fails with the "
    "task's error; each retry is placed on a different executor when one is "
    "available (Spark spark.task.maxFailures)").integer_conf(4)

CLUSTER_TASK_TIMEOUT = conf("spark.rapids.tpu.cluster.task.timeoutSeconds").doc(
    "Deadline for one MiniCluster task; a task running past it has its "
    "executor killed (the pipe protocol cannot cancel a hung task) and is "
    "retried on another executor, counting as a task failure against the "
    "slow executor. <=0 disables the deadline").double_conf(0.0)

CLUSTER_BLACKLIST_MAX_TASK_FAILURES = conf(
    "spark.rapids.tpu.cluster.blacklist.maxTaskFailures").doc(
    "Task failures charged to one executor before the driver blacklists it "
    "from further task placement (Spark spark.blacklist.* / "
    "spark.excludeOnFailure.*); a respawned executor starts with a clean "
    "record").integer_conf(2)

CLUSTER_STAGE_MAX_RECOMPUTES = conf(
    "spark.rapids.tpu.cluster.stage.maxRecomputes").doc(
    "Partial (lineage-scoped) recomputes one shuffle's map outputs may go "
    "through after executor losses before the driver falls back to the "
    "whole-query heal ladder (Spark spark.stage.maxConsecutiveAttempts)"
).integer_conf(4)

CLUSTER_SPECULATION_ENABLED = conf(
    "spark.rapids.tpu.cluster.speculation.enabled").doc(
    "Speculatively duplicate a stage's straggler tasks on idle executors "
    "once they exceed speculation.multiplier x the median completed task "
    "time; the first finisher wins and the loser's map outputs are "
    "discarded so results stay bit-identical (Spark spark.speculation)"
).boolean_conf(False)

CLUSTER_SPECULATION_MULTIPLIER = conf(
    "spark.rapids.tpu.cluster.speculation.multiplier").doc(
    "How many times slower than the median completed task time a running "
    "task must be before it is speculated "
    "(Spark spark.speculation.multiplier)").double_conf(3.0)

CLUSTER_PLACEMENT_SEED = conf("spark.rapids.tpu.cluster.placement.seed").doc(
    "Seed for the MiniCluster's deterministic round-robin task placement "
    "(rotates which executor gets the first task); tests use it to pin "
    "which executor hosts which map split").integer_conf(0)

CLUSTER_HEARTBEAT_TIMEOUT = conf(
    "spark.rapids.tpu.cluster.heartbeat.timeoutSeconds").doc(
    "Seconds without a liveness beat before the driver's heartbeat manager "
    "expires a MiniCluster executor (expire_dead -> partial stage "
    "recompute); beats are recorded on every task reply and liveness scan"
).double_conf(60.0)

CLUSTER_MESH_ENABLED = conf("spark.rapids.tpu.cluster.mesh.enabled").doc(
    "Unified mesh-cluster plane: every MiniCluster executor brings up a "
    "LOCAL device mesh (distributed/mesh.LocalMesh) and the driver groups a "
    "hash-partitioned map stage's splits into mesh tasks of up to "
    "devicesPerExecutor lanes — partition ids for all lanes are computed in "
    "ONE jitted shard_map program over the executor's chips with the "
    "map-output statistics all-reduced over ICI, while shuffle blocks still "
    "cross executors over the TCP transport (N processes x M chips, the "
    "reference's production shape). A mesh failure degrades transparently "
    "to per-split TCP execution, bit-identical (docs/cluster.md)"
).boolean_conf(False)

CLUSTER_MESH_TWO_LEVEL = conf(
    "spark.rapids.tpu.cluster.mesh.exchange.twoLevel").doc(
    "Two-level shuffle exchange on the mesh-cluster plane: the driver "
    "assigns every reduce partition an OWNING executor; inside that "
    "executor's mesh tasks the owned partitions' content moves lane→lane "
    "as lax.all_to_all over ICI (LocalMesh.exchange_wave) and lands "
    "directly in the process-local block store, while only partitions "
    "owned by OTHER hosts are sliced out and parked for the TCP fetch. "
    "Consumers are placed at their partition's owner so the ICI-moved "
    "bytes are read via the local short-circuit. Waves with string keys "
    "or variable-width columns fall back to slice-and-park per batch "
    "without breaking the mesh group; any exchange failure degrades the "
    "task to per-split TCP under a bumped epoch, bit-identical "
    "(docs/cluster.md)").boolean_conf(True)

CLUSTER_MESH_DEVICES = conf(
    "spark.rapids.tpu.cluster.mesh.devicesPerExecutor").doc(
    "Devices in each executor's local mesh (also the lane width of one mesh "
    "map task); 0 uses every device visible to the executor process. "
    "Executors report their ACTUAL attached width on the spawn handshake "
    "(mesh.attach), and a mesh that comes up narrower than the group being "
    "dispatched degrades that task to the per-split TCP path"
).integer_conf(0)

CLUSTER_PLACEMENT_MOVEMENT_AWARE = conf(
    "spark.rapids.tpu.cluster.placement.movementAware").doc(
    "Schedule a reduce task on the executor already holding the most "
    "map-output bytes for its reduce partition (per-split sizes tracked by "
    "the MapOutputTracker from every map reply), so the biggest input is a "
    "local block-store read instead of a TCP fetch — Theseus's "
    "movement-optimized placement. Falls back to seeded round-robin when "
    "the preferred host is busy, blacklisted, dead, or over "
    "placement.maxLoadedBytes").boolean_conf(True)

CLUSTER_PLACEMENT_MAX_LOADED_BYTES = conf(
    "spark.rapids.tpu.cluster.placement.maxLoadedBytes").doc(
    "Spill-aware demotion threshold for movement-aware placement: when the "
    "byte-dominant executor already parks more than this many shuffle bytes "
    "(a proxy for its HBM+host spill budget), the preferred pick is DEMOTED "
    "back to round-robin so reduce work does not pile onto a host that "
    "would only spill it to disk (placement.demoted event)").bytes_conf("2g")

CLUSTER_SPAWN_MAX_RETRIES = conf(
    "spark.rapids.tpu.cluster.spawn.maxRetries").doc(
    "Extra bring-up attempts a MiniCluster executor slot gets when the "
    "spawn handshake fails on a transient socket/pipe error before the "
    "driver gives up on the slot (executor.spawn.retry event per retry)"
).integer_conf(1)

SCHEDULER_MAX_CONCURRENT = conf("spark.rapids.tpu.scheduler.maxConcurrent").doc(
    "Queries the driver-side scheduler admits concurrently "
    "(runtime/scheduler.py; the Spark fair-scheduler pool-size analog). "
    "Structural: process-global, applied only by a session that sets it "
    "explicitly").integer_conf(4)

SCHEDULER_QUEUE_MAX_DEPTH = conf("spark.rapids.tpu.scheduler.queue.maxDepth").doc(
    "Submissions allowed to wait for admission; one more is SHED immediately "
    "with a retryable QueryRejectedError carrying a backoff hint (load "
    "shedding at the front door instead of OOM cascades). 0 disables the "
    "depth bound").integer_conf(32)

SCHEDULER_QUEUE_TIMEOUT = conf("spark.rapids.tpu.scheduler.queue.timeoutSeconds").doc(
    "A submission still queued for admission after this long is shed with a "
    "retryable QueryRejectedError (backoff hint included); <=0 waits "
    "forever").double_conf(30.0)

SCHEDULER_PRIORITY = conf("spark.rapids.tpu.scheduler.priority").doc(
    "Admission priority of THIS session's queries (higher admits first; the "
    "Spark fair-scheduler pool-weight analog). Read per submission, so "
    "sessions with different priorities share one scheduler").integer_conf(0)

SCHEDULER_PRIORITY_AGING = conf(
    "spark.rapids.tpu.scheduler.priority.agingSeconds").doc(
    "Queue-wait seconds that add +1 effective priority to a waiting "
    "submission, so low-priority tenants cannot be starved by a stream of "
    "high-priority arrivals; <=0 disables aging").double_conf(10.0)

SCHEDULER_QUERY_DEADLINE = conf(
    "spark.rapids.tpu.scheduler.query.deadlineSeconds").doc(
    "Per-query wall-clock deadline measured from submission (queue wait "
    "included); past it the query's CancelToken flips and every cooperative "
    "checkpoint raises QueryDeadlineError, draining the pipeline without "
    "leaking threads, device buffers or semaphore permits. <=0 disables"
).double_conf(0.0)

SCHEDULER_FOOTPRINT_FLOOR = conf(
    "spark.rapids.tpu.scheduler.footprint.floorBytes").doc(
    "Lower bound on the admission footprint estimate "
    "(scheduler.estimate_footprint): no query books less HBM than this, so "
    "tiny plans cannot stampede admission. Applies to both the static "
    "heuristic and history-based estimates").bytes_conf("16m")

SCHEDULER_FOOTPRINT_DECODE_EXPANSION = conf(
    "spark.rapids.tpu.scheduler.footprint.decodeExpansion").doc(
    "Multiplier from on-disk scan bytes to estimated decoded device bytes "
    "in the static (cold-start) footprint heuristic; only used when the "
    "plan-shape history store has no observation for the plan's "
    "fingerprint").double_conf(3.0)

TRANSPORT_MAX_FRAME_BYTES = conf(
    "spark.rapids.tpu.shuffle.transport.maxFrameBytes").doc(
    "Upper bound on one length-prefixed wire frame (shuffle data plane AND "
    "the query endpoint); a longer length prefix raises TransportError "
    "BEFORE any allocation, so a corrupt/truncated header cannot trigger a "
    "multi-GB read. Applied process-wide by whichever server/endpoint is "
    "constructed with it").bytes_conf("1g")

ENDPOINT_HOST = conf("spark.rapids.tpu.endpoint.host").doc(
    "Bind address of the Arrow-over-TCP query endpoint "
    "(runtime/endpoint.py); loopback by default — bind wider only behind "
    "a trusted network boundary (the error channel carries pickled typed "
    "exceptions)").string_conf("127.0.0.1")

ENDPOINT_PORT = conf("spark.rapids.tpu.endpoint.port").doc(
    "TCP port of the query endpoint; 0 picks an ephemeral port (exposed as "
    "QueryEndpoint.port)").integer_conf(0)

ENDPOINT_IDLE_TIMEOUT = conf("spark.rapids.tpu.endpoint.idleTimeoutSeconds").doc(
    "Per-connection blocking-I/O timeout on the query endpoint: a client "
    "that neither submits nor drains its result stream for this long is "
    "treated as disconnected — its in-flight query is cancelled and its "
    "connection closed (the keepalive window of the serving contract). "
    "<=0 disables").double_conf(300.0)

ENDPOINT_REQUEST_TIMEOUT = conf(
    "spark.rapids.tpu.endpoint.requestTimeoutSeconds").doc(
    "Wall-clock bound on one endpoint submission (queue wait + execution + "
    "result streaming); past it the query's CancelToken flips with reason "
    "request_timeout and the client receives the typed cancellation error. "
    "<=0 disables (per-query scheduler deadlines still apply)"
).double_conf(0.0)

ENDPOINT_DRAIN_GRACE = conf("spark.rapids.tpu.endpoint.drain.graceSeconds").doc(
    "Graceful-drain budget of QueryEndpoint.shutdown() (the SIGTERM path): "
    "new submissions are shed immediately with a retryable "
    "QueryRejectedError while in-flight queries get this long to finish; "
    "past it their CancelTokens flip (reason drain) — the hard-kill "
    "escalation — before the endpoint closes").double_conf(30.0)

ENDPOINT_STREAM_BUFFER = conf(
    "spark.rapids.tpu.endpoint.maxStreamBufferBytes").doc(
    "Byte bound on result batches buffered between a query's executor and "
    "its client connection (Arrow-IPC payload bytes); a slow client "
    "backpressures the producer instead of growing the heap. The effective "
    "budget also shrinks to the spill catalog's free host headroom "
    "(runtime/memory.host_prefetch_budget), sharing the prefetch budget "
    "with the scan readahead and pipeline queues").bytes_conf("64m")

SHUFFLE_CHECKSUM = conf("spark.rapids.tpu.shuffle.checksum.enabled").doc(
    "Stamp every serialized shuffle block with a CRC32C checksum in the "
    "transport metadata and verify on fetch; a mismatch is a fetch failure "
    "routed through the existing retry/failover/recompute ladder (Spark "
    "shuffle checksums, SPARK-35275 analog)").boolean_conf(True)

SPILL_CHECKSUM = conf("spark.rapids.tpu.memory.spill.checksum.enabled").doc(
    "Stamp disk-tier spill payloads with a CRC32C checksum and verify on "
    "unspill; a mismatch raises SpillCorruptionError, which shuffle readers "
    "treat as a fetch failure (map-stage recompute) instead of decoding "
    "silently corrupt rows").boolean_conf(True)

EVENT_LOG_DIR = conf("spark.rapids.tpu.eventLog.dir").doc(
    "Directory for the structured JSONL event log (query/stage/batch "
    "lifecycle, spill, OOM-retry/split, fetch retry/failover/recompute, "
    "heartbeat loss, executor health gauges — runtime/eventlog.py; the Spark "
    "event-log analog consumed by tools/profiler.py). Empty disables with "
    "near-zero overhead").string_conf(None)

EVENT_LOG_HEALTH_INTERVAL = conf(
    "spark.rapids.tpu.eventLog.healthSample.intervalSeconds").doc(
    "Period of the executor-health gauge sampler (HBM used/free + "
    "spill-catalog tier occupancy) written to the event log by the "
    "heartbeat/sampler thread; <=0 disables sampling. Only meaningful when "
    "eventLog.dir is set").double_conf(5.0)

EVENT_LOG_MAX_BYTES = conf("spark.rapids.tpu.eventLog.maxBytes").doc(
    "Size at which the event-log JSONL file rotates (events-*.jsonl -> "
    ".1 -> .2 ... keepFiles retained), so a long-lived serving session "
    "cannot grow one file without bound; 0 disables rotation").bytes_conf(0)

EVENT_LOG_KEEP_FILES = conf("spark.rapids.tpu.eventLog.keepFiles").doc(
    "Rotated event-log files retained per active file (the keep-N of the "
    "size-based rotation; older rotations are deleted). Only meaningful "
    "when eventLog.maxBytes > 0").integer_conf(4)

STATS_HISTORY_DIR = conf("spark.rapids.tpu.stats.history.dir").doc(
    "Directory of the on-disk plan-shape history store "
    "(runtime/history.py): per-fingerprint observed peak device bytes, "
    "cardinalities and shuffle skew, written at query end and read at "
    "submit so scheduler.estimate_footprint books HBM from observation "
    "instead of the static decode heuristic. Structural: process-global, "
    "applied only by a session that sets it explicitly. Empty disables"
).string_conf(None)

STATS_HISTORY_MAX_SHAPES = conf("spark.rapids.tpu.stats.history.maxShapes").doc(
    "Plan-shape fingerprints retained in the history store; beyond it the "
    "least-recently-updated shapes are evicted on write, bounding the file "
    "for long-lived serving sessions").integer_conf(256)

STATS_HISTORY_ENABLED = conf("spark.rapids.tpu.stats.history.enabled").doc(
    "Consult and update the plan-shape history store (when history.dir is "
    "set). false keeps the static footprint heuristic while the stats "
    "plane still captures per-node observations").boolean_conf(True)

TRACE_DIR = conf("spark.rapids.tpu.trace.dir").doc(
    "Directory for per-process JSONL span files (runtime/tracing.py): every "
    "trace_range/span region and span_event instant is appended with its "
    "wall-clock start, duration, pid/thread, span id and parent, and the "
    "ambient query's trace "
    "id, which propagates across MiniCluster tasks, shuffle fetches and "
    "endpoint submissions. tools/profiler.py trace merges the files into "
    "Chrome-trace JSON (Perfetto) with a critical-path table. Empty "
    "disables with near-zero overhead").string_conf(None)

TRACE_ID_OVERRIDE = conf("spark.rapids.tpu.trace.id").doc(
    "Explicit trace id for this session's next queries (normally derived "
    "from the query id); clients submitting over the endpoint can instead "
    "set 'trace' per request. Empty derives per query").string_conf(None)

FLEET_DIR = conf("spark.rapids.tpu.fleet.dir").doc(
    "Shared fleet directory (runtime/fleet.py): every QueryEndpoint replica "
    "registers a lease-stamped membership record here (heartbeat-renewed, "
    "mtime-expired), so replicas and clients discover live peers and a "
    "survivor's sweeper can adopt a dead replica's lease plus its "
    "shared-store write intents. Must be on a filesystem visible to every "
    "replica. Empty disables fleet membership").string_conf(None)

FLEET_LEASE_TIMEOUT = conf("spark.rapids.tpu.fleet.lease.timeoutSeconds").doc(
    "Age past which a replica's membership lease (its record file's mtime) "
    "is considered expired: the replica stops being returned as a live "
    "member and any surviving replica's sweeper may adopt the lease — "
    "unlinking the record and reclaiming orphaned shared-store write "
    "intents. Must comfortably exceed fleet.heartbeat.intervalSeconds"
).double_conf(10.0)

FLEET_HEARTBEAT_INTERVAL = conf(
    "spark.rapids.tpu.fleet.heartbeat.intervalSeconds").doc(
    "Period of a registered replica's lease-renewal heartbeat (an mtime "
    "touch on its membership record); each beat also sweeps expired peer "
    "leases, so fleet adoption needs no dedicated coordinator. <=0 "
    "disables the heartbeat thread (the lease then expires unless renewed "
    "manually)").double_conf(2.0)

STREAM_WATERMARK_DELAY = conf(
    "spark.rapids.tpu.streaming.watermark.delaySeconds").doc(
    "Event-time lateness bound of a windowed streaming aggregation "
    "(streaming/coordinator.py): after each committed epoch the watermark "
    "advances to max(event time) - delay, window groups entirely below it "
    "are retired out of the incremental state (emitted once as finalized "
    "rows), and later-arriving rows for a retired window are dropped — "
    "this is what keeps state bytes bounded on an unbounded stream. <0 "
    "(the default) disables retirement (state grows with the key space)"
).double_conf(-1.0)

STREAM_MAX_BATCHES_PER_EPOCH = conf(
    "spark.rapids.tpu.streaming.maxBatchesPerEpoch").doc(
    "Cap on the input batches one micro-batch epoch consumes "
    "(streaming/coordinator.py): a backlogged source is drained over "
    "several epochs of bounded footprint instead of one giant admitted "
    "query. <=0 means unbounded (drain everything pending)"
).integer_conf(32)

STREAM_JOURNAL_HISTORY = conf(
    "spark.rapids.tpu.streaming.journal.maxCommits").doc(
    "Commit records retained in a stream's epoch journal for "
    "observability (profiler.py streaming); the exactly-once state itself "
    "(committed epoch, consumed batch ids, pending begin) is never "
    "truncated").integer_conf(256)

ENDPOINT_RESULT_CACHE_ENABLED = conf(
    "spark.rapids.tpu.endpoint.resultCache.enabled").doc(
    "Serve identical hot queries from an in-memory result cache on the "
    "endpoint: hits are keyed by (catalog epoch, parameterized plan "
    "signature, SQL text digest), stream the recorded Arrow-IPC frames "
    "bit-identically, bypass scheduler admission entirely, and are "
    "invalidated when the session catalog changes (any view "
    "registration)").boolean_conf(False)

ENDPOINT_RESULT_CACHE_MAX_BYTES = conf(
    "spark.rapids.tpu.endpoint.resultCache.maxBytes").doc(
    "Byte budget of the endpoint result cache (sum of cached Arrow-IPC "
    "payload bytes); least-recently-hit entries are evicted beyond it, and "
    "a single result larger than the budget is never admitted"
).bytes_conf("64m")

ENDPOINT_RESULT_CACHE_MAX_ENTRIES = conf(
    "spark.rapids.tpu.endpoint.resultCache.maxEntries").doc(
    "Entry-count bound on the endpoint result cache (bounds key/metadata "
    "overhead independently of maxBytes)").integer_conf(64)

ENDPOINT_STATS_ENABLED = conf("spark.rapids.tpu.endpoint.stats.enabled").doc(
    "Serve STATS frames on the query endpoint: a Prometheus-style text "
    "snapshot of live serving metrics — admission/shed/cancel/deadline "
    "counters, the resilience registry, HBM/spill-tier/queue-depth gauges "
    "and latency histograms per priority class (tools/tpu_client.py "
    "--stats)").boolean_conf(True)

ENDPOINT_STATS_HISTOGRAMS = conf(
    "spark.rapids.tpu.endpoint.stats.histograms.enabled").doc(
    "Include histogram families (query latency per priority class, "
    "admission queue wait) in STATS snapshots; counters and gauges are "
    "always served").boolean_conf(True)

ENDPOINT_SLO_LATENCY_TARGET = conf(
    "spark.rapids.tpu.endpoint.slo.latencyTargetSeconds").doc(
    "Per-query serving-latency objective of the endpoint's SLO accounting "
    "(runtime/endpoint.py): every served/cached submission whose wall time "
    "exceeds the target counts an slo.breach event and an srt_slo_total "
    "breach, and failed submissions count against availability; the "
    "per-replica SLO snapshot rides the fleet heartbeat's lease-record "
    "health summary so profiler.py fleet / fleet-stats can render a "
    "fleet-merged breach table. <=0 disables SLO accounting"
).double_conf(0.0)

FLIGHT_RECORDER_MAX_EVENTS = conf(
    "spark.rapids.tpu.flightRecorder.maxEvents").doc(
    "Bound of the black-box flight recorder's in-memory ring "
    "(runtime/blackbox.py): the most recent event-log records and tracing "
    "instants are retained per process at near-zero cost (a deque append, "
    "no I/O) and flushed to blackbox-<pid>.json on an unhandled endpoint "
    "error, a deadline/drain hard-kill, or a stuck-query detection from the "
    "fleet heartbeat — so a SIGKILLed replica leaves a record of what it "
    "was doing for the survivor that adopts its lease. 0 disables the "
    "ring; dumps land in eventLog.dir").integer_conf(512)

PROFILE_DIR = conf("spark.rapids.tpu.profile.dir").doc(
    "Directory for a whole-session XProf/Perfetto capture "
    "(jax.profiler.start_trace; the reference's Nsight workflow, "
    "docs/dev/nvtx_profiling.md); empty disables").string_conf(None)

OOM_DUMP_DIR = conf("spark.rapids.tpu.memory.hbm.oomDumpDir").doc(
    "Directory to write allocator state on device OOM "
    "(reference spark.rapids.memory.gpu.oomDumpDir)").string_conf(None)

MEMORY_WATERMARK_INTERVAL = conf(
    "spark.rapids.tpu.memory.profile.watermarkIntervalBytes").doc(
    "Granularity of the HBM watermark timeline: a memory.watermark event "
    "(+ Chrome counter-track sample when trace.dir is set) is emitted when "
    "any spill tier's occupancy or the device high-water mark moves by this "
    "many bytes since the last sample, bounding sample volume to "
    "O(peak/interval) rather than one per allocation. The allocation-site "
    "accounting itself is always on (a few dict updates under the catalog "
    "lock)").bytes_conf("16m")

MOVEMENT_ENABLED = conf("spark.rapids.tpu.movement.enabled").doc(
    "Meter every byte crossing a process/device boundary in the unified "
    "movement ledger (runtime/movement.py): shuffle send/recv per link "
    "class, disk spill I/O, host-device transfers, ICI collective "
    "estimates and endpoint egress. Feeds the query.end movement section, "
    "movement.sample events, srt_movement_bytes STATS gauges and the "
    "profiler's movement read-out. Off leaves only the raw per-node "
    "h2d/d2h meters").boolean_conf(True)

MOVEMENT_SAMPLE_INTERVAL = conf(
    "spark.rapids.tpu.movement.sample.intervalBytes").doc(
    "Granularity of movement.sample ledger snapshots (+ Chrome "
    "counter-track samples when trace.dir is set): a cumulative snapshot "
    "is emitted when the process has moved this many more bytes since the "
    "last sample, bounding event volume to O(moved/interval) rather than "
    "one per transfer. Forced flushes at query end and executor task "
    "completion always happen regardless").bytes_conf("32m")

MEMORY_PROFILE_TOPK = conf("spark.rapids.tpu.memory.profile.topK").doc(
    "Allocation sites listed per watermark sample, per-query memory "
    "summary and STATS gauge family (sites beyond the top K by bytes are "
    "dropped from the EVENT payloads only — session.heap_snapshot() and "
    "the leak detector always see every site)").integer_conf(10)

MEMORY_LEAK_CHECK = conf("spark.rapids.tpu.memory.leak.check").doc(
    "End-of-query leak detection: after an action drains, any non-retained "
    "catalog buffer still tagged to the finished query raises a "
    "memory.leak event + memoryLeakedBuffers resilience counter with the "
    "per-site breakdown, and the buffers are reclaimed. false disables "
    "(the buffers then linger until process exit)").boolean_conf(True)

MEMORY_LEAK_STRICT = conf("spark.rapids.tpu.memory.leak.strict").doc(
    "Escalate a detected end-of-query leak into a MemoryLeakError after "
    "the event/counter/reclaim, so test suites fail loudly on any leak "
    "instead of logging it (chaos specs use the 'leak' fault kind to prove "
    "the detector end to end)").boolean_conf(False)

SPARK_VERSION = conf("spark.rapids.tpu.spark.version").doc(
    "Spark behavior generation to emulate; selects the semantic shim "
    "(reference ShimLoader picks a per-release shim jar the same way). "
    "A -<platform> suffix (3.0.1-databricks, 3.0.1-emr) selects that "
    "platform's shim variant (reference spark301db/spark301emr/spark310db)"
).string_conf("3.5.0")

PARQUET_DEVICE_DECODE = conf(
    "spark.rapids.tpu.sql.parquet.deviceDecode.enabled").doc(
    "Decode dictionary-encoded uncompressed parquet chunks on device "
    "(bit-unpack + gather in one jitted program, ops/parquet_decode.py); "
    "out-of-scope chunks fall back to arrow per column (reference "
    "GpuParquetScan device decode, stage one)").boolean_conf(True)

PARQUET_ENCODED_UPLOAD = conf(
    "spark.rapids.tpu.sql.parquet.encodedUpload.enabled").doc(
    "Upload in-scope parquet data pages ENCODED — bit-packed dictionary "
    "indices, definition levels and the dictionary itself — and expand to "
    "dense columns lazily on device inside the first consuming kernel, so "
    "H2D carries encoded bytes instead of dense columns (movement-ledger "
    "h2d site scan.encoded). Out-of-scope pages upload dense; requires "
    "parquet.deviceDecode.enabled").boolean_conf(True)

PARQUET_REBASE_MODE = conf(
    "spark.rapids.tpu.sql.parquet.datetimeRebaseModeInRead").doc(
    "EXCEPTION | CORRECTED | LEGACY for dates before 1582-10-15 in parquet "
    "files (Spark spark.sql.parquet.datetimeRebaseModeInRead; LEGACY applies "
    "the Julian->proleptic-Gregorian rebase, shims.rebase_julian_to_gregorian_days)"
).string_conf("EXCEPTION")

ALLUXIO_PATHS_REPLACE = conf(
    "spark.rapids.tpu.alluxio.pathsToReplace").doc(
    "List of 'scheme://from->scheme://to' path-prefix rewrites applied to "
    "every file scan, so cached-filesystem mounts transparently replace "
    "direct storage paths (reference spark.rapids.alluxio.pathsToReplace, "
    "RapidsConf.scala:1031); ';'-separated").string_conf(None)


class RapidsConf:
    """Resolved view over user settings (reference RapidsConf.scala:1162 class)."""

    def __init__(self, settings: dict | None = None):
        self.settings = dict(settings or {})
        unknown = [k for k in self.settings
                   if k.startswith("spark.rapids.tpu.") and k not in _REGISTERED]
        if unknown:
            raise ValueError(f"unknown spark.rapids.tpu confs: {unknown}")

    def get(self, entry: ConfEntry):
        return entry.get(self.settings)

    # convenience typed properties used throughout the engine
    @property
    def is_sql_enabled(self):
        return self.get(SQL_ENABLED)

    @property
    def explain(self):
        return self.get(EXPLAIN).upper()

    @property
    def batch_size_bytes(self):
        return self.get(BATCH_SIZE_BYTES)

    @property
    def concurrent_tpu_tasks(self):
        return self.get(CONCURRENT_TPU_TASKS)

    @property
    def metrics_level(self):
        return self.get(METRICS_LEVEL).upper()

    @property
    def is_test_enabled(self):
        return self.get(TEST_ENABLED)

    @property
    def allowed_non_tpu(self):
        v = self.get(TEST_ALLOWED_NON_TPU)
        return set(x.strip() for x in v.split(",") if x.strip())

    @property
    def is_cpu_fallback_enabled(self):
        return self.get(CPU_FALLBACK_ENABLED)

    @property
    def stage_fusion_enabled(self):
        return self.get(ENABLE_WHOLE_STAGE_FUSION)

    @property
    def scan_fusion_enabled(self):
        return (self.get(ENABLE_SCAN_FUSION)
                and self.get(ENABLE_WHOLE_STAGE_FUSION))

    @property
    def groupby_chain_enabled(self):
        return (self.get(ENABLE_GROUPBY_CHAIN)
                and self.get(ENABLE_WHOLE_STAGE_FUSION))

    @property
    def stage_cache_enabled(self):
        return self.get(STAGE_CACHE_ENABLED)

    @property
    def stage_cache_dir(self):
        return self.get(STAGE_CACHE_DIR)

    @property
    def stage_cache_max_bytes(self):
        return self.get(STAGE_CACHE_MAX_BYTES)

    def copy_with(self, **kv):
        s = dict(self.settings)
        s.update(kv)
        return RapidsConf(s)


def all_entries():
    return dict(_REGISTERED)


def generate_docs() -> str:
    """Markdown doc table (reference RapidsConf.scala:1259 main → docs/configs.md)."""
    lines = [
        "# spark_rapids_tpu configuration",
        "",
        "Generated by `python -m spark_rapids_tpu.config`. "
        "Mirrors the reference's docs/configs.md generator (RapidsConf.scala:1259).",
        "",
        "| Name | Default | Description |",
        "|---|---|---|",
    ]
    for key in sorted(_REGISTERED):
        e = _REGISTERED[key]
        if e.internal:
            continue
        lines.append(f"| {e.key} | {e.default} | {e.doc} |")
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    import pathlib
    out = pathlib.Path(__file__).resolve().parent.parent / "docs" / "configs.md"
    out.parent.mkdir(exist_ok=True)
    out.write_text(generate_docs())
    print(f"wrote {out}")
