"""File scan: plan node + device exec with partition-values handling.

Reference: GpuFileSourceScanExec.scala:59 (DSv1), GpuBatchScanExec (DSv2),
GpuMultiFileReader.scala plumbing, ColumnarPartitionReaderWithPartitionValues
(partition-directory values concatenated as constant columns). Files are grouped
into FilePartitions by target size like Spark's FilePartition packing."""

from __future__ import annotations

import dataclasses
import datetime
import os
import typing

import pyarrow as pa

from spark_rapids_tpu import types as T
from spark_rapids_tpu import config as CFG
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.base import TpuExec, acquire_semaphore
from spark_rapids_tpu.io import readers as R
from spark_rapids_tpu.plan.nodes import PlanNode
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime.tracing import trace_range


@dataclasses.dataclass(frozen=True)
class FilePartition:
    """Files + constant partition-column values (from dir names a/b=1/...)."""
    paths: tuple
    partition_values: tuple = ()   # ((name, value), ...) applied to every row


def discover_partitions(root: str, fmt: str) -> list[FilePartition]:
    """Walk a (possibly hive-partitioned) directory into per-directory partitions."""
    exts = {"parquet": (".parquet", ".pq"), "orc": (".orc",), "csv": (".csv",)}
    out = []
    for dirpath, dirnames, files in os.walk(root):
        # prune hidden/metadata dirs (uncommitted _temporary-* output, _SUCCESS
        # siblings…) the way Spark's file index skips '_'/'.' paths. NB: os.walk
        # must not be wrapped in sorted() — that would drain the generator before
        # this in-place prune is seen.
        dirnames[:] = sorted(d for d in dirnames if not d.startswith(("_", ".")))
        paths = tuple(sorted(
            os.path.join(dirpath, f) for f in files
            if f.endswith(exts[fmt]) and not f.startswith(("_", "."))))
        if not paths:
            continue
        rel = os.path.relpath(dirpath, root)
        pvals = []
        if rel != ".":
            for seg in rel.split(os.sep):
                if "=" in seg:
                    k, v = seg.split("=", 1)
                    pvals.append((k, v))
        out.append(FilePartition(paths, tuple(pvals)))
    out.sort(key=lambda p: p.paths)
    return out




# proleptic-Gregorian vs hybrid-Julian calendars agree on every date from the
# 1582-10-15 Gregorian cutover onward, so the legacy datetime rebase
# (readers.py _rebase) is the identity there in EVERY rebase mode
_GREGORIAN_CUTOVER = datetime.date(1582, 10, 15)


def _dates_post_cutover(md, date_cols: list) -> bool:
    """True when every row group's footer statistics PROVE all values of the
    named date columns are on/after the Gregorian cutover — the condition
    under which device decode (which never rebases) is bit-identical to the
    arrow path's rebase handling. Missing stats fail closed."""
    leaf = {}
    for i in range(md.num_columns):
        p = md.schema.column(i).path
        if "." not in p:
            leaf[p] = i
    for name in date_cols:
        i = leaf.get(name)
        if i is None:
            return False
        for g in range(md.num_row_groups):
            st = md.row_group(g).column(i).statistics
            if st is None or not st.has_min_max:
                return False
            mn = st.min
            if not isinstance(mn, datetime.date) or \
                    isinstance(mn, datetime.datetime) or \
                    mn < _GREGORIAN_CUTOVER:
                return False
    return True


def _scan_meta(path: str) -> dict:
    """Scan provenance for the input_file_name expression family; whole-file
    reads expose the file as one block (Spark: split start/length)."""
    return {"input_file": path, "block_start": 0,
            "block_length": os.path.getsize(path)}


def _infer_partition_type(values: list) -> T.DataType:
    try:
        for v in values:
            int(v)
        return T.INT if all(-2**31 <= int(v) < 2**31 for v in values) else T.LONG
    except ValueError:
        return T.STRING


def rewrite_scan_path(path, conf):
    """Alluxio-style path-prefix replacement (reference
    spark.rapids.alluxio.pathsToReplace, RapidsConf.scala:1031): rewrite
    'from->to' prefixes on every scan path so a caching filesystem mount
    transparently fronts direct storage."""
    from spark_rapids_tpu import config as CFG
    spec = conf.get(CFG.ALLUXIO_PATHS_REPLACE) if conf is not None else None
    if not spec or not isinstance(path, (str, list, tuple)):
        return path
    rules = []
    for rule in spec.split(";"):
        rule = rule.strip()
        if not rule:
            continue
        if "->" not in rule:
            raise ValueError(
                f"bad {CFG.ALLUXIO_PATHS_REPLACE.key} rule {rule!r}: "
                "expected 'from->to'")
        frm, to = rule.split("->", 1)
        rules.append((frm.strip(), to.strip()))

    def one(p):
        for frm, to in rules:
            if p.startswith(frm):
                return to + p[len(frm):]
        return p
    return one(path) if isinstance(path, str) else [one(p) for p in path]


class FileScanNode(PlanNode):
    """CPU plan node for a file scan; the override layer converts it to
    FileSourceScanExec. Host execution = the same readers without the device
    upload (the CPU-Spark oracle path)."""

    def __init__(self, paths_or_dir, fmt: str = "parquet",
                 schema: T.StructType | None = None,
                 pushed_filter=None, options: dict | None = None,
                 files_per_partition: int = 1):
        super().__init__()
        self.fmt = fmt
        self.options = options or {}
        if isinstance(paths_or_dir, str) and os.path.isdir(paths_or_dir):
            parts = discover_partitions(paths_or_dir, fmt)
        else:
            paths = ([paths_or_dir] if isinstance(paths_or_dir, str)
                     else list(paths_or_dir))
            parts = [FilePartition(tuple(paths[i:i + files_per_partition]))
                     for i in range(0, len(paths), files_per_partition)]
        if not parts:
            raise ValueError(f"no {fmt} files under {paths_or_dir}")
        keys0 = tuple(k for k, _ in parts[0].partition_values)
        for p in parts[1:]:
            if tuple(k for k, _ in p.partition_values) != keys0:
                raise ValueError(
                    "inconsistent partition directory layout: "
                    f"{keys0} vs {tuple(k for k, _ in p.partition_values)} "
                    f"under {p.paths[0]}")
        self.partitions = parts
        self.pushed_filter = pushed_filter  # Expression; converted per-read
        self.reader = R.reader_for(fmt, **self.options)
        if schema is None:
            file_schema = T.StructType.from_arrow(
                self.reader.schema_of(parts[0].paths[0]))
            pfields = []
            if parts[0].partition_values:
                for i, (k, _) in enumerate(parts[0].partition_values):
                    vals = [p.partition_values[i][1] for p in parts]
                    pfields.append(T.StructField(
                        k, _infer_partition_type(vals), False))
            schema = T.StructType(list(file_schema.fields) + pfields)
        self._schema = schema
        self._n_partition_cols = (len(parts[0].partition_values)
                                  if parts[0].partition_values else 0)

    @property
    def output(self):
        return self._schema

    @property
    def num_partitions(self):
        return len(self.partitions)

    def _data_columns(self) -> list:
        n = len(self._schema.fields) - self._n_partition_cols
        return [f.name for f in self._schema.fields[:n]]

    def _arrow_filter(self):
        if self.pushed_filter is None:
            return None
        return R.spark_filter_to_arrow(self.pushed_filter)

    def _append_partition_values(self, tbl: pa.Table, part: FilePartition):
        """Constant partition columns for every row (reference
        ColumnarPartitionReaderWithPartitionValues)."""
        if not part.partition_values:
            return tbl
        n = len(self._schema.fields) - self._n_partition_cols
        for (k, v), f in zip(part.partition_values, self._schema.fields[n:]):
            val = int(v) if isinstance(f.data_type, T.IntegralType) else v
            tbl = tbl.append_column(
                pa.field(k, T.to_arrow_type(f.data_type)),
                pa.array([val] * tbl.num_rows, T.to_arrow_type(f.data_type)))
        return tbl

    def _residual_filter(self, tbl: pa.Table) -> pa.Table:
        """Exact Spark-semantics filter on the host for predicates the arrow
        scanner cannot express (float comparisons with NaN ordering, etc.)."""
        from spark_rapids_tpu.plan.host_eval import eval_host
        from spark_rapids_tpu.expr.core import bind_references
        if tbl.num_rows == 0:
            return tbl
        cond = bind_references(self.pushed_filter, self._schema)
        pred = eval_host(cond, tbl)
        return tbl.filter(pa.array([v is True for v in pred.data]))

    def tables_for(self, split: int, batch_rows: int,
                   strategy: str = "PERFILE", num_threads: int = 4,
                   target_rows: int = 1 << 20, rebase_mode: str | None = None):
        reader = self.reader
        if rebase_mode is not None and hasattr(reader, "rebase_mode") and \
                reader.rebase_mode != rebase_mode.upper():
            # fresh reader per divergent call: never mutate the shared one
            # (concurrent host/device scans of this node must not interleave)
            opts = {k: v for k, v in self.options.items()
                    if k != "rebase_mode"}
            reader = R.reader_for(self.fmt, rebase_mode=rebase_mode, **opts)
        part = self.partitions[split]
        filt = self._arrow_filter()
        residual = self.pushed_filter is not None and filt is None
        cols = self._data_columns()
        if strategy == "MULTITHREADED":
            gen = R.multithreaded_tables(reader, list(part.paths), cols,
                                         filt, batch_rows, num_threads)
        elif strategy == "COALESCING":
            gen = R.coalescing_tables(reader, list(part.paths), cols, filt,
                                      batch_rows, target_rows)
        else:
            gen = R.perfile_tables(reader, list(part.paths), cols, filt,
                                   batch_rows)
        for tbl in gen:
            tbl = self._append_partition_values(tbl, part)
            if residual:
                tbl = self._residual_filter(tbl)
            yield tbl

    def execute_host(self, split):
        tables = list(self.tables_for(split, batch_rows=1 << 20))
        if not tables:
            return self._empty()
        return pa.concat_tables(tables, promote_options="permissive")

    def args_string(self):
        return (f"{self.fmt} {len(self.partitions)} partitions"
                + (f" filter={self.pushed_filter!r}" if self.pushed_filter is not None
                   else ""))


class FileSourceScanExec(TpuExec):
    """Leaf device exec: host decode (strategy-selected) → one H2D per batch
    (reference GpuFileSourceScanExec.doExecuteColumnar:376)."""

    def __init__(self, node: FileScanNode, conf=None):
        from spark_rapids_tpu.config import RapidsConf
        super().__init__(conf=conf or RapidsConf())
        self.node = node
        self._scan_time = self.metrics.metric(M.READ_FS_TIME, M.MODERATE)

    @property
    def output(self):
        return self.node.output

    @property
    def num_partitions(self):
        return self.node.num_partitions

    def _device_decode_batches(self, split, batch_rows: int,
                               batch_bytes: int):
        """Row-group-at-a-time device decode (no arrow materialization).
        Returns None when the partition is out of the device path's scope
        (pushed filters, partition-dir values, temporal columns needing the
        rebase, or row groups larger than the reader batch caps)."""
        import pyarrow.parquet as pq
        from spark_rapids_tpu.io import parquet_native as PN
        node = self.node
        if node.fmt != "parquet" or node.pushed_filter is not None:
            return None
        part = node.partitions[split]
        if part.partition_values:
            return None
        # timestamps stay on the arrow path (it owns the legacy datetime
        # rebase, readers.py _rebase); nested columns need the arrow
        # list/struct conversion. DATE columns are admitted when footer
        # statistics prove every value post-dates the Gregorian cutover
        # (rebase is the identity there) — without this, scan-heavy TPC-H
        # queries like q1 (l_shipdate filter) never reach device decode.
        if any(isinstance(f.data_type, (T.TimestampType,
                                        T.ArrayType, T.StructDataType))
               for f in self.output):
            return None
        date_cols = [f.name for f in self.output
                     if isinstance(f.data_type, T.DateType)]
        files = []
        for path in part.paths:
            pf = pq.ParquetFile(path)
            md = pf.metadata
            # honor BOTH reader caps: the arrow path re-chunks oversized
            # groups, this path emits one batch per row group
            if any(md.row_group(g).num_rows > batch_rows
                   or md.row_group(g).total_byte_size > batch_bytes
                   for g in range(md.num_row_groups)):
                return None
            if date_cols and not _dates_post_cutover(md, date_cols):
                return None
            files.append((path, pf, md.num_row_groups))
        encoded = self.conf.get(CFG.PARQUET_ENCODED_UPLOAD)

        def it():
            cols = node._data_columns()
            for path, pf, n_groups in files:
                meta = _scan_meta(path)
                for rg in range(n_groups):
                    acquire_semaphore(self.metrics)
                    with trace_range("FileScan.devdecode",
                                     self._scan_time) as sp:
                        batch = PN.read_row_group_device(
                            path, rg, self.output, cols, pf=pf,
                            encoded=encoded)
                        if sp:   # the row count is the footer's: a host int
                            sp.set(rows=batch.num_rows,
                                   capacity=batch.capacity,
                                   columns=batch.num_cols)
                    batch.metadata = meta
                    yield batch
        return it()

    def _csv_device_decode_batches(self, split):
        """Whole-file device CSV parse for in-scope files (io/csv_native.py).
        ALL scope checks run up front in one host pass per file — if any
        file is out of scope the whole partition takes the host arrow
        reader (reference gates per type the same way); the committed
        device iterator can always finish."""
        from spark_rapids_tpu.io import csv_native as CN
        node = self.node
        if node.fmt != "csv" or node.pushed_filter is not None:
            return None
        part = node.partitions[split]
        if part.partition_values:
            return None
        allow_f = self.conf.get(CFG.CSV_READ_FLOATS)
        schema = self.output
        rdr = node.reader
        shapes = []
        for path in part.paths:
            shape = CN.try_scan_for_device(path, schema, rdr.delimiter,
                                           rdr.header, allow_f)
            if shape is None:
                return None
            shapes.append(shape)
        from spark_rapids_tpu.columnar.vector import bucket_capacity

        def it():
            for path, shape in zip(part.paths, shapes):
                acquire_semaphore(self.metrics)
                with trace_range("FileScan.csvdevdecode", self._scan_time):
                    batch = CN.decode_shape_device(shape, schema,
                                                   bucket_capacity)
                batch.metadata = _scan_meta(path)
                yield batch
        return it()

    def _orc_device_decode_batches(self, split, batch_rows, batch_bytes):
        """Stripe-at-a-time device ORC decode (io/orc_native.py); None →
        host arrow reader. Scope gates (compression, stripe caps) run up
        front; unsupported COLUMNS fall back per column inside the stripe
        read, mirroring the parquet path's granularity."""
        from spark_rapids_tpu.io import orc_native as ON
        node = self.node
        if node.fmt != "orc" or node.pushed_filter is not None:
            return None
        part = node.partitions[split]
        if part.partition_values:
            return None
        metas = []
        for path in part.paths:
            try:
                meta = ON.read_meta(path)
            except (NotImplementedError, OSError, IndexError):
                return None
            if any(si.num_rows > batch_rows
                   or si.data_length > batch_bytes
                   for si in meta.stripes):
                return None  # arrow path re-chunks oversized stripes
            metas.append(meta)
        schema = self.output

        def it():
            import pyarrow.orc as orc
            for path, meta in zip(part.paths, metas):
                pf = None
                fmeta = _scan_meta(path)
                for si_ in range(len(meta.stripes)):
                    acquire_semaphore(self.metrics)
                    with trace_range("FileScan.orcdevdecode",
                                     self._scan_time):
                        if pf is None:
                            pf = orc.ORCFile(path)
                        batch = ON.read_stripe_device(path, meta, si_,
                                                      schema, pf=pf)
                    batch.metadata = fmeta
                    yield batch
        return it()

    def _maybe_pipeline(self, it, edge, depth=None):
        """Detach a device-batch iterator onto its own pipeline segment:
        decode/upload work runs on the stage's worker thread (charged to
        this scan's selfTime there), queued batches sit spillable in the
        catalog, and the downstream consumer overlaps its compute."""
        from spark_rapids_tpu.runtime import pipeline as P
        if not P.enabled(self.conf):
            return it
        return P.stage_iterator(
            it, edge=edge, conf=self.conf, registry=self.metrics,
            node_id=self._node_id, self_time_metric=self._self_time,
            spillable=True, depth=depth)

    def execute_partition(self, split):
        conf = self.conf
        strategy = conf.get(CFG.PARQUET_READER_TYPE).upper()
        batch_rows = min(conf.get(CFG.MAX_READER_BATCH_SIZE_ROWS), 1 << 20)
        threads = conf.get(CFG.MULTITHREADED_READ_NUM_THREADS)

        def decode_engaged(entry):
            """Device decode pays only when a real accelerator is attached:
            on the CPU backend the 'device' IS the host, so arrow decode is
            strictly cheaper. An explicitly-set conf always wins (tests force
            the device path on the CPU platform)."""
            if entry.key in conf.settings:
                return conf.get(entry)
            if not conf.get(entry):
                return False
            import jax
            return jax.default_backend() != "cpu"

        if decode_engaged(CFG.PARQUET_DEVICE_DECODE):
            dev_it = self._device_decode_batches(
                split, batch_rows, conf.get(CFG.MAX_READER_BATCH_SIZE_BYTES))
            if dev_it is not None:
                return self.wrap_output(
                    self._maybe_pipeline(dev_it, "scan.device"))

        if decode_engaged(CFG.CSV_DEVICE_DECODE):
            dev_it = self._csv_device_decode_batches(split)
            if dev_it is not None:
                return self.wrap_output(
                    self._maybe_pipeline(dev_it, "scan.device"))

        if decode_engaged(CFG.ORC_DEVICE_DECODE):
            dev_it = self._orc_device_decode_batches(
                split, batch_rows, conf.get(CFG.MAX_READER_BATCH_SIZE_BYTES))
            if dev_it is not None:
                return self.wrap_output(
                    self._maybe_pipeline(dev_it, "scan.device"))

        part = self.node.partitions[split]
        # 1:1 provenance is provable only for single-file partitions on the
        # host reader path (multi-file strategies may stitch files)
        host_meta = _scan_meta(part.paths[0]) if len(part.paths) == 1 else None
        from spark_rapids_tpu.runtime import pipeline as P
        pipe_on = P.enabled(conf)

        def it():
            gen = self.node.tables_for(
                split, batch_rows, strategy, threads,
                rebase_mode=conf.get(CFG.PARQUET_REBASE_MODE))
            depth = conf.get(CFG.SCAN_READAHEAD_DEPTH)
            if pipe_on and depth <= 0:
                depth = conf.get(CFG.PIPELINE_QUEUE_DEPTH)
            if depth > 0:
                # decode readahead stays BEFORE the semaphore: it buffers
                # host arrow tables only, so admission control still gates
                # every device upload. One mechanism, one byte budget: the
                # scan's decode edge is a pipeline stage whose cap is the
                # tighter of the readahead and pipeline byte knobs
                from spark_rapids_tpu.runtime.memory import (
                    host_prefetch_budget)
                budget = host_prefetch_budget(min(
                    conf.get(CFG.SCAN_READAHEAD_MAX_BUFFER),
                    conf.get(CFG.PIPELINE_MAX_QUEUE_BYTES)))
                gen = P.stage_iterator(
                    gen, edge="scan.decode", conf=conf,
                    registry=self.metrics, node_id=self._node_id,
                    self_time_metric=self._self_time,
                    depth=depth, max_bytes=budget,
                    stall_metric=self.metrics.metric(
                        M.READAHEAD_STALL_TIME, M.MODERATE))
            for tbl in gen:
                acquire_semaphore(self.metrics)
                with trace_range("FileScan.h2d", self._scan_time):
                    batch = ColumnarBatch.from_arrow(tbl, self.output)
                batch.metadata = host_meta
                yield batch

        # double-buffered host→device transfer: the upload stage's worker
        # converts batch N+1 (and its decode edge prefetches N+2) while the
        # consumer computes on batch N
        return self.wrap_output(self._maybe_pipeline(it(), "scan.upload"))

    def args_string(self):
        return self.node.args_string()


# self-registration with the override engine (kept here, not in overrides.py, so
# plan/ never imports io/ — mirrors the reference's per-format ScanRule modules)
def _register_scan_rule():
    from spark_rapids_tpu.plan.overrides import REGISTRY, ExecRule
    from spark_rapids_tpu.plan.typesig import ExecChecks, ORDERABLE

    def conv_filescan(meta, kids):
        return FileSourceScanExec(meta.node, conf=meta.conf)

    def tag_filescan(meta):
        fmt = meta.node.fmt
        if fmt == "csv" and not meta.conf.get(CFG.CSV_ENABLED):
            meta.will_not_work("CSV scan disabled by conf")
        if fmt == "orc" and not meta.conf.get(CFG.ORC_ENABLED):
            meta.will_not_work("ORC scan disabled by conf")

    REGISTRY.exec_rule(FileScanNode, ExecRule(
        "accelerated parquet/orc/csv scan", conv_filescan,
        ExecChecks(ORDERABLE), None, tag_filescan))


_register_scan_rule()
