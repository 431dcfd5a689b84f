"""Columnar file writers with commit protocol and write statistics.

Reference: ColumnarOutputWriter.scala (per-partition writer), GpuParquetFileFormat
(348) / GpuOrcFileFormat (178), GpuFileFormatDataWriter (419: single-directory and
dynamic-partitioning writers), GpuFileFormatWriter (345: job setup/commit),
BasicColumnarWriteStatsTracker (180). The commit protocol mirrors Hadoop's
FileOutputCommitter v2: task writes into `_temporary/<task>/`, task-commit renames
into the final directory, job-commit writes `_SUCCESS`."""

from __future__ import annotations

import dataclasses
import os
import shutil
import threading
import uuid

import pyarrow as pa
import pyarrow.parquet as pq

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.plan.nodes import PlanNode
from spark_rapids_tpu.runtime import tracing


@dataclasses.dataclass
class WriteStats:
    """Reference BasicColumnarWriteStatsTracker: files/partitions/rows/bytes."""
    num_files: int = 0
    num_rows: int = 0
    num_bytes: int = 0
    partitions: list = dataclasses.field(default_factory=list)

    def merge(self, other: "WriteStats"):
        self.num_files += other.num_files
        self.num_rows += other.num_rows
        self.num_bytes += other.num_bytes
        self.partitions.extend(other.partitions)


def _write_table(tbl: pa.Table, path: str, fmt: str, compression: str):
    if fmt == "parquet":
        pq.write_table(tbl, path, compression=compression)
    elif fmt == "orc":
        import pyarrow.orc as orc
        orc.write_table(tbl, path)
    elif fmt == "csv":
        import pyarrow.csv as pcsv
        pcsv.write_csv(tbl, path)
    else:
        raise ValueError(f"unknown format {fmt}")


class _TaskWriter:
    """One task's output: plain or dynamic-partitioned
    (reference GpuFileFormatDataWriter SingleDirectory/DynamicPartition writers)."""

    def __init__(self, temp_dir: str, task_id: int, fmt: str, compression: str,
                 partition_by: list, schema: T.StructType, job_uuid: str,
                 native: bool = False):
        self.temp = os.path.join(temp_dir, f"task_{task_id}")
        os.makedirs(self.temp, exist_ok=True)
        self.fmt = fmt
        self.compression = compression
        self.partition_by = partition_by
        self.schema = schema
        self.stats = WriteStats()
        self._file_counter = 0
        self._task_id = task_id
        self._job_uuid = job_uuid
        self.native = native

    def _next_name(self, subdir: str = "") -> str:
        # job-unique uuid in the filename (Spark's FileOutputCommitter naming)
        # so mode=append never collides with files from an earlier job that
        # used the same task ids.
        ext = {"parquet": "parquet", "orc": "orc", "csv": "csv"}[self.fmt]
        name = (f"part-{self._task_id:05d}-{self._job_uuid}"
                f"-{self._file_counter:04d}.{ext}")
        self._file_counter += 1
        d = os.path.join(self.temp, subdir)
        os.makedirs(d, exist_ok=True)
        return os.path.join(d, name)

    def _native_module(self):
        if self.fmt == "parquet":
            from spark_rapids_tpu.io import parquet_write_native as m
        elif self.fmt == "orc":
            from spark_rapids_tpu.io import orc_write_native as m
        elif self.fmt == "csv":
            from spark_rapids_tpu.io import csv_write_native as m
        else:
            return None
        return m

    def write_batch(self, batch):
        """Device-path write: encode Parquet pages / ORC stripes / CSV text
        straight from the device columns (reference ColumnarOutputWriter
        device-buffer write; GpuOrcFileFormat.scala). Falls back to the
        arrow path for partitioned writes and schemas the native encoders
        can't frame."""
        m = self._native_module() if self.native else None
        if m is not None and not self.partition_by:
            from spark_rapids_tpu.columnar.batch import ColumnarBatch
            from spark_rapids_tpu.columnar.vector import TpuColumnVector
            if (isinstance(batch, ColumnarBatch)
                    and m.supports_schema(self.schema)
                    # exact type: subclasses (ListVector) carry structure the
                    # flat encoders can't frame
                    and all(type(c) is TpuColumnVector
                            for c in batch.columns)):
                path = self._next_name()
                try:
                    if self.fmt == "csv":
                        nbytes = m.write_batch_file(path, batch, self.schema)
                    else:
                        nbytes = m.write_batch_file(
                            path, batch, self.schema, self.compression)
                except (TypeError, ValueError) as e:
                    # schema/codec are pre-validated, so this is an encoder
                    # defect — fall back to arrow but never silently
                    import warnings
                    warnings.warn(
                        f"native {self.fmt} encoder failed ({e!r}); "
                        f"falling back to arrow writer for this task")
                    if os.path.exists(path):
                        os.unlink(path)
                    self._file_counter -= 1
                else:
                    self.stats.num_files += 1
                    self.stats.num_rows += batch.num_rows
                    self.stats.num_bytes += nbytes
                    return
        self.write(batch.to_arrow())

    def write(self, tbl: pa.Table):
        if not self.partition_by:
            path = self._next_name()
            _write_table(tbl, path, self.fmt, self.compression)
            self.stats.num_files += 1
            self.stats.num_rows += tbl.num_rows
            self.stats.num_bytes += os.path.getsize(path)
            return
        # dynamic partitioning: group rows by partition values, one dir per combo
        keys = [tbl.column(c).to_pylist() for c in self.partition_by]
        data_cols = [c for c in tbl.column_names if c not in self.partition_by]
        groups: dict = {}
        for i in range(tbl.num_rows):
            combo = tuple(k[i] for k in keys)
            groups.setdefault(combo, []).append(i)
        for combo, rows in groups.items():
            subdir = os.path.join(*[
                f"{c}={'__HIVE_DEFAULT_PARTITION__' if v is None else v}"
                for c, v in zip(self.partition_by, combo)])
            sub = tbl.select(data_cols).take(pa.array(rows, pa.int64()))
            path = self._next_name(subdir)
            _write_table(sub, path, self.fmt, self.compression)
            self.stats.num_files += 1
            self.stats.num_rows += sub.num_rows
            self.stats.num_bytes += os.path.getsize(path)
            if subdir not in self.stats.partitions:
                self.stats.partitions.append(subdir)

    def commit(self, final_dir: str):
        """Move task output into the final directory (FileOutputCommitter v2)."""
        for dirpath, _, files in os.walk(self.temp):
            rel = os.path.relpath(dirpath, self.temp)
            dest = final_dir if rel == "." else os.path.join(final_dir, rel)
            os.makedirs(dest, exist_ok=True)
            for f in files:
                os.replace(os.path.join(dirpath, f), os.path.join(dest, f))
        shutil.rmtree(self.temp, ignore_errors=True)

    def abort(self):
        shutil.rmtree(self.temp, ignore_errors=True)


def write_columnar(exec_or_node, path: str, fmt: str = "parquet",
                   partition_by: list | None = None, compression: str = "snappy",
                   mode: str = "error", conf=None) -> WriteStats:
    """Write a device exec's (or host node's) output — the
    GpuInsertIntoHadoopFsRelationCommand analog (job setup → per-partition task
    writers → commit + _SUCCESS)."""
    from spark_rapids_tpu.exec.base import TaskContext, TpuExec

    if mode not in ("error", "overwrite", "append", "ignore"):
        raise ValueError(f"unknown save mode {mode!r}")
    if os.path.exists(path) and os.listdir(path):
        if mode == "error":
            raise FileExistsError(path)
        if mode == "ignore":
            return WriteStats()
        if mode == "overwrite":
            shutil.rmtree(path)
    os.makedirs(path, exist_ok=True)
    job_uuid = uuid.uuid4().hex[:12]
    temp_dir = os.path.join(path, f"_temporary-{job_uuid}")
    os.makedirs(temp_dir, exist_ok=True)
    partition_by = partition_by or []
    schema = exec_or_node.output
    total = WriteStats()
    lock = threading.Lock()
    from spark_rapids_tpu import config as CFG
    entry = {"parquet": CFG.PARQUET_WRITER_TYPE, "orc": CFG.ORC_WRITER_TYPE,
             "csv": CFG.CSV_WRITER_TYPE}.get(fmt)
    writer_type = (conf.get(entry) if conf is not None
                   else entry.default) if entry is not None else "ARROW"
    native = str(writer_type).upper() == "NATIVE"

    from spark_rapids_tpu.runtime import metrics as M
    collector = M.current_collector()
    parent_span = tracing.current_span()

    def run_split(split):
        writer = _TaskWriter(temp_dir, split, fmt, compression, partition_by,
                             schema, job_uuid, native=native)
        try:
            if isinstance(exec_or_node, TpuExec):
                with M.collector_context(collector), TaskContext(), \
                        tracing.child_of(parent_span):
                    for batch in exec_or_node.execute_partition(split):
                        writer.write_batch(batch)
            else:
                writer.write(exec_or_node.execute_host(split))
            writer.commit(path)
            with lock:
                total.merge(writer.stats)
        except BaseException:
            writer.abort()
            raise

    from concurrent.futures import ThreadPoolExecutor
    n = exec_or_node.num_partitions
    with ThreadPoolExecutor(max_workers=min(4, n)) as pool:
        list(pool.map(run_split, range(n)))
    shutil.rmtree(temp_dir, ignore_errors=True)
    with open(os.path.join(path, "_SUCCESS"), "w"):
        pass
    return total


class FileWriteNode(PlanNode):
    """Plan node for INSERT INTO path (host side runs the same writer)."""

    def __init__(self, child: PlanNode, path: str, fmt: str = "parquet",
                 partition_by: list | None = None, mode: str = "error"):
        super().__init__(child)
        self.path = path
        self.fmt = fmt
        self.partition_by = partition_by or []
        self.mode = mode

    @property
    def output(self):
        return self.child.output

    def execute_host(self, split):
        raise NotImplementedError("use write_columnar() to run a write job")
