"""Native Parquet encode: device computes, host frames (VERDICT r3 weak #7).

Reference: ColumnarOutputWriter.scala / GpuParquetFileFormat.scala:348 write
Parquet straight from device buffers (libcudf's writer); the previous path
here round-tripped every batch device -> host arrow -> pyarrow re-encode.
This module keeps the WORK on the device and leaves only byte FRAMING to the
host — the same split io/parquet_native.py uses for reads (metadata on host,
bulk bits on device):

- device (one jitted kernel per column dtype/capacity): null-compaction of
  the value stream (Parquet PLAIN stores only non-null values), null_count,
  and min/max statistics (masked reductions). String columns never
  materialize bytes on device — their int32 dictionary codes ARE the
  dictionary-page indices (the engine's order-preserving sorted dictionary
  maps 1:1 onto a Parquet dictionary page, so string min/max = code min/max).
- host: definition-level RLE/bit-pack hybrid, thrift compact metadata
  (PageHeader / ColumnMetaData / FileMetaData — mirror image of
  parquet_native._CompactReader), page compression, file assembly.

Codecs: UNCOMPRESSED, GZIP (zlib, real compression), SNAPPY (real
compression via pyarrow's bundled codec — the same `pa.Codec` the ORC
native writer uses, io/orc_write_native.py:_compress_chunked; spec-valid
all-literal framing remains as the fallback if the codec is unavailable).
Schemas with list columns or decimals beyond DECIMAL64 fall back to the
arrow writer (io/writer.py routes).
"""

from __future__ import annotations

import functools
import struct
import zlib

import numpy as np
import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.ops.filtering import front_perm

MAGIC = b"PAR1"

# --- thrift compact protocol writer (inverse of parquet_native._CompactReader)

_CT_BOOL_TRUE, _CT_BOOL_FALSE = 1, 2
_CT_I16, _CT_I32, _CT_I64 = 4, 5, 6
_CT_BINARY, _CT_LIST, _CT_STRUCT = 8, 9, 12


def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _zigzag(v: int) -> bytes:
    return _varint((v << 1) ^ (v >> 63))


class _CompactWriter:
    """Emit one thrift-compact struct. Fields must be written in ascending
    field-id order (the compact protocol encodes the id as a delta)."""

    def __init__(self):
        self.buf = bytearray()
        self._last_fid = [0]

    def _field_header(self, fid: int, ftype: int):
        delta = fid - self._last_fid[-1]
        if 0 < delta <= 15:
            self.buf.append((delta << 4) | ftype)
        else:
            self.buf.append(ftype)
            self.buf += _zigzag(fid)
        self._last_fid[-1] = fid

    def field_bool(self, fid: int, v: bool):
        self._field_header(fid, _CT_BOOL_TRUE if v else _CT_BOOL_FALSE)

    def field_i32(self, fid: int, v: int, *, wide: int = _CT_I32):
        self._field_header(fid, wide)
        self.buf += _zigzag(v)

    def field_i64(self, fid: int, v: int):
        self.field_i32(fid, v, wide=_CT_I64)

    def field_binary(self, fid: int, v: bytes):
        self._field_header(fid, _CT_BINARY)
        self.buf += _varint(len(v))
        self.buf += v

    def begin_struct(self, fid: int):
        self._field_header(fid, _CT_STRUCT)
        self._last_fid.append(0)

    def end_struct(self):
        self.buf.append(0)
        self._last_fid.pop()

    def begin_list(self, fid: int, elem_type: int, size: int):
        self._field_header(fid, _CT_LIST)
        if size < 15:
            self.buf.append((size << 4) | elem_type)
        else:
            self.buf.append(0xF0 | elem_type)
            self.buf += _varint(size)

    def list_i32(self, v: int):
        self.buf += _zigzag(v)

    def list_binary(self, v: bytes):
        self.buf += _varint(len(v))
        self.buf += v

    def end_top(self) -> bytes:
        self.buf.append(0)
        return bytes(self.buf)


# --- physical-type mapping -------------------------------------------------

# parquet Type enum
_PT_BOOLEAN, _PT_INT32, _PT_INT64 = 0, 1, 2
_PT_FLOAT, _PT_DOUBLE, _PT_BYTE_ARRAY = 4, 5, 6
# ConvertedType enum values actually used
_CV_UTF8, _CV_DECIMAL, _CV_DATE, _CV_TS_MICROS = 0, 5, 6, 10
_CV_INT8, _CV_INT16 = 15, 16
# CompressionCodec enum
CODECS = {"uncompressed": 0, "none": 0, "snappy": 1, "gzip": 2}
# Encoding enum
_ENC_PLAIN, _ENC_PLAIN_DICTIONARY, _ENC_RLE = 0, 2, 3


def _physical(dt: T.DataType):
    """(parquet Type, converted_type|None, value numpy dtype for the PLAIN
    byte image). Raises TypeError for schemas the native writer can't frame —
    the caller falls back to arrow."""
    if isinstance(dt, T.BooleanType):
        return _PT_BOOLEAN, None, np.bool_
    if isinstance(dt, T.ByteType):
        return _PT_INT32, _CV_INT8, np.int32
    if isinstance(dt, T.ShortType):
        return _PT_INT32, _CV_INT16, np.int32
    if isinstance(dt, T.IntegerType):
        return _PT_INT32, None, np.int32
    if isinstance(dt, T.LongType):
        return _PT_INT64, None, np.int64
    if isinstance(dt, T.FloatType):
        return _PT_FLOAT, None, np.float32
    if isinstance(dt, T.DoubleType):
        return _PT_DOUBLE, None, np.float64
    if isinstance(dt, T.StringType):
        return _PT_BYTE_ARRAY, _CV_UTF8, np.int32
    if isinstance(dt, T.DateType):
        return _PT_INT32, _CV_DATE, np.int32
    if isinstance(dt, T.TimestampType):
        return _PT_INT64, _CV_TS_MICROS, np.int64
    if isinstance(dt, T.DecimalType):
        if dt.precision > 18:
            raise TypeError(f"native writer: decimal precision {dt.precision}")
        return _PT_INT64, _CV_DECIMAL, np.int64
    raise TypeError(f"native parquet writer: unsupported type {dt}")


def supports_schema(schema: T.StructType) -> bool:
    try:
        for f in schema.fields:
            _physical(f.data_type)
    except TypeError:
        return False
    return True


# --- device kernel: compact + stats ---------------------------------------

@functools.lru_cache(maxsize=256)
def _prep_kernel(cap: int, dt_name: str):
    """Per (capacity, dtype) jitted column prep: stable-compact non-null
    values to the front (ops/filtering.front_perm, as compact_cols does)
    and reduce min/max/null_count in one program."""
    dt = jnp.dtype(dt_name)
    if jnp.issubdtype(dt, jnp.floating):
        lo, hi = -jnp.inf, jnp.inf
    elif dt == jnp.bool_:
        lo, hi = False, True
    else:
        info = jnp.iinfo(dt)
        lo, hi = info.min, info.max

    @jax.jit
    def k(vals, valid, n):
        live = jnp.arange(cap) < n
        vl = valid & live
        perm, cnt = front_perm(vl)
        comp = vals[perm]
        if dt == jnp.bool_:
            vmin = jnp.where(vl, vals, True).all()
            vmax = jnp.where(vl, vals, False).any()
        else:
            vmin = jnp.where(vl, vals, hi).min()
            vmax = jnp.where(vl, vals, lo).max()
        return comp, cnt, n - cnt, vmin, vmax, vl

    return k


def _prep_column(col, num_rows: int):
    """Run the device prep; returns host-side (values[:n_valid], n_valid,
    null_count, vmin, vmax, valid[:num_rows]) — one device->host transfer
    for the stream (the validity rides along so _encode_column doesn't pay
    a second per-column transfer for definition levels)."""
    k = _prep_kernel(col.capacity, np.dtype(col.data.dtype).name)
    comp, cnt, nulls, vmin, vmax, vl = k(col.data, col.validity,
                                         jnp.int32(num_rows))
    cnt, nulls = int(cnt), int(nulls)
    # static device-side slice before transfer: capacities are power-of-two
    # bucketed, so the padded tail can dwarf the live rows (to_host pattern).
    # All-valid columns (the common case) skip the validity transfer.
    valid = np.asarray(vl[:num_rows]) if nulls else None
    return (np.asarray(comp[:num_rows])[:cnt], cnt, nulls,
            np.asarray(vmin)[()], np.asarray(vmax)[()], valid)


# --- host framing ----------------------------------------------------------

def _rle_bitpacked(values: np.ndarray, bit_width: int) -> bytes:
    """RLE/bit-packed hybrid, bit-packed branch only (groups of 8 values,
    LSB-first within each byte — Parquet's layout matches numpy's
    bitorder='little')."""
    n = len(values)
    if n == 0:
        return b""
    groups = (n + 7) // 8
    padded = np.zeros(groups * 8, dtype=np.uint32)
    padded[:n] = values.astype(np.uint32)
    bits = ((padded[:, None] >> np.arange(bit_width, dtype=np.uint32)) & 1)
    packed = np.packbits(bits.astype(np.uint8).ravel(), bitorder="little")
    return _varint((groups << 1) | 1) + packed.tobytes()


def _def_levels_v1(valid: np.ndarray) -> bytes:
    """Definition levels for one optional flat column, v1 framing: 4-byte LE
    length prefix + RLE/bit-packed hybrid of 1-bit levels."""
    n = len(valid)
    if n and valid.all():
        body = _varint(n << 1) + b"\x01"      # one RLE run of 1s
    elif n and not valid.any():
        body = _varint(n << 1) + b"\x00"
    else:
        body = _rle_bitpacked(valid.astype(np.uint8), 1)
    return struct.pack("<I", len(body)) + body


def _snappy(raw: bytes) -> bytes:
    """Real SNAPPY page compression via pyarrow's bundled codec (ported
    from the ORC writer, io/orc_write_native.py:77 — parquet compresses the
    whole page body as one raw snappy block, no chunk headers needed).
    Falls back to the spec-valid all-literal framing when the codec is
    missing from the arrow build."""
    try:
        import pyarrow as pa
        return bytes(pa.Codec("snappy").compress(raw))
    except (ImportError, NotImplementedError, OSError):
        return _snappy_literal(raw)


def _snappy_literal(raw: bytes) -> bytes:
    """Spec-valid snappy framing of one all-literal chunk (no compression —
    the _snappy fallback)."""
    n = len(raw)
    out = bytearray(_varint(n))
    if n == 0:
        return bytes(out)
    if n <= 60:
        out.append((n - 1) << 2)
    else:
        length = n - 1
        nbytes = (length.bit_length() + 7) // 8
        out.append((59 + nbytes) << 2)
        out += length.to_bytes(nbytes, "little")
    out += raw
    return bytes(out)


def _compress(raw: bytes, codec: str) -> bytes:
    if codec in ("uncompressed", "none"):
        return raw
    if codec == "gzip":
        co = zlib.compressobj(6, zlib.DEFLATED, 31)
        return co.compress(raw) + co.flush()
    if codec == "snappy":
        return _snappy(raw)
    raise ValueError(f"native parquet writer: codec {codec}")


def _plain_stat_bytes(dt: T.DataType, v, dictionary=None) -> bytes | None:
    """PLAIN byte image of one statistics value; None suppresses the stat."""
    if isinstance(dt, T.StringType):
        if dictionary is None or len(dictionary) == 0:
            return None
        return dictionary[int(v)].as_py().encode("utf-8")
    pt, _, np_dt = _physical(dt)
    if pt == _PT_BOOLEAN:
        return b"\x01" if bool(v) else b"\x00"
    a = np.asarray(v).astype(np_dt)
    if np.issubdtype(a.dtype, np.floating) and np.isnan(a):
        return None
    return a.tobytes()


class _ColumnResult(object):
    __slots__ = ("pages", "meta_fields", "dict_page_len")

    def __init__(self, pages, meta_fields, dict_page_len):
        self.pages = pages                # list[bytes] ready to append
        self.meta_fields = meta_fields    # dict for ColumnMetaData
        self.dict_page_len = dict_page_len


def _page_header(page_type: int, unc: int, comp: int, body_writer) -> bytes:
    w = _CompactWriter()
    w.field_i32(1, page_type)
    w.field_i32(2, unc)
    w.field_i32(3, comp)
    body_writer(w)
    return w.end_top()


def _stats_struct(w: _CompactWriter, fid: int, null_count: int,
                  min_b: bytes | None, max_b: bytes | None):
    w.begin_struct(fid)
    w.field_i64(3, null_count)
    if max_b is not None:
        w.field_binary(5, max_b)
    if min_b is not None:
        w.field_binary(6, min_b)
    w.end_struct()


def _encode_column(col, dt: T.DataType, num_rows: int, codec: str):
    """Encode one column chunk: optional dictionary page + one v1 data page."""
    vals, n_valid, null_count, vmin, vmax, valid = _prep_column(col, num_rows)
    if valid is None:
        valid = np.ones(num_rows, dtype=bool)

    pt, _, np_dt = _physical(dt)
    is_string = isinstance(dt, T.StringType)
    pages = []
    dict_page_len = 0
    raw_bytes = 0   # spec: total_uncompressed_size = headers + RAW page bodies
    encodings = [_ENC_RLE, _ENC_PLAIN]

    if is_string:
        # dictionary page: PLAIN byte arrays of the engine's sorted dictionary
        entries = ([] if col.dictionary is None
                   else [s.as_py().encode("utf-8") for s in col.dictionary])
        raw = b"".join(struct.pack("<I", len(e)) + e for e in entries)
        comp = _compress(raw, codec)
        hdr = _page_header(2, len(raw), len(comp), lambda w: (
            w.begin_struct(7),
            w.field_i32(1, len(entries)),
            w.field_i32(2, _ENC_PLAIN_DICTIONARY),
            w.end_struct()))
        pages.append(hdr + comp)
        dict_page_len = len(hdr) + len(comp)
        raw_bytes += len(hdr) + len(raw)
        # data page payload: bit width byte + RLE/bit-packed dictionary codes
        bw = max(1, (max(1, len(entries)) - 1).bit_length())
        payload = bytes([bw]) + _rle_bitpacked(vals.astype(np.uint32), bw)
        encodings = [_ENC_RLE, _ENC_PLAIN_DICTIONARY]
    elif pt == _PT_BOOLEAN:
        payload = np.packbits(vals.astype(np.uint8),
                              bitorder="little").tobytes()
    else:
        payload = vals.astype(np_dt).tobytes()

    raw_page = _def_levels_v1(valid) + payload
    comp_page = _compress(raw_page, codec)
    min_b = _plain_stat_bytes(dt, vmin, col.dictionary) if n_valid else None
    max_b = _plain_stat_bytes(dt, vmax, col.dictionary) if n_valid else None
    enc = _ENC_PLAIN_DICTIONARY if is_string else _ENC_PLAIN
    hdr = _page_header(0, len(raw_page), len(comp_page), lambda w: (
        w.begin_struct(5),
        w.field_i32(1, num_rows),
        w.field_i32(2, enc),
        w.field_i32(3, _ENC_RLE),
        w.field_i32(4, _ENC_RLE),
        _stats_struct(w, 5, null_count, min_b, max_b),
        w.end_struct()))
    pages.append(hdr + comp_page)
    raw_bytes += len(hdr) + len(raw_page)

    meta = {
        "type": pt,
        "encodings": encodings,
        "codec": CODECS[codec],
        "num_values": num_rows,
        "total_uncompressed_size": raw_bytes,
        "null_count": null_count,
        "min_b": min_b,
        "max_b": max_b,
    }
    return _ColumnResult(pages, meta, dict_page_len)


def _schema_elements(w: _CompactWriter, schema: T.StructType):
    w.begin_list(2, _CT_STRUCT, len(schema.fields) + 1)
    # root
    r = _CompactWriter()
    r.field_binary(4, b"schema")
    r.field_i32(5, len(schema.fields))
    w.buf += r.end_top()
    for f in schema.fields:
        pt, cv, _ = _physical(f.data_type)
        e = _CompactWriter()
        e.field_i32(1, pt)
        e.field_i32(3, 1)                      # OPTIONAL
        e.field_binary(4, f.name.encode("utf-8"))
        if cv is not None:
            e.field_i32(6, cv)
        if isinstance(f.data_type, T.DecimalType):
            e.field_i32(7, f.data_type.scale)
            e.field_i32(8, f.data_type.precision)
        if isinstance(f.data_type, T.TimestampType):
            # LogicalType TIMESTAMP(isAdjustedToUTC=true, MICROS) — readers
            # reconstruct timestamp[us, UTC] (converted_type alone is naive)
            e.begin_struct(10)
            e.begin_struct(8)
            e.field_bool(1, True)
            e.begin_struct(2)
            e.begin_struct(2)                  # TimeUnit.MICROS (empty)
            e.end_struct()
            e.end_struct()
            e.end_struct()
            e.end_struct()
        w.buf += e.end_top()


class NativeParquetFile:
    """Streaming writer: one row group per append_batch(). Mirrors the task
    writer lifecycle (open -> append* -> close) of ColumnarOutputWriter."""

    def __init__(self, path: str, schema: T.StructType,
                 compression: str = "snappy"):
        codec = compression.lower()
        if codec not in CODECS:
            raise ValueError(f"native parquet writer: codec {compression}")
        if not supports_schema(schema):
            raise TypeError("schema unsupported by native writer")
        self.path = path
        self.schema = schema
        self.codec = codec
        self._f = open(path, "wb")
        self._f.write(MAGIC)
        self._offset = len(MAGIC)
        self._row_groups = []   # (columns_meta, num_rows, total_bytes)
        self._num_rows = 0

    def append_batch(self, batch) -> int:
        """Encode one ColumnarBatch as a row group; returns bytes written."""
        n = batch.num_rows
        cols_meta = []
        group_bytes = 0
        for field, col in zip(self.schema.fields, batch.columns):
            res = _encode_column(col, field.data_type, n, self.codec)
            first_off = self._offset
            for p in res.pages:
                self._f.write(p)
                self._offset += len(p)
            m = dict(res.meta_fields)
            m["path"] = field.name
            if res.dict_page_len:
                m["dictionary_page_offset"] = first_off
                m["data_page_offset"] = first_off + res.dict_page_len
            else:
                m["data_page_offset"] = first_off
            m["file_offset"] = first_off
            m["total_compressed_size"] = self._offset - first_off
            cols_meta.append(m)
            group_bytes += m["total_uncompressed_size"]
        self._row_groups.append((cols_meta, n, group_bytes))
        self._num_rows += n
        return sum(m["total_compressed_size"] for m in cols_meta)

    def close(self):
        if self._f is None:
            return
        w = _CompactWriter()
        w.field_i32(1, 1)                       # version
        _schema_elements(w, self.schema)
        w.field_i64(3, self._num_rows)
        w.begin_list(4, _CT_STRUCT, len(self._row_groups))
        for cols_meta, n, group_bytes in self._row_groups:
            g = _CompactWriter()
            g.begin_list(1, _CT_STRUCT, len(cols_meta))
            for m in cols_meta:
                c = _CompactWriter()
                c.field_i64(2, m["file_offset"])
                c.begin_struct(3)               # ColumnMetaData
                c.field_i32(1, m["type"])
                c.begin_list(2, _CT_I32, len(m["encodings"]))
                for e in m["encodings"]:
                    c.list_i32(e)
                c.begin_list(3, _CT_BINARY, 1)
                c.list_binary(m["path"].encode("utf-8"))
                c.field_i32(4, m["codec"])
                c.field_i64(5, m["num_values"])
                c.field_i64(6, m["total_uncompressed_size"])
                c.field_i64(7, m["total_compressed_size"])
                c.field_i64(9, m["data_page_offset"])
                if "dictionary_page_offset" in m:
                    c.field_i64(11, m["dictionary_page_offset"])
                _stats_struct(c, 12, m["null_count"], m["min_b"], m["max_b"])
                c.end_struct()
                g.buf += c.end_top()
            g.field_i64(2, group_bytes)
            g.field_i64(3, n)
            w.buf += g.end_top()
        w.field_binary(6, b"spark-rapids-tpu native writer")
        # ColumnOrder TYPE_ORDER per column — without this readers must treat
        # min_value/max_value statistics as having undefined ordering
        w.begin_list(7, _CT_STRUCT, len(self.schema.fields))
        for _ in self.schema.fields:
            o = _CompactWriter()
            o.begin_struct(1)      # TypeDefinedOrder (empty struct)
            o.end_struct()
            w.buf += o.end_top()
        footer = w.end_top()
        self._f.write(footer)
        self._f.write(struct.pack("<I", len(footer)))
        self._f.write(MAGIC)
        self._f.close()
        self._f = None

    def abort(self):
        if self._f is not None:
            self._f.close()
            self._f = None


def write_batch_file(path: str, batch, schema: T.StructType,
                     compression: str = "snappy") -> int:
    """One batch -> one file with one row group (the per-batch shape
    io/writer.py's task writer uses). Returns bytes written."""
    f = NativeParquetFile(path, schema, compression)
    try:
        f.append_batch(batch)
        f.close()
    except BaseException:
        f.abort()
        raise
    import os
    return os.path.getsize(path)
