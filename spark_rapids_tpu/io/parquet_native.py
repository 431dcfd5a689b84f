"""Native parquet page access: thrift metadata + page splitting on host, bulk
index decode on device (stage-one device decode, SURVEY.md §7).

Reference: GpuParquetScan.scala:1235 hands raw column-chunk bytes to
`Table.readParquet` so the GPU does page decode. TPU realization: the THRIFT
page headers and RLE run STRUCTURE are metadata (bytes to kilobytes — parsed
on host, like string dictionaries), while the BULK bytes — bit-packed
dictionary indices and definition levels — go to the device, where one jitted
program a column chunk unpacks bits and gathers dictionary values
(ops/parquet_decode.py): the single-page decode for a chunk bit-packed at one
width, the segment-table decode for RLE runs and widths that differ by page.
The parquet dictionary page maps 1:1 onto the engine's own dictionary-encoded
string representation, so a string column never materializes per-row bytes.

Scope: UNCOMPRESSED / SNAPPY / GZIP / ZSTD chunks (compressed page bodies
decompress on host through arrow's C codecs — stage 1.5; the reference uses
nvcomp on GPU), RLE_DICTIONARY-encoded data pages (v1 and v2), flat schemas,
physical types INT32/INT64/FLOAT/DOUBLE/BYTE_ARRAY. Anything else falls
back to the arrow decode path per column chunk.
"""

from __future__ import annotations

import struct
import typing

import numpy as np

from spark_rapids_tpu.runtime import tracing


# -- thrift compact protocol (just enough for PageHeader) --------------------

class _CompactReader:
    def __init__(self, buf: bytes, pos: int = 0):
        self.buf = buf
        self.pos = pos

    def byte(self) -> int:
        b = self.buf[self.pos]
        self.pos += 1
        return b

    def varint(self) -> int:
        out = shift = 0
        while True:
            b = self.byte()
            out |= (b & 0x7F) << shift
            if not b & 0x80:
                return out
            shift += 7

    def zigzag(self) -> int:
        v = self.varint()
        return (v >> 1) ^ -(v & 1)

    def skip_binary(self):
        # NB: two statements — `self.pos += self.varint()` would load the
        # pre-varint pos before the call mutates it
        n = self.varint()
        self.pos += n

    def read_struct(self) -> dict:
        """Generic struct → {field_id: value}; nested structs recurse, lists
        and binaries are skipped (we never need them in page headers)."""
        out = {}
        fid = 0
        while True:
            head = self.byte()
            if head == 0:
                return out
            delta = head >> 4
            ftype = head & 0x0F
            fid = fid + delta if delta else self.zigzag()
            if ftype in (1, 2):            # BOOLEAN_TRUE / BOOLEAN_FALSE
                out[fid] = ftype == 1
            elif ftype == 3:               # byte
                out[fid] = self.byte()
            elif ftype in (4, 5, 6):       # i16/i32/i64
                out[fid] = self.zigzag()
            elif ftype == 7:               # double
                out[fid] = struct.unpack_from("<d", self.buf, self.pos)[0]
                self.pos += 8
            elif ftype == 8:               # binary/string
                self.skip_binary()
            elif ftype == 12:              # struct
                out[fid] = self.read_struct()
            elif ftype in (9, 10):         # list/set: skip elements
                sz_type = self.byte()
                n = sz_type >> 4
                if n == 15:
                    n = self.varint()
                et = sz_type & 0x0F
                for _ in range(n):
                    if et in (4, 5, 6):
                        self.zigzag()
                    elif et == 8:
                        self.skip_binary()
                    elif et == 12:
                        self.read_struct()
                    elif et == 3:
                        self.byte()
                    elif et == 7:
                        self.pos += 8
                    else:
                        raise NotImplementedError(f"thrift list elem {et}")
            else:
                raise NotImplementedError(f"thrift compact type {ftype}")


class PageHeader(typing.NamedTuple):
    page_type: int            # 0=data, 2=dictionary, 3=data v2
    uncompressed_size: int
    compressed_size: int
    num_values: int
    encoding: int             # 8=RLE_DICTIONARY(PLAIN_DICT=2), 0=PLAIN
    header_len: int
    # v2 only: level-section byte lengths (levels are NEVER compressed) and
    # whether the values section is compressed
    def_len: int = 0
    rep_len: int = 0
    v2_compressed: bool = True


def parse_page_header(buf: bytes, pos: int) -> PageHeader:
    r = _CompactReader(buf, pos)
    d = r.read_struct()
    ptype = d[1]
    dl = rl = 0
    v2c = True
    if ptype == 0:      # DataPageHeader (field 5)
        dph = d.get(5, {})
        nv, enc = dph.get(1, 0), dph.get(2, 0)
    elif ptype == 2:    # DictionaryPageHeader (field 7)
        dph = d.get(7, {})
        nv, enc = dph.get(1, 0), dph.get(2, 0)
    elif ptype == 3:    # DataPageHeaderV2 (field 8)
        dph = d.get(8, {})
        nv, enc = dph.get(1, 0), dph.get(4, 0)
        dl, rl = dph.get(5, 0), dph.get(6, 0)
        v2c = bool(dph.get(7, 1))
    else:
        nv, enc = 0, 0
    return PageHeader(ptype, d[2], d[3], nv, enc, r.pos - pos, dl, rl, v2c)


# -- RLE / bit-packed hybrid structure ---------------------------------------

class RleSegment(typing.NamedTuple):
    kind: str          # "rle" | "packed"
    count: int         # decoded value count
    value: int         # rle: the repeated value
    byte_off: int      # packed: offset of packed bytes in the stream
    byte_len: int


def parse_rle_hybrid(buf: bytes, pos: int, end: int, bit_width: int,
                     total: int) -> list[RleSegment]:
    """Split an RLE/bit-packed hybrid stream into segments. Headers are
    varints (metadata); packed payload bytes are NOT touched here — the
    device unpacks them."""
    r = _CompactReader(buf, pos)
    segs: list[RleSegment] = []
    got = 0
    vbytes = (bit_width + 7) // 8
    while got < total and r.pos < end:
        h = r.varint()
        if h & 1:
            groups = h >> 1
            n = groups * 8
            blen = groups * bit_width  # bytes: 8 values * bw bits / 8
            segs.append(RleSegment("packed", min(n, total - got), 0,
                                   r.pos, blen))
            r.pos += blen
        else:
            run = h >> 1
            v = int.from_bytes(buf[r.pos:r.pos + vbytes], "little") \
                if vbytes else 0
            r.pos += vbytes
            segs.append(RleSegment("rle", min(run, total - got), v, 0, 0))
        got += segs[-1].count
    return segs


# a page's segments as the scan keeps them: int64 (n, 5), one row a segment
RUN_KIND, RUN_COUNT, RUN_VALUE, RUN_OFF, RUN_LEN = range(5)   # kind: 1=packed


def decode_rle_host(buf: bytes, pos: int, end: int, bit_width: int,
                    total: int) -> np.ndarray:
    """Host (numpy-vectorized) hybrid decode — def levels and fallback path."""
    out = np.empty(total, dtype=np.int32)
    at = 0
    for seg in parse_rle_hybrid(buf, pos, end, bit_width, total):
        if seg.kind == "rle":
            out[at:at + seg.count] = seg.value
        else:
            bits = np.unpackbits(
                np.frombuffer(buf, np.uint8, seg.byte_len, seg.byte_off),
                bitorder="little")
            vals = bits.reshape(-1, bit_width)[:seg.count]
            out[at:at + seg.count] = (
                vals.astype(np.int32) * (1 << np.arange(bit_width,
                                                        dtype=np.int32))
            ).sum(axis=1)
        at += seg.count
    return out


# -- column chunk reading -----------------------------------------------------

class ChunkPages(typing.NamedTuple):
    physical_type: str
    dict_values: np.ndarray | list      # decoded PLAIN dictionary (host)
    index_segments: list                # per data page: (num_values,
                                        #   def_levels np, bit_width,
                                        #   page bytes, offset of the
                                        #   bit-width byte, segments
                                        #   int64 (n, 5): RUN_* columns)
    num_values: int


_FIXED = {"INT32": ("<i4", 4), "INT64": ("<i8", 8),
          "FLOAT": ("<f4", 4), "DOUBLE": ("<f8", 8)}


class OutOfScope(NotImplementedError):
    """A column chunk the fused decode does not take. ``reason`` is one fixed
    word a cause, which the ``scan.fallback`` span counts: ``codec``,
    ``encodings`` (no dictionary encoding at all), ``type``, ``nested``,
    ``no_dictionary`` (the chunk has no dictionary page), ``page`` (a data
    page that is not dictionary-encoded, as a writer makes once its
    dictionary outgrows its limit, or one the page walk cannot read)."""

    def __init__(self, reason: str, message: str):
        super().__init__(message)
        self.reason = reason


def _decode_plain_dictionary(physical_type: str, raw: bytes, n: int):
    if physical_type in _FIXED:
        dt, _ = _FIXED[physical_type]
        return np.frombuffer(raw, dtype=dt, count=n).copy()
    if physical_type == "BYTE_ARRAY":
        out, pos = [], 0
        for _ in range(n):
            (ln,) = struct.unpack_from("<I", raw, pos)
            pos += 4
            out.append(raw[pos:pos + ln].decode("utf-8"))
            pos += ln
        return out
    raise OutOfScope("type", physical_type)


def read_chunk_pages(path: str, row_group: int, column: int,
                     md=None) -> ChunkPages:
    """Parse one dictionary-encoded column chunk (UNCOMPRESSED, or
    SNAPPY/GZIP/ZSTD with page bodies decompressed on host) into its raw
    device-ready pieces. Raises NotImplementedError (an ``OutOfScope`` with
    its reason) when out of scope (caller falls back to arrow decode). `md`
    avoids re-parsing the footer per chunk (wide-table footers are MBs).

    One ``scan.read`` span: the chunk's bytes read, its pages scanned and
    its dictionary decoded; counts ``bytes`` (the chunk's compressed size),
    ``pages`` (data pages) and ``native`` (1 where one native call scanned
    the chunk, 0 for the page walk)."""
    if md is None:
        import pyarrow.parquet as pq
        md = pq.ParquetFile(path).metadata
    col = md.row_group(row_group).column(column)
    with tracing.span("scan.read", bytes=col.total_compressed_size) as sp:
        pages = _chunk_pages(path, col, md.schema.column(column), sp)
        if sp:
            sp.set(pages=len(pages.index_segments))
    return pages


def _chunk_pages(path: str, col, leaf, span) -> ChunkPages:
    """``read_chunk_pages`` for the chunk ``col`` (its footer entry) of the
    leaf column ``leaf`` (its schema entry)."""
    dec = None
    if col.compression != "UNCOMPRESSED":
        # stage 1.5: page bodies decompress on host via arrow's C codecs
        # (the reference decompresses on GPU through nvcomp; the DECODE —
        # the bulk bit work — still runs on device either way)
        import pyarrow as pa
        if col.compression not in ("SNAPPY", "GZIP", "ZSTD"):
            raise OutOfScope("codec", f"codec {col.compression}")
        try:
            dec = pa.Codec(col.compression.lower())
        except Exception as e:
            raise OutOfScope("codec", f"codec {col.compression}: {e}")
    if "RLE_DICTIONARY" not in col.encodings and \
            "PLAIN_DICTIONARY" not in col.encodings:
        raise OutOfScope("encodings", f"encodings {col.encodings}")
    if col.physical_type not in _FIXED and \
            col.physical_type != "BYTE_ARRAY":
        raise OutOfScope("type", f"type {col.physical_type}")

    max_def = leaf.max_definition_level
    if leaf.max_repetition_level:
        raise OutOfScope("nested", "nested (repeated) columns")

    with open(path, "rb") as f:
        start = col.dictionary_page_offset or col.data_page_offset
        f.seek(start)
        buf = f.read(col.total_compressed_size)

    # fast path: one native C call scans the whole chunk (thrift headers,
    # def-level RLE decode, hybrid segmentation — native/parquet_host.cpp);
    # the Python loop below is the path for chunks the native scanner
    # declines and the compressed-chunk path (bodies must decompress before
    # scanning): it walks the pages, and the native scanner still splits each
    # index stream into its runs (tens of thousands a chunk in a flag
    # column; `parse_rle_hybrid` is that step's executable spec). A scanner
    # that cannot be built or loaded (NativeBuildError, OSError) is an
    # error: the toolchain is part of this installation.
    from spark_rapids_tpu.native import scan_hybrid_native
    raw_pages = None
    if dec is None:  # compressed bodies must decompress before scanning
        from spark_rapids_tpu.native import scan_chunk_native
        try:
            raw_pages, dict_info = scan_chunk_native(buf, col.num_values,
                                                     max_def)
        except NotImplementedError:
            pass  # e.g. v2 data pages: the Python parser below handles them
    if raw_pages is not None:
        span.set(native=1)
        d_off, d_len, d_n = dict_info
        dict_vals = _decode_plain_dictionary(
            col.physical_type, buf[d_off:d_off + d_len], d_n)
        pages = []
        for (nv, dl, bw, values_off, body_off, body_len, _np_, segs) in \
                raw_pages:
            page_bytes = buf[body_off:body_off + body_len]
            pages.append((nv, dl, bw, page_bytes, values_off, segs))
        return ChunkPages(col.physical_type, dict_vals, pages, col.num_values)

    span.set(native=0)
    pos = 0
    dict_vals = None
    pages = []
    values_seen = 0
    while pos < len(buf) and values_seen < col.num_values:
        ph = parse_page_header(buf, pos)
        body = pos + ph.header_len
        raw_body = buf[body:body + ph.compressed_size]
        if ph.page_type == 2:                       # dictionary page
            page_body = (raw_body if dec is None else
                         bytes(dec.decompress(raw_body,
                                              ph.uncompressed_size)))
            dict_vals = _decode_plain_dictionary(
                col.physical_type, page_body, ph.num_values)
        elif ph.page_type == 0:                     # data page v1
            if ph.encoding not in (8, 2):           # RLE_DICT / PLAIN_DICT
                raise OutOfScope("page", f"page encoding {ph.encoding}")
            page_body = (raw_body if dec is None else
                         bytes(dec.decompress(raw_body,
                                              ph.uncompressed_size)))
            # work PAGE-relative so RleSegment offsets index page_bytes
            page_bytes = page_body
            p = 0
            if max_def:
                # optional-field def levels: RLE with 4-byte length prefix
                (dl_len,) = struct.unpack_from("<I", page_bytes, p)
                p += 4
                def_levels = decode_rle_host(page_bytes, p, p + dl_len, 1,
                                             ph.num_values)
                p += dl_len
            else:
                def_levels = np.ones(ph.num_values, dtype=np.int32)
            bw = page_bytes[p]
            p += 1
            n_present = int(def_levels.sum())
            segs = scan_hybrid_native(page_bytes, p, len(page_bytes), bw,
                                      n_present)
            pages.append((ph.num_values, def_levels, bw, page_bytes,
                          p - 1, segs))
            values_seen += ph.num_values
        elif ph.page_type == 3:                     # data page v2
            if ph.encoding not in (8, 2):
                raise OutOfScope("page", f"page encoding {ph.encoding}")
            if ph.rep_len:
                raise OutOfScope("nested", "repeated (nested) v2 page")
            # levels ride UNCOMPRESSED ahead of the (optionally compressed)
            # values section; def levels have NO length prefix in v2
            levels = raw_body[:ph.def_len]
            data = raw_body[ph.def_len:]
            if dec is not None and ph.v2_compressed:
                data = bytes(dec.decompress(
                    data, ph.uncompressed_size - ph.def_len - ph.rep_len))
            if max_def and ph.def_len:
                def_levels = decode_rle_host(levels, 0, ph.def_len, 1,
                                             ph.num_values)
            else:
                def_levels = np.ones(ph.num_values, dtype=np.int32)
            bw = data[0]
            n_present = int(def_levels.sum())
            segs = scan_hybrid_native(data, 1, len(data), bw, n_present)
            pages.append((ph.num_values, def_levels, bw, data, 0, segs))
            values_seen += ph.num_values
        else:
            raise OutOfScope("page", f"page type {ph.page_type}")
        pos = body + ph.compressed_size
    if dict_vals is None:
        raise OutOfScope("no_dictionary", "no dictionary page")
    return ChunkPages(col.physical_type, dict_vals, pages, col.num_values)


# -- chunk → engine vector ----------------------------------------------------

def _merge_packed_pages(pages: ChunkPages) -> ChunkPages:
    """Fold a chunk's data pages into ONE page when their packed index bytes
    can simply be concatenated: every page all bit-packed, one bit width, and
    every page but the last filling its packed bytes exactly (a bit-packed
    run holds whole 8-value groups, so it then ends on a value boundary).
    Writers cut a row group's chunk into many pages (pyarrow: 20,000 rows
    each, ~38 per TPC-H SF 1 chunk); folded, the chunk rides the
    single-page decode program, whose expansion a consumer can fuse into
    itself (``encoded``). A chunk that does not fold takes the segment-table
    program."""
    if len(pages.index_segments) < 2:
        return pages
    bw0 = pages.index_segments[0][2]
    last = len(pages.index_segments) - 1
    parts, levels, n_values, n_present = [], [], 0, 0
    for i, (nv, dl, bw, page_bytes, _off, segs) in enumerate(
            pages.index_segments):
        if bw != bw0 or not len(segs) or not segs[:, RUN_KIND].all():
            return pages
        packed = _packed_bytes(page_bytes, segs)
        present = int(dl.sum())
        if i < last and len(packed) * 8 != present * bw:
            return pages
        parts.append(packed)
        levels.append(dl)
        n_values += nv
        n_present += present
    packed = b"".join(parts)
    seg = np.array([[1, n_present, 0, 0, len(packed)]], np.int64)
    page = (n_values, np.concatenate(levels), bw0, packed, 0, seg)
    return ChunkPages(pages.physical_type, pages.dict_values, [page],
                      pages.num_values)


def _packed_bytes(page_bytes: bytes, segs: np.ndarray) -> bytes:
    """The payload of a page's packed segments, concatenated. Segments each
    hold whole 8-value groups at byte boundaries: concatenating their BYTES
    preserves bit alignment."""
    return b"".join(page_bytes[o:o + n] for k, o, n in
                    segs[:, (RUN_KIND, RUN_OFF, RUN_LEN)].tolist() if k)


def chunk_to_device(pages: ChunkPages, spark_type, capacity: int,
                    encoded: bool = False, span=tracing.NO_SPAN):
    """Decode a parsed chunk into a TpuColumnVector: host preparation, the
    upload of its buffers, and ONE fused program, whatever its hybrid
    segments are. Which program is read from the chunk: every segment
    bit-packed at one width (after `_merge_packed_pages`) → the single-page
    decode, which ``encoded`` may defer into the consumer; RLE runs, or pages
    of different bit widths → the segment-table decode, always dense.
    ``span`` is the caller's ``scan.column`` span: it learns which of the two
    the chunk took and what the chunk was made of.

    The host preparation and the upload are one ``scan.stage`` span (counts
    ``values``, and ``arrays`` put with their ``bytes``), which closes where
    the decode is dispatched, or where ``encoded`` defers it.

    Cached via the fuse kernel cache like every exec stage: one program per
    spec (bit width or widest width, shape buckets, output type). Under
    ``encoded`` a single-page chunk's buffers are wrapped in an
    EncodedColumnVector and its first consumer runs the same decode body,
    fused into its own program when it can, standalone otherwise."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.vector import TpuColumnVector
    from spark_rapids_tpu.ops import parquet_decode as PD
    from spark_rapids_tpu.runtime import fuse

    with tracing.span("scan.stage", values=pages.num_values) as stage:
        if pages.physical_type == "BYTE_ARRAY":
            # parquet dictionary == the engine's string dictionary, sorted
            # for order-preserving codes (columnar/arrow.py design)
            from spark_rapids_tpu.ops.strings import sorted_dict_and_rank
            sorted_dict, dict_host = sorted_dict_and_rank(pages.dict_values)
        else:                               # dict_host: parquet idx -> value
            sorted_dict, dict_host = None, np.asarray(pages.dict_values)

        if span:
            segs = sum(len(p[5]) for p in pages.index_segments)
            packed = sum(int(p[5][:, RUN_KIND].sum())
                         for p in pages.index_segments)
            span.set(pages=len(pages.index_segments), packed=packed,
                     rle=segs - packed, encoded_bytes=sum(
                         len(p[3]) for p in pages.index_segments))
        pages = _merge_packed_pages(pages)
        span.set(path="fused")
        segs = pages.index_segments[0][5] \
            if len(pages.index_segments) == 1 else None
        packed_page = segs is not None and len(segs) > 0 \
            and bool(segs[:, RUN_KIND].all())
        if packed_page:
            (num_values, def_levels, bw, page_bytes, _off, segs) = \
                pages.index_segments[0]
            span.set(decode="packed", segments=1)
            spec, st, args = _page_spec_and_args(
                _packed_bytes(page_bytes, segs), bw, def_levels,
                jnp.asarray(dict_host), num_values, capacity, pages,
                spark_type)
        else:
            spec, st, args = _runs_spec_and_args(
                pages, dict_host, capacity, spark_type,
                sorted_dict is not None, span)
        if stage:
            stage.set(arrays=len(args), bytes=sum(a.nbytes for a in args))
    if packed_page and encoded:
        from spark_rapids_tpu.columnar.encoded import (EncodedCol,
                                                       EncodedColumnVector)
        return EncodedColumnVector(EncodedCol(*args, spec, st, sorted_dict))
    if packed_page:
        key, name, decode = (("pq_page_decode", spec), "ParquetScan.decode",
                             PD.decode_page_cols)
    else:
        key, name, decode = (("pq_runs_decode", spec),
                             "ParquetScan.decode_runs", PD.decode_runs_cols)

    def build():
        def kernel(*operands):
            return decode(spec, *operands)
        return kernel

    v, m = fuse.call_fused(key, name, build, args, lambda: build()(*args))
    cv = TpuColumnVector(st, v, m)
    return cv.with_dictionary(sorted_dict) if spec.is_string else cv


def _segment_table(pages: ChunkPages):
    """A chunk's parsed pages → (uint8 packed bytes of every packed segment,
    concatenated; int32 (4, rows) segment table as
    ops/parquet_decode.unpack_runs_device reads it; widest bit width).
    A segment whose row would repeat the row before it — a packed run whose
    bytes continue the previous run's exactly, as a writer's 504-value runs
    do — adds none."""
    per_page = [p[5] for p in pages.index_segments]
    segs = (np.concatenate(per_page) if per_page
            else np.zeros((0, 5), np.int64))
    n_segs = [len(s) for s in per_page]
    bw = np.repeat(np.array([p[2] for p in pages.index_segments], np.int64),
                   n_segs)
    base = np.cumsum([0] + [len(p[3]) for p in pages.index_segments])[:-1]
    off = np.repeat(base, n_segs) + segs[:, RUN_OFF]      # in all pages' bytes
    live = segs[:, RUN_COUNT] > 0
    segs, bw, off = segs[live], bw[live], off[live]

    packed = (segs[:, RUN_KIND] == 1) & (bw > 0)
    count = segs[:, RUN_COUNT]
    start = np.cumsum(count) - count
    nbytes = np.where(packed, segs[:, RUN_LEN], 0)
    before = np.cumsum(nbytes) - nbytes
    rows = np.stack([start,
                     np.where(packed, bw, 0),
                     np.where(packed, before * 8 - start * bw, 0),
                     np.where(packed, 0, segs[:, RUN_VALUE])])
    keep = np.ones(rows.shape[1], bool)
    keep[1:] = (rows[1:, 1:] != rows[1:, :-1]).any(axis=0)
    # the bytes: byte j of the output is byte (j - before + off) of its run
    every = np.frombuffer(b"".join(p[3] for p in pages.index_segments),
                          np.uint8)
    take = np.arange(int(nbytes.sum())) + np.repeat(off - before, nbytes)
    # an RLE value is an unsigned bw-bit pattern; the table carries its bits
    table = rows[:, keep].astype(np.uint32).view(np.int32)
    return every[take], table, int(rows[1].max(initial=0))


def _type_facts(pages: ChunkPages, spark_type):
    """(spark type, decoded dtype, canonical default) of a chunk's column."""
    import jax.numpy as jnp
    from spark_rapids_tpu import types as T
    if pages.physical_type == "BYTE_ARRAY":
        return T.STRING, jnp.dtype(jnp.int32), 0
    np_to_spark = {"INT32": T.INT, "INT64": T.LONG,
                   "FLOAT": T.FLOAT, "DOUBLE": T.DOUBLE}
    st = spark_type or np_to_spark[pages.physical_type]
    return st, jnp.dtype(st.jnp_dtype), st.default_value()


def _padded(a: np.ndarray, cap: int) -> np.ndarray:
    """`a` along its last axis, padded with zeros (or cut) to `cap`."""
    out = np.zeros(a.shape[:-1] + (cap,), a.dtype)
    n = min(a.shape[-1], cap)
    out[..., :n] = a[..., :n]
    return out


def _runs_spec_and_args(pages: ChunkPages, dict_host, capacity: int,
                        spark_type, is_string: bool, span):
    """Host prep of the segment-table decode, for a chunk with RLE runs or
    pages of different bit widths: static EncodedRunsSpec (widest bit width,
    shape buckets, output type) + the device argument tuple (packed, table,
    dict, def-levels, n) of ops/parquet_decode.decode_runs_cols (segment
    lookup → per-element bit-unpack → dictionary gather → definition-level
    spread → canonical nulls). Table, bytes and dictionary pad to their
    buckets so chunks share programs."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.vector import bucket_capacity
    from spark_rapids_tpu.ops import parquet_decode as PD

    packed, table, max_bw = _segment_table(pages)
    def_levels = (np.concatenate([p[1] for p in pages.index_segments])
                  if pages.index_segments else np.zeros(0, np.int32))
    n_present = int(def_levels.sum())
    st, want, default = _type_facts(pages, spark_type)
    pcap = bucket_capacity(max(n_present, 1))
    scap = bucket_capacity(table.shape[1])
    spec = PD.EncodedRunsSpec(
        max_bw, scap, pcap, bucket_capacity(max(len(packed), 1)), capacity,
        str(want), is_string, default)
    span.set(decode="runs", segments=table.shape[1])
    table_h = _padded(table, scap)
    # rows past the last segment start beyond every position, each at its own
    table_h[PD.SEG_START, table.shape[1]:] = \
        pcap + np.arange(scap - table.shape[1], dtype=np.int32)
    n = min(pages.num_values, capacity)
    args = (jnp.asarray(_padded(packed, spec.bcap)),
            jnp.asarray(table_h),
            jnp.asarray(_padded(dict_host,
                                bucket_capacity(len(dict_host)))),
            jnp.asarray(_padded(def_levels.astype(bool), capacity)),
            # 0-d array: a Python or NumPy scalar is converted by an eager
            # program of its own
            jnp.asarray(np.asarray(n, np.int32)))
    return spec, st, args


def _page_spec_and_args(packed: bytes, bw: int, def_levels, dict_dev,
                        num_values: int, capacity: int, pages, spark_type):
    """Host prep of the single-page decode, shared by the standalone fused
    decode and the encoded-upload vector (ops/parquet_decode.decode_page_cols:
    bit-unpack → dictionary gather → definition-level spread → canonical
    nulls): static EncodedPageSpec + the device argument tuple
    (packed, dict, def-levels, n_present, n). The ONE place page bytes become
    device buffers, so both paths upload identical payloads."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.vector import bucket_capacity
    from spark_rapids_tpu.ops import parquet_decode as PD

    n_present = int(def_levels.sum())
    pcap = max(bucket_capacity(max(n_present, 1)), 8)
    bcap = max(bucket_capacity(max(len(packed), 1)), 8)
    st, want, default = _type_facts(pages, spark_type)
    # the present count is an operand, not part of the spec: pages that
    # differ in it alone share one compiled program
    spec = PD.EncodedPageSpec(bw, pcap, bcap, capacity, str(want),
                              pages.physical_type == "BYTE_ARRAY", default)
    packed_in = jnp.asarray(_padded(np.frombuffer(packed, np.uint8), bcap))
    n = min(num_values, pages.num_values, capacity)
    args = (packed_in, dict_dev,
            jnp.asarray(_padded(def_levels.astype(bool), capacity)),
            # 0-d arrays: a Python or NumPy scalar is converted by an eager
            # program of its own
            jnp.asarray(np.asarray(n_present, np.int32)),
            jnp.asarray(np.asarray(n, np.int32)))
    return spec, st, args


def read_row_group_device(path: str, row_group: int, schema,
                          columns: list[str] | None = None, pf=None,
                          encoded: bool = False):
    """Read one row group entirely via the device decode path; out-of-scope
    column chunks (compressed, non-dictionary, nested) fall back to arrow
    PER COLUMN (reference falls back per-file; per-column is strictly
    finer). Pass `pf` to reuse one parsed footer across row groups.

    Every column's H2D payload is metered on the movement ledger with a
    per-path site (scan.encoded / scan.device / scan.fallback), so the
    encoded-upload win shows up as fewer h2d bytes, not just wall clock."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.encoded import EncodedColumnVector
    from spark_rapids_tpu.columnar.vector import bucket_capacity
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.arrow import array_to_device
    from spark_rapids_tpu.runtime import movement as _MV

    if pf is None:
        import pyarrow.parquet as pq
        pf = pq.ParquetFile(path)
    md = pf.metadata
    # leaf paths: a flat column's path IS its name; nested leaves look like
    # "l.list.element" and must never match a top-level name
    leaf_of = {}
    for i in range(md.num_columns):
        path_in_schema = md.schema.column(i).path
        if "." not in path_in_schema:
            leaf_of[path_in_schema] = i
    want = columns if columns is not None else         [f.name for f in (schema.fields if schema is not None else [])] or         list(leaf_of)
    n_rows = md.row_group(row_group).num_rows
    cap = bucket_capacity(max(n_rows, 1))
    cols, fields = [], []
    for name in want:
        sf = schema[name] if schema is not None else None
        # one span a column chunk, with the path it took: fused (one
        # program, of which `decode` says which) or fallback (pyarrow); its
        # children scan.read, then scan.stage or scan.fallback, hold the
        # chunk's host work
        with tracing.span("scan.column", column=name) as sp:
            try:
                if name not in leaf_of:
                    raise OutOfScope("nested", f"nested column {name}")
                pages = read_chunk_pages(path, row_group, leaf_of[name],
                                         md=md)
                cv = chunk_to_device(pages, sf.data_type if sf else None,
                                     cap, encoded=encoded, span=sp)
                if isinstance(cv, EncodedColumnVector):
                    _MV.record_h2d(cv.encoded_payload_bytes(),
                                   site="scan.encoded")
                else:
                    _MV.record_h2d(cv.device_memory_size(),
                                   site="scan.device")
            except NotImplementedError as e:
                # the page walk's own parsers (thrift headers, hybrid runs)
                # raise it plainly: a page it cannot read
                with tracing.span("scan.fallback", rows=n_rows,
                                  reason=getattr(e, "reason", "page")) as fb:
                    arr = pf.read_row_group(row_group,
                                            columns=[name]).column(0)
                    cv = array_to_device(arr, sf.data_type if sf else None,
                                         cap)
                    if fb:
                        fb.set(bytes=cv.device_memory_size())
                _MV.record_h2d(cv.device_memory_size(), site="scan.fallback")
                if sp:
                    sp.set(path="fallback", encoded_bytes=(
                        md.row_group(row_group).column(leaf_of[name])
                        .total_compressed_size if name in leaf_of else 0))
            if sp:
                sp.set(decoded_bytes=cv.device_memory_size())
        cols.append(cv)
        fields.append(sf or T.StructField(name, cols[-1].dtype, True))
    return ColumnarBatch(cols, n_rows, T.StructType(fields))
