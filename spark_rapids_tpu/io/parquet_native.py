"""Native parquet page access: thrift metadata + page splitting on host, bulk
index decode on device (stage-one device decode, SURVEY.md §7).

Reference: GpuParquetScan.scala:1235 hands raw column-chunk bytes to
`Table.readParquet` so the GPU does page decode. TPU realization: the THRIFT
page headers and RLE run STRUCTURE are metadata (bytes to kilobytes — parsed
on host, like string dictionaries), while the BULK bytes — bit-packed
dictionary indices and definition levels — go to the device, where one jitted
program unpacks bits and gathers dictionary values (ops/parquet_decode.py).
The parquet dictionary page maps 1:1 onto the engine's own dictionary-encoded
string representation, so a string column never materializes per-row bytes.

Scope: UNCOMPRESSED / SNAPPY / GZIP / ZSTD chunks (compressed page bodies
decompress on host through arrow's C codecs — stage 1.5; the reference uses
nvcomp on GPU), RLE_DICTIONARY-encoded data pages (v1), flat schemas,
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
                                        #   def_levels np | None,
                                        #   bit_width, packed bytes | np idx)
    num_values: int


_FIXED = {"INT32": ("<i4", 4), "INT64": ("<i8", 8),
          "FLOAT": ("<f4", 4), "DOUBLE": ("<f8", 8)}


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
    raise NotImplementedError(physical_type)


def read_chunk_pages(path: str, row_group: int, column: int,
                     md=None) -> ChunkPages:
    """Parse one dictionary-encoded column chunk (UNCOMPRESSED, or
    SNAPPY/GZIP/ZSTD with page bodies decompressed on host) into its raw
    device-ready pieces. Raises NotImplementedError when out of scope
    (caller falls back to arrow decode). `md` avoids re-parsing the
    footer per chunk (wide-table footers are MBs)."""
    if md is None:
        import pyarrow.parquet as pq
        md = pq.ParquetFile(path).metadata
    col = md.row_group(row_group).column(column)
    dec = None
    if col.compression != "UNCOMPRESSED":
        # stage 1.5: page bodies decompress on host via arrow's C codecs
        # (the reference decompresses on GPU through nvcomp; the DECODE —
        # the bulk bit work — still runs on device either way)
        import pyarrow as pa
        if col.compression not in ("SNAPPY", "GZIP", "ZSTD"):
            raise NotImplementedError(f"codec {col.compression}")
        try:
            dec = pa.Codec(col.compression.lower())
        except Exception as e:
            raise NotImplementedError(f"codec {col.compression}: {e}")
    if "RLE_DICTIONARY" not in col.encodings and \
            "PLAIN_DICTIONARY" not in col.encodings:
        raise NotImplementedError(f"encodings {col.encodings}")
    if col.physical_type not in _FIXED and \
            col.physical_type != "BYTE_ARRAY":
        raise NotImplementedError(f"type {col.physical_type}")

    max_def = md.schema.column(column).max_definition_level
    if md.schema.column(column).max_repetition_level:
        raise NotImplementedError("nested (repeated) columns")

    with open(path, "rb") as f:
        start = col.dictionary_page_offset or col.data_page_offset
        f.seek(start)
        buf = f.read(col.total_compressed_size)

    # fast path: one native C call scans the whole chunk (thrift headers,
    # def-level RLE decode, hybrid segmentation — native/parquet_host.cpp);
    # the Python loop below is the executable spec, the path for chunks the
    # native scanner declines, and the compressed-chunk path (bodies must
    # decompress before scanning). A scanner that cannot be built or loaded
    # (NativeBuildError, OSError) is an error: the toolchain is part of
    # this installation.
    raw_pages = None
    if dec is None:  # compressed bodies must decompress before scanning
        from spark_rapids_tpu.native import scan_chunk_native
        try:
            raw_pages, dict_info = scan_chunk_native(buf, col.num_values,
                                                     max_def)
        except NotImplementedError:
            pass  # e.g. v2 data pages: the Python parser below handles them
    if raw_pages is not None:
        d_off, d_len, d_n = dict_info
        dict_vals = _decode_plain_dictionary(
            col.physical_type, buf[d_off:d_off + d_len], d_n)
        pages = []
        for (nv, dl, bw, values_off, body_off, body_len, _np_, rs) in raw_pages:
            page_bytes = buf[body_off:body_off + body_len]
            segs = [RleSegment("packed" if k == 1 else "rle", c, v, bo, bl)
                    for (k, c, v, bo, bl) in rs]
            pages.append((nv, dl, bw, page_bytes, values_off, segs))
        return ChunkPages(col.physical_type, dict_vals, pages, col.num_values)

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
                raise NotImplementedError(f"page encoding {ph.encoding}")
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
            segs = parse_rle_hybrid(page_bytes, p, len(page_bytes), bw,
                                    n_present)
            pages.append((ph.num_values, def_levels, bw, page_bytes,
                          p - 1, segs))
            values_seen += ph.num_values
        elif ph.page_type == 3:                     # data page v2
            if ph.encoding not in (8, 2):
                raise NotImplementedError(f"page encoding {ph.encoding}")
            if ph.rep_len:
                raise NotImplementedError("repeated (nested) v2 page")
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
            segs = parse_rle_hybrid(data, 1, len(data), bw, n_present)
            pages.append((ph.num_values, def_levels, bw, data, 0, segs))
            values_seen += ph.num_values
        else:
            raise NotImplementedError(f"page type {ph.page_type}")
        pos = body + ph.compressed_size
    if dict_vals is None:
        raise NotImplementedError("no dictionary page")
    return ChunkPages(col.physical_type, dict_vals, pages, col.num_values)


# -- chunk → engine vector ----------------------------------------------------

def _merge_packed_pages(pages: ChunkPages) -> ChunkPages:
    """Fold a chunk's data pages into ONE page when their packed index bytes
    can simply be concatenated: every page all bit-packed, one bit width, and
    every page but the last filling its packed bytes exactly (a bit-packed
    run holds whole 8-value groups, so it then ends on a value boundary).
    Writers cut a row group's chunk into many pages (pyarrow: 20,000 rows
    each, ~38 per TPC-H SF 1 chunk); folded, the chunk rides the single
    fused decode program instead of one eager pipeline per page — on the
    chip that per-page pipeline re-lowered the Pallas unpack kernel for every
    page of every run (~27 s per scan batch, hot or cold)."""
    if len(pages.index_segments) < 2:
        return pages
    bw0 = pages.index_segments[0][2]
    last = len(pages.index_segments) - 1
    parts, levels, n_values, n_present = [], [], 0, 0
    for i, (nv, dl, bw, page_bytes, _off, segs) in enumerate(
            pages.index_segments):
        if bw != bw0 or not segs or any(s.kind != "packed" for s in segs):
            return pages
        packed = b"".join(page_bytes[s.byte_off:s.byte_off + s.byte_len]
                          for s in segs)
        present = int(dl.sum())
        if i < last and len(packed) * 8 != present * bw:
            return pages
        parts.append(packed)
        levels.append(dl)
        n_values += nv
        n_present += present
    packed = b"".join(parts)
    seg = RleSegment("packed", n_present, 0, 0, len(packed))
    page = (n_values, np.concatenate(levels), bw0, packed, 0, [seg])
    return ChunkPages(pages.physical_type, pages.dict_values, [page],
                      pages.num_values)


def chunk_to_device(pages: ChunkPages, spark_type, capacity: int,
                    encoded: bool = False, span=tracing.NO_SPAN):
    """Decode a parsed chunk into a TpuColumnVector. The common fast path
    (every hybrid segment bit-packed) unpacks indices ON DEVICE; pages with
    mixed RLE runs fall back to the host hybrid decode, keeping the
    dictionary gather on device either way. ``span`` is the caller's
    ``scan.column`` span: it learns which of the two paths the chunk took
    and what the chunk was made of."""
    import jax.numpy as jnp
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.vector import TpuColumnVector
    from spark_rapids_tpu.ops import parquet_decode as PD

    is_string = pages.physical_type == "BYTE_ARRAY"
    sorted_dict = None
    if is_string:
        # parquet dictionary == the engine's string dictionary, sorted for
        # order-preserving codes (columnar/arrow.py design)
        from spark_rapids_tpu.ops.strings import sorted_dict_and_rank
        sorted_dict, rank = sorted_dict_and_rank(pages.dict_values)
        dict_dev = jnp.asarray(rank)        # parquet idx -> sorted code
    else:
        dict_dev = jnp.asarray(np.asarray(pages.dict_values))
    from spark_rapids_tpu.columnar.vector import bucket_capacity

    if span:
        segs = [s for p in pages.index_segments for s in p[5]]
        span.set(pages=len(pages.index_segments),
                 packed=sum(s.kind == "packed" for s in segs),
                 rle=sum(s.kind != "packed" for s in segs),
                 encoded_bytes=sum(len(p[3]) for p in pages.index_segments))
    pages = _merge_packed_pages(pages)
    # fast path: ONE data page, all-packed index segments → a single fused
    # program (unpack + dict gather + null spread + canonicalize). The eager
    # per-page pipeline below cost ~25 XLA dispatches per chunk — at TPC-H
    # scan width that dominated hot-query wall time on XLA:CPU.
    if len(pages.index_segments) == 1:
        (num_values, def_levels, bw, page_bytes, values_off, segs) = \
            pages.index_segments[0]
        if segs and all(s.kind == "packed" for s in segs):
            packed = b"".join(page_bytes[s.byte_off:s.byte_off + s.byte_len]
                              for s in segs)
            span.set(path="fused")
            return _decode_single_page_fused(
                packed, bw, def_levels, dict_dev, num_values, capacity,
                pages, spark_type, sorted_dict, encoded=encoded)

    # page by page: some twenty-five eager device calls a page
    span.set(path="pages")
    all_vals, all_valid = [], []
    for (num_values, def_levels, bw, page_bytes, values_off, segs) in \
            pages.index_segments:
        with tracing.span("scan.page", values=num_values):
            pcap = bucket_capacity(max(num_values, 1))
            n_present = int(def_levels.sum())
            if segs and all(s.kind == "packed" for s in segs):
                # segments each hold whole 8-value groups at byte boundaries:
                # concatenating their BYTES preserves bit alignment
                packed = b"".join(
                    page_bytes[s.byte_off:s.byte_off + s.byte_len]
                    for s in segs)
                vals, valid = PD.decode_dictionary_page(
                    np.frombuffer(packed, np.uint8), bw, n_present,
                    def_levels, dict_dev, pcap)
            else:
                idx = decode_rle_host(page_bytes, values_off + 1,
                                      len(page_bytes), bw, n_present) \
                    if segs else np.zeros(0, np.int32)
                nd = int(dict_dev.shape[0])
                idx_d = jnp.zeros((pcap,), jnp.int32).at[:len(idx)].set(
                    jnp.asarray(np.clip(idx, 0, max(nd - 1, 0))))
                # an all-null page may carry an EMPTY dictionary — nothing to
                # gather, every slot is the canonical default
                present = dict_dev[idx_d] if nd else jnp.zeros((pcap,),
                                                               dict_dev.dtype)
                dl = jnp.zeros((pcap,), jnp.bool_).at[:len(def_levels)].set(
                    jnp.asarray(def_levels.astype(bool)))
                vals, valid = PD.expand_present_to_rows(present, dl, pcap)
        all_vals.append(vals[:num_values])
        all_valid.append(valid[:num_values])

    vals = jnp.concatenate(all_vals) if len(all_vals) > 1 else all_vals[0]
    valid = jnp.concatenate(all_valid) if len(all_valid) > 1 else all_valid[0]
    n = pages.num_values
    out_v = jnp.zeros((capacity,), vals.dtype).at[:n].set(vals[:n])
    out_m = jnp.zeros((capacity,), jnp.bool_).at[:n].set(valid[:n])

    if is_string:
        # canonical-null invariant (columnar/vector.py:10): invalid slots
        # hold code 0, never rank-gather residue — group-by compares raw
        # codes (ops/grouping.py)
        codes = jnp.where(out_m, out_v.astype(jnp.int32), 0)
        cv = TpuColumnVector(T.STRING, codes, out_m)
        return cv.with_dictionary(sorted_dict)
    np_to_spark = {"INT32": T.INT, "INT64": T.LONG,
                   "FLOAT": T.FLOAT, "DOUBLE": T.DOUBLE}
    st = spark_type or np_to_spark[pages.physical_type]
    want = st.jnp_dtype
    if out_v.dtype != jnp.dtype(want):
        out_v = out_v.astype(want)
    default = jnp.asarray(st.default_value(), out_v.dtype)
    out_v = jnp.where(out_m, out_v, default)
    return TpuColumnVector(st, out_v, out_m)


def _page_spec_and_args(packed: bytes, bw: int, def_levels, dict_dev,
                        num_values: int, capacity: int, pages, spark_type):
    """Host prep shared by the standalone fused decode and the encoded-upload
    vector: static EncodedPageSpec + the device argument tuple
    (packed, dict, def-levels, n_present, n). The ONE place page bytes become
    device buffers, so both paths upload identical payloads."""
    import jax.numpy as jnp
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.vector import bucket_capacity
    from spark_rapids_tpu.ops import parquet_decode as PD
    from spark_rapids_tpu.ops import pallas_kernels as PK

    is_string = pages.physical_type == "BYTE_ARRAY"
    n_present = int(def_levels.sum())
    pcap = max(bucket_capacity(max(n_present, 1)), 8)
    bcap = max(bucket_capacity(max(len(packed), 1)), 8)
    use_pallas = PK.should_use("bitunpack")     # probe OUTSIDE the traced program

    np_to_spark = {"INT32": T.INT, "INT64": T.LONG,
                   "FLOAT": T.FLOAT, "DOUBLE": T.DOUBLE}
    st = T.STRING if is_string else (spark_type
                                     or np_to_spark[pages.physical_type])
    want = jnp.dtype(jnp.int32) if is_string else jnp.dtype(st.jnp_dtype)
    default = 0 if is_string else st.default_value()
    # n_present is only STATIC under pallas (tile shapes); zeroing it
    # otherwise keeps the non-pallas compile cache shared across present
    # counts, exactly like the pre-spec key did
    spec = PD.EncodedPageSpec(bw, pcap, bcap, capacity, str(want), is_string,
                              default, use_pallas,
                              n_present if use_pallas else 0)
    if use_pallas:
        words = PK.bytes_to_words_u32(np.frombuffer(packed, np.uint8))
        packed_in = jnp.asarray(words)
    else:
        ph = np.zeros(bcap, np.uint8)
        ph[:len(packed)] = np.frombuffer(packed, np.uint8)
        packed_in = jnp.asarray(ph)
    dh = np.zeros(capacity, bool)
    nd_lv = min(len(def_levels), capacity)
    dh[:nd_lv] = def_levels[:nd_lv].astype(bool)
    n = min(num_values, pages.num_values, capacity)
    args = (packed_in, dict_dev, jnp.asarray(dh),
            jnp.asarray(n_present, jnp.int32), jnp.asarray(n, jnp.int32))
    return spec, st, args


def _decode_single_page_fused(packed: bytes, bw: int, def_levels, dict_dev,
                              num_values: int, capacity: int, pages,
                              spark_type, sorted_dict, encoded: bool = False):
    """One jitted program per (bit width, shape bucket, output type):
    bit-unpack → dictionary gather → definition-level spread → canonical
    nulls (ops/parquet_decode.decode_page_cols). Cached via the fuse kernel
    cache like every exec stage. Under ``encoded`` the expansion is DEFERRED:
    the encoded buffers are wrapped in an EncodedColumnVector and the first
    consumer runs the same decode body — fused into its own program when it
    can, standalone otherwise."""
    from spark_rapids_tpu.columnar.vector import TpuColumnVector
    from spark_rapids_tpu.columnar.encoded import (EncodedCol,
                                                   EncodedColumnVector)
    from spark_rapids_tpu.ops import parquet_decode as PD
    from spark_rapids_tpu.runtime import fuse

    spec, st, args = _page_spec_and_args(packed, bw, def_levels, dict_dev,
                                         num_values, capacity, pages,
                                         spark_type)
    if encoded:
        enc = EncodedCol(*args, spec, st,
                         sorted_dict if spec.is_string else None)
        return EncodedColumnVector(enc)

    def build():
        def kernel(packed_d, dict_d, dl_d, n_present_t, n_t):
            return PD.decode_page_cols(spec, packed_d, dict_d, dl_d,
                                       n_present_t, n_t)
        return kernel

    key = ("pq_page_decode", spec)
    v, m = fuse.call_fused(key, "ParquetScan.decode", build, args,
                           lambda: build()(*args))
    cv = TpuColumnVector(st, v, m)
    return cv.with_dictionary(sorted_dict) if spec.is_string else cv


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
        # program), pages (page by page, eager) or fallback (pyarrow)
        with tracing.span("scan.column", column=name) as sp:
            try:
                if name not in leaf_of:
                    raise NotImplementedError(f"nested column {name}")
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
            except NotImplementedError:
                arr = pf.read_row_group(row_group, columns=[name]).column(0)
                cv = array_to_device(arr, sf.data_type if sf else None, cap)
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
