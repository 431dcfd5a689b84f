"""Device parquet decode kernels — bit-unpack + dictionary gather in one jit.

Reference: GpuParquetScan.scala:1235 (`Table.readParquet` decodes raw chunk
bytes on the GPU). TPU stage one (SURVEY.md §7): the bulk bytes of a
dictionary-encoded column are bit-packed indices; one jitted program unpacks
bits with shifts/masks (VPU-friendly, no scalar loops) and gathers dictionary
values, then scatters present values over the null layout via a rank gather.
Static shapes throughout: byte buffers pad to the capacity bucket.
"""

from __future__ import annotations

import typing

import jax.numpy as jnp
from spark_rapids_tpu.ops.windowing import cumsum


class EncodedPageSpec(typing.NamedTuple):
    """Static shape/type facts of one encoded data page — everything the
    traceable decode prologue (`decode_page_cols`) closes over. Hashable, so
    it rides fuse-cache keys and pytree aux data directly; two pages with the
    same spec share one compiled program regardless of their byte content."""
    bit_width: int
    pcap: int          # present-value capacity bucket
    bcap: int          # packed-byte capacity bucket
    capacity: int      # output row capacity bucket
    want: str          # decoded value dtype name (int32 codes for strings)
    is_string: bool
    default: object    # canonical fill for invalid slots


def unpack_bits_device(packed: jnp.ndarray, bit_width: int, n: int,
                       capacity: int) -> jnp.ndarray:
    """(bytes,) uint8 → (capacity,) int32 of `n` bit-packed values.

    value i occupies bits [i*bw, (i+1)*bw): gather the (up to) 5 covering
    bytes, combine little-endian into an int64 window, shift and mask —
    pure vector ops, one fused XLA kernel."""
    idx = jnp.arange(capacity, dtype=jnp.int32)
    bit0 = idx * bit_width
    byte0 = bit0 >> 3
    shift = (bit0 & 7).astype(jnp.int64)
    nbytes = packed.shape[0]
    window = jnp.zeros((capacity,), jnp.int64)
    # a bw-bit value starting at any bit offset 0..7 spans ceil((bw+7)/8)
    # bytes — at most 5 for bw<=32
    for k in range((bit_width + 14) // 8):
        b = packed[jnp.clip(byte0 + k, 0, nbytes - 1)].astype(jnp.int64)
        window = window | (b << (8 * k))
    mask = jnp.int64((1 << bit_width) - 1)
    vals = (window >> shift) & mask
    return jnp.where(idx < n, vals.astype(jnp.int32), 0)


def expand_present_to_rows(present_vals: jnp.ndarray,
                           def_levels: jnp.ndarray,
                           capacity: int):
    """Parquet stores values only for non-null slots; spread them over the
    full row layout: row j takes present value rank(j) where rank is the
    prefix count of set definition levels (a gather, not a scatter)."""
    ranks = cumsum(def_levels.astype(jnp.int32)) - 1
    safe = jnp.clip(ranks, 0, capacity - 1)
    vals = present_vals[safe]
    valid = def_levels.astype(jnp.bool_)
    return vals, valid


class EncodedRunsSpec(typing.NamedTuple):
    """Static facts of one chunk decoded from its segment table
    (`decode_runs_cols`): the hybrid index streams of all its pages, RLE runs
    and bit-packed runs of any widths. Hashable like EncodedPageSpec; two
    chunks with the same spec share one compiled program whatever their
    segments are."""
    max_bw: int        # widest page: bounds the bytes that cover a value
    scap: int          # segment-table capacity bucket
    pcap: int          # present-value capacity bucket
    bcap: int          # packed-byte capacity bucket
    capacity: int      # output row capacity bucket
    want: str          # decoded value dtype name (int32 codes for strings)
    is_string: bool
    default: object    # canonical fill for invalid slots


# rows of the segment table (int32 (4, scap)), one column a segment
SEG_START, SEG_BW, SEG_BIT0, SEG_VALUE = range(4)


def unpack_runs_device(packed: jnp.ndarray, table: jnp.ndarray, max_bw: int,
                       capacity: int) -> jnp.ndarray:
    """(bytes,) uint8 + (4, scap) int32 segment table → (capacity,) int32
    indices of a whole chunk's RLE / bit-packed hybrid streams.

    Segment s covers present-value positions [start[s], start[s+1]). A packed
    segment holds value i at bits [bit0 + i*bw, +bw) of `packed` (bit0 is
    byte_off*8 - start*bw, so consecutive runs whose bytes are contiguous are
    one row); an RLE segment has bw 0 — nothing to read — and its repeated
    value in the value row, which is 0 on packed rows. Rows past the last
    segment start at `capacity` or beyond. The segment of a position is a
    prefix count of the starts at or before it; the bit width is then a
    per-element operand of the same shift-and-mask as `unpack_bits_device`,
    over as many covering bytes as the widest page needs."""
    pos = jnp.arange(capacity, dtype=jnp.int32)
    marks = jnp.zeros((capacity,), jnp.int32).at[table[SEG_START]].add(
        1, mode="drop", indices_are_sorted=True, unique_indices=True)
    seg = jnp.clip(cumsum(marks) - 1, 0, table.shape[1] - 1)
    # gathers of whole columns, one for the table and one for the bytes: on
    # the chip a 1-D gather of as many elements takes three times as long
    # (PERF.md, PR 27)
    row = table[:, seg]
    bw = row[SEG_BW]
    bit0 = row[SEG_BIT0] + pos * bw
    shift = (bit0 & 7).astype(jnp.int64)
    # the bytes that cover a value, a row a byte: the buffer beside itself
    # shifted by one, two .. bytes
    nbytes, cover = packed.shape[0], (max_bw + 14) // 8
    beyond = jnp.concatenate([packed, jnp.zeros((cover,), packed.dtype)])
    covering = jnp.stack([beyond[k:k + nbytes] for k in range(cover)])[
        :, jnp.clip(bit0 >> 3, 0, nbytes - 1)]
    window = jnp.zeros((capacity,), jnp.int64)
    for k in range(cover):
        window = window | (covering[k].astype(jnp.int64) << (8 * k))
    mask = (jnp.int64(1) << bw.astype(jnp.int64)) - 1
    vals = ((window >> shift) & mask).astype(jnp.int32)
    return vals | row[SEG_VALUE]


def _indices_to_rows(spec, idx, dict_d, dl_d, n_t):
    """The tail every dictionary decode shares: (pcap,) indices of the
    present values → dictionary gather → definition-level spread → live mask
    → canonical nulls, at spec.capacity."""
    want = jnp.dtype(spec.want)
    nd = dict_d.shape[0]
    # an all-null page may carry an EMPTY dictionary: nothing to gather
    present = (dict_d[jnp.clip(idx, 0, max(nd - 1, 0))] if nd
               else jnp.zeros((spec.pcap,), dict_d.dtype))
    cap = spec.capacity
    present_padded = jnp.zeros((cap,), present.dtype
                               ).at[:min(spec.pcap, cap)].set(present[:cap])
    vals, valid = expand_present_to_rows(present_padded, dl_d, cap)
    live = jnp.arange(cap, dtype=jnp.int32) < n_t
    m = valid & live
    v = jnp.where(m, vals.astype(want), jnp.asarray(spec.default, want))
    return v, m


def decode_page_cols(spec: EncodedPageSpec, packed_d, dict_d, dl_d,
                     n_present_t, n_t):
    """TRACEABLE single-page decode: bit-unpack → dictionary gather →
    definition-level spread → canonical nulls, returning (values, validity)
    at spec.capacity. This is the single source of truth for page expansion —
    the standalone fused decode kernel (io/parquet_native.py) and the
    encoded-upload consumers (columnar/encoded.py, exec/aggregate.py) all
    trace THIS body, so encoded-vs-dense results are bit-identical by
    construction. Device args: the packed bytes, the device
    dictionary, def-levels as bool (capacity,), and int32 scalars for the
    present/live counts."""
    idx = unpack_bits_device(packed_d, spec.bit_width, n_present_t,
                             spec.pcap)
    return _indices_to_rows(spec, idx, dict_d, dl_d, n_t)


def decode_runs_cols(spec: EncodedRunsSpec, packed_d, table_d, dict_d, dl_d,
                     n_t):
    """TRACEABLE whole-chunk decode for a chunk whose index streams are not
    one bit-packed run: indices from the segment table
    (`unpack_runs_device`), then `decode_page_cols`'s own tail. Device args:
    the concatenated packed bytes, the (4, scap) segment table, the device
    dictionary, def-levels as bool (capacity,), the live row count."""
    idx = unpack_runs_device(packed_d, table_d, spec.max_bw, spec.pcap)
    return _indices_to_rows(spec, idx, dict_d, dl_d, n_t)
