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

import numpy as np
import jax.numpy as jnp
from spark_rapids_tpu.ops.windowing import cumsum


class EncodedPageSpec(typing.NamedTuple):
    """Static shape/type facts of one encoded data page — everything the
    traceable decode prologue (`decode_page_cols`) closes over. Hashable, so
    it rides fuse-cache keys and pytree aux data directly; two pages with the
    same spec share one compiled program regardless of their byte content."""
    bit_width: int
    pcap: int          # present-value capacity bucket
    bcap: int          # packed-byte capacity bucket (0 under pallas words)
    capacity: int      # output row capacity bucket
    want: str          # decoded value dtype name (int32 codes for strings)
    is_string: bool
    default: object    # canonical fill for invalid slots
    use_pallas: bool
    n_present: int     # static present count (pallas tile shapes need it)


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


def decode_page_cols(spec: EncodedPageSpec, packed_d, dict_d, dl_d,
                     n_present_t, n_t):
    """TRACEABLE single-page decode: bit-unpack → dictionary gather →
    definition-level spread → canonical nulls, returning (values, validity)
    at spec.capacity. This is the single source of truth for page expansion —
    the standalone fused decode kernel (io/parquet_native.py) and the
    encoded-upload consumers (columnar/encoded.py, exec/aggregate.py) all
    trace THIS body, so encoded-vs-dense results are bit-identical by
    construction. Device args: packed bytes (or pallas words), the device
    dictionary, def-levels as bool (capacity,), and int32 scalars for the
    present/live counts."""
    want = jnp.dtype(spec.want)
    if spec.use_pallas:
        from spark_rapids_tpu.ops import pallas_kernels as PK
        # pallas tile shapes need the STATIC present count (part of the spec,
        # hence part of every cache key that embeds the spec)
        idx = PK.bitunpack128(packed_d, spec.bit_width, spec.n_present,
                              spec.pcap)
    else:
        idx = unpack_bits_device(packed_d, spec.bit_width, n_present_t,
                                 spec.pcap)
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


def decode_dictionary_page(packed_bytes: np.ndarray, bit_width: int,
                           n_present: int, def_levels: np.ndarray,
                           dict_values: jnp.ndarray, capacity: int):
    """One data page → (values, validity) padded to capacity. The packed
    index bytes and the dictionary live on device; run structure was already
    validated host-side (single bit-packed region — parse_rle_hybrid)."""
    from spark_rapids_tpu.columnar.vector import bucket_capacity
    from spark_rapids_tpu.ops import pallas_kernels as PK
    pcap = max(bucket_capacity(n_present), 8)
    if PK.should_use("bitunpack"):
        words = PK.bytes_to_words_u32(np.asarray(packed_bytes, np.uint8))
        idx = PK.bitunpack128(jnp.asarray(words), bit_width, n_present, pcap)
    else:
        packed_d = jnp.zeros((max(len(packed_bytes), 1),), jnp.uint8
                             ).at[:len(packed_bytes)].set(
            jnp.asarray(packed_bytes, dtype=jnp.uint8))
        idx = unpack_bits_device(packed_d, bit_width, n_present, pcap)
    nd = dict_values.shape[0]
    present = dict_values[jnp.clip(idx, 0, max(nd - 1, 0))]
    dl = jnp.zeros((capacity,), jnp.bool_).at[:len(def_levels)].set(
        jnp.asarray(def_levels.astype(bool)))
    # pad present values out to capacity before the rank gather (pcap <=
    # capacity: n_present <= num_values and capacity is the row bucket)
    present_padded = jnp.zeros((capacity,), present.dtype
                               ).at[:pcap].set(present)
    vals, valid = expand_present_to_rows(present_padded, dl, capacity)
    return vals, valid
