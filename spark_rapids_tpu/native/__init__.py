"""Native (C++) runtime components, loaded via ctypes.

Reference: the reference's native layer is cuDF/RMM/nvcomp/UCX consumed through
JNI (SURVEY.md L0). The TPU build keeps compute in XLA but implements the
host-side native pieces in C++: the LZ4 block codec (nvcomp analog) here, built by
`make` on first import and cached next to the sources."""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libtpulz4.so")
_lock = threading.Lock()
_lib = None


class NativeBuildError(RuntimeError):
    pass


def _build() -> None:
    res = subprocess.run(["make", "-C", _DIR, "-s"], capture_output=True,
                         text=True)
    if res.returncode != 0:
        raise NativeBuildError(
            f"native build failed:\n{res.stdout}\n{res.stderr}")


def lz4_lib():
    """Load (building if needed) the native LZ4 library."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        src = os.path.join(_DIR, "lz4.cpp")
        if (not os.path.exists(_LIB_PATH)
                or os.path.getmtime(_LIB_PATH) < os.path.getmtime(src)):
            _build()
        lib = ctypes.CDLL(_LIB_PATH)
        lib.tpu_lz4_compress_bound.restype = ctypes.c_size_t
        lib.tpu_lz4_compress_bound.argtypes = [ctypes.c_size_t]
        lib.tpu_lz4_compress.restype = ctypes.c_size_t
        lib.tpu_lz4_compress.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_void_p, ctypes.c_size_t]
        lib.tpu_lz4_decompress.restype = ctypes.c_size_t
        lib.tpu_lz4_decompress.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_void_p, ctypes.c_size_t]
        _lib = lib
        return _lib


def lz4_compress(data: bytes) -> bytes:
    if not data:
        return b""
    lib = lz4_lib()
    bound = lib.tpu_lz4_compress_bound(len(data))
    out = ctypes.create_string_buffer(bound)
    n = lib.tpu_lz4_compress(data, len(data), out, bound)
    if n == 0:
        raise ValueError("lz4 compression failed")
    return out.raw[:n]


def lz4_decompress(data: bytes, decompressed_len: int) -> bytes:
    if decompressed_len == 0:
        return b""
    lib = lz4_lib()
    out = ctypes.create_string_buffer(decompressed_len)
    n = lib.tpu_lz4_decompress(data, len(data), out, decompressed_len)
    if n != decompressed_len:
        raise ValueError("lz4 decompression failed (corrupt frame)")
    return out.raw[:n]


# ---------------------------------------------------------------------------
# native parquet chunk scanner (parquet_host.cpp)
# ---------------------------------------------------------------------------

_PQ_LIB_PATH = os.path.join(_DIR, "libtpuparquet.so")
_pq_lib = None

# error codes mirrored from parquet_host.cpp — each maps onto the scope the
# Python parser signals with NotImplementedError (caller falls back to arrow)
_SR_ERRORS = {-1: "malformed chunk", -2: "unsupported page type",
              -3: "unsupported page encoding", -4: "capacity exceeded",
              -5: "no dictionary page", -6: "def levels exceed num_values"}


def parquet_lib():
    """Load (building if needed) the native parquet scanner."""
    global _pq_lib
    with _lock:
        if _pq_lib is not None:
            return _pq_lib
        src = os.path.join(_DIR, "parquet_host.cpp")
        if (not os.path.exists(_PQ_LIB_PATH)
                or os.path.getmtime(_PQ_LIB_PATH) < os.path.getmtime(src)):
            _build()
        lib = ctypes.CDLL(_PQ_LIB_PATH)
        lib.sr_scan_chunk.restype = ctypes.c_int64
        lib.sr_scan_chunk.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,        # buf, buf_len
            ctypes.c_int64, ctypes.c_int32,         # num_values, max_def
            ctypes.c_void_p, ctypes.c_int64,        # pages, cap
            ctypes.c_void_p, ctypes.c_int64,        # segs, cap
            ctypes.c_void_p, ctypes.c_int64,        # def_levels, cap
            ctypes.c_void_p,                        # dict_out[3]
        ]
        lib.sr_scan_hybrid.restype = ctypes.c_int64
        lib.sr_scan_hybrid.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,        # page, page_len
            ctypes.c_int64, ctypes.c_int64,         # pos, end
            ctypes.c_int64, ctypes.c_int64,         # bit_width, total
            ctypes.c_void_p, ctypes.c_int64,        # segs, cap
        ]
        _pq_lib = lib
        return _pq_lib


_PAGE_FIELDS = 9   # int64 per SrPage (see parquet_host.cpp)
_SEG_FIELDS = 5    # int64 per SrSeg


def scan_hybrid_native(page: bytes, pos: int, end: int, bit_width: int,
                       total: int):
    """The run structure of the hybrid stream page[pos:end] holding `total`
    values: int64 (n, 5) rows of (kind 1=packed/0=rle, count, value,
    byte_off, byte_len), as io/parquet_native.parse_rle_hybrid lists them."""
    import numpy as np
    end = min(end, len(page))
    # a run takes a header byte at least, and value or payload bytes if the
    # width is not 0
    cap = max(end - pos, 0) // (2 if bit_width else 1) + 1
    segs = np.zeros((cap, _SEG_FIELDS), np.int64)
    n = parquet_lib().sr_scan_hybrid(page, len(page), pos, end, bit_width,
                                     total, segs.ctypes.data, cap)
    if n < 0:
        raise NotImplementedError(
            f"native parquet scan: {_SR_ERRORS.get(int(n), n)}")
    return segs[:n]


def scan_chunk_native(buf: bytes, num_values: int, max_def: int):
    """One native call over a column-chunk buffer → (pages, dict_info).

    pages: list of (num_values, def_levels[np.int32], bit_width, values_off,
                    body_off, body_len, n_present, segs) with segs int64
                    (n, 5) rows of page-relative
                    (kind, count, value, byte_off, byte_len);
    dict_info: (body_off, body_len, num_values).
    Raises NotImplementedError for out-of-stage-one chunks (same contract as
    the Python parser in io/parquet_native.py).
    """
    import numpy as np
    lib = parquet_lib()
    pages_cap, segs_cap = 1024, 8192
    for _attempt in range(6):  # -4 growth is bounded; then treat as corrupt
        pages_buf = np.zeros((pages_cap, _PAGE_FIELDS), np.int64)
        segs_buf = np.zeros((segs_cap, _SEG_FIELDS), np.int64)
        def_buf = np.zeros(max(num_values, 1), np.int32)
        dict_buf = np.zeros(3, np.int64)
        n = lib.sr_scan_chunk(
            buf, len(buf), num_values, max_def,
            pages_buf.ctypes.data, pages_cap,
            segs_buf.ctypes.data, segs_cap,
            def_buf.ctypes.data, len(def_buf),
            dict_buf.ctypes.data)
        if n == -4:  # capacity: grow and retry (pathological many-run pages)
            pages_cap *= 4
            segs_cap *= 16
            continue
        if n < 0:
            raise NotImplementedError(
                f"native parquet scan: {_SR_ERRORS.get(int(n), n)}")
        pages = []
        for i in range(int(n)):
            (nv, def_off, n_present, bw, body_off, body_len, values_off,
             seg_off, seg_count) = (int(v) for v in pages_buf[i])
            def_levels = def_buf[def_off:def_off + nv].copy()
            pages.append((nv, def_levels, bw, values_off, body_off, body_len,
                          n_present, segs_buf[seg_off:seg_off + seg_count]))
        return pages, (int(dict_buf[0]), int(dict_buf[1]), int(dict_buf[2]))
    raise NotImplementedError(
        "native parquet scan: segment/page capacity never converged "
        "(pathological or corrupt chunk)")
