"""Hand-written Pallas TPU kernels for the irregular hot ops.

SURVEY.md §7 design stance: XLA fuses the dense columnar math; Pallas covers
the parts XLA lowers poorly on TPU — byte-level bit twiddling with per-row
data-dependent control (string murmur3) and bit-packed decode (parquet
RLE_DICTIONARY indices). Reference analogs: cudf's murmur3 device hash
(GpuHashPartitioning.scala:92 depends on it) and libcudf's parquet index
decoder (GpuParquetScan.scala:1235 `Table.readParquet`).

Both kernels are lane-static reformulations — no dynamic gathers, which
Mosaic lowers badly:

* ``murmur3_words``: rows tile over the grid; the word loop and the
  per-row tail-byte selection unroll over static columns with vector
  selects, so each (TILE, W) block is pure VPU work.
* ``bitunpack128``: 128 consecutive bit-packed values of width ``bw``
  occupy exactly ``4*bw`` 32-bit words, so value lane j always reads word
  ``(j*bw)>>5`` — a static column index. The unpack becomes a per-lane
  shift/mask over statically-selected columns: zero gathers.
* ``radix_ranks``: stable counting-sort ranks over a small partition domain
  as dense (BK, DP) one-hot cumsums, with the sequential TPU grid carrying
  the per-partition running count between row tiles. Backs both the
  exchange partition step (GpuPartitioning.sliceInternalOnGpu analog) and
  the hash-table build.
* ``hash_join_build``/``hash_join_probe``: the cudf innerJoinGatherMaps
  analog (GpuHashJoin.scala:289) for unique fixed-point keys — an open
  (H, HJ_SLOTS) hash table whose build is a radix partition by Fibonacci
  hash bucket and whose probe unrolls the slot loop statically over a
  VMEM-resident table.

Dispatch: compiled on TPU; ``interpret=True`` elsewhere (tests force the
CPU platform). The jnp reference implementations in ops/hashing.py and
ops/parquet_decode.py remain the oracle and the fallback.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

_C1 = np.int32(np.uint32(0xCC9E2D51))
_C2 = np.int32(np.uint32(0x1B873593))
_M5 = np.int32(np.uint32(0xE6546B64))
_FX1 = np.int32(np.uint32(0x85EBCA6B))
_FX2 = np.int32(np.uint32(0xC2B2AE35))
# the package runs with jax_enable_x64: a bare Python int in an index map or
# a kernel body is a 64-bit scalar, which Mosaic refuses. Block indices and
# in-kernel constants are spelled as 32-bit values.
_I0 = np.int32(0)


# dispatch switch: None = auto (the table below, on the TPU backend); True
# forces the kernels (interpret-mode off-TPU — tests); False forces the jnp
# paths (spark.rapids.tpu.sql.pallas.enabled=false)
_FORCE: bool | None = None

# The one switch table: kernel -> None (on) or the reason it is off. "On"
# means the chip's compiler accepted the kernel at the shapes the TPC-H SF 1
# main path uses (tests/test_tpu_compile.py compiles each for a described
# v5e). A kernel that is on and fails to compile on the chip raises the
# compiler's error; nothing latches it off at run time. A kernel that is off
# carries its reason: the compiler's message (its compile test is then a
# strict xfail quoting it), or, after "chip:", what a run on the chip showed
# of a kernel that does compile.
KERNELS: dict[str, str | None] = {
    # compiles, and is wrong on the chip: a smoke run on a TPU v5 lite
    # (PR 24) unpacked 1 Mi random values per width and compared with NumPy.
    # Widths 1-16 came back exact; every width 17-31 had wrong values (135 of
    # 1 Mi at width 17, 1971 at width 24), always a value that straddles two
    # words with its low part 16 bits or longer, always with bits >= 16
    # missing (got 11459, want 76995). Interpret mode is exact at every
    # width, and so is ops/parquet_decode.unpack_bits_device on the chip. It
    # made TPC-H q5 wrong (c/s_nationkey and l_suppkey pages).
    "bitunpack": (
        "chip: Mosaic accepts the kernel but on a TPU v5 lite it drops bits "
        ">= 16 of values that straddle two words, for every bit width "
        "17-31 (135 wrong of 1 Mi at width 17; interpret mode is exact)"),
    "radix": None,
    # build compiles (it rides radix_ranks); the probe does not
    "hashjoin": (
        "hash_join_probe: RecursionError: maximum recursion depth exceeded "
        "in Mosaic lowering (the kernel is 64-bit: int64 key refs and a "
        "64-bit multiply hash; the integer convert rule has no 64-bit "
        "case). With keys split into int32 lanes and the hash moved out, "
        "the per-row table gather tk[base + s] is refused: "
        "NotImplementedError: Only 2D gather is supported"),
    "onehot": None,
    "murmur3": None,
}

_traced: collections.Counter = collections.Counter()


def set_mode(force: bool | None) -> None:
    global _FORCE
    _FORCE = force


def should_use(kernel: str = "murmur3") -> bool:
    """Does the engine route `kernel`'s op here on this backend?"""
    off = KERNELS[kernel]          # unknown kernel name: KeyError
    if _FORCE is not None:
        return _FORCE
    return jax.default_backend() == "tpu" and off is None


def traced() -> dict:
    """{kernel: times its pallas_call was traced into a program} in this
    process — which kernels a run really reached (chip_smoke.py prints it)."""
    return dict(_traced)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _rotl(x, n):
    return lax.shift_left(x, jnp.int32(n)) | lax.shift_right_logical(
        x, jnp.int32(32 - n))


def _mix_k1(k1):
    return _rotl(k1 * _C1, 15) * _C2


def _mix_h1(h1, k1):
    return _rotl(h1 ^ k1, 13) * jnp.int32(5) + _M5


def _fmix(h1, length):
    h1 = h1 ^ length
    h1 = h1 ^ lax.shift_right_logical(h1, jnp.int32(16))
    h1 = h1 * _FX1
    h1 = h1 ^ lax.shift_right_logical(h1, jnp.int32(13))
    h1 = h1 * _FX2
    return h1 ^ lax.shift_right_logical(h1, jnp.int32(16))


# ---------------------------------------------------------------------------
# murmur3 string hash
# ---------------------------------------------------------------------------

_HASH_ROWS = 64  # sublane rows of 128 strings per grid step (8192 strings)


def _murmur3_kernel(words_ref, len_ref, seed_ref, out_ref, *, W: int):
    # strings lie along (sublane, lane): every operand is a dense (R, 128)
    # slab, word column i is the slab words_ref[i]
    lens = len_ref[...]                       # (R, 128) int32
    h1 = seed_ref[...]                        # (R, 128) int32 running hash
    n_words = lax.shift_right_logical(lens, jnp.int32(2))   # lens >= 0
    n_tail = lens & jnp.int32(3)
    # whole-word rounds, statically unrolled; rows shorter than column i
    # keep their running hash through a vector select
    for i in range(W):
        h1 = jnp.where(i < n_words, _mix_h1(h1, _mix_k1(words_ref[i])), h1)
    # the tail word (index n_words, per row) via static-column selects —
    # a dynamic per-row gather would not vectorize on the VPU
    tail_word = jnp.zeros_like(lens)
    for i in range(W):
        tail_word = jnp.where(n_words == i, words_ref[i], tail_word)
    for t in range(3):
        byte = lax.shift_right_logical(tail_word,
                                       jnp.int32(8 * t)) & jnp.int32(0xFF)
        sbyte = jnp.where(byte >= 128, byte - 256, byte)
        h1 = jnp.where(t < n_tail, _mix_h1(h1, _mix_k1(sbyte)), h1)
    out_ref[...] = _fmix(h1, lens)


def murmur3_words(words, lengths, seed) -> jnp.ndarray:
    """Spark Murmur3_x86_32.hashUnsafeBytes over packed word rows, as a
    Pallas kernel. Same contract as ops.hashing.hash_string_words:
    words (n, W) int32 little-endian UTF-8, lengths (n,) int32 → (n,) int32.
    `seed` may be a scalar or a per-row (n,) running hash (the partitioner
    chains column hashes, so the seed is usually row-varying).
    """
    _traced["murmur3"] += 1
    n, W = words.shape
    rows = -(-max(n, 1) // 128)
    tile = min(_HASH_ROWS, -(-rows // 8) * 8)
    rows_p = -(-rows // tile) * tile
    n_pad = rows_p * 128

    def slab(x):                              # (n,) -> (rows_p, 128)
        return jnp.zeros((n_pad,), jnp.int32).at[:n].set(
            x.astype(jnp.int32)).reshape(rows_p, 128)

    words_p = jnp.zeros((W, n_pad), jnp.int32).at[:, :n].set(
        words.astype(jnp.int32).T).reshape(W, rows_p, 128)
    seed_rows = jnp.broadcast_to(jnp.asarray(seed, jnp.int32), (n,))
    out = pl.pallas_call(
        functools.partial(_murmur3_kernel, W=W),
        out_shape=jax.ShapeDtypeStruct((rows_p, 128), jnp.int32),
        grid=(rows_p // tile,),
        in_specs=[
            pl.BlockSpec((W, tile, 128), lambda i: (_I0, i, _I0)),
            pl.BlockSpec((tile, 128), lambda i: (i, _I0)),
            pl.BlockSpec((tile, 128), lambda i: (i, _I0)),
        ],
        out_specs=pl.BlockSpec((tile, 128), lambda i: (i, _I0)),
        interpret=_interpret(),
        name="srt_pallas_murmur3",
    )(words_p, slab(lengths), slab(seed_rows))
    return out.reshape(n_pad)[:n]


# ---------------------------------------------------------------------------
# parquet bit-unpack
# ---------------------------------------------------------------------------

_UNPACK_TILE = 64  # rows of 128 values → 8192 values per grid step


def _bitunpack_kernel(w_ref, out_ref, *, bw: int):
    w = w_ref[:]                              # (T, 4*bw) int32 words
    mask = jnp.int32((1 << bw) - 1) if bw < 32 else jnp.int32(-1)
    cols = []
    for j in range(128):
        off = j * bw
        w0, sh = off >> 5, off & 31
        v = lax.shift_right_logical(w[:, w0:w0 + 1], jnp.int32(sh))
        if sh + bw > 32:                      # value spans two words
            v = v | lax.shift_left(w[:, w0 + 1:w0 + 2], jnp.int32(32 - sh))
        cols.append(v & mask)
    out_ref[:] = jnp.concatenate(cols, axis=1)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def bitunpack128(words_u32, bit_width: int, n: int, capacity: int):
    """Unpack `n` bit-packed values of `bit_width` bits from 32-bit words
    into (capacity,) int32. 128 values of width bw span exactly 4*bw words,
    so the kernel reads only statically-indexed columns.

    words_u32: (ceil(n/128)*4*bw,) int32 — packed little-endian words.

    Jitted on its static arguments: an eager caller then compiles once per
    shape, where a bare eager pallas_call is re-lowered by Mosaic on every
    call.
    """
    if not 1 <= bit_width <= 32:
        raise ValueError(f"bit width {bit_width} out of range")
    _traced["bitunpack"] += 1
    bw = bit_width
    n128 = max(1, -(-n // 128))
    tile = min(_UNPACK_TILE, n128)
    rows = -(-n128 // tile) * tile
    need = rows * 4 * bw
    # a legal parquet chunk's final bit-packed run may declare more 8-value
    # groups than remaining values — the packed buffer can be LONGER than
    # `need`; truncate before writing into the padded buffer
    k = min(words_u32.shape[0], need)
    w = jnp.zeros((need,), jnp.int32).at[:k].set(
        words_u32[:k].astype(jnp.int32)).reshape(rows, 4 * bw)
    out = pl.pallas_call(
        functools.partial(_bitunpack_kernel, bw=bw),
        out_shape=jax.ShapeDtypeStruct((rows, 128), jnp.int32),
        grid=(rows // tile,),
        in_specs=[pl.BlockSpec((tile, 4 * bw), lambda i: (i, _I0))],
        out_specs=pl.BlockSpec((tile, 128), lambda i: (i, _I0)),
        interpret=_interpret(),
        name="srt_pallas_bitunpack",
    )(w)
    flat = out.reshape(-1)
    idx = jnp.arange(capacity, dtype=jnp.int32)
    safe = jnp.clip(idx, 0, flat.shape[0] - 1)
    return jnp.where(idx < n, flat[safe], 0)


def bytes_to_words_u32(packed: np.ndarray) -> np.ndarray:
    """Host prep: pad a uint8 byte buffer to 4-byte alignment and view as
    little-endian int32 words for bitunpack128."""
    nb = len(packed)
    pad = -nb % 4
    if pad:
        packed = np.concatenate([packed, np.zeros(pad, np.uint8)])
    return packed.view("<i4").astype(np.int32)


# ---------------------------------------------------------------------------
# blocked one-hot matmul (medium-domain dense group-by / histogram)
# ---------------------------------------------------------------------------

_OH_BK = 1024    # row-block (codes/values) per grid step
_OH_BD = 128     # domain lanes per grid step (one MXU/VPU lane tile)


def _onehot_kernel(codes_ref, vals_ref, out_ref, *, bk: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    d0 = pl.program_id(0) * _OH_BD
    # the one-hot is built transposed, (domain lane-block, rows): the codes
    # row broadcasts along sublanes, so no lane->sublane relayout is needed,
    # and the contraction is the MXU's native A @ B^T form
    lanes = d0 + lax.broadcasted_iota(jnp.int32, (_OH_BD, bk), 0)
    onehot_t = (codes_ref[...] == lanes).astype(jnp.float32)   # (128, bk)
    out_ref[...] += lax.dot_general(
        vals_ref[...], onehot_t, (((1,), (1,)), ((), ())),
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                    # (1, 128)


def onehot_sum_f32(vals, codes, n_domain: int):
    """(n_domain,) f32 bucket sums of `vals` over int32 `codes` — the
    generalized one-hot-matmul group-by (reference analog: cudf's hash groupby behind aggregate.scala:706).

    The jnp formulation in ops/grouping.dense_group_sum materializes the
    (cap, D) one-hot in HBM — fine at D<=128, ruinous at medium domains.
    This kernel generates each (BK, 128) one-hot tile on the fly in VMEM
    and feeds the MXU, cutting HBM traffic from O(cap*D) one-hot elements
    to O(cap * D/128) input re-streams (rows stream once per 128-lane
    domain block) + O(D) output; nothing is scattered (large scatters
    serialize on the TPU), and every shape is static.

    Exactness: f32 accumulation — callers use it for 0/1 histograms and
    per-batch counts (exact below 2^24) and f32 sums; f64 sums stay on the
    jnp path."""
    _traced["onehot"] += 1
    cap = vals.shape[0]
    # lane-aligned row block: Mosaic wants multiples of 128 (the probe's
    # aligned instance would not catch a misaligned caller)
    bk = min(_OH_BK, -(-max(cap, 128) // 128) * 128)
    capp = -(-cap // bk) * bk
    dp = -(-n_domain // _OH_BD) * _OH_BD
    codes2 = jnp.full((1, capp), -1, jnp.int32).at[0, :cap].set(
        codes.astype(jnp.int32))
    vals2 = jnp.zeros((1, capp), jnp.float32).at[0, :cap].set(
        vals.astype(jnp.float32))
    out = pl.pallas_call(
        functools.partial(_onehot_kernel, bk=bk),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        grid=(dp // _OH_BD, capp // bk),
        in_specs=[pl.BlockSpec((1, bk), lambda i, k: (_I0, k)),
                  pl.BlockSpec((1, bk), lambda i, k: (_I0, k))],
        out_specs=pl.BlockSpec((1, _OH_BD), lambda i, k: (_I0, i)),
        interpret=_interpret(),
        name="srt_pallas_onehot",
    )(codes2, vals2)
    return out[0, :n_domain]


# ---------------------------------------------------------------------------
# radix partition (stable counting-sort ranks over small partition domains)
# ---------------------------------------------------------------------------

_RP_BK = 256            # max rows per grid step
_RP_TILE_BUDGET = 1 << 19  # one-hot tile elements (2 MB i32): bk*dp bound
RADIX_MAX_PARTS = 4096  # lane cap (hash_join_buckets tops out here)


def _radix_kernel(ids_ref, rank_ref, counts_ref, *, bk: int, dp: int):
    """One grid step over a row tile. The per-partition running count
    (`counts_ref`, one block revisited every step — the sequential TPU grid
    is the carry chain) turns per-tile exclusive one-hot cumsums into global
    stable ranks: rank(row) = rows with the same id in earlier tiles +
    same-id rows above it in this tile. The one-hot is held transposed,
    (DP, BK): the ids row broadcasts along sublanes and the ranks come out
    as a row. Mosaic lowers no cumsum, so the running count along the tile
    is one MXU product with an upper-triangular 0/1 matrix (0/1 is exact in
    bf16, the f32 accumulator is exact to 2^24 > BK). The scatter that
    cudf's radix partition would do stays outside the kernel."""
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    lanes = lax.broadcasted_iota(jnp.int32, (dp, bk), 0)
    hit = ids_ref[...] == lanes                       # (dp, bk); id>=dp → 0s
    onehot = hit.astype(jnp.int32)
    upper = (lax.broadcasted_iota(jnp.int32, (bk, bk), 0)
             <= lax.broadcasted_iota(jnp.int32, (bk, bk), 1))
    incl = jnp.dot(hit.astype(jnp.bfloat16), upper.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    carry = counts_ref[...]                           # (dp, 1) prior tiles
    rank_ref[...] = jnp.sum(onehot * (incl - onehot + carry),
                            axis=0, keepdims=True, dtype=jnp.int32)
    counts_ref[...] = carry + jnp.sum(onehot, axis=1, keepdims=True,
                                      dtype=jnp.int32)


def radix_ranks(ids, num_lanes: int):
    """Stable radix ranks: for int32 `ids` in [0, num_lanes), returns
    (ranks, counts) where ranks[i] = #{j < i : ids[j] == ids[i]} and
    counts[l] = #{ids == l}. Ids outside [0, num_lanes) (padding sentinel)
    get rank 0 and are not counted."""
    _traced["radix"] += 1
    cap = ids.shape[0]
    dp = -(-max(num_lanes, 1) // 128) * 128
    if dp > RADIX_MAX_PARTS:
        raise ValueError(f"radix domain {num_lanes} exceeds {RADIX_MAX_PARTS}")
    bk = min(_RP_BK, max(8, cap), max(8, _RP_TILE_BUDGET // dp))
    n_pad = -(-cap // bk) * bk
    # out-of-range ids (incl. callers' padding sentinels) map to id=dp —
    # no lane match, so zero rank and zero count; dp-pad lanes beyond
    # num_lanes must not silently rank rows either
    ids = ids.astype(jnp.int32)
    ids = jnp.where((ids >= 0) & (ids < num_lanes), ids, jnp.int32(dp))
    ids_p = jnp.full((1, n_pad), dp, jnp.int32).at[0, :cap].set(ids)
    ranks, counts = pl.pallas_call(
        functools.partial(_radix_kernel, bk=bk, dp=dp),
        out_shape=[jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
                   jax.ShapeDtypeStruct((dp, 1), jnp.int32)],
        grid=(n_pad // bk,),
        in_specs=[pl.BlockSpec((1, bk), lambda i: (_I0, i))],
        out_specs=[pl.BlockSpec((1, bk), lambda i: (_I0, i)),
                   pl.BlockSpec((dp, 1), lambda i: (_I0, _I0))],
        interpret=_interpret(),
        name="srt_pallas_radix",
    )(ids_p)
    return ranks[0, :cap], counts[:num_lanes, 0]


def radix_partition_permutation(ids, num_lanes: int):
    """Stable permutation grouping rows by id (== argsort(ids, stable) for
    ids in [0, num_lanes)) via the radix-rank kernel plus one 1:1 scatter —
    the GpuPartitioning.sliceInternalOnGpu radix analog, replacing the
    comparator `lax.sort` the partition step otherwise pays."""
    cap = ids.shape[0]
    ranks, counts = radix_ranks(ids, num_lanes)
    offsets = jnp.cumsum(counts) - counts                 # exclusive
    dest = offsets[jnp.clip(ids, 0, num_lanes - 1)] + ranks
    return jnp.zeros((cap,), jnp.int32).at[dest].set(
        jnp.arange(cap, dtype=jnp.int32), mode="drop")


# ---------------------------------------------------------------------------
# VMEM hash-table join build + probe (unique fixed-point keys)
# ---------------------------------------------------------------------------

HJ_SLOTS = 8            # bucket capacity; build falls back above this load
_HJ_TILE = 8192         # stream rows per grid step: big tiles keep the grid
#                         short (interpret mode pays per-step overhead; the
#                         (tile, HJ_SLOTS) gather is ~512 KB in VMEM)
_HJ_EMPTY = np.int64(np.iinfo(np.int64).min)  # slot sentinel (engage gate
#                                               requires vmin > int64 min)
# Fibonacci multiplicative constant 0x9E3779B97F4A7C15 as a signed int64
_HJ_MULT = np.int64(np.uint64(0x9E3779B97F4A7C15).astype(np.int64))


def _hj_bucket(vals_i64, h_bits: int):
    h = vals_i64 * _HJ_MULT
    return lax.shift_right_logical(h, jnp.int64(64 - h_bits)).astype(jnp.int32)


def hash_join_build(keys_i64, eligible, num_buckets: int):
    """Build the (num_buckets, HJ_SLOTS) open hash table over unique int64
    keys: bucket = Fibonacci hash of the key, slot = the key's stable radix
    rank within its bucket (the radix kernel again — build IS a radix
    partition by hash bucket). Returns (table_keys, table_rows, ok) flat
    (H*S,) arrays + a device scalar; ok=False means a bucket overflowed
    HJ_SLOTS and the table must be discarded (caller falls back to the
    searchsorted probe). cudf's innerJoinGatherMaps builds the same shape
    with atomics (GpuHashJoin.scala:289); here the bucket ranks come from
    the sequential-grid carry chain instead."""
    if num_buckets & (num_buckets - 1) or num_buckets < 128:
        raise ValueError(f"num_buckets {num_buckets}: need a power of two >= 128")
    h_bits = num_buckets.bit_length() - 1
    cap = keys_i64.shape[0]
    bucket = jnp.where(eligible, _hj_bucket(keys_i64, h_bits),
                       jnp.int32(num_buckets))            # sentinel lane
    ranks, counts = radix_ranks(bucket, num_buckets)
    ok = jnp.max(counts) <= HJ_SLOTS
    slot = bucket * HJ_SLOTS + jnp.minimum(ranks, HJ_SLOTS - 1)
    slot = jnp.where(eligible, slot, jnp.int32(num_buckets * HJ_SLOTS))
    table_keys = jnp.full((num_buckets * HJ_SLOTS,), _HJ_EMPTY,
                          jnp.int64).at[slot].set(keys_i64, mode="drop")
    table_rows = jnp.full((num_buckets * HJ_SLOTS,), -1,
                          jnp.int32).at[slot].set(
        jnp.arange(cap, dtype=jnp.int32), mode="drop")
    # duplicate keys land in one bucket (same hash) with distinct ranks: the
    # unique-keys probe contract would silently under-count them, so the
    # build refuses — S*(S-1)/2 static column compares over the table
    t2 = table_keys.reshape(num_buckets, HJ_SLOTS)
    dup = jnp.zeros((), jnp.bool_)
    for s in range(HJ_SLOTS):
        for t in range(s + 1, HJ_SLOTS):
            dup = dup | jnp.any((t2[:, s] == t2[:, t])
                                & (t2[:, s] != _HJ_EMPTY))
    return table_keys, table_rows, ok & ~dup


def _hash_probe_kernel(sk_ref, tk_ref, tr_ref, pos_ref, found_ref,
                       *, h_bits: int):
    """Probe one stream tile against the whole table (resident in VMEM —
    both table blocks map to (0, 0) every grid step). The slot loop unrolls
    statically; the only dynamic access is the per-row bucket gather, the
    same class as the engine's dictionary-decode gathers."""
    svals = sk_ref[0, :]                                  # (T,) int64
    base = _hj_bucket(svals, h_bits) * HJ_SLOTS
    tk = tk_ref[0, :]
    tr = tr_ref[0, :]
    pos = jnp.full(svals.shape, -1, jnp.int32)
    found = jnp.zeros(svals.shape, jnp.bool_)
    for s in range(HJ_SLOTS):
        cand = tk[base + s]
        hit = cand == svals                               # EMPTY never matches
        pos = jnp.where(hit, tr[base + s], pos)
        found = found | hit
    pos_ref[0, :] = pos
    found_ref[0, :] = found.astype(jnp.int32)


def hash_join_probe(table_keys, table_rows, stream_i64, num_buckets: int):
    """(build_row, found) per stream key — the innerJoinGatherMaps probe.
    Unique-keys contract: at most one slot matches. Validity/liveness
    masking is the caller's job (hash of an invalid row's value is
    harmless; its hit is masked off outside)."""
    _traced["hashjoin"] += 1
    h_bits = num_buckets.bit_length() - 1
    n = stream_i64.shape[0]
    tile = min(_HJ_TILE, max(8, n))
    n_pad = -(-n // tile) * tile
    hs = num_buckets * HJ_SLOTS
    sp = jnp.zeros((1, n_pad), jnp.int64).at[0, :n].set(stream_i64)
    pos, found = pl.pallas_call(
        functools.partial(_hash_probe_kernel, h_bits=h_bits),
        out_shape=[jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
                   jax.ShapeDtypeStruct((1, n_pad), jnp.int32)],
        grid=(n_pad // tile,),
        in_specs=[
            pl.BlockSpec((1, tile), lambda i: (_I0, i)),
            pl.BlockSpec((1, hs), lambda i: (_I0, _I0)),
            pl.BlockSpec((1, hs), lambda i: (_I0, _I0)),
        ],
        out_specs=[pl.BlockSpec((1, tile), lambda i: (_I0, i)),
                   pl.BlockSpec((1, tile), lambda i: (_I0, i))],
        interpret=_interpret(),
        name="srt_pallas_hashjoin",
    )(sp, table_keys.reshape(1, hs), table_rows.reshape(1, hs))
    return pos[0, :n], found[0, :n].astype(jnp.bool_)


def hash_join_buckets(n_build: int) -> int:
    """Bucket count for a build of `n_build` rows: ~0.25 load factor over
    HJ_SLOTS-deep buckets, clamped to the VMEM table budget. Returns 0 when
    the build cannot meet the load factor (too big — caller falls back)."""
    want = 128
    while want * HJ_SLOTS < 4 * max(n_build, 1) and want < 4096:
        want *= 2
    if want * HJ_SLOTS < 2 * n_build:   # >0.5 load: overflow too likely
        return 0
    return want
