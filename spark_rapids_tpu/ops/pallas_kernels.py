"""Hand-written Pallas TPU kernels for the irregular hot ops.

SURVEY.md §7 design stance: XLA fuses the dense columnar math; Pallas covers
the parts XLA lowers poorly on TPU — byte-level bit twiddling with per-row
data-dependent control (string murmur3), the medium-domain one-hot group-by
and the counting-sort partition step. Reference analog: cudf's murmur3
device hash (GpuHashPartitioning.scala:92 depends on it).

The kernels are lane-static reformulations — no dynamic gathers, which
Mosaic lowers badly:

* ``murmur3_words``: rows tile over the grid; the word loop and the
  per-row tail-byte selection unroll over static columns with vector
  selects, so each (TILE, W) block is pure VPU work.
* ``onehot_sum_f32``: bucket sums over a medium code domain as a blocked
  one-hot matmul whose (BK, 128) tiles are made in VMEM and fed to the MXU.
* ``radix_ranks``: stable counting-sort ranks over a small partition domain
  as dense (BK, DP) one-hot cumsums, with the sequential TPU grid carrying
  the per-partition running count between row tiles. Backs the exchange
  partition step (GpuPartitioning.sliceInternalOnGpu analog).

Dispatch: compiled on TPU; ``interpret=True`` elsewhere (tests force the
CPU platform). The jnp reference implementations in ops/hashing.py,
ops/grouping.py and ops/sorting.py remain the oracle and the fallback.
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
# forces the kernels (interpret mode off the TPU: the tests' way in); False
# forces the jnp paths
_FORCE: bool | None = None

# The one switch table: kernel -> None (on) or the reason it is off. "On"
# means the chip's compiler accepted the kernel at the shapes the TPC-H SF 1
# main path uses (tests/test_tpu_compile.py compiles each for a described
# v5e). A kernel that is on and fails to compile on the chip raises the
# compiler's error; nothing latches it off at run time.
KERNELS: dict[str, str | None] = {
    "radix": None,
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
RADIX_MAX_PARTS = 4096  # lane cap


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
