"""Pallas kernel equivalence tests (interpret mode on the forced-CPU
platform; the same kernels compile with Mosaic on TPU — bench path).

Oracles: the jnp reference implementations in ops/hashing.py (itself pinned
to Spark golden vectors in test_columnar.py) and ops/parquet_decode.py.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from spark_rapids_tpu.ops import hashing as H
from spark_rapids_tpu.ops import parquet_decode as PD
from spark_rapids_tpu.ops import pallas_kernels as PK


def test_murmur3_words_matches_host_oracle():
    rng = np.random.default_rng(7)
    strs = ["", "a", "ab", "abc", "abcd", "hello world", "ünïcødé",
            "x" * 37, "tail3_", "padded to sixteen"]
    strs += ["".join(chr(rng.integers(32, 127)) for _ in range(rng.integers(0, 30)))
             for _ in range(50)]
    words, lens = H.pack_utf8_words(strs)
    out = np.asarray(PK.murmur3_words(jnp.asarray(words), jnp.asarray(lens), 42))
    host = [H.murmur3_bytes_host(s.encode("utf-8"), 42) for s in strs]
    assert list(out) == host


def test_murmur3_words_row_varying_seed():
    strs = ["alpha", "bravo", "charlie", "d", ""]
    words, lens = H.pack_utf8_words(strs)
    seeds = np.array([42, -7, 0, 123456, 99], dtype=np.int32)
    out = np.asarray(PK.murmur3_words(jnp.asarray(words), jnp.asarray(lens),
                                      jnp.asarray(seeds)))
    host = [H.murmur3_bytes_host(s.encode("utf-8"), int(sd))
            for s, sd in zip(strs, seeds)]
    assert list(out) == host


def test_murmur3_words_matches_jnp_kernel_large():
    rng = np.random.default_rng(11)
    strs = ["s%d_%s" % (i, "y" * int(rng.integers(0, 25))) for i in range(1000)]
    words, lens = H.pack_utf8_words(strs)
    w, l = jnp.asarray(words), jnp.asarray(lens)
    ref = np.asarray(H.hash_string_words(w, l, jnp.int32(42)))
    out = np.asarray(PK.murmur3_words(w, l, 42))
    assert (out == ref).all()


@pytest.mark.parametrize("bw", [1, 2, 3, 5, 7, 8, 11, 13, 16, 20, 24, 31, 32])
def test_bitunpack128_matches_reference(bw):
    rng = np.random.default_rng(bw)
    n = 300
    vals = rng.integers(0, 2 ** min(bw, 31), size=n, dtype=np.int64)
    # pack: value i at bits [i*bw, (i+1)*bw), little-endian bit order
    total_bits = n * bw
    buf = np.zeros((total_bits + 7) // 8, dtype=np.uint8)
    for i, v in enumerate(vals):
        for b in range(bw):
            bit = i * bw + b
            if (v >> b) & 1:
                buf[bit >> 3] |= 1 << (bit & 7)
    cap = 512
    words = PK.bytes_to_words_u32(buf)
    out = np.asarray(PK.bitunpack128(jnp.asarray(words), bw, n, cap))
    ref = np.asarray(PD.unpack_bits_device(
        jnp.asarray(buf), bw, n, cap)) if bw <= 25 else None
    expect = np.zeros(cap, dtype=np.int64)
    expect[:n] = vals
    assert (out.astype(np.uint32) == expect.astype(np.uint32)).all()
    if ref is not None:  # also agree with the stage-one jnp decoder
        assert (out[:n] == ref[:n]).all()


def test_bitunpack128_tiny_run():
    # fewer than 128 values, width 4
    vals = np.array([3, 9, 15, 0, 7, 1, 2, 4], dtype=np.int64)
    buf = np.zeros(4, dtype=np.uint8)
    for i, v in enumerate(vals):
        for b in range(4):
            bit = i * 4 + b
            if (v >> b) & 1:
                buf[bit >> 3] |= 1 << (bit & 7)
    words = PK.bytes_to_words_u32(buf)
    out = np.asarray(PK.bitunpack128(jnp.asarray(words), 4, len(vals), 16))
    assert list(out[:8]) == list(vals)
    assert (out[8:] == 0).all()


def test_pallas_dispatch_through_partitioning(monkeypatch):
    """Force the dispatch on (interpret mode off-TPU) and hash-partition a
    string column end-to-end — device results must match the forced-off jnp
    path bit for bit."""
    import pyarrow as pa
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.shuffle.partitioning import HashPartitioner
    from spark_rapids_tpu.expr.core import col

    t = pa.table({"s": pa.array(["a", "bb", "ccc", None, "dddd", "é"] * 10),
                  "v": pa.array(list(range(60)), pa.int64())})
    batch = ColumnarBatch.from_arrow(t)

    def run():
        p = HashPartitioner([col("s")], 4).bind(batch.schema)
        return {pid: part.to_arrow().to_pylist()
                for pid, part in p.partition(batch)}

    PK.set_mode(True)
    try:
        with_pallas = run()
    finally:
        PK.set_mode(False)
    without = run()
    PK.set_mode(None)
    assert with_pallas == without


def test_pallas_dispatch_through_parquet_decode(tmp_path):
    """decode_page_cols with the Pallas unpack in its spec equals without."""
    rng = np.random.default_rng(3)
    dict_vals = jnp.asarray(rng.integers(0, 1000, 32), dtype=jnp.int64)
    n = 100
    idx = rng.integers(0, 32, n)
    bw = 5
    buf = np.zeros((n * bw + 7) // 8, dtype=np.uint8)
    for i, v in enumerate(idx):
        for b in range(bw):
            bit = i * bw + b
            if (v >> b) & 1:
                buf[bit >> 3] |= 1 << (bit & 7)
    dl = jnp.arange(128) < n
    count = jnp.asarray(n, jnp.int32)

    def decode(pallas):
        spec = PD.EncodedPageSpec(bw, 128, 0 if pallas else len(buf), 128,
                                  "int64", False, 0, pallas,
                                  n if pallas else 0)
        packed = PK.bytes_to_words_u32(buf) if pallas else buf
        return PD.decode_page_cols(spec, jnp.asarray(packed), dict_vals, dl,
                                   count, count)

    v1, m1 = decode(True)
    v2, m2 = decode(False)
    assert (np.asarray(v1) == np.asarray(v2)).all()
    assert (np.asarray(m1) == np.asarray(m2)).all()
    assert np.asarray(v2)[:n].tolist() == np.asarray(dict_vals)[idx].tolist()


def test_onehot_sum_matches_numpy():
    """Blocked one-hot matmul kernel (medium-domain dense group-by,
    VERDICT r4 next #7) vs a numpy bucket-add oracle; histograms of 0/1
    values are exact."""
    rng = np.random.default_rng(11)
    for cap, D in [(4096, 12), (2048, 1000), (1500, 300), (100, 5),
                   (8192, 1024)]:
        codes = rng.integers(-1, D, cap).astype(np.int32)
        vals = rng.normal(0, 10, cap).astype(np.float32)
        got = np.asarray(PK.onehot_sum_f32(jnp.asarray(vals),
                                           jnp.asarray(codes), D))
        exp = np.zeros(D, np.float64)
        np.add.at(exp, codes[codes >= 0], vals[codes >= 0].astype(np.float64))
        assert np.allclose(got, exp, rtol=1e-3, atol=1e-2), (cap, D)
    ones = np.ones(65536, np.float32)
    codes = rng.integers(0, 1024, 65536).astype(np.int32)
    got = np.asarray(PK.onehot_sum_f32(jnp.asarray(ones),
                                       jnp.asarray(codes), 1024))
    assert np.array_equal(got.astype(np.int64), np.bincount(codes,
                                                            minlength=1024))


def _np_stable_ranks(ids, lanes):
    ranks = np.zeros(len(ids), np.int32)
    seen = {}
    for i, v in enumerate(ids):
        if 0 <= v < lanes:
            ranks[i] = seen.get(v, 0)
            seen[v] = seen.get(v, 0) + 1
    return ranks, np.bincount(ids[(ids >= 0) & (ids < lanes)],
                              minlength=lanes)[:lanes]


@pytest.mark.parametrize("shape", ["uniform", "skewed", "single", "empty"])
def test_radix_ranks_matches_numpy(shape):
    rng = np.random.default_rng(3)
    lanes = 9
    if shape == "uniform":
        ids = rng.integers(0, lanes, 700).astype(np.int32)
    elif shape == "skewed":          # one partition takes almost everything
        ids = np.where(rng.random(700) < 0.95, 4,
                       rng.integers(0, lanes, 700)).astype(np.int32)
    elif shape == "single":
        ids = np.full(300, 7, np.int32)
    else:                            # every row out of range (all padding)
        ids = np.full(128, lanes, np.int32)
    ranks, counts = PK.radix_ranks(jnp.asarray(ids), lanes)
    exp_ranks, exp_counts = _np_stable_ranks(ids, lanes)
    assert (np.asarray(counts) == exp_counts).all()
    assert (np.asarray(ranks) == exp_ranks).all()


@pytest.mark.parametrize("nparts", [1, 2, 5, 64, 300])
def test_radix_partition_permutation_is_stable_argsort(nparts):
    rng = np.random.default_rng(nparts)
    ids = rng.integers(0, nparts, 1000).astype(np.int32)
    perm = np.asarray(PK.radix_partition_permutation(jnp.asarray(ids),
                                                     nparts))
    assert (perm == np.argsort(ids, kind="stable")).all()


def test_partition_permutation_routing_with_padding():
    """ops/sorting.partition_permutation forced through the radix kernel
    equals the stable-argsort path, padding sunk to the end."""
    from spark_rapids_tpu.ops.sorting import partition_permutation
    rng = np.random.default_rng(8)
    cap, n = 512, 389
    ids = jnp.asarray(rng.integers(0, 6, cap).astype(np.int32))
    PK.set_mode(True)
    try:
        with_pallas = np.asarray(partition_permutation(ids, 6, n, cap))
    finally:
        PK.set_mode(False)
    without = np.asarray(partition_permutation(ids, 6, n, cap))
    PK.set_mode(None)
    assert (with_pallas == without).all()


def _np_hash_oracle(bk, sk):
    lookup = {int(k): i for i, k in enumerate(bk)}
    pos = np.array([lookup.get(int(s), -1) for s in sk], np.int32)
    return pos, pos >= 0


@pytest.mark.parametrize("dtype", [np.int64, np.int32, np.int16])
def test_hash_join_build_probe_dtypes(dtype):
    rng = np.random.default_rng(hash(dtype.__name__) % 2**31)
    lo = int(np.iinfo(dtype).min) // 2
    hi = int(np.iinfo(dtype).max) // 2
    bk = rng.choice(np.arange(lo, hi, max((hi - lo) // 4000, 1),
                              dtype=np.int64), 1500, replace=False)
    sk = np.concatenate([rng.choice(bk, 800),
                         rng.integers(lo, hi, 700)]).astype(np.int64)
    H = PK.hash_join_buckets(len(bk))
    tk, tr, ok = PK.hash_join_build(jnp.asarray(bk),
                                    jnp.ones(len(bk), bool), H)
    assert bool(ok)
    pos, found = PK.hash_join_probe(tk, tr, jnp.asarray(sk), H)
    exp_pos, exp_found = _np_hash_oracle(bk, sk)
    assert (np.asarray(found) == exp_found).all()
    assert (np.asarray(pos)[exp_found] == exp_pos[exp_found]).all()


def test_hash_join_build_null_mask_and_empty():
    rng = np.random.default_rng(4)
    bk = rng.permutation(np.arange(0, 10**7, 2500)[:2000]).astype(np.int64)
    elig = rng.random(2000) < 0.7     # ineligible = null / beyond n_build
    H = PK.hash_join_buckets(2000)
    tk, tr, ok = PK.hash_join_build(jnp.asarray(bk), jnp.asarray(elig), H)
    assert bool(ok)
    pos, found = PK.hash_join_probe(tk, tr, jnp.asarray(bk), H)
    # eligible keys find themselves; ineligible keys were never inserted
    assert (np.asarray(found) == elig).all()
    assert (np.asarray(pos)[elig] == np.arange(2000)[elig]).all()
    # empty build: nothing matches
    tk0, tr0, ok0 = PK.hash_join_build(
        jnp.asarray(bk), jnp.zeros(2000, bool), H)
    assert bool(ok0)
    _, found0 = PK.hash_join_probe(tk0, tr0, jnp.asarray(bk), H)
    assert not np.asarray(found0).any()


def test_hash_join_build_refuses_duplicates():
    bk = np.array([5, 9, 5, 11] * 40, np.int64)    # duplicate keys
    H = PK.hash_join_buckets(len(bk))
    _, _, ok = PK.hash_join_build(jnp.asarray(bk),
                                  jnp.ones(len(bk), bool), H)
    assert not bool(ok)


def test_hash_join_build_refuses_bucket_overflow():
    # 128 buckets x 8 slots; hash all keys into few buckets by volume:
    # 2000 unique keys over 128 buckets averages >8 per bucket
    bk = np.arange(1, 2001, dtype=np.int64) * 977
    _, _, ok = PK.hash_join_build(jnp.asarray(bk),
                                  jnp.ones(len(bk), bool), 128)
    assert not bool(ok)


def test_switch_table_dispatch(monkeypatch):
    """should_use() is "TPU backend and the table says on": nothing probes
    and nothing latches. Off the TPU no kernel is routed; on it exactly the
    kernels whose table entry is None; set_mode() overrides both ways."""
    import spark_rapids_tpu.ops.pallas_kernels as mod
    assert not any(mod.should_use(k) for k in mod.KERNELS)
    monkeypatch.setattr(mod.jax, "default_backend", lambda: "tpu")
    for kernel, why_off in mod.KERNELS.items():
        assert mod.should_use(kernel) is (why_off is None), kernel
        assert why_off is None or len(why_off) > 20   # the compiler's words
    mod.set_mode(False)
    try:
        assert not any(mod.should_use(k) for k in mod.KERNELS)
        mod.set_mode(True)
        assert all(mod.should_use(k) for k in mod.KERNELS)
    finally:
        mod.set_mode(None)


def test_join_core_pallas_hash_equivalence():
    """_JoinCore forced through the pallas_hash probe mode equals the
    forced-off jnp paths for every join type the mode serves, across
    sparse int64 keys with nulls."""
    import pyarrow as pa
    from spark_rapids_tpu.session import TpuSession
    rng = np.random.default_rng(9)
    bk = rng.permutation(np.arange(0, 2**44, 2**44 // 3000)[:3000])
    sk = np.concatenate([rng.choice(bk, 2000),
                         rng.integers(0, 2**44, 1000)]).astype(np.int64)
    bnull = rng.random(3000) < 0.05
    snull = rng.random(3000) < 0.05
    spark = TpuSession()
    build = spark.create_dataframe(pa.table({
        "k": pa.array([None if m else int(v) for v, m in zip(bk, bnull)],
                      pa.int64()),
        "b": pa.array(np.arange(3000, dtype=np.int64))}))
    stream = spark.create_dataframe(pa.table({
        "k": pa.array([None if m else int(v) for v, m in zip(sk, snull)],
                      pa.int64()),
        "s": pa.array(np.arange(3000, dtype=np.int64))}))

    def run(how):
        out = stream.join(build, on="k", how=how).collect().to_pylist()
        return sorted((tuple(r.values()) for r in out),
                      key=lambda t: tuple((v is None, v or 0) for v in t))

    for how in ("inner", "left", "left_semi", "left_anti"):
        PK.set_mode(True)
        try:
            a = run(how)
        finally:
            PK.set_mode(False)
        b = run(how)
        PK.set_mode(None)
        assert a == b, how


def test_dense_group_sum_pallas_dispatch_equivalence():
    """dense_group_sum(count_like) forced through the Pallas kernel equals
    the jnp one-hot path — the dense aggregation spine's TPU route."""
    from spark_rapids_tpu.ops import grouping as G
    rng = np.random.default_rng(12)
    cap, D = 4096, 700
    codes = jnp.asarray(rng.integers(0, D + 1, cap).astype(np.int32))
    ones = jnp.ones((cap,), jnp.int64)
    mask = jnp.asarray(rng.random(cap) < 0.9)
    PK.set_mode(True)
    try:
        a = np.asarray(G.dense_group_sum(ones, mask, codes, D, True,
                                         count_like=True))
    finally:
        PK.set_mode(False)
    b = np.asarray(G.dense_group_sum(ones, mask, codes, D, True,
                                     count_like=True))
    c = np.asarray(G.dense_group_sum(ones, mask, codes, D, False,
                                     count_like=True))
    PK.set_mode(None)
    assert np.array_equal(a, b) and np.array_equal(a, c)
