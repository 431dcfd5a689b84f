"""Pallas kernel equivalence tests (interpret mode on the forced-CPU
platform; the same kernels compile with Mosaic on TPU: test_tpu_compile.py).

Oracles: the jnp reference implementations in ops/hashing.py (itself pinned
to Spark golden vectors in test_columnar.py), ops/grouping.py and
ops/sorting.py.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from spark_rapids_tpu.ops import hashing as H
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


def test_switch_table_dispatch(monkeypatch):
    """should_use() is "TPU backend and the table says on": nothing probes
    and nothing latches. Off the TPU no kernel is routed; on it exactly the
    kernels whose table entry is None; set_mode() overrides both ways."""
    import spark_rapids_tpu.ops.pallas_kernels as mod
    assert not any(mod.should_use(k) for k in mod.KERNELS)
    monkeypatch.setattr(mod.jax, "default_backend", lambda: "tpu")
    for kernel, why_off in mod.KERNELS.items():
        assert mod.should_use(kernel) is (why_off is None), kernel
    mod.set_mode(False)
    try:
        assert not any(mod.should_use(k) for k in mod.KERNELS)
        mod.set_mode(True)
        assert all(mod.should_use(k) for k in mod.KERNELS)
    finally:
        mod.set_mode(None)


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
