"""The sort-path group-by folds its keys into as few int64 sort operands as
they need (ops/sorting.fold_keys), by what the keys hold: against the
variadic comparator sort on random keys, and through the aggregate at a
capacity where the key-stats probe runs (2^17 and up)."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.core import Col
from spark_rapids_tpu.ops import grouping as G
from spark_rapids_tpu.ops import sorting as S
from spark_rapids_tpu.ops.filtering import gather_cols
from spark_rapids_tpu.runtime import tracing
from spark_rapids_tpu.session import TpuSession

CAP, ROWS = 2048, 1800
DICT = pa.array([f"name {i:04d}" for i in range(300)])


def _col(rng, values, dtype, nulls=0.15, dictionary=None):
    valid = rng.random(CAP) >= nulls
    values = np.where(valid, values, np.zeros_like(values))
    return Col(jnp.asarray(values), jnp.asarray(valid), dtype, dictionary)


def _keys(rng, kinds):
    made = {
        "string": lambda: _col(rng, rng.integers(0, 300, CAP)
                               .astype(np.int32), T.STRING, dictionary=DICT),
        "int32": lambda: _col(rng, rng.integers(-40, 40, CAP)
                              .astype(np.int32), T.INT),
        "int64": lambda: _col(rng, (1 << 40) + rng.integers(0, 9, CAP)
                              .astype(np.int64), T.LONG),
        "gid": lambda: _col(rng, (1 << rng.integers(0, 9, CAP)) - 1, T.LONG,
                            nulls=0.0),
        "gid32": lambda: _col(rng, ((1 << rng.integers(0, 9, CAP)) - 1)
                              .astype(np.int32), T.INT, nulls=0.0),
        "bool": lambda: _col(rng, rng.integers(0, 2, CAP).astype(bool),
                             T.BOOLEAN),
        "wide": lambda: _col(rng, rng.integers(-(1 << 62), 1 << 62, CAP),
                             T.LONG),
    }
    return [made[k]() for k in kinds]


def _variadic(cols, orders):
    """The comparator sort that folding replaces: an operand a rank and a
    value a key, the row index last."""
    pad = (jnp.arange(CAP) >= ROWS).astype(jnp.int8)
    operands = [pad]
    for c, o in zip(cols, orders):
        operands.extend(S._key_arrays(c, o))
    iota = jnp.arange(CAP, dtype=jnp.int32)
    return jax.lax.sort(tuple(operands) + (iota,),
                        num_keys=len(operands) + 1)[-1]


NINE = ["string", "string", "string", "string", "int32", "int32", "int32",
        "string", "gid"]


@pytest.mark.parametrize("kinds,n_words,fits", [
    (["int64"], 1, True),
    (["string"], None, True),
    (["int64", "int32", "int32"], 1, True),
    (["string", "bool", "int32"], None, True),
    (NINE, 4, True),                  # 95 bits: four 31-bit words
    (NINE, 5, True),
    (NINE[:-1] + ["gid32"], None, True),
    (NINE, 3, False),                 # 93 bits do not hold 95
    (NINE, 1, False),
    (["wide", "string"], 2, False),   # a span past 2^62 fits no word
    (["int64", "wide"], 3, False),
], ids=lambda v: "-".join(v) if isinstance(v, list) else str(v))
def test_folded_sort_matches_variadic(kinds, n_words, fits):
    rng = np.random.default_rng(len(kinds) * 7 + (n_words or 0))
    cols = _keys(rng, kinds)
    orders = [S.SortOrder(bool(i % 3), bool(i % 2)) for i in range(len(cols))]
    folded = S.fold_keys(cols, orders, jnp.int32(ROWS), CAP, n_words)
    assert folded is not None
    held = S.held_bits(len(folded.words))
    assert folded.words[0].dtype == (jnp.int64 if len(folded.words) == 1
                                     else jnp.int32)
    if not fits:
        # the caller reads this and falls back; nothing else is promised
        assert int(folded.need_bits) > held
        return
    assert int(folded.need_bits) <= held
    if n_words is not None:
        assert len(folded.words) == n_words
    perm, words = S.sort_folded(folded)
    assert (perm[:ROWS] == _variadic(cols, orders)[:ROWS]).all()
    live = jnp.arange(CAP) < ROWS
    for back, want in zip(S.unfold_keys(folded, words, cols, orders, live),
                          gather_cols(cols, perm, live)):
        assert (back.validity == want.validity).all()
        assert (back.values == want.values).all()
        assert back.values.dtype == want.values.dtype


def test_unfoldable_keys_take_the_wider_sorts():
    rng = np.random.default_rng(3)
    wide = _keys(rng, ["wide"])
    floats = [_col(rng, rng.normal(size=CAP), T.DOUBLE)]
    for cols in (wide, floats, wide + floats):
        orders = [S.SortOrder() for _ in cols]
        assert S.fold_keys(cols, orders, jnp.int32(ROWS), CAP) is None
        gs = G.sorted_groups(cols, jnp.int32(ROWS), CAP)
        assert gs.folded is None
        assert gs.operands == S.unfolded_operands(cols) >= 2
        assert (gs.perm[:ROWS] == _variadic(cols, orders)[:ROWS]).all()


def test_groups_read_from_the_sorted_words():
    """sorted_groups' boundaries and sorted keys equal those of the keys
    gathered through the permutation and compared a key."""
    rng = np.random.default_rng(11)
    cols = _keys(rng, ["string", "int64", "gid"])
    gs = G.sorted_groups(cols, jnp.int32(ROWS), CAP, n_words=1)
    assert gs.operands == 1 and int(gs.folded.need_bits) <= S.held_bits(1)
    want = gather_cols(cols, gs.perm, gs.live)
    neq = np.zeros(CAP, bool)
    for c in want:
        v, m = np.asarray(c.values), np.asarray(c.validity)
        neq |= (v != np.roll(v, 1)) | (m != np.roll(m, 1))
    neq[0] = True
    assert (np.asarray(gs.boundary) == (neq & np.asarray(gs.live))).all()
    for back, c in zip(gs.sorted_keys, want):
        assert (back.values == c.values).all()
        assert (back.validity == c.validity).all()


# -- through the aggregate -----------------------------------------------------

N = 150_000     # a batch: capacity 2^18, where the key-stats probe runs


@pytest.fixture
def traced():
    tracing.drain()
    tracing.set_enabled(True)
    yield
    tracing.set_enabled(False)
    tracing.drain()


def _part(rng, base, span):
    return pa.table({
        "k": pa.array(base + rng.integers(0, span, N).astype(np.int64)),
        "d": pa.array(rng.integers(0, 50, N).astype(np.int32)),
        "s": pa.array(np.array(["a", "b", None, "d"], dtype=object)[
            rng.integers(0, 4, N)]),
        "v": pa.array(rng.integers(0, 1000, N).astype(np.int64)),
    })


def _grouped(tmp_path, parts):
    for i, t in enumerate(parts):
        pq.write_table(t, str(tmp_path / f"part-{i}.parquet"))
    spark = TpuSession()
    df = (spark.read_parquet(str(tmp_path), files_per_partition=len(parts))
          .group_by("k", "d", "s")
          .agg(F.sum(F.col("v")).alias("total"),
               F.count(None).alias("n")))
    got = {(r["k"], r["d"], r["s"]): (r["total"], r["n"])
           for r in df.collect().to_pylist()}
    spans = [s for s in tracing.drain()
             if s["name"].startswith("HashAggregate.")]
    pdf = pa.concat_tables(parts).to_pandas()
    want = {}
    for (k, d, s), g in pdf.groupby(["k", "d", "s"], dropna=False):
        s = None if s != s else s
        want[(k, d, s)] = (int(g["v"].sum()), len(g))
    return got, want, spans


def test_aggregate_folds_wide_keys_by_what_they_hold(tmp_path, traced):
    rng = np.random.default_rng(5)
    got, want, spans = _grouped(tmp_path, [_part(rng, 1 << 45, 2000)])
    assert got == want
    (agg,) = [s for s in spans if s["counts"].get("path") == "sort"
              and s["counts"].get("capacity", 0) >= 1 << 17]
    counts = agg["counts"]
    # an int64 key (11 bits held), an int32 (6), a string (2), 18 index bits
    assert counts["keys"] == 3 and counts["sort_operands"] == 1
    assert counts["packed_bits"] <= S.held_bits(1)


def test_keys_that_outgrow_their_words_fall_back(tmp_path, traced):
    """The chain predicts the words from the batch before; a batch whose keys
    need more is redone unchained, and the answer is the same."""
    rng = np.random.default_rng(6)
    parts = [_part(rng, 7, 2000), _part(rng, 7, 2000),
             _part(rng, 1 << 50, 1 << 52), _part(rng, 7, 2000)]
    got, want, spans = _grouped(tmp_path, parts)
    assert got == want
    words = [s["counts"]["sort_operands"] for s in spans
             if s["counts"].get("path") == "sort"
             and s["counts"].get("capacity", 0) >= 1 << 17]
    assert min(words) == 1 and max(words) == 3    # 63 bits, then 3 x 31
