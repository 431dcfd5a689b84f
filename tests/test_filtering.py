"""ops/filtering.compact_cols against a plain NumPy `x[mask]`.

Every filter, join chain, sort-path aggregate and mesh exchange moves its
survivors to the front through this one kernel, and `maybe_host_resize` and
the chain's `slice_to_capacity` cut its output at a host count: what lies
past the count must be the dtype's default with validity false."""

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu  # noqa: F401  (x64)
from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.core import Col
from spark_rapids_tpu.ops.filtering import compact_cols, front_perm

SMALL, LARGE = 8, 1 << 20


def _mask(kind: str, cap: int) -> np.ndarray:
    rng = np.random.default_rng(cap)
    if kind == "none":
        return np.zeros(cap, bool)
    if kind == "all":
        return np.ones(cap, bool)
    if kind == "last":              # a single kept row, in the last slot
        m = np.zeros(cap, bool)
        m[-1] = True
        return m
    if kind == "first_dropped":     # rows ahead of the first kept one
        m = rng.random(cap) < 0.5
        m[:3] = False
        m[3] = True
        return m
    return rng.random(cap) < {"tenth": 0.1, "half": 0.5, "most": 0.9}[kind]


def _column(kind: str, cap: int):
    """(Col, host values, host validity) with nulls among the rows."""
    rng = np.random.default_rng(cap + len(kind))
    valid = rng.random(cap) < 0.8
    dictionary = None
    if kind == "int64":
        vals, dtype = rng.integers(-(1 << 62), 1 << 62, cap), T.LONG
    elif kind == "float64":
        vals, dtype = rng.standard_normal(cap), T.DOUBLE
    elif kind == "bool":
        vals, dtype = rng.random(cap) < 0.5, T.BOOLEAN
    else:                           # dictionary-coded strings: int32 codes
        dictionary = pa.array(["", "BUILDING", "MACHINERY", "x" * 40])
        vals = rng.integers(0, len(dictionary), cap).astype(np.int32)
        dtype = T.STRING
    vals = np.where(valid, vals, dtype.default_value()).astype(vals.dtype)
    return (Col(jnp.asarray(vals), jnp.asarray(valid), dtype, dictionary),
            vals, valid)


def _check(cols_in, mask):
    """The whole contract of one call: count, the stable front, the tail."""
    out, count = compact_cols([c for c, _, _ in cols_in], jnp.asarray(mask))
    n = int(count)
    assert n == int(mask.sum())
    for got, (col, vals, valid) in zip(out, cols_in):
        g_vals, g_valid = np.asarray(got.values), np.asarray(got.validity)
        assert g_vals.dtype == vals.dtype and g_vals.shape == vals.shape
        assert got.dtype == col.dtype and got.dictionary is col.dictionary
        np.testing.assert_array_equal(g_valid[:n], valid[mask])
        np.testing.assert_array_equal(g_vals[:n], vals[mask])
        assert not g_valid[n:].any()
        assert (g_vals[n:] == col.dtype.default_value()).all()


@pytest.mark.parametrize("cap", [SMALL, LARGE])
@pytest.mark.parametrize(
    "mask", ["none", "all", "last", "first_dropped", "tenth", "half", "most"])
def test_compact_cols_is_numpy_boolean_indexing(mask, cap):
    """Null-validity rows that the mask keeps are kept, as nulls; a kept row
    never reads as the default because a dropped neighbour did."""
    _check([_column("int64", cap), _column("float64", cap)], _mask(mask, cap))


@pytest.mark.parametrize("cap", [SMALL, LARGE])
@pytest.mark.parametrize("kind", ["int64", "float64", "bool", "dictionary"])
def test_compact_cols_keeps_every_column_type(kind, cap):
    _check([_column(kind, cap)], _mask("half", cap))


@pytest.mark.parametrize("kind", ["int64", "float64", "bool", "dictionary"])
def test_past_the_count_is_the_default_and_invalid(kind):
    """Rows the mask drops hold values far from the default and are VALID:
    none of them may show through behind the survivors."""
    cap = 64
    col, vals, _ = _column(kind, cap)
    loud = {"int64": np.int64(-7), "float64": np.float64("nan"),
            "bool": np.bool_(True), "dictionary": np.int32(3)}[kind]
    mask = _mask("tenth", cap)
    vals = np.where(mask, vals, loud).astype(vals.dtype)
    valid = np.ones(cap, bool)
    col = Col(jnp.asarray(vals), jnp.asarray(valid), col.dtype,
              col.dictionary)
    assert not mask.all()
    _check([(col, vals, valid)], mask)


@pytest.mark.parametrize("cap", [1, SMALL, 4096 + 8])
def test_front_perm_is_the_kept_rows_then_the_dropped(cap):
    """The helper both compact_cols and the parquet writer's null
    compaction call: the j-th kept row for j < count, the dropped rows
    behind them, every row once."""
    mask = _mask("half", cap) if cap > 1 else np.ones(1, bool)
    perm, count = front_perm(jnp.asarray(mask))
    perm, want = np.asarray(perm), np.nonzero(mask)[0]
    assert perm.dtype == np.int32 and perm.shape == (cap,)
    assert int(count) == want.size
    np.testing.assert_array_equal(perm[:want.size], want)
    np.testing.assert_array_equal(perm[want.size:], np.nonzero(~mask)[0])
