"""Test harness setup.

Tests run on a virtual 8-device CPU platform (mirrors the reference's ring-1/ring-2
strategy, SURVEY.md §4: protocol/memory logic testable without real hardware; the
driver separately dry-runs the multi-chip path). Env must be set before jax imports.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force CPU even when a TPU is attached
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# persistent XLA compile cache (the same helper chip_smoke.py, benchmark/run.py
# and the driver entry points use): a cold full suite on a small box is mostly
# LLVM compilation; repeated runs reload executables instead of re-compiling
from spark_rapids_tpu.runtime import compile_cache  # noqa: E402

compile_cache.enable()

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _bound_xla_code_memory():
    """Free compiled executables between test modules. XLA:CPU's LLVM JIT
    code memory is bounded: ~3000 live executables in one process make later
    compiles abort/segfault. The engine
    budgets its own fuse kernels; this drops everything else tests compile."""
    yield
    import gc
    from spark_rapids_tpu.runtime import fuse
    fuse.clear_kernels()
    jax.clear_caches()
    gc.collect()


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def make_table(n=1000, seed=0, with_nulls=True):
    """Random mixed-type pyarrow table, the data_gen.py analog
    (reference integration_tests/src/main/python/data_gen.py)."""
    r = np.random.default_rng(seed)
    null_mask = lambda: r.random(n) < 0.1 if with_nulls else np.zeros(n, bool)

    def witness(vals, mask):
        return pa.array([None if m else v for v, m in zip(vals.tolist(), mask)])

    ints = witness(r.integers(-1000, 1000, n, dtype=np.int32), null_mask())
    longs = witness(r.integers(-10**12, 10**12, n, dtype=np.int64), null_mask())
    doubles = witness(r.normal(0, 100, n), null_mask())
    floats = pa.array([None if m else float(np.float32(v)) for v, m in
                       zip(r.normal(0, 10, n), null_mask())], type=pa.float32())
    words = np.array(["apple", "banana", "cherry", "date", "elderberry", "fig",
                      "grape", "", "kiwi", "lemon"])
    strs = witness(words[r.integers(0, len(words), n)], null_mask())
    bools = witness(r.integers(0, 2, n).astype(bool), null_mask())
    # temporal + decimal columns (VERDICT r1 weak #4: the equivalence harness
    # cannot catch what it never generates) — dates span pre-epoch through
    # 2100, timestamps cover sub-second micros, decimal(12,2) covers signed
    # money-style values
    dates = pa.array([None if m else int(v) for v, m in
                      zip(r.integers(-10_000, 47_482, n), null_mask())],
                     type=pa.int32()).cast(pa.date32())
    ts = pa.array([None if m else int(v) for v, m in
                   zip(r.integers(-10**15, 4 * 10**15, n), null_mask())],
                  type=pa.int64()).cast(pa.timestamp("us", tz="UTC"))
    import decimal as _dec
    decs = pa.array([None if m else
                     _dec.Decimal(int(v)).scaleb(-2) for v, m in
                     zip(r.integers(-10**10, 10**10, n), null_mask())],
                    type=pa.decimal128(12, 2))
    return pa.table({
        "i": ints, "l": longs, "d": doubles, "f": floats, "s": strs, "b": bools,
        "dt": dates, "ts": ts, "dec": decs,
    })


@pytest.fixture
def mixed_table():
    return make_table()
