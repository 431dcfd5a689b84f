"""What the chip's compiler says, asked without the chip.

The TPU compiler is installed here and compiles for a v5e that is described,
not attached. Each case lowers one Pallas kernel, or one jitted XLA program
that only exists on a TPU, at the shapes the TPC-H SF 1 main path uses
(1 Mi-row scan batches, the radix kernel's lane cap) and compiles it
for one v5e chip. Nothing runs: a pass says the compiler accepts the program
and that it fits the device, not that its results are right (the interpret
mode tests in test_pallas.py cover the arithmetic, chip_smoke.py the chip).

The topology is described inside the module-scoped fixture below and nowhere
else: only one process may hold the TPU library, so this must not happen
while any module is imported, and every compile runs in this process.

Left out to keep the file cheap, compiled by hand for this chip instead: a
packed-int64 `lax.sort` at 1 Mi rows (37 s here; the join build and sort
spine), and the whole fused stage programs of q3/q5/q18, which chip_smoke.py
compiles on the chip itself.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import spark_rapids_tpu  # noqa: F401  (x64)
from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.core import Col
from spark_rapids_tpu.ops import pallas_kernels as PK

N = 1 << 20                 # io/filescan.py batch_rows
HBM_BYTES = 16 * 10**9      # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Steer the backend question the code asks (`jax.default_backend()` is
    'cpu' here) to its TPU answer, in the test and not in the program."""
    monkeypatch.setattr(PK, "_interpret", lambda: False)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _fits(compiled) -> bool:
    ma = compiled.memory_analysis()
    return (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes) < HBM_BYTES


def _kernel_cases():
    """(kernel, fn, [(shape, dtype)...]) at SF 1 shapes, one per table row."""
    return {
        # exchange partition step (<= 200 partitions) and the lane cap
        "radix": [
            (lambda ids: PK.radix_partition_permutation(ids, 200),
             [((N,), jnp.int32)]),
            (lambda ids: PK.radix_ranks(ids, PK.RADIX_MAX_PARTS),
             [((N,), jnp.int32)])],
        "onehot": [
            (lambda v, c: PK.onehot_sum_f32(v, c, 1000),
             [((N,), jnp.float32), ((N,), jnp.int32)])],
        "murmur3": [
            (lambda w, l, s: PK.murmur3_words(w, l, s),
             [((N, 4), jnp.int32), ((N,), jnp.int32), ((N,), jnp.int32)])],
    }


@pytest.mark.parametrize("kernel", list(PK.KERNELS))
def test_pallas_kernel_compiles_for_v5e(kernel, one_chip, as_on_tpu):
    """Every kernel in the switch table: Mosaic accepts it at SF 1 shapes
    and the kernel is in the program."""
    for fn, shapes in _kernel_cases()[kernel]:
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        compiled = _compile(fn, *args)
        assert "tpu_custom_call" in compiled.as_text()
        assert _fits(compiled)


def test_switch_table_names_every_routed_kernel():
    """should_use() is the table: an unknown name is an error, and off the
    TPU backend nothing is routed unless a test forces it."""
    assert set(PK.KERNELS) == {"radix", "onehot", "murmur3"}
    with pytest.raises(KeyError):
        PK.should_use("no_such_kernel")
    assert not any(PK.should_use(k) for k in PK.KERNELS)   # cpu backend


def test_dense_f64_group_sum_compiles(one_chip, as_on_tpu):
    """q1's small-domain group-by on a TPU (`use_matmul=True`, the
    scatter-free form): D masked f64 reductions in one pass."""
    from spark_rapids_tpu.ops.grouping import dense_group_sum
    compiled = _compile(
        lambda v, m, c: dense_group_sum(v, m, c, 16, True),
        jax.ShapeDtypeStruct((N,), jnp.float64, sharding=one_chip),
        jax.ShapeDtypeStruct((N,), jnp.bool_, sharding=one_chip),
        jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip))
    assert _fits(compiled)


@pytest.mark.parametrize("capacity", [N, 4 * N], ids=["batch", "merge"])
def test_compaction_compiles_to_one_scatter(capacity, one_chip):
    """Filter compaction (ops/filtering.compact_cols) at a 1 Mi-row batch
    and at the 4 Mi received slots the mesh exchange merges: a prefix sum,
    ONE scatter for the permutation and a gather a column, with no `while`
    (the `searchsorted` it replaced was a 19-step loop over the capacity)."""
    from spark_rapids_tpu.ops.filtering import compact_cols

    def fn(k, kv, x, xv, keep):
        cols, n = compact_cols([Col(k, kv, T.LONG), Col(x, xv, T.DOUBLE)],
                               keep)
        return [c.values for c in cols], [c.validity for c in cols], n

    b = jax.ShapeDtypeStruct((capacity,), jnp.bool_, sharding=one_chip)
    compiled = _compile(
        fn, jax.ShapeDtypeStruct((capacity,), jnp.int64, sharding=one_chip),
        b, jax.ShapeDtypeStruct((capacity,), jnp.float64, sharding=one_chip),
        b, b)
    hlo = compiled.as_text()
    assert "scatter" in hlo and "while" not in hlo
    assert _fits(compiled)


@pytest.mark.parametrize("keys, slots, bcap, rows", [
    ((jnp.int64,), 2 << 20, 1 << 18, N),
    ((jnp.int64, jnp.int32), 4 << 20, 1 << 18, 1 << 19)])
def test_dense_join_probe_compiles_without_a_loop(one_chip, as_on_tpu, keys,
                                                  slots, bcap, rows):
    """The direct-address probe of a unique build over a compact key domain
    (exec/joins.py mode `dense`) at q3's shapes, a 1 Mi-row lineitem batch
    against the 2 Mi-slot table of the filtered orders (capacity 256 Ki),
    and at q5's two-key hop, a 512 Ki-row batch against a bigint and an int
    key packed into a 4 Mi-slot table. Packing is elementwise and the lookup
    a gather, with no `while` (mode `one`'s `searchsorted` is a 19-step loop
    over the 64-bit key's halves, the rank path runs five of them a batch);
    the table is one scatter a build."""
    import functools
    from spark_rapids_tpu.exec import joins as XJ
    s32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    n = len(keys)

    def lookup(table, lims, vals, valid):
        cols = [Col(v, ok, T.LONG) for v, ok in zip(vals, valid)]
        packed, ok = XJ._pack_keys(cols, lims)
        return table[packed.astype(jnp.int32)], ok

    probe = _compile(
        lookup, jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((2, n), jnp.int64, sharding=one_chip),
        [jax.ShapeDtypeStruct((rows,), k, sharding=one_chip) for k in keys],
        [jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip)] * n)
    hlo = probe.as_text()
    assert "while" not in hlo and "gather" in hlo
    # a single key is `k - vmin`: packing multiplies from the second key on
    assert (" multiply(" in hlo) == (n > 1)
    assert _fits(probe)
    searched = _compile(
        lambda build, keys: jnp.searchsorted(build, keys),
        jax.ShapeDtypeStruct((bcap,), jnp.int64, sharding=one_chip),
        jax.ShapeDtypeStruct((rows,), jnp.int64, sharding=one_chip))
    assert "while" in searched.as_text()     # what the probe no longer pays
    table = _compile(
        functools.partial(XJ._dense_table, slots=slots),
        jax.ShapeDtypeStruct((bcap,), jnp.int64, sharding=one_chip), s32)
    assert "scatter" in table.as_text() and "while" not in table.as_text()
    assert _fits(table)


@pytest.fixture(scope="module")
def q5_chain_kernels():
    """The fused two-hop chain at q5's shapes (two 1 Mi-row batches of one
    partition; a 256 Ki build under a 2 Mi-slot table, then a 16 Ki build),
    run on the CPU with each kernel the operator builds captured: the first
    batch runs at the stream's capacity, the second at the bucket the first
    needed, 256 Ki (about 16 % of a batch survives, as q5's first chain
    keeps of lineitem). Returns {output capacity: (kernel, args)}."""
    import pyarrow as pa
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.exec import joins as XJ
    from spark_rapids_tpu.exec.basic import ArrowScanExec
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.runtime import fuse
    r = np.random.default_rng(1)
    st = pa.table({"k1": pa.array(r.integers(1, 1_500_001, 2 * N), pa.int64()),
                   "k2": pa.array(r.integers(1, 10_001, 2 * N), pa.int64()),
                   "x": pa.array(r.random(2 * N), pa.float64())})
    b1 = pa.table({"a": pa.array(np.sort(r.permutation(1_500_000)[:240_000]),
                                 pa.int64()),
                   "av": pa.array(np.arange(240_000), pa.int64())})
    b2 = pa.table({"b": pa.array(np.arange(1, 10_001), pa.int64()),
                   "bv": pa.array(np.arange(10_000), pa.int32())})
    conf = RapidsConf()
    inner = XJ.BroadcastHashJoinExec(
        "inner", [col("k1")], [col("a")],
        ArrowScanExec([st], conf=conf, batch_rows=N),
        ArrowScanExec([b1], conf=conf))
    chain = XJ.maybe_chain(XJ.BroadcastHashJoinExec(
        "inner", [col("k2")], [col("b")], inner,
        ArrowScanExec([b2], conf=conf)), conf)
    captured = {}
    real = fuse.call_fused

    def spy(key, name, build, args, eager):
        if name == "HashJoinChain.probe":      # key: ("join_chain", cap, ...)
            captured.setdefault(key[1], (build(), args))
        return real(key, name, build, args, eager)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fuse, "call_fused", spy)
        assert chain.execute_collect().num_rows > 200_000
    assert sorted(captured) == [N // 4, N]
    return captured


def _chain_hlo(captured, one_chip):
    kernel, args = captured
    return _compile(kernel, *jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=one_chip), args)).as_text()


def _lookup_tables(hlo):
    """The layout of each operand the chain's lookup fusions read (a hop's
    table and its position->row permutation)."""
    import re
    lookups = {m.group(1) for m in re.finditer(
        r"^%(fused_computation[\w.]*) \(.*?\n(.*?)^\}", hlo, re.M | re.S)
        if re.search(r" gather\(.*hop\d/lookup/", m.group(2))}
    entry = hlo[hlo.index("ENTRY"):]
    layout = dict(re.findall(r"^\s*(%[\w.\-]+) = (\S+) ", entry, re.M))
    return [layout[m.group(1)] for m in re.finditer(
        r"fusion\((%[\w.\-]+), .*calls=%(fused_computation[\w.]*)", entry)
        if m.group(2) in lookups]


def test_join_chain_reads_its_lookup_tables_from_fast_memory(
        one_chip, as_on_tpu, q5_chain_kernels):
    """The chain's first run of a stream capacity, at the batch's 1 Mi
    rows: the compiler moves each hop's table and position->row permutation
    into the chip's fast memory (`S(1)` in the layout) before the gathers
    that read them. A gather of 1 Mi rows from a table left in HBM took
    36 ms on a v5e where one from fast memory took 9 (PERF.md section 6):
    the permutation's gather has to depend on the table's gather alone, or
    the second hop's stays behind."""
    hlo = _chain_hlo(q5_chain_kernels[N], one_chip)
    assert "while" not in hlo
    tables = _lookup_tables(hlo)
    assert len(tables) == 4 and all("S(1)" in t for t in tables), tables


def test_join_chain_gathers_at_its_output_bucket(one_chip, as_on_tpu,
                                                 q5_chain_kernels):
    """The chain predicted at 256 Ki for a 1 Mi-row batch: only the lookups
    (and the build columns a later hop reads, `hop<i>/gather_cols`; none in
    this chain) gather 1 Mi rows. The compaction gathers the stream's
    columns and the hops' build rows through the first 256 Ki slots of its
    permutation, and each build column is gathered once behind it, at
    256 Ki (`deferred`). The four lookup tables still lie in `S(1)`."""
    import re
    hlo = _chain_hlo(q5_chain_kernels[N // 4], one_chip)
    assert "while" not in hlo
    gathers = [(int(m.group(1)), m.group(2)) for m in re.finditer(
        r"= \w+\[(\d+)\]\S* gather\(.*op_name=\"([^\"]*)\"", hlo)]
    assert {rows for rows, _ in gathers} == {N, N // 4}, gathers
    at_n = [name for rows, name in gathers if rows == N]
    assert len(at_n) == 4 and all(
        re.search(r"/hop\d/(lookup|gather_cols)/", n) for n in at_n), at_n
    assert any("/deferred/" in name for _, name in gathers), gathers
    tables = _lookup_tables(hlo)
    assert len(tables) == 4 and all("S(1)" in t for t in tables), tables


def test_parquet_dictionary_decode_compiles(one_chip, as_on_tpu):
    """One encoded lineitem page → rows: the jnp bit-unpack, dictionary
    gather, definition-level spread (ops/parquet_decode.decode_page_cols);
    no hand-written kernel is in the program."""
    from spark_rapids_tpu.ops import parquet_decode as PD
    bw = 6
    spec = PD.EncodedPageSpec(bw, N, N * bw // 8, N, "float64", False, 0.0)
    s32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = _compile(
        lambda w, d, dl, npres, n: PD.decode_page_cols(spec, w, d, dl,
                                                       npres, n),
        jax.ShapeDtypeStruct((N * bw // 8,), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((50,), jnp.float64, sharding=one_chip),
        jax.ShapeDtypeStruct((N,), jnp.bool_, sharding=one_chip), s32, s32)
    assert "tpu_custom_call" not in compiled.as_text()
    assert _fits(compiled)


@pytest.mark.parametrize("max_bw,dict_dtype", [(2, jnp.int32),
                                               (17, jnp.int64)])
def test_parquet_runs_decode_compiles(one_chip, as_on_tpu, max_bw,
                                      dict_dtype):
    """One lineitem chunk from its segment table → rows
    (ops/parquet_decode.decode_runs_cols): a flag column's string codes, and
    l_orderkey's pages of growing bit width."""
    from spark_rapids_tpu.ops import parquet_decode as PD
    is_string = dict_dtype == jnp.int32
    spec = PD.EncodedRunsSpec(max_bw, 512, N, N * max_bw // 8, N,
                              jnp.dtype(dict_dtype).name, is_string, 0)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = _compile(
        lambda b, t, d, dl, n: PD.decode_runs_cols(spec, b, t, d, dl, n),
        shape((spec.bcap,), jnp.uint8), shape((4, spec.scap), jnp.int32),
        shape((8 if is_string else 1 << 17,), dict_dtype),
        shape((N,), jnp.bool_), shape((), jnp.int32))
    assert _fits(compiled)


def test_mesh_all_to_all_exchange_compiles_for_four_chips(topo, as_on_tpu):
    """The mesh data plane's exchange step (distributed/exchange.row_exchange
    under shard_map) on a 4-device mesh built from the described chips: the
    compiler puts an all-to-all in, and each device's share fits."""
    from spark_rapids_tpu.distributed.exchange import row_exchange
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    cap = 1 << 18

    def shard_step(k, kv, x, xv, nrows):
        cols = [Col(k[0], kv[0], T.LONG), Col(x[0], xv[0], T.DOUBLE)]
        pids = (k[0] & jnp.int64(3)).astype(jnp.int32)
        merged, m_rows = row_exchange(cols, nrows[0], pids, 4, cap)
        return (tuple(c.values[None] for c in merged)
                + tuple(c.validity[None] for c in merged) + (m_rows[None],))

    spec = P("data", None)
    step = jax.shard_map(shard_step, mesh=mesh,
                         in_specs=(spec,) * 4 + (P("data"),),
                         out_specs=(spec,) * 4 + (P("data"),))
    sh = NamedSharding(mesh, spec)

    def arg(dt):
        return jax.ShapeDtypeStruct((4, cap), dt, sharding=sh)

    compiled = _compile(
        step, arg(jnp.int64), arg(jnp.bool_), arg(jnp.float64),
        arg(jnp.bool_),
        jax.ShapeDtypeStruct((4,), jnp.int32,
                             sharding=NamedSharding(mesh, P("data"))))
    assert "all-to-all" in compiled.as_text()
    assert _fits(compiled)


def test_mesh_exchange_program_of_q3_at_sf1_compiles_for_four_chips(
        topo, as_on_tpu):
    """The exchange as the program builds it since PR 29 (`exchange_step`
    from the kernel table, named for the trace) at the shape Q3's lineitem
    side has at SF 1 with its rows dealt evenly: 4 columns, 1 Mi rows a
    shard; and the cut of one partition out of its chip's piece."""
    from spark_rapids_tpu.distributed import exchange as X
    from spark_rapids_tpu.expr.core import BoundReference
    from spark_rapids_tpu.shuffle.partitioning import HashPartitioner
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    schema = T.StructType([T.StructField("l_orderkey", T.LONG, True),
                           T.StructField("l_extendedprice", T.DOUBLE, True),
                           T.StructField("l_discount", T.DOUBLE, True),
                           T.StructField("l_shipdate", T.DateType(), True)])
    step = X.exchange_step(
        mesh, schema, N, HashPartitioner([BoundReference(0, T.LONG)], 4), {})
    assert step._jit.__wrapped__.__name__ == "srt_MeshExchange_hash"
    sh = NamedSharding(mesh, P("data", None))
    dtypes = [f.data_type.jnp_dtype for f in schema.fields]
    compiled = step._jit.lower(
        *[jax.ShapeDtypeStruct((4, N), dt, sharding=sh) for dt in dtypes],
        *[jax.ShapeDtypeStruct((4, N), jnp.bool_, sharding=sh)
          for _ in dtypes],
        jax.ShapeDtypeStruct((4,), jnp.int32,
                             sharding=NamedSharding(mesh, P("data")))
    ).compile()
    hlo = compiled.as_text()
    # five compactions (one a destination, one over the 4 Mi received
    # slots), each one scatter: no `searchsorted` loop is left in it
    assert "all-to-all" in hlo and "while" not in hlo
    assert _fits(compiled)
    one = SingleDeviceSharding(topo.devices[2])
    cut = X._slice_kernel(N)._jit.lower(
        tuple(jax.ShapeDtypeStruct((1, 4 * N), dt, sharding=one)
              for dt in dtypes),
        tuple(jax.ShapeDtypeStruct((1, 4 * N), jnp.bool_, sharding=one)
              for _ in dtypes), 760000).compile()
    assert _fits(cut)


def test_rollup_group_sort_at_q67_shapes_folds_into_three_operands(one_chip):
    """The aggregate's update program over TPC-DS q67's expanded batch (nine
    keys: four item strings, three int32 dates, the store id, the grouping
    id; 8 Mi slots), its keys folded by what they hold (87 bits at SF 1,
    three 31-bit words, as the key-stats probe finds): the widest sort the
    program holds takes three int32 operands where the comparator sort took
    20 (the chip's compiler takes its time by them), and the program fits
    the chip."""
    import re
    import pyarrow as pa
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.exec.aggregate import HashAggregateExec
    from spark_rapids_tpu.exec.basic import ArrowScanExec
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.core import Alias, EvalContext, col
    cap = 8 << 20
    strings = {"i_category": 10, "i_class": 100, "i_brand": 1000,
               "i_product_name": 18000, "s_store_id": 12}
    names = ["i_category", "i_class", "i_brand", "i_product_name", "d_year",
             "d_qoy", "d_moy", "s_store_id", "spark_grouping_id"]
    tiny = pa.table(
        {n: pa.array(["x"]) if n in strings else pa.array([1], pa.int32())
         for n in names} | {"sales": pa.array([1], pa.int64())})
    conf = RapidsConf()
    agg = HashAggregateExec([col(n) for n in names],
                            [Alias(Sum(col("sales")), "sumsales")],
                            ArrowScanExec([tiny], conf=conf), conf=conf)

    def shape(dtype):
        return jax.ShapeDtypeStruct((cap,), dtype, sharding=one_chip)

    cols = [Col(shape(jnp.int32), shape(jnp.bool_),
                T.STRING if n in strings else T.INT,
                pa.array([f"{i:05d}" for i in range(strings[n])])
                if n in strings else None) for n in names]
    cols.append(Col(shape(jnp.int64), shape(jnp.bool_), T.LONG))
    rows = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def kernel(cols, num_rows):
        out, n, need, _facts = agg._agg_kernel(
            EvalContext(cols, num_rows, cap), merge=False, n_words=3)
        return out, n, need

    lowered = jax.jit(kernel).lower(cols, rows)
    sorts = [len(m.group(1).split(",")) for m in re.finditer(
        r'"?stablehlo\.sort"?\(([^)]*)\)', lowered.as_text())]
    assert sorts and max(sorts) == 3, sorts
    assert "xi64>" not in "".join(re.findall(
        r'stablehlo\.sort.*?\n', lowered.as_text()))
    assert _fits(lowered.compile())
