"""The mesh data plane of PR 29, on 4 of the suite's 8 virtual devices: Q3's
text over the all_to_all exchange, a partition that lives and runs on its own
device, the exchange's row conservation and Spark-exact placement, ONE cached
exchange program a shape, and the exchange's spans and ledger sites."""

import collections

import jax
import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.columnar.batch import (ColumnarBatch, batch_device,
                                             batch_devices, batch_to_device)
from spark_rapids_tpu.runtime import fuse, movement, tracing
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.sql.tpch_queries import SQL_QUERIES

MESH = {"spark.rapids.tpu.mesh.enabled": True,
        "spark.rapids.tpu.mesh.devices": 4}


@pytest.fixture(scope="module")
def paths(tmp_path_factory):
    return tpch.generate(0.01, str(tmp_path_factory.mktemp("tpch_mesh")))


def session_over(paths, conf):
    spark = TpuSession(dict(conf))
    for name, df in tpch.load(spark, paths, files_per_partition=1).items():
        spark.create_or_replace_temp_view(name, df)
    return spark


@pytest.fixture(scope="module")
def mesh_q3(paths):
    """(session, rows of Q3's text run once): the programs are built."""
    spark = session_over(paths, MESH)
    return spark, spark.sql(SQL_QUERIES["q3"]).collect().to_pylist()


def physical_tree(df):
    from spark_rapids_tpu.plan.overrides import TpuOverrides
    return repr(TpuOverrides(df.session.conf).apply(df._plan))


class KernelSpy:
    """Where every fused kernel's outputs lie, by kernel name."""

    def __init__(self, monkeypatch):
        self.devices = collections.defaultdict(list)
        call = fuse.BatchKernel.__call__

        def spy(kernel, *args):
            out = call(kernel, *args)
            devs = set()
            for leaf in jax.tree_util.tree_leaves(out):
                if hasattr(leaf, "devices"):
                    devs |= leaf.devices()
            self.devices[kernel.name].append(frozenset(devs))
            return out
        monkeypatch.setattr(fuse.BatchKernel, "__call__", spy)


class CompileWatch:
    """Backend compiles (or loads from the persistent cache) since made."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


@pytest.fixture(scope="module")
def compile_watch():
    return CompileWatch()


# -- (a) the text, the reference, the one-device session ---------------------

def test_q3_text_over_the_mesh_equals_reference_and_one_device(paths,
                                                               mesh_q3):
    spark, rows = mesh_q3
    df = spark.sql(SQL_QUERIES["q3"])
    assert physical_tree(df).count("MeshExchangeExec") >= 5
    tpch.CHECKS["q3"](rows, tpch.np_q3(tpch.load_np(paths)))
    single = session_over(paths, {}).sql(SQL_QUERIES["q3"]).collect()
    assert len(rows) == single.num_rows == 10
    for got, want in zip(rows, single.to_pylist()):
        assert got["l_orderkey"] == want["l_orderkey"]
        assert got["o_orderdate"] == want["o_orderdate"]
        assert got["o_shippriority"] == want["o_shippriority"]
        assert got["revenue"] == pytest.approx(want["revenue"], rel=1e-12)


# -- (b) placement -------------------------------------------------------------

POST_EXCHANGE = ("HashJoin.", "HashAggregateExec", "ProjectExec", "SortExec",
                 "MeshExchange.slice")


def test_post_exchange_kernels_run_on_one_device_each(mesh_q3, monkeypatch):
    spark, _ = mesh_q3
    spy = KernelSpy(monkeypatch)
    spark.sql(SQL_QUERIES["q3"]).collect()
    mesh = set(jax.devices()[:4])
    seen = {name: devs for name, devs in spy.devices.items()
            if name.startswith(POST_EXCHANGE)}
    assert {"HashJoin.probe", "HashJoin.emit", "HashAggregateExec",
            "MeshExchange.slice"} <= set(seen)
    for name, runs in seen.items():
        for devs in runs:
            assert len(devs) == 1 and devs <= mesh, (name, devs)
    # each join's four partitions (two joins) on four different devices,
    # and so the aggregate's and every partition cut out of an exchange
    for name in ("HashJoin.probe", "HashJoin.emit", "HashAggregateExec",
                 "MeshExchange.slice"):
        assert {next(iter(d)) for d in seen[name]} == mesh, name
        per_device = collections.Counter(seen[name])
        assert len(set(per_device.values())) == 1, (name, per_device)
    # the SPMD step itself spans the mesh
    for name in ("MeshExchange.hash", "MeshExchange.range"):
        assert all(devs == mesh for devs in spy.devices[name]), name


def test_partition_batches_are_committed_to_their_device():
    ex, _ = made_up_exchange(n_rows=300)
    for d, dev in enumerate(jax.devices()[:4]):
        for b in ex.execute_partition(d):
            assert batch_devices(b) == {dev}
            assert batch_device(b) == dev
            assert all(c.data.committed for c in b.columns)


def test_batch_to_device_moves_once_and_is_nothing_at_home():
    ex, _ = made_up_exchange(n_rows=300)
    devs = jax.devices()[:4]
    b = next(iter(ex.execute_partition(2)))
    assert batch_to_device(b, devs[2]) is b
    assert batch_to_device(b, None) is b
    moved = batch_to_device(b, devs[0])
    assert batch_devices(moved) == {devs[0]} and batch_devices(b) == {devs[2]}
    assert moved.to_arrow().equals(b.to_arrow())
    assert moved.schema is b.schema and moved.num_rows == b.num_rows


def mesh_frame(spark, n, seed=3):
    rng = np.random.default_rng(seed)
    return spark.create_dataframe(pa.table({
        "k": np.arange(n, dtype=np.int64),
        "v": rng.uniform(0, 1, n)}))


def test_what_gathers_partitions_moves_them(monkeypatch):
    """A broadcast build over a mesh exchange (partitions of four chips in
    one relation), a nested-loop stream side on its own chip against a build
    on another, and a gather to one partition: each moves explicitly."""
    spark = TpuSession(dict(MESH))
    a, small = mesh_frame(spark, 120), spark.create_dataframe(
        pa.table({"j": np.arange(5, dtype=np.int64)}))
    stream_on_mesh = a.repartition(4, "k").join(small, how="cross")
    assert "MeshExchangeExec" in physical_tree(stream_on_mesh)
    assert stream_on_mesh.collect().num_rows == 600
    build_on_mesh = small.join(a.repartition(4, "k"), how="cross")
    got = build_on_mesh.collect()
    assert got.num_rows == 600
    assert sorted(set(got.column("k").to_pylist())) == list(range(120))
    top = a.repartition(4, "k").order_by("v").limit(7).collect()
    assert top.column("v").to_pylist() == sorted(
        a.collect().column("v").to_pylist())[:7]


# -- (c) the exchange conserves rows, each on the device its hash names ---------

def made_up_table(n_rows, seed=11):
    rng = np.random.default_rng(seed)
    keys = rng.integers(-50, 50, n_rows)
    words = np.array([f"w{i % 23}" for i in rng.integers(0, 1000, n_rows)],
                     dtype=object)
    return pa.table({
        "k": pa.array(keys, pa.int64(), mask=rng.uniform(size=n_rows) < 0.1),
        "s": pa.array(words, pa.string(),
                      mask=rng.uniform(size=n_rows) < 0.1),
        "x": pa.array(rng.uniform(-1, 1, n_rows), pa.float64(),
                      mask=rng.uniform(size=n_rows) < 0.2),
        "i": pa.array(np.arange(n_rows), pa.int32())})


def made_up_exchange(n_rows, keys=("k", "s"), child_partitions=3):
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.distributed.exchange import MeshExchangeExec
    from spark_rapids_tpu.exec.basic import ArrowScanExec
    from spark_rapids_tpu.shuffle.partitioning import HashPartitioner
    t = made_up_table(n_rows)
    conf = RapidsConf({k: str(v).lower() for k, v in MESH.items()})
    per = -(-n_rows // child_partitions)
    parts = [t.slice(i * per, per) for i in range(child_partitions)]
    ex = MeshExchangeExec(HashPartitioner([F.col(k) for k in keys], 4),
                          ArrowScanExec(parts, conf=conf), conf=conf)
    return ex, t


@pytest.mark.parametrize("keys", [("k",), ("s",), ("k", "s")])
def test_exchange_conserves_rows_on_their_spark_partition(keys):
    from spark_rapids_tpu.shuffle.partitioning import HashPartitioner
    ex, t = made_up_exchange(1000, keys)
    # what Spark's murmur3 says, worked out on one device off the mesh path
    whole = ColumnarBatch.from_arrow(t)
    want = np.asarray(HashPartitioner(
        [F.col(k) for k in keys], 4).bind(ex.output).part_ids(whole))[:1000]
    home = dict(zip(t.column("i").to_pylist(), want.tolist()))
    arrived = []
    for d, dev in enumerate(jax.devices()[:4]):
        for b in ex.execute_partition(d):
            assert batch_devices(b) == {dev}
            rows = b.to_arrow().to_pylist()
            assert rows and all(home[r["i"]] == d for r in rows), d
            arrived += rows
    as_sent = collections.Counter(tuple(r.values()) for r in t.to_pylist())
    assert collections.Counter(tuple(r.values()) for r in arrived) == as_sent
    assert len(arrived) == 1000
    assert len({home[r["i"]] for r in arrived}) == 4      # it does spread


def test_exchange_deals_rows_evenly_whatever_the_child_partitions():
    """A child of one partition must not put every row in shard 0 and pad
    the other three to its size: the capacity is a quarter's bucket."""
    from spark_rapids_tpu.columnar.vector import bucket_capacity
    tracing.set_enabled(True)
    try:
        tracing.drain()
        ex, _ = made_up_exchange(1000, child_partitions=1)
        assert sum(b.num_rows for d in range(4)
                   for b in ex.execute_partition(d)) == 1000
        spans = [s for s in tracing.drain()
                 if s["name"] == "MeshExchange.collective"]
    finally:
        tracing.set_enabled(False)
    assert [s["counts"]["capacity"] for s in spans] == [bucket_capacity(250)]


# -- (d) one exchange program a shape --------------------------------------------

def test_second_run_of_the_text_builds_no_program(mesh_q3, compile_watch):
    spark, first = mesh_q3
    kernels = {k for k in fuse._kernels if k[0] == "MeshExchange"}
    assert len(kernels) >= 5
    traces, compiles = fuse.stage_metrics()["traces"], compile_watch.n
    again = spark.sql(SQL_QUERIES["q3"]).collect().to_pylist()
    assert again == first
    assert fuse.stage_metrics()["traces"] == traces
    assert compile_watch.n == compiles
    assert {k for k in fuse._kernels if k[0] == "MeshExchange"} == kernels


def test_exchange_programs_are_named_for_the_device_trace():
    from spark_rapids_tpu.distributed import exchange as X
    ex, _ = made_up_exchange(100)
    step = X.exchange_step(ex.mesh, ex.output, 32, ex.partitioner, {})
    assert step.name == "MeshExchange.hash"
    assert fuse.program_name(step.name) == "srt_MeshExchange_hash"
    assert step._jit.__wrapped__.__name__ == "srt_MeshExchange_hash"
    # the same shape again is the same program; another capacity is not
    assert X.exchange_step(ex.mesh, ex.output, 32, ex.partitioner,
                           {}) is step
    assert X.exchange_step(ex.mesh, ex.output, 64, ex.partitioner,
                           {}) is not step
    assert X._slice_kernel(32) is X._slice_kernel(32)


def test_range_bounds_are_operands_not_a_reason_to_rebuild():
    """Two global sorts over data whose bounds differ replay one program."""
    spark = TpuSession(dict(MESH))
    out = []
    for seed in (5, 6):
        df = mesh_frame(spark, 400, seed).order_by("v")
        assert "RangePartitioner" in physical_tree(df)
        out.append(df.collect().column("v").to_pylist())
        assert out[-1] == sorted(out[-1])
        if seed == 5:
            ranges = {k for k in fuse._kernels
                      if k[0] == "MeshExchange" and k[4][0] == "range"}
            traces = fuse.stage_metrics()["traces"]
    assert out[0] != out[1]
    assert fuse.stage_metrics()["traces"] == traces
    assert {k for k in fuse._kernels
            if k[0] == "MeshExchange" and k[4][0] == "range"} == ranges


# -- (e) spans and the movement ledger ---------------------------------------------

def test_exchange_spans_hang_under_the_query_with_their_counts(paths):
    spark = session_over(paths, dict(
        MESH, **{"spark.rapids.tpu.sql.trace.enabled": True}))
    try:
        spark.sql(SQL_QUERIES["q3"]).collect()      # builds the programs
        tracing.drain()
        before = movement.snapshot()
        spark.sql(SQL_QUERIES["q3"]).collect()
        spans = tracing.drain()
        after = movement.snapshot()
    finally:
        tracing.set_enabled(False)
    by_id = {s["id"]: s for s in spans}
    roots = [s for s in spans if s["name"] == "query"
             and s["parent"] not in by_id]
    assert len(roots) == 1

    def under_root(s):
        while s["parent"] in by_id:
            s = by_id[s["parent"]]
        return s is roots[0]

    def named(name):
        return [s for s in spans if s["name"] == name]

    maps, ingests, colls = (named("MeshExchange." + n)
                            for n in ("map", "ingest", "collective"))
    assert len(maps) == len(ingests) == len(colls) == 6
    assert all(under_root(s) for s in maps + ingests + colls)
    for m, i, c in zip(maps, ingests, colls):
        assert m["counts"]["rows"] == i["counts"]["rows"] \
            == c["counts"]["rows"] > 0
        assert m["counts"]["d2h_bytes"] > 0 and i["counts"]["h2d_bytes"] > 0
        assert i["counts"]["capacity"] == c["counts"]["capacity"]
        assert c["counts"]["devices"] == 4
        assert c["counts"]["partitioner"] in ("hash", "range")
        assert c["counts"]["operand_bytes"] == (
            16 * c["counts"]["capacity"] * c["counts"]["row_bytes"])
    assert sorted(c["counts"]["partitioner"] for c in colls) \
        == ["hash"] * 5 + ["range"]
    # the count read is the stage's one sync, under the name the rest use
    syncs = [s for s in named("sync.count")
             if s["counts"].get("capacity") in
             {16 * c["counts"]["capacity"] for c in colls}]
    assert len(syncs) >= 6

    def gained(edge, site):
        key = next(k for k in after if k[0] == edge and k[2] == site)
        return {f: after[key][f] - before.get(key, {}).get(f, 0)
                for f in ("bytes", "payload_bytes", "transfers")}

    ici = gained("ici.collective", "mesh.exchange")
    assert ici["transfers"] == 6
    assert ici["payload_bytes"] == sum(
        c["counts"]["rows"] * c["counts"]["row_bytes"] for c in colls)
    assert ici["bytes"] == sum(c["counts"]["operand_bytes"] for c in colls)
    assert gained("d2h", "mesh.exchange.map")["bytes"] == sum(
        m["counts"]["d2h_bytes"] for m in maps)
    assert gained("h2d", "mesh.exchange.ingest")["bytes"] == sum(
        i["counts"]["h2d_bytes"] for i in ingests)


def test_no_conf_entry_was_added_for_the_mesh_cell():
    """The deployment is two keys that were there."""
    from spark_rapids_tpu import config
    keys = list(config.all_entries())
    assert [k for k in keys if k.startswith("spark.rapids.tpu.mesh.")] == [
        "spark.rapids.tpu.mesh.enabled", "spark.rapids.tpu.mesh.devices"]
