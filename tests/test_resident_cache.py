"""``df.cache()`` on the device tier as a deployment: a table read once, kept
in HBM as the scan produced it, and queried again and again through
``session.sql()`` (plan/cache.py; the benchmark's ``tpch_sf1_resident``).

TPC-H at SF 0.01 from the generator's seed, every answer against the
independent NumPy oracles of ``benchmarks/tpch.py``, which read the Parquet
files through pyarrow and never the cache."""

import jax
import pytest

from spark_rapids_tpu import config
from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.columnar.vector import bucket_capacity
from spark_rapids_tpu.plan.cache import CacheNode
from spark_rapids_tpu.runtime import movement, tracing
from spark_rapids_tpu.runtime.memory import DeviceManager, TierEnum
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.sql.tpch_queries import SQL_QUERIES

SF = 0.01
CACHED = {"q1": ["lineitem"], "q3": ["orders", "lineitem"]}
ORACLE = {"q1": (tpch.np_q1, tpch._check_q1), "q3": (tpch.np_q3, tpch._check_q3)}

_backend_compiles = [0]


def _count_compile(event, seconds, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _backend_compiles[0] += 1


jax.monitoring.register_event_duration_secs_listener(_count_compile)


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    paths = tpch.generate(SF, str(tmp_path_factory.mktemp("tpch")))
    return paths, tpch.load_np(paths)


def resident_session(paths, tables):
    """A session whose views of ``tables`` are cached DataFrames."""
    spark = TpuSession()
    dfs = tpch.load(spark, paths, files_per_partition=2)
    cached = {t: dfs[t].cache() for t in tables}
    for t, df in cached.items():
        spark.create_or_replace_temp_view(t, df)
    return spark, cached


@pytest.fixture
def traced():
    tracing.drain()
    tracing.set_enabled(True)
    yield
    tracing.set_enabled(False)
    tracing.drain()


def h2d_bytes(*sites) -> int:
    """The movement ledger's h2d bytes at sites that start with one of
    ``sites``."""
    return sum(rec["bytes"] for (edge, _link, site), rec
               in movement.snapshot().items()
               if edge == "h2d" and site.startswith(sites))


SCAN_SITES = ("scan.", "batch.from_arrow")


def cache_site() -> dict:
    sites = {s["site"]: s for s in
             DeviceManager.get().catalog.heap_snapshot()["sites"]}
    s = sites.get("cache.device", {})
    return {k: s.get(k, 0) for k in ("buffers", "live_bytes", "device_bytes",
                                     "retained_bytes")}


def queries_of(spans):
    """[(root, [its descendants])] a recorded query, oldest first."""
    kids = {}
    for s in spans:
        kids.setdefault(s["parent"], []).append(s)
    out = []
    for root in sorted((s for s in spans if s["name"] == "query"),
                       key=lambda s: s["t0"]):
        made, todo = [], [root]
        while todo:
            s = todo.pop()
            made.append(s)
            todo.extend(kids.get(s["id"], ()))
        out.append((root, made))
    return out


@pytest.mark.parametrize("query", ["q1", "q3"])
def test_text_over_cached_views_equals_the_oracle_and_the_uncached_rows(
        data, query):
    """Q1 over a cached leaf; Q3 over cached ``orders`` and ``lineitem``, so
    a cached leaf under a join's build and stream sides is covered."""
    paths, tb = data
    oracle, check = ORACLE[query]
    plain = TpuSession()
    tpch.load(plain, paths, files_per_partition=2)
    want = plain.sql(SQL_QUERIES[query]).collect().to_pylist()
    spark, cached = resident_session(paths, CACHED[query])
    try:
        for _ in range(2):   # the run that fills the cache, and one that reads
            got = spark.sql(SQL_QUERIES[query]).collect().to_pylist()
            check(got, oracle(tb))
            assert len(got) == len(want)
            for g, w in zip(got, want):
                assert list(g) == list(w)
                for a, b in zip(g.values(), w.values()):
                    if isinstance(b, float):
                        assert abs(a - b) <= 1e-12 * max(1.0, abs(b)), (g, w)
                    else:
                        assert a == b, (g, w)
    finally:
        for df in cached.values():
            df.unpersist()


@pytest.fixture
def resident_q1(data, traced):
    """Q1 run twice over a cached ``lineitem`` with the spans on:
    (session, cached DataFrame, rows of the first run, spans, oracle rows,
    backend compiles and scan-site h2d bytes of the second run)."""
    paths, tb = data
    spark, cached = resident_session(paths, ["lineitem"])
    first = spark.sql(SQL_QUERIES["q1"]).collect().to_pylist()
    compiles, h2d = _backend_compiles[0], h2d_bytes(*SCAN_SITES)
    second = spark.sql(SQL_QUERIES["q1"]).collect().to_pylist()
    after = {"compiles": _backend_compiles[0] - compiles,
             "scan_h2d": h2d_bytes(*SCAN_SITES) - h2d}
    assert second == first
    yield spark, cached["lineitem"], first, tracing.recorded(), tb, after
    cached["lineitem"].unpersist()


def test_the_second_query_scans_nothing_and_compiles_nothing(resident_q1):
    _spark, _df, rows, spans, tb, after = resident_q1
    tpch._check_q1(rows, tpch.np_q1(tb))
    (_r1, first), (_r2, second) = queries_of(spans)
    assert any(s["name"].startswith("FileScan.") for s in first)
    assert not [s["name"] for s in second
                if s["name"].startswith(("FileScan", "scan."))]
    assert after == {"compiles": 0, "scan_h2d": 0}


@pytest.mark.parametrize("target, batches", [(None, 1), ("3m", 2)])
def test_cached_batches_are_coalesced_to_the_target_at_bucket_capacities(
        data, traced, target, batches):
    """A partition under the engine's target batch size is ONE cached batch;
    a smaller target (the conf every coalesce obeys, not a switch of the
    cache) cuts it into several, none a program of a size of its own: every
    capacity is its row count's bucket. The answer is the oracle's either way."""
    paths, tb = data
    conf = {} if target is None else {
        "spark.rapids.tpu.sql.batchSizeBytes": target}
    spark = TpuSession(conf)
    dfs = tpch.load(spark, paths, files_per_partition=4)
    cached = dfs["lineitem"].cache()
    spark.create_or_replace_temp_view("lineitem", cached)
    try:
        got = spark.sql(SQL_QUERIES["q1"]).collect().to_pylist()
        tpch._check_q1(got, tpch.np_q1(tb))
        (part,) = cached._plan._device_batches      # 4 files, one partition
        scanned = [s for s in tracing.recorded()
                   if s["name"].startswith("FileScan.")]
        assert len(scanned) == 4 and len(part) == batches
        for sb in part:
            assert sb.capacity == bucket_capacity(sb.num_rows)
        assert sum(sb.num_rows for sb in part) == len(tb["lineitem"]["l_tax"])
        assert spark.sql(SQL_QUERIES["q1"]).collect().to_pylist() == got
    finally:
        cached.unpersist()


def test_materialize_and_read_spans_carry_their_counts(resident_q1):
    _spark, df, _rows, spans, tb, _after = resident_q1
    node = df._plan
    held = [sb for part in node._device_batches for sb in part]
    n = len(tb["lineitem"]["l_tax"])
    (mat,) = [s for s in spans if s["name"] == "cache.materialize"]
    assert mat["counts"] == {
        "tier": "device", "rows": n, "partitions": len(node._device_batches),
        "batches": len(held), "capacity": sum(sb.capacity for sb in held),
        "columns": len(df.columns), "bytes": sum(sb.size for sb in held)}
    assert mat["counts"]["bytes"] >= 60 * n
    (r1, first), (r2, second) = queries_of(spans)
    assert mat in first and mat not in second    # once a node
    for root, made in ((r1, first), (r2, second)):
        reads = [s for s in made if s["name"] == "CachedScan.read"]
        assert len(reads) == len(held)
        assert sum(s["counts"]["rows"] for s in reads) == n
        assert ({(s["counts"]["capacity"], s["counts"]["bytes"])
                 for s in reads}
                == {(sb.capacity, sb.size) for sb in held})
        assert {s["counts"]["tier"] for s in reads} == {"device"}
        assert all(root["t0"] <= s["t0"] and s["t1"] <= root["t1"]
                   for s in reads)


@pytest.mark.parametrize("tier", [TierEnum.HOST, TierEnum.DISK])
def test_a_demoted_batch_comes_back_with_the_same_rows_and_says_so(
        resident_q1, tier, tmp_path):
    spark, df, rows, _spans, _tb, _after = resident_q1
    cat = DeviceManager.get().catalog
    held = [sb for part in df._plan._device_batches for sb in part]
    saved = cat.host_budget, cat._spill_dir
    try:
        if tier == TierEnum.DISK:
            cat.host_budget, cat._spill_dir = 0, str(tmp_path)
        cat.synchronous_spill(0)
        assert {cat.get_tier(sb.buffer_id) for sb in held} == {tier}
        assert cache_site()["device_bytes"] == 0
        tracing.drain()
        moved = h2d_bytes("cache.unspill")
        assert spark.sql(SQL_QUERIES["q1"]).collect().to_pylist() == rows
    finally:
        cat.host_budget, cat._spill_dir = saved
    reads = [s for s in tracing.recorded() if s["name"] == "CachedScan.read"]
    assert [s["counts"]["tier"] for s in reads] == [tier.lower()] * len(held)
    assert (h2d_bytes("cache.unspill") - moved
            == sum(sb.size for sb in held))
    # it came back to stay: the next query finds every batch in HBM again
    assert {cat.get_tier(sb.buffer_id) for sb in held} == {TierEnum.DEVICE}
    tracing.drain()
    moved = h2d_bytes("cache.unspill")
    assert spark.sql(SQL_QUERIES["q1"]).collect().to_pylist() == rows
    reads = [s for s in tracing.recorded() if s["name"] == "CachedScan.read"]
    assert {s["counts"]["tier"] for s in reads} == {"device"}
    assert h2d_bytes("cache.unspill") == moved


def test_unpersist_returns_the_retained_bytes(data):
    paths, _tb = data
    before = cache_site()
    spark, cached = resident_session(paths, ["lineitem"])
    spark.sql(SQL_QUERIES["q1"]).collect()
    held = cache_site()
    assert held["buffers"] > before["buffers"]
    assert (held["retained_bytes"] - before["retained_bytes"]
            == held["device_bytes"] - before["device_bytes"] > 0)
    plain = cached["lineitem"].unpersist()
    assert cache_site() == before
    assert not isinstance(plain._plan, CacheNode)
    # and the cache fills again when asked again
    assert spark.sql(SQL_QUERIES["q1"]).collect().num_rows > 0
    assert cache_site()["buffers"] == held["buffers"]
    cached["lineitem"].unpersist()
    assert cache_site() == before


def test_no_conf_entry_chooses_the_cached_form():
    assert len(config.all_entries()) == 139
    assert [k for k in config.all_entries() if ".sql.cache." in k] == [
        "spark.rapids.tpu.sql.cache.serializer"]
