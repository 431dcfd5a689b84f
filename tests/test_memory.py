"""Spill framework tests — mirrors the reference's RapidsBufferCatalogSuite /
RapidsDeviceMemoryStoreSuite / RapidsDiskStoreSuite (SURVEY.md §4 ring 2, runnable on
the CPU backend like ring 1)."""

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu.runtime.memory as mem_mod

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.runtime.memory import (
    BufferCatalog, DeviceManager, SpillableColumnarBatch, TierEnum,
    ACTIVE_ON_DECK_PRIORITY, OUTPUT_FOR_SHUFFLE_INITIAL_PRIORITY,
)


def make_batch(n=100, seed=0):
    r = np.random.default_rng(seed)
    t = pa.table({
        "a": pa.array(r.integers(0, 1000, n), type=pa.int64()),
        "b": pa.array(r.normal(size=n)),
        "s": pa.array([["x", "yy", "zzz"][i % 3] for i in range(n)]),
    })
    return ColumnarBatch.from_arrow(t), t


def test_add_and_acquire_roundtrip(tmp_path):
    cat = BufferCatalog(device_budget=1 << 30, host_budget=1 << 30,
                        spill_dir=str(tmp_path))
    batch, t = make_batch()
    bid = cat.add_batch(batch)
    assert cat.get_tier(bid) == TierEnum.DEVICE
    out = cat.acquire_batch(bid)
    assert out.to_arrow().equals(t)
    cat.remove(bid)
    assert cat.num_buffers == 0
    assert cat.device_bytes == 0


def test_budget_spills_to_host_then_disk(tmp_path):
    batch, t = make_batch()
    one = batch.device_memory_size()
    # room for ~2 batches on device and ~1 on host → 3rd add pushes one to disk
    cat = BufferCatalog(device_budget=int(one * 2.5), host_budget=int(one * 1.2),
                        spill_dir=str(tmp_path))
    ids = [cat.add_batch(make_batch(seed=i)[0]) for i in range(4)]
    tiers = [cat.get_tier(i) for i in ids]
    assert tiers.count(TierEnum.DEVICE) <= 2
    assert TierEnum.HOST in tiers or TierEnum.DISK in tiers
    assert cat.device_bytes <= cat.device_budget
    assert cat.host_bytes <= cat.host_budget
    # every buffer still readable from any tier, bit-identical
    for i, bid in enumerate(ids):
        got = cat.acquire_batch(bid).to_arrow()
        assert got.equals(make_batch(seed=i)[1])
    assert cat.spilled_to_host_bytes > 0


def test_spill_priority_order(tmp_path):
    batch, _ = make_batch()
    one = batch.device_memory_size()
    cat = BufferCatalog(device_budget=one * 10, host_budget=one * 10,
                        spill_dir=str(tmp_path))
    shuffle_id = cat.add_batch(make_batch(seed=1)[0],
                               priority=OUTPUT_FOR_SHUFFLE_INITIAL_PRIORITY)
    active_id = cat.add_batch(make_batch(seed=2)[0], priority=ACTIVE_ON_DECK_PRIORITY)
    spilled = cat.synchronous_spill(int(one * 1.5))
    assert spilled > 0
    # the low-priority shuffle output spilled first; the active batch stayed
    assert cat.get_tier(shuffle_id) != TierEnum.DEVICE
    assert cat.get_tier(active_id) == TierEnum.DEVICE


def test_unspill_promotes_back(tmp_path):
    batch, t = make_batch()
    one = batch.device_memory_size()
    cat = BufferCatalog(device_budget=one * 10, host_budget=one * 10,
                        spill_dir=str(tmp_path), unspill=True)
    bid = cat.add_batch(batch)
    cat.synchronous_spill(0)
    assert cat.get_tier(bid) == TierEnum.HOST
    out = cat.acquire_batch(bid)
    assert cat.get_tier(bid) == TierEnum.DEVICE
    assert out.to_arrow().equals(t)


def test_spillable_columnar_batch_lifecycle(tmp_path):
    DeviceManager.reset()
    batch, t = make_batch()
    scb = SpillableColumnarBatch(batch)
    try:
        assert scb.num_rows == 100
        assert scb.get_batch().to_arrow().equals(t)
    finally:
        scb.close()
    with pytest.raises(mem_mod.BufferClosedError):
        scb.get_batch()


def test_spill_callback_feeds_metrics(tmp_path):
    batch, _ = make_batch()
    one = batch.device_memory_size()
    cat = BufferCatalog(device_budget=one * 10, host_budget=one * 10,
                        spill_dir=str(tmp_path))
    seen = []
    cat.add_batch(batch, spill_callback=seen.append)
    cat.synchronous_spill(0)
    assert seen and seen[0] == one


def test_oom_dump_dir_and_strict_raise(tmp_path):
    """When spill cannot reach the budget, allocator state is dumped
    (spark.rapids.tpu.memory.hbm.oomDumpDir, reference oomDumpDir) and
    strict mode (hbm.strictBudget, the default) raises a retryable
    DeviceOomError with the spillable/pinned breakdown, rolling the failed
    registration back out of the catalog."""
    from spark_rapids_tpu.runtime.memory import (ACTIVE_ON_DECK_PRIORITY,
                                                 BufferCatalog)
    from spark_rapids_tpu.runtime.retry import DeviceOomError
    cat = BufferCatalog(device_budget=1, host_budget=1 << 30,
                        oom_dump_dir=str(tmp_path))
    b, _ = make_batch(64)
    # a single unspillable-situation: add under a tiny budget; after spilling
    # everything else (nothing), the new buffer itself keeps us over budget
    with pytest.raises(DeviceOomError) as ei:
        cat.add_batch(b, ACTIVE_ON_DECK_PRIORITY)
    assert ei.value.retryable and ei.value.budget == 1
    assert "spillable" in str(ei.value)
    # rollback: the phantom registration must not stay charged
    assert cat.num_buffers == 0 and cat.device_bytes == 0
    dumps = list(tmp_path.glob("hbm-oom-*.txt"))
    assert dumps, "expected an OOM dump file"
    txt = dumps[0].read_text()
    assert "device_bytes=" in txt and "buffer_id" in txt
    # per-tier spillable-vs-pinned breakdown (postmortem satellite)
    assert "tier=DEVICE spillable_bytes=" in txt and "pinned_bytes=" in txt


def test_lenient_budget_keeps_legacy_over_budget(tmp_path):
    """strictBudget=false restores the pre-retry behavior: the catalog stays
    (knowingly) over budget instead of raising."""
    from spark_rapids_tpu.runtime.memory import BufferCatalog
    cat = BufferCatalog(device_budget=1, host_budget=1 << 30,
                        strict_budget=False, oom_dump_dir=str(tmp_path))
    b, t = make_batch(64)
    bid = cat.add_batch(b)
    assert cat.get_tier(bid) == TierEnum.DEVICE
    assert cat.device_bytes > cat.device_budget
    assert cat.acquire_batch(bid).to_arrow().equals(t)


def test_direct_spill_store_roundtrip(tmp_path):
    """GDS-analog batched aligned store (reference RapidsGdsStore +
    BatchSpiller): aligned offsets, batching into shared files, refcounted
    deletion."""
    from spark_rapids_tpu.runtime.direct_spill import ALIGN, DirectSpillStore
    st = DirectSpillStore(str(tmp_path / "d"), batch_bytes=1 << 14)
    payloads = [bytes([i]) * (100 + 1000 * i) for i in range(8)]
    handles = [st.write(p) for p in payloads]
    for h, p in zip(handles, payloads):
        assert h[1] % ALIGN == 0          # aligned offsets
        assert st.read(h) == p
    # several buffers share batch files (BatchSpiller coalescing)
    assert len({h[0] for h in handles}) < len(handles)
    for h in handles:
        st.delete(h)
    import os
    leftover = [f for f in os.listdir(tmp_path / "d")]
    assert len(leftover) <= 1             # only the open batch file may remain
    st.close()


def test_direct_spill_through_catalog(tmp_path):
    """Disk-tier spills ride the direct store when enabled; reads are
    bit-identical across tiers and removal cleans the blobs."""
    batch, t = make_batch()
    one = batch.device_memory_size()
    cat = BufferCatalog(device_budget=int(one * 1.2), host_budget=int(one * 0.5),
                        spill_dir=str(tmp_path), direct_spill=True,
                        direct_batch_bytes=1 << 16)
    ids = [cat.add_batch(make_batch(seed=i)[0]) for i in range(4)]
    tiers = [cat.get_tier(i) for i in ids]
    assert TierEnum.DISK in tiers
    for i, bid in enumerate(ids):
        assert cat.acquire_batch(bid).to_arrow().equals(make_batch(seed=i)[1])
    for bid in ids:
        cat.remove(bid)
    assert cat.num_buffers == 0


def test_direct_spill_with_unspill(tmp_path):
    """unspill + direct store: reading a direct-spilled buffer promotes it
    back to the device tier and releases the blob refcount."""
    batch, t = make_batch()
    one = batch.device_memory_size()
    cat = BufferCatalog(device_budget=int(one * 1.2), host_budget=int(one * 0.5),
                        spill_dir=str(tmp_path), direct_spill=True,
                        unspill=True, direct_batch_bytes=1 << 16)
    ids = [cat.add_batch(make_batch(seed=i)[0]) for i in range(4)]
    disk = [bid for bid in ids if cat.get_tier(bid) == TierEnum.DISK]
    assert disk
    bid = disk[0]
    got = cat.acquire_batch(bid)
    assert cat.get_tier(bid) == TierEnum.DEVICE
    assert got.to_arrow().equals(make_batch(seed=ids.index(bid))[1])
    for b in ids:
        cat.remove(b)


def test_sort_spills_accumulated_inputs(monkeypatch, tmp_path):
    """SortExec holds its input batches in the spill store while
    accumulating (reference GpuSortExec + RequireSingleBatch): a tiny HBM
    budget forces spills mid-sort and the order is still correct."""
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.exec.basic import ArrowScanExec
    from spark_rapids_tpu.exec.sort import SortExec
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.ops.sorting import SortOrder
    from spark_rapids_tpu.runtime.memory import BufferCatalog, DeviceManager

    rng = np.random.default_rng(2)
    vals = rng.integers(0, 10000, 4000)
    tables = [pa.table({"v": pa.array(vals[i::4])}) for i in range(4)]
    scan = ArrowScanExec(tables, batch_rows=250)  # many small batches
    # one batch ≈ 256-capacity int64 + validity ≈ 2.3KB; budget holds one
    small = BufferCatalog(device_budget=3000, host_budget=20000,
                          spill_dir=str(tmp_path))
    monkeypatch.setattr(DeviceManager.get(), "catalog", small)
    ex = SortExec([col("v")], [SortOrder()], scan)
    out = []
    for split in range(scan.num_partitions):
        for b in ex.execute_partition(split):
            out.extend(b.to_arrow()["v"].to_pylist())
    # per-partition sort: each partition independently ordered
    assert small.spilled_to_host_bytes > 0   # pressure actually spilled
    at = 0
    for t in tables:
        n = t.num_rows
        assert out[at:at + n] == sorted(t["v"].to_pylist())
        at += n


# -- device choice and HBM budget (DeviceManager) ------------------------------

class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform = platform
        self._stats = stats

    def memory_stats(self):
        return self._stats

    def __repr__(self):
        return f"FakeDevice({self.platform})"


@pytest.mark.parametrize("stats", [None, {}, {"bytes_limit": 0}])
def test_device_manager_missing_limit_is_an_error_off_cpu(monkeypatch, stats):
    """A 16 GiB guess is for the CPU platform only: an accelerator that
    reports no bytes_limit must not be budgeted by assumption."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.runtime import memory
    monkeypatch.setattr(memory.jax, "devices",
                        lambda: [_FakeDevice("tpu", stats)])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        memory.DeviceManager(C.RapidsConf())
    # an explicit budget needs no report from the device
    dm = memory.DeviceManager(C.RapidsConf(
        {"spark.rapids.tpu.memory.hbm.limitBytes": 1 << 30}))
    assert dm.catalog.device_budget == 1 << 30


def test_device_manager_cpu_assumes_16gib_and_follows_ordinal():
    import jax
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.runtime import memory
    frac = C.RapidsConf().get(C.DEVICE_MEMORY_FRACTION)
    dm = memory.DeviceManager(C.RapidsConf())
    assert dm.device == jax.devices()[0]
    assert dm.catalog.device_budget == int((16 << 30) * frac)
    try:
        dm = memory.DeviceManager(C.RapidsConf(
            {"spark.rapids.tpu.device.ordinal": 3}))
        assert dm.device == jax.devices()[3]
        # uploads follow the chosen device
        assert jax.numpy.ones((8,)).devices() == {jax.devices()[3]}
    finally:
        jax.config.update("jax_default_device", None)
