"""Device & memory runtime — HBM budget, tiered spill stores, spillable batches.

Reference (SURVEY.md components #4-#7):
- GpuDeviceManager.scala:36,125,204 — acquire device, init RMM pool, pinned host pool.
- RapidsBufferCatalog.scala:40,156 / RapidsBufferStore.scala:41 — catalog keyed by
  buffer id over chained tiers device→host→disk with `synchronousSpill`:145.
- DeviceMemoryEventHandler.scala:42 — RMM alloc-failure callback triggering spill.
- SpillableColumnarBatch.scala:29 / SpillPriorities.scala:26.

TPU twist: XLA has no alloc-failure callback to trap (SURVEY.md §7 hard parts), so the
budget is enforced *proactively*: every batch registered with the catalog is counted
against an HBM budget, and registration spills lower-priority buffers synchronously
until the new buffer fits. Spill tiers are HBM → host numpy → disk pickle; "pinned"
staging is plain host RAM (TPU DMA runs from pageable host memory via PJRT).
"""

from __future__ import annotations

import contextlib
import dataclasses
import heapq
import itertools
import os
import pickle
import tempfile
import threading
import time
import typing

import numpy as np
import jax
import jax.numpy as jnp

from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.vector import TpuColumnVector
from spark_rapids_tpu.runtime import eventlog as EL
from spark_rapids_tpu.runtime import faults as F
from spark_rapids_tpu.runtime import tracing as TR
from spark_rapids_tpu.runtime.arm import LeakTracker
from spark_rapids_tpu.runtime.retry import DeviceOomError, SpillCapacityError

# -- spill priorities (reference SpillPriorities.scala:26) ---------------------
# Lower value spills FIRST.
OUTPUT_FOR_SHUFFLE_INITIAL_PRIORITY = -1000.0   # shuffle output: spill early
ACTIVE_ON_DECK_PRIORITY = 100.0                 # batches queued for processing
# batches an operator is actively coalescing/probing spill LAST (reference:
# ACTIVE_BATCHING_PRIORITY = ACTIVE_ON_DECK_PRIORITY + 100)
ACTIVE_BATCHING_PRIORITY = 200.0


class TierEnum:
    DEVICE = "DEVICE"
    HOST = "HOST"
    DISK = "DISK"


# -- allocation-site attribution ----------------------------------------------
# Every catalogued buffer is tagged with the subsystem that registered it
# ("joins.build", "exchange.map", "pipeline.queue", ...) plus the ambient
# plan-node id, so the heap profiler can say WHO holds device memory, not
# just how much is held. The label resolves through a dedicated thread-local
# first (explicit alloc_site() blocks at registration call sites), then the
# fault-injection scope (runtime/retry.py already wraps every retry attempt
# in F.scope(site), which names exactly the subsystems we want), and only
# then the unattributed bucket.

UNATTRIBUTED_SITE = "catalog.add_batch"

_alloc_tls = threading.local()


@contextlib.contextmanager
def alloc_site(site: str, retained: bool = False):
    """Tag catalog registrations inside the block with allocation site
    `site`. ``retained=True`` marks the buffers as intentionally outliving
    their query (DataFrame cache partitions), exempting them from the
    end-of-query leak detector while keeping their query tag for the
    fair-share demotion accounting."""
    prev = getattr(_alloc_tls, "site", None)
    _alloc_tls.site = (site, retained)
    try:
        yield
    finally:
        _alloc_tls.site = prev


def current_alloc_site() -> "tuple[str, bool]":
    """(site, retained) for a registration happening now on this thread."""
    v = getattr(_alloc_tls, "site", None)
    if v is not None:
        return v
    s = F.current_scope()
    if s:
        return s, False
    return UNATTRIBUTED_SITE, False


class MemoryLeakError(RuntimeError):
    """The end-of-query leak detector found buffers still tagged to a
    finished query and ``memory.leak.strict`` is on. Non-strict mode only
    emits the ``memory.leak`` event + resilience counter and reclaims the
    buffers; strict mode additionally fails the query so tests can turn
    any leak into a hard failure."""


class BufferClosedError(RuntimeError):
    """A spillable buffer was acquired after close()/remove() — raised as a
    dedicated type so callers that legitimately race a concurrent release
    (broadcast host-bridge rebuild) can retry it without masking unrelated
    assertion failures."""


class SpillCorruptionError(RuntimeError):
    """A disk-tier spill payload failed its CRC on unspill
    (memory.spill.checksum.enabled). Shuffle readers treat this exactly
    like a fetch failure — invalidate the map outputs, recompute — instead
    of decoding silently corrupt rows (the Spark shuffle-checksum →
    FetchFailed contract, SPARK-35275 analog). ``retryable`` marks a
    resubmission safe at the serving boundary (the recompute ladder already
    ran server-side); single-arg construction keeps the default pickle
    round-trip lossless for the endpoint's error channel."""

    retryable = True


@dataclasses.dataclass
class HostColumn:
    """Host image of one TpuColumnVector (the RapidsHostColumnVector analog)."""
    dtype: T.DataType
    data: np.ndarray
    validity: np.ndarray
    dictionary: typing.Any  # pyarrow StringArray or None


@dataclasses.dataclass
class HostBatch:
    columns: list
    num_rows: int
    schema: typing.Any
    metadata: typing.Any = None   # scan provenance (input_file_name family)

    def nbytes(self) -> int:
        out = 0
        for c in self.columns:
            out += c.data.nbytes + c.validity.nbytes
            if c.dictionary is not None:
                out += c.dictionary.nbytes
        return out


def batch_to_host(batch: ColumnarBatch) -> HostBatch:
    cols = [HostColumn(c.dtype, np.asarray(c.data), np.asarray(c.validity), c.dictionary)
            for c in batch.columns]
    return HostBatch(cols, batch.num_rows, batch.schema,
                     getattr(batch, "metadata", None))


def host_to_batch(hb: HostBatch) -> ColumnarBatch:
    cols = [TpuColumnVector(c.dtype, jnp.asarray(c.data), jnp.asarray(c.validity),
                            c.dictionary) for c in hb.columns]
    return ColumnarBatch(cols, hb.num_rows, hb.schema,
                         metadata=getattr(hb, "metadata", None))


class RapidsBuffer:
    """One catalogued buffer; knows which tier currently holds it
    (reference RapidsBufferStore.RapidsBufferBase)."""

    __slots__ = ("buffer_id", "tier", "priority", "size", "_device", "_host",
                 "_path", "_handle", "spill_callback", "query", "_crc",
                 "site", "node", "retained", "_disk_len")

    def __init__(self, buffer_id: int, batch: ColumnarBatch, priority: float,
                 spill_callback=None, query: str | None = None,
                 site: str = UNATTRIBUTED_SITE, node: int | None = None,
                 retained: bool = False):
        self.buffer_id = buffer_id
        self.tier = TierEnum.DEVICE
        self.priority = priority
        self.size = batch.device_memory_size()
        self._device: ColumnarBatch | None = batch
        self._host: HostBatch | None = None
        self._path: str | None = None
        self._handle = None          # (file, offset, len) in the direct store
        self.spill_callback = spill_callback
        # owning query (ambient collector at registration): the multi-tenant
        # scheduler's per-query accounting + fair-share demotion key
        self.query = query
        self._crc = None             # disk-tier payload checksum
        # allocation-site attribution (heap profiler): subsystem label +
        # ambient plan-node id; retained buffers outlive their query on
        # purpose (cache partitions) and are exempt from leak detection
        self.site = site
        self.node = node
        self.retained = retained
        self._disk_len = 0           # bytes held in the disk tier


class _SiteStats:
    """Process-lifetime accounting for one allocation site: live device
    bytes (maintained across spill/unspill transitions), the site's own
    device high-water mark, and cumulative alloc/free traffic."""

    __slots__ = ("live_device", "peak_device", "cumulative", "allocs",
                 "frees")

    def __init__(self):
        self.live_device = 0
        self.peak_device = 0
        self.cumulative = 0
        self.allocs = 0
        self.frees = 0


class BufferCatalog:
    """Tiered buffer catalog with proactive budget-driven spill.

    Reference: RapidsBufferCatalog.scala:40 (registry) + RapidsBufferStore.scala:145
    (`synchronousSpill`) + DeviceMemoryEventHandler (OOM→spill). Here the device tier's
    budget check runs at registration time instead of inside a malloc callback.
    """

    def __init__(self, device_budget: int, host_budget: int, spill_dir: str | None = None,
                 unspill: bool = False, oom_dump_dir: str | None = None,
                 direct_spill: bool = False, direct_batch_bytes: int = 64 << 20,
                 strict_budget: bool = True, spill_checksum: bool = True,
                 watermark_interval_bytes: int = 16 << 20,
                 profile_top_k: int = 10):
        self.device_budget = device_budget
        self.host_budget = host_budget
        # CRC disk-tier spill payloads and verify on unspill
        # (memory.spill.checksum.enabled)
        self._spill_checksum = spill_checksum
        # strict: registration that cannot spill back under budget raises a
        # retryable DeviceOomError (spark.rapids.tpu.memory.hbm.strictBudget)
        # instead of silently leaving the device tier over budget
        self._strict = strict_budget
        self._spill_dir = spill_dir
        self._unspill = unspill
        self._oom_dump_dir = oom_dump_dir
        self._direct_spill = direct_spill
        self._direct_batch_bytes = direct_batch_bytes
        self._direct_store = None  # lazily created GDS-analog batch store
        self._lock = threading.RLock()
        self._buffers: dict[int, RapidsBuffer] = {}
        self._ids = itertools.count(1)
        self.device_bytes = 0
        self.host_bytes = 0
        # metrics (reference GpuMetric spill counters)
        self.spilled_to_host_bytes = 0
        self.spilled_to_disk_bytes = 0
        # allocation-site heap profiler: per-site process-lifetime stats,
        # per-query peak/cumulative breakdowns (popped by finish_query so
        # long-lived serving processes stay bounded), the process device
        # high-water mark, and the last watermark sample emitted into the
        # event log / Chrome counter track
        self.disk_bytes = 0
        self.watermark_bytes = 0
        self._watermark_interval = max(1, int(watermark_interval_bytes))
        self._top_k = max(1, int(profile_top_k))
        self._site_stats: dict[str, _SiteStats] = {}
        self._query_mem: dict[str, dict] = {}
        self._last_sample: "tuple | None" = None
        self._last_sample_watermark = 0

    # -- registration --------------------------------------------------------
    def add_batch(self, batch: ColumnarBatch, priority: float = ACTIVE_ON_DECK_PRIORITY,
                  spill_callback=None) -> int:
        # fault-injection checkpoint (runtime/faults.py): chaos specs target
        # either the ambient operator scope ("joins.build" …) or the bare
        # registration site
        F.maybe_inject("oom", F.current_scope() or "catalog.add_batch")
        from spark_rapids_tpu.runtime import metrics as M
        site, retained = current_alloc_site()
        with self._lock:
            bid = next(self._ids)
            buf = RapidsBuffer(bid, batch, priority, spill_callback,
                               query=M.current_query_id(), site=site,
                               node=M.current_node(), retained=retained)
            self._buffers[bid] = buf
            self.device_bytes += buf.size
            try:
                self._ensure_device_budget(exclude=bid, strict=self._strict)
            except DeviceOomError:
                # roll back: a failed registration must not leave a phantom
                # buffer charged against the budget — the retry framework
                # re-attempts registration from scratch
                del self._buffers[bid]
                self.device_bytes -= buf.size
                raise
            self._account_alloc(buf)
            return bid

    # -- allocation-site heap accounting (under self._lock) ------------------
    def _account_alloc(self, buf: RapidsBuffer):
        st = self._site_stats.get(buf.site)
        if st is None:
            st = self._site_stats[buf.site] = _SiteStats()
        st.live_device += buf.size
        if st.live_device > st.peak_device:
            st.peak_device = st.live_device
        st.cumulative += buf.size
        st.allocs += 1
        if buf.query is not None:
            qm = self._query_mem.get(buf.query)
            if qm is None:
                # bound the per-query map: queries finished through
                # session._run_action pop their entry; out-of-band
                # registrations (tests driving collectors by hand) must not
                # grow it forever in a long-lived process
                if len(self._query_mem) > 512:
                    self._query_mem.pop(next(iter(self._query_mem)))
                qm = self._query_mem[buf.query] = {
                    "live": 0, "peak": 0, "cum": 0, "allocs": 0, "sites": {}}
            qm["live"] += buf.size
            qm["peak"] = max(qm["peak"], qm["live"])
            qm["cum"] += buf.size
            qm["allocs"] += 1
            # per-(query, site): [live_device, peak_device, cumulative,
            # plan-node ids seen]
            s = qm["sites"].get(buf.site)
            if s is None:
                s = qm["sites"][buf.site] = [0, 0, 0, set()]
            s[0] += buf.size
            s[1] = max(s[1], s[0])
            s[2] += buf.size
            if buf.node is not None:
                s[3].add(buf.node)
        self._maybe_sample()

    def _account_device_delta(self, buf: RapidsBuffer, delta: int):
        """A buffer moved into (+) or out of (-) the device tier without
        being allocated or freed (spill, unspill)."""
        st = self._site_stats.get(buf.site)
        if st is not None:
            st.live_device += delta
            if delta > 0 and st.live_device > st.peak_device:
                st.peak_device = st.live_device
        if buf.query is not None:
            qm = self._query_mem.get(buf.query)
            if qm is not None:
                qm["live"] += delta
                if delta > 0:
                    qm["peak"] = max(qm["peak"], qm["live"])
                s = qm["sites"].get(buf.site)
                if s is not None:
                    s[0] += delta
                    if delta > 0:
                        s[1] = max(s[1], s[0])

    def _account_free(self, buf: RapidsBuffer):
        st = self._site_stats.get(buf.site)
        if st is not None:
            st.frees += 1
        if buf.tier == TierEnum.DEVICE:
            self._account_device_delta(buf, -buf.size)
        self._maybe_sample()

    def _maybe_sample(self):
        """Watermark-timeline sample (under self._lock): update the process
        device high-water mark, and when telemetry is on emit a
        ``memory.watermark`` event + a Chrome counter-track sample — on the
        first allocation, whenever the watermark grows by the configured
        interval, and whenever any tier's occupancy moved by the interval
        since the last sample. Bounded: monotone growth emits
        O(peak / interval) samples, not one per allocation."""
        if self.device_bytes > self.watermark_bytes:
            self.watermark_bytes = self.device_bytes
        if not (EL.enabled() or TR.spans_enabled()):
            return
        cur = (self.device_bytes, self.host_bytes, self.disk_bytes)
        if (self._last_sample is not None
                and self.watermark_bytes - self._last_sample_watermark
                < self._watermark_interval
                and all(abs(a - b) < self._watermark_interval
                        for a, b in zip(cur, self._last_sample))):
            return
        self._last_sample = cur
        self._last_sample_watermark = self.watermark_bytes
        top = sorted(((s, st.live_device)
                      for s, st in self._site_stats.items()
                      if st.live_device > 0),
                     key=lambda kv: -kv[1])[:self._top_k]
        if EL.enabled():
            EL.emit("memory.watermark", device_bytes=cur[0],
                    host_bytes=cur[1], disk_bytes=cur[2],
                    watermark_bytes=self.watermark_bytes,
                    budget=self.device_budget, sites=dict(top))
        TR.counter("memory", {"device_bytes": cur[0], "host_bytes": cur[1],
                              "disk_bytes": cur[2]})

    def _ensure_device_budget(self, exclude: int | None = None,
                              strict: bool = False):
        if self.device_bytes <= self.device_budget:
            return
        # spill lowest-priority device buffers first (reference spill-priority queue)
        heap = [(b.priority, b.buffer_id) for b in self._buffers.values()
                if b.tier == TierEnum.DEVICE and b.buffer_id != exclude]
        heapq.heapify(heap)
        while self.device_bytes > self.device_budget and heap:
            _, bid = heapq.heappop(heap)
            self._spill_device_buffer(self._buffers[bid])
        if self.device_bytes > self.device_budget:
            # nothing left to spill and still over budget: the OOM analog —
            # dump allocator state for postmortems (reference
            # spark.rapids.memory.gpu.oomDumpDir / DeviceMemoryEventHandler)
            self._dump_oom_state(exclude)
            if strict:
                spillable, pinned = self._device_breakdown(exclude)
                new_sz = (self._buffers[exclude].size
                          if exclude in self._buffers else 0)
                raise DeviceOomError(
                    f"device tier over budget after spill exhaustion: "
                    f"{self.device_bytes}B > budget {self.device_budget}B "
                    f"(new buffer {new_sz}B, other device buffers: "
                    f"spillable {spillable}B, pinned>=ACTIVE_BATCHING "
                    f"{pinned}B)",
                    requested=new_sz, budget=self.device_budget,
                    spillable_bytes=spillable, pinned_bytes=pinned)

    def _device_breakdown(self, exclude=None):
        """(spillable, pinned) device-tier byte totals excluding `exclude` —
        pinned counts ACTIVE_BATCHING_PRIORITY and above (batches an
        operator is actively consuming spill last)."""
        spillable = pinned = 0
        for b in self._buffers.values():
            if b.tier != TierEnum.DEVICE or b.buffer_id == exclude:
                continue
            if b.priority >= ACTIVE_BATCHING_PRIORITY:
                pinned += b.size
            else:
                spillable += b.size
        return spillable, pinned

    def _dump_oom_state(self, exclude):
        if not self._oom_dump_dir:
            return
        import datetime
        import os
        import time as _time
        # rate-limit: a workload stuck over budget would otherwise write a
        # file per allocation, under the catalog lock
        now = _time.monotonic()
        if now - getattr(self, "_last_oom_dump", -1e9) < 60.0:
            return
        self._last_oom_dump = now
        try:
            os.makedirs(self._oom_dump_dir, exist_ok=True)
            stamp = datetime.datetime.now().strftime("%Y%m%d-%H%M%S-%f")
            path = os.path.join(self._oom_dump_dir, f"hbm-oom-{stamp}.txt")
            with open(path, "w") as f:
                f.write(f"device_bytes={self.device_bytes} "
                        f"budget={self.device_budget} "
                        f"host_bytes={self.host_bytes} "
                        f"host_budget={self.host_budget} "
                        f"buffers={len(self._buffers)} "
                        f"over_budget_buffer={exclude}\n")
                # per-tier spillable vs pinned (>= ACTIVE_BATCHING_PRIORITY)
                # totals: the postmortem's "why couldn't spill free enough"
                for tier in (TierEnum.DEVICE, TierEnum.HOST, TierEnum.DISK):
                    spillable = pinned = 0
                    for b in self._buffers.values():
                        if b.tier != tier:
                            continue
                        if b.priority >= ACTIVE_BATCHING_PRIORITY:
                            pinned += b.size
                        else:
                            spillable += b.size
                    f.write(f"tier={tier} spillable_bytes={spillable} "
                            f"pinned_bytes={pinned}\n")
                # per-site live breakdown (heap profiler): the OOM names the
                # culprit SUBSYSTEM, not just tier totals. Derived from the
                # live registry (the over-budget buffer is registered but
                # not yet site-accounted at this point), joined with the
                # process-lifetime site stats where they exist
                live_by_site: dict = {}
                for b in self._buffers.values():
                    if b.tier == TierEnum.DEVICE:
                        live_by_site[b.site] = \
                            live_by_site.get(b.site, 0) + b.size
                f.write("top sites by live device bytes:\n")
                for site, live in sorted(live_by_site.items(),
                                         key=lambda kv: -kv[1])[:10]:
                    st = self._site_stats.get(site) or _SiteStats()
                    f.write(f"site={site} live_device={live} "
                            f"peak_device={max(st.peak_device, live)} "
                            f"cumulative={st.cumulative} "
                            f"allocs={st.allocs} frees={st.frees}\n")
                f.write("buffer_id\ttier\tsize\tpriority\tsite\tnode\t"
                        "query\n")
                for b in sorted(self._buffers.values(),
                                key=lambda x: -x.size):
                    f.write(f"{b.buffer_id}\t{b.tier}\t{b.size}\t"
                            f"{b.priority}\t{b.site}\t{b.node}\t"
                            f"{b.query}\n")
        except OSError:
            pass  # dumping must never turn an OOM into a crash

    def _spill_device_buffer(self, buf: RapidsBuffer):
        hb = batch_to_host(buf._device)
        # block so the device arrays can actually be freed before we drop the refs
        buf._host = hb
        buf._device = None
        buf.tier = TierEnum.HOST
        self.device_bytes -= buf.size
        self.host_bytes += hb.nbytes()
        self.spilled_to_host_bytes += buf.size
        self._account_device_delta(buf, -buf.size)
        if EL.enabled():
            EL.emit("spill", tier_from=TierEnum.DEVICE, tier_to=TierEnum.HOST,
                    bytes=buf.size, buffer=buf.buffer_id,
                    priority=buf.priority)
        # spill-tier transition as an instant on the trace timeline, next to
        # the memory counter lanes (span-file only; the event log line above
        # is the analysis copy)
        TR.instant("memory.spill", tier_from=TierEnum.DEVICE,
                   tier_to=TierEnum.HOST, bytes=buf.size, site=buf.site)
        if buf.spill_callback:
            buf.spill_callback(buf.size)
        self._maybe_sample()
        self._ensure_host_budget()

    def _ensure_host_budget(self):
        if self.host_bytes <= self.host_budget:
            return
        heap = [(b.priority, b.buffer_id) for b in self._buffers.values()
                if b.tier == TierEnum.HOST]
        heapq.heapify(heap)
        while self.host_bytes > self.host_budget and heap:
            _, bid = heapq.heappop(heap)
            self._spill_host_buffer(self._buffers[bid])

    def _spill_dir_path(self) -> str:
        if self._spill_dir is None:
            self._spill_dir = tempfile.mkdtemp(prefix="rapids_tpu_spill_")
        os.makedirs(self._spill_dir, exist_ok=True)
        return self._spill_dir

    def _get_direct_store(self):
        if self._direct_store is None:
            from spark_rapids_tpu.runtime.direct_spill import DirectSpillStore
            self._direct_store = DirectSpillStore(
                os.path.join(self._spill_dir_path(), "direct"),
                batch_bytes=self._direct_batch_bytes)
        return self._direct_store

    def _spill_host_buffer(self, buf: RapidsBuffer):
        hb = buf._host
        payload = pickle.dumps(hb, protocol=pickle.HIGHEST_PROTOCOL)
        # CRC the CLEAN payload, then the chaos checkpoint
        # ("corrupt:spill.write:N") may flip a byte of what actually lands
        # on disk — modeling bit rot between write and unspill, which the
        # read-side verification must DETECT rather than decode
        if self._spill_checksum:
            from spark_rapids_tpu.runtime.checksum import block_checksum
            buf._crc = block_checksum(payload)
        payload = F.maybe_corrupt("spill.write", payload)
        # disk-capacity checkpoint BEFORE any bytes land: the injected
        # ENOSPC ("disk_full:spill.write:N") and a real ENOSPC from the
        # writes below both surface as the typed, RETRYABLE
        # SpillCapacityError — the buffer stays intact in its host tier and
        # the OOM ladder (spill elsewhere / split / retry) absorbs it,
        # instead of a raw OSError escaping the operator mid-spill
        F.maybe_inject("disk_full", "spill.write")
        try:
            if self._direct_spill:
                # GDS-analog batched aligned store (reference RapidsGdsStore)
                # — the store itself meters its aligned I/O into the
                # movement ledger (site "direct_spill")
                buf._handle = self._get_direct_store().write(payload)
                buf._path = None
            else:
                path = os.path.join(self._spill_dir_path(),
                                    f"buffer-{buf.buffer_id}.spill")
                t0 = time.perf_counter()
                try:
                    with open(path, "wb") as f:
                        f.write(payload)
                except OSError:
                    # a partial file must not survive to be unspilled later
                    with contextlib.suppress(OSError):
                        os.unlink(path)
                    raise
                from spark_rapids_tpu.runtime import movement as MV
                MV.record("spill.write", len(payload), link="disk",
                          site="spill.file",
                          seconds=time.perf_counter() - t0)
                buf._path = path
                buf._handle = None
        except OSError as e:
            import errno
            buf._crc = None
            if e.errno == errno.ENOSPC:
                raise SpillCapacityError(
                    f"disk spill tier full writing buffer "
                    f"{buf.buffer_id} ({len(payload)} B): {e}") from e
            raise
        self.host_bytes -= hb.nbytes()
        self.spilled_to_disk_bytes += hb.nbytes()
        buf._disk_len = hb.nbytes()
        self.disk_bytes += buf._disk_len
        if EL.enabled():
            EL.emit("spill", tier_from=TierEnum.HOST, tier_to=TierEnum.DISK,
                    bytes=hb.nbytes(), buffer=buf.buffer_id,
                    priority=buf.priority)
        TR.instant("memory.spill", tier_from=TierEnum.HOST,
                   tier_to=TierEnum.DISK, bytes=hb.nbytes(), site=buf.site)
        buf._host = None
        buf.tier = TierEnum.DISK
        self._maybe_sample()

    # -- access --------------------------------------------------------------
    def acquire_batch(self, buffer_id: int) -> ColumnarBatch:
        return self.acquire(buffer_id)[0]

    def acquire(self, buffer_id: int,
                unspill: "bool | None" = None) -> "tuple[ColumnarBatch, str]":
        """Materialize the buffer on device: (batch, the tier it was found
        in). If it was spilled and unspill is enabled (``unspill``, or the
        catalog's memory.hbm.unspill.enabled where that is None) it is
        re-registered in the device tier (reference unspill.enabled,
        RapidsBufferStore copy-back); otherwise the device copy is transient."""
        if unspill is None:
            unspill = self._unspill
        # (bytes, seconds) collected under the lock, metered after release:
        # a sample-interval crossing in MV.record emits event-log/tracing
        # I/O, which must not run under the hot buffer-catalog lock (same
        # split direct_spill.py uses for its write path)
        spill_read = None
        try:
            with self._lock:
                try:
                    buf = self._buffers[buffer_id]
                except KeyError:
                    raise BufferClosedError(
                        f"buffer {buffer_id} removed") from None
                found = buf.tier
                if found == TierEnum.DEVICE:
                    return buf._device, found
                hb = buf._host
                if hb is None:
                    if buf._handle is not None:
                        payload = self._get_direct_store().read(buf._handle)
                    else:
                        t0 = time.perf_counter()
                        with open(buf._path, "rb") as f:
                            payload = f.read()
                        spill_read = (len(payload),
                                      time.perf_counter() - t0)
                    if buf._crc is not None:
                        from spark_rapids_tpu.runtime.checksum import \
                            block_checksum
                        got = block_checksum(payload)
                        if got != buf._crc:
                            raise SpillCorruptionError(
                                f"buffer {buffer_id} spill payload checksum "
                                f"mismatch on unspill (stored {buf._crc:#x}, "
                                f"read {got:#x}, {len(payload)}B)")
                    hb = pickle.loads(payload)
                batch = host_to_batch(hb)
                if unspill:
                    if buf.tier == TierEnum.HOST:
                        self.host_bytes -= hb.nbytes()
                    elif buf._handle is not None:
                        self._get_direct_store().delete(buf._handle)
                        buf._handle = None
                    else:
                        os.unlink(buf._path)
                        buf._path = None
                    if buf.tier == TierEnum.DISK:
                        self.disk_bytes -= buf._disk_len
                        buf._disk_len = 0
                    buf._host = None
                    buf._device = batch
                    buf.tier = TierEnum.DEVICE
                    self.device_bytes += buf.size
                    self._account_device_delta(buf, buf.size)
                    self._ensure_device_budget(exclude=buffer_id)
                    self._maybe_sample()
                return batch, found
        finally:
            if spill_read is not None:
                from spark_rapids_tpu.runtime import movement as MV
                MV.record("spill.read", spill_read[0], link="disk",
                          site="spill.file", seconds=spill_read[1])

    def get_tier(self, buffer_id: int) -> str:
        return self._buffers[buffer_id].tier

    def update_priority(self, buffer_id: int, priority: float):
        with self._lock:
            self._buffers[buffer_id].priority = priority

    def remove(self, buffer_id: int):
        with self._lock:
            buf = self._buffers.pop(buffer_id, None)
            if buf is None:
                return
            if buf.tier == TierEnum.DEVICE:
                self.device_bytes -= buf.size
            elif buf.tier == TierEnum.HOST:
                self.host_bytes -= buf._host.nbytes()
            else:
                self.disk_bytes -= buf._disk_len
                if buf._handle is not None:
                    self._get_direct_store().delete(buf._handle)
                elif buf._path:
                    try:
                        os.unlink(buf._path)
                    except OSError:
                        pass
            self._account_free(buf)

    def synchronous_spill(self, target_device_bytes: int) -> int:
        """Spill until the device tier holds <= target bytes; returns bytes spilled
        (reference RapidsBufferStore.synchronousSpill:145)."""
        with self._lock:
            before = self.device_bytes
            saved = self.device_budget
            try:
                self.device_budget = target_device_bytes
                self._ensure_device_budget()
            finally:
                self.device_budget = saved
            return before - self.device_bytes

    # -- per-query accounting (multi-tenant scheduler, runtime/scheduler.py) --
    def query_device_bytes(self) -> dict:
        """{query_id: device-tier bytes} for every owning query (None key =
        buffers registered outside any query scope) — the fair-share input
        of the scheduler's OOM demotion policy."""
        with self._lock:
            out: dict = {}
            for b in self._buffers.values():
                if b.tier == TierEnum.DEVICE:
                    out[b.query] = out.get(b.query, 0) + b.size
            return out

    def spill_query_device(self, query_id: str) -> int:
        """Demote ONE query's device tier: spill its spillable device
        buffers (below ACTIVE_BATCHING priority — a batch an operator is
        mid-consume stays pinned), lowest priority first; returns bytes
        spilled. The fair-share degradation path: an over-share peer pays
        a recoverable unspill instead of the under-share faulting query
        paying with batch splits."""
        with self._lock:
            victims = sorted(
                (b for b in self._buffers.values()
                 if b.tier == TierEnum.DEVICE and b.query == query_id
                 and b.priority < ACTIVE_BATCHING_PRIORITY),
                key=lambda b: b.priority)
            spilled = 0
            for b in victims:
                spilled += b.size
                self._spill_device_buffer(b)
            return spilled

    # -- allocation-site heap profiler read-out ------------------------------
    def buffer_site(self, buffer_id: int) -> str:
        with self._lock:
            buf = self._buffers.get(buffer_id)
            return buf.site if buf is not None else UNATTRIBUTED_SITE

    def heap_snapshot(self) -> dict:
        """Live heap structure by allocation site: per-site tier occupancy
        of the buffers alive right now (computed by scanning the registry —
        bounded by live buffer count), joined with the site's process-
        lifetime peak/cumulative/alloc/free stats. The programmatic face of
        ``tools/profiler.py memory`` (session.heap_snapshot())."""
        with self._lock:
            live: dict = {}
            for b in self._buffers.values():
                e = live.setdefault(b.site, {
                    "buffers": 0, "tiers": {}, "nodes": set(),
                    "queries": set(), "retained_bytes": 0})
                if b.tier == TierEnum.DEVICE:
                    sz = b.size
                elif b.tier == TierEnum.HOST:
                    sz = b._host.nbytes()
                else:
                    sz = b._disk_len
                e["buffers"] += 1
                e["tiers"][b.tier] = e["tiers"].get(b.tier, 0) + sz
                if b.node is not None:
                    e["nodes"].add(b.node)
                if b.query is not None:
                    e["queries"].add(b.query)
                if b.retained:
                    e["retained_bytes"] += sz
            sites = []
            for site, st in self._site_stats.items():
                e = live.get(site) or {"buffers": 0, "tiers": {},
                                       "nodes": set(), "queries": set(),
                                       "retained_bytes": 0}
                sites.append({
                    "site": site,
                    "buffers": e["buffers"],
                    "tiers": dict(e["tiers"]),
                    "live_bytes": sum(e["tiers"].values()),
                    "device_bytes": e["tiers"].get(TierEnum.DEVICE, 0),
                    "retained_bytes": e["retained_bytes"],
                    "nodes": sorted(e["nodes"]),
                    "queries": sorted(e["queries"]),
                    "peak_device_bytes": st.peak_device,
                    "cumulative_bytes": st.cumulative,
                    "allocs": st.allocs,
                    "frees": st.frees,
                })
            sites.sort(key=lambda s: (-s["device_bytes"], -s["live_bytes"],
                                      -s["cumulative_bytes"]))
            return {
                "device_bytes": self.device_bytes,
                "host_bytes": self.host_bytes,
                "disk_bytes": self.disk_bytes,
                "watermark_bytes": self.watermark_bytes,
                "device_budget": self.device_budget,
                "buffers": len(self._buffers),
                "sites": sites,
            }

    def query_memory(self, query_id: str) -> dict:
        """Per-query memory summary (peak/cumulative device bytes + the
        top-K sites by peak) without finishing the query's accounting."""
        with self._lock:
            qm = self._query_mem.get(query_id)
            return self._query_summary(qm)

    def _query_summary(self, qm) -> dict:
        ranked = sorted((qm or {}).get("sites", {}).items(),
                        key=lambda kv: -kv[1][1])[:self._top_k]
        return {
            "peak_device_bytes": qm["peak"] if qm else 0,
            "cumulative_bytes": qm["cum"] if qm else 0,
            "allocs": qm["allocs"] if qm else 0,
            "sites": {site: {"peak_bytes": v[1], "cumulative_bytes": v[2],
                             "nodes": sorted(v[3])}
                      for site, v in ranked},
        }

    def finish_query(self, query_id: str, leak_check: bool = True):
        """End-of-query epilogue: pop the query's memory accounting and
        return (summary, leak). When ``leak_check``, any non-retained
        buffer still tagged to the finished query is a LEAK — a
        ``memory.leak`` event + resilience counter fire with the per-site
        breakdown, and the buffers are reclaimed so one leaky operator
        cannot bleed the HBM budget across queries. ``leak`` is None on a
        clean query, else {bytes, buffers, sites}."""
        with self._lock:
            qm = self._query_mem.pop(query_id, None)
            summary = self._query_summary(qm)
            leaked = ([b for b in self._buffers.values()
                       if b.query == query_id and not b.retained]
                      if leak_check else [])
        if not leaked:
            return summary, None
        by_site: dict = {}
        total = 0
        for b in leaked:
            by_site[b.site] = by_site.get(b.site, 0) + b.size
            total += b.size
        leak = {"bytes": total, "buffers": len(leaked), "sites": by_site}
        from spark_rapids_tpu.runtime import metrics as M
        M.resilience_add(M.MEMORY_LEAKS, len(leaked))
        TR.span_event("memory.leak", bytes=total, buffers=len(leaked),
                      sites=by_site)
        # reclaim: the detector's report is the alarm; holding the bytes
        # hostage afterwards would punish every later tenant for it
        for b in leaked:
            self.remove(b.buffer_id)
        return summary, leak

    @property
    def num_buffers(self):
        return len(self._buffers)


# memory-profile knobs applied by a session that sets them EXPLICITLY
# (the process-global-switch pattern of tracing/faults/eventlog): the
# DeviceManager catalog is constructed lazily with default conf, so the
# session pushes the values onto the live catalog and remembers them for a
# catalog created later
_profile_override: "tuple[int, int] | None" = None


def set_profile_options(watermark_interval_bytes: int, top_k: int) -> None:
    global _profile_override
    _profile_override = (int(watermark_interval_bytes), int(top_k))
    dm = DeviceManager._instance
    if dm is not None:
        cat = dm.catalog
        with cat._lock:
            cat._watermark_interval = max(1, int(watermark_interval_bytes))
            cat._top_k = max(1, int(top_k))


def host_prefetch_budget(max_buffer_bytes: int) -> int:
    """Byte budget for prefetch buffering ahead of a consumer (scan
    readahead and every pipeline queue edge, runtime/pipeline.py): the
    configured cap, shrunk to the spill catalog's free host headroom so
    prefetched data never evicts spilled device buffers to disk. The floor
    guarantees a producer can always stage at least one typical reader
    batch (a zero budget would serialize decode behind compute again)."""
    cat = DeviceManager.get().catalog
    headroom = max(cat.host_budget - cat.host_bytes, 0)
    return max(min(max_buffer_bytes, headroom), 16 << 20)


# historical name (the scan readahead predates the generalized pipeline)
scan_readahead_budget = host_prefetch_budget


class SpillableColumnarBatch:
    """Handle over a catalogued batch; keeps data spillable while an operator holds it
    (reference SpillableColumnarBatch.scala:29,74)."""

    def __init__(self, batch: ColumnarBatch, priority: float = ACTIVE_ON_DECK_PRIORITY,
                 catalog: "BufferCatalog | None" = None, spill_callback=None):
        self.catalog = catalog or DeviceManager.get().catalog
        self.buffer_id = self.catalog.add_batch(batch, priority, spill_callback)
        self._site = self.catalog.buffer_site(self.buffer_id)
        self.num_rows = batch.num_rows
        self.schema = batch.schema
        self.capacity = batch.capacity
        self.size = batch.device_memory_size()
        self._closed = False
        self._leak = LeakTracker.track(f"SpillableColumnarBatch#{self.buffer_id}")

    def get_batch(self) -> ColumnarBatch:
        return self.acquire()[0]

    def acquire(self, unspill: "bool | None" = None):
        """(batch, the tier that held it): ``BufferCatalog.acquire``."""
        if self._closed:
            raise BufferClosedError(f"buffer {self.buffer_id} used after close")
        return self.catalog.acquire(self.buffer_id, unspill)

    def set_priority(self, priority: float):
        self.catalog.update_priority(self.buffer_id, priority)

    def close(self):
        if not self._closed:
            self._closed = True
            LeakTracker.release(self._leak)
            # chaos hook ("leak:<site>:N", runtime/faults.py): model a
            # refcount bug — the handle closes normally but the catalog
            # entry is never freed, which the end-of-query leak detector
            # (BufferCatalog.finish_query) MUST catch and reclaim
            if F.should_leak(self._site):
                return
            self.catalog.remove(self.buffer_id)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class DeviceManager:
    """Process-wide device state: the chosen device, the HBM budget, and the buffer
    catalog (reference GpuDeviceManager.scala:36 + RapidsBufferCatalog.init:177).

    One executor owns one TPU chip in the reference's model (GpuDeviceManager.scala:103);
    here the local runtime owns the device at spark.rapids.tpu.device.ordinal and multi-chip execution goes through
    the Mesh path (distributed/), matching SURVEY.md §7's executor-per-chip decision.
    """

    _instance: "DeviceManager | None" = None
    _lock = threading.Lock()

    def __init__(self, conf: C.RapidsConf):
        self.conf = conf
        devices = jax.devices()
        self.device = devices[conf.get(C.DEVICE_ORDINAL)]
        if self.device != devices[0]:
            # uploads and uncommitted programs follow the chosen device
            jax.config.update("jax_default_device", self.device)
        limit = conf.get(C.DEVICE_MEMORY_LIMIT)
        if not limit:
            hbm = (self.device.memory_stats() or {}).get("bytes_limit", 0)
            if not hbm:
                if self.device.platform != "cpu":
                    raise RuntimeError(
                        f"{self.device} reports no memory bytes_limit; set "
                        f"{C.DEVICE_MEMORY_LIMIT.key} to budget it by hand")
                hbm = 16 << 30  # the CPU backend exposes no limit; assume one v5e chip's HBM
            limit = int(hbm * conf.get(C.DEVICE_MEMORY_FRACTION))
        spill_dirs = conf.get(C.SPILL_DIRS)
        self.catalog = BufferCatalog(
            device_budget=limit,
            host_budget=conf.get(C.HOST_SPILL_STORAGE_SIZE),
            spill_dir=spill_dirs.split(",")[0] if spill_dirs else None,
            unspill=conf.get(C.UNSPILL_ENABLED),
            oom_dump_dir=conf.get(C.OOM_DUMP_DIR),
            direct_spill=conf.get(C.DIRECT_SPILL_ENABLED),
            direct_batch_bytes=conf.get(C.DIRECT_SPILL_BATCH_BYTES),
            strict_budget=conf.get(C.STRICT_DEVICE_BUDGET),
            spill_checksum=conf.get(C.SPILL_CHECKSUM),
            watermark_interval_bytes=conf.get(C.MEMORY_WATERMARK_INTERVAL),
            profile_top_k=conf.get(C.MEMORY_PROFILE_TOPK),
        )
        if _profile_override is not None:
            self.catalog._watermark_interval = max(1, _profile_override[0])
            self.catalog._top_k = max(1, _profile_override[1])

    @classmethod
    def initialize(cls, conf: C.RapidsConf | None = None) -> "DeviceManager":
        with cls._lock:
            cls._instance = DeviceManager(conf or C.RapidsConf())
            return cls._instance

    @classmethod
    def get(cls) -> "DeviceManager":
        with cls._lock:
            if cls._instance is None:
                cls._instance = DeviceManager(C.RapidsConf())
            return cls._instance

    @classmethod
    def reset(cls):
        with cls._lock:
            cls._instance = None
