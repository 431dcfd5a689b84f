"""Shuffle exchange exec — partition on device, exchange through the block store.

Reference (SURVEY.md component #30): GpuShuffleExchangeExecBase.scala:80
(`prepareBatchShuffleDependency`:167 partitions + slices on device and hands sliced
batches to the shuffle manager), ShuffledBatchRDD reads one reduce partition.

The map stage runs once, lazily, the first time any reduce partition executes
(Spark's stage barrier stands in as a threading.Event here since scheduling is local;
the distributed Mesh path in distributed/ replaces this with an ICI all_to_all).
"""

from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor

from spark_rapids_tpu import config as C
from spark_rapids_tpu.exec.base import TpuExec, TaskContext
from spark_rapids_tpu.exec.coalesce import coalesce_iterator, TargetSize
from spark_rapids_tpu.runtime import eventlog as EL
from spark_rapids_tpu.runtime import faults as F
from spark_rapids_tpu.runtime import memory as mem
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import retry as R
from spark_rapids_tpu.runtime import tracing
from spark_rapids_tpu.shuffle.manager import ShuffleBlockStore
from spark_rapids_tpu.shuffle.partitioning import Partitioner, RangePartitioner


class ShuffleExchangeExec(TpuExec):
    """Reference GpuShuffleExchangeExecBase:80."""

    def __init__(self, partitioner: Partitioner, child: TpuExec, conf=None):
        super().__init__(child, conf=conf)
        self.partitioner = partitioner.bind(child.output)
        self._map_done = threading.Event()
        self._map_lock = threading.Lock()
        self._shuffle_id = None
        self._pending_shuffle_id = None
        self._partition_time = self.metrics.metric(M.PARTITION_TIME, M.MODERATE)
        self._reads_left = self.partitioner.num_partitions
        self._reads_lock = threading.Lock()

    @property
    def output(self):
        return self.child.output

    @property
    def num_partitions(self):
        return self.partitioner.num_partitions

    def _run_map_stage(self):
        store = ShuffleBlockStore.get()
        serialized = not self.conf.get(C.SHUFFLE_MANAGER_ENABLED)
        # write to a PRIVATE shuffle id and publish it only when every block
        # is in the store: a concurrent reader re-resolving self._shuffle_id
        # mid-rebuild (its fetch failure raced this recompute) must never see
        # a half-written shuffle as complete — it sees the stale/None id,
        # gets KeyError, and its own recompute ladder blocks on the barrier
        sid = store.register_shuffle(serialized=serialized)
        self._pending_shuffle_id = sid
        collector = M.current_collector()
        parent_span = tracing.current_span()
        EL.emit("stage.map.start", node=self._node_id,
                shuffle=sid,
                map_partitions=self.child.num_partitions,
                reduce_partitions=self.partitioner.num_partitions)

        if isinstance(self.partitioner, RangePartitioner):
            # driver-side sample pass to pick range bounds (reference
            # GpuRangePartitioner.sketch over a reservoir sample; we sample the
            # first batch of every input partition)
            samples = []
            for split in range(self.child.num_partitions):
                with TaskContext():
                    for b in self.child.execute_partition(split):
                        samples.append(b)
                        break
            if samples:
                self.partitioner.set_bounds_from_sample(samples)

        from spark_rapids_tpu.runtime import pipeline as P
        pipe_on = P.enabled(self.conf)

        def map_task(split):
            # pool thread: re-enter the query scope and open an attribution
            # frame for this exchange so map-side partitioning time lands on
            # this node's selfTime (child operator frames subtract their own)
            with M.collector_context(collector), \
                    M.node_frame(self._node_id, self._self_time), \
                    TaskContext(), tracing.child_of(parent_span):
                child_it = self.child.execute_partition(split)
                if pipe_on:
                    # map-segment boundary: upstream compute produces on the
                    # stage's worker thread while THIS thread partitions,
                    # serializes and writes the previous batch
                    child_it = P.stage_iterator(
                        child_it, edge="exchange.map", conf=self.conf,
                        registry=self.metrics,
                        node_id=getattr(self.child, "_node_id", None),
                        spillable=True)
                piece_seq = 0
                for batch in child_it:
                    if batch.num_rows == 0:
                        continue

                    def partition_one(b):
                        with self._partition_time.timed():
                            return self.partitioner.partition(b, split)

                    # map-side writer under the OOM ladder: partitioning a
                    # split half writes the same rows to the same reduce ids,
                    # so piece-granularity recovery is transparent downstream
                    for pieces in R.with_retry([batch], partition_one,
                                               conf=self.conf,
                                               scope="exchange.map"):
                        piece_seq += 1
                        for pid, piece in pieces:
                            # per-piece spill-only retry: a failed block
                            # registration rolls back before raising, so the
                            # re-attempt never double-writes. seq pins each
                            # block's position to (map split, piece order):
                            # concurrent map tasks + pipeline stages may
                            # WRITE out of order, but order-sensitive
                            # consumers (first/last) still see a stable
                            # stream per reduce partition
                            R.call_with_retry(
                                lambda p=pid, b=piece, s=piece_seq:
                                    store.write_block(sid, p, b,
                                                      seq=(split, s)),
                                scope="exchange.write")

        nthreads = max(1, min(self.conf.get(C.NUM_LOCAL_TASKS),
                              self.child.num_partitions))
        if self.child.num_partitions == 1:
            map_task(0)
        else:
            with ThreadPoolExecutor(max_workers=nthreads) as pool:
                list(pool.map(map_task, range(self.child.num_partitions)))
        # per-reduce-partition byte sizes: the shuffle-skew input of the
        # stats plane (bounded: one int per reduce partition). Recorded into
        # the query's collector unconditionally so skew survives into
        # plan.stats/history even with the event log off or the map stage run
        # by the mesh plane
        sizes = ShuffleBlockStore.get().partition_sizes(
            sid, self.partitioner.num_partitions)
        collector = M.current_collector()
        if collector is not None:
            collector.record_shuffle_sizes(self._node_id, sid, sizes)
        if EL.enabled():
            EL.emit("stage.map.end", node=self._node_id,
                    shuffle=sid,
                    partition_sizes=[int(s) for s in sizes])
        self._shuffle_id = sid          # publish: the map outputs are complete
        self._pending_shuffle_id = None

    def _ensure_map_stage(self):
        if self._map_done.is_set():
            self._raise_if_failed()
            return
        with self._map_lock:
            if not self._map_done.is_set():
                try:
                    self._run_map_stage()
                except BaseException as e:
                    # don't re-run the map stage per reduce task, and don't strand
                    # the partially written blocks in the catalog (the failed
                    # build wrote to the still-unpublished pending id)
                    self._map_error = e
                    pending = getattr(self, "_pending_shuffle_id", None)
                    if pending is not None:
                        ShuffleBlockStore.get().unregister_shuffle(pending)
                        self._pending_shuffle_id = None
                finally:
                    self._map_done.set()
        self._raise_if_failed()

    def _raise_if_failed(self):
        err = getattr(self, "_map_error", None)
        if err is not None:
            from spark_rapids_tpu.runtime.scheduler import QueryCancelledError
            if isinstance(err, QueryCancelledError):
                # keep the typed cancellation visible at the session so the
                # lifecycle classifies as cancelled/deadline, not query.error
                raise err
            raise RuntimeError("shuffle map stage failed") from err

    def _invalidate_map_stage(self, observed):
        """Forget the map outputs so the next read recomputes them (the
        standalone analog of Spark's FetchFailed → stage retry,
        RapidsShuffleIterator.scala:82,153). `_reads_left` is NOT reset: it
        counts reader completions, and each reduce partition still finishes
        exactly once — the last one out unregisters whatever shuffle id is
        then current.

        `observed` is the shuffle generation the caller's read actually
        failed against. Concurrent reduce readers (pipeline stage threads)
        all race the same invalidation: the first one tears the stale
        generation down and rebuilds; the rest fail against that SAME stale
        id (KeyError/BufferClosedError mid-yank) and must not invalidate the
        freshly rebuilt outputs — they see `_shuffle_id != observed` and
        fall through to `_ensure_map_stage`, which hands them the new
        generation (or blocks on the in-flight rebuild)."""
        with self._map_lock:
            if observed is None or self._shuffle_id != observed:
                # this reader never saw a live generation (it raced the
                # invalidate→republish window) or a newer one exists: either
                # way there is nothing of its own to tear down
                return
            if self._shuffle_id is not None:
                ShuffleBlockStore.get().unregister_shuffle(self._shuffle_id)
                self._shuffle_id = None
            self._map_error = None
            self._map_done.clear()

    def _read_with_recompute(self, split):
        """Stream one reduce partition; a fetch failure detected BEFORE any
        batch was emitted invalidates the map outputs and recomputes them
        (bounded by shuffle.fetch.maxRetries). A mid-stream failure after
        partial emission cannot be retried safely — the consumer already saw
        rows — and surfaces as TransportError (Spark would re-run the reduce
        task there; the local scheduler has no task-level rerun).
        KeyError counts as a fetch failure: a concurrent reader's
        invalidation can yank the shuffle between ensure and read, and
        BufferClosedError the same way when the invalidation lands after
        this reader snapshotted the block list. SpillCorruptionError too:
        a shuffle block whose disk-tier spill payload failed its CRC is a
        lost block — recompute the map outputs rather than decode corrupt
        rows (the Spark shuffle-checksum → FetchFailed contract)."""
        from spark_rapids_tpu.shuffle.transport import TransportError
        from spark_rapids_tpu.runtime import scheduler as SCHED
        store = ShuffleBlockStore.get()
        retries = self.conf.get(C.SHUFFLE_FETCH_MAX_RETRIES)
        for attempt in range(retries + 1):
            # cancellation wins over the stage-retry ladder: a cancelled
            # query must not pay for a map-stage recompute first
            SCHED.check_cancel()
            emitted = False
            # pin the generation this attempt reads: on failure only THIS id
            # may be invalidated (a concurrent reader's recompute may already
            # have published a newer one that must survive)
            sid = self._shuffle_id
            try:
                # fault-injection checkpoint: "transport:fetch:N" chaos specs
                # drop reduce-side fetches here (the stage-retry ladder), the
                # same site name the peer ladder in shuffle/fetch.py checks
                F.maybe_inject("transport", "fetch")
                for b in store.read_partition(sid, split):
                    emitted = True
                    yield b
                return
            except (TransportError, KeyError, mem.BufferClosedError,
                    mem.SpillCorruptionError) as e:
                if emitted or attempt == retries:
                    raise TransportError(
                        f"reduce {split} fetch failed"
                        f"{' after partial read' if emitted else ''}: {e}"
                    ) from e
                M.resilience_add(M.FETCH_RECOMPUTES)
                tracing.span_event("fetch.recompute", split=split,
                                   error=str(e)[:120])
                self._invalidate_map_stage(sid)
                with M.node_frame(self._node_id, None):
                    self._ensure_map_stage()

    def abort_query(self):
        """Query-death cleanup (called by session._run_action on cancel/
        error): when reduce partitions were never all consumed, the
        read-completion countdown can never free the shuffle blocks — a
        cancelled query's unvisited splits have no reader to account them.
        Unregister whatever map outputs are live so the query leaks no
        device buffers. Racing readers (worker threads still draining)
        observe BufferClosedError/KeyError, whose recompute ladder checks
        the cancel token first and drains instead of rebuilding."""
        with self._reads_lock:
            if self._reads_left <= 0:
                return                  # normal completion already freed them
        store = ShuffleBlockStore.get()
        with self._map_lock:
            for sid in (self._shuffle_id, self._pending_shuffle_id):
                if sid is not None:
                    store.unregister_shuffle(sid)
            self._shuffle_id = None
            self._pending_shuffle_id = None

    def account_read_done(self):
        """One reduce partition finished (drained OR abandoned unopened);
        the last one frees the shuffle blocks — the reference keeps them
        until Spark unregisters the shuffle; our local scheduler reads each
        partition exactly once."""
        with self._reads_lock:
            self._reads_left -= 1
            done = self._reads_left == 0
        if done:
            ShuffleBlockStore.get().unregister_shuffle(self._shuffle_id)

    def read_reduce(self, pid):
        """Stream ONE reduce partition with recompute + cleanup accounting;
        shared by the direct reader and AdaptiveShuffleReaderExec. Each pid
        must be consumed (or closed) exactly once across all readers."""
        try:
            yield from self._read_with_recompute(pid)
        finally:
            self.account_read_done()

    def _reader(self, split):
        # post-shuffle coalesce to target batch size (reference
        # GpuShuffleCoalesceExec inserted by GpuTransitionOverrides:57-63)
        goal = TargetSize(self.conf.batch_size_bytes)
        yield from coalesce_iterator(self.read_reduce(split), goal,
                                     self.metrics, conf=self.conf)

    def execute_partition(self, split):
        # drop this task's permit before (possibly) blocking on the map stage —
        # holding it would starve the map tasks and deadlock (the reference
        # releases the semaphore while waiting on shuffle fetches,
        # RapidsShuffleIterator.scala:300)
        from spark_rapids_tpu.exec.base import current_task_id
        from spark_rapids_tpu.runtime.semaphore import TpuSemaphore
        TpuSemaphore.get().release_if_necessary(current_task_id())
        # metric=None frame: waiting for (or inline-running) the map stage is
        # charged by the map tasks' own frames; the parent operator's frame
        # must not double-count the blocked wall time
        with M.node_frame(self._node_id, None):
            self._ensure_map_stage()
        from spark_rapids_tpu.runtime import pipeline as P
        it = self._reader(split)
        if P.enabled(self.conf):
            # reduce-segment boundary: fetch + decompress + coalesce run on
            # the stage's worker thread, overlapping downstream compute
            it = P.stage_iterator(
                it, edge="exchange.reduce", conf=self.conf,
                registry=self.metrics, node_id=self._node_id,
                self_time_metric=self._self_time, spillable=True)
        return self.wrap_output(it)

    def args_string(self):
        return f"{type(self.partitioner).__name__}({self.partitioner.num_partitions})"


class AdaptiveShuffleReaderExec(TpuExec):
    """AQE coalescing shuffle reader (reference GpuCustomShuffleReaderExec +
    Spark's CoalesceShufflePartitions): after the map stage materializes,
    contiguous small reduce partitions merge into reader partitions of
    roughly `adaptive.advisoryPartitionSizeInBytes`, so a skewed or
    over-partitioned shuffle doesn't pay per-partition read overhead.

    The coalescing decision is EXECUTION-time (the AQE stage barrier):
    `num_partitions` stays the exchange's static count so plan conversion
    never triggers the upstream query; splits beyond the merged spec list
    simply come up empty and account for nothing.

    Only planned above exchanges with a single consumer (aggregate/window):
    merging changes the row distribution across splits, which would break
    the co-partitioning contract between the two sides of a shuffled join."""

    def __init__(self, exchange: ShuffleExchangeExec, conf=None):
        super().__init__(exchange, conf=conf)
        self._specs: list | None = None
        self._spec_lock = threading.Lock()

    @property
    def output(self):
        return self.child.output

    @property
    def num_partitions(self):
        # static: asking must NOT run the map stage (the planner asks during
        # conversion); empty tail splits are cheap no-op tasks
        return self.child.num_partitions

    def _ensure_specs(self):
        if self._specs is not None:
            return self._specs
        ex = self.child
        # same no-double-count contract as ShuffleExchangeExec.execute_partition
        with M.node_frame(ex._node_id, None):
            ex._ensure_map_stage()    # own double-checked synchronization
        with self._spec_lock:
            if self._specs is None:
                n = ex.partitioner.num_partitions
                sizes = ShuffleBlockStore.get().partition_sizes(
                    ex._shuffle_id, n)
                target = self.conf.get(C.ADVISORY_PARTITION_BYTES)
                specs, cur, cur_bytes = [], [], 0
                for pid in range(n):
                    if cur and cur_bytes + sizes[pid] > target:
                        specs.append(cur)
                        cur, cur_bytes = [], 0
                    cur.append(pid)
                    cur_bytes += sizes[pid]
                if cur:
                    specs.append(cur)
                self._specs = specs
        return self._specs

    def execute_partition(self, split):
        ex = self.child
        goal = TargetSize(self.conf.batch_size_bytes)

        def it():
            # drop this task's permit before (possibly) blocking on the map
            # stage — holding it would starve the map tasks and deadlock
            # (same guard as ShuffleExchangeExec.execute_partition)
            from spark_rapids_tpu.exec.base import current_task_id
            from spark_rapids_tpu.runtime.semaphore import TpuSemaphore
            TpuSemaphore.get().release_if_necessary(current_task_id())
            specs = self._ensure_specs()
            pids = specs[split] if split < len(specs) else []
            opened = 0
            try:
                for pid in pids:
                    opened += 1
                    yield from ex.read_reduce(pid)   # accounts for itself
            finally:
                # early close mid-spec (limit): the open pid's read_reduce
                # already accounted; the never-opened tail must too, or the
                # shuffle blocks leak
                for _ in pids[opened:]:
                    ex.account_read_done()
        from spark_rapids_tpu.runtime import pipeline as P
        out = coalesce_iterator(it(), goal, self.metrics, conf=self.conf)
        if P.enabled(self.conf):
            # same reduce-segment boundary as the direct reader
            out = P.stage_iterator(
                out, edge="exchange.reduce", conf=self.conf,
                registry=self.metrics, node_id=self._node_id,
                self_time_metric=self._self_time, spillable=True)
        return self.wrap_output(out)

    def args_string(self):
        specs = self._specs
        n = len(specs) if specs is not None else "?"
        return f"coalesced={n}"
