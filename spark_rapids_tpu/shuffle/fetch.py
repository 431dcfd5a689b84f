"""Fetch-failure recovery: transport retries, failover, then recompute.

Reference: RapidsShuffleIterator.scala:82,153 — a TransferError from the UCX
client surfaces as a FetchFailedException, Spark retries the fetch and
ultimately recomputes the map stage. Two complementary layers here:

- THIS module is the peer/network ladder for transport-backed reads
  (cross-process fetches over shuffle/transport.py): retry the same peer with
  a fresh connection, fail over to replica peers, finally call a recompute
  callback.
- exec/exchange.py owns the STAGE ladder for its local reads: a failed read
  invalidates the map outputs and re-runs the map stage (Spark's
  FetchFailed → stage retry), bounded by spark.rapids.tpu.shuffle.fetch.maxRetries.

Retries back off EXPONENTIALLY with jitter and a hard cap (a linear,
jitter-free backoff synchronizes a fleet of failed fetchers into retry
stampedes against a recovering peer), and every retry/failover/recompute is
counted into the process-wide resilience registry
(runtime/metrics.global_registry) so chaos tests can assert on them.
"""

from __future__ import annotations

import random
import time

from spark_rapids_tpu.runtime import faults as F
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import movement as MV
from spark_rapids_tpu.runtime import tracing
from spark_rapids_tpu.runtime.memory import SpillCorruptionError
from spark_rapids_tpu.shuffle.transport import _NO_KEY, TransportError


class ShuffleFetchIterator:
    """Iterate one reduce partition's batches with retry → failover →
    recompute (RapidsShuffleIterator analog)."""

    def __init__(self, client_factories: list, shuffle_id: int, reduce_id: int,
                 recompute=None, max_retries: int = 2,
                 retry_backoff_s: float = 0.05,
                 retry_backoff_max_s: float = 2.0, jitter=None):
        """client_factories: zero-arg callables, each returning a FRESH
        ShuffleClient for one peer (a dead connection must not be reused).
        recompute: zero-arg callable yielding the partition's batches by
        re-running the map-side work; raises if it cannot.
        max_retries: EXTRA attempts per peer beyond the first.
        retry_backoff_s / retry_backoff_max_s: base and cap of the jittered
        exponential backoff between same-peer attempts.
        jitter: optional random.Random override; the default seeds from the
        (shuffle, reduce) ids so a schedule is reproducible per partition
        while staying decorrelated across partitions."""
        self.client_factories = client_factories
        self.shuffle_id = shuffle_id
        self.reduce_id = reduce_id
        self.recompute = recompute
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.retry_backoff_max_s = retry_backoff_max_s
        self._rng = jitter or random.Random(
            0x5F37 ^ (shuffle_id << 16) ^ reduce_id)
        self.errors: list[str] = []

    def _backoff(self, attempt: int) -> float:
        """Jittered exponential delay: base·2^attempt capped, scaled by a
        uniform [0.5, 1.0) factor (decorrelates concurrent fetchers)."""
        d = min(self.retry_backoff_s * (2 ** attempt),
                self.retry_backoff_max_s)
        return d * (0.5 + self._rng.random() / 2)

    def __iter__(self):
        for _, b in self.iter_keyed():
            yield b

    def iter_keyed(self):
        """The retry→failover→recompute ladder, yielding (sort_key, batch)
        via the clients' keyed fetch API: sort_key is the block's
        (map_split, seq) wire key so a multi-peer union reader can merge
        several peers' disjoint block sets into one canonical order
        (recomputed batches carry the sort-last sentinel)."""
        from spark_rapids_tpu.runtime import scheduler as SCHED
        for pi, factory in enumerate(self.client_factories):
            for attempt in range(self.max_retries + 1):
                # a cancelled query must not grind through the whole
                # retry -> failover -> recompute ladder first
                SCHED.check_cancel()
                batches = []
                # movement-ledger attempt scope: bytes this attempt pulls
                # land on shuffle.recv; a failed attempt discards its
                # buffered batches below, so abort_attempt moves exactly
                # those bytes onto the shuffle.retry edge (re-fetching must
                # not double-count the recv ledger against partition sizes)
                tok = MV.begin_attempt()
                try:
                    # chaos checkpoint, shared site name with the stage
                    # ladder in exec/exchange.py ("transport:fetch:N")
                    F.maybe_inject("transport", "fetch")
                    client = factory()
                    keyed_fetch = getattr(client, "fetch_blocks_with_keys",
                                          None)
                    if keyed_fetch is not None:
                        stream = keyed_fetch(self.shuffle_id, self.reduce_id)
                    else:
                        # duck-typed client without the keyed API: sentinel
                        # keys keep per-client arrival order
                        stream = ((_NO_KEY, b) for b in client.fetch_blocks(
                            self.shuffle_id, self.reduce_id))
                    for kb in stream:
                        # buffer before yielding: a mid-stream failure must
                        # not emit a partial partition twice
                        batches.append(kb)
                except (TransportError, SpillCorruptionError) as e:
                    MV.abort_attempt(tok)
                    # a CRC mismatch — on the wire (TransportError from the
                    # TCP client) or in a peer's spilled block (unspill
                    # verification) — IS a fetch failure: retry, fail over,
                    # recompute; never decode corrupt rows
                    self.errors.append(
                        f"peer {pi} attempt {attempt}: {e}")
                    tracing.span_event("fetch.error", peer=pi,
                                       attempt=attempt, error=str(e)[:120])
                    if attempt < self.max_retries:  # no sleep before failover
                        M.resilience_add(M.FETCH_RETRIES)
                        tracing.span_event("fetch.retry", peer=pi,
                                           attempt=attempt,
                                           shuffle=self.shuffle_id,
                                           reduce=self.reduce_id)
                        SCHED.check_cancel()   # don't sleep a dead query
                        time.sleep(self._backoff(attempt))
                    continue
                except BaseException:
                    # cancellation or an unexpected error: nothing retries
                    # these bytes, keep them on shuffle.recv
                    MV.commit_attempt(tok)
                    raise
                MV.commit_attempt(tok)
                yield from batches
                return
            if pi < len(self.client_factories) - 1:
                M.resilience_add(M.FETCH_FAILOVERS)
                tracing.span_event("fetch.failover", from_peer=pi,
                                   shuffle=self.shuffle_id,
                                   reduce=self.reduce_id)
        if self.recompute is None:
            raise TransportError(
                "all peers failed for shuffle %d reduce %d: %s"
                % (self.shuffle_id, self.reduce_id, "; ".join(self.errors)))
        M.resilience_add(M.FETCH_RECOMPUTES)
        tracing.span_event("fetch.recompute", shuffle=self.shuffle_id,
                           reduce=self.reduce_id)
        for b in self.recompute():
            yield _NO_KEY, b


def iter_union_blocks(peer_factories: list, shuffle_id: int, reduce_id: int,
                      max_retries: int = 2, epoch: int | None = None):
    """Fetch one reduce partition as the UNION of every peer's blocks (the
    MiniCluster data layout: each mapper parked its buckets locally, so
    peers hold DISJOINT block sets — failing over between them would lose
    data, unlike the replica semantics of ShuffleFetchIterator). Each peer
    gets its own same-peer retry ladder with jittered backoff; a peer that
    stays unreachable raises TransportError so the driver can classify the
    loss and run a lineage-scoped recompute. `epoch` tags the retry events
    with the map-output epoch the fetch was planned under.

    The union is merged into canonical (map_split, seq) key order, NOT
    concatenated in peer order: after a partial stage recompute a map
    split's blocks live on a DIFFERENT peer than in a clean run, and
    order-sensitive consumers (float aggregation, limit) must still see a
    bit-identical stream. Untagged blocks carry the sort-last sentinel and
    keep their (peer, arrival) order."""
    keyed = []
    # task-level movement attempt: when one peer stays unreachable the
    # WHOLE reduce task fails and the driver's recompute re-fetches every
    # peer — the bytes the healthy peers already delivered to this failed
    # attempt must move to the shuffle.retry edge (inner per-peer aborts
    # already deducted their share from this outer token)
    union_tok = MV.begin_attempt()
    try:
        for pi, factory in enumerate(peer_factories):
            it = ShuffleFetchIterator([factory], shuffle_id, reduce_id,
                                      recompute=None,
                                      max_retries=max_retries,
                                      jitter=random.Random(
                                          0x7A11 ^ (shuffle_id << 16)
                                          ^ (reduce_id << 4) ^ pi))
            try:
                for key, batch in it.iter_keyed():
                    keyed.append((key, pi, len(keyed), batch))
            except TransportError as e:
                raise TransportError(
                    f"peer {pi} unreachable for shuffle {shuffle_id} reduce "
                    f"{reduce_id} (epoch {epoch}): {e}") from e
    except TransportError:
        MV.abort_attempt(union_tok)
        raise
    except BaseException:
        MV.commit_attempt(union_tok)
        raise
    MV.commit_attempt(union_tok)
    keyed.sort(key=lambda t: (t[0], t[1], t[2]))
    for _, _, _, batch in keyed:
        yield batch
