"""Partition-parallel MVCC range scan + compaction over a generic engine.

Reference: pkg/backend/scanner/scanner.go — THE hot loop (worker.run
:389-516). One worker per storage partition iterates internal keys in order
and, in a single pass, implements:

- MVCC visibility: per user key, keep the *last* version <= read_revision
  (ascending (key, revision) order makes this a "next row differs" test);
- tombstone suppression for reads;
- in compact mode: GC of superseded versions, tombstone removal, deletion of
  flagged revision records (guarded against in-flight uncertain retries), and
  TTL expiry of ``/events/`` keys when the engine lacks native TTL.

This module is the *engine-generic* (iterator-based) implementation — the
correctness reference and CPU fallback. The TPU implementation
(``kubebrain_tpu.storage.tpu`` + ``kubebrain_tpu.ops.scan``) computes the same
single-pass visibility/GC decisions as a vectorized kernel over sorted key
blocks, sharded across the device mesh; both satisfy the same ``Scanner``
contract so the backend swaps them freely.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Iterator

from .. import coder
from ..storage import CASFailedError, KvStorage, Partition
from ..trace import TRACER
from .common import TOMBSTONE, KeyValue
from .errors import CompactedError

RANGE_STREAM_BATCH = 300  # reference scanner.go:44 (rangeStreamBatch)
WORKER_RETRIES = 3  # reference scanner.go:351-387 (exponential backoff x3)
EVENTS_TTL_PREFIX = b"/events/"  # reference util.go:28-42
EVENTS_TTL_SECONDS = 3600


@dataclass
class CompactStats:
    scanned: int = 0
    deleted_versions: int = 0
    deleted_tombstones: int = 0
    deleted_rev_records: int = 0
    expired_ttl: int = 0
    # device-mirror accounting (kubebrain_tpu.storage.tpu; the engine-generic
    # host path reports mirror_path="host" and leaves the rest zero):
    # how the mirror absorbed the compaction — "stored_incremental" is the
    # steady path (survivor gather + k-way stored-domain merge, dirty shards
    # only), "full_rebuild" the width-drift/dict-overflow fallback,
    # "superseded" a mirror swapped under the compaction (the fresher mirror
    # came from the post-GC store), "escalated" the bounded-retry give-up
    # (mirror quarantined, background rebuild recovering).
    mirror_path: str = "host"
    survivor_rows: int = 0
    dirty_partitions: int = 0
    #: wall seconds per pipeline phase (mark | gc | merge | publish) —
    #: the same split kb_compact_seconds{phase=} exports
    phase_seconds: dict = field(default_factory=dict)


@dataclass
class _PartitionResult:
    kvs: list[KeyValue] = field(default_factory=list)
    count: int = 0


class CompactHistory:
    """(compact revision, wall time) log used to derive the TTL cutoff
    revision when the engine lacks native TTL.

    Reference: scanner.go:147-177 (logCompactHistory + timeout revision).
    """

    def __init__(self, capacity: int = 128):
        self._entries: list[tuple[int, float]] = []
        self._cap = capacity
        self._lock = threading.Lock()

    def log(self, revision: int, now: float | None = None) -> None:
        with self._lock:
            self._entries.append((revision, time.time() if now is None else now))
            if len(self._entries) > self._cap:
                self._entries = self._entries[-self._cap :]

    def timeout_revision(self, ttl_seconds: float, now: float | None = None) -> int:
        """Largest revision whose compact-log time is older than the TTL —
        keys written at or below it are expired."""
        now = time.time() if now is None else now
        cutoff = now - ttl_seconds
        best = 0
        with self._lock:
            for rev, t in self._entries:
                if t <= cutoff and rev > best:
                    best = rev
        return best


def adjust_partition_borders(
    partitions: list[Partition], start: bytes, end: bytes
) -> list[Partition]:
    """Clamp engine partitions to [start, end) and snap interior borders to
    user-key boundaries so one key's version chain never straddles workers.

    Reference: scanner.go:202-225 (adjustPartitionsBorders) — tested against
    real region keys in scanner_test.go:27.
    """
    borders: list[bytes] = [start]
    for p in partitions:
        b = p.right
        if not b:
            continue
        if b <= start or (end and b >= end):
            continue
        if coder.is_internal_key(b):
            user_key, _ = coder.decode(b)
            b = coder.encode_revision_key(user_key)
            if b <= start or (end and b >= end):
                continue
        if b != borders[-1]:
            borders.append(b)
    borders.append(end)
    out = []
    for i in range(len(borders) - 1):
        left, right = borders[i], borders[i + 1]
        if not right or left < right:
            out.append(Partition(left, right))
    return out or [Partition(start, end)]


class Scanner:
    """Engine-generic scanner (reference Scanner iface, interface.go:23-37)."""

    def __init__(
        self,
        store: KvStorage,
        get_compact_revision: Callable[[int | None], int],
        retry_min_revision: Callable[[], int] = lambda: 0,
        compact_history: CompactHistory | None = None,
        max_workers: int = 8,
    ):
        self._store = store
        self._get_compact_revision = get_compact_revision
        self._retry_min_revision = retry_min_revision
        self.compact_history = compact_history or CompactHistory()
        self._pool = ThreadPoolExecutor(max_workers=max_workers, thread_name_prefix="kb-scan")

    def close(self) -> None:
        self._pool.shutdown(wait=False, cancel_futures=True)

    # ------------------------------------------------------------------ reads
    def range_(
        self, start: bytes, end: bytes, read_revision: int, limit: int = 0
    ) -> tuple[list[KeyValue], bool]:
        """Visible KVs of user-key range [start, end) at read_revision.

        Returns (kvs, more). With a limit, runs a single sequential worker and
        stops early (reference rangeWithLimit, scanner.go:96-119); otherwise
        fans out one worker per partition and merges in partition order
        (scanner.go:227-300).
        """
        lo, hi = coder.internal_range(start, end)
        snapshot = self._snapshot_checked(read_revision)
        # trace attribution: the engine iteration is ``host_scan`` (no
        # device is involved: the device_* stages are the TPU scanner's
        # kernel path alone), the result merge is the host copy
        if limit > 0:
            kvs: list[KeyValue] = []
            with TRACER.stage("host_scan"):
                self._scan_partition(
                    Partition(lo, hi), snapshot, read_revision, kvs.append,
                    limit=limit + 1,
                )
            with TRACER.stage("host_copy"):
                more = len(kvs) > limit
                out = kvs[:limit]
            return out, more
        with TRACER.stage("host_scan"):
            results = self._parallel_scan(lo, hi, snapshot, read_revision)
        with TRACER.stage("host_copy"):
            merged: list[KeyValue] = []
            for r in results:
                merged.extend(r.kvs)
        return merged, False

    def count(self, start: bytes, end: bytes, read_revision: int) -> int:
        lo, hi = coder.internal_range(start, end)
        snapshot = self._snapshot_checked(read_revision)
        with TRACER.stage("host_scan"):
            results = self._parallel_scan(
                lo, hi, snapshot, read_revision, count_only=True)
        return sum(r.count for r in results)

    def range_stream(
        self,
        start: bytes,
        end: bytes,
        read_revision: int,
        batch_size: int = RANGE_STREAM_BATCH,
    ) -> Iterator[list[KeyValue]]:
        """Stream visible KVs in bounded batches so unbounded ranges never
        materialize (reference receiver.go:105-160)."""
        lo, hi = coder.internal_range(start, end)
        snapshot = self._snapshot_checked(read_revision)
        parts = adjust_partition_borders(self._store.get_partitions(lo, hi), lo, hi)
        batch: list[KeyValue] = []
        for part in parts:
            sink: list[KeyValue] = []
            self._scan_with_retry(part, snapshot, read_revision, sink.append)
            for kv in sink:
                batch.append(kv)
                if len(batch) >= batch_size:
                    yield batch
                    batch = []
        if batch:
            yield batch

    # ----------------------------------------------------------------- compact
    def compact(self, start: bytes, end: bytes, compact_revision: int) -> CompactStats:
        """GC every internal row made unreachable by compacting to
        compact_revision (reference scan(compact=true), scanner.go:195-232).

        Runs on an exclusive engine handle so bulk deletes don't contend with
        serving traffic (reference ExclusiveKvStorage, interface.go:28-31).
        """
        lo, hi = (start, end)
        store = self._store.exclusive_client()
        snapshot = store.get_timestamp_oracle()
        self.compact_history.log(compact_revision)
        ttl_cutoff_rev = 0
        if not store.support_ttl():
            ttl_cutoff_rev = self.compact_history.timeout_revision(EVENTS_TTL_SECONDS)
        parts = adjust_partition_borders(store.get_partitions(lo, hi), lo, hi)
        stats = CompactStats()
        futures = [
            self._pool.submit(
                self._compact_partition, store, p, snapshot, compact_revision, ttl_cutoff_rev
            )
            for p in parts
        ]
        for f in futures:
            s = f.result()
            stats.scanned += s.scanned
            stats.deleted_versions += s.deleted_versions
            stats.deleted_tombstones += s.deleted_tombstones
            stats.deleted_rev_records += s.deleted_rev_records
            stats.expired_ttl += s.expired_ttl
        return stats

    # --------------------------------------------------------------- internals
    def _snapshot_checked(self, read_revision: int) -> int:
        snapshot = self._store.get_timestamp_oracle()
        compacted = self._get_compact_revision(snapshot)
        if read_revision and compacted and read_revision < compacted:
            raise CompactedError(read_revision, compacted)
        return snapshot

    def _parallel_scan(
        self,
        lo: bytes,
        hi: bytes,
        snapshot: int,
        read_revision: int,
        count_only: bool = False,
    ) -> list[_PartitionResult]:
        parts = adjust_partition_borders(self._store.get_partitions(lo, hi), lo, hi)
        futures = [
            self._pool.submit(self._run_partition, p, snapshot, read_revision, count_only)
            for p in parts
        ]
        return [f.result() for f in futures]

    def _run_partition(
        self, part: Partition, snapshot: int, read_revision: int, count_only: bool
    ) -> _PartitionResult:
        result = _PartitionResult()
        if count_only:
            def emit(kv: KeyValue) -> None:
                result.count += 1
        else:
            def emit(kv: KeyValue) -> None:
                result.kvs.append(kv)
                result.count += 1
        self._scan_with_retry(part, snapshot, read_revision, emit)
        return result

    def _scan_with_retry(
        self,
        part: Partition,
        snapshot: int,
        read_revision: int,
        emit: Callable[[KeyValue], None],
        limit: int = 0,
    ) -> None:
        backoff = 0.01
        for attempt in range(WORKER_RETRIES):
            # buffer per attempt: a retry after a mid-scan failure must not
            # re-emit rows the failed attempt already produced
            buf: list[KeyValue] = []
            try:
                self._scan_partition(part, snapshot, read_revision, buf.append, limit)
            except Exception:
                if attempt == WORKER_RETRIES - 1:
                    raise
                time.sleep(backoff)
                backoff *= 2
                continue
            for kv in buf:
                emit(kv)
            return

    def _scan_partition(
        self,
        part: Partition,
        snapshot: int,
        read_revision: int,
        emit: Callable[[KeyValue], None],
        limit: int = 0,
    ) -> None:
        """The single-pass visibility loop (reference worker.run :389-516)."""
        emitted = 0
        cur_key: bytes | None = None
        candidate: KeyValue | None = None

        def flush() -> bool:
            nonlocal candidate, emitted
            if candidate is not None and candidate.value != TOMBSTONE:
                emit(candidate)
                emitted += 1
                candidate = None
                return bool(limit and emitted >= limit)
            candidate = None
            return False

        it = self._store.iter(part.left, part.right, snapshot_ts=snapshot)
        for ikey, value in it:
            user_key, rev = coder.decode(ikey)
            if user_key != cur_key:
                if flush():
                    return
                cur_key = user_key
            if rev == 0:
                continue  # revision record, not a version row
            if rev <= read_revision:
                # ascending revision order: later rows supersede
                candidate = KeyValue(user_key, value, rev)
        flush()

    def _compact_partition(
        self,
        store: KvStorage,
        part: Partition,
        snapshot: int,
        compact_revision: int,
        ttl_cutoff_rev: int,
    ) -> CompactStats:
        """One pass collecting GC victims, then batched engine deletes.

        Victim classes (reference worker.run :445-491,566-591):
        - version rows superseded by a newer version <= compact_revision;
        - tombstone version rows at <= compact_revision;
        - revision records whose latest write is a tombstone <= compact_revision
          (deleted via del_current, and only when no uncertain retry below
          that revision is in flight — scanner.go:477-491);
        - ``/events/`` rows whose revision is below the TTL cutoff revision.
        """
        stats = CompactStats()
        retry_min = self._retry_min_revision()
        plain_victims: list[bytes] = []
        guarded_victims: list[tuple[bytes, bytes]] = []  # (rev_key, expected_value)

        rows: list[tuple[bytes, int, bytes]] = []  # (user_key, rev, value)
        rev_record: tuple[bytes, bytes] | None = None  # (internal rev key, raw value)

        def flush_group() -> None:
            nonlocal rows, rev_record
            if not rows and rev_record is None:
                return
            user_key = rows[0][0] if rows else coder.decode(rev_record[0])[0]
            is_events = user_key.startswith(EVENTS_TTL_PREFIX)
            # last version <= compact_revision survives; older ones are victims
            last_visible = -1
            for i, (_k, rev, _v) in enumerate(rows):
                if rev <= compact_revision:
                    last_visible = i
            expired = bool(
                is_events
                and ttl_cutoff_rev
                and rows
                and rows[-1][1] <= ttl_cutoff_rev
            )
            for i, (_k, rev, value) in enumerate(rows):
                doomed = i < last_visible or expired
                if i == last_visible and value == TOMBSTONE:
                    doomed = True  # the visible version is a tombstone: gone
                    stats.deleted_tombstones += 1
                if doomed:
                    plain_victims.append(coder.encode_object_key(user_key, rev))
                    if i < last_visible:
                        stats.deleted_versions += 1
                    elif expired and value != TOMBSTONE:
                        stats.expired_ttl += 1
            # revision record GC: only when the key is fully gone
            if rev_record is not None:
                rev_key, raw = rev_record
                try:
                    latest_rev, deleted = coder.decode_rev_value(raw)
                except coder.CodecError:
                    latest_rev, deleted = 0, False
                fully_gone = (deleted and latest_rev <= compact_revision) or (
                    expired and latest_rev <= ttl_cutoff_rev
                )
                uncertain_inflight = retry_min and latest_rev >= retry_min
                if fully_gone and not uncertain_inflight:
                    guarded_victims.append((rev_key, raw))
            rows = []
            rev_record = None

        it = store.iter(part.left, part.right, snapshot_ts=snapshot)
        cur_key: bytes | None = None
        for ikey, value in it:
            user_key, rev = coder.decode(ikey)
            stats.scanned += 1
            if user_key != cur_key:
                flush_group()
                cur_key = user_key
            if rev == 0:
                rev_record = (ikey, value)
            else:
                rows.append((user_key, rev, value))
        flush_group()

        # batched deletes: unconditional for superseded rows, guarded
        # (delete-if-unchanged) for revision records. Each batch retries with
        # backoff like the scan workers (scanner.go:351-387) — deletes are
        # idempotent, so re-running a batch is safe.
        BATCH = 256
        for i in range(0, len(plain_victims), BATCH):
            chunk = plain_victims[i : i + BATCH]

            def commit_chunk() -> None:
                b = store.begin_batch_write()
                for k in chunk:
                    b.delete(k)
                b.commit()

            backoff = 0.01
            for attempt in range(WORKER_RETRIES):
                try:
                    commit_chunk()
                    break
                except CASFailedError:
                    raise
                except Exception:
                    if attempt == WORKER_RETRIES - 1:
                        raise
                    time.sleep(backoff)
                    backoff *= 2
        for rev_key, expected in guarded_victims:
            try:
                store.del_current(rev_key, expected)
                stats.deleted_rev_records += 1
            except CASFailedError:
                continue  # key was rewritten since the scan: skip
        # engine-level history pruning: logical deletes above only append
        # markers; physically free chains invisible to snapshots taken after
        # the GC (fresh clock — the pre-GC snapshot would spare the GC's own
        # markers). No-op for engines without the capability.
        pruner = getattr(store, "prune_versions", None)
        if pruner is not None:
            pruner(store.get_timestamp_oracle())
        return stats
