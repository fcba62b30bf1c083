"""The device-aware request scheduler.

Queue model (docs/scheduler.md):

- every range read (list / count / list_wire / list_by_stream) becomes a
  ``_Request`` in one of three priority lanes (lanes.py), with per-client
  FIFO sub-queues served round-robin inside a lane — one chatty client
  cannot monopolize its lane;
- ONE dispatcher thread pops strictly by lane priority and hands requests
  to a worker pool whose in-flight count is bounded by ``depth``. Workers
  block on their own result, so up to ``depth`` device dispatches are in
  flight at once (JAX's async dispatch), with host-side
  overlay/materialize work overlapping device compute for neighbors;
- identical queued requests coalesce: followers attach to the queued
  leader and share its one execution. This is revision-safe for rev-0
  reads because the leader resolves its read revision at *execution*
  start, which is later than every follower's enqueue — so each follower
  sees everything it wrote before asking (read-your-writes holds);
  explicit-revision requests additionally join an already-executing
  leader, whose result is deterministic;
- DISTINCT queued scan requests batch: when a dispatch slot frees, the
  dispatcher drains up to ``batch - 1`` additional compatible ready scan
  requests (same backend batch executor; iterators and wire-encoded
  lists excluded) and the worker launches them as ONE batched backend
  call — over the TPU engine that is one query-batched kernel dispatch
  for the whole set (``TpuScanner.scan_batch``) — then demuxes each
  member's result (or per-query error) to its own waiter. Rev-0 members
  are safe for the same reason coalescing is: the batch resolves read
  revisions at execution start, after every member's enqueue. Batching
  composes with lanes (members drain in strict lane-priority order, so a
  SYSTEM read rides the next slot rather than queuing behind it),
  with coalescing (a drained member's followers share its demuxed
  result), and with pipelined depth (each slot now carries a batch);
- WRITES ride the same lanes (create/update/delete entry points;
  docs/writes.md): a write never coalesces (it is an effect, not a pure
  read), but when a dispatch slot frees behind a write leader the
  dispatcher drains up to ``write_batch - 1`` additional queued write ops
  (same head-only per-client pops, so same-client order is sequential
  order) into ONE ``backend.write_batch`` commit group — a contiguous
  revision block, one engine round trip with per-op CAS/exists demux,
  one event-ring pass. Conflicts inside a group fail only their own op,
  byte-identical to back-to-back sequential commits by construction;
- overload: each lane queue is bounded (``queue_limit``; enqueue sheds
  immediately when full) and every request carries an age deadline
  (``shed_ms``; stale requests shed at pop). Shed requests surface as
  ``SchedOverloadError`` which the etcd surface maps to the
  ``ResourceExhausted`` wire status kube-apiserver already retries on —
  for writes this is new but safe admission control: a shed write was
  never dealt a revision, and the apiserver's etcd3 client retries the
  txn exactly like an overloaded etcd.

The scheduler is engine-agnostic: it schedules *backend* entry points, so
the same admission path runs over the TPU mirror scanner and the generic
iterator scanner (the CPU fallback exercised by tier-1).
"""

from __future__ import annotations

import math
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable

from ..trace import TRACER
from ..util import fieldcheck
from .lanes import Lane, classify, classify_write

#: wire message kube-apiserver's etcd3 client recognizes and retries on
ERR_TOO_MANY_REQUESTS = "etcdserver: too many requests"

#: auto-depth (--sched-depth 0) bounds: the measured dispatch-RTT / compute
#: ratio is clamped here so a noisy EWMA can neither serialize the pipeline
#: nor oversubscribe the device queue
AUTO_DEPTH_MIN = 2
AUTO_DEPTH_MAX = 16
#: depth used in auto mode until the tracer has device-stage measurements
AUTO_DEPTH_DEFAULT = 4


class SchedOverloadError(Exception):
    """Request shed by admission control (queue full or deadline passed)."""

    def __init__(self, lane: Lane, reason: str) -> None:
        super().__init__(f"{ERR_TOO_MANY_REQUESTS} (lane={lane.name.lower()}, {reason})")
        self.lane = lane
        self.reason = reason


class SchedResultTimeoutError(SchedOverloadError):
    """The submitter gave up waiting for a result AFTER the request may
    have been dispatched: the outcome is ambiguous (the op may yet commit).
    Distinct from admission-control sheds (queue full / deadline passed,
    which happen strictly before a revision is dealt) so write surfaces can
    map it to an ambiguous status (DEADLINE_EXCEEDED) instead of etcd's
    safe-to-retry RESOURCE_EXHAUSTED."""


class SchedClosedError(Exception):
    """Scheduler shut down while the request was queued."""


def client_of(context: Any) -> str:
    """Fair-queuing flow id for a gRPC(-ish) context: the transport peer
    when the context has one (python-grpc), else anonymous (native-front
    backhaul contexts have no peer()). Shared by every service surface so
    flow ids cannot drift between protocols."""
    peer = getattr(context, "peer", None)
    try:
        return peer() if callable(peer) else ""
    except Exception:
        return ""


@dataclass
class SchedConfig:
    depth: int = 4           # bounded in-flight device dispatches; 0 = auto
    #                          (sized from the tracer's dispatch-RTT EWMA,
    #                          clamped AUTO_DEPTH_MIN..MAX)
    queue_limit: int = 1024  # per-lane queued-request bound
    shed_ms: float = 5000.0  # max queue age before a request is shed
    workers: int = 0         # worker threads; 0 = same as depth
    batch: int = 8           # max distinct ready scan requests per dispatch
    #                          slot (query-batched device scan); 1 disables
    write_batch: int = 8     # max queued write ops drained into one commit
    #                          group (backend.write_batch: one contiguous
    #                          revision block + one engine round trip);
    #                          1 disables grouping


class _Request:
    __slots__ = ("fn", "lane", "client", "key", "deterministic", "enqueued",
                 "done", "result", "error", "followers", "span", "joined",
                 "finished_at", "bargs", "bexec", "batch_members",
                 "joined_batch")

    def __init__(self, fn: Callable[[], Any], lane: Lane, client: str,
                 key: Any, deterministic: bool = False,
                 bargs: Any = None, bexec: Any = None) -> None:
        self.fn = fn
        self.lane = lane
        self.client = client
        self.key = key
        self.deterministic = deterministic
        self.enqueued = time.monotonic()
        self.done = threading.Event()
        self.result = None
        self.error: BaseException | None = None
        self.followers: list["_Request"] = []
        # the submitting thread's trace span: workers adopt it so scheduler
        # and backend stages land on the RPC's span tree
        self.span = TRACER.current()
        self.joined = False       # attached to a coalesced leader
        self.finished_at = 0.0    # monotonic completion time (result_deliver)
        # query-batching descriptor + executor: requests sharing ``bexec``
        # may ride one dispatch slot as ``bexec([bargs...]) -> [result...]``
        self.bargs = bargs
        self.bexec = bexec
        self.batch_members: list["_Request"] = []  # set on a batch leader
        self.joined_batch = False  # rode another leader's batched dispatch

    # ---- completion (leader result fans out to coalesced followers)
    def finish(self, result: Any = None,
               error: BaseException | None = None,
               executed_at: float = 0.0) -> None:
        """``executed_at`` is when the execution ended, where the worker
        stamped it before its slot release and bookkeeping: those are part
        of handing the result back (``result_deliver``), not a hole in the
        request's account."""
        self.result = result
        self.error = error
        self.finished_at = executed_at or time.monotonic()
        self.done.set()
        for f in self.followers:
            f.result = result
            f.error = error
            f.finished_at = self.finished_at
            f.done.set()

    def wait(self, timeout: float) -> object:
        if not self.done.wait(timeout):
            raise SchedResultTimeoutError(self.lane, "result wait timed out")
        if self.error is not None:
            raise self.error
        return self.result


class _LaneQueue:
    """Per-client FIFOs + round-robin service order, O(1) ops.

    Invariant: a client appears in ``order`` exactly once while (and only
    while) it has a non-empty deque in ``clients`` — push creates both
    together, pop removes both together when the deque drains, or re-queues
    the client at the back of the service order otherwise. Anything looser
    accumulates stale ``order`` entries across drain/refill cycles, which
    both leaks and skews the round-robin toward long-lived clients."""

    __slots__ = ("clients", "order", "size")

    def __init__(self):
        self.clients: dict[str, deque] = {}
        self.order: deque[str] = deque()
        self.size = 0

    def push(self, req: _Request) -> None:
        q = self.clients.get(req.client)
        if q is None:
            q = self.clients[req.client] = deque()
            self.order.append(req.client)
        q.append(req)
        self.size += 1

    def pop(self) -> _Request | None:
        while self.order:
            client = self.order.popleft()
            q = self.clients.get(client)
            if not q:  # defensive; unreachable while the invariant holds
                self.clients.pop(client, None)
                continue
            req = q.popleft()
            self.size -= 1
            if q:
                self.order.append(client)  # back of the service order
            else:
                del self.clients[client]
            return req
        return None

    def pop_matching(self, pred: Callable[[_Request], bool]) -> _Request | None:
        """Pop the first request satisfying ``pred``, scanning clients in
        service order but inspecting only each client's queue HEAD — a
        client's own FIFO order is never reordered, and non-matching
        clients keep their place in the round-robin."""
        for i, client in enumerate(self.order):
            q = self.clients.get(client)
            if not q or not pred(q[0]):
                continue
            req = q.popleft()
            self.size -= 1
            del self.order[i]
            if q:
                self.order.append(client)  # back of the service order
            else:
                del self.clients[client]
            return req
        return None


@fieldcheck.track
class RequestScheduler:
    """Admission + coalescing + bounded-depth pipelined dispatch.

    ``backend`` may be None for generic use (``submit``/``submit_async``
    only, as tests/test_sched.py drives it).
    """

    def __init__(self, backend: Any = None,
                 config: SchedConfig | None = None,
                 metrics: Any = None) -> None:
        self.backend = backend
        self.config = config or SchedConfig()
        self.metrics = metrics
        self._cv = threading.Condition()
        self._queues = {lane: _LaneQueue() for lane in Lane}
        self._pending: dict[object, _Request] = {}   # queued, by coalesce key
        self._inflight: dict[object, _Request] = {}  # executing, by key
        self._inflight_count = 0
        # dispatch-slot gate (was a BoundedSemaphore): a counter + condition
        # so the bound can follow current_depth() when depth is auto (0)
        self._slots_cv = threading.Condition()
        self._slots_used = 0
        self._closed = False
        self._started = False
        self._dispatcher: threading.Thread | None = None
        self._workers: list[threading.Thread] = []
        self._runq: deque[_Request] = deque()
        self._run_cv = threading.Condition()
        self.shed_counts = {lane: 0 for lane in Lane}
        self.coalesced = 0
        self.dispatched = 0
        self.batched = 0  # requests that rode another leader's batch slot
        self.write_batched = 0  # write ops that rode another leader's group
        # the backend's batch executors, resolved ONCE so member
        # compatibility is an identity check (bound methods are fresh
        # objects per access). Scan batches and write groups never mix:
        # each request carries exactly one executor identity.
        self._backend_bexec = (
            getattr(backend, "list_batch", None) if backend is not None else None
        )
        self._backend_wexec = (
            getattr(backend, "write_batch", None) if backend is not None else None
        )
        if metrics is not None:
            for lane in Lane:
                metrics.register_gauge_fn(
                    "kb.sched.queue.depth",
                    (lambda l=lane: self._queues[l].size), lane=lane.name.lower(),
                )
            metrics.register_gauge_fn(
                "kb.sched.inflight", lambda: self._inflight_count)
            metrics.register_gauge_fn("kb.sched.depth", self.current_depth)
            metrics.register_gauge_fn(
                "kb.sched.dispatch.rtt.seconds",
                lambda: TRACER.dispatch_rtt() or 0.0)

    # ---------------------------------------------------------------- depth
    def current_depth(self) -> int:
        """The in-flight dispatch bound. Fixed (--sched-depth N) or, in auto
        mode (N=0), derived from the tracer's measured device timings: to
        keep the device busy the pipeline must cover the full dispatch round
        trip, so depth ≈ ceil(dispatch_rtt / device_compute) — where the
        dispatch round trip dwarfs compute, depth grows toward
        AUTO_DEPTH_MAX; where it is small it settles near AUTO_DEPTH_MIN."""
        if self.config.depth > 0:
            return self.config.depth
        # only the TPU engine's kernel path records the device_* stages
        # (a host scan is host_scan), so a µs-scale host iteration never
        # shrinks the divisor
        rtt = TRACER.dispatch_rtt()
        compute = TRACER.ewma("device_compute")
        if not rtt or not compute or compute <= 0:
            return AUTO_DEPTH_DEFAULT
        return max(AUTO_DEPTH_MIN, min(AUTO_DEPTH_MAX, math.ceil(rtt / compute)))

    def _acquire_slot(self) -> bool:
        """Block until an in-flight slot frees (False when closing). The
        bound is re-read each wakeup so auto depth applies immediately."""
        with self._slots_cv:
            while True:
                if self._closed:
                    return False
                if self._slots_used < self.current_depth():
                    self._slots_used += 1
                    return True
                self._slots_cv.wait(timeout=0.2)

    def _release_slot(self) -> None:
        with self._slots_cv:
            self._slots_used -= 1
            self._slots_cv.notify()

    # ------------------------------------------------------------- lifecycle
    def _ensure_started(self) -> None:
        if self._started:
            return
        with self._cv:
            if self._started or self._closed:
                return
            from ..util.env import crash_guard

            self._dispatcher = threading.Thread(
                target=crash_guard(self._dispatch_loop), name="kb-sched",
                daemon=True,
            )
            # auto depth (0) can grow to AUTO_DEPTH_MAX at runtime; the
            # worker pool must already be wide enough to use those slots
            n = self.config.workers or max(1, self.config.depth or AUTO_DEPTH_MAX)
            self._workers = [
                threading.Thread(target=self._work_loop,
                                 name=f"kb-sched-w{i}", daemon=True)
                for i in range(n)
            ]
            self._started = True
            self._dispatcher.start()
            for w in self._workers:
                w.start()

    def close(self) -> None:
        with self._cv:
            if self._closed:
                return
            # the close latch is read under all three condition variables
            # (dispatcher under _cv, slot waiters under _slots_cv, workers
            # under _run_cv): set it while holding each so every reader
            # shares a guard with this write, and notify inside the same
            # holds — waiters wake immediately instead of riding out
            # their 0.2 s poll timeout (kblint KB120). Acquisition order
            # _cv -> _slots_cv -> _run_cv is new; KB115's static graph
            # stays acyclic (no path takes them in reverse).
            with self._slots_cv:
                with self._run_cv:
                    self._closed = True
                    self._run_cv.notify_all()
                self._slots_cv.notify_all()
            dangling: list[_Request] = []
            for lq in self._queues.values():
                while True:
                    r = lq.pop()
                    if r is None:
                        break
                    dangling.append(r)
            self._pending.clear()
            self._cv.notify_all()
        for r in dangling:
            r.finish(error=SchedClosedError("scheduler closed"))
        if self._dispatcher is not None:
            self._dispatcher.join(timeout=2.0)
        for w in self._workers:
            w.join(timeout=2.0)
        # final sweep: anything the dispatcher managed to hand off after
        # the workers exited must still be completed, not strand a caller
        with self._run_cv:
            leftovers = list(self._runq)
            self._runq.clear()
        for r in leftovers:
            for m in r.batch_members:  # batch riders must not strand either
                m.finish(error=SchedClosedError("scheduler closed"))
            r.finish(error=SchedClosedError("scheduler closed"))
        if self.metrics is not None:
            # drop the gauge callbacks registered in __init__: they close
            # over this instance, so a dangling registration keeps a dead
            # scheduler (and its backend) alive in the metrics registry
            for lane in Lane:
                self.metrics.unregister_gauge_fn(
                    "kb.sched.queue.depth", lane=lane.name.lower())
            self.metrics.unregister_gauge_fn("kb.sched.inflight")
            self.metrics.unregister_gauge_fn("kb.sched.depth")
            self.metrics.unregister_gauge_fn("kb.sched.dispatch.rtt.seconds")

    # -------------------------------------------------------------- enqueue
    def submit_async(self, fn: Callable[[], Any],
                     lane: Lane = Lane.NORMAL, client: str = "",
                     key: Any = None, deterministic: bool = False,
                     bargs: Any = None, bexec: Any = None) -> _Request:
        """Enqueue ``fn`` and return the waitable request (``.wait(t)``).
        Raises SchedOverloadError immediately when the lane queue is full.
        ``deterministic`` marks a request whose result is a pure function
        of its key (explicit read revision): it may additionally join an
        already-executing leader. ``bargs`` (with an optional ``bexec``
        override, default: the backend's ``list_batch``) marks the request
        query-batchable: a freed dispatch slot may drain it alongside other
        requests sharing the same executor and run
        ``bexec([bargs, ...]) -> [result-or-Exception, ...]`` as one
        dispatch, demuxing element i to waiter i."""
        self._ensure_started()
        if bargs is not None and bexec is None:
            bexec = self._backend_bexec
        req = _Request(fn, lane, client, key, deterministic,
                       bargs=bargs, bexec=bexec)
        with self._cv:
            if self._closed:
                raise SchedClosedError("scheduler closed")
            if key is not None:
                leader = self._pending.get(key)
                if leader is not None:
                    req.joined = True
                    leader.followers.append(req)
                    self.coalesced += 1
                    self._emit_counter("kb.sched.coalesced.total", lane)
                    return req
                if req.deterministic:
                    running = self._inflight.get(key)
                    if running is not None:
                        req.joined = True
                        running.followers.append(req)
                        self.coalesced += 1
                        self._emit_counter("kb.sched.coalesced.total", lane)
                        return req
            lq = self._queues[lane]
            if lq.size >= self.config.queue_limit:
                self.shed_counts[lane] += 1
                self._emit_counter("kb.sched.shed.total", lane, reason="queue_full")
                raise SchedOverloadError(lane, "queue full")
            lq.push(req)
            if key is not None:
                self._pending[key] = req
            self._cv.notify()
        return req

    def submit(self, fn: Callable[[], Any], lane: Lane = Lane.NORMAL,
               client: str = "", key: Any = None,
               deterministic: bool = False, bargs: Any = None,
               bexec: Any = None) -> Any:
        """Blocking submit: schedule ``fn`` and return its result."""
        req = self.submit_async(fn, lane, client, key, deterministic,
                                bargs=bargs, bexec=bexec)
        timeout = self.config.shed_ms / 1000.0 * 4 + 60.0
        try:
            res = req.wait(timeout)
        finally:
            now = time.monotonic()
            if req.joined:
                # follower: its whole scheduler residency is one stage — the
                # execution stages live on the leader's span
                TRACER.record_stage("coalesce_join", req.enqueued, now,
                                    span=req.span)
            elif req.finished_at:
                # execution end -> waiter wakeup (the worker's slot release
                # and bookkeeping, then the handoff), so stage durations sum
                # to the observed end-to-end latency (no unattributed tail)
                TRACER.record_stage("result_deliver", req.finished_at, now,
                                    span=req.span)
        if self.metrics is not None:
            self.metrics.emit_histogram(
                "kb.sched.wait.seconds", time.monotonic() - req.enqueued,
                lane=lane.name.lower(),
            )
        return res

    # ----------------------------------------------- backend range entries
    # (the only scan path the service layer may use; kblint KB106)
    def list_(self, start: bytes, end: bytes, revision: int = 0,
              limit: int = 0, client: str = "") -> Any:
        lane = classify(start, end, limit)
        key = ("list", start, end, revision, limit)
        return self.submit(
            lambda: self.backend.list_(start, end, revision, limit),
            lane, client, key, deterministic=revision != 0,
            bargs=("list", start, end, revision, limit),
        )

    def count(self, start: bytes, end: bytes, revision: int = 0,
              client: str = "") -> Any:
        lane = classify(start, end, count_only=True)
        key = ("count", start, end, revision)
        return self.submit(
            lambda: self.backend.count(start, end, revision), lane, client,
            key, deterministic=revision != 0,
            bargs=("count", start, end, revision),
        )

    def list_wire(self, start: bytes, end: bytes, revision: int = 0,
                  limit: int = 0, client: str = "") -> Any:
        if getattr(self.backend.scanner, "list_wire", None) is None:
            return None  # engine has no wire encoder; skip the queue round
        lane = classify(start, end, limit)
        key = ("wire", start, end, revision, limit)
        return self.submit(
            lambda: self.backend.list_wire(start, end, revision, limit),
            lane, client, key, deterministic=revision != 0,
            bargs=("wire", start, end, revision, limit),
        )

    def list_by_stream(self, start: bytes, end: bytes, revision: int = 0,
                       client: str = "") -> Any:
        """Admission + initial dispatch for a streamed list. The returned
        iterator is consumed on the caller's thread (a stream can outlive
        any sane queue deadline); coalescing is disabled — iterators are
        single-consumer."""
        lane = classify(start, end, limit=0)
        return self.submit(
            lambda: self.backend.list_by_stream(start, end, revision),
            lane, client, key=None,
        )

    # ----------------------------------------------- backend write entries
    # (the only write path the service layer may use; kblint KB106. Writes
    # never coalesce — every op is an effect, not a pure read — but a freed
    # dispatch slot drains up to ``write_batch - 1`` additional queued write
    # ops behind a write leader into ONE backend.write_batch commit group:
    # a contiguous revision block, one engine round trip, one event-ring
    # pass, per-op conflict demux. Per-client FIFO through pop_matching
    # keeps same-client ordering identical to sequential submission.)
    def create(self, key: bytes, value: bytes, ttl: int | None = None,
               lease: int = 0, client: str = "") -> Any:
        wexec = self._backend_wexec
        return self.submit(
            lambda: self.backend.create(key, value, ttl=ttl, lease=lease),
            classify_write(key), client, key=None,
            bargs=("create", key, value, ttl, lease) if wexec else None,
            bexec=wexec,
        )

    def update(self, key: bytes, value: bytes, expected_revision: int,
               ttl: int | None = None, lease: int = 0,
               client: str = "") -> Any:
        wexec = self._backend_wexec
        return self.submit(
            lambda: self.backend.update(key, value, expected_revision,
                                        ttl=ttl, lease=lease),
            classify_write(key), client, key=None,
            bargs=("update", key, value, expected_revision, ttl, lease)
            if wexec else None,
            bexec=wexec,
        )

    def put_counted(self, key: bytes, value: bytes, expected: int,
                    by_version: bool, client: str = "") -> Any:
        """``Backend.put_counted`` (etcd's Version guard) through the write
        lanes; never part of a commit group, as its count rides its own
        engine batch."""
        return self.submit(
            lambda: self.backend.put_counted(key, value, expected, by_version),
            classify_write(key), client, key=None,
        )

    def delete(self, key: bytes, expected_revision: int = 0,
               client: str = "") -> Any:
        wexec = self._backend_wexec
        return self.submit(
            lambda: self.backend.delete(key, expected_revision),
            classify_write(key), client, key=None,
            bargs=("delete", key, expected_revision) if wexec else None,
            bexec=wexec,
        )

    # ------------------------------------------------------------- dispatch
    def _dispatch_loop(self) -> None:
        while True:
            req = self._next_request()
            if req is None:
                return
            # bound in-flight depth: block until a dispatch slot frees
            if not self._acquire_slot():
                # closing: never strand the popped request in _runq where
                # nothing will finish it
                req.finish(error=SchedClosedError("scheduler closed"))
                return
            try:
                with self._cv:
                    closed = self._closed
                shed = False if closed else self._shed_if_stale(req)
                if not closed and not shed:
                    self._form_batch(req)
                    with self._cv:
                        for r in (req, *req.batch_members):
                            if r.key is not None:
                                self._inflight[r.key] = r
                            self._inflight_count += 1
                    self.dispatched += 1 + len(req.batch_members)
            except BaseException as e:
                # a dispatch-path failure must not shrink scheduler depth
                # for the rest of the process (kblint KB124): give the slot
                # back and fail the request instead of stranding both
                self._release_slot()
                req.finish(error=e)
                raise
            if closed:
                self._release_slot()
                req.finish(error=SchedClosedError("scheduler closed"))
                return
            if shed:
                self._release_slot()
                continue
            with self._run_cv:
                self._runq.append(req)
                self._run_cv.notify()

    def _form_batch(self, req: _Request) -> None:
        """Drain additional compatible ready requests into ``req``'s
        dispatch slot: up to ``batch - 1`` scan requests behind a scan
        leader, or up to ``write_batch - 1`` write ops behind a write
        leader — one mechanism, two executors. Compatible = carries the
        SAME batch executor identity (the backend's ``list_batch`` for
        scans, ``write_batch`` for writes; streamed lists and wire-encoded
        fast paths never set one), so scan batches and write groups can
        never mix. Members drain in strict lane-priority order through the
        per-client round-robin (head-only pops — per-client FIFO is
        preserved, which is what makes same-client write ordering inside a
        group identical to sequential), so a queued SYSTEM op rides the
        very next slot instead of waiting out lower-priority work ahead of
        it."""
        is_write = (self._backend_wexec is not None
                    and req.bexec is self._backend_wexec)
        limit = self.config.write_batch if is_write else self.config.batch
        if req.bexec is None or limit <= 1:
            return
        members: list[_Request] = []
        want = limit - 1
        compatible = lambda r: r.bexec is req.bexec
        while len(members) < want:
            with self._cv:
                m = None
                for lane in Lane:
                    m = self._queues[lane].pop_matching(compatible)
                    if m is not None:
                        break
                if m is None:
                    break
                if m.key is not None and self._pending.get(m.key) is m:
                    del self._pending[m.key]
            if self._shed_if_stale(m):
                continue  # shed members don't occupy a batch position
            members.append(m)
        if not members:
            return
        req.batch_members = members
        for m in members:
            m.joined_batch = True
        if is_write:
            self.write_batched += len(members)
        else:
            self.batched += len(members)
        if self.metrics is not None:
            self.metrics.emit_histogram(
                "kb.sched.write.batch.size" if is_write
                else "kb.sched.batch.size", float(1 + len(members)))

    def _next_request(self) -> _Request | None:
        with self._cv:
            while True:
                if self._closed:
                    return None
                for lane in Lane:  # strict priority order
                    req = self._queues[lane].pop()
                    if req is not None:
                        if req.key is not None and \
                                self._pending.get(req.key) is req:
                            del self._pending[req.key]
                        return req
                self._cv.wait(timeout=0.2)

    def _shed_if_stale(self, req: _Request) -> bool:
        age_ms = (time.monotonic() - req.enqueued) * 1000.0
        if age_ms <= self.config.shed_ms:
            return False
        with self._cv:
            self.shed_counts[req.lane] += 1 + len(req.followers)
        self._emit_counter("kb.sched.shed.total", req.lane, reason="deadline")
        req.finish(error=SchedOverloadError(req.lane, f"queued {age_ms:.0f}ms"))
        return True

    def _work_loop(self) -> None:
        while True:
            with self._run_cv:
                while not self._runq:
                    if self._closed:
                        return
                    self._run_cv.wait(timeout=0.2)
                req = self._runq.popleft()
            if req.batch_members:
                self._run_batch(req)
                continue
            # enqueue -> execution start; recorded on the submitter's span
            TRACER.record_stage("queue_wait", req.enqueued, time.monotonic(),
                                span=req.span)
            try:
                with TRACER.use(req.span):
                    result = req.fn()
                err = None
            except BaseException as e:  # surfaced to the waiting caller
                result, err = None, e
            finally:
                t_done = time.monotonic()
                self._release_slot()
                with self._cv:
                    if req.key is not None and \
                            self._inflight.get(req.key) is req:
                        del self._inflight[req.key]
                    self._inflight_count -= 1
            req.finish(result=result, error=err, executed_at=t_done)

    def _run_batch(self, req: _Request) -> None:
        """Execute a batch leader + members as ONE backend call and demux.
        The executor returns one result per descriptor, an Exception
        element failing only its own query (e.g. a compacted revision);
        an executor-level raise fails every member — the same visibility a
        shared single dispatch would have had."""
        batch = [req, *req.batch_members]
        t_exec = time.monotonic()
        for r in batch:
            # enqueue -> execution start, on every rider's own span
            TRACER.record_stage("queue_wait", r.enqueued, t_exec, span=r.span)
        try:
            with TRACER.use(req.span):
                results = req.bexec([r.bargs for r in batch])
            err = None
            if len(results) != len(batch):  # executor contract violation
                raise RuntimeError(
                    f"batch executor returned {len(results)} results "
                    f"for {len(batch)} queries")
        except BaseException as e:
            results, err = None, e
        finally:
            t_done = time.monotonic()
            self._release_slot()
            with self._cv:
                for r in batch:
                    if r.key is not None and \
                            self._inflight.get(r.key) is r:
                        del self._inflight[r.key]
                    self._inflight_count -= 1
        for i, r in enumerate(batch):
            if err is not None:
                r.finish(error=err, executed_at=t_done)
            elif isinstance(results[i], BaseException):
                r.finish(error=results[i], executed_at=t_done)
            else:
                r.finish(result=results[i], executed_at=t_done)
            if r is not req:
                # the member's whole device residency happened inside the
                # leader's execution — one stage, coalesce_join-style
                TRACER.record_stage("batch_join", t_exec, t_done, span=r.span)

    # -------------------------------------------------------------- metrics
    def _emit_counter(self, name: str, lane: Lane, **tags: Any) -> None:
        if self.metrics is not None:
            self.metrics.emit_counter(name, 1, lane=lane.name.lower(), **tags)


_ENSURE_LOCK = threading.Lock()


def ensure_scheduler(backend: Any, config: SchedConfig | None = None,
                     metrics: Any = None) -> RequestScheduler:
    """The process-wide scheduler for ``backend``: every service surface
    (sync etcd, aio, native front, brain) must share one admission queue or
    lanes mean nothing. First caller wins; cli.build_endpoint calls this
    early with the flag-derived config + real metrics."""
    sched = getattr(backend, "_kb_scheduler", None)
    if sched is not None:
        return sched
    with _ENSURE_LOCK:
        sched = getattr(backend, "_kb_scheduler", None)
        if sched is None:
            sched = RequestScheduler(backend, config, metrics)
            backend._kb_scheduler = sched
    return sched
