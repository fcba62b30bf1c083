"""End-to-end request tracing with device-time attribution.

Every KV RPC becomes one root span with named *stages* — the per-layer
time slices of the serving path:

    endpoint_recv    request decode + peer revision sync (service terminal)
    queue_wait       scheduler admission: enqueue -> worker pickup
    coalesce_join    follower attached to a coalesced leader's execution
    batch_join       rider of a batch leader's one dispatch / commit group
    delta_overlay    the TPU engine's delta on the read path: publish check,
                     the wait for the writers' lock, the overlay taken under
                     it, a Count's overlay correction
    device_dispatch  building + enqueuing the device kernel (async dispatch)
    device_compute   the host's wait for the device, timed across
                     ``block_until_ready`` / the first blocking transfer
    host_scan        the host engine's iteration (generic scanner, small
                     pages, the C wire encoder): no device involved
    host_copy        materializing rows on the host (overlay merge, sort)
    result_deliver   worker completion -> waiter wakeup (sched handoff)
    response_encode  building the wire response
    backend_write    Txn write path (create/update/delete)

One emission point, three sinks. A finished span lands in a bounded
in-memory ring (``/debug/traces``; slow requests additionally in a
slow-request log, ``--trace-slow-ms``), and on ``/metrics`` as one
``kb_rpc_stage_seconds{stage=,rpc=}`` observation per stage the RPC spent
time in (``rpc`` = the span's name, ``""`` for a spanless stage) plus one
``kb_rpc_unaccounted_seconds{rpc=}`` observation: the span's duration minus
the union of its stages, i.e. the promise "stages account for the latency",
measured. The third sink is the profiler: ``stage()`` also enters an
annotation ``kb.<name>``, so during a ``jax.profiler`` capture every stage a
thread is DOING lies in the ``/host:CPU`` plane on the profiler's clock,
beside the device's ops. This package imports no JAX: the TPU engine hands
the annotation factory over (``set_annotator``); without it the sink is
absent. Stages recorded after the fact from two timestamps (``queue_wait``,
``result_deliver``, ``coalesce_join``, ``batch_join``) are waits no thread
does and get no annotation. ``annotate(name)`` gives background work outside
any RPC (merge, compaction, boot) the annotation alone.

The tracer also keeps per-stage EWMAs; ``dispatch_rtt()`` (device_dispatch
+ device_compute, which only the TPU engine's kernel path records) is the
measured device round trip the scheduler uses to size its pipeline depth
when ``--sched-depth 0`` (auto) is configured — the ROADMAP "size
--sched-depth from the measured dispatch RTT" lever.

Trace context propagates as a W3C ``traceparent`` header
(``00-<trace_id>-<span_id>-01``) in gRPC metadata: client.py injects it,
the service terminals extract it, so a client-side trace id finds its
server-side span tree in ``/debug/traces``.

All timestamps are ``time.monotonic()`` — the same clock the scheduler
stamps ``_Request.enqueued`` with, so cross-thread stage math never mixes
clock domains.
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import os
import re
import threading
import time
from collections import deque
from typing import Any, Iterator

from ..util import fieldcheck

logger = logging.getLogger("kubebrain.trace")

_SPAN: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "kb_trace_span", default=None
)

_TRACEPARENT_RE = re.compile(
    r"^[0-9a-f]{2}-([0-9a-f]{32})-([0-9a-f]{16})-[0-9a-f]{2}$"
)

#: histogram fed by every completed stage (prom: kb_rpc_stage_seconds)
STAGE_METRIC = "kb.rpc.stage.seconds"
#: histogram of what a span's stages leave out (kb_rpc_unaccounted_seconds)
UNACCOUNTED_METRIC = "kb.rpc.unaccounted.seconds"
#: profiler annotations are named kb.<stage> / kb.<background work>
ANNOTATION_PREFIX = "kb."


def _gen_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


def parse_traceparent(header: str | bytes | None) -> tuple[str, str] | None:
    """(trace_id, parent_span_id) from a W3C traceparent header, or None."""
    if not header:
        return None
    if isinstance(header, bytes):
        try:
            header = header.decode("ascii")
        except UnicodeDecodeError:
            return None
    m = _TRACEPARENT_RE.match(header.strip().lower())
    if not m:
        return None
    trace_id, span_id = m.group(1), m.group(2)
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


def make_traceparent(span: "Span | None" = None) -> str:
    """W3C traceparent for an outgoing call: continues ``span``'s trace (or
    the ambient one) with a fresh span id, else starts a new trace."""
    span = span if span is not None else _SPAN.get()
    trace_id = span.trace_id if span is not None else _gen_id(16)
    return f"00-{trace_id}-{_gen_id(8)}-01"


class Span:
    """One traced request. ``stages`` is a list of
    ``(name, offset_seconds, duration_seconds)`` relative to ``t0``;
    appends are GIL-atomic, so worker threads record stages directly."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "t0", "wall0",
                 "duration", "stages", "error", "hwm")

    def __init__(self, name: str, trace_id: str | None = None,
                 parent_id: str | None = None) -> None:
        self.name = name
        self.trace_id = trace_id or _gen_id(16)
        self.span_id = _gen_id(8)
        self.parent_id = parent_id
        self.t0 = time.monotonic()
        self.wall0 = time.time()
        self.duration: float | None = None
        self.stages: list[tuple[str, float, float]] = []
        self.error: str | None = None
        self.hwm = 0.0  # latest recorded stage end (offset); gap-glue anchor

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start_unix": round(self.wall0, 6),
            "duration_ms": (
                round(self.duration * 1e3, 4) if self.duration is not None else None
            ),
            "error": self.error,
            "stages": [
                {
                    "stage": name,
                    "offset_ms": round(off * 1e3, 4),
                    "duration_ms": round(dur * 1e3, 4),
                }
                for name, off, dur in list(self.stages)
            ],
        }


@fieldcheck.track
class Tracer:
    """Process-wide span recorder: bounded trace ring + slow-request log +
    per-stage EWMAs + the stage-latency histogram."""

    #: stages whose EWMAs form the device dispatch RTT the scheduler sizes
    #: its pipeline depth from (``--sched-depth 0``)
    RTT_STAGES = ("device_dispatch", "device_compute")

    def __init__(self, capacity: int = 512, slow_ms: float = 500.0,
                 metrics: Any = None, slow_capacity: int = 128) -> None:
        self._lock = threading.Lock()
        self._ring: deque[Span] = deque(maxlen=capacity)
        self._slow: deque[Span] = deque(maxlen=slow_capacity)
        self.slow_ms = slow_ms
        self.metrics = metrics
        self._ewma: dict[str, float] = {}
        self._ewma_alpha = 0.2
        # name -> context manager on the profiler's clock
        # (jax.profiler.TraceAnnotation), handed over by the TPU engine
        self._annotate: Any = None
        # False turns span *recording* off (stage histograms and EWMAs
        # still update); the server never does, tests do
        self.enabled = True

    # ------------------------------------------------------------ configure
    def configure(self, metrics: Any = None, slow_ms: float | None = None,
                  capacity: int | None = None) -> None:
        if metrics is not None:
            self.metrics = metrics
        if slow_ms is not None:
            self.slow_ms = slow_ms
        if capacity is not None:
            with self._lock:
                self._ring = deque(self._ring, maxlen=capacity)

    def set_annotator(self, factory: Any) -> None:
        """The profiler sink: ``factory(name)`` is a context manager that
        puts a span on the profiler's clock while a capture runs and costs
        one flag test when none does."""
        self._annotate = factory

    def reset(self) -> None:
        """Drop recorded traces and EWMAs (test isolation)."""
        with self._lock:
            self._ring.clear()
            self._slow.clear()
            self._ewma = {}

    # ---------------------------------------------------------------- spans
    def current(self) -> Span | None:
        return _SPAN.get()

    @contextlib.contextmanager
    def span(self, name: str,
             traceparent: str | bytes | None = None) -> Iterator[Span | None]:
        """Root-span scope. A nested call reuses the active span — service
        terminals stack (front backhaul -> KVService), one RPC = one span."""
        active = _SPAN.get()
        if active is not None or not self.enabled:
            yield active
            return
        parent = parse_traceparent(traceparent)
        sp = Span(name, trace_id=parent[0] if parent else None,
                  parent_id=parent[1] if parent else None)
        token = None
        try:
            token = _SPAN.set(sp)
            yield sp
        except BaseException as e:
            sp.error = f"{type(e).__name__}: {e}"
            raise
        finally:
            # finish FIRST, and unconditionally: the ring append is the
            # side that must survive any teardown hiccup — a span that
            # opened but never reaches the ring would under-count exactly
            # the failed requests
            self.finish(sp)
            if token is not None:
                _SPAN.reset(token)

    @contextlib.contextmanager
    def use(self, span: Span | None) -> Iterator[None]:
        """Adopt ``span`` as the ambient span on this thread (scheduler
        workers execute a request captured on the submitting thread)."""
        if span is None:
            yield
            return
        token = _SPAN.set(span)
        try:
            yield
        finally:
            _SPAN.reset(token)

    def finish(self, span: Span) -> None:
        span.duration = time.monotonic() - span.t0
        m = self.metrics
        if m is not None:
            # span-attached stage histograms are emitted here, once, after
            # the clock stops: an inline prometheus observe per stage
            # boundary costs ~tens of µs that would show up as unattributed
            # time *inside* the span.
            # One observation per stage NAME: a stage entered twice (a
            # Count's delta_overlay, before and after the kernel) is one
            # share of this RPC, so a stage's mean is per RPC that had it
            # and the means of one kind of RPC add up to its duration.
            stages = list(span.stages)
            per_stage: dict[str, float] = {}
            for name, _off, dur in stages:
                per_stage[name] = per_stage.get(name, 0.0) + dur
            for name, dur in per_stage.items():
                m.emit_histogram(STAGE_METRIC, dur, stage=name, rpc=span.name)
            m.emit_histogram(
                UNACCOUNTED_METRIC,
                max(0.0, span.duration - _covered(stages, span.duration)),
                rpc=span.name)
        with self._lock:
            self._ring.append(span)
            slow = self.slow_ms and span.duration * 1e3 >= self.slow_ms
            if slow:
                self._slow.append(span)
        if slow:
            stages = ", ".join(
                f"{n}={d * 1e3:.1f}ms" for n, _o, d in list(span.stages)
            )
            logger.warning(
                "slow request %s trace=%s %.1fms (%s)",
                span.name, span.trace_id, span.duration * 1e3, stages or "no stages",
            )

    # --------------------------------------------------------------- stages
    @contextlib.contextmanager
    def stage(self, name: str) -> Iterator[None]:
        """A stage this thread is doing: recorded on the ambient span and,
        during a profiler capture, an annotation ``kb.<name>``."""
        t0 = time.monotonic()
        try:
            with self.annotate(name):
                yield
        finally:
            self.record_stage(name, t0, time.monotonic())

    def annotate(self, name: str) -> Any:
        """The profiler annotation ``kb.<name>`` alone, for background work
        outside any RPC (merge phases, compaction, boot); a no-op context
        without the profiler sink."""
        annotate = self._annotate
        if annotate is None:
            return contextlib.nullcontext()
        return annotate(ANNOTATION_PREFIX + name)

    #: a stage whose start trails the previous stage's end by less than this
    #: is glued to it — instrumentation/transition overhead between stages
    #: is attributed to the next stage instead of vanishing (stage sums must
    #: account for the observed end-to-end latency); genuine gaps larger
    #: than this remain visible as missing time
    GAP_GLUE_S = 0.0005

    def record_stage(self, name: str, t0: float, t1: float,
                     span: Span | None = None) -> None:
        """Record one ``[t0, t1]`` monotonic interval as stage ``name`` on
        ``span`` (default: the ambient span), feed the stage histogram
        (immediately when spanless; at span finish otherwise), and update
        the stage EWMA. Callable from any thread."""
        dur = max(0.0, t1 - t0)
        sp = span if span is not None else _SPAN.get()
        if sp is not None and self.enabled:
            off = t0 - sp.t0
            end = off + dur
            if 0.0 < off - sp.hwm <= self.GAP_GLUE_S:
                off = sp.hwm
            sp.stages.append((name, off, end - off))
            if end > sp.hwm:
                sp.hwm = end
        else:
            m = self.metrics
            if m is not None:
                m.emit_histogram(STAGE_METRIC, dur, stage=name, rpc="")
        # EWMA update is a read-modify-write racing every worker thread
        # (and reset()'s dict swap, which holds _lock): unguarded, two
        # concurrent stages lose updates and a racing reset resurrects
        # pre-reset values (kblint KB120)
        with self._lock:
            prev = self._ewma.get(name)
            self._ewma[name] = (
                dur if prev is None else prev + self._ewma_alpha * (dur - prev)
            )

    # ---------------------------------------------------------------- ewmas
    def ewma(self, stage: str) -> float | None:
        with self._lock:
            return self._ewma.get(stage)

    def dispatch_rtt(self) -> float | None:
        """EWMA of the device dispatch round trip (dispatch + compute).
        Only the TPU engine's kernel path records those two stages (the
        host scanner's iteration is ``host_scan``); None until it has been
        observed (pure host deployments never set it)."""
        with self._lock:
            vals = [self._ewma[s] for s in self.RTT_STAGES if s in self._ewma]
        return sum(vals) if vals else None

    # ------------------------------------------------------------- snapshot
    def snapshot(self, limit: int = 64) -> dict:
        with self._lock:
            traces = [s.to_dict() for s in list(self._ring)[-limit:]]
            slow = [s.to_dict() for s in list(self._slow)]
            ewma = dict(self._ewma)
        rtt = self.dispatch_rtt()
        return {
            "enabled": self.enabled,
            "slow_ms": self.slow_ms,
            "traces": traces,
            "slow": slow,
            "stage_ewma_seconds": {k: round(v, 9) for k, v in ewma.items()},
            "dispatch_rtt_seconds": round(rtt, 9) if rtt is not None else None,
        }


def _covered(stages: list[tuple[str, float, float]], duration: float) -> float:
    """Seconds of ``[0, duration]`` that the union of the stage intervals
    covers: stages overlap (``backend_write`` wraps the write's
    ``queue_wait``), so their sum would count time twice."""
    total, edge = 0.0, 0.0
    for off, end in sorted((off, off + dur) for _n, off, dur in stages):
        end = min(end, duration)
        if end > edge:
            total += end - max(off, edge)
            edge = end
    return total


def emit_histogram(name: str, value: float, **tags: Any) -> None:
    """Forward a histogram observation to the process metrics sink when one
    is configured (used by layers without their own metrics handle, e.g.
    the watch pumps)."""
    m = TRACER.metrics
    if m is not None:
        m.emit_histogram(name, value, **tags)


def emit_counter(name: str, value: float = 1, **tags: Any) -> None:
    """The counter twin of :func:`emit_histogram`."""
    m = TRACER.metrics
    if m is not None:
        m.emit_counter(name, value, **tags)


def traceparent_of(context: Any) -> str | bytes | None:
    """The ``traceparent`` metadata value of a gRPC(-ish) server context,
    if the transport exposes invocation metadata (grpcio does; the native
    front / aio context adapters may not)."""
    md = getattr(context, "invocation_metadata", None)
    if not callable(md):
        return None
    try:
        for item in md() or ():
            key = getattr(item, "key", None)
            if key is None and isinstance(item, tuple):
                key, value = item
            else:
                value = getattr(item, "value", None)
            if key == "traceparent":
                return value
    except Exception:
        return None
    return None


#: the process-wide tracer; cli.build_endpoint configures it with the real
#: metrics sink and --trace-slow-ms
TRACER = Tracer()
