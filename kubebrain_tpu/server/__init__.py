"""Server composition: gRPC services + info HTTP handlers.

Reference: pkg/server/server.go:70-180 — composes the etcd RPC server, the
brain RPC server, and the HTTP handlers ``/health``, ``/status`` (the
follower→leader revision-sync endpoint, :151-165) and ``/election``.
"""

from __future__ import annotations

import json
import sys
import tempfile
import threading
import time
import traceback

from .. import __version__
from ..backend import Backend
from ..metrics import Metrics, NoopMetrics
from .brain import BrainServer, make_brain_handlers
from .etcd import make_etcd_handlers
from .service import PeerService, SingleNodePeerService


class Server:
    def __init__(
        self,
        backend: Backend,
        peers: PeerService | SingleNodePeerService,
        metrics: Metrics | None = None,
        identity: str = "kubebrain-tpu",
        client_urls: list[str] | None = None,
        compact_interval: float = 60.0,
        replica=None,
    ):
        self.backend = backend
        self.peers = peers
        self.metrics = metrics or NoopMetrics()
        self.identity = identity
        #: follower role (kubebrain_tpu/replica; docs/replication.md)
        self.replica = replica
        self.brain = BrainServer(backend, peers, compact_interval=compact_interval)
        self._capture: dict | None = None  # the running profiler capture
        self._device_gauges: list[str] = []  # register_device_metrics
        self.grpc_handlers = (
            make_etcd_handlers(backend, peers, identity, client_urls or [],
                               replica=replica)
            + make_brain_handlers(self.brain)
            + [self._health_handler()]
        )

    def _health_handler(self):
        """grpc.health.v1 terminal; the "leader" service reflects leadership
        (reference wires election callbacks into grpc-health, server.go:72-78)."""
        import grpc

        from ..proto import health_pb2

        def check(request, context):
            if request.service in ("", "etcd", "brain"):
                status = health_pb2.HealthCheckResponse.SERVING
            elif request.service == "leader":
                status = (
                    health_pb2.HealthCheckResponse.SERVING
                    if self.peers.is_leader()
                    else health_pb2.HealthCheckResponse.NOT_SERVING
                )
            else:
                context.abort(grpc.StatusCode.NOT_FOUND, "unknown service")
            return health_pb2.HealthCheckResponse(status=status)

        def watch(request, context):
            """Long-lived status stream (grpc.health.v1 contract): emit the
            current status, then only on change, until the client departs."""
            import time as _time

            last = check(request, context)
            yield last
            while context.is_active():
                _time.sleep(0.5)
                cur = check(request, context)
                if cur.status != last.status:
                    last = cur
                    yield cur

        return grpc.method_handlers_generic_handler("grpc.health.v1.Health", {
            "Check": grpc.unary_unary_rpc_method_handler(
                check,
                request_deserializer=health_pb2.HealthCheckRequest.FromString,
                response_serializer=health_pb2.HealthCheckResponse.SerializeToString,
            ),
            "Watch": grpc.unary_stream_rpc_method_handler(
                watch,
                request_deserializer=health_pb2.HealthCheckRequest.FromString,
                response_serializer=health_pb2.HealthCheckResponse.SerializeToString,
            ),
        })

    def start_background(self) -> None:
        self.brain.start_background()

    def register_device_metrics(self) -> None:
        """``kb_device_memory_peak_bytes{device=}``: each local device's
        ``memory_stats()`` peak, sampled at scrape time (0 where the backend
        reports none, as the CPU's does). Only the process that holds the
        chip can say it, so it is this one's to report."""
        import jax

        for d in jax.local_devices():
            self.metrics.register_gauge_fn(
                "kb.device.memory.peak.bytes",
                lambda d=d: float(
                    (d.memory_stats() or {}).get("peak_bytes_in_use", 0)),
                device=str(d))
            self._device_gauges.append(str(d))

    # ------------------------------------------------------------------ HTTP
    def http_handlers(self) -> dict:
        """path -> fn() -> (content_type, body). The /status payload is the
        revision-sync contract consumed by HttpRevisionSyncer."""
        return {
            "/health": self._health,
            "/status": self._status,
            "/election": self._election,
            "/debug/threads": self._threads,
            "/debug/traces": self._traces,
            "/debug/profile": self._profile,
            "/debug/profile/start": self._profile_start,
            "/debug/profile/stop": self._profile_stop,
            "/tier/failover": self._tier_failover,
        }

    def _traces(self):
        """Recent request span trees + slow-request log + stage EWMAs from
        the process tracer (kubebrain_tpu.trace)."""
        from ..trace import TRACER

        return "application/json", json.dumps(TRACER.snapshot()).encode()

    def _health(self):
        return "application/json", json.dumps({"health": "true"}).encode()

    def _status(self):
        payload = {
            "revision": self.backend.current_revision(),
            "compact_revision": self.backend.compact_revision(),
            "is_leader": self.peers.is_leader(),
            "leader": self.peers.leader_peer_address(),
            "identity": self.identity,
            "watchers": self.backend.watcher_hub.watcher_count(),
            "version": __version__,
        }
        if self.replica is not None:
            # follower: replication watermark/lag + served/forwarded/
            # refused counters (the workload harness's per-replica view)
            payload["replica"] = self.replica.status()
        return "application/json", json.dumps(payload).encode()

    def _election(self):
        return "application/json", json.dumps({
            "leader": self.peers.leader_peer_address(),
            "identity": self.identity,
            "is_leader": self.peers.is_leader(),
        }).encode()

    def _tier_failover(self):
        """Operator-driven storage-tier failover: promote the first
        reachable kbstored follower and repoint this node's pool
        (RemoteKvStorage.failover). Deliberately a manual surface — the tier
        has no raft quorum, so WHEN to flip is the operator's (or the
        election layer's) call; see README 'Tier replication'."""
        from ..storage import unwrap_store

        store = unwrap_store(self.backend.store, "failover")
        if store is None:
            return "application/json", json.dumps(
                {"error": "storage tier has no failover (not --storage=remote?)"}
            ).encode()
        try:
            idx = store.failover()
            return "application/json", json.dumps({"promoted_index": idx}).encode()
        except Exception as exc:  # surfaced to the operator, not swallowed
            return "application/json", json.dumps({"error": str(exc)}).encode()

    def _threads(self):
        """Poor man's pprof: live thread stacks (reference mounts Go pprof,
        pkg/endpoint/pprof.go — the Python analogue is stack dumps; kernel
        profiling goes through jax.profiler instead)."""
        out = []
        for tid, frame in sys._current_frames().items():
            name = next(
                (t.name for t in threading.enumerate() if t.ident == tid), str(tid)
            )
            out.append(f"--- thread {name} ---")
            out.extend(line.rstrip() for line in traceback.format_stack(frame))
        return "text/plain", "\n".join(out).encode()

    _profile_lock = threading.Lock()

    def _capture_start(self, query) -> dict:
        """Start the one ``jax.profiler`` capture this process may run, into
        ``dir=`` (default: a fresh directory under the system's temporary
        one, ``TMPDIR``). The Python tracer is off: it hooks every call of
        this (Python) server and slows the very window it traces; device
        ops and the tracer's ``kb.*`` annotations stay. Times are on
        ``time.monotonic()``, the tracer's clock."""
        import jax

        if not self._profile_lock.acquire(blocking=False):
            return {"error": "profile capture already in progress"}
        try:
            out_dir = (query or {}).get("dir") or tempfile.mkdtemp(
                prefix="kb-profile-")
            t0 = time.monotonic()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(out_dir, profiler_options=options)
            start = time.monotonic()
            self._capture = {"dir": out_dir, "start": start,
                             "init_s": start - t0}
            return dict(self._capture)  # the lock is _capture_stop's to release
        except BaseException:
            self._profile_lock.release()
            raise

    def _capture_stop(self) -> dict:
        import jax

        capture = self._capture
        if not capture:
            return {"error": "no profile capture is running"}
        try:
            stop = time.monotonic()
            jax.profiler.stop_trace()
            return dict(capture, stop=stop, flush_s=time.monotonic() - stop)
        finally:
            self._capture = None
            self._profile_lock.release()

    def _profile_start(self, query=None):
        """``/debug/profile/start?dir=D``: begin a capture; answers ``dir``,
        ``start`` and ``init_s`` (the first capture of a process pays the
        profiler's initialisation)."""
        return "application/json", json.dumps(
            self._capture_start(query)).encode()

    _profile_start.kb_query = True

    def _profile_stop(self):
        """``/debug/profile/stop``: end it; answers the start's fields plus
        ``stop`` and ``flush_s``."""
        return "application/json", json.dumps(self._capture_stop()).encode()

    def _profile(self, query=None):
        """``/debug/profile?seconds=N&dir=D``: capture an on-demand
        ``jax.profiler`` device trace of the data plane for N seconds
        (default 2, clamped to [0.05, 60]) — the kernel analogue of the
        reference's pprof mounts, pkg/endpoint/pprof.go; inspect with
        tensorboard or xprof. One capture at a time — an overlapping request
        would stop the in-flight trace mid-capture."""
        try:
            seconds = float((query or {}).get("seconds", 2.0))
        except (TypeError, ValueError):
            return "application/json", json.dumps(
                {"error": "seconds must be a number"}
            ).encode()
        seconds = min(60.0, max(0.05, seconds))
        started = self._capture_start(query)
        if "error" in started:
            return "application/json", json.dumps(started).encode()
        try:
            time.sleep(seconds)
        finally:
            out = self._capture_stop()
        return "application/json", json.dumps(
            dict(out, seconds=seconds)).encode()

    _profile.kb_query = True  # HTTP layers pass the parsed query string

    def start_tier_watchdog(self, interval: float = 1.0, failures: int = 3) -> bool:
        """Auto-failover for the replicated kbstored tier: probe the tier
        primary every ``interval``; after ``failures`` consecutive misses,
        attempt ``failover()``. Split-brain safety does NOT rest on this
        node's view: the FOLLOWER refuses promotion while its replication
        stream from the primary is alive (heartbeat-armed, kbstored
        OP_PROMOTE guard), so a node merely partitioned from a healthy
        primary cannot fork the tier. Returns False when the storage stack
        has no failover surface (not a replicated remote tier)."""
        from ..storage import unwrap_store

        store = unwrap_store(self.backend.store, "failover")
        if store is None or len(getattr(store, "_addresses", [])) < 2:
            return False

        import logging

        log = logging.getLogger("kubebrain.tier")

        def loop():
            misses = 0
            while not self._watchdog_stop.wait(interval):
                try:
                    store.role(timeout=min(2.0, interval))
                    misses = 0
                    continue
                except Exception:
                    misses += 1
                if misses < failures:
                    continue
                # Quorum tier (kbstored --peers): leadership moved by
                # internal election — just find it. Legacy tier: no one
                # self-elects, so promote a follower via failover().
                try:
                    idx = store.find_leader()
                    log.warning("tier primary unreachable %d probes; "
                                "repointed at elected leader %d", misses, idx)
                    misses = 0
                    continue
                except Exception:
                    pass
                try:
                    idx = store.failover()
                    log.warning("tier primary unreachable %d probes; "
                                "promoted follower %d", misses, idx)
                    misses = 0
                except Exception as exc:
                    # follower refused (primary alive from ITS view — we are
                    # the partitioned side) or nothing promotable yet
                    log.warning("tier failover attempt failed: %s", exc)

        from ..util.env import crash_guard

        self._watchdog_stop = threading.Event()
        self._watchdog = threading.Thread(
            target=crash_guard(loop), name="kb-tier-watchdog", daemon=True)
        self._watchdog.start()
        return True

    def close(self) -> None:
        for device in self._device_gauges:
            self.metrics.unregister_gauge_fn("kb.device.memory.peak.bytes",
                                             device=device)
        self._device_gauges = []
        if getattr(self, "_watchdog_stop", None) is not None:
            self._watchdog_stop.set()
        self.brain.close()
        self.peers.close()
