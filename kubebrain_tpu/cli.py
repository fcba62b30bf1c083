"""Process bootstrap: flags, wiring, graceful shutdown.

Reference: cmd/main.go (cobra root command, SIGINT/SIGTERM graceful exit
with a 3s force-kill watchdog, :35-97) and cmd/option/option.go (flags,
validation, dependency wiring — storage → metrics decorator → backend →
endpoint, :230-259). Engine choice is a runtime flag (--storage) instead of
the reference's compile-time Go build tags (option_badger.go:15).
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import threading
import time

from . import __version__

#: where boot's clock starts: this module's import (the package's own
#: ``__init__`` has run by then; the interpreter's start is ~0.1 s earlier)
_T_IMPORT = time.monotonic()


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="kubebrain-tpu",
        description="TPU-native etcd3-compatible metadata store for Kubernetes",
    )
    p.add_argument("--storage", default="memkv",
                   choices=["memkv", "tpu", "native", "remote"],
                   help="storage engine (reference: build-tag selected TiKV/Badger; "
                        "'remote' = shared kbstored server, the TiKV role)")
    p.add_argument("--storage-address", default="127.0.0.1:2389",
                   help="kbstored address for --storage=remote; comma-"
                        "separated primary,follower,... enables failover()")
    p.add_argument("--tier-auto-failover", action="store_true",
                   help="probe the kbstored tier primary and auto-promote a "
                        "follower after 3 missed probes (split-brain-guarded "
                        "by the follower's stream-liveness check)")
    p.add_argument("--storage-read-followers", action="store_true",
                   help="route snapshot-pinned reads to kbstored followers "
                        "(tier-level read scaling; falls back to the "
                        "primary on replica lag)")
    p.add_argument("--storage-pool", type=int, default=8,
                   help="connection pool size to kbstored (reference keeps "
                        "200 round-robin TiKV clients, tikv.go:36-82)")
    p.add_argument("--inner-storage", default="memkv",
                   help="host engine backing the tpu mirror (tpu engine only)")
    p.add_argument("--use-pallas", action="store_true",
                   help="run range scans through the Pallas/Mosaic kernel "
                        "instead of the fused-jnp kernel (tpu engine only; "
                        "interpret-mode off-TPU; env KB_USE_PALLAS)")
    p.add_argument("--mesh-part", type=int, default=0,
                   help="devices on the scan mesh's `part` axis (tpu engine "
                        "only): the mirror's 20M-row keyspace shards across "
                        "this many chips so per-chip HBM bounds the dataset; "
                        "0 = every visible device (docs/multichip.md)")
    p.add_argument("--key-encoding", choices=("encoded", "raw"), default="",
                   help="mirror key layout (--storage=tpu): 'encoded' = "
                        "order-preserving prefix/dictionary compression of "
                        "the device key column (docs/compression.md), "
                        "'raw' = full-width packed keys; default follows "
                        "KB_ENCODE_KEYS (encoded)")
    p.add_argument("--merge-threshold", type=int, default=0,
                   help="TPU engine: delta rows that trigger an incremental "
                        "mirror merge (0 = engine default 4096). Chaos runs "
                        "lower it so merge-fault windows exercise the real "
                        "merge/retry/escalation machinery (docs/faults.md)")
    p.add_argument("--scan-partitions", type=int, default=0,
                   help="mirror partition count, decoupled from the mesh "
                        "size (must be a multiple of --mesh-part; each "
                        "device then holds P/N contiguous partitions); "
                        "0 = one partition per mesh device")
    p.add_argument("--data-dir", default="",
                   help="durable storage dir for the native engine (WAL + "
                        "snapshot); empty = in-memory")
    p.add_argument("--native-partitions", type=int, default=4,
                   help="partition count the native engine samples for "
                        "partition-parallel host scans")
    p.add_argument("--fsync", action="store_true",
                   help="fsync the WAL on every commit")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--client-port", type=int, default=2379)
    p.add_argument("--peer-port", type=int, default=2380)
    p.add_argument("--info-port", type=int, default=8081)
    p.add_argument("--prefix", default="/", help="key prefix served/compacted")
    p.add_argument("--skip-prefixes", default="", help="comma-separated prefixes compaction skips")
    p.add_argument("--watch-cache-size", type=int, default=200_000)
    p.add_argument("--disable-etcd-compatibility", action="store_true",
                   help="serve only the native brain protocol semantics "
                        "(Count over etcd is rejected; reference etcd-compat flag)")
    p.add_argument("--identity", default="", help="host:peerPort; autodetected when empty")
    p.add_argument("--single-node", action="store_true",
                   help="stub leader election (always leader)")
    p.add_argument("--enable-etcd-proxy", action="store_true",
                   help="followers forward writes to the leader")
    p.add_argument("--role", choices=("leader", "follower"), default="leader",
                   help="serving role (docs/replication.md): 'follower' "
                        "keeps a local mirror fed by a resumable "
                        "replication stream from --leader-address, serves "
                        "explicit-revision + bounded-staleness reads and "
                        "Watch locally, fences linearizable reads on the "
                        "leader's revision, and forwards writes/leases/"
                        "compaction")
    p.add_argument("--leader-address", default="",
                   help="leader client (gRPC) host:port (--role follower): "
                        "replication stream source + write/lease forward "
                        "target")
    p.add_argument("--leader-info", default="",
                   help="leader info/peer (HTTP) host:port (--role "
                        "follower): /status for the linearizable-read "
                        "revision fence + compact-watermark sync")
    p.add_argument("--max-staleness-rev", type=int, default=0,
                   help="follower bounded-staleness bound in revisions: "
                        "serializable reads REFUSE (etcdserver: replica "
                        "too stale) once the replication lag exceeds it; "
                        "0 = unbounded")
    p.add_argument("--max-staleness-ms", type=float, default=5000.0,
                   help="follower bounded-staleness bound in wall ms since "
                        "the watermark last covered the leader head; "
                        "refusal past it, 0 = unbounded")
    p.add_argument("--fence-timeout-ms", type=float, default=3000.0,
                   help="follower linearizable-read fence: how long the "
                        "applied watermark may chase the leader revision "
                        "before the read refuses (never answers stale)")
    p.add_argument("--enable-storage-metrics", action="store_true")
    p.add_argument("--tpu-fanout", action="store_true",
                   help="vectorized watch fan-out on the device mesh "
                        "(block-batched persistent-table matcher, "
                        "docs/watch.md)")
    p.add_argument("--mesh-wat", type=int, default=0,
                   help="devices on the watch fan-out mesh's `wat` axis: "
                        "the watcher table lives sharded across them and "
                        "each shard matches + compacts locally "
                        "(docs/watch.md). Composes with --mesh-part — the "
                        "two axes may share chips. Requires --tpu-fanout; "
                        "0 = single-device table")
    p.add_argument("--fanout-impl", choices=("block", "legacy"),
                   default="block",
                   help="--tpu-fanout implementation: 'block' = persistent "
                        "sharded watcher table, one dispatch per sequencer "
                        "drain block; 'legacy' = per-batch mask matcher "
                        "(kept for differential runs)")
    p.add_argument("--cert-file", default="")
    p.add_argument("--key-file", default="")
    p.add_argument("--ca-file", default="")
    p.add_argument("--secure-only", action="store_true",
                   help="with TLS configured, refuse plaintext clients "
                        "(reference endpoint secure modes, config.go:159)")
    p.add_argument("--sched-depth", type=int, default=4,
                   help="request scheduler: bounded in-flight device scan "
                        "dispatches (pipelined). 0 = auto: sized from the "
                        "tracer's measured dispatch-RTT EWMA, clamped 2-16")
    p.add_argument("--trace-slow-ms", type=float, default=500.0,
                   help="request tracer: RPCs slower than this land in the "
                        "slow-request log (/debug/traces \"slow\") and a "
                        "warning log line; 0 disables the slow log")
    p.add_argument("--sched-shed-ms", type=float, default=5000.0,
                   help="request scheduler: shed queued range reads older "
                        "than this (etcd ResourceExhausted on the wire)")
    p.add_argument("--sched-queue-limit", type=int, default=1024,
                   help="request scheduler: per-lane queued-request bound; "
                        "enqueue past it sheds immediately")
    p.add_argument("--sched-batch", type=int, default=8,
                   help="request scheduler: max distinct ready Range/Count "
                        "requests drained into one dispatch slot — over the "
                        "TPU engine they become ONE query-batched kernel "
                        "launch; 1 disables")
    p.add_argument("--sched-write-batch", type=int, default=8,
                   help="request scheduler: max queued write ops (create/"
                        "update/delete) drained into one group commit — a "
                        "contiguous revision block + ONE engine round trip "
                        "with per-op conflict demux (docs/writes.md); "
                        "1 disables")
    p.add_argument("--grpc-workers", type=int, default=256,
                   help="gRPC worker threads; each open watch stream holds one")
    p.add_argument("--aio-port", type=int, default=0,
                   help="additional asyncio etcd3 listener (coroutine-held "
                        "watch streams — no thread-per-stream ceiling); 0 = off")
    p.add_argument("--front-port", type=int, default=0,
                   help="native C++ gRPC/HTTP frontend (kbfront) on this port: "
                        "single-port h2+http demux (reference cmux) with the "
                        "protocol work in C++; 0 = off")
    p.add_argument("--lease-reap-interval", type=float, default=1.0,
                   help="lease subsystem: leader-only reaper cadence; expired "
                        "leases' keys become revision-stamped deletes through "
                        "the sequencer (watch-visible, compaction-safe)")
    p.add_argument("--lease-checkpoint-interval", type=float, default=5.0,
                   help="lease subsystem: cadence for persisting remaining "
                        "TTLs + attachments through the storage engine "
                        "(grant/revoke checkpoint synchronously; this covers "
                        "keepalive-refreshed deadlines)")
    p.add_argument("--legacy-ttl-patterns", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="key-pattern TTL fallback (/events/ = 1h, the "
                        "reference's lease.go behavior) for writes WITHOUT an "
                        "explicit lease; an attached lease always wins. "
                        "--no-legacy-ttl-patterns makes leases the only "
                        "expiry mechanism")
    p.add_argument("--faults", default="",
                   help="chaos mode (docs/faults.md): arm a deterministic "
                        "fault-injection plane with this preset (none, "
                        "smoke, storage, watch, merge, full). The plane is "
                        "INERT until GET /faults/arm on the info port "
                        "starts the window clock; 'none'/empty = no plane")
    p.add_argument("--fault-seed", type=int, default=0,
                   help="seed for the fault schedule (same preset+seed+"
                        "horizon => byte-identical schedule sha)")
    p.add_argument("--fault-horizon-s", type=float, default=30.0,
                   help="fault schedule horizon in real seconds from arm; "
                        "after it the plane goes quiet (recovery window)")
    p.add_argument("--cluster-name", default="")
    p.add_argument("--compact-interval", type=float, default=60.0)
    p.add_argument("--jax-platform", default=os.environ.get("KB_JAX_PLATFORM", ""),
                   help="pin the jax backend in-process before any kernel "
                        "runs (e.g. 'cpu' for CPU simulation) — same effect "
                        "as JAX_PLATFORMS in the environment; empty = jax's "
                        "own choice (the TPU when one is attached)")
    p.add_argument("--version", action="store_true", help="print version and exit")
    return p


def apply_jax_platform(platform: str) -> None:
    if not platform:
        return
    os.environ["JAX_PLATFORMS"] = platform
    import jax

    jax.config.update("jax_platforms", platform)


def uses_jax(args) -> bool:
    """The configurations whose process computes on a jax backend."""
    return args.storage == "tpu" or args.tpu_fanout


class BootPhases:
    """Boot by phase (``kb_boot_seconds{phase=}``, ``boot_s`` in the boot
    line): each ``mark`` closes the phase that ran since the one before, so
    ``jax_init`` (imports + the first ``jax.devices()``), ``store_open``
    (the inner store, WAL replay included) and ``listen`` (the rest of the
    wiring, to the instant the ports answer) tile the time from ``t0``. The
    mirror is built by the first read, not here: ``TpuScanner`` sets
    ``mirror_build`` when that read has paid for it."""

    def __init__(self, t0: float, metrics) -> None:
        self._last = t0
        self._metrics = metrics
        self.seconds: dict[str, float] = {}

    def mark(self, phase: str) -> None:
        now = time.monotonic()
        self.seconds[phase] = now - self._last
        self._last = now
        self._metrics.emit_gauge("kb.boot.seconds", self.seconds[phase],
                                 phase=phase)


def boot_line(backend, boot_s: dict | None = None) -> str:
    """The ONE line that says where this process computes: platform, device
    kind and count, scan mesh, resolved scan kernel, compile-cache
    directory, the jax stack's versions and boot by phase. A server meant
    for the chip that quietly came up on the CPU (or on the interpreted
    Pallas kernel) is visible here and nowhere else at boot."""
    import importlib.metadata
    import json

    import jax
    import jaxlib

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = ""
    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "devices": len(devices),
        "compile_cache": jax.config.jax_compilation_cache_dir,
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
    }
    describe = getattr(backend.scanner, "describe", None)
    if describe is not None:
        info.update(describe())
    if boot_s:
        info["boot_s"] = {k: round(v, 4) for k, v in boot_s.items()}
    return "kubebrain-tpu boot: " + json.dumps(info)


def validate_args(args) -> None:
    """Flag validation (reference KubeBrainOption.Validate, option.go:207)."""
    ports = [args.client_port, args.peer_port, args.info_port]
    if len(set(ports)) != len(ports):
        raise SystemExit(f"client/peer/info ports must be distinct, got {ports}")
    for p in ports:
        if not 0 < p < 65536:
            raise SystemExit(f"invalid port {p}")
    if bool(args.cert_file) != bool(args.key_file):
        raise SystemExit("--cert-file and --key-file must be set together")
    if args.secure_only and not args.cert_file:
        raise SystemExit("--secure-only requires --cert-file/--key-file")
    for f in (args.cert_file, args.key_file, args.ca_file):
        if f and not os.path.exists(f):
            raise SystemExit(f"TLS file not found: {f}")
    if args.storage == "tpu" and args.inner_storage == "tpu":
        raise SystemExit("--inner-storage cannot be tpu")
    mesh_part = getattr(args, "mesh_part", 0)
    scan_parts = getattr(args, "scan_partitions", 0)
    if mesh_part < 0 or scan_parts < 0:
        raise SystemExit("--mesh-part and --scan-partitions must be >= 0")
    if (mesh_part or scan_parts) and args.storage != "tpu":
        raise SystemExit("--mesh-part/--scan-partitions require --storage=tpu")
    if getattr(args, "key_encoding", "") and args.storage != "tpu":
        raise SystemExit("--key-encoding requires --storage=tpu")
    if mesh_part and scan_parts and scan_parts % mesh_part:
        raise SystemExit(
            f"--scan-partitions {scan_parts} must be a multiple of "
            f"--mesh-part {mesh_part}")
    if getattr(args, "mesh_wat", 0) < 0:
        raise SystemExit("--mesh-wat must be >= 0")
    if getattr(args, "mesh_wat", 0) and not getattr(args, "tpu_fanout", False):
        raise SystemExit("--mesh-wat requires --tpu-fanout")
    if getattr(args, "sched_depth", 1) < 0 or getattr(args, "sched_queue_limit", 1) < 1:
        raise SystemExit("--sched-depth must be >= 0 (0 = auto) and "
                         "--sched-queue-limit must be >= 1")
    if getattr(args, "sched_batch", 1) < 1:
        raise SystemExit("--sched-batch must be >= 1 (1 disables batching)")
    if getattr(args, "sched_write_batch", 1) < 1:
        raise SystemExit(
            "--sched-write-batch must be >= 1 (1 disables group commit)")
    if getattr(args, "sched_shed_ms", 1.0) <= 0:
        raise SystemExit("--sched-shed-ms must be > 0")
    if getattr(args, "trace_slow_ms", 0.0) < 0:
        raise SystemExit("--trace-slow-ms must be >= 0")
    if getattr(args, "lease_reap_interval", 1.0) <= 0 or \
            getattr(args, "lease_checkpoint_interval", 1.0) <= 0:
        raise SystemExit("--lease-reap-interval and --lease-checkpoint-interval "
                         "must be > 0")
    if args.data_dir and not (
        args.storage == "native" or (args.storage == "tpu" and args.inner_storage == "native")
    ):
        raise SystemExit("--data-dir requires --storage=native (or tpu over native)")
    if getattr(args, "role", "leader") == "follower":
        if not getattr(args, "leader_address", ""):
            raise SystemExit("--role follower requires --leader-address")
        if not getattr(args, "leader_info", ""):
            raise SystemExit("--role follower requires --leader-info "
                             "(the leader's info/peer HTTP host:port)")
        if getattr(args, "aio_port", 0) or getattr(args, "front_port", 0):
            # those fronts build their services WITHOUT the replica gate:
            # they would serve ungated (silently stale) "linearizable"
            # reads and refuse lease RPCs instead of forwarding — refuse
            # loudly until they grow replica routing
            raise SystemExit("--role follower serves the sync gRPC front "
                             "only (--aio-port/--front-port have no "
                             "replica read gate yet)")
        if getattr(args, "fence_timeout_ms", 1.0) <= 0:
            raise SystemExit("--fence-timeout-ms must be > 0")
        if (getattr(args, "max_staleness_rev", 0) < 0
                or getattr(args, "max_staleness_ms", 0.0) < 0):
            raise SystemExit("--max-staleness-rev/--max-staleness-ms "
                             "must be >= 0 (0 = unbounded)")
    elif getattr(args, "leader_address", "") or getattr(args, "leader_info", ""):
        raise SystemExit("--leader-address/--leader-info require "
                         "--role follower")
    faults = getattr(args, "faults", "") or ""
    if faults:
        from .faults.schedule import PRESETS

        if faults not in PRESETS:
            raise SystemExit(
                f"--faults {faults!r} unknown; presets: {', '.join(PRESETS)}")
        if getattr(args, "fault_horizon_s", 1.0) <= 0:
            raise SystemExit("--fault-horizon-s must be > 0")


def build_endpoint(args, boot_t0: float | None = None):
    """Dependency wiring (reference KubeBrainOption.Run, option.go:230-259):
    storage → [metrics decorator] → backend → server → endpoint.
    ``boot_t0`` is where boot's clock started (``main``: this module's
    import); an embedding caller's boot starts here."""
    validate_args(args)
    from .metrics import new_metrics

    metrics = new_metrics(args.cluster_name)
    boot = BootPhases(time.monotonic() if boot_t0 is None else boot_t0,
                      metrics)
    # must happen before anything imports jax (embedding callers reach here
    # without going through main())
    apply_jax_platform(args.jax_platform)
    if uses_jax(args):
        from .util.jaxcache import use_compile_cache

        use_compile_cache()
        import jax

        # the backend's (TPU's) initialisation, paid here so that it is
        # jax_init's and not the store's or the mesh's
        jax.devices()
        boot.mark("jax_init")
    from .backend import Backend, BackendConfig
    from .endpoint import Endpoint, EndpointConfig
    from .server import Server
    from .server.service import PeerService, SingleNodePeerService
    from .storage import new_storage
    from .util.net import get_host

    # arm the process tracer: stage histograms (kb_rpc_stage_seconds) flow
    # into this metrics sink, slow requests into the /debug/traces slow log
    from .trace import TRACER

    TRACER.configure(metrics=metrics,
                     slow_ms=getattr(args, "trace_slow_ms", 500.0))

    # chaos mode (docs/faults.md): build the deterministic fault plane.
    # INERT until /faults/arm — a --faults none (or never-armed) server is
    # byte-identical to a plain one by construction.
    fault_plane = None
    faults_preset = getattr(args, "faults", "") or ""
    if faults_preset and faults_preset != "none":
        from .faults import FaultPlane
        from .faults import generate as generate_faults

        fault_plane = FaultPlane(
            generate_faults(faults_preset, getattr(args, "fault_seed", 0),
                            getattr(args, "fault_horizon_s", 30.0)),
            metrics=metrics)

    native_kw = {"partitions": args.native_partitions}
    if getattr(args, "data_dir", ""):
        native_kw.update({"data_dir": args.data_dir, "fsync": args.fsync})
    if args.storage == "tpu":
        if args.inner_storage == "native":
            inner_kw = native_kw
        elif args.inner_storage == "remote":
            # the composed production topology: TPU data plane over the
            # shared kbstored tier (reference: scanner over TiKV partitions)
            inner_kw = {"address": args.storage_address, "pool": args.storage_pool,
                        "read_followers": args.storage_read_followers}
        else:
            inner_kw = {}
        if args.use_pallas:
            inner_kw["use_pallas"] = True
        if getattr(args, "key_encoding", ""):
            inner_kw["encode_keys"] = args.key_encoding == "encoded"
        if getattr(args, "merge_threshold", 0):
            inner_kw["merge_threshold"] = args.merge_threshold
        # multichip sharded serving (docs/multichip.md): an explicit mesh
        # flag builds the partition mesh HERE, so the flag errors surface at
        # boot, not on the first scan; no flags = today's every-device mesh
        mesh = None
        mesh_part = getattr(args, "mesh_part", 0)
        scan_parts = getattr(args, "scan_partitions", 0)
        if mesh_part or scan_parts:
            import jax

            from .parallel.mesh import make_mesh

            avail = len(jax.devices())
            if mesh_part > avail:
                raise SystemExit(
                    f"--mesh-part {mesh_part} exceeds the {avail} visible "
                    f"device(s); set XLA_FLAGS=--xla_force_host_platform_"
                    f"device_count for CPU simulation")
            mesh = make_mesh(n_devices=mesh_part or None)
            n_dev = int(mesh.devices.size)
            if scan_parts and scan_parts % n_dev:
                raise SystemExit(
                    f"--scan-partitions {scan_parts} must be a multiple of "
                    f"the mesh part-axis size {n_dev}")
        if fault_plane is not None:
            # wrap the INNER host engine so injected uncertainty poisons
            # (and quarantines) the device mirror like a real engine fault
            from .faults import FaultyStorage

            inner_kw["inner_wrap"] = (
                lambda s: FaultyStorage(s, fault_plane))
        store = new_storage("tpu", inner=args.inner_storage, mesh=mesh,
                            scan_partitions=scan_parts, **inner_kw)
    elif args.storage == "native":
        store = new_storage("native", **native_kw)
    elif args.storage == "remote":
        store = new_storage(
            "remote", address=args.storage_address, pool=args.storage_pool,
            partitions=args.native_partitions,
            read_followers=args.storage_read_followers,
        )
    else:
        store = new_storage(args.storage)
    boot.mark("store_open")
    if fault_plane is not None and args.storage != "tpu":
        from .faults import FaultyStorage

        store = FaultyStorage(store, fault_plane)
    if args.enable_storage_metrics:
        from .storage.metrics_wrap import MetricsKvStorage

        store = MetricsKvStorage(store, metrics)

    fanout = None
    if args.tpu_fanout:
        # the fan-out mesh is independent of the scan mesh: the watcher
        # table is the large shardable side of the (E x W) product and
        # followers build one too (follower offload — fan-out capacity
        # scales with replicas, docs/watch.md)
        wat_mesh = None
        mesh_wat = getattr(args, "mesh_wat", 0)
        if mesh_wat:
            import jax

            from .parallel.mesh import make_mesh

            avail = len(jax.devices())
            if mesh_wat > avail:
                raise SystemExit(
                    f"--mesh-wat {mesh_wat} exceeds the {avail} visible "
                    f"device(s); set XLA_FLAGS=--xla_force_host_platform_"
                    f"device_count for CPU simulation")
            wat_mesh = make_mesh(n_devices=mesh_wat, axes=("wat",))
        if getattr(args, "fanout_impl", "block") == "legacy":
            from .ops.fanout import FanoutMatcher

            fanout = FanoutMatcher(mesh=wat_mesh)
        else:
            from .fanout import DeviceFanout

            fanout = DeviceFanout(mesh=wat_mesh)
        # kb_fanout_sharded: 1 when the table is really distributed
        fanout.set_metrics(metrics)

    backend = Backend(store, BackendConfig(
        prefix=args.prefix.encode(),
        skip_prefixes=[s.encode() for s in args.skip_prefixes.split(",") if s],
        watch_cache_capacity=args.watch_cache_size,
        enable_etcd_compatibility=not args.disable_etcd_compatibility,
        fanout_matcher=fanout,
    ))

    # watch-path lag instrumentation: commit->delivery histogram + per-
    # watcher backlog gauges on /metrics
    backend.watcher_hub.set_metrics(metrics)

    # uncertain-write repair observability: queue-depth gauge + per-outcome
    # repair counters (the chaos report reconciles against these)
    backend.retry.set_metrics(metrics)

    if fault_plane is not None:
        # bind the endpoint-level injections: the watch-reset daemon picks
        # victims from the hub; the TPU scanner gets the merge/encode
        # hooks; the gRPC front adds the conn-drop interceptor (endpoint
        # discovers the plane via backend._kb_faults)
        fault_plane.bind_hub(backend.watcher_hub)
        backend._kb_faults = fault_plane
        if hasattr(backend.scanner, "set_fault_plane"):
            backend.scanner.set_fault_plane(fault_plane)

    # per-shard HBM accounting (tpu engine): kb_mirror_bytes{device=}
    # scrape-time gauges off the live mirror (docs/multichip.md)
    if hasattr(backend.scanner, "register_metrics"):
        backend.scanner.register_metrics(metrics)

    # the device-aware request scheduler, created here (before any service
    # constructs a KVService) so every surface shares the flag-configured
    # instance with real metrics — later ensure_scheduler calls adopt it
    from .sched import SchedConfig, ensure_scheduler

    ensure_scheduler(backend, SchedConfig(
        depth=args.sched_depth,
        queue_limit=args.sched_queue_limit,
        shed_ms=args.sched_shed_ms,
        batch=args.sched_batch,
        write_batch=args.sched_write_batch,
    ), metrics=metrics)

    identity = args.identity or f"{get_host()}:{args.peer_port}"
    replica_role = None
    if getattr(args, "role", "leader") == "follower":
        # follower role (docs/replication.md): the role object IS the
        # peers surface (is_leader False, no-op revision sync) so every
        # existing service works unchanged, plus the per-RPC replica
        # routing the etcd terminals consult
        from .replica import FollowerConfig, FollowerRole

        leader_creds = None
        if args.ca_file:
            # a TLS-serving leader: verify it against the configured CA
            # on the forwarding + replication channels
            import grpc as _grpc

            with open(args.ca_file, "rb") as f:
                leader_creds = _grpc.ssl_channel_credentials(
                    root_certificates=f.read())
        replica_role = FollowerRole(
            backend,
            FollowerConfig(
                leader_address=args.leader_address,
                leader_info=args.leader_info,
                max_staleness_rev=getattr(args, "max_staleness_rev", 0),
                max_staleness_ms=getattr(args, "max_staleness_ms", 5000.0),
                fence_timeout_s=getattr(args, "fence_timeout_ms", 3000.0)
                / 1000.0,
                credentials=leader_creds,
            ),
            metrics=metrics, fault_plane=fault_plane, identity=identity)
        peers = replica_role
    elif args.single_node:
        peers = SingleNodePeerService(backend, identity)
    else:
        peers = PeerService(
            backend, identity, args.client_port, enable_proxy=args.enable_etcd_proxy
        )

    # lease subsystem: key-pattern TTLs demoted to a flag-gated fallback
    # (explicit PutRequest.lease always wins); registry + leader-only reaper
    # created here with the flag-derived cadences so every service surface
    # shares one table (later ensure_lease calls adopt it)
    from .backend import creator
    from .lease import ensure_lease

    creator.LEGACY_TTL_PATTERNS = bool(
        getattr(args, "legacy_ttl_patterns", True))
    ensure_lease(
        backend, peers=peers, metrics=metrics,
        reap_interval=args.lease_reap_interval,
        checkpoint_interval=args.lease_checkpoint_interval,
    )
    server = Server(
        backend, peers, metrics, identity,
        client_urls=[f"http://{identity.rsplit(':', 1)[0]}:{args.client_port}"],
        compact_interval=args.compact_interval,
        replica=replica_role,
    )
    if uses_jax(args):
        server.register_device_metrics()
    extra_http = {}
    if fault_plane is not None:
        # chaos-runner control surface on the info port: arm aligns the
        # fault windows with replay start; state feeds the SLO report's
        # injected/observed reconciliation
        extra_http["/faults/arm"] = fault_plane.http_arm
        extra_http["/faults/state"] = fault_plane.http_state
    endpoint = Endpoint(server, metrics, EndpointConfig(
        host=args.host,
        client_port=args.client_port,
        peer_port=args.peer_port,
        info_port=args.info_port,
        cert_file=args.cert_file,
        key_file=args.key_file,
        ca_file=args.ca_file,
        insecure=not args.secure_only,
        grpc_workers=args.grpc_workers,
        extra_http=extra_http,
    ))
    endpoint.boot = boot  # main() closes ``listen`` once the ports answer
    if args.aio_port:
        from .endpoint.aio import AioEndpoint

        creds = None
        if args.cert_file and args.key_file:
            creds = endpoint._grpc_creds()
        aio = AioEndpoint(
            backend, peers, args.host, args.aio_port, identity,
            credentials=creds, insecure=not args.secure_only,
        )
        _orig_run, _orig_close = endpoint.run, endpoint.close

        def run_both():
            _orig_run()
            aio.run()

        def close_both(grace: float = 1.0):
            aio.close(grace)
            _orig_close(grace)

        endpoint.run = run_both
        endpoint.close = close_both
    if getattr(args, "front_port", 0):
        from .endpoint.front import FrontServer

        front = FrontServer(
            backend, peers, server, identity, metrics=metrics,
            brain=server.brain,
            inline_unary=args.storage != "remote",
        )
        _frun, _fclose = endpoint.run, endpoint.close

        def run_with_front():
            _frun()
            front.run(args.front_port, args.host,
                      cert_file=args.cert_file, key_file=args.key_file,
                      ca_file=args.ca_file, secure_only=args.secure_only)

        def close_with_front(grace: float = 1.0):
            front.close()
            _fclose(grace)

        endpoint.run = run_with_front
        endpoint.close = close_with_front
    if replica_role is not None:
        # start the replication stream once the listeners are up; stop it
        # (and the forwarding channel) before the backend goes away
        _rp_run, _rp_close = endpoint.run, endpoint.close

        def run_with_replica():
            _rp_run()
            replica_role.start()

        def close_with_replica(grace: float = 1.0):
            replica_role.close()
            _rp_close(grace)

        endpoint.run = run_with_replica
        endpoint.close = close_with_replica
    return endpoint, backend, store


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.version:
        print(f"kubebrain-tpu {__version__} (storage engines: memkv, tpu, native)")
        return 0

    # server-profile gc: the default thresholds collect every ~700
    # allocations — at informer fan-out scale (10k watch streams, 100k+
    # protobuf deliveries) collection pauses halved write throughput in the
    # config-5 sim. Protobufs/events are acyclic; raise the thresholds.
    # KB_GC_THRESHOLD=a[,b[,c]] overrides; 0 keeps Python defaults.
    gc_env = os.environ.get("KB_GC_THRESHOLD", "")
    if gc_env != "0":
        import gc

        try:
            parts = [int(x) for x in gc_env.split(",") if x.strip()]
        except ValueError:
            print(f"ignoring malformed KB_GC_THRESHOLD={gc_env!r}", file=sys.stderr)
            parts = []
        if not parts or any(p <= 0 for p in parts):
            # zero disables gc entirely; negatives crash set_threshold
            parts = [200_000, 1000, 1000]
        gc.set_threshold(*parts[:3])

    endpoint, backend, store = build_endpoint(args, boot_t0=_T_IMPORT)
    if args.tier_auto_failover:
        if not endpoint.server.start_tier_watchdog():
            # an explicitly requested HA feature that cannot arm must not
            # be silently dropped (validate_args style)
            raise SystemExit(
                "--tier-auto-failover requires --storage=remote (or "
                "tpu-over-remote) with --storage-address primary,follower,...")
    stop = threading.Event()
    watchdog: list[threading.Timer] = []

    def _graceful_exit(signum, frame):  # noqa: ARG001
        # force-kill watchdog (reference forceExitWhileGracefulExitTimeout,
        # cmd/main.go:62): a wedged close must not block exit; budget covers
        # grpc drain + aio loop stop + engine checkpoint
        t = threading.Timer(10.0, lambda: os._exit(2))
        t.daemon = True
        t.start()
        watchdog.append(t)
        stop.set()

    signal.signal(signal.SIGINT, _graceful_exit)
    signal.signal(signal.SIGTERM, _graceful_exit)

    endpoint.run()
    endpoint.boot.mark("listen")
    if uses_jax(args):
        print(boot_line(backend, endpoint.boot.seconds), file=sys.stderr)
    print(
        f"kubebrain-tpu {__version__} serving: etcd3+brain gRPC :{args.client_port}, "
        f"peer http :{args.peer_port}, info http :{args.info_port} "
        f"(storage={args.storage})",
        file=sys.stderr,
    )
    stop.wait()
    endpoint.close()
    backend.close()
    store.close()
    for t in watchdog:
        t.cancel()
    return 0


if __name__ == "__main__":
    sys.exit(main())
