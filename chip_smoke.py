#!/usr/bin/env python3
"""The quickest proof that the served ``--storage=tpu`` path still starts
and answers correctly ON THE CHIP.

It starts the server a user would start (README "Run":
``python -m kubebrain_tpu.cli --single-node --storage=tpu
--inner-storage=native --data-dir ... --use-pallas``) as a child process and
drives it over the etcd3 gRPC front with ``kubebrain_tpu.client`` at the size
of Kubernetes' documented large-cluster envelope: 150,000 pod keys over 100
namespaces, 1 KiB values, then 10 % of the keys updated twice (CAS) and 5 %
deleted, so the device mirror holds history and tombstones. Every read is
compared with the smoke's own dict of acknowledged writes; after every phase
the server's ``/metrics`` must show that the DEVICE answered (a quarantined
mirror serves byte-identical rows from the host store, so right answers alone
prove nothing about the chip). Then the server is stopped and a second one is
started on the same data dir with the default ``jnp`` kernel: both kernels,
the rebuild-from-store path, and a clean hand-over of the chip between two
processes are covered.

One process per chip: this parent never imports JAX, and the two servers run
one after the other. The child gets ``JAX_PLATFORMS=tpu``, so an absent or
busy chip is a boot failure — never a quiet CPU server.

Only when every phase passed: exit 0 and two stdout lines, the observations
(one JSON object) and, last, exactly ``{"ok": true, "device": {"platform":
..., "kind": ..., "count": ...}}``. Any failure exits non-zero with nothing on
stdout and the server log on stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import grpc

from kubebrain_tpu.client import EtcdCompatClient, WatchMux
from kubebrain_tpu.workload import slo
from kubebrain_tpu.workload.runner import free_port

HERE = os.path.dirname(os.path.abspath(__file__))
PODS = b"/registry/pods/"
NAMESPACES = 100
VALUE_BYTES = 1024   # the workload harness's median object size
BOOT_PREFIX = "kubebrain-tpu boot: "
# stop loading (and say so) after this long, so a slow wire cannot push the
# run past its time limit
LOAD_BUDGET_S = 500.0
# a server that is not serving by then (a chip held by another process can
# hang it) fails the run
BOOT_TIMEOUT_S = 300.0


class SmokeFailure(AssertionError):
    """A check did not hold; the run ends non-zero."""


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ oracle
class Oracle:
    """The plain reference: what the server acknowledged, kept by the smoke
    itself. ``live`` maps pod index -> (value version, mod_revision);
    ``created`` keeps every create revision (the pre-update snapshot).
    Values are regenerated from ``seed``, never stored."""

    n_ns = NAMESPACES

    def __init__(self, seed: int):
        self._pool = random.Random(seed).randbytes(1 << 20)
        self.live: dict[int, tuple[int, int]] = {}
        self.created: dict[int, int] = {}

    def key(self, i: int) -> bytes:
        return PODS + b"ns-%03d/pod-%06d" % (i % self.n_ns, i)

    def ns_range(self, ns: int) -> tuple[bytes, bytes]:
        prefix = PODS + b"ns-%03d/" % ns
        return prefix, prefix[:-1] + b"0"

    def value(self, i: int, ver: int) -> bytes:
        head = b"pod-%d/v%d/" % (i, ver)
        off = (i * 7919 + ver * 104729) % (len(self._pool) - VALUE_BYTES)
        return head + self._pool[off: off + VALUE_BYTES - len(head)]

    def _ns_indices(self, ns: int) -> range:
        # pods load in index order, so the created indices are 0..n-1 and
        # a namespace's pods, in key order, are every n_ns-th of them
        return range(ns, len(self.created), self.n_ns)

    def head_rows(self, ns: int) -> list[tuple[bytes, bytes, int]]:
        return [(self.key(i), self.value(i, self.live[i][0]), self.live[i][1])
                for i in self._ns_indices(ns) if i in self.live]

    def created_rows(self, ns: int) -> list[tuple[bytes, bytes, int]]:
        return [(self.key(i), self.value(i, 0), self.created[i])
                for i in self._ns_indices(ns)]


def compare_rows(what: str, got, want) -> None:
    """Row-for-row on key, value and mod_revision."""
    require(len(got) == len(want),
            f"{what}: {len(got)} rows, the oracle holds {len(want)}")
    for kv, row in zip(got, want):
        if (kv.key, kv.value, kv.mod_revision) != row:
            raise SmokeFailure(
                f"{what}: row {kv.key!r} rev {kv.mod_revision} "
                f"({len(kv.value)} B) differs from the oracle's "
                f"{row[0]!r} rev {row[2]}")


# ----------------------------------------------------------------- context
class Ctx:
    """One served endpoint under test plus what the run has learned."""

    def __init__(self, target: str, info_port: int, oracle: Oracle, *,
                 n_keys: int, n_devices: int, device_prefix: str, seed: int):
        self.target = target
        self.info_port = info_port
        self.oracle = oracle
        self.n_keys = n_keys
        self.n_devices = n_devices
        self.device_prefix = device_prefix
        self.rng = random.Random(seed)
        self.client = EtcdCompatClient(target)
        self.checks: list[str] = []
        self.obs: dict = {}
        self.snap_rev = 0   # revision of the last create
        self.head_rev = 0   # highest acknowledged revision

    def scrape(self) -> slo.PromSnapshot:
        url = f"http://127.0.0.1:{self.info_port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as r:
            return slo.parse_prom(r.read().decode())

    def passed(self, name: str, t0: float) -> None:
        self.checks.append(name)
        log(f"{name}: ok ({time.monotonic() - t0:.1f}s)")

    def close(self) -> None:
        self.client.close()


STAGES = "kb_rpc_stage_seconds_count"


def check_device(ctx: Ctx, phase: str, before: slo.PromSnapshot,
                 device_reads: int, mirror: bool = True) -> slo.PromSnapshot:
    """The server's own account of the phase: the mirror is serving from
    the expected devices, the device stages moved at least once per
    device-path read, and nothing failed, escalated or was shed."""
    deadline = time.monotonic() + 5.0
    while True:
        # stage histograms land when the RPC's span finishes, a moment
        # after the client has its response
        snap = ctx.scrape()
        moved = {st: slo.delta(snap, before, STAGES, stage=st)
                 for st in ("device_dispatch", "device_compute")}
        if min(moved.values()) >= device_reads or time.monotonic() > deadline:
            break
        time.sleep(0.1)
    require(min(moved.values()) >= device_reads,
            f"{phase}: {device_reads} device-path reads but the device "
            f"stages moved by {moved} — the host store answered")
    require(slo.series_sum(snap, "kb_mirror_state", state="serving") == 1.0,
            f"{phase}: mirror is not serving: {snap.get('kb_mirror_state')}")
    if mirror:
        series = snap.get("kb_mirror_bytes", [])
        labels = sorted(lb.get("device", "") for lb, _v in series)
        require(len(series) == ctx.n_devices,
                f"{phase}: kb_mirror_bytes on {labels}, expected "
                f"{ctx.n_devices} device(s)")
        for lb, v in series:
            require(lb.get("device", "").startswith(ctx.device_prefix),
                    f"{phase}: mirror on {lb.get('device')!r}, expected a "
                    f"{ctx.device_prefix}* device")
            require(v > 0, f"{phase}: kb_mirror_bytes{lb} is 0")
    for name in ("kb_mirror_merge_errors_total",
                 "kb_mirror_merge_escalations_total",
                 "kb_compact_errors_total", "kb_compact_escalations_total",
                 "kb_sched_shed_total"):
        require(slo.series_sum(snap, name) == 0,
                f"{phase}: {name} = {slo.series_sum(snap, name)}")
    return snap


def _parallel(ctx: Ctx, fn, items, threads: int = 8) -> list:
    """``fn(client, item)`` over ``items`` from ``threads`` clients; the
    first failure propagates."""
    def work(chunk):
        client = EtcdCompatClient(ctx.target)
        try:
            return [fn(client, it) for it in chunk]
        finally:
            client.close()

    with ThreadPoolExecutor(threads) as pool:
        outs = list(pool.map(work, [items[t::threads] for t in range(threads)]))
    return [r for out in outs for r in out]


def _range_ns(ctx: Ctx, client: EtcdCompatClient, ns: int, what: str,
              revision: int = 0) -> None:
    start, end = ctx.oracle.ns_range(ns)
    got, _rev = client.list_unpaged(start, end, revision=revision)
    want = (ctx.oracle.created_rows(ns) if revision
            else ctx.oracle.head_rows(ns))
    compare_rows(f"{what} ns-{ns:03d}", got, want)


# ------------------------------------------------------------------ phases
def phase_load(ctx: Ctx) -> None:
    """Preload through the front exactly as the workload runner does:
    pipelined create Txns, 128 in flight."""
    t0 = time.monotonic()
    before = ctx.scrape()
    orc = ctx.oracle
    sent: list[int] = []

    def items():
        for i in range(ctx.n_keys):
            if time.monotonic() - t0 > LOAD_BUDGET_S:
                return  # cut: the wire load rate does not fit the time limit
            sent.append(i)
            yield orc.key(i), orc.value(i, 0)

    results = ctx.client.create_bulk(items(), window=128)
    dt = time.monotonic() - t0
    for i, (ok, rev) in zip(sent, results):
        require(ok, f"load: create of {orc.key(i)!r} refused (rev {rev})")
        orc.created[i] = rev
        orc.live[i] = (0, rev)
    ctx.snap_rev = ctx.head_rev = max(orc.created.values())
    ctx.obs.update(keys_target=ctx.n_keys, keys_loaded=len(sent),
                   keys_cut=len(sent) < ctx.n_keys,
                   load_seconds=round(dt, 2),
                   load_ops_per_s=round(len(sent) / dt, 1))
    if len(sent) < ctx.n_keys:
        log(f"CUT: loaded {len(sent)} of {ctx.n_keys} keys in the "
            f"{LOAD_BUDGET_S:.0f}s load budget")
    check_device(ctx, "load", before, 0, mirror=False)
    ctx.passed("load", t0)


def phase_first_reads(ctx: Ctx) -> None:
    """Cold then warm: the first unpaged Range pays the last delta merge
    and every compilation; the second pays neither. Run sequentially so a
    cold compile never has reads queued (and shed) behind it."""
    t0 = time.monotonic()
    before = ctx.scrape()
    _range_ns(ctx, ctx.client, 0, "first read")
    cold = time.monotonic() - t0
    t1 = time.monotonic()
    _range_ns(ctx, ctx.client, 0, "second read")
    ctx.obs["first_read_seconds"] = {
        "cold": round(cold, 3), "warm": round(time.monotonic() - t1, 3)}
    check_device(ctx, "first reads", before, 2)
    ctx.passed("first_reads", t0)


def phase_range_all(ctx: Ctx, name: str = "range_all") -> None:
    """Unpaged per-namespace Range (limit 0 -> device path), every
    namespace, row for row."""
    t0 = time.monotonic()
    before = ctx.scrape()
    for ns in range(ctx.oracle.n_ns):
        _range_ns(ctx, ctx.client, ns, name)
    check_device(ctx, name, before, ctx.oracle.n_ns)
    ctx.passed(name, t0)


def phase_count(ctx: Ctx, name: str = "count") -> None:
    t0 = time.monotonic()
    before = ctx.scrape()
    got = ctx.client.count(PODS, PODS[:-1] + b"0")
    require(got == len(ctx.oracle.live),
            f"{name}: Count {got}, the oracle holds {len(ctx.oracle.live)}")
    check_device(ctx, name, before, 1)
    ctx.passed(name, t0)


def phase_concurrent(ctx: Ctx, name: str = "concurrent") -> None:
    """8 threads, each its own distinct namespaces, so the scheduler drains
    distinct ready Ranges into one query-batched dispatch (scan_batch)."""
    t0 = time.monotonic()
    before = ctx.scrape()
    n_ns = ctx.oracle.n_ns
    reads = 0
    for _round in range(5):
        _parallel(ctx, lambda c, ns: _range_ns(ctx, c, ns, name),
                  list(range(n_ns)))
        reads += n_ns
        count, members = slo.hist_count_sum(ctx.scrape(), "kb_sched_batch_size")
        count0, members0 = slo.hist_count_sum(before, "kb_sched_batch_size")
        batches = count - count0
        if batches > 0:
            break
    require(batches > 0, f"{name}: 8 threads of distinct Ranges never "
            "formed a query batch (kb_sched_batch_size_count did not move)")
    # a batch is one dispatch: its leader's span carries the device stages,
    # its riders' spans a batch_join
    riders = int(members - members0 - batches)
    ctx.obs.setdefault("query_batches", {})[name] = {
        "batches": int(batches), "riders": riders, "reads": reads}
    check_device(ctx, name, before, reads - riders)
    ctx.passed(name, t0)


def phase_churn_and_watch(ctx: Ctx) -> None:
    """10 % of the keys updated twice (CAS on mod_revision), 5 % deleted,
    under a Watch on one namespace opened first: exact event count,
    strictly increasing revisions."""
    t0 = time.monotonic()
    before = ctx.scrape()
    orc = ctx.oracle
    loaded = sorted(orc.live)
    updated = ctx.rng.sample(loaded, len(loaded) // 10)
    deleted = ctx.rng.sample(loaded, len(loaded) // 20)
    watch_ns = 42 % orc.n_ns
    mux = WatchMux(ctx.client, streams=1, record_revisions=True)
    try:
        watch = mux.add(*orc.ns_range(watch_ns))

        def update(client, i):
            ver, rev = orc.live[i]
            ok, new_rev = client.update(orc.key(i), orc.value(i, ver + 1), rev)
            require(ok, f"churn: CAS update of {orc.key(i)!r} at {rev} "
                        f"refused (server has {new_rev})")
            return i, ver + 1, new_rev

        for _round in range(2):
            for i, ver, rev in _parallel(ctx, update, updated):
                orc.live[i] = (ver, rev)

        def delete(client, i):
            require(client.delete(orc.key(i), orc.live[i][1]),
                    f"churn: CAS delete of {orc.key(i)!r} refused")
            return i

        for i in _parallel(ctx, delete, deleted):
            del orc.live[i]
        ctx.head_rev = ctx.client.current_revision()

        want = (2 * sum(1 for i in updated if i % orc.n_ns == watch_ns)
                + sum(1 for i in deleted if i % orc.n_ns == watch_ns))
        deadline = time.monotonic() + 60.0
        while watch.events < want and time.monotonic() < deadline:
            time.sleep(0.05)
        time.sleep(0.5)  # an extra event would be a failure too
        require(watch.events == want and not watch.cancelled,
                f"watch: {watch.events} events on ns-{watch_ns:03d}, "
                f"expected {want} (cancelled={watch.cancelled})")
        revs = watch.revisions
        require(all(a < b for a, b in zip(revs, revs[1:])),
                "watch: revisions not strictly increasing")
    finally:
        mux.close()
    ctx.obs.update(rows=len(orc.created) + 2 * len(updated) + len(deleted),
                   live_keys=len(orc.live), watch_events=want)
    check_device(ctx, "churn", before, 0)
    ctx.passed("churn_and_watch", t0)


def phase_snapshot(ctx: Ctx) -> None:
    """A read at the revision of the last create equals the pre-update
    state (the mirror holds history)."""
    t0 = time.monotonic()
    before = ctx.scrape()
    nss = sorted({0, 42 % ctx.oracle.n_ns, ctx.oracle.n_ns - 1})
    for ns in nss:
        _range_ns(ctx, ctx.client, ns, "snapshot", revision=ctx.snap_rev)
    check_device(ctx, "snapshot", before, len(nss))
    ctx.passed("snapshot", t0)


def phase_paged(ctx: Ctx) -> None:
    """client-go's shape: limit 500 pages (the host path by design) give
    the same rows as the unpaged device answer."""
    t0 = time.monotonic()
    before = ctx.scrape()
    ns = 42 % ctx.oracle.n_ns
    got, _rev = ctx.client.list(*ctx.oracle.ns_range(ns), page=500)
    compare_rows("paged list", got, ctx.oracle.head_rows(ns))
    check_device(ctx, "paged", before, 0)
    ctx.passed("paged", t0)


def phase_compact(ctx: Ctx) -> None:
    """Compact through the front at a mid-history revision: the head is
    unchanged, a read below the floor gets etcd's compacted error, and the
    device-side compaction ran clean."""
    t0 = time.monotonic()
    before = ctx.scrape()
    ctx.client.compact((ctx.snap_rev + ctx.head_rev) // 2)
    nss = list(range(0, ctx.oracle.n_ns, max(1, ctx.oracle.n_ns // 8)))
    for ns in nss:
        _range_ns(ctx, ctx.client, ns, "post-compact")
    got = ctx.client.count(PODS, PODS[:-1] + b"0")
    require(got == len(ctx.oracle.live), f"post-compact Count {got}")
    try:
        ctx.client.list_unpaged(*ctx.oracle.ns_range(0), revision=ctx.snap_rev)
    except grpc.RpcError as e:
        require(e.code() == grpc.StatusCode.OUT_OF_RANGE
                and "compacted" in (e.details() or ""),
                f"compact: read below the floor failed with {e.code()} "
                f"{e.details()!r}, expected etcd's compacted error")
    else:
        raise SmokeFailure("compact: a read below the floor was answered")
    snap = check_device(ctx, "compact", before, len(nss) + 1)
    for ph in ("mark", "gc", "merge", "publish"):
        require(slo.delta(snap, before, "kb_compact_seconds_count",
                          phase=ph) >= 1,
                f"compact: kb_compact_seconds_count{{phase={ph}}} did not move")
    ctx.obs["compact_victims"] = int(
        slo.delta(snap, before, "kb_compact_victims_total"))
    ctx.passed("compact", t0)


def phase_fanout(ctx: Ctx) -> None:
    """The watcher population that makes the hub route drain blocks to the
    device matcher (--tpu-fanout): 80 broad + 70 distinct-prefix watchers,
    70 creates, per-watch delivery counts exact."""
    t0 = time.monotonic()
    before = ctx.scrape()
    fan = b"/registry/fan/"
    prefixes = [fan + b"p-%03d/" % j for j in range(70)]
    keys = [prefixes[j % 3] + b"obj-%03d" % j for j in range(70)]
    mux = WatchMux(ctx.client, streams=4, record_revisions=True)
    try:
        watches = [mux.add(b"/registry/", b"/registry0") for _ in range(80)]
        watches += [mux.add(p, p[:-1] + b"0") for p in prefixes]
        watches.append(mux.add(keys[0], keys[0] + b"\0"))
        for k in keys:
            ok, rev = ctx.client.create(k, b"fan")
            require(ok, f"fanout: create of {k!r} refused")
        want = [sum(1 for k in keys if w.key <= k < w.range_end)
                for w in watches]
        deadline = time.monotonic() + 60.0
        while (mux.total_events() < sum(want)
               and time.monotonic() < deadline):
            time.sleep(0.05)
        time.sleep(0.5)
        got = [w.events for w in watches]
        require(got == want, f"fanout: per-watch deliveries {got} != {want}")
        for w in watches:
            require(all(a < b for a, b in zip(w.revisions, w.revisions[1:])),
                    f"fanout: revisions out of order on {w.key!r}")
    finally:
        mux.close()
    snap = check_device(ctx, "fanout", before, 0)
    moved = slo.delta(snap, before, STAGES, stage="fanout_dispatch")
    require(moved >= 1, "fanout: the hub never dispatched a drain block to "
                        "the device matcher (stage fanout_dispatch)")
    ctx.obs["fanout_dispatches"] = int(moved)
    ctx.passed("fanout", t0)


def drive_first_server(ctx: Ctx, tamper=None) -> None:
    """Everything the first (--use-pallas --tpu-fanout) server must get
    right. ``tamper(oracle)`` is the tests' hook for proving that a wrong
    row fails the run; nothing else passes it."""
    phase_load(ctx)
    if tamper is not None:
        tamper(ctx.oracle)
    phase_first_reads(ctx)
    phase_range_all(ctx)
    phase_concurrent(ctx)
    phase_count(ctx)
    phase_churn_and_watch(ctx)
    phase_range_all(ctx, "range_all_after_churn")
    phase_snapshot(ctx)
    phase_paged(ctx)
    phase_compact(ctx)
    phase_fanout(ctx)
    snap = ctx.scrape()
    ctx.obs["mirror_bytes"] = {
        lb["device"]: int(v) for lb, v in snap.get("kb_mirror_bytes", [])}


def drive_restarted_server(ctx: Ctx) -> None:
    """The same store behind a fresh process and the other kernel: the
    mirror rebuilds from the store and every answer is byte-identical."""
    phase_first_reads(ctx)
    phase_range_all(ctx, "range_all_restarted")
    phase_concurrent(ctx, "concurrent_restarted")
    phase_count(ctx, "count_restarted")


# ------------------------------------------------------------------ server
class Server:
    """One ``python -m kubebrain_tpu.cli`` child on the chip."""

    def __init__(self, data_dir: str, log_path: str, mesh_part: int,
                 flags: list[str]):
        self.client_port, self.info_port = free_port(), free_port()
        self.log_path = log_path
        argv = [sys.executable, "-m", "kubebrain_tpu.cli", "--single-node",
                "--storage", "tpu", "--inner-storage", "native",
                "--data-dir", data_dir, "--host", "127.0.0.1",
                "--client-port", str(self.client_port),
                "--peer-port", str(free_port()),
                "--info-port", str(self.info_port),
                # with no flag the scanner takes EVERY visible device
                "--mesh-part", str(mesh_part),
                "--sched-batch", "8",
                # the smoke owns compaction: the periodic compactor would
                # move the floor under the snapshot read
                "--compact-interval", "86400", *flags]
        # a busy or absent chip must be a boot failure, not a CPU server;
        # inherited KB_* knobs (KB_JAX_PLATFORM, KB_USE_PALLAS, ...) would
        # change what is being proven
        env = {k: v for k, v in os.environ.items() if not k.startswith("KB_")}
        env["JAX_PLATFORMS"] = "tpu"
        self._log = open(log_path, "wb")
        self.proc = subprocess.Popen(argv, cwd=HERE, env=env,
                                     stdout=self._log, stderr=self._log)

    @property
    def target(self) -> str:
        return f"127.0.0.1:{self.client_port}"

    def log_text(self) -> str:
        with open(self.log_path, "rb") as f:
            return f.read().decode(errors="replace")

    def wait_ready(self, timeout_s: float) -> dict:
        """Serve one Count, then return the boot line's record."""
        deadline = time.monotonic() + timeout_s
        while True:
            require(self.proc.poll() is None,
                    f"server exited rc={self.proc.returncode} before serving")
            require(time.monotonic() < deadline,
                    f"server not serving after {timeout_s:.0f}s")
            probe = EtcdCompatClient(self.target)
            try:
                probe.count(b"/smoke-probe", b"/smoke-probe0")
                break
            except grpc.RpcError:
                time.sleep(0.3)
            finally:
                probe.close()
        for line in self.log_text().splitlines():
            if line.startswith(BOOT_PREFIX):
                return json.loads(line[len(BOOT_PREFIX):])
        raise SmokeFailure("server logged no boot line")

    def stop(self) -> None:
        """Graceful stop (the native store checkpoints on close); a server
        that does not exit in time is killed and the run fails."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                raise SmokeFailure("server ignored SIGTERM for 60s") from None
            finally:
                self._log.close()

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def run(opts, tamper=None) -> dict:
    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    data_dir = tempfile.mkdtemp(prefix="kb-chip-smoke-")
    oracle = Oracle(opts.seed)
    summary: dict = {}
    server = None
    try:
        passes = [("pallas", ["--use-pallas", "--tpu-fanout"]), ("jnp", [])]
        for n, (kernel, flags) in enumerate(passes, 1):
            t0 = time.monotonic()
            server = Server(data_dir, os.path.join(
                out_dir, f"chip_smoke_server{n}.log"), opts.mesh_part, flags)
            boot = server.wait_ready(BOOT_TIMEOUT_S)
            boot_s = round(time.monotonic() - t0, 1)
            log(f"server {n} up in {boot_s}s: {boot}")
            require(boot["platform"] == "tpu",
                    f"server {n} computes on {boot['platform']!r}, not a TPU")
            require(boot["scan_kernel"] == kernel,
                    f"server {n} resolved scan kernel "
                    f"{boot['scan_kernel']!r}, expected {kernel!r}")
            require(boot["mesh"] == {"part": opts.mesh_part},
                    f"server {n} mesh {boot['mesh']}")
            if n == 1:
                cache_dir = boot["compile_cache"]
                summary.update(
                    device={"platform": boot["platform"],
                            "kind": boot["device_kind"],
                            "count": boot["devices"]},
                    versions={k: boot[k] for k in ("jax", "jaxlib", "libtpu")},
                    mesh=boot["mesh"], scan_kernels=[],
                    # entries once server 1 serves, after server 1, after
                    # server 2: a run that adds none came from the cache
                    compile_cache={"dir": cache_dir, "entries": [
                        _cache_entries(cache_dir)]})
            summary["scan_kernels"].append(boot["scan_kernel"])
            summary.setdefault("boot_seconds", {})[kernel] = boot_s
            ctx = Ctx(server.target, server.info_port, oracle,
                      n_keys=opts.keys, n_devices=opts.mesh_part,
                      device_prefix="TPU", seed=opts.seed)
            try:
                if n == 1:
                    drive_first_server(ctx, tamper)
                else:
                    drive_restarted_server(ctx)
            finally:
                ctx.close()
            server.stop()
            summary["compile_cache"]["entries"].append(
                _cache_entries(cache_dir))
            summary.setdefault("checks", []).extend(
                f"{kernel}:{c}" for c in ctx.checks)
            summary.setdefault("first_read_seconds", {})[kernel] = (
                ctx.obs.pop("first_read_seconds"))
            summary.setdefault("query_batches", {}).update(
                ctx.obs.pop("query_batches"))
            summary.update(ctx.obs)
        summary["claim"] = None
        return summary
    except BaseException:
        if server is not None:
            server.kill()
            sys.stderr.write(f"---- {server.log_path} (tail) ----\n"
                             f"{server.log_text()[-12000:]}\n")
        raise
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)


def result_lines(summary: dict) -> list[str]:
    """The standard output of a passed run: what was observed, then — last,
    and with exactly these keys, because the chip check parses it — the
    verdict and the device as the server's JAX reported it."""
    dev = summary["device"]
    verdict = {"ok": True,
               "device": {"platform": str(dev["platform"]),
                          "kind": str(dev["kind"]),
                          "count": int(dev["count"])}}
    return [json.dumps(summary), json.dumps(verdict)]


def main(argv=None, tamper=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--keys", type=int, default=150_000,
                    help="pod keys to load (Kubernetes' documented "
                         "large-cluster envelope: 150,000 pods)")
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--mesh-part", type=int, default=1,
                    help="chips on the scan mesh's part axis")
    opts = ap.parse_args(argv)
    try:
        summary = run(opts, tamper)
    except Exception:
        traceback.print_exc()
        return 1
    finally:
        # the chip belongs to the server child: this process stayed off jax
        imported = "jax" in sys.modules
        log(f"parent imported jax: {imported}")
    if imported:
        return 1
    for line in result_lines(summary):
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
