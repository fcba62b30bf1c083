"""Deterministic fault injection + graceful degradation (docs/faults.md).

Covers the three chaos pieces: the pure fault schedule (replay identity),
the FaultyStorage injection classification through a real Backend (definite vs
uncertain outcomes, group-commit per-op demux, the async-FIFO read-back
repair), the TPU mirror's quarantine / merge-retry / escalation state
machine, and the end-to-end chaos smoke that asserts the keystone
acknowledged-write consistency invariant.
"""

import threading
import time

import pytest

from kubebrain_tpu import faults
from kubebrain_tpu.backend import Backend, BackendConfig
from kubebrain_tpu.backend.errors import KeyExistsError
from kubebrain_tpu.faults import FaultInjectedError, FaultPlane, FaultyStorage
from kubebrain_tpu.storage import new_storage
from kubebrain_tpu.storage.errors import (
    KeyNotFoundError,
    StorageError,
    UncertainResultError,
)


def _plane(preset="none", seed=0, horizon=30.0, armed=False) -> FaultPlane:
    p = FaultPlane(faults.generate(preset, seed, horizon))
    if armed:
        p.arm()
    return p


class _ScriptedPlane(FaultPlane):
    """Deterministic decision script for unit tests: pops one decision per
    storage WRITE boundary call (None = no fault); reads stay clean."""

    def __init__(self, script):
        super().__init__(faults.generate("none", 0, 30.0))
        self.script = list(script)
        self.arm()

    def decide_storage(self, write: bool):
        if not write or not self.script:
            return None
        d = self.script.pop(0)
        if d is not None:
            self._count("scripted_" + d[0])
        return d


# ------------------------------------------------------------- schedule
def test_schedule_deterministic_sha():
    a = faults.generate("smoke", 7, 12.0)
    b = faults.generate("smoke", 7, 12.0)
    assert a.sha256() == b.sha256()
    assert a.trace_bytes() == b.trace_bytes()
    assert a.sha256() != faults.generate("smoke", 8, 12.0).sha256()
    assert a.sha256() != faults.generate("full", 7, 12.0).sha256()
    assert a.sha256() != faults.generate("smoke", 7, 13.0).sha256()


def test_schedule_windows_inside_horizon():
    s = faults.generate("full", 3, 9.0)
    assert s.windows, "full preset must lay windows"
    for w in s.windows:
        assert 0 <= w.t0_ms < w.t1_ms <= s.horizon_ms
        assert 0.0 < w.rate <= 1.0
    # every single-server classification kind is scheduled by the full preset;
    # the follower-boundary kinds ride their own `replica` preset (armed
    # on follower processes only — docs/replication.md)
    assert set(s.kinds()) == set(faults.ALL_KINDS) - set(faults.REPLICA_KINDS)
    r = faults.generate("replica", 3, 9.0)
    assert set(r.kinds()) == set(faults.REPLICA_KINDS)
    for w in r.windows:
        assert 0 <= w.t0_ms < w.t1_ms <= r.horizon_ms
        assert 0.0 < w.rate <= 1.0


def test_schedule_none_is_empty_and_unknown_preset_rejected():
    assert faults.generate("none", 0, 5.0).windows == ()
    with pytest.raises(ValueError):
        faults.generate("nope", 0, 5.0)
    with pytest.raises(ValueError):
        faults.generate("smoke", 0, 0.0)


def test_merge_windows_disjoint():
    # fail-then-suppress layout: an overlap would starve the fail window
    for seed in range(10):
        s = faults.generate("smoke", seed, 20.0)
        fail = [w for w in s.windows if w.kind == faults.MERGE_FAIL]
        supp = [w for w in s.windows if w.kind == faults.MERGE_SUPPRESS]
        for f in fail:
            for sup in supp:
                assert f.t1_ms <= sup.t0_ms or sup.t1_ms <= f.t0_ms


# ----------------------------------------------------------------- plane
def test_plane_inert_until_armed():
    p = _plane("full", 1, 30.0, armed=False)
    for _ in range(200):
        assert p.decide_storage(write=True) is None
        assert p.decide_storage(write=False) is None
        assert not p.conn_drop()
        assert not p.merge_fault()
        assert not p.merges_suppressed()
        assert not p.encode_overflow()
        assert not p.compact_fault()
    assert p.snapshot() == {}


def test_plane_reads_never_uncertain():
    p = _plane("full", 1, 30.0, armed=True)
    # walk through the whole horizon; read decisions must never be
    # uncertain (a read cannot be "maybe applied")
    for ms in range(0, 30000, 37):
        p._t0 = time.monotonic() - ms / 1000.0
        d = p.decide_storage(write=False)
        assert d is None or d[0] in ("latency", "error")


# ------------------------------------------------- inertness (FAULTS=none)
def _drive(backend: Backend) -> list:
    """A fixed single-threaded op sequence; returns the full observable
    outcome stream (revisions, values, errors) for byte-comparison."""
    out = []
    for i in range(30):
        key = b"/inert/k-%02d" % (i % 7)
        try:
            out.append(("create", backend.create(key, b"v%d" % i)))
        except KeyExistsError as e:
            out.append(("exists", e.revision))
    kvs, _ = backend.scanner.range_(b"/inert/", b"/inert0",
                                    backend.current_revision())
    out.append([(kv.key, kv.value, kv.revision) for kv in kvs])
    for i in range(7):
        key = b"/inert/k-%02d" % i
        kv = backend.get(key)
        out.append(("get", kv.key, kv.value, kv.revision))
        out.append(("update", backend.update(key, b"u%d" % i, kv.revision)))
    for i in range(3):
        key = b"/inert/k-%02d" % i
        rev, prev = backend.delete(key)
        out.append(("delete", rev, prev.value))
        try:
            backend.get(key)
            out.append("alive")
        except KeyNotFoundError:
            out.append("gone")
    out.append(("final_rev", backend.current_revision()))
    return out


def test_faults_none_is_byte_identical():
    """The inertness contract: a 'none'-armed (and even an armed-but-
    windowless) fault layer produces the EXACT revision stream and
    response set a bare engine produces."""
    plain_store = new_storage("memkv")
    plain = Backend(plain_store, BackendConfig())
    faulty_store = FaultyStorage(new_storage("memkv"),
                                 _plane("none", 5, 30.0, armed=True))
    faulty = Backend(faulty_store, BackendConfig())
    try:
        assert _drive(plain) == _drive(faulty)
    finally:
        plain.close()
        plain_store.close()
        faulty.close()
        faulty_store.close()


# ----------------------------------------------- storage fault classes
def test_definite_error_nothing_applied_and_sequencer_advances():
    store = FaultyStorage(new_storage("memkv"),
                          _ScriptedPlane([("error", 0.0)]))
    b = Backend(store, BackendConfig())
    try:
        with pytest.raises(StorageError):
            b.create(b"/f/k1", b"v")
        # nothing applied: the key must be absent
        with pytest.raises(KeyNotFoundError):
            b.get(b"/f/k1")
        # the dealt revision was consumed (etcd revision gaps) and the
        # sequencer advanced past it — the NEXT write must succeed and
        # carry a higher revision
        rev = b.create(b"/f/k2", b"v2")
        assert rev >= 2
        assert b.get(b"/f/k2").revision == rev
    finally:
        b.close()
        store.close()


def test_uncertain_applied_resolves_via_retry_fifo():
    store = FaultyStorage(new_storage("memkv"),
                          _ScriptedPlane([("uncertain_applied", 0.0)]))
    b = Backend(store, BackendConfig())
    try:
        with pytest.raises(UncertainResultError):
            b.create(b"/u/k1", b"vv")
        # the op DID land (applied arm) but the client couldn't know
        assert b.get(b"/u/k1").value == b"vv"
        assert len(b.retry) == 1
        # compaction is fenced below the unresolved uncertain revision
        assert b.retry.min_revision() >= 1
        # read-back resolution: the record still holds the uncertain op's
        # revision, so the repair rewrites at a FRESH revision (emitting a
        # proper watch event)
        old_rev = b.get(b"/u/k1").revision
        resolved = b.retry.process_ready(now=time.monotonic() + 60.0)
        assert resolved == 1 and len(b.retry) == 0
        kv = b.get(b"/u/k1")
        assert kv.value == b"vv" and kv.revision > old_rev
    finally:
        b.close()
        store.close()


def test_uncertain_dropped_resolves_to_nothing():
    store = FaultyStorage(new_storage("memkv"),
                          _ScriptedPlane([("uncertain_dropped", 0.0)]))
    b = Backend(store, BackendConfig())
    try:
        with pytest.raises(UncertainResultError):
            b.create(b"/u/k2", b"vv")
        with pytest.raises(KeyNotFoundError):
            b.get(b"/u/k2")
        assert len(b.retry) == 1
        resolved = b.retry.process_ready(now=time.monotonic() + 60.0)
        assert resolved == 1
        # the op never landed: resolution drops it, nothing appears
        with pytest.raises(KeyNotFoundError):
            b.get(b"/u/k2")
    finally:
        b.close()
        store.close()


def test_group_commit_per_op_uncertainty_no_orphaned_riders():
    """One poisoned member of a commit group fails alone: its riders
    commit normally with contiguous revisions, the uncertain member's
    dealt revision is notified (sequencer never stalls), and the FIFO
    read-back resolves it."""
    script = [None, ("uncertain_applied", 0.0), ("error", 0.0), None]
    store = FaultyStorage(new_storage("memkv"), _ScriptedPlane(script))
    b = Backend(store, BackendConfig())
    try:
        ops = [("create", b"/g/k%d" % i, b"v%d" % i, None, 0)
               for i in range(4)]
        out = b.write_batch(ops)
        assert isinstance(out[0], int)
        assert isinstance(out[1], UncertainResultError)
        assert isinstance(out[2], StorageError)
        assert isinstance(out[3], int)
        # contiguous revision block in op order (gaps stay dealt)
        assert out[3] == out[0] + 3
        # riders committed; the definite-error member is absent; the
        # uncertain member actually landed (applied arm)
        assert b.get(b"/g/k0").revision == out[0]
        assert b.get(b"/g/k3").revision == out[3]
        with pytest.raises(KeyNotFoundError):
            b.get(b"/g/k2")
        assert b.get(b"/g/k1").value == b"v1"
        # and the FIFO repairs the uncertain member at a fresh revision
        assert len(b.retry) == 1
        assert b.retry.process_ready(now=time.monotonic() + 60.0) == 1
        assert b.get(b"/g/k1").revision > out[3]
        # the sequencer fully advanced (no orphaned revision wedges it)
        rev = b.create(b"/g/tail", b"t")
        assert rev > out[3]
    finally:
        b.close()
        store.close()


def test_injected_latency_delays_but_preserves_semantics():
    store = FaultyStorage(new_storage("memkv"),
                          _ScriptedPlane([("latency", 0.15)]))
    b = Backend(store, BackendConfig())
    try:
        t0 = time.monotonic()
        rev = b.create(b"/l/k", b"v")
        assert time.monotonic() - t0 >= 0.14
        assert b.get(b"/l/k").revision == rev
    finally:
        b.close()
        store.close()


# --------------------------------------- TPU mirror degradation machinery
def _tpu_backend(merge_threshold=64):
    # built by hand so a faulty layer could sit UNDER the mirror decorator
    from kubebrain_tpu.storage.tpu.engine import TpuKvStorage

    store = TpuKvStorage(new_storage("memkv"),
                         merge_threshold=merge_threshold)
    b = Backend(store, BackendConfig())
    return b, store


def _scan(b):
    kvs, _ = b.scanner.range_(b"/t/", b"/t0", b.current_revision())
    return [(kv.key, kv.value, kv.revision) for kv in kvs]


def test_quarantine_serves_host_store_then_recovers():
    b, store = _tpu_backend()
    try:
        for i in range(30):
            b.create(b"/t/k-%03d" % i, b"v%d" % i)
        before = _scan(b)  # publishes the mirror
        scanner = b.scanner
        assert scanner._mirror_state == "serving"
        # poison: reads must KEEP SERVING (host store, byte-identical)
        # while the background rebuild runs — no stop-the-world
        scanner.mark_uncertain()
        during = _scan(b)
        assert during == before
        b.create(b"/t/new", b"nv")  # writes keep flowing while degraded
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline \
                and scanner._mirror_state != "serving":
            time.sleep(0.02)
        assert scanner._mirror_state == "serving", "rebuild never completed"
        assert scanner.rebuild_bg_count >= 1
        assert scanner.degraded_seconds_total > 0.0
        after = _scan(b)
        assert (b"/t/new", b"nv", b.get(b"/t/new").revision) in after
        assert [r for r in after if r[0] != b"/t/new"] == before
    finally:
        b.close()
        store.close()


def test_merge_failure_bounded_retry_then_escalation():
    """A persistently failing merge retries with backoff, then escalates
    to ONE full rebuild from the store — the delta never grows forever,
    and readers stay byte-identical throughout (satellite regression)."""
    b, store = _tpu_backend(merge_threshold=16)
    try:
        scanner = b.scanner

        class _AlwaysFail:
            def merge_fault(self):
                return True

            def merge_fail_active(self):
                return True

            def merges_suppressed(self):
                return False

            def encode_overflow(self):
                return False

        for i in range(10):
            b.create(b"/t/a-%03d" % i, b"v%d" % i)
        baseline = _scan(b)  # publish a healthy mirror
        scanner.set_fault_plane(_AlwaysFail())
        # cross the merge threshold: the write-kicked merge now fails
        for i in range(40):
            b.create(b"/t/b-%03d" % i, b"w%d" % i)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline \
                and scanner.merge_escalations_total == 0:
            time.sleep(0.02)
        assert scanner.merge_bg_errors > 0
        assert scanner.merge_retries_total >= 1, "no bounded retries"
        assert scanner.merge_escalations_total >= 1, "never escalated"
        # escalation rebuilt from the store: delta absorbed, reads exact
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline \
                and scanner._mirror_state != "serving":
            time.sleep(0.02)
        got = _scan(b)
        assert len(got) == 50
        assert [r for r in got if r[0].startswith(b"/t/a-")] == baseline
        # accounting is scrape-visible
        assert scanner._merge_bg_last_error is not None
    finally:
        b.close()
        store.close()


def test_reader_byte_identity_during_merge_failures():
    """Reads during the whole fail->retry->escalate->recover arc must be
    byte-identical to the authoritative store (no serving gap)."""
    b, store = _tpu_backend(merge_threshold=16)
    try:
        scanner = b.scanner
        fail = [True]

        class _Plane:
            def merge_fault(self):
                return fail[0]

            def merge_fail_active(self):
                return fail[0]

            def merges_suppressed(self):
                return False

            def encode_overflow(self):
                return False

        for i in range(8):
            b.create(b"/t/k-%03d" % i, b"v%d" % i)
        _scan(b)
        scanner.set_fault_plane(_Plane())
        stop = threading.Event()
        diffs = []

        def reader():
            from kubebrain_tpu.backend.scanner import Scanner

            while not stop.is_set():
                # one pinned snapshot revision for BOTH paths: the served
                # scan and the host-store oracle must agree byte-for-byte
                rev = b.current_revision()
                got, _ = b.scanner.range_(b"/t/", b"/t0", rev)
                want, _ = Scanner.range_(b.scanner, b"/t/", b"/t0", rev)
                got = [(kv.key, kv.value, kv.revision) for kv in got]
                want = [(kv.key, kv.value, kv.revision) for kv in want]
                if got != want:
                    diffs.append((rev, got, want))
                    return
                time.sleep(0.005)

        t = threading.Thread(target=reader, daemon=True)
        t.start()
        for i in range(60):
            b.create(b"/t/m-%03d" % i, b"x%d" % i)
            time.sleep(0.002)
        fail[0] = False  # window closes; recovery completes
        time.sleep(0.5)
        stop.set()
        t.join(timeout=10)
        assert not diffs, f"reader diverged from the host oracle: {diffs[:1]}"
    finally:
        b.close()
        store.close()


def test_forced_encode_overflow_takes_full_rebuild_path():
    b, store = _tpu_backend(merge_threshold=16)
    try:
        scanner = b.scanner
        once = [True]

        class _Plane:
            def merge_fault(self):
                return False

            def merge_fail_active(self):
                return False

            def merges_suppressed(self):
                return False

            def encode_overflow(self):
                if once[0]:
                    once[0] = False
                    return True
                return False

        for i in range(8):
            b.create(b"/t/k-%03d" % i, b"v%d" % i)
        before = _scan(b)
        scanner.set_fault_plane(_Plane())
        for i in range(40):
            b.create(b"/t/o-%03d" % i, b"y%d" % i)
        scanner.publish()  # forces the pending merge through
        assert scanner.full_rebuild_total >= 1, \
            "forced overflow never took the re-dictionary rebuild"
        got = _scan(b)
        assert [r for r in got if r[0].startswith(b"/t/k-")] == before
        assert len(got) == 48
    finally:
        b.close()
        store.close()


def test_merge_suppression_grows_delta_and_reads_stay_exact():
    b, store = _tpu_backend(merge_threshold=16)
    try:
        scanner = b.scanner

        class _Plane:
            suppressed = 0

            def merge_fault(self):
                return False

            def merge_fail_active(self):
                return False

            def merges_suppressed(self):
                return True

            def note_suppressed_merge(self):
                _Plane.suppressed += 1

            def encode_overflow(self):
                return False

        for i in range(8):
            b.create(b"/t/k-%03d" % i, b"v%d" % i)
        _scan(b)
        scanner.set_fault_plane(_Plane())
        for i in range(50):
            b.create(b"/t/s-%03d" % i, b"z%d" % i)
        assert _Plane.suppressed > 0, "suppression never observed"
        # merges were suppressed: the delta grew past the threshold
        assert len(scanner._delta) >= 50
        # ... and overlay reads are still exact
        got = _scan(b)
        assert len(got) == 58
        assert all(r[1] == b"z%d" % i for i, r in enumerate(
            r for r in got if r[0].startswith(b"/t/s-")))
    finally:
        b.close()
        store.close()


# ------------------------------------------------------- end-to-end chaos
def test_chaos_smoke_end_to_end():
    """The CI chaos gate (FAULTS=smoke): a small replay under an armed
    fault schedule must reconcile every scheduled kind, prove the
    acknowledged-write consistency invariant, and re-derive the identical
    fault-trace sha (determinism)."""
    from kubebrain_tpu.workload.runner import run_workload
    from kubebrain_tpu.workload.spec import WorkloadSpec

    spec = WorkloadSpec.for_chaos(
        12, preset="smoke", fault_seed=3, seed=1,
        duration_s=10.0, time_scale=2.0,
        write_shards=4, range_shards=4, watch_streams=2, lease_streams=2)
    report = run_workload(spec, write_report=False)
    f = report["faults"]
    assert f["armed"] and f["determinism_checked"]
    assert f["schedule"]["sha256"] == faults.generate(
        "smoke", 3, spec.duration_s / spec.time_scale).sha256()
    cons = f["consistency"]
    assert cons["ok"], (cons["losses"], cons["ghosts"],
                        cons["rev_mismatches"])
    assert cons["checked_keys"] > 0 and cons["acked_live"] > 0
    # storage faults must actually have fired (memkv run: engine kinds
    # are reconciled as ineligible)
    assert f["injected"].get("storage_error", 0) > 0
    assert f["injected"].get("storage_uncertain", 0) > 0
    assert all(r["ok"] for r in f["reconcile"].values()), f["reconcile"]
    assert report["reconcile"]["ok"], report["reconcile"]["checks"]
    assert report["slo"]["pass"], report["slo"]["violations"]


def test_classify_rpc_error_three_way():
    """The safe / definite / ambiguous split (docs/faults.md): writes are
    retried only on provably-not-applied-and-maybe-transient failures."""
    import grpc

    from kubebrain_tpu.client import classify_rpc_error

    class _Err(grpc.RpcError):
        def __init__(self, code, details=""):
            self._code, self._details = code, details

        def code(self):
            return self._code

        def details(self):
            return self._details

    C = grpc.StatusCode
    # transient refusals: retry may succeed
    assert classify_rpc_error(_Err(C.RESOURCE_EXHAUSTED), True) == "safe"
    assert classify_rpc_error(
        _Err(C.UNAVAILABLE, "etcdserver: revision drift, retry txn"),
        True) == "safe"
    # deterministic refusals: not applied, retrying identical is pointless
    assert classify_rpc_error(_Err(C.NOT_FOUND, "lease"), True) == "definite"
    assert classify_rpc_error(_Err(C.OUT_OF_RANGE), True) == "definite"
    assert classify_rpc_error(_Err(C.UNIMPLEMENTED), False) == "definite"
    # maybe applied: never blind-retry a write
    for code, details in ((C.DEADLINE_EXCEEDED, "etcdserver: request timed out"),
                          (C.CANCELLED, ""), (C.UNKNOWN, ""),
                          (C.UNAVAILABLE, "connection dropped (fault injection)")):
        assert classify_rpc_error(_Err(code, details), True) == "ambiguous"
        # ...but reads are idempotent: the same failures retry safely
        assert classify_rpc_error(_Err(code, details), False) == "safe"
