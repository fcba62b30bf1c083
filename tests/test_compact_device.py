"""Device-side compaction: stored-domain survivor merge off the engine lock
(docs/compaction.md).

The pipeline under test: device victim marking → shard-local adaptive
victim/survivor index pull → victim-ONLY host decode driving the engine GC
→ stored-domain survivor gather k-way-merged with any pending delta →
dirty-shard-only republish, with ``_mlock`` held only for snapshot + swap
and the delta merge's retry/backoff → quarantine+rebuild escalation on
failure. Semantics must equal the engine-generic host compactor's; the
steady path must never decode a survivor, re-encode a key, or take a full
rebuild.

Runs on the 8-device virtual CPU mesh (conftest.py).
"""

import threading
import time
from unittest import mock

import numpy as np
import pytest

from kubebrain_tpu import coder
from kubebrain_tpu.backend import Backend, BackendConfig, wait_for_revision
from kubebrain_tpu.storage import new_storage
from kubebrain_tpu.storage.errors import KeyNotFoundError


@pytest.fixture
def tb():
    store = new_storage("tpu", inner="memkv")
    b = Backend(store, BackendConfig(event_ring_capacity=8192,
                                     watch_cache_capacity=4096))
    b.scanner._host_limit_threshold = 0
    b.scanner._merge_threshold = 64
    yield b
    b.close()
    store.close()


def _churn(b, n_keys=120, prefix=b"/registry/pods/"):
    """A realistic victim mix: superseded chains, tombstoned keys (full
    chains doomed + rev-record GC), and clean singletons. Returns the live
    key->revision map and the last dealt revision."""
    live = {}
    last = 0
    for i in range(n_keys):
        k = prefix + b"p%04d" % i
        r = b.create(k, b"v0")
        if i % 3 == 0:  # superseded chain
            for j in range(3):
                r = b.update(k, b"v%d" % (j + 1), r)
            live[k] = r
        elif i % 3 == 1:  # tombstoned: whole chain compacts away
            r, _ = b.delete(k, r)
        else:  # clean singleton survivor
            live[k] = r
        last = max(last, r)
    assert wait_for_revision(b, last)
    return live, last


def test_compact_steady_path_stays_stored_domain(tb):
    """The acceptance shape: a steady-state compaction performs ZERO full
    rebuilds and ZERO re-dictionary encodes — the published KeyEncoding
    object survives compaction by identity, full_rebuild_total stays flat,
    and the stats report the stored-incremental mirror path."""
    live, last = _churn(tb)
    sc = tb.scanner
    sc.publish()
    enc_before = sc._mirror.encoding
    assert enc_before is not None  # the encoded-mirror default
    rebuilds_before = sc.full_rebuild_total

    done = tb.compact(last)
    assert done == last

    assert sc.full_rebuild_total == rebuilds_before
    assert sc._mirror.encoding is enc_before, \
        "steady-state compact must not re-dictionary"
    assert sc.compact_count == 1
    assert sc.compact_victims_total > 0
    st = sc.encoding_stats()
    assert st["compact_count"] == 1 and st["full_rebuild_total"] == rebuilds_before

    # semantics: the mirror serves exactly the live set, values intact
    res = tb.list_(b"/registry/", b"/registry0")
    assert {kv.key: kv.revision for kv in res.kvs} == live
    cnt, _ = tb.count(b"/registry/", b"/registry0")
    assert cnt == len(live)


@pytest.mark.parametrize("rung", ["stored", "full"])
def test_compact_differential_vs_generic_engine(rung):
    """After the same op sequence + compaction on the generic engine and
    the device path, the post-compact STORE contents are byte-identical
    and every read agrees — on the steady stored-domain rung, and on the
    decode-everything full-rebuild rung, reached the way serving reaches
    it: a write whose key the published dictionary cannot express lands in
    the delta while the pass is marking victims."""
    from unittest import mock

    g_store = new_storage("memkv")
    g = Backend(g_store, BackendConfig(event_ring_capacity=8192))
    t_store = new_storage("tpu", inner="memkv")
    t = Backend(t_store, BackendConfig(event_ring_capacity=8192))
    sc = t.scanner
    sc._host_limit_threshold = 0
    sc._merge_threshold = 32

    for be in (g, t):
        _, last = _churn(be, n_keys=90)
    if rung == "stored":
        assert g.compact(last) == last
        assert t.compact(last) == last
    else:
        sc.publish()
        sc._merge_threshold = 10 ** 9  # the late row stays in the delta
        long_key = b"/registry/pods/" + b"x" * (
            sc._mirror.encoding.suffix_width + 40)
        pull = sc._pull_victim_indices

        def write_then_pull(*args):
            t.create(long_key, b"late")
            return pull(*args)

        g.create(long_key, b"late")
        assert g.compact(last) == last
        with mock.patch.object(sc, "_pull_victim_indices", write_then_pull):
            assert t.compact(last) == last

    def dump(store):
        lo, hi = coder.internal_range(b"", b"")
        return list(store.iter(lo, hi))

    g_rows = dump(g_store)
    t_rows = dump(t_store._inner)
    assert g_rows == t_rows, "post-compact store contents diverged"

    gl = [(kv.key, kv.value, kv.revision)
          for kv in g.list_(b"/registry/", b"/registry0").kvs]
    tl = [(kv.key, kv.value, kv.revision)
          for kv in t.list_(b"/registry/", b"/registry0").kvs]
    assert gl == tl
    assert sc.full_rebuild_total == (1 if rung == "full" else 0)
    for be, st in ((g, g_store), (t, t_store)):
        be.close()
        st.close()


def test_compact_bulk_and_per_key_gc_agree():
    """memkv now implements the native engine's ``bulk_gc`` contract; the
    device compactor auto-selects it. The bulk path and the per-key
    fallback (engines without bulk_gc) must leave byte-identical store
    state and identical stats."""
    from unittest import mock

    from kubebrain_tpu.storage.memkv import MemKv

    dumps, stats_pairs = [], []
    for hide_bulk in (False, True):
        store = new_storage("tpu", inner="memkv")
        b = Backend(store, BackendConfig(event_ring_capacity=8192))
        b.scanner._host_limit_threshold = 0
        live, last = _churn(b, n_keys=60)
        if hide_bulk:
            # hasattr-driven selection: no bulk_gc attribute -> per-key path
            with mock.patch.object(MemKv, "bulk_gc", None):
                assert not callable(getattr(store._inner, "bulk_gc", None))
                stats = b.scanner.compact(*_borders(b), last)
        else:
            stats = b.scanner.compact(*_borders(b), last)
        lo, hi = coder.internal_range(b"", b"")
        dumps.append(list(store._inner.iter(lo, hi)))
        stats_pairs.append((stats.deleted_versions, stats.deleted_tombstones,
                            stats.deleted_rev_records, stats.expired_ttl))
        b.close()
        store.close()
    assert dumps[0] == dumps[1], "bulk vs per-key GC store state diverged"
    assert stats_pairs[0] == stats_pairs[1]


def test_compact_victim_only_decode(tb):
    """Decode volume is confined to victim rows: every decoded_keys call
    during compact() materializes a subset of that partition's victims —
    never a whole partition (the pre-PR-12 host tax, now also statically
    flagged by kblint KB116)."""
    from unittest import mock

    from kubebrain_tpu.storage.tpu.blocks import Mirror

    live, last = _churn(tb)
    sc = tb.scanner
    sc.publish()
    mirror = sc._mirror

    victims_by_part = {}
    orig_pull = type(sc)._pull_victim_indices

    def pull_spy(self, mask_dev, m):
        out = orig_pull(self, mask_dev, m)
        victims_by_part.update(out)
        return out

    decoded = []
    orig_decode = Mirror.decoded_keys

    def decode_spy(self, p, rows):
        decoded.append((p, np.asarray(rows)))
        return orig_decode(self, p, rows)

    with mock.patch.object(type(sc), "_pull_victim_indices", pull_spy), \
            mock.patch.object(Mirror, "decoded_keys", decode_spy):
        tb.compact(last)

    assert decoded, "compact must decode its victims"
    n_victims = sum(len(v) for v in victims_by_part.values())
    n_decoded = sum(len(rows) for _p, rows in decoded)
    assert n_decoded == n_victims, (n_decoded, n_victims)
    for p, rows in decoded:
        assert set(rows.tolist()) <= set(
            np.asarray(victims_by_part.get(p, [])).tolist()), \
            f"partition {p} decoded non-victim rows"
    # total decode is a strict subset of the mirror: survivors never decode
    assert n_decoded < mirror.rows


def test_compact_dirty_shard_only_republish():
    """Partitions without victims must keep their device buffers — the
    compaction republish is dirty-shard-only, exactly like the delta
    merge's (PR 7/10 machinery, reused)."""
    store = new_storage("tpu", inner="memkv")
    b = Backend(store, BackendConfig(event_ring_capacity=16384,
                                     watch_cache_capacity=1024))
    sc = b.scanner
    sc._host_limit_threshold = 0
    sc._merge_threshold = 10 ** 9  # manual publish only
    # wide keyspace: singletons everywhere...
    last = 0
    for i in range(400):
        last = b.create(b"/registry/ds/k%04d" % i, b"v")
    # ...with version churn confined to the LAST partition's key range
    r = b.create(b"/registry/ds/zzz", b"v0")
    for j in range(6):
        r = b.update(b"/registry/ds/zzz", b"v%d" % (j + 1), r)
    last = max(last, r)
    assert wait_for_revision(b, last)
    sc.publish()
    m0 = sc._mirror
    P = m0.partitions
    assert P >= 2

    def shard_ptrs(mirror):
        return [s.data.unsafe_buffer_pointer()
                for s in mirror.keys_dev.addressable_shards]

    ptrs0 = shard_ptrs(m0)
    assert b.compact(last) == last
    m1 = sc._mirror
    assert m1 is not m0
    ptrs1 = shard_ptrs(m1)
    changed = [p for p in range(len(ptrs0)) if ptrs1[p] != ptrs0[p]]
    assert changed, "the dirty shard must re-upload"
    assert len(changed) < len(ptrs0), (
        f"only dirty shards may re-upload; all {len(ptrs0)} changed")
    # correctness after the in-place shrink
    res = b.list_(b"/registry/ds/", b"/registry/ds0")
    assert len(res.kvs) == 401
    assert res.kvs[-1].key == b"/registry/ds/zzz"
    assert res.kvs[-1].value == b"v6"
    b.close()
    store.close()


def test_compact_merges_pending_delta(tb):
    """Rows sealed into the delta before the compact snapshot ride the
    stored-domain k-way merge into the compacted mirror — no re-encode, no
    full rebuild — and rows landing DURING the pass stay in the successor
    overlay."""
    live, last = _churn(tb, n_keys=60)
    sc = tb.scanner
    sc.publish()
    sc._merge_threshold = 10 ** 9  # keep fresh rows in the delta
    r1 = tb.create(b"/registry/pods/fresh-a", b"da")
    r2 = tb.create(b"/registry/pods/fresh-b", b"db")
    assert wait_for_revision(tb, r2)
    assert len(sc._delta) > 0

    assert tb.compact(last) == last
    assert sc.full_rebuild_total == 0
    # the delta rows merged (or re-overlaid) — reads see everything
    res = tb.list_(b"/registry/", b"/registry0")
    got = {kv.key: kv.revision for kv in res.kvs}
    want = dict(live)
    want[b"/registry/pods/fresh-a"] = r1
    want[b"/registry/pods/fresh-b"] = r2
    assert got == want


def test_compact_ttl_expiry_device_path(monkeypatch):
    """/events/ TTL expiry through the DEVICE compactor: the victim kernel's
    TTL verdict + victim-only decode must GC the whole events chain (object
    rows + rev record) exactly like the generic scanner."""
    from kubebrain_tpu.backend import scanner as scanner_mod

    store = new_storage("tpu", inner="memkv", ttl_supported=False)
    b = Backend(store, BackendConfig(event_ring_capacity=2048))
    b.scanner._host_limit_threshold = 0
    KE = b"/events/ev1"
    KN = b"/registry/pods/a"
    b.create(KE, b"event-payload")
    r2 = b.create(KN, b"pod")
    assert wait_for_revision(b, r2)

    assert b.compact(r2) == r2
    assert b.get(KE).value == b"event-payload"  # not expired yet

    hist = b.scanner.compact_history
    monkeypatch.setattr(scanner_mod, "EVENTS_TTL_SECONDS", 0.5)
    with hist._lock:
        hist._entries = [(rev, t - 3600) for rev, t in hist._entries]

    r3 = b.create(b"/registry/pods/b", b"x")
    assert wait_for_revision(b, r3)
    stats_rev = b.compact(r3)
    assert stats_rev == r3
    with pytest.raises(KeyNotFoundError):
        b.get(KE)
    inner = store._inner
    with pytest.raises(KeyNotFoundError):
        inner.get(coder.encode_revision_key(KE))
    assert b.get(KN).value == b"pod"
    assert b.scanner.full_rebuild_total == 0
    b.close()
    store.close()


class _CompactFailPlane:
    """Minimal fault-plane stub: fail the compaction's mirror half N times
    (rate-1.0 window stand-in); every other decision is inert."""

    def __init__(self, fail_times=10 ** 9):
        self.fail_times = fail_times
        self.rolls = 0

    def compact_fault(self):
        self.rolls += 1
        if self.fail_times > 0:
            self.fail_times -= 1
            return True
        return False

    def merge_fault(self):
        return False

    def merge_fail_active(self):
        return False

    def merges_suppressed(self):
        return False

    def note_suppressed_merge(self):
        pass

    def encode_overflow(self):
        return False


def test_compact_retry_then_recover(tb):
    """A transiently failing mirror half retries with backoff and lands the
    stored-domain merge on a later attempt — no escalation, no rebuild."""
    live, last = _churn(tb, n_keys=60)
    sc = tb.scanner
    sc.publish()
    plane = _CompactFailPlane(fail_times=2)
    sc.set_fault_plane(plane)
    stats = sc.compact(*_borders(tb), last)
    sc.set_fault_plane(None)
    assert stats.mirror_path == "stored_incremental"
    assert sc.compact_retries_total == 2
    assert sc.compact_escalations_total == 0
    assert sc.full_rebuild_total == 0
    res = tb.list_(b"/registry/", b"/registry0")
    assert {kv.key: kv.revision for kv in res.kvs} == live


def test_compact_escalates_to_quarantine_rebuild(tb):
    """Exhausting the bounded retries must ESCALATE: the mirror
    quarantines (readers divert to the authoritative host store —
    byte-identical), one background rebuild from the post-GC store
    recovers, and the engine deletes stay durable throughout."""
    live, last = _churn(tb, n_keys=60)
    sc = tb.scanner
    sc._merge_max_retries = 2  # keep the backoff ladder short
    sc.publish()
    plane = _CompactFailPlane()  # fails forever
    sc.set_fault_plane(plane)
    stats = sc.compact(*_borders(tb), last)
    sc.set_fault_plane(None)
    assert stats.mirror_path == "escalated"
    assert sc.compact_escalations_total == 1
    assert plane.rolls >= 2

    # degraded reads serve the host store and stay correct immediately
    res = tb.list_(b"/registry/", b"/registry0")
    assert {kv.key: kv.revision for kv in res.kvs} == live

    # the background rebuild recovers the mirror to serving
    deadline = time.time() + 10
    while time.time() < deadline and sc._mirror_state != "serving":
        time.sleep(0.05)
    assert sc._mirror_state == "serving"
    res = tb.list_(b"/registry/", b"/registry0")
    assert {kv.key: kv.revision for kv in res.kvs} == live
    assert sc.full_rebuild_total == 0  # the escalation rebuild is the
    # quarantine-recovery path (rebuild_bg_count), not a merge full rebuild
    assert sc.rebuild_bg_count >= 1


def test_compact_mirror_half_runs_off_engine_lock(tb):
    """Readers must keep serving mirror+overlay while the compaction's
    mirror half runs: park the stored-domain merge on an event and prove a
    concurrent list_ completes before the merge is released (deadlock-free
    by handshake, not by timing)."""
    from unittest import mock

    from kubebrain_tpu.storage.tpu import engine as eng

    live, last = _churn(tb, n_keys=60)
    sc = tb.scanner
    sc.publish()

    in_merge = threading.Event()
    release = threading.Event()
    orig = eng.compact_partitions_stored

    def slow(*args, **kw):
        in_merge.set()
        assert release.wait(timeout=30), "reader never released the merge"
        return orig(*args, **kw)

    result = {}

    def compactor():
        with mock.patch.object(eng, "compact_partitions_stored", slow):
            result["stats"] = sc.compact(*_borders(tb), last)

    th = threading.Thread(target=compactor)
    th.start()
    try:
        assert in_merge.wait(timeout=30), "compact never reached the merge"
        # the reader runs WHILE the mirror half is parked inside the merge
        res = tb.list_(b"/registry/", b"/registry0")
        assert {kv.key: kv.revision for kv in res.kvs} == live
    finally:
        release.set()
        th.join(timeout=30)
    assert not th.is_alive()
    assert result["stats"].mirror_path == "stored_incremental"


def test_concurrent_merge_cannot_supersede_compact(tb):
    """A write burst crossing the merge threshold DURING a compaction must
    not supersede it (the recurring quarantine-per-compact shape): the
    pass holds the merge lock end to end, threshold-crossing readers skip
    the opportunistic merge (overlay stays exact, nobody blocks), and the
    kicked background merge lands AFTER the compacted mirror swaps."""
    from unittest import mock

    from kubebrain_tpu.storage.tpu import engine as eng

    live, last = _churn(tb, n_keys=60)
    sc = tb.scanner
    sc.publish()
    sc._merge_threshold = 8  # a tiny burst crosses it

    in_merge = threading.Event()
    release = threading.Event()
    orig = eng.compact_partitions_stored

    def slow(*args, **kw):
        in_merge.set()
        assert release.wait(timeout=30)
        return orig(*args, **kw)

    result = {}

    def compactor():
        with mock.patch.object(eng, "compact_partitions_stored", slow):
            result["stats"] = sc.compact(*_borders(tb), last)

    th = threading.Thread(target=compactor)
    th.start()
    fresh = {}
    try:
        assert in_merge.wait(timeout=30)
        # the burst: crosses the threshold and write-kicks a merge whose
        # thread must park behind the compaction's merge-lock hold
        for i in range(12):
            k = b"/registry/pods/burst-%03d" % i
            fresh[k] = tb.create(k, b"fb")
        assert wait_for_revision(tb, max(fresh.values()))
        # a reader during the parked compaction must complete (the
        # threshold merge is SKIPPED, not waited on) and see everything
        res = tb.list_(b"/registry/", b"/registry0")
        assert {kv.key for kv in res.kvs} == set(live) | set(fresh)
    finally:
        release.set()
        th.join(timeout=30)
    assert not th.is_alive()
    assert result["stats"].mirror_path == "stored_incremental", \
        "a routine merge superseded the compaction"
    assert sc._mirror_state == "serving"
    assert sc.compact_escalations_total == 0
    # everything still correct once the parked background merge drains
    res = tb.list_(b"/registry/", b"/registry0")
    got = {kv.key: kv.revision for kv in res.kvs}
    assert got == {**live, **fresh}


class _CaptureMetrics:
    def __init__(self):
        self.hist = []
        self.counters = []

    def emit_histogram(self, name, value, **tags):
        self.hist.append((name, value, tags))

    def emit_counter(self, name, value=1, **tags):
        self.counters.append((name, value, tags))

    def register_gauge_fn(self, *a, **k):
        pass

    def emit_gauge(self, name, value, **tags):
        self.counters.append((name, value, tags))


def test_compact_phase_metrics_and_stats(tb):
    """kb_compact_seconds{phase=mark|gc|merge|publish} and
    kb_compact_victims_total{kind=} must move, and CompactStats must carry
    the mirror-path/phase accounting (the contract the bench report and
    docs/observability.md document)."""
    live, last = _churn(tb, n_keys=60)
    sc = tb.scanner
    sc.publish()
    m = _CaptureMetrics()
    sc._metrics = m
    stats = sc.compact(*_borders(tb), last)
    sc._metrics = None

    phases = {t["phase"] for n, _v, t in m.hist if n == "kb.compact.seconds"}
    assert phases == {"mark", "gc", "merge", "publish"}
    kinds = {t["kind"]: v for n, v, t in m.counters
             if n == "kb.compact.victims.total"}
    assert kinds.get("superseded", 0) > 0
    assert kinds.get("tombstone", 0) > 0
    assert kinds.get("rev_record", 0) > 0

    assert stats.mirror_path == "stored_incremental"
    assert stats.dirty_partitions >= 1
    assert stats.survivor_rows > 0
    assert set(stats.phase_seconds) == {"mark", "gc", "merge", "publish"}
    assert stats.deleted_versions == kinds["superseded"]
    assert stats.deleted_tombstones == kinds["tombstone"]


def _borders(b):
    """The backend's whole-keyspace compact borders (internal keys)."""
    lo, hi = coder.internal_range(b"", b"")
    return lo, hi


def _warmed(sc, timeout=120.0):
    """Wait for the compaction warm-up the first publish kicked."""
    deadline = time.monotonic() + timeout
    while sc.compact_warm_s is None:
        assert time.monotonic() < deadline, "the compaction warm-up never ran"
        time.sleep(0.05)
    return sc.compact_warm_s


def _compaction_cache_sizes():
    from kubebrain_tpu.storage.tpu import engine

    return {f: getattr(engine, f)._cache_size() for f in (
        "_victim_batch", "_victim_batch_pallas", "_victim_part_counts",
        "_part_indices_of_mask", "_survivor_mask")}


@pytest.mark.parametrize("kernel", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("shape", ["few_victims", "few_survivors", "dense"])
def test_compact_after_the_warm_compiles_nothing(kernel, shape, monkeypatch):
    """The first publish warms every function a Compact runs at the
    mirror's shape (the mark, the victim counts, the pull's index block
    over the victims and over the survivors) on a thread of its own: a
    Compact after it finds every one compiled, whichever pull it takes —
    the victims' indices, the survivors' indices or the byte mask."""
    from kubebrain_tpu.storage.tpu import engine

    from kubebrain_tpu.parallel.mesh import make_mesh

    monkeypatch.setenv("KB_PALLAS_INTERPRET", "1")
    # one partition, as on one chip: the per-partition counts decide the pull
    store = new_storage("tpu", inner="memkv", use_pallas=kernel != "jnp",
                        mesh=make_mesh(1))
    b = Backend(store, BackendConfig(event_ring_capacity=8192,
                                     watch_cache_capacity=4096))
    try:
        sc = b.scanner
        sc._host_limit_threshold = 0
        assert sc._scan_kernel == kernel
        if shape == "few_survivors":
            # 6 keys x 30 revisions: 174 victims, 6 survivors
            for i in range(6):
                r = b.create(b"/registry/pods/c%d" % i, b"v0")
                for j in range(29):
                    r = b.update(b"/registry/pods/c%d" % i, b"v%d" % j, r)
            last = r
            assert wait_for_revision(b, last)
        else:
            # dense: over N/8 victims AND survivors in the partition (the
            # Pallas layout pads the mask wider than the jnp one)
            n_keys = 90 if shape == "few_victims" else (
                150 if kernel == "jnp" else 1700)
            live, last = _churn(b, n_keys=n_keys)
        if shape == "few_victims":
            k = next(iter(live))
            last = b.update(k, b"again", live[k])
            assert wait_for_revision(b, last)
        sc.publish()
        assert _warmed(sc) > 0
        before = _compaction_cache_sizes()
        pulls = []
        orig = engine._host_pull
        with mock.patch.object(engine, "_host_pull",
                               lambda x: pulls.append(x.shape) or orig(x)):
            if shape == "few_victims":
                b.compact(last - 1)  # the chain below the one update: 1 victim
            b.compact(last)
        assert _compaction_cache_sizes() == before
        assert sc.compact_errors == 0
        # the pull each shape takes: [P, N/8] indices, or the [P, N] mask
        n = (sc._mirror.keys_host.shape[1] if kernel == "jnp"
             else sc._pallas_layout(sc._mirror)[0].shape[2])
        blocks = [s[-1] for s in pulls if len(s) == 2]
        assert blocks and set(blocks) == ({n} if shape == "dense" else {n // 8})
    finally:
        b.close()
        store.close()


def test_compact_warm_is_boots_compact_warm_phase(tb):
    """Its seconds are ``kb_boot_seconds{phase="compact_warm"}``, and a
    publish that keeps the mirror's shape starts no second warm-up."""
    m = _CaptureMetrics()
    sc = tb.scanner
    sc._metrics = m
    live, last = _churn(tb, n_keys=30)
    sc.publish()
    _warmed(sc)
    gauges = [(v, t) for n, v, t in m.counters if n == "kb.boot.seconds"]
    assert [t["phase"] for _v, t in gauges].count("compact_warm") == 1
    key = sc._warm_key
    k = next(iter(live))
    assert wait_for_revision(tb, tb.update(k, b"more", live[k]))
    sc.publish()
    time.sleep(0.2)
    assert sc._warm_key == key
    gauges = [t for n, _v, t in m.counters if n == "kb.boot.seconds"]
    assert [t["phase"] for t in gauges].count("compact_warm") == 1
    sc._metrics = None


def test_compact_phases_are_profiler_annotations(tb):
    """The pass's phases stand on the profiler's clock as the merge's do:
    ``kb.compact.mark|gc|merge|publish``, in that order."""
    from kubebrain_tpu.trace import TRACER

    live, last = _churn(tb, n_keys=30)
    sc = tb.scanner
    sc.publish()
    _warmed(sc)
    names = []

    class _Rec:
        def __init__(self, name):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    TRACER.set_annotator(_Rec)
    try:
        tb.compact(last)
    finally:
        TRACER.set_annotator(None)
    compact = [n for n in names if n.startswith("kb.compact.")]
    assert compact == ["kb.compact.mark", "kb.compact.gc",
                       "kb.compact.merge", "kb.compact.publish"]
