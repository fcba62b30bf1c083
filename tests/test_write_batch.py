"""Write-path group commit tests (docs/writes.md).

Grouped commits must be byte-identical to back-to-back sequential
commits BY CONSTRUCTION — same revisions, same per-op results, same
errors, same watch events in the same order. These tests pin that
construction:

- randomized grouped-vs-sequential differential (incl. concurrent
  readers on the grouped backend);
- per-op conflict demux inside one group (CAS mismatch / KeyExists /
  KeyNotFound fail ONLY their own op, and consume their dealt revision
  exactly like the sequential paths);
- same-key-in-group ordering (a group member validates against the
  state as mutated by earlier members of the SAME group);
- watch events strictly revision-ordered across group boundaries;
- scheduler group formation (plugged-slot deterministic) equals the
  sequential oracle byte for byte and per-client FIFO survives;
- the TPU mirror's incremental stored-domain delta merge equals the
  full host rebuild byte for byte (jnp + pallas-interpret, one and two
  partitions per device) with merge accounting proving no full rebuild
  ran in steady state;
- engines without ``write_batch`` fall back per-op with identical
  results.
"""

import threading
import time

import numpy as np
import pytest

from kubebrain_tpu.backend import (
    Backend,
    BackendConfig,
    CASRevisionMismatchError,
    FutureRevisionError,
    KeyExistsError,
)
from kubebrain_tpu.parallel.mesh import make_mesh
from kubebrain_tpu.storage import new_storage
from kubebrain_tpu.storage.errors import KeyNotFoundError
from kubebrain_tpu.storage.tpu.engine import TpuKvStorage


def mk_backend(store=None, ring=16384):
    store = store or new_storage("memkv")
    return store, Backend(store, BackendConfig(event_ring_capacity=ring,
                                               watch_cache_capacity=4096))


def fp_op_result(r):
    """One comparable fingerprint per op result (success value or error)."""
    if isinstance(r, BaseException):
        return (type(r).__name__, str(r))
    if isinstance(r, tuple):  # delete: (rev, KeyValue)
        rev, kv = r
        return ("del", rev, kv.key, kv.value, kv.revision)
    return ("rev", r)


def fp_state(b: Backend):
    res = b.list_(b"/registry/", b"/registry0", 0, 0)
    return ([(kv.key, kv.value, kv.revision) for kv in res.kvs],
            res.revision, b.current_revision())


def gen_ops(rng, n, keyspace=24):
    """A random create/update/delete stream with plausible conflicts:
    updates CAS against a tracked (sometimes stale) revision, creates
    sometimes target live keys, deletes sometimes guard a wrong rev."""
    live: dict[bytes, int] = {}
    next_rev = [0]
    ops = []
    for step in range(n):
        k = b"/registry/pods/ns-%d/p-%02d" % (step % 3, rng.randint(keyspace))
        roll = rng.rand()
        if k not in live or roll < 0.3:
            ops.append(("create", k, b"c%04d" % step, None, 0))
            kind = "create"
        elif roll < 0.75:
            exp = live[k] if rng.rand() < 0.8 else max(1, live[k] - 1)
            ops.append(("update", k, b"u%04d" % step, exp, None, 0))
            kind = "update" if exp == live[k] else "update-stale"
        else:
            droll = rng.rand()
            if droll < 0.5:
                exp = 0
            elif droll < 0.8:
                exp = live[k]
            else:
                exp = live[k] + 7  # stale guard: this delete MUST fail
            ops.append(("delete", k, exp))
            kind = "delete" if exp in (0, live[k]) else "delete-stale"
        # track what a successful sequential application would do (close
        # enough for conflict-shaping; exactness comes from the oracle)
        next_rev[0] += 1
        if kind == "create" and k not in live:
            live[k] = next_rev[0]
        elif kind == "update":
            live[k] = next_rev[0]
        elif kind == "delete" and (exp in (0, live.get(k))):
            live.pop(k, None)
    return ops


def test_grouped_vs_sequential_randomized_byte_identity():
    """Random op stream chopped into random-size groups on backend A vs
    the same stream sequentially on backend B: per-op results AND final
    state identical, while reader threads hammer A mid-commit."""
    rng = np.random.RandomState(7)
    ops = gen_ops(rng, 240)
    _, grouped = mk_backend()
    _, seq = mk_backend()

    stop = threading.Event()
    reader_errs: list = []

    def reader():
        while not stop.is_set():
            try:
                res = grouped.list_(b"/registry/", b"/registry0", 0, 0)
                keys = [kv.key for kv in res.kvs]
                assert keys == sorted(keys) and len(set(keys)) == len(keys)
            except Exception as e:  # pragma: no cover - surfaced below
                reader_errs.append(e)
                return

    readers = [threading.Thread(target=reader) for _ in range(3)]
    for t in readers:
        t.start()

    got, want = [], []
    i = 0
    try:
        while i < len(ops):
            g = int(rng.randint(1, 9))
            group = ops[i:i + g]
            got.extend(fp_op_result(r) for r in grouped.write_batch(group))
            for op in group:
                try:
                    want.append(fp_op_result(seq._apply_single(op)))
                except BaseException as e:
                    want.append(fp_op_result(e))
            i += g
    finally:
        stop.set()
        for t in readers:
            t.join(10)

    assert not reader_errs, reader_errs[0]
    assert got == want
    assert fp_state(grouped) == fp_state(seq)
    grouped.close()
    seq.close()


def test_per_op_conflict_demux_in_one_group():
    """One group holding every conflict kind: each failure is demuxed to
    its own op, later ops still land, and every dealt revision is
    consumed (etcd-style gaps) exactly like the sequential paths."""
    _, b = mk_backend()
    r1 = b.create(b"/registry/a", b"v1")       # rev 1
    r2 = b.update(b"/registry/a", b"v2", r1)   # rev 2: r1 is now truly stale
    base = b.current_revision()

    res = b.write_batch([
        ("create", b"/registry/ok", b"x", None, 0),        # ok      -> base+1
        ("create", b"/registry/a", b"dup", None, 0),       # exists  (base+2 consumed)
        ("update", b"/registry/a", b"y", r1, None, 0),     # CAS mism(base+3 consumed)
        ("delete", b"/registry/missing", 0),               # not found
        ("update", b"/registry/a", b"z", r2, None, 0),     # ok      -> base+5
        ("delete", b"/registry/ok", 0),                    # ok      -> base+6
    ])
    assert res[0] == base + 1
    assert isinstance(res[1], KeyExistsError) and res[1].revision == r2
    assert isinstance(res[2], CASRevisionMismatchError)
    assert res[2].revision == r2 and res[2].value == b"v2"
    assert isinstance(res[3], KeyNotFoundError)
    assert res[4] == base + 5
    rev, kv = res[5]
    assert rev == base + 6 and kv.value == b"x" and kv.revision == base + 1
    # failed ops consumed their revisions: the clock advanced by the
    # full group size and the sequencer is fully drained
    assert b.current_revision() == base + 6
    got = b.list_(b"/registry/", b"/registry0", 0, 0)
    assert [(kv.key, kv.value) for kv in got.kvs] == [(b"/registry/a", b"z")]
    b.close()


def test_failed_delete_consumes_revision_grouped_and_sequential():
    """A stale-guard delete consumes its dealt revision on BOTH paths —
    grouped (block dealt up front) and sequential (memkv's mvcc_delete
    routes deletes through _delete_fast) — so the revision a later op
    lands on cannot depend on whether it happened to ride a group.
    Regression: memkv's slow-path delete used to pre-validate without
    dealing, so sequential skipped the revision a group consumed."""
    _, grouped = mk_backend()
    _, seq = mk_backend()
    for b in (grouped, seq):
        b.create(b"/registry/a", b"v1")  # rev 1

    ops = [("delete", b"/registry/a", 999),          # stale guard: fails
           ("create", b"/registry/b", b"v2", None, 0)]
    got = [fp_op_result(r) for r in grouped.write_batch(ops)]
    want = []
    for op in ops:
        try:
            want.append(fp_op_result(seq._apply_single(op)))
        except BaseException as e:
            want.append(fp_op_result(e))

    assert got == want
    assert got[0][0] == "CASRevisionMismatchError"
    # the failed delete consumed rev 2 on both: /registry/b landed on 3
    assert got[1] == ("rev", 3)
    assert fp_state(grouped) == fp_state(seq)
    assert seq.current_revision() == 3
    grouped.close()
    seq.close()


def test_same_key_in_group_ordering():
    """Same-key ops inside ONE group behave as back-to-back sequential
    commits: each validates against the state as mutated by earlier
    members (create -> update-over-that-create -> delete-over-that)."""
    _, b = mk_backend()
    base = b.current_revision()
    res = b.write_batch([
        ("create", b"/registry/k", b"v0", None, 0),
        ("update", b"/registry/k", b"v1", base + 1, None, 0),
        ("update", b"/registry/k", b"v2", base + 2, None, 0),
        ("update", b"/registry/k", b"stale", base + 1, None, 0),  # loses
        ("delete", b"/registry/k", base + 3),
        ("create", b"/registry/k", b"reborn", None, 0),  # over the tombstone
    ])
    assert res[0] == base + 1
    assert res[1] == base + 2
    assert res[2] == base + 3
    assert isinstance(res[3], CASRevisionMismatchError)
    assert res[3].revision == base + 3
    rev, kv = res[4]
    assert rev == base + 5 and kv.value == b"v2" and kv.revision == base + 3
    assert res[5] == base + 6
    got = b.list_(b"/registry/", b"/registry0", 0, 0)
    assert [(kv.key, kv.value, kv.revision) for kv in got.kvs] == [
        (b"/registry/k", b"reborn", base + 6)]
    b.close()


def test_watch_events_strictly_ordered_across_groups():
    """Watch events stay strictly revision-ordered across group
    boundaries, with failed group members invisible (their dealt
    revisions are notified invalid, never streamed)."""
    _, b = mk_backend()
    wid, q = b.watch(b"/registry/")
    try:
        b.write_batch([
            ("create", b"/registry/w/a", b"1", None, 0),
            ("create", b"/registry/w/b", b"2", None, 0),
            ("create", b"/registry/w/a", b"dup", None, 0),  # fails, rev consumed
        ])
        b.create(b"/registry/w/c", b"3")  # sequential between groups
        b.write_batch([
            ("update", b"/registry/w/a", b"4", 1, None, 0),
            ("delete", b"/registry/w/b", 0),
        ])
        events = []
        deadline = time.time() + 10
        while len(events) < 5 and time.time() < deadline:
            batch = q.get(timeout=5)
            assert batch is not None
            events.extend(batch)
        revs = [e.revision for e in events]
        assert revs == sorted(revs) and len(set(revs)) == len(revs)
        assert [(e.key, e.verb.name, e.revision) for e in events] == [
            (b"/registry/w/a", "CREATE", 1),
            (b"/registry/w/b", "CREATE", 2),
            (b"/registry/w/c", "CREATE", 4),
            (b"/registry/w/a", "PUT", 5),
            (b"/registry/w/b", "DELETE", 6),
        ]
    finally:
        b.unwatch(wid)
        b.close()


def test_scheduler_group_formation_byte_identity():
    """Plug a depth-1 scheduler's slot, queue 8 writes, release: they
    must ride ONE commit group (write_batched > 0, one batch-size
    histogram sample) and equal the sequential oracle byte for byte."""
    from kubebrain_tpu.sched import Lane, SchedConfig, ensure_scheduler

    _, b = mk_backend()
    _, oracle = mk_backend()
    sched = ensure_scheduler(b, SchedConfig(depth=1, write_batch=8))
    assert sched.config.write_batch == 8

    release = threading.Event()
    sched.submit_async(release.wait, Lane.SYSTEM)
    time.sleep(0.1)

    keys = [b"/registry/pods/g/p-%d" % i for i in range(8)]
    outs: dict = {}

    def one(i):
        # distinct clients: queue arrival order == submission index order
        # is NOT guaranteed across clients, so ops commute (disjoint keys)
        outs[i] = sched.create(keys[i], b"val-%d" % i, client="c%d" % i)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    time.sleep(0.3)
    release.set()
    for t in threads:
        t.join(30)
    assert sched.write_batched > 0, "plugged slot formed no write group"
    assert sorted(outs) == list(range(8))

    for i in range(8):
        oracle.create(keys[i], b"val-%d" % i)
    # disjoint keys: the final value set matches; revisions are a
    # contiguous block in both worlds
    got = sorted((kv.key, kv.value) for kv in
                 b.list_(b"/registry/pods/g/", b"/registry/pods/g0", 0, 0).kvs)
    want = sorted((kv.key, kv.value) for kv in
                  oracle.list_(b"/registry/pods/g/",
                               b"/registry/pods/g0", 0, 0).kvs)
    assert got == want
    assert sorted(outs.values()) == list(
        range(min(outs.values()), min(outs.values()) + 8))
    b.close()
    oracle.close()


def test_scheduler_per_client_fifo_within_groups():
    """Same-client writes keep submission order even when drained into
    groups: a client's create->update->update chain on one key must land
    in order (each CAS sees its predecessor), across many clients."""
    from kubebrain_tpu.sched import SchedConfig, ensure_scheduler

    _, b = mk_backend()
    sched = ensure_scheduler(b, SchedConfig(depth=2, write_batch=8))
    errs: list = []

    def client(ci):
        try:
            k = b"/registry/fifo/c-%d" % ci
            rev = sched.create(k, b"v0", client=f"c{ci}")
            for step in range(6):
                rev = sched.update(k, b"v%d" % (step + 1), rev,
                                   client=f"c{ci}")
        except BaseException as e:  # pragma: no cover - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errs, errs[0]
    res = b.list_(b"/registry/fifo/", b"/registry/fifo0", 0, 0)
    assert len(res.kvs) == 8
    assert all(kv.value == b"v6" for kv in res.kvs)
    b.close()


class _NoBatchStore:
    """Engine shim hiding ``write_batch``: forces the per-op fallback."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        if name == "write_batch":
            raise AttributeError(name)
        return getattr(self._inner, name)


def test_engine_without_write_batch_falls_back_per_op():
    rng = np.random.RandomState(3)
    ops = gen_ops(rng, 80)
    _, plain = mk_backend(store=_NoBatchStore(new_storage("memkv")))
    _, seq = mk_backend()
    assert plain._engine_write_batch is None
    got = [fp_op_result(r) for r in plain.write_batch(list(ops))]
    want = []
    for op in ops:
        try:
            want.append(fp_op_result(seq._apply_single(op)))
        except BaseException as e:
            want.append(fp_op_result(e))
    assert got == want
    assert fp_state(plain) == fp_state(seq)
    plain.close()
    seq.close()


def test_demux_failure_cannot_strand_the_revision_block():
    """A transient engine error while demuxing one op's outcome (here:
    reading a CAS conflict's latest value) fails ONLY that op — the
    block's events still reach the ring and the sequencer advances, so
    later writes proceed. Regression: a demux exception escaped
    Backend.write_batch before _notify_many, stranding the dealt block
    and stalling every subsequent write behind the sequencer."""
    from kubebrain_tpu.storage.errors import StorageError

    _, b = mk_backend()
    r1 = b.create(b"/registry/a", b"v1")
    r2 = b.update(b"/registry/a", b"v2", r1)  # r1 is now truly stale

    def flaky_read(key, rev):
        raise StorageError("transient wire error")

    orig, b._read_object = b._read_object, flaky_read
    try:
        res = b.write_batch([
            ("update", b"/registry/a", b"x", r1, None, 0),  # CAS conflict
            ("create", b"/registry/b", b"v2", None, 0),
        ])
    finally:
        b._read_object = orig
    assert isinstance(res[0], StorageError)
    assert res[1] == r2 + 2  # the conflict consumed r2+1, create landed after
    # the sequencer advanced past the whole block: a later write completes
    assert b.create(b"/registry/c", b"v3") == r2 + 3
    b.close()


def test_tso_deal_block_contiguous_under_race():
    from kubebrain_tpu.backend.tso import TSO

    tso = TSO()
    blocks: list = []
    lock = threading.Lock()

    def dealer():
        for _ in range(50):
            first = tso.deal_block(3)
            with lock:
                blocks.append((first, 3))
            tso.commit(first + 2)

    threads = [threading.Thread(target=dealer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    spans = sorted(blocks)
    covered = []
    for first, n in spans:
        covered.extend(range(first, first + n))
    assert covered == list(range(1, 601)), "blocks must tile with no overlap"
    with pytest.raises(ValueError):
        tso.deal_block(0)


# ---------------------------------------------------------------- TPU merge
def mk_tpu_backend(ndev, partitions=0, kernel="jnp", merge_threshold=64):
    mesh = make_mesh(n_devices=ndev)
    store = TpuKvStorage(new_storage("memkv"), mesh=mesh,
                         partitions=partitions)
    b = Backend(store, BackendConfig(event_ring_capacity=16384))
    b.scanner._host_limit_threshold = 0  # always the device path
    b.scanner._merge_threshold = merge_threshold
    b.scanner._scan_kernel = kernel
    b.scanner._kernel_mesh = mesh if kernel != "jnp" else None
    return b


def churn(b: Backend, rng, steps, keyspace=60, live=None):
    live = {} if live is None else live
    for step in range(steps):
        k = b"/registry/pods/ns-%d/p-%03d" % (step % 4, rng.randint(keyspace))
        if k not in live:
            live[k] = b.create(k, b"v%04d" % step)
        elif rng.rand() < 0.6:
            live[k] = b.update(k, b"u%04d" % step, live[k])
        else:
            b.delete(k, live.pop(k))


@pytest.mark.parametrize("kernel", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("ndev,parts", [(8, 0), (4, 8)])
def test_incremental_merge_vs_full_rebuild_identity(kernel, ndev, parts):
    """Churn through a low merge threshold (many incremental stored-
    domain merges) vs a twin whose every publish is a full store rebuild:
    reads must agree byte for byte at head AND at snapshots, and the
    incremental engine's accounting must show NO full rebuild — every
    delta row accounted by merge_rows_total."""
    inc = mk_tpu_backend(ndev, partitions=parts, kernel=kernel,
                         merge_threshold=32)
    full = mk_tpu_backend(ndev, partitions=parts, kernel=kernel,
                          merge_threshold=10**9)  # delta overlay stays live
    try:
        rng = np.random.RandomState(19)
        live: dict[bytes, int] = {}
        checkpoints: list[int] = []
        for i in range(40):  # seed, then publish: merges need a mirror
            k = b"/registry/pods/ns-%d/p-%03d" % (i % 4, i)
            for be in (inc, full):
                r = be.create(k, b"seed")
            live[k] = r
        inc.scanner.publish()
        full.scanner.publish()
        for step in range(300):
            k = b"/registry/pods/ns-%d/p-%03d" % (step % 4, rng.randint(60))
            if k not in live:
                for be in (inc, full):
                    r = be.create(k, b"v%04d" % step)
                live[k] = r
            elif rng.rand() < 0.6:
                for be in (inc, full):
                    r = be.update(k, b"u%04d" % step, live[k])
                live[k] = r
            else:
                for be in (inc, full):
                    be.delete(k, live[k])
                live.pop(k)
            if step % 10 == 3:
                # reads cross the merge threshold naturally on `inc`; the
                # twin keeps everything in its live overlay
                inc.count(b"/registry/pods/", b"/registry/pods0")
            if step % 60 == 30:
                checkpoints.append(inc.current_revision())
        inc.scanner.publish()
        full.scanner._force_rebuild = True  # twin: one full store rebuild
        full.scanner.publish()

        sc = inc.scanner
        assert sc.merge_count > 0, "threshold crossings must have merged"
        assert sc.full_rebuild_total == 0, \
            "steady-state churn must never take the full-rebuild path"
        assert sc.merge_rows_total > 0

        for ns in range(4):
            s = b"/registry/pods/ns-%d/" % ns
            e = b"/registry/pods/ns-%d0" % ns
            for rev in [0, *checkpoints]:
                a = inc.list_(s, e, rev, 0)
                bres = full.list_(s, e, rev, 0)
                assert [(kv.key, kv.value, kv.revision) for kv in a.kvs] == \
                    [(kv.key, kv.value, kv.revision) for kv in bres.kvs], \
                    (kernel, ndev, parts, ns, rev)
                assert inc.count(s, e, rev) == full.count(s, e, rev)
    finally:
        inc.close()
        full.close()


def test_incremental_merge_runs_off_engine_lock(monkeypatch):
    """Readers are NOT blocked behind the merge interleave: while one
    thread sits inside the heavy merge step (off ``_mlock``), a reader on
    another thread completes. (Regression shape: the old _merge_delta
    rebuilt host-side under the engine lock, stalling every read for the
    whole rebuild.)"""
    from kubebrain_tpu.storage.tpu import engine as engine_mod

    b = mk_tpu_backend(8, merge_threshold=10**9)
    try:
        for i in range(200):
            b.create(b"/registry/off/k%04d" % i, b"v")
        b.scanner.publish()
        for i in range(500):
            b.create(b"/registry/off/m%04d" % i, b"v")

        sc = b.scanner
        entered = threading.Event()
        release = threading.Event()
        real = engine_mod.merge_partitions_stored

        def slow_merge(*args, **kwargs):
            entered.set()
            release.wait(10)
            return real(*args, **kwargs)

        monkeypatch.setattr(engine_mod, "merge_partitions_stored", slow_merge)
        merger = threading.Thread(target=sc._merge_delta)
        merger.start()
        assert entered.wait(10), "merge never started"
        done = threading.Event()
        got: list = []

        def read():
            got.append(b.count(b"/registry/off/", b"/registry/off0"))
            done.set()

        reader = threading.Thread(target=read)
        reader.start()
        finished = done.wait(8)
        release.set()
        merger.join(30)
        reader.join(10)
        assert finished, "reader stalled behind the off-lock merge"
        assert got and got[0][0] == 700
        # post-merge reads still exact
        assert b.count(b"/registry/off/", b"/registry/off0")[0] == 700
    finally:
        b.close()


def test_reader_over_threshold_serves_overlay_beside_merge_in_flight(
        monkeypatch):
    """A reader that finds the delta over the threshold while a merge holds
    ``_merge_lock`` serves mirror + overlay (exact) at once: it neither
    parks on the lock nor merges the tail behind it, so one merge absorbs
    the delta."""
    from kubebrain_tpu.storage.tpu import engine as engine_mod

    b = mk_tpu_backend(8, merge_threshold=10**9)
    try:
        for i in range(200):
            b.create(b"/registry/off/k%04d" % i, b"v")
        b.scanner.publish()
        for i in range(500):
            b.create(b"/registry/off/m%04d" % i, b"v")

        sc = b.scanner
        sc._merge_threshold = 100  # the delta's 500 rows are over it
        entered = threading.Event()
        release = threading.Event()
        real = engine_mod.merge_partitions_stored

        def slow_merge(*args, **kwargs):
            entered.set()
            release.wait(10)
            return real(*args, **kwargs)

        monkeypatch.setattr(engine_mod, "merge_partitions_stored", slow_merge)
        merges_before = sc.merge_count
        merger = threading.Thread(target=sc._merge_delta)
        merger.start()
        assert entered.wait(10), "merge never started"
        got: list = []
        reader = threading.Thread(target=lambda: got.append(
            b.count(b"/registry/off/", b"/registry/off0")))
        reader.start()
        reader.join(8)
        finished = not reader.is_alive()
        release.set()
        merger.join(30)
        reader.join(10)
        assert finished, "reader parked behind the merge in flight"
        assert got and got[0][0] == 700
        assert b.count(b"/registry/off/", b"/registry/off0")[0] == 700
        assert sc.merge_count == merges_before + 1, "a reader merged too"
        assert len(sc._delta) == 0
    finally:
        b.close()


def test_read_path_merge_skips_a_tail_under_the_threshold():
    """The read path's merge re-reads the delta once ``_merge_lock`` is
    its own: a tail under the threshold (left by a merge that took the
    lock first) is served from the overlay, not merged; a merge with no
    threshold (publish, the write kick) still takes it."""
    b = mk_tpu_backend(8, merge_threshold=10**9)
    try:
        for i in range(200):
            b.create(b"/registry/off/k%04d" % i, b"v")
        b.scanner.publish()
        for i in range(10):
            b.create(b"/registry/off/t%04d" % i, b"v")
        sc = b.scanner
        before = sc.merge_count
        sc._merge_delta(threshold=100)
        assert sc.merge_count == before and len(sc._delta) == 10
        assert b.count(b"/registry/off/", b"/registry/off0")[0] == 210
        sc._merge_delta()
        assert sc.merge_count == before + 1 and len(sc._delta) == 0
        assert b.count(b"/registry/off/", b"/registry/off0")[0] == 210
    finally:
        b.close()


def test_merge_metrics_emitted():
    """kb_mirror_merge_seconds{kind=incremental} + merge_rows_total move
    on an incremental merge; kb_sched_write_batch_size moves on group
    formation."""
    from prometheus_client import generate_latest

    from kubebrain_tpu.metrics.prom import PrometheusMetrics

    m = PrometheusMetrics()
    b = mk_tpu_backend(8, merge_threshold=16)
    b.scanner.register_metrics(m)
    try:
        rng = np.random.RandomState(5)
        # seed the SAME keyspace churn writes into, so delta rows spread
        # across partitions instead of overflowing one (which would take
        # the full-rebuild path this test asserts against)
        seeded = {}
        for ns in range(4):
            for i in range(0, 60, 2):
                k = b"/registry/pods/ns-%d/p-%03d" % (ns, i)
                seeded[k] = b.create(k, b"s")
        b.scanner.publish()
        churn(b, rng, 120, live=seeded)
        b.scanner.publish()
        text = generate_latest(m.registry).decode()
        assert 'kb_mirror_merge_seconds_count{kind="incremental"}' in text
        rows = [line for line in text.splitlines()
                if line.startswith("kb_mirror_merge_rows_total ")
                or line.startswith("kb_mirror_merge_rows_total_total ")]
        assert rows and float(rows[0].split()[-1]) > 0
        assert b.scanner.merge_rows_total == float(rows[0].split()[-1])
    finally:
        b.close()


def test_post_compact_merge_stays_incremental():
    """compact() must bind its fresh delta to the NEW mirror's stored
    domain (key width + encoding): the next threshold merge stays
    incremental. Regression: compact reset the delta with a bare
    _DeltaIndex(), so post-compact sealed blocks were raw default-width
    and the width check forced a full rebuild on every merge after a
    compaction."""
    b = mk_tpu_backend(8, merge_threshold=16)
    try:
        rng = np.random.RandomState(11)
        seeded = {}
        for ns in range(4):
            for i in range(0, 60, 2):
                k = b"/registry/pods/ns-%d/p-%03d" % (ns, i)
                seeded[k] = b.create(k, b"s")
        b.scanner.publish()
        churn(b, rng, 60, live=seeded)
        b.scanner.publish()
        assert b.scanner.full_rebuild_total == 0
        b.compact(b.current_revision() - 1)
        churn(b, rng, 60, live=seeded)
        b.scanner.publish()
        assert b.scanner.full_rebuild_total == 0, \
            "post-compact merge took the full-rebuild path"
        assert b.scanner.merge_rows_total > 0
    finally:
        b.close()


def test_group_commit_through_tpu_engine_records_delta_once():
    """A grouped commit over the TPU engine lands ALL its rows in the
    delta in revision order (one _on_committed call), and subsequent
    device reads see them — grouped == sequential over the mirror too."""
    b = mk_tpu_backend(8, merge_threshold=10**9)
    try:
        b.create(b"/registry/gd/seed", b"s")
        b.scanner.publish()
        base = b.current_revision()
        res = b.write_batch([
            ("create", b"/registry/gd/a", b"1", None, 0),
            ("create", b"/registry/gd/b", b"2", None, 0),
            ("update", b"/registry/gd/a", b"3", base + 1, None, 0),
            ("delete", b"/registry/gd/b", 0),
        ])
        assert res[:3] == [base + 1, base + 2, base + 3]
        got = b.list_(b"/registry/gd/", b"/registry/gd0", 0, 0)
        assert [(kv.key, kv.value, kv.revision) for kv in got.kvs] == [
            (b"/registry/gd/a", b"3", base + 3),
            (b"/registry/gd/seed", b"s", base),
        ]
        # delta rows arrived in revision order (merge-sort precondition)
        revs = [r for (_, r, _) in b.scanner._delta.rows()]
        assert revs == sorted(revs)
    finally:
        b.close()
