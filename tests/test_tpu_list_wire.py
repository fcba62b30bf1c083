"""``TpuScanner.list_wire`` against ``range_``: a differential test.

A wire read answers ``RangeResponse.kvs`` bytes written from the mirror's
host arrays by one native call (``kb_wire_read``: key decode, the delta
overlay merged in by one walk, the cut at ``limit``). Whatever it answers, ``scalar + blob`` parsed as a
``RangeResponse`` must be what the rows of ``range_`` give through
``shim.to_kv`` — and serialize back to the very same bytes — over both key
encodings, one and several partitions, every way an overlay entry can meet
the mirror's rows, limits on both sides of ``more``, and the query-batched
path, which shares the single read's materialization.

Runs on the virtual CPU mesh (conftest.py).
"""

import pytest

from kubebrain_tpu.backend import Backend, BackendConfig
from kubebrain_tpu.parallel.mesh import make_mesh
from kubebrain_tpu.proto import rpc_pb2
from kubebrain_tpu.server.etcd import shim
from kubebrain_tpu.storage import new_storage
from kubebrain_tpu.storage.tpu.engine import TpuKvStorage
from kubebrain_tpu.trace import TRACER

NS = b"/registry/pods/ns-%02d/"
ROWS = 40  # mirror rows a namespace
HOST_LIMIT = 4  # pages above it take the device path

OVERLAYS = ("none", "update", "delete", "insert_first", "insert_between",
            "insert_last", "overlay_only", "mixed")


def span_of(ns: int) -> tuple[bytes, bytes]:
    lo = NS % ns
    return lo, lo[:-1] + b"0"


def apply_overlay(b: Backend, kind: str, revs: dict) -> None:
    """Writes that stay in the delta (the merge threshold is out of reach),
    placed against namespace 1's mirror rows."""
    def key(name: bytes) -> bytes:
        return NS % 1 + name

    if kind in ("update", "mixed"):
        k = key(b"pod-0017")
        revs[k] = b.update(k, b"updated" * 9, revs[k])
    if kind in ("delete", "mixed"):
        b.delete(key(b"pod-0023"), revs.pop(key(b"pod-0023")))
    if kind in ("insert_first", "mixed"):
        b.create(key(b"a-before-the-first"), b"first")
    if kind in ("insert_between", "mixed"):
        b.create(key(b"pod-0009x"), b"")  # an empty value, too
        b.create(key(b"pod-0030x"), b"between" * 40)
    if kind in ("insert_last", "mixed"):
        b.create(key(b"z-after-the-last"), b"last")
    if kind in ("overlay_only", "mixed"):
        # namespace 7 has no mirror row: the reply is the overlay alone
        for i in range(3):
            b.create(NS % 7 + b"only-%d" % i, b"o%d" % i)
        b.delete(NS % 7 + b"only-1")
    if kind == "mixed":  # first and last mirror rows superseded
        b.delete(key(b"pod-0000"), revs.pop(key(b"pod-0000")))
        k = key(b"pod-%04d" % (ROWS - 1))
        revs[k] = b.update(k, b"tail", revs[k])


_BACKENDS: dict[tuple, Backend] = {}


def backend(encode: bool, partitions: int, overlay: str,
            apply=apply_overlay) -> Backend:
    """One backend a (key encoding, partition count, overlay): the cases
    over limits and modes read it, none writes. ``apply`` writes the
    overlay named ``overlay``."""
    if (encode, partitions, overlay) in _BACKENDS:
        return _BACKENDS[encode, partitions, overlay]
    store = TpuKvStorage(new_storage("memkv"), mesh=make_mesh(n_devices=1),
                         partitions=partitions, encode_keys=encode)
    b = Backend(store, BackendConfig(event_ring_capacity=8192))
    sc = b.scanner
    sc._host_limit_threshold = HOST_LIMIT
    sc._merge_threshold = 1 << 20  # the overlay stays an overlay
    sc._scan_kernel, sc._kernel_mesh = "jnp", None
    revs = {}
    for ns in (1, 2, 3):
        for i in range(ROWS):
            k = NS % ns + b"pod-%04d" % i
            revs[k] = b.create(k, b"v%d-" % i * (1 + i % 7))
    sc.publish()
    mirror = sc._mirror
    assert mirror.partitions == partitions and (mirror.encoding is not None) == encode
    assert (mirror.n_valid > 0).sum() == partitions  # rows in every one
    apply(b, overlay, revs)
    assert sc.merge_count == 0 and (len(sc._delta) > 0) == (overlay != "none")
    _BACKENDS[encode, partitions, overlay] = b
    return b


@pytest.fixture(scope="module", autouse=True)
def _close_backends():
    yield
    while _BACKENDS:
        _BACKENDS.popitem()[1].close()


def reply(blob: bytes, n: int, more: bool, rev: int) -> bytes:
    return rpc_pb2.RangeResponse(
        header=shim.header(rev), more=more, count=n).SerializeToString() + blob


def rows_reply(sc, start, end, rev, limit) -> rpc_pb2.RangeResponse:
    kvs, more = sc.range_(start, end, rev, limit)
    return rpc_pb2.RangeResponse(
        header=shim.header(rev), more=more, count=len(kvs),
        kvs=[shim.to_kv(kv) for kv in kvs])


def assert_same(wire: bytes, want: rpc_pb2.RangeResponse) -> None:
    got = rpc_pb2.RangeResponse.FromString(wire)
    assert got == want
    assert got.SerializeToString() == want.SerializeToString()
    # scalar fields first, then kvs: python-protobuf writes fields by
    # number (header 1, kvs 2, more 3, count 4), so compare by parse, and
    # the rows' own bytes exactly
    assert [kv.SerializeToString() for kv in got.kvs] == [
        kv.SerializeToString() for kv in want.kvs]


#: (limit, what it must do to namespace 1's ~40 rows)
LIMITS = {"unlimited": 0, "cut": 11, "roomy": 1000}


@pytest.mark.parametrize("mode", ["single", "batch"])
@pytest.mark.parametrize("limit", list(LIMITS))
@pytest.mark.parametrize("overlay", OVERLAYS)
@pytest.mark.parametrize("partitions", [1, 4], ids=["one_part", "four_parts"])
@pytest.mark.parametrize("encode", [False, True], ids=["raw", "encoded"])
def test_wire_reply_is_the_rows_reply(encode, partitions, overlay, limit, mode):
    b = backend(encode, partitions, overlay)
    sc, rev, lim = b.scanner, b.current_revision(), LIMITS[limit]
    s1, e1 = span_of(1)
    whole = (b"/registry/pods/", b"/registry/pods0")  # every partition
    seven = span_of(7)  # overlay rows only (or nothing at all)
    if mode == "single":
        for start, end in (s1, e1), whole, seven:
            blob, n, more = sc.list_wire(start, end, rev, lim)
            want = rows_reply(sc, start, end, rev, lim)
            assert (n, more) == (want.count, want.more)
            assert_same(reply(blob, n, more, rev), want)
        if limit == "cut":
            assert sc.list_wire(s1, e1, rev, lim)[1:] == (lim, True)
        return
    # 2-3 wire reads and a Count in one dispatch: the riders' replies are
    # the single reads' (one materialization)
    specs = [("wire", s1, e1, rev, lim), ("count", s1, e1, rev),
             ("wire", *whole, rev, 0), ("wire", *seven, rev, lim)]
    got = sc.scan_batch(specs)
    assert got[1] == sc.count(s1, e1, rev)
    for spec, res in zip(specs, got):
        if spec[0] == "wire":
            assert res == sc.list_wire(*spec[1:])
            assert_same(reply(*res, rev), rows_reply(sc, *spec[1:]))
    # and through the backend's batch executor, the scheduler's shape
    out = b.list_batch([("wire", s1, e1, 0, lim), ("list", s1, e1, 0, lim),
                        ("count", s1, e1, 0)])
    (blob, n, more, rr), rows, (cnt, _rr) = out
    assert rr == rows.revision == rev and (n, more) == (rows.count, rows.more)
    assert_same(reply(blob, n, more, rr), rows_reply(sc, s1, e1, rev, lim))
    assert cnt == sc.count(s1, e1, rev)


EDGE_OVERLAYS = ("equals_first_and_last", "consecutive_deletions",
                 "between_partitions", "limit_on_overlay_row", "dead_only")


def apply_edge_overlay(b: Backend, kind: str, revs: dict) -> None:
    """The ways an overlay meets the mirror's rows that ``OVERLAYS`` does
    not reach (``edge-`` + one of ``EDGE_OVERLAYS``)."""
    kind = kind.removeprefix("edge-")

    def key(ns: int, i: int) -> bytes:
        return NS % ns + b"pod-%04d" % i

    if kind == "equals_first_and_last":
        # an entry ON the first and on the last visible row, of namespace
        # 1's span and of the whole keyspace's: one updated, one deleted
        for k in (key(1, 0), key(3, ROWS - 1)):
            revs[k] = b.update(k, b"edge" * 5, revs[k])
        b.delete(key(1, ROWS - 1), revs.pop(key(1, ROWS - 1)))
        b.delete(key(2, 0), revs.pop(key(2, 0)))
    if kind == "consecutive_deletions":
        # three rows in a row gone, a key made and deleted between two of
        # them (a dead entry with no row), then two rewritten neighbours
        for i in (10, 11, 12):
            b.delete(key(1, i), revs.pop(key(1, i)))
        b.delete(key(1, 11) + b"x", b.create(key(1, 11) + b"x", b"gone"))
        for i in (13, 14):
            revs[key(1, i)] = b.update(key(1, i), b"", revs[key(1, i)])
    if kind == "between_partitions":
        # entries that sort after one partition's last row and before the
        # next one's first, live and dead, at every border
        mirror = b.scanner._mirror
        for p in range(mirror.partitions):
            last = mirror.user_key(p, int(mirror.n_valid[p]) - 1)
            b.create(last + b"-border", b"between %d" % p)
            b.delete(last + b"-gone", b.create(last + b"-gone", b"x"))
    if kind == "limit_on_overlay_row":
        # rows 11 and 12 of namespace 1 are the overlay's: LIMITS' cut (11)
        # ends the reply ON an overlay row, with another one behind it
        b.create(key(1, 9) + b"x", b"the eleventh row")
        b.create(key(1, 9) + b"y", b"the twelfth")
    if kind == "dead_only":
        # nothing but deletions of keys the mirror never had
        for i in (0, 1):
            k = NS % 1 + b"never-%d" % i
            b.delete(k, b.create(k, b"x"))


@pytest.mark.parametrize("overlay", EDGE_OVERLAYS)
@pytest.mark.parametrize("partitions", [1, 4], ids=["one_part", "four_parts"])
@pytest.mark.parametrize("encode", [False, True], ids=["raw", "encoded"])
def test_wire_reply_is_the_rows_reply_at_the_overlays_edges(encode, partitions,
                                                            overlay):
    """The overlay cases ``OVERLAYS`` leaves out, each over every limit
    that moves the cut across the rows in question, single and batched."""
    b = backend(encode, partitions, "edge-" + overlay, apply_edge_overlay)
    sc, rev = b.scanner, b.current_revision()
    spans = [span_of(1), (b"/registry/pods/", b"/registry/pods0"), span_of(2)]
    limits = (0, 10, 11, 12, 13, ROWS - 1, ROWS, ROWS + 1, 3 * ROWS, 1000)
    for start, end in spans:
        for lim in limits:
            blob, n, more = sc.list_wire(start, end, rev, lim)
            want = rows_reply(sc, start, end, rev, lim)
            assert (n, more) == (want.count, want.more), (start, lim)
            assert_same(reply(blob, n, more, rev), want)
    specs = [("wire", *spans[0], rev, 11), ("wire", *spans[1], rev, 0),
             ("count", *spans[0], rev), ("wire", *spans[2], rev, ROWS)]
    for spec, res in zip(specs, sc.scan_batch(specs)):
        if spec[0] == "wire":
            assert res == sc.list_wire(*spec[1:])
            assert_same(reply(*res, rev), rows_reply(sc, *spec[1:]))
    if overlay == "limit_on_overlay_row":
        last = rpc_pb2.RangeResponse.FromString(
            sc.list_wire(*spans[0], rev, 11)[0]).kvs[-1]
        assert last.value == b"the eleventh row"
        assert sc.list_wire(*spans[0], rev, 11)[1:] == (11, True)


@pytest.mark.parametrize("encode", [False, True], ids=["raw", "encoded"])
def test_a_wire_read_is_one_foreign_call_and_no_python_per_row(encode, monkeypatch):
    """The host half of a device-path wire read (``_materialize_wire``) is
    ONE ``kb_wire_read`` and a fixed number of Python-level calls: a read of
    three times the rows under three times the overlay entries makes
    exactly as many (counted through ``sys.setprofile``, Python and C
    callees alike), and neither the Python decode funnel nor the run
    gather is on its way."""
    import sys

    from kubebrain_tpu.storage import native
    from kubebrain_tpu.storage.tpu.blocks import Mirror

    b = backend(encode, 1, "mixed")
    sc, rev = b.scanner, b.current_revision()
    calls = {"foreign": 0, "python": 0}
    real = native.load_lib().kb_wire_read

    def foreign(*args):
        calls["foreign"] += 1
        return real(*args)

    def refuse(*_a, **_k):
        raise AssertionError("the wire read left its one native call")

    monkeypatch.setattr(native._lib, "kb_wire_read", foreign, raising=False)
    monkeypatch.setattr(Mirror, "decoded_keys", refuse)
    monkeypatch.setattr(native, "wire_gather", refuse)
    materialize = sc._materialize_wire

    def counted(*args):
        def on_event(_frame, event, _arg):
            calls["python"] += event in ("call", "c_call")
        sys.setprofile(on_event)
        try:
            return materialize(*args)
        finally:
            sys.setprofile(None)

    monkeypatch.setattr(sc, "_materialize_wire", counted)
    seen = []
    for start, end in span_of(1), (b"/registry/pods/", b"/registry/pods0"):
        calls.update(foreign=0, python=0)
        blob, n, _more = sc.list_wire(start, end, rev, 0)
        seen.append((n, calls["foreign"], calls["python"]))
        assert len(rpc_pb2.RangeResponse.FromString(blob).kvs) == n
    (n1, f1, py1), (n3, f3, py3) = seen
    assert n3 > 2.5 * n1 > 100  # the second read is three namespaces'
    assert f1 == f3 == 1
    assert py1 == py3 and py1 < 100, seen


def test_wire_read_at_an_old_revision_ignores_the_later_overlay():
    b = backend(False, 4, "mixed")
    sc = b.scanner
    s1, e1 = span_of(1)
    old = 3 * ROWS  # the last preloaded revision: no overlay entry applies
    blob, n, more = sc.list_wire(s1, e1, old, 0)
    assert (n, more) == (ROWS, False)
    assert_same(reply(blob, n, more, old), rows_reply(sc, s1, e1, old, 0))


def test_wire_reads_ride_one_dispatch_and_join_an_identical_one():
    """``Scheduler.list_wire`` is query-batchable: distinct wire reads and
    a Count queued behind one slot go out as ONE kernel dispatch, an
    identical wire read joins the one in flight (the ``("wire", …)`` key),
    and every waiter gets what a read alone gets."""
    import threading
    import time

    from kubebrain_tpu.sched import Lane, SchedConfig, ensure_scheduler

    store = new_storage("tpu", inner="memkv", mesh=make_mesh(n_devices=1))
    b = Backend(store, BackendConfig(event_ring_capacity=8192))
    sc = b.scanner
    sc._host_limit_threshold, sc._merge_threshold = 0, 1 << 20
    sched = ensure_scheduler(b, SchedConfig(depth=1, queue_limit=64, batch=8))
    try:
        for ns in (1, 2, 3):
            for i in range(ROWS):
                b.create(NS % ns + b"pod-%04d" % i, b"v%d" % i)
        sc.publish()
        b.create(NS % 2 + b"pod-0003x", b"overlay")
        for ns in (1, 2, 3):  # each range's index bucket remembered
            sc.list_wire(*span_of(ns), b.current_revision(), 0)
        calls = {"batch": 0, "single": 0}
        orig_batch, orig_single = sc._dev_mask_batch, sc._dev_mask
        sc._dev_mask_batch = lambda *a: calls.__setitem__(
            "batch", calls["batch"] + 1) or orig_batch(*a)
        sc._dev_mask = lambda *a: calls.__setitem__(
            "single", calls["single"] + 1) or orig_single(*a)
        release = threading.Event()
        sched.submit_async(release.wait, Lane.SYSTEM)  # plug the one slot
        time.sleep(0.15)
        reads = [("wire", *span_of(1), 0), ("wire", *span_of(2), 0),
                 ("wire", *span_of(3), 17), ("count", *span_of(2)),
                 ("wire", *span_of(2), 0)]  # the last: identical to the second
        results: dict[int, object] = {}

        def run(i, r):
            results[i] = (sched.count(r[1], r[2], 0) if r[0] == "count"
                          else sched.list_wire(r[1], r[2], 0, r[3]))
        threads = [threading.Thread(target=run, args=(i, r))
                   for i, r in enumerate(reads)]
        for t in threads:
            t.start()
        time.sleep(0.5)  # everything queued against the plugged slot
        release.set()
        for t in threads:
            t.join(60.0)
        assert calls == {"batch": 1, "single": 0}
        assert sched.batched >= 1 and sched.coalesced == 1
        sc._dev_mask_batch, sc._dev_mask = orig_batch, orig_single
        rev = b.current_revision()
        for i, r in enumerate(reads):
            if r[0] == "count":
                assert results[i] == b.count(r[1], r[2])
                continue
            blob, n, more, rr = results[i]
            assert rr == rev and (blob, n, more, rr) == b.list_wire(r[1], r[2], 0, r[3])
            assert_same(reply(blob, n, more, rr), rows_reply(sc, r[1], r[2], rev, r[3]))
        assert results[2][1:3] == (17, True)
    finally:
        b.close()
        store.close()


def stages_of(fn) -> list[str]:
    TRACER.reset()
    with TRACER.span("test"):
        fn()
    return [s["stage"] for s in TRACER.snapshot()["traces"][-1]["stages"]]


@pytest.mark.parametrize("inner", ["memkv", "native"])
@pytest.mark.parametrize("why", ["small_page", "degraded_mirror"])
def test_the_two_fall_backs_answer_from_the_host_in_one_round(inner, why, monkeypatch):
    """A page at or under the threshold and a quarantined mirror answer
    through the host path ``range_`` takes: the inner engine's own C wire
    scan where it has one (native), else the host scanner's rows through
    the shared encoder — same bytes, and no device stage."""
    store = new_storage("tpu", inner=inner)
    b = Backend(store, BackendConfig(event_ring_capacity=4096))
    try:
        sc = b.scanner
        revs = [b.create(NS % 1 + b"pod-%04d" % i, b"v%d" % i) for i in range(30)]
        b.delete(NS % 1 + b"pod-0004", revs[4])
        sc.publish()
        b.create(NS % 1 + b"pod-0004x", b"in the delta")
        rev, (s1, e1) = b.current_revision(), span_of(1)
        assert sc._host_limit_threshold == 1024
        limit = 5 if why == "small_page" else 0
        if why == "degraded_mirror":
            monkeypatch.setattr(sc, "_degraded", lambda: True)
        stages = stages_of(lambda: assert_same(
            reply(*sc.list_wire(s1, e1, rev, limit), rev),
            rows_reply(sc, s1, e1, rev, limit)))
        assert "host_scan" in stages and not {
            "device_dispatch", "device_compute"} & set(stages)
        # and as riders of a batch
        specs = [("wire", s1, e1, rev, limit), ("count", s1, e1, rev),
                 ("wire", s1, e1, rev, 7)]
        got = sc.scan_batch(specs)
        assert got[0] == sc.list_wire(s1, e1, rev, limit)
        assert got[1] == 30 and got[2] == sc.list_wire(s1, e1, rev, 7)
        assert got[2][1:] == (7, True)
    finally:
        b.close()
        store.close()


def test_a_stale_library_is_rebuilt_or_refused_at_load(tmp_path, monkeypatch):
    """A ``libkbstore.so`` older than its source is rebuilt before it is
    loaded; one that is there, not older, and lacks the wire read's symbol
    (a build of an earlier tree — PR 32's had the gather and not yet
    ``kb_wire_read`` — that kept its place through a copy) is an error at
    load — never a silent rows path."""
    import os
    import subprocess

    from kubebrain_tpu.storage import native
    from kubebrain_tpu.storage.errors import StorageError

    lib, src = str(tmp_path / "libkbstore.so"), tmp_path / "kbstore.cc"
    src.write_text('extern "C" { unsigned long kb_mvcc_list_wire() { return 0; }\n'
                   'unsigned long kb_wire_gather() { return 0; } }\n')
    assert native._lib_stale(lib)  # no library yet
    built = []

    def build(path):
        built.append(path)
        subprocess.run(["g++", "-shared", "-fPIC", "-o", path, str(src)], check=True)

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_LIB_PATH", lib)
    monkeypatch.setattr(native, "_build_lib", build)
    with pytest.raises(StorageError, match="lacks kb_wire_read:"):
        native.load_lib()  # the wire read's symbol alone is missing
    assert built == [lib] and not native._lib_stale(lib)
    with pytest.raises(StorageError, match="stale build"):
        native.load_lib()
    assert built == [lib]  # newer than its source: loaded as it is, refused
    os.utime(lib, (1, 1))
    assert native._lib_stale(lib)
    with pytest.raises(StorageError):
        native.load_lib()
    assert built == [lib, lib]  # older than its source: built again first
    assert native._lib is None


def test_a_scanner_loads_the_library_when_built_not_on_a_range(monkeypatch):
    """The gather is in ``libkbstore.so`` whatever the inner engine: over
    memkv too, a library that cannot be had stops the scanner's
    construction (the boot), and no Range ever meets the build."""
    from kubebrain_tpu.storage import native
    from kubebrain_tpu.storage.errors import StorageError
    from kubebrain_tpu.storage.tpu import engine

    def refuse():
        raise StorageError("no toolchain")

    monkeypatch.setattr(engine, "load_lib", refuse)
    store = TpuKvStorage(new_storage("memkv"), mesh=make_mesh(n_devices=1))
    with pytest.raises(StorageError, match="no toolchain"):
        Backend(store, BackendConfig())
    monkeypatch.undo()
    monkeypatch.setattr(native, "_lib", None)  # as in a fresh process
    b = Backend(store, BackendConfig())
    try:
        assert native._lib is not None  # loaded by the constructor
    finally:
        b.close()
        store.close()


def _good_source():
    from kubebrain_tpu.storage.tpu.blocks import rows_wire_source

    return rows_wire_source([(b"k1", b"v1", 3), (b"k22", b"", 4), (b"k3", b"vvv", 5)])


@pytest.mark.parametrize("spoil", ["offsets_int64", "keys_strided", "rows_short"])
def test_a_wire_source_of_the_wrong_layout_is_refused_not_read(spoil):
    """``kb_wire_gather`` reads raw pointers: a source whose dtype, stride
    or row alignment is off raises (under ``python -O`` too) instead of
    reaching C."""
    import numpy as np

    from kubebrain_tpu.storage.native import wire_gather

    src = list(_good_source())
    assert len(wire_gather([tuple(src)], [(0, 0, 3)])) > 0
    if spoil == "offsets_int64":
        src[4] = src[4].astype(np.int64)
    elif spoil == "keys_strided":
        src[0] = np.asfortranarray(src[0])
    else:
        src[5] = src[5][:2]
    with pytest.raises(ValueError, match="wire source"):
        wire_gather([tuple(src)], [(0, 0, 2)])


def test_the_mirror_holds_its_value_columns_as_the_gather_reads_them():
    """Whatever built or merged a mirror, its value columns are uint8 /
    uint64 and contiguous from construction on, and a read hands the
    native call those very arrays (``Mirror.wire_cols``, made once a
    mirror): no conversion (a copy of a partition's offsets under the GIL)
    on a Range."""
    import dataclasses

    import numpy as np

    mirror = backend(True, 3, "none").scanner._mirror
    for p in range(mirror.partitions):
        keys, lens, revs, arena, offsets, n = (int(w) for w in mirror.wire_cols[p])
        assert arena == mirror.val_arena[p].ctypes.data
        assert offsets == mirror.val_offsets[p].ctypes.data
        assert n == len(mirror.val_offsets[p]) - 1 == mirror.n_valid[p]
        # and the key, length and revision columns where the mirror has them
        assert (keys, lens, revs) == (mirror.keys_host[p].ctypes.data,
                                      mirror.lens_host[p].ctypes.data,
                                      mirror.revs_host[p].ctypes.data)
    as_int64 = dataclasses.replace(
        mirror, val_offsets=[o.astype(np.int64) for o in mirror.val_offsets])
    assert all(o.dtype == np.uint64 and o.flags.c_contiguous
               for o in as_int64.val_offsets)
    assert all((a == b).all()
               for a, b in zip(as_int64.val_offsets, mirror.val_offsets))
