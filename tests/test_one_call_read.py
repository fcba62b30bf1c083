"""A device-path Range is ONE device call and ONE pull (``_pull_visible``).

The bounds and the read revision go in as one packed host array, and the
visible counts come back with the row indices in one block, sized from the
last count the scanner saw for the same range. What a read answers must not
depend on that: the one-call path (a remembered bucket), the two-step path
(a range never read: the counts, then the indices at the exact bucket) and
the refetch (a remembered bucket too short: one more call at the exact
bucket, ``kb_scan_index_refetch_total``) answer the same wire bytes and the
same rows, byte for byte, as the host store does — single and batched, on
the ``jnp`` and the interpreted Pallas kernel, on one partition and on
several over the CPU mesh (conftest.py), across a merge and a Compact that
swap the mirror. And a read on a remembered bucket hands over to the device
once each way: no put, one call, one pull.
"""

import pytest

from kubebrain_tpu.backend import Backend, BackendConfig
from kubebrain_tpu.backend.scanner import Scanner
from kubebrain_tpu.metrics import NoopMetrics
from kubebrain_tpu.parallel.mesh import make_mesh
from kubebrain_tpu.proto import rpc_pb2
from kubebrain_tpu.server.etcd import shim
from kubebrain_tpu.storage import new_storage
from kubebrain_tpu.storage.tpu import engine
from kubebrain_tpu.storage.tpu.engine import TpuKvStorage

NS = b"/registry/pods/ns-%02d/"
ROWS = 40  # mirror rows a namespace
REFETCH = "kb.scan.index.refetch.total"


def span_of(ns: int) -> tuple[bytes, bytes]:
    lo = NS % ns
    return lo, lo[:-1] + b"0"


WHOLE = (b"/registry/pods/", b"/registry/pods0")  # every partition
EMPTY = span_of(9)  # no row, no overlay entry


class Counters(NoopMetrics):
    """The counters a scanner emits, by (name, sorted tags)."""

    def __init__(self):
        self.seen: dict[tuple, float] = {}

    def emit_counter(self, name, value=1, **tags):
        key = (name, tuple(sorted(tags.items())))
        self.seen[key] = self.seen.get(key, 0) + value

    def refetches(self, path: str) -> float:
        return self.seen.get((REFETCH, (("path", path),)), 0)


#: (kernel, mirror layout) → one backend; the cases read it, few write
_BACKENDS: dict[tuple, Backend] = {}
LAYOUTS = {"one_part": (1, 0), "mesh4_parts8": (4, 8)}  # (devices, partitions)


def backend(kernel: str, layout: str) -> Backend:
    if (kernel, layout) in _BACKENDS:
        return _BACKENDS[kernel, layout]
    n_dev, partitions = LAYOUTS[layout]
    mesh = make_mesh(n_devices=n_dev)
    store = TpuKvStorage(new_storage("memkv"), mesh=mesh, partitions=partitions)
    b = Backend(store, BackendConfig(event_ring_capacity=8192))
    sc = b.scanner
    sc._host_limit_threshold = 0  # every read takes the device path
    sc._merge_threshold = 1 << 20  # the overlay stays an overlay
    sc._scan_kernel = kernel  # pinned: the ambient environment must not flip it
    sc._kernel_mesh = mesh if kernel != "jnp" else None
    sc._metrics = Counters()
    revs = {}
    for ns in (1, 2, 3):
        for i in range(ROWS):
            k = NS % ns + b"pod-%04d" % i
            revs[k] = b.create(k, b"v%d-" % i * (1 + i % 5))
    sc.publish()
    assert sc._mirror.partitions == max(partitions, 1)
    # a delta overlay the device answer is merged with: an update, a
    # deletion, an insertion, and a namespace held by the overlay alone
    k = NS % 1 + b"pod-0017"
    b.update(k, b"updated", revs[k])
    b.delete(NS % 1 + b"pod-0023", revs[NS % 1 + b"pod-0023"])
    b.create(NS % 2 + b"pod-0009x", b"between")
    b.create(NS % 7 + b"only", b"overlay only")
    _BACKENDS[kernel, layout] = b
    return b


@pytest.fixture(scope="module", autouse=True)
def _close_backends():
    yield
    while _BACKENDS:
        _BACKENDS.popitem()[1].close()


def host_rows(sc, start, end, rev) -> list[tuple]:
    """The host store's answer: the oracle every path is held to."""
    kvs, _more = Scanner.range_(sc, start, end, rev, 0)
    return [(kv.key, kv.value, kv.revision) for kv in kvs]


def wire_rows(blob: bytes) -> list[tuple]:
    return [(kv.key, kv.value, kv.mod_revision)
            for kv in rpc_pb2.RangeResponse.FromString(blob).kvs]


def host_wire(sc, start, end, rev) -> bytes:
    """The rows path's bytes for the host store's answer."""
    kvs, _more = Scanner.range_(sc, start, end, rev, 0)
    return rpc_pb2.RangeResponse(
        kvs=[shim.to_kv(kv) for kv in kvs]).SerializeToString()


def set_memo(sc, ranges, memo: str, rev: int) -> None:
    """Put the scanner's bucket memo for ``ranges`` in the state the case
    names: ``miss`` (never read), ``hit`` (its own count remembered) or
    ``short`` (a count of 1 remembered: the block is too short)."""
    for r in ranges:
        sc._buckets.pop(r, None)
    if memo == "hit":
        for r in ranges:
            sc.list_wire(*r, rev, 0)
    elif memo == "short":
        for r in ranges:
            sc._buckets[r] = 1


KERNELS = ["jnp", "pallas_interpret"]
MEMOS = ["hit", "miss", "short"]
SPANS = {"ns1": span_of(1), "whole": WHOLE, "overlay_only": span_of(7),
         "empty": EMPTY}


@pytest.mark.parametrize("span", list(SPANS))
@pytest.mark.parametrize("memo", MEMOS)
@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("kernel", KERNELS)
def test_a_single_read_answers_the_same_on_every_memo_state(kernel, layout,
                                                            memo, span):
    b = backend(kernel, layout)
    sc, rev, (start, end) = b.scanner, b.current_revision(), SPANS[span]
    want = host_rows(sc, start, end, rev)
    counted = sc._metrics.refetches("single")
    answers = []
    for read in ("wire", "rows", "stream"):
        set_memo(sc, [(start, end)], memo, rev)
        if read == "wire":
            blob, n, more = sc.list_wire(start, end, rev, 0)
            assert (n, more) == (len(want), False)
            assert blob == host_wire(sc, start, end, rev)
            answers.append(wire_rows(blob))
        elif read == "rows":
            kvs, more = sc.range_(start, end, rev, 0)
            answers.append([(kv.key, kv.value, kv.revision) for kv in kvs])
        else:
            answers.append([(kv.key, kv.value, kv.revision)
                            for batch in sc.range_stream(start, end, rev, 7)
                            for kv in batch])
    assert answers == [want] * 3
    # the mirror's rows of the range (the overlay's own entries aside)
    # overflow a remembered bucket of 1 wherever a partition holds two
    short = memo == "short" and sc._buckets[(start, end)] > 1
    assert sc._metrics.refetches("single") - counted == (3 if short else 0)
    # and the count, whatever the memo
    assert sc.count(start, end, rev) == len(want)


@pytest.mark.parametrize("memo", MEMOS)
@pytest.mark.parametrize("q", [1, 2, 3])
@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("kernel", KERNELS)
def test_a_batched_read_answers_what_the_single_reads_do(kernel, layout, q, memo):
    """Q queries through one ``_dev_mask_batch`` call, a Count among them
    deselected (its rows never pulled): each answers what it answers alone,
    and what the host store answers."""
    b = backend(kernel, layout)
    sc, rev = b.scanner, b.current_revision()
    specs = {1: [("wire", *span_of(1), rev, 0)],
             2: [("wire", *span_of(1), rev, 0), ("count", *span_of(1), rev)],
             3: [("wire", *span_of(1), rev, 0), ("count", *WHOLE, rev),
                 ("range", *span_of(2), rev, 0)]}[q]  # 3: padded to 4
    ranges = [s[1:3] for s in specs if s[0] != "count"]
    set_memo(sc, ranges, memo, rev)
    counted = sc._metrics.refetches("batch")
    if q == 1:
        # a batch of one rides the single path in ``scan_batch``; the
        # batched program at Q = 1 is held to it here directly
        mirror, want = sc._mirror, ranges[0]
        qs = [(*want, rev, True)]
        counts, rows = sc._pull_visible(
            lambda size: sc._dev_mask_batch(mirror, qs, size), ranges, [0],
            mirror.keys_host.shape[1], "batch")
        one_counts, one_rows = sc._pull_visible(
            lambda size: sc._dev_mask(mirror, *want, rev, size), ranges, [0],
            mirror.keys_host.shape[1], "single")
        assert counts.shape == (1, mirror.partitions)
        assert (counts[0] == one_counts).all()
        for p, n in enumerate(one_counts):
            assert (rows[0, p, :n] == one_rows[p, :n]).all()
        got = [sc._materialize_wire(mirror, (counts[0], rows[0]),
                                    sc._delta.overlay(*want, rev))]
    else:
        got = sc.scan_batch(specs)
    short = memo == "short"
    assert sc._metrics.refetches("batch") - counted == (1 if short else 0)
    for spec, res in zip(specs, got):
        want = host_rows(sc, spec[1], spec[2], rev)
        if spec[0] == "count":
            assert res == len(want) == sc.count(*spec[1:])
        elif spec[0] == "wire":
            blob, n, more = res
            assert (n, more) == (len(want), False)
            assert blob == host_wire(sc, spec[1], spec[2], rev)
            assert res == sc.list_wire(*spec[1:])
        else:
            kvs, more = res
            assert [(kv.key, kv.value, kv.revision) for kv in kvs] == want


def test_a_read_after_a_merge_and_a_compact_swapped_the_mirror():
    """The memo outlives the mirror it was read from: a merge that grows a
    range past its bucket costs that read one refetch, a Compact that
    shrinks it none, and both answer what the host store does."""
    store = TpuKvStorage(new_storage("memkv"), mesh=make_mesh(n_devices=1))
    b = Backend(store, BackendConfig(event_ring_capacity=8192))
    try:
        sc = b.scanner
        sc._host_limit_threshold, sc._merge_threshold = 0, 1 << 20
        sc._scan_kernel, sc._kernel_mesh = "jnp", None
        sc._metrics = Counters()
        s1, e1 = span_of(1)
        revs = [b.create(s1 + b"pod-%04d" % i, b"v%d" % i) for i in range(30)]
        sc.publish()
        rev = b.current_revision()
        assert wire_rows(sc.list_wire(s1, e1, rev, 0)[0]) == host_rows(sc, s1, e1, rev)
        assert sc._buckets[(s1, e1)] == 30  # a bucket of 32 from here on
        for i in range(30, 40):
            b.create(s1 + b"pod-%04d" % i, b"v%d" % i)
        mirror = sc._mirror
        sc.publish()  # the merge swaps the mirror
        assert sc._mirror is not mirror
        rev = b.current_revision()
        for read in range(2):
            blob, n, _more = sc.list_wire(s1, e1, rev, 0)
            assert n == 40 and wire_rows(blob) == host_rows(sc, s1, e1, rev)
            assert sc._metrics.refetches("single") == 1  # the first read only
        for i in range(25):
            b.delete(s1 + b"pod-%04d" % i, revs[i])
        sc.publish()
        mirror, rev = sc._mirror, b.current_revision()
        b.compact(rev)  # 25 tombstones and their versions go
        assert sc._mirror is not mirror and sc.compact_count == 1
        blob, n, _more = sc.list_wire(s1, e1, rev, 0)
        assert n == 15 and wire_rows(blob) == host_rows(sc, s1, e1, rev)
        assert sc._metrics.refetches("single") == 1  # a bucket too wide is no refetch
        assert sc._buckets[(s1, e1)] == 15
    finally:
        b.close()
        store.close()


def test_the_memo_is_bounded_and_forgets_the_oldest_range(monkeypatch):
    b = backend("jnp", "one_part")
    sc, rev = b.scanner, b.current_revision()
    monkeypatch.setattr(engine, "_BUCKET_MEMO", 2)
    sc._buckets.clear()
    for ns in (1, 2, 3):
        sc.list_wire(*span_of(ns), rev, 0)
    assert list(sc._buckets) == [span_of(2), span_of(3)]
    sc.list_wire(*span_of(2), rev, 0)  # read again: remembered in place
    assert list(sc._buckets) == [span_of(2), span_of(3)]


class _Counting:
    """A module seen through one counted attribute."""

    def __init__(self, module, name: str, calls: dict):
        self._module, self._name, self._calls = module, name, calls

    def __getattr__(self, attr):
        real = getattr(self._module, attr)
        if attr != self._name:
            return real

        def counted(*a, **kw):
            self._calls[attr] += 1
            return real(*a, **kw)
        return counted


@pytest.mark.parametrize("kernel", KERNELS)
def test_a_remembered_read_hands_over_once_each_way(kernel, monkeypatch):
    """On a remembered bucket a wire Range makes exactly ONE call of the
    fused program and ONE ``_host_pull``, and puts nothing on the device
    by hand; a Count puts nothing either, and pulls its counts alone."""
    b = backend(kernel, "one_part")
    sc, rev, (s1, e1) = b.scanner, b.current_revision(), span_of(1)
    sc.list_wire(s1, e1, rev, 0)  # compiled, laid out, and remembered
    sc.count(s1, e1, rev)
    calls = {"asarray": 0, "device_put": 0, "_vis_rows": 0, "_host_pull": 0}
    monkeypatch.setattr(engine, "jnp", _Counting(engine.jnp, "asarray", calls))
    monkeypatch.setattr(engine, "jax", _Counting(engine.jax, "device_put", calls))
    for name in ("_vis_rows", "_host_pull"):
        real = getattr(engine, name)

        def counted(*a, _real=real, _name=name, **kw):
            calls[_name] += 1
            return _real(*a, **kw)
        monkeypatch.setattr(engine, name, counted)
    pulled = engine.TRANSFER_METER.snapshot()[0]
    blob, n, _more = sc.list_wire(s1, e1, rev, 0)
    assert calls == {"asarray": 0, "device_put": 0, "_vis_rows": 1,
                     "_host_pull": 1}
    # the one pull: the counts column and a 64-row index block
    assert engine.TRANSFER_METER.snapshot()[0] - pulled == 4 * (1 + 64)
    assert wire_rows(blob) == host_rows(sc, s1, e1, rev)
    calls.update(dict.fromkeys(calls, 0))
    assert sc.count(s1, e1, rev) == n
    assert calls == {"asarray": 0, "device_put": 0, "_vis_rows": 1,
                     "_host_pull": 1}
