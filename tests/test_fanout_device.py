"""Block-batched device fan-out (docs/watch.md): the persistent sharded
watcher table + one-dispatch-per-block matcher held byte-identical to the
brute-force raw-bytes oracle and the hub's segment index, under watcher
churn, NUL-bearing bounds, version regression, and wat-mesh sharding."""

import queue

import numpy as np
import pytest

from kubebrain_tpu import coder
from kubebrain_tpu.backend.common import WatchEvent
from kubebrain_tpu.backend.watcherhub import ProgressMarker, WatcherHub, _RangeIndex
from kubebrain_tpu.fanout.matcher import DeviceFanout, match_oracle
from kubebrain_tpu.fanout.table import MIN_WIDTH, WatcherTable
from kubebrain_tpu.ops.fanout import FanoutMatcher


def _events(rng, n, rev0=100, keymaker=None):
    keymaker = keymaker or (
        lambda i: b"/registry/%s/ns%02d/obj-%03d" % (
            (b"pods", b"leases")[rng.randint(2)], rng.randint(16),
            rng.randint(64)))
    return [WatchEvent(revision=rev0 + i, key=keymaker(i), value=b"v")
            for i in range(n)]


def _population(rng, n, wid0=0):
    specs = []
    for w in range(n):
        roll = rng.rand()
        if roll < 0.1:  # single-key watch: end carries a NUL
            key = b"/registry/pods/ns%02d/obj-%03d" % (rng.randint(16),
                                                       rng.randint(64))
            specs.append((wid0 + w, key, key + b"\x00", int(rng.randint(3))))
        elif roll < 0.2:  # unbounded from-key watch
            specs.append((wid0 + w, b"/registry/p", b"", int(rng.randint(3))))
        else:
            start = b"/registry/%s/ns%02d/" % ((b"pods", b"leases")[
                rng.randint(2)], rng.randint(16))
            specs.append((wid0 + w, start, coder.prefix_end(start),
                          int(rng.randint(0, 110))))
    return specs


def _deliver_via_index(events, specs):
    """The hub's segment-index path as an oracle: interval stabbing +
    min_rev filter, batch order per watcher."""
    filters = {wid: (s, e, r) for wid, s, e, r in specs}
    index = _RangeIndex(filters)
    assert not index.dense
    out = {}
    for ev in events:
        for wid in index.lookup(ev.key):
            if ev.revision >= filters[wid][2]:
                out.setdefault(wid, []).append(ev)
    return out


def test_block_deliver_identity_under_churn():
    """segment-index vs device vs brute-force byte-identity while the
    watcher set churns (adds, deletes, filter rewrites) across blocks."""
    rng = np.random.RandomState(3)
    matcher = DeviceFanout()
    specs = _population(rng, 70)
    version = 1
    for round_ in range(5):
        events = _events(rng, 48, rev0=90 + 30 * round_)
        mask = matcher(events, specs, version=version)
        assert (mask == match_oracle(events, specs)).all(), round_
        got = DeviceFanout().deliver(events, specs, version=1)
        bounded = [s for s in specs if s[2]]
        got_bounded = {wid: evs for wid, evs in got.items()
                       if wid in {w for w, *_ in bounded}}
        assert got_bounded == _deliver_via_index(events, bounded), round_
        # churn: drop a third, rewrite a third's filters, add new watchers
        keep = [s for s in specs if rng.rand() > 0.3]
        rewritten = [
            (wid, s, e, int(rng.randint(0, 140))) if rng.rand() < 0.3
            else (wid, s, e, r)
            for wid, s, e, r in keep
        ]
        specs = rewritten + _population(rng, 12, wid0=1000 + 100 * round_)
        version += 1
    assert matcher.stats["blocks"] == 0  # legacy protocol doesn't count blocks
    assert matcher.stats["dispatches"] >= 5


def test_block_deliver_matches_legacy_mask_protocol():
    rng = np.random.RandomState(5)
    specs = _population(rng, 40)
    events = _events(rng, 32)
    matcher = DeviceFanout()
    delivered = matcher.deliver(events, specs, version=7)
    mask = match_oracle(events, specs)
    want = {}
    for j, (wid, *_rest) in enumerate(specs):
        evs = [events[i] for i in np.flatnonzero(mask[:, j])]
        if evs:
            want[wid] = evs
    assert delivered == want
    assert matcher.stats["blocks"] == 1


def test_sharded_wat_table_byte_identical():
    """The wat-mesh-sharded table delivers the exact events of the
    unsharded table and the oracle — no ragged fallback, any population
    size (the bucket rounds up to a device-count multiple)."""
    from kubebrain_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(axes=("wat",))
    assert mesh.devices.size > 1  # conftest forces 8 virtual devices
    rng = np.random.RandomState(11)
    plain = DeviceFanout()
    sharded = DeviceFanout(mesh=mesh)
    # 70 is deliberately NOT a multiple of 8: the capacity bucket must
    # absorb it without falling back to an unsharded table
    specs = _population(rng, 70)
    for round_ in range(3):
        events = _events(rng, 24, rev0=95 + 20 * round_)
        a = plain.deliver(events, specs, version=round_ + 1)
        b = sharded.deliver(events, specs, version=round_ + 1)
        assert a == b, round_
        assert (match_oracle(events, specs)
                == plain(events, specs, version=round_ + 1)).all()
        specs = specs[10:] + _population(rng, 10, wid0=500 + 100 * round_)
    assert sharded.table.stats()["sharded"] is True
    assert sharded.table.stats()["capacity"] % mesh.devices.size == 0


def test_nul_bound_single_key_watch():
    """Single-key watches (end = key + b"\\0", the etcd single-key range)
    deliver exactly their key. Stored keys are NUL-free (the packed
    zero-padded compare's domain) — the NUL appears only in BOUNDS, which
    canonicalize_bound rewrites to sit strictly between the key and every
    longer NUL-free key."""
    base = b"/registry/pods/ns00/obj-007"
    specs = [
        (1, base, base + b"\x00", 0),          # watches base only
        (2, base, coder.prefix_end(base), 0),  # prefix: base + extensions
        (3, base + b"\x00", b"", 0),           # from strictly-after base
    ]
    events = [
        WatchEvent(revision=10, key=base, value=b"v"),
        WatchEvent(revision=11, key=base + b"0", value=b"v"),  # obj-0070
        WatchEvent(revision=12, key=b"/registry/pods/ns00/obj-008",
                   value=b"v"),
    ]
    matcher = DeviceFanout()
    mask = matcher(events, specs, version=1)
    assert (mask == match_oracle(events, specs)).all()
    got = DeviceFanout().deliver(events, specs, version=1)
    assert [e.revision for e in got[1]] == [10]
    assert [e.revision for e in got[2]] == [10, 11]
    assert [e.revision for e in got[3]] == [11, 12]


def test_progress_mark_ordering_across_block_delivery():
    """post_progress after a block stream lands AFTER every event of the
    block on the subscriber queue (FIFO carries the ordering), with the
    hub routed through the device block path."""
    matcher = DeviceFanout()
    hub = WatcherHub(fanout_matcher=matcher)
    assert hub.prefers_blocks
    qs = {}
    for i in range(8):
        start = b"/registry/pods/ns%02d/" % i
        wid, q = hub.add_watcher(start, coder.prefix_end(start), 0)
        qs[wid] = (q, i)
    # 8 watchers x 512 events >= 4096 pairs -> device path on CPU
    batch = [
        WatchEvent(revision=100 + i,
                   key=b"/registry/pods/ns%02d/obj-%03d" % (i % 8, i),
                   value=b"v")
        for i in range(512)
    ]
    hub.stream(batch)
    assert matcher.stats["blocks"] == 1, matcher.stats
    top = max(e.revision for e in batch)
    for wid in qs:
        hub.post_progress(wid, top)
    for wid, (q, ns) in qs.items():
        got = []
        while not q.empty():
            got.append(q.get_nowait())
        *event_batches, marker = got
        assert isinstance(marker, ProgressMarker) and marker.revision == top
        revs = [e.revision for b in event_batches for e in b]
        assert revs == sorted(revs)
        assert revs == [e.revision for e in batch if e.key.startswith(
            b"/registry/pods/ns%02d/" % ns)]
    hub.close()


def test_version_regression_rebuilds_packed_state():
    """A restarted hub reuses watcher-set versions from 0: a version that
    moves BACKWARD with different specs must not serve the dead
    population's packed table — both matcher generations."""
    rng = np.random.RandomState(23)
    old = _population(rng, 30)
    new = _population(rng, 30, wid0=2000)
    events = _events(rng, 16)
    for matcher in (DeviceFanout(), FanoutMatcher()):
        m5 = matcher(events, old, version=5)
        assert (m5 == match_oracle(events, old)).all()
        m2 = matcher(events, new, version=2)  # regression + new population
        assert (m2 == match_oracle(events, new)).all()


class _GaugeRecorder:
    def __init__(self):
        self.gauges = {}
        self.fns = {}

    def emit_gauge(self, name, value, **tags):
        self.gauges[name] = value

    def register_gauge_fn(self, name, fn, **tags):
        self.fns[name] = fn

    def emit_counter(self, *a, **k):
        pass

    def emit_histogram(self, *a, **k):
        pass


def test_fanout_sharded_gauge():
    """kb.fanout.sharded is 1 only when the table is REALLY distributed —
    the observable replacing the old silent unsharded fallback."""
    from kubebrain_tpu.parallel.mesh import make_mesh

    for matcher_cls in (DeviceFanout, FanoutMatcher):
        rec = _GaugeRecorder()
        matcher_cls().set_metrics(rec)
        assert rec.gauges["kb.fanout.sharded"] == 0.0
        assert rec.fns["kb.fanout.sharded"]() == 0.0
        rec = _GaugeRecorder()
        matcher_cls(mesh=make_mesh(axes=("wat",))).set_metrics(rec)
        assert rec.gauges["kb.fanout.sharded"] == 1.0
        assert rec.fns["kb.fanout.sharded"]() == 1.0
        # a single-device mesh is NOT sharded
        rec = _GaugeRecorder()
        matcher_cls(mesh=make_mesh(n_devices=1, axes=("wat",))).set_metrics(rec)
        assert rec.gauges["kb.fanout.sharded"] == 0.0


# ---------------------------------------------------------------- table units
def test_table_capacity_buckets():
    t = WatcherTable()
    assert t._capacity_for(1) == 64       # MIN_CAPACITY
    assert t._capacity_for(65) == 128     # pow2 to 1024
    assert t._capacity_for(1024) == 1024
    assert t._capacity_for(1025) == 2048  # 1024-step buckets beyond
    assert t._capacity_for(10_016) == 10_240
    assert t._capacity_for(10_241) == 11_264


def test_table_capacity_rounds_to_device_multiple():
    from kubebrain_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(axes=("wat",))
    nd = int(mesh.devices.size)
    t = WatcherTable(mesh=mesh)
    for n in (1, 65, 1025, 10_016):
        assert t._capacity_for(n) % nd == 0
        assert t._capacity_for(n) >= n


def test_table_width_grows_with_population():
    t = WatcherTable()
    assert t.width == MIN_WIDTH
    t.sync([(1, b"/registry/a/", b"/registry/b", 0)], version=1)
    assert t.width == MIN_WIDTH
    epoch0 = t.stats()["epoch"]
    long_start = b"/registry/pods/" + b"n" * 40 + b"/"
    t.sync([(1, b"/registry/a/", b"/registry/b", 0),
            (2, long_start, coder.prefix_end(long_start), 0)], version=2)
    assert t.width == 64  # pow2 over the longest bound + margin
    assert t.stats()["epoch"] > epoch0  # growth = full republish
    # the re-packed rows still match correctly at the new width
    m = DeviceFanout()
    specs = [(1, b"/registry/a/", b"/registry/b", 0),
             (2, long_start, coder.prefix_end(long_start), 0)]
    events = [WatchEvent(revision=5, key=long_start + b"x", value=b"v"),
              WatchEvent(revision=6, key=b"/registry/aa", value=b"v")]
    assert (m(events, specs, version=1) == match_oracle(events, specs)).all()


def test_table_explicit_width_is_pinned():
    t = WatcherTable(width=32)
    with pytest.raises(ValueError):
        t.sync([(1, b"/k" * 40, b"", 0)], version=1)
    assert t.width == 32


def test_event_side_width_growth():
    """A long EVENT key (not watcher bound) also grows the auto width —
    the kernel compares chunk-for-chunk at one width."""
    m = DeviceFanout()
    specs = [(1, b"/registry/", b"", 0)]
    long_key = b"/registry/" + b"x" * 80
    events = [WatchEvent(revision=5, key=long_key, value=b"v")]
    got = m.deliver(events, specs, version=1)
    assert [e.key for e in got[1]] == [long_key]
    assert m.table.width >= len(long_key) + 2


def test_overflow_regrows_index_bucket():
    """A drain whose matches exceed the compacted-index bucket re-dispatches
    with a doubled bucket — and still delivers every pair."""
    rng = np.random.RandomState(31)
    m = DeviceFanout()
    m._idx_size = 8  # force an immediate overflow
    specs = [(w, b"/registry/", b"", 0) for w in range(16)]  # all match all
    events = _events(rng, 16)
    got = m.deliver(events, specs, version=1)
    assert m.stats["redispatches"] >= 1
    assert m._idx_size >= 16 * 16
    for w in range(16):
        assert [e.revision for e in got[w]] == [e.revision for e in events]


def test_compact_unit():
    import jax.numpy as jnp

    from kubebrain_tpu.fanout.dispatch import _compact

    rng = np.random.RandomState(41)
    for n, density, size in ((256, 0.5, 256), (4096, 0.01, 64),
                             (4096, 0.0, 16), (512, 1.0, 1024)):
        flat = rng.rand(n) < density
        out = np.asarray(_compact(jnp.asarray(flat), size))
        ref = np.flatnonzero(flat)
        k = min(size, len(ref))
        assert (out[:k] == ref[:k]).all(), (n, density, size)
        assert (out[k:] == n).all(), "fill must be len(flat)"


def test_hub_block_path_drops_slow_consumer():
    """The block route honors the drop protocol: a full subscriber queue
    still gets flagged + poisoned, never silently skipped."""
    hub = WatcherHub(fanout_matcher=DeviceFanout())
    small = lambda maxsize: queue.Queue(maxsize=1)
    wid, q = hub.add_watcher(b"/registry/", b"", 0, queue_factory=small)
    # pad population so the pair count crosses the device-path threshold
    for i in range(7):
        s = b"/registry/pods/ns%02d/" % i
        hub.add_watcher(s, coder.prefix_end(s), 0)
    batch = [WatchEvent(revision=100 + i, key=b"/registry/pods/ns00/o%03d" % i,
                        value=b"v") for i in range(512)]
    hub.stream(batch)   # fills wid's 1-slot queue
    hub.stream([WatchEvent(revision=1000 + i, key=b"/registry/x%03d" % i,
                           value=b"v") for i in range(512)])  # overflows it
    assert wid not in hub.watcher_ids()
    assert getattr(q, "kb_dropped", False)
    hub.close()
