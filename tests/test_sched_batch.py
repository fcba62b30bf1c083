"""Query-batched device scan (ISSUE 5 acceptance):

- batched vs sequential execution is BYTE-IDENTICAL across randomized
  ranges, read revisions, limits, and live delta overlays (the scheduler
  is a throughput layer, never a semantics layer — same bar the coalescing
  tests hold);
- Count rides the same kernel launch as Range (one `_dev_mask_batch`
  dispatch per batch, zero single-query dispatches);
- per-query demux: a compacted read revision fails its own query, not the
  batch;
- batching does not starve the SYSTEM lane at 10x background overload;
- the batched overlay probes (`_host_visible_batch`) equal the per-key
  `_host_visible` oracle.

Runs entirely on the CPU fallback (jnp kernel over the tpu engine's memkv
inner store; one pallas-interpret differential for the kernel wiring).
"""

import random
import threading
import time

import pytest

from kubebrain_tpu.backend import Backend, BackendConfig
from kubebrain_tpu.backend.errors import CompactedError
from kubebrain_tpu.parallel.mesh import make_mesh
from kubebrain_tpu.sched import Lane, SchedConfig, ensure_scheduler
from kubebrain_tpu.storage import new_storage


def _snapshot(res):
    """Byte-string fingerprint of a RangeResult (order included)."""
    out = [b"%d|%d|%d" % (res.revision, res.count, int(res.more))]
    for kv in res.kvs:
        out.append(kv.key + b"\x00" + kv.value + b"\x00%d" % kv.revision)
    return b"\xff".join(out)


def _tpu_backend(n_devices=1, scan_kernel="jnp", host_limit_threshold=0,
                 merge_threshold=10**9):
    """A tpu-engine backend over memkv: device path for every unpaged read,
    delta kept as a live overlay (huge merge threshold) unless merged."""
    mesh = make_mesh(n_devices=n_devices)
    store = new_storage("tpu", inner="memkv", mesh=mesh)
    backend = Backend(store, BackendConfig(event_ring_capacity=8192,
                                           watch_cache_capacity=4096))
    sc = backend.scanner
    sc._host_limit_threshold = host_limit_threshold
    sc._merge_threshold = merge_threshold
    if scan_kernel != "jnp":
        sc._scan_kernel = scan_kernel
        sc._kernel_mesh = mesh
    return store, backend


def _populate(backend, rng, n_keys=50, n_ops=120):
    """Create/update/delete churn; returns (keys, revision checkpoints)."""
    keys = [b"/registry/%s/obj-%04d" % (
        rng.choice([b"pods", b"services", b"secrets"]), i)
        for i in range(n_keys)]
    checkpoints = []
    for k in keys:
        backend.create(k, b"v0-" + k)
    checkpoints.append(backend.current_revision())
    for _ in range(n_ops):
        k = rng.choice(keys)
        try:
            kv = backend.get(k)
            if rng.random() < 0.2:
                backend.delete(k, kv.revision)
            else:
                backend.update(k, b"v%d" % rng.randrange(10**6), kv.revision)
        except Exception:
            try:
                backend.create(k, b"re-" + k)
            except Exception:
                pass
        if rng.random() < 0.1:
            checkpoints.append(backend.current_revision())
    checkpoints.append(backend.current_revision())
    return keys, checkpoints


def _workloads(rng, keys, checkpoints, n=40):
    bounds = sorted(rng.sample(keys, min(16, len(keys)))) + \
        [b"/registry/", b"/registry0"]
    out = []
    for _ in range(n):
        a, b = rng.choice(bounds), rng.choice(bounds)
        if a > b:
            a, b = b, a
        if a == b:
            b = a + b"\xff"
        rev = rng.choice([0] + checkpoints)
        if rng.random() < 0.25:
            out.append(("count", a, b, rev))
        else:
            # limit 3 exercises the host small-page fallback inside a batch
            out.append(("list", a, b, rev, rng.choice([0, 0, 3, 25, 500])))
    return out


# ---------------------------------------------------------------- property
def test_batched_vs_sequential_byte_identical_randomized():
    """The tentpole property: randomized Range/Count workloads executed as
    scheduler batches (forced formation: plugged single slot) are
    byte-identical to sequential unscheduled execution — with a LIVE delta
    overlay (mirror published mid-churn, never merged)."""
    rng = random.Random(20260803)
    store, backend = _tpu_backend()
    sc = backend.scanner
    sched = ensure_scheduler(backend, SchedConfig(depth=1, queue_limit=512,
                                                  batch=8))
    try:
        keys, checkpoints = _populate(backend, rng)
        sc.publish()  # mirror snapshot here...
        for k in rng.sample(keys, 20):  # ...then more churn -> live overlay
            try:
                kv = backend.get(k)
                if rng.random() < 0.3:
                    backend.delete(k, kv.revision)
                else:
                    backend.update(k, b"overlay", kv.revision)
            except Exception:
                try:
                    backend.create(k, b"overlay-new")
                except Exception:
                    pass
        checkpoints.append(backend.current_revision())
        assert len(sc._delta) > 0, "test needs a live overlay"

        workloads = _workloads(rng, keys, checkpoints, n=48)
        sc._host_limit_threshold = 4  # limit-3 lists take the host path

        release = threading.Event()
        sched.submit_async(release.wait, Lane.SYSTEM)  # plug the one slot
        time.sleep(0.15)
        results: dict[int, object] = {}

        def run(i, w):
            try:
                if w[0] == "count":
                    results[i] = sched.count(w[1], w[2], w[3], client=f"c{i%5}")
                else:
                    results[i] = sched.list_(w[1], w[2], w[3], w[4],
                                             client=f"c{i%5}")
            except BaseException as e:  # surfaced to the assert below
                results[i] = e
        threads = [threading.Thread(target=run, args=(i, w))
                   for i, w in enumerate(workloads)]
        for t in threads:
            t.start()
        time.sleep(0.5)  # everything enqueued against the plugged slot
        release.set()
        for t in threads:
            t.join(60.0)

        assert sched.batched > 0, "no batches formed"
        assert len(sc._delta) > 0, "overlay merged away mid-test"
        for i, w in enumerate(workloads):
            assert not isinstance(results[i], BaseException), (w, results[i])
            if w[0] == "count":
                assert results[i] == backend.count(w[1], w[2], w[3]), w
            else:
                want = backend.list_(w[1], w[2], w[3], w[4])
                assert _snapshot(results[i]) == _snapshot(want), w
    finally:
        backend.close()
        store.close()


# ----------------------------------------------- one launch for the batch
def test_count_rides_the_same_launch_as_range():
    """A mixed Range+Count batch must cost exactly ONE `_dev_mask_batch`
    dispatch and ZERO single-query `_dev_mask` dispatches — once its
    ranges' index buckets are remembered; the batch that first reads them
    takes two (the counts, then the indices at the exact bucket)."""
    rng = random.Random(7)
    store, backend = _tpu_backend()
    sc = backend.scanner
    try:
        _populate(backend, rng, n_keys=30, n_ops=40)
        sc.publish()
        head = backend.current_revision()
        calls = {"batch": 0, "single": 0}
        orig_batch, orig_single = sc._dev_mask_batch, sc._dev_mask

        def count_batch(*a, **kw):
            calls["batch"] += 1
            return orig_batch(*a, **kw)

        def count_single(*a, **kw):
            calls["single"] += 1
            return orig_single(*a, **kw)
        sc._dev_mask_batch, sc._dev_mask = count_batch, count_single

        specs = [
            ("range", b"/registry/pods/", b"/registry/pods0", head, 0),
            ("count", b"/registry/", b"/registry0", head),
            ("range", b"/registry/", b"/registry0", head, 0),
            ("count", b"/registry/pods/", b"/registry/pods0", head),
        ]
        sc.scan_batch(specs)
        assert calls == {"batch": 2, "single": 0}, calls
        calls["batch"] = 0
        got = sc.scan_batch(specs)
        assert calls == {"batch": 1, "single": 0}, calls

        sc._dev_mask_batch, sc._dev_mask = orig_batch, orig_single
        for spec, g in zip(specs, got):
            if spec[0] == "count":
                assert g == sc.count(spec[1], spec[2], spec[3]), spec
            else:
                kvs, more = sc.range_(spec[1], spec[2], spec[3], spec[4])
                assert g[1] == more
                assert [(kv.key, kv.value, kv.revision) for kv in g[0]] == \
                       [(kv.key, kv.value, kv.revision) for kv in kvs], spec
    finally:
        backend.close()
        store.close()


def test_batched_pallas_interpret_matches_jnp_engine():
    """The pallas-interpret batched path (what a real TPU runs compiled)
    against the jnp engine on the same op sequence — scan_batch results
    must match across kernels, on the multi-device mesh (shard_map)."""
    rng = random.Random(11)
    stores = []
    for kernel in ("jnp", "pallas_interpret"):
        s, b = _tpu_backend(n_devices=None, scan_kernel=kernel)
        stores.append((s, b))
    try:
        for _s, b in stores:
            brng = random.Random(3)
            _populate(b, brng, n_keys=24, n_ops=30)
            b.scanner.publish()
        b_jnp, b_pal = stores[0][1], stores[1][1]
        assert b_jnp.current_revision() == b_pal.current_revision()
        head = b_jnp.current_revision()
        specs = [
            ("range", b"/registry/", b"/registry0", head, 0),
            ("count", b"/registry/", b"/registry0", head),
            ("range", b"/registry/pods/", b"/registry/pods0", head, 10),
        ]
        r1 = b_jnp.scanner.scan_batch(specs)
        r2 = b_pal.scanner.scan_batch(specs)
        assert r1[1] == r2[1]
        for a, b_ in ((r1[0], r2[0]), (r1[2], r2[2])):
            assert a[1] == b_[1]
            assert [(kv.key, kv.value, kv.revision) for kv in a[0]] == \
                   [(kv.key, kv.value, kv.revision) for kv in b_[0]]
    finally:
        for s, b in stores:
            b.close()
            s.close()


# ------------------------------------------------------------------ demux
def test_per_query_error_demux_compacted_revision():
    """One compacted read revision inside a batch fails only its own
    waiter; the rest of the batch serves normally."""
    rng = random.Random(5)
    store, backend = _tpu_backend()
    sched = ensure_scheduler(backend, SchedConfig(depth=1, queue_limit=256,
                                                  batch=8))
    try:
        keys, checkpoints = _populate(backend, rng, n_keys=20, n_ops=40)
        old = checkpoints[0]
        assert checkpoints[-1] > old
        backend.compact(checkpoints[-1])
        head = backend.current_revision()

        # backend-level: the batch executor demuxes the exception element
        out = backend.list_batch([
            ("list", b"/registry/", b"/registry0", head, 0),
            ("list", b"/registry/", b"/registry0", old, 0),
            ("count", b"/registry/", b"/registry0", old),
        ])
        assert not isinstance(out[0], BaseException)
        assert isinstance(out[1], CompactedError)
        assert isinstance(out[2], CompactedError)

        # scheduler-level: the waiter of the compacted query raises, the
        # good query (batched into the same slot) still answers
        release = threading.Event()
        sched.submit_async(release.wait, Lane.SYSTEM)
        time.sleep(0.1)
        results: dict[str, object] = {}

        def good():
            results["good"] = sched.list_(b"/registry/", b"/registry0", head, 0)

        def bad():
            try:
                sched.list_(b"/registry/", b"/registry0", old, 0)
                results["bad"] = None
            except CompactedError as e:
                results["bad"] = e
        threads = [threading.Thread(target=good), threading.Thread(target=bad)]
        for t in threads:
            t.start()
        time.sleep(0.2)
        release.set()
        for t in threads:
            t.join(30.0)
        assert isinstance(results["bad"], CompactedError)
        assert _snapshot(results["good"]) == \
               _snapshot(backend.list_(b"/registry/", b"/registry0", head, 0))
    finally:
        backend.close()
        store.close()


# ------------------------------------------------------- overlay probing
def test_host_visible_batch_matches_per_key_oracle():
    """`_host_visible_batch` (one searchsorted pass per partition) must
    agree with the per-key `_host_visible` binary search for every key —
    present, deleted, superseded, and absent."""
    rng = random.Random(13)
    store, backend = _tpu_backend()
    sc = backend.scanner
    try:
        keys, checkpoints = _populate(backend, rng, n_keys=40, n_ops=80)
        sc.publish()
        mirror = sc._mirror
        probes = keys + [b"/registry/absent/x%d" % i for i in range(5)]
        for rev in (checkpoints[0], checkpoints[len(checkpoints) // 2],
                    checkpoints[-1]):
            got = sc._host_visible_batch(mirror, probes, rev)
            want = [sc._host_visible(mirror, uk, rev) for uk in probes]
            assert got == want, rev
        assert any(got) and not all(got)  # the check has teeth both ways
    finally:
        backend.close()
        store.close()


def test_count_overlay_correction_batched():
    """count() with a live overlay (adds, deletes, supersedes) must match
    a freshly-published mirror's count at every checkpoint revision."""
    rng = random.Random(17)
    store, backend = _tpu_backend()
    sc = backend.scanner
    try:
        keys, _ = _populate(backend, rng, n_keys=30, n_ops=30)
        sc.publish()
        mid = backend.current_revision()
        for k in rng.sample(keys, 12):  # overlay churn on the published mirror
            try:
                kv = backend.get(k)
                if rng.random() < 0.4:
                    backend.delete(k, kv.revision)
                else:
                    backend.update(k, b"ov", kv.revision)
            except Exception:
                try:
                    backend.create(k, b"ov-new")
                except Exception:
                    pass
        head = backend.current_revision()
        assert len(sc._delta) > 0
        got_mid = sc.count(b"/registry/", b"/registry0", mid)
        got_head = sc.count(b"/registry/", b"/registry0", head)
        sc.publish()  # merge the overlay; pure-mirror counts as oracle
        assert sc.count(b"/registry/", b"/registry0", mid) == got_mid
        assert sc.count(b"/registry/", b"/registry0", head) == got_head
    finally:
        backend.close()
        store.close()


# ------------------------------------------------------------- starvation
def test_batching_does_not_starve_system_lane_at_10x_overload():
    """10x queue oversubscription of batchable BACKGROUND lists: SYSTEM
    reads must keep a bounded p99 (they ride the next freed slot — batch
    draining pops in strict lane-priority order), and batches must
    actually form under the flood."""
    rng = random.Random(23)
    store, backend = _tpu_backend()
    qlimit = 16
    sched = ensure_scheduler(backend, SchedConfig(depth=2, queue_limit=qlimit,
                                                  shed_ms=30_000.0, batch=8))
    try:
        _populate(backend, rng, n_keys=30, n_ops=30)
        backend.scanner.publish()
        for i in range(3):
            backend.create(b"/registry/leases/kube-system/l%d" % i, b"x")
        # warm the jit caches (single-dispatch path + the pow2 batched Q
        # shapes) so the timed loop measures scheduling, not compilation
        sched.list_(b"/registry/leases/", b"/registry/leases0", 0, 10)
        backend.list_batch([
            ("list", b"/registry/", b"/registry0", 0, 1000 + i)
            for i in range(8)
        ])
        stop = threading.Event()
        shed = 0
        shed_lock = threading.Lock()
        from kubebrain_tpu.sched import SchedOverloadError

        def flood():
            # async floods (no per-request wait) keep the background queue
            # pinned at its limit — 10x oversubscription like test_sched's
            nonlocal shed
            i = 0
            pending = []
            while not stop.is_set():
                i += 1
                a, b = b"/registry/", b"/registry0"
                # distinct limits -> distinct coalesce keys: every request
                # is its own batchable unit
                lim = 1000 + (i % 64)
                try:
                    pending.append(sched.submit_async(
                        lambda lim=lim: backend.list_(a, b, 0, lim),
                        Lane.BACKGROUND, client=f"f{i % 4}",
                        key=("list", a, b, 0, lim, i),
                        bargs=("list", a, b, 0, lim)))
                except SchedOverloadError:
                    with shed_lock:
                        shed += 1
                if len(pending) >= 64:
                    try:
                        pending[0].wait(30.0)
                    except SchedOverloadError:
                        pass
                    del pending[0]
            for r in pending:
                try:
                    r.wait(30.0)
                except SchedOverloadError:
                    pass
        flooders = [threading.Thread(target=flood, daemon=True)
                    for _ in range(4)]
        for t in flooders:
            t.start()
        time.sleep(0.3)
        lat = []
        for _ in range(20):
            t0 = time.monotonic()
            sched.list_(b"/registry/leases/", b"/registry/leases0", 0, 10)
            lat.append(time.monotonic() - t0)
        stop.set()
        for t in flooders:
            t.join(30.0)
        lat.sort()
        assert lat[-1] < 2.0, f"system p99 {lat[-1]:.3f}s under batched flood"
        assert sched.batched > 0, "flood never formed a batch"
        assert shed > 0, "flood never oversubscribed the queue"
    finally:
        backend.close()
        store.close()
