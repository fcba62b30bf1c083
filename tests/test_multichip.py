"""Multichip sharded serving tests (the promoted `part`-axis path).

Proven here on the 8-device virtual CPU mesh (conftest.py):

- sharded-vs-single-device byte identity under LIVE delta overlays and
  mixed Range/Count batches, across both the jnp and pallas-interpret
  kernels, including partitions > devices (P//N partitions per device);
- per-scan host transfer bounded by visible rows (never the [P, N] mask or
  a replicated key gather) — the transfer meter backing kblint KB111;
- delta-overlay publish re-uploads ONLY dirty device shards, including
  under concurrent writers;
- kb_mirror_bytes{device=} per-shard HBM accounting on /metrics;
- the --mesh-part/--scan-partitions serving-front flags and the workload
  spec's mesh knobs validate correctly.
"""

import threading

import numpy as np
import pytest

from kubebrain_tpu.backend import Backend, BackendConfig
from kubebrain_tpu.parallel.mesh import make_mesh
from kubebrain_tpu.storage import new_storage
from kubebrain_tpu.storage.tpu.engine import (
    TRANSFER_METER,
    TpuKvStorage,
    TpuScanner,
    _pow2_bucket,
)


def make_backend(ndev, partitions=0, kernel="jnp", merge_threshold=8):
    mesh = make_mesh(n_devices=ndev)
    store = TpuKvStorage(new_storage("memkv"), mesh=mesh,
                         partitions=partitions)
    b = Backend(store, BackendConfig(event_ring_capacity=8192))
    b.scanner._host_limit_threshold = 0  # always the device path
    b.scanner._merge_threshold = merge_threshold
    # pin the kernel explicitly (ambient KB_USE_PALLAS / a TPU backend must
    # not flip the differential under test)
    b.scanner._scan_kernel = kernel
    b.scanner._kernel_mesh = mesh if kernel != "jnp" else None
    return b


def fp_result(res):
    return [(kv.key, kv.value, kv.revision) for kv in res.kvs] + \
        [(res.revision, res.count, res.more)]


def fp_batch(out):
    fps = []
    for r in out:
        assert not isinstance(r, BaseException), r
        fps.append(r if isinstance(r, tuple) else fp_result(r))
    return fps


NSR = [(b"/registry/pods/ns-%02d/" % ns, b"/registry/pods/ns-%02d0" % ns)
       for ns in range(8)]


@pytest.mark.parametrize("kernel", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("ndev,parts", [(8, 0), (4, 8)])
def test_sharded_vs_single_byte_identity_live_overlays(kernel, ndev, parts):
    """Random mixed workload on a 1-device engine vs a sharded one (one
    partition per device AND two partitions per device); every read —
    head + snapshot Ranges, Counts, mixed list_batch — must agree byte for
    byte while the sharded engine still holds a LIVE delta overlay (its
    merge threshold is effectively infinite)."""
    rng = np.random.RandomState(11)
    ref = make_backend(1, kernel="jnp", merge_threshold=4)
    shard = make_backend(ndev, partitions=parts, kernel=kernel,
                         merge_threshold=10**9)  # delta overlay stays live
    try:
        live: dict[bytes, int] = {}
        checkpoints = []
        for step in range(160):
            ns = rng.randint(8)
            k = b"/registry/pods/ns-%02d/p-%04d" % (ns, rng.randint(40))
            op = rng.rand()
            if k not in live:
                action = "create"
            elif op < 0.45:
                action = "recreate"
            elif op < 0.85:
                action = "update"
            else:
                action = "delete"
            for be in (ref, shard):
                if action == "create":
                    r = be.create(k, b"v%03d" % step)
                elif action == "recreate":
                    be.delete(k)
                    r = be.create(k, b"v%03d" % step)
                elif action == "update":
                    r = be.update(k, b"u%03d" % step, live[k])
                else:
                    r, _ = be.delete(k)
            if action == "delete":
                live.pop(k)
            else:
                live[k] = r
            if step % 40 == 17:
                checkpoints.append(ref.current_revision())

            if step % 10 == 3:  # reads interleaved with the writes
                s, e = NSR[ns]
                assert fp_result(ref.list_(s, e)) == fp_result(shard.list_(s, e))
                assert ref.count(s, e) == shard.count(s, e)

        # the sharded engine must still be overlay-serving (nothing merged)
        assert len(shard.scanner._delta) > 0
        assert shard.scanner._mirror.partitions == (parts or ndev)

        # full + per-ns reads at head and at historical snapshots
        assert fp_result(ref.list_(b"/registry/", b"/registry0")) == \
            fp_result(shard.list_(b"/registry/", b"/registry0"))
        for rev in checkpoints:
            for s, e in NSR[:4]:
                assert fp_result(ref.list_(s, e, revision=rev)) == \
                    fp_result(shard.list_(s, e, revision=rev))

        # mixed Range/Count batches through the batch executor (the
        # scheduler's query-batched path): one device dispatch on the
        # sharded engine, byte-identical demux
        queries = []
        for i, (s, e) in enumerate(NSR):
            if i % 3 == 2:
                queries.append(("count", s, e, 0))
            else:
                queries.append(("list", s, e, 0, 0))
        assert fp_batch(ref.list_batch(queries)) == \
            fp_batch(shard.list_batch(queries))
    finally:
        for be in (ref, shard):
            store = be.store
            be.close()
            store.close()


def _scanner_over_rows(n_rows, ndev=8, partitions=0):
    """A published TpuScanner over ``n_rows`` single-revision keys written
    straight into the host engine (bulk batches — no Backend overhead)."""
    from kubebrain_tpu import coder

    store = TpuKvStorage(new_storage("memkv"),
                         mesh=make_mesh(n_devices=ndev),
                         partitions=partitions)
    rev = 0
    for base in range(0, n_rows, 2000):
        b = store.begin_batch_write()
        for i in range(base, min(base + 2000, n_rows)):
            rev += 1
            b.put(coder.encode_object_key(b"/registry/pods/p%07d" % i, rev),
                  b"v" * 16)
        b.commit()
    scanner = store.make_scanner(get_compact_revision=lambda _s: 0)
    scanner._host_limit_threshold = 0
    scanner.publish()
    return store, scanner, rev


def test_host_transfer_budget_bounded_by_visible_rows():
    """Per-scan device→host bytes scale with VISIBLE rows, never with the
    dataset: a 64-row window over a 16k-row mirror must move orders of
    magnitude less than the [P, N] mask (let alone the packed keys), and
    the bound is the documented P·pow2(max-per-shard)·8B index block."""
    P = 8
    n_rows = 16_384
    store, scanner, head = _scanner_over_rows(n_rows, ndev=P)
    try:
        n_pad = scanner._mirror.keys_host.shape[1]
        mask_bytes = P * n_pad            # bool [P, N] — the forbidden pull
        # the unthinkable pull, at RAW key width: the prefix-encoded mirror
        # shrinks the stored column ~6x, which must not relax the absolute
        # index-block budget asserted below
        key_bytes = P * n_pad * scanner._mirror.raw_key_width

        def measured(fn):
            fn()  # warm: compile + bucket shapes off the meter's budget
            b0, _ = TRANSFER_METER.snapshot()
            out = fn()
            b1, _ = TRANSFER_METER.snapshot()
            return out, b1 - b0

        # narrow window: 64 visible rows
        s, e = b"/registry/pods/p0000000", b"/registry/pods/p0000064"
        (kvs, _more), cost = measured(lambda: scanner.range_(s, e, head))
        visible = len(kvs)
        assert visible == 64
        budget = P * _pow2_bucket(visible, n_pad) * 8 + 16 * P + 64
        assert cost <= budget, (cost, budget)
        assert cost < mask_bytes, (cost, mask_bytes)
        assert cost < key_bytes // 100

        # full scan: the transfer may be O(visible)·8B, still never the keys
        (kvs_all, _), cost_all = measured(
            lambda: scanner.range_(b"/registry/pods/", b"/registry/pods0",
                                   head))
        assert len(kvs_all) == n_rows
        per_shard = -(-n_rows // P)
        assert cost_all <= P * _pow2_bucket(per_shard, n_pad) * 8 + 16 * P + 64
        assert cost_all < key_bytes // 10

        # batched path (mixed Range/Count): same O(visible) discipline —
        # Count rows never cross the wire
        def batched():
            return scanner.scan_batch([
                ("range", s, e, head, 0),
                ("count", b"/registry/pods/", b"/registry/pods0", head),
                ("range", b"/registry/pods/p0001000",
                 b"/registry/pods/p0001032", head, 0),
            ])
        out, cost_b = measured(batched)
        assert out[1] == n_rows and len(out[0][0]) == 64 and len(out[2][0]) == 32
        qpad = 4  # 3 queries pow2-padded
        budget_b = qpad * P * _pow2_bucket(64, n_pad) * 8 + qpad * P * 8 + 64
        assert cost_b <= budget_b, (cost_b, budget_b)
        assert cost_b < mask_bytes
    finally:
        store.close()


def test_dirty_shard_only_republish_on_mesh():
    """Delta merges re-upload ONLY the device shards holding dirty
    partitions: clean shards must reuse the previous mirror's device
    buffers (buffer-pointer identity), including with concurrent writers
    hammering one namespace while readers scan."""
    P = 8
    store, scanner, head = _scanner_over_rows(4096, ndev=P)
    try:
        scanner._merge_threshold = 1  # every publish merges the delta
        mirror1 = scanner._mirror
        shards1 = list(mirror1.keys_dev.addressable_shards)
        if not hasattr(shards1[0].data, "unsafe_buffer_pointer"):
            pytest.skip("jax.Array.unsafe_buffer_pointer unavailable")
        ptrs1 = {str(s.device): s.data.unsafe_buffer_pointer()
                 for s in shards1}

        # dirty exactly one partition: keys above every existing key land
        # in the LAST partition
        from kubebrain_tpu import coder

        b = store.begin_batch_write()
        for i in range(16):
            b.put(coder.encode_object_key(b"/registry/pods/zzz-%03d" % i,
                                          head + 1 + i), b"w")
        b.commit()
        scanner.publish()
        mirror2 = scanner._mirror
        assert mirror2 is not mirror1
        ptrs2 = {str(s.device): s.data.unsafe_buffer_pointer()
                 for s in mirror2.keys_dev.addressable_shards}
        changed = [d for d in ptrs1 if ptrs1[d] != ptrs2[d]]
        assert len(changed) == 1, (
            f"expected exactly the last partition's shard re-uploaded, "
            f"got {changed}")

        # concurrent writers + readers: correctness holds and the next
        # publish still only re-uploads the written-to shards
        stop = threading.Event()
        errors: list = []

        def writer():
            # bounded + paced: the tail partition has ~500 rows of padded
            # headroom, and overflowing it forces the full-rebuild fallback
            # (a different, legitimate path — not the one under test)
            import time as _time

            for i in range(120):
                if stop.is_set():
                    return
                bw = store.begin_batch_write()
                bw.put(coder.encode_object_key(
                    b"/registry/pods/zzz-live-%04d" % i,
                    head + 100 + i), b"c")
                bw.commit()
                _time.sleep(0.002)

        def reader():
            try:
                while not stop.is_set():
                    kvs, _ = scanner.range_(b"/registry/pods/p0000000",
                                            b"/registry/pods/p0000064", head)
                    assert len(kvs) == 64
            except Exception as e:  # surfaced to the main thread
                errors.append(e)

        threads = [threading.Thread(target=writer),
                   threading.Thread(target=reader)]
        for t in threads:
            t.start()
        import time as _time

        for _ in range(5):
            _time.sleep(0.05)
            scanner.publish()
        stop.set()
        for t in threads:
            t.join(10.0)
        assert not errors, errors

        mirror3 = scanner._mirror
        ptrs3 = {str(s.device): s.data.unsafe_buffer_pointer()
                 for s in mirror3.keys_dev.addressable_shards}
        unchanged = [d for d in ptrs2 if ptrs2.get(d) == ptrs3.get(d)]
        # every concurrent write landed in the tail partition; at least the
        # low partitions' buffers must have survived every merge untouched
        assert len(unchanged) >= P - 2, unchanged
    finally:
        store.close()


def test_partitions_multiple_of_devices_enforced():
    with pytest.raises(ValueError, match="multiple of the mesh"):
        TpuScanner(new_storage("memkv"), get_compact_revision=lambda _s: 0,
                   mesh=make_mesh(n_devices=4), partitions=6)


def test_mirror_bytes_gauge_per_device():
    """kb_mirror_bytes{device=}: one scrape-time gauge per mesh device,
    each bounded well below the whole-mirror total — the observable form
    of 'per-chip HBM bounds the dataset, not the whole mirror'."""
    prom = pytest.importorskip("prometheus_client")  # noqa: F841
    from kubebrain_tpu.metrics import new_metrics

    store, scanner, _head = _scanner_over_rows(4096, ndev=8)
    try:
        metrics = new_metrics("")
        scanner.register_metrics(metrics)
        _ctype, body = metrics.http_handler()()
        values = {}
        for line in body.decode().splitlines():
            if line.startswith("kb_mirror_bytes{"):
                label, val = line.rsplit(" ", 1)
                values[label] = float(val)
        assert len(values) == 8, values
        total = sum(values.values())
        assert total > 0
        for label, v in values.items():
            assert v > 0, (label, values)
            assert v <= total * 0.5, (label, values)
    finally:
        store.close()


def test_cli_mesh_flags_validate():
    from kubebrain_tpu.cli import build_parser, validate_args

    p = build_parser()
    ok = p.parse_args(["--storage", "tpu", "--mesh-part", "4",
                       "--scan-partitions", "8"])
    validate_args(ok)

    with pytest.raises(SystemExit):  # flags require the tpu engine
        validate_args(p.parse_args(["--mesh-part", "4"]))
    with pytest.raises(SystemExit):  # P must be a multiple of N
        validate_args(p.parse_args(
            ["--storage", "tpu", "--mesh-part", "4",
             "--scan-partitions", "6"]))
    with pytest.raises(SystemExit):
        validate_args(p.parse_args(["--storage", "tpu", "--mesh-part", "-1"]))


def test_workload_spec_mesh_knobs_validate():
    from kubebrain_tpu.workload.spec import WorkloadSpec

    WorkloadSpec.for_smoke(4, storage="tpu", mesh_part=2,
                           scan_partitions=4).validate()
    with pytest.raises(ValueError, match="storage='tpu'"):
        WorkloadSpec.for_smoke(4, mesh_part=2).validate()
    with pytest.raises(ValueError, match=">= 0"):
        WorkloadSpec.for_smoke(4, storage="tpu", mesh_part=-1).validate()
    with pytest.raises(ValueError, match="multiple of mesh_part"):
        # the cli boot check, mirrored: fail at validate, not at spawn
        WorkloadSpec.for_smoke(4, storage="tpu", mesh_part=4,
                               scan_partitions=6).validate()
