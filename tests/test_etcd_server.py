"""End-to-end etcd3 protocol tests: a raw grpcio client speaking
etcdserverpb (the same wire bytes kube-apiserver sends) against a running
endpoint. Reference analogue: endpoint_test.go TestRunEndpoint :50 plus the
txn-shape coverage of etcd/kv.go.
"""

import queue
import socket
import threading
import time

import grpc
import pytest

from kubebrain_tpu.cli import build_endpoint, build_parser
from kubebrain_tpu.proto import rpc_pb2, kv_pb2


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class EtcdClient:
    """Minimal etcd3 client built on raw grpc channels (no etcd3 pip pkg in
    this image) — mirrors what kube-apiserver's etcd3 store emits."""

    def __init__(self, target):
        self.ch = grpc.insecure_channel(target)
        p = rpc_pb2
        self.range_ = self.ch.unary_unary(
            "/etcdserverpb.KV/Range",
            request_serializer=p.RangeRequest.SerializeToString,
            response_deserializer=p.RangeResponse.FromString,
        )
        self.txn = self.ch.unary_unary(
            "/etcdserverpb.KV/Txn",
            request_serializer=p.TxnRequest.SerializeToString,
            response_deserializer=p.TxnResponse.FromString,
        )
        self.compact = self.ch.unary_unary(
            "/etcdserverpb.KV/Compact",
            request_serializer=p.CompactionRequest.SerializeToString,
            response_deserializer=p.CompactionResponse.FromString,
        )
        self.watch = self.ch.stream_stream(
            "/etcdserverpb.Watch/Watch",
            request_serializer=p.WatchRequest.SerializeToString,
            response_deserializer=p.WatchResponse.FromString,
        )
        self.lease_grant = self.ch.unary_unary(
            "/etcdserverpb.Lease/LeaseGrant",
            request_serializer=p.LeaseGrantRequest.SerializeToString,
            response_deserializer=p.LeaseGrantResponse.FromString,
        )
        self.member_list = self.ch.unary_unary(
            "/etcdserverpb.Cluster/MemberList",
            request_serializer=p.MemberListRequest.SerializeToString,
            response_deserializer=p.MemberListResponse.FromString,
        )
        self.status = self.ch.unary_unary(
            "/etcdserverpb.Maintenance/Status",
            request_serializer=p.StatusRequest.SerializeToString,
            response_deserializer=p.StatusResponse.FromString,
        )

    # --- the four txn shapes kube-apiserver emits (etcd3 store semantics)
    def create(self, key, value):
        req = rpc_pb2.TxnRequest()
        c = req.compare.add()
        c.result = rpc_pb2.Compare.EQUAL
        c.target = rpc_pb2.Compare.MOD
        c.key = key
        c.mod_revision = 0
        req.success.add().request_put.CopyFrom(rpc_pb2.PutRequest(key=key, value=value))
        req.failure.add().request_range.CopyFrom(rpc_pb2.RangeRequest(key=key))
        return self.txn(req)

    def update(self, key, value, mod_rev):
        req = rpc_pb2.TxnRequest()
        c = req.compare.add()
        c.result = rpc_pb2.Compare.EQUAL
        c.target = rpc_pb2.Compare.MOD
        c.key = key
        c.mod_revision = mod_rev
        req.success.add().request_put.CopyFrom(rpc_pb2.PutRequest(key=key, value=value))
        req.failure.add().request_range.CopyFrom(rpc_pb2.RangeRequest(key=key))
        return self.txn(req)

    def delete(self, key, mod_rev):
        req = rpc_pb2.TxnRequest()
        c = req.compare.add()
        c.result = rpc_pb2.Compare.EQUAL
        c.target = rpc_pb2.Compare.MOD
        c.key = key
        c.mod_revision = mod_rev
        req.success.add().request_delete_range.CopyFrom(
            rpc_pb2.DeleteRangeRequest(key=key)
        )
        req.failure.add().request_range.CopyFrom(rpc_pb2.RangeRequest(key=key))
        return self.txn(req)

    def compact_coordination(self, version_token, rev_value):
        """The apiserver compactor txn on compact_rev_key (VERSION guard)."""
        req = rpc_pb2.TxnRequest()
        c = req.compare.add()
        c.result = rpc_pb2.Compare.EQUAL
        c.target = rpc_pb2.Compare.VERSION
        c.key = b"compact_rev_key"
        c.version = version_token
        req.success.add().request_put.CopyFrom(
            rpc_pb2.PutRequest(key=b"compact_rev_key", value=rev_value)
        )
        req.failure.add().request_range.CopyFrom(
            rpc_pb2.RangeRequest(key=b"compact_rev_key")
        )
        return self.txn(req)

    def close(self):
        self.ch.close()


@pytest.fixture(scope="module")
def server():
    port = free_port()
    args = build_parser().parse_args([
        "--single-node", "--storage", "memkv", "--host", "127.0.0.1",
        "--client-port", str(port),
        "--peer-port", str(free_port()), "--info-port", str(free_port()),
    ])
    endpoint, backend, store = build_endpoint(args)
    endpoint.run()
    client = EtcdClient(f"127.0.0.1:{port}")
    yield client, backend, args
    client.close()
    endpoint.close()
    backend.close()
    store.close()


K = b"/registry/pods/default/nginx"


def test_create_get_update_delete_txn_flow(server):
    client, backend, _ = server
    resp = client.create(K, b"spec-v1")
    assert resp.succeeded
    rev1 = resp.responses[0].response_put.header.revision
    assert rev1 > 0

    # duplicate create fails; failure branch returns current kv
    resp = client.create(K, b"other")
    assert not resp.succeeded
    assert resp.responses[0].response_range.kvs[0].mod_revision == rev1
    assert resp.responses[0].response_range.kvs[0].value == b"spec-v1"

    # get via Range (no range_end)
    r = client.range_(rpc_pb2.RangeRequest(key=K))
    assert r.count == 1 and r.kvs[0].value == b"spec-v1"

    # guarded update
    resp = client.update(K, b"spec-v2", rev1)
    assert resp.succeeded
    rev2 = resp.responses[0].response_put.header.revision
    # stale guard fails with current kv in failure branch
    resp = client.update(K, b"nope", rev1)
    assert not resp.succeeded
    assert resp.responses[0].response_range.kvs[0].mod_revision == rev2

    # guarded delete
    resp = client.delete(K, rev2)
    assert resp.succeeded
    r = client.range_(rpc_pb2.RangeRequest(key=K))
    assert r.count == 0


def test_list_count_pagination(server):
    client, _, _ = server
    for i in range(10):
        client.create(b"/registry/cm/item%02d" % i, b"v%d" % i)
    r = client.range_(rpc_pb2.RangeRequest(key=b"/registry/cm/", range_end=b"/registry/cm0"))
    assert r.count == 10 and not r.more
    r = client.range_(
        rpc_pb2.RangeRequest(key=b"/registry/cm/", range_end=b"/registry/cm0", limit=4)
    )
    assert len(r.kvs) == 4 and r.more
    # apiserver continuation: start from last key + \x00
    cont = r.kvs[-1].key + b"\x00"
    r2 = client.range_(
        rpc_pb2.RangeRequest(key=cont, range_end=b"/registry/cm0", limit=100)
    )
    assert len(r2.kvs) == 6
    # count_only
    r = client.range_(
        rpc_pb2.RangeRequest(key=b"/registry/cm/", range_end=b"/registry/cm0", count_only=True)
    )
    assert r.count == 10 and not r.kvs


def test_snapshot_list_and_compaction_error(server):
    client, backend, _ = server
    resp = client.create(b"/registry/snap/a", b"1")
    rev1 = resp.responses[0].response_put.header.revision
    client.update(b"/registry/snap/a", b"2", rev1)
    r = client.range_(
        rpc_pb2.RangeRequest(key=b"/registry/snap/", range_end=b"/registry/snap0", revision=rev1)
    )
    assert r.kvs[0].value == b"1"
    # compact past rev1, stale read must fail with the etcd error string
    client.compact(rpc_pb2.CompactionRequest(revision=backend.current_revision()))
    with pytest.raises(grpc.RpcError) as ei:
        client.range_(
            rpc_pb2.RangeRequest(
                key=b"/registry/snap/", range_end=b"/registry/snap0", revision=rev1
            )
        )
    assert ei.value.code() == grpc.StatusCode.OUT_OF_RANGE
    assert "compacted" in ei.value.details()


def test_watch_stream(server):
    client, _, _ = server
    requests: queue.Queue = queue.Queue()
    responses = client.watch(iter(requests.get, None))
    req = rpc_pb2.WatchRequest()
    req.create_request.key = b"/registry/watched/"
    req.create_request.range_end = b"/registry/watched0"
    req.create_request.prev_kv = True
    requests.put(req)

    created = next(responses)
    assert created.created
    watch_id = created.watch_id

    resp = client.create(b"/registry/watched/pod1", b"w1")
    rev1 = resp.responses[0].response_put.header.revision
    client.update(b"/registry/watched/pod1", b"w2", rev1)

    events = []
    while len(events) < 2:
        wr = next(responses)
        events.extend(wr.events)
    assert events[0].type == kv_pb2.Event.PUT and events[0].kv.value == b"w1"
    assert events[1].kv.value == b"w2"
    assert events[1].kv.mod_revision > events[0].kv.mod_revision

    # delete event carries prev_kv
    client.delete(b"/registry/watched/pod1", events[1].kv.mod_revision)
    wr = next(responses)
    assert wr.events[0].type == kv_pb2.Event.DELETE
    assert wr.events[0].prev_kv.value == b"w2"

    # cancel
    creq = rpc_pb2.WatchRequest()
    creq.cancel_request.watch_id = watch_id
    requests.put(creq)
    wr = next(responses)
    assert wr.canceled
    requests.put(None)


def test_watch_from_revision_replays(server):
    client, backend, _ = server
    resp = client.create(b"/registry/replay/a", b"1")
    rev1 = resp.responses[0].response_put.header.revision
    client.create(b"/registry/replay/b", b"2")

    requests: queue.Queue = queue.Queue()
    responses = client.watch(iter(requests.get, None))
    req = rpc_pb2.WatchRequest()
    req.create_request.key = b"/registry/replay/"
    req.create_request.range_end = b"/registry/replay0"
    req.create_request.start_revision = rev1
    requests.put(req)
    assert next(responses).created
    events = []
    while len(events) < 2:
        events.extend(next(responses).events)
    assert [e.kv.value for e in events] == [b"1", b"2"]
    requests.put(None)


def test_watch_compacted_revision_cancels(server):
    client, backend, _ = server
    resp = client.create(b"/registry/wcomp/a", b"1")
    rev1 = resp.responses[0].response_put.header.revision
    client.update(b"/registry/wcomp/a", b"2", rev1)
    client.compact(rpc_pb2.CompactionRequest(revision=backend.current_revision()))
    requests: queue.Queue = queue.Queue()
    responses = client.watch(iter(requests.get, None))
    req = rpc_pb2.WatchRequest()
    req.create_request.key = b"/registry/"
    req.create_request.range_end = b"/registry0"
    req.create_request.start_revision = rev1  # below the compact watermark
    requests.put(req)
    wr = next(responses)
    assert wr.canceled and wr.compact_revision >= 1
    requests.put(None)


def test_compactor_coordination_protocol(server):
    """The kube-apiserver compactor's txn dance on compact_rev_key."""
    client, _, _ = server
    # first run: version token 0 => create
    resp = client.compact_coordination(0, b"100")
    if not resp.succeeded:
        # key exists from a previous test run: read token and retry
        token = resp.responses[0].response_range.kvs[0].version
        resp = client.compact_coordination(token, b"100")
    assert resp.succeeded
    # another replica with a stale token loses and reads the fresh token
    resp2 = client.compact_coordination(0, b"200")
    assert not resp2.succeeded
    kv = resp2.responses[0].response_range.kvs[0]
    assert kv.value == b"100" and kv.version > 0
    # retry with the fresh token wins
    resp3 = client.compact_coordination(kv.version, b"200")
    assert resp3.succeeded


def test_lease_and_memberlist_and_status(server):
    client, _, _ = server
    lg = client.lease_grant(rpc_pb2.LeaseGrantRequest(TTL=3600))
    # real lease subsystem: a server-chosen id, not the old ID:=TTL stub
    assert lg.ID > 0 and lg.TTL == 3600
    ml = client.member_list(rpc_pb2.MemberListRequest())
    assert len(ml.members) == 1
    st = client.status(rpc_pb2.StatusRequest())
    assert "kubebrain-tpu" in st.version


def test_raw_put_rejected(server):
    client, _, _ = server
    put = client.ch.unary_unary(
        "/etcdserverpb.KV/Put",
        request_serializer=rpc_pb2.PutRequest.SerializeToString,
        response_deserializer=rpc_pb2.PutResponse.FromString,
    )
    with pytest.raises(grpc.RpcError) as ei:
        put(rpc_pb2.PutRequest(key=b"/x", value=b"y"))
    assert ei.value.code() == grpc.StatusCode.UNIMPLEMENTED


def test_partition_magic_revision(server):
    client, _, _ = server
    r = client.range_(
        rpc_pb2.RangeRequest(
            key=b"/registry/", range_end=b"/registry0", revision=1888
        )
    )
    borders = [kv.key for kv in r.kvs]
    assert borders[0] == b"/registry/" and borders[-1] == b"/registry0"


def test_http_status_and_health(server):
    client, backend, args = server
    import json
    import urllib.request

    with urllib.request.urlopen(f"http://127.0.0.1:{args.peer_port}/status", timeout=5) as resp:
        payload = json.loads(resp.read())
    assert payload["revision"] == backend.current_revision()
    assert payload["is_leader"] is True
    with urllib.request.urlopen(f"http://127.0.0.1:{args.peer_port}/health", timeout=5) as resp:
        assert json.loads(resp.read())["health"] == "true"
    with urllib.request.urlopen(f"http://127.0.0.1:{args.info_port}/metrics", timeout=5) as resp:
        assert resp.status == 200


def test_maintenance_snapshot_and_defrag(server):
    client, backend, _ = server
    client.create(b"/registry/snapme/a", b"payload-a")
    client.create(b"/registry/snapme/b", b"payload-b")
    snap = client.ch.unary_stream(
        "/etcdserverpb.Maintenance/Snapshot",
        request_serializer=rpc_pb2.SnapshotRequest.SerializeToString,
        response_deserializer=rpc_pb2.SnapshotResponse.FromString,
    )
    blob = b""
    for resp in snap(rpc_pb2.SnapshotRequest()):
        blob += resp.blob
        last_remaining = resp.remaining_bytes
    assert last_remaining == 0
    assert blob.startswith(b"KBSNAP1")
    assert b"/registry/snapme/a" in blob and b"payload-b" in blob
    defrag = client.ch.unary_unary(
        "/etcdserverpb.Maintenance/Defragment",
        request_serializer=rpc_pb2.DefragmentRequest.SerializeToString,
        response_deserializer=rpc_pb2.DefragmentResponse.FromString,
    )
    assert defrag(rpc_pb2.DefragmentRequest()).header.revision > 0


def test_lease_keepalive_and_revoke(server):
    client, _, _ = server
    lg = client.lease_grant(rpc_pb2.LeaseGrantRequest(TTL=60))
    ka = client.ch.stream_stream(
        "/etcdserverpb.Lease/LeaseKeepAlive",
        request_serializer=rpc_pb2.LeaseKeepAliveRequest.SerializeToString,
        response_deserializer=rpc_pb2.LeaseKeepAliveResponse.FromString,
    )
    # a live lease refreshes to its granted TTL; an unknown one gets the
    # etcd TTL=0 encoding of "lease not found"
    resp = next(ka(iter([rpc_pb2.LeaseKeepAliveRequest(ID=lg.ID)])))
    assert resp.ID == lg.ID and resp.TTL == 60
    resp = next(ka(iter([rpc_pb2.LeaseKeepAliveRequest(ID=999999)])))
    assert resp.TTL == 0
    revoke = client.ch.unary_unary(
        "/etcdserverpb.Lease/LeaseRevoke",
        request_serializer=rpc_pb2.LeaseRevokeRequest.SerializeToString,
        response_deserializer=rpc_pb2.LeaseRevokeResponse.FromString,
    )
    assert revoke(rpc_pb2.LeaseRevokeRequest(ID=lg.ID)).header.revision > 0
    with pytest.raises(grpc.RpcError) as ei:
        revoke(rpc_pb2.LeaseRevokeRequest(ID=lg.ID))  # already gone
    assert ei.value.code() == grpc.StatusCode.NOT_FOUND


def test_snapshot_save_restore_roundtrip(server, tmp_path):
    """Backup from one server, restore into a fresh one (tools.py)."""
    import subprocess
    import sys as _sys

    client, backend, args = server
    client.create(b"/registry/backup/a", b"va")
    client.create(b"/registry/backup/b", b"vb")
    snap_path = str(tmp_path / "backup.snap")
    rc = subprocess.run(
        [_sys.executable, "-m", "kubebrain_tpu.tools", "snapshot-save",
         "--endpoint", f"127.0.0.1:{args.client_port}", snap_path],
        cwd="/root/repo", capture_output=True,
    )
    assert rc.returncode == 0, rc.stderr.decode()

    from kubebrain_tpu.tools import parse_snapshot

    with open(snap_path, "rb") as f:
        header_rev, kvs = parse_snapshot(f.read())
    keys = {k for k, _, _ in kvs}
    assert b"/registry/backup/a" in keys and b"/registry/backup/b" in keys
    assert header_rev >= max(r for _, _, r in kvs)

    # restore into a brand-new server
    port2 = free_port()
    args2 = build_parser().parse_args([
        "--single-node", "--storage", "memkv", "--host", "127.0.0.1",
        "--client-port", str(port2),
        "--peer-port", str(free_port()), "--info-port", str(free_port()),
    ])
    ep2, be2, st2 = build_endpoint(args2)
    ep2.run()
    try:
        rc = subprocess.run(
            [_sys.executable, "-m", "kubebrain_tpu.tools", "snapshot-restore",
             "--endpoint", f"127.0.0.1:{port2}", snap_path],
            cwd="/root/repo", capture_output=True,
        )
        assert rc.returncode == 0, rc.stderr.decode()
        c2 = EtcdClient(f"127.0.0.1:{port2}")
        r = c2.range_(rpc_pb2.RangeRequest(key=b"/registry/backup/", range_end=b"/registry/backup0"))
        assert {kv.key: kv.value for kv in r.kvs} == {
            b"/registry/backup/a": b"va", b"/registry/backup/b": b"vb",
        }
        c2.close()
    finally:
        ep2.close()
        be2.close()
        st2.close()


def test_lease_attached_put_expires():
    """A put with a lease expires via the lease subsystem: the reaper turns
    the expired lease's keys into revision-stamped MVCC deletes (covers
    apiserver masterleases and events uniformly — broader than the
    reference's /events/-pattern TTL; docs/leases.md)."""
    import time as _time

    port = free_port()
    args = build_parser().parse_args([
        "--single-node", "--storage", "native", "--host", "127.0.0.1",
        "--client-port", str(port),
        "--peer-port", str(free_port()), "--info-port", str(free_port()),
        "--lease-reap-interval", "0.1",
    ])
    endpoint, backend, store = build_endpoint(args)
    endpoint.run()
    client = EtcdClient(f"127.0.0.1:{port}")
    try:
        lg = client.lease_grant(rpc_pb2.LeaseGrantRequest(TTL=1))
        req = rpc_pb2.TxnRequest()
        c = req.compare.add()
        c.result, c.target, c.key, c.mod_revision = (
            rpc_pb2.Compare.EQUAL, rpc_pb2.Compare.MOD, b"/registry/masterleases/1.2.3.4", 0,
        )
        req.success.add().request_put.CopyFrom(rpc_pb2.PutRequest(
            key=b"/registry/masterleases/1.2.3.4", value=b"lease-me", lease=lg.ID,
        ))
        assert client.txn(req).succeeded
        r = client.range_(rpc_pb2.RangeRequest(key=b"/registry/masterleases/1.2.3.4"))
        assert r.count == 1
        deadline = _time.time() + 5.0
        while _time.time() < deadline:
            r = client.range_(rpc_pb2.RangeRequest(key=b"/registry/masterleases/1.2.3.4"))
            if r.count == 0:
                break
            _time.sleep(0.1)
        assert r.count == 0  # expired with the lease TTL
    finally:
        client.close()
        endpoint.close()
        backend.close()
        store.close()


# ------------------------------------------- kube-apiserver's compactor
# k8s.io/apiserver storage/etcd3/compact.go, one tick: Txn(If(Version(
# compact_rev_key) == t) Then(Put(compact_rev_key, rev)) Else(Get)); on
# success it keeps t + 1 without reading the key back and compacts to rev
# (not on the first tick, where rev is 0); on failure it takes t from the
# Get. The next tick's rev is this Txn's header revision.

STORAGES = {
    "memkv": ["--storage", "memkv"],
    "native": ["--storage", "native"],
    "tpu": ["--storage", "tpu", "--inner-storage", "memkv"],
}


class _Served:
    """A fresh single-node endpoint of one storage engine, with a client."""

    def __init__(self, flags):
        port = free_port()
        args = build_parser().parse_args([
            "--single-node", *flags, "--host", "127.0.0.1",
            "--client-port", str(port), "--peer-port", str(free_port()),
            "--info-port", str(free_port()),
        ])
        self.endpoint, self.backend, self.store = build_endpoint(args)
        self.endpoint.run()
        self.client = EtcdClient(f"127.0.0.1:{port}")

    def close(self):
        self.client.close()
        self.endpoint.close()
        self.backend.close()
        self.store.close()


@pytest.fixture
def served(request):
    s = _Served(STORAGES[request.param])
    yield s
    s.close()


class Compactor:
    """One kube-apiserver's compactor state (t, rev) and its tick."""

    def __init__(self, client):
        self.client, self.t, self.rev = client, 0, 0

    def tick(self) -> bool:
        resp = self.client.compact_coordination(self.t, b"%d" % self.rev)
        target, self.rev = self.rev, resp.header.revision
        if not resp.succeeded:
            self.t = resp.responses[0].response_range.kvs[0].version
            return False
        self.t += 1
        if target:
            self.client.compact(rpc_pb2.CompactionRequest(revision=target))
        return True


def _version(client) -> int:
    r = client.range_(rpc_pb2.RangeRequest(key=b"compact_rev_key"))
    return r.kvs[0].version


@pytest.mark.parametrize("served", list(STORAGES), indirect=True)
def test_compact_go_sequence_acknowledged_on_every_tick(served):
    """t = 0 creates the key, then t + 1 twice: every tick is acknowledged
    (it used to be refused every second tick), and the key reads version 3."""
    c = Compactor(served.client)
    for i in range(3):
        served.client.create(b"/registry/pods/ns/p%d" % i, b"v")
        assert c.tick(), f"tick {i + 1} refused"
    assert c.t == 3 and _version(served.client) == 3


@pytest.mark.parametrize("served", ["memkv", "native"], indirect=True)
def test_three_compactors_one_success_a_round(served):
    """kube-apiserver's three replicas, each keeping t as compact.go does,
    interleaved in a rotating order: exactly one tick a round is
    acknowledged — the first of the round — as etcd answers them."""
    cs = [Compactor(served.client) for _ in range(3)]
    got = []
    for rnd in range(6):
        served.client.create(b"/registry/pods/ns/r%d" % rnd, b"v")
        order = [(rnd + k) % 3 for k in range(3)]
        got.append([cs[i].tick() for i in order])
    assert got == [[True, False, False]] * 6
    assert _version(served.client) == 6
    assert [c.t for c in cs] == [6, 6, 6]


@pytest.mark.parametrize("served", list(STORAGES), indirect=True)
def test_version_survives_the_keys_own_compaction(served):
    """Each tick compacts to the previous tick's revision, so the key's own
    older revisions go; its Version does not, and the next tick with
    t + 1 still wins. A read at an older revision gives the Version then."""
    c = Compactor(served.client)
    revs = []
    for i in range(5):
        served.client.create(b"/registry/pods/ns/s%d" % i, b"v")
        assert c.tick()
        revs.append(c.rev)
    assert served.backend.compact_revision() == revs[-2]
    assert _version(served.client) == 5
    old = served.client.range_(rpc_pb2.RangeRequest(
        key=b"compact_rev_key", revision=revs[-1] - 1))
    assert old.kvs[0].version == 4 and old.kvs[0].value == b"%d" % revs[-3]
    assert c.tick() and _version(served.client) == 6


def test_version_is_as_durable_as_the_key(tmp_path):
    """The count rides the put's own engine batch: a restart of a native
    store on its data directory reads it back, after the compaction of the
    key's older revisions."""
    flags = ["--storage", "native", "--data-dir", str(tmp_path)]
    s = _Served(flags)
    try:
        c = Compactor(s.client)
        for i in range(4):
            s.client.create(b"/registry/pods/ns/d%d" % i, b"v")
            assert c.tick()
    finally:
        s.close()
    s = _Served(flags)
    try:
        assert _version(s.client) == 4
        again = Compactor(s.client)  # a restarted apiserver: t = 0
        assert not again.tick() and again.t == 4
        assert again.tick() and _version(s.client) == 5
    finally:
        s.close()


def test_other_keys_keep_mod_revision_guards(server):
    """A Version guard on any other key stays a mod-revision guard, and a
    row of any other key reads version 1."""
    client, _, _ = server
    key = b"/registry/configmaps/ns/versioned"
    created = client.create(key, b"a")
    rev = created.header.revision
    req = rpc_pb2.TxnRequest()
    cmp = req.compare.add(result=rpc_pb2.Compare.EQUAL,
                          target=rpc_pb2.Compare.VERSION, key=key, version=rev)
    assert cmp.version == rev
    req.success.add().request_put.CopyFrom(rpc_pb2.PutRequest(key=key, value=b"b"))
    req.failure.add().request_range.CopyFrom(rpc_pb2.RangeRequest(key=key))
    assert client.txn(req).succeeded
    got = client.range_(rpc_pb2.RangeRequest(key=key))
    assert got.kvs[0].value == b"b" and got.kvs[0].version == 1
