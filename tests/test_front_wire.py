"""The sync and the asyncio gRPC fronts forward a Range's wire bytes.

``KVService._list`` answers an unpaged default-sort list as ready
``RangeResponse`` bytes when the scanner has a wire encoder (the TPU
mirror's gather, the native store's C scan); every front serializes Range
with ``serialize_reply``, which passes bytes through. Through the real servers, the
same Range on the wire path and — ``keys_only``, sorted — on the rows path
parses to what ``range_`` holds, and ``kb_range_reply_total`` says which
path each took.
"""

import importlib.util
import os
import urllib.request

import pytest

from kubebrain_tpu.cli import build_endpoint, build_parser
from kubebrain_tpu.proto import rpc_pb2
from kubebrain_tpu.server.etcd import shim

from test_etcd_server import EtcdClient, free_port

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "bench_prom", os.path.join(ROOT, "benchmarks", "prom.py"))
prom = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(prom)

LO, HI = b"/registry/pods/ns-1/", b"/registry/pods/ns-10"
STORAGES = {"tpu": ["--storage", "tpu", "--inner-storage", "memkv",
                    "--merge-threshold", "100000"],
            "native": ["--storage", "native"]}


@pytest.fixture(scope="module", params=list(STORAGES))
def served(request):
    port, aio_port, info_port = free_port(), free_port(), free_port()
    args = build_parser().parse_args([
        "--single-node", *STORAGES[request.param], "--host", "127.0.0.1",
        "--client-port", str(port), "--aio-port", str(aio_port),
        "--peer-port", str(free_port()), "--info-port", str(info_port)])
    endpoint, backend, store = build_endpoint(args)
    endpoint.run()
    clients = {"sync": EtcdClient(f"127.0.0.1:{port}"),
               "aio": EtcdClient(f"127.0.0.1:{aio_port}")}
    c = clients["sync"]
    revs = {}
    for i in range(40):
        k = b"/registry/pods/ns-%d/pod-%04d" % (i % 2, i)
        revs[k] = c.create(k, b"v%d" % i * (1 + i % 5)).responses[
            0].response_put.header.revision
    if request.param == "tpu":
        backend.scanner._host_limit_threshold = 4
        c.range_(rpc_pb2.RangeRequest(key=LO, range_end=HI))  # builds the mirror
    # a delta row, an update and a delete after it: the overlay on the wire
    k = b"/registry/pods/ns-1/pod-0007"
    c.update(k, b"newer", revs[k])
    c.delete(b"/registry/pods/ns-1/pod-0011", revs[b"/registry/pods/ns-1/pod-0011"])
    c.create(b"/registry/pods/ns-1/pod-0008x", b"fresh")
    assert c.create(b"compact_rev_key", b"41").succeeded
    yield clients, info_port, backend
    for cl in clients.values():
        cl.close()
    endpoint.close()
    backend.close()
    store.close()


def replies(info_port) -> dict:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{info_port}/metrics", timeout=60) as r:
        snap = prom.parse(r.read().decode())
    return {p: prom.series_sum(snap, "kb_range_reply_total", path=p)
            for p in ("wire", "rows")}


def moved(info_port, before) -> dict:
    after = replies(info_port)
    return {p: after[p] - before[p] for p in after}


def held(backend, limit=0, revision=0):
    """What ``range_`` holds: the reply a front must give, as a message."""
    rev = revision or backend.current_revision()
    kvs, more = backend.scanner.range_(LO, HI, rev, limit)
    return rpc_pb2.RangeResponse(
        header=shim.header(rev), more=more, count=len(kvs),
        kvs=[shim.to_kv(kv) for kv in kvs])


@pytest.mark.parametrize("front", ["sync", "aio"])
def test_default_range_leaves_as_wire_bytes(served, front):
    clients, info_port, backend = served
    before = replies(info_port)
    got = clients[front].range_(rpc_pb2.RangeRequest(key=LO, range_end=HI))
    assert got == held(backend) and got.count == 20
    assert b"newer" in {kv.value for kv in got.kvs}
    # a limit above the host threshold cuts after the merge; a snapshot
    # read sees none of the later overlay
    cut = clients[front].range_(rpc_pb2.RangeRequest(key=LO, range_end=HI, limit=7))
    assert cut == held(backend, limit=7) and cut.more and cut.count == 7
    old = clients[front].range_(
        rpc_pb2.RangeRequest(key=LO, range_end=HI, revision=40))
    assert old == held(backend, revision=40) and old.count == 20
    assert b"newer" not in {kv.value for kv in old.kvs}
    assert moved(info_port, before) == {"wire": 3, "rows": 0}


@pytest.mark.parametrize("front", ["sync", "aio"])
def test_keys_only_and_sorted_ranges_stay_on_rows(served, front):
    clients, info_port, backend = served
    want = held(backend)
    before = replies(info_port)
    range_ = clients[front].range_
    bare = range_(rpc_pb2.RangeRequest(key=LO, range_end=HI, keys_only=True))
    assert [(kv.key, kv.mod_revision) for kv in bare.kvs] == [
        (kv.key, kv.mod_revision) for kv in want.kvs]
    assert {kv.value for kv in bare.kvs} == {b""}
    down = range_(rpc_pb2.RangeRequest(
        key=LO, range_end=HI, sort_order=rpc_pb2.RangeRequest.DESCEND))
    assert list(down.kvs) == list(reversed(want.kvs))
    by_mod = range_(rpc_pb2.RangeRequest(
        key=LO, range_end=HI, sort_target=rpc_pb2.RangeRequest.MOD,
        sort_order=rpc_pb2.RangeRequest.ASCEND))
    assert list(by_mod.kvs) == sorted(want.kvs, key=lambda kv: kv.mod_revision)
    assert bare.count == down.count == by_mod.count == want.count
    assert moved(info_port, before) == {"wire": 0, "rows": 3}


@pytest.mark.parametrize("front", ["sync", "aio"])
def test_compact_rev_key_stays_on_rows(served, front):
    clients, info_port, _backend = served
    before = replies(info_port)
    got = clients[front].range_(rpc_pb2.RangeRequest(
        key=b"compact_rev_key", range_end=b"compact_rev_kez"))
    assert [(kv.key, kv.value) for kv in got.kvs] == [(b"compact_rev_key", b"41")]
    assert moved(info_port, before) == {"wire": 0, "rows": 1}


def test_a_service_called_in_process_answers_bytes_the_serializer_passes(served):
    """No front decides: ``KVService.Range`` itself answers wire bytes for
    a request of the gated shape and a message for any other, and
    ``serialize_reply`` — what every front installs for Range — takes
    both."""
    from kubebrain_tpu.server.etcd.kv import KVService, serialize_reply

    _clients, _info_port, backend = served
    svc = KVService(backend)
    raw = svc.Range(rpc_pb2.RangeRequest(key=LO, range_end=HI), None)
    msg = svc.Range(rpc_pb2.RangeRequest(
        key=LO, range_end=HI, sort_target=rpc_pb2.RangeRequest.MOD), None)
    assert type(raw) is bytes and isinstance(msg, rpc_pb2.RangeResponse)
    assert serialize_reply(raw) is raw
    assert serialize_reply(msg) == msg.SerializeToString()
    got = rpc_pb2.RangeResponse.FromString(raw)
    assert got.count == msg.count and got.more == msg.more
    assert sorted(got.kvs, key=lambda kv: kv.mod_revision) == list(msg.kvs)
