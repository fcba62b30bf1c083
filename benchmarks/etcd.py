"""The benchmark's own etcd3 stubs: the protobuf modules under ``proto/`` (a
copy, so a later PR cannot change what the yardstick sends) and the handful
of requests a kube-apiserver issues. Nothing of ``kubebrain_tpu`` is
imported here; every load-generator process stays off JAX.
"""

from __future__ import annotations

import os
import sys

import grpc
import grpc.aio

_PROTO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "proto")
if _PROTO not in sys.path:
    # protoc emits flat sibling imports (``import kv_pb2``)
    sys.path.insert(0, _PROTO)

import kv_pb2  # noqa: E402,F401
import rpc_pb2  # noqa: E402

RANGE = "/etcdserverpb.KV/Range"
TXN = "/etcdserverpb.KV/Txn"
WATCH = "/etcdserverpb.Watch/Watch"
COMPACT = "/etcdserverpb.KV/Compact"
#: kube-apiserver's compactor coordinates its replicas on this key
COMPACT_REV_KEY = b"compact_rev_key"

#: a 1.5 MB unpaged namespace list must fit one message
_CHANNEL_OPTIONS = [("grpc.max_receive_message_length", 256 << 20),
                    ("grpc.max_send_message_length", 64 << 20)]


def prefix_end(prefix: bytes) -> bytes:
    """The etcd range_end of a prefix: its last byte plus one."""
    return prefix[:-1] + bytes([prefix[-1] + 1])


class Stub:
    """One channel and its Range / Txn / Watch callables."""

    def __init__(self, target: str):
        self.channel = grpc.insecure_channel(target, options=_CHANNEL_OPTIONS)
        self.range = self.channel.unary_unary(
            RANGE, request_serializer=rpc_pb2.RangeRequest.SerializeToString,
            response_deserializer=rpc_pb2.RangeResponse.FromString)
        self.txn = self.channel.unary_unary(
            TXN, request_serializer=rpc_pb2.TxnRequest.SerializeToString,
            response_deserializer=rpc_pb2.TxnResponse.FromString)
        self.watch = self.channel.stream_stream(
            WATCH, request_serializer=rpc_pb2.WatchRequest.SerializeToString,
            response_deserializer=rpc_pb2.WatchResponse.FromString)
        self.compact = self.channel.unary_unary(
            COMPACT, request_serializer=rpc_pb2.CompactionRequest.SerializeToString,
            response_deserializer=rpc_pb2.CompactionResponse.FromString)

    def close(self) -> None:
        self.channel.close()


def range_request(start: bytes, end: bytes = b"", limit: int = 0,
                  revision: int = 0, count_only: bool = False):
    return rpc_pb2.RangeRequest(key=start, range_end=end, limit=limit,
                                revision=revision, count_only=count_only)


def _cas(key: bytes, mod_revision: int):
    """``If(mod_revision(key) == r)`` with the failure branch kube-apiserver
    sends: read the key back."""
    req = rpc_pb2.TxnRequest()
    c = req.compare.add()
    c.result, c.target, c.key, c.mod_revision = (
        rpc_pb2.Compare.EQUAL, rpc_pb2.Compare.MOD, key, mod_revision)
    req.failure.add().request_range.CopyFrom(rpc_pb2.RangeRequest(key=key))
    return req


def put_txn(key: bytes, value: bytes, mod_revision: int):
    """Create (``mod_revision`` 0) or CAS update."""
    req = _cas(key, mod_revision)
    req.success.add().request_put.CopyFrom(
        rpc_pb2.PutRequest(key=key, value=value))
    return req


def delete_txn(key: bytes, mod_revision: int):
    req = _cas(key, mod_revision)
    req.success.add().request_delete_range.CopyFrom(
        rpc_pb2.DeleteRangeRequest(key=key))
    return req


def compactor_txn(token: int, revision: int):
    """The Txn of one tick of kube-apiserver's compactor (k8s.io/apiserver
    ``storage/etcd3/compact.go``): ``If(Version(compact_rev_key) == token)
    Then(Put(compact_rev_key, revision)) Else(Get(compact_rev_key))``."""
    req = rpc_pb2.TxnRequest()
    c = req.compare.add()
    c.result, c.target, c.key, c.version = (
        rpc_pb2.Compare.EQUAL, rpc_pb2.Compare.VERSION, COMPACT_REV_KEY, token)
    req.success.add().request_put.CopyFrom(
        rpc_pb2.PutRequest(key=COMPACT_REV_KEY, value=b"%d" % revision))
    req.failure.add().request_range.CopyFrom(
        rpc_pb2.RangeRequest(key=COMPACT_REV_KEY))
    return req


def compaction_request(revision: int):
    return rpc_pb2.CompactionRequest(revision=revision)


def txn_revision(resp) -> int:
    """The revision a succeeded write Txn committed at."""
    return resp.header.revision


def watch_create(start: bytes, end: bytes, start_revision: int = 0,
                 watch_id: int = 0):
    """A watch on ``[start, end)`` (``end`` empty: the one key ``start``);
    ``watch_id`` 0 lets the server name it."""
    return rpc_pb2.WatchRequest(create_request=rpc_pb2.WatchCreateRequest(
        key=start, range_end=end, start_revision=start_revision,
        watch_id=watch_id))


def aio_watch(target: str):
    """An asyncio channel and its Watch callable, made inside the event
    loop that will read every watcher stream of a process."""
    channel = grpc.aio.insecure_channel(target, options=_CHANNEL_OPTIONS)
    return channel, channel.stream_stream(
        WATCH, request_serializer=rpc_pb2.WatchRequest.SerializeToString,
        response_deserializer=rpc_pb2.WatchResponse.FromString)
