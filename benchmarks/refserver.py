#!/usr/bin/env python3
"""The plain reference put in the program's place: a single-threaded-store,
dict-and-sorted-list MVCC server that speaks the same etcd3 subset (Range,
Txn, Watch, Compact) over the same start state, its history included. It is what ``correct`` is calibrated
against: served whole it must read correct, and with ONE guarantee of the
configuration broken (``--break``) it must read not correct.

    python benchmarks/refserver.py <config.json> <seed> <port> [--break NAME]

Breaks (each is what a tempting shortcut in the program would do):
    stale_read     Ranges are answered from the state three revisions back
                   (a mirror published late / a read that skips the delta)
    lost_write     1 write in 200 is acknowledged and never applied
                   (an acknowledgement sent before the commit)
    dropped_event  1 watch event in 200 is not delivered
    altered_row    1 Range answer in 4 carries one row with another revision
                   (an answer altered where it is produced)
    uncompacted    a Compact is acknowledged and nothing is removed: reads
                   below it are still served (a floor never set)
    over_compacted a Compact also drops the revision each table's first key
                   keeps at C (a survivor taken for a victim)
"""

from __future__ import annotations

import bisect
import json
import os
import queue
import sys
import threading
from concurrent import futures

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import grpc  # noqa: E402

from etcd import COMPACT_REV_KEY, kv_pb2, rpc_pb2  # noqa: E402
from state import State  # noqa: E402

BREAKS = ("stale_read", "lost_write", "dropped_event", "altered_row",
          "uncompacted", "over_compacted")
ERR_COMPACTED = "etcdserver: mvcc: required revision has been compacted"


class Compacted(Exception):
    """A read below the compact revision."""


class Store:
    def __init__(self, state: State, broken: str = ""):
        self.lock = threading.Lock()
        self.broken = broken
        self.rev = state.head_revision
        self.compacted = 0
        self.state = state
        # key -> [(mod_revision, value or None)], oldest first: the start
        # state's every revision, a value made when it is read
        self.hist: dict[bytes, list] = {}
        for rev, (verb, t, i, ver, _guard) in enumerate(state.start_ops(), 1):
            self.hist.setdefault(t.key(i), []).append(
                (rev, None if verb == "delete" else (t, i, ver)))
        self.keys = sorted(self.hist)
        self.watchers: list = []
        self.counter = {"write": 0, "event": 0, "range": 0}
        self.base = self.rev

    def _tick(self, what: str, every: int) -> bool:
        self.counter[what] += 1
        return self.counter[what] % every == 0

    def read_revision(self) -> int:
        if self.broken != "stale_read":
            return self.rev
        return max(self.base, self.rev - 3)

    def _value(self, val):
        return self.state.value(*val) if isinstance(val, tuple) else val

    def range(self, req):
        with self.lock:
            head = self.read_revision()
            at = req.revision or head
            if req.revision and req.revision < self.compacted:
                raise Compacted()
            if not req.range_end:
                keys = [bytes(req.key)] if req.key in self.hist else []
            else:
                lo = bisect.bisect_left(self.keys, req.key)
                hi = bisect.bisect_left(self.keys, req.range_end)
                keys = self.keys[lo:hi]
            rows = []
            for k in keys:
                cur = None
                for rev, val in self.hist[k]:
                    if rev > at:
                        break
                    cur = (rev, val)
                if cur and cur[1] is not None:
                    rows.append((k, cur[0], self._value(cur[1])))
            alter = (self.broken == "altered_row" and rows
                     and self._tick("range", 4))
        resp = rpc_pb2.RangeResponse(count=len(rows))
        resp.header.revision = head
        if req.count_only:
            return resp
        if req.limit and len(rows) > req.limit:
            rows, resp.more = rows[:req.limit], True
        for n, (k, rev, val) in enumerate(rows):
            if alter and n == len(rows) // 2:
                rev -= 1
            resp.kvs.add(key=k, value=val, mod_revision=rev, create_revision=rev)
        return resp

    def txn(self, req):
        c = req.compare[0]
        key = bytes(c.key)
        with self.lock:
            chain = self.hist.get(key)
            cur = chain[-1] if chain and chain[-1][1] is not None else None
            # the compactor's Version guard: its token is the key's version
            # here (the number of its writes), a mod_revision elsewhere
            version = c.target == rpc_pb2.Compare.VERSION
            have = (len(chain) if cur else 0) if version else (
                cur[0] if cur else 0)
            if have != (c.version if version else c.mod_revision):
                resp = rpc_pb2.TxnResponse(succeeded=False)
                resp.header.revision = self.rev
                rr = resp.responses.add().response_range
                if cur:
                    rr.kvs.add(key=key, value=self._value(cur[1]),
                               mod_revision=cur[0], version=len(chain))
                return resp
            self.rev += 1
            rev = self.rev
            op = req.success[0]
            put = op.WhichOneof("request") == "request_put"
            val = bytes(op.request_put.value) if put else None
            if not (self.broken == "lost_write" and self._tick("write", 200)):
                if chain is None:
                    self.hist[key] = chain = []
                    bisect.insort(self.keys, key)
                chain.append((rev, val))
                for w in self.watchers:
                    if w["start"] <= key < w["end"]:
                        if (self.broken == "dropped_event"
                                and self._tick("event", 200)):
                            continue
                        w["queue"].put((rev, key, val))
        resp = rpc_pb2.TxnResponse(succeeded=True)
        resp.header.revision = rev
        if put:
            resp.responses.add().response_put.header.revision = rev
        else:
            resp.responses.add().response_delete_range.deleted = 1
        return resp

    def compact(self, req):
        """etcd's Compact: each key keeps its latest revision at or below C,
        unless it is a tombstone, which goes too; reads below C refuse."""
        c = req.revision
        with self.lock:
            if c > self.rev:
                raise ValueError("a future revision")
            if self.broken != "uncompacted" and c > self.compacted:
                self.compacted = c
                # over_compacted: the first key of each table loses its
                # survivor too
                spared = {t.prefix for t in self.state.tables.values()}
                for k in self.keys:
                    chain = self.hist[k]
                    keep = bisect.bisect_right(chain, c, key=lambda e: e[0]) - 1
                    if keep < 0 or k == COMPACT_REV_KEY:
                        continue
                    drop = keep + (chain[keep][1] is None)
                    prefix = next((p for p in spared if k.startswith(p)), None)
                    if (self.broken == "over_compacted" and prefix
                            and drop == keep):
                        spared.discard(prefix)
                        drop += 1
                    del chain[:drop]
        resp = rpc_pb2.CompactionResponse()
        resp.header.revision = self.rev
        return resp

    def watch(self, requests, context):
        create = next(requests).create_request
        w = {"start": bytes(create.key), "end": bytes(create.range_end),
             "queue": queue.SimpleQueue()}
        with self.lock:
            self.watchers.append(w)
            created = self.rev
        context.add_callback(lambda: w["queue"].put(None))
        first = rpc_pb2.WatchResponse(created=True)
        first.header.revision = created
        yield first
        while True:
            item = w["queue"].get()
            if item is None:
                return
            rev, key, val = item
            resp = rpc_pb2.WatchResponse()
            resp.header.revision = rev
            if val is None:
                resp.events.add(type=kv_pb2.Event.DELETE).kv.CopyFrom(
                    kv_pb2.KeyValue(key=key, mod_revision=rev))
            else:
                resp.events.add(type=kv_pb2.Event.PUT).kv.CopyFrom(
                    kv_pb2.KeyValue(key=key, value=val, mod_revision=rev))
            yield resp


def _refusing(context, call, req):
    """etcd's error strings for a read below the compact revision and a
    Compact above the head."""
    try:
        return call(req)
    except Compacted:
        context.abort(grpc.StatusCode.OUT_OF_RANGE, ERR_COMPACTED)
    except ValueError:
        context.abort(grpc.StatusCode.OUT_OF_RANGE,
                      "etcdserver: mvcc: required revision is a future revision")


def serve(config: dict, seed: int, port: int, broken: str = ""):
    """Start the reference on ``port``; returns the grpc server."""
    store = Store(State(config, seed), broken)
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=64),
                         options=[("grpc.max_send_message_length", 256 << 20),
                                  ("grpc.max_receive_message_length", 64 << 20)])
    server.add_generic_rpc_handlers((
        grpc.method_handlers_generic_handler("etcdserverpb.KV", {
            "Range": grpc.unary_unary_rpc_method_handler(
                lambda req, ctx: _refusing(ctx, store.range, req),
                request_deserializer=rpc_pb2.RangeRequest.FromString,
                response_serializer=rpc_pb2.RangeResponse.SerializeToString),
            "Txn": grpc.unary_unary_rpc_method_handler(
                lambda req, _ctx: store.txn(req),
                request_deserializer=rpc_pb2.TxnRequest.FromString,
                response_serializer=rpc_pb2.TxnResponse.SerializeToString),
            "Compact": grpc.unary_unary_rpc_method_handler(
                lambda req, ctx: _refusing(ctx, store.compact, req),
                request_deserializer=rpc_pb2.CompactionRequest.FromString,
                response_serializer=rpc_pb2.CompactionResponse.SerializeToString)}),
        grpc.method_handlers_generic_handler("etcdserverpb.Watch", {
            "Watch": grpc.stream_stream_rpc_method_handler(
                store.watch,
                request_deserializer=rpc_pb2.WatchRequest.FromString,
                response_serializer=rpc_pb2.WatchResponse.SerializeToString)}),
    ))
    server.add_insecure_port(f"127.0.0.1:{port}")
    server.start()
    return server


def main(argv: list[str]) -> int:
    config_path, seed, port, *rest = argv
    broken = rest[1] if rest[:1] == ["--break"] else ""
    if broken and broken not in BREAKS:
        raise SystemExit(f"unknown break {broken!r}; one of {BREAKS}")
    with open(config_path) as f:
        config = json.load(f)
    server = serve(config, int(seed), int(port), broken)
    print("ready", flush=True)
    try:
        server.wait_for_termination()
    except KeyboardInterrupt:
        server.stop(0)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
