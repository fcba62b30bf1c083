#!/usr/bin/env python3
"""The plain reference put in the program's place: a single-threaded-store,
dict-and-sorted-list MVCC server that speaks the same etcd3 subset (Range,
Txn, Watch, Compact) over the same start state, its history included. It is what ``correct`` is calibrated
against: served whole it must read correct, and with ONE guarantee of the
configuration broken (``--break``) it must read not correct.

    python benchmarks/refserver.py <config.json> <seed> <port> [--break NAME]

A Watch stream holds any number of watches, each under its ``watch_id``
(the client's, else the next of the stream's own), each on its range
(``range_end`` empty: the one key). A write finds the watches it owes an
event through an index of the watched ranges (``Ranges``), never a loop
over every watch. One asyncio event loop serves every call and every
stream (grpc's asyncio server): no watch or stream waits for a thread, and
a watch's events that came meanwhile leave in one response, as etcd's do.

Breaks (each is what a tempting shortcut in the program would do):
    stale_read     Ranges are answered from the state three revisions back
                   (a mirror published late / a read that skips the delta)
    lost_write     1 write in 200 is acknowledged and never applied
                   (an acknowledgement sent before the commit)
    dropped_event  1 watch event in 200 is not delivered (of the events
                   owed to every watch of every stream, counted together)
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
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import argparse  # noqa: E402
import asyncio  # noqa: E402

import grpc  # noqa: E402
import grpc.aio  # noqa: E402

from etcd import COMPACT_REV_KEY, kv_pb2, rpc_pb2  # noqa: E402
from state import State  # noqa: E402

BREAKS = ("stale_read", "lost_write", "dropped_event", "altered_row",
          "uncompacted", "over_compacted")
ERR_COMPACTED = "etcdserver: mvcc: required revision has been compacted"


class Compacted(Exception):
    """A read below the compact revision."""


class Ranges:
    """The watched ranges as an index: the sorted bounds of every range
    and, for each stretch between two neighbouring bounds, the watches whose
    range covers it. A key finds its watches by one bisection; the index is
    rebuilt on the first write after a watch comes or goes."""

    def __init__(self):
        self.watches: dict[int, dict] = {}
        self.bounds: list[bytes] = []
        self.cover: list[list] = []
        self.dirty = False

    def add(self, w: dict) -> None:
        self.watches[id(w)] = w
        self.dirty = True

    def remove(self, w: dict) -> None:
        self.watches.pop(id(w), None)
        self.dirty = True

    def match(self, key: bytes) -> list:
        if self.dirty:
            self._build()
        i = bisect.bisect_right(self.bounds, key) - 1
        return self.cover[i] if 0 <= i < len(self.cover) else []

    def _build(self) -> None:
        ranges: dict[tuple, list] = {}
        for w in self.watches.values():
            ranges.setdefault((w["start"], w["end"]), []).append(w)
        bounds = sorted({b for r in ranges for b in r})
        cover: list[list] = [[] for _ in bounds]
        for (start, end), ws in ranges.items():
            for i in range(bisect.bisect_left(bounds, start),
                           bisect.bisect_left(bounds, end)):
                cover[i].extend(ws)
        self.bounds, self.cover, self.dirty = bounds, cover, False


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
        self.ranges = Ranges()
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
                for w in self.ranges.match(key):
                    if (self.broken == "dropped_event"
                            and self._tick("event", 200)):
                        continue
                    w["queue"].put_nowait(("event", w["id"], rev, key, val))
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

    async def watch(self, requests, context):
        """One Watch stream: a task reads its create and cancel requests;
        this coroutine writes its responses, every event a watch is owed
        that has come meanwhile in one response, as etcd does."""
        out: asyncio.Queue = asyncio.Queue()
        mine: dict[int, dict] = {}
        reader = asyncio.get_running_loop().create_task(
            self._watch_requests(requests, out, mine))
        try:
            while True:
                items = [await out.get()]
                while not out.empty():
                    items.append(out.get_nowait())
                for resp in _responses(items):
                    await context.write(resp)
        finally:
            reader.cancel()
            with self.lock:
                for w in mine.values():
                    self.ranges.remove(w)

    async def _watch_requests(self, requests, out, mine: dict) -> None:
        """A watch is in the index, and its created response queued ahead of
        every event it is owed, under the store's lock."""
        next_id = 0
        async for req in requests:
            which = req.WhichOneof("request_union")
            if which == "create_request":
                c = req.create_request
                next_id += 1
                wid = c.watch_id if c.watch_id > 0 else next_id
                w = {"start": bytes(c.key),
                     "end": bytes(c.range_end) or bytes(c.key) + b"\0",
                     "id": wid, "queue": out}
                with self.lock:
                    mine[wid] = w
                    self.ranges.add(w)
                    out.put_nowait(("created", wid, self.rev))
            elif which == "cancel_request":
                wid = req.cancel_request.watch_id
                with self.lock:
                    if wid in mine:
                        self.ranges.remove(mine.pop(wid))
                        out.put_nowait(("canceled", wid, self.rev))


def _responses(items: list):
    """The responses for a stream's queued items, in order: a created or
    canceled one as it comes; a watch's events gathered into one response
    until the next such item (its header the last event's revision)."""
    events: dict[int, rpc_pb2.WatchResponse] = {}
    for kind, wid, rev, *kv in items:
        if kind != "event":
            yield from events.values()
            events = {}
            resp = rpc_pb2.WatchResponse(watch_id=wid, created=kind == "created",
                                         canceled=kind == "canceled")
            resp.header.revision = rev
            yield resp
            continue
        resp = events.get(wid)
        if resp is None:
            resp = events[wid] = rpc_pb2.WatchResponse(watch_id=wid)
        resp.header.revision = rev
        key, val = kv
        if val is None:
            resp.events.add(type=kv_pb2.Event.DELETE).kv.CopyFrom(
                kv_pb2.KeyValue(key=key, mod_revision=rev))
        else:
            resp.events.add(type=kv_pb2.Event.PUT).kv.CopyFrom(
                kv_pb2.KeyValue(key=key, value=val, mod_revision=rev))
    yield from events.values()


async def _refusing(context, call, req):
    """etcd's error strings for a read below the compact revision and a
    Compact above the head."""
    try:
        return call(req)
    except Compacted:
        await context.abort(grpc.StatusCode.OUT_OF_RANGE, ERR_COMPACTED)
    except ValueError:
        await context.abort(
            grpc.StatusCode.OUT_OF_RANGE,
            "etcdserver: mvcc: required revision is a future revision")


async def serve(config: dict, seed: int, port: int, broken: str = ""):
    """Start the reference on ``port`` in the running event loop; returns
    the grpc server. One thread serves every call and every watch stream:
    no stream waits for a thread."""
    store = Store(State(config, seed), broken)

    async def range_(req, ctx):
        return await _refusing(ctx, store.range, req)

    async def txn(req, _ctx):
        return store.txn(req)

    async def compact(req, ctx):
        return await _refusing(ctx, store.compact, req)

    server = grpc.aio.server(options=[("grpc.max_send_message_length", 256 << 20),
                                      ("grpc.max_receive_message_length", 64 << 20)])
    server.add_generic_rpc_handlers((
        grpc.method_handlers_generic_handler("etcdserverpb.KV", {
            "Range": grpc.unary_unary_rpc_method_handler(
                range_,
                request_deserializer=rpc_pb2.RangeRequest.FromString,
                response_serializer=rpc_pb2.RangeResponse.SerializeToString),
            "Txn": grpc.unary_unary_rpc_method_handler(
                txn,
                request_deserializer=rpc_pb2.TxnRequest.FromString,
                response_serializer=rpc_pb2.TxnResponse.SerializeToString),
            "Compact": grpc.unary_unary_rpc_method_handler(
                compact,
                request_deserializer=rpc_pb2.CompactionRequest.FromString,
                response_serializer=rpc_pb2.CompactionResponse.SerializeToString)}),
        grpc.method_handlers_generic_handler("etcdserverpb.Watch", {
            "Watch": grpc.stream_stream_rpc_method_handler(
                store.watch,
                request_deserializer=rpc_pb2.WatchRequest.FromString,
                response_serializer=rpc_pb2.WatchResponse.SerializeToString)}),
    ))
    server.add_insecure_port(f"127.0.0.1:{port}")
    await server.start()
    return server


async def _main(opts, config: dict) -> None:
    server = await serve(config, opts.seed, opts.port, opts.broken)
    print("ready", flush=True)
    await server.wait_for_termination()


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("seed", type=int)
    ap.add_argument("port", type=int)
    ap.add_argument("--break", dest="broken", default="", choices=("",) + BREAKS)
    opts = ap.parse_args(argv)
    with open(opts.config) as f:
        config = json.load(f)
    asyncio.run(_main(opts, config))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
