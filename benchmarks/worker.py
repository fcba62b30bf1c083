#!/usr/bin/env python3
"""One load-generator process: THE general traffic generator. It reads one
stream of a workload file (``workloads/<cell>.json``) — a closed loop of N
callers or an open loop at a fixed rate, over a weighted list of operations
on the configuration's tables — or a list of watches, and drives the served
etcd3 front with it. A new traffic mix is a new data file, never new code;
an operation is ``ops/<op>.py`` and a key order ``orders/<order>.py``, found
by the name the data file gives, so a new one is a new file.

Protocol with ``run.py`` (lines on stdin / stdout):
    -> ready                      connected, state built
    <- start <t>                  begin the warm-up at monotonic time t
    -> warmed                     this process's share of the mix's
                                  ``warmup_writes`` is sent and acknowledged
    <- window <t0> <t1>           requests due in [t0, t1) are the window's
    <- finish <rev>...            watchers: wait for these sentinel revisions
    -> done                       drained, records written to ``out``

The warm-up is the stream's own traffic, but its WRITES are counted, not
timed: exactly ``warmup_writes`` of them, then none until the window opens,
so the server's delta holds the same number of rows in every run at that
moment (the merge-phase rule, README.md). An open loop's schedule starts
anew at ``t0``: the window holds the same requests at the same offsets
whatever the warm-up took.

Everything is timed on ``time.monotonic()``, which all processes of one
machine share. The process never imports JAX (asserted on exit).
"""

from __future__ import annotations

import json
import os
import pickle
import queue
import random
import sys
import threading
import time
import zlib

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import grpc  # noqa: E402

import etcd  # noqa: E402
import plugin  # noqa: E402
from state import State  # noqa: E402

RPC_TIMEOUT_S = 60.0
DRAIN_S = 60.0
RANGE, TXN, COMPACT = 0, 1, 2


class Rec:
    """One RPC as the generator saw it (a compactor's tick: its Txn and
    its Compact, timed together from the tick's due time)."""

    __slots__ = ("family", "op", "due", "sent", "done", "ok", "rev", "key_id",
                 "ver", "rows", "walk", "req", "err", "dead", "count_only")

    def __init__(self, family, op, due):
        self.family, self.op, self.due = family, op, due
        self.sent = self.done = 0.0
        self.ok = False
        self.rev = 0
        self.key_id = -1
        self.ver = 0
        self.rows = 0
        self.walk = None
        self.req = None
        self.err = ""
        self.dead = False         # a write that leaves its key deleted
        self.count_only = False

    def row(self):
        return (self.family, self.op, self.due, self.sent, self.done, self.ok,
                self.rev, self.key_id, self.ver, self.rows, self.err, self.dead)


class Control:
    """What ``run.py`` has said so far."""

    def __init__(self):
        self.start = None
        self.window = None
        self.finish = None
        self._cond = threading.Condition()
        threading.Thread(target=self._read, daemon=True).start()

    def _read(self):
        for line in sys.stdin:
            word, *args = line.split()
            with self._cond:
                if word == "start":
                    self.start = float(args[0])
                elif word == "window":
                    self.window = (float(args[0]), float(args[1]))
                elif word == "finish":
                    self.finish = [int(a) for a in args]
                self._cond.notify_all()

    def wait(self, attr: str, timeout: float):
        with self._cond:
            self._cond.wait_for(lambda: getattr(self, attr) is not None, timeout)
            return getattr(self, attr)


def say(word: str) -> None:
    sys.stdout.write(word + "\n")
    sys.stdout.flush()


# ------------------------------------------------------------------ traffic
class Traffic:
    """A closed or open loop over the stream's weighted operations."""

    def __init__(self, spec: dict):
        self.spec = spec
        self.stream = spec["stream"]
        self.state = State(spec["config"], spec["seed"])
        self.rng = random.Random(spec["seed"] * 1009 + spec["worker"])
        self.stub = etcd.Stub(spec["target"])
        self.done_q: queue.SimpleQueue = queue.SimpleQueue()
        self.inflight = 0
        self.recs: list[Rec] = []
        self.samples: list = []
        self.sample_prob = float(spec.get("sample_prob", 0.0))
        # the keys this writer owns: index % writers == writer
        self.writers = max(1, int(spec["writers"]))
        self.writer = int(spec["writer"])
        self.busy: set[int] = set()
        self.warming = True
        self.warm_budget = int(spec.get("warmup_writes", 0))
        self.writes_inflight = 0
        self.warm_failed = 0
        self.pools: dict[str, dict] = {}
        for op in self.stream["ops"]:
            if "table" in op:
                self._pool(op["table"])
        # the compactor's memory between ticks, as compact.go keeps it
        self.compact_token = 0
        self.compact_rev = 0
        self.window0 = None
        # every block of the schedule holds the same operations, in an order
        # drawn from the seed
        self.block = [i for i, op in enumerate(self.stream["ops"])
                      for _ in range(int(op.get("weight", 1)))]
        self.slots: list[int] = []

    def _pool(self, name: str) -> dict:
        if name in self.pools:
            return self.pools[name]
        t = self.state.tables[name]
        st = self.state
        owned = [i for i in range(self.writer, t.ids, self.writers)
                 if st.live[name][i]]
        rnd = random.Random(self.spec["seed"] * 7 + self.writer)
        rnd.shuffle(owned)
        pool = {"table": t, "live": owned, "cursor": 0,
                "rev": {i: int(st.rev[name][i]) for i in owned},
                "ver": {i: int(st.ver[name][i]) for i in owned},
                # fresh indices stay in this writer's residue class, after
                # every index the start state used
                "next_new": t.ids + (self.writer - t.ids) % self.writers,
                "cycle": sorted(owned)}
        self.pools[name] = pool
        return pool

    # ---------------------------------------------------------- operations
    def next_op(self, due: float) -> bool:
        """Send the schedule's next operation; False where its table has no
        key left to write."""
        if not self.slots:
            self.slots = self.block[:]
            self.rng.shuffle(self.slots)
        op = self.stream["ops"][self.slots.pop()]
        mod = plugin.load(os.path.join(HERE, "ops"), op["op"])
        if self.warming and mod.WRITES:
            if self.warm_budget <= 0:
                return False
            self.warm_budget -= 1
        return mod.issue(self, op, self.pools.get(op.get("table")), due)

    def pick(self, pool: dict, order: str, remove: bool):
        """A live key of this writer's with no write in flight, in the named
        order; None where there is none."""
        return plugin.load(os.path.join(HERE, "orders"), order).pick(
            self, pool, remove)

    def next_free(self, pool: dict, keys: list, remove: bool):
        """The next key of ``keys`` after the pool's cursor that is live and
        has no write in flight."""
        for _ in range(len(keys)):
            pool["cursor"] = (pool["cursor"] + 1) % len(keys)
            i = keys[pool["cursor"]]
            if i not in self.busy and i in pool["rev"]:
                if remove:
                    del keys[pool["cursor"]]
                return i
        return None

    def send_range(self, due, kind, start, limit=0, revision=0, walk=None,
                   end=None, count_only=False):
        rec = Rec(RANGE, kind, due)
        rec.walk, rec.count_only = walk, count_only
        rec.ver = revision    # non-zero: a page pinned to its list's revision
        end = etcd.prefix_end(start) if end is None else end
        rec.req = (start, end, limit, revision)
        self._send(rec, self.stub.range, etcd.range_request(
            start, end, limit, revision, count_only=count_only))

    def send_write(self, due, kind, pool, i, ver, guard, delete=False):
        t = pool["table"]
        rec = Rec(TXN, kind, due)
        rec.key_id, rec.ver, rec.dead = self.state.key_id(t, i), ver, delete
        self.busy.add(i)
        self.writes_inflight += 1
        if delete:
            req = etcd.delete_txn(t.key(i), guard)
        else:
            req = etcd.put_txn(t.key(i), self.state.value(t, i, ver), guard)
        rec.req = (pool, i)
        self._send(rec, self.stub.txn, req)

    def send_compact(self, due, kind, interval_s):
        """One compactor tick (``ops/compact.py``): its Txn now, its Compact
        when the Txn has succeeded (``_compactor_leg``). The target: the
        revision the previous tick's Txn returned or, on the window's first
        tick, the history's head ``interval_s`` before the due time (the
        window opens at the history's end, at nominal times)."""
        target = self.compact_rev or self.state.head_at(
            self.state.history_seconds + round(due - self.window0, 3)
            - interval_s)
        rec = Rec(COMPACT, kind, due)
        rec.rev, rec.req = target, ("txn", target)
        self._send(rec, self.stub.txn,
                   etcd.compactor_txn(self.compact_token, target))

    def _compactor_leg(self, rec: Rec, fut) -> bool:
        """A compactor tick's leg has landed; True where the Compact is now
        in flight. As compact.go: the Txn's revision is the next tick's
        target either way; a refused Txn takes the key's version as the next
        token and compacts nothing, a succeeded one adds one to the token."""
        leg, target = rec.req
        rec.req = None
        try:
            resp = fut.result()
        except grpc.RpcError as e:
            rec.err = f"{e.code().name}: {e.details()}"[:200]
            return False
        if leg == "compact":
            rec.ok, rec.rows = True, resp.header.revision
            return False
        self.compact_rev = etcd.txn_revision(resp)
        if not resp.succeeded:
            kvs = resp.responses[0].response_range.kvs if resp.responses else ()
            self.compact_token = kvs[0].version if kvs else 0
            rec.err = "refused"
            return False
        self.compact_token += 1
        rec.ver = self.compact_rev
        rec.req = ("compact", target)
        self._send(rec, self.stub.compact, etcd.compaction_request(target))
        return True

    def _send(self, rec: Rec, call, req):
        self.inflight += 1
        rec.sent = rec.sent or time.monotonic()
        fut = call.future(req, timeout=RPC_TIMEOUT_S)
        fut.add_done_callback(lambda f, rec=rec: self._landed(rec, f))

    def _landed(self, rec: Rec, fut):
        rec.done = time.monotonic()   # on gRPC's thread: the reply is parsed
        self.done_q.put((rec, fut))

    # ---------------------------------------------------------- completion
    def handle(self, rec: Rec, fut) -> None:
        self.inflight -= 1
        if rec.family == COMPACT:
            if not self._compactor_leg(rec, fut):
                self.recs.append(rec)
            return
        self.recs.append(rec)
        if rec.family == TXN:
            self.writes_inflight -= 1
        try:
            resp = fut.result()
        except grpc.RpcError as e:
            rec.err = f"{e.code().name}: {e.details()}"[:200]
            self.warm_failed += self.warming
            if rec.family == TXN:
                # maybe applied: never touch the key again
                pool, i = rec.req
                pool["rev"].pop(i, None)
            rec.req = None
            return
        if rec.family == TXN:
            pool, i = rec.req
            rec.req = None
            self.busy.discard(i)
            if not resp.succeeded:
                rec.err = "refused"
                self.warm_failed += self.warming
                pool["rev"].pop(i, None)
                return
            rec.ok, rec.rev = True, etcd.txn_revision(resp)
            if rec.dead:
                pool["rev"].pop(i, None)
            else:
                if i not in pool["rev"]:     # a key the table never held
                    pool["live"].append(i)
                pool["rev"][i], pool["ver"][i] = rec.rev, rec.ver
            return
        rec.ok, rec.rev = True, resp.header.revision
        rec.rows = resp.count if rec.count_only else len(resp.kvs)
        start, end, limit, revision = rec.req
        rec.req = None
        # a seeded sample of the answers, the first always in it
        if self.rng.random() < self.sample_prob or not self.samples:
            self.samples.append({
                "sent": rec.sent, "start": start, "end": end, "limit": limit,
                "revision": revision, "header": rec.rev, "more": resp.more,
                "count": resp.count if rec.count_only else None,
                "rows": [(kv.key, kv.mod_revision, zlib.crc32(kv.value))
                         for kv in resp.kvs]})
        walk = rec.walk
        if walk is not None and resp.more and resp.kvs:
            # the next page of the same list, pinned to the first page's
            # revision, is due the moment this one lands
            walk["revision"] = walk["revision"] or rec.rev
            self.send_range(rec.done, rec.op, resp.kvs[-1].key + b"\0",
                            walk["page"], walk["revision"], walk,
                            end=walk["end"])

    # ----------------------------------------------------------------- loop
    def run(self, control: Control) -> None:
        begin = control.wait("start", 600.0)
        if begin is None:
            raise RuntimeError("never told to start")
        closed = self.stream["loop"] == "closed"
        clients = int(self.spec.get("clients", 0))
        # the warm-up may run more callers than the window (``warm_clients``)
        warm_clients = int(self.spec.get("warm_clients", 0)) or clients
        gap = 1.0 / float(self.spec["rate"]) if not closed else 0.0
        phase = gap * float(self.spec.get("phase", 0.0))
        next_due = begin + phase
        warmed_said = False
        drain_deadline = None
        while True:
            now = time.monotonic()
            window = control.window
            if self.warming and window is not None and now >= window[0]:
                # the window opens: its schedule starts at t0, not where the
                # warm-up's left off
                self.warming = False
                self.window0 = window[0]
                next_due = window[0] + phase
            stopping = window is not None and now >= window[1]
            if now >= begin:
                if closed:
                    limit = warm_clients if self.warming else clients
                    while not stopping and self.inflight < limit:
                        if not self.next_op(time.monotonic()):
                            break
                else:
                    # every request due before the window's end is sent,
                    # however late this process got to it; the warm-up's
                    # schedule ends where the window's begins
                    end = window[1 if not self.warming else 0] if window \
                        else float("inf")
                    while next_due <= now and next_due < end:
                        self.next_op(next_due)
                        next_due += gap
                    stopping = stopping and next_due >= end
            if (not warmed_said and now >= begin and self.warm_budget <= 0
                    and self.writes_inflight == 0):
                warmed_said = True
                say("warmed")
            if stopping:
                if self.inflight == 0:
                    return
                drain_deadline = drain_deadline or now + DRAIN_S + RPC_TIMEOUT_S
                if now > drain_deadline:
                    raise RuntimeError(f"{self.inflight} RPCs never returned")
            if closed or stopping or now < begin:
                wait = 0.05
            else:
                wait = max(0.0, min(0.05, next_due - time.monotonic()))
            if self.warming and window is not None:
                wait = max(0.0, min(wait, window[0] - time.monotonic()))
            try:
                self.handle(*self.done_q.get(timeout=wait) if wait
                            else self.done_q.get_nowait())
            except queue.Empty:
                continue
            while True:   # whatever else has landed meanwhile
                try:
                    self.handle(*self.done_q.get_nowait())
                except queue.Empty:
                    break

    def dump(self, path: str) -> None:
        with open(path, "wb") as f:
            pickle.dump({"kind": "traffic", "stream": self.stream["name"],
                         "judged": bool(self.stream.get("judged", True)),
                         "loop": self.stream["loop"],
                         "recs": [r.row() for r in self.recs],
                         "warm_failed": self.warm_failed,
                         "warm_unsent": self.warm_budget,
                         "samples": self.samples}, f)


# ----------------------------------------------------------------- watchers
class Watchers:
    """The stream's watches, one gRPC stream and one reader thread each."""

    def __init__(self, spec: dict):
        self.spec = spec
        self.state = State(spec["config"], spec["seed"])
        self.stub = etcd.Stub(spec["target"])
        self.watches = []
        for w in spec["stream"]["watch"]:
            t = self.state.tables[w["table"]]
            for _ in range(int(w["count"])):
                self.watches.append({
                    "table": t.name, "start": t.prefix,
                    "end": etcd.prefix_end(t.prefix), "created": None,
                    "events": [], "error": "", "requests": queue.SimpleQueue()})

    def open(self) -> None:
        for w in self.watches:
            w["requests"].put(etcd.watch_create(w["start"], w["end"]))
            w["thread"] = threading.Thread(target=self._read, args=(w,),
                                           daemon=True)
            w["thread"].start()
        deadline = time.monotonic() + 60.0
        while any(w["created"] is None and not w["error"] for w in self.watches):
            if time.monotonic() > deadline:
                raise RuntimeError("a watch was never created")
            time.sleep(0.01)

    def _read(self, w: dict) -> None:
        events = w["events"]
        try:
            for resp in self.stub.watch(iter(w["requests"].get, None)):
                t = time.monotonic()
                if resp.created:
                    w["created"] = resp.header.revision
                if resp.canceled:
                    w["error"] = f"canceled: {resp.cancel_reason}"
                    return
                for ev in resp.events:
                    events.append((ev.kv.mod_revision, int(ev.type),
                                   zlib.crc32(ev.kv.key),
                                   zlib.crc32(ev.kv.value), t))
        except grpc.RpcError as e:
            if e.code() != grpc.StatusCode.CANCELLED:
                w["error"] = f"{e.code().name}: {e.details()}"[:200]

    def run(self, control: Control) -> None:
        finish = control.wait("finish", 3600.0)
        if finish is None:
            raise RuntimeError("never told to finish")
        # the sentinel writes come last, so in revision order everything
        # before them has been delivered once they are seen
        want = dict(zip(sorted({w["table"] for w in self.watches}), finish))
        deadline = time.monotonic() + DRAIN_S
        for w in self.watches:
            while not w["error"] and time.monotonic() < deadline and not (
                    w["events"] and w["events"][-1][0] >= want[w["table"]]):
                time.sleep(0.01)
        self.stub.close()

    def dump(self, path: str) -> None:
        with open(path, "wb") as f:
            pickle.dump({"kind": "watch", "watches": [
                {k: w[k] for k in ("table", "created", "events", "error")}
                for w in self.watches]}, f)


def main(argv: list[str]) -> int:
    with open(argv[0]) as f:
        spec = json.load(f)
    control = Control()
    if "watch" in spec["stream"]:
        job = Watchers(spec)
        job.open()
    else:
        job = Traffic(spec)
        # the channel connects before the clock starts
        grpc.channel_ready_future(job.stub.channel).result(timeout=60.0)
    say("ready")
    if "watch" in spec["stream"]:
        say("warmed")      # a watcher writes nothing
    job.run(control)
    job.dump(spec["out"])
    if "jax" in sys.modules:
        raise RuntimeError("a load generator imported jax")
    say("done")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
