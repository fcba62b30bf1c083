"""The watches of a mix: the key range each watch covers, how the watches
share gRPC streams and how the streams are dealt over generator processes
(``plan``, ``share``); the compact record of the events a stream received
(``FIELDS``, ``pack``); and what the drain waits for (``targets``).

A ``watch`` stream of a traffic file is a list of entries, each
``{"table", "count"}`` and optionally:

- ``scope``: ``"table"`` (the default: the table's whole prefix, as every
  watch was before there was a scope), ``"namespace"`` (one namespace's
  ``ns_prefix``; the table's namespaces are dealt over the entry's watches
  in an order drawn from the seed) or ``"key"`` (one object's key, drawn
  from the seed among the start state's live objects);
- ``per_stream``: how many of the entry's watches share one ``Watch``
  stream, each under a ``watch_id`` of its own (default 1: a stream a
  watch, and the create request carries no ``watch_id``, byte for byte what
  it was).

Every process of a run computes the same plan from the seed: ``run.py``
for the drain, and each watcher process for its share of the streams
(``share``).
"""

from __future__ import annotations

import bisect

import numpy as np

from etcd import prefix_end

SCOPES = ("table", "namespace", "key")

#: one received event as recorded: the watch's index in its process, the
#: event's mod_revision, its type (0 put, 1 delete), crc32 of its key and
#: of its value, and the monotonic instant its response was parsed
FIELDS = (("watch", "i", np.int32), ("rev", "q", np.int64),
          ("type", "b", np.int8), ("key", "I", np.uint32),
          ("value", "I", np.uint32), ("arrival", "d", np.float64))
#: the bytes one event costs in a generator's record
EVENT_BYTES = sum(np.dtype(dt).itemsize for _n, _c, dt in FIELDS)


def plan(stream: dict, state) -> list[list[dict]]:
    """The gRPC streams of one ``watch`` stream of a mix, in the file's
    order: each a list of watches ``{"table", "scope", "start", "end"}``
    (``end`` is the range's exclusive end; a key's is the key + NUL)."""
    out: list[list[dict]] = []
    for n, entry in enumerate(stream["watch"]):
        t = state.tables[entry["table"]]
        scope = entry.get("scope", "table")
        count, per = int(entry["count"]), int(entry.get("per_stream", 1))
        if per < 1 or count < 1:
            raise ValueError(f"watch entry {n}: count and per_stream are >= 1")
        rng = np.random.default_rng([state.seed, 0x7761, n])
        if scope == "table":
            ranges = [(t.prefix, prefix_end(t.prefix))] * count
        elif scope == "namespace":
            order = rng.permutation(t.namespaces)
            ranges = [(p, prefix_end(p)) for p in (
                t.ns_prefix(int(order[j % t.namespaces])) for j in range(count))]
        elif scope == "key":
            keys = [t.key(int(i)) for i in rng.choice(
                np.flatnonzero(state.live[t.name][:t.count]), size=count)]
            ranges = [(k, k + b"\0") for k in keys]
        else:
            raise ValueError(f"watch entry {n}: scope {scope!r}, one of {SCOPES}")
        for s in range(0, count, per):
            out.append([{"table": t.name, "scope": scope, "start": a, "end": b}
                        for a, b in ranges[s:s + per]])
    return out


def share(stream: dict, state, proc: int, procs: int) -> list[list[dict]]:
    """The streams generator process ``proc`` of ``procs`` opens: every
    ``procs``-th, from its own index on."""
    return plan(stream, state)[proc::procs]


def pack(columns: dict) -> dict:
    """The record's columns as arrays of their types (``FIELDS``)."""
    return {name: np.asarray(columns.get(name, ()), dt) for name, _c, dt in FIELDS}


def targets(streams: list[list[dict]], sentinels: dict, last: dict) -> list[int]:
    """For each watch of one process, in order, the revision its drain
    waits to have seen: a whole table's watch its table's sentinel write
    (``run.py`` writes one a table that has such a watch, after every other
    write), any other the last acknowledged write of its own range
    (``last``, by ``(start, end)``; 0 where none lies in it)."""
    return [sentinels[w["table"]] if w["scope"] == "table"
            else last.get((w["start"], w["end"]), 0)
            for s in streams for w in s]


def last_acked(writes, ranges) -> dict:
    """``(start, end) -> the highest revision`` of the acknowledged
    ``writes`` ((key, revision) pairs) whose key lies in the range, for each
    of ``ranges``; a range no write lies in is left out."""
    keyed = sorted(writes)
    keys = [k for k, _r in keyed]
    out = {}
    for start, end in set(ranges):
        lo, hi = bisect.bisect_left(keys, start), bisect.bisect_left(keys, end)
        if hi > lo:
            out[(start, end)] = max(r for _k, r in keyed[lo:hi])
    return out
