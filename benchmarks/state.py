"""The data of a configuration, made from the seed: keys, values and the
state a run starts from. The loader writes it, the generators address it and
the reference (``check.py``) predicts every answer from it, so nothing here
reads anything the program made.

A configuration's ``tables`` give each keyspace: a key pattern, an object
count, a value-size distribution and, optionally, the churn that leaves
history and tombstones behind. The start state is a fixed sequence of writes
(creates in index order table by table, then per table two rounds of updates
and one of deletes); a single sequencer deals revisions 1, 2, 3, ... so every
row's ``mod_revision`` is known before the store exists.

**History** (README.md, "A store's history"). A table may declare a
``history``: the configuration's own write stream, ``{"update_per_s",
"create_per_s", "delete_per_s"}``, replayed for the configuration's
``history_seconds`` after those writes, at nominal times — the j-th write of
a verb at ``j / rate`` s, the verbs of all tables merged by time (ties in
table order, then create, update, delete). Updates renew the table's objects
in index order, round and round (a Lease a node every period); creates take
new indices after the last one; deletes take the start state's objects in an
order drawn from the seed (each is live when deleted). So a key may hold any
number of revisions, and still every one is known before the store exists.
The history is arrays, never a Python object a row: ``at`` / ``at_many``
answer a key's (version, mod_revision) at any revision, ``head_at`` the head
revision at a second of the history. A configuration without ``history``
makes the same plan, byte for byte, as before there was one
(``tests/test_history.py``).
"""

from __future__ import annotations

import hashlib
import zlib
from dataclasses import dataclass

import numpy as np

_POOL_BYTES = 4 << 20
_MAX_VALUE = 1 << 16
#: a start event's sort key is ``index * _KEY + revision``
_KEY = 1 << 38
#: the history's verbs, in the order they are merged at one instant
VERBS = ("create", "update", "delete")


@dataclass
class Table:
    name: str
    pattern: str
    prefix: bytes          # every key of the table starts with it
    ns_pattern: str        # the prefix of one namespace
    count: int
    namespaces: int
    sizes: np.ndarray      # value bytes of object i (i < count)
    offset: int            # first global key id of this table
    hash_chars: int = 0    # width of the pattern's ``{h}`` field, if any
    salt: bytes = b""
    ids: int = 0           # indices the start state used: count + history creates

    def key(self, i: int) -> bytes:
        """Key of object i. ``{h}`` is a random suffix: hex of a hash of
        (seed, i), so keys land all over the keyspace and none repeats."""
        h = ""
        if self.hash_chars:
            h = hashlib.blake2b(b"%d" % i, key=self.salt,
                                digest_size=32).hexdigest()[:self.hash_chars]
        return self.pattern.format(i=i, ns=i % self.namespaces, h=h).encode()

    def ns_prefix(self, ns: int) -> bytes:
        return self.ns_pattern.format(ns=ns).encode()

    def size(self, i: int) -> int:
        return int(self.sizes[i % self.count])


#: a namespace holds the objects i with i % namespaces == ns, in key order
#: only while the object field is zero-padded wider than any index in use
SPACE = 10_000_000   # key ids per table: table t owns [t*SPACE, (t+1)*SPACE)


class State:
    """Tables, the value pool and the start state of one seed."""

    def __init__(self, config: dict, seed: int):
        self.seed = int(seed)
        rng = np.random.default_rng([self.seed, 0x6b62])
        self.pool = rng.bytes(_POOL_BYTES)
        self.tables: dict[str, Table] = {}
        self.plan: list[tuple[str, str, np.ndarray]] = []
        for t, spec in enumerate(config["tables"]):
            n = int(spec["count"])
            self.tables[spec["name"]] = Table(
                spec["name"], spec["key"], spec["prefix"].encode(),
                spec["ns_prefix"], n, int(spec.get("namespaces", 1)),
                _sizes(spec["value_bytes"], n, rng), t * SPACE,
                int(spec.get("hash_chars", 0)), b"kb-%d" % self.seed, n)
        # creates first, table by table; then each table's churn
        for spec in config["tables"]:
            self.plan.append(("create", spec["name"],
                              np.arange(int(spec["count"]))))
        for spec in config["tables"]:
            n = int(spec["count"])
            upd = int(n * float(spec.get("updated_twice_share", 0)))
            dele = int(n * float(spec.get("deleted_share", 0)))
            if upd:
                chosen = rng.choice(n, size=upd, replace=False)
                self.plan += [("update", spec["name"], chosen)] * 2
            if dele:
                self.plan.append(("delete", spec["name"],
                                  rng.choice(n, size=dele, replace=False)))
        self.history_seconds = float(config.get("history_seconds", 0))
        self.hist = _history(config, self.seed, self.history_seconds)
        for t, name in enumerate(self.tables):
            mine = self.hist["table"] == t
            self.tables[name].ids += int(
                (mine & (self.hist["verb"] == VERBS.index("create"))).sum())
        # the start state, by replaying the plan with revisions 1, 2, 3, ...
        # and then the history; each table's events kept as arrays
        self.ver = {n: np.zeros(t.ids, np.int32) for n, t in self.tables.items()}
        self.rev = {n: np.zeros(t.ids, np.int64) for n, t in self.tables.items()}
        self.live = {n: np.zeros(t.ids, bool) for n, t in self.tables.items()}
        events: dict[str, list] = {n: [] for n in self.tables}
        rev = 0
        for verb, name, idx in self.plan:
            revs = rev + 1 + np.arange(len(idx))
            rev += len(idx)
            if verb == "create":
                self.live[name][idx] = True
            elif verb == "update":
                self.ver[name][idx] += 1
            else:
                self.live[name][idx] = False
            self.rev[name][idx] = revs
            events[name].append((idx, revs, self.ver[name][idx].copy(),
                                 np.full(len(idx), verb != "delete")))
        self.hist["rev"] = rev + 1 + np.arange(len(self.hist["idx"]))
        for t, name in enumerate(self.tables):
            mine = self.hist["table"] == t
            if not mine.any():
                continue
            idx, revs = self.hist["idx"][mine], self.hist["rev"][mine]
            ver, live = self.hist["ver"][mine], self.hist["verb"][mine] != 2
            events[name].append((idx, revs, ver, live))
            # the latest event of each index (the history is in revision
            # order): where it last occurs
            last = len(idx) - 1 - np.unique(idx[::-1], return_index=True)[1]
            self.ver[name][idx[last]] = ver[last]
            self.rev[name][idx[last]] = revs[last]
            self.live[name][idx[last]] = live[last]
        rev += len(self.hist["idx"])
        self.head_revision = rev
        self.rows = rev    # one mirror row per write
        # every table's events sorted by (index, revision)
        self.events = {}
        for name, parts in events.items():
            idx, revs, ver, live = (np.concatenate([p[k] for p in parts])
                                    for k in range(4))
            key = idx.astype(np.int64) * _KEY + revs
            order = np.argsort(key, kind="stable")
            self.events[name] = {"key": key[order], "idx": idx[order],
                                 "rev": revs[order], "ver": ver[order],
                                 "live": live[order],
                                 "revs": np.sort(revs),
                                 "tombs": np.sort(revs[~live])}

    # ---------------------------------------------------------------- data
    def value(self, table: Table, i: int, ver: int) -> bytes:
        head = b"%s-%d/v%d/" % (table.name.encode(), i, ver)
        n = max(table.size(i), len(head))
        off = (i * 7919 + ver * 104729 + table.offset) % (_POOL_BYTES - _MAX_VALUE)
        return head + self.pool[off: off + n - len(head)]

    def value_crc(self, table: Table, i: int, ver: int) -> int:
        return zlib.crc32(self.value(table, i, ver))

    def key_id(self, table: Table, i: int) -> int:
        return table.offset + i

    def locate(self, key_id: int) -> tuple[Table, int]:
        for t in self.tables.values():
            if t.offset <= key_id < t.offset + SPACE:
                return t, key_id - t.offset
        raise KeyError(key_id)

    # ------------------------------------------------- the start state's MVCC
    def at_many(self, name: str, ids, revision: int):
        """(version, mod_revision, exists) arrays: each index's latest start
        event at or below ``revision`` (a delete: it does not exist)."""
        ev = self.events[name]
        ids = np.asarray(ids, np.int64)
        if not len(ev["key"]):
            zero = np.zeros(len(ids), np.int64)
            return zero, zero, np.zeros(len(ids), bool)
        pos = np.searchsorted(ev["key"], ids * _KEY + int(revision),
                              side="right") - 1
        at = np.maximum(pos, 0)
        found = (pos >= 0) & (ev["idx"][at] == ids)
        return ev["ver"][at], ev["rev"][at], found & ev["live"][at]

    def at(self, name: str, i: int, revision: int):
        """(version, mod_revision) of index ``i`` at ``revision`` in the
        start state, None where it does not exist then."""
        ver, rev, live = self.at_many(name, [i], revision)
        return (int(ver[0]), int(rev[0])) if live[0] else None

    def live_count(self, name: str, revision: int) -> int:
        """The table's live keys at ``revision`` in the start state."""
        if revision >= self.head_revision:
            return int(self.live[name].sum())
        return int(self.at_many(name, np.arange(self.tables[name].ids),
                                revision)[2].sum())

    def events_upto(self, name: str, revision: int,
                    tombstones: bool = False) -> int:
        """The table's start revisions (or only its tombstones) at or below
        ``revision``."""
        return int(np.searchsorted(
            self.events[name]["tombs" if tombstones else "revs"], revision,
            side="right"))

    def head_at(self, second: float) -> int:
        """The head revision at ``second`` of the history: every write of
        the history due at or before it (the plan's writes come first)."""
        return self.head_revision - len(self.hist["idx"]) + int(
            np.searchsorted(self.hist["time"], second, side="right"))

    def start_ops(self):
        """The start state as ``(verb, table, index, version, guard)`` in
        commit order, for the loader."""
        ver = {n: np.zeros(t.ids, np.int32) for n, t in self.tables.items()}
        rev = {n: np.zeros(t.ids, np.int64) for n, t in self.tables.items()}
        r = 0
        for verb, name, idx in self.plan:
            t = self.tables[name]
            for i in idx.tolist():
                r += 1
                if verb == "update":
                    ver[name][i] += 1
                yield verb, t, i, int(ver[name][i]), int(rev[name][i])
                rev[name][i] = r
        names = list(self.tables)
        h = self.hist
        for t, verb, i, v in zip(h["table"].tolist(), h["verb"].tolist(),
                                 h["idx"].tolist(), h["ver"].tolist()):
            name = names[t]
            r += 1
            yield VERBS[verb], self.tables[name], i, v, int(rev[name][i])
            rev[name][i] = r


def _history(config: dict, seed: int, seconds: float) -> dict:
    """The history's writes as arrays in commit order: nominal ``time``,
    ``table`` (its place in the configuration), ``verb`` (``VERBS``),
    ``idx`` and ``ver``; empty without one."""
    parts = []
    for t, spec in enumerate(config["tables"]):
        rates = spec.get("history") or {}
        if not rates:
            continue
        unknown = set(rates) - {v + "_per_s" for v in VERBS}
        if unknown:
            raise ValueError(f"table {spec['name']}: unknown history {unknown}")
        n = int(spec["count"])
        # updates and deletes address the start state's objects, every one
        # live and at version 0: so no churn beside them, and not both
        touches = [v for v in ("update", "delete") if rates.get(v + "_per_s")]
        if len(touches) > 1 or touches and any(float(spec.get(c, 0)) for c in (
                "updated_twice_share", "deleted_share")):
            raise ValueError(f"table {spec['name']}: a history may update or "
                             "delete the start state's objects, not both, "
                             "and not beside churn")
        rng = np.random.default_rng([seed, 0x6b63, t])
        for v, verb in enumerate(VERBS):
            rate = float(rates.get(verb + "_per_s", 0))
            m = int(np.ceil(seconds * rate)) if rate > 0 else 0
            if not m:
                continue
            j = np.arange(m)
            ver = np.zeros(m, np.int32)
            if verb == "create":
                idx = n + j
            elif verb == "update":
                idx, ver = j % n, (j // n + 1).astype(np.int32)
            elif m > n:
                raise ValueError(f"table {spec['name']}: {m} deletes of {n}")
            else:
                idx = rng.permutation(n)[:m]
            parts.append((j / rate, np.full(m, t), np.full(m, v), idx, ver))
    if not parts:
        return {"time": np.zeros(0), "table": np.zeros(0, np.int64),
                "verb": np.zeros(0, np.int64), "idx": np.zeros(0, np.int64),
                "ver": np.zeros(0, np.int32)}
    time, table, verb, idx, ver = (np.concatenate([p[k] for p in parts])
                                   for k in range(5))
    order = np.lexsort((verb, table, time))
    return {"time": time[order], "table": table[order], "verb": verb[order],
            "idx": idx[order].astype(np.int64), "ver": ver[order]}


def _sizes(spec: dict, n: int, rng) -> np.ndarray:
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["bytes"]), np.int32)
    if spec["dist"] == "lognormal":
        s = rng.lognormal(np.log(float(spec["median"])), float(spec["sigma"]), n)
        return np.clip(s, spec["min"], spec["max"]).astype(np.int32)
    raise ValueError(f"unknown value size distribution {spec['dist']!r}")
