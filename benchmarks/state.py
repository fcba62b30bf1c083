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
"""

from __future__ import annotations

import hashlib
import zlib
from dataclasses import dataclass

import numpy as np

_POOL_BYTES = 4 << 20
_MAX_VALUE = 1 << 16


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
                int(spec.get("hash_chars", 0)), b"kb-%d" % self.seed)
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
        # the start state, by replaying the plan with revisions 1, 2, 3, ...
        self.ver = {n: np.zeros(t.count, np.int32) for n, t in self.tables.items()}
        self.rev = {n: np.zeros(t.count, np.int64) for n, t in self.tables.items()}
        self.live = {n: np.zeros(t.count, bool) for n, t in self.tables.items()}
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
        self.head_revision = rev
        self.rows = rev    # one mirror row per write

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

    def start_ops(self):
        """The start state as ``(verb, table, index, version, guard)`` in
        commit order, for the loader."""
        ver = {n: np.zeros(t.count, np.int32) for n, t in self.tables.items()}
        rev = {n: np.zeros(t.count, np.int64) for n, t in self.tables.items()}
        r = 0
        for verb, name, idx in self.plan:
            t = self.tables[name]
            for i in idx.tolist():
                r += 1
                if verb == "update":
                    ver[name][i] += 1
                yield verb, t, i, int(ver[name][i]), int(rev[name][i])
                rev[name][i] = r


def _sizes(spec: dict, n: int, rng) -> np.ndarray:
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["bytes"]), np.int32)
    if spec["dist"] == "lognormal":
        s = rng.lognormal(np.log(float(spec["median"])), float(spec["sigma"]), n)
        return np.clip(s, spec["min"], spec["max"]).astype(np.int32)
    raise ValueError(f"unknown value size distribution {spec['dist']!r}")
