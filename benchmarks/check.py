"""The comparison that decides ``correct``: every answer of the timed path
against the plain reference, once the window has closed.

The reference is the benchmark's own record — the start state, which
``state.py`` makes from the seed, plus every write the server acknowledged
with the revision it acknowledged it at — replayed as a plain MVCC store. It
imports nothing of the program and reads nothing the program made. It holds
the server to the guarantees the configuration states:

- ``range_rows_wrong``: a sampled Range answer differs, row for row (key,
  mod_revision, value), from the reference's state at the answer's revision;
- ``range_stale``: a Range at revision 0 answered from a revision older than
  a write that had been acknowledged before the Range was sent;
- ``writes_refused`` / ``revisions_reused``: a CAS Txn on a key only this
  writer writes was refused; two acknowledged writes share a revision;
- ``readback_wrong``: after the window, Count over each written table and a
  seeded sample of written keys, read back, differ from the reference;
- ``watch_wrong``: a watcher's events are not exactly its range's
  acknowledged writes, once each, in revision order, with their keys/values;
- where the mix holds a Compact (kube-apiserver's compactor, ``ops/
  compact.py``): ``compact_refused``, a compactor tick not acknowledged;
  ``compact_readback_wrong``, after the window a seeded sample of each
  written table's namespaces and a Count, read at the compact revision C,
  differ from the reference; ``compacted_reads_not_refused``, the same reads
  at C - 1 did not answer "required revision has been compacted";
  ``compact_victims_wrong``, the server's ``kb_compact_victims_total``
  (``superseded`` + ``tombstone``) differs from the revisions the reference
  removed (etcd's Compact: each key keeps its latest revision at or below
  C, unless that is a tombstone, which goes too);
- ``mirror_not_serving`` / ``device_reads_unmoved`` /
  ``readback_device_unmoved``: the server's own account says the device
  mirror was not serving, or it dispatched fewer device scans than the
  device reads it answered, less those that rode another's dispatch: in the
  window, and across the read-back (a quarantined mirror, or a read sent
  down the host iterator, answers byte-identically from the host store, so
  right rows alone prove nothing about the chip).

Each is an exact comparison, so its limit is 0; ``compared_*`` hold what was
compared, and a run that compared nothing is not correct.
"""

from __future__ import annotations

import bisect
import zlib

import numpy as np

from state import SPACE, State

CLOCK_SLOP_S = 0.002
RANGE, TXN, COMPACT = 0, 1, 2
#: etcd's answer to a read below the compact revision
ERR_COMPACTED = "required revision has been compacted"


class Reference:
    """The start state (its whole history: ``State.at_many``) plus the
    acknowledged writes, as per-key histories, and the acknowledged
    Compacts."""

    def __init__(self, state: State, traffic: list[dict]):
        self.state = state
        self.writes = []       # (rev, key_id, ver, dead, sent, done)
        self.uncertain: set[int] = set()
        self.refused = 0
        self.compacts = []     # (compact revision, its Txn's revision)
        self.ticks = self.ticks_failed = 0
        for dump in traffic:
            for (family, _op, _due, sent, done, ok, rev, key_id, ver, _rows,
                 err, dead) in dump["recs"]:
                if family == COMPACT:
                    self.ticks += 1
                    self.ticks_failed += not ok
                    if ok:
                        self.compacts.append((rev, ver))
                if family != TXN:
                    continue
                if ok:
                    self.writes.append((rev, key_id, ver, dead, sent, done))
                else:
                    self.uncertain.add(key_id)
                    self.refused += err == "refused"
        self.writes.sort()
        self._keys: dict[str, list] = {}
        self.history: dict[int, list] = {}
        for rev, key_id, ver, dead, _s, _d in self.writes:
            self.history.setdefault(key_id, []).append((rev, ver, not dead))

    def at(self, key_id: int, revision: int, start=None):
        """(version, mod_revision) of the key at ``revision``, None if it
        does not exist then; ``start``: its start state's, if known."""
        if start is None:
            t, i = self.state.locate(key_id)
            start = self.state.at(t.name, i, revision)
        cur = start
        for rev, ver, live in self.history.get(key_id, ()):
            if rev > revision:
                break
            cur = (ver, rev) if live else None
        return cur

    @property
    def compacted(self) -> int:
        """The highest compact revision acknowledged, 0 without one."""
        return max((c for c, _ in self.compacts), default=0)

    def removed(self, revision: int) -> tuple[int, int]:
        """(superseded, tombstones): the revisions a Compact at ``revision``
        removes, by etcd's rule — every revision of a key below its latest
        one at or below ``revision``, and that one too where it is a
        tombstone. So every tombstone at or below it goes, and of the rest
        all but one for each key live at ``revision``."""
        events = tombs = live = 0
        for name, t in self.state.tables.items():
            events += self.state.events_upto(name, revision)
            tombs += self.state.events_upto(name, revision, tombstones=True)
            live += self.count(t.prefix, revision)
        for rev, _k, _v, dead, _s, _d in self.writes:
            if rev <= revision:
                events += 1
                tombs += dead
        return events - tombs - live, tombs

    def table_of(self, key: bytes):
        for t in self.state.tables.values():
            if key.startswith(t.prefix):
                return t
        return None

    def all_keys(self, t) -> list[tuple[bytes, int]]:
        """(key, key_id) of every key the table ever held (the start state's
        and every one a write touched), sorted; built once."""
        if t.name not in self._keys:
            ids = set(range(t.offset, t.offset + t.ids))
            ids.update(k for k in self.history
                       if t.offset <= k < t.offset + SPACE)
            self._keys[t.name] = sorted((t.key(k - t.offset), k) for k in ids)
        return self._keys[t.name]

    def candidates(self, start: bytes, end: bytes) -> list[tuple[bytes, int]]:
        """(key, key_id) of every key that ever existed in [start, end)."""
        t = self.table_of(start)
        if t is None:
            return []
        keys = self.all_keys(t)
        return keys[bisect.bisect_left(keys, (start,)):
                    bisect.bisect_left(keys, (end,))]

    def rows(self, start: bytes, end: bytes, revision: int) -> list:
        """The reference's answer to an unlimited Range, without the keys
        whose write failed."""
        rows = []
        cands = self.candidates(start, end)
        if not cands:
            return rows
        t = self.table_of(start)
        ver, rev, live = self.state.at_many(
            t.name, [k - t.offset for _key, k in cands], revision)
        for n, (key, key_id) in enumerate(cands):
            if key_id in self.uncertain:
                continue
            cur = self.at(key_id, revision,
                          (int(ver[n]), int(rev[n])) if live[n] else None)
            if cur is not None:
                rows.append((key, cur[1], self.state.value_crc(
                    t, key_id - t.offset, cur[0])))
        return rows

    def count(self, start: bytes, revision: int) -> int:
        """The live keys of the table whose prefix is ``start`` at
        ``revision``: the start state's, moved by every write up to it."""
        t = self.table_of(start)
        n = self.state.live_count(t.name, revision)
        for k, hist in self.history.items():
            if not t.offset <= k < t.offset + SPACE:
                continue
            # every write of the window comes after the start state's head
            i = k - t.offset
            live = i < t.ids and bool(self.state.live[t.name][i])
            for rev, _ver, alive in hist:
                if rev > revision:
                    break
                n += int(alive) - int(live)
                live = alive
        return n

    def uncertain_keys(self) -> set[bytes]:
        return {t.key(i) for t, i in map(self.state.locate, self.uncertain)}


def _entry(value, limit=0, op="<="):
    return {"value": value, "limit": limit, "op": op}


def compare(state: State, traffic: list[dict], watches: list[dict],
            readback: dict, sentinels: dict, prom: dict | None) -> dict:
    """Every number compared, beside its limit."""
    ref = readback.get("ref") or Reference(state, traffic)
    skip = ref.uncertain_keys()
    out: dict[str, dict] = {}

    # ---- sampled Range answers, row for row at their own revision
    wrong = rows_compared = answers = 0
    first_wrong = ""
    for dump in traffic:
        for s in dump["samples"]:
            answers += 1
            n, message = sample_differs(ref, s, skip)
            rows_compared += n
            if message:
                wrong += 1
                first_wrong = first_wrong or message
    sent_ranges = any(r[0] == RANGE for dump in traffic for r in dump["recs"])
    if sent_ranges:
        out["range_rows_wrong"] = _entry(wrong)
        out["compared_range_answers"] = _entry(answers, 1, ">=")
        out["compared_range_rows"] = _entry(rows_compared, 1, ">=")
        if first_wrong:
            out["range_rows_wrong"]["first"] = first_wrong

    # ---- freshness of every Range at revision 0
    acks = sorted((done, rev) for rev, _k, _v, _o, _s, done in ref.writes)
    ack_t = [a[0] for a in acks]
    ack_max = np.maximum.accumulate([a[1] for a in acks]) if acks else []
    stale = fresh_checked = 0
    for dump in traffic:
        for (family, _op, _due, sent, _done, ok, rev, _key, pinned,
             *_rest) in dump["recs"]:
            # pages after a list's first are pinned to the first's revision
            if family != RANGE or not ok or pinned:
                continue
            n = bisect.bisect_left(ack_t, sent - CLOCK_SLOP_S)
            fresh_checked += 1
            if n and rev < ack_max[n - 1]:
                stale += 1
    if sent_ranges:
        out["range_stale"] = _entry(stale)
        out["compared_range_fresh"] = _entry(fresh_checked, 1, ">=")

    # ---- writes (a compactor's Txn is one too)
    revs = [w[0] for w in ref.writes] + [txn for _c, txn in ref.compacts]
    out["writes_refused"] = _entry(ref.refused)
    out["revisions_reused"] = _entry(
        len(revs) - len(set(revs)) + sum(r <= state.head_revision for r in revs))
    out["readback_wrong"] = _entry(readback["wrong"])
    out["compared_readback"] = _entry(readback["compared"], 1, ">=")
    if readback.get("first"):
        out["readback_wrong"]["first"] = readback["first"]

    # ---- watches: every event of the range once, in revision order
    if watches:
        out.update(compare_watches(state, ref, watches, sentinels))

    # ---- kube-apiserver's compactor: every tick acknowledged; after the
    # window the reads at C exact and those at C - 1 refused as compacted;
    # the server's victims the revisions etcd's rule removes
    if ref.ticks:
        out["compact_refused"] = _entry(ref.ticks_failed)
        out["compared_compacts"] = _entry(ref.ticks, 1, ">=")
        back = readback.get("compaction") or {}
        out["compact_readback_wrong"] = _entry(back.get("wrong", 0))
        out["compared_compact_readback"] = _entry(back.get("compared", 0), 1, ">=")
        out["compacted_reads_not_refused"] = _entry(back.get("not_refused", 0))
        out["compared_compacted_reads"] = _entry(back.get("below", 0), 1, ">=")
        if back.get("first"):
            out["compact_readback_wrong"]["first"] = back["first"]
        victims = (prom or {}).get("compact_victims")
        removed = sum(back.get("removed") or ())
        if victims is not None and back.get("removed") is not None:
            out["compact_victims_wrong"] = _entry(abs(int(victims) - removed))
            out["compared_compact_victims"] = _entry(removed, 1, ">=")

    # ---- the server's own account: the device served
    if prom is not None:
        out["mirror_not_serving"] = _entry(prom["not_serving"])
        if prom["device_reads"]:
            out["device_reads_unmoved"] = _entry(
                undispatched(prom["device_reads"], prom["window"]))
            out["compared_device_reads"] = _entry(prom["device_reads"], 1, ">=")
        out["readback_device_unmoved"] = _entry(
            undispatched(readback["device_reads"], prom["readback"]))
    return out


def compare_watches(state: State, ref: Reference, watches: list[dict],
                    sentinels: dict) -> dict:
    """Each watch's events after its ``created`` revision (a sentinel's
    left out) against the expected list of its range: the reference's
    acknowledged writes whose key lies in it, in revision order, each as
    (revision, type, key crc, value crc). The list is built once for each
    distinct range."""
    import watches as plan

    keys, cols = [], []
    for rev, key_id, ver, dead, _s, _d in ref.writes:
        t, i = state.locate(key_id)
        key = t.key(i)
        keys.append(key)
        cols.append((rev, 1, zlib.crc32(key), 0) if dead else (
            rev, 0, zlib.crc32(key), state.value_crc(t, i, ver)))
    by_key = sorted(range(len(keys)), key=lambda n: (keys[n], cols[n][0]))
    sorted_keys = [keys[n] for n in by_key]
    cols = np.array(cols, np.int64).reshape(-1, 4)
    expected: dict[tuple, np.ndarray] = {}

    def want_of(start: bytes, end: bytes) -> np.ndarray:
        if (start, end) not in expected:
            lo = bisect.bisect_left(sorted_keys, start)
            hi = bisect.bisect_left(sorted_keys, end)
            rows = cols[by_key[lo:hi]] if hi > lo else cols[:0]
            expected[(start, end)] = rows[np.argsort(rows[:, 0], kind="stable")]
        return expected[(start, end)]

    skip = np.array(sorted(sentinels.values()), np.int64)
    bad = events = 0
    first = ""
    for dump in watches:
        ev = dump["events"]
        order = np.argsort(ev["watch"], kind="stable")
        got_all = np.stack([ev[f][order].astype(np.int64) for f, _c, _d
                            in plan.FIELDS[1:5]], axis=1)
        at = np.searchsorted(ev["watch"][order], np.arange(len(dump["watches"]) + 1))
        for i, w in enumerate(dump["watches"]):
            want = want_of(w["start"], w["end"])
            want = want[np.searchsorted(want[:, 0], w["created"] or 0, "right"):]
            got = got_all[at[i]:at[i + 1]]
            got = got[~np.isin(got[:, 0], skip)]
            events += len(want)
            if w["error"] or got.shape != want.shape or not (got == want).all():
                bad += 1
                first = first or (
                    f"{w['table']} [{w['start']!r}, {w['end']!r}): {len(got)} "
                    f"events for {len(want)} writes {w['error']}")
    out = {"watch_wrong": _entry(bad),
           "compared_watch_events": _entry(events, 1, ">=")}
    if first:
        out["watch_wrong"]["first"] = first
    return out


def device_account(later: dict, earlier: dict, rpc: str | None = None) -> dict:
    """What the server's ``/metrics`` say of the device read path between
    two scrapes. ``device_dispatch`` is the one stage that only
    ``TpuScanner``'s kernel path records (the host scanner records its
    iteration as ``device_compute``); a batch of n reads is one dispatch
    (``kb_sched_batch_size``: sum - count are its riders) and a read that
    joined an identical one in flight has none of its own. ``rpc`` (where
    the mix holds a Compact: ``READ_RPC``) counts only the dispatches of
    that RPC's spans, so that no dispatch of other work could stand for a
    read that skipped the device."""
    import prom

    labels = {"stage": "device_dispatch"}
    if rpc is not None:
        labels["rpc"] = rpc
    return {
        "dispatches": prom.delta(later, earlier, "kb_rpc_stage_seconds_count",
                                 **labels),
        "riders": prom.delta(later, earlier, "kb_sched_batch_size_sum")
        - prom.delta(later, earlier, "kb_sched_batch_size_count"),
        "coalesced": prom.delta(later, earlier, "kb_sched_coalesced")}


#: the span every Range and Count is served in (``kb_rpc_stage_seconds``'s
#: ``rpc`` label since PR 26)
READ_RPC = "etcd.KV/Range"


def undispatched(reads: int, account: dict) -> int:
    """Device reads answered with no device dispatch to show for them."""
    return max(0, int(reads - account["riders"] - account["coalesced"]
                      - account["dispatches"]))


def sample_differs(ref: Reference, s: dict, skip: set[bytes]):
    """One sampled Range answer against the reference at the answer's own
    revision: (rows compared, what differs or "").

    A key whose write FAILED at the client (refused, shed, timed out) may or
    may not have been written, so it is left out on both sides. In a page
    that has a consequence: the server's ``limit`` rows may hold such keys,
    so the reference's rows are taken up to the page's LAST KEY, never by
    re-applying the limit to what is left — that reads "499 rows, the
    reference holds 500" for every right page that holds one (PERF.md
    section 6, the fault of chip sets C and D)."""
    snap = s["revision"] or s["header"]
    if s.get("count") is not None:
        want_n = ref.count(s["start"], snap)
        if s["count"] != want_n and not ref.uncertain:
            return 1, (f"Count {s['start']!r} at {snap}: {s['count']}, the "
                       f"reference holds {want_n}")
        return 1, ""
    want = ref.rows(s["start"], s["end"], snap)
    got = [r for r in s["rows"] if r[0] not in skip]
    more, full = False, True
    if s["limit"] and s["rows"]:
        last = s["rows"][-1][0]
        more = any(w[0] > last for w in want)
        want = [w for w in want if w[0] <= last]
        # a page is full, or the list ends with it
        full = len(s["rows"]) == s["limit"] or not more
    if got != want or not full or (s["limit"] and bool(s["more"]) != more):
        return len(want), _diff(s, got, want)
    return len(want), ""


def _diff(sample: dict, got: list, want: list) -> str:
    where = f"{sample['start']!r} at {sample['revision'] or sample['header']}"
    if len(got) != len(want):
        return f"{where}: {len(got)} rows, the reference holds {len(want)}"
    for g, w in zip(got, want):
        if g != w:
            return f"{where}: row {g[0]!r} rev {g[1]} != {w[0]!r} rev {w[1]}"
    return f"{where}: the more flag differs"


def verdict(numbers: dict) -> bool:
    return all(n["value"] <= n["limit"] if n["op"] == "<=" else
               n["value"] >= n["limit"] for n in numbers.values())


def read_back(stub, etcd, state: State, traffic: list[dict], seed: int,
              sample: int = 256) -> dict:
    """After the window: Count over every written table, a seeded sample of
    the written keys by point Get, and one unpaged namespace Range per
    written table that has namespaces, against the reference's last state."""
    import random

    ref = Reference(state, traffic)
    skip = ref.uncertain_keys()
    top = max([state.head_revision] + [w[0] for w in ref.writes])
    rnd = random.Random(seed)
    wrong = compared = device_reads = 0
    first = ""
    written = sorted(set(k for k in ref.history if k not in ref.uncertain))
    tables = {state.locate(k)[0].name for k in written}
    for name in sorted(tables):
        t = state.tables[name]
        end = etcd.prefix_end(t.prefix)
        if ref.uncertain:
            continue   # a Count cannot leave a key out
        want = ref.count(t.prefix, top)
        got = stub.range(etcd.range_request(t.prefix, end, count_only=True),
                         timeout=120.0).count
        compared += 1
        device_reads += 1
        if got != want:
            wrong += 1
            first = first or f"Count {name}: {got}, the reference holds {want}"
        # unpaged Ranges, which the device answers: one namespace, or three
        # 1/256 slices of a table whose keys end in a random suffix
        if t.namespaces > 1:
            prefixes = [t.ns_prefix(rnd.randrange(t.namespaces))]
        elif t.hash_chars:
            prefixes = [t.prefix + b"%02x" % rnd.randrange(256)
                        for _ in range(3)]
        else:
            prefixes = [t.prefix]
        for prefix in prefixes:
            resp = stub.range(etcd.range_request(prefix, etcd.prefix_end(prefix)),
                              timeout=120.0)
            want_rows = ref.rows(prefix, etcd.prefix_end(prefix), top)
            got_rows = [(kv.key, kv.mod_revision, zlib.crc32(kv.value))
                        for kv in resp.kvs if kv.key not in skip]
            compared += len(want_rows)
            device_reads += 1
            if got_rows != want_rows:
                wrong += 1
                first = first or (f"Range {prefix!r} after the window: "
                                  f"{len(got_rows)} rows, the reference "
                                  f"holds {len(want_rows)}")
    for k in rnd.sample(written, min(sample, len(written))):
        t, i = state.locate(k)
        resp = stub.range(etcd.range_request(t.key(i)), timeout=60.0)
        cur = ref.at(k, top)
        got = [(kv.mod_revision, zlib.crc32(kv.value)) for kv in resp.kvs]
        want = [] if cur is None else [(cur[1], state.value_crc(t, i, cur[0]))]
        compared += 1
        if got != want:
            wrong += 1
            first = first or f"Get {t.key(i)!r}: {got} != {want}"
    # the reference, with its key lists built, for ``compare`` to go on with
    return {"wrong": wrong, "compared": compared, "first": first, "top": top,
            "ref": ref, "device_reads": device_reads,
            "compaction": read_back_compacted(stub, etcd, ref, rnd)}


def read_back_compacted(stub, etcd, ref: Reference, rnd) -> dict | None:
    """After a Compact to C: a seeded sample of each written table's
    namespaces (three at most) and a Count over it, read at C, against the
    reference at C; the same reads at C - 1 have to answer "required
    revision has been compacted". And what the reference removed at C, by
    kind (None where a failed write may have landed at or below C)."""
    import grpc

    c = ref.compacted
    if not c:
        return None
    out = {"wrong": 0, "compared": 0, "not_refused": 0, "below": 0, "first": "",
           "target": c, "removed": None}
    if not (ref.uncertain and c > ref.state.head_revision):
        out["removed"] = ref.removed(c)
    written = {ref.state.locate(w[1])[0].name for w in ref.writes}
    for name in sorted(written):
        t = ref.state.tables[name]
        prefixes = [t.ns_prefix(ns) for ns in sorted(rnd.sample(
            range(t.namespaces), min(3, t.namespaces)))]
        reads = [(p, etcd.prefix_end(p), False) for p in prefixes]
        reads.append((t.prefix, etcd.prefix_end(t.prefix), True))
        for start, end, count_only in reads:
            if count_only and ref.uncertain:
                continue    # a Count cannot leave a key out
            resp = stub.range(etcd.range_request(start, end, revision=c,
                                                 count_only=count_only),
                              timeout=120.0)
            if count_only:
                got, want = resp.count, ref.count(start, c)
                out["compared"] += 1
            else:
                skip = ref.uncertain_keys()
                got = [(kv.key, kv.mod_revision, zlib.crc32(kv.value))
                       for kv in resp.kvs if kv.key not in skip]
                want = ref.rows(start, end, c)
                out["compared"] += len(want)
            if got != want:
                out["wrong"] += 1
                out["first"] = out["first"] or (
                    f"{'Count' if count_only else 'Range'} {start!r} at the "
                    f"compact revision {c}: {got if count_only else len(got)}"
                    f", the reference holds {want if count_only else len(want)}")
            out["below"] += 1
            try:
                stub.range(etcd.range_request(start, end, revision=c - 1,
                                              count_only=count_only),
                           timeout=120.0)
                out["not_refused"] += 1
                out["first"] = out["first"] or (
                    f"{start!r} at {c - 1}, below the compact revision {c}: "
                    "served")
            except grpc.RpcError as e:
                if ERR_COMPACTED not in (e.details() or ""):
                    out["not_refused"] += 1
                    out["first"] = out["first"] or (
                        f"{start!r} at {c - 1}: {e.code().name} {e.details()}")
    return out
