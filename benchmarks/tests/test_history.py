"""A store's history (``state.py``) and etcd's Compact in the plain
reference (``check.Reference``, ``refserver.py``): every revision made from
the seed as arrays, the head revision at a second of the history, and the
start plan of a configuration without a history exactly the parent's."""

import hashlib
import json
import os
import types

import numpy as np
import pytest

import check
import run
from conftest import BENCH
from state import State

with open(os.path.join(BENCH, "tests", "history_cell.json")) as _f:
    HISTORY_CELL = json.load(_f)


def _digest(state: State) -> str:
    h = hashlib.sha256()
    for verb, t, i, ver, guard in state.start_ops():
        h.update(b"%s %s %d %d\n" % (verb.encode(), t.key(i), ver, guard))
        h.update(state.value(t, i, ver))
    h.update(b"head %d rows %d" % (state.head_revision, state.rows))
    return h.hexdigest()


@pytest.mark.parametrize("seed,parent", [
    (2**31 + 5, "a98b3e1f7d4e3beffccd0e3a814d1a3ef5458c9414bc759da5c44c7f335bf084"),
    (3 * (2**31 - 1) + 1,
     "f79def9312e9118d8f7d0e5176459771028508ec96a1ae2134740e62f74c36fe"),
])
def test_without_a_history_the_plan_is_the_parents_byte_for_byte(seed, parent):
    """``k8s-2500``'s start state: every write the loader makes (verb, key,
    version, guard, value) and the head, hashed; the digests are the parent
    commit's (a66fd12), computed with its own ``state.py``."""
    assert _digest(State(run.load_json("configs", "k8s-2500.json"), seed)) == parent


#: a history small enough to follow by hand: pods 0-2 created (revisions
#: 1-3), Leases 0-1 (4-5); then per second, pods before Leases and create,
#: update, delete in that order: second 0 creates pod 3 (6), deletes one of
#: pods 0-2 (7), renews Lease 0 (8); second 1: pod 4 (9), a delete (10),
#: Lease 1 (11); second 2: pod 5 (12), the third delete (13), Lease 0 (14)
HAND = {"history_seconds": 3, "tables": [
    {"name": "pods", "key": "/registry/pods/ns-{ns:03d}/pod-{i:06d}",
     "prefix": "/registry/pods/", "ns_prefix": "/registry/pods/ns-{ns:03d}/",
     "count": 3, "namespaces": 1, "value_bytes": {"dist": "fixed", "bytes": 64},
     "history": {"create_per_s": 1, "delete_per_s": 1}},
    {"name": "leases", "key": "/registry/leases/node-{i:05d}",
     "prefix": "/registry/leases/", "ns_prefix": "/registry/leases/",
     "count": 2, "namespaces": 1, "value_bytes": {"dist": "fixed", "bytes": 64},
     "history": {"update_per_s": 1}}]}


def test_a_hand_built_history():
    s = State(HAND, 7)
    assert (s.head_revision, s.rows) == (14, 14)
    assert s.tables["pods"].ids == 6 and s.tables["leases"].ids == 2
    assert [s.head_at(t) for t in (-1, 0, 0.5, 1, 2, 2.5, 99)] == [5, 8, 8, 11, 14, 14, 14]
    ops = list(s.start_ops())
    assert [(v, t.name, i) for v, t, i, _ver, _g in ops[5:9]] == [
        ("create", "pods", 3), ("delete", "pods", ops[6][2]), ("update", "leases", 0),
        ("create", "pods", 4)]
    # Lease 0: created at 4, renewed at 8 (version 1) and 14 (version 2)
    assert [s.at("leases", 0, r) for r in (3, 4, 7, 8, 13, 14)] == [
        None, (0, 4), (0, 4), (1, 8), (1, 8), (2, 14)]
    assert ops[13][4] == 8     # its second renewal is guarded on the first
    # the pods deleted, in an order from the seed, each live when deleted
    gone = [i for v, _t, i, *_r in ops if v == "delete"]
    assert sorted(gone) == [0, 1, 2]
    assert s.at("pods", gone[0], 6) == (0, gone[0] + 1) and s.at("pods", gone[0], 7) is None
    assert s.at("pods", 4, 8) is None and s.at("pods", 4, 9) == (0, 9)
    assert [s.live_count("pods", r) for r in (3, 6, 7, 11, 14)] == [3, 4, 3, 3, 3]
    ver, rev, live = s.at_many("pods", np.arange(7), 11)
    assert live.tolist() == [i not in gone[:2] for i in range(3)] + [True, True, False, False]
    # without a history nothing of it is there
    plain = json.loads(json.dumps(HAND))
    del plain["history_seconds"]
    p = State(plain, 7)
    assert (p.head_revision, p.tables["pods"].ids, p.head_at(10)) == (5, 3, 5)


def test_the_history_of_the_deployment_the_issue_names():
    """``k8s-2500`` with 600 s of its own stream: 162,000 revisions beside
    the 77,500 creates; a Compact to the head at second 300 removes 30
    superseded renewals of each Lease and the create and tombstone of each
    pod deleted by then."""
    config = run.load_json("configs", "k8s-2500.json")
    config["history_seconds"] = 600
    config["tables"][0]["history"] = {"create_per_s": 10, "delete_per_s": 10}
    config["tables"][1]["history"] = {"update_per_s": 250}
    s = State(config, 2**31 + 3)
    assert s.rows == 77_500 + 162_000 and s.tables["pods"].ids == 81_000
    c = s.head_at(300)
    ref = check.Reference(s, [])
    # 75,001 renewals and 3,001 deletes at or before second 300 (both at 0)
    assert ref.removed(c) == (75_001 + 3_001, 3_001)
    assert s.live_count("pods", c) == 75_000 and s.live_count("leases", c) == 2_500


def _ticked(ref_state, c, writes=()):
    """A reference whose traffic acknowledged one Compact to ``c`` (its Txn
    at the next revision) and ``writes``."""
    recs = [(check.TXN, "update", 0.0, 0.0, 0.0, True, rev, key_id, ver, 0, "", dead)
            for rev, key_id, ver, dead in writes]
    recs.append((check.COMPACT, "compact", 0.0, 0.0, 0.0, True, c, -1, 99, 0, "", False))
    return check.Reference(ref_state, [{"recs": recs, "samples": [],
                                        "judged": False, "loop": "open"}])


def test_the_references_compact_on_the_hand_built_history():
    """etcd's rule at C = 11: each key keeps its latest revision at or below
    C unless it is a tombstone. Pods 0-2 and the two created by then: the
    two deleted lose their create and tombstone (2 + 2); Leases: both
    creates are superseded by renewals (2). A window write at 20 moves
    nothing at 11 and is counted at 20."""
    s = State(HAND, 7)
    ref = _ticked(s, 11)
    assert ref.compacted == 11 and ref.compacts == [(11, 99)]
    assert ref.removed(11) == (4, 2)
    # at 14: the three deleted pods' creates; Lease 0's 4 and 8, Lease 1's 5
    assert ref.removed(14) == (3 + 3, 3)
    lease0 = s.key_id(s.tables["leases"], 0)
    ref = _ticked(s, 11, [(20, lease0, 3, False)])
    assert ref.removed(11) == (4, 2) and ref.removed(20) == (3 + 3 + 1, 3)
    assert ref.at(lease0, 19) == (2, 14) and ref.at(lease0, 20) == (3, 20)


def test_the_reference_server_compacts_as_etcd_does():
    import refserver

    s = State(HAND, 7)
    store = refserver.Store(s)
    ref = check.Reference(s, [])
    req = lambda rev, **kw: types.SimpleNamespace(
        key=b"/registry/", range_end=b"/registry0", revision=rev, count_only=False,
        limit=0, **kw)
    rows = lambda resp: [(kv.key, kv.mod_revision) for kv in resp.kvs]
    # the reference answers a table at a time: Leases sort before pods
    want = lambda r: [(k, rev) for p in (b"/registry/leases/", b"/registry/pods/")
                      for k, rev, _crc in ref.rows(p, p[:-1] + b"0", r)]
    before = {r: rows(store.range(req(r))) for r in (10, 11, 14)}
    assert before[11] == want(11) and before[10] == want(10)
    store.compact(types.SimpleNamespace(revision=11))
    with pytest.raises(refserver.Compacted):
        store.range(req(10))
    assert rows(store.range(req(11))) == want(11) == before[11]
    assert rows(store.range(req(14))) == before[14]
    kept = sum(len(chain) for chain in store.hist.values())
    assert kept == s.rows - sum(ref.removed(11))
    # the break that takes a survivor: each table's first key loses it
    broken = refserver.Store(s, "over_compacted")
    broken.compact(types.SimpleNamespace(revision=11))
    assert len(rows(broken.range(req(11)))) == len(want(11)) - 2
    # and the one that sets no floor
    lax = refserver.Store(s, "uncompacted")
    lax.compact(types.SimpleNamespace(revision=11))
    assert rows(lax.range(req(10))) == want(10)


def test_the_generator_writes_after_the_history():
    """A writer's pools hold the keys live at the history's end; creates take
    indices no write of the start state used."""
    import worker

    config = HISTORY_CELL["config"]
    spec = {"target": "127.0.0.1:1", "seed": 5, "worker": 0, "config": config,
            "stream": HISTORY_CELL["traffic"]["streams"][0], "writers": 1,
            "writer": 0, "rate": 30.0}
    sent = []

    class Recording(worker.Traffic):
        def _send(self, rec, call, req):
            sent.append((rec.op, rec.key_id, rec.ver))
            self.busy.discard(rec.req[1])

    gen = Recording(spec)
    gen.warming = False
    for _ in range(300):
        gen.next_op(0.0)
    gen.stub.close()
    st, pods = gen.state, gen.state.tables["pods"]
    created = [k - pods.offset for op, k, _v in sent if op == "create"]
    assert min(created) >= pods.ids > pods.count
    for op, k, ver in sent:
        t, i = st.locate(k)
        if op != "create" and i < t.ids:
            assert st.live[t.name][i], (op, k)


@pytest.mark.parametrize("mix,stream,parent", [
    ("steady", 0, "62b90ac1325b620ab3f52c5ca400222e1eb811e7a7a815a9e555f857221bc41d"),
    ("relist", 0, "61ce8099c88f3e26846794c592eb198d404d4ae715e5d8bfd0a560583605e0f5"),
    ("relist-merge", 1, "62b90ac1325b620ab3f52c5ca400222e1eb811e7a7a815a9e555f857221bc41d"),
])
def test_without_a_history_the_generators_send_the_parents_requests(mix, stream, parent):
    """400 operations of a stream of the three mixes (``test_generator``'s
    recording generator, seed 2**31 + 9), hashed: the digests are the
    parent commit's (a66fd12)."""
    import test_generator

    h = hashlib.sha256()
    for op, key_id, ver, req in test_generator._ops(2**31 + 9, 400, mix=mix, stream=stream):
        h.update(b"%s %d %d " % (op.encode(), key_id, ver) + req)
    assert h.hexdigest() == parent
