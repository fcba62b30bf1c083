"""Watches at scale (``watches.py``, ``worker.Watchers``, the drain in
``run.py``, ``check.compare_watches``, the reference's ``Ranges``): each
watch on its own key range, many watches on one stream, a drain and a check
per watch — and steady's old watch form, one whole-table watch a stream,
held to the parent's plan, drain, check and pairs, on a recorded dump."""

import json
import os
import random
from types import SimpleNamespace

import numpy as np
import pytest

import check
import etcd
import refserver
import run
import watches
import witness
from conftest import BENCH
from state import State

TESTS = os.path.join(BENCH, "tests")
with open(os.path.join(TESTS, "watch_cell.json")) as _f:
    WATCH_CELL = json.load(_f)
with open(os.path.join(TESTS, "steady_watch_dump.json")) as _f:
    DUMP = json.load(_f)
STEADY = run.load_json("traffic", "steady.json")
WATCHERS = next(s for s in STEADY["streams"] if "watch" in s)


def _state(config, seed, scale=1.0):
    config = json.loads(json.dumps(config))
    run.scale_tables(config, scale)
    return State(config, seed)


# ---- steady's form: the parent's plan and drain
def test_steady_plans_the_parents_six_streams():
    """Three whole-table watches on pods, then three on Leases, a stream
    each, whose create requests are the parent's byte for byte (no
    ``watch_id``: the server names the one watch of a stream)."""
    state = _state(run.load_json("configs", "k8s-2500.json"), 2**31 + 3, 0.01)
    plan = watches.plan(WATCHERS, state)
    assert [len(s) for s in plan] == [1] * 6
    assert [(w["table"], w["scope"], w["start"], w["end"]) for s in plan for w in s] == (
        [("pods", "table", b"/registry/pods/", b"/registry/pods0")] * 3
        + [("leases", "table", b"/registry/leases/", b"/registry/leases0")] * 3)
    assert [etcd.watch_create(s[0]["start"], s[0]["end"]).SerializeToString()
            for s in plan] == [b'\n"\n\x0f/registry/pods/\x12\x0f/registry/pods0'] * 3 + [
                b'\n&\n\x11/registry/leases/\x12\x11/registry/leases0'] * 3
    assert watches.share(WATCHERS, state, 0, 1) == plan


def test_steady_drains_on_the_parents_sentinels(tmp_path):
    """One sentinel a watched table, in table order, the parent's Txn byte
    for byte; every watch waits for its table's."""
    state = _state(run.load_json("configs", "k8s-2500.json"), 5, 0.01)
    sent, told = [], []

    class Stub:
        def txn(self, req, timeout):
            sent.append(req.SerializeToString())
            return SimpleNamespace(header=SimpleNamespace(revision=1000 + len(sent)))

    class Watcher:
        name = "watchers-0"
        spec = {"stream": WATCHERS, "watch_proc": 0, "watch_procs": 1}

        def tell(self, line):
            told.append(line)

    sentinels = {}
    run.drain_watches(Stub(), state, [], [Watcher()], str(tmp_path), sentinels)
    with open(told[0].split()[1]) as f:
        want = json.load(f)
    assert sentinels == {"leases": 1001, "pods": 1002}
    assert sent[1] == (b'\n$\x10\x02\x1a\x1e/registry/pods/~bench-sentinel0\x00\x12\'\x12%'
                       b'\n\x1e/registry/pods/~bench-sentinel\x12\x03end\x1a"\n \n'
                       b'\x1e/registry/pods/~bench-sentinel')
    assert want == [1002] * 3 + [1001] * 3


# ---- steady's form: the parent's check and pairs on a recorded dump
def _recorded(drop=None):
    """The recorded run (``steady_watch_dump.*``): the writers' records and
    the six watchers' events, the window and two made-up pauses."""
    z = np.load(os.path.join(TESTS, "steady_watch_dump.npz"))
    config = run.load_json("configs", "k8s-2500.json")
    run.scale_tables(config, DUMP["scale"])
    state = State(config, DUMP["seed"])
    ops = ["update", "create", "delete"]
    recs = [(1, ops[int(o)], float(a), float(b), float(c), True, int(r), int(k),
             int(v), 0, "", bool(d)) for o, a, b, c, r, k, v, d in zip(
                 z["rec_op"], z["rec_due"], z["rec_sent"], z["rec_done"],
                 z["rec_rev"], z["rec_key"], z["rec_ver"], z["rec_dead"])]
    traffic = [{"stream": "writers", "judged": True, "loop": "open", "recs": recs,
                "samples": [], "warm_failed": 0, "warm_unsent": 0}]
    keep = np.ones(len(z["ev_rev"]), bool)
    if drop is not None:
        keep[drop] = False
    plan = watches.plan(WATCHERS, state)
    dump = {"kind": "watch", "threads": 4, "streams": len(plan),
            "watches": [dict(w, created=c, error="") for w, c in zip(
                [w for s in plan for w in s], DUMP["created"])],
            "events": {f: z["ev_" + f][keep] for f, _c, _d in watches.FIELDS}}
    machine = witness.Witness()
    machine.pauses = [tuple(p) for p in DUMP["pauses"]]
    workload = json.loads(json.dumps(STEADY))
    workload["streams"] = [s for s in workload["streams"]
                           if s["name"] in ("writers", "watchers")]
    workload["warmup_writes"] = 100
    opts = SimpleNamespace(workload="k8s-2500.steady", trace=0, sut="reference",
                           broken="")
    ctx = run.Context(opts, workload, config, state, traffic, [dump],
                      tuple(DUMP["window"]), 1.0, None, None, 0, {}, machine=machine)
    return state, traffic, dump, ctx


#: the parent's (34b4ff5) readings of the recorded dump, by its own
#: ``check.compare``, ``Context.watch_pairs``, readers and summary line, on
#: the dump in its form (a list of event tuples a watch)
PARENT = {
    "whole": {"watch_wrong": 0, "compared_watch_events": 1923},
    "one_event_dropped": {"watch_wrong": 1, "compared_watch_events": 1923},
    "pairs": 1623,
    "pairs_sum": "5.672834666835115",
    "watch_lag_p50_ms": "2.00014258371084",
    "watch_lag_p99_ms": "35.87324029489537",
    "watch_lag_p99_quiet_ms": "8.371839132523746",
    "line": "watch lag (event, watcher) pairs: n=1623 p50=2.00ms p75=2.61ms "
            "p95=8.39ms p99=35.87ms max=44.18ms; touched by a pause 909; untouched "
            "p50=1.98ms p75=2.50ms p95=5.09ms p99=8.37ms max=10.83ms",
}


@pytest.mark.parametrize("case,drop", [("whole", None), ("one_event_dropped", 100)])
def test_steady_checks_as_the_parent_did(case, drop):
    state, traffic, dump, _ctx = _recorded(drop)
    sentinels = {k: int(v) for k, v in DUMP["sentinels"].items()}
    n = check.compare(state, traffic, [dump], {"wrong": 0, "compared": 1,
                                               "device_reads": 0}, sentinels, None)
    assert {k: n[k]["value"] for k in PARENT[case]} == PARENT[case]
    if drop is not None:
        assert n["watch_wrong"]["first"].startswith(
            "pods [b'/registry/pods/', b'/registry/pods0'): ")


def test_steady_pairs_and_lags_are_the_parents_to_the_digit():
    _state_, _traffic, _dump, ctx = _recorded()
    due, arrival = ctx.watch_pairs()
    assert len(due) == PARENT["pairs"]
    assert repr(sum(b - a for a, b in zip(due.tolist(), arrival.tolist()))) == \
        PARENT["pairs_sum"]
    for name in ("watch_lag_p50_ms", "watch_lag_p99_ms", "watch_lag_p99_quiet_ms"):
        assert repr(run.read_metric(name, ctx)) == PARENT[name], name
    assert [line for line in run.summary_lines(ctx)
            if line.startswith("watch lag")] == [PARENT["line"]]


# ---- the watch spec
def _fixture_state(seed):
    return State(WATCH_CELL["config"], seed)


def test_namespaces_are_dealt_in_an_order_drawn_from_the_seed():
    entry = {"table": "pods", "count": 20, "scope": "namespace", "per_stream": 7}
    plans = {seed: watches.plan({"watch": [entry]}, _fixture_state(seed))
             for seed in (2**31 + 1, 2**31 + 2)}
    for plan in plans.values():
        assert [len(s) for s in plan] == [7, 7, 6]
        ns = [w["start"] for s in plan for w in s]
        # every namespace once before any twice: 8, 8, then 4 of them
        assert sorted(ns[:8]) == sorted(ns[8:16]) == sorted(
            _fixture_state(1).tables["pods"].ns_prefix(i) for i in range(8))
        assert ns[:8] == ns[8:16] and ns[16:] == ns[:4]
        assert all(w["end"] == etcd.prefix_end(w["start"]) for s in plan for w in s)
    a, b = plans.values()
    assert a != b
    assert a == watches.plan({"watch": [entry]}, _fixture_state(2**31 + 1))


def test_a_key_watch_is_one_live_object_of_the_start_state():
    state = _fixture_state(7)
    plan = watches.plan({"watch": [{"table": "pods", "count": 50, "scope": "key",
                                    "per_stream": 50}]}, state)
    t = state.tables["pods"]
    live = {t.key(i) for i in range(t.count) if state.live["pods"][i]}
    assert len(plan) == 1 and len(plan[0]) == 50
    assert all(w["start"] in live and w["end"] == w["start"] + b"\0" for w in plan[0])
    assert len({w["start"] for w in plan[0]}) > 40


def test_the_streams_are_dealt_over_the_processes():
    state = _fixture_state(9)
    stream = WATCH_CELL["traffic"]["streams"][1]
    plan = watches.plan(stream, state)
    assert [len(s) for s in plan] == [32, 32, 64, 64, 16, 16, 2, 1]
    shares = [watches.share(stream, state, p, 2) for p in (0, 1)]
    assert shares == [plan[0::2], plan[1::2]]
    assert sum(len(s) for s in plan) == 227 > 64


def test_a_watch_waits_for_the_last_acknowledged_write_of_its_range():
    ranges = [(b"/a/", b"/a0"), (b"/a/x", b"/a/x\0"), (b"/b/", b"/b0")]
    writes = [(b"/a/x", 7), (b"/a/y", 9), (b"/a/x", 12), (b"/c", 30)]
    assert watches.last_acked(writes, ranges) == {
        (b"/a/", b"/a0"): 12, (b"/a/x", b"/a/x\0"): 12}
    streams = [[{"table": "t", "scope": "namespace", "start": b"/a/", "end": b"/a0"},
                {"table": "t", "scope": "key", "start": b"/b/", "end": b"/b0"}],
               [{"table": "t", "scope": "table", "start": b"/", "end": b"0"}]]
    assert watches.targets(streams, {"t": 31}, watches.last_acked(writes, ranges)) == [
        12, 0, 31]


def test_an_event_costs_29_bytes_of_record():
    assert watches.EVENT_BYTES == 29
    assert sum(np.dtype(dt).itemsize for _n, _c, dt in watches.FIELDS) == 29


# ---- the comparison per range
def _one_range_case(error="", extra=None, missing=False):
    state = _fixture_state(11)
    t = state.tables["pods"]
    ns = t.ns_prefix(3)
    recs = []
    for n, i in enumerate(range(3, 400, 8)):       # namespace 3's objects
        recs.append((1, "update", 0.0, 0.0, 0.1, True, 10_000 + n,
                     state.key_id(t, i), 1, 0, "", False))
    other = (1, "update", 0.0, 0.0, 0.1, True, 20_000, state.key_id(t, 4), 1, 0, "", False)
    traffic = [{"recs": recs + [other], "samples": [], "judged": True, "loop": "open"}]
    want = [(r[6], 0, check.zlib.crc32(t.key(r[7] - t.offset)),
             state.value_crc(t, r[7] - t.offset, 1)) for r in recs]
    got = want[5:]                                  # created at 10_004
    if missing:
        got = got[:-1]
    if extra:
        got = got + [extra]
    cols = {"watch": [0] * len(got), "rev": [g[0] for g in got],
            "type": [g[1] for g in got], "key": [g[2] for g in got],
            "value": [g[3] for g in got], "arrival": [0.2] * len(got)}
    dump = {"watches": [{"table": "pods", "scope": "namespace", "start": ns,
                         "end": etcd.prefix_end(ns), "created": 10_004, "error": error}],
            "events": watches.pack(cols)}
    ref = check.Reference(state, traffic)
    return check.compare_watches(state, ref, [dump], {"pods": 30_000})


def test_a_scoped_watch_is_held_to_its_own_range():
    n = _one_range_case()
    assert n["watch_wrong"]["value"] == 0
    assert n["compared_watch_events"]["value"] == 45      # 50 writes after 10_004: 45
    assert _one_range_case(extra=(30_000, 0, 0, 0))["watch_wrong"]["value"] == 0
    bad = _one_range_case(missing=True)
    assert bad["watch_wrong"]["value"] == 1
    assert "b'/registry/pods/ns-003/'" in bad["watch_wrong"]["first"]
    assert _one_range_case(extra=(20_000, 0, 1, 1))["watch_wrong"]["value"] == 1
    assert _one_range_case(error="canceled: x")["watch_wrong"]["value"] == 1


# ---- the reference's index of watched ranges
def test_the_references_index_finds_exactly_the_watches_a_loop_would():
    rnd = random.Random(5)
    index = refserver.Ranges()
    ws = []
    for n in range(300):
        a = b"/k/%03d" % rnd.randrange(200)
        end = rnd.choice([a + b"\0", etcd.prefix_end(a[:-1]), b"/k/%03d" % rnd.randrange(200), b"/l"])
        w = {"start": a, "end": end, "id": n}
        ws.append(w)
        index.add(w)
    for w in ws[::7]:
        index.remove(w)
    live = [w for n, w in enumerate(ws) if n % 7]
    for _ in range(500):
        key = b"/k/%03d" % rnd.randrange(210) + rnd.choice([b"", b"x", b"\0"])
        assert sorted(w["id"] for w in index.match(key)) == sorted(
            w["id"] for w in live if w["start"] <= key < w["end"])
    assert index.match(b"/a") == [] and index.match(b"/z") == []

