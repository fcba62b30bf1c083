"""The pause rule's arithmetic (README.md, "The pause rule"), on made-up
clocks: what the witness counts a pause, which requests and watch pairs a
pause touches, and that touching a request takes it out of the per-layer
``*_quiet_ms`` tails and out of nothing else: every end-to-end metric, and
``attempted`` and ``failed``, are taken over every request."""

import json
import os
from types import SimpleNamespace

import pytest

import run
import watches
import witness
from conftest import BENCH
from stats import percentile

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as _f:
    B = json.load(_f)
WINDOW = (0.0, 10.0)
# one witnessed pause of 100 ms, as the witness's tick would record it
PAUSE = [(5.0, 5.1 + witness.TICK_S)]
# where the drain behind it ends: DRAIN times its own length later
END = PAUSE[0][1] + witness.DRAIN * (PAUSE[0][1] - PAUSE[0][0])


def _ctx(ticks, txns, events=()):
    """A run's context whose witness saw ``ticks`` (before, after) and whose
    one judged open loop sent ``txns`` (due, done, ok); ``events`` are
    (index of the write, arrival) at one watcher."""
    machine = witness.Witness()
    for before, after in ticks:
        machine.observe(before, after)
    recs = [(1, "update", due, due, done, ok, 100 + i, i, 1, 0, "" if ok else "x",
             False) for i, (due, done, ok) in enumerate(txns)]
    watch_dumps = [{"watches": [{}], "events": watches.pack({
        "watch": [0] * len(events), "rev": [100 + i for i, _a in events],
        "arrival": [arrived for _i, arrived in events]})}]
    opts = SimpleNamespace(workload="k8s-2500.steady", trace=0, sut="cpu",
                           broken="")
    return run.Context(
        opts, {}, {}, SimpleNamespace(rows=0),
        [{"judged": True, "loop": "open", "recs": recs}], watch_dumps, WINDOW,
        1.0, None, None, 0, {}, machine=machine)


def _ms(pairs):
    return [(b - a) * 1e3 for a, b in pairs]


CASES = {
    # name: (ticks, txns, events, the txns kept, the watch pairs kept)
    "a request that ends inside the pause is touched":
        (PAUSE, [(4.95, 5.05, True), (1.0, 1.002, True)], [], [1], []),
    "a request due inside the pause is touched":
        (PAUSE, [(5.09, 5.3, True), (1.0, 1.002, True)], [], [1], []),
    "a request due in the drain after the pause (DRAIN lengths) is touched":
        (PAUSE, [(END - 0.01, END - 0.005, True), (1.0, 1.002, True)], [], [1], []),
    "a request due past the widened end is not":
        (PAUSE, [(END + 0.01, END + 0.015, True), (1.0, 1.002, True)], [], [0, 1], []),
    "a request wholly inside the pause is touched, one that spans it too":
        (PAUSE, [(5.02, 5.08, True), (4.0, 6.0, True), (1.0, 1.002, True)],
         [], [2], []),
    "a request wholly before the pause is not":
        (PAUSE, [(4.0, 4.99, True), (1.0, 1.002, True)], [], [0, 1], []),
    "a touched request that failed still counts as attempted and failed":
        (PAUSE, [(5.05, 5.4, False), (1.0, 1.002, True)], [], [1], []),
    "a watch pair is touched between the write's due time and the event's "
    "arrival, though the write itself was answered before the pause":
        (PAUSE, [(4.9, 4.95, True), (1.0, 1.002, True)],
         [(0, 5.12), (1, 1.003)], [0, 1], [1]),
    "an overshoot under PAUSE_MIN_S is no pause":
        ([(5.0, 5.0 + witness.TICK_S + witness.PAUSE_MIN_S - 1e-4)],
         [(5.0, 5.01, True), (1.0, 1.002, True)], [(0, 5.011)], [0, 1], [0]),
    "an overshoot of PAUSE_MIN_S is one":
        ([(5.0, 5.0 + witness.TICK_S + witness.PAUSE_MIN_S + 1e-4)],
         [(5.0, 5.01, True), (1.0, 1.002, True)], [(0, 5.011)], [1], []),
    "no pause: the quiet percentile is the percentile over all":
        ([(t / 1e3, t / 1e3 + 0.0011) for t in range(0, 10000, 50)],
         [(i * 0.01, i * 0.01 + 0.001 * (1 + i % 17), True) for i in range(900)],
         [(i, i * 0.01 + 0.002 * (1 + i % 13)) for i in range(900)],
         list(range(900)), list(range(900))),
}


@pytest.mark.parametrize("name", list(CASES))
def test_the_pause_rule(name):
    ticks, txns, events, kept, kept_pairs = CASES[name]
    ctx = _ctx(ticks, txns, events)
    ok = [i for i, t in enumerate(txns) if t[2]]
    all_ms = _ms([txns[i][:2] for i in ok])
    kept_ms = _ms([txns[i][:2] for i in kept])
    assert run.read_metric("txn_p99_ms", ctx) == percentile(all_ms, 99)
    assert run.read_metric("txn_p99_quiet_ms", ctx) == percentile(kept_ms, 99)
    assert run.read_metric("touched_ops.txn", ctx) == len(ok) - len(kept)
    assert run.read_metric("touched_ops.txn", ctx) == sum(
        ctx.touched(*txns[i][:2]) for i in ok)
    pairs = [(txns[i][0], arrived) for i, arrived in events]
    assert list(zip(*(a.tolist() for a in ctx.watch_pairs()))) == pairs
    if pairs:
        assert run.read_metric("watch_lag_p99_ms", ctx) == percentile(
            _ms(pairs), 99)
        want = _ms([pairs[j] for j in kept_pairs])
        assert run.read_metric("watch_lag_p99_quiet_ms", ctx) == (
            percentile(want, 99) if want else None)
    # nothing is excused from the counts, nor from what is judged
    out = run.finish(ctx, B, {})["result"]
    assert out["attempted"] == len(txns)
    assert out["failed"] == len(txns) - len(ok)
    assert out["metrics"]["txn_p50_ms"]["value"] == percentile(all_ms, 50)
    if pairs:
        assert out["metrics"]["watch_lag_p50_ms"]["value"] == percentile(
            _ms(pairs), 50)
    if not ctx.machine.pauses:
        assert kept_ms == all_ms and run.read_metric(
            "machine_pause_ms.txn", ctx) == 0.0


def test_the_witnessed_total_is_clipped_to_the_window():
    ctx = _ctx([(-0.05, 0.05), (5.0, 5.1), (9.95, 10.2), (11.0, 11.5)], [])
    assert run.read_metric("machine_pause_ms.txn", ctx) == pytest.approx(200.0)
    line = ctx.machine.line(WINDOW)
    assert line.startswith("machine pauses: n=3 total=200.0 ms max=250.0 ms "
                           "at seconds [-0.05, 5.00, 9.95] (+1 in the drain")


def test_the_thread_witnesses_a_stopped_clock_and_nothing_else():
    """The loop itself, on a clock that jumps once: 60 ms pass in one sleep."""
    now = [100.0]

    def sleep(s):
        now[0] += s + (0.060 if 100.0104 < now[0] < 100.0116 else 0.0001)
        if now[0] > 100.2:
            w._stop.set()

    w = witness.Witness(clock=lambda: now[0], sleep=sleep)
    w._run()
    assert len(w.pauses) == 1
    (f, t), = w.pauses
    assert t - f == pytest.approx(0.061) and 100.0104 < f < 100.0116
    assert max(o for o in w.overshoots if o < witness.PAUSE_MIN_S) < 0.001


@pytest.mark.parametrize("metric", [m["name"] for m in B["end_to_end"]])
def test_no_end_to_end_metric_leaves_a_request_out(metric):
    """The pause rule reads beside the judged metrics, never inside one."""
    with open(os.path.join(BENCH, "metrics", metric + ".json")) as f:
        assert "quiet" not in json.load(f).get("args", {})
