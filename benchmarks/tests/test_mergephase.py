"""The merge-phase rule: the designed number of delta merges in a window is
arithmetic on the engine's threshold T, the warm-up's residue r, the write
rate w and the window W — and every traffic file keeps to it."""

import json
import os
from types import SimpleNamespace

import pytest

import mergephase
import run
from conftest import BENCH

ROOT = os.path.dirname(BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    B = json.load(_f)


def test_the_engine_still_merges_every_4096_rows():
    """A program PR that changes the default meets a loud benchmark, not a
    silently different cell."""
    import inspect
    import sys

    sys.path.insert(0, ROOT)
    from kubebrain_tpu.storage.tpu.engine import TpuScanner

    init = TpuScanner.__init__
    # the field sanitizer wraps __init__ and keeps the original as a default
    init = inspect.signature(init).parameters.get(
        "_orig", inspect.Parameter("x", 1, default=init)).default
    default = inspect.signature(init).parameters["merge_threshold"].default
    assert default == mergephase.MERGE_THRESHOLD == 4096


@pytest.mark.parametrize("t,r,w,seconds,want", [
    (4096, 48, 40, 50, 0),        # relist: 2,048 rows, half the threshold
    (4096, 1000, 270, 50, 3),     # steady: kicked at 11.5, 26.6 and 41.8 s
    (4096, 800, 200, 50, 2),
    (4096, 400, 200, 10, 0),      # the output check's short window
    (4096, 400, 200, 18.4, 0), (4096, 400, 200, 18.6, 1),
    (4096, 600, 505, 50, 6),      # 300 closed-loop inserters at PR 24's rate
    (4096, 0, 90, 50, 1),         # PR 24's relist: 16 rows under, by chance
    (1000, 999, 1, 1, 1),
])
def test_designed_integer(t, r, w, seconds, want):
    assert mergephase.merges(r, w, seconds, t) == want


def test_write_rate_counts_only_the_operations_that_write():
    steady = run.load_json("traffic", "steady.json")
    assert mergephase.write_rate(steady) == (270.0, 270.0)   # polls: none
    assert mergephase.write_rate(steady, 0.5) == (135.0, 135.0)
    # a closed loop's write rate is what its file says it reaches
    closed = {"warmup_writes": 600, "merges_in_window": {"min": 5, "max": 8},
              "closed_loop_writes_per_s": {"min": 440, "max": 660},
              "streams": [{"loop": "closed", "clients": 300, "ops": [
                  {"op": "create", "table": "kv", "weight": 1}]}]}
    assert mergephase.write_rate(closed) == (440.0, 660.0)
    assert mergephase.crossings(closed, 50) == (5, 8)
    assert mergephase.design_faults(closed, 50) == []


@pytest.mark.parametrize("cell", [w["name"] for w in B["workloads"]])
def test_every_traffic_file_keeps_the_rule_at_run_seconds(cell):
    traffic = run.load_json("traffic", cell.split(".", 1)[1] + ".json")
    assert mergephase.design_faults(traffic, B["run_seconds"]) == []
    lo, hi = mergephase.crossings(traffic, B["run_seconds"])
    want = traffic["merges_in_window"]
    assert (lo, hi) == ((want["min"], want["max"]) if isinstance(want, dict)
                        else (want, want))


def _mix(*streams, warmup_writes=0, **more):
    return dict(more, warmup_writes=warmup_writes, streams=list(streams))


_WRITERS = {"loop": "open", "rate": 270, "ops": [
    {"op": "update", "table": "leases", "weight": 1}]}
_CLOSED_WRITERS = {"loop": "closed", "clients": 300, "ops": [
    {"op": "create", "table": "kv", "weight": 1}]}
_COUNT_POLL = {"loop": "open", "rate": 0.05, "ops": [
    {"op": "count", "table": "leases", "weight": 1}]}


def _listers(clients):
    return {"loop": "closed", "clients": clients, "ops": [
        {"op": "range_unpaged", "table": "pods", "weight": 1}]}


@pytest.mark.parametrize("traffic,seconds,want", [
    # the three cells: k crossings, up to R follow-ups behind each
    ("steady", 50, (3, 3)),          # no closed-loop reader: R = 0
    ("relist", 50, (0, 0)),          # 3 listers, but no crossing to follow
    ("relist-merge", 50, (3, 12)),   # 3 crossings x (1 + 3 listers)
    # a closed loop of writers alone reads what it read: R = 0
    (_mix(_CLOSED_WRITERS, warmup_writes=600,
          closed_loop_writes_per_s={"min": 440, "max": 660}), 50, (5, 8)),
    # 5 closed-loop readers at k = 2: 2 x (1 + 5)
    (_mix(_WRITERS, _listers(5), warmup_writes=800), 30, (2, 12)),
    # two reader streams add up; a paged list (host iterator) is no device read
    (_mix(_WRITERS, _listers(2), _listers(1), {"loop": "closed", "clients": 9, "ops": [
        {"op": "range_paged", "table": "pods", "page": 500, "weight": 1}]},
          warmup_writes=800), 30, (2, 8)),
    # an open-loop Count adds nothing: an open loop stays out of R
    (_mix(_WRITERS, _COUNT_POLL, warmup_writes=1000), 50, (3, 3)),
    # the fewest stays the fewest crossings where a closed loop sets the rate
    (_mix(_CLOSED_WRITERS, _listers(3), warmup_writes=600,
          closed_loop_writes_per_s={"min": 440, "max": 660}), 50, (5, 32)),
])
def test_the_rule_allows_reader_followups_and_never_requires_them(
        traffic, seconds, want):
    """``TpuScanner._ensure_published`` lets a closed-loop device reader that
    meets a merge in flight merge the tail itself: up to R follow-ups a
    crossing. A program without read-path merges counts k and is inside."""
    if isinstance(traffic, str):
        traffic = run.load_json("traffic", traffic + ".json")
    assert mergephase.expected(traffic, seconds) == want
    assert want[0] == mergephase.crossings(traffic, seconds)[0]


def test_relist_merge_keeps_the_design_and_reads_r_from_its_file():
    mix = run.load_json("traffic", "relist-merge.json")
    assert mergephase.design_faults(mix, 50) == []
    assert mergephase.followup_readers(mix) == 3
    assert mergephase.crossings(mix, 50) == (3, 3)
    # its two streams are the other two cells', unchanged
    assert mix["streams"][0] == run.load_json("traffic", "relist.json")["streams"][0]
    assert mix["streams"][1] == run.load_json("traffic", "steady.json")["streams"][0]


def test_a_design_on_the_edge_is_refused():
    relist = run.load_json("traffic", "relist.json")
    relist["streams"][1]["rate"] = 80          # PR 24's near miss: 4,048 rows
    assert any("T/2" in f for f in mergephase.design_faults(relist, 50))
    steady = run.load_json("traffic", "steady.json")
    steady["warmup_writes"] = 0                # the last merge ends too late
    assert any("not over" in f for f in mergephase.design_faults(steady, 45))
    steady["warmup_writes"] = 1000
    steady["streams"][0]["rate"] = 300         # a fourth merge within T/4
    assert mergephase.design_faults(steady, 50)


@pytest.mark.parametrize("mix,crossings,expected", [
    ("relist", (0, 0), (0, 0)), ("steady", (3, 3), (3, 3)),
    ("relist-merge", (3, 3), (3, 12))])
def test_the_three_mixes_read_what_they_read_before_the_compact(mix, crossings, expected):
    """The parent's answers: no Compact in any of the three files, so no
    stretch but the whole window."""
    traffic = run.load_json("traffic", mix + ".json")
    assert mergephase.compacts(traffic, 50) == []
    assert mergephase.crossings(traffic, 50) == crossings
    assert mergephase.expected(traffic, 50) == expected
    assert mergephase.design_faults(traffic, 50) == []
    assert mergephase.segments(traffic, 50, 270, late=True) == [
        (0.0, 50, int(traffic["warmup_writes"]))]


def _compactor(second, interval=300):
    """kube-apiserver's compactor placed as a count poll is: due at
    ``second`` of the window."""
    return {"name": "compactor", "loop": "open", "rate": 1 / interval,
            "phase": second / interval, "ops": [{"op": "compact", "interval_s": interval}]}


def test_a_compact_publishes_the_delta_and_the_crossings_move():
    """Steady's writers from a residue of 3,000 rows: crossings at 4.1 s,
    then the Compact of 12 s publishes the delta (one merge), and the rows
    count from 0 again — crossings at 27.2 and 42.3 s, whether the pass lasts
    0 or 7 s. Three crossings and the Compact's merge; with 3 closed-loop
    listers up to 3 follow-ups a crossing, none behind the Compact (its pass
    holds the merge lock, ``_compact_active``)."""
    mix = _mix(_WRITERS, _compactor(12), warmup_writes=3000, merges_in_window=3)
    assert mergephase.compacts(mix, 50) == [pytest.approx(12.0)]
    assert [(round(a, 6), round(b, 6), r) for a, b, r in
            mergephase.segments(mix, 50, 270, late=True)] == [(0, 12, 3000), (19, 50, 0)]
    assert mergephase.crossings(mix, 50) == (3, 3)
    assert mergephase.expected(mix, 50) == (4, 4)
    assert mergephase.design_faults(mix, 50) == []
    listers = _mix(_WRITERS, _listers(3), _compactor(12), warmup_writes=3000)
    assert mergephase.expected(listers, 50) == (4, 3 * 4 + 1)
    # without the Compact the same writes cross four times, at 4.1, 19.2,
    # 34.4 and 49.5 s: the last one's stall outlasts the window
    plain = _mix(_WRITERS, warmup_writes=3000, merges_in_window=4)
    assert mergephase.crossings(plain, 50) == (4, 4)
    assert any("not over" in f for f in mergephase.design_faults(plain, 50))


@pytest.mark.parametrize("second,fault", [
    (15, "if a Compact's pass lasts 7 s"),        # 2 crossings after it, or 1
    (8, "not over by the Compact at 8.0 s"),      # the crossing of 4.1 s meets it
    (45, "its stall is not over by 50 s"),
])
def test_a_compact_on_the_edge_is_refused(second, fault):
    mix = _mix(_WRITERS, _compactor(second), warmup_writes=3000, merges_in_window=3)
    assert any(fault in f for f in mergephase.design_faults(mix, 50)), \
        mergephase.design_faults(mix, 50)


def test_a_compact_stands_alone_in_an_open_loop():
    bad = _compactor(12)
    bad["ops"].append({"op": "count", "table": "pods", "weight": 1})
    with pytest.raises(ValueError):
        mergephase.compacts(_mix(_WRITERS, bad), 50)


def test_two_compacts_closer_than_a_stall_count_no_rows_between():
    mix = _mix(_WRITERS, _compactor(10, interval=3), warmup_writes=0)
    assert mergephase.compacts(mix, 20) == [pytest.approx(c) for c in (10, 13, 16, 19)]
    assert mergephase.crossings(mix, 20) == (0, 0)
    assert mergephase.expected(mix, 20) == (4, 4)


# --- a traffic file's own merge allowance ----------------------------------

#: the 5,000-node cluster's documented stream: 500 Lease renewals (nodes /
#: 10 s) + 10 pod creates + 10 pod deletes a second, open loop
_CHURN_520 = {"loop": "open", "rate": 520, "ops": [
    {"op": "update", "table": "leases", "weight": 50},
    {"op": "create", "table": "pods", "weight": 1},
    {"op": "delete", "table": "pods", "weight": 1}]}


def _churn(r, **more):
    return _mix(_CHURN_520, _COUNT_POLL, warmup_writes=r, merges_in_window=6, **more)


def test_a_520_rows_per_s_stream_needs_an_allowance_under_the_default():
    """At 520 rows/s crossings come T/w = 7.88 s apart; the default 7 s
    allowance and the T/4 margin need 8.97 s, so no warm-up fill gives a
    design. At 4 s, every fill from 656 to 1,647 gives six crossings (at
    5.6 ... 45.0 s with r = 1,200; the seventh at 52.8 s), and no other."""
    assert not any(mergephase.design_faults(_churn(r), 50) == [] for r in range(4096))
    admitted = [r for r in range(4096)
                if mergephase.design_faults(_churn(r), 50, stall_s=4.0) == []]
    assert admitted == list(range(656, 1648))
    assert [r for r in range(4096) if mergephase.design_faults(
        _churn(r), 50, stall_s=5.0) == []] == list(range(1176, 1648))
    assert mergephase.crossing_times(_churn(1200), 50) == pytest.approx(
        [(i * 4096 - 1200) / 520 for i in range(1, 7)])
    assert (7 * 4096 - 1200) / 520 == pytest.approx(52.83, abs=0.01)


@pytest.mark.parametrize("stall_s", [5.91, 6.0, 7.0])
def test_no_520_rows_per_s_design_above_5_9_s(stall_s):
    assert all(mergephase.design_faults(_churn(r), 50, stall_s=stall_s)
               for r in range(4096))


def test_the_file_states_its_allowance_on_a_measurement():
    """``merge_stall_s`` in the file is the allowance the rule reads, and it
    stands on ``merge_stall_measured_s``: at least twice it, and cited in
    the file's ``merge_rule``."""
    rule = "six crossings; the longest stall and backlog measured 1.85 s"
    good = _churn(1200, merge_stall_s=4.0, merge_stall_measured_s=1.85, merge_rule=rule)
    assert mergephase.merge_stall_s(good) == 4.0
    assert mergephase.design_faults(good, 50) == []
    unmeasured = _churn(1200, merge_stall_s=4.0, merge_rule=rule)
    assert any("merge_stall_measured_s" in f
               for f in mergephase.design_faults(unmeasured, 50))
    thin = _churn(1200, merge_stall_s=4.0, merge_stall_measured_s=2.1,
                  merge_rule=rule.replace("1.85", "2.1"))
    assert any("2 x" in f for f in mergephase.design_faults(thin, 50))
    uncited = _churn(1200, merge_stall_s=4.0, merge_stall_measured_s=1.85,
                     merge_rule="six crossings")
    assert any("does not cite" in f for f in mergephase.design_faults(uncited, 50))
    # the file's allowance, not the default: r = 700 is a design at 4 s only
    assert mergephase.design_faults(dict(good, warmup_writes=700), 50) == []
    assert mergephase.design_faults(_churn(700), 50)


@pytest.mark.parametrize("mix,crossings,expected,times", [
    ("relist", (0, 0), (0, 0), []),
    ("steady", (3, 3), (3, 3), [11.47, 26.64, 41.81]),
    ("relist-merge", (3, 3), (3, 12), [9.61, 24.79, 39.96]),
    ("relist-compact", (2, 2), (3, 9), [14.99, 39.17])])
def test_the_four_accepted_files_read_as_before(mix, crossings, expected, times):
    """None states an allowance: each reads the default, the parent's
    crossings and expected merges, and an empty ``design_faults``; the
    Compact's pass of relist-compact still counts at 7 s."""
    traffic = run.load_json("traffic", mix + ".json")
    assert "merge_stall_s" not in traffic
    assert mergephase.merge_stall_s(traffic) == mergephase.STALL_S == 7.0
    assert mergephase.crossings(traffic, 50) == crossings
    assert mergephase.expected(traffic, 50) == expected
    assert mergephase.design_faults(traffic, 50) == []
    assert [round(c, 2) for c in mergephase.crossing_times(traffic, 50)] == times
    if mix == "relist-compact":
        # the late stretch starts once the Compact's 7 s pass is over
        assert mergephase.segments(traffic, 50, 270, late=True)[1][0] == pytest.approx(31.0)


def test_a_compacts_allowance_does_not_follow_the_files():
    """A short merge allowance leaves the Compact's at ``STALL_S``: a
    Compact due at 45 s is still refused, and its late stretch still starts
    7 s after it."""
    mix = _mix(_WRITERS, _compactor(12), warmup_writes=3000, merges_in_window=3)
    assert mergephase.design_faults(mix, 50, stall_s=2.0) == []
    late = _mix(_WRITERS, _compactor(45), warmup_writes=3000, merges_in_window=3)
    assert any("its stall is not over by 50 s" in f
               for f in mergephase.design_faults(late, 50, stall_s=2.0))
    assert mergephase.crossings(mix, 50) == (3, 3)


@pytest.mark.parametrize("worst,crossings,want", [
    # a crossing at 5.5 holds seconds 5-7 up: over at the end of second 7
    ([3, 3, 3, 3, 3, 90, 80, 70, 3, 3], [5.5], [2.5]),
    # a held-up second that does not touch the crossing's run is not its
    ([3, 3, 3, 3, 3, 90, 3, 70, 3, 3], [5.5], [0.5]),
    # the crossing's own second held nobody up
    ([3, 3, 3, 3, 3, 3, 90, 3, 3, 3], [5.5], [0.0]),
    # two crossings, and a run to the window's end
    ([3, 40, 3, 3, 3, 3, 3, 3, 50, 50], [1.2, 8.9], [0.8, 1.1]),
    ([], [1.0], []),
])
def test_a_crossings_stall_is_the_run_of_held_up_seconds_from_it(worst, crossings, want):
    """Held up: a second whose worst Txn took more than twice the window's
    median worst Txn of a second (3 ms here, so 6 ms)."""
    assert mergephase.stalls(worst, crossings) == pytest.approx(want)


def _recorded(held_up: range, allowance=4.0):
    """A recorded 50 s window of the 520 rows/s design (r = 1,200, crossings
    at 5.6 ... 45.0 s): every Txn answered in 3 ms, but those due in the
    seconds ``held_up``, 400 ms."""
    mix = _churn(1200, merge_stall_s=allowance, merge_stall_measured_s=allowance / 2,
                 merge_rule=f"measured {allowance / 2:g} s")
    recs = []
    for i in range(520 * 50):
        due = 100.0 + i / 520
        ms = 400.0 if int(due - 100.0) in held_up else 3.0
        recs.append((1, "update", due, due, due + ms / 1e3, True, i + 1, 0, 0, 0, ""))
    traffic = [{"judged": True, "loop": "open", "recs": recs}]
    opts = SimpleNamespace(sut="reference", trace=0, workload="x")
    return run.Context(opts, mix, {}, SimpleNamespace(rows=0, head_revision=0),
                       traffic, [], (100.0, 150.0), 1.0, None, None, 0,
                       {"platform": "reference"})


def _merge_line(ctx) -> str:
    return next(line for line in run.summary_lines(ctx)
                if line.startswith("merges in window:"))


def test_a_stall_over_the_allowance_is_loud_and_one_under_it_is_not():
    """The crossing of 21.3 s (r = 1,200, 520 rows/s): writers held up in
    seconds 21-26 are 5.68 s of stall and backlog against the file's 4 s;
    in seconds 21-22, 1.68 s. Only the first run prints the loud line, and
    every run prints each crossing's reading beside the allowance."""
    over = _merge_line(_recorded(range(21, 27)))
    assert "stall and backlog after each crossing (s): 0.00 0.00 5.68 0.00 0.00 0.00 " \
           "(allowance 4 s)" in over
    assert over.endswith("*** MERGE STALL OVER THE DESIGN'S ALLOWANCE ***")
    under = _merge_line(_recorded(range(21, 23)))
    assert "0.00 0.00 1.68 0.00" in under and "ALLOWANCE ***" not in under
    # the same 5.68 s under a file that allows 7 s is quiet
    assert "ALLOWANCE ***" not in _merge_line(_recorded(range(21, 27), allowance=7.0))
    assert "MERGE PHASE OFF" not in over     # no /metrics: nothing counted
