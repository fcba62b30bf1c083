"""The merge-phase rule: the designed number of delta merges in a window is
arithmetic on the engine's threshold T, the warm-up's residue r, the write
rate w and the window W — and every traffic file keeps to it."""

import json
import os

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
