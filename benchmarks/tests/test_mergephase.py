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
    assert mergephase.expected(closed, 50) == (5, 8)
    assert mergephase.design_faults(closed, 50) == []


@pytest.mark.parametrize("cell", [w["name"] for w in B["workloads"]])
def test_every_traffic_file_keeps_the_rule_at_run_seconds(cell):
    traffic = run.load_json("traffic", cell.split(".", 1)[1] + ".json")
    assert mergephase.design_faults(traffic, B["run_seconds"]) == []
    lo, hi = mergephase.expected(traffic, B["run_seconds"])
    want = traffic["merges_in_window"]
    assert (lo, hi) == ((want["min"], want["max"]) if isinstance(want, dict)
                        else (want, want))


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
