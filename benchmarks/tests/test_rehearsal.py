"""Every cell, end to end, at a tiny size on the CPU backend (about 2,000
start-state rows, a 3 s window, Pallas interpreted): the same command,
server, generators, warm-up, comparison and printing as on the chip, so a
wrong path or argument is found here. A rehearsal is never a result:
``correct`` is false and no number it prints is a device's."""

import json
import os
import subprocess
import sys

import pytest

import run
from conftest import BENCH
from state import State

ROOT = os.path.dirname(BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    B = json.load(_f)
SEED = 2**31 + 11
#: start-state rows a rehearsal shrinks each configuration to
ROWS = 2000


def _scale(config: str) -> float:
    """The ``--scale`` that brings the configuration's start state (its
    objects and its history) to about ``ROWS`` rows."""
    return ROWS / State(run.load_json("configs", config + ".json"), SEED).rows


def _metrics(kind: str, cell: str) -> set[str]:
    return {m["name"] for m in B[kind] if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in B["workloads"]])
def test_cell_on_the_cpu_backend(cell, trace):
    config = next(w["config"] for w in B["workloads"] if w["name"] == cell)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(SEED), "--seconds", "3", "--trace", str(trace),
         "--sut", "cpu", "--scale", str(_scale(config))],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=dict(os.environ, BENCH_RUN="x"))
    assert out.returncode == 0, out.stderr[-4000:]
    lines = out.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared" and line["correct"] is False
    assert line["rehearsal"]["comparison_passed"], out.stderr[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert all(set(v) == {"value", "limit"} for v in line["compared"].values())
    # the metrics the cell has to report: device-trace ones need a device,
    # and a metric whose file says what it ``needs`` (a merge, a group of
    # two writes, a Compact) may find nothing in a 3 s window at this size;
    # a reader that finds nothing to read returns nothing, never 0
    mine = _metrics("per_layer" if trace else "end_to_end", cell)
    want = {n for n in mine if "needs" not in run.load_json(
        "metrics", n + ".json")} - {
        m["name"] for m in B["per_layer"] if m["source"] == "device_trace"}
    assert want <= set(line["metrics"]), want - set(line["metrics"])
    # and none of another cell's, nor of the other list
    assert set(line["metrics"]) <= mine, set(line["metrics"]) - mine
    assert all(m["value"] is not None and m["unit"] for m in line["metrics"].values())
    # the earlier lines that make a refusal readable
    text = "\n".join(lines[:-1])
    for needle in ("failed requests by error:", "max latency by second",
                   "merges in window: counted 0, designed 0"):
        assert needle in text, needle
    assert "compared readback_wrong: 0 (limit <= 0)" in out.stderr


def test_no_accelerator_no_result():
    """On a machine without a TPU the command fails and prints no result."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "k8s-2500.steady", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"})
    assert out.returncode != 0 and out.stdout.strip() == ""
