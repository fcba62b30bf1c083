"""The trace reduction: busy union, idle gaps and operation totals, on
hand-made planes and on a small recorded trace of the served path on a
TPU v5 lite (``recorded.xplane.pb``, a 0.2 s capture of k8s-5k.relist)."""

import json
import os
import subprocess
import sys

import pytest

import tracered
from conftest import BENCH

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded.xplane.pb")


def test_union_merges_overlaps():
    assert tracered.union_ns([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert tracered.union_ns([]) == 0


def test_gaps_between_merged_intervals():
    assert sorted(tracered.gaps_ns([(10, 20), (15, 30), (50, 60)], 0, 100)) == [
        10, 20, 40]


def test_reduce_hand_made_planes():
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [("x", 0, 1_000_000_000)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [("jit_f", 0, 900_000_000)]},
            {"name": "XLA Ops", "events": [
                ("fusion.1", 100_000_000, 100_000_000),
                ("fusion.1", 150_000_000, 100_000_000),
                ("copy.2", 600_000_000, 50_000_000)]}]}]
    out = tracered.reduce(planes)
    assert out["busy_s"] == pytest.approx(0.2)      # 150 ms merged + 50 ms
    assert out["window_s"] == pytest.approx(1.0)
    assert out["ops"][0][0] == "fusion.1" and out["ops"][0][1] == pytest.approx(0.2)
    assert out["idle_gaps"][0] == ["unattributed", pytest.approx(0.35)]


def test_no_device_plane_is_an_error_not_an_idle_device():
    with pytest.raises(ValueError):
        tracered.reduce([{"name": "/host:CPU", "lines": []}])


def test_recorded_trace_reduces():
    """Through the command ``run.py`` uses (a process of its own)."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tracered.py"), RECORDED],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    red = json.loads(out.stdout.strip().splitlines()[-1])
    assert red["devices"] == 1 and 0 < red["busy_s"] < red["window_s"]
    assert red["ops"] and all(s > 0 for _n, s, _c in red["ops"])


# ---- the yardstick of the roofline share: peaks and unavoidable bytes
def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_is_an_error():
    import roofline

    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_read_bytes_and_the_share():
    """k8s-5k's mirror on the chip: 262,144 padded rows, 8,912,900 B (my chip
    run, PR 21). One dispatch carrying two queries must move the mirror once
    and write two masks; at 819 GB/s that is 11.5 us."""
    from types import SimpleNamespace

    import plugin
    import roofline

    assert roofline.padded_rows(192_500) == 262_144
    assert roofline.padded_rows(262_145) == 524_288
    need = roofline.read_bytes(1, 2, 8_912_900, 262_144)
    assert need == 8_912_900 + 2 * 262_144
    reader = plugin.load(os.path.join(BENCH, "readers"), "read_roofline_pct")
    scr = lambda n, members, batches: {
        "kb_rpc_stage_seconds_count": [({"stage": "device_compute"}, n)],
        "kb_sched_batch_size_sum": [({}, members)],
        "kb_sched_batch_size_count": [({}, batches)],
        "kb_mirror_bytes": [({"device": "TPU_0"}, 8_912_900.0)]}
    ctx = SimpleNamespace(
        trace={"busy_s": 0.007, "scrapes": [scr(10, 4, 2), scr(11, 6, 3)]},
        mirror_rows=192_500, device={"kind": "TPU v5 lite"})
    # 1 dispatch, 1 rider -> 2 queries: 11.5 us of 7 ms busy
    assert reader.read(ctx) == pytest.approx(100 * (need / 819e9) / 0.007)
    assert 0.1 < reader.read(ctx) < 0.2
    # nothing to read is nothing, never 0
    ctx.trace["scrapes"] = [scr(10, 4, 2), scr(10, 4, 2)]
    assert reader.read(ctx) is None
    ctx.trace = None
    assert reader.read(ctx) is None
