"""The /metrics delta readers on a recorded exposition."""

import os
from types import SimpleNamespace

import pytest

import prom
import run

HERE = os.path.dirname(os.path.abspath(__file__))
BEFORE = prom.parse(open(os.path.join(HERE, "metrics_before.txt")).read())
AFTER = prom.parse(open(os.path.join(HERE, "metrics_after.txt")).read())
CTX = SimpleNamespace(before=BEFORE, after=AFTER)


def test_parse_and_delta():
    assert prom.series_sum(BEFORE, "kb_mirror_state", state="serving") == 1.0
    assert prom.delta(AFTER, BEFORE, "rpc_server_count",
                      method="/etcdserverpb.KV/Range") == 10.0   # _total suffix
    assert prom.delta(AFTER, BEFORE, "no_such_series") == 0.0


def test_mean_between_two_scrapes():
    assert prom.mean_delta(AFTER, BEFORE, "kb_rpc_stage_seconds",
                           stage="queue_wait") == pytest.approx(0.005)
    # nothing observed in the window: nothing to read, never 0
    assert prom.mean_delta(AFTER, BEFORE, "kb_rpc_stage_seconds",
                           stage="host_copy") is None


def test_readers_over_the_recorded_scrapes():
    assert run.read_metric("sched_residency_ms.range", CTX) == pytest.approx(5.0)
    assert run.read_metric("host_copy_ms", CTX) is None
    # 10 batched Ranges in 4 dispatches: 6 rode
    assert run.read_metric("batch_riders_pct", CTX) == pytest.approx(60.0)


def test_no_scrape_no_metric():
    assert run.read_metric("batch_riders_pct",
                           SimpleNamespace(before=None, after=None)) is None
