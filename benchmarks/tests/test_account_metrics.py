"""The per-layer metrics that read the program's own account (PR 26): each
is a file under ``metrics/`` found by ``run.py``'s loader, over a reader of
``readers/``; two readers are new (``prom_gauge``, ``prom_sum``). Read on a
scrape of a program that keeps the account, and on one of a program that
does not (the recorded ``metrics_*.txt``: no ``rpc`` label, no phase, no
gauge), where each finds nothing to read and raises nothing."""

import json
import os
from types import SimpleNamespace

import pytest

import plugin
import prom
import run
from conftest import BENCH

HERE = os.path.dirname(os.path.abspath(__file__))


def _ctx(stem: str) -> SimpleNamespace:
    def snap(end):
        with open(os.path.join(HERE, f"{stem}_{end}.txt")) as f:
            return prom.parse(f.read())
    return SimpleNamespace(before=snap("before"), after=snap("after"))


NEW = _ctx("account")   # a program that keeps the account
OLD = _ctx("metrics")   # the parent's exposition: none of the new series
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as _f:
    PER_LAYER = {m["name"]: m for m in json.load(_f)["per_layer"]}

#: metric -> (value on the account's scrapes, value on the parent's)
EXPECTED = {
    "queue_wait_ms.range": (7.0, None),          # (0.19 - 0.05) / 20 Ranges
    "response_encode_ms.range": (20.0, None),
    "range_unaccounted_ms": (0.5, None),
    "queue_wait_ms.txn": (2.0, None),            # 0.8 s over 400 Txns
    "merge_locked_ms": (1200.0, 0.0),            # snapshot 0.3 s + swap 0.9 s
    "merge_build_ms": (2100.0, 0.0),
    "write_lock_wait_s": (7.5, 0.0),             # who="write" alone
    "count_overlay_ms": (2500.0, 0.0),
    "delta_rows_at_open.range": (1000.0, None),  # the window's FIRST scrape
    "delta_rows_at_open.txn": (1000.0, None),
    "boot_jax_init_s": (9.5, None),
    "boot_store_open_s": (4.25, None),
    "boot_mirror_build_s": (6.0, None),
    "boot_compact_warm_s": (1.75, None),         # the compaction warm-up
}
# k8s-2500.relist-merge reads eight of them under twin names (the same reader
# and arguments: test_contract.py), so the same values
EXPECTED.update({base + ".beside": EXPECTED[base] for base in (
    "queue_wait_ms.range", "response_encode_ms.range", "range_unaccounted_ms",
    "delta_rows_at_open.range", "queue_wait_ms.txn", "write_lock_wait_s",
    "merge_locked_ms", "merge_build_ms")})


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_resolves_through_the_loader(name):
    assert name in PER_LAYER, "every new metric file has its per_layer entry"
    kept, parent = EXPECTED[name]
    assert run.read_metric(name, NEW) == pytest.approx(kept)
    got = run.read_metric(name, OLD)
    assert got == parent if parent is None else got == pytest.approx(parent)
    # no scrape at all (the plain reference in the program's place): nothing
    assert run.read_metric(name, SimpleNamespace(before=None, after=None)) is None


def test_every_metric_over_the_accounts_series_is_expected_here():
    added = ('"rpc"', "kb_rpc_unaccounted_seconds", "kb_mirror_merge_phase_seconds",
             "kb_mirror_lock_wait_seconds", "kb_mirror_delta_rows", "kb_boot_seconds")
    for name in PER_LAYER:
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            text = f.read()
        assert (name in EXPECTED) == any(s in text for s in added), name


def test_the_merge_account_closes_on_the_scrapes():
    """Phases tile a merge: their window totals over the merges counted are
    its mean duration, ``merge_stall_ms``."""
    merges = run.read_metric("merges_in_window.txn", NEW)
    phases = (run.read_metric("merge_locked_ms", NEW)
              + run.read_metric("merge_build_ms", NEW))
    assert merges == 3
    assert phases / merges == pytest.approx(run.read_metric("merge_stall_ms", NEW))


def test_prom_gauge_on_a_present_and_an_absent_series():
    gauge = plugin.load(os.path.join(BENCH, "readers"), "prom_gauge")
    assert gauge.read(NEW, "kb_mirror_delta_rows") == 1000.0
    assert gauge.read(NEW, "kb_mirror_delta_rows", at="after") == 2212.0
    assert gauge.read(NEW, "kb_boot_seconds", {"phase": "listen"}, "after") == 0.5
    # a label subset sums, as everywhere in the benchmark
    assert gauge.read(NEW, "kb_boot_seconds", at="after") == pytest.approx(22.0)
    # absent: nothing, never 0 -- a series, a label value, a whole scrape
    assert gauge.read(NEW, "kb_no_such_gauge") is None
    assert gauge.read(NEW, "kb_boot_seconds", {"phase": "relayout"}) is None
    assert gauge.read(OLD, "kb_mirror_delta_rows") is None
    assert gauge.read(SimpleNamespace(before=None, after=None), "kb_boot_seconds") is None
    with pytest.raises(ValueError):
        gauge.read(NEW, "kb_boot_seconds", at="middle")


def test_prom_sum_adds_series_and_reads_zero_where_nothing_ran():
    total = plugin.load(os.path.join(BENCH, "readers"), "prom_sum")
    phase = lambda p: {"name": "kb_mirror_merge_phase_seconds", "labels": {"phase": p}}
    assert total.read(NEW, [phase("snapshot")]) == pytest.approx(0.3)
    assert total.read(NEW, [phase("snapshot"), phase("swap")], scale=1000) == pytest.approx(1200.0)
    assert total.read(NEW, [phase("relayout")]) == 0.0
    assert total.read(SimpleNamespace(before=None, after=None), [phase("swap")]) is None
