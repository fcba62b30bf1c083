"""The warm-up's quiet rule (README.md, "What one run does", step 3), on a
made-up compile cache: a cache that grows by nothing ends the warm-up on
the floor alone, and a compile that took a while to land its entry makes
the warm-up wait longer than that for the next."""

import time

import pytest

import run


@pytest.fixture
def cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    return tmp_path


def _until(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline
        time.sleep(0.01)


def test_a_cache_that_does_not_grow_keeps_the_floor(cache):
    (cache / "old-entry").write_bytes(b"")
    growth = run.CacheGrowth(time.monotonic())
    time.sleep(3 * run.CacheGrowth.POLL_S)
    growth.stop()
    assert (growth.added, growth.longest) == (0, 0.0)
    assert growth.quiet_s(3.0) == 3.0


def test_the_longest_wait_for_an_entry_sets_the_quiet(cache):
    begin = time.monotonic()
    growth = run.CacheGrowth(begin)
    time.sleep(0.6)
    (cache / "first").write_bytes(b"")
    _until(lambda: growth.added == 1)
    (cache / "second").write_bytes(b"")
    _until(lambda: growth.added == 2)
    growth.stop()
    # the wait from the warm-up's first request to the first entry is the
    # longest; the second came within a poll of the first
    assert 0.6 <= growth.longest < 0.6 + 3 * run.CacheGrowth.POLL_S
    assert growth.last > begin + 0.6
    assert growth.quiet_s(0.1) == pytest.approx(
        run.CacheGrowth.QUIET_FACTOR * growth.longest)
    assert growth.quiet_s(3.0) == 3.0
