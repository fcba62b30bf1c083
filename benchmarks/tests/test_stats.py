"""The percentile arithmetic on fixed samples."""

import pytest

from stats import percentile


def test_percentile_fixed_samples():
    xs = list(range(1, 101))            # 1..100
    assert percentile(xs, 50) == 50.5
    assert percentile(xs, 95) == pytest.approx(95.05)
    assert percentile(xs, 0) == 1 and percentile(xs, 100) == 100
    assert percentile([7.0], 95) == 7.0
    assert percentile([3, 1, 2], 50) == 2     # order does not matter


def test_percentile_of_nothing_is_an_error_not_zero():
    with pytest.raises(ValueError):
        percentile([], 95)
