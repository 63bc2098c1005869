"""Percentiles and the histogram-delta quantile the readers use."""

import pytest

from benchmark import stats


def test_percentile_interpolates_like_numpy():
    xs = [4.0, 1.0, 3.0, 2.0]
    assert stats.percentile(xs, 0) == 1.0
    assert stats.percentile(xs, 100) == 4.0
    assert stats.percentile(xs, 50) == 2.5
    assert stats.percentile(xs, 95) == pytest.approx(3.85)


def test_histogram_delta_p50():
    edges = (0.1, 0.25, 0.5, 1.0)
    before = {("a",): [5, 0, 0, 0, 0]}
    after = {("a",): [5, 2, 0, 0, 0], ("b",): [0, 1, 1, 0, 0]}
    delta = stats.histogram_delta(before, after)
    assert delta == [0, 3, 1, 0, 0]
    # rank 2 of 4 falls in (0.1, 0.25], two thirds of the way through it
    assert stats.histogram_quantile(edges, delta, 0.5) == pytest.approx(0.2)
    assert stats.histogram_quantile(edges, [0, 0, 0, 0, 3], 0.5) == 1.0
    assert stats.histogram_quantile(edges, [0] * 5, 0.5) is None
