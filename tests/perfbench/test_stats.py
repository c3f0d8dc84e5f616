"""Percentiles, pooled gaps and interval arithmetic against hand-worked
values."""

import pytest

from perfbench import stats


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (95, 4.8),
                                    (99, 4.96), (100, 5.0)])
def test_percentile_interpolates(q, want):
    assert stats.percentile([5, 1, 4, 2, 3], q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_pooled_gaps_count_where_the_gap_ends():
    streams = [[0.0, 1.0, 2.5, 4.0], [1.9, 2.0, 9.0], [3.0]]
    gaps = stats.pooled_gaps(streams, 2.0, 5.0)
    assert sorted(gaps) == pytest.approx([0.1, 1.5, 1.5])
    assert stats.tokens_in_window(streams, 2.0, 5.0) == 4


def test_interval_arithmetic():
    xs = stats.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert xs == [(0, 3), (5, 8)]
    assert stats.intersect(xs, [(2, 6), (7.5, 10)]) == [(2, 3), (5, 6),
                                                        (7.5, 8)]
    assert stats.subtract([(0, 10)], xs) == [(3, 5), (8, 10)]
    assert stats.subtract(xs, [(0, 10)]) == []
