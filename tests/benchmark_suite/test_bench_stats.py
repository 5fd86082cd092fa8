"""The arithmetic every end-to-end number goes through, on hand-made input."""

import pytest

from benchmarks.lib.stats import percentile, rate_between_fences


@pytest.mark.parametrize("q,want", [(0, 10.0), (50, 25.0), (95, 38.5),
                                    (100, 40.0), (25, 17.5)])
def test_percentile_interpolates_between_order_statistics(q, want):
    # unsorted on purpose; positions (n-1)*q/100 = 0, 1.5, 2.85, 3, 0.75
    assert percentile([40.0, 10.0, 30.0, 20.0], q) == pytest.approx(want)


def test_percentile_of_one_value_and_of_none():
    assert percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        percentile([], 50)


def test_rate_is_over_the_two_outer_fences_not_the_nominal_window():
    # fences at 100.0, 102.5 and 105.0 s after 0, 10 and 20 steps of 8192
    # tokens: 163840 tokens in 5.0 s, whatever the window was meant to be
    fences = [(100.0, 0), (102.5, 81920), (105.0, 163840)]
    assert rate_between_fences(fences) == pytest.approx(32768.0)


@pytest.mark.parametrize("fences", [[], [(1.0, 0)], [(2.0, 0), (2.0, 5)],
                                    [(3.0, 0), (1.0, 5)]])
def test_rate_refuses_what_is_not_a_span_of_time(fences):
    with pytest.raises(ValueError):
        rate_between_fences(fences)
