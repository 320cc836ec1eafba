"""Window arithmetic: the rate is all the work over all the time."""

import pytest

from pb import window


def _completions(t0, rounds):
    out, t = [], t0
    for r in rounds:
        t += r
        out.append(t)
    return out


def test_a_stall_moves_the_rate_and_not_the_median():
    steady = [0.8] * 40
    stalled = [0.8] * 20 + [1.8] + [0.8] * 19
    r0 = window.rounds_per_s(5.0, _completions(5.0, steady))
    r1 = window.rounds_per_s(5.0, _completions(5.0, stalled))
    assert r0 == pytest.approx(1.25)
    assert r1 == pytest.approx(40 / 33.0)
    assert r1 < 0.975 * r0
    assert window.percentile(steady, 50) == window.percentile(stalled, 50)
    assert window.percentile(stalled, 100) == 1.8


def test_gaps_between_rounds_count_against_the_rate():
    # Three rounds of 1 s with 0.5 s of host time before each.
    ends = [1.5, 3.0, 4.5]
    assert window.rounds_per_s(0.0, ends) == pytest.approx(3 / 4.5)


def test_no_round_is_an_error():
    with pytest.raises(ValueError):
        window.rounds_per_s(0.0, [])


@pytest.mark.parametrize("q,want", [(50, 3), (95, 5), (100, 5), (20, 1)])
def test_percentile_is_nearest_rank(q, want):
    assert window.percentile([5, 1, 4, 2, 3], q) == want


def test_spread_is_the_drivers():
    vals = [1.20, 1.21, 1.21, 1.22, 1.21, 1.16]
    import statistics

    q1, q2, q3 = statistics.quantiles(vals, n=4)
    assert window.iqr_share(vals) == pytest.approx((q3 - q1) / q2)
