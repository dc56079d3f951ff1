"""Step-time and percentile arithmetic on synthetic completion lists."""
import pytest

from benchmark import stats


def test_percentile_interpolates_between_ranks():
    xs = [4.0, 1.0, 3.0, 2.0]
    assert stats.percentile(xs, 0) == 1.0
    assert stats.percentile(xs, 100) == 4.0
    assert stats.percentile(xs, 50) == 2.5
    assert stats.percentile(list(range(1, 102)), 95) == pytest.approx(96.0)
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_step_times_are_lag_one_differences_from_the_window_start():
    # window opens at 10.0; completions at 10.07, 10.14, 10.30
    got = stats.step_times(10.0, [10.07, 10.14, 10.30])
    assert got == pytest.approx([0.07, 0.07, 0.16])


def test_seconds_per_step_is_the_whole_window_over_all_its_steps():
    # 6 steps of 1 s and one stall of 3 s: the stall is in the number, and
    # not in the median of the single steps
    comps = [1.0, 2.0, 3.0, 6.0, 7.0, 8.0, 9.0]      # 7 steps from t=0
    assert stats.seconds_per_step(0.0, comps) == pytest.approx(9.0 / 7)
    assert stats.percentile(stats.step_times(0.0, comps), 50) == 1.0
    # and it is the throughput's reciprocal
    assert stats.seconds_per_step(0.0, comps) == pytest.approx(
        1.0 / stats.throughput(1, 0.0, comps, 1))
    with pytest.raises(ValueError):
        stats.seconds_per_step(0.0, [])


def test_throughput_counts_every_completed_step_to_the_last_completion():
    # 4 steps of 8192 tokens, the last completes 0.5 s after the window opens
    assert stats.throughput(8192, 2.0, [2.1, 2.2, 2.4, 2.5], 1) == \
        pytest.approx(4 * 8192 / 0.5)
    assert stats.throughput(8192, 2.0, [2.5], 4) == pytest.approx(8192 / 0.5 / 4)
    with pytest.raises(ValueError):
        stats.throughput(8192, 2.0, [], 1)
