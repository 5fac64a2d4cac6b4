"""The rate over whole steps has no whole-step quantisation."""
from chipbench.common import percentile, spread, whole_step_rate


def steps_until(seconds, step_s, t0=100.0):
    done, t = [], t0
    while not done or done[-1] - t0 < seconds:
        t += step_s
        done.append(t)
    return done


def test_rate_does_not_move_with_where_the_window_ends():
    step_s, tokens = 0.5525, 8192
    rates = [whole_step_rate(100.0, steps_until(sec, step_s), tokens) for sec in (10.0, 10.3, 11.04, 30.0)]
    for r in rates:
        assert abs(r - tokens / step_s) < 1e-6 * r
    # what a count of whole steps in a fixed window would have given
    counted = [int(sec / step_s) * tokens / sec for sec in (10.0, 10.3, 11.04)]
    assert max(counted) / min(counted) - 1 > 0.02


def test_percentile_and_spread():
    assert percentile([1, 2, 3, 4, 5], 50) == 3
    assert percentile(list(range(101)), 95) == 95
    assert abs(spread([10, 10, 10, 10, 11, 9]) - 0.05) < 1e-9   # quartiles 9.75 and 10.25
