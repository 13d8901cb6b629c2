"""The closed loop's window rule, on a fake clock."""
import pytest

from bench.loops import closed_loop


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def _steps(clock, durations):
    def step(i):
        clock.t += durations[i]
    return step


def test_time_runs_to_the_last_call_that_ends_inside():
    clock = Clock()
    # 14 s calls in a 45 s window: three end by 42 s; a fourth would end
    # at 56 s, so it never starts
    n, elapsed = closed_loop(_steps(clock, [14.0] * 10), 45.0, 1, clock)
    assert (n, elapsed) == (3, 42.0)
    assert elapsed / n == 14.0


def test_a_stall_counts_in_the_time():
    clock = Clock()
    n, elapsed = closed_loop(_steps(clock, [1.0, 1.0, 6.0, 1.0, 1.0]), 9.0, 1,
                             clock)
    # 1 + 1 + 6 = 8; the stalled call's 6 s then estimates the next, which
    # would end past 9 s
    assert (n, elapsed) == (3, 8.0)


def test_at_least_one_call_runs():
    clock = Clock()
    n, elapsed = closed_loop(_steps(clock, [30.0, 30.0]), 10.0, 1, clock)
    assert (n, elapsed) == (1, 30.0)


def test_many_short_calls_fill_the_window():
    clock = Clock()
    n, elapsed = closed_loop(_steps(clock, [0.25] * 100), 10.0, 1, clock)
    assert n == 40 and elapsed == 10.0


@pytest.mark.parametrize("call_s, passes", [
    (13.9, 1),   # a pool of three 13.9 s colorings: one pass, 41.7 s
    (12.7, 1),   # 8% faster: a fourth call would fit, a second pass not
    (9.0, 1),
    (8.0, 2),    # a second pass, 48 s in all, fits inside 51 s
    (4.0, 4)])
def test_the_window_ends_only_at_whole_passes(call_s, passes):
    clock = Clock()
    n, elapsed = closed_loop(_steps(clock, [call_s] * 30), 51.0, 3, clock)
    assert n == 3 * passes
    assert elapsed == pytest.approx(n * call_s)


def test_a_slow_pass_estimates_the_next():
    clock = Clock()
    # the first pass stalls (6 s); its length says a second would end at
    # 12 s, past the 10 s window, so none starts
    n, elapsed = closed_loop(_steps(clock, [1.0, 5.0, 1.0, 1.0]), 10.0, 2,
                             clock)
    assert (n, elapsed) == (2, 6.0)
