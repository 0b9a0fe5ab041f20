"""The plain circuit breaker (``perfbench/reference/plain_breaker.py``) on its
own, against sequences written by hand from the upstream description:
``ResponseTimeCircuitBreaker.onRequestComplete``, ``AbstractCircuitBreaker.
tryPass``.  One resource, the demo's rule with ``DegradeRule``'s defaults:
50 ms is not slow, more than 0.6 slow of at least 5 trips, retry after 10 s,
one bucket of 1000 ms."""

import numpy as np
import pytest

from perfbench.reference.plain_breaker import CLOSED, HALF_OPEN, OPEN, PlainBreakers


def breaker(**kw):
    rule = dict(max_rt_ms=50, slow_ratio=0.6, retry_ms=10_000, min_requests=5, stat_interval_ms=1000)
    return PlainBreakers(1, **{**rule, **kw})


def exits(b, now_ms, rts, admitted_ms=None):
    b.exits(now_ms, np.zeros(len(rts), np.int64), np.array(rts, np.float32), admitted_ms)


def admitted(b, now_ms, n):
    _ids, _n, got = b.entries(now_ms, np.zeros(n, np.int64))
    return int(got[0])


def test_four_slow_of_five_trips_and_three_of_five_does_not():
    b = breaker()
    exits(b, 100, [51, 60, 70, 10, 50])  # 50 is not slow, 51 is: 3 of 5, exactly 0.6
    assert b.state[0] == CLOSED and b.seen["ratio_ties"] == 1 and admitted(b, 100, 3) == 3
    b = breaker()
    exits(b, 100, [51, 60, 70, 80, 50])
    assert b.state[0] == OPEN and b.deadline[0] == 10_100 and b.seen["opened"] == 1
    assert admitted(b, 100, 3) == 0  # the tick that tripped it admits nothing
    b = breaker()
    exits(b, 100, [51, 60, 70, 80])  # four slow of four: under min_request_amount
    assert b.state[0] == CLOSED
    exits(b, 125, [20])  # the fifth exit, ticks later, completes the window
    assert b.state[0] == OPEN and b.deadline[0] == 10_125


def test_six_of_ten_is_a_tie_and_the_one_point_zero_case_trips_on_equality():
    b = breaker()
    exits(b, 100, [90] * 6 + [10] * 4)
    assert b.state[0] == CLOSED and b.seen["ratio_ties"] == 1
    b = breaker(slow_ratio=1.0)
    exits(b, 100, [90] * 5)  # 1.0 is not over 1.0: upstream trips when both equal 1.0
    assert b.state[0] == OPEN


def test_an_exit_while_open_counts_and_moves_nothing():
    b = breaker()
    exits(b, 100, [90] * 5)
    exits(b, 200, [90, 10, 10])
    assert b.state[0] == OPEN and b.deadline[0] == 10_100 and b.seen["exits_while_open"] == 3
    assert (b.total[0, 0], b.slow[0, 0]) == (8, 6)


def test_one_probe_a_retry_and_nothing_while_half_open():
    b = breaker()
    exits(b, 100, [90] * 5)
    assert admitted(b, 10_099, 4) == 0 and b.state[0] == OPEN
    assert admitted(b, 10_100, 4) == 1 and b.state[0] == HALF_OPEN and b.seen["half_opened"] == 1
    assert admitted(b, 10_125, 4) == 0


def test_the_probes_slow_exit_reopens_with_a_new_deadline():
    b = breaker()
    exits(b, 100, [90] * 5)
    assert admitted(b, 10_100, 1) == 1
    exits(b, 10_250, [120], admitted_ms=[10_100])
    assert b.state[0] == OPEN and b.deadline[0] == 20_250 and b.seen["reopened"] == 1
    assert b.seen["probes_resolved_by_an_earlier_call"] == 0
    assert admitted(b, 20_249, 2) == 0 and admitted(b, 20_250, 2) == 1


def test_a_fast_exit_of_an_earlier_call_closes_a_half_open_breaker_and_resets_its_counts():
    b = breaker()
    exits(b, 100, [90] * 5)
    assert admitted(b, 10_100, 1) == 1
    # a call admitted before the trip comes back fast before the probe does
    exits(b, 10_125, [40], admitted_ms=[90])
    assert b.state[0] == CLOSED and b.seen["closed_again"] == 1
    assert b.seen["probes_resolved_by_an_earlier_call"] == 1
    assert b.total[0].sum() == 0 and b.slow[0].sum() == 0
    assert admitted(b, 10_125, 7) == 7
    exits(b, 10_150, [90] * 4)  # four slow of four again: the reset window starts over
    assert b.state[0] == CLOSED


def test_a_fast_and_a_slow_exit_in_one_tick_reopen():
    b = breaker()
    exits(b, 100, [90] * 5)
    assert admitted(b, 10_100, 1) == 1
    exits(b, 10_200, [10, 90])
    assert b.state[0] == OPEN and b.deadline[0] == 20_200


def test_a_window_that_rolls_over_forgets():
    b = breaker()
    exits(b, 900, [90] * 4)
    exits(b, 1000, [90])  # the grid moved on: one of one, not five of five
    assert b.state[0] == CLOSED and (b.total[0, 0], b.slow[0, 0]) == (1, 1)
    # a ring of two half-buckets slides instead: the four are still in the window
    b = breaker(sample_count=2)
    exits(b, 900, [90] * 4)
    exits(b, 1000, [90])
    assert b.state[0] == OPEN
    # and forgets a bucket of fast exits that expires, tripping with no new exit
    b = breaker(sample_count=2)
    exits(b, 400, [10] * 10)
    exits(b, 600, [90] * 5)
    assert b.state[0] == CLOSED
    exits(b, 1000, [])
    assert b.state[0] == OPEN


def test_resources_do_not_share_state():
    b = PlainBreakers(3, 50, 0.6, 10_000, 5, 1000)
    b.exits(100, np.array([1] * 5 + [2] * 5), np.array([90.0] * 5 + [10.0] * 5))
    assert b.state.tolist() == [CLOSED, OPEN, CLOSED]
    ids, n, got = b.entries(100, np.array([0, 1, 1, 2, 2, 2]))
    assert ids.tolist() == [0, 1, 2] and n.tolist() == [1, 2, 3] and got.tolist() == [1, 0, 3]
