"""The plain leaky bucket (``perfbench/reference/plain_pacer.py``) against
sequences worked by hand from ``RateLimiterController.canPass``: what the
``pacing_client`` cells are held to has to be right on its own."""

import ast
import os

import pytest

from perfbench import manifest as M
from perfbench.reference import plain_pacer as P
from perfbench.reference.plain_pacer import BLOCK_FLOW, NEVER, PASS, PASS_WAIT, PlainPacer, cost_ms


def test_it_imports_nothing_of_the_program_and_its_codes_are_the_programs():
    with open(os.path.join(M.ROOT, "perfbench", "reference", "plain_pacer.py")) as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    names += [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    assert sorted(names) == ["__future__", "math", "typing"]
    from sentinel_tpu.core import errors as ERR

    assert (PASS, BLOCK_FLOW, PASS_WAIT) == (ERR.PASS, ERR.BLOCK_FLOW, ERR.PASS_WAIT)


@pytest.mark.parametrize("count, cost", [
    (10, 100), (1000, 1), (667, 1), (666, 2), (16, 63), (80, 13), (400, 3), (3, 333), (7, 143),
    (2000, 1), (2001, 0),  # Math.round: a half rounds up, and over 2,000 a second the cost is 0
])
def test_cost_is_java_s_round_of_a_thousand_over_the_count(count, cost):
    assert cost_ms(count) == cost


@pytest.mark.parametrize("queue_ms, admitted", [(20_000, 100), (500, 6)])
def test_pace_flow_demo_a_hundred_at_once_on_count_ten(queue_ms, admitted):
    """``PaceFlowDemo``: count 10, a hundred requests released at once.  With
    its 20 s of queue all hundred are admitted, at 0, 100, ... 9,900 ms; with
    ``FlowRule``'s default of 500 ms the first six are (0 to 500), and a
    refused item moves nothing."""
    p = PlainPacer([10], queue_ms)
    verdicts, waits = p.tick(0, [0] * 100)
    assert verdicts == [PASS] + [PASS_WAIT] * (admitted - 1) + [BLOCK_FLOW] * (100 - admitted)
    assert waits[:admitted] == [100 * j for j in range(admitted)]
    assert set(waits[admitted:]) <= {0}
    assert p.latest == [100 * (admitted - 1)]


def test_an_idle_bucket_passes_at_once_however_early_now_is():
    p = PlainPacer([10, 1000])
    assert p.latest == [NEVER, NEVER]
    assert p.can_pass(0, 0) == (PASS, 0) and p.can_pass(1, 3) == (PASS, 0)
    assert p.latest == [0, 3] and p.seen["idle_passes"] == 2


def test_a_bucket_reanchors_to_now_once_its_cost_has_lapsed():
    p = PlainPacer([10])
    assert p.tick(1_000, [0, 0]) == ([PASS, PASS_WAIT], [0, 100])  # latest 1,100
    assert p.can_pass(0, 1_199) == (PASS_WAIT, 1)  # expected 1,200: a millisecond early
    assert p.latest == [1_200]
    assert p.can_pass(0, 1_300) == (PASS, 0)  # expected 1,300 <= now: not a wait of 0
    assert p.latest == [1_300] and p.seen["reanchored"] == 1
    assert p.can_pass(0, 9_000) == (PASS, 0) and p.latest == [9_000]


def test_the_limit_is_admitted_and_a_millisecond_more_is_refused():
    p = PlainPacer([1000], 500)
    verdicts, waits = p.tick(50, [0] * 503)
    assert verdicts == [PASS] + [PASS_WAIT] * 500 + [BLOCK_FLOW] * 2
    assert waits[500] == 500 and waits[501:] == [0, 0]
    assert p.latest == [550]  # the refused items took no time
    assert p.seen["waits_of_exactly_the_limit"] == 1
    assert p.seen["refused_one_ms_past_the_limit"] == 2  # both found the same 501
    assert p.seen["items_at_cost_1"] == 503 and p.seen["items_at_cost_100"] == 0


def test_a_backlog_is_carried_into_the_next_tick_and_topics_do_not_share_a_bucket():
    p = PlainPacer([10, 20])
    assert p.tick(0, [0, 0, 0, 1]) == ([PASS, PASS_WAIT, PASS_WAIT, PASS], [0, 100, 200, 0])
    assert p.seen["backlogs_carried_over"] == 0
    # 25 ms on, topic 0's next is due at 300; topic 1's (cost 50) at 50
    assert p.tick(25, [0, 1, 0]) == ([PASS_WAIT, PASS_WAIT, PASS_WAIT], [275, 25, 375])
    assert p.seen["backlogs_carried_over"] == 2 and p.latest == [400, 50]


def test_a_ticks_items_are_taken_in_submission_order_at_one_now():
    """Interleaved topics: each sees its own items in the order they came."""
    p = PlainPacer([100, 100], 25)
    verdicts, waits = p.tick(7, [0, 1, 0, 1, 0, 1, 0])
    assert verdicts == [PASS, PASS, PASS_WAIT, PASS_WAIT, PASS_WAIT, PASS_WAIT, BLOCK_FLOW]
    assert waits == [0, 0, 10, 10, 20, 20, 0]
    assert P.PlainPacer([100], 25).tick(7, [0] * 4)[0] == [PASS, PASS_WAIT, PASS_WAIT, BLOCK_FLOW]
