"""The check of a ``breaker_client`` deployment, held to the plain reference
``perfbench/reference/plain_breaker.py``: one slow-call breaker a resource,
evaluated once a tick in the order the program documents.

Inside the window: every request resolved, none errored or answered
BLOCK_SYSTEM, no verdict code but PASS and BLOCK_DEGRADE and both of them
seen; over a seeded sample of the window's blocks no resource whose verdicts
in one tick are anything but all PASS, all BLOCK_DEGRADE or one probe among
BLOCK_DEGRADE; no exit sent for a blocked item, and none left unsent.  A
block answered in full but later than the client's own timeout is late and
not wrong, as in ``flow_replay``.

After the window, on the same client and the same compiled programs: the tick
thread is stopped and the cell's generator drives seeded blocks at stated
virtual times, every admitted item's exit landing ticks after its entry,
while the reference is given the same tick-stamped entries and exits.  Both
start from every breaker CLOSED (the generator sets the program's so before
the first tick, after a gap in which every statistic window has lapsed; the
state the window left is read once, for the summary, and seeds nothing), and
the replay's ticks run at every shape the tick is compiled for, each counted.
Admissions per (tick, resource) and the state of every breaker after every
tick are compared, and **the tolerance is 0, because nothing here is approximate**: a
breaker has an exact row, its counts are integers, and the trip rule compares
a ratio of two small integers with the threshold (3 slow of 5 is not over
0.6).  So that the comparison cannot pass with nothing compared, each kind of
transition, an exit counted while OPEN, a probe resolved by a call admitted
before it, a window standing exactly on the threshold, and a transition on a
row past ``check_params.rows_past`` (16,368, the most rows the other
configurations' tables hold) must each have been seen at least once.
"""

from __future__ import annotations

import collections
from typing import Dict, List, Tuple

import numpy as np

from perfbench.checks import Compared
from perfbench.generators import BLOCK_SYSTEM, PASS, Window
from perfbench.generators.open_loop_exit_blocks import BLOCK_DEGRADE
from perfbench.reference.plain_breaker import PlainBreakers


def in_window(win: Window) -> List[Compared]:
    other = sum(v for k, v in win.codes.items() if k not in (PASS, BLOCK_DEGRADE))
    x = win.extra
    return [
        Compared("window_requests", win.attempted, 1, at_least=True),
        Compared("window_failed", win.failed - win.late, 0),
        Compared("window_unresolved", win.unresolved, 0),
        Compared("window_block_system_items", win.codes.get(BLOCK_SYSTEM, 0), 0),
        Compared("window_other_codes", other, 0),
        Compared("window_passed_items", win.codes.get(PASS, 0), 1, at_least=True),
        Compared("window_degrade_blocked_items", win.codes.get(BLOCK_DEGRADE, 0), 1, at_least=True),
        Compared("window_blocks_sampled", x["blocks_sampled"], 1, at_least=True),
        Compared("window_mixed_verdict_resources", x["mixed_verdict_resources"], 0),
        Compared("window_completions_for_blocked_items", x["exits_for_blocked_items"], 0),
        Compared("window_exits_unsent", x["exits_unsent"], 0),
    ]


def reference_of(dep) -> PlainBreakers:
    """The plain breakers the configuration states, one a rank."""
    r = dep.config["rules"]
    return PlainBreakers(len(dep.ids), r["count"], r["slow_ratio_threshold"], r["time_window"] * 1000,
                         r["min_request_amount"], r["stat_interval_ms"],
                         dep.config["engine"].get("cb_sample_count", 2))


def compare_replay(dep, ticks) -> Tuple[List[Compared], Dict]:
    """Hold the replayed ticks (``generator.replay``'s: the state the window
    left, then a tuple a tick) against the plain reference."""
    from sentinel_tpu.ops import wire

    ticks = iter(ticks)
    ref = reference_of(dep)
    left_by_the_window = np.asarray(next(ticks))
    rank_of = np.full(int(dep.ids.max()) + 1, -1, np.int64)
    rank_of[dep.ids] = np.arange(len(dep.ids))
    far = dep.ids > dep.config["check_params"]["rows_past"]
    verdict_off = state_off = other = blocked = pairs = far_moves = n_ticks = items = exits = 0
    before = ref.state.copy()
    by_shape = collections.Counter()
    for now_ms, ids, verdicts, x_ids, x_rt, x_at, state, shape in ticks:
        n_ticks += 1
        by_shape[shape] += 1
        items += len(ids)
        exits += len(x_ids)
        other += int(((verdicts != PASS) & (verdicts != BLOCK_DEGRADE)).sum())
        blocked += int((verdicts == BLOCK_DEGRADE).sum())
        uniq, _n, want = ref.tick(now_ms, rank_of[x_ids], x_rt, rank_of[ids], x_at)
        got = np.bincount(np.searchsorted(uniq, rank_of[ids]), weights=verdicts == PASS,
                          minlength=len(uniq)).astype(np.int64)
        verdict_off += int((got != want).sum())
        pairs += len(uniq)
        state_off += int((state != ref.state).sum())
        far_moves += int(((state != before) & far).sum())
        before = state
    seen = ref.seen
    numbers = [
        Compared("replay_pairs_compared", pairs, 1, at_least=True),
        Compared("replay_blocked_items", blocked, 1, at_least=True),
        Compared("replay_verdict_mismatches", verdict_off, 0),
        Compared("replay_state_mismatches", state_off, 0),
        Compared("replay_other_codes", other, 0),
        Compared("replay_opened", seen["opened"], 1, at_least=True),
        Compared("replay_half_opened", seen["half_opened"], 1, at_least=True),
        Compared("replay_closed_again", seen["closed_again"], 1, at_least=True),
        Compared("replay_reopened", seen["reopened"], 1, at_least=True),
        Compared("replay_exits_while_open", seen["exits_while_open"], 1, at_least=True),
        Compared("replay_probes_resolved_by_an_earlier_call",
                 seen["probes_resolved_by_an_earlier_call"], 1, at_least=True),
        Compared("replay_transitions_on_rows_past_16368", far_moves, 1, at_least=True),
        Compared("replay_ratio_ties", seen["ratio_ties"], 1, at_least=True),
        # every shape the tick is compiled for (ops/wire.tick_shapes) was replayed
        Compared("replay_tick_shapes_never_run",
                 len(set(wire.tick_shapes(dep.client.cfg)) - set(by_shape)), 0),
    ]
    summary = {"ticks": n_ticks, "items": items, "exits": exits,
               "ticks_by_shape": {f"{b}x{b2}": n for (b, b2), n in sorted(by_shape.items())},
               "breakers_not_closed_as_the_window_left_them": int((left_by_the_window != 0).sum()),
               "breakers_not_closed_at_the_end": int((before != 0).sum()),
               "rows_past": int(dep.config["check_params"]["rows_past"])}
    return numbers, summary


def decide(dep, generator, params: dict, seed: int, win: Window) -> Tuple[bool, List[Compared], Dict]:
    """Stop the client's tick thread, let the cell's generator replay at
    virtual times, compare.  Returns ``(correct, every number compared, the
    replay's summary)``."""
    dep.stop()
    replayed, summary = compare_replay(dep, generator.replay(dep, params, seed))
    numbers = in_window(win) + replayed
    return all(n.ok for n in numbers), numbers, summary
