"""The check of a ``param_client`` deployment, held to the plain reference
``perfbench/reference/param_shadow.py``: exact counts per (route, value)
pair.

Inside the window: every request resolved, none errored or answered
BLOCK_SYSTEM, no verdict code but PASS and BLOCK_PARAM and both of them seen,
and no pair admitted more than its windows allow over the run.  A block
answered in full but later than the client's own timeout is late and not
wrong, as in ``flow_replay``.

After the window, on the same client and the same compiled programs: the tick
thread is stopped and a seeded sample of the cell's traffic is driven at
stated virtual times, at the rate the cell offers (a store loaded lighter
than in the window says nothing of the window), while the shadow follows.
The program's store is a count-min sketch, approximate in one direction
only: a cell counts every pair hashed to it, so an estimate is never under
the pair's own count and the program never admits what an exact count would
block (``replay_param_over_admitted``, limit 0).  It may block what an exact
count would admit.  So that one such false block does not make the two
drift apart, the shadow is told what the program admitted, tick by tick, and
asked each time what it would admit next: the share of those admissions the
program refused is held to ``PARAM_FALSE_BLOCK_LIMIT``.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from perfbench.checks import Compared
from perfbench.generators import BLOCK_SYSTEM, PASS, Window
from perfbench.reference.param_shadow import ParamShadow, pair_keys

#: verdict code of a hot-parameter block, as sentinel_tpu.core.errors numbers it
BLOCK_PARAM = 3
#: Share of the admissions an exact count allows that the program's store
#: refuses in a replay at the cell's rate.  A pair is refused wrongly when in
#: every depth of the store its cell also counts another pair's admissions:
#: with d depths of w cells and K pairs admitted a window, about (K / w)**d of
#: the pairs, fewer of the admissions since most colliding counts leave room.
#: At the cell's rate (2.5 M items/s) the replay admits 201,000 pairs a
#: second, and reads (PERF.md section 6, PR 33): 2**22 cells and two depths
#: 0.16 to 0.20 % over six seeds on the chip (0.20 % counted on the CPU);
#: 2**21 cells 0.74 %; today's 2**14 cells (13 pairs to a cell) 88.6 % on the
#: chip.  The limit stands between the readings of 2**22 and 2**14 with room
#: on both sides (five times the first, a ninetieth of the second), and is
#: the 1 % the deployment's guarantee states.
PARAM_FALSE_BLOCK_LIMIT = 0.01
Tick = Tuple[int, np.ndarray, np.ndarray, np.ndarray]  # now_ms, ids, value hashes, verdicts


def in_window(win: Window) -> List[Compared]:
    other = sum(v for k, v in win.codes.items() if k not in (PASS, BLOCK_PARAM))
    return [
        Compared("window_requests", win.attempted, 1, at_least=True),
        Compared("window_failed", win.failed - win.late, 0),
        Compared("window_unresolved", win.unresolved, 0),
        Compared("window_block_system_items", win.codes.get(BLOCK_SYSTEM, 0), 0),
        Compared("window_other_codes", other, 0),
        Compared("window_passed_items", win.codes.get(PASS, 0), 1, at_least=True),
        Compared("window_param_blocked_items", win.codes.get(BLOCK_PARAM, 0), 1, at_least=True),
        Compared("window_over_admitted_pairs", win.extra["pairs_over_their_windows"], 0),
    ]


def compare_replay(dep, ticks: List[Tick]) -> Tuple[List[Compared], Dict]:
    """Hold the replayed ticks against the exact shadow."""
    r = dep.config["rules"]
    rule_thr, item_thr = dep.thresholds()
    w = dep.config["window"]
    shadow = ParamShadow(rule_thr, item_thr, w["window_ms"], w["sample_count"])
    rule_count = r["count"] * r["duration_in_sec"] + r["burst_count"]
    over = allowed_all = refused = other = blocked = 0
    item_pairs = item_past_rule = 0
    admitted_pairs = set()
    for now_ms, ids, values, verdicts in ticks:
        other += int(((verdicts != PASS) & (verdicts != BLOCK_PARAM)).sum())
        blocked += int((verdicts == BLOCK_PARAM).sum())
        keys = pair_keys(ids, values)
        uniq, _n, allowed = shadow.tick(now_ms, keys)
        got = np.bincount(np.searchsorted(uniq, keys), weights=verdicts == PASS,
                          minlength=len(uniq)).astype(np.int64)
        over += int(np.maximum(got - allowed, 0).sum())
        allowed_all += int(allowed.sum())
        refused += int(np.maximum(allowed - got, 0).sum())
        is_item = np.fromiter((k in item_thr for k in uniq.tolist()), bool, len(uniq))
        item_pairs += int(is_item.sum())
        # an exception item's pair admitted past the rule's own count shows
        # the item's threshold honoured upward; over_admitted holds it downward
        item_past_rule += int((got[is_item] > rule_count).sum())
        shadow.admit(now_ms, uniq, got)
        shadow.forget_before(now_ms)
        admitted_pairs.update(uniq[got > 0].tolist())
    span_s = (ticks[-1][0] - ticks[0][0]) / 1e3 if len(ticks) > 1 else 0.0
    numbers = [
        Compared("replay_pairs_compared", allowed_all, 1, at_least=True),
        Compared("replay_blocked_items", blocked, 1, at_least=True),
        Compared("replay_item_keys_compared", item_pairs, 1, at_least=True),
        Compared("replay_item_keys_past_the_rules_count", item_past_rule, 1, at_least=True),
        Compared("replay_param_over_admitted", over, 0),
        Compared("replay_param_false_block_share", refused / max(allowed_all, 1),
                 PARAM_FALSE_BLOCK_LIMIT),
        Compared("replay_other_codes", other, 0),
    ]
    summary = {
        "ticks": len(ticks), "items": int(sum(len(t[1]) for t in ticks)),
        "offered_items_per_s": sum(len(t[1]) for t in ticks[:-1]) / span_s if span_s else None,
        "pairs_admitted": len(admitted_pairs),
        "pairs_admitted_per_s": len(admitted_pairs) / span_s if span_s else None,
        "admissions_allowed": allowed_all, "admissions_refused": refused,
    }
    return numbers, summary


def store_occupancy(dep) -> Dict:
    """Cells of the store's newest bucket that count something, a depth:
    read once here, after the replay, never on the tick thread."""
    probe = getattr(dep.client, "param_store_occupancy", None)
    return probe() if probe else {}


def decide(dep, generator, params: dict, seed: int, win: Window) -> Tuple[bool, List[Compared], Dict]:
    """Stop the client's tick thread, let the cell's generator replay a
    sample at virtual times, compare.  Returns ``(correct, every number
    compared, the replay's summary)``."""
    dep.stop()
    ticks = generator.replay(dep, params, seed)
    replayed, summary = compare_replay(dep, ticks)
    numbers = in_window(win) + replayed
    summary.update(store_occupancy(dep), universe_pairs=dep.universe, pool_pairs=dep.pool_pairs)
    return all(n.ok for n in numbers), numbers, summary
