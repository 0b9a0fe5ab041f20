"""The check of a ``pacing_client`` deployment, held to the plain reference
``perfbench/reference/plain_pacer.py``: one leaky bucket a topic
(``RateLimiterController.canPass``), its items taken one at a time in
submission order at their tick's ``now_ms``.

Inside the window: every request resolved, none errored or answered
BLOCK_SYSTEM, no verdict code but PASS, PASS_WAIT and BLOCK_FLOW and each of
them seen, PASS_WAIT the most common; every PASS_WAIT item's wait in
(0, ``max_queueing_time_ms``] and every other item's 0; no exit sent for a
blocked item, none left unsent, none sent before its wait was over.  A block
answered in full but later than the client's own timeout is late and not
wrong, as in ``flow_replay``.

After the window, on the same client and the same compiled programs: the tick
thread is stopped and the cell's generator drives seeded bursts at stated
virtual times through every shape the tick is compiled for, while the
reference is given the same tick-stamped items in the same order.  Both start
from every bucket idle (the generator sets the program's so before the first
tick).  **Per item the verdict code and the wait, and after every tick every
rule's ``latestPassedTime``, are compared, and the tolerance is 0, because
nothing here is approximate**: a rule's cost is a whole number of
milliseconds (``round(1000 / count)``), every acquire takes one token, so a
rule has one cost and the program's batched bucket (a closed form over a
tick's items, ``ops/engine._apply_latest``) is the sequential one exactly;
its drift bound concerns mixed costs only.  The program keeps
``latestPassedTime`` in int32 engine milliseconds since PR 42 (in float32
before, which holds no odd millisecond past 2^24 ms = 4.66 h): a stretch of
the replay runs past that instant and decides ``correct`` like the rest.

So that the comparison cannot pass with nothing compared, each of these is
counted and must have been seen: a PASS on an idle bucket; a bucket that
re-anchors to ``now`` after lapsing; a wait of exactly the limit admitted and
one of a millisecond more refused (the ``>``); a backlog carried from one tick
into the next; an item on a topic of cost 1 ms and one of cost 100 ms; a tick
with 1 to ``EXC_K`` waiting rows (the wire's sidecar carries the waits) and a
tick with more (the whole wait column is read: the program's own counter of
those reads must have moved by as many); an admitted item on a row past
``check_params.rows_past``; and ticks whose ``now_ms`` lies past
``check_params.engine_ms_past`` (2^24).
"""

from __future__ import annotations

import collections
from typing import Dict, List, Tuple

import numpy as np

from perfbench.checks import Compared
from perfbench.generators import BLOCK_FLOW, BLOCK_SYSTEM, PASS, Window
from perfbench.generators.open_loop_paced_bursts import PASS_WAIT
from perfbench.reference.plain_pacer import PlainPacer


def in_window(win: Window) -> List[Compared]:
    codes, x = win.codes, win.extra
    other = sum(v for k, v in codes.items() if k not in (PASS, PASS_WAIT, BLOCK_FLOW))
    most = max(codes, key=codes.get) if codes else -1
    return [
        Compared("window_requests", win.attempted, 1, at_least=True),
        Compared("window_failed", win.failed - win.late, 0),
        Compared("window_unresolved", win.unresolved, 0),
        Compared("window_block_system_items", codes.get(BLOCK_SYSTEM, 0), 0),
        Compared("window_other_codes", other, 0),
        Compared("window_passed_items", codes.get(PASS, 0), 1, at_least=True),
        Compared("window_pass_wait_items", codes.get(PASS_WAIT, 0), 1, at_least=True),
        Compared("window_flow_blocked_items", codes.get(BLOCK_FLOW, 0), 1, at_least=True),
        Compared("window_pass_wait_is_the_most_common_code", int(most == PASS_WAIT), 1, at_least=True),
        Compared("window_waits_out_of_range", x["waits_out_of_range"], 0),
        Compared("window_waits_on_items_not_pass_wait", x["waits_on_items_not_pass_wait"], 0),
        Compared("window_completions_for_blocked_items", x["exits_for_blocked_items"], 0),
        Compared("window_exits_unsent", x["exits_unsent"], 0),
        Compared("window_exits_sent_before_the_wait_was_over",
                 x["exits_sent_before_the_wait_was_over"], 0),
    ]


def reference_of(dep) -> PlainPacer:
    """The plain buckets the configuration states, one a rank."""
    return PlainPacer(dep.counts.tolist(), dep.config["rules"]["max_queueing_time_ms"])


def compare_replay(dep, ticks) -> Tuple[List[Compared], Dict]:
    """Hold the replayed ticks (``generator.replay``'s: the program's count
    of whole-column reads before the first tick, then a tuple a tick) against
    the plain reference."""
    from sentinel_tpu.ops import wire

    ref = reference_of(dep)
    cp = dep.config["check_params"]
    far = dep.ids > cp["rows_past"]
    verdict_off = wait_off = plane_off = other = n_ticks = items = 0
    far_admitted = sidecar_ticks = column_ticks = late_ticks = late_items = 0
    by_code = collections.Counter()
    by_shape = collections.Counter()
    off_by_cost = collections.Counter()  # items whose verdict or wait is off, by their rule's cost
    ticks = iter(ticks)
    reads_before = column_reads = next(ticks)
    for now_ms, ranks, verdicts, waits, latest, shape, reads in ticks:
        n_ticks += 1
        by_shape[shape] += 1
        items += len(ranks)
        want_v, want_w = ref.tick(now_ms, ranks.tolist())
        want_v, want_w = np.asarray(want_v, np.int64), np.asarray(want_w, np.int64)
        verdict_off += int((verdicts != want_v).sum())
        wait_off += int((waits != want_w).sum())
        off_by_cost.update(dep.cost_ms[ranks[(verdicts != want_v) | (waits != want_w)]].tolist())
        plane_off += int((latest != np.asarray(ref.latest, np.int64)).sum())
        by_code.update(dict(zip(*np.unique(verdicts, return_counts=True))))
        other += int(((verdicts != PASS) & (verdicts != PASS_WAIT) & (verdicts != BLOCK_FLOW)).sum())
        far_admitted += int(((verdicts == PASS_WAIT) & far[ranks]).sum())
        waiting = int((waits > 0).sum())
        sidecar_ticks += 1 <= waiting <= wire.EXC_K
        column_ticks += waiting > wire.EXC_K
        column_reads = reads
        if now_ms > cp["engine_ms_past"]:
            late_ticks += 1
            late_items += len(ranks)
    # the whole column was read once a tick that held more than EXC_K waiting
    # rows, by the program's own count of those reads
    reads = column_reads - reads_before
    seen = ref.seen
    numbers = [
        Compared("replay_items_compared", items, 1, at_least=True),
        Compared("replay_verdict_mismatches", verdict_off, 0),
        Compared("replay_wait_ms_mismatches", wait_off, 0),
        Compared("replay_latest_passed_mismatches", plane_off, 0),
        Compared("replay_other_codes", other, 0),
        Compared("replay_pass_wait_items", int(by_code[PASS_WAIT]), 1, at_least=True),
        Compared("replay_flow_blocked_items", int(by_code[BLOCK_FLOW]), 1, at_least=True),
        Compared("replay_idle_passes", seen["idle_passes"], 1, at_least=True),
        Compared("replay_reanchored", seen["reanchored"], 1, at_least=True),
        Compared("replay_waits_of_exactly_the_limit", seen["waits_of_exactly_the_limit"], 1, at_least=True),
        Compared("replay_refused_one_ms_past_the_limit", seen["refused_one_ms_past_the_limit"], 1,
                 at_least=True),
        Compared("replay_backlogs_carried_over", seen["backlogs_carried_over"], 1, at_least=True),
        Compared("replay_items_at_cost_1", seen["items_at_cost_1"], 1, at_least=True),
        Compared("replay_items_at_cost_100", seen["items_at_cost_100"], 1, at_least=True),
        Compared("replay_sidecar_ticks", sidecar_ticks, 1, at_least=True),
        Compared("replay_whole_column_ticks", column_ticks, 1, at_least=True),
        Compared("replay_whole_column_reads_not_counted", abs(column_ticks - reads), 0),
        Compared("replay_admitted_with_a_wait_on_rows_past", far_admitted, 1, at_least=True),
        Compared("replay_ticks_past_2_24_ms", late_ticks, 1, at_least=True),
        # every shape the tick is compiled for (ops/wire.tick_shapes) was replayed
        Compared("replay_tick_shapes_never_run",
                 len(set(wire.tick_shapes(dep.client.cfg)) - set(by_shape)), 0),
    ]
    summary = {"ticks": n_ticks, "items": items,
               "ticks_by_shape": {f"{b}x{b2}": n for (b, b2), n in sorted(by_shape.items())},
               "items_by_code": {int(k): int(v) for k, v in sorted(by_code.items())},
               "sidecar_ticks": int(sidecar_ticks), "whole_column_ticks": int(column_ticks),
               "whole_column_reads_counted": reads,
               "ticks_past_2_24_ms": late_ticks, "items_past_2_24_ms": late_items,
               "items_off_by_cost_ms": dict(off_by_cost.most_common(8)),
               "rows_past": int(cp["rows_past"]), **{f"reference_{k}": int(v) for k, v in seen.items()}}
    return numbers, summary


def decide(dep, generator, params: dict, seed: int, win: Window) -> Tuple[bool, List[Compared], Dict]:
    """Stop the client's tick thread, let the cell's generator replay at
    virtual times, compare.  Returns ``(correct, every number compared, the
    replay's summary)``."""
    dep.stop()
    replayed, summary = compare_replay(dep, generator.replay(dep, params, seed))
    numbers = in_window(win) + replayed
    return all(n.ok for n in numbers), numbers, summary
