"""The check of a ``single_client`` deployment under FlowRule traffic, held
to the plain reference ``perfbench/reference/leap.py``.

Inside the window: every request resolved, none errored or answered
BLOCK_SYSTEM, only the verdict codes this traffic can produce, and no ruled
resource admitted more than its windows allow over the run.  A block that
was answered in full but later than the client's own timeout (``Window.late``)
is late and not wrong: it stays in the result's ``failed``, is lost to the
rate and the latency samples, is printed beside the window, and fails no
comparison here (PERF.md, PR 27: one standstill of 12 s in a run of the
driver's check made eight such blocks, and the run not correct).

After the window, on the same client and the same compiled programs: the
tick thread is stopped and a seeded sample of the cell's traffic is driven
at stated virtual times (``tick_once(now_ms=...)``), so that the plain
reference can follow it tick for tick.  Passes per (resource, tick) are
compared exactly: the client's presort may reorder the items of one resource
within a tick, never their number.

The sketch tier is approximate in one direction only.  A tail rule never
admits more than its threshold in a window.  An unruled sketch id may be
blocked when every one of its hashed threshold cells collides with a tail
rule's (``rule_tensors.TailFlowTensors``): that is the tier's stated error,
so the share of such blocks is held to ``SKETCH_FALSE_BLOCK_LIMIT`` (PERF.md
gives the readings it was set from), while an unruled id of the exact tier is
never blocked.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from perfbench.checks import Compared
from perfbench.generators import BLOCK_FLOW, PASS, Window
from perfbench.reference.leap import FlowReference, LeapWindows

#: blocked share of the unruled sketch-tier items of a replay
SKETCH_FALSE_BLOCK_LIMIT = 7.5e-4
Tick = Tuple[int, np.ndarray, np.ndarray]  # now_ms, ids, verdicts


def flow_rules(dep) -> Tuple[np.ndarray, np.ndarray]:
    """(engine ids, thresholds) of the configuration's FlowRules, from the
    configuration file and the ids the registry handed out."""
    r = dep.config["rules"]
    n = len(dep.ruled_names)
    ids = np.concatenate([np.arange(1, n + 1), dep.tail_ids]).astype(np.int64)
    thr = np.concatenate([np.full(n, r["flow_qps"]), np.full(len(dep.tail_ids), r["tail_qps"])])
    return ids, thr


def in_window(dep, win: Window) -> List[Compared]:
    ids, thr = flow_rules(dep)
    known = ids < len(win.passes)
    allowed = thr[known] * (np.floor(win.span_s) + 2)
    over = int((win.passes[ids[known]] > allowed).sum())
    other = sum(v for k, v in win.codes.items() if k not in (PASS, BLOCK_FLOW))
    return [
        Compared("window_requests", win.attempted, 1, at_least=True),
        Compared("window_failed", win.failed - win.late, 0),
        Compared("window_unresolved", win.unresolved, 0),
        Compared("window_other_codes", other, 0),
        Compared("window_over_admitted_resources", over, 0),
    ]


def compare_replay(dep, ticks: List[Tick]) -> List[Compared]:
    """Hold the replayed ticks against the plain reference."""
    w = dep.config["window"]
    rule_ids, thr = flow_rules(dep)
    ref = FlowReference(rule_ids, thr, w["sample_count"], w["window_ms"])
    n_exact = len(dep.ruled_names)
    # tail rules are held to their guarantee, which is one-sided: what the
    # engine admitted never exceeds the threshold in any window
    tail_rows = ref.rows_of(dep.tail_ids)
    tail_seen = LeapWindows(len(ref.rule_ids), w["sample_count"], w["window_ms"])
    mismatch = tail_over = exact_unruled_blocked = other = pairs = blocked = 0
    sketch_unruled = sketch_unruled_blocked = 0
    for now_ms, ids, verdicts in ticks:
        other += int(((verdicts != PASS) & (verdicts != BLOCK_FLOW)).sum())
        blocked += int((verdicts == BLOCK_FLOW).sum())
        uniq, n, want = ref.tick(now_ms, ids)
        got = np.bincount(
            np.searchsorted(uniq, ids), weights=verdicts == PASS, minlength=len(uniq)
        ).astype(np.int64)
        rows = ref.rows_of(uniq)
        exact = (uniq >= 1) & (uniq <= n_exact)
        mismatch += int((got[exact] != want[exact]).sum())
        pairs += int(exact.sum())
        unruled = rows < 0
        in_sketch = uniq >= dep.sketch_base
        exact_unruled_blocked += int((n - got)[unruled & ~in_sketch].sum())
        sketch_unruled += int(n[unruled & in_sketch].sum())
        sketch_unruled_blocked += int((n - got)[unruled & in_sketch].sum())
        tail = (rows >= 0) & ~exact
        if tail.any():
            tail_seen.add(now_ms, rows[tail], got[tail])
            seen = tail_seen.window(now_ms)[tail_rows]
            tail_over += int((seen > ref.thresholds[tail_rows]).sum())
    return [
        Compared("replay_pairs_compared", pairs, 1, at_least=True),
        Compared("replay_blocked_items", blocked, 1, at_least=True),
        Compared("replay_pass_count_mismatches", mismatch, 0),
        Compared("replay_tail_over_admitted", tail_over, 0),
        Compared("replay_exact_unruled_blocked", exact_unruled_blocked, 0),
        Compared("replay_sketch_unruled_blocked_share",
                 sketch_unruled_blocked / max(sketch_unruled, 1), SKETCH_FALSE_BLOCK_LIMIT),
        Compared("replay_other_codes", other, 0),
    ]


def decide(dep, generator, params: dict, seed: int, win: Window) -> Tuple[bool, List[Compared], Dict]:
    """Stop the client's tick thread, let the cell's generator replay a
    sample at virtual times, compare.  Returns ``(correct, every number
    compared, the replayed ticks' summary)``."""
    dep.stop()
    ticks = generator.replay(dep, params, seed)
    numbers = in_window(dep, win) + compare_replay(dep, ticks)
    items = sum(len(t[1]) for t in ticks)
    return all(n.ok for n in numbers), numbers, {"ticks": len(ticks), "items": items}
