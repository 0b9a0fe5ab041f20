"""The check of an ``rls_fleet`` deployment, held to the plain reference
``perfbench/reference/plain_bucket.py``.

Inside the window: every request answered, none with an error (an answer
that missed the sidecar's deadline, ``Window.late``, is late and not wrong: it
stays in the result's ``failed``, is lost to the latency samples, and fails
no comparison here, as PERF.md, PR 27, has it for blocks).  And between the
two readings of the program's counters that the generator takes, one when the
window opens and one when the last answer of the post-roll has come, each of
these is 0: requests shed, errors at the door, failed RPCs to a shard, shards
entering or leaving the degraded state, local lease admits, answers by a
degraded shard's fallback (each of those answers ``OVER_LIMIT`` by the
guarantees, which would be a sound answer and a different deployment).  No
shard is degraded when the window opens or when it closes.  The token columns
decided exactly the hits sent: every hit of a request that was sent after the
first reading and answered, and no more than those, the hits of requests the
sidecars gave up on (which the door may or may not have taken) and the hits
in flight while the first reading was taken.  Every shard's own column
decided some (``TokenColumnBatcher.decided``, not what the ring routed); no
descriptor admitted more than its count allows over the run; both codes seen.
A run whose window a stall reached (a traced run's profiler start is healed
in the pre-roll, by the generator, before the window opens) is not correct.

After the window, on the same fleet, door, nodes and compiled programs: the
load has stopped, and a seeded sample of the cell's traffic is driven through
the door in steps, the shards' clocks held at each step's instant, while the
plain buckets follow.  Within a step the requests are in flight together and
the order the shards see them in is not known; every hit is one unit, so the
number a descriptor admits in a step does not depend on it, and that number
is compared exactly, per (descriptor, step).  Which *request* gets a
descriptor's last unit does depend on it, and with it whether a request of
two descriptors comes out ``OVER_LIMIT``.  So the count of ``OVER_LIMIT``
answers a step is held to the reference's where the order cannot move it,
over the requests of one descriptor that no request of two names in that
step, and every answer's overall code is held to its own descriptors' codes
(``OVER_LIMIT`` iff any is).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from perfbench.checks import Compared
from perfbench.generators import Window
from perfbench.reference.plain_bucket import OK, OVER_LIMIT, PlainBuckets

Step = Tuple[int, np.ndarray, np.ndarray, np.ndarray]  # t_ms, descriptors, codes, overall


def in_window(dep, win: Window) -> List[Compared]:
    x = win.extra
    moved = {k[len("moved."):]: v for k, v in x.items() if k.startswith("moved.")}
    decided = [moved[f"column_decisions.{name}"] for name in dep.shards]
    allowed = dep.counts * (np.floor(win.span_s) + 2)
    # the columns' decisions between the readings lie in [least, most]
    least = x["hits_answered"]
    most = least + x["hits_unanswered"] + x["hits_across_open"]
    off = max(least - moved["column_decisions"], moved["column_decisions"] - most, 0)
    return [
        Compared("window_requests", win.attempted, 1, at_least=True),
        Compared("window_failed", win.failed - win.late, 0),
        Compared("window_unresolved", win.unresolved, 0),
        Compared("window_shed", moved["shed"], 0),
        Compared("window_door_errors", moved["door_errors"], 0),
        Compared("window_rpc_failures", moved["rpc_failures"], 0),
        Compared("window_degrade_transitions", moved["degrade_transitions"], 0),
        Compared("window_shards_degraded_at_open_or_close",
                 len(x["degraded_at_open"]) + len(x["degraded_at_close"]), 0),
        Compared("window_lease_local_admits", moved["lease_local_admits"], 0),
        Compared("window_fallback_answers", moved["fallback_admits"] + moved["fallback_blocks"], 0),
        Compared("window_column_decisions_off_hits_sent", off, 0),
        Compared("window_shards_whose_column_decided", sum(1 for n in decided if n > 0),
                 len(decided), at_least=True),
        Compared("window_over_admitted_descriptors", int((win.passes > allowed).sum()), 0),
        Compared("window_ok_answers", win.codes.get(OK, 0), 1, at_least=True),
        Compared("window_over_limit_answers", win.codes.get(OVER_LIMIT, 0), 1, at_least=True),
    ]


def compare_replay(dep, steps: List[Step], min_hits: int) -> List[Compared]:
    """Hold the replayed steps against the plain buckets."""
    w = dep.config["window"]
    units = dep.config["nodes"]["hits_addend"]
    ref = PlainBuckets(dep.counts, w["sample_count"], w["window_ms"])
    hits = pairs = mismatches = disagree = unanswered = two = 0
    ok_answers = over_answers = over_held = over_off = 0
    for t_ms, desc, codes, overall in steps:
        live = desc >= 0
        unanswered += int(((overall != OK) & (overall != OVER_LIMIT)).sum())
        # the door's own rule, answer by answer: OVER_LIMIT iff any descriptor is
        any_over = ((codes == OVER_LIMIT) & live).any(axis=1)
        disagree += int((any_over != (overall == OVER_LIMIT)).sum())
        ok_answers += int((overall == OK).sum())
        over_answers += int((overall == OVER_LIMIT).sum())
        two += int(live[:, 1].sum())
        # the reference, hit by hit in the order the step lists them
        want = np.zeros(len(dep.counts), np.int64)
        want_code = np.zeros(len(desc), np.int64)
        for k, row in enumerate(desc):
            want_code[k], admitted = ref.request(t_ms, [int(d) for d in row if d >= 0], units)
            for d, yes in zip(row, admitted):
                want[d] += units * yes
        got = np.bincount(desc[live & (codes == OK)], minlength=len(dep.counts)) * units
        asked = np.bincount(desc[live], minlength=len(dep.counts)) > 0
        hits += int(live.sum())
        pairs += int(asked.sum())
        mismatches += int((got != want)[asked].sum())
        # where the order cannot move the count of OVER_LIMIT answers: the
        # requests of one descriptor that no request of two names in this step
        shared = np.zeros(len(dep.counts), bool)
        shared[desc[live[:, 1]].ravel()] = True
        alone = ~live[:, 1] & ~shared[desc[:, 0]]
        over_held += int(alone.sum())
        over_off += abs(int((overall[alone] == OVER_LIMIT).sum())
                        - int((want_code[alone] == OVER_LIMIT).sum()))
    return [
        Compared("replay_hits_compared", hits, min_hits, at_least=True),
        Compared("replay_pairs_compared", pairs, 1, at_least=True),
        Compared("replay_two_descriptor_requests", two, 1, at_least=True),
        Compared("replay_unanswered", unanswered, 0),
        Compared("replay_granted_mismatches", mismatches, 0),
        Compared("replay_answers_held_to_the_over_limit_count", over_held, 1, at_least=True),
        Compared("replay_over_limit_count_off_the_reference", over_off, 0),
        Compared("replay_overall_code_disagreements", disagree, 0),
        Compared("replay_ok_answers", ok_answers, 1, at_least=True),
        Compared("replay_over_limit_answers", over_answers, 1, at_least=True),
    ]


def decide(dep, generator, params: dict, seed: int, win: Window) -> Tuple[bool, List[Compared], Dict]:
    """The window's account, then the replay through the same door.  Returns
    ``(correct, every number compared, the replay's summary)``."""
    before = dep.counters()
    steps = generator.replay(dep, params, seed)
    moved = {k: v - before[k] for k, v in dep.counters().items()}
    numbers = in_window(dep, win) + compare_replay(dep, steps, params["replay"]["min_hits"])
    return all(n.ok for n in numbers), numbers, {
        "steps": len(steps), "requests": sum(len(s[1]) for s in steps),
        "virtual_ms": steps[-1][0] - steps[0][0] if steps else 0,
        # what the program counted meanwhile: a replay that met a failed RPC
        # or a degraded shard mismatches for that reason
        **{f"moved.{k}": v for k, v in moved.items()}}
