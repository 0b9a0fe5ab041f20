"""A plain leaky bucket per topic, the reference a ``pacing_client``
deployment is held to.  Python integers in a list, one item at a time,
nothing of ``sentinel_tpu`` but the numbers of the three verdict codes it
answers in.

Written from alibaba/Sentinel 1.8.1 ``sentinel-core``
``slots/block/flow/controller/RateLimiterController.java:50-105`` as the
issue of PR 42 describes it (``/root/reference`` was not on the builder's
machine)::

    costTime = Math.round(1.0 * acquireCount / count * 1000)
    expected = latestPassedTime + costTime
    if (expected <= now) { latestPassedTime = now; return true; }
    wait = expected - now
    if (wait > maxQueueingTimeInMs) return false
    latestPassedTime += costTime;  sleep(wait);  return true

**Where this departs from upstream**, each on purpose:

- *The caller is told the wait and does not sleep inside the check.*  An item
  that upstream would put to sleep for ``wait`` ms is answered PASS_WAIT with
  ``wait``; it counts as admitted at ``now + wait``, which is when
  upstream's thread would have woken.
- *A tick's items share one ``now``.*  The program decides a tick's items
  together at the tick's ``now_ms``; the reference is handed the same
  tick-stamped items, in submission order, and reads no clock of its own.
- *A bucket that has admitted nothing is idle however early ``now`` is.*
  Upstream starts ``latestPassedTime`` at -1 against a wall clock in
  milliseconds since 1970, which is the same thing; ``NEVER`` says so for a
  clock that starts near 0.
- Upstream re-reads the wait after its ``addAndGet`` and takes the cost back
  if another thread pushed it past the limit meanwhile.  One item at a time,
  that second look sees what the first saw.
"""

from __future__ import annotations

import math
from typing import List, Sequence, Tuple

#: the three verdict codes it answers in, as sentinel_tpu.core.errors numbers
#: them (a test holds them equal; the module imports nothing of the program)
PASS, BLOCK_FLOW, PASS_WAIT = 0, 1, 6
#: ``latestPassedTime`` of a topic that has admitted nothing yet
NEVER = -(1 << 62)


def cost_ms(count: float, acquire: int = 1) -> int:
    """``Math.round(1.0 * acquire / count * 1000)``: Java rounds half up."""
    return int(math.floor(1.0 * acquire / count * 1000 + 0.5))


class PlainPacer:
    """Topics ``0..n-1``, one uniform-rate rule each (``counts[k]`` a second,
    every acquire one token), all with the same ``max_queue_ms``."""

    def __init__(self, counts: Sequence[float], max_queue_ms: int = 500):
        self.cost: List[int] = [cost_ms(c) for c in counts]
        self.max_queue_ms = int(max_queue_ms)
        self.latest: List[int] = [NEVER] * len(self.cost)
        #: what happened, for a check that must not pass with nothing compared
        self.seen = dict(idle_passes=0, reanchored=0, waits_of_exactly_the_limit=0,
                         refused_one_ms_past_the_limit=0, backlogs_carried_over=0,
                         items_at_cost_1=0, items_at_cost_100=0)
        self._first_of_tick = set()

    def can_pass(self, k: int, now_ms: int) -> Tuple[int, int]:
        """One item of topic ``k`` at ``now_ms``: ``(verdict code, wait ms)``."""
        cost, latest, seen = self.cost[k], self.latest[k], self.seen
        if cost == 1:
            seen["items_at_cost_1"] += 1
        elif cost == 100:
            seen["items_at_cost_100"] += 1
        expected = latest + cost
        if expected <= now_ms:
            seen["idle_passes" if latest == NEVER else "reanchored"] += 1
            self.latest[k] = now_ms
            return PASS, 0
        wait = expected - now_ms
        if wait > self.max_queue_ms:
            seen["refused_one_ms_past_the_limit"] += wait == self.max_queue_ms + 1
            return BLOCK_FLOW, 0
        seen["waits_of_exactly_the_limit"] += wait == self.max_queue_ms
        self.latest[k] = expected
        return PASS_WAIT, wait

    def tick(self, now_ms: int, topics: Sequence[int]) -> Tuple[List[int], List[int]]:
        """A tick's items in submission order, all at ``now_ms``:
        ``(verdict codes, waits)``."""
        now_ms = int(now_ms)
        first, latest = self._first_of_tick, self.latest
        first.clear()
        verdicts, waits = [], []
        for k in topics:
            if k not in first:
                first.add(k)
                # the topic's first item of this tick waits for what an
                # earlier tick's items took
                self.seen["backlogs_carried_over"] += latest[k] + self.cost[k] > now_ms
            v, w = self.can_pass(k, now_ms)
            verdicts.append(v)
            waits.append(w)
        return verdicts, waits
