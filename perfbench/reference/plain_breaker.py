"""A plain slow-call circuit breaker per resource, the reference a
``breaker_client`` deployment is held to.  NumPy and Python only, nothing of
``sentinel_tpu``.

Written from alibaba/Sentinel 1.8.1 ``sentinel-core`` ``slots/block/degrade``
as the issue of PR 39 describes it (``/root/reference`` was not on the
builder's machine) and from the docstring of the program's ``ops/degrade.py``:

- ``DegradeRule`` grade 0 (slow-request ratio): ``count`` is the largest RT in
  whole milliseconds that is not slow (50 is not slow, 51 is),
  ``slow_ratio_threshold``, ``time_window`` seconds until a retry,
  ``min_request_amount``, ``stat_interval_ms``.
- ``ResponseTimeCircuitBreaker.onRequestComplete``: every exit counts into
  the total, a slow one into the slow count, of a ``LeapArray`` of **one**
  bucket of ``stat_interval_ms`` (``sample_count`` 1 here: a bucket on the
  global grid ``now // stat_interval_ms``, forgotten when the grid moves on;
  a ring of more buckets of ``stat_interval_ms / sample_count`` each is what
  the program's default of two keeps, and the class takes either).  Then by
  state: OPEN counts and does nothing more; HALF_OPEN goes OPEN on a slow
  exit (a new retry deadline) and else CLOSED with its counts reset; CLOSED
  trips once ``total >= min_request_amount`` and ``slow / total >
  threshold``, or when both are exactly 1.0.
- ``AbstractCircuitBreaker.tryPass``: CLOSED passes; OPEN passes one probe
  once the retry deadline has come and goes HALF_OPEN; HALF_OPEN passes
  nothing.
- ``DegradeSlot.exit`` skips an entry that carries a block error: a blocked
  entry records no completion (the caller simply hands none in).

**The order inside a tick, which is the program's** (``ops/engine.py``
``tick``: "exits first"): all of the tick's exits, then all of its entries.

1. every exit is counted into its resource's current bucket;
2. a breaker that was HALF_OPEN when the tick began and saw an exit resolves:
   OPEN with deadline ``now + time_window`` if any of those exits was slow,
   else CLOSED with its counts reset;
3. a breaker that is CLOSED now trips on its window's sums (deadline
   ``now + time_window``);
4. the tick's entries are decided against that state: CLOSED admits all,
   HALF_OPEN none, OPEN none before its deadline and from ``now >= deadline``
   exactly one, going HALF_OPEN.

**Where that departs from upstream's evaluation request by request**, all
from deciding once a tick: (a) the trip rule sees a tick's exits together
(4 slow then 6 fast in one tick is 4 of 10, where upstream had tripped at
the fifth); (b) a HALF_OPEN breaker that sees a fast and a slow exit in one
tick reopens, where upstream lets whichever came first decide; (c) an exit
that arrives in the tick that trips its breaker is counted before the trip,
not after; (d) a probe elected in a tick resolves no earlier than the next
tick's exits.  Which exit resolves a probe is upstream's own rule: any exit
of the resource that completes while it is HALF_OPEN, the probe's or an
earlier call's.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

CLOSED, OPEN, HALF_OPEN = 0, 1, 2


class PlainBreakers:
    """``n`` resources ``0..n-1``, one slow-ratio rule each, all alike."""

    def __init__(self, n: int, max_rt_ms: float, slow_ratio: float, retry_ms: int,
                 min_requests: int, stat_interval_ms: int, sample_count: int = 1):
        self.max_rt_ms, self.slow_ratio, self.retry_ms = max_rt_ms, slow_ratio, retry_ms
        self.min_requests, self.nb = min_requests, sample_count
        self.bucket_ms = max(stat_interval_ms // sample_count, 1)
        self.state = np.zeros(n, np.int8)
        self.deadline = np.zeros(n, np.int64)
        self.total = np.zeros((n, sample_count), np.int64)
        self.slow = np.zeros((n, sample_count), np.int64)
        self.grid = np.full((n, sample_count), -(1 << 40), np.int64)  # grid cell a bucket holds
        #: what happened, for a check that must not pass with nothing compared
        self.seen = dict(opened=0, half_opened=0, closed_again=0, reopened=0, exits_while_open=0,
                         probes_resolved_by_an_earlier_call=0, ratio_ties=0)
        self._probe_ms = np.zeros(n, np.int64)  # when a HALF_OPEN resource's probe was admitted

    def _sums(self, rows: np.ndarray, cell: int) -> Tuple[np.ndarray, np.ndarray]:
        live = (self.grid[rows] > cell - self.nb) & (self.grid[rows] <= cell)
        return (self.total[rows] * live).sum(axis=1), (self.slow[rows] * live).sum(axis=1)

    def exits(self, now_ms: int, ids: np.ndarray, rt_ms: np.ndarray, admitted_ms=None) -> None:
        """Steps 1 to 3 of a tick.  ``admitted_ms``, where the caller knows
        it, is when each exiting call was admitted: it moves no state and
        only tells a probe resolved by its own exit from one resolved by an
        earlier call's."""
        cell = now_ms // self.bucket_ms
        col = cell % self.nb
        touched, n_total = np.unique(ids, return_counts=True)
        if len(touched):
            is_slow = np.asarray(rt_ms) > self.max_rt_ms
            n_slow = np.bincount(np.searchsorted(touched, ids), weights=is_slow,
                                 minlength=len(touched)).astype(np.int64)
            stale = self.grid[touched, col] != cell
            self.total[touched[stale], col] = 0
            self.slow[touched[stale], col] = 0
            self.grid[touched, col] = cell
            self.total[touched, col] += n_total
            self.slow[touched, col] += n_slow
            self.seen["exits_while_open"] += int(n_total[self.state[touched] == OPEN].sum())
            half = self.state[touched] == HALF_OPEN
            again, shut = touched[half & (n_slow > 0)], touched[half & (n_slow == 0)]
            if admitted_ms is not None:
                first = np.full(len(touched), np.iinfo(np.int64).max)
                np.minimum.at(first, np.searchsorted(touched, ids), np.asarray(admitted_ms, np.int64))
                self.seen["probes_resolved_by_an_earlier_call"] += int(
                    (half & (first < self._probe_ms[touched])).sum())
            self.state[again] = OPEN
            self.deadline[again] = now_ms + self.retry_ms
            self.state[shut] = CLOSED
            self.total[shut] = 0
            self.slow[shut] = 0
            self.seen["reopened"] += len(again)
            self.seen["closed_again"] += len(shut)
        # every CLOSED breaker, not only those an exit landed on: in a ring of
        # several buckets a bucket of fast exits that expires raises the ratio
        rows = np.flatnonzero(self.state == CLOSED)
        total, slow = self._sums(rows, cell)
        enough = total >= self.min_requests
        ratio = slow / np.maximum(total, 1)
        trip = enough & ((ratio > self.slow_ratio) | ((ratio == 1.0) & (self.slow_ratio == 1.0)))
        # a window that stands exactly on the threshold: 3 of 5 is not over 0.6
        landed = np.isin(rows, touched, assume_unique=True)
        self.seen["ratio_ties"] += int((landed & enough & ~trip
                                        & (slow * 1000 == np.round(self.slow_ratio * 1000) * total)).sum())
        self.state[rows[trip]] = OPEN
        self.deadline[rows[trip]] = now_ms + self.retry_ms
        self.seen["opened"] += int(trip.sum())

    def entries(self, now_ms: int, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Step 4: ``(resources, entries of each, admitted of each)``."""
        uniq, n = np.unique(ids, return_counts=True)
        st = self.state[uniq]
        probe = (st == OPEN) & (now_ms >= self.deadline[uniq])
        admitted = np.where(st == CLOSED, n, probe.astype(np.int64))
        self.state[uniq[probe]] = HALF_OPEN
        self._probe_ms[uniq[probe]] = now_ms
        self.seen["half_opened"] += int(probe.sum())
        return uniq, n, admitted

    def tick(self, now_ms: int, exit_ids, exit_rt_ms, entry_ids, admitted_ms=None):
        self.exits(now_ms, np.asarray(exit_ids, np.int64), np.asarray(exit_rt_ms), admitted_ms)
        return self.entries(now_ms, np.asarray(entry_ids, np.int64))
