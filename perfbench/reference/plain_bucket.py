"""The plain reference of the ``rls_fleet`` kind: a sliding-window token
bucket per descriptor, decided one hit after another.  NumPy and nothing of
the program under test.

A descriptor owns a ring of ``buckets`` buckets of ``bucket_ms`` each (10 x
100 ms: upstream's ``ServerFlowConfig`` sample count and interval, the
``ClusterMetric`` every cluster flow rule gets).  A hit of ``h`` units at time
``t`` is admitted iff the units admitted in the live buckets plus ``h`` stay
within the descriptor's ``count``; an admitted hit is charged to the bucket
``t`` falls in.  That is upstream's ``SimpleClusterFlowChecker
.acquireClusterToken`` (``globalThreshold - latestQps - acquireCount >= 0``
over ``ClusterMetric.getAvg(PASS)``), which is what
``SentinelEnvoyRlsServiceImpl.shouldRateLimit`` calls for every descriptor of
a request.  Departures from upstream, each where it is made:

- time is an argument, never read from a clock (upstream reads
  ``TimeUtil.currentTimeMillis()`` inside ``LeapArray.currentWindow``);
- ``getAvg`` divides the window's sum by the interval in seconds; the
  interval here is one second (10 x 100 ms), so the sum is compared as it is;
- upstream also counts ``PASS_REQUEST`` / ``BLOCK`` / ``BLOCK_REQUEST`` events
  for its dashboards; nothing decides on them, and they are left out;
- no namespace guard (``GlobalRequestLimiter``, 30,000 QPS): upstream's RLS
  path calls ``SimpleClusterFlowChecker``, which has none, and the program's
  guard is held to "shed 0" by the check instead.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

OK, OVER_LIMIT = 1, 2  # envoy.service.ratelimit.v2.RateLimitResponse.Code


class PlainBuckets:
    """``counts[d]`` is descriptor ``d``'s threshold: the most units its live
    buckets may hold."""

    def __init__(self, counts: Sequence[float], buckets: int = 10, bucket_ms: int = 100):
        self.counts = np.asarray(counts, np.float64)
        self.buckets, self.bucket_ms = int(buckets), int(bucket_ms)
        n = len(self.counts)
        # which bucket of time each ring slot holds (-1: never written), and
        # the units admitted in it
        self._id = np.full((n, self.buckets), -1, np.int64)
        self._admitted = np.zeros((n, self.buckets), np.int64)

    def live(self, t_ms: int, d: int) -> int:
        """Units admitted to descriptor ``d`` in the window that ends with
        the bucket ``t_ms`` falls in: that bucket and the nine before it."""
        now = int(t_ms) // self.bucket_ms
        alive = (self._id[d] > now - self.buckets) & (self._id[d] <= now)
        return int(self._admitted[d][alive].sum())

    def hit(self, t_ms: int, d: int, units: int = 1) -> bool:
        """Decide one hit of ``units`` on descriptor ``d`` at ``t_ms``;
        admitted units are charged, refused ones leave no mark."""
        if self.live(t_ms, d) + units > self.counts[d]:
            return False
        now = int(t_ms) // self.bucket_ms
        slot = now % self.buckets
        if self._id[d, slot] != now:  # the slot held a bucket that has lapsed
            self._id[d, slot] = now
            self._admitted[d, slot] = 0
        self._admitted[d, slot] += units
        return True

    def request(self, t_ms: int, descriptors: Sequence[int], units: int = 1) -> Tuple[int, List[bool]]:
        """One ``ShouldRateLimit``: every descriptor is decided on its own
        and charged whether or not another of the same request is refused
        (upstream's loop has no early exit); the answer is ``OVER_LIMIT``
        iff any descriptor was refused."""
        admitted = [self.hit(t_ms, d, units) for d in descriptors]
        return (OK if all(admitted) else OVER_LIMIT), admitted
