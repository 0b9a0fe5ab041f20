"""Hot-parameter QPS limits with exact counts, written straightforwardly.

Semantics (alibaba/Sentinel ``ParamFlowChecker.passDefaultLocalCheck``, as
this system batches it): every (rule, value) pair has a budget of
``threshold`` admissions per window; time is cut into buckets of
``bucket_ms`` on one global grid and the window at ``now`` is the
``window_buckets`` newest buckets; an item of count 1 is admitted while
``admitted_in_window + rank + 1 <= threshold``, where ``rank`` counts the
items of the same pair earlier in the same tick.  All items of one tick carry
the tick's time, so a pair with ``n`` items admits
``min(n, max(0, floor(threshold - window)))`` of them whatever their order.
A value that a rule lists as an exception item (``ParamFlowItem``) has that
item's threshold in place of the rule's.

The counts are a dictionary keyed by the pair itself, so nothing collides and
nothing is estimated: upstream keeps min(4000 x durationInSec, 200,000)
values a rule in an LRU map (``ParameterMetric.java``), and a deployment
whose universe fits that has no eviction to model.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def pair_keys(rules: np.ndarray, values: np.ndarray) -> np.ndarray:
    """One int64 a (rule, value) pair; values are 31-bit hashes."""
    return (np.asarray(rules, np.int64) << 32) | np.asarray(values, np.int64)


class ParamShadow:
    """Exact admitted counts per pair on the global bucket grid."""

    def __init__(self, thresholds: Dict[int, float], items: Dict[int, float],
                 bucket_ms: int, window_buckets: int):
        self.thresholds = thresholds  # rule -> admissions a window
        self.items = items  # pair key -> its exception item's threshold
        self.bucket_ms = bucket_ms
        self.window_buckets = window_buckets
        self.counts: Dict[Tuple[int, int], int] = {}  # (pair key, bucket id) -> admitted

    def threshold(self, key: int) -> float:
        own = self.items.get(key)
        return self.thresholds[key >> 32] if own is None else own

    def window(self, key: int, now_ms: int) -> int:
        wid = now_ms // self.bucket_ms
        return sum(self.counts.get((key, wid - k), 0) for k in range(self.window_buckets))

    def tick(self, now_ms: int, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """What one tick's items (count 1 each) may be admitted:
        ``(unique pair keys, items, admissions allowed)``.  Nothing is
        counted yet: ``admit`` says what was."""
        uniq, n = np.unique(np.asarray(keys, np.int64), return_counts=True)
        room = np.array([np.floor(self.threshold(k) - self.window(k, now_ms))
                         for k in uniq.tolist()], np.float64)
        allowed = np.minimum(n, np.maximum(room, 0)).astype(np.int64)
        return uniq, n, allowed

    def admit(self, now_ms: int, keys: np.ndarray, admitted: np.ndarray) -> None:
        """Count ``admitted[i]`` admissions of pair ``keys[i]`` at ``now_ms``."""
        wid = now_ms // self.bucket_ms
        for k, a in zip(np.asarray(keys, np.int64).tolist(), np.asarray(admitted).tolist()):
            if a:
                self.counts[(k, wid)] = self.counts.get((k, wid), 0) + a

    def forget_before(self, now_ms: int) -> None:
        """Drop the buckets no window at or after ``now_ms`` can see."""
        oldest = now_ms // self.bucket_ms - self.window_buckets + 1
        self.counts = {kw: c for kw, c in self.counts.items() if kw[1] >= oldest}
