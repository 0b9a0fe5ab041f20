"""LeapArray windows and QPS admission, written straightforwardly.

Semantics (alibaba/Sentinel ``LeapArray.java``, ``DefaultController.canPass``):
time is cut into buckets of ``window_ms``; a bucket whose slot holds an older
start is reset when first touched; the window at ``now`` is the
``sample_count`` newest buckets; a request of count 1 passes while
``passes_in_window + 1 <= threshold`` and each pass is added to the current
bucket.  All items of one tick carry the tick's time, so a resource with
``n`` items admits ``min(n, max(0, floor(threshold - window)))`` of them
whatever their order in the batch.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


class LeapWindows:
    """Pass counts of ``rows`` resources in ``sample_count`` buckets."""

    def __init__(self, rows: int, sample_count: int, window_ms: int):
        self.sample_count = sample_count
        self.window_ms = window_ms
        self.counts = np.zeros((rows, sample_count), np.int64)
        self.starts = np.full(sample_count, -1, np.int64)

    def _current(self, now_ms: int) -> int:
        wid = now_ms // self.window_ms
        idx = wid % self.sample_count
        start = wid * self.window_ms
        if self.starts[idx] != start:
            self.counts[:, idx] = 0
            self.starts[idx] = start
        return idx

    def window(self, now_ms: int) -> np.ndarray:
        """Passes of every row in the window that ends at ``now_ms``."""
        self._current(now_ms)
        age = now_ms - self.starts
        live = (self.starts >= 0) & (age >= 0) & (
            age < self.sample_count * self.window_ms
        )
        return self.counts[:, live].sum(axis=1)

    def add(self, now_ms: int, rows: np.ndarray, n: np.ndarray) -> None:
        np.add.at(self.counts[:, self._current(now_ms)], rows, n)


class FlowReference:
    """QPS FlowRules (grade QPS, DEFAULT behaviour, DIRECT strategy) over
    engine ids.  ``rule_ids`` are the ids that carry a rule, ``thresholds``
    their counts per second; every other id is unruled and always passes."""

    def __init__(self, rule_ids, thresholds, sample_count: int, window_ms: int):
        order = np.argsort(rule_ids)
        self.rule_ids = np.asarray(rule_ids, np.int64)[order]
        self.thresholds = np.asarray(thresholds, np.float64)[order]
        if sample_count * window_ms != 1000:
            raise ValueError("a QPS threshold is per 1000 ms of window")
        self.windows = LeapWindows(len(self.rule_ids), sample_count, window_ms)

    def rows_of(self, ids: np.ndarray) -> np.ndarray:
        """Row of each id, -1 where the id carries no rule."""
        if not len(self.rule_ids):
            return np.full(len(ids), -1, np.int64)
        pos = np.clip(np.searchsorted(self.rule_ids, ids), 0, len(self.rule_ids) - 1)
        return np.where(self.rule_ids[pos] == ids, pos, -1)

    def tick(self, now_ms: int, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One tick's items (count 1 each): ``(unique ids, items, passes)``."""
        uniq, n = np.unique(np.asarray(ids, np.int64), return_counts=True)
        rows = self.rows_of(uniq)
        ruled = rows >= 0
        passes = n.copy()
        if ruled.any():
            r = rows[ruled]
            room = np.floor(self.thresholds[r] - self.windows.window(now_ms)[r])
            passes[ruled] = np.minimum(n[ruled], np.maximum(room, 0)).astype(np.int64)
            self.windows.add(now_ms, r, passes[ruled])
        return uniq, n, passes
