"""Load generators.  A traffic file names one of these modules under
``generator``; each has ``run(dep, params, seed, seconds, hooks) -> Window``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import numpy as np

#: a replay's virtual time starts this far past the client's clock, so that
#: every window of the measured run has lapsed
REPLAY_GAP_MS = 10_000
# verdict codes, as sentinel_tpu.core.errors numbers them
PASS = 0
BLOCK_FLOW = 1
BLOCK_SYSTEM = 4


class Hooks:
    """What the harness wants to know while a generator runs.  The generator
    calls ``opened()`` at the instant the measured window opens and
    ``closed()`` when it closes.  A generator may set ``progress`` to a
    function that returns a count of requests sent or answered so far: the
    harness's witness asks it ten times a second to see a window stand still."""

    progress = None

    def opened(self) -> None:
        pass

    def closed(self) -> None:
        pass


@dataclasses.dataclass
class Window:
    """What one generator run observed."""

    seconds: float  # length of the measured window
    open_ns: int  # time.monotonic_ns() at window open
    close_ns: int
    attempted: int  # requests due in the window
    failed: int  # of those: errored, never answered, answered BLOCK_SYSTEM, or answered late
    latency_ms: np.ndarray  # due -> resolved, per request due in the window
    due_ns: np.ndarray  # due time of each of those requests (for slices)
    visible_items: int  # items whose verdict became visible inside the window
    late_ms: np.ndarray  # sent - due per request (open loop), else empty
    passes: np.ndarray  # admitted items per engine id, over the whole run
    codes: Dict[int, int]  # verdict code -> items, over the whole run
    unresolved: int  # requests of the whole run that never resolved
    span_s: float  # first submit -> last resolve, whole run
    extra: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: of ``failed``: blocks answered in full and without BLOCK_SYSTEM, but
    #: later than the client's own ``entry_timeout_s`` after they were due (a
    #: caller of the blocking form would have given up).  Late, not wrong:
    #: they are lost to the rate and to the latency samples, and a check
    #: holds them against no answer's limit
    late: int = 0


def now_ns() -> int:
    return time.monotonic_ns()


def sleep_until(t_ns: int) -> None:
    """Sleep, never spin: a spinning Python thread would take the
    interpreter lock from the client's tick thread."""
    d = t_ns - time.monotonic_ns()
    if d > 0:
        time.sleep(d / 1e9)


class PassCounter:
    """Admitted items per engine id, accumulated per pool item so that the
    generator's bookkeeping after the window is a few vector operations."""

    def __init__(self, pool: List[tuple]):
        self._pool = pool
        self._item_pass = [np.zeros(len(b[0]), np.int64) for b in pool]
        self.codes = np.zeros(256, np.int64)

    def add(self, batch: int, start: int, verdicts: np.ndarray) -> None:
        self._item_pass[batch][start : start + len(verdicts)] += verdicts == PASS
        self.codes += np.bincount(verdicts.view(np.uint8), minlength=256)

    def passes(self) -> np.ndarray:
        n = 1 + max(int(b[0].max()) for b in self._pool)
        out = np.zeros(n, np.int64)
        for b, w in zip(self._pool, self._item_pass):
            out += np.bincount(b[0], weights=w, minlength=n).astype(np.int64)
        return out

    def code_counts(self) -> Dict[int, int]:
        return {int(k): int(v) for k, v in enumerate(self.codes) if v}
