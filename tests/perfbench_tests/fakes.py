"""A stand-in for ``SentinelClient`` that answers at once, so that the
generators' own arithmetic can be tested without JAX."""

from __future__ import annotations

import threading
from concurrent.futures import Future

import numpy as np


class FakeClient:
    """Resolves every block on a timer thread ``delay_s`` after submission,
    admitting the items whose id is odd."""

    def __init__(self, delay_s: float = 0.002, entry_timeout_s: float = 1.0):
        self.delay_s = delay_s
        self.entry_timeout_s = entry_timeout_s
        self.blocks = 0
        self.completions = 0

    def submit_block(self, res, **cols) -> Future:
        self.blocks += 1
        fut: Future = Future()
        verdicts = np.where(np.asarray(res) % 2 == 1, 0, 1).astype(np.int8)
        timer = threading.Timer(
            self.delay_s, fut.set_result, args=((verdicts, np.zeros(len(res), np.int32)),)
        )
        timer.daemon = True
        timer.start()
        return fut

    def submit_completion_block(self, res, rt, **cols) -> None:
        self.completions += 1


class FakeDeployment:
    def __init__(self, batch: int = 256, batches: int = 4, seed: int = 0, **client_kw):
        rng = np.random.default_rng(seed)
        self.client = FakeClient(**client_kw)
        self.batch = batch
        self.stops = 0
        self.pool = []
        for _ in range(batches):
            ids = rng.integers(1, 40, batch).astype(np.int32)
            z = np.zeros(batch, np.int32)
            self.pool.append((ids, z, z, np.zeros((batch, 2), np.int32), z,
                              np.ones(batch, np.float32)))

    def stop(self) -> None:
        self.stops += 1
