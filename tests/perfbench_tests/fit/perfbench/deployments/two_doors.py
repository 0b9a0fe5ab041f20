"""A deployment kind the harness has never seen: two doors, each a thread
with a queue of its own, answering under one rule that both were loaded
with.  No JAX, no ``SentinelClient``: what it stands in for is a kind with
several clients behind a front door."""

from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time
from concurrent.futures import Future
from typing import List, Optional

import numpy as np

from perfbench.deployments import intervals, with_sizes

GRANTED, SPENT = 0, 9  # 9: a verdict code that no FlowRule traffic produces
#: how a traced run of this kind's cells would be read (perfbench/deployments/
#: __init__.py): a door's answer is its tick, and the host spans that can
#: explain an idle device are its own two, neither of them a ``tick.*``
TICK_SPAN = "door.answer"
HOST_SPANS = ("door.answer", "door.queue")
_extra_grants = 0  # what ``control()`` loads the doors with beyond the configuration


class Door(threading.Thread):
    def __init__(self, grants_per_request: int, delay_s: float):
        super().__init__(daemon=True)
        self.requests: queue.Queue = queue.Queue()
        self.grants_per_request, self.delay_s = grants_per_request, delay_s

    def ask(self, ids: np.ndarray) -> Future:
        fut: Future = Future()
        self.requests.put((ids, fut))
        return fut

    def run(self) -> None:
        while True:
            ids, fut = self.requests.get()
            if fut is None:
                return
            time.sleep(self.delay_s)
            seen: dict = {}
            codes = np.empty(len(ids), np.int16)
            for k, i in enumerate(ids.tolist()):
                seen[i] = seen.get(i, 0) + 1
                codes[k] = GRANTED if seen[i] <= self.grants_per_request else SPENT
            fut.set_result(codes)


@dataclasses.dataclass
class Deployment:
    config: dict
    batch: int
    doors: List[Door]
    answer_timeout_s: float

    def start(self) -> None:
        for d in self.doors:
            d.start()

    def stop(self) -> None:
        for d in self.doors:
            if d.is_alive():
                d.requests.put((None, None))
                d.join(timeout=5.0)


def host_intervals(spans: List[dict]) -> list:
    return [intervals(spans, n) for n in HOST_SPANS]


@contextlib.contextmanager
def control():
    """While this holds, ``build`` loads both doors with one grant a request
    more than the configuration states."""
    global _extra_grants
    _extra_grants = 1
    try:
        yield
    finally:
        _extra_grants = 0


def build(cfg: dict, seed: int, sizes: Optional[dict] = None) -> Deployment:
    cfg = with_sizes(cfg, sizes)
    doors = [Door(cfg["rule"]["grants_per_request"] + _extra_grants, cfg["doors"]["delay_s"])
             for _ in range(cfg["doors"]["n"])]
    return Deployment(cfg, 1, doors, cfg["doors"]["answer_timeout_s"])
