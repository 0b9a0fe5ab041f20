"""``open_loop_blocks`` with the rate raised for a stretch of the window: a
flash crowd.  The traffic file gives ``phases``, each ``{"from_s", "to_s",
"rate_x"}`` in seconds after the window opens, for a window of
``phases_window_s`` seconds; a shorter window (a traced run, the priming)
keeps the phases at the same shares of itself.  Outside every phase, and in
the pre-roll and post-roll, the rate is the cell's base rate.

Every stretch has its own Poisson gaps, drawn once from ``arrival_seed`` and
fitted to the stretch: the seed shuffles the gaps inside a stretch and never
moves one, so every seed offers the same blocks in each phase.
"""

from __future__ import annotations

import contextlib

import numpy as np

from perfbench.generators import Hooks, Window
from perfbench.generators import open_loop_blocks as base

replay = base.replay


def stretches(params: dict, seconds: float) -> list:
    """``[(start s, end s, rate items/s)]`` from the generator's start,
    end to end."""
    pre, rate = params["preroll_s"], params["rate_items_per_s"]
    scale = seconds / params["phases_window_s"]
    out, at = [], 0.0
    for ph in sorted(params["phases"], key=lambda p: p["from_s"]):
        a, b = pre + ph["from_s"] * scale, pre + ph["to_s"] * scale
        out += [(at, a, rate), (a, b, rate * ph["rate_x"])]
        at = b
    out.append((at, pre + seconds + params["postroll_s"], rate))
    return [s for s in out if s[1] > s[0]]


def schedule(params: dict, seed: int, seconds: float) -> np.ndarray:
    """Absolute due times in ns from the generator's start."""
    drawn = np.random.default_rng(params["arrival_seed"])
    order = np.random.default_rng(seed)
    due = []
    for a, b, rate in stretches(params, seconds):
        n = int((b - a) * rate / params["block_items"])
        gaps = drawn.exponential(1.0, n)
        gaps *= (b - a) / gaps.sum()
        order.shuffle(gaps)
        due.append(a + np.cumsum(gaps))
    return (np.concatenate(due) * 1e9).astype(np.int64)


@contextlib.contextmanager
def _this_schedule():
    real, base.schedule = base.schedule, schedule
    try:
        yield
    finally:
        base.schedule = real


def run(dep, params: dict, seed: int, seconds: float, hooks: Hooks) -> Window:
    with _this_schedule():
        return base.run(dep, params, seed, seconds, hooks)
