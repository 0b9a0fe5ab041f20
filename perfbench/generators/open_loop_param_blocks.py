"""``open_loop_blocks`` for a deployment whose items carry a parameter value
(``param_client``): the same arrivals, blocks and window, and beside them
what the check of such a deployment needs and ``open_loop_blocks`` does not
keep: admissions per (route, value) pair over the run, and a replay that
hands back the value column beside the ids.
"""

from __future__ import annotations

import contextlib

import numpy as np

from perfbench.generators import REPLAY_GAP_MS, Hooks, PassCounter, Window
from perfbench.generators import open_loop_blocks as base
from perfbench.reference.param_shadow import pair_keys


@contextlib.contextmanager
def _kept_counter(kept: list):
    """While this holds, the ``PassCounter`` that ``open_loop_blocks.run``
    makes is kept in ``kept``: it counts admissions per pool item, which is
    what a count per pair is summed from."""

    class Kept(PassCounter):
        def __init__(self, pool):
            super().__init__(pool)
            kept.append(self)

    real, base.PassCounter = base.PassCounter, Kept
    try:
        yield
    finally:
        base.PassCounter = real


def pair_admissions(pool, item_pass) -> tuple:
    """``(pair keys, admissions over the run)`` of every pair admitted at
    least once, from the admissions per pool item."""
    keys = np.concatenate([pair_keys(b[0], b[3][:, 0])[w > 0] for b, w in zip(pool, item_pass)])
    n = np.concatenate([w[w > 0] for w in item_pass])
    uniq, inverse = np.unique(keys, return_inverse=True)
    return uniq, np.bincount(inverse, weights=n, minlength=len(uniq)).astype(np.int64)


def run(dep, params: dict, seed: int, seconds: float, hooks: Hooks) -> Window:
    kept: list = []
    with _kept_counter(kept):
        win = base.run(dep, params, seed, seconds, hooks)
    keys, admitted = pair_admissions(dep.pool, kept[0]._item_pass)
    rule, item = dep.thresholds()
    thr = np.array([item.get(k, rule[k >> 32]) for k in keys.tolist()], np.float64)
    win.extra.update(
        pairs_admitted=int(len(keys)),
        pairs_admitted_per_s=float(len(keys) / max(win.span_s, 1e-9)),
        # a pair's windows tumble on a grid of half a second: over a run of
        # span_s seconds it sees at most floor(span_s) + 2 whole budgets
        pairs_over_their_windows=int((admitted > thr * (np.floor(win.span_s) + 2)).sum()),
    )
    return win


def replay(dep, params: dict, seed: int) -> list:
    """Drive ``replay.ticks`` virtual ticks of block traffic by hand; a tick
    is ``(now_ms, ids, value hashes, verdicts)``."""
    c = dep.client
    rp = params["replay"]
    views = base.block_views(dep.pool, params.get("block_items", dep.batch))
    pick = np.random.default_rng(seed + 2).permutation(len(views))
    per_tick = rp["blocks_per_tick"]
    t = c.time.now_ms() + REPLAY_GAP_MS
    ticks, k = [], 0
    for i in range(rp["ticks"]):
        futs, ids_parts, value_parts = [], [], []
        for _ in range(per_tick[i % len(per_tick)]):
            _b, _s, ids, cols, rt = views[pick[k % len(pick)]]
            k += 1
            futs.append(c.submit_block(ids, **cols))
            c.submit_completion_block(
                ids, rt, inbound=cols["inbound"], param_hash=cols["param_hash"]
            )
            ids_parts.append(ids)
            value_parts.append(cols["param_hash"][:, 0])
        c.tick_once(now_ms=t)
        verdicts = [f.result(timeout=c.entry_timeout_s)[0] for f in futs]
        ticks.append((t, np.concatenate(ids_parts), np.concatenate(value_parts),
                      np.concatenate(verdicts)))
        t += rp["step_ms"]
    return ticks
