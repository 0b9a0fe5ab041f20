"""Open loop: blocks of ``block_items`` items (acquire + completion) at seeded
Poisson arrivals of a fixed rate.  A block's latency runs from the moment it
was due, so a stall charges every block it delays.
"""

from __future__ import annotations

import functools
from concurrent.futures import wait

import numpy as np

from perfbench.generators import (
    BLOCK_SYSTEM, REPLAY_GAP_MS, Hooks, PassCounter, Window, now_ns, sleep_until,
)


def schedule(params: dict, seed: int, seconds: float) -> np.ndarray:
    """Absolute due times in ns from the generator's start.  Every seed gets
    the same multiset of gaps (drawn once from ``arrival_seed``) in another
    order, so the seed moves the arrivals and not the amount of work."""
    total_s = params["preroll_s"] + seconds + params["postroll_s"]
    n = int(total_s * params["rate_items_per_s"] / params["block_items"])
    gaps = np.random.default_rng(params["arrival_seed"]).exponential(1.0, n)
    gaps *= total_s / gaps.sum()
    np.random.default_rng(seed).shuffle(gaps)
    return (np.cumsum(gaps) * 1e9).astype(np.int64)


def block_views(pool, block: int):
    """Every ``block``-item slice of the pool as ready column views:
    ``(batch, start, acquire kwargs, completion args)``."""
    views = []
    for b, (ids, onode, oid, ph, inb, rt) in enumerate(pool):
        for s in range(0, len(ids) - block + 1, block):
            e = s + block
            views.append((
                b, s, ids[s:e],
                dict(origin_node=onode[s:e], origin_id=oid[s:e],
                     param_hash=ph[s:e], inbound=inb[s:e]),
                rt[s:e],
            ))
    return views


def run(dep, params: dict, seed: int, seconds: float, hooks: Hooks) -> Window:
    c = dep.client
    due = schedule(params, seed, seconds)
    n = len(due)
    views = block_views(dep.pool, params["block_items"])
    order = np.random.default_rng(seed + 1).permutation(len(views))
    view_of = order[np.arange(n) % len(order)]  # the view block k sends
    sent = np.zeros(n, np.int64)
    done = np.zeros(n, np.int64)
    futs = [None] * n

    def stamp(k, _fut):
        done[k] = now_ns()

    stamps = [functools.partial(stamp, k) for k in range(n)]
    open_rel = int(params["preroll_s"] * 1e9)
    close_rel = open_rel + int(seconds * 1e9)
    in_win = (due >= open_rel) & (due < close_rel)
    k_mid = int(np.searchsorted(due, (open_rel + close_rel) // 2))
    k_end = int(np.searchsorted(due, close_rel))
    pending = {}
    hooks.progress = lambda: int(np.count_nonzero(done))

    t0 = now_ns() + 2_000_000
    opened = closed = False
    for k in range(n):
        if not opened and due[k] >= open_rel:
            sleep_until(t0 + open_rel)
            hooks.opened()
            opened = True
        if not closed and due[k] >= close_rel:
            sleep_until(t0 + close_rel)
            hooks.closed()
            closed = True
        if k == k_mid or k == k_end:
            pending[k] = k - int(np.count_nonzero(done[:k]))
        sleep_until(t0 + due[k])
        _b, _s, ids, cols, rt = views[view_of[k]]
        sent[k] = now_ns()
        fut = c.submit_block(ids, **cols)
        c.submit_completion_block(
            ids, rt, inbound=cols["inbound"], param_hash=cols["param_hash"]
        )
        futs[k] = fut
        fut.add_done_callback(stamps[k])
    if not closed:
        sleep_until(t0 + close_rel)
        hooks.closed()
    wait(futs, timeout=c.entry_timeout_s)

    counter = PassCounter(dep.pool)
    ok = np.zeros(n, bool)
    for k, fut in enumerate(futs):
        if not fut.done() or fut.exception() is not None:
            continue
        verdicts = fut.result()[0]
        b, s = views[view_of[k]][:2]
        counter.add(b, s, verdicts)
        ok[k] = not (verdicts == BLOCK_SYSTEM).any()
    futs.clear()
    lat_ms = (done - (t0 + due)) / 1e6
    good = in_win & ok & (lat_ms <= c.entry_timeout_s * 1e3)
    vis = ok & (done >= t0 + open_rel) & (done < t0 + close_rel)
    return Window(
        seconds=seconds,
        open_ns=t0 + open_rel,
        close_ns=t0 + close_rel,
        attempted=int(in_win.sum()),
        failed=int((in_win & ~good).sum()),
        latency_ms=lat_ms[good],
        due_ns=t0 + due[good],
        visible_items=int(vis.sum()) * params["block_items"],
        late_ms=(sent - (t0 + due))[in_win] / 1e6,
        passes=counter.passes(),
        codes=counter.code_counts(),
        unresolved=int(n - np.count_nonzero(done)),
        span_s=float((done.max() - sent[0]) / 1e9),
        late=int((in_win & ok & ~good).sum()),
        extra={
            "pending_mid": pending.get(k_mid, 0),
            "pending_end": pending.get(k_end, 0),
            "offered_items_per_s": n * params["block_items"]
            / (params["preroll_s"] + seconds + params["postroll_s"]),
            "failed_block_system_or_error": int((in_win & ~ok).sum()),
            "worst_latency_ms": float(lat_ms[in_win & (done > 0)].max(initial=0.0)),
        },
    )


def replay(dep, params: dict, seed: int) -> list:
    """Drive ``replay.ticks`` virtual ticks of block traffic by hand."""
    c = dep.client
    rp = params["replay"]
    block = params.get("block_items", dep.batch)
    views = block_views(dep.pool, block)
    rng = np.random.default_rng(seed + 2)
    pick = rng.permutation(len(views))
    per_tick = rp["blocks_per_tick"]
    t = c.time.now_ms() + REPLAY_GAP_MS
    ticks, k = [], 0
    for i in range(rp["ticks"]):
        futs, ids_parts = [], []
        for _ in range(per_tick[i % len(per_tick)]):
            _b, _s, ids, cols, rt = views[pick[k % len(pick)]]
            k += 1
            futs.append(c.submit_block(ids, **cols))
            c.submit_completion_block(
                ids, rt, inbound=cols["inbound"], param_hash=cols["param_hash"]
            )
            ids_parts.append(ids)
        c.tick_once(now_ms=t)
        verdicts = [f.result(timeout=c.entry_timeout_s)[0] for f in futs]
        ticks.append((t, np.concatenate(ids_parts), np.concatenate(verdicts)))
        t += rp["step_ms"]
    return ticks
