"""Closed loop: ``inflight`` full-batch blocks (acquire + completion) kept in
flight; each resolved block's callback submits the next, as a caller that
waits for its reply would.  The client's own tick thread does the ticking.
"""

from __future__ import annotations

import threading

import numpy as np

from perfbench.generators import (
    BLOCK_SYSTEM, Hooks, PassCounter, Window, now_ns, sleep_until,
)
from perfbench.generators.open_loop_blocks import replay  # noqa: F401  (same replay: blocks at virtual ticks)


def run(dep, params: dict, seed: int, seconds: float, hooks: Hooks) -> Window:
    c = dep.client
    pool = dep.pool
    order = np.random.default_rng(seed + 1).permutation(len(pool))
    total_s = params["preroll_s"] + seconds + params["postroll_s"]
    cap = int(total_s * params["max_blocks_per_s"]) + params["inflight"]
    sent = np.zeros(cap, np.int64)
    done = np.zeros(cap, np.int64)
    futs = [None] * cap
    lock = threading.Lock()
    state = {"next": 0, "stop": False}

    def feed():
        with lock:
            k = state["next"]
            if state["stop"] or k >= cap:
                return
            state["next"] = k + 1
        ids, onode, oid, ph, inb, rt = pool[order[k % len(order)]]
        sent[k] = now_ns()
        fut = c.submit_block(
            ids, origin_node=onode, origin_id=oid, param_hash=ph, inbound=inb
        )
        c.submit_completion_block(ids, rt, inbound=inb, param_hash=ph)
        futs[k] = fut

        def on_done(_f, k=k):  # resolver-pool thread: must not block
            done[k] = now_ns()
            feed()

        fut.add_done_callback(on_done)

    hooks.progress = lambda: state["next"]
    t0 = now_ns()
    open_ns = t0 + int(params["preroll_s"] * 1e9)
    close_ns = open_ns + int(seconds * 1e9)
    for _ in range(params["inflight"]):
        feed()
    sleep_until(open_ns)
    hooks.opened()
    sleep_until(close_ns)
    hooks.closed()
    sleep_until(close_ns + int(params["postroll_s"] * 1e9))
    with lock:
        state["stop"] = True
        n = state["next"]
    deadline = now_ns() + int(c.entry_timeout_s * 1e9)
    while np.count_nonzero(done[:n]) < n and now_ns() < deadline:
        sleep_until(now_ns() + 5_000_000)

    counter = PassCounter(pool)
    ok = np.zeros(n, bool)
    for k in range(n):
        fut = futs[k]
        if not done[k] or fut.exception() is not None:
            continue
        verdicts = fut.result()[0]
        counter.add(order[k % len(order)], 0, verdicts)
        ok[k] = not (verdicts == BLOCK_SYSTEM).any()
    futs.clear()
    sent, done = sent[:n], done[:n]
    # a block belongs to the window in which its verdicts became visible
    in_win = (done >= open_ns) & (done < close_ns)
    lat_ms = (done - sent) / 1e6
    good = in_win & ok & (lat_ms <= c.entry_timeout_s * 1e3)
    failed = in_win & ~good
    # blocks that never resolved count against the window they were sent in
    lost = (done == 0) & (sent >= open_ns) & (sent < close_ns)
    # the longest the window went without a reply, and when: a stall shows
    # here whatever it did to the rate
    seen = np.sort(np.concatenate([[open_ns], done[in_win], [close_ns]]))
    gap = int(np.argmax(np.diff(seen)))
    return Window(
        seconds=seconds,
        open_ns=open_ns,
        close_ns=close_ns,
        attempted=int(in_win.sum() + lost.sum()),
        failed=int(failed.sum() + lost.sum()),
        latency_ms=lat_ms[good],
        due_ns=sent[good],
        visible_items=int(good.sum()) * dep.batch,
        late_ms=np.zeros(0),
        passes=counter.passes(),
        codes=counter.code_counts(),
        unresolved=int(n - np.count_nonzero(done)),
        span_s=float((done.max() - sent[0]) / 1e9),
        late=int((in_win & ok & ~good).sum()),
        extra={
            "longest_reply_gap_s": float((seen[gap + 1] - seen[gap]) / 1e9),
            "longest_reply_gap_at_s": float((seen[gap] - open_ns) / 1e9),
            "failed_block_system_or_error": int((in_win & ~ok).sum()),
            "failed_lost": int(lost.sum()),
            "worst_latency_ms": float(lat_ms[in_win].max()) if in_win.any() else None,
        },
    )
