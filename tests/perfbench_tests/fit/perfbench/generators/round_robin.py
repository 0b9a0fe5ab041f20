"""Open loop at a fixed rate: one request of ``ids_per_request`` seeded ids
every ``1 / requests_per_s`` seconds, the doors asked in turn."""

from __future__ import annotations

from concurrent.futures import wait

import numpy as np

from perfbench.generators import Hooks, Window, now_ns, sleep_until


def requests(dep, params: dict, seed: int, seconds: float) -> np.ndarray:
    """Every request of a run as a row of ids, from the seed."""
    total_s = params["preroll_s"] + seconds + params["postroll_s"]
    n = int(total_s * params["requests_per_s"])
    return np.random.default_rng(seed).integers(
        0, dep.config["rule"]["ids"], (n, params["ids_per_request"]))


def run(dep, params: dict, seed: int, seconds: float, hooks: Hooks) -> Window:
    ids = requests(dep, params, seed, seconds)
    n, n_ids = len(ids), dep.config["rule"]["ids"]
    due = (np.arange(n) / params["requests_per_s"] * 1e9).astype(np.int64)
    open_rel = int(params["preroll_s"] * 1e9)
    close_rel = open_rel + int(seconds * 1e9)
    sent, done = np.zeros(n, np.int64), np.zeros(n, np.int64)
    futs = []
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
        sleep_until(t0 + due[k])
        sent[k] = now_ns()
        fut = dep.doors[k % len(dep.doors)].ask(ids[k])
        fut.add_done_callback(lambda _f, k=k: done.__setitem__(k, now_ns()))
        futs.append(fut)
    if not closed:
        sleep_until(t0 + close_rel)
        hooks.closed()
    wait(futs, timeout=dep.answer_timeout_s)

    answered = np.array([f.done() for f in futs])
    codes = np.stack([f.result() if f.done() else np.full(ids.shape[1], -1) for f in futs])
    in_win = (due >= open_rel) & (due < close_rel)
    good = in_win & answered
    vis = answered & (done >= t0 + open_rel) & (done < t0 + close_rel)
    return Window(
        seconds=seconds, open_ns=t0 + open_rel, close_ns=t0 + close_rel,
        attempted=int(in_win.sum()), failed=int((in_win & ~answered).sum()),
        latency_ms=(done - (t0 + due))[good] / 1e6, due_ns=t0 + due[good],
        visible_items=int(vis.sum()) * ids.shape[1],
        late_ms=(sent - (t0 + due))[in_win] / 1e6,
        passes=np.bincount(ids[codes == 0], minlength=n_ids),
        codes={int(c): int((codes == c).sum()) for c in np.unique(codes)},
        unresolved=int((~answered).sum()),
        span_s=float((done.max() - sent[0]) / 1e9),
    )
