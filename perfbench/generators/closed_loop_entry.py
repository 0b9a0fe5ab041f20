"""Closed loop, one request at a time: ``threads`` caller threads, each
``entry()`` ... ``exit()`` and then the next (upstream's JMH shape,
``SentinelEntryBenchmark``).  A request is due the moment its thread is free.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from perfbench.generators import (
    BLOCK_SYSTEM, PASS, REPLAY_GAP_MS, Hooks, Window, now_ns, sleep_until,
)

FAILED = -1  # the call raised something that is not a verdict, or timed out


def run(dep, params: dict, seed: int, seconds: float, hooks: Hooks) -> Window:
    from sentinel_tpu.core.errors import BlockException

    c = dep.client
    n_threads = params["threads"]
    n_ruled = len(dep.ruled_names)
    cap = int((params["preroll_s"] + seconds + params["postroll_s"] + 1)
              * params["max_entries_per_s_per_thread"])
    order = np.random.default_rng(seed + 1).permutation(len(dep.pool))
    stop = threading.Event()
    rows = []

    def caller(i: int):
        ids = dep.pool[order[i % len(order)]][0]
        ids = ids[(ids >= 1) & (ids <= n_ruled)][:cap]
        t_due = np.zeros(len(ids), np.int64)
        t_done = np.zeros(len(ids), np.int64)
        code = np.full(len(ids), FAILED, np.int16)
        rows.append((ids, t_due, t_done, code))
        for j, rid in enumerate(ids):
            if stop.is_set():
                break
            t_due[j] = now_ns()
            try:
                e = c.entry(dep.ruled_names[rid - 1])
            except BlockException as exc:
                t_done[j] = now_ns()
                code[j] = exc.code
            except Exception:  # a timeout or a broken tick: counted as failed
                t_done[j] = now_ns()
            else:
                t_done[j] = now_ns()
                code[j] = PASS
                e.exit()

    threads = [
        threading.Thread(target=caller, args=(i,), name=f"perfbench-caller-{i}")
        for i in range(n_threads)
    ]
    hooks.progress = lambda: sum(int(np.count_nonzero(r[2])) for r in list(rows))
    t0 = now_ns()
    open_ns = t0 + int(params["preroll_s"] * 1e9)
    close_ns = open_ns + int(seconds * 1e9)
    for t in threads:
        t.start()
    sleep_until(open_ns)
    hooks.opened()
    sleep_until(close_ns)
    hooks.closed()
    sleep_until(close_ns + int(params["postroll_s"] * 1e9))
    stop.set()
    for t in threads:
        t.join(timeout=c.entry_timeout_s + 1.0)
    alive = sum(t.is_alive() for t in threads)

    ids = np.concatenate([r[0] for r in rows])
    due = np.concatenate([r[1] for r in rows])
    done = np.concatenate([r[2] for r in rows])
    code = np.concatenate([r[3] for r in rows])
    ran = due > 0
    ids, due, done, code = ids[ran], due[ran], done[ran], code[ran]
    in_win = (due >= open_ns) & (due < close_ns)
    bad = (code == FAILED) | (code == BLOCK_SYSTEM) | (done == 0)
    good = in_win & ~bad
    vis = ~bad & (done >= open_ns) & (done < close_ns)
    codes = {int(k): int((code == k).sum()) for k in np.unique(code)}
    return Window(
        seconds=seconds,
        open_ns=open_ns,
        close_ns=close_ns,
        attempted=int(in_win.sum()),
        failed=int((in_win & bad).sum()),
        latency_ms=(done - due)[good] / 1e6,
        due_ns=due[good],
        visible_items=int(vis.sum()),
        late_ms=np.zeros(0),
        passes=np.bincount(ids[code == PASS], minlength=n_ruled + 1),
        codes=codes,
        unresolved=alive + int((done == 0).sum()),
        span_s=float((done.max() - due.min()) / 1e9),
    )


def replay(dep, params: dict, seed: int) -> list:
    """Virtual ticks of ``threads`` concurrent ``entry()`` calls each: the
    callers queue their requests, the harness ticks once, they read their
    verdicts and ``exit()``."""
    from sentinel_tpu.core.errors import BlockException

    c = dep.client
    rp = params["replay"]
    n_thr = params["threads"]
    n_ruled = len(dep.ruled_names)
    ids_all = dep.pool[np.random.default_rng(seed + 2).integers(len(dep.pool))][0]
    ids_all = ids_all[(ids_all >= 1) & (ids_all <= n_ruled)]
    need = rp["ticks"] * n_thr
    ids_all = np.resize(ids_all, need).reshape(rp["ticks"], n_thr)
    verdicts = np.full((rp["ticks"], n_thr), -1, np.int16)
    go = [threading.Semaphore(0) for _ in range(n_thr)]
    finished = threading.Semaphore(0)

    def caller(j: int):
        for i in range(rp["ticks"]):
            go[j].acquire()
            try:
                e = c.entry(dep.ruled_names[ids_all[i, j] - 1])
            except BlockException as exc:
                verdicts[i, j] = exc.code
            except Exception:  # timeout: stays -1 and fails the comparison
                pass
            else:
                verdicts[i, j] = PASS
                e.exit()
            finished.release()

    threads = [threading.Thread(target=caller, args=(j,)) for j in range(n_thr)]
    for th in threads:
        th.start()
    t = c.time.now_ms() + REPLAY_GAP_MS
    ticks = []
    for i in range(rp["ticks"]):
        for s in go:
            s.release()
        deadline = now_ns() + int(c.entry_timeout_s * 1e9)
        while c.pending_acquires() < n_thr and now_ns() < deadline:
            time.sleep(0.0001)
        c.tick_once(now_ms=t)
        for _ in range(n_thr):
            finished.acquire(timeout=c.entry_timeout_s + 1.0)
        ticks.append((t, ids_all[i].astype(np.int64), verdicts[i].copy()))
        t += rp["step_ms"]
    for th in threads:
        th.join(timeout=c.entry_timeout_s + 1.0)
    return ticks
