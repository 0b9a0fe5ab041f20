"""Open loop, for a deployment under circuit breakers (``breaker_client``):
``open_loop_blocks``' arrivals of acquire blocks, and exits that follow the
verdicts.  Upstream's ``DegradeSlot.exit`` records nothing for an entry that
was blocked, and an admitted call's exit comes its response time later.  So a
block's done-callback (non-blocking, as ``submit_block`` demands) keeps the
PASS items only, gives each the response time of its service's health at that
moment, stamps it with ``verdict time + rt`` and hands it to the sender; the
sender's thread sends what has come due on a grid of ``exit_grid_ms`` as one
``submit_completion_block`` a slot.  A blocked item sends nothing.

A block of ``block_items`` divides the tick's batch, and the client takes
whole blocks while they fit, so a block's items are decided in one tick: what
a breaker may do in a tick (all PASS, all BLOCK_DEGRADE, or one probe among
BLOCK_DEGRADE) holds per block, and is counted over a seeded sample of the
blocks after the run, off the sender's thread.
"""

from __future__ import annotations

import collections
import functools
import threading
from concurrent.futures import wait

import numpy as np

from perfbench.generators import (
    BLOCK_SYSTEM, PASS, REPLAY_GAP_MS, Hooks, PassCounter, Window, now_ns, sleep_until,
)
from perfbench.generators.open_loop_blocks import block_views, schedule

#: verdict code of a breaker's block, as sentinel_tpu.core.errors numbers it
BLOCK_DEGRADE = 2
#: how often the sender's thread works out which services are sick
HEALTH_EVERY_NS = 100_000_000


class ExitSender(threading.Thread):
    """Sends the exits that have come due, a slot of the grid at a time."""

    def __init__(self, dep, params: dict, t0_ns: int):
        from sentinel_tpu import obs

        super().__init__(name="perfbench-exit-sender", daemon=True)
        self._tracer = obs.TRACER
        self.dep, self.t0 = dep, t0_ns
        self.grid_ns = int(params["exit_grid_ms"] * 1e6)
        tr = dep.config["traffic"]
        self.period_s, self.sick_s = tr["sick_period_s"], tr["sick_s"]
        self.sick_now = dep.sick(0.0, self.period_s, self.sick_s)  # callbacks read it
        self.handed = collections.deque()  # (due_ns, ids, rt, inbound) of a block's PASS items
        self.sent = 0
        self.late_ns = []  # per slot: send time less the oldest due time it carried
        self._parts = [np.zeros(0, np.int64), np.zeros(0, np.int32), np.zeros(0, np.float32),
                       np.zeros(0, np.int32)]
        self._last = False  # set once nothing more will be handed in

    def pending(self) -> int:
        return len(self._parts[0]) + len(self.handed)

    def finish(self) -> None:
        """Nothing more will be handed in: send what is left as it comes due
        (the longest response time is ``rt_ms_cap``)."""
        self._last = True
        self.join(timeout=30.0)

    def _slot(self, now: int) -> None:
        got = [self.handed.popleft() for _ in range(len(self.handed))]
        if got:
            self._parts = [np.concatenate([p] + [g[i] for g in got]) for i, p in enumerate(self._parts)]
        due, ids, rt, inb = self._parts
        go = due <= now
        n = int(go.sum())
        if not n:
            return
        oldest = int(due[go].min())
        self.dep.client.submit_completion_block(ids[go], rt[go], inbound=inb[go])
        sent_ns = now_ns()
        self.sent += n
        self.late_ns.append(sent_ns - oldest)
        if self._tracer.enabled:
            # exit.due: the oldest exit of the slot came due -> the slot is with the client
            self._tracer.record("exit.due", oldest, sent_ns - oldest, 0, {"n": n})
        self._parts = [p[~go] for p in self._parts]

    def run(self) -> None:
        nxt = now_ns()
        health_at = 0
        while True:
            nxt += self.grid_ns
            sleep_until(nxt)
            now = now_ns()
            if now >= health_at:
                self.sick_now = self.dep.sick((now - self.t0) / 1e9, self.period_s, self.sick_s)
                health_at = now + HEALTH_EVERY_NS
            self._slot(now)
            if self._last and not self.pending():
                return


def mixed_verdicts(ids: np.ndarray, verdicts: np.ndarray) -> int:
    """Resources of one tick's items whose verdicts are neither all PASS, all
    BLOCK_DEGRADE, nor one PASS among BLOCK_DEGRADE."""
    _uniq, inv = np.unique(ids, return_inverse=True)
    passed = np.bincount(inv, weights=verdicts == PASS)
    blocked = np.bincount(inv, weights=verdicts != PASS)
    return int(((passed >= 2) & (blocked >= 1)).sum())


def run(dep, params: dict, seed: int, seconds: float, hooks: Hooks) -> Window:
    c = dep.client
    block = params["block_items"]
    if dep.batch % block:
        raise ValueError(f"block_items {block} does not divide the batch {dep.batch}: a block "
                         f"could lie across two ticks")
    due = schedule(params, seed, seconds)
    n = len(due)
    views = block_views(dep.pool, block)
    order = np.random.default_rng(seed + 1).permutation(len(views))
    view_of = order[np.arange(n) % len(order)]  # the view block k sends
    sent = np.zeros(n, np.int64)
    done = np.zeros(n, np.int64)
    handed = np.zeros(n, np.int64)  # exits handed to the sender, per block
    futs = [None] * n

    t0 = now_ns() + 2_000_000
    sender = ExitSender(dep, params, t0)

    def on_verdicts(k, fut):
        t = now_ns()
        done[k] = t
        if fut.exception() is not None:
            return
        keep = np.flatnonzero(fut.result()[0] == PASS)
        if not len(keep):
            return
        b, s, ids, cols, rt = views[view_of[k]]
        sick = sender.sick_now[dep.pool_rank[b][s + keep]]
        rt = np.where(sick, dep.pool_rt_sick[b][s + keep], rt[keep])
        handed[k] = len(keep)
        sender.handed.append((t + (rt * 1e6).astype(np.int64), ids[keep], rt, cols["inbound"][keep]))

    callbacks = [functools.partial(on_verdicts, k) for k in range(n)]
    open_rel = int(params["preroll_s"] * 1e9)
    close_rel = open_rel + int(seconds * 1e9)
    in_win = (due >= open_rel) & (due < close_rel)
    k_mid = int(np.searchsorted(due, (open_rel + close_rel) // 2))
    k_end = int(np.searchsorted(due, close_rel))
    pending = {}
    hooks.progress = lambda: int(np.count_nonzero(done))

    sender.start()
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
        _b, _s, ids, cols, _rt = views[view_of[k]]
        sent[k] = now_ns()
        fut = c.submit_block(ids, **cols)
        futs[k] = fut
        fut.add_done_callback(callbacks[k])
    if not closed:
        sleep_until(t0 + close_rel)
        hooks.closed()
    wait(futs, timeout=c.entry_timeout_s)
    sender.finish()  # every admitted call exits, its response time after its verdict

    counter = PassCounter(dep.pool)
    ok = np.zeros(n, bool)
    n_pass = np.zeros(n, np.int64)
    sample = np.random.default_rng(seed + 3).random(n) < params["verdict_sample_share"]
    mixed = sampled = 0
    for k, fut in enumerate(futs):
        if not fut.done() or fut.exception() is not None:
            continue
        verdicts = fut.result()[0]
        b, s, ids = views[view_of[k]][:3]
        counter.add(b, s, verdicts)
        ok[k] = not (verdicts == BLOCK_SYSTEM).any()
        n_pass[k] = int((verdicts == PASS).sum())
        if sample[k] and in_win[k]:
            sampled += 1
            mixed += mixed_verdicts(ids, verdicts)
    futs.clear()
    lat_ms = (done - (t0 + due)) / 1e6
    good = in_win & ok & (lat_ms <= c.entry_timeout_s * 1e3)
    vis = ok & (done >= t0 + open_rel) & (done < t0 + close_rel)
    late = np.asarray(sender.late_ns, np.float64) / 1e6
    return Window(
        seconds=seconds,
        open_ns=t0 + open_rel,
        close_ns=t0 + close_rel,
        attempted=int(in_win.sum()),
        failed=int((in_win & ~good).sum()),
        latency_ms=lat_ms[good],
        due_ns=t0 + due[good],
        visible_items=int(vis.sum()) * block,
        late_ms=(sent - (t0 + due))[in_win] / 1e6,
        passes=counter.passes(),
        codes=counter.code_counts(),
        unresolved=int(n - np.count_nonzero(done)),
        span_s=float((done.max() - sent[0]) / 1e9),
        late=int((in_win & ok & ~good).sum()),
        extra={
            "pending_mid": pending.get(k_mid, 0),
            "pending_end": pending.get(k_end, 0),
            "offered_items_per_s": n * block / (params["preroll_s"] + seconds + params["postroll_s"]),
            "failed_block_system_or_error": int((in_win & ~ok).sum()),
            "worst_latency_ms": float(lat_ms[in_win & (done > 0)].max(initial=0.0)),
            "exits_sent": sender.sent,
            "exits_unsent": int(handed.sum()) - sender.sent,
            "exits_for_blocked_items": int(np.maximum(handed - n_pass, 0).sum()),
            "exit_late_ms_mean": float(late.mean()) if len(late) else 0.0,
            "exit_late_ms_p99": float(np.percentile(late, 99)) if len(late) else 0.0,
            "blocks_sampled": sampled,
            "mixed_verdict_resources": mixed,
            "blocked_share": 1.0 - float(n_pass.sum()) / max(int(np.count_nonzero(done)) * block, 1),
        },
    )


def replay(dep, params: dict, seed: int):
    """Drive ``replay.ticks`` virtual ticks by hand, the tick thread stopped:
    seeded blocks at stated times through ``tick_once(now_ms=t)``, each
    admitted item's exit submitted at the first tick at or after ``its tick +
    rt`` (1 to ``rt_ms_cap / step_ms`` ticks on), over a sick schedule
    compressed (``replay.sick_period_s``, ``replay.sick_s``) so that the span
    holds every transition many times.

    ``replay.tick_items`` is a cycle of runs ``[ticks, items a tick]``, so
    that every compiled shape of the tick is replayed: a run of a few items a
    tick (a slice of a block) runs the light shape once the exits of the
    ticks before it have thinned out, a few blocks the middle one, a full
    batch the full one.  **The replay's own, and no part of the timed
    window's traffic**: a share ``replay.hung_share_sick`` of the calls
    admitted to a sick service hangs for ``replay.hung_rt_ms`` (about the
    rules' ``time_window``), because with response times of at most a second
    no other call's exit can meet a breaker that is HALF_OPEN ten seconds
    after its trip, which is the case upstream's "any exit resolves the
    probe" exists for.

    Before the first tick every breaker is set CLOSED
    (``dep.reset_breakers()``) and a gap lets every statistic window lapse, so
    the reference starts from a known state, not from one read back.  Yields,
    first, the breakers' state per rank as the window left them (read after
    one empty tick has taken in what the window left queued; for the
    summary), then per tick ``(now_ms, ids, verdicts, exit ids, exit rts,
    when each exiting call was admitted, state per rank after the tick,
    (acquire rows, completion rows) of the shape the tick ran at)``."""
    from sentinel_tpu.ops import wire

    c = dep.client
    rp = params["replay"]
    step, block = rp["step_ms"], params["block_items"]
    views = block_views(dep.pool, block)
    pick = np.random.default_rng(seed + 2).permutation(len(views))
    hang = np.random.default_rng(seed + 4)
    per_tick = np.repeat([n for _t, n in rp["tick_items"]], [t for t, _n in rp["tick_items"]])
    t = c.time.now_ms() + REPLAY_GAP_MS
    c.tick_once(now_ms=t)
    yield dep.breaker_states()
    dep.reset_breakers()
    t += 2 * dep.config["rules"]["stat_interval_ms"] + step
    due = collections.defaultdict(list)  # tick index -> [(ids, rts, admitted ms, inbound)]
    k = 0
    for i in range(rp["ticks"]):
        sick = dep.sick(i * step / 1e3, rp["sick_period_s"], rp["sick_s"])
        out = due.pop(i, [])
        x_ids, x_rt, x_at, x_inb = (np.concatenate([o[j] for o in out]) if out else np.zeros(0, dt)
                                    for j, dt in enumerate((np.int32, np.float32, np.int64, np.int32)))
        if len(x_ids):
            c.submit_completion_block(x_ids, x_rt, inbound=x_inb)
        futs, parts = [], []
        left = int(per_tick[i % len(per_tick)])
        while left > 0:
            b, s, ids, cols, rt = views[pick[k % len(pick)]]
            k += 1
            n = min(left, block)
            left -= n
            futs.append(c.submit_block(ids[:n], **{name: col[:n] for name, col in cols.items()}))
            parts.append((b, s, ids[:n], cols["inbound"][:n], rt[:n]))
        c.tick_once(now_ms=t)
        verdicts = [f.result(timeout=c.entry_timeout_s)[0] for f in futs]
        for (b, s, ids, inb, rt), v in zip(parts, verdicts):
            keep = np.flatnonzero(v == PASS)
            is_sick = sick[dep.pool_rank[b][s + keep]]
            rt = np.where(is_sick, dep.pool_rt_sick[b][s + keep], rt[keep])
            hung = is_sick & (hang.random(len(keep)) < rp["hung_share_sick"])
            rt = np.where(hung, hang.integers(rp["hung_rt_ms"][0], rp["hung_rt_ms"][1] + 1, len(keep)),
                          rt).astype(np.float32)
            at = i + np.maximum(np.ceil(rt / step), 1).astype(np.int64)
            for j in np.unique(at).tolist():
                m = at == j
                due[j].append((ids[keep][m], rt[m], np.full(int(m.sum()), t, np.int64), inb[keep][m]))
        yield (t, np.concatenate([p[2] for p in parts]), np.concatenate(verdicts),
               x_ids, x_rt, x_at, dep.breaker_states(),
               wire.tick_shape_for(c.cfg, sum(len(p[2]) for p in parts), len(x_ids)))
        t += step
