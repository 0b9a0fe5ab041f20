"""Open loop, for a deployment under uniform-rate rules (``pacing_client``):
``open_loop_blocks``' arrivals of acquire blocks, whose items are bursts, and
exits that follow the waits.  A paced call is told its wait with its verdict
(PASS_WAIT), is held that long by its caller, is served, and exits: upstream
creates the ``Entry`` before the chain sleeps, so the response time its exit
records (``completeTime - createTime``) holds the wait.  So a block's
done-callback (non-blocking, as ``submit_block`` demands) keeps the PASS and
PASS_WAIT items, gives each ``wait + service time``, stamps it with ``verdict
time + wait + service time`` and hands it to the sender; the sender's thread
(``open_loop_exit_blocks``', less the health schedule) sends what has come due
on a grid of ``exit_grid_ms`` as one ``submit_completion_block`` a slot.  A
BLOCK_FLOW item sends nothing.

The pool is one stream of bursts (``pacing_client.burst_stream``) and the
blocks are sent in the stream's order, so a burst that a block's end cuts
goes on in the next block.
"""

from __future__ import annotations

import collections
import functools
import threading
from concurrent.futures import wait as wait_all

import numpy as np

from perfbench.generators import (
    BLOCK_FLOW, BLOCK_SYSTEM, PASS, REPLAY_GAP_MS, Hooks, PassCounter, Window, now_ns, sleep_until,
)
from perfbench.generators.open_loop_blocks import block_views, schedule
from perfbench.generators.open_loop_exit_blocks import ExitSender

#: verdict code of an item admitted with a wait, as sentinel_tpu.core.errors numbers it
PASS_WAIT = 6


class HoldSender(ExitSender):
    """``ExitSender`` for calls that are held before they are served: an item
    is handed in with the instant its wait is over beside the instant its
    exit is due, and every slot also records how many calls it still holds."""

    def __init__(self, dep, params: dict, t0_ns: int):
        from sentinel_tpu import obs

        # not ExitSender's: that one reads the deployment's health schedule
        threading.Thread.__init__(self, name="perfbench-exit-sender", daemon=True)
        self._tracer = obs.TRACER
        self.dep, self.t0 = dep, t0_ns
        self.grid_ns = int(params["exit_grid_ms"] * 1e6)
        self.handed = collections.deque()  # (due_ns, ids, rt, inbound, wait_over_ns) of a block's admitted items
        self.sent = 0
        self.sent_before_wait_over = 0
        self.late_ns = []  # per slot: send time less the oldest due time it carried
        self._parts = [np.zeros(0, np.int64), np.zeros(0, np.int32), np.zeros(0, np.float32),
                       np.zeros(0, np.int32), np.zeros(0, np.int64)]
        self._last = False  # set once nothing more will be handed in

    def _slot(self, now: int) -> None:
        got = [self.handed.popleft() for _ in range(len(self.handed))]
        if got:
            self._parts = [np.concatenate([p] + [g[i] for g in got]) for i, p in enumerate(self._parts)]
        due, ids, rt, inb, wait_over = self._parts
        go = due <= now
        n = int(go.sum())
        if not n:
            return
        oldest = int(due[go].min())
        self.dep.client.submit_completion_block(ids[go], rt[go], inbound=inb[go])
        sent_ns = now_ns()
        self.sent += n
        self.sent_before_wait_over += int((wait_over[go] > now).sum())
        self.late_ns.append(sent_ns - oldest)
        self._parts = [p[~go] for p in self._parts]
        if self._tracer.enabled:
            # exit.due: the oldest exit of the slot came due -> the slot is with the client
            self._tracer.record("exit.due", oldest, sent_ns - oldest, 0, {"n": n})
            # pace.hold: the slot's own work, and the calls still held after it
            # (waiting out their wait, or being served)
            self._tracer.record("pace.hold", now, sent_ns - now, 0,
                                {"sent": n, "held": len(self._parts[0])})

    def run(self) -> None:
        nxt = now_ns()
        while True:
            nxt += self.grid_ns
            sleep_until(nxt)
            self._slot(now_ns())
            if self._last and not self.pending():
                return


def burst_lengths(ranks: np.ndarray) -> np.ndarray:
    """Lengths of the runs of equal topics in a sequence of items."""
    edges = np.flatnonzero(np.diff(ranks)) + 1
    return np.diff(np.concatenate([[0], edges, [len(ranks)]]))


def run(dep, params: dict, seed: int, seconds: float, hooks: Hooks) -> Window:
    c = dep.client
    block = params["block_items"]
    if dep.batch % block:
        raise ValueError(f"block_items {block} does not divide the batch {dep.batch}: a block "
                         f"could lie across two ticks")
    due = schedule(params, seed, seconds)
    n = len(due)
    views = block_views(dep.pool, block)
    # the stream's own order, from a seeded place in it
    view_of = (np.arange(n) + int(np.random.default_rng(seed + 1).integers(len(views)))) % len(views)
    sent = np.zeros(n, np.int64)
    done = np.zeros(n, np.int64)
    handed = np.zeros(n, np.int64)  # exits handed to the sender, per block
    futs = [None] * n
    max_wait = dep.config["rules"]["max_queueing_time_ms"]

    t0 = now_ns() + 2_000_000
    sender = HoldSender(dep, params, t0)

    def on_verdicts(k, fut):
        t = now_ns()
        done[k] = t
        if fut.exception() is not None:
            return
        verdicts, waits = fut.result()
        keep = np.flatnonzero((verdicts == PASS) | (verdicts == PASS_WAIT))
        if not len(keep):
            return
        _b, _s, ids, cols, service = views[view_of[k]]
        held = waits[keep].astype(np.int64) * 1_000_000
        rt = (waits[keep] + service[keep]).astype(np.float32)  # the exit's rt holds the wait
        handed[k] = len(keep)
        sender.handed.append((t + held + (service[keep] * 1e6).astype(np.int64), ids[keep], rt,
                              cols["inbound"][keep], t + held))

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
        _b, _s, ids, cols, _service = views[view_of[k]]
        sent[k] = now_ns()
        fut = c.submit_block(ids, **cols)
        futs[k] = fut
        fut.add_done_callback(callbacks[k])
    if not closed:
        sleep_until(t0 + close_rel)
        hooks.closed()
    wait_all(futs, timeout=c.entry_timeout_s)
    sender.finish()  # every admitted call exits, its wait and its service time after its verdict

    counter = PassCounter(dep.pool)
    ok = np.zeros(n, bool)
    n_admitted = np.zeros(n, np.int64)
    wait_hist = np.zeros(max_wait + 2, np.int64)  # PASS_WAIT items by their wait, the window's blocks
    in_window = np.zeros(256, np.int64)  # items by verdict code, the window's blocks
    waits_out_of_range = waits_on_others = 0
    for k, fut in enumerate(futs):
        if not fut.done() or fut.exception() is not None:
            continue
        verdicts, waits = fut.result()
        b, s = views[view_of[k]][:2]
        counter.add(b, s, verdicts)
        ok[k] = not (verdicts == BLOCK_SYSTEM).any()
        paced = verdicts == PASS_WAIT
        n_admitted[k] = int(paced.sum()) + int((verdicts == PASS).sum())
        w = waits[paced]
        waits_out_of_range += int(((w <= 0) | (w > max_wait)).sum())
        waits_on_others += int((waits[~paced] != 0).sum())
        if in_win[k]:
            wait_hist += np.bincount(np.clip(w, 0, max_wait + 1), minlength=max_wait + 2)
            in_window += np.bincount(verdicts.view(np.uint8), minlength=256)
    futs.clear()
    lat_ms = (done - (t0 + due)) / 1e6
    good = in_win & ok & (lat_ms <= c.entry_timeout_s * 1e3)
    vis = ok & (done >= t0 + open_rel) & (done < t0 + close_rel)
    late = np.asarray(sender.late_ns, np.float64) / 1e6
    cum = np.cumsum(wait_hist)
    items = max(int(in_window.sum()), 1)

    def wait_at(q: float) -> float:
        return float(np.searchsorted(cum, q * cum[-1])) if cum[-1] else 0.0

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
            "exits_for_blocked_items": int(np.maximum(handed - n_admitted, 0).sum()),
            "exits_sent_before_the_wait_was_over": sender.sent_before_wait_over,
            "exit_late_ms_mean": float(late.mean()) if len(late) else 0.0,
            "exit_late_ms_p99": float(np.percentile(late, 99)) if len(late) else 0.0,
            "waits_out_of_range": waits_out_of_range,
            "waits_on_items_not_pass_wait": waits_on_others,
            "window_pass_share": float(in_window[PASS]) / items,
            "window_pass_wait_share": float(in_window[PASS_WAIT]) / items,
            "window_flow_blocked_share": float(in_window[BLOCK_FLOW]) / items,
            "window_wait_ms_p50": wait_at(0.5),
            "window_wait_ms_p95": wait_at(0.95),
        },
    )


def replay(dep, params: dict, seed: int):
    """Drive virtual ticks by hand, the tick thread stopped: the stream of
    bursts from a seeded place, a slice a tick, through ``tick_once(now_ms=t)``
    at stated times, ``replay.step_ms`` apart, from every bucket idle
    (``dep.reset_buckets()``, after one empty tick has taken in what the
    window left queued).

    ``replay.tick_items`` is a cycle of runs ``[ticks, items a tick]`` sized
    so that every compiled shape of the tick is replayed, and so that some
    ticks hold 1 to 64 waiting rows (the wire's sidecar carries their waits)
    and some hold more (the whole wait column is read).  ``replay.stretches``
    is a list of ``[ticks, from_ms]``: the stretch's ticks start at engine
    time ``from_ms`` or, where that is null or already past, go on from the
    tick before; one of them starts past 2^24 ms, where a float32 holds no
    odd millisecond.  **The replay's own, and no part of the timed window's
    traffic**: on the ticks of a cycle that ``replay.edge_ticks`` names, a
    burst of ``edge_items_fast`` items on the fastest topic (cost 1 ms: waits
    of 1, 2, ... 500 admitted, 501 refused) and one of ``edge_items_slow`` on
    the slowest (cost 100 ms: 0, 100, ... 500 admitted, 600 refused) stand in
    front of the tick's slice, because a burst of at most 64 items reaches a
    1 ms rule's 500th millisecond only through eight bursts that overlap.  No
    exits are replayed: a leaky bucket reads no completion.

    Yields, first, the program's count of whole-column reads so far
    (``sentinel_wire_wait_overflow_ticks_total``), then per tick ``(now_ms,
    rank of every item, verdicts, waits, latestPassedTime per rank after the
    tick, (acquire rows, completion rows) of the shape the tick ran at, that
    count after the tick)``."""
    from sentinel_tpu.ops import wire

    c = dep.client
    rp = params["replay"]
    step, block = rp["step_ms"], params["block_items"]
    stream = np.concatenate(dep.pool_rank)
    at = int(np.random.default_rng(seed + 2).integers(len(stream)))
    id_of = dep.ids.astype(np.int32)
    onode, oid, ph = (dep.pool[0][j] for j in (1, 2, 3))
    inbound = np.zeros(dep.batch, np.int32)
    sizes = np.repeat([n for _t, n in rp["tick_items"]], [t for t, _n in rp["tick_items"]])
    fast, slow = int(np.argmin(dep.cost_ms)), int(np.argmax(dep.cost_ms))
    edge = np.concatenate([np.full(rp["edge_items_fast"], fast, np.int32),
                           np.full(rp["edge_items_slow"], slow, np.int32)])
    t = c.time.now_ms() + REPLAY_GAP_MS
    c.tick_once(now_ms=t)
    dep.reset_buckets()
    yield dep.wait_overflow_ticks()
    i = 0
    for ticks, from_ms in rp["stretches"]:
        t = max(t + step, from_ms or 0)
        for _ in range(ticks):
            j = i % len(sizes)
            ranks = stream[(at + np.arange(sizes[j])) % len(stream)]
            at += int(sizes[j])
            if j in rp["edge_ticks"]:
                ranks = np.concatenate([edge, ranks])
            i += 1
            futs = []
            for s in range(0, len(ranks), block):
                part = id_of[ranks[s:s + block]]
                m = len(part)
                futs.append(c.submit_block(part, origin_node=onode[:m], origin_id=oid[:m],
                                           param_hash=ph[:m], inbound=inbound[:m]))
            c.tick_once(now_ms=t)
            got = [f.result(timeout=c.entry_timeout_s) for f in futs]
            yield (t, ranks, np.concatenate([g[0] for g in got]), np.concatenate([g[1] for g in got]),
                   dep.latest_passed(), wire.tick_shape_for(c.cfg, len(ranks), 0),
                   dep.wait_overflow_ticks())
            t += step
