"""Cluster token decision service.

The reference's token server answers requestToken(flowId, count, priority)
with a verdict from a per-rule ClusterMetric sliding window
(DefaultTokenService.java:34-44 → ClusterFlowChecker.acquireClusterToken:55-88).

TPU inversion: each cluster flowId is interned as a resource
(``$cluster/flow/<id>``) on a dedicated decision ``SentinelClient``, so token
verdicts ride the same batched device engine as local rules — concurrent
requests from many connections coalesce into one micro-batch tick.  The
global threshold
``count × (1 if thresholdType==GLOBAL else connectedCount) × exceedCount``
(ClusterFlowChecker.java:38,68) is recomputed and pushed to the engine
whenever rules or the connection census change.

Host-side pieces (naturally request-scoped, not tensor-shaped):
  * GlobalRequestLimiter — per-namespace QPS guard
    (GlobalRequestLimiter.java:28, RequestLimiter.java:29-39)
  * ConcurrentTokenManager — cluster-wide concurrency tokens with TTL expiry
    (ConcurrentClusterFlowChecker.java:34-81, CurrentConcurrencyManager,
    TokenCacheNodeManager, RegularExpireStrategy)
"""

from __future__ import annotations

import itertools
import threading
import time as _time
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from sentinel_tpu.chaos import failpoints as FP
from sentinel_tpu.cluster import constants as C
from sentinel_tpu.cluster.rules import (
    ClusterFlowRuleManager,
    ClusterParamFlowRuleManager,
    ClusterServerConfigManager,
    flow_resource,
    param_resource,
)
from sentinel_tpu.core import errors as ERR
from sentinel_tpu.core import rules as R
from sentinel_tpu.obs import profile as PROF
from sentinel_tpu.obs import trace as OT
from sentinel_tpu.obs.registry import REGISTRY as _OBS
from sentinel_tpu.utils.host_window import HostWindow

_H_DECISION = _OBS.histogram(
    "sentinel_token_decision_ms",
    "engine-backed token decision latency (request to verdict)",
)
_C_DECISIONS = _OBS.counter(
    "sentinel_token_decisions_total", "token verdicts served by this process"
)
_C_SHED = _OBS.counter(
    "sentinel_token_shed_total",
    "token requests shed before the engine (namespace guard or backpressure)",
)
_C_BATCHED = _OBS.counter(
    "sentinel_cluster_batched_decisions_total",
    "token entries decided by the device column kernel (ops/token_col.py)",
)

#: chaos failpoint on the decision path: a raise here exercises every
#: caller's STATUS_FAIL conversion (request_token's catch, the TCP
#: server's _flow_and_reply/_process catches) — degrade, never PASS
_FP_DECIDE = FP.register(
    "cluster.token.decide", "token service decision entry", FP.HIT_ACTIONS
)


#: engine stages the cluster token decision path exercises: flow checks
#: (with occupy-ahead for prioritized SHOULD_WAIT grants) and hot-param
#: token checks.  The decision client's resources are interned flowIds —
#: no ctx/origin node fan-out, no circuit breakers, no authority/system
#: rules ever bind to them, so a dedicated decision engine compiled with
#: exactly this set serves token verdicts with the minimal tick.  The
#: jaxpr analyzer (sentinel_tpu/analysis/jaxpr) traces `ops.engine.tick`
#: under this feature set as its `tick/cluster-token` entry point, so
#: CI pins the compiled token-decision program alongside the local ones.
DECISION_FEATURES = frozenset({"flow", "occupy", "param"})


@dataclass
class TokenResult:
    status: int
    remaining: int = 0
    wait_ms: int = 0
    token_id: int = 0
    # deny provenance (protocol v3 _T_PROV, obs/explain.py): populated on
    # STATUS_BLOCKED by services that know WHY — verdict kind, blamed rule
    # (flow id), observed usage at decision time, and the limit it hit.
    # None on OK results, on pre-v3 peers, and on transport failures, so
    # every consumer must treat provenance as best-effort.
    prov_kind: Optional[int] = None
    prov_rule: Optional[int] = None
    prov_observed: Optional[float] = None
    prov_limit: Optional[float] = None

    @property
    def ok(self) -> bool:
        return self.status == C.STATUS_OK

    @property
    def blocked(self) -> bool:
        return self.status == C.STATUS_BLOCKED


class TokenService:
    """Abstract token service (cluster/TokenService.java:26-62)."""

    #: lease validity window granted to holders; implementations with a
    #: configured TTL (``DefaultTokenService``) shadow this per instance
    lease_ttl_ms: int = C.DEFAULT_LEASE_TTL_MS

    def request_token(self, flow_id: int, count: int = 1, prioritized: bool = False) -> TokenResult:
        raise NotImplementedError

    def request_token_batch(self, flow_id: int, units: int) -> TokenResult:
        """Partial-grant acquire: ask for ``units`` single tokens, receive
        granted k in ``remaining`` (0..units).  Default maps onto the
        all-or-nothing request_token for foreign implementations."""
        r = self.request_token(flow_id, units, False)
        if r.status == C.STATUS_OK:
            return TokenResult(C.STATUS_OK, remaining=units, wait_ms=r.wait_ms)
        if r.status == C.STATUS_BLOCKED:
            return TokenResult(C.STATUS_BLOCKED, remaining=0)
        return r

    def request_param_token(self, flow_id: int, count: int, params: List[Any]) -> TokenResult:
        raise NotImplementedError

    def request_concurrent_token(self, flow_id: int, count: int = 1) -> TokenResult:
        raise NotImplementedError

    def release_concurrent_token(self, token_id: int) -> TokenResult:
        raise NotImplementedError

    def request_lease(self, flow_id: int, units: int) -> TokenResult:
        """Bounded-slack budget lease (cluster/shard.py): grant up to
        ``units`` tokens spendable by the holder for one validity window
        (``remaining`` = granted k, ``wait_ms`` = window ms).  The grant
        rides the partial-grant batch acquire — debited from the SAME
        global budget as ordinary tokens, which is what makes the
        holder's offline spending conserve it — so any TokenService can
        serve as a lease source.  Units clamp to ``MAX_LEASE_UNITS``
        here, for EVERY implementation: a hostile/miscalibrated request
        must not stall the decision backend."""
        r = self.request_token_batch(flow_id, min(units, C.MAX_LEASE_UNITS))
        if r.status == C.STATUS_OK:
            return TokenResult(
                C.STATUS_OK, remaining=r.remaining, wait_ms=self.lease_ttl_ms
            )
        return r


class GlobalRequestLimiter:
    """Per-namespace request-QPS guard in front of the decision engine."""

    def __init__(self, config: ClusterServerConfigManager):
        self._config = config
        self._windows: Dict[str, HostWindow] = {}
        self._lock = threading.Lock()

    def _window(self, namespace: str, cfg) -> HostWindow:
        # a pushed config is unvalidated: round interval up to a multiple of
        # sample_count instead of letting HostWindow's divisibility assert
        # fire on the request hot path
        sample_count = max(int(cfg.sample_count), 1)
        interval_ms = max(int(cfg.interval_ms), sample_count)
        interval_ms = ((interval_ms + sample_count - 1) // sample_count) * sample_count
        w = self._windows.get(namespace)
        if w is None or (w.sample_count, w.interval_ms) != (sample_count, interval_ms):
            # (re)build to the configured shape; a config push that reshapes
            # the window restarts its accounting, like the reference's
            # per-namespace RequestLimiter re-creation
            with self._lock:
                w = self._windows.get(namespace)
                if w is None or (w.sample_count, w.interval_ms) != (
                    sample_count,
                    interval_ms,
                ):
                    w = HostWindow(sample_count, interval_ms)
                    self._windows[namespace] = w
        return w

    def try_pass(self, namespace: str, now_ms: int) -> bool:
        cfg = self._config.flow_config(namespace)
        return self._window(namespace, cfg).try_pass(now_ms, cfg.max_allowed_qps)

    def current_qps(self, namespace: str, now_ms: int) -> float:
        w = self._windows.get(namespace)
        return w.qps(now_ms) if w else 0.0


class ConcurrentTokenManager:
    """Cluster-wide concurrency tokens with TTL expiry."""

    def __init__(self, ttl_ms: int = 5000):
        self.ttl_ms = ttl_ms
        self._lock = threading.Lock()
        self._current: Dict[int, int] = {}  # flowId -> concurrency in flight
        self._tokens: Dict[int, tuple] = {}  # tokenId -> (flowId, count, deadline)
        self._ids = itertools.count(1)

    def acquire(self, flow_id: int, count: int, limit: float, now_ms: int) -> Optional[int]:
        with self._lock:
            cur = self._current.get(flow_id, 0)
            if cur + count > limit:
                return None
            self._current[flow_id] = cur + count
            tid = next(self._ids)
            self._tokens[tid] = (flow_id, count, now_ms + self.ttl_ms)
            return tid

    def release(self, token_id: int) -> bool:
        with self._lock:
            node = self._tokens.pop(token_id, None)
            if node is None:
                return False
            fid, count, _ = node
            self._current[fid] = max(self._current.get(fid, 0) - count, 0)
            return True

    def current(self, flow_id: int) -> int:
        return self._current.get(flow_id, 0)

    def expire(self, now_ms: int) -> int:
        """Drop expired tokens (RegularExpireStrategy sweep). Returns count."""
        with self._lock:
            dead = [tid for tid, (_, _, dl) in self._tokens.items() if dl <= now_ms]
            for tid in dead:
                fid, count, _ = self._tokens.pop(tid)
                self._current[fid] = max(self._current.get(fid, 0) - count, 0)
            return len(dead)


class TokenColumnBatcher:
    """Coalesces token decisions into one jitted device column call.

    Every decision entry path — the blocking API, the thread-free TCP
    FLOW path, and whole protocol-v2 BATCH frames from many connections
    — submits ``(flow_id, units, partial)`` entries here; a worker
    thread drains the queue and answers a whole chunk with ONE
    ``ops/token_col.decide_batch`` call.  All paths therefore debit the
    SAME device-resident budget ledger (the per-slot sliding window IS
    the ledger), so coalescing can never double-admit against a separate
    engine-side account.

    Entries are presorted by slot host-side (native batch_sort3, stable)
    and rebased prefix sums inside the kernel make one coalesced batch
    admit exactly what sequential requests would have.

    Slot assignment is stable across rule pushes: retained flows keep
    their row (the standing ledger survives a reprojection, matching the
    engine tier where windows persist across rule reloads); dropped
    flows release their row with its ledger zeroed before reuse.
    """

    #: entries per device call — one compiled shape per slot capacity;
    #: bigger drains chunk sequentially (same-slot carry is exact: the
    #: window is updated between chunks)
    CAPACITY = 256

    def __init__(self, service: "DefaultTokenService", device=None):
        # lazy heavyweight imports: the cluster codec/client modules must
        # stay importable without dragging jax in
        from sentinel_tpu.native import ring as NR
        from sentinel_tpu.obs import timeline as TLM
        from sentinel_tpu.ops import token_col as TC

        self._TC = TC
        self._NR = NR
        self._TLM = TLM
        self.svc = service
        # per-window cumulative [TL_COLS] rows fed to the decision
        # client's TimelineRecorder: the col path answers off-engine, so
        # it must land the same per-second `$cluster/flow/<id>` rows the
        # engine's device top-K matrix used to produce (worker-thread
        # only — no lock needed beyond the recorder's own)
        self._tl_wid = -1
        self._tl_acc: Dict[int, np.ndarray] = {}
        self._tl_rids: Dict[int, int] = {}
        self._q_lock = threading.Lock()
        self._cv = threading.Condition(self._q_lock)
        self._pending: List[tuple] = []  # (flow_id, units, partial, forced, submit ns, Future)
        self._s_lock = threading.Lock()  # slots + device state
        self._slots: Dict[int, int] = {}
        self._free: List[int] = []
        self._next_slot = 0
        # flow id -> projected global threshold, for deny provenance
        # (replaced wholesale in project(); dict swap is GIL-atomic so
        # the worker thread reads it lock-free)
        self._limits_by_fid: Dict[int, float] = {}
        self._cap = 8
        #: the device this column's ledger lives on (a shard's own chip);
        #: None = wherever JAX puts an uncommitted array.  Only the state
        #: is ever committed: a call's inputs are host arrays and follow it
        self.device = device
        #: entries this column has decided (the process-wide
        #: sentinel_cluster_batched_decisions_total, for this column alone)
        self.decided = 0
        self._state = self._put(TC.init_state(self._cap))
        # memory ledger (obs/profile.py): token-column device state under
        # a per-batcher owner so close() releases exactly this claim
        self._ledger_name = f"tokencol:{id(self):x}"
        with PROF.ledger_owner(self._ledger_name):
            PROF.LEDGER.track("tokens", "token_col.state", self._state)
        self._decide = TC.jitted_decide()
        self._closed = False
        self._worker = threading.Thread(
            target=self._run, name="sentinel-token-col", daemon=True
        )
        self._worker.start()

    def _put(self, x):
        """``x`` (an array or a pytree of them) as device arrays, committed
        to this column's device where it has one."""
        import jax

        return jax.device_put(x, self.device)

    def pending_entries(self) -> int:
        return len(self._pending)

    def submit(
        self, flow_id: int, units: int, partial: bool, forced: bool = False
    ) -> "Future":
        """Enqueue one decision entry; resolves to ``(granted, observed,
        limit)`` — granted units plus the window usage and threshold the
        entry was decided against (deny provenance, obs/explain.py).  A
        flow whose rule dropped between guard and decide grants 0 — fail
        closed, like every ambiguity on this path.  ``forced`` charges
        unconditionally (the occupy-ahead emulation)."""
        f: Future = Future()
        with self._cv:
            if self._closed:
                f.set_exception(RuntimeError("token column batcher closed"))
                return f
            # OT.t0(): the submit instant for token.col.queue, 0 when off
            self._pending.append((flow_id, units, partial, forced, OT.t0(), f))
            self._cv.notify()
        return f

    def ms_to_next_bucket(self, now_ms: int) -> int:
        return self._TC.ms_to_next_bucket(int(now_ms))

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        PROF.LEDGER.drop_owner(self._ledger_name)

    def warm(self) -> None:
        """Pay the XLA compile for the current capacity off the request
        path — a cold first decision would outlive entry timeouts and
        read as a dead shard (the ShardFleet warm lesson)."""
        with self._s_lock:
            self._warm_locked()

    def _warm_locked(self) -> None:
        TC = self._TC
        now = np.int32(int(self.svc.client.time.now_ms()))
        slots = np.zeros(self.CAPACITY, np.int32)
        units = np.zeros(self.CAPACITY, np.int32)
        heads = np.arange(self.CAPACITY, dtype=np.int32)
        partial = np.zeros(self.CAPACITY, bool)
        forced = np.zeros(self.CAPACITY, bool)
        g, _obs, self._state = self._decide(
            self._state, now, slots, units, heads, partial, forced
        )
        np.asarray(g)  # block until the executable is built

    def project(self, thresholds: Dict[int, float]) -> None:
        """Rebuild slot map + per-slot limits from a rule/census push.
        Retained flows keep their slot AND their standing window ledger;
        recycled and grown rows start zeroed."""
        TC = self._TC
        W = TC.W
        with self._s_lock:
            zero_rows: List[int] = []
            for fid in [f for f in self._slots if f not in thresholds]:
                s = self._slots.pop(fid)
                self._free.append(s)
            for fid in thresholds:
                if fid not in self._slots:
                    if self._free:
                        s = self._free.pop()
                        zero_rows.append(s)  # no inherited ledger
                    else:
                        s = self._next_slot
                        self._next_slot += 1
                    self._slots[fid] = s
            cap = self._cap
            while cap < self._next_slot:
                cap *= 2
            if zero_rows or cap != self._cap:
                counts = np.zeros(
                    (cap,) + tuple(self._state.win.counts.shape[1:]), np.int32
                )
                rt_sum = np.zeros((cap,) + tuple(self._state.win.rt_sum.shape[1:]), np.float32)
                rt_min = np.full(
                    (cap,) + tuple(self._state.win.rt_min.shape[1:]),
                    W.RT_MIN_INIT,
                    np.float32,
                )
                run = np.zeros((cap, W.NUM_EVENTS), np.int32)
                run_rt = np.zeros((cap,), np.float32)
                run_rt_min = np.full((cap,), W.RT_MIN_INIT, np.float32)
                old = self._cap
                counts[:old] = np.asarray(self._state.win.counts)
                rt_sum[:old] = np.asarray(self._state.win.rt_sum)
                rt_min[:old] = np.asarray(self._state.win.rt_min)
                run[:old] = np.asarray(self._state.win.run)
                run_rt[:old] = np.asarray(self._state.win.run_rt)
                run_rt_min[:old] = np.asarray(self._state.win.run_rt_min)
                if zero_rows:
                    counts[zero_rows] = 0
                    rt_sum[zero_rows] = 0.0
                    rt_min[zero_rows] = W.RT_MIN_INIT
                    run[zero_rows] = 0
                    run_rt[zero_rows] = 0.0
                    run_rt_min[zero_rows] = W.RT_MIN_INIT
                win = W.WindowState(
                    counts=self._put(counts),
                    rt_sum=self._put(rt_sum),
                    rt_min=self._put(rt_min),
                    epochs=self._state.win.epochs,
                    run=self._put(run),
                    run_rt=self._put(run_rt),
                    run_rt_min=self._put(run_rt_min),
                    rot_wid=self._state.win.rot_wid,
                )
                grew = cap != self._cap
                self._state = TC.TokenColState(win=win, limits=self._state.limits)
                self._cap = cap
                with PROF.ledger_owner(self._ledger_name):
                    PROF.LEDGER.track("tokens", "token_col.state", self._state)
            else:
                grew = False
            limits = np.zeros(cap, np.float32)
            for fid, thr in thresholds.items():
                limits[self._slots[fid]] = thr
            self._limits_by_fid = dict(thresholds)
            self._state = TC.set_limits(self._state, self._put(limits))
            if grew:
                # rule pushes pay the new shape's compile, requests don't
                self._warm_locked()

    # -- worker -------------------------------------------------------------

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._pending and not self._closed:
                    # bounded: the predicate loop makes the timeout free
                    # (spurious wakeups just re-check), and a notify lost
                    # to a future refactor degrades to a 1 s idle poll
                    # instead of wedging this thread and close() forever
                    self._cv.wait(timeout=1.0)
                if not self._pending and self._closed:
                    return
                batch, self._pending = self._pending, []
            try:
                now = int(self.svc.client.time.now_ms())
                with self._s_lock:
                    for i in range(0, len(batch), self.CAPACITY):
                        self._decide_chunk(batch[i : i + self.CAPACITY], now)
            except Exception as e:  # stlint: disable=fail-open — a failed future is STATUS_FAIL at every caller: degrade, never PASS
                for *_, f in batch:
                    if not f.done():
                        f.set_exception(e)

    def _decide_chunk(self, chunk: List[tuple], now: int) -> None:
        n = len(chunk)
        # token.col: this chunk taken -> its granted units on the host;
        # token.col.queue: each entry's submit() -> taken.  Off, one flag check
        _t = OT.t0()
        if _t:
            for *_, queued, _f in chunk:
                if queued:
                    OT.stage_ns("token.col.queue", queued, _t - queued)
        raw_slots = np.zeros(n, np.int32)
        raw_units = np.zeros(n, np.int32)
        raw_partial = np.zeros(n, bool)
        raw_forced = np.zeros(n, bool)
        for i, (fid, u, p, fo, _q, _f) in enumerate(chunk):
            s = self._slots.get(fid, -1)
            if s >= 0 and u > 0:
                raw_slots[i] = s
                raw_units[i] = u  # unknown/dropped flows keep units 0 → granted 0
            raw_partial[i] = bool(p)
            raw_forced[i] = bool(fo)
        z = np.zeros(n, np.int32)
        order, _ = self._NR.batch_sort3(raw_slots, z, z, want_inv=False)
        s_sorted = raw_slots[order]
        u_sorted = raw_units[order]
        slots = np.zeros(self.CAPACITY, np.int32)
        units = np.zeros(self.CAPACITY, np.int32)
        partial = np.zeros(self.CAPACITY, bool)
        forced = np.zeros(self.CAPACITY, bool)
        heads = np.arange(self.CAPACITY, dtype=np.int32)
        slots[:n], units[:n] = s_sorted, u_sorted
        partial[:n], forced[:n] = raw_partial[order], raw_forced[order]
        if n:
            newseg = np.ones(n, bool)
            newseg[1:] = s_sorted[1:] != s_sorted[:-1]
            heads[:n] = np.maximum.accumulate(
                np.where(newseg, np.arange(n), 0)
            ).astype(np.int32)
        _t_call = OT.now_ns() if _t else 0
        g, obs, self._state = self._decide(
            self._state, np.int32(now), slots, units, heads, partial, forced
        )
        _t_read = OT.now_ns() if _t else 0
        g, obs = np.asarray(g), np.asarray(obs)  # the one blocking read-back
        if _t:
            # the jit call, then the read-back, then the span's end with
            # nothing between: each is an interval a reader can place
            _t_end = OT.now_ns()
            OT.stage_ns(
                "token.col", _t, _t_end - _t,
                attrs={"n": n, "shard": self.svc.shard,
                       "call_ns": _t_read - _t_call, "read_ns": _t_end - _t_read},
            )
        granted = np.empty(n, np.int32)
        granted[order] = g[:n]
        observed = np.empty(n, np.float32)
        observed[order] = obs[:n]
        _C_BATCHED.inc(n)
        self.decided += n  # the worker thread alone writes it
        self._note_timeline(chunk, granted, now)
        lims = self._limits_by_fid
        for i, (fid, _u, _p, _fo, _q, f) in enumerate(chunk):
            if not f.done():
                f.set_result(
                    (int(granted[i]), float(observed[i]), lims.get(fid, 0.0))
                )

    def _note_timeline(self, chunk: List[tuple], granted: np.ndarray, now: int) -> None:
        """Land this chunk's verdicts in the decision client's timeline.

        The recorder keeps the LATEST cumulative row per (window,
        resource), so this accumulates per-window pass/block counts and
        re-emits the whole current window each call — byte-for-byte the
        contract of the engine's device top-K matrix, minus the stages
        (rt/concurrency) a token verdict doesn't have."""
        TLM = self._TLM
        tl = self.svc.client.timeline
        if tl is None:
            return
        wid = int(now) // tl.window_ms
        if wid != self._tl_wid:
            # the recorder already holds the previous window's final
            # cumulative rows; only the open window needs an accumulator
            self._tl_wid = wid
            self._tl_acc.clear()
        for i, (fid, u, p, fo, _q, _f) in enumerate(chunk):
            rid = self._tl_rids.get(fid)
            if rid is None:
                rid = self.svc.client.registry.resource_id(flow_resource(fid))
                if rid is None:
                    continue  # registry exhausted: stats degrade, verdicts don't
                self._tl_rids[fid] = rid
            row = self._tl_acc.get(rid)
            if row is None:
                row = np.zeros(8, np.float32)  # ops/engine TL_COLS layout
                row[TLM.TL_RID] = rid
                row[TLM.TL_RT_MIN] = TLM._RT_MIN_INIT
                self._tl_acc[rid] = row
            g = int(granted[i])
            ok = fo or g >= u or (p and g > 0)
            row[TLM.TL_PASS if ok else TLM.TL_BLOCK] += 1.0
        if self._tl_acc:
            tl.note_tick(
                np.stack(list(self._tl_acc.values())),
                now,
                int(self.svc.client.time.wall_ms(now)) - int(now),
            )


class DefaultTokenService(TokenService):
    """Engine-backed token service.

    ``decision_client`` is a dedicated SentinelClient whose resources are the
    cluster flowIds.  ``connected_count_fn(namespace) -> int`` feeds the
    AVG_LOCAL threshold scaling; the server wires it to its ConnectionManager
    (ConnectionGroup.getConnectedCount), standalone/embedded default is 1.

    Prioritized requests that exceed the current bucket borrow from the next
    one (engine occupy-ahead, DefaultController.tryOccupyNext) and surface as
    STATUS_SHOULD_WAIT with the wait until that bucket starts — the client
    sleeps and enters, matching TokenResultStatus.SHOULD_WAIT semantics.

    ``device`` is the chip the token column's ledger lives on (a shard's
    own; ``None`` = JAX's default).  The decision client is not moved.
    ``shard`` is the ring member this service answers for, where it is one
    of a fleet's (``ShardFleet``): it labels the column's ``token.col`` spans.
    """

    def __init__(
        self,
        decision_client,
        config: Optional[ClusterServerConfigManager] = None,
        connected_count_fn: Optional[Callable[[str], int]] = None,
        concurrent_ttl_ms: int = 5000,
        lease_ttl_ms: int = C.DEFAULT_LEASE_TTL_MS,
        use_token_column: bool = True,
        device=None,
        shard: str = "",
    ):
        self.client = decision_client
        self.shard = shard
        self.lease_ttl_ms = lease_ttl_ms
        self.config = config or ClusterServerConfigManager()
        self.connected_count_fn = connected_count_fn or (lambda ns: 1)
        # device column batcher first: _reproject (fired by every rule
        # push below) projects thresholds into it
        self.col = TokenColumnBatcher(self, device) if use_token_column else None
        self.flow_rules = ClusterFlowRuleManager(on_change=self._reproject)
        self.param_rules = ClusterParamFlowRuleManager(on_change=self._reproject)
        self.limiter = GlobalRequestLimiter(self.config)
        self.concurrent = ConcurrentTokenManager(ttl_ms=concurrent_ttl_ms)
        self.config.add_listener(self._reproject)
        self._lock = threading.Lock()
        if self.col is not None:
            self.col.warm()

    def warm(self) -> None:
        """Compile the device decision path off the request clock (a cold
        first decision outlives entry timeouts and reads as a dead shard)."""
        if self.col is not None:
            self.col.warm()

    def close(self) -> None:
        if self.col is not None:
            self.col.close()

    # -- projection onto the engine ----------------------------------------

    def _global_threshold(self, rule: R.FlowRule, namespace: str) -> float:
        cfg = self.config.flow_config(namespace)
        n = (
            1
            if rule.cluster_threshold_type == C.FLOW_THRESHOLD_GLOBAL
            else max(self.connected_count_fn(namespace), 1)
        )
        return rule.count * n * cfg.exceed_count

    def _reproject(self) -> None:
        """Rebuild the decision client's engine rules from cluster rules."""
        with self._lock:
            flow = []
            thresholds: Dict[int, float] = {}
            for fid in self.flow_rules.all_ids():
                rule = self.flow_rules.get_by_id(fid)
                if rule is None:
                    continue  # unloaded between snapshot and lookup
                ns = self.flow_rules.namespace_of(fid) or C.DEFAULT_NAMESPACE
                thr = self._global_threshold(rule, ns)
                thresholds[fid] = thr
                flow.append(
                    R.FlowRule(
                        resource=flow_resource(fid),
                        count=thr,
                        grade=R.GRADE_QPS,
                    )
                )
            param = []
            for fid in self.param_rules.all_ids():
                rule = self.param_rules.get_by_id(fid)
                if rule is None:
                    continue
                param.append(
                    R.ParamFlowRule(
                        resource=param_resource(fid),
                        count=rule.count,
                        grade=rule.grade,
                        param_idx=0,  # client sends extracted values
                        duration_in_sec=rule.duration_in_sec,
                        param_flow_item_list=rule.param_flow_item_list,
                    )
                )
            self.client.flow_rules.load(flow)
            self.client.param_flow_rules.load(param)
            if self.col is not None:
                self.col.project(thresholds)

    def refresh_connected_count(self) -> None:
        """Call when the connection census changes.  Only AVG_LOCAL rules
        scale with the census — with purely GLOBAL rules this is a no-op,
        so a churning client fleet doesn't trigger recompiles."""
        has_avg_local = any(
            r is not None and r.cluster_threshold_type != C.FLOW_THRESHOLD_GLOBAL
            for r in (
                self.flow_rules.get_by_id(fid) for fid in self.flow_rules.all_ids()
            )
        )
        if has_avg_local:
            self._reproject()

    # -- TokenService --------------------------------------------------------

    def request_token(self, flow_id: int, count: int = 1, prioritized: bool = False) -> TokenResult:
        """Blocking token grant — delegates to the async path so the guards
        and verdict mapping live in exactly one place."""
        try:
            return self.request_token_async(flow_id, count, prioritized).result(
                timeout=self.client.entry_timeout_s
            )
        except Exception:  # stlint: disable=fail-open — STATUS_FAIL makes the caller degrade to local enforcement, never PASS
            return TokenResult(C.STATUS_FAIL)

    def request_token_async(self, flow_id: int, count: int = 1, prioritized: bool = False):
        """Non-blocking request_token: returns a concurrent Future of
        TokenResult (or a completed result for no-rule / namespace-guard
        outcomes).  Lets the TCP server keep thousands of token requests
        in flight with no thread per request — they coalesce into the
        decision engine's micro-batches (the TPU-native shape)."""
        from concurrent.futures import Future as _F

        FP.hit(_FP_DECIDE)
        done = _F()
        rule = self.flow_rules.get_by_id(flow_id)
        if rule is None:
            done.set_result(TokenResult(C.STATUS_NO_RULE))
            return done
        ns = self.flow_rules.namespace_of(flow_id) or C.DEFAULT_NAMESPACE
        if not self.limiter.try_pass(ns, self.client.time.now_ms()):
            _C_SHED.inc()
            done.set_result(TokenResult(C.STATUS_TOO_MANY_REQUEST))
            return done
        if self.col is not None:
            if self.col.pending_entries() > 4 * TokenColumnBatcher.CAPACITY:
                _C_SHED.inc()
                done.set_result(TokenResult(C.STATUS_TOO_MANY_REQUEST))
                return done
            if count <= 0:  # zero-unit ask: nothing to debit
                _C_DECISIONS.inc()
                done.set_result(TokenResult(C.STATUS_OK))
                return done
            _span = OT.TRACER.begin("token.decision", flow_id=flow_id)
            cf = self.col.submit(flow_id, count, partial=False)

            def _chain_col(fut):
                _C_DECISIONS.inc()
                if _span is not None:
                    OT.stage_ns(
                        "token.decision",
                        _span.t0_ns,
                        OT.now_ns() - _span.t0_ns,
                        _H_DECISION,
                        trace=_span.trace,
                        attrs=_span.attrs,
                    )
                try:
                    granted, observed, limit = fut.result()
                except Exception:  # stlint: disable=fail-open — STATUS_FAIL makes the caller degrade to local enforcement, never PASS
                    done.set_result(TokenResult(C.STATUS_FAIL))
                    return
                if granted >= count:
                    done.set_result(TokenResult(C.STATUS_OK))
                    return
                if not prioritized:
                    done.set_result(
                        TokenResult(
                            C.STATUS_BLOCKED,
                            prov_kind=ERR.BLOCK_FLOW,
                            prov_rule=flow_id,
                            prov_observed=observed,
                            prov_limit=limit,
                        )
                    )
                    return
                # occupy-ahead emulation: charge the ask unconditionally
                # (debits the CURRENT bucket — one earlier than the
                # engine's tryOccupyNext, the conservative direction) and
                # tell the caller to sleep into the next bucket
                f2 = self.col.submit(flow_id, count, partial=False, forced=True)

                def _chain_occ(fut2):
                    try:
                        fut2.result()
                    except Exception:  # stlint: disable=fail-open — STATUS_FAIL makes the caller degrade to local enforcement, never PASS
                        done.set_result(TokenResult(C.STATUS_FAIL))
                        return
                    wait = self.col.ms_to_next_bucket(
                        int(self.client.time.now_ms())
                    )
                    done.set_result(
                        TokenResult(C.STATUS_SHOULD_WAIT, wait_ms=wait)
                    )

                f2.add_done_callback(_chain_occ)

            cf.add_done_callback(_chain_col)
            return done
        # backpressure: with the thread-free TCP path nothing else bounds
        # in-flight requests, so shed load once the acquire queue exceeds a
        # few engine batches (the reference's namespace guard plays this
        # role only when configured tightly)
        if self.client.pending_acquires() > 4 * self.client.cfg.batch_size:
            _C_SHED.inc()
            done.set_result(TokenResult(C.STATUS_TOO_MANY_REQUEST))
            return done
        f = self.client.submit_acquire(
            flow_resource(flow_id), count=count, prioritized=prioritized
        )
        if f is None:
            _C_DECISIONS.inc()  # fast-path verdict is still a served decision
            done.set_result(TokenResult(C.STATUS_OK))
            return done
        # cross-thread span: begun here (adopting the wire trace context
        # the TCP server installed, if any), ended on the resolver/tick
        # thread that fires the engine future — the handle carries the
        # trace id and the caller's span id (attrs["parent"]) across
        _span = OT.TRACER.begin("token.decision", flow_id=flow_id)

        def _chain(fut):
            _C_DECISIONS.inc()
            if _span is not None:
                OT.stage_ns(
                    "token.decision",
                    _span.t0_ns,
                    OT.now_ns() - _span.t0_ns,
                    _H_DECISION,
                    trace=_span.trace,
                    attrs=_span.attrs,
                )
            try:
                verdict, wait_ms = fut.result()
            except Exception:  # stlint: disable=fail-open — STATUS_FAIL makes the caller degrade to local enforcement, never PASS
                done.set_result(TokenResult(C.STATUS_FAIL))
                return
            if verdict == ERR.PASS:
                done.set_result(TokenResult(C.STATUS_OK))
            elif verdict == ERR.PASS_WAIT:
                done.set_result(TokenResult(C.STATUS_SHOULD_WAIT, wait_ms=wait_ms))
            else:
                # engine path: the verdict code names the kind; observed/
                # limit stay unknown (the tick already consumed them)
                done.set_result(
                    TokenResult(
                        C.STATUS_BLOCKED,
                        prov_kind=int(verdict),
                        prov_rule=flow_id,
                    )
                )

        f.add_done_callback(_chain)
        return done

    def request_token_batch(self, flow_id: int, units: int) -> TokenResult:
        """Partial grant: `units` unit-acquires coalesce into one engine
        micro-batch; granted = how many passed (within-tick prefix-sum
        admission makes this bit-exact with sequential acquisition)."""
        FP.hit(_FP_DECIDE)
        rule = self.flow_rules.get_by_id(flow_id)
        if rule is None:
            return TokenResult(C.STATUS_NO_RULE)
        if units <= 0:
            return TokenResult(C.STATUS_BAD_REQUEST)
        ns = self.flow_rules.namespace_of(flow_id) or C.DEFAULT_NAMESPACE
        if not self.limiter.try_pass(ns, self.client.time.now_ms()):
            _C_SHED.inc()
            return TokenResult(C.STATUS_TOO_MANY_REQUEST)
        if self.col is not None:
            with OT.TRACER.span("token.decision_batch", flow_id=flow_id, units=units):
                try:
                    granted, observed, limit = self.col.submit(
                        flow_id, units, partial=True
                    ).result(timeout=self.client.entry_timeout_s)
                    granted = int(granted)
                except Exception:  # stlint: disable=fail-open — STATUS_FAIL makes the caller degrade to local enforcement, never PASS
                    return TokenResult(C.STATUS_FAIL)
            _C_DECISIONS.inc(units)
            if granted == 0:
                return TokenResult(
                    C.STATUS_BLOCKED,
                    remaining=0,
                    prov_kind=ERR.BLOCK_FLOW,
                    prov_rule=flow_id,
                    prov_observed=observed,
                    prov_limit=limit,
                )
            return TokenResult(C.STATUS_OK, remaining=granted)
        with OT.TRACER.span("token.decision_batch", flow_id=flow_id, units=units):
            results = self.client.check_batch([flow_resource(flow_id)] * units)
        _C_DECISIONS.inc(units)
        granted = sum(1 for v, _ in results if v in (ERR.PASS, ERR.PASS_WAIT))
        wait = max((w for v, w in results if v == ERR.PASS_WAIT), default=0)
        if granted == 0:
            return TokenResult(
                C.STATUS_BLOCKED,
                remaining=0,
                prov_kind=ERR.BLOCK_FLOW,
                prov_rule=flow_id,
            )
        return TokenResult(C.STATUS_OK, remaining=granted, wait_ms=wait)

    def request_param_token(self, flow_id: int, count: int, params: List[Any]) -> TokenResult:
        FP.hit(_FP_DECIDE)
        rule = self.param_rules.get_by_id(flow_id)
        if rule is None:
            return TokenResult(C.STATUS_NO_RULE)
        if not params:
            return TokenResult(C.STATUS_BAD_REQUEST)
        ns = self.param_rules.namespace_of(flow_id) or C.DEFAULT_NAMESPACE
        if not self.limiter.try_pass(ns, self.client.time.now_ms()):
            _C_SHED.inc()
            return TokenResult(C.STATUS_TOO_MANY_REQUEST)
        name = param_resource(flow_id)
        with OT.TRACER.span("token.decision_param", flow_id=flow_id):
            results = self.client.check_batch(
                [name] * len(params),
                counts=[count] * len(params),
                params=list(params),
            )
        _C_DECISIONS.inc(len(params))
        if all(v == ERR.PASS for v, _ in results):
            return TokenResult(C.STATUS_OK)
        return TokenResult(
            C.STATUS_BLOCKED, prov_kind=ERR.BLOCK_PARAM, prov_rule=flow_id
        )

    # request_lease: the TokenService base implementation already rides
    # request_token_batch with the MAX_LEASE_UNITS clamp and honors this
    # instance's lease_ttl_ms — no override needed

    def request_concurrent_token(self, flow_id: int, count: int = 1) -> TokenResult:
        rule = self.flow_rules.get_by_id(flow_id)
        if rule is None:
            return TokenResult(C.STATUS_NO_RULE)
        ns = self.flow_rules.namespace_of(flow_id) or C.DEFAULT_NAMESPACE
        limit = self._global_threshold(rule, ns)
        tid = self.concurrent.acquire(
            flow_id, count, limit, self.client.time.now_ms()
        )
        if tid is None:
            return TokenResult(
                C.STATUS_BLOCKED,
                prov_kind=ERR.BLOCK_FLOW,
                prov_rule=flow_id,
                prov_limit=limit,
            )
        return TokenResult(C.STATUS_OK, token_id=tid)

    def release_concurrent_token(self, token_id: int) -> TokenResult:
        ok = self.concurrent.release(token_id)
        return TokenResult(C.STATUS_RELEASE_OK if ok else C.STATUS_ALREADY_RELEASE)

    # -- protocol v2 BATCH frames -------------------------------------------

    def decide_frame(
        self, kinds, ids, counts, flags
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, list]:
        """Answer one protocol-v2 BATCH frame's entry columns.

        Host-side guards (rule lookup, namespace limiter, validation) run
        per entry; every surviving entry joins ONE column submission, so a
        frame carrying a hundred flows costs one device decision.  Entry
        kinds map onto the existing verdict surface:

          BATCH_KIND_FLOW        all-or-nothing → OK / BLOCKED
          BATCH_KIND_FLOW_BATCH  partial grant  → OK(remaining=granted) / BLOCKED
          BATCH_KIND_LEASE       MAX_LEASE_UNITS-clamped partial grant;
                                 wait_ms carries the lease TTL

        The prioritized flag has no occupy-ahead on the column path: an
        over-limit prioritized entry is BLOCKED (fail closed), never
        SHOULD_WAIT.  Returns (statuses i8, remainings i32, waits i32,
        token_ids i64, prov) aligned with the request entries; ``prov[i]``
        is ``(kind, rule, observed|None, limit|None)`` on BLOCKED entries
        whose cause is known, else None — the server ships it back only
        when the client set BATCH_FLAG_EXPLAIN (protocol v3 _T_PROV).
        """
        n = len(kinds)
        # seed FAIL, not OK: any entry a bug leaves untouched must read as
        # a failure the client degrades on, never as a grant
        statuses = np.full(n, C.STATUS_FAIL, np.int8)
        remainings = np.zeros(n, np.int32)
        waits = np.zeros(n, np.int32)
        token_ids = np.zeros(n, np.int64)
        prov: List[Optional[Tuple[int, int, Optional[float], Optional[float]]]] = [
            None
        ] * n
        if self.col is None:
            for i in range(n):
                kind, fid, cnt = int(kinds[i]), int(ids[i]), int(counts[i])
                prio = bool(int(flags[i]) & C.BATCH_FLAG_PRIORITIZED)
                if kind == C.BATCH_KIND_FLOW:
                    r = self.request_token(fid, cnt, prio)
                elif kind == C.BATCH_KIND_FLOW_BATCH:
                    r = self.request_token_batch(fid, cnt)
                elif kind == C.BATCH_KIND_LEASE:
                    r = self.request_lease(fid, cnt)
                else:
                    r = TokenResult(C.STATUS_BAD_REQUEST)
                statuses[i] = r.status
                remainings[i] = r.remaining
                waits[i] = r.wait_ms
                token_ids[i] = r.token_id
                if r.prov_kind is not None:
                    prov[i] = (
                        r.prov_kind,
                        r.prov_rule if r.prov_rule is not None else fid,
                        r.prov_observed,
                        r.prov_limit,
                    )
            return statuses, remainings, waits, token_ids, prov
        now = self.client.time.now_ms()
        futs: List[Future] = []
        meta: List[Tuple[int, int, int]] = []
        for i in range(n):
            FP.hit(_FP_DECIDE)
            kind, fid, cnt = int(kinds[i]), int(ids[i]), int(counts[i])
            if kind not in (
                C.BATCH_KIND_FLOW,
                C.BATCH_KIND_FLOW_BATCH,
                C.BATCH_KIND_LEASE,
            ):
                statuses[i] = C.STATUS_BAD_REQUEST
                continue
            rule = self.flow_rules.get_by_id(fid)
            if rule is None:
                statuses[i] = C.STATUS_NO_RULE
                continue
            if cnt <= 0:
                # a zero-unit all-or-nothing ask requests nothing and
                # passes; a zero/negative batch or lease ask is malformed
                statuses[i] = (
                    C.STATUS_OK
                    if kind == C.BATCH_KIND_FLOW and cnt == 0
                    else C.STATUS_BAD_REQUEST
                )
                continue
            ns = self.flow_rules.namespace_of(fid) or C.DEFAULT_NAMESPACE
            if not self.limiter.try_pass(ns, now):
                _C_SHED.inc()
                statuses[i] = C.STATUS_TOO_MANY_REQUEST
                continue
            units = min(cnt, C.MAX_LEASE_UNITS) if kind == C.BATCH_KIND_LEASE else cnt
            futs.append(
                self.col.submit(fid, units, partial=kind != C.BATCH_KIND_FLOW)
            )
            meta.append((i, kind, units, fid))
        timeout = self.client.entry_timeout_s
        for f, (i, kind, units, fid) in zip(futs, meta):
            try:
                granted, observed, limit = f.result(timeout=timeout)
                granted = int(granted)
            except Exception:  # stlint: disable=fail-open — STATUS_FAIL makes the caller degrade to local enforcement, never PASS
                statuses[i] = C.STATUS_FAIL
                continue
            _C_DECISIONS.inc(1 if kind == C.BATCH_KIND_FLOW else units)
            blocked = (
                granted < units if kind == C.BATCH_KIND_FLOW else granted == 0
            )
            if blocked:
                statuses[i] = C.STATUS_BLOCKED
                prov[i] = (ERR.BLOCK_FLOW, fid, observed, limit)
            else:
                statuses[i] = C.STATUS_OK
                if kind != C.BATCH_KIND_FLOW:
                    remainings[i] = granted
                    if kind == C.BATCH_KIND_LEASE:
                        waits[i] = self.lease_ttl_ms
        return statuses, remainings, waits, token_ids, prov
