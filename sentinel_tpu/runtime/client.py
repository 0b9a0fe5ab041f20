"""The host runtime: micro-batching client around the device engine.

This layer replaces the reference's per-request machinery (CtSph.java:43,
CtEntry, the slot-chain walk) with an accumulate→tick→fan-out loop:

  entry("res")  ──► AcquireRequest + Future ──┐
  entry.exit()  ──► completion record ────────┤  pending queues
                                              ▼
                         tick thread (every ~tick_interval_ms, or manual):
                           drain queues → fixed-shape batches → jitted
                           engine tick → resolve futures with verdicts

Modes:
  * ``sync``    — every entry() runs a tick inline (batch of whatever is
                  queued).  Deterministic; pairs with VirtualTimeSource for
                  tests (the AbstractTimeBasedTest analog, SURVEY.md §4.1).
  * ``threaded``— a daemon tick loop services futures; entry() blocks.
                  This is the serving configuration.

Bulk paths: ``check_batch`` submits N acquires in one call (per-item
objects); ``submit_block``/``check_batch_ids`` submit COLUMN ARRAYS of
resource ids with zero per-item Python — the TPU-native surface used by
the cluster token server, gateway adapters, and the benchmark.

Fast-path integration (the config defaults to it on TPU — see
core.config.platform_engine_config): with the segment-compacted engine
enabled, the tick builder presorts every batch by the engine's segment
keys (np.lexsort; stable, so per-key arrival order and therefore every
rank/verdict is bit-identical) and maps verdicts back through the inverse
permutation; observed live-segment counts auto-grow cfg.seg_u via a
compile-then-swap resize; with ``pipeline_depth`` > 0 the loop runs up to
that many ticks ahead of verdict readback so the device→host transfer
overlaps compute (it drains fully before going idle).
"""

from __future__ import annotations

import threading
import time as _time
from concurrent.futures import Future
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from sentinel_tpu.adaptive import degrade as DG
from sentinel_tpu.adaptive.controller import AdaptiveConfig, AdaptiveController
from sentinel_tpu.chaos import failpoints as FP
from sentinel_tpu.core import errors as ERR
from sentinel_tpu.core import rules as R
from sentinel_tpu.core.config import EngineConfig
from sentinel_tpu.core.rule_tensors import compile_system_rules, hash_param
from sentinel_tpu.ops import engine as E
from sentinel_tpu.ops import window as W
from sentinel_tpu.ops import wire as WIRE
from sentinel_tpu.obs import flight as FL
from sentinel_tpu.obs import profile as PROF
from sentinel_tpu.obs import timeline as TLM
from sentinel_tpu.obs import trace as OT
from sentinel_tpu.obs.registry import REGISTRY as OBS
from sentinel_tpu.native import ring as RING
from sentinel_tpu.runtime import context as CTX
from sentinel_tpu.runtime.registry import Registry
from sentinel_tpu.metrics import extension as MEXT
from sentinel_tpu.utils.system_status import SystemStatusSampler
from sentinel_tpu.utils.time_source import TimeSource, VirtualTimeSource, mono_s

# -- observability plane (obs/): per-stage tick histograms, pipeline
# gauges, and incident counters.  Stage HISTOGRAMS update only while
# tracing is enabled (OT.t0() truthiness is the hot path's single flag
# check); pipeline gauges (one float store) and incident counters (seg
# drops, degrade transitions — rare) update unconditionally so the
# always-on /metrics surface is trustworthy even untraced.
_H_ASSEMBLE = OBS.histogram(
    "sentinel_tick_assemble_ms", "host batch assembly (columns + uploads) per tick"
)
_H_PRESORT = OBS.histogram(
    "sentinel_tick_presort_ms", "host segment-key presort (sort of the live rows + column gather, one native call a side) per tick"
)
_H_DISPATCH = OBS.histogram(
    "sentinel_tick_dispatch_ms", "engine tick dispatch (async jit call) per tick"
)
_H_DEVICE = OBS.histogram(
    "sentinel_tick_device_ms",
    "dispatch to verdicts-host-visible per tick (device compute + transfer; "
    "includes pipeline queue wait)",
)
_H_READBACK = OBS.histogram(
    "sentinel_tick_readback_ms", "verdict/wait/drop-count device-to-host reads per tick"
)
_H_RESOLVE = OBS.histogram(
    "sentinel_tick_resolve_ms", "verdict fan-out (futures, blocks, front doors) per tick"
)
# tick.idle attrs: shared, so an idle span allocates nothing
_IDLE_INTERVAL = {"why": "interval"}
_IDLE_RESOLVERS = {"why": "resolvers"}
_IDLE_DEPTH = {"why": "depth"}
_G_OCCUPANCY = OBS.gauge(
    "sentinel_pipeline_occupancy", "dispatched-but-unresolved engine ticks"
)
_G_RESOLVER_Q = OBS.gauge(
    "sentinel_resolver_queue_depth", "in-flight resolver-pool readbacks"
)
_C_SEG_DROPPED = OBS.counter(
    "sentinel_seg_dropped_total",
    "items whose effects a seg_fallback=False engine dropped on capacity overflow",
)
_G_DEGRADED = OBS.gauge(
    "sentinel_cluster_degraded", "1 while cluster enforcement is degraded to local rules"
)
_C_DEGRADE_ENTER = OBS.counter(
    "sentinel_cluster_degrade_transitions_total",
    "cluster degrade state transitions",
    labels={"transition": "enter"},
)
_C_DEGRADE_EXIT = OBS.counter(
    "sentinel_cluster_degrade_transitions_total",
    "cluster degrade state transitions",
    labels={"transition": "exit"},
)
_C_SEG_RESIZE = OBS.counter(
    "sentinel_seg_resizes_total", "seg_u capacity grow-and-hot-swap events"
)
_C_RESOLVE_FAILED = OBS.counter(
    "sentinel_resolve_failures_total",
    "tick resolutions that raised; their items failed CLOSED (system block)",
)
# -- adaptive protection / backpressure (adaptive/): shed accounting, the
# live admission ceiling, and the tick watchdog.  Registered at import so
# the exposition surface carries them from process start.
_SHED_HELP = "admissions shed before device dispatch, by stage and reason"
_C_SHED: Dict[tuple, Any] = {
    (st, rs): OBS.counter(
        "sentinel_shed_total", _SHED_HELP, labels={"stage": st, "reason": rs}
    )
    for st, rs in (
        ("admit", "queue_full"),
        ("admit", "low_priority"),
        ("admit", "fail_closed"),
        ("admit", "deadline"),
        ("admit", "chaos"),
        ("tick", "deadline"),
    )
}
_C_WATCHDOG = OBS.counter(
    "sentinel_watchdog_fired_total",
    "stalled engine ticks the watchdog failed CLOSED",
)
# -- device-resident telemetry (cfg.device_telemetry): the engine emits a
# compact stats row per tick (ops/engine.STAT_*) and the readback folds it
# here — the registry's verdict-mix/ceiling/window view comes from the
# DEVICE's accounting, not a host-side re-scan of the verdict array.
_DEV_VERDICTS_HELP = (
    "per-tick verdict mix reported by the device telemetry row, by verdict"
)
_C_DEV_VERDICTS: Dict[str, Any] = {
    v: OBS.counter(
        "sentinel_device_verdicts_total", _DEV_VERDICTS_HELP, labels={"verdict": v}
    )
    for v in (
        "pass",
        "pass_wait",
        "block_authority",
        "block_system",
        "block_param",
        "block_flow",
        "block_degrade",
    )
}
_C_PARAM_BLOCKED = OBS.counter(
    "sentinel_param_blocked_total",
    "items a hot-parameter rule blocked (BLOCK_PARAM), from the device telemetry row",
)
# circuit breakers that moved, from the same row (ops/engine.STAT_CB_*):
# upstream's EventObserverRegistry state-change observers, a tick at a time
_C_BREAKER_MOVES = {
    to: (idx, OBS.counter(
        "sentinel_breaker_transitions_total",
        "circuit breakers that changed state, by the state they went to "
        "(reopen: a HALF_OPEN breaker whose probe failed)",
        labels={"to": to},
    ))
    for to, idx in (
        ("open", E.STAT_CB_OPENED),
        ("half_open", E.STAT_CB_HALF_OPENED),
        ("closed", E.STAT_CB_CLOSED),
        ("reopen", E.STAT_CB_REOPENED),
    )
}
_G_BREAKERS_OPEN = OBS.gauge(
    "sentinel_breakers_open",
    "circuit breakers OPEN or HALF_OPEN after the last tick",
)
#: what tick.resolve carries of the row where a pacing (RATE_LIMITER) rule is loaded
_RESOLVE_PACED_ATTRS = (
    ("items", E.STAT_VALID),
    ("pass_wait", E.STAT_PASS_WAIT),
    ("flow_blocked", E.STAT_BLOCK_FLOW),
)
#: what tick.resolve carries of the row where the degrade stage is compiled
_RESOLVE_BREAKER_ATTRS = (
    ("items", E.STAT_VALID),
    ("degrade_blocked", E.STAT_BLOCK_DEGRADE),
    ("cb_opened", E.STAT_CB_OPENED),
    ("cb_half_opened", E.STAT_CB_HALF_OPENED),
    ("cb_closed", E.STAT_CB_CLOSED),
    ("cb_reopened", E.STAT_CB_REOPENED),
    ("cb_open_now", E.STAT_CB_OPEN_NOW),
)
_C_DEV_TOKENS = {
    r: OBS.counter(
        "sentinel_device_tokens_total",
        "admitted/blocked token sums from the device telemetry row",
        labels={"result": r},
    )
    for r in ("pass", "block")
}
_C_DEV_FORCED = OBS.counter(
    "sentinel_device_forced_verdicts_total",
    "host-injected pre-verdicts (cluster token denials) the device recorded",
)
_G_DEV_WIN_PASS = OBS.gauge(
    "sentinel_device_entry_pass_window",
    "ENTRY-node sliding-window pass sum as computed on-device",
)
_G_DEV_MIN_RT = OBS.gauge(
    "sentinel_device_entry_min_rt_ms",
    "ENTRY-node windowed RT floor as computed on-device (0 = no completions)",
)
_G_DEV_CONC = OBS.gauge(
    "sentinel_device_entry_concurrency",
    "global inbound concurrency as computed on-device",
)
_G_DEV_CEIL_UTIL = OBS.gauge(
    "sentinel_device_ceiling_utilization",
    "windowed ENTRY pass over the active system qps ceiling (0 = no ceiling)",
)
_G_DEV_SEG_LIVE = OBS.gauge(
    "sentinel_device_seg_live",
    "live compacted segments in the last tick (seg path only)",
)
# -- wire byte accounting: what actually crosses the host<->device link
# and the cluster protocol per tick — the 5.37 MB/tick ROADMAP item 1
# must shrink, so it is measured where it moves.
_C_WIRE = {
    d: OBS.counter(
        "sentinel_wire_bytes_total",
        "bytes moved, by path (device|cluster) and direction (tx|rx)",
        labels={"path": "device", "direction": d},
    )
    for d in ("tx", "rx")
}
_C_PACKED_DECODE = OBS.counter(
    "sentinel_packed_decode_failures_total",
    "fused wire readbacks rejected by the packed decoder (tick fails CLOSED)",
)
_C_WAIT_OVERFLOW = OBS.counter(
    "sentinel_wire_wait_overflow_ticks_total",
    "packed ticks with more PASS_WAIT rows than the wire's sidecar holds "
    "(ops/wire.EXC_K): each read the whole wait column in a second transfer",
)
# -- window rotation cadence (r14 running-sum windows, ops/window.py):
# refresh() is a pure function of the stamped tick timestamp, so the
# host derives the device's rotation/skip decisions from the timestamps
# it stamps — no readback.  "second" is the exact tier (g=1, every
# boundary rotates); "sketch" is the minute-scale tier where slack_frac
# batches the purge every g buckets (skips = deferred boundaries).
_C_WIN_ROT = {
    w: OBS.counter(
        "sentinel_window_rotations_total",
        "window bucket rotations whose batched expiry purge ran (host-derived"
        " from the tick timestamps; mirrors the device rotation condition)",
        labels={"window": w},
    )
    for w in ("second", "sketch")
}
_C_WIN_SLACK = {
    w: OBS.counter(
        "sentinel_window_slack_skips_total",
        "window bucket boundaries crossed with the expiry purge deferred by"
        " slack batching (bounded overestimate until the next rotation)",
        labels={"window": w},
    )
    for w in ("second", "sketch")
}


def _shed_counter(stage: str, reason: str):
    c = _C_SHED.get((stage, reason))
    if c is None:
        c = _C_SHED[(stage, reason)] = OBS.counter(
            "sentinel_shed_total", _SHED_HELP, labels={"stage": stage, "reason": reason}
        )
    return c

#: chaos failpoints (chaos/failpoints.py) on the tick loop's own failure
#: surfaces — one flag check per site when disarmed
_FP_TICK_CLOCK = FP.register(
    "runtime.tick.clock", "engine tick timestamp (skew shifts windows)",
    FP.SKEW_ACTIONS,
)
_FP_READBACK = FP.register(
    "runtime.resolve.readback", "verdict device-to-host readback", FP.HIT_ACTIONS
)
_FP_FANOUT = FP.register(
    "runtime.resolve.fanout", "verdict fan-out to futures/blocks/doors",
    FP.HIT_ACTIONS,
)
_FP_SEG_RESIZE = FP.register(
    "runtime.seg.resize", "background seg_u grow-and-swap compile", FP.HIT_ACTIONS
)
_FP_ADMIT = FP.register(
    "runtime.client.admit",
    "pre-engine admission shed check (a raise sheds the request CLOSED)",
    FP.HIT_ACTIONS,
)
_FP_WD_STALL = FP.register(
    "runtime.watchdog.stall",
    "verdict readback entry (a delay stalls the tick for the watchdog)",
    FP.HIT_ACTIONS,
)
_FP_PACKED_DECODE = FP.register(
    "transport.packed.decode",
    "fused packed-wire readback bytes (mangled bytes fail the tick CLOSED)",
    FP.PIPE_ACTIONS,
)


@dataclass
class AcquireRequest:
    res: int
    count: int
    prio: int
    origin_id: int
    origin_node: int
    ctx_node: int
    ctx_name: int
    inbound: int
    param_hash: tuple  # param_dims hashed hot-param lanes (0 = none)
    pre_verdict: int = 0  # host-decided verdict (cluster denial) to record
    #: absolute engine-time ms past which the answer is worthless to the
    #: caller (0 = none); expired entries shed CLOSED before dispatch
    deadline_ms: int = 0
    future: Optional[Future] = None
    submitted_ns: int = 0  # obs: enqueue stamp for req.queue (0 = tracing off)
    resolved_ns: int = 0  # obs: when the resolver set the future (req.wake)
    tick_id: int = 0  # obs: the tick that took this request


@dataclass
class Completion:
    res: int
    origin_node: int
    ctx_node: int
    inbound: int
    rt: float
    success: int
    error: int
    param_hash: tuple = ()  # THREAD-grade release lanes


@dataclass
class ArrayBlock:
    """A bulk acquire submission: column arrays, no per-item Python.

    The TPU-native high-throughput surface (gateway adapters, the cluster
    token server, the benchmark): resource IDS (registry currency) and
    optional per-item columns.  The tick loop slices blocks into engine
    batches; ``future`` resolves to (verdicts int8 [n], waits int32 [n])
    in submission order once every item has been decided."""

    res: np.ndarray  # int32 [n]
    count: Optional[np.ndarray] = None
    prio: Optional[np.ndarray] = None
    origin_id: Optional[np.ndarray] = None
    origin_node: Optional[np.ndarray] = None
    ctx_node: Optional[np.ndarray] = None
    ctx_name: Optional[np.ndarray] = None
    inbound: Optional[np.ndarray] = None
    param_hash: Optional[np.ndarray] = None  # int32 [n, param_dims]
    pre_verdict: Optional[np.ndarray] = None
    #: block-wide absolute engine-time deadline (0 = none); the untaken
    #: remainder of an expired block sheds CLOSED at the tick builder
    deadline_ms: int = 0
    future: Optional[Future] = None
    submitted_ns: int = 0  # obs: enqueue stamp for req.queue (0 = tracing off)
    # internal progress
    taken: int = 0  # items already placed into ticks
    unresolved: int = 0  # items whose verdicts are still pending
    verdicts: Optional[np.ndarray] = None  # int8 [n] result buffer
    waits: Optional[np.ndarray] = None  # int32 [n] result buffer


#: the acquire side's presort keys, most significant first: the segment
#: keys of engine_seg.prepare_acquire
_ACQ_SEG_KEYS = ("res", "ctx_node", "origin_node", "origin_id", "ctx_name")


@dataclass
class _PendingTick:
    """A dispatched engine tick whose outputs haven't been read back.

    With ``pipeline_depth`` > 0 the tick loop hands each of these to the
    resolver pool at dispatch, so the device→host verdict transfer of
    tick t overlaps the host build + device compute of tick t+1, and
    dispatches no tick that would leave more than ``pipeline_depth``
    unresolved; depth 0 reads each tick back before the next one is
    built."""

    acq: List[AcquireRequest]
    blocks: list  # [(ArrayBlock, src_off, take), ...] at batch offset n
    fronts: list
    inv_a: Optional[np.ndarray]
    out: Any  # TickOutput (device arrays)
    check_dropped: bool
    n_obj: int  # object-request count (blocks start here)
    n_blk: int  # block item count (fronts start at n_obj + n_blk)
    #: packed-wire offset table for this tick's batch shape (ops/wire.py);
    #: captured at DISPATCH so a concurrent cfg swap can't skew the decode
    wire_lo: Any = None
    #: the host buffer this tick's input crossed from (ops/wire.InputBuffer),
    #: lent from SentinelClient._wire_free.  The transfer may still read it
    #: after dispatch, so it is this tick's alone until the tick has
    #: resolved, which returns it; a tick that fails keeps it for good
    wire_in: Any = None
    tick_id: int = 0  # obs trace correlation id (0 = tracing disabled)
    dispatched_ns: int = 0  # obs: dispatch-complete stamp for the device span
    handed_ns: int = 0  # obs: when the tick thread handed this tick to a resolver
    resolving_ns: int = 0  # obs: when _resolve_tick started on its thread
    now_ms: int = 0  # engine timestamp the tick ran at (timeline fold key)
    # fan-out progress (count of blocks/fronts fully resolved): a failed
    # resolve must fail CLOSED only the consumers the normal path hadn't
    # reached — no double-decrement, no double-respond (_fail_tick)
    blocks_done: int = 0
    fronts_done: int = 0
    # watchdog handshake: exactly ONE side fans this tick out.  The
    # resolver claims "done" after readback, the watchdog (or the
    # resolve-failure path) claims "failed" — whoever wins the state
    # transition under state_lock owns the fan-out; the loser discards.
    state: str = "pending"  # pending | done | failed
    state_lock: threading.Lock = field(default_factory=threading.Lock)
    deadline_mono: float = 0.0  # mono_s() stall deadline (0 = unwatched)
    #: set once the tick loop need not wait for this tick any more: its
    #: resolver returned, the watchdog failed it over while the resolver
    #: is still wedged, or a bounded wait gave it up (_await_resolved)
    settled: threading.Event = field(default_factory=threading.Event)


class Entry:
    """Live entry handle (the reference's Entry/CtEntry).

    ``exit()`` records RT + success; ``trace(exc)`` marks a business
    exception for exception-ratio circuit breakers (Tracer.java).
    """

    __slots__ = (
        "client",
        "resource",
        "res",
        "origin_node",
        "ctx_node",
        "inbound",
        "count",
        "create_ms",
        "wait_ms",
        "param_hash",
        "_errors",
        "_exited",
        "slots",
        "slot_ctx",
    )

    def __init__(self, client, resource, res, origin_node, ctx_node, inbound, count, create_ms, wait_ms=0, param_hash=()):
        self.client = client
        self.resource = resource
        self.res = res
        self.origin_node = origin_node
        self.ctx_node = ctx_node
        self.inbound = inbound
        self.count = count
        self.create_ms = create_ms
        self.wait_ms = wait_ms
        self.param_hash = param_hash
        self._errors = 0
        self._exited = False
        self.slots = ()  # entered custom slots (runtime/slots.py)
        self.slot_ctx = None

    def trace(self, exc: Optional[BaseException] = None, count: int = 1) -> None:
        if exc is not None and isinstance(exc, ERR.BlockException):
            return  # block exceptions are not business errors (Tracer semantics)
        self._errors += count

    def exit(self, count: Optional[int] = None) -> None:
        if self._exited:
            return
        self._exited = True
        CTX.pop_entry(self)
        if self.res is None:
            return  # pass-through entry (capacity overflow)
        now = self.client.time.now_ms()
        rt = float(max(now - self.create_ms, 0))
        n = count if count is not None else self.count
        MEXT.safe_dispatch("on_complete", self.resource, rt, n, "")
        if self._errors:
            MEXT.safe_dispatch("on_exception", self.resource, self._errors, "")
        self.client._submit_completion(
            Completion(
                res=self.res,
                origin_node=self.origin_node,
                ctx_node=self.ctx_node,
                inbound=self.inbound,
                rt=rt,
                success=count if count is not None else self.count,
                error=self._errors,
                param_hash=self.param_hash,
            )
        )
        if self.slots:
            from sentinel_tpu.runtime.slots import run_exit

            self.slot_ctx.rt_ms = rt
            self.slot_ctx.success = n
            self.slot_ctx.errors = self._errors
            run_exit(self.slots, self.slot_ctx)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc is not None:
            self.trace(exc)
        self.exit()
        return False


class _PassThroughEntry(Entry):
    def __init__(self, client, resource):
        super().__init__(client, resource, None, 0, 0, 0, 1, 0)


class RuleManager:
    """Typed rule holder with push-style listeners.

    The analog of FlowRuleManager/DegradeRuleManager/...: ``load`` replaces
    the full rule set and triggers engine recompilation
    (FlowRuleManager.loadRules → property.updateValue → listener).
    """

    def __init__(self, client: "SentinelClient", kind: str):
        self._client = client
        self.kind = kind
        self._rules: list = []
        self._listeners: list = []
        self._property = None

    def load(self, rules: Sequence) -> None:
        self._rules = list(rules) if rules else []
        self._client._recompile_rules()
        for fn in list(self._listeners):
            fn(self._rules)

    def get(self) -> list:
        return list(self._rules)

    def add_listener(self, fn) -> None:
        self._listeners.append(fn)

    def register_property(self, prop) -> None:
        """Subscribe this manager to a SentinelProperty so datasource pushes
        drive rule reloads (FlowRuleManager.register2Property analog)."""
        from sentinel_tpu.datasource.property import SimplePropertyListener

        if self._property is not None:
            self._property.remove_listener(self._prop_listener)
        self._property = prop
        # None means "property not populated yet" — keep existing rules
        # (FlowPropertyListener.configLoad null-check); an empty list is a
        # real "clear all rules" push.
        self._prop_listener = SimplePropertyListener(
            lambda rules: None if rules is None else self.load(rules)
        )
        prop.add_listener(self._prop_listener)


class SentinelClient:
    def __init__(
        self,
        app_name: Optional[str] = None,
        cfg: Optional[EngineConfig] = None,
        time_source: Optional[TimeSource] = None,
        mode: str = "threaded",  # "threaded" | "sync"
        tick_interval_ms: float = 1.0,
        entry_timeout_s: float = 5.0,
        metric_log: bool = False,
        metric_log_dir: Optional[str] = None,
        timeline_log: Any = False,  # bool | obs.timeline.MetricLog
        timeline_dir: Optional[str] = None,
        block_log: bool = False,
        pipeline_depth: int = 0,
        watchdog_timeout_s: float = 0.0,
        admission_queue_limit: int = 0,
        sketch_audit_k: int = 0,
        sketch_audit_period: int = 16,
    ):
        from sentinel_tpu.core.config import app_name as cfg_app_name
        from sentinel_tpu.core.config import platform_engine_config

        self.app_name = app_name or cfg_app_name()
        # default config is platform-detected: on TPU the fast path
        # (MXU tables + fused effects + segment compaction) is ON — the
        # product hot path IS the benchmarked engine configuration
        self.cfg = cfg or platform_engine_config()
        # tri-state packed_wire resolves to ON here and OFF everywhere
        # else (core/config.py): the client path is exactly where the
        # fused readback + narrow uploads pay; direct engine callers keep
        # the classic TickOutput arrays.  An explicit False opts out.
        if self.cfg.packed_wire is None:
            import dataclasses as _dc

            self.cfg = _dc.replace(self.cfg, packed_wire=True)
        self.time = time_source or TimeSource()
        self.mode = mode if not isinstance(self.time, VirtualTimeSource) else "sync"
        self.tick_interval_ms = tick_interval_ms
        self.entry_timeout_s = entry_timeout_s

        # global protection switch (Constants.ON / OnOffSetCommandHandler):
        # when off, every entry is a pass-through and nothing is counted
        self.enabled = True

        # custom entry hooks — the lightweight pre-check form: each hook
        # sees (resource, origin, args) before the engine check and may
        # raise a BlockException to reject
        self.entry_hooks: List[Any] = []
        # full custom-slot SPI (ProcessorSlot analog, runtime/slots.py):
        # ordered slots with entry AND exit hooks; register via
        # client.slots.register(slot)
        from sentinel_tpu.runtime.slots import SlotChain

        self.slots = SlotChain()

        self.registry = Registry(self.cfg)
        self.flow_rules = RuleManager(self, "flow")
        self.degrade_rules = RuleManager(self, "degrade")
        self.system_rules = RuleManager(self, "system")
        self.authority_rules = RuleManager(self, "authority")
        self.param_flow_rules = RuleManager(self, "param-flow")
        # gateway rules project onto param rules in a separate manager so
        # gateway pushes never clobber user param rules (GatewayRuleManager)
        self.gateway_param_rules = RuleManager(self, "gateway-param")

        # cluster-mode wiring (FlowRuleChecker.passClusterCheck analog):
        # cluster rules are checked against a TokenService; on token-server
        # loss the client degrades to local enforcement for rules that allow
        # it (fallbackToLocalOrPass:166) and re-probes after a cooldown.
        self.cluster = None  # Optional[ClusterStateManager]
        self._cluster_flow_by_res: Dict[str, R.FlowRule] = {}
        self._cluster_param_by_res: Dict[str, R.ParamFlowRule] = {}
        self._auth_host_rules: Dict[str, list] = {}
        self._param_lanes_by_res: Dict[str, list] = {}
        self._param_ruled = np.zeros(1, bool)
        #: a local FlowRule paces (RATE_LIMITER or WARM_UP_RATE_LIMITER): its
        #: ticks' spans carry the PASS_WAIT counts (_RESOLVE_PACED_ATTRS, wait_rows)
        self._paced = False
        # the shared degrade-hysteresis primitive (adaptive/degrade.py):
        # enter-on-failure with cooldown, exit on first healthy probe —
        # same journal kinds / counters / gauge as before the refactor
        self._cluster_hy = DG.Hysteresis(
            "cluster.degrade",
            cooldown_s=5.0,
            counter_enter=_C_DEGRADE_ENTER,
            counter_exit=_C_DEGRADE_EXIT,
            gauge=_G_DEGRADED,
        )
        # guards degrade-state transitions AND every ruleset recompile, so
        # the degraded flag each compile reads matches the ruleset committed
        self._cluster_lock = threading.RLock()
        self.cluster_retry_interval_s = 5.0

        self._sys = SystemStatusSampler()
        # -- adaptive protection / deadline-aware backpressure -------------
        # disabled mode is one `is None` / one flag check per call site
        # (same contract as obs tracing and chaos failpoints, guarded by
        # tests); enable_adaptive() arms the closed loop.
        self._adaptive: Optional[AdaptiveController] = None
        #: host copy of the STATIC system-rule tensors — the base the
        #: controller folds its live ceilings into (tightest wins)
        self._system_static = None
        #: hard bound on the un-ticked acquire queue (0 = unbounded);
        #: enable_adaptive() defaults it from AdaptiveConfig.queue_max
        self._admission_max = max(0, int(admission_queue_limit))
        #: single pre-computed flag the submit paths check — True only
        #: while backpressure has anything to do (bound set or ladder up)
        self._bp_armed = self._admission_max > 0
        #: set on the first deadline-carrying submission; the tick
        #: builder's expiry sweep runs only while True
        self._deadlines_live = False
        #: tick watchdog: fail a dispatched tick CLOSED when its outputs
        #: are not host-visible within this budget (0 = off).  Threaded
        #: mode only — sync mode has no loop to stall independently.
        self.watchdog_timeout_s = max(0.0, float(watchdog_timeout_s))
        self._wd_thread: Optional[threading.Thread] = None
        #: dispatched ticks the watchdog may fail over; populated only
        #: while the watchdog is armed (zero cost otherwise)
        self._inflight_ticks: Dict[int, _PendingTick] = {}
        self._inflight_lock = threading.Lock()
        # the tick compiles only the stages the loaded rule set needs (the
        # SPI slot-chain analog: absent slots cost nothing); rule loads that
        # change the feature set swap in a freshly compiled tick
        self._features = self._select_features()
        self._breaker_noted: Dict[str, int] = {}  # journal kind -> the second it was last noted in
        # memory-ledger ownership (obs/profile.py): every device buffer
        # built FOR this client — engine state (the sketch tier registers
        # itself inside init_state), ruleset tensors, wire staging — is
        # claimed under this owner tag so stop() releases exactly them;
        # the first make_tick per config is a warmup retrace by contract
        self._ledger_name = f"client:{self.app_name}:{id(self):x}"
        with PROF.ledger_owner(self._ledger_name), \
                PROF.expected_retrace("client-init"):
            self._tick = self._make_tick(self.cfg, self._features)
            self._state = E.init_state(self.cfg)
            self._rules_dev = E.compile_ruleset(self.cfg, self.registry)
        self._system_static = compile_system_rules([], self.cfg)
        self._rules_dirty = False

        self._front_doors: list = []
        self._lock = threading.Lock()  # guards the acquire queue
        self._engine_lock = threading.Lock()  # guards state/tick execution
        # resolver-pool shared-state guards: block progress accounting and
        # front-door response rings (single-producer C side)
        self._blk_lock = threading.Lock()
        self._respond_lock = threading.Lock()
        self._acquires: List[AcquireRequest] = []
        # bulk column-array submissions (ArrayBlock) + bulk completions
        self._acq_blocks: List[ArrayBlock] = []
        self._comp_blocks: List[tuple] = []
        # dispatched-but-unresolved ticks: each is handed to the resolver
        # pool at dispatch, so the device→host transfer overlaps the next
        # tick's host build and compute, and the loop dispatches none that
        # would leave more than pipeline_depth unresolved (it always drains
        # to empty before going idle).  A small resolver pool fetches
        # concurrently — transfers overlap each other AND the next tick's
        # host build (host↔device transfer latency pipelines)
        self._pipeline_depth = max(0, int(pipeline_depth))
        self._pending_ticks: List[_PendingTick] = []
        # obs: top of the first drain that found nothing (0 = the tick thread
        # is not idle): the drain that next finds work records one tick.idle
        self._idle_since = 0
        self._resolver_pool = None  # created lazily (see _pool)
        # serializes whole tick iterations: sync-mode clients call
        # tick_once from arbitrary request threads, and the pending-tick
        # bookkeeping above must not interleave.  Reentrant for SYNC-mode
        # future callbacks (a callback runs on the resolving caller's
        # thread and may re-enter tick_once).  API contract for THREADED
        # clients with a resolver pool: done-callbacks must be
        # non-blocking — submit_block/submit_completion_block are fine,
        # but a BLOCKING entry()/check_batch_ids inside a callback waits
        # on a tick only the (currently waiting) tick thread can run and
        # stalls all traffic until its timeout
        self._tick_mutex = threading.RLock()
        # two-slot staging for batch assembly: per-column host buffers
        # reused on alternating parity, so a slot filled for tick t is not
        # rewritten until t+2 — zero per-tick column allocation on the
        # steady path.  Nothing is uploaded from a slot (a pipelined tick
        # can stay queued longer than that, and on CPU the device array
        # would BE the slot): the presort gathers out of them into the
        # tick's own input buffer
        self._stage: Dict[tuple, list] = {}
        self._stage_parity = 0
        # the tick's input buffer (ops/wire.InputBuffer: one flat host
        # buffer, a view a column) crosses in ONE transfer, which may read
        # it after dispatch (and on the CPU backend an aligned buffer IS
        # the device array).  So it is not copied but owned: the
        # _PendingTick holds it until the tick has resolved and then
        # returns it here, keyed by its layout.  At most pipeline_depth + 1
        # are out a shape, and steady serving allocates none
        self._wire_free: Dict[Any, list] = {}
        self._wire_bytes = 0  # input buffers allocated (memory ledger)
        # the presort's inverse permutation outlives its tick (the
        # _PendingTick holds it until its verdicts are unsorted, and the
        # cap on unresolved ticks can change on a live client, so no ring
        # is sized from it): it is lent from a free list keyed by batch
        # shape, the resolver returns it, and steady serving allocates none
        self._inv_free: Dict[int, List[np.ndarray]] = {}
        # packed-wire offset tables keyed by (cfg, batch shape): the
        # read-back's by (cfg, b), the input's by (cfg, b, b2)
        self._wire_layouts: Dict[tuple, Any] = {}
        # completions are fire-and-forget (no futures), so they ride the
        # native MPMC event ring: Entry.exit() from any request thread is
        # one C call, and the tick drains straight into numpy arrays
        from sentinel_tpu.native import EventRing

        self._comp_ring = EventRing(1 << 16)
        # completions must NEVER be lost (they release concurrency and feed
        # circuit breakers) — when the ring is full (tick thread stalled,
        # e.g. mid-recompile) they overflow into this unbounded list
        self._comp_overflow: List[Completion] = []

        self._thread: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()
        self._started = False
        self.stats = ClientStats(self)

        # hot-set manager (sketch/hotset.py): folds the device's
        # TickOutput.hot candidate rows and promotes/demotes between the
        # exact tier and the sketch tail on its own cadence
        self.hotset = None
        if self.cfg.sketch_stats and E.hotset_k(self.cfg) > 0:
            from sentinel_tpu.sketch.hotset import HotSetManager

            self.hotset = HotSetManager(self)

        # online sketch-accuracy audit (obs/profile.SketchAudit): a
        # rotating exact shadow of up to sketch_audit_k sketched
        # resources, compared against the device estimates every
        # sketch_audit_period ticks.  Disarmed (k=0, the default) the
        # tick hot path pays exactly ONE `is not None` check.
        self._audit = None
        self._audit_scfg = None
        self._audit_provider = None
        self._audit_est = None
        if sketch_audit_k > 0 and self.cfg.sketch_stats:
            scfg = E.sketch_config(self.cfg)
            self._audit_scfg = scfg
            self._audit = PROF.SketchAudit(
                node_rows=self.cfg.node_rows,
                window_ms=scfg.window_ms,
                sample_count=scfg.sample_count,
                slack_buckets=scfg.slack_buckets,
                width=scfg.width,
                k=int(sketch_audit_k),
                period=int(sketch_audit_period),
                trash_row=self.cfg.trash_row,
            )

        # segment-compacted path bookkeeping: the tick builder presorts
        # batches by the engine's segment keys (see _presort_cols) and
        # tracks observed live-segment counts so seg_u can grow to fit the
        # real traffic (the seg_fallback=True safety net keeps overflow
        # ticks exact — just slower — while the resize compiles)
        self._seg_over_ticks = 0
        self._seg_obs_peak = 0
        self._seg_sample_ctr = 0
        self._seg_sample_ctr_c = 0  # completion side (ticks may lack acquires)
        self._seg_resizing = False
        # host mirror of the device window-rotation cadence: refresh is a
        # pure function of the stamped tick timestamp, so bucket-boundary
        # crossings and the slack-deferred purges are derivable here
        # without any readback ({window: (window_ms, slack_buckets,
        # last_wid, last_rot_wid)})
        self._rot_track = {
            "second": [cfg.second_window_ms, 1, None, None],
        }
        if cfg.sketch_stats:
            scfg = E.sketch_config(cfg)
            self._rot_track["sketch"] = [
                scfg.window_ms, scfg.slack_buckets, None, None,
            ]
        #: items whose EFFECTS a seg_fallback=False engine dropped on
        #: capacity overflow (verdicts fail closed; see EngineConfig.seg_u)
        self.seg_dropped_total = 0
        self._seg_drop_last_log_s = -1

        # host-side hot-param value tracking: the device CMS holds hashes
        # only; the command plane's topParams view needs the VALUES, so the
        # entry path keeps a small capped counter per resource
        self._hot_params: Dict[str, Dict[Any, int]] = {}
        self._hot_params_lock = threading.Lock()

        # observability plane (MetricTimerListener / EagleEye block log)
        self._metric_log_enabled = metric_log
        self._metric_log_dir = metric_log_dir
        # per-resource timeline (obs/timeline.py): created in start() when
        # the engine emits res_stats; an on-disk MetricLog is attached
        # only when asked for (timeline_log=True / a prebuilt MetricLog /
        # timeline_dir) — the in-memory ring serves /api/metric regardless
        self._timeline_log_opt = timeline_log
        self._timeline_dir = timeline_dir
        self.timeline = None
        self._timeline_provider = None
        self.metric_timer = None
        self.block_log = None
        if block_log:
            from sentinel_tpu.metrics.block_log import default_block_logger

            self.block_log = default_block_logger()

        # verdict provenance plane (obs/explain.py): decodes the fused
        # readback's explain section into per-resource "why blocked"
        # rings.  Rides only the packed wire (E.explain_k gates on
        # cfg.packed_wire); eps annotation comes from the sketch audit
        # when armed, names from the registry.  The plane carries no
        # client reference — both inputs are injected callables.
        self.explain_plane = None
        self._explain_provider = None
        if E.explain_k(self.cfg) > 0:
            from sentinel_tpu.obs.explain import ExplainPlane

            def _audit_eps() -> Optional[float]:
                au = self._audit
                if au is None:
                    return None
                return au._last_audit.get("eps_budget")

            self.explain_plane = ExplainPlane(
                eps_source=_audit_eps,
                name_source=self.registry.resource_name,
            )

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        if self._started:
            return
        self._started = True
        self._stop_evt = threading.Event()  # allow stop() → start() restart
        if self.timeline is None and E.timeline_k(self.cfg) > 0:
            log = None
            if isinstance(self._timeline_log_opt, TLM.MetricLog):
                log = self._timeline_log_opt
            elif self._timeline_log_opt or self._timeline_dir:
                import os as _os

                from sentinel_tpu.utils.record_log import log_dir

                # pid-suffixed like the text MetricWriter's file names: two
                # same-app processes sharing a log dir must never append to
                # (or "recover" = truncate) each other's live segments
                log = TLM.MetricLog(
                    _os.path.join(
                        self._timeline_dir or log_dir(),
                        f"{self.app_name}-timeline.pid{_os.getpid()}",
                    )
                )
            self.timeline = TLM.TimelineRecorder(
                self.registry.resource_name,
                self.cfg.second_window_ms,
                self.cfg.second_sample_count,
                log=log,
                name=self.app_name,
            )
            # flight bundles carry the last ~30 s of top-K rows — the
            # post-mortem's "what was each hot resource doing" table
            self._timeline_provider = self.timeline.flight_section
            FL.FLIGHT.register_provider("timeline", self._timeline_provider)
        if self.mode == "threaded":
            # Warm the compile cache before serving: the first jitted tick
            # can take tens of seconds; without this, early entry() futures
            # hit entry_timeout_s while XLA compiles.
            self._warm_shapes()
            self._thread = threading.Thread(
                target=self._tick_loop,
                args=(self._stop_evt,),
                name="sentinel-tpu-tick",
                daemon=True,
            )
            self._thread.start()
            if self.watchdog_timeout_s > 0:
                self._wd_thread = threading.Thread(
                    target=self._watchdog_loop,
                    args=(self._stop_evt,),
                    name="sentinel-tpu-watchdog",
                    daemon=True,
                )
                self._wd_thread.start()
        if self._metric_log_enabled and self.metric_timer is None:
            from sentinel_tpu.metrics.timer import MetricTimerListener
            from sentinel_tpu.metrics.writer import MetricWriter
            from sentinel_tpu.utils.record_log import log_dir

            writer = MetricWriter(self._metric_log_dir or log_dir(), self.app_name)
            self.metric_timer = MetricTimerListener(self, writer)
            if self.mode == "threaded":
                self.metric_timer.start()
        # black-box providers: every flight bundle captured while this
        # client serves includes its rule fingerprints, pipeline state,
        # and a config digest (last started client wins the name)
        self._flight_provider = self._flight_state
        FL.FLIGHT.register_provider("client", self._flight_provider)
        if self._audit is not None:
            self._audit_provider = self._audit.flight_section
            FL.FLIGHT.register_provider("audit", self._audit_provider)
        if self.explain_plane is not None:
            self._explain_provider = self.explain_plane.flight_section
            FL.FLIGHT.register_provider("explain", self._explain_provider)

    def _flight_state(self) -> dict:
        """Flight-bundle section: what a post-mortem needs to know about
        this client at capture time (obs/flight.py provider contract)."""
        import hashlib
        import json as _json
        from dataclasses import asdict

        fps = {}
        for name in (
            "flow_rules",
            "degrade_rules",
            "system_rules",
            "authority_rules",
            "param_flow_rules",
        ):
            rules = getattr(self, name).get()
            js = _json.dumps(R.rules_to_json_list(rules), sort_keys=True)
            fps[name] = {
                "count": len(rules),
                "sha1": hashlib.sha1(js.encode()).hexdigest()[:12],
            }
        cfg = {
            k: v
            for k, v in asdict(self.cfg).items()
            if isinstance(v, (int, float, str, bool))
        }
        ad = self._adaptive
        return {
            "app": self.app_name,
            "mode": self.mode,
            "enabled": self.enabled,
            "degraded": self._cluster_degraded_active,
            "adaptive": {
                "level": DG.LEVEL_NAMES[ad.ladder.level],
                "ceiling": (
                    -1.0 if ad.ceiling == float("inf") else round(ad.ceiling, 3)
                ),
            }
            if ad is not None
            else None,
            "pending_ticks": len(self._pending_ticks),
            "registered_resources": self.registry.num_resources,
            "rule_fingerprints": fps,
            "config": cfg,
        }

    def stop(self) -> None:
        fp = getattr(self, "_flight_provider", None)
        if fp is not None:
            # only if still ours — a newer client may have taken the slot
            FL.FLIGHT.unregister_provider("client", fp)
        ap = getattr(self, "_audit_provider", None)
        if ap is not None:
            FL.FLIGHT.unregister_provider("audit", ap)
            self._audit_provider = None
        ep = getattr(self, "_explain_provider", None)
        if ep is not None:
            FL.FLIGHT.unregister_provider("explain", ep)
            self._explain_provider = None
        self._stop_evt.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        if self._wd_thread is not None:
            self._wd_thread.join(timeout=2.0)
            self._wd_thread = None
        # flush deferred readbacks so no caller future is abandoned, then
        # release the resolver threads (start() re-creates the pool)
        try:
            with self._tick_mutex:
                self._drain_resolves()
        except Exception:  # pragma: no cover — surfaced via record log  # stlint: disable=fail-open — shutdown path: flush is best-effort, no admission decision rides on it
            from sentinel_tpu.utils.record_log import record_log

            record_log().warning("resolve flush failed in stop()", exc_info=True)
        if self._resolver_pool is not None:
            self._resolver_pool.shutdown(wait=True)
            self._resolver_pool = None
        if self.metric_timer is not None:
            self.metric_timer.stop()
            self.metric_timer = None
        if self.timeline is not None:
            if self._timeline_provider is not None:
                FL.FLIGHT.unregister_provider(
                    "timeline", self._timeline_provider
                )
                self._timeline_provider = None
            # flush the still-open second so shutdown loses no rows, then
            # release the log handles (start() rebuilds the recorder)
            self.timeline.close()
            self.timeline = None
        if self.block_log is not None:
            self.block_log.flush()
        # release this client's memory-ledger claims (engine state, rule
        # tensors, wire staging) — the owner tag brackets exactly them
        PROF.LEDGER.drop_owner(self._ledger_name)
        self._started = False

    # -- adaptive protection / backpressure ---------------------------------

    def enable_adaptive(self, cfg: Optional[AdaptiveConfig] = None) -> AdaptiveController:
        """Arm closed-loop system-adaptive protection (adaptive/): a
        per-tick controller republishes the SystemSlot ceilings
        (maxPass × minRT) as live rule-tensor column values — a scalar
        upload, never a recompile — and drives the unified degrade
        ladder whose rungs the admission path enforces.  Idempotent;
        returns the controller for inspection."""
        with self._cluster_lock:
            if self._adaptive is not None:
                return self._adaptive
            self._adaptive = AdaptiveController(cfg)
            if self._admission_max == 0:
                self._admission_max = int(self._adaptive.cfg.queue_max)
            self._bp_armed = True
        # the SystemSlot stage must exist in the compiled tick even with
        # no static system rule; _select_features now includes it
        self._recompile_rules()
        return self._adaptive

    def disable_adaptive(self) -> None:
        """Disarm the closed loop and restore the static thresholds."""
        with self._cluster_lock:
            ad, self._adaptive = self._adaptive, None
            if ad is None:
                return
            ad.disarm()
            self._bp_armed = self._admission_max > 0
        self._recompile_rules()

    def _admission_shed(self, prio: int) -> Optional[str]:
        """Pre-engine shed decision for one submission; returns the shed
        reason or None to admit.  Fast path (backpressure disarmed) is
        the single ``_bp_armed`` flag check."""
        if not self._bp_armed:
            return None
        try:
            FP.hit(_FP_ADMIT)  # chaos: a raise sheds this admission CLOSED
        except Exception:  # stlint: disable=fail-open — sheds CLOSED (the caller maps any reason to BLOCK_SYSTEM); nothing is admitted
            return "chaos"
        ad = self._adaptive
        level = ad.ladder.level if ad is not None else DG.NORMAL
        if level >= DG.FAIL_CLOSED:
            return "fail_closed"
        qmax = self._admission_max
        if qmax:
            # unlocked reads — approximate is fine; blocks count too (a
            # submit_block flood must not slip past the bound just
            # because its items sit in _acq_blocks, not _acquires)
            qd = len(self._acquires) + sum(
                len(b.res) - b.taken for b in self._acq_blocks
            )
            if qd >= qmax:
                return "queue_full"
            if (
                level >= DG.SHED_LOW_PRIORITY
                and not prio
                and ad is not None
                and qd >= qmax * ad.cfg.shed_lowprio_frac
            ):
                return "low_priority"
        elif level >= DG.SHED_LOW_PRIORITY and not prio:
            # no queue bound configured: the rung itself sheds the
            # non-prioritized share
            return "low_priority"
        return None

    def _shed_blocked(self, stage: str, reason: str, n: int = 1) -> None:
        _shed_counter(stage, reason).inc(n)

    def _adaptive_step(self, ad: AdaptiveController, now_ms: int, load, cpu) -> None:
        """One closed-loop control step, on the tick thread: collect the
        signals row, advance controller + ladder, apply rung effects,
        and publish changed ceilings into the live system columns."""
        with self._lock:
            qd = len(self._acquires) + sum(
                len(b.res) - b.taken for b in self._acq_blocks
            )
        sig = ad.signals.observe_tick(
            now_ms,
            qd,
            len(self._pending_ticks),
            len(self._pending_ticks),
            load,
            cpu,
        )
        want = ad.on_tick(sig)
        level = ad.ladder.level
        self._bp_armed = level > DG.NORMAL or self._admission_max > 0
        if level >= DG.CLUSTER_FALLBACK and (
            self._cluster_flow_by_res or self._cluster_param_by_res
        ):
            # rung effect: stop paying token-server round-trips on the
            # admission path; fallback-enabled cluster rules enforce
            # locally.  Re-entering every tick extends the cooldown, so
            # probes resume only after the ladder descends.
            self._enter_cluster_degraded()
        if want is not None:
            qps, max_thread = want
            sys_np = ad.system_columns(self._system_static, qps, max_thread)
            with self._engine_lock:
                # re-read under the lock: a concurrent rule recompile may
                # have swapped the whole ruleset; only the system leaves
                # are replaced (same shapes/dtypes — no recompile)
                self._rules_dev = E.replace_system_columns(self._rules_dev, sys_np)

    # -- tick watchdog -------------------------------------------------------

    def _watchdog_loop(self, stop_evt: threading.Event) -> None:
        period = max(self.watchdog_timeout_s / 4.0, 0.01)
        while not stop_evt.wait(period):
            try:
                self._watchdog_scan()
            except Exception:  # pragma: no cover  # stlint: disable=fail-open — a dead watchdog must not take serving down; next scan retries
                from sentinel_tpu.utils.record_log import record_log

                record_log().warning("watchdog scan failed", exc_info=True)

    def _watchdog_scan(self) -> None:
        """Fail CLOSED every dispatched tick whose outputs are not
        host-visible past its stall deadline.  The state handshake with
        the resolver guarantees exactly one side fans the tick out."""
        now = mono_s()
        with self._inflight_lock:
            stalled = [
                p
                for p in self._inflight_ticks.values()
                if p.deadline_mono and now > p.deadline_mono
            ]
        for p in stalled:
            if not self._claim_tick(p, "failed"):
                continue  # resolver won the race; tick is being fanned out
            _C_WATCHDOG.inc()
            OT.event("watchdog.fired")
            FL.note(
                "watchdog.fired",
                n_obj=p.n_obj,
                n_blk=p.n_blk,
                budget_s=self.watchdog_timeout_s,
            )
            ad = self._adaptive
            if ad is not None:
                ad.note_severe()  # a stalled device is overload evidence
            from sentinel_tpu.utils.record_log import record_log

            record_log().error(
                "tick watchdog: device tick stalled past %.2fs — failing "
                "%d object / %d block item(s) CLOSED",
                self.watchdog_timeout_s,
                p.n_obj,
                p.n_blk,
            )
            self._fail_tick(p)
            self._untrack_tick(p)
            p.settled.set()  # the tick loop need not wait for the wedged resolver

    @staticmethod
    def _claim_tick(p: _PendingTick, state: str) -> bool:
        """Atomically move a tick pending→done/failed; False if another
        side already owns the fan-out."""
        with p.state_lock:
            if p.state != "pending":
                return False
            p.state = state
            return True

    def _track_tick(self, p: _PendingTick) -> None:
        if self.watchdog_timeout_s > 0:
            p.deadline_mono = mono_s() + self.watchdog_timeout_s
            with self._inflight_lock:
                self._inflight_ticks[id(p)] = p

    def _untrack_tick(self, p: _PendingTick) -> None:
        if p.deadline_mono:
            with self._inflight_lock:
                self._inflight_ticks.pop(id(p), None)

    # -- rule compilation ---------------------------------------------------

    def _select_features(self, local_flow=None, local_param=None) -> frozenset:
        """Engine stages the current rule set needs.  'nodes' and 'occupy'
        stay on (their unused paths are runtime-gated and near-free);
        'warmup' joins when a warm-up shaper exists."""
        feats = {"nodes", "occupy", "flow"}
        flow = self.flow_rules.get() if local_flow is None else local_flow
        param = (
            (self.param_flow_rules.get() + self.gateway_param_rules.get())
            if local_param is None
            else local_param
        )
        if self.degrade_rules.get():
            feats.add("degrade")
        if param:
            feats.add("param")
        if self.authority_rules.get():
            feats.add("authority")
        if self.system_rules.get() or self._adaptive is not None:
            # the adaptive controller publishes live ceilings through the
            # system columns — the SystemSlot stage must be compiled in
            # even with no static rule loaded
            feats.add("system")
        if any(
            r.control_behavior in (R.CONTROL_WARM_UP, R.CONTROL_WARM_UP_RATE_LIMITER)
            for r in flow
        ):
            feats.add("warmup")
        if self.cfg.sketch_stats and any(
            (rid := self.registry.peek_resource_id(r.resource)) is not None
            and self.registry.is_sketch_id(rid)
            for r in flow
        ):
            feats.add("tail_flow")
        return frozenset(feats)

    def _recompile_rules(self) -> None:
        # cluster-mode rules are enforced via the TokenService, not the local
        # engine — except while degraded, when fallback-enabled cluster rules
        # are compiled in as local rules (fallbackToLocalOrPass semantics)
        with self._cluster_lock:
            changed = self._recompile_rules_noted()
        self._warm_after_recompile(changed)

    def _recompile_rules_noted(self) -> bool:
        """The traced + journaled recompile body; caller holds
        _cluster_lock.  Returns whether the compiled tick changed (the
        caller owns warming it — see _warm_after_recompile)."""
        with OT.TRACER.span("client.recompile_rules"):
            changed = self._recompile_rules_locked()
        FL.note(
            "rules.recompile",
            degraded=self._cluster_degraded_active,
            flow=len(self.flow_rules.get()),
            param=len(self.param_flow_rules.get()),
        )
        return changed

    def _warm_after_recompile(self, changed: bool) -> None:
        """Pre-compile a changed tick for every tick shape, OUTSIDE
        _cluster_lock.  Lock order: _tick_mutex is the canonical OUTER
        lock — tick_once holds it across the serving tick, and the
        sync-mode seg-resize acquires _cluster_lock under it — so the
        warm-up (which needs _tick_mutex to keep first calls of the
        jitted tick from interleaving with serving ticks) must never run
        while _cluster_lock is held.  A recompile that lands between the
        release and the warm just means we warm the newer tick: warming
        is idempotent performance work, never a correctness gate."""
        if changed and self._started and self.mode == "threaded":
            with self._tick_mutex:
                self._warm_shapes()  # stlint: disable=blocking-under-lock — deliberate: warm-up first-calls must exclude serving ticks (concurrent first-calls corrupt the jitted dispatch fastpath); runs post-recompile on the control plane

    def _recompile_rules_locked(self) -> bool:
        flow = self.flow_rules.get()
        local_flow = [r for r in flow if not r.cluster_mode]
        cluster_flow = [r for r in flow if r.cluster_mode]
        self._cluster_flow_by_res = {r.resource: r for r in cluster_flow}

        # rules binding to sketch-tail resources first try PROMOTION into
        # the exact row space (Registry.promote_resource) so they get real
        # windows; whatever stays in the tail enforces approximately.
        # Priority when the reserve is short: rules the TAIL CANNOT SERVE
        # go first — the tail tables enforce only QPS/DEFAULT/DIRECT
        # default-limitApp flow rules (compile_ruleset), so a rate-limiter
        # / THREAD-grade / origin-scoped / RELATE rule or a circuit
        # breaker on a tail id is unenforceable unless it wins an exact
        # row, while a plain QPS rule still has its approximate fallback.
        def _tail_can_serve(r) -> bool:
            # must match engine.compile_ruleset's tail-table admission —
            # including limitApp: the tail table has no origin dimension,
            # so an origin-scoped rule there would throttle ALL origins
            return (
                isinstance(r, R.FlowRule)
                and r.grade == R.GRADE_QPS
                and r.control_behavior == R.CONTROL_DEFAULT
                and r.strategy == R.STRATEGY_DIRECT
                and (r.limit_app or "default") == "default"
            )

        candidates = sorted(
            local_flow + self.degrade_rules.get(),
            key=_tail_can_serve,  # False (must-promote) sorts first
        )
        # promotion routes through the hot-set guard (sketch/hotset.py):
        # a failed promotion leaves the rule on its sketch id, where the
        # tail tables still enforce it conservatively (fail-closed
        # verdicts) and the sketch keeps observing it (fail-open stats)
        from sentinel_tpu.sketch.hotset import guarded_promote

        for r in candidates:
            rid = self.registry.peek_resource_id(r.resource)
            if rid is not None and self.registry.is_sketch_id(rid):
                guarded_promote(self.registry, r.resource)

        param = self.param_flow_rules.get() + self.gateway_param_rules.get()
        local_param = [r for r in param if not r.cluster_mode]
        cluster_param = [r for r in param if r.cluster_mode]
        self._cluster_param_by_res = {r.resource: r for r in cluster_param}

        # host mirror of the authority gate, used ONLY to order cluster
        # token consumption after the authority slot (the reference checks
        # cluster INSIDE FlowSlot, after AuthoritySlot —
        # FlowRuleChecker.java:64-72): a request the authority gate will
        # reject must not consume a cluster token.  The device decision
        # stays authoritative, and the mirror MUST only ever be
        # host-LENIENT-or-equal — a host-stricter verdict would skip the
        # token check on traffic the device then passes, silently opening
        # an unenforced cluster-limit window.  It therefore replicates
        # compile_authority_rules' selection exactly: invalid rules
        # (empty origins) skipped, sketch-id / over-capacity resources
        # skipped, origins capped at KA, LAST rule per resource wins.
        # A rule origin past the intern cap is stored as -1 device-side,
        # where it matches every UN-INTERNED request origin: under WHITE
        # the device then passes traffic whose origin string the mirror
        # would reject, and under BLACK it blocks traffic the mirror would
        # pass — in both cases the mirror must never be the stricter side,
        # so any rule carrying a failed-intern origin drops out of the
        # mirror entirely (never pre-blocks; the device stays
        # authoritative).  ADVICE r5 medium, case (2).
        KA = self.cfg.authority_origins_per_resource
        auth_host: Dict[str, tuple] = {}
        for r in self.authority_rules.get():
            if not r.is_valid():
                continue
            rid = self.registry.resource_id(r.resource)
            if rid is None or rid > self.cfg.max_resources:
                continue
            origins = r.origins()[:KA]
            if any(self.registry.origin_id(o) == -1 for o in origins):
                # failed intern -> device matches -1 wildcard; mirror
                # cannot replicate that, so it must not pre-block AND a
                # later rule must not resurrect a stale entry: last-wins
                # means this rule's outcome for the resource is "no mirror"
                auth_host.pop(r.resource, None)
                continue
            auth_host[r.resource] = (frozenset(origins), r.strategy)
        self._auth_host_rules = auth_host
        # per-resource hash LANES: each entry hashes up to param_dims
        # distinct argument indices; every rule reads the lane its
        # param_idx was assigned (ParamFlowChecker.java:78 paramIdx
        # dispatch).  Gateway rules claim lanes first on shared resources:
        # gateway traffic supplies the (short) parsed gateway vector as
        # args, and a user rule's larger param_idx would index past it.
        # Lane 0 also feeds the cluster token request, so healthy
        # (token-service) and degraded (local-engine) modes throttle the
        # same argument.
        from sentinel_tpu.core.rule_tensors import param_lanes

        lane_map = param_lanes(
            param, self.cfg.param_dims, priority=self.gateway_param_rules.get()
        )
        self._param_lanes_by_res = lane_map

        if self._cluster_degraded_active:
            local_flow += [r for r in cluster_flow if r.cluster_fallback_to_local]
            local_param += cluster_param
        # resource ids under a local hot-parameter rule (tick.resolve's
        # param_rows counts a tick's items that carried a value under one)
        ruled = np.zeros(self.cfg.max_resources + 2, bool)
        for r in local_param:
            rid = self.registry.resource_id(r.resource)  # interned, as the compile below does
            if rid is not None and rid <= self.cfg.max_resources:
                ruled[rid] = True
        self._param_ruled = ruled
        self._paced = any(
            r.control_behavior in (R.CONTROL_RATE_LIMITER, R.CONTROL_WARM_UP_RATE_LIMITER)
            for r in local_flow
        )

        # engine specialization: with the client presorting every batch
        # (see _run_tick), a ruleset of single-lane DIRECT/default-limitApp
        # flow rules qualifies for the cond-free segmented-scan ranks
        # (EngineConfig.seg_static_ranks — the engine still verifies the
        # contract at runtime and fails closed, so a stale flip can never
        # misrank silently)
        import dataclasses as _dc

        static_flip = False
        if self.cfg.seg_effects:
            want_static = (
                self.cfg.flow_rules_per_resource == 1
                and self.cfg.degrade_rules_per_resource == 1
                and self.cfg.param_rules_per_resource == 1
                and all(
                    r.strategy == R.STRATEGY_DIRECT
                    and (r.limit_app or "default") == "default"
                    for r in local_flow
                )
            )
            if want_static != self.cfg.seg_static_ranks:
                self.cfg = _dc.replace(self.cfg, seg_static_ranks=want_static)
                self.registry.cfg = self.cfg
                static_flip = True

        with self._engine_lock:
            self._rules_dev = E.compile_ruleset(
                self.cfg,
                self.registry,
                flow_rules=local_flow,
                degrade_rules=self.degrade_rules.get(),
                param_rules=local_param,
                authority_rules=self.authority_rules.get(),
                system_rules=self.system_rules.get(),
                param_lanes=lane_map,
            )
            # host copy of the STATIC system thresholds: the adaptive
            # controller folds its live ceilings into these (tightest
            # wins), so a recompile resets the base, never the loop
            self._system_static = compile_system_rules(
                self.system_rules.get(), self.cfg
            )
            feats = self._select_features(local_flow, local_param)
            changed = static_flip or feats != self._features
            if changed:
                self._features = feats
                with PROF.expected_retrace("rule-feature-change"):
                    self._tick = self._make_tick(self.cfg, feats)
        # the caller warms the changed tick for EVERY tick shape once
        # _cluster_lock is released (_warm_after_recompile) so the first
        # post-reload entry doesn't eat the XLA compile inside its
        # entry_timeout_s window; warming under _tick_mutex keeps the
        # warm-up ticks from interleaving with the serving loop's tick
        # iterations — two threads first-calling the same jitted tick
        # concurrently corrupts the dispatch fastpath on this jaxlib
        # (observed as 'Execution supplied N buffers but compiled program
        # expected N+1' on subsequent calls)
        return changed

    # -- cluster consultation -----------------------------------------------

    def set_cluster(self, cluster_state_manager) -> None:
        """Attach a ClusterStateManager; cluster-mode rules consult its
        token service (client or embedded server role)."""
        self.cluster = cluster_state_manager

    # attribute-compatible views of the shared hysteresis state (tests
    # and the chaos harness read/poke these directly)
    @property
    def _cluster_degraded_active(self) -> bool:
        return self._cluster_hy.active

    @_cluster_degraded_active.setter
    def _cluster_degraded_active(self, v: bool) -> None:
        self._cluster_hy.active = bool(v)

    @property
    def _cluster_degraded_until(self) -> float:
        return self._cluster_hy.until

    @_cluster_degraded_until.setter
    def _cluster_degraded_until(self, v: float) -> None:
        self._cluster_hy.until = float(v)

    def _enter_cluster_degraded(self) -> None:
        """Token service unreachable: enforce fallback-enabled cluster rules
        locally until a probe succeeds.  Idempotent — extends the cooldown
        without recompiling if already degraded.  The flag flip and the
        recompile are atomic under _cluster_lock so a concurrent exit/enter
        pair can't commit a stale ruleset for the winning state.
        Transition mechanics (cooldown arithmetic, counters, gauge,
        journal) live in the shared adaptive.degrade.Hysteresis."""
        entered = False
        changed = False
        with self._cluster_lock:
            entered = self._cluster_hy.enter(
                cooldown_s=self.cluster_retry_interval_s
            )
            if entered:
                changed = self._recompile_rules_noted()
        if entered:
            self._warm_after_recompile(changed)
            # black box: freeze the state that produced the degrade —
            # outside the lock (bundle capture reads rule managers and
            # the registry) and rate-limited inside trigger()
            FL.FLIGHT.trigger("cluster-degrade-enter")

    def _exit_cluster_degraded(self) -> None:
        changed = False
        exited = False
        with self._cluster_lock:
            exited = self._cluster_hy.exit()
            if exited:
                changed = self._recompile_rules_noted()
        if exited:
            self._warm_after_recompile(changed)

    def _authority_pre_blocks(self, resource: str, origin: str) -> bool:
        """True when the device authority gate is going to reject this
        (resource, origin) — consult BEFORE spending a cluster token so
        the slot order matches the reference (AuthoritySlot before the
        in-FlowSlot cluster check).  Must stay host-lenient-or-equal vs
        the device gate; see the mirror construction in
        _recompile_rules_locked."""
        ent = self._auth_host_rules.get(resource)
        if ent is None:
            return False
        from sentinel_tpu.core.rules import AUTHORITY_BLACK, AUTHORITY_WHITE

        origins, strategy = ent
        listed = bool(origin) and origin in origins
        if strategy == AUTHORITY_WHITE:
            return not listed
        return strategy == AUTHORITY_BLACK and listed

    def _cluster_check(
        self, resource: str, count: int, prioritized: bool, param_value
    ) -> Tuple[int, int]:
        """Consult the token service for cluster-mode rules on `resource`.

        Returns (pre_verdict, wait_ms): pre_verdict > 0 forces a recorded
        block; wait_ms > 0 means SHOULD_WAIT pacing before proceeding.

        Degrade protocol: on transport failure (or namespace-guard overload,
        which the reference also routes to fallbackToLocalOrPass), flip to
        local enforcement of fallback-enabled cluster rules.  The fallback
        rules STAY compiled through re-probes — only a successful probe
        response drops them — so the token server being down never opens an
        unenforced window.

        Slot ordering vs the reference (cluster check inside FlowSlot,
        after AuthoritySlot/SystemSlot — FlowRuleChecker.java:64-72):
        AUTHORITY-doomed requests are filtered host-side before this runs
        (_authority_pre_blocks mirrors the device gate over the same rule
        data), so they consume no token.  The SYSTEM gate alone still
        evaluates after token consumption — its verdict needs the device's
        live window counters, and folding it in would cost a device
        round-trip per request; the residual divergence is bounded by the
        system-blocked share of cluster-ruled traffic and only matters in
        overload (documented).
        """
        from sentinel_tpu.cluster import constants as CC

        frule = self._cluster_flow_by_res.get(resource)
        prule = self._cluster_param_by_res.get(resource)
        if frule is None and prule is None:
            return 0, 0
        degraded = self._cluster_degraded_active
        if degraded and mono_s() < self._cluster_degraded_until:
            return 0, 0  # cooling down; local fallback rules enforce
        svc = self.cluster.token_service() if self.cluster is not None else None
        if svc is None:
            self._enter_cluster_degraded()
            return 0, 0

        wait_total = 0
        responded = False
        if frule is not None:
            try:
                r = svc.request_token(frule.cluster_flow_id, count, prioritized)
            except Exception:  # stlint: disable=fail-open — degrade-to-LOCAL: fallback rules recompile into the engine, enforcement continues (fallbackToLocalOrPass)
                # any service failure degrades, never escapes to the caller
                # (reference wraps acquisition → fallbackToLocalOrPass)
                if frule.cluster_fallback_to_local:
                    self._enter_cluster_degraded()
                return 0, 0
            if r.status in (CC.STATUS_FAIL, CC.STATUS_TOO_MANY_REQUEST):
                # unreachable or overloaded server → local fallback
                if frule.cluster_fallback_to_local:
                    self._enter_cluster_degraded()
                return 0, 0
            # BAD_REQUEST is synthesized client-side without touching the
            # network — it proves nothing about server health, so it must
            # not count as a successful probe out of degraded mode
            if r.status != CC.STATUS_BAD_REQUEST:
                responded = True
            if r.status == CC.STATUS_BLOCKED:
                if degraded:
                    self._exit_cluster_degraded()
                self._fold_remote_deny(resource, r, ERR.BLOCK_FLOW)
                return ERR.BLOCK_FLOW, 0
            if r.status == CC.STATUS_SHOULD_WAIT:
                wait_total += r.wait_ms
            # OK / NO_RULE → proceed

        if prule is not None and param_value is not None:
            try:
                r = svc.request_param_token(prule.cluster_flow_id, count, [param_value])
            except Exception:  # stlint: disable=fail-open — degrade-to-LOCAL: fallback rules recompile into the engine, enforcement continues
                self._enter_cluster_degraded()
                return 0, wait_total
            if r.status in (CC.STATUS_FAIL, CC.STATUS_TOO_MANY_REQUEST):
                self._enter_cluster_degraded()
                return 0, wait_total
            if r.status != CC.STATUS_BAD_REQUEST:
                responded = True
            if r.status == CC.STATUS_BLOCKED:
                if degraded:
                    self._exit_cluster_degraded()
                self._fold_remote_deny(resource, r, ERR.BLOCK_PARAM)
                return ERR.BLOCK_PARAM, 0

        if degraded and responded:
            self._exit_cluster_degraded()  # probe succeeded: back to remote
        return 0, wait_total

    def _fold_remote_deny(self, resource: str, r, default_kind: int, n: int = 1) -> None:
        """Land a cluster deny's provenance in the explain plane.  A v3
        peer's TokenResult carries (kind, rule, observed, limit); an
        embedded service fills the same fields; a pre-v3 peer leaves them
        None and the deny is counted unexplained — coverage stays honest."""
        plane = self.explain_plane
        if plane is None:
            return
        rid = self.registry.peek_resource_id(resource)
        if rid is None or n <= 0:
            return
        if r.prov_kind is None:
            plane.count_unexplained(n)
            return
        from sentinel_tpu.obs.explain import KIND_NAMES

        kind = int(r.prov_kind) if int(r.prov_kind) in KIND_NAMES else default_kind
        for _ in range(n):
            plane.fold_remote(
                rid,
                kind,
                r.prov_rule,
                r.prov_observed,
                r.prov_limit,
                ts_ms=int(self.time.wall_ms()),
            )

    def _cluster_check_bulk(
        self, resource: str, item_counts: List[int], param_value
    ) -> Tuple[List[int], List[int]]:
        """Bulk-path cluster consultation with partial grant: ONE
        request_token_batch roundtrip covers all items of a (resource,
        param) group; granted units are assigned to items greedily in
        order.  Falls back to the same degrade protocol as _cluster_check.
        """
        from sentinel_tpu.cluster import constants as CC

        n = len(item_counts)
        verdicts, waits = [0] * n, [0] * n
        frule = self._cluster_flow_by_res.get(resource)
        prule = self._cluster_param_by_res.get(resource)
        if frule is None and prule is None:
            return verdicts, waits
        degraded = self._cluster_degraded_active
        if degraded and mono_s() < self._cluster_degraded_until:
            return verdicts, waits
        svc = self.cluster.token_service() if self.cluster is not None else None
        if svc is None:
            self._enter_cluster_degraded()
            return verdicts, waits

        responded = False
        if frule is not None:
            total = sum(item_counts)
            try:
                r = svc.request_token_batch(frule.cluster_flow_id, total)
            except Exception:  # stlint: disable=fail-open — r=None routes to the degrade-to-LOCAL branch below
                r = None
            if r is None or r.status in (CC.STATUS_FAIL, CC.STATUS_TOO_MANY_REQUEST):
                if frule.cluster_fallback_to_local:
                    self._enter_cluster_degraded()
                return verdicts, waits
            if r.status != CC.STATUS_BAD_REQUEST:
                responded = True
            if r.status in (CC.STATUS_OK, CC.STATUS_SHOULD_WAIT, CC.STATUS_BLOCKED):
                granted = r.remaining if r.status != CC.STATUS_BLOCKED else 0
                acc = 0
                blocked_items = 0
                for i, c in enumerate(item_counts):
                    if acc + c <= granted:
                        acc += c
                        waits[i] = r.wait_ms
                    else:
                        verdicts[i] = ERR.BLOCK_FLOW
                        blocked_items += 1
                if blocked_items:
                    self._fold_remote_deny(
                        resource, r, ERR.BLOCK_FLOW, n=blocked_items
                    )
            # NO_RULE → proceed

        if prule is not None and param_value is not None:
            live = [i for i in range(n) if verdicts[i] == 0]
            if live:
                total = sum(item_counts[i] for i in live)
                try:
                    r = svc.request_param_token(
                        prule.cluster_flow_id, total, [param_value]
                    )
                except Exception:  # stlint: disable=fail-open — r=None routes to the degrade-to-LOCAL branch below
                    r = None
                if r is None or r.status in (CC.STATUS_FAIL, CC.STATUS_TOO_MANY_REQUEST):
                    self._enter_cluster_degraded()
                    return verdicts, waits
                if r.status != CC.STATUS_BAD_REQUEST:
                    responded = True
                if r.status == CC.STATUS_BLOCKED:
                    for i in live:
                        verdicts[i] = ERR.BLOCK_PARAM
                    self._fold_remote_deny(
                        resource, r, ERR.BLOCK_PARAM, n=len(live)
                    )

        if degraded and responded:
            self._exit_cluster_degraded()
        return verdicts, waits

    # -- public entry API ---------------------------------------------------

    def entry(
        self,
        resource: str,
        count: int = 1,
        prioritized: bool = False,
        args: Optional[Sequence[Any]] = None,
        inbound: bool = False,
        origin: Optional[str] = None,
        deadline_ms: int = 0,
        _ctx: Optional[Tuple[str, str]] = None,
        _push_ctx: bool = True,
    ) -> Entry:
        """Acquire; raises BlockException on rejection (SphU.entry).

        ``deadline_ms`` (absolute engine-time ms, 0 = none): past it the
        caller no longer wants the answer — still-queued expired entries
        shed CLOSED before device dispatch instead of burning a tick.

        ``_ctx``/``_push_ctx`` support entry_async: the context is captured
        in the awaiting task and the push happens there too."""
        if not self.enabled:
            e = _PassThroughEntry(self, resource)
            if _push_ctx:
                CTX.push_entry(e)
            return e
        _t_in = OT.t0()
        ctx_name, ctx_origin = _ctx if _ctx is not None else CTX.current()
        origin = origin if origin is not None else ctx_origin
        # custom-slot hooks: a raised BlockException is carried as a
        # pre-verdict so the ENGINE records the block (stats + block log +
        # SPI, like a custom ProcessorSlot's exception flowing through
        # StatisticSlot) and the ORIGINAL exception is rethrown at the end
        hook_exc: Optional[ERR.BlockException] = None
        for hook in self.entry_hooks:
            try:
                hook(resource, origin, args)
            except ERR.BlockException as he:
                hook_exc = he
                break
        rid = self.registry.resource_id(resource)
        if rid is None:
            e = _PassThroughEntry(self, resource)
            if _push_ctx:
                CTX.push_entry(e)
            return e  # capacity overflow → pass-through (CtSph.java:200)
        if self._bp_armed:
            # backpressure rungs / bounded admission (adaptive/degrade.py):
            # shed CLOSED before any engine or cluster work — but AFTER
            # the pass-through branch (ungoverned traffic never enters
            # the queue, so backpressure must not turn it into a block)
            reason = self._admission_shed(1 if prioritized else 0)
            if reason is not None:
                self._shed_blocked("admit", reason)
                if self.mode == "sync":
                    # the control loop must keep stepping even when every
                    # submission sheds — a sync client's ONLY tick driver
                    # is its submissions, and without this FAIL_CLOSED
                    # could never observe calm and descend
                    self.tick_once()
                raise ERR.SystemBlockException(resource)
        if deadline_ms and deadline_ms < self.time.now_ms():
            self._shed_blocked("admit", "deadline")
            raise ERR.SystemBlockException(resource)

        # ordered custom slots (runtime/slots.py): entry side here; the
        # exit side unwinds on Entry.exit OR on rejection below.  Pass-
        # through entries above skip custom slots entirely — the analog of
        # lookProcessChain returning null (no chain runs at all).
        slot_ctx = None
        entered_slots: list = []
        slot_list = self.slots.snapshot()
        if slot_list and hook_exc is None:
            from sentinel_tpu.runtime.slots import SlotContext, run_entry

            slot_ctx = SlotContext(
                resource=resource,
                origin=origin or "",
                args=args,
                count=count,
                prioritized=prioritized,
                inbound=inbound,
            )
            entered_slots, slot_exc = run_entry(slot_list, slot_ctx)
            if slot_exc is not None:
                hook_exc = slot_exc

        origin_id = self.registry.origin_id(origin) if origin else -1
        origin_node = (
            self.registry.origin_node_row(resource, origin)
            if origin
            else self.cfg.trash_row
        )
        if ctx_name != CTX.DEFAULT_CONTEXT_NAME:
            ctx_node = self.registry.ctx_node_row(resource, ctx_name)
            ctx_id = self.registry.context_id(ctx_name)
        else:
            ctx_node = self.cfg.trash_row
            ctx_id = -1

        M = self.cfg.param_dims
        param_hashes = [0] * M
        param_value = None
        if args:
            # hash one argument per assigned lane (rule param_idx -> lane
            # mapping from rule_tensors.param_lanes); lane 0's value also
            # feeds the cluster token request.  At PARAM_TAIL_OFF and
            # above the ladder sheds the host-side param TAIL work (the
            # hot-param value counters) — enforcement hashes still flow.
            ad = self._adaptive
            tail_off = ad is not None and ad.ladder.level >= DG.PARAM_TAIL_OFF
            lanes = self._param_lanes_by_res.get(resource) or [0]
            for li, idx in enumerate(lanes[:M]):
                if 0 <= idx < len(args):
                    v = args[idx]
                    param_hashes[li] = hash_param(v)
                    if li == 0:
                        param_value = v
                    if not tail_off:
                        self._note_hot_param(resource, v)

        pre_verdict, cluster_wait = 0, 0
        if hook_exc is not None:
            code = getattr(hook_exc, "code", 0)
            pre_verdict = code if code > 0 else ERR.BLOCK_FLOW
        elif (
            self._cluster_flow_by_res or self._cluster_param_by_res
        ) and not self._authority_pre_blocks(resource, origin or ""):
            # authority-doomed requests skip the token service entirely:
            # slot order matches the reference (cluster check lives inside
            # FlowSlot, after AuthoritySlot — FlowRuleChecker.java:64-72)
            pre_verdict, cluster_wait = self._cluster_check(
                resource, count, prioritized, param_value
            )
        if cluster_wait > 0:
            # SHOULD_WAIT: pace before entering (TokenResultStatus.SHOULD_WAIT)
            self.time.sleep_ms(cluster_wait)

        req = AcquireRequest(
            res=rid,
            count=count,
            prio=1 if prioritized else 0,
            origin_id=origin_id,
            origin_node=origin_node,
            ctx_node=ctx_node,
            ctx_name=ctx_id,
            inbound=1 if inbound else 0,
            param_hash=tuple(param_hashes),
            pre_verdict=pre_verdict,
            deadline_ms=int(deadline_ms),
            future=Future(),
            submitted_ns=OT.t0(),
        )
        with self._lock:
            if deadline_ms:
                self._deadlines_live = True
            self._acquires.append(req)

        if self.mode == "sync":
            self.tick_once()
        verdict, wait_ms = req.future.result(timeout=self.entry_timeout_s)
        if req.resolved_ns:
            # the caller's own share of an entry(): req.admit is this call
            # up to the enqueue, req.wake the future being set to this
            # thread running again; both carry the serving tick's id
            if _t_in and req.submitted_ns:
                OT.TRACER.record(
                    "req.admit", _t_in, req.submitted_ns - _t_in, req.tick_id
                )
            OT.TRACER.record(
                "req.wake", req.resolved_ns, OT.now_ns() - req.resolved_ns, req.tick_id
            )

        if verdict not in (ERR.PASS, ERR.PASS_WAIT):
            # the engine already counted the block; here only the
            # observability side-channels fire (block log + extension SPI)
            exc = (
                hook_exc
                if hook_exc is not None
                else ERR.exception_for_verdict(verdict, resource)
            )
            if self.block_log is not None:
                kind_name = rule_slot = None
                if self.explain_plane is not None:
                    from sentinel_tpu.obs.explain import KIND_NAMES

                    kind_name = KIND_NAMES.get(int(verdict))
                    # the resolver folded this tick's explain records
                    # BEFORE resolving our future, so the newest matching
                    # record is this block's provenance
                    rule_slot = self.explain_plane.latest_rule(rid, int(verdict))
                self.block_log.log(
                    self.time.wall_ms(), resource, type(exc).__name__,
                    origin or "", count, kind=kind_name, rule=rule_slot,
                )
            MEXT.safe_dispatch("on_block", resource, count, origin or "", exc, args)
            if entered_slots:
                from sentinel_tpu.runtime.slots import run_exit

                slot_ctx.block_exception = exc
                run_exit(entered_slots, slot_ctx)
            raise exc
        if verdict == ERR.PASS_WAIT and wait_ms > 0:
            self.time.sleep_ms(wait_ms)
        MEXT.safe_dispatch("on_pass", resource, count, origin or "", args)

        e = Entry(
            self,
            resource,
            rid,
            origin_node,
            ctx_node,
            1 if inbound else 0,
            count,
            self.time.now_ms(),
            wait_ms,
            tuple(param_hashes),
        )
        e.slots = entered_slots
        e.slot_ctx = slot_ctx
        if _push_ctx:
            CTX.push_entry(e)
        return e

    def try_entry(self, resource: str, **kw) -> Optional[Entry]:
        """SphO-style boolean variant."""
        try:
            return self.entry(resource, **kw)
        except ERR.BlockException:
            return None

    async def entry_async(self, resource: str, **kw) -> Entry:
        """AsyncEntry analog: the entry handshake (a blocking wait on the
        engine tick, ~ms) runs in an executor so the event loop never
        blocks; raises BlockException like entry().  Exit the returned
        Entry normally — exits are non-blocking (one ring push).

        The caller's context (ContextUtil name/origin) is captured HERE and
        the Entry is pushed onto the AWAITING task's context stack after the
        handshake — run_in_executor does not propagate contextvars, so both
        must happen on this side of the await (AsyncEntry's context capture,
        AsyncEntry.java)."""
        import asyncio
        import functools as _ft

        ctx = CTX.current()
        loop = asyncio.get_running_loop()
        e = await loop.run_in_executor(
            None, _ft.partial(self.entry, resource, _ctx=ctx, _push_ctx=False, **kw)
        )
        CTX.push_entry(e)
        return e

    _HOT_PARAM_CAP = 512

    def _note_hot_param(self, resource: str, value) -> None:
        """Count a parameter value sighting (ParameterMetric's value-keyed
        CacheMap analog, host side, capped with decimation on overflow)."""
        try:
            with self._hot_params_lock:
                counter = self._hot_params.setdefault(resource, {})
                counter[value] = counter.get(value, 0) + 1
                if len(counter) > self._HOT_PARAM_CAP:
                    top = sorted(counter.items(), key=lambda kv: -kv[1])
                    self._hot_params[resource] = dict(top[: self._HOT_PARAM_CAP // 2])
        except TypeError:
            pass  # unhashable param value — not trackable

    def rt_quantiles(self, qs=(0.5, 0.9, 0.99)) -> Dict[float, float]:
        """Service-level inbound RT quantiles over the trailing window
        (ops/rtq.py log-bucket histogram; ~11% bucket resolution)."""
        from sentinel_tpu.ops import rtq as RQ

        rcfg = E.rtq_config(self.cfg)
        now = jnp.int32(self.time.now_ms())
        with self._engine_lock:
            counts = np.asarray(RQ.windowed_counts(self._state.rtq, now, rcfg))
        return RQ.quantiles(counts, qs, rcfg)

    def top_params(self, resource: str, n: int = 16) -> list:
        """[(value, sightings)] — the hottest parameter values seen."""
        with self._hot_params_lock:
            counter = dict(self._hot_params.get(resource, {}))
        return sorted(counter.items(), key=lambda kv: -kv[1])[:n]

    def explain(self, resource: str, limit: int = 0) -> list:
        """Why was ``resource`` blocked?  Newest-first provenance records
        (obs/explain.ExplainRecord) from the device-packed explain section
        plus any cluster deny provenance.  Empty when the plane is off
        (cfg.packed_wire falsy or cfg.explain_k == 0) or nothing was
        blocked.  Accepts a resource name or a raw device id."""
        if self.explain_plane is None:
            return []
        if isinstance(resource, int):
            rid: Optional[int] = resource
        else:
            rid = self.registry.peek_resource_id(resource)
        if rid is None:
            return []
        return self.explain_plane.explain(rid, limit=limit)

    def explain_top_causes(self, n: int = 10) -> list:
        """Most frequent (resource, kind, rule, origin) block causes."""
        if self.explain_plane is None:
            return []
        return self.explain_plane.top_causes(n)

    def explain_coverage(self) -> dict:
        """Blocked-decision explainability: {blocked, explained, frac}."""
        if self.explain_plane is None:
            return {"blocked": 0, "explained": 0, "frac": 1.0}
        return self.explain_plane.coverage()

    def param_lane(self, resource: str, param_idx: int) -> Optional[int]:
        """Hash lane the compile assigned to ``param_idx`` on ``resource``,
        or None if that index holds no lane (rule unenforceable).  Public
        accessor for transports (e.g. the native front door) that must
        hash a value into the same lane the engine reads."""
        lanes = self._param_lanes_by_res.get(resource)
        if not lanes:
            return 0 if param_idx == 0 else None
        try:
            return lanes.index(param_idx)
        except ValueError:
            return None

    def trace(self, exc: BaseException, count: int = 1) -> None:
        e = CTX.current_entry()
        if e is not None:
            e.trace(exc, count)

    def enter_context(self, name: str, origin: str = ""):
        return CTX.enter(name, origin)

    def exit_context(self, token) -> None:
        CTX.exit_ctx(token)

    @contextmanager
    def context(self, name: str, origin: str = ""):
        """Context-manager form of ContextUtil.enter/exit."""
        token = CTX.enter(name, origin)
        try:
            yield
        finally:
            CTX.exit_ctx(token)

    # -- bulk API -----------------------------------------------------------

    def submit_acquire(
        self,
        resource: str,
        count: int = 1,
        prioritized: bool = False,
        inbound: bool = False,
        deadline_ms: int = 0,
    ) -> Optional[Future]:
        """Non-blocking single acquire: queue the request and return its
        Future of (verdict, wait_ms), or None for unknown resources
        (pass-through).  The async surface for event-loop callers (the
        cluster token server) — thousands of in-flight requests coalesce
        into engine micro-batches without a thread each."""
        if not self.enabled:
            return None
        rid = self.registry.resource_id(resource)
        if rid is None:
            return None  # pass-through: never queued, never backpressured
        if self._bp_armed:
            reason = self._admission_shed(1 if prioritized else 0)
            if reason is not None:
                self._shed_blocked("admit", reason)
                if self.mode == "sync":
                    self.tick_once()  # keep the control loop stepping
                f: Future = Future()
                f.set_result((int(ERR.BLOCK_SYSTEM), 0))
                return f
        req = AcquireRequest(
            res=rid,
            count=count,
            prio=1 if prioritized else 0,
            origin_id=-1,
            origin_node=self.cfg.trash_row,
            ctx_node=self.cfg.trash_row,
            ctx_name=-1,
            inbound=1 if inbound else 0,
            param_hash=(0,) * self.cfg.param_dims,
            pre_verdict=0,
            deadline_ms=int(deadline_ms),
            future=Future(),
            submitted_ns=OT.t0(),
        )
        with self._lock:
            if deadline_ms:
                self._deadlines_live = True
            self._acquires.append(req)
        if self.mode == "sync":
            self.tick_once()
        return req.future

    def check_batch(
        self,
        resources: Sequence[str],
        counts: Optional[Sequence[int]] = None,
        origins: Optional[Sequence[str]] = None,
        params: Optional[Sequence[Any]] = None,
        prioritized: Optional[Sequence[bool]] = None,
        inbound: bool = False,
        deadline_ms: int = 0,
    ) -> List[Tuple[int, int]]:
        """Vector acquire: returns [(verdict, wait_ms)] per resource.

        This is the TPU-native surface: N decisions in one tick.
        """
        if not self.enabled:
            return [(ERR.PASS, 0)] * len(resources)
        shed: List[Optional[str]] = [None] * len(resources)
        if self._bp_armed:
            for i in range(len(resources)):
                pr = 1 if (prioritized is not None and prioritized[i]) else 0
                shed[i] = self._admission_shed(pr)
        has_cluster = bool(self._cluster_flow_by_res or self._cluster_param_by_res)
        # cluster consultation happens OUTSIDE self._lock (it may block on a
        # token-server roundtrip, which must not stall the tick thread) and
        # is AGGREGATED: one request_token per distinct (resource, param)
        # group carrying the summed count — the protocol's count field exists
        # exactly for this — instead of one roundtrip per item
        pre_verdicts = [0] * len(resources)
        pre_waits = [0] * len(resources)
        if has_cluster:
            groups: Dict[Tuple[str, Any], List[int]] = {}
            for i, name in enumerate(resources):
                if shed[i] is not None:
                    continue  # shed CLOSED below; must consume no token
                if name in self._cluster_flow_by_res or name in self._cluster_param_by_res:
                    if self._authority_pre_blocks(
                        name, origins[i] if origins else ""
                    ):
                        continue  # engine rejects it; consume no token
                    groups.setdefault((name, params[i] if params else None), []).append(i)
            for (name, pv), idxs in groups.items():
                item_counts = [counts[i] if counts else 1 for i in idxs]
                vs, ws = self._cluster_check_bulk(name, item_counts, pv)
                for j, i in enumerate(idxs):
                    pre_verdicts[i], pre_waits[i] = vs[j], ws[j]
        futures = []
        with self._lock:
            if deadline_ms:
                # armed under the queue lock so the sweep's all-clear
                # check serializes with the items it must cover
                self._deadlines_live = True
            for i, name in enumerate(resources):
                rid = self.registry.resource_id(name)
                if rid is None:
                    # registry capacity exhausted -> contractually a
                    # pass-through (CtSph.java:200); it never enters the
                    # queue, so backpressure must not turn it into a block
                    futures.append(None)
                    continue
                if shed[i] is not None:
                    self._shed_blocked("admit", shed[i])
                    futures.append("shed")
                    continue
                origin = origins[i] if origins else ""
                pv = params[i] if params else None
                req = AcquireRequest(
                    res=rid,
                    count=counts[i] if counts else 1,
                    prio=1 if (prioritized is not None and prioritized[i]) else 0,
                    origin_id=self.registry.origin_id(origin) if origin else -1,
                    origin_node=self.registry.origin_node_row(name, origin)
                    if origin
                    else self.cfg.trash_row,
                    ctx_node=self.cfg.trash_row,
                    ctx_name=-1,
                    inbound=1 if inbound else 0,
                    param_hash=(hash_param(pv),) + (0,) * (self.cfg.param_dims - 1)
                    if pv is not None
                    else (0,) * self.cfg.param_dims,
                    pre_verdict=pre_verdicts[i],
                    deadline_ms=int(deadline_ms),
                    future=Future(),
                    submitted_ns=OT.t0(),
                )
                self._acquires.append(req)
                futures.append(req.future)
        if self.mode == "sync":
            self.tick_once()
        out = []
        for i, f in enumerate(futures):
            if f is None:
                out.append((ERR.PASS, 0))
                continue
            if f == "shed":
                out.append((ERR.BLOCK_SYSTEM, 0))
                continue
            v, w = f.result(timeout=self.entry_timeout_s)
            if pre_waits[i] > 0 and v == ERR.PASS:
                # cluster SHOULD_WAIT pacing surfaces to bulk callers too
                v, w = ERR.PASS_WAIT, w + pre_waits[i]
            out.append((v, w))
        return out

    # -- bulk array API (TPU-native surface) --------------------------------

    def submit_block(
        self,
        res: np.ndarray,
        counts: Optional[np.ndarray] = None,
        prio: Optional[np.ndarray] = None,
        origin_id: Optional[np.ndarray] = None,
        origin_node: Optional[np.ndarray] = None,
        ctx_node: Optional[np.ndarray] = None,
        ctx_name: Optional[np.ndarray] = None,
        inbound: Optional[np.ndarray] = None,
        param_hash: Optional[np.ndarray] = None,
        pre_verdict: Optional[np.ndarray] = None,
        deadline_ms: int = 0,
    ) -> Optional[Future]:
        """Bulk acquire: COLUMN ARRAYS of engine resource ids (from
        ``registry.resource_id``), no per-item Python objects.  Returns a
        Future of (verdicts int8 [n], waits int32 [n]) in submission
        order; blocks larger than the batch size span multiple ticks.

        This is the product bulk path — the same batch assembly, host
        presort, engine tick, and verdict fan-out that serves ``entry()``,
        minus the per-request object overhead the reference also avoids
        in its hot loop.

        Done-callbacks on the returned future must be NON-BLOCKING in
        threaded mode: they may submit more work (submit_block /
        submit_completion_block), but a blocking entry()/check_batch_ids
        inside a callback waits on a tick the busy tick thread can't run
        and stalls traffic until its timeout (see _tick_mutex)."""
        if not self.enabled:
            return None
        res = np.ascontiguousarray(res, dtype=np.int32)
        n = len(res)
        if self._bp_armed:
            reason = self._admission_shed(1)  # blocks shed only on hard limits
            if reason in ("fail_closed", "queue_full", "chaos"):
                self._shed_blocked("admit", reason, n)
                if self.mode == "sync":
                    self.tick_once()  # keep the control loop stepping
                f: Future = Future()
                f.set_result(
                    (np.full(n, ERR.BLOCK_SYSTEM, np.int8), np.zeros(n, np.int32))
                )
                return f
        # negative ids would wrap in scatter paths — sanitize to trash
        if (res < 0).any():
            res = np.where(res < 0, np.int32(self.cfg.trash_row), res)

        def col(x):
            if x is None:
                return None
            x = np.ascontiguousarray(x, dtype=np.int32)
            assert len(x) == n
            return x

        blk = ArrayBlock(
            res=res,
            count=col(counts),
            prio=col(prio),
            origin_id=col(origin_id),
            origin_node=col(origin_node),
            ctx_node=col(ctx_node),
            ctx_name=col(ctx_name),
            inbound=col(inbound),
            param_hash=(
                np.ascontiguousarray(param_hash, dtype=np.int32)
                if param_hash is not None
                else None
            ),
            pre_verdict=col(pre_verdict),
            deadline_ms=int(deadline_ms),
            future=Future(),
            unresolved=n,
            verdicts=np.zeros(n, np.int8),
            waits=np.zeros(n, np.int32),
            submitted_ns=OT.t0(),
        )
        with self._lock:
            if deadline_ms:
                self._deadlines_live = True
            self._acq_blocks.append(blk)
        if self.mode == "sync":
            self.tick_once()
        return blk.future

    def check_batch_ids(
        self,
        res: np.ndarray,
        counts: Optional[np.ndarray] = None,
        timeout_s: Optional[float] = None,
        **cols,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Blocking form of submit_block: (verdicts, waits) arrays."""
        fut = self.submit_block(res, counts=counts, **cols)
        if fut is None:
            n = len(res)
            return np.full(n, ERR.PASS, np.int8), np.zeros(n, np.int32)
        return fut.result(timeout=timeout_s or self.entry_timeout_s)

    def submit_completion_block(
        self,
        res: np.ndarray,
        rt: np.ndarray,
        success: Optional[np.ndarray] = None,
        error: Optional[np.ndarray] = None,
        inbound: Optional[np.ndarray] = None,
        origin_node: Optional[np.ndarray] = None,
        ctx_node: Optional[np.ndarray] = None,
        param_hash: Optional[np.ndarray] = None,
    ) -> None:
        """Bulk exits for block-acquired traffic: column arrays, queued
        for the next tick (completions are fire-and-forget).  A column the
        caller does not pass is queued as ``None``: it stays absent through
        the drain and the tick's build, which write its default straight
        into the input buffer (_join_completions, _run_tick)."""
        from sentinel_tpu.native.ring import FLAG_COMPLETION, FLAG_INBOUND

        res = np.ascontiguousarray(res, dtype=np.int32)
        n = len(res)

        def col(x, dt=np.int32):
            if x is None:
                return None
            x = np.ascontiguousarray(x, dtype=dt)
            assert len(x) == n
            return x

        inbound = col(inbound)
        flags = None
        if inbound is not None:
            flags = np.where(
                inbound != 0, np.int32(FLAG_COMPLETION | FLAG_INBOUND),
                np.int32(FLAG_COMPLETION),
            )
        aux = [None] * 4
        if param_hash is not None:
            ph = np.ascontiguousarray(param_hash, dtype=np.int32)
            assert len(ph) == n
            aux = [ph[:, k] if k < ph.shape[1] else None for k in range(4)]
        block = (
            res,
            col(success),
            col(origin_node),
            col(ctx_node),
            flags,
            col(rt, np.float32),
            col(error),
            None,  # the tag: nothing reads a completion's
            *aux,
        )
        with self._lock:
            self._comp_blocks.append(block)
        if self.mode == "sync":
            self.tick_once()

    def _submit_completion(self, c: Completion) -> None:
        from sentinel_tpu.native.ring import FLAG_COMPLETION, FLAG_INBOUND

        ph = tuple(c.param_hash) + (0, 0, 0, 0)
        ok = self._comp_ring.push(
            res=c.res,
            count=c.success,
            origin_id=c.origin_node,
            param_hash=c.ctx_node,
            flags=FLAG_COMPLETION | (FLAG_INBOUND if c.inbound else 0),
            rt_ms=c.rt,
            error=c.error,
            aux0=ph[0],
            aux1=ph[1],
            aux2=ph[2],
            aux3=ph[3],
        )
        if not ok:
            with self._lock:
                self._comp_overflow.append(c)
        if self.mode == "sync":
            self.tick_once()

    # -- tick machinery -----------------------------------------------------

    def _tick_loop(self, stop_evt: threading.Event) -> None:
        # stop_evt is captured by argument: a restart swaps self._stop_evt,
        # and an old loop still draining a slow tick must keep observing the
        # event that stop() actually set, not the fresh one.
        interval = self.tick_interval_ms / 1000.0
        while not stop_evt.is_set():
            t0 = mono_s()
            try:
                self.tick_once()
            except Exception:  # pragma: no cover - keep the loop alive  # stlint: disable=fail-open — a dead tick loop strands EVERY pending future; failure is printed, next tick retries
                import traceback

                traceback.print_exc()
            dt = mono_s() - t0
            if dt < interval:
                stop_evt.wait(interval - dt)

    def tick_once(self, now_ms: Optional[int] = None) -> None:
        """Drain queues and run engine ticks until empty.

        Each tick is handed to a resolver at dispatch and at most
        pipeline_depth are unresolved at a time (see _PendingTick); the
        loop always resolves everything before returning idle.  Whole
        iterations serialize on _tick_mutex — sync-mode clients call this
        from request threads."""
        _t_lock = OT.t0()
        with self._tick_mutex:
            self._tick_once_locked(now_ms, _t_lock)  # stlint: disable=blocking-under-lock — the tick IS the device dispatch: _tick_mutex exists to serialize exactly this work; readbacks ride the resolver pool, not this lock
        # hot-set promote/demote loop: one cheap cadence check per
        # iteration, outside the tick mutex (the manager takes its own
        # locks; a promotion-triggered rule recompile must not hold up
        # the serving path's mutex holders)
        hs = self.hotset
        if hs is not None:
            _t_hs = OT.t0()
            if hs.maybe_evaluate() and _t_hs:
                # tick.hotset: a promote/demote pass that ran on this thread
                # (the cadence check alone gets no span); the pass's own
                # hotset.* spans carry the same number as their trace id
                OT.TRACER.record(
                    "tick.hotset", _t_hs, OT.now_ns() - _t_hs, 0,
                    {"pass": hs._eval_n},
                )

    def _await_room(self) -> None:
        """Back-pressure, before the drain so that what arrives during the
        wait still joins the tick that waited: pipeline_depth caps the
        dispatched-but-unresolved ticks, and a tick that would not be full
        goes only behind at most ONE unresolved tick.  The device takes as
        long over a tick as over a full one of its shape (a fixed ladder
        of shapes, ops/wire.tick_shapes: a part-filled tick is padded to
        the next), so with one tick running and one queued behind it the
        device cannot go idle, and a third, dispatched as soon as the host
        had it built, would add a tick's worth of waiting to every request
        in it and to those behind it, while a full one holds requests that
        wait as long in the queue as on the device.  Never a delay of a
        finished verdict; the watchdog's fail-over releases the wait."""
        cap = self._pipeline_depth
        if len(self._pending_ticks) < min(cap, 2):
            return
        self._reap_resolved()
        if cap > 2 and not self._full_tick_queued():
            cap = 2
        over = len(self._pending_ticks) - cap
        if over >= 0:
            self._await_resolved(over + 1, _IDLE_DEPTH)

    def _full_tick_queued(self) -> bool:
        """Whether the acquire queues hold a full batch."""
        room = self.cfg.batch_size
        with self._lock:
            room -= len(self._acquires)
            for blk in self._acq_blocks:
                if room <= 0:
                    break
                room -= len(blk.res) - blk.taken
        return room <= 0

    def _tick_once_locked(self, now_ms: Optional[int], _t_lock: int = 0) -> None:
        while True:
            if self._pipeline_depth > 0:
                self._await_room()
            # tick.drain: this iteration's top to the call of _run_tick.  An
            # iteration that finds nothing opens an idle stretch instead.
            _t_drain = OT.t0()
            tick_id = 0
            if self._deadlines_live:
                # deadline-aware backpressure: work that has already
                # expired is worthless — shed it CLOSED here, BEFORE it
                # costs device dispatch (one queue pass, only while any
                # deadline-carrying submission is live)
                self._sweep_expired(now_ms)
            blocks = []
            with self._lock:
                acq = self._acquires[: self.cfg.batch_size]
                self._acquires = self._acquires[self.cfg.batch_size :]
                # bulk array blocks fill the rest of the batch (API
                # object requests first — they carry per-request futures
                # a human caller is actively blocked on)
                room_blk = self.cfg.batch_size - len(acq)
                while room_blk > 0 and self._acq_blocks:
                    blk = self._acq_blocks[0]
                    take = min(room_blk, len(blk.res) - blk.taken)
                    blocks.append((blk, blk.taken, take))
                    blk.taken += take
                    room_blk -= take
                    if blk.taken >= len(blk.res):
                        self._acq_blocks.pop(0)
                if _t_drain:
                    # the backlog, counted where it is known
                    left_blocks = len(self._acq_blocks)
                    left_items = len(self._acquires) + sum(
                        len(b.res) - b.taken for b in self._acq_blocks
                    )
            if _t_drain and (acq or blocks):
                # req.queue: enqueue -> taken by this tick, one span per
                # object request and per piece of a block.  The tick id is
                # drawn here, before _run_tick, so that these spans and the
                # drain carry the id of the tick that serves them.
                tick_id = OT.TRACER.next_trace_id()
                _taken = OT.now_ns()
                for r in acq:
                    if r.submitted_ns:
                        r.tick_id = tick_id
                        OT.TRACER.record(
                            "req.queue", r.submitted_ns, _taken - r.submitted_ns,
                            tick_id, {"n": 1, "kind": "entry"},
                        )
                for blk, _off, take in blocks:
                    if blk.submitted_ns:
                        OT.TRACER.record(
                            "req.queue", blk.submitted_ns, _taken - blk.submitted_ns,
                            tick_id, {"n": take, "kind": "block"},
                        )
            # Overflow entries spilled when the ring was FULL, so they
            # postdate everything that was in the ring at spill time; the
            # ring must drain first.  Consuming spill only when the ring
            # drains short (= empty) keeps spill after all pre-spill ring
            # entries; it can land after post-spill pushes, a bounded
            # delay in the "processed late" direction only — never a jump
            # ahead — which circuit-breaker probe resolution tolerates.
            comp = self._comp_ring.drain(self.cfg.complete_batch_size)
            n_comp = len(comp[0])
            if n_comp < self.cfg.complete_batch_size and self._comp_overflow:
                with self._lock:
                    spill = self._comp_overflow[: self.cfg.complete_batch_size - n_comp]
                    self._comp_overflow = self._comp_overflow[len(spill) :]
                if spill:
                    comp = tuple(
                        np.concatenate([col, np.asarray(extra, col.dtype)])
                        for col, extra in zip(
                            comp,
                            zip(
                                *[
                                    (s.res, s.success, s.origin_node, s.ctx_node,
                                     4 | (1 if s.inbound else 0), s.rt, s.error, 0)
                                    + (tuple(s.param_hash) + (0, 0, 0, 0))[:4]
                                    for s in spill
                                ]
                            ),
                        )
                    )
                    n_comp += len(spill)
            # bulk completion blocks join after ring + spill.  Under the
            # lock, which every producer and every resolver callback wants,
            # the pieces are only taken off the queue (slices are views);
            # they are joined once it is released
            joined = 0
            if n_comp < self.cfg.complete_batch_size and self._comp_blocks:
                pieces = []
                room_c = self.cfg.complete_batch_size - n_comp
                with self._lock:
                    while room_c > 0 and self._comp_blocks:
                        cb = self._comp_blocks[0]
                        k = len(cb[0])
                        if k <= room_c:
                            pieces.append(cb)
                            self._comp_blocks.pop(0)
                            room_c -= k
                        else:
                            pieces.append(tuple(
                                None if col is None else col[:room_c] for col in cb
                            ))
                            self._comp_blocks[0] = tuple(
                                None if col is None else col[room_c:] for col in cb
                            )
                            room_c = 0
                if pieces:
                    comp, joined = self._join_completions(
                        comp if n_comp else None, pieces
                    )
                    n_comp = len(comp[0])
            fronts = []
            room = self.cfg.batch_size - len(acq) - sum(t for _b, _o, t in blocks)
            # rotate the drain order so a saturated first shard can't
            # starve later shards' rings across ticks
            doors = self._front_doors
            if len(doors) > 1:
                rr = self._door_rr = (getattr(self, "_door_rr", -1) + 1) % len(doors)
                doors = doors[rr:] + doors[:rr]
            for door in doors:
                if room <= 0:
                    break
                row, cnt, prio, corr, kind, a0, a1 = door.drain(room)
                if not len(row):
                    continue
                host = kind >= 3  # concurrent acquire/release
                if host.any():
                    door.handle_host_events(
                        kind[host], cnt[host], corr[host], a0[host], a1[host]
                    )
                eng = ~host
                if eng.any():
                    cols = (
                        row[eng].copy(), cnt[eng].copy(), prio[eng].copy(),
                        corr[eng].copy(), a0[eng].copy(), a1[eng].copy(),
                    )
                    fronts.append((door, cols))
                    room -= len(cols[0])
            if _t_drain and (acq or n_comp or fronts or blocks or now_ms is not None):
                # a tick will run: close the idle stretch it ends, name the
                # wait for the tick mutex (this call's first iteration), and
                # record the drain
                tick_id = tick_id or OT.TRACER.next_trace_id()
                if self._idle_since:
                    OT.TRACER.record(
                        "tick.idle", self._idle_since, _t_drain - self._idle_since,
                        0, _IDLE_INTERVAL,
                    )
                if _t_lock:
                    OT.TRACER.record("tick.lock", _t_lock, _t_drain - _t_lock, tick_id)
                OT.TRACER.record(
                    "tick.drain", _t_drain, OT.now_ns() - _t_drain, tick_id,
                    {
                        "n_obj": len(acq), "n_blk": sum(t for _b, _o, t in blocks),
                        "n_comp": n_comp, "blocks": len(blocks),
                        "left_blocks": left_blocks, "left_items": left_items,
                        # completion columns concatenated (_join_completions)
                        "joined": joined,
                    },
                )
            _t_lock = 0
            if not acq and not n_comp and not fronts and not blocks and now_ms is None:
                if _t_drain and not self._idle_since and self.mode == "threaded":
                    # tick.idle (why="interval") is one span per idle stretch:
                    # from here, through every poll of the queues that finds
                    # nothing and every sleep of _tick_loop between them, to
                    # the drain that finds work.  An idle server records
                    # nothing; a sync-mode caller's absence is not idleness.
                    self._idle_since = _t_drain
                ad = self._adaptive
                if ad is not None and (
                    ad.ladder.level > DG.NORMAL or ad.ceiling != float("inf")
                ):
                    # the closed loop must keep stepping on EMPTY ticks:
                    # at FAIL_CLOSED everything sheds before the engine,
                    # and without this the ladder would never observe the
                    # calm that lets it descend
                    load, cpu = self._sys.sample()
                    self._adaptive_step(ad, self.time.now_ms(), load, cpu)
                # idle: flush any deferred readbacks before returning
                self._drain_resolves()
                return
            self._idle_since = 0
            # while tracing: one host event per tick on the profiler's own
            # clock beside the spans on monotonic_ns, a tie point per tick and
            # the step number that joins a device execution to its tick id
            with (
                jax.profiler.StepTraceAnnotation("sentinel.tick", step_num=tick_id)
                if _t_drain
                else OT.NOOP
            ):
                pending = self._run_tick(
                    acq, comp if n_comp else None, now_ms, fronts=fronts,
                    blocks=blocks, tick_id=tick_id,
                )
            # hand the tick to a resolver NOW: its blocking readback returns
            # when the device is done, and the verdicts fan out at once
            pending.handed_ns = OT.t0()
            if self._pipeline_depth > 0:
                self._pool().submit(self._resolve_tick, pending).add_done_callback(
                    lambda f, p=pending: self._resolution_done(p, f)
                )
                self._pending_ticks.append(pending)
            else:
                self._resolve_tick(pending)
            self._reap_resolved()
            with self._lock:
                more = (
                    bool(self._acquires)
                    or bool(self._acq_blocks)
                    or bool(self._comp_blocks)
                    or bool(self._comp_ring)
                    or bool(self._comp_overflow)
                )
            if not more:
                more = any(d.pending() > 0 for d in self._front_doors)
            if pending.dispatched_ns:
                # tick.handoff: dispatch end -> the hand-over and the sweep
                # of finished resolutions are done; both attrs read the
                # unresolved count (every unresolved tick is with the pool)
                n = len(self._pending_ticks)
                OT.TRACER.record(
                    "tick.handoff", pending.dispatched_ns,
                    OT.now_ns() - pending.dispatched_ns, pending.tick_id,
                    {"pending": n, "resolvers": n},
                )
            if not more:
                # wait out in-flight resolutions; their callbacks may
                # enqueue new work (closed-loop callers) — re-check
                self._drain_resolves()
                with self._lock:
                    more = bool(
                        self._acquires or self._acq_blocks or self._comp_blocks
                    )
                if not more:
                    return
            now_ms = None  # subsequent drain loops use fresh time

    def _join_completions(self, head, pieces) -> Tuple[tuple, int]:
        """One tick's completion columns out of what the ring and the spill
        gave (``head``, None when they gave nothing) and the pieces taken
        off ``_comp_blocks``, in that order.  A column that no part carries
        stays ``None`` (the build writes its default into the input buffer,
        _run_tick); one that some part carries gets the others' default; the
        tag and the lanes past ``param_dims``, which nothing reads, are
        dropped.  One piece alone is handed on as it is.  Returns the columns
        and how many of them were concatenated."""
        if head is None and len(pieces) == 1:
            return pieces[0], 0
        from sentinel_tpu.native.ring import FLAG_COMPLETION

        parts = ([] if head is None else [head]) + pieces
        total = sum(len(p[0]) for p in parts)
        trash = self.cfg.trash_row
        # (res, success, origin_node, ctx_node, flags, rt, error, tag, lanes)
        fills = (None, 1, trash, trash, FLAG_COMPLETION, None, 0, None, 0, 0, 0, 0)
        out: list = []
        joined = 0
        for j, fill in enumerate(fills):
            if (
                j == 7
                or j >= 8 + self.cfg.param_dims
                or all(p[j] is None for p in parts)
            ):
                out.append(None)
                continue
            col = np.empty(total, np.float32 if j == 5 else np.int32)
            o = 0
            for p in parts:
                k = len(p[0])
                col[o : o + k] = fill if p[j] is None else p[j]
                o += k
            out.append(col)
            joined += 1
        return tuple(out), joined

    def _sweep_expired(self, now_ms: Optional[int]) -> None:
        """Shed already-expired queued work CLOSED before device dispatch
        (the admission half of deadline-aware backpressure; the watchdog
        covers work already ON the device)."""
        now = now_ms if now_ms is not None else self.time.now_ms()
        expired: List[AcquireRequest] = []
        exp_blocks: List[ArrayBlock] = []
        with self._lock:
            if any(r.deadline_ms and r.deadline_ms < now for r in self._acquires):
                keep = []
                for r in self._acquires:
                    (expired if r.deadline_ms and r.deadline_ms < now else keep).append(r)
                self._acquires = keep
            if any(
                b.deadline_ms and b.deadline_ms < now for b in self._acq_blocks
            ):
                kept = []
                for b in self._acq_blocks:
                    (exp_blocks if b.deadline_ms and b.deadline_ms < now else kept).append(b)
                self._acq_blocks = kept
            if not any(r.deadline_ms for r in self._acquires) and not any(
                b.deadline_ms for b in self._acq_blocks
            ):
                # no deadline-carrying work left anywhere: disarm the
                # sweep (the flag re-arms under this same lock at the
                # next deadline submission, so nothing can slip between)
                self._deadlines_live = False
        for r in expired:
            if r.future is not None and not r.future.done():
                r.future.set_result((int(ERR.BLOCK_SYSTEM), 0))
        if expired:
            self._shed_blocked("tick", "deadline", len(expired))
        for blk in exp_blocks:
            remaining = len(blk.res) - blk.taken
            blk.verdicts[blk.taken :] = ERR.BLOCK_SYSTEM
            blk.waits[blk.taken :] = 0
            blk.taken = len(blk.res)
            with self._blk_lock:
                blk.unresolved -= remaining
                fire = blk.unresolved <= 0
            if fire and blk.future is not None and not blk.future.done():
                blk.future.set_result((blk.verdicts, blk.waits))
            self._shed_blocked("tick", "deadline", remaining)

    def update_window_shape(
        self,
        sample_count: Optional[int] = None,
        window_ms: Optional[int] = None,
        minute_sample_count: Optional[int] = None,
        minute_window_ms: Optional[int] = None,
    ) -> None:
        """LIVE window reshaping — the IntervalProperty/SampleCountProperty
        analog (node/IntervalProperty.java): swap the engine onto a new
        window grid under the tick lock, MIGRATING current windowed totals
        so admission budgets don't reopen mid-flight (the reference resets
        node metrics instead).  The new tick compiles before the swap
        completes, so serving never waits on XLA."""
        import dataclasses

        changes = {}
        if sample_count is not None:
            changes["second_sample_count"] = int(sample_count)
        if window_ms is not None:
            changes["second_window_ms"] = int(window_ms)
        if minute_sample_count is not None:
            changes["minute_sample_count"] = int(minute_sample_count)
        if minute_window_ms is not None:
            changes["minute_window_ms"] = int(minute_window_ms)
        if not changes:
            return
        new_cfg = dataclasses.replace(self.cfg, **changes)
        if new_cfg == self.cfg:
            return
        self._swap_engine(new_cfg, "window-reshape", **changes)

    def _swap_engine(self, new_cfg, cause: str, **span_attrs) -> None:
        """Compile-then-swap the engine onto ``new_cfg`` LIVE: compile +
        warm the new tick while the old engine keeps serving, then
        migrate state under the engine lock.  Every caller's recompile
        journals as an EXPECTED retrace under ``cause`` — a tuning or
        reshaping session must keep the surprise-retrace count flat."""
        _h = OT.TRACER.begin("client.engine_swap", cause=cause, **span_attrs)
        try:
            with PROF.ledger_owner(self._ledger_name), \
                    PROF.expected_retrace(cause):
                new_tick = self._make_tick(new_cfg, self._features)
            # pre-compile EVERY tick shape against a throwaway state while
            # the old engine keeps serving: XLA compiles take seconds, and a
            # window whose budget migrated would legitimately EXPIRE during
            # that gap — compiling first makes the actual swap a few ms of
            # migration math
            # ledger_owner: the throwaway state re-claims this client's
            # windows/sketch pool entries at the NEW config's sizes — the
            # same shapes the migrated state lands in below
            with PROF.ledger_owner(self._ledger_name):
                dummy = E.init_state(new_cfg)
            dummy = self._warm_tick(new_tick, new_cfg, dummy)
            jax.block_until_ready(dummy.concurrency)
            with self._engine_lock:
                old_cfg = self.cfg
                self._state = E.migrate_state(
                    self._state, old_cfg, new_cfg, self.time.now_ms()
                )
                self.cfg = new_cfg
                self.registry.cfg = new_cfg
                self._tick = new_tick
            # ruleset tensors are capacity-shaped, not window-shaped — the
            # recompile only keeps future rule edits keyed to the active cfg
            self._recompile_rules()
        finally:
            OT.TRACER.end(_h)

    def apply_operating_point(self, op, cause: str = "tuner-retune") -> dict:
        """Apply a ``workload.OperatingPoint`` LIVE — the autotuner's
        actuator.  Host-only knobs (pipeline depth, audit cadence) are
        plain attribute writes with no compiled-program impact; engine
        knobs (batch/sketch shapes) ride the same compile-then-swap path
        as ``update_window_shape``, journaled as one expected retrace
        under ``cause``.  ``op`` is duck-typed (``engine_changes`` +
        the knob attributes) so runtime never imports workload.

        Returns ``{"engine": bool, "host": [knob, ...]}`` describing
        what actually changed (an identity apply returns all-empty)."""
        import dataclasses

        applied = {"engine": False, "host": []}
        depth = getattr(op, "pipeline_depth", None)
        if depth is not None and int(depth) != self._pipeline_depth:
            self._pipeline_depth = max(0, int(depth))
            applied["host"].append("pipeline_depth")
        period = getattr(op, "audit_period", None)
        if (
            period is not None
            and self._audit is not None
            and max(1, int(period)) != self._audit.period
        ):
            self._audit.period = max(1, int(period))
            applied["host"].append("audit_period")
        changes = op.engine_changes(self.cfg)
        if changes:
            self._swap_engine(
                dataclasses.replace(self.cfg, **changes), cause, **changes
            )
            applied["engine"] = True
        return applied

    def register_window_property(self, prop) -> None:
        """Subscribe window shape to a SentinelProperty pushing dicts like
        {"sampleCount": 4, "intervalMs": 1000} — datasource-driven live
        reshaping (SampleCountProperty.register2Property analog)."""
        from sentinel_tpu.datasource.property import SimplePropertyListener

        def apply(v):
            if not v:
                return
            # reference semantics: intervalMs is the TOTAL window and
            # sampleCount re-slices it — missing fields default to the
            # CURRENT values so a partial push never changes the other
            # dimension (a sampleCount-only push must not grow the window)
            cur_total = self.cfg.second_sample_count * self.cfg.second_window_ms
            sc = int(v.get("sampleCount") or self.cfg.second_sample_count)
            iv = int(v.get("intervalMs") or cur_total)
            if sc <= 0 or iv <= 0 or iv % sc:
                return
            self.update_window_shape(sample_count=sc, window_ms=iv // sc)

        prop.add_listener(SimplePropertyListener(apply))

    def attach_front_door(self, door) -> None:
        """Serve a NativeFrontDoor's traffic from this client's tick loop:
        its pending acquires join every engine batch as array lanes and
        their verdicts return through the door's response ring —
        per-request work never touches Python (cluster/front_door.py).
        May be called once per SO_REUSEPORT shard — every attached door is
        drained into the same engine batches."""
        self._front_doors.append(door)

    def pending_acquires(self) -> int:
        """Depth of the un-ticked acquire queue (load-shedding probe)."""
        with self._lock:
            return len(self._acquires)

    def _make_tick(self, cfg, features):
        """The compiled tick this client calls: under packed_wire the
        one-buffer form ``(state, rules, wire_in)``; else the classic
        per-column signature, the golden tests' full-upload reference."""
        return E.make_tick(
            cfg, donate=True, features=features, wire_in=bool(cfg.packed_wire)
        )

    def _upload(self, cfg, wb: WIRE.InputBuffer, t: int, load: float, cpu: float):
        """Send one built tick input.  Returns the tick's arguments after
        ``(state, rules)``, the transfers made and the bytes sent.

        Packed: ONE transfer of the whole buffer, header included, and no
        copy first — the buffer is its _PendingTick's until the tick has
        resolved (see _wire_free).  Classic (packed_wire=False): every
        column and scalar on its own, out of a buffer built for this tick
        alone and never written again."""
        if cfg.packed_wire:
            wb.set_header(t, load, cpu)
            return (jax.device_put(wb.buf),), 1, wb.buf.nbytes
        a = E.AcquireBatch(**{f: jnp.asarray(v) for f, v in wb.acq.items()})
        c = E.CompleteBatch(**{f: jnp.asarray(v) for f, v in wb.comp.items()})
        nbytes = sum(v.nbytes for v in (*wb.acq.values(), *wb.comp.values()))
        return (
            (a, c, jnp.int32(t), jnp.float32(load), jnp.float32(cpu)),
            len(wb.acq) + len(wb.comp) + 3,
            nbytes + 12,
        )

    def _warm_tick(self, new_tick, cfg, state):
        """Run ``new_tick`` on idle inputs once a tick shape against the
        throwaway ``state``, so that serving compiles none."""
        for b, b2 in WIRE.tick_shapes(cfg):
            wb = WIRE.InputBuffer(WIRE.input_layout_for(cfg, b, b2))
            wb.idle_acquire()
            wb.idle_complete()
            args, _puts, _nb = self._upload(cfg, wb, self.time.now_ms(), 0.0, 0.0)
            state, _ = new_tick(state, self._rules_dev, *args)
        return state

    def _sbuf(self, name: str, shape, dt) -> np.ndarray:
        """Current-parity slot of the two-slot host staging buffer for one
        assembly column (see __init__) — caller fills it completely."""
        key = (name, shape, np.dtype(dt).str)
        s = self._stage.get(key)
        if s is None:
            s = self._stage[key] = [np.empty(shape, dt), np.empty(shape, dt)]
            self._ledger_wire()  # cold: new staging slot pair
        return s[self._stage_parity]

    def _ledger_wire(self) -> None:
        """Re-claim the wire pool (obs/profile.LEDGER) after a cold
        allocation: two-slot host staging buffers plus the input buffers
        allocated so far (one a failed tick kept stays counted).  Ledger
        entries change only on allocation events, never per tick."""
        nb = self._wire_bytes + sum(
            s[0].nbytes + s[1].nbytes for s in self._stage.values()
        )
        with PROF.ledger_owner(self._ledger_name):
            PROF.LEDGER.set("wire", "client.staging", nb)

    def _wire_layout(self, cfg, b: int) -> WIRE.WireLayout:
        """Cached packed-wire offset table for (cfg, batch shape)."""
        key = (cfg, b)
        lo = self._wire_layouts.get(key)
        if lo is None:
            lo = self._wire_layouts[key] = WIRE.layout_for(cfg, b)
        return lo

    def _input_buffer(self, cfg, b: int, b2: int) -> WIRE.InputBuffer:
        """The buffer the tick being built writes its input into: under
        packed_wire one lent from _wire_free (a resolved tick's), else,
        and for the classic reference path always, a new one."""
        key = (cfg, b, b2)
        lo = self._wire_layouts.get(key)
        if lo is None:
            lo = self._wire_layouts[key] = WIRE.input_layout_for(cfg, b, b2)
        if cfg.packed_wire:
            free = self._wire_free.setdefault(lo, [])
            if free:
                return free.pop()
            self._wire_bytes += lo.nbytes
            self._ledger_wire()  # cold: one more input buffer
        return WIRE.InputBuffer(lo)

    # -- segment-capacity adaptation ---------------------------------------

    @staticmethod
    def _host_seg_count(cols, pad_to: Optional[int] = None) -> int:
        """Live-segment count the engine will see for these (sorted) key
        columns — key-change heads plus ops/segment.heads_from_keys'
        synthetic BLOCK-boundary heads.  ``pad_to``: columns are about to
        be padded to this length with one equal-key run (trash rows)."""
        from sentinel_tpu.ops import segment as SG

        n = len(cols[0])
        if n == 0:
            return 0
        change = np.zeros(n - 1, dtype=bool)
        for c in cols:
            c = np.asarray(c)
            change |= c[1:] != c[:-1]
        pos = np.arange(1, n)
        segs = 1 + int(np.count_nonzero(change | (pos % SG.BLOCK == 0)))
        if pad_to is not None and pad_to > n:
            # padding: one key change at n + block heads inside the run
            segs += 1 + (pad_to - 1) // SG.BLOCK - n // SG.BLOCK
        return segs

    def _note_seg_count(self, segs: int, b: int, full: int) -> Tuple[int, int]:
        """Track observed live-segment counts of a side of ``b`` rows
        (``full`` at the full tick shape); returns ``(segs, capacity)``,
        which a traced tick.presort carries.  Grows ``seg_u`` (recompile +
        hot-swap the tick) when traffic persistently overflows the
        compacted capacity.  With seg_fallback=True overflow ticks are
        exact but ride the slower per-item kernels, so the resize is a
        performance recovery; with seg_fallback=False it stops the
        fail-closed drops.  seg_u is one capacity for every tick shape, so
        a resize never goes under the full shape's own: a light or middle
        tick's overflow that the full shape's capacity covers starts
        none, and stays exact on the per-item kernels."""
        from sentinel_tpu.ops import engine_seg as ES

        if segs > self._seg_obs_peak:
            self._seg_obs_peak = segs
        cap = ES.seg_capacity(self.cfg, b, full)
        if segs <= cap:
            return segs, cap
        self._seg_over_ticks += 1
        # a tick of this shape that left the compacted path (exact on the
        # per-item kernels, and slow) or, without the fallback, dropped
        OBS.counter(
            "sentinel_seg_overflow_ticks_total",
            "sampled ticks whose live segments exceeded the compacted "
            "capacity of their shape (rows a side)",
            labels={"shape": str(b)},
        ).inc()
        # fail-closed configs resize at the FIRST overflow (drops are
        # happening); fallback configs wait out a transient burst
        threshold = 1 if not self.cfg.seg_fallback else 4
        if self._seg_over_ticks < threshold or self._seg_resizing:
            return segs, cap
        b_full = self.cfg.batch_size
        new_u = min(
            b_full, -(-int(self._seg_obs_peak * 1.25 + 128) // 128) * 128
        )
        if new_u <= ES.seg_capacity(self.cfg, b_full):
            return segs, cap  # the full-shape capacity already covers the peak
        self._seg_resizing = True
        if self.mode == "threaded":
            threading.Thread(
                target=self._resize_seg_u,
                args=(new_u,),
                name="sentinel-seg-resize",
                daemon=True,
            ).start()
        else:
            self._resize_seg_u(new_u)
        return segs, cap

    def _resize_seg_u(self, new_u: int) -> None:
        """Compile a tick with the larger compacted capacity against a
        throwaway state (serving continues on the old tick), then swap —
        the update_window_shape compile-first pattern.

        The background compile is safe on host-attached TPU/CPU (XLA is
        thread-safe); a failure here must never take the serving thread
        down, so everything is caught and logged — the engine keeps
        running on the old capacity (exact via seg_fallback)."""
        import dataclasses

        _C_SEG_RESIZE.inc()
        FL.note("seg.resize", seg_u=int(new_u), old_u=int(self.cfg.seg_u))
        _h = OT.TRACER.begin("engine.seg_resize", seg_u=int(new_u))
        try:
            FP.hit(_FP_SEG_RESIZE)  # chaos: a raise keeps the old capacity
            feats = self._features
            new_cfg = dataclasses.replace(self.cfg, seg_u=int(new_u))
            with PROF.ledger_owner(self._ledger_name), \
                    PROF.expected_retrace("segment-resize"):
                new_tick = self._make_tick(new_cfg, feats)
                dummy = E.init_state(new_cfg)
            dummy = self._warm_tick(new_tick, new_cfg, dummy)
            jax.block_until_ready(dummy.concurrency)  # stlint: disable=host-sync — blocks on a THROWAWAY warmup state; threaded mode runs this off-loop
            with self._cluster_lock, self._engine_lock:
                if (
                    dataclasses.replace(self.cfg, seg_u=new_cfg.seg_u) != new_cfg
                    or feats != self._features
                ):
                    return  # cfg/features moved underneath us; next overflow retries
                self.cfg = new_cfg
                self.registry.cfg = new_cfg
                self._tick = new_tick
                self._seg_over_ticks = 0
        except Exception:  # stlint: disable=fail-open — background compile: on failure serving continues on the old capacity (exact via seg_fallback), logged
            from sentinel_tpu.utils.record_log import record_log

            record_log().warning(
                "seg_u resize to %d failed; serving continues on the old "
                "capacity", new_u, exc_info=True,
            )
        finally:
            OT.TRACER.end(_h)
            self._seg_resizing = False

    def _fold_device_stats(self, s) -> None:
        """Land one device telemetry row (ops/engine.STAT_* float32 vector,
        already host-resident) in the obs registry: verdict-mix counters
        plus window/ceiling gauges.  Runs on the resolver path once per
        tick — a dozen counter bumps against a ms-scale tick."""
        n_pass = int(s[E.STAT_PASS])
        n_wait = int(s[E.STAT_PASS_WAIT])
        if n_pass:
            _C_DEV_VERDICTS["pass"].inc(n_pass)
        if n_wait:
            _C_DEV_VERDICTS["pass_wait"].inc(n_wait)
        for key, idx in (
            ("block_authority", E.STAT_BLOCK_AUTHORITY),
            ("block_system", E.STAT_BLOCK_SYSTEM),
            ("block_param", E.STAT_BLOCK_PARAM),
            ("block_flow", E.STAT_BLOCK_FLOW),
            ("block_degrade", E.STAT_BLOCK_DEGRADE),
        ):
            n = int(s[idx])
            if n:
                _C_DEV_VERDICTS[key].inc(n)
                if idx == E.STAT_BLOCK_PARAM:
                    _C_PARAM_BLOCKED.inc(n)
        n = int(s[E.STAT_FORCED])
        if n:
            _C_DEV_FORCED.inc(n)
        n = int(s[E.STAT_PASS_TOKENS])
        if n:
            _C_DEV_TOKENS["pass"].inc(n)
        n = int(s[E.STAT_BLOCK_TOKENS])
        if n:
            _C_DEV_TOKENS["block"].inc(n)
        _G_DEV_WIN_PASS.set(float(s[E.STAT_WIN_PASS]))
        _G_DEV_MIN_RT.set(_mask_min_rt(float(s[E.STAT_WIN_RT_MIN])))
        _G_DEV_CONC.set(float(s[E.STAT_ENTRY_CONC]))
        _G_DEV_CEIL_UTIL.set(float(s[E.STAT_CEIL_UTIL]))
        _G_DEV_SEG_LIVE.set(float(s[E.STAT_SEG_LIVE]))
        if "degrade" in self._features:
            self._fold_breaker_moves(s)

    def _fold_breaker_moves(self, s) -> None:
        """The row's STAT_CB_* counts: the transition counters and the open
        gauge, and the flight journal's note of the first trip and of the
        first close of each second (the journal keeps rare events: a mesh
        whose services take turns being slow moves breakers every tick)."""
        _G_BREAKERS_OPEN.set(float(s[E.STAT_CB_OPEN_NOW]))
        moved = {to: int(s[idx]) for to, (idx, _c) in _C_BREAKER_MOVES.items()}
        if not any(moved.values()):
            return
        for to, n in moved.items():
            if n:
                _C_BREAKER_MOVES[to][1].inc(n)
        second = int(mono_s())
        for kind, n in (("breaker.trip", moved["open"] + moved["reopen"]),
                        ("breaker.close", moved["closed"])):
            if n and self._breaker_noted.get(kind) != second:
                self._breaker_noted[kind] = second
                FL.FLIGHT.note(kind, n=n, open_now=int(s[E.STAT_CB_OPEN_NOW]), **moved)

    def _record_seg_dropped(self, n: int) -> None:
        """Surface fail-closed segment-overflow drops: counter + block log
        (the reference logs every rejection, EagleEyeLogUtil.java:24-36) +
        rate-limited record-log warning."""
        from sentinel_tpu.ops import engine_seg as ES

        _C_SEG_DROPPED.inc(n)
        with self._blk_lock:
            self.seg_dropped_total += n
        now = self.time.wall_ms()
        if self.block_log is not None:
            self.block_log.log(now, "__seg_overflow__", "SegCapacityDrop", "", n)
        sec = int(now // 1000)
        if sec != self._seg_drop_last_log_s:
            self._seg_drop_last_log_s = sec
            from sentinel_tpu.utils.record_log import record_log

            record_log().warning(
                "segment capacity overflow: %d items FAILED CLOSED this tick "
                "(total %d) — seg_u=%d is undersized for the live traffic; "
                "raise seg_u or set seg_fallback=True",
                n,
                self.seg_dropped_total,
                ES.seg_capacity(self.cfg, self.cfg.batch_size),
            )

    def _audit_attempts(self, rids, now_ms: int):
        """SketchAudit reader: the device sketch's windowed ATTEMPTS
        estimate (PASS + BLOCK planes — exactly the units the engine
        folds: ``acq.count`` per valid entry) for the tracked ids.

        The estimate is jit-cached and the id column padded to the
        audit's fixed K, so steady-state audits dispatch ONE compiled
        executable instead of tracing op-by-op — this read is the whole
        serving-path cost of the audit, amortized over its period."""
        if self._audit_est is None:
            from sentinel_tpu.sketch import impl_for

            impl, scfg = impl_for(self.cfg), self._audit_scfg
            self._audit_est = jax.jit(
                lambda gs, t, r: impl.estimate(gs, t, r, scfg)
            )
        k = len(rids)
        ids = list(rids) + [self.cfg.node_rows] * (self._audit.k - k)
        with self._engine_lock:
            est = np.asarray(
                self._audit_est(
                    self._state.gs,
                    jnp.int32(now_ms),
                    jnp.asarray(ids, jnp.int32),
                )
            )[:k]
        return est[:, W.EV_PASS] + est[:, W.EV_BLOCK]

    def _warm_shapes(self) -> None:
        """Compile the tick for every tick shape (ops/wire.tick_shapes)
        with idle batches so serving never waits on XLA.  One shape
        after the other: tracing and lowering the next shape on a helper
        thread while one loads and runs was measured and cost set-up 5 s
        more than it saved (PERF.md section 6, PR 32)."""
        for shape in WIRE.tick_shapes(self.cfg):
            _tw = _time.perf_counter()
            self._resolve_tick(
                self._run_tick([], None, self.time.now_ms(), shape=shape)
            )
            PROF.RETRACE.observe_compile_ms(
                "engine.tick", (_time.perf_counter() - _tw) * 1000.0
            )

    def _run_tick(
        self,
        acq: List[AcquireRequest],
        comp,  # Optional[Tuple[np.ndarray, ...]] — drained ring columns
        now_ms: Optional[int],
        fronts=(),  # [(door, (row, count, prio, corr, a0, a1)), ...]
        blocks=(),  # [(ArrayBlock, src_off, take), ...]
        tick_id: int = 0,  # drawn by the drain while tracing is on
        shape=None,  # the warm-up's: this tick shape, whatever the rows
    ) -> _PendingTick:
        cfg = self.cfg
        M = cfg.param_dims
        trash = cfg.trash_row
        n_blk = sum(t for _b, _o, t in blocks)
        # flip the staging parity: every _sbuf below hands out the slot
        # the PREVIOUS tick did not touch (double-buffered async safety)
        self._stage_parity ^= 1
        # process-unique trace id correlating this tick's spans across the
        # submitting thread and the resolver pool (per-client counters
        # would collide in multi-client processes sharing the ring)
        tick_id = tick_id or OT.TRACER.next_trace_id()
        # stage brackets (obs/trace.py): _t_asm truthiness is the single
        # flag check; presort time is accumulated separately so the
        # assemble span reports pure column work
        _t_asm = OT.t0()
        _tp0 = 0
        _ns_presort = 0
        _n_a = _n_c = 0  # live rows presorted a side; with _path, span attrs
        _path = ""
        _segs = None  # (segments, capacity) of a side whose segments were counted
        # concatenate every attached door's drained engine items; responses
        # route back per door by slice
        if fronts:
            f_cols = [
                np.concatenate([cols[j] for _d, cols in fronts]) for j in range(6)
            ]
            front = tuple(f_cols)
        else:
            front = None
        n_front = 0 if front is None else len(front[0])

        # adaptive batch shape: the tick runs at the SMALLEST shape of the
        # ladder (ops/wire.tick_shapes: light, middle, full) that holds the
        # live rows of both sides, since the device, the fill of the
        # columns and the upload pay for the padded rows: on the v5e a
        # tick of (256, 256) costs 1.1 ms of device time and one of
        # (131072, 131072) 8.7 ms whatever its fill.  The ladder is FIXED
        # and short, so every shape compiles during start()/rule-load/
        # resize warm-up: the two sides are sized TOGETHER (sizing them
        # independently squares the executables, most of them first
        # compiled inside a serving tick), and an open-ended power-of-two
        # ladder would push multi-second XLA compiles into the serving
        # path at the first load spike.
        B, B2 = shape or WIRE.tick_shape_for(
            cfg, len(acq) + n_blk + n_front, 0 if comp is None else len(comp[0])
        )

        from sentinel_tpu.ops.engine import _use_fused

        clamp = _use_fused(cfg)
        # the segment-compacted engine aggregates per key-run: presort
        # batches by its segment keys (stably — arrival order within equal
        # keys is preserved, so every rank/verdict is bit-identical; see
        # ops/segment.py module docstring) and map verdicts back through
        # the inverse permutation.  Nothing overlaps this: the device
        # idles under it (6.8 ms of a full 131,072-row tick and 2.5 ms of
        # a third-full one on the v5e's host; PERF.md section 5), so
        # native/ring.presort sorts the live rows only, in linear time,
        # and gathers every column in the same call.
        presort = cfg.seg_effects and clamp

        # the tick's input: every column below is written, once, into a
        # view of this one buffer (ops/wire.py), which then crosses whole
        wb = self._input_buffer(cfg, B, B2)
        va, vc = wb.acq, wb.comp

        inv_a = None
        _au_cols = None
        _abs_a = _abs_c = 0  # columns written as a fill a side (span attrs)
        if acq or n_front or n_blk:
            n = len(acq)
            # A column that no row of this tick carries stays absent: it is
            # not staged, not a sort key, not gathered and not downcast; its
            # view of the input buffer is written as the fill below, which
            # gives the same bytes.  An object request and a front-door item
            # carry every column; a block piece carries what its caller
            # passed (most pass res, the origin, inbound and the hot-param
            # lanes and nothing else: SphU.entry(name)'s count 1, no
            # priority, the default context, no pre-verdict).
            every = bool(acq) or bool(n_front)

            def carried(f):
                return every or any(
                    getattr(blk, f) is not None for blk, _o, _t in blocks
                )

            def arr(f, fill, dt, front_col=None, blk_default=None):
                """Column assembly: object requests [0:n], array-block
                slices [n:n+n_blk] (vectorized), front-door items after.
                Assembles into a two-slot staging buffer — the steady
                serving path allocates no per-tick columns."""
                out = self._sbuf("a." + f, B, dt)
                out.fill(fill)
                for i, r in enumerate(acq):
                    out[i] = getattr(r, f)
                o = n
                for blk, off, take in blocks:
                    src = getattr(blk, f)
                    if src is not None:
                        out[o : o + take] = src[off : off + take]
                    elif blk_default is not None and blk_default != fill:
                        out[o : o + take] = blk_default
                    o += take
                if front_col is not None and n_front:
                    out[n + n_blk : n + n_blk + n_front] = front_col
                return out
            def _ph_cols():
                ph = self._sbuf("a.ph", (B, M), np.int32)
                ph.fill(0)
                for i, r in enumerate(acq):
                    t = tuple(r.param_hash)[:M]
                    ph[i, : len(t)] = t
                o = n
                for blk, off, take in blocks:
                    if blk.param_hash is not None:
                        src = blk.param_hash[off : off + take, :M]
                        ph[o : o + take, : src.shape[1]] = src
                    o += take
                if n_front:
                    # native param requests carry pre-hashed lane values
                    ph[n + n_blk : n + n_blk + n_front, 0] = front[4]
                    if M > 1:
                        ph[n + n_blk : n + n_blk + n_front, 1] = front[5]
                return ph

            _n_a = n + n_blk + n_front
            # the carried columns in the order sx_presort gathers them, each
            # at the fill a padding row holds (the layout's); a block piece
            # without counts counts 1 a row
            fills = {
                c.field: c.fill for c in wb.layout.acq if c.field != "param_hash"
            }
            front_col = dict(zip(("res", "count", "prio"), front)) if n_front else {}
            cols = {
                f: arr(
                    f, fill, np.int32, front_col.get(f),
                    blk_default=1 if f == "count" else None,
                )
                for f, fill in fills.items()
                if carried(f)
            }
            # the fused digit planes carry counts exactly up to
            # max_batch_count (EngineConfig docs); clamping at the
            # single batch-build choke point makes that envelope real
            # for every source (API, async, front door, cluster).  The
            # clamp tracks the ACTIVE path (engine._use_fused, incl.
            # the SENTINEL_NO_PALLAS kill switch) — the unfused paths
            # are exact to 65535 and stay unclamped.
            cnt_live = min(1, cfg.max_batch_count) if clamp else 1
            if clamp and "count" in cols:
                np.minimum(cols["count"], cfg.max_batch_count, out=cols["count"])
            if self._audit is not None:
                # shadow-fold input: the CLAMPED columns, pre-presort
                # (fold order is irrelevant — sums) — exactly the units
                # the engine lands in the sketch.  The staging buffers
                # are not reused before observe() runs below this tick.
                # An absent count is its constant (a padding row is on
                # the trash row, which the fold leaves out).
                _au_cols = (
                    cols["res"],
                    cols["count"] if "count" in cols
                    else np.broadcast_to(np.int32(cnt_live), (B,)),
                )
            ph_np = _ph_cols() if carried("param_hash") else None
            if presort:
                _tp = OT.t0()
                # key order matches engine_seg.prepare_acquire's segment
                # keys, res-major (seg ranks also need res nondecreasing);
                # trash-row padding sorts wherever its id lands — padding
                # items are engine no-ops at any position.  One native
                # call (native/ring.presort, with a bit-identical numpy
                # fallback) sorts the live rows, splices the padding run,
                # writes the inverse permutation and gathers the columns
                # straight into the input buffer's views; a narrow column
                # (the gather moves 4-byte rows) goes through an s.* slot
                dst = {
                    f: va[f] if va[f].dtype == np.int32
                    else self._sbuf("s." + f, B, np.int32)
                    for f in cols
                }
                free = self._inv_free.setdefault(B, [])
                inv_a = free.pop() if free else np.empty(B, np.int32)
                _path = RING.presort(
                    # a constant key orders nothing
                    tuple(cols[f] for f in _ACQ_SEG_KEYS if f in cols), _n_a,
                    self._sbuf("s.order", B, np.int32), inv_a,
                    self._sbuf("s.scratch", 2 * max(B, B2), np.uint64),
                    tuple(cols.values()), tuple(dst.values()),
                    ph_np, None if ph_np is None else va["param_hash"],
                )
                cols = dst
                if ph_np is not None:
                    ph_np = va["param_hash"]
                if _tp:
                    _tp0 = _tp0 or _tp
                    _ns_presort += OT.now_ns() - _tp
                # sampled (1-in-8 full-size ticks): a handful of numpy
                # passes over B — resize detection doesn't need every tick
                self._seg_sample_ctr += 1
                if B <= 4096 or (self._seg_sample_ctr & 7) == 0:
                    _segs = self._note_seg_count(
                        self._host_seg_count(
                            tuple(cols[f] for f in _ACQ_SEG_KEYS if f in cols)
                        ),
                        B, cfg.batch_size,
                    )
            # what the presort did not already put there: the narrow
            # columns (flag / verdict-code / clamped-count values fit the
            # wire dtype by construction, so the downcast is exact; the
            # tick widens at its entry), or every column without a presort
            for f, x in cols.items():
                if x is not va[f]:
                    np.copyto(va[f], x, casting="unsafe")
            if ph_np is None:
                va["param_hash"].fill(0)
                _abs_a += 1
            elif ph_np is not va["param_hash"]:
                np.copyto(va["param_hash"], ph_np.T)  # lane by lane
            # the absent columns: a live row's value and a padding row's
            # are one, except count's (a block's rows count 1, padding 0),
            # whose padding run lies where the presort spliced it
            for f, fill in fills.items():
                if f in cols:
                    continue
                _abs_a += 1
                if f != "count":
                    va[f].fill(fill)
                    continue
                va[f].fill(cnt_live)
                if _n_a < B:
                    p0 = _n_a if inv_a is None else int(inv_a[_n_a])
                    va[f][p0 : p0 + B - _n_a] = fill
        else:
            wb.idle_acquire()  # an idle side is its fill
        if comp is not None:
            from sentinel_tpu.native.ring import FLAG_INBOUND

            # a column is None where no completion of this tick carries it
            # (submit_completion_block, _join_completions): it stays out of
            # the presort and its live rows are written as the default
            (res_a, cnt_a, org_a, ctx_a, flags_a, rt_a, err_a, _tag,
             *aux_a) = comp
            aux_a = list(aux_a[:M])
            n = len(res_a)
            if self._adaptive is not None and n:
                # BBR minRT input: this tick's completion RT floor
                self._adaptive.signals.note_completions(n, float(rt_a.min()))
            placed = ()  # the fields whose live rows the presort put in place
            if presort and n > 1:
                _tp = OT.t0()
                # completions carry no futures — sorted for good, no unsort
                # (all completion effects are order-independent sums/minima);
                # only the aux lanes that become param_hash are carried.
                # The 4-byte columns that cross as they are (the aux lanes
                # among them) land in their views; the ones narrowed,
                # clamped or masked on the way (below) go through sc.* slots
                _n_c = n
                slot = lambda i, x: (
                    None if x is None else self._sbuf(f"sc.{i}", B2, np.int32)[:n]
                )
                # (column, where its gathered rows go), as comp orders them
                sides = [
                    (res_a, vc["res"][:n]), (cnt_a, slot(1, cnt_a)),
                    (org_a, vc["origin_node"][:n]), (ctx_a, vc["ctx_node"][:n]),
                    (flags_a, slot(4, flags_a)), (rt_a, vc["rt"][:n]),
                    (err_a, slot(6, err_a)),
                    *((x, vc["param_hash"][k, :n]) for k, x in enumerate(aux_a)),
                ]
                # (a producer may hand a strided or a wider integer column,
                # as submit_completion_block's lanes are: made plain here)
                src = [
                    None if x is None else np.ascontiguousarray(
                        x, np.float32 if j == 5 else np.int32
                    )
                    for j, (x, _d) in enumerate(sides)
                ]
                dst = [None if x is None else d for x, d in sides]
                placed = ("res", "origin_node", "ctx_node", "rt", "param_hash")
                _path_c = RING.presort(
                    # a constant key orders nothing
                    tuple(src[j] for j in (0, 3, 2) if src[j] is not None), n,
                    self._sbuf("sc.order", B2, np.int32)[:n], None,
                    self._sbuf("s.scratch", 2 * max(B, B2), np.uint64),
                    tuple(x for x in src if x is not None),
                    tuple(d for d in dst if d is not None),
                )
                _path = _path or _path_c
                res_a, cnt_a, org_a, ctx_a, flags_a, rt_a, err_a = dst[:7]
                aux_a = dst[7:]
                if _tp:
                    _tp0 = _tp0 or _tp
                    _ns_presort += OT.now_ns() - _tp
                self._seg_sample_ctr_c += 1
                if B2 <= 4096 or (self._seg_sample_ctr_c & 7) == 0:
                    _segs_c = self._note_seg_count(
                        self._host_seg_count(
                            tuple(
                                x for x in (res_a, ctx_a, org_a) if x is not None
                            ),
                            pad_to=B2,
                        ),
                        B2, cfg.complete_batch_size,
                    )
                    _segs = _segs or _segs_c

            # live rows [:n] (unless the presort gathered them in place),
            # then the tail [n:] filled in place; narrow wire dtypes
            # downcast exactly (0/1 flags, counts clamped to
            # max_batch_count, the acquire side's envelope)
            live = {f: v[..., :n] for f, v in vc.items()}

            def put(f, x, absent=None):
                nonlocal _abs_c
                if x is None:
                    live[f][...] = absent
                    _abs_c += 1
                elif f not in placed:
                    np.copyto(live[f], x, casting="unsafe")

            def put_count(f, x, absent):
                if clamp and x is not None:
                    np.minimum(
                        x, cfg.max_batch_count, out=live[f], casting="unsafe"
                    )
                else:
                    put(f, x, min(absent, cfg.max_batch_count) if clamp else absent)

            put("res", res_a)
            put("origin_node", org_a, trash)
            put("ctx_node", ctx_a, trash)
            if flags_a is None:
                put("inbound", None, 0)
            else:
                np.bitwise_and(
                    flags_a, FLAG_INBOUND, out=live["inbound"], casting="unsafe"
                )
            put("rt", rt_a)
            put_count("success", cnt_a, 1)
            put_count("error", err_a, 0)
            lanes = live["param_hash"]  # (M, n): lane by lane
            for k in range(M):
                if k >= len(aux_a) or aux_a[k] is None:
                    lanes[k] = 0
                elif "param_hash" not in placed:
                    np.copyto(lanes[k], aux_a[k], casting="unsafe")
            if all(x is None for x in aux_a):
                _abs_c += 1
            for c in wb.layout.comp:
                vc[c.field][..., n:] = c.fill
        else:
            wb.idle_complete()

        load, cpu = self._sys.sample()
        t = now_ms if now_ms is not None else self.time.now_ms()
        t += FP.skew_ms(_FP_TICK_CLOCK)  # chaos: deterministic clock skew
        _t_put = OT.now_ns() if _t_asm else 0
        batch_args, puts, tx_bytes = self._upload(cfg, wb, t, load, cpu)
        _C_WIRE["tx"].inc(tx_bytes)
        _t_disp = OT.t0()
        if _t_asm:
            OT.stage_ns(
                "tick.assemble",
                _t_asm,
                (_t_disp or OT.now_ns()) - _t_asm - _ns_presort,
                _H_ASSEMBLE,
                trace=tick_id,
                # puts: host-to-device transfers made for this tick's
                # input; put_ns: the time they held this thread
                # absent_a / absent_c: the columns of each side that no row
                # carried, written as a fill
                attrs={
                    "b": B, "b2": B2, "puts": puts, "tx_bytes": tx_bytes,
                    "put_ns": (_t_disp or OT.now_ns()) - _t_put,
                    "absent_a": _abs_a, "absent_c": _abs_c,
                },
            )
            if _ns_presort:
                # path is the acquire side's when it sorted, else the
                # completion side's: radix / small (chosen on the live row
                # count) or numpy (no native library); segs / seg_cap
                # likewise, on the ticks whose segments were counted (all
                # at 4,096 rows a side or fewer, else one in eight)
                attrs = {"n_a": _n_a, "n_c": _n_c, "path": _path}
                if _segs:
                    attrs["segs"], attrs["seg_cap"] = _segs
                OT.stage_ns(
                    "tick.presort", _tp0, _ns_presort, _H_PRESORT, trace=tick_id,
                    attrs=attrs,
                )
        self._count_rotations(int(t))
        au = self._audit
        if au is not None:
            # audit-then-fold (obs/profile.py): the estimate read and the
            # shadow both cover the stream through the PREVIOUS tick —
            # this tick's batch lands on device only in the dispatch
            # below.  Runs outside _engine_lock; fails OPEN internally.
            au.observe(
                int(t),
                _au_cols[0] if _au_cols is not None else None,
                _au_cols[1] if _au_cols is not None else None,
                self._audit_attempts,
            )
        ad = self._adaptive
        if ad is not None:
            # closed loop: signals row -> controller -> ladder + live
            # system-column ceilings (disabled mode: the one check above)
            self._adaptive_step(ad, t, load, cpu)
        with self._engine_lock:
            _t_call = OT.now_ns() if _t_disp else 0
            self._state, out = self._tick(
                self._state, self._rules_dev, *batch_args
            )
            _call_ns = OT.now_ns() - _t_call if _t_disp else 0
        _disp_done = 0
        if _t_disp:
            _disp_done = OT.now_ns()
            # the span also covers the system sample, the rotation count, the
            # audit and the adaptive step above; call_ns is the jit call alone
            OT.stage_ns(
                "tick.dispatch", _t_disp, _disp_done - _t_disp, _H_DISPATCH,
                trace=tick_id, attrs={"call_ns": _call_ns},
            )
        p = _PendingTick(
            acq=acq,
            blocks=list(blocks),
            fronts=list(fronts),
            inv_a=inv_a,
            out=out,
            check_dropped=bool(presort and not cfg.seg_fallback),
            n_obj=len(acq),
            n_blk=n_blk,
            wire_lo=self._wire_layout(cfg, B) if cfg.packed_wire else None,
            wire_in=wb if cfg.packed_wire else None,
            tick_id=tick_id,
            dispatched_ns=_disp_done,
            now_ms=int(t),
        )
        self._track_tick(p)  # watchdog coverage (no-op while disarmed)
        if self._pipeline_depth:
            # start the device→host transfer NOW so it overlaps the next
            # tick's host build + device compute (transfer latency
            # hiding); resolution happens in _resolve_tick.
            # Packed mode prefetches the ONE fused buffer instead.
            try:
                (out.wire if out.wire is not None else out.verdict).copy_to_host_async()
            except Exception:  # stlint: disable=fail-open — prefetch hint only; _resolve_tick still reads the verdict synchronously
                pass
        return p

    def _count_rotations(self, t: int) -> None:
        """Advance the host mirror of the device window-rotation cadence
        for one stamped tick timestamp (see _C_WIN_ROT): a refresh at a
        new bucket rotates iff ``wid - last_rot_wid >= slack_buckets``
        (ops/window.refresh's cond), otherwise slack deferred it."""
        for key, tr in self._rot_track.items():
            wms, g, last_wid, last_rot = tr
            wid = (t & 0xFFFFFFFF) // wms  # uint32 view, as W.wid_of
            if last_wid is None:
                tr[2] = tr[3] = wid
                continue
            if wid == last_wid:
                continue
            if wid - last_rot >= g:
                _C_WIN_ROT[key].inc()
                tr[3] = wid
            else:
                _C_WIN_SLACK[key].inc()
            tr[2] = wid

    def _pool(self):
        """Lazily (re)create the resolver pool — stop() shuts it down."""
        if self._resolver_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._resolver_pool = ThreadPoolExecutor(
                max_workers=min(8, self._pipeline_depth + 2),
                thread_name_prefix="sentinel-resolve",
            )
        return self._resolver_pool

    def _drain_resolves(self) -> None:
        """Wait out every dispatched tick that is not resolved yet.
        _resolve_tick fails its own tick closed instead of raising, so
        this wait cannot abort mid-drain and strand later ticks."""
        self._await_resolved(len(self._pending_ticks), _IDLE_RESOLVERS)

    def _await_resolved(self, n: int, why: dict) -> None:
        """Wait until the ``n`` oldest unresolved ticks are resolved, and
        drop them (and whatever else finished meanwhile) from the books;
        the wait is recorded as ``tick.idle`` with the attrs ``why``."""
        waited, abandoned = self._pending_ticks[:n], 0
        # bounded wait: _resolve_tick fails its own tick closed, so a tick
        # that does not settle means the resolver thread is WEDGED (a
        # readback that never returns), and the caller holds _tick_mutex —
        # an unbounded wait would hang shutdown forever while blocking
        # every admission thread.  One shared deadline across the batch:
        # the ticks resolve concurrently, so waiting entry_timeout_s per
        # tick would pay N timeouts for one wedged device.
        budget = max(2.0 * self.entry_timeout_s, 5.0)
        deadline = mono_s() + budget
        _t_idle = OT.t0() if waited else 0
        for p in waited:
            if not p.settled.wait(max(0.0, deadline - mono_s())):
                abandoned += 1  # still running; its watchdog fails it over
                p.settled.set()  # off the books: never waited for twice
        if _t_idle:
            OT.TRACER.record("tick.idle", _t_idle, OT.now_ns() - _t_idle, 0, why)
        if abandoned:
            from sentinel_tpu.utils.record_log import record_log

            record_log().warning(
                "resolve wait abandoned %d wedged tick(s) after %.1fs",
                abandoned, budget,
            )
        self._reap_resolved()

    @staticmethod
    def _resolution_done(p: _PendingTick, fut: Future) -> None:
        """Done-callback of a tick's pool future (resolver thread)."""
        exc = fut.exception()
        if exc is not None:
            # a lost resolution strands its tick's futures — it must
            # never vanish silently
            from sentinel_tpu.utils.record_log import record_log

            record_log().error("tick resolution failed: %r", exc, exc_info=exc)
        p.settled.set()

    def _reap_resolved(self) -> None:
        """Drop the resolved ticks from the books and publish the count
        that is left: both gauges read it, since every unresolved tick is
        with the resolver pool from its dispatch on."""
        if self._pending_ticks:
            self._pending_ticks = [
                p for p in self._pending_ticks if not p.settled.is_set()
            ]
        # unconditional: the gauges are on the always-on /metrics surface
        # (one float store each — cheaper than the flag test dance would
        # be worth), and an idle loop must never report a stale occupancy
        n = len(self._pending_ticks)
        _G_OCCUPANCY.set(n)
        _G_RESOLVER_Q.set(n)

    def _resolve_tick(self, p: _PendingTick) -> None:
        """Read back one dispatched tick's outputs and fan verdicts out —
        and if ANYTHING in that path raises (backend readback failure,
        chaos injection), fail the tick CLOSED instead of stranding its
        futures: every waiting caller gets a system-block verdict
        immediately rather than an entry_timeout_s hang.  The same
        degrade-never-break contract the seg-overflow path follows."""
        if p.dispatched_ns:
            # tick.resident: dispatch end -> a resolver starts on the tick.
            # The tick is handed over at dispatch (handed_ns), so this is
            # the resolver pool's queue and no more.
            p.resolving_ns = OT.now_ns()
            OT.TRACER.record(
                "tick.resident", p.dispatched_ns, p.resolving_ns - p.dispatched_ns,
                p.tick_id, {"handed_ns": p.handed_ns},
            )
        try:
            self._resolve_tick_inner(p)
        except Exception as exc:  # stlint: disable=fail-open — items fail CLOSED (BLOCK_SYSTEM) below; nothing is admitted or stranded
            if not self._claim_tick(p, "failed"):
                with p.state_lock:
                    if p.state == "failed":
                        return  # the watchdog already failed this tick over
                # state == "done": this thread claimed the fan-out and then
                # broke partway — finish the remaining consumers CLOSED
                # (_fail_tick is partial-fan-out safe)
            _C_RESOLVE_FAILED.inc()
            FL.note(
                "resolve.fail_closed",
                error=f"{type(exc).__name__}: {exc}",
                n_obj=p.n_obj,
                n_blk=p.n_blk,
            )
            from sentinel_tpu.utils.record_log import record_log

            record_log().error(
                "tick resolution failed (%r) — failing %d object / %d block "
                "item(s) CLOSED",
                exc,
                p.n_obj,
                p.n_blk,
                exc_info=True,
            )
            self._fail_tick(p)
        finally:
            self._untrack_tick(p)

    def _fail_tick(self, p: _PendingTick) -> None:
        """Resolve every consumer of a failed tick with a fail-closed
        system-block verdict.  Safe against partial fan-out: futures are
        done-guarded, and block/front-door slices the normal path already
        resolved (p.blocks_done / p.fronts_done) are left untouched — no
        double-decrement of block accounting, no double-respond."""
        v_fail, w_fail = int(ERR.BLOCK_SYSTEM), 0
        for r in p.acq:
            if r.future is not None and not r.future.done():
                r.future.set_result((v_fail, w_fail))
        for blk, off, take in p.blocks[p.blocks_done :]:
            blk.verdicts[off : off + take] = v_fail
            blk.waits[off : off + take] = w_fail
            with self._blk_lock:
                blk.unresolved -= take
                fire = blk.unresolved <= 0
            if fire and blk.future is not None and not blk.future.done():
                blk.future.set_result((blk.verdicts, blk.waits))
            p.blocks_done += 1
        if p.fronts_done < len(p.fronts):
            with self._respond_lock:
                for door, cols in p.fronts[p.fronts_done :]:
                    # advance FIRST: a door whose respond fails here
                    # failed the normal path too — retrying it would
                    # raise out of the fail-closed handler and strand
                    # every other pending tick (_drain_resolves aborts)
                    p.fronts_done += 1
                    k = len(cols[0])
                    try:
                        door.respond(
                            cols[3],
                            np.full(k, v_fail, np.int32),
                            np.zeros(k, np.int32),
                        )
                    except Exception:  # stlint: disable=fail-open — the door transport itself is broken; its clients time out while every OTHER consumer still fails closed
                        from sentinel_tpu.utils.record_log import record_log

                        record_log().error(
                            "front-door respond failed during fail-closed "
                            "fan-out; its clients will time out",
                            exc_info=True,
                        )

    def _resolve_tick_inner(self, p: _PendingTick) -> None:
        """The actual readback + fan-out; may run on a resolver-pool
        thread.  Everything it touches is per-tick (futures, disjoint
        block slices) or lock-protected (drop counters)."""
        FP.hit(_FP_READBACK)  # chaos: a raise fails this tick closed
        FP.hit(_FP_WD_STALL)  # chaos: a delay here stalls the readback —
        # the stand-in for a hung device tick the watchdog must fail over
        out = p.out
        frame = None
        _t_ready = 0
        if p.resolving_ns:
            # tracer on: wait for the device's hand-over apart from the
            # read below, so that tick.wait says which part of it lay after
            # the buffer was ready (copy_ns: the transfer's way home, this
            # thread's wake-up and the unpack's first touch)
            # stlint: disable-next-line=host-sync — the designed readback point, split in two while tracing
            (out.wire if out.wire is not None else out.verdict).block_until_ready()
            _t_ready = OT.now_ns()
        if out.wire is not None:
            # THE single fused readback: verdict bitmap + wait sidecar +
            # telemetry row + timeline top-K + hot-set candidates in one
            # device→host transfer (ops/wire.py layout)
            lo = p.wire_lo
            # stlint: disable-next-line=host-sync — THE designed readback point (fused wire buffer)
            raw = np.asarray(out.wire)
            tl_bytes = lo.tl_rows * lo.tl_cols * 4
            _C_WIRE["rx"].inc(raw.nbytes - tl_bytes)
            if tl_bytes:
                # timeline rows keep their own wire accounting path
                TLM._C_WIRE["rx"].inc(tl_bytes)
            # chaos: mangled bytes must be DETECTED and fail the tick
            # CLOSED — never fan out garbage verdicts.  The pipe covers
            # only the fail-CLOSED main section; the trailing explain
            # section fails OPEN by design and has its own failpoint
            # (obs.explain.decode), so this site's corrupt action stays
            # a deterministic BLOCK_SYSTEM for every seed.
            buf = raw.tobytes()
            split = lo.off_expl * 4
            if lo.expl_k and len(buf) > split:
                data = FP.pipe(_FP_PACKED_DECODE, buf[:split]) + buf[split:]
            else:
                data = FP.pipe(_FP_PACKED_DECODE, buf)
            try:
                frame = WIRE.unpack(data, lo)
            except WIRE.WireDecodeError:
                _C_PACKED_DECODE.inc()
                raise
            verdict = frame.verdict
        else:
            # stlint: disable-next-line=host-sync — THE designed readback point (see class docstring)
            verdict = np.asarray(out.verdict)
            _C_WIRE["rx"].inc(verdict.nbytes)
        if p.dispatched_ns and OT.TRACER.enabled:
            # tick.device is NOT device time: dispatch -> verdicts
            # host-visible = tick.resident (the resolver pool's queue)
            # + tick.wait (this thread blocked on the readback above).  Its
            # name and edges stay: the histogram, the adaptive controller and
            # the req_p99 SLO read it.
            _seen = OT.now_ns()
            if p.resolving_ns:
                OT.TRACER.record(
                    "tick.wait", p.resolving_ns, _seen - p.resolving_ns, p.tick_id,
                    {"copy_ns": _seen - _t_ready},
                )
            OT.stage_ns(
                "tick.device",
                p.dispatched_ns,
                _seen - p.dispatched_ns,
                _H_DEVICE,
                trace=p.tick_id,
            )
        # readback starts AFTER the verdict wait so it measures only the
        # residual host reads (drop count, wait column) — the device span
        # above already owns the blocking verdict transfer
        _t_rb = OT.t0()
        # device telemetry row (ops/engine.STAT_*): one 112-byte transfer in
        # the same readback phase; replaces the host-side verdict re-scans
        # below (PASS_WAIT probe, adaptive pass/block accounting)
        stats = None
        if frame is not None:
            # packed mode: every block below was decoded from the ONE
            # fused transfer — no further device reads on this path
            # (except the wait-sidecar overflow escape hatch further down)
            stats = frame.stats
            if stats is not None:
                self._fold_device_stats(stats)
            if frame.res_stats is not None and self.timeline is not None:
                self.timeline.note_tick(
                    frame.res_stats, p.now_ms,
                    self.time.wall_ms(p.now_ms) - p.now_ms,
                )
            if frame.hot is not None and self.hotset is not None:
                self.hotset.fold(frame.hot)
            if frame.expl is not None and self.explain_plane is not None:
                # BEFORE the verdict fan-out below, so an entry() that
                # raises a BlockException can already look itself up in
                # the provenance rings (block-log key, explain())
                self.explain_plane.ingest_section(frame.expl, ts_ms=p.now_ms)
        else:
            if out.stats is not None:
                stats = np.asarray(out.stats)  # stlint: disable=host-sync — readback point
                _C_WIRE["rx"].inc(stats.nbytes)
                self._fold_device_stats(stats)
            # per-resource timeline matrix (ops/engine.TL_*): K rows in the
            # same readback phase, folded write-behind into per-second
            # records (obs/timeline.py) — its wire cost is accounted under
            # path="timeline" so the transport work sees it separately
            if out.res_stats is not None and self.timeline is not None:
                rs = np.asarray(out.res_stats)  # stlint: disable=host-sync — readback point
                TLM._C_WIRE["rx"].inc(rs.nbytes)
                self.timeline.note_tick(
                    rs, p.now_ms, self.time.wall_ms(p.now_ms) - p.now_ms
                )
            # hot-set candidate rows ([K, 2] id/estimate): folded into the
            # promotion loop's candidate map (sketch/hotset.py)
            if out.hot is not None and self.hotset is not None:
                hot = np.asarray(out.hot)  # stlint: disable=host-sync — readback point
                _C_WIRE["rx"].inc(hot.nbytes)
                self.hotset.fold(hot)
        if p.check_dropped:
            # fail-closed capacity overflow must be LOUD (an engine
            # rejecting traffic because seg_u is undersized is an incident,
            # not a silent counter)
            if frame is not None:
                dropped = frame.seg_dropped  # always in the packed header
            elif stats is not None:
                dropped = int(stats[E.STAT_SEG_DROPPED])
            else:
                dropped = int(np.asarray(out.seg_dropped))  # stlint: disable=host-sync — readback point
                _C_WIRE["rx"].inc(4)
            if dropped:
                self._record_seg_dropped(dropped)
        # the wait column is only nonzero when some verdict is PASS_WAIT
        # (engine zeroes wait for non-passing items) — skip the 4x-larger
        # transfer entirely on the common no-pacing tick.  The device
        # telemetry row answers "any PASS_WAIT?" without scanning the
        # verdict array on the host.
        _rb_attrs = None
        if frame is not None:
            wait = frame.wait
            if self._paced and _t_rb:
                _rb_attrs = {"wait_rows": frame.n_wait}
            if wait is None:
                # > EXC_K waiting rows this tick: the sidecar overflowed — the
                # ONE escape-hatch read outside the fused transfer, a second
                # blocking read of the whole wait column (ops/wire.EXC_K says
                # when that is rare and when it is every tick)
                _t_w = OT.now_ns()
                wait = np.asarray(out.wait_ms)  # stlint: disable=host-sync — sidecar-overflow escape hatch (counted, and timed while tracing)
                _C_WIRE["rx"].inc(wait.nbytes)
                _C_WAIT_OVERFLOW.inc()
                if _t_rb:
                    _rb_attrs = {
                        "wait_rows": frame.n_wait,
                        "wait_read_ns": OT.now_ns() - _t_w,
                        "wait_read_bytes": wait.nbytes,
                    }
        elif stats is not None and not stats[E.STAT_PASS_WAIT] > 0:
            wait = np.zeros(verdict.shape[0], np.int32)
        elif stats is None and not (verdict == ERR.PASS_WAIT).any():
            wait = np.zeros(verdict.shape[0], np.int32)
        else:
            wait = np.asarray(out.wait_ms)  # stlint: disable=host-sync — readback point
            _C_WIRE["rx"].inc(wait.nbytes)
        if _t_rb:
            OT.stage("tick.readback", _t_rb, _H_READBACK, trace=p.tick_id, attrs=_rb_attrs)
        FP.hit(_FP_FANOUT)  # chaos: raise BEFORE any consumer resolves
        if not self._claim_tick(p, "done"):
            return  # the watchdog failed this tick over while we read back
        self._untrack_tick(p)
        _t_res = OT.t0()
        _param_rows = None
        if p.inv_a is not None:
            # map sorted-batch verdicts back to submission order
            verdict = verdict[p.inv_a]
            wait = wait[p.inv_a]
            # the tick is claimed and its permutation spent: lend the
            # buffer to a later tick (see _inv_free)
            inv, p.inv_a = p.inv_a, None
            self._inv_free[inv.shape[0]].append(inv)
        if p.wire_in is not None:
            # its verdicts are read, so the tick ran and its input's
            # transfer is over: the buffer is free for a later tick.  (A
            # tick that fails, by the watchdog or here, never gets this
            # far: it may still run on the device, and keeps its buffer.)
            wb, p.wire_in = p.wire_in, None
            if _t_res:
                _param_rows = self._param_rows(wb)
            self._wire_free[wb.layout].append(wb)
        if self._adaptive is not None:
            if stats is not None:
                # device accounting: valid items ARE the real items (all
                # padding carries the trash row), so the telemetry row
                # replaces the host-side verdict scan
                n_real = int(stats[E.STAT_VALID])
                passed = int(stats[E.STAT_PASS] + stats[E.STAT_PASS_WAIT])
                if n_real:
                    self._adaptive.signals.note_resolved(passed, n_real - passed)
                self._adaptive.signals.note_device_stats(stats)
            else:
                n_real = p.n_obj + p.n_blk + sum(
                    len(cols[0]) for _d, cols in p.fronts
                )
                if n_real:
                    v = verdict[:n_real]
                    passed = int(((v == ERR.PASS) | (v == ERR.PASS_WAIT)).sum())
                    self._adaptive.signals.note_resolved(passed, n_real - passed)
        for i, r in enumerate(p.acq):
            if r.future is not None:
                if r.tick_id:
                    r.resolved_ns = OT.now_ns()
                r.future.set_result((int(verdict[i]), int(wait[i])))
        o = p.n_obj
        for blk, off, take in p.blocks:
            blk.verdicts[off : off + take] = verdict[o : o + take]
            blk.waits[off : off + take] = wait[o : o + take]
            with self._blk_lock:
                blk.unresolved -= take
                fire = blk.unresolved <= 0
            if fire and blk.future is not None:
                blk.future.set_result((blk.verdicts, blk.waits))
            p.blocks_done += 1
            o += take
        if p.fronts:
            off = p.n_obj + p.n_blk
            with self._respond_lock:
                for door, cols in p.fronts:
                    k = len(cols[0])
                    door.respond(
                        cols[3],
                        verdict[off : off + k].astype(np.int32),
                        wait[off : off + k].astype(np.int32),
                    )
                    p.fronts_done += 1
                    off += k
        if _t_res:
            attrs = {"n_obj": p.n_obj, "n_blk": p.n_blk}
            if _param_rows is not None:
                # of the tick's items under a hot-parameter rule, those it blocked
                attrs["param_rows"] = _param_rows
                attrs["param_blocked"] = int(np.count_nonzero(verdict == ERR.BLOCK_PARAM))
            if stats is not None and "degrade" in self._features:
                # the tick's breaker blocks over its items, and its STAT_CB_* row
                attrs.update((k, int(stats[i])) for k, i in _RESOLVE_BREAKER_ATTRS)
            if stats is not None and self._paced:
                # of the tick's items, those admitted with a wait and those refused
                attrs.update((k, int(stats[i])) for k, i in _RESOLVE_PACED_ATTRS)
            OT.stage("tick.resolve", _t_res, _H_RESOLVE, trace=p.tick_id, attrs=attrs)

    def _param_rows(self, wb) -> int:
        """Items of a tick's input that carried a parameter value under a
        local hot-parameter rule (read on the resolver's thread, with the
        tracer on only, from the input buffer before it is lent on)."""
        ruled = self._param_ruled
        res = np.minimum(wb.acq["res"], len(ruled) - 1)
        return int(np.count_nonzero(ruled[res] & (wb.acq["param_hash"] != 0).any(axis=0)))

    def param_store_occupancy(self) -> dict:
        """Cells of the hot-parameter store's newest bucket that count
        something, a depth: how loaded the count-min sketch is.  A whole-
        table read: for a summary after serving, never on the tick thread."""
        from sentinel_tpu.ops import param as P

        with self._engine_lock:
            pcms, epochs = self._state.pcms, self._state.pcms_epochs
        newest = int(np.argmax(np.asarray(epochs)))
        # a wide store's bucket is [depth, Q/128, 128]: count over both tile axes
        bucket = pcms[:, newest] if P.wide(self.cfg) else pcms[:, :, newest]
        counting = jnp.count_nonzero(bucket.reshape(bucket.shape[0], -1), axis=1)
        return {
            "store_cells": int(self.cfg.param_width),
            "store_cells_counting": np.asarray(counting).tolist(),
        }


def _mask_min_rt(v: float) -> float:
    """RT_MIN_INIT (5000) is the 'no completions in window' sentinel
    (every backend maintains per-row minRt exactly — ops/rowmin.py).
    Report 0.0 instead of a phantom 5-second minimum."""
    return 0.0 if v >= W.RT_MIN_INIT else v


class ClientStats:
    """Read-side node statistics (the ClusterNode/StatisticNode getters:
    passQps/blockQps/successQps/exceptionQps/avgRt/curThreadNum)."""

    def __init__(self, client: SentinelClient):
        self._c = client

    def _row_stats(self, row: int) -> Dict[str, float]:
        c = self._c
        sec_cfg = W.WindowConfig(c.cfg.second_sample_count, c.cfg.second_window_ms)
        now = jnp.int32(c.time.now_ms())
        with c._engine_lock:
            st = c._state
            rows = jnp.asarray([row], dtype=jnp.int32)
            counts = np.asarray(W.gather_window_counts(st.win_sec, now, rows, sec_cfg))[0]
            rt_tot, rt_min = W.gather_window_rt(st.win_sec, now, rows, sec_cfg)
            conc = int(np.asarray(st.concurrency[row]))
        interval_s = sec_cfg.interval_ms / 1000.0
        succ = float(counts[W.EV_SUCCESS])
        return {
            "passQps": float(counts[W.EV_PASS]) / interval_s,
            "blockQps": float(counts[W.EV_BLOCK]) / interval_s,
            "successQps": succ / interval_s,
            "exceptionQps": float(counts[W.EV_EXCEPTION]) / interval_s,
            "occupiedPassQps": float(counts[W.EV_OCCUPIED]) / interval_s,
            "avgRt": float(np.asarray(rt_tot)[0]) / succ if succ > 0 else 0.0,
            "minRt": _mask_min_rt(float(np.asarray(rt_min)[0])),
            "curThreadNum": conc,
        }

    def resource(self, name: str) -> Optional[Dict[str, float]]:
        rid = self.registry_peek(name)
        if rid is None:
            return None
        if self._c.registry.is_sketch_id(rid):
            return self._sketch_stats([rid])[0]
        return self._row_stats(rid)

    def origin(self, resource: str, origin: str) -> Optional[Dict[str, float]]:
        """Per-(resource, caller) stats — the ClusterNode.getOriginNode
        read (ClusterBuilderSlot origin rows).  None until that caller has
        been seen (the row is created on first entry with the origin)."""
        row = self._c.registry.origin_row_if_exists(resource, origin)
        return None if row is None else self._row_stats(row)

    def _sketch_stats(self, rids, now_ms: Optional[int] = None) -> list:
        """Windowed CMS estimates for sketch-id resources (the salsa tier
        or the seed ops/gsketch.py, per cfg.sketch_salsa); pass/block are
        small overestimates bounded by the sketch (eps, delta)."""
        from sentinel_tpu.ops import engine as E
        from sentinel_tpu.ops import gsketch as GS
        from sentinel_tpu.sketch import impl_for

        c = self._c
        scfg = E.sketch_config(c.cfg)
        now = jnp.int32(c.time.now_ms() if now_ms is None else now_ms)
        with c._engine_lock:
            est = np.asarray(
                impl_for(c.cfg).estimate(
                    c._state.gs, now, jnp.asarray(rids, jnp.int32), scfg
                )
            )
        interval_s = scfg.interval_ms / 1000.0
        out = []
        for i in range(len(rids)):
            succ = float(est[i, W.EV_SUCCESS])
            out.append(
                {
                    "passQps": float(est[i, W.EV_PASS]) / interval_s,
                    "blockQps": float(est[i, W.EV_BLOCK]) / interval_s,
                    "successQps": succ / interval_s,
                    "exceptionQps": float(est[i, W.EV_EXCEPTION]) / interval_s,
                    "occupiedPassQps": float(est[i, W.EV_OCCUPIED]) / interval_s,
                    "avgRt": float(est[i, GS.RT_PLANE]) / GS.RT_SCALE / succ
                    if succ > 0
                    else 0.0,
                    "minRt": 0.0,
                    "curThreadNum": 0,
                }
            )
        return out

    def snapshot(self, now_ms: Optional[int] = None) -> Dict[str, Dict[str, float]]:
        """Trailing-second stats for ALL registered resources in ONE batched
        device gather — the TPU-shaped walk of the ClusterNode map that
        MetricTimerListener does per second.  Sketch-id resources (beyond
        the exact row space) are served from the global CMS in a second
        batched read."""
        c = self._c
        resources = c.registry.resources()
        if not resources:
            return {}
        # ONE timestamp for the whole snapshot: the read paths may jit-compile
        # on first use (hundreds of ms), and a per-phase `now` would let the
        # trailing window slide between the exact and sketch reads
        now_ms = c.time.now_ms() if now_ms is None else now_ms
        exact = {n: r for n, r in resources.items() if not c.registry.is_sketch_id(r)}
        sketch = {n: r for n, r in resources.items() if c.registry.is_sketch_id(r)}
        out: Dict[str, Dict[str, float]] = {}
        if exact:
            names = list(exact.keys())
            rows_np = np.asarray(list(exact.values()), dtype=np.int32)
            rows = jnp.asarray(rows_np)
            sec_cfg = W.WindowConfig(c.cfg.second_sample_count, c.cfg.second_window_ms)
            now = jnp.int32(now_ms)
            with c._engine_lock:
                st = c._state
                counts = np.asarray(
                    W.gather_window_counts(st.win_sec, now, rows, sec_cfg)
                )
                rt_tot, rt_min = W.gather_window_rt(st.win_sec, now, rows, sec_cfg)
                conc = np.asarray(st.concurrency)[rows_np]
            rt_tot = np.asarray(rt_tot)
            rt_min = np.asarray(rt_min)
            interval_s = sec_cfg.interval_ms / 1000.0
            for i, name in enumerate(names):
                succ = float(counts[i, W.EV_SUCCESS])
                out[name] = {
                    "passQps": float(counts[i, W.EV_PASS]) / interval_s,
                    "blockQps": float(counts[i, W.EV_BLOCK]) / interval_s,
                    "successQps": succ / interval_s,
                    "exceptionQps": float(counts[i, W.EV_EXCEPTION]) / interval_s,
                    "occupiedPassQps": float(counts[i, W.EV_OCCUPIED]) / interval_s,
                    "avgRt": float(rt_tot[i]) / succ if succ > 0 else 0.0,
                    "minRt": _mask_min_rt(float(rt_min[i])),
                    "curThreadNum": int(conc[i]),
                }
        if sketch:
            s_names = list(sketch.keys())
            stats = self._sketch_stats(list(sketch.values()), now_ms=now_ms)
            for name, s in zip(s_names, stats):
                out[name] = s
        return out

    def entry_node(self) -> Dict[str, float]:
        return self._row_stats(self._c.cfg.entry_node_row)

    def registry_peek(self, name: str) -> Optional[int]:
        return self._c.registry.peek_resource_id(name)
