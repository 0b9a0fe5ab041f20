"""The fused decision engine: one jitted tick per micro-batch.

This is the TPU inversion of the reference's per-request slot chain
(CtSph.java:43 → DefaultProcessorSlotChain → NodeSelector/ClusterBuilder/
Log/Statistic/Authority/System/Flow/Degrade slots, SURVEY.md §3.1): instead
of every request walking a pointer chain under CAS, a tick ingests

    AcquireBatch  — entry attempts   (SphU.entry side)
    CompleteBatch — exits            (Entry.exit + Tracer side)

as fixed-shape int32/float32 tensors and produces a verdict per attempt.
Rule evaluation order matches the reference slot order exactly
(Authority −6000 → System −5000 → ParamFlow −3000 → Flow −2000 →
Degrade −1000); the first failing check determines the verdict code.

Within-tick contention is resolved by grouped prefix sums (ops/rank.py)
instead of CAS loops: requests hitting the same decision node are ranked in
arrival order, and each check sees the tokens consumed by its group
predecessors.  This makes single-threshold admission bit-exact with
sequential processing; the documented approximation is that two *different*
rules watching the same node inside one tick each assume the other's
candidates pass (error bounded by one batch).

Everything below is a pure function of (state, rules, batch, now_ms) —
time is an explicit input (see SURVEY.md §4.1).
"""

from __future__ import annotations

import functools
import threading
from typing import NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from sentinel_tpu.core import rule_tensors as RT
from sentinel_tpu.core.config import EngineConfig
from sentinel_tpu.obs import profile as PROF
from sentinel_tpu.obs import trace as OT
from sentinel_tpu.obs.registry import REGISTRY as _OBS
from sentinel_tpu.core.errors import (
    BLOCK_AUTHORITY,
    BLOCK_DEGRADE,
    BLOCK_FLOW,
    BLOCK_PARAM,
    BLOCK_SYSTEM,
    PASS,
    PASS_WAIT,
)
from sentinel_tpu.core.rules import (
    CONTROL_DEFAULT,
    CONTROL_RATE_LIMITER,
    CONTROL_WARM_UP,
    CONTROL_WARM_UP_RATE_LIMITER,
    GRADE_QPS,
    GRADE_THREAD,
    STRATEGY_CHAIN,
    STRATEGY_DIRECT,
    STRATEGY_RELATE,
)
from sentinel_tpu.ops import degrade as D
from sentinel_tpu.ops import fused as FU
from sentinel_tpu.ops import gsketch as GS
from sentinel_tpu.sketch import impl_for as _sketch
from sentinel_tpu.ops import rtq as RQ
from sentinel_tpu.ops import param as P
from sentinel_tpu.ops import rowmin as RM
from sentinel_tpu.ops import tables as T
from sentinel_tpu.ops import window as W
from sentinel_tpu.ops import wire as WIRE
from sentinel_tpu.ops.rank import (
    fast_cumsum,
    grouped_exclusive_cumsum,
    grouped_exclusive_cumsum_small,
    grouped_first,
)

#: max dense key space for the sort-free bucketed rank (ops/rank.py)
_SMALL_RANK_LIMIT = 65536


def _rank(cfg: EngineConfig, keys, values, eligible, key_space: int):
    """Grouped exclusive cumsum, picking the sort-free bucketed kernel when
    the key space is dense and small (the MXU path at scale)."""
    if cfg.use_mxu_tables and key_space <= _SMALL_RANK_LIMIT:
        return grouped_exclusive_cumsum_small(keys, values, eligible, key_space)
    return grouped_exclusive_cumsum(keys, values, eligible)


def _fan(x, K: int):
    """Per-item -> per-(item, rule-lane) fan-out: x[repeat(arange(b), K)]
    expressed as jnp.repeat, which lowers to broadcast+reshape instead of a
    serialized row gather (~1.2 ms at B=128K, measured)."""
    return x if K == 1 else jnp.repeat(x, K, axis=0)


class EngineState(NamedTuple):
    win_sec: W.WindowState  # [node_rows] second window (2 x 500 ms default)
    win_min: W.WindowState  # [node_rows] minute window (60 x 1 s default)
    concurrency: jax.Array  # int32 [node_rows] curThreadNum per node
    # per flow-rule controller state
    latest_passed_ms: jax.Array  # int32 [F+1] RateLimiterController.latestPassedTime (whole engine-ms, as now_ms)
    warmup_tokens: jax.Array  # float32 [F+1] WarmUpController.storedTokens
    warmup_last_s: jax.Array  # int32 [F+1] lastFilledTime (seconds)
    # per-slot admitted counts of the CURRENT second (exact passQps for the
    # warm-up sync — a boundary-moment window read underestimates ~2x)
    warm_acc: jax.Array  # float32 [F+1]
    # prioritized occupy-ahead (OccupiableBucketLeapArray / tryOccupyNext):
    # tokens borrowed against window epoch occ_epoch, folded into that
    # window's pass counts when it becomes current.  Keyed by NODE row
    # (the FutureBucket lives on the node), so RELATE/CHAIN/origin-metered
    # rules borrow like DIRECT ones
    occ_tokens: jax.Array  # float32 [node_rows]
    occ_epoch: jax.Array  # int32 [node_rows]
    # per degrade-rule circuit breaker
    cb_state: jax.Array  # int32 [D+1]
    cb_retry_ms: jax.Array  # int32 [D+1]
    cb_counts: jax.Array  # int32 [D+1, nbc, 3]
    cb_epochs: jax.Array  # int32 [D+1, nbc]
    # hashed (rule,value) param store (ops/param.py v2)
    # (a wide store, P.wide(cfg), keeps its cell axis as (Q/128, 128) tiles:
    # pcms [depth, nbp, Q/128, 128], pconc [depth, Q/128, 128])
    pcms: jax.Array  # int32 [depth, Q, nbp] windowed counts
    pcms_epochs: jax.Array  # int32 [nbp] global bucket epochs
    pconc: jax.Array  # int32 [depth, Q] per-(rule,value) concurrency
    # global observability sketch for tail resources (ops/gsketch.py);
    # [1,1,1,1]-shaped dummy when sketch_stats is off
    gs: GS.SketchState
    # ENTRY-node RT quantile histogram (ops/rtq.py)
    rtq: RQ.RtqState


class RuleSet(NamedTuple):
    flow: RT.FlowRuleTensors
    degrade: RT.DegradeRuleTensors
    param: RT.ParamRuleTensors
    auth: RT.AuthorityTensors
    system: RT.SystemTensors
    tail: RT.TailFlowTensors  # sketch-tail QPS thresholds (rule_tensors.py)


class AcquireBatch(NamedTuple):
    """Entry attempts. Padding items carry res == trash_row."""

    res: jax.Array  # int32 [B] resource id == cluster-node row
    count: jax.Array  # int32 [B] tokens to acquire
    prio: jax.Array  # int32 [B] prioritized flag
    origin_id: jax.Array  # int32 [B] interned origin (-1 none)
    origin_node: jax.Array  # int32 [B] origin stat row (trash if none)
    ctx_node: jax.Array  # int32 [B] context DefaultNode row (trash if none)
    ctx_name: jax.Array  # int32 [B] interned context name (-1 default)
    inbound: jax.Array  # int32 [B] 1 = entrance context (EntranceNode)
    param_hash: jax.Array  # int32 [B, param_dims] hashed hot-param lanes (0 none)
    # host-decided verdict override (0 = none): a cluster token denial is
    # injected here so the device still records the block into the stat
    # windows (the reference counts cluster blocks through StatisticSlot the
    # same way — FlowRuleChecker.passClusterCheck → BlockException path)
    pre_verdict: jax.Array  # int32 [B]


class CompleteBatch(NamedTuple):
    """Exits. Padding items carry res == trash_row."""

    res: jax.Array  # int32 [B2]
    origin_node: jax.Array  # int32 [B2]
    ctx_node: jax.Array  # int32 [B2]
    inbound: jax.Array  # int32 [B2]
    rt: jax.Array  # float32 [B2] response time ms
    success: jax.Array  # int32 [B2] completions (usually 1)
    error: jax.Array  # int32 [B2] business exceptions (Tracer.trace)
    param_hash: jax.Array  # int32 [B2, param_dims] — THREAD-grade release lanes


class TickOutput(NamedTuple):
    verdict: jax.Array  # int8 [B] PASS / BLOCK_* / PASS_WAIT
    wait_ms: jax.Array  # int32 [B] pacing delay for PASS_WAIT
    # items hit by segment-capacity overflow (only ever nonzero with
    # seg_effects=True, seg_fallback=False).  Overflow items FAIL CLOSED:
    # their verdict is forced to BLOCK (the client surfaces them as
    # "FAILED CLOSED", test_seg_overflow_drop_surfaced_and_fails_closed
    # asserts BLOCK_SYSTEM) and only their EFFECTS are dropped-counted
    # here — verdicts are NOT exact for them.  Operators must treat a
    # nonzero value as an incident: resize seg_u or re-enable the
    # fallback; disabling the fallback never trades exactness for
    # openness.  (Plain-int default: a jnp scalar here would initialize
    # the backend at import time.)
    seg_dropped: object = 0  # int32 scalar on the seg path
    # device-resident telemetry row (cfg.device_telemetry): float32
    # [N_STATS], computed on-device from tensors the tick already holds
    # and read back alongside the verdicts — see _device_stats.  None
    # when telemetry is off (the traced program is then unchanged).
    stats: object = None
    # per-resource timeline matrix (cfg.timeline_k): float32
    # [K, TL_COLS] — the top-K resource rows by windowed pass+block with
    # their current second-window bucket's cumulative stats — see
    # _device_res_stats.  None when telemetry or timeline_k is off.
    res_stats: object = None
    # hot-set candidates (cfg.hotset_k + sketch_stats): float32 [K, 2]
    # (sketch resource id, windowed pass estimate) — the top-K SKETCHED
    # ids of this batch by sketch estimate, the device half of the
    # promotion loop (sentinel_tpu/sketch/hotset.py).  Ids stay f32-exact
    # (node_rows + sketch_capacity < 2^24).  None when off (traced
    # program unchanged).
    hot: object = None
    # packed wire buffer (cfg.packed_wire, ops/wire.py): ONE flat uint32
    # array carrying the verdict bitmap, PASS_WAIT sidecar, seg_dropped,
    # and the bitcast stats/res_stats/hot blocks behind a checksummed
    # header — the client's single fused readback.  When set, verdict/
    # stats/res_stats/hot are None (they ride the buffer) and wait_ms
    # stays as the sidecar-overflow escape hatch.
    wire: object = None


# -- device-resident telemetry (TickOutput.stats) ---------------------------
#
# One compact float32 row per tick, summarizing what the host previously
# re-derived by scanning the verdict array and re-reading engine state:
# verdict mix by block reason, admitted/blocked token sums, segment
# occupancy, adaptive-ceiling utilization, and the global ENTRY node's
# sliding-window pass/RT sums.  The window reads are O(1) in window length
# (per-bucket running sums maintained by ops/window.py — the "Efficient
# Summing over Sliding Windows" shape, arXiv 1604.02450), so the whole row
# costs a handful of small reductions against a tick that already streams
# the full batch.  N_STATS * 4 bytes must stay <= 256 (readback budget,
# pinned by tests/test_device_telemetry.py).

STAT_VALID = 0  # non-padding items in the acquire batch
STAT_PASS = 1  # verdict mix over valid items (first-fail slot order)
STAT_PASS_WAIT = 2
STAT_BLOCK_AUTHORITY = 3
STAT_BLOCK_SYSTEM = 4
STAT_BLOCK_PARAM = 5
STAT_BLOCK_FLOW = 6
STAT_BLOCK_DEGRADE = 7
STAT_FORCED = 8  # host-injected pre_verdicts (cluster token denials)
STAT_PASS_TOKENS = 9  # admitted token sum (count column)
STAT_BLOCK_TOKENS = 10
STAT_SEG_DROPPED = 11  # fail-closed seg-overflow items (0 off the seg path)
STAT_SEG_LIVE = 12  # live compacted segments this tick (0 off the seg path)
STAT_WIN_PASS = 13  # ENTRY-node sliding-window sums (post-tick)
STAT_WIN_BLOCK = 14
STAT_WIN_SUCCESS = 15
STAT_WIN_EXCEPTION = 16
STAT_WIN_RT_SUM = 17
STAT_WIN_RT_MIN = 18  # W.RT_MIN_INIT when no completions in window
STAT_ENTRY_CONC = 19  # global inbound concurrency
STAT_CEIL_QPS = 20  # active SystemTensors qps ceiling (-1 = unset)
STAT_CEIL_THREAD = 21  # active SystemTensors max_thread ceiling
STAT_CEIL_UTIL = 22  # windowed ENTRY pass / qps ceiling (0 when unset)
# circuit breakers that moved in this tick, by where they went, and those
# not CLOSED after it (0 where the degrade stage is not compiled)
STAT_CB_OPENED = 23  # CLOSED -> OPEN: the trip rule, on this tick's exits
STAT_CB_HALF_OPENED = 24  # OPEN -> HALF_OPEN: a probe elected among this tick's entries
STAT_CB_CLOSED = 25  # HALF_OPEN -> CLOSED: an exit that was not slow
STAT_CB_REOPENED = 26  # HALF_OPEN -> OPEN: an exit that was
STAT_CB_OPEN_NOW = 27  # OPEN or HALF_OPEN after the tick
N_STATS = 28  # 112 bytes per tick


def _device_stats(
    cfg: EngineConfig,
    state: EngineState,
    rules: RuleSet,
    acq: AcquireBatch,
    verdict,
    valid,
    now_ms,
    seg_dropped,
    seg_live,
    cb_moves=None,
):
    """Build the TickOutput.stats row (see the STAT_* index block).

    Runs AFTER the acquire effects landed, so the window sums include
    this tick — the numbers the next host-side control decision (adaptive
    controller, SLO engine) actually wants."""
    sec_cfg = W.WindowConfig(cfg.second_sample_count, cfg.second_window_ms)
    erow = cfg.entry_node_row
    entry = jnp.array([erow], dtype=jnp.int32)
    # effects for this tick already landed (and refreshed) — run is exact
    ec = W.gather_window_counts_run(state.win_sec, entry)[0]
    ert, emin = W.gather_window_rt_run(state.win_sec, entry)

    def n_of(code):
        return jnp.sum(valid & (verdict == jnp.int8(code)))

    admitted = valid & (
        (verdict == jnp.int8(PASS)) | (verdict == jnp.int8(PASS_WAIT))
    )
    forced = valid & (acq.pre_verdict > 0)
    win_pass = ec[W.EV_PASS].astype(jnp.float32)
    qps = jnp.asarray(rules.system.qps, jnp.float32)
    util = jnp.where(qps > 0, win_pass / jnp.maximum(qps, 1.0), 0.0)
    vals = [
        jnp.sum(valid),
        n_of(PASS),
        n_of(PASS_WAIT),
        n_of(BLOCK_AUTHORITY),
        n_of(BLOCK_SYSTEM),
        n_of(BLOCK_PARAM),
        n_of(BLOCK_FLOW),
        n_of(BLOCK_DEGRADE),
        jnp.sum(forced),
        jnp.sum(jnp.where(admitted, acq.count, 0)),
        jnp.sum(jnp.where(valid & ~admitted, acq.count, 0)),
        seg_dropped,
        seg_live,
        win_pass,
        ec[W.EV_BLOCK],
        ec[W.EV_SUCCESS],
        ec[W.EV_EXCEPTION],
        ert[0],
        emin[0],
        state.concurrency[erow],
        qps,
        jnp.asarray(rules.system.max_thread, jnp.float32),
        util,
        *(cb_moves or (0,) * 5),
    ]
    assert len(vals) == N_STATS
    return jnp.stack(
        [jnp.asarray(v, jnp.float32).reshape(()) for v in vals]
    )


# -- per-resource timeline rows (TickOutput.res_stats) ----------------------
#
# The reference's third observability channel is the per-second,
# per-resource metric log (MetricWriter/MetricSearcher).  Re-deriving it
# host-side would mean re-scanning up to max_resources rows every second;
# instead the tick emits a compact [K, TL_COLS] matrix of the top-K
# hottest resource rows — the FPGA-sketch flow-stat shape (arXiv
# 2504.16896): selection by windowed pass+block over the O(1)
# sliding-window sums already on device (arXiv 1604.02450), stats read
# from the CURRENT window bucket.  Bucket reads are CUMULATIVE, so the
# host's write-behind fold (obs/timeline.py) keeps the LAST read per
# (row, bucket) and lands exact per-second records once the engine clock
# leaves the second — robust to ticks that skip a bucket, lossy only for
# resources that fall out of the top K mid-bucket.

TL_RID = 0  # resource row id (registry maps it back to the name)
TL_PASS = 1  # current-bucket cumulative counts (token-weighted)
TL_BLOCK = 2
TL_SUCCESS = 3
TL_EXCEPTION = 4
TL_RT_SUM = 5  # current-bucket RT sum (ms)
TL_RT_MIN = 6  # current-bucket RT min (W.RT_MIN_INIT = none)
TL_CONC = 7  # live concurrency (gauge, not bucketed)
TL_COLS = 8


def timeline_k(cfg: EngineConfig) -> int:
    """Effective top-K row count (0 = res_stats emission off).  Clamped
    to the resource-row space [1, max_resources) — small test configs
    simply emit every resource row."""
    if not cfg.device_telemetry or cfg.timeline_k <= 0:
        return 0
    return min(int(cfg.timeline_k), cfg.max_resources - 1)


def _device_res_stats(cfg: EngineConfig, state: EngineState, now_ms):
    """Build the TickOutput.res_stats matrix (see the TL_* index block).

    Runs AFTER the effects landed, so the current bucket's cumulative
    counts include this tick.  Stale buckets (no write since the window
    wrapped) read as zero — the epoch check below is the batched form of
    LeapArray's isWindowDeprecated."""
    K = timeline_k(cfg)
    sec_cfg = W.WindowConfig(cfg.second_sample_count, cfg.second_window_ms)
    win = state.win_sec
    wid = W._wid(now_ms, sec_cfg)
    bidx = W.current_index(now_ms, sec_cfg)
    # rank resource rows [1, max_resources) by windowed pass+block; row 0
    # is the global ENTRY node (already covered by the scalar stats row).
    # The effects phase refreshed at this now_ms, so the running sums are
    # exact here — O(rows) instead of the old masked [rows, nb] reduction.
    r = win.run[1 : cfg.max_resources]
    score = r[:, W.EV_PASS] + r[:, W.EV_BLOCK]
    _, idx = jax.lax.top_k(score, K)
    rows = idx.astype(jnp.int32) + 1
    fresh = win.epochs[bidx] == wid
    c = jnp.where(fresh, win.counts[rows, bidx, :], 0)  # [K, NE]
    rt_sum = jnp.where(fresh, win.rt_sum[rows, bidx], 0.0)
    rt_min = jnp.where(
        fresh, win.rt_min[rows, bidx], jnp.float32(W.RT_MIN_INIT)
    )
    cols = [
        rows,
        c[:, W.EV_PASS],
        c[:, W.EV_BLOCK],
        c[:, W.EV_SUCCESS],
        c[:, W.EV_EXCEPTION],
        rt_sum,
        rt_min,
        state.concurrency[rows],
    ]
    assert len(cols) == TL_COLS
    return jnp.stack([jnp.asarray(x, jnp.float32) for x in cols], axis=1)


# ---------------------------------------------------------------------------


def init_state(cfg: EngineConfig) -> EngineState:
    state = _init_state(cfg)
    # memory ledger (obs/profile.py): the hot-parameter store is a pool of
    # its own, the window rings + breaker/rtq state the "windows" pool; the
    # global sketch is accounted by its own init (salsa/gsketch)
    store = PROF.LEDGER.track("param_store", "engine.init_state", (state.pcms, state.pconc))
    PROF.LEDGER.set(
        "windows", "engine.init_state",
        PROF.tree_nbytes(state) - PROF.tree_nbytes(state.gs) - store,
    )
    return state


def _init_state(cfg: EngineConfig) -> EngineState:
    rows = cfg.node_rows
    sec_cfg = W.WindowConfig(cfg.second_sample_count, cfg.second_window_ms)
    min_cfg = W.WindowConfig(cfg.minute_sample_count, cfg.minute_window_ms)
    min_rows = rows if cfg.enable_minute_window else 1
    F = cfg.max_flow_rules
    Dn = cfg.max_degrade_rules
    Pn = cfg.max_param_rules
    return EngineState(
        win_sec=W.init_window(rows, sec_cfg),
        win_min=W.init_window(min_rows, min_cfg),
        concurrency=jnp.zeros((rows,), dtype=jnp.int32),
        latest_passed_ms=jnp.full((F + 1,), LATEST_IDLE_MS, dtype=jnp.int32),
        warmup_tokens=jnp.zeros((F + 1,), dtype=jnp.float32),
        warmup_last_s=jnp.full((F + 1,), -1, dtype=jnp.int32),
        warm_acc=jnp.zeros((F + 1,), dtype=jnp.float32),
        occ_tokens=jnp.zeros((rows,), dtype=jnp.float32),
        occ_epoch=jnp.full((rows,), -1, dtype=jnp.int32),
        cb_state=jnp.zeros((Dn + 1,), dtype=jnp.int32),
        cb_retry_ms=jnp.zeros((Dn + 1,), dtype=jnp.int32),
        cb_counts=jnp.zeros((Dn + 1, cfg.cb_sample_count, 3), dtype=jnp.int32),
        cb_epochs=jnp.full((Dn + 1, cfg.cb_sample_count), -10, dtype=jnp.int32),
        pcms=jnp.zeros(
            P.store_shape(cfg),  # [depth, Q, nb]; [depth, nb, Q/128, 128] when wide
            dtype=jnp.int32,
        ),
        pcms_epochs=jnp.full(
            (cfg.param_sample_count,), -(cfg.param_sample_count + 1), dtype=jnp.int32
        ),
        pconc=jnp.zeros(P.conc_shape(cfg), dtype=jnp.int32),
        gs=_sketch(cfg).init_sketch(sketch_config(cfg))
        if cfg.sketch_stats
        else GS.SketchState(
            counts=jnp.zeros((1, 1, 1, GS.PLANES), jnp.int32),
            epochs=jnp.full((1,), -2, jnp.int32),
        ),
        rtq=RQ.init_rtq(rtq_config(cfg)),
    )


def rtq_config(cfg: EngineConfig) -> RQ.RtqConfig:
    return RQ.RtqConfig(
        sample_count=cfg.second_sample_count,
        window_ms=cfg.second_window_ms,
        max_rt=float(cfg.statistic_max_rt),
    )


def sketch_config(cfg: EngineConfig) -> GS.SketchConfig:
    nb, wms = cfg.sketch_shape
    return GS.SketchConfig(
        sample_count=nb,
        window_ms=wms,
        depth=cfg.sketch_depth,
        width=cfg.sketch_width,
        slack_frac=cfg.sketch_slack_frac,
    )


def hotset_k(cfg: EngineConfig) -> int:
    """Effective hot-candidate row count (0 = TickOutput.hot off)."""
    if not cfg.sketch_stats or cfg.hotset_k <= 0:
        return 0
    return int(cfg.hotset_k)


def _device_hot_candidates(cfg: EngineConfig, state: EngineState, acq, valid, now_ms):
    """Build TickOutput.hot: [K, 2] (sketch id, windowed pass estimate).

    Runs AFTER the acquire effects landed, so the estimate includes this
    tick.  Only ids the batch actually carried can surface — the sketch
    alone cannot be inverted back to ids, so candidate discovery rides
    the traffic stream (the heavy-hitter side channel every CMS deployment
    needs); the host manager folds successive ticks, which covers any
    resource hot enough to matter within one evaluation period."""
    K = min(hotset_k(cfg), acq.res.shape[0])
    SK = _sketch(cfg)
    est = SK.estimate_plane_mxu(
        cfg, state.gs, now_ms, acq.res, W.EV_PASS, sketch_config(cfg)
    )
    score = jnp.where(valid & (acq.res >= cfg.node_rows), est, -1.0)
    v, i = jax.lax.top_k(score, K)
    return jnp.stack([acq.res[i].astype(jnp.float32), v], axis=1)


def explain_k(cfg: EngineConfig) -> int:
    """Effective explain-record row count (0 = wire explain block off).
    Provenance rides ONLY the fused packed wire — the classic multi-array
    TickOutput is unchanged for direct tick callers."""
    if not cfg.packed_wire or cfg.explain_k <= 0:
        return 0
    return int(cfg.explain_k)


# fixed-point encoding for observed/threshold words — canonical
# constants live with the host decoder (obs/explain.py, jax-free) and
# are shared with the cluster _T_PROV block
from sentinel_tpu.obs.explain import (  # noqa: E402
    FX as EXPLAIN_FX,
    FX_MAX as _EXPLAIN_FX_MAX,
    FX_UNKNOWN as EXPLAIN_UNKNOWN,
)


def _explain_fx(x, known):
    """float -> x256 fixed-point uint32; EXPLAIN_UNKNOWN where not known."""
    v = jnp.clip(x.astype(jnp.float32) * EXPLAIN_FX, 0.0, _EXPLAIN_FX_MAX)
    return jnp.where(known, v.astype(jnp.uint32), jnp.uint32(EXPLAIN_UNKNOWN))


def _device_explain(
    cfg: EngineConfig,
    state: EngineState,
    rules: RuleSet,
    acq,
    verdict,
    valid,
    forced,
    fslots,
    now_ms,
):
    """Provenance records for up to explain_k BLOCKED rows of this tick.

    Per record (4 uint32 words — obs/explain.py owns the host decode):
      w0  resource id (node_rows + sketch_capacity < 2**24, id-exact)
      w1  verdict kind (bits 0..2) | sketch-tier flag (bit 3) | forced
          flag (bit 4) | blamed rule slot + 1 in bits 16..31 (0 = n/a)
      w2  observed value, x256 fixed point (EXPLAIN_UNKNOWN = n/a)
      w3  threshold, same encoding
    All attribution reads are K-row gathers against state the tick
    already holds, so the marginal cost is O(K), not O(B).  The blamed
    slot is the resource's FIRST rule lane — exact whenever
    *_rules_per_resource == 1 (the common shape), first-of-several
    otherwise; observed/threshold always come from that blamed slot.
    Runs at the tick tail (after effects), matching the hot-candidate
    convention: observed values include this tick."""
    b = acq.res.shape[0]
    K = min(explain_k(cfg), b)
    is_blocked = valid & (verdict >= BLOCK_FLOW) & (verdict <= BLOCK_AUTHORITY)
    n_blocked = jnp.sum(is_blocked).astype(jnp.uint32)
    # first-K blocked rows in batch order; score 0 rows are padding
    score = jnp.where(is_blocked, b - jnp.arange(b, dtype=jnp.int32), 0)
    score_v, rows = jax.lax.top_k(score, K)
    live = score_v > 0
    res = acq.res[rows]
    kind = jnp.where(live, verdict[rows].astype(jnp.uint32), 0)
    is_tail = res >= cfg.node_rows
    frc = forced[rows]

    flow = kind == BLOCK_FLOW
    degr = kind == BLOCK_DEGRADE
    parm = kind == BLOCK_PARAM
    syst = kind == BLOCK_SYSTEM
    auth = kind == BLOCK_AUTHORITY
    attributable = ~frc  # forced rows carry a host pre_verdict, no rule

    slot = jnp.full((K,), -1, jnp.int32)
    obs = jnp.zeros((K,), jnp.float32)
    obs_known = jnp.zeros((K,), bool)
    thr = jnp.zeros((K,), jnp.float32)
    thr_known = jnp.zeros((K,), bool)

    # FLOW exact tier: blamed slot from the check's slot lanes; observed
    # is the node's windowed pass run (O(1) running-sum gather)
    if fslots is not None:
        Kf = cfg.flow_rules_per_resource
        slot_f = fslots.reshape(b, Kf)[rows, 0]
        f_ok = flow & ~is_tail & attributable & (slot_f < cfg.max_flow_rules)
        slot = jnp.where(f_ok, slot_f, slot)
        thr_f = jnp.asarray(rules.flow.count)[jnp.minimum(slot_f, cfg.max_flow_rules)]
        thr = jnp.where(f_ok, thr_f, thr)
        thr_known = thr_known | f_ok
        obs_f = W.gather_window_event_run(
            state.win_sec, jnp.minimum(res, cfg.node_rows - 1), W.EV_PASS
        ).astype(jnp.float32)
        obs = jnp.where(f_ok, obs_f, obs)
        obs_known = obs_known | f_ok

    # FLOW sketch tier: threshold from the depth-hashed cells, observed
    # from the windowed pass CMS estimate (both K-row reads)
    if cfg.sketch_stats:
        t_cols = P.cms_cell(res, cfg.sketch_depth, cfg.sketch_width)
        t_cells = T.depth_gather_1col(
            cfg, jnp.asarray(rules.tail.thr), t_cols, cfg.sketch_width
        )
        thr_t = jnp.max(
            jnp.where(is_tail[None, :], t_cells, RT.TAIL_UNRULED), axis=0
        )
        t_ok = flow & is_tail & attributable
        thr = jnp.where(t_ok, thr_t, thr)
        thr_known = thr_known | (t_ok & (thr_t < RT.TAIL_UNRULED / 2))
        obs_t = _sketch(cfg).estimate_plane_mxu(
            cfg, state.gs, now_ms, res, W.EV_PASS, sketch_config(cfg)
        )
        obs = jnp.where(t_ok, obs_t, obs)
        obs_known = obs_known | t_ok

    # DEGRADE: blamed breaker slot; observed is its circuit state
    # (0 closed / 1 open / 2 half-open), threshold the rule's count
    res_d = jnp.minimum(res, cfg.max_resources)
    slot_d = jnp.asarray(rules.degrade.res_cbs)[res_d, 0]
    slot_dc = jnp.minimum(slot_d, cfg.max_degrade_rules)
    d_ok = degr & attributable & (slot_d < cfg.max_degrade_rules)
    slot = jnp.where(d_ok, slot_d, slot)
    thr = jnp.where(d_ok, jnp.asarray(rules.degrade.count)[slot_dc], thr)
    thr_known = thr_known | d_ok
    obs = jnp.where(d_ok, state.cb_state[slot_dc].astype(jnp.float32), obs)
    obs_known = obs_known | d_ok

    # PARAM: blamed rule slot + window budget; the offending hashed value
    # is not recoverable from the CMS, so observed stays unknown
    rp = jnp.asarray(rules.param.res_params)
    slot_p = rp[jnp.minimum(res, rp.shape[0] - 1), 0]
    slot_pc = jnp.minimum(slot_p, cfg.max_param_rules)
    p_ok = parm & attributable & (slot_p < cfg.max_param_rules)
    slot = jnp.where(p_ok, slot_p, slot)
    thr = jnp.where(p_ok, jnp.asarray(rules.param.threshold)[slot_pc], thr)
    thr_known = thr_known | p_ok

    # SYSTEM: global gate — report the entry node's windowed pass run
    # against the qps ceiling (the most common trip; load/cpu/rt trips
    # still carry the kind, with threshold unknown when qps is unset)
    s_ok = syst & attributable
    qps = jnp.asarray(rules.system.qps).astype(jnp.float32)
    thr = jnp.where(s_ok, qps, thr)
    thr_known = thr_known | (s_ok & (qps >= 0))
    entry = jnp.full((K,), cfg.entry_node_row, jnp.int32)
    obs_s = W.gather_window_event_run(state.win_sec, entry, W.EV_PASS)
    obs = jnp.where(s_ok, obs_s.astype(jnp.float32), obs)
    obs_known = obs_known | s_ok

    # AUTHORITY: observed is the rule mode (1 white / 2 black)
    a_ok = auth & attributable
    mode = jnp.asarray(rules.auth.mode)
    obs_a = mode[jnp.minimum(res, mode.shape[0] - 1)].astype(jnp.float32)
    obs = jnp.where(a_ok, obs_a, obs)
    obs_known = obs_known | a_ok

    w0 = jnp.where(live, res.astype(jnp.uint32), 0)
    slot_word = jnp.minimum(slot + 1, 0xFFFF).astype(jnp.uint32)
    w1 = (
        kind
        | (jnp.where(flow & is_tail, 1, 0).astype(jnp.uint32) << 3)
        | (frc.astype(jnp.uint32) << 4)
        | (slot_word << 16)
    )
    w1 = jnp.where(live, w1, 0)
    w2 = jnp.where(live, _explain_fx(obs, obs_known & live), 0)
    w3 = jnp.where(live, _explain_fx(thr, thr_known & live), 0)
    return n_blocked, jnp.stack([w0, w1, w2, w3], axis=1)


def _tick_output(
    cfg: EngineConfig, verdict, wait_ms, seg_dropped, stats, res_stats, hot,
    expl=None,
) -> TickOutput:
    """Assemble the TickOutput — classic multi-array form, or (under
    cfg.packed_wire) everything packed into the single fused wire buffer
    (ops/wire.py).  Packed mode keeps wait_ms as a device output too: it
    is only ever READ on the rare tick whose PASS_WAIT rows overflow the
    wire's fixed sidecar, so it costs nothing on the transport."""
    if cfg.packed_wire:
        return TickOutput(
            verdict=None,
            wait_ms=wait_ms,
            stats=None,
            res_stats=None,
            hot=None,
            wire=WIRE.pack_tick_output(
                cfg, verdict, wait_ms, seg_dropped, stats, res_stats, hot,
                expl,
            ),
        )
    return TickOutput(
        verdict=verdict,
        wait_ms=wait_ms,
        seg_dropped=seg_dropped,
        stats=stats,
        res_stats=res_stats,
        hot=hot,
    )


def _empty_batch(cls, cfg: EngineConfig, b: int, fills, wire_dtypes: dict):
    # every leaf gets its OWN buffer — two pytree leaves sharing one device
    # buffer bakes a deduplicated parameter list into the executable that
    # compiles from that call, and a later call with a different sharing
    # pattern fails with a buffer-count mismatch (observed on jaxlib CPU:
    # 'Execution supplied 57 buffers but compiled program expected 58').
    # packed_wire ships the range-bounded columns narrow (ops/wire.py): an
    # empty batch for the classic signature carries those dtypes
    return cls(**{
        f: jnp.full(
            (b, cfg.param_dims) if f == "param_hash" else (b,),
            fill,
            dtype=np.float32 if f == "rt" else wire_dtypes.get(f, np.int32),
        )
        for f, fill in fills
    })


def empty_acquire(cfg: EngineConfig, b: Optional[int] = None) -> AcquireBatch:
    """Every row padding: the fills of ops/wire.acquire_fills."""
    return _empty_batch(
        AcquireBatch, cfg, b or cfg.batch_size, WIRE.acquire_fills(cfg),
        WIRE.acquire_wire_dtypes(cfg),
    )


def empty_complete(cfg: EngineConfig, b: Optional[int] = None) -> CompleteBatch:
    return _empty_batch(
        CompleteBatch, cfg, b or cfg.complete_batch_size,
        WIRE.complete_fills(cfg), WIRE.complete_wire_dtypes(cfg),
    )


def _stat_rows(cfg: EngineConfig, res, ctx_node, origin_node, with_nodes: bool):
    """Stat rows an item writes to: the per-resource ClusterNode row, plus
    (with the "nodes" feature) the context DefaultNode and origin rows
    (StatisticSlot.java:54-123).  The global ENTRY node is handled by a
    masked reduction instead of a scatter lane — its row is fixed.

    Trash-row lanes are remapped to an out-of-range sentinel so every
    scatter path DROPS them: the trash row stays identically zero, which
    keeps the two backends bit-identical regardless of which fan-out branch
    a tick takes.  (The sentinel must be LARGE, not -1 — JAX array indexing
    wraps negatives NumPy-style, which would land on the last row.)"""

    def clean(x):
        return jnp.where(x == cfg.trash_row, jnp.int32(2**30), x)

    if with_nodes:
        return jnp.concatenate([clean(res), clean(ctx_node), clean(origin_node)])
    return clean(res)


def _stat_update(
    cfg: EngineConfig,
    state: EngineState,
    now_ms,
    rows,  # [N] or [3N] stat rows
    deltas,  # int32 [same, len(plane_idx)]
    rt,  # float32 [same] or None
    entry_deltas,  # int32 [NUM_EVENTS] — ENTRY-node contribution (reduction)
    entry_rt,  # f32 scalar or None
    entry_rt_min,  # f32 scalar or None — min inbound RT this tick
    plane_idx: tuple = tuple(range(W.NUM_EVENTS)),  # which events deltas carry
) -> EngineState:
    """Land one batch of stat events.

    CPU path: scatter-add per window (exact, incl. per-row minRt).
    MXU path: one-hot-matmul histogram → dense column add (ops/tables.py);
    per-row minRt rides the sort/segmented-min path (ops/rowmin.py) and is
    exact over raw rts; the ENTRY-row min additionally lands via
    min_into_row.

    ``plane_idx`` names the event planes ``deltas`` carries — the acquire
    side only writes PASS/OCCUPIED/BLOCK and the completion side only
    SUCCESS/EXCEPTION, so contracting just those planes cuts the histogram
    matmuls ~40%."""
    sec_cfg = W.WindowConfig(cfg.second_sample_count, cfg.second_window_ms)
    min_cfg = W.WindowConfig(cfg.minute_sample_count, cfg.minute_window_ms)
    erow = cfg.entry_node_row

    if cfg.use_mxu_tables:
        vals = deltas
        if rt is not None:
            # quantize to 1/8 ms so the RT plane rides the exact bf16 digit
            # path (values ≤ statistic_max_rt*8 < 2^16) instead of a slow
            # f32 contraction, and FUSE it into the counts histogram so the
            # one-hot build is shared; RT is clamped like the reference's
            # statisticMaxRt (SentinelConfig.java:63)
            rt_q = jnp.round(
                jnp.minimum(rt, float(cfg.statistic_max_rt)) * 8.0
            ).astype(jnp.int32)
            vals = jnp.concatenate([deltas, rt_q[:, None]], axis=1)
        h = T.histogram(cfg, rows, vals, cfg.node_rows)
        hist_small = h[:, : len(plane_idx)]
        hist = jnp.zeros((cfg.node_rows, W.NUM_EVENTS), hist_small.dtype)
        hist = hist.at[:, jnp.asarray(plane_idx)].set(hist_small)
        hist = hist.at[erow].add(entry_deltas)
        rt_hist = None
        row_min = None
        if rt is not None:
            rt_hist = h[:, -1].astype(jnp.float32) / 8.0
            rt_hist = rt_hist.at[erow].add(entry_rt)
            # exact per-row windowed minRt over RAW rts (ops/rowmin.py) —
            # closes the former MXU-path snapshot divergence
            row_min = RM.per_row_min(
                cfg, rows, rt, jnp.ones_like(rows, bool), cfg.node_rows
            )
        win_sec = W.add_dense(
            state.win_sec, now_ms, hist, rt_hist, sec_cfg, row_min=row_min
        )
        if entry_rt_min is not None:
            win_sec = W.min_into_row(win_sec, now_ms, erow, entry_rt_min, sec_cfg)
        win_min = state.win_min
        if cfg.enable_minute_window:
            win_min = W.add_dense(
                state.win_min, now_ms, hist, rt_hist, min_cfg, row_min=row_min
            )
        return state._replace(win_sec=win_sec, win_min=win_min), hist
    # CPU/scatter path
    if len(plane_idx) != W.NUM_EVENTS:
        full = jnp.zeros((deltas.shape[0], W.NUM_EVENTS), deltas.dtype)
        deltas = full.at[:, jnp.asarray(plane_idx)].set(deltas)
    win_sec = W.add_batch(state.win_sec, now_ms, rows, deltas, rt, sec_cfg)
    win_sec = W.add_row_delta(
        win_sec, now_ms, erow, entry_deltas,
        None if rt is None else entry_rt, sec_cfg,
    )
    if entry_rt_min is not None:
        win_sec = W.min_into_row(win_sec, now_ms, erow, entry_rt_min, sec_cfg)
    win_min = state.win_min
    if cfg.enable_minute_window:
        win_min = W.add_batch(state.win_min, now_ms, rows, deltas, rt, min_cfg)
        win_min = W.add_row_delta(
            win_min, now_ms, erow, entry_deltas,
            None if rt is None else entry_rt, min_cfg,
        )
    return state._replace(win_sec=win_sec, win_min=win_min), None


# ---------------------------------------------------------------------------
# tick phases
# ---------------------------------------------------------------------------


def _completion_entry_stats(cfg: EngineConfig, comp: CompleteBatch, valid):
    """(inb, entry_deltas, entry_rt, entry_rt_min) — the global ENTRY-node
    reductions shared by the fused and unfused completion paths."""
    inb = valid & (comp.inbound > 0)
    entry_deltas = jnp.zeros((W.NUM_EVENTS,), jnp.int32)
    entry_deltas = entry_deltas.at[W.EV_SUCCESS].set(
        jnp.sum(jnp.where(inb, comp.success, 0))
    )
    entry_deltas = entry_deltas.at[W.EV_EXCEPTION].set(
        jnp.sum(jnp.where(inb, comp.error, 0))
    )
    entry_rt = jnp.sum(jnp.where(inb, comp.rt, 0.0))
    # rt <= 0 means "no RT data", matching the add_batch per-row min filter
    # (window.py rt_for_min) — a sub-ms completion must not collapse the
    # BBR capacity estimate to zero
    entry_rt_min = jnp.min(
        jnp.where(inb & (comp.rt > 0), comp.rt, jnp.float32(W.RT_MIN_INIT))
    )
    return inb, entry_deltas, entry_rt, entry_rt_min


def _param_release_ctx(cfg: EngineConfig, rules: RuleSet, comp: CompleteBatch, valid):
    """(rel, prows_c, rel_cnt): which completion lanes release THREAD-grade
    param concurrency, their hashed (rule,value) rows, and the release
    counts (ParamFlowSlot.exit: decreaseThreadCount) — shared by both
    completion paths."""
    KPp = cfg.param_rules_per_resource
    res_lp = jnp.minimum(comp.res, cfg.max_resources)
    pslots = T.big_gather(
        cfg,
        rules.param.res_params,
        res_lp,
        cfg.max_resources + 1,
        max_int=cfg.max_param_rules,
    )
    pslots_f = pslots.reshape(-1)
    pgc = T.small_gather_fields(
        cfg,
        T.pack_fields([rules.param.enabled, rules.param.grade, rules.param.lane]),
        pslots_f,
    )
    lane_c = pgc[:, 2].astype(jnp.int32)
    lane_oh_c = jnp.clip(lane_c, 0, cfg.param_dims - 1)[
        :, None
    ] == jax.lax.broadcasted_iota(jnp.int32, (1, cfg.param_dims), 1)
    ph_c = jnp.sum(jnp.where(lane_oh_c, _fan(comp.param_hash, KPp), 0), axis=1)
    ph_c = jnp.where(lane_c >= 0, ph_c, 0)
    rel = (
        (pgc[:, 0] > 0)
        & (pgc[:, 1].astype(jnp.int32) == GRADE_THREAD)
        & (ph_c != 0)
        & _fan(valid, KPp)
    )
    prows_c = P.pair_rows(pslots_f, ph_c, cfg.param_depth, cfg.param_width)
    return rel, prows_c, _fan(comp.success, KPp)


def _degrade_completion_masks(
    cfg: EngineConfig, state: EngineState, rules: RuleSet, comp: CompleteBatch,
    valid, now_ms,
):
    """Refresh CB columns and derive the per-lane event masks the exit path
    scatters (DegradeSlot.exit:60-75) — shared by both completion paths.
    Returns (slots_f, cb_counts, cb_epochs, active, is_err, is_slow, g_idx,
    half_open)."""
    KD = cfg.degrade_rules_per_resource
    res_l = jnp.minimum(comp.res, cfg.max_resources)  # row max_resources = pad
    slots = T.big_gather(
        cfg,
        rules.degrade.res_cbs,
        res_l,
        cfg.max_resources + 1,
        max_int=cfg.max_degrade_rules,
    )
    slots_f = slots.reshape(-1)
    cb_counts, cb_epochs, cur_idx = D.refresh_columns(
        state.cb_counts, state.cb_epochs, rules.degrade.window_ms, now_ms
    )
    # one packed matmul for all per-slot fields (enabled/grade/count/cur_idx)
    dg = T.small_gather_fields(
        cfg,
        T.pack_fields(
            [
                rules.degrade.enabled,
                rules.degrade.grade,
                rules.degrade.count,
                cur_idx,
                state.cb_state,
            ]
        ),
        slots_f,
    )
    enabled = dg[:, 0] > 0
    g_grade = dg[:, 1].astype(jnp.int32)
    g_count = dg[:, 2]
    g_idx = dg[:, 3].astype(jnp.int32)
    active = enabled & _fan(valid, KD)
    is_err = (_fan(comp.error, KD) > 0) & active
    is_slow = (g_grade == D.GRADE_SLOW_RATIO) & (_fan(comp.rt, KD) > g_count) & active
    half_open = dg[:, 4].astype(jnp.int32) == D.CB_HALF_OPEN
    return slots_f, cb_counts, cb_epochs, active, is_err, is_slow, g_idx, half_open


def _cb_transitions(
    cfg: EngineConfig,
    state: EngineState,
    rules: RuleSet,
    cb_counts,
    cb_epochs,
    seen,
    failed,
    now_ms,
):
    """Half-open probe resolution + CLOSED-breaker trip evaluation
    (AbstractCircuitBreaker.java:68-136) from the probe histograms —
    shared tail of both completion paths."""
    was_half = state.cb_state == D.CB_HALF_OPEN
    to_open = was_half & (seen > 0) & (failed > 0)
    to_close = was_half & (seen > 0) & (failed == 0)
    cb_state = jnp.where(to_open, D.CB_OPEN, state.cb_state)
    cb_state = jnp.where(to_close, D.CB_CLOSED, cb_state)
    cb_retry = jnp.where(
        to_open, now_ms + rules.degrade.retry_timeout_ms, state.cb_retry_ms
    )
    # closing resets the rule's stat window (fromHalfOpenToClose → resetStat)
    cb_counts = jnp.where(to_close[:, None, None], 0, cb_counts)

    sums = D.window_sums(cb_counts, cb_epochs, rules.degrade.window_ms, now_ms)
    trip = D.trip_condition(
        sums,
        rules.degrade.grade,
        rules.degrade.count,
        rules.degrade.slow_ratio,
        rules.degrade.min_request,
    )
    newly_open = (cb_state == D.CB_CLOSED) & trip & rules.degrade.enabled
    cb_state = jnp.where(newly_open, D.CB_OPEN, cb_state)
    cb_retry = jnp.where(newly_open, now_ms + rules.degrade.retry_timeout_ms, cb_retry)
    return cb_counts, cb_state, cb_retry


def _process_completions(
    cfg: EngineConfig,
    state: EngineState,
    rules: RuleSet,
    comp: CompleteBatch,
    now_ms,
    features: frozenset,
) -> EngineState:
    """Exit path: RT/success/exception recording + circuit-breaker feedback
    (StatisticSlot.exit:125-164, DegradeSlot.exit:60-75)."""
    b = comp.res.shape[0]
    valid = comp.res != cfg.trash_row
    with_nodes = "nodes" in features

    deltas1 = jnp.stack(
        [jnp.where(valid, comp.success, 0), jnp.where(valid, comp.error, 0)], axis=1
    )  # planes (SUCCESS, EXCEPTION) only — the exit path writes nothing else
    rt1 = jnp.where(valid, comp.rt, 0.0)
    inb, entry_deltas, entry_rt, entry_rt_min = _completion_entry_stats(
        cfg, comp, valid
    )

    def _land(fanned: bool):
        rows = _stat_rows(cfg, comp.res, comp.ctx_node, comp.origin_node, fanned)
        f = 3 if fanned else 1
        return _stat_update(
            cfg,
            state,
            now_ms,
            rows,
            jnp.tile(deltas1, (f, 1)) if fanned else deltas1,
            jnp.tile(rt1, (f,)) if fanned else rt1,
            entry_deltas,
            entry_rt,
            entry_rt_min,
            plane_idx=(W.EV_SUCCESS, W.EV_EXCEPTION),
        )

    if with_nodes:
        # batches whose items carry no ctx/origin rows (the common
        # decorator-style workload) skip the 3x stat fan-out entirely
        any_fan = jnp.any(
            valid
            & ((comp.ctx_node != cfg.trash_row) | (comp.origin_node != cfg.trash_row))
        )
        state, hist = jax.lax.cond(
            any_fan, lambda: _land(True), lambda: _land(False)
        )
    else:
        state, hist = _land(False)
    # service-level RT quantiles over inbound completions (ops/rtq.py)
    state = state._replace(
        rtq=RQ.add(state.rtq, now_ms, comp.rt, inb & (comp.rt > 0), rtq_config(cfg))
    )
    if cfg.sketch_stats:
        rt_q = jnp.round(
            jnp.minimum(comp.rt, float(cfg.statistic_max_rt)) * GS.RT_SCALE
        ).astype(jnp.int32)
        vals = jnp.stack([comp.success, comp.error, rt_q], axis=1)
        state = state._replace(
            gs=_sketch(cfg).add(
                state.gs,
                now_ms,
                comp.res,
                vals,
                (W.EV_SUCCESS, W.EV_EXCEPTION, GS.RT_PLANE),
                valid,
                sketch_config(cfg),
                ecfg=cfg,
            )
        )

    # concurrency release on all touched rows (+ ENTRY via its fixed row)
    if hist is not None:  # MXU: reuse the success histogram, no extra matmul
        # (the histogram already carries the ENTRY-row reduction)
        concurrency = state.concurrency - hist[:, W.EV_SUCCESS]
    else:
        fan = 3 if with_nodes else 1
        rows = _stat_rows(cfg, comp.res, comp.ctx_node, comp.origin_node, with_nodes)
        dec = jnp.tile(jnp.where(valid, comp.success, 0), (fan,))
        concurrency = state.concurrency.at[rows].add(-dec, mode="drop")
        concurrency = concurrency.at[cfg.entry_node_row].add(
            -entry_deltas[W.EV_SUCCESS]
        )
    concurrency = jnp.maximum(concurrency, 0)

    # THREAD-grade param release (ParamFlowSlot.exit: decreaseThreadCount)
    if "param" in features:
        rel, prows_c, rel_cnt = _param_release_ctx(cfg, rules, comp, valid)

        def _release():
            return P.conc_add(
                cfg,
                state.pconc,
                jnp.where(rel[:, None], prows_c, -1),
                jnp.zeros_like(rel_cnt),
                rel_cnt,
            )

        pconc = jax.lax.cond(jnp.any(rel), _release, lambda: state.pconc)
        state = state._replace(pconc=pconc)

    if "degrade" not in features:
        return state._replace(concurrency=concurrency)

    # --- circuit-breaker windows -----------------------------------------
    slots_f, cb_counts, cb_epochs, active, is_err, is_slow, g_idx, half_open = (
        _degrade_completion_masks(cfg, state, rules, comp, valid, now_ms)
    )
    upd = jnp.stack(
        [
            jnp.where(active, 1, 0),
            jnp.where(is_err, 1, 0),
            jnp.where(is_slow, 1, 0),
        ],
        axis=-1,
    )  # [B2*KD, 3]
    safe_slots = jnp.minimum(slots_f, cfg.max_degrade_rules)
    nbd = cfg.cb_sample_count
    Dn1 = cfg.max_degrade_rules + 1
    flat = safe_slots * nbd + g_idx
    cb_counts = T.small_scatter_add(
        cfg, cb_counts.reshape(Dn1 * nbd, 3), flat, upd, max_int=1
    ).reshape(Dn1, nbd, 3)

    # --- half-open probe flags (one fused 2-plane 0/1 histogram) ----------
    probe_done = active & half_open
    probe_fail = probe_done & (is_err | is_slow)
    sf = T.small_scatter_add(
        cfg,
        jnp.zeros((Dn1, 2), jnp.int32),
        safe_slots,
        jnp.stack(
            [probe_done.astype(jnp.int32), probe_fail.astype(jnp.int32)], axis=1
        ),
        max_int=1,
    )
    cb_counts, cb_state, cb_retry = _cb_transitions(
        cfg, state, rules, cb_counts, cb_epochs, sf[:, 0], sf[:, 1], now_ms
    )

    return state._replace(
        concurrency=concurrency,
        cb_counts=cb_counts,
        cb_epochs=cb_epochs,
        cb_state=cb_state,
        cb_retry_ms=cb_retry,
    )


def _acquire_entry_stats(cfg: EngineConfig, acq: AcquireBatch, valid, passed, occupying):
    """(pass_c, block_c, occ_c, entry_deltas) — the acquire-side stat
    planes and global ENTRY-node reductions shared by the fused and
    unfused effect paths (StatisticSlot.java:54-123)."""
    pass_c = jnp.where(passed & ~occupying, acq.count, 0)
    block_c = jnp.where(valid & ~passed, acq.count, 0)
    occ_c = jnp.where(occupying, acq.count, 0)
    inb = valid & (acq.inbound > 0)
    entry_deltas = jnp.zeros((W.NUM_EVENTS,), jnp.int32)
    entry_deltas = entry_deltas.at[W.EV_PASS].set(
        jnp.sum(jnp.where(inb & passed & ~occupying, acq.count, 0))
    )
    entry_deltas = entry_deltas.at[W.EV_OCCUPIED].set(
        jnp.sum(jnp.where(inb & occupying, acq.count, 0))
    )
    entry_deltas = entry_deltas.at[W.EV_BLOCK].set(
        jnp.sum(jnp.where(inb & ~passed, acq.count, 0))
    )
    return pass_c, block_c, occ_c, entry_deltas


def _scatter_with_stat_fan(
    cfg: EngineConfig, other_jobs, res, ctx_node, origin_node, valid,
    stat_vals, stat_digits, with_nodes: bool,
):
    """Run scatter_many with the stat job's fan width picked at runtime:
    no ctx/origin rows -> R=1, origin rows only -> R=2, else the full
    [res, ctx, origin] fan (StatisticSlot.java:54-123).  Dropped-row
    semantics make every variant bit-identical; the narrow ones just skip
    the all-trash row-vectors' dot passes (~1/3 of the stat units each).
    Output shapes are fan-independent, so the variants live in one
    lax.switch."""
    res_row = _clean_rows(cfg, res)
    if not with_nodes:
        return FU.scatter_many(
            [FU.Job("stat", cfg.max_nodes, res_row[None, :], stat_vals, stat_digits)]
            + other_jobs
        )
    ctx_row = _clean_rows(cfg, ctx_node)
    org_row = _clean_rows(cfg, origin_node)

    def _run(stat_rows):
        return FU.scatter_many(
            [FU.Job("stat", cfg.max_nodes, stat_rows, stat_vals, stat_digits)]
            + other_jobs
        )

    any_ctx = jnp.any(valid & (ctx_node != cfg.trash_row))
    any_org = jnp.any(valid & (origin_node != cfg.trash_row))
    idx = jnp.where(any_ctx, 2, jnp.where(any_org, 1, 0))
    return jax.lax.switch(
        idx,
        [
            lambda: _run(res_row[None, :]),
            lambda: _run(jnp.stack([res_row, org_row])),
            lambda: _run(jnp.stack([res_row, ctx_row, org_row])),
        ],
    )


def _use_fused(cfg: EngineConfig) -> bool:
    """Fused effects require the MXU table path and honor the
    SENTINEL_NO_PALLAS kill switch (ops/fused.available)."""
    return cfg.fused_effects and cfg.use_mxu_tables and FU.available()


def _clean_rows(cfg: EngineConfig, x):
    """Trash-row lanes → out-of-range sentinel so scatters drop them (see
    _stat_rows; sentinel must be large — negative indices wrap)."""
    return jnp.where(x == cfg.trash_row, jnp.int32(2**30), x)


def _param_upd(cfg: EngineConfig, p_out):
    """What the tick lands in the store, from the ``param{d}`` jobs' tables
    ([depth, Q, 2]; [depth, 2, Q/128, 128] of a wide store, _param_tiles):
    int32 (counts, concurrency), P.conc_shape(cfg) each; None without."""
    if p_out is None:
        return None
    upd = jnp.round(p_out).astype(jnp.int32)
    if P.wide(cfg):
        return upd[:, 0], upd[:, 1]
    return upd[:, :, 0], upd[:, :, 1]


def _param_tiles(pjobs):
    """A WIDE store's ``param{d}`` / ``prel{d}`` jobs: f32 [depth, P, Q/128,
    128], each depth's planes as scatter_sorted wrote them.  The store keeps
    the same tiles (ops/param.py), so [Q, P] is never formed and no plane is
    laid out anew on its way to P.land."""
    return jnp.stack([FU.scatter_sorted_tiles(j) for j in pjobs])


def _process_completions_fused(
    cfg: EngineConfig,
    state: EngineState,
    rules: RuleSet,
    comp: CompleteBatch,
    now_ms,
    features: frozenset,
) -> EngineState:
    """_process_completions with every scatter fused into ONE Pallas
    megakernel (ops/fused.py): stat fan-out histogram, circuit-breaker
    columns, half-open probe flags, CMS sketch, THREAD-param release.
    Bit-identical effects to the unfused MXU path — same digit bounds,
    same drop semantics; the lax.cond fan gating disappears because the
    fused kernel prices the ctx/origin row-vectors at two extra dot
    passes instead of a second histogram."""
    b = comp.res.shape[0]
    valid = comp.res != cfg.trash_row
    with_nodes = "nodes" in features
    sec_cfg = W.WindowConfig(cfg.second_sample_count, cfg.second_window_ms)
    min_cfg = W.WindowConfig(cfg.minute_sample_count, cfg.minute_window_ms)
    erow = cfg.entry_node_row

    succ_w = jnp.where(valid, comp.success, 0)
    err_w = jnp.where(valid, comp.error, 0)
    rt1 = jnp.where(valid, comp.rt, 0.0)
    rt_q = jnp.round(
        jnp.minimum(rt1, float(cfg.statistic_max_rt)) * 8.0
    ).astype(jnp.int32)
    inb, entry_deltas, entry_rt, entry_rt_min = _completion_entry_stats(
        cfg, comp, valid
    )

    vals3 = jnp.stack([succ_w, err_w, rt_q])  # shared by stat + sketch jobs
    cd = cfg.count_digits
    digits3 = (cd, cd, cfg.rt_digits)

    # exact per-row windowed minRt (ops/rowmin.py): sorted min heads are
    # unique per row, so they land as ONE extra sum-scatter job on the
    # shared item axis (fan reshaped to R=3 row-vectors); trash/absent
    # rows drop, making this fan-switch-invariant
    RMIN = 3 if with_nodes else 1
    min_rows_flat = _stat_rows(
        cfg, comp.res, comp.ctx_node, comp.origin_node, with_nodes
    )
    min_rt_flat = jnp.tile(rt1, (RMIN,)) if with_nodes else rt1
    mh_rows, mh_vals = RM.min_heads(
        min_rows_flat, min_rt_flat, jnp.ones_like(min_rows_flat, bool), cfg.max_nodes
    )
    min_job = FU.Job(
        "rowmin",
        cfg.max_nodes,
        mh_rows.reshape(RMIN, b),
        mh_vals.T.reshape(3, RMIN, b).transpose(1, 0, 2),
        (2, 2, 1),
    )

    # Job shaping rule (measured on v5e in round 2, before the ledger): every
    # MXU dot streams the whole item axis and costs ceil(n/16384) passes,
    # so tables are kept <= 16384 rows per job — real stat rows live below
    # max_nodes (the +8 node_rows tail is trash/padding only), per-depth
    # sketch/param planes are separate jobs, and rule-table pad slots drop
    # via row -1 instead of landing on a pad row.  The stat fan width is
    # chosen at runtime (lax.switch below): batches without ctx/origin rows
    # pay one row-vector instead of three.
    jobs = [min_job]

    if cfg.sketch_stats:
        cols = P.cms_cell(comp.res, cfg.sketch_depth, cfg.sketch_width)  # [B, depth]
        for d in range(cfg.sketch_depth):
            jobs.append(
                FU.Job(
                    f"sketch{d}",
                    cfg.sketch_width,
                    jnp.where(valid, cols[:, d], -1)[None, :],
                    vals3,
                    digits3,
                )
            )

    # --- THREAD-grade param release lanes (gathers stay XLA; only the
    # concurrency scatter rides the kernel) ---------------------------------
    with_param = "param" in features
    if with_param:
        KPp = cfg.param_rules_per_resource
        rel, prows_c, rel_cnt_f = _param_release_ctx(cfg, rules, comp, valid)
        # per-depth jobs on the [Q] plane (Q <= one MXU tile); KPp lanes
        # ride as row-vectors with per-row release counts
        pr = jnp.where(rel[:, None], prows_c, -1).reshape(b, KPp, cfg.param_depth)
        rel_cnt = rel_cnt_f.reshape(b, KPp).T[:, None, :]  # [KPp, 1, B]
        prel_jobs = [
            FU.Job(f"prel{d}", cfg.param_width, pr[:, :, d].T, rel_cnt, (cd,))
            for d in range(cfg.param_depth)
        ]
        if not P.wide(cfg):
            jobs.extend(prel_jobs)

    # --- circuit-breaker columns + probe flags -----------------------------
    with_degrade = "degrade" in features
    if with_degrade:
        KD = cfg.degrade_rules_per_resource
        slots_f, cb_counts, cb_epochs, active, is_err, is_slow, g_idx, half_open = (
            _degrade_completion_masks(cfg, state, rules, comp, valid, now_ms)
        )
        nbd = cfg.cb_sample_count
        Dn = cfg.max_degrade_rules
        Dn1 = Dn + 1
        # pad slots (slot == Dn) drop via row -1 — their values are zero
        # anyway (enabled gathers 0), and dropping keeps the table at
        # Dn*nbd rows instead of Dn1*nbd (tile-count parity)
        flat = jnp.where(slots_f < Dn, slots_f * nbd + g_idx, -1)
        cb_vals = jnp.stack(
            [
                jnp.where(active, 1, 0),
                jnp.where(is_err, 1, 0),
                jnp.where(is_slow, 1, 0),
            ]
        )  # [3, B*KD]
        jobs.append(
            FU.Job(
                "cb",
                Dn * nbd,
                flat.reshape(b, KD).T,
                cb_vals.reshape(3, b, KD).transpose(2, 0, 1),
                (1, 1, 1),
            )
        )
        probe_done = active & half_open
        probe_fail = probe_done & (is_err | is_slow)
        pr_vals = jnp.stack(
            [probe_done.astype(jnp.int32), probe_fail.astype(jnp.int32)]
        )
        jobs.append(
            FU.Job(
                "probe",
                Dn,
                jnp.where(slots_f < Dn, slots_f, -1).reshape(b, KD).T,
                pr_vals.reshape(2, b, KD).transpose(2, 0, 1),
                (1, 1),
            )
        )

    outs = _scatter_with_stat_fan(
        cfg, jobs, comp.res, comp.ctx_node, comp.origin_node, valid,
        vals3, digits3, with_nodes,
    )
    oi = 0
    stat_out = outs[oi]
    oi += 1
    min_out = outs[oi]  # [max_nodes, 3] — (bits_hi, bits_lo, present)
    oi += 1
    sk_out = None
    if cfg.sketch_stats:
        sk_out = jnp.stack(outs[oi : oi + cfg.sketch_depth])  # [depth, width, 3]
        oi += cfg.sketch_depth
    prel_out = None
    if with_param and P.wide(cfg):
        prel_out = _param_tiles(prel_jobs)[:, 0]  # [depth, Q/128, 128]
    elif with_param:
        prel_out = jnp.stack(
            [outs[oi + d][:, 0] for d in range(cfg.param_depth)]
        )  # [depth, Q]
        oi += cfg.param_depth
    if with_degrade:
        cb_out = outs[oi]
        probe_out = outs[oi + 1]

    # --- land the stat histogram (same tail as _stat_update dense path) ---
    pad_tail = cfg.node_rows - cfg.max_nodes
    hist = jnp.zeros((cfg.node_rows, W.NUM_EVENTS), jnp.int32)
    hist = hist.at[: cfg.max_nodes, W.EV_SUCCESS].set(
        jnp.round(stat_out[:, 0]).astype(jnp.int32)
    )
    hist = hist.at[: cfg.max_nodes, W.EV_EXCEPTION].set(
        jnp.round(stat_out[:, 1]).astype(jnp.int32)
    )
    hist = hist.at[erow].add(entry_deltas)
    rt_hist = jnp.concatenate(
        [stat_out[:, 2] / 8.0, jnp.zeros((pad_tail,), jnp.float32)]
    )
    rt_hist = rt_hist.at[erow].add(entry_rt)
    mins_m, present_m = RM.combine(min_out)
    row_min = (
        jnp.concatenate([mins_m, jnp.full((pad_tail,), W.RT_MIN_INIT, jnp.float32)]),
        jnp.concatenate([present_m, jnp.zeros((pad_tail,), bool)]),
    )
    win_sec = W.add_dense(
        state.win_sec, now_ms, hist, rt_hist, sec_cfg, row_min=row_min
    )
    win_sec = W.min_into_row(win_sec, now_ms, erow, entry_rt_min, sec_cfg)
    win_min = state.win_min
    if cfg.enable_minute_window:
        win_min = W.add_dense(
            state.win_min, now_ms, hist, rt_hist, min_cfg, row_min=row_min
        )
    state = state._replace(win_sec=win_sec, win_min=win_min)

    state = state._replace(
        rtq=RQ.add(state.rtq, now_ms, comp.rt, inb & (comp.rt > 0), rtq_config(cfg))
    )
    if sk_out is not None:
        upd = jnp.round(sk_out).astype(jnp.int32)  # [depth, width, 3]
        state = state._replace(
            gs=_sketch(cfg).add_dense(
                state.gs,
                now_ms,
                upd,
                (W.EV_SUCCESS, W.EV_EXCEPTION, GS.RT_PLANE),
                sketch_config(cfg),
            )
        )

    concurrency = jnp.maximum(state.concurrency - hist[:, W.EV_SUCCESS], 0)

    if prel_out is not None:
        dec = jnp.round(prel_out).astype(jnp.int32)  # P.conc_shape(cfg)
        state = state._replace(pconc=jnp.maximum(state.pconc - dec, 0))

    if not with_degrade:
        return state._replace(concurrency=concurrency)

    cb_upd = jnp.round(cb_out).astype(jnp.int32).reshape(Dn, nbd, 3)
    cb_counts = cb_counts.at[:Dn].add(cb_upd)
    sf = jnp.concatenate(
        [jnp.round(probe_out).astype(jnp.int32), jnp.zeros((1, 2), jnp.int32)]
    )  # pad row back to Dn1
    cb_counts, cb_state, cb_retry = _cb_transitions(
        cfg, state, rules, cb_counts, cb_epochs, sf[:, 0], sf[:, 1], now_ms
    )

    return state._replace(
        concurrency=concurrency,
        cb_counts=cb_counts,
        cb_epochs=cb_epochs,
        cb_state=cb_state,
        cb_retry_ms=cb_retry,
    )


def _acquire_effects_fused(
    cfg: EngineConfig,
    state: EngineState,
    rules: RuleSet,
    acq: AcquireBatch,
    now_ms,
    features: frozenset,
    passed,
    occupying,
    valid,
    fslots,  # [B*K] flow slots from _check_flow (None without "flow")
    occ_grant,  # (grant_lane, oslots, ocnt) or None
    rl_info,  # (rl_ok, cost) from _check_flow or None
    param_ctx,  # (prows, q_add, thread_add) or None
) -> EngineState:
    """Acquire-side effects in ONE Pallas megakernel: stat fan histogram,
    CMS sketch, warm-up drain accounting, occupy-ahead booking, the
    RateLimiter latestPassedTime sums, and the param-flow pass/concurrency
    scatters.  Same job-shaping rules as _process_completions_fused; the
    flow-slot scatters (warm/occupy/latest) share one row-vector, and the
    param scatters mask VALUES instead of rows (pair_rows cells are always
    in range) so pcms and pconc ride the same one-hot build."""
    b = acq.res.shape[0]
    with_nodes = "nodes" in features
    sec_cfg = W.WindowConfig(cfg.second_sample_count, cfg.second_window_ms)
    min_cfg = W.WindowConfig(cfg.minute_sample_count, cfg.minute_window_ms)
    erow = cfg.entry_node_row
    cd = cfg.count_digits

    pass_c, block_c, occ_c, entry_deltas = _acquire_entry_stats(
        cfg, acq, valid, passed, occupying
    )

    jobs = []
    stat_vals = jnp.stack([pass_c, block_c, occ_c])
    stat_digits = (cd, cd, cd)

    if cfg.sketch_stats:
        cols = P.cms_cell(acq.res, cfg.sketch_depth, cfg.sketch_width)
        sk_vals = jnp.stack(
            [jnp.where(passed, acq.count, 0), block_c]
        )
        for d in range(cfg.sketch_depth):
            jobs.append(
                FU.Job(
                    f"sketch{d}",
                    cfg.sketch_width,
                    jnp.where(valid, cols[:, d], -1)[None, :],
                    sk_vals,
                    (cd, cd),
                )
            )

    # --- flow-slot scatters: warm drain + occupy booking + latest sums ----
    slot_planes = []  # (kind, digits)
    n_flow_jobs = 0
    if fslots is not None:
        K = cfg.flow_rules_per_resource
        F = cfg.max_flow_rules
        rows_f = jnp.where(fslots < F, fslots, -1).reshape(b, K).T  # [K, B]
        planes = []
        digits = []
        cnt_f = _fan(acq.count, K)
        if "warmup" in features:
            adm = _fan(passed, K)
            planes.append(jnp.where(adm, cnt_f, 0))
            digits.append(cd)
            slot_planes.append("warm")
        if rl_info is not None:
            rl_ok, cost = rl_info
            # costs are whole ms (RateLimiter rounds); values beyond the
            # 3-digit bound (~4.6 h of pacing per item) are unreal
            planes.append(jnp.where(rl_ok, jnp.round(cost).astype(jnp.int32), 0))
            digits.append(3)
            planes.append(jnp.where(rl_ok, 1, 0))
            digits.append(cd)
            slot_planes.append("latest")
        if planes:
            vals_f = jnp.stack(planes).reshape(len(planes), b, K).transpose(2, 0, 1)
            jobs.append(FU.Job("fslots", F, rows_f, vals_f, tuple(digits)))
            n_flow_jobs = 1

    # --- occupy booking: node-keyed (the grant's metered node row) --------
    n_occ_jobs = 0
    if occ_grant is not None:
        K = cfg.flow_rules_per_resource
        grant_lane, onodes, ocnt = occ_grant
        commit = grant_lane & _fan(occupying, K)
        occ_rows = jnp.where(commit & (onodes < cfg.max_nodes), onodes, -1)
        jobs.append(
            FU.Job(
                "occ",
                cfg.max_nodes,
                occ_rows.reshape(b, K).T,
                jnp.where(commit, jnp.round(ocnt).astype(jnp.int32), 0)
                .reshape(b, K)
                .T[:, None, :],
                (cd,),
            )
        )
        n_occ_jobs = 1

    # --- param pass + THREAD concurrency (values masked, rows shared) -----
    if param_ctx is not None:
        prows, q_add, thread_add = param_ctx
        KP = cfg.param_rules_per_resource
        adm = _fan(passed, KP)
        cnt_p = _fan(acq.count, KP)
        p_vals = jnp.stack(
            [
                jnp.where(q_add & adm, cnt_p, 0),
                jnp.where(thread_add & adm, cnt_p, 0),
            ]
        )  # [2, B*KP]
        p_vals_r = p_vals.reshape(2, b, KP).transpose(2, 0, 1)  # [KP, 2, B]
        param_jobs = [
            FU.Job(
                f"param{d}",
                cfg.param_width,
                prows[:, d].reshape(b, KP).T,
                p_vals_r,
                (cd, cd),
            )
            for d in range(cfg.param_depth)
        ]
        if not P.wide(cfg):
            jobs.extend(param_jobs)

    outs = _scatter_with_stat_fan(
        cfg, jobs, acq.res, acq.ctx_node, acq.origin_node, valid,
        stat_vals, stat_digits, with_nodes,
    )
    oi = 0
    stat_out = outs[oi]
    oi += 1
    sk_out = None
    if cfg.sketch_stats:
        sk_out = jnp.stack(outs[oi : oi + cfg.sketch_depth])
        oi += cfg.sketch_depth
    f_out = None
    if n_flow_jobs:
        f_out = outs[oi]
        oi += 1
    occ_out = None
    if n_occ_jobs:
        occ_out = outs[oi]  # [max_nodes, 1]
        oi += 1
    p_out = None
    if param_ctx is not None and P.wide(cfg):
        p_out = _param_tiles(param_jobs)  # [depth, 2, Q/128, 128]
    elif param_ctx is not None:
        p_out = jnp.stack(outs[oi : oi + cfg.param_depth])  # [depth, Q, 2]
        oi += cfg.param_depth

    # --- land stat + concurrency ------------------------------------------
    pad_tail = cfg.node_rows - cfg.max_nodes
    hist = jnp.zeros((cfg.node_rows, W.NUM_EVENTS), jnp.int32)
    hist = hist.at[: cfg.max_nodes, W.EV_PASS].set(
        jnp.round(stat_out[:, 0]).astype(jnp.int32)
    )
    hist = hist.at[: cfg.max_nodes, W.EV_BLOCK].set(
        jnp.round(stat_out[:, 1]).astype(jnp.int32)
    )
    hist = hist.at[: cfg.max_nodes, W.EV_OCCUPIED].set(
        jnp.round(stat_out[:, 2]).astype(jnp.int32)
    )
    hist = hist.at[erow].add(entry_deltas)
    win_sec = W.add_dense(state.win_sec, now_ms, hist, None, sec_cfg)
    win_min = state.win_min
    if cfg.enable_minute_window:
        win_min = W.add_dense(state.win_min, now_ms, hist, None, min_cfg)
    concurrency = state.concurrency + hist[:, W.EV_PASS] + hist[:, W.EV_OCCUPIED]
    state = state._replace(
        win_sec=win_sec, win_min=win_min, concurrency=concurrency
    )

    if sk_out is not None:
        # the completion phase already refreshed the sketch bucket at this
        # now_ms (its write is unconditional under sketch_stats), so the
        # acquire side skips the masked-multiply copy of the counts tensor
        state = state._replace(
            gs=_sketch(cfg).add_dense(
                state.gs,
                now_ms,
                jnp.round(sk_out).astype(jnp.int32),
                (W.EV_PASS, W.EV_BLOCK),
                sketch_config(cfg),
                pre_refreshed=True,
            )
        )

    if f_out is not None:
        pi = 0
        pad1 = jnp.zeros((1,), jnp.float32)
        if "warm" in slot_planes:
            acc_add = jnp.concatenate([f_out[:, pi], pad1])
            state = state._replace(warm_acc=state.warm_acc + acc_add)
            pi += 1
        if "latest" in slot_planes:
            T_s = jnp.concatenate([f_out[:, pi], pad1])
            n_s = jnp.concatenate([f_out[:, pi + 1], pad1])
            state = state._replace(
                latest_passed_ms=_apply_latest(
                    state.latest_passed_ms, T_s, n_s, now_ms
                )
            )

    if occ_out is not None:
        add = jnp.concatenate(
            [
                occ_out[:, 0],
                jnp.zeros((cfg.node_rows - cfg.max_nodes,), jnp.float32),
            ]
        )
        cur_wid = W.wid_of(now_ms, cfg.second_window_ms)
        pool_vec = jnp.where(state.occ_epoch == cur_wid + 1, state.occ_tokens, 0.0)
        state = state._replace(
            occ_tokens=pool_vec + add,
            occ_epoch=jnp.where(add > 0, cur_wid + 1, state.occ_epoch),
        )

    return state, _param_upd(cfg, p_out)


@jax.named_scope("stage.authority")
def _check_authority(cfg: EngineConfig, rules: RuleSet, acq: AcquireBatch):
    """AuthoritySlot: origin allow/deny (AuthorityRuleChecker.java:28-54)."""
    res_l = jnp.minimum(acq.res, cfg.max_resources)
    n = cfg.max_resources + 1
    mode = T.big_gather(cfg, rules.auth.mode, res_l, n, max_int=255)  # [B]
    origins = T.big_gather(cfg, rules.auth.origins, res_l, n)  # [B, KA]
    listed = ((origins == acq.origin_id[:, None]) & (origins != RT.AUTH_EMPTY)).any(
        axis=1
    )
    white_block = (mode == 1) & ~listed
    black_block = (mode == 2) & listed
    return white_block | black_block


@jax.named_scope("stage.system")
def _check_system(
    cfg: EngineConfig,
    state: EngineState,
    rules: RuleSet,
    acq: AcquireBatch,
    now_ms,
    sys_load,
    sys_cpu,
    eligible,
):
    """SystemSlot: global inbound-only adaptive gate incl. BBR check
    (SystemRuleManager.checkSystem / checkBbr)."""
    sec_cfg = W.WindowConfig(cfg.second_sample_count, cfg.second_window_ms)
    entry = jnp.array([cfg.entry_node_row], dtype=jnp.int32)
    # completions refreshed at this now_ms before checks run, so the
    # running sums are exact — single gathers, no [nb] reduction per read
    ec = W.gather_window_counts_run(state.win_sec, entry)[0]
    ert, emin = W.gather_window_rt_run(state.win_sec, entry)
    e_pass = ec[W.EV_PASS].astype(jnp.float32)
    e_succ = ec[W.EV_SUCCESS].astype(jnp.float32)
    e_rt_avg = jnp.where(e_succ > 0, ert[0] / jnp.maximum(e_succ, 1.0), 0.0)
    e_conc = state.concurrency[cfg.entry_node_row].astype(jnp.float32)
    # max single-bucket success * sample_count ≈ maxSuccessQps (StatisticNode)
    mask = W.valid_mask(state.win_sec, now_ms, sec_cfg)
    bucket_succ = state.win_sec.counts[cfg.entry_node_row, :, W.EV_SUCCESS]
    max_succ_qps = (
        jnp.max(jnp.where(mask, bucket_succ, 0)).astype(jnp.float32)
        * cfg.second_sample_count
    )
    min_rt = emin[0]

    inbound = (acq.inbound > 0) & eligible
    cnt = acq.count.astype(jnp.float32)
    # single group (the global ENTRY node) → plain exclusive prefix sum.
    # Fused path: int32 cumsum, exact (counts clamp to max_batch_count at
    # batch build, so the batch total stays < 2^31; the f32 MXU prefix
    # lost exactness at 2^24 and cost ~0.6 ms at B=128K).  Unfused path:
    # counts run to 65535 and an int32 total can WRAP negative (admitting
    # the whole batch); f32 is monotone under positive addends — inexact
    # past 2^24 but it never un-blocks, so it keeps the old behavior.
    vim_i = jnp.where(inbound, acq.count, 0)
    if _use_fused(cfg):
        rank_q = (jnp.cumsum(vim_i) - vim_i).astype(jnp.float32)
    else:
        vim_f = vim_i.astype(jnp.float32)
        rank_q = jnp.cumsum(vim_f) - vim_f
    rank_t = rank_q  # one concurrent slot per inbound attempt (count≈1)

    s = rules.system
    blk = jnp.zeros_like(inbound)
    blk |= (s.qps >= 0) & (e_pass + rank_q + cnt > s.qps)
    blk |= (s.max_thread >= 0) & (e_conc + rank_t + 1 > s.max_thread)
    blk |= (s.avg_rt >= 0) & (e_rt_avg > s.avg_rt)
    # BBR: under high load only allow while concurrency fits the pipe
    bbr_ok = (e_conc + rank_t + 1) <= jnp.maximum(max_succ_qps * min_rt / 1000.0, 1.0)
    blk |= (s.load >= 0) & (sys_load > s.load) & ~bbr_ok
    blk |= (s.cpu >= 0) & (sys_cpu > s.cpu)
    return blk & inbound


@jax.named_scope("stage.param")
def _check_param(
    cfg: EngineConfig,
    state: EngineState,
    rules: RuleSet,
    acq: AcquireBatch,
    now_ms,
    eligible,
):
    """ParamFlowSlot: per-parameter-value limiting over hashed rows
    (ParamFlowChecker.passLocalCheck:78-188 — QPS grade as a windowed
    budget, THREAD grade as per-value concurrency; paramIdx dispatch via
    per-resource hash lanes).

    Reads the store as the tick refreshed it (``tick``: stage.param_refresh).
    Returns (blocked[B], prows, qps_add_mask, thread_add_mask).
    """
    KP = cfg.param_rules_per_resource
    b = acq.res.shape[0]
    res_l = jnp.minimum(acq.res, cfg.max_resources)
    slots = T.big_gather(cfg, rules.param.res_params, res_l, cfg.max_resources + 1, max_int=cfg.max_param_rules)
    slots_f = slots.reshape(-1)
    item = jnp.repeat(jnp.arange(b), KP)

    pg = T.small_gather_fields(
        cfg,
        T.pack_fields(
            [
                rules.param.enabled,
                rules.param.threshold,
                rules.param.grade,
                rules.param.cls,
                rules.param.lane,
            ]
        ),
        slots_f,
    )
    enabled = pg[:, 0] > 0
    grade = pg[:, 2].astype(jnp.int32)
    cls = pg[:, 3].astype(jnp.int32)
    lane = pg[:, 4].astype(jnp.int32)

    # the rule's param_idx was lane-assigned at compile; pick that hash
    # lane via a tiny one-hot sum (take_along_axis serializes on TPU)
    ph_all = _fan(acq.param_hash, KP)  # [N, M]
    lane_oh = jnp.clip(lane, 0, cfg.param_dims - 1)[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (1, cfg.param_dims), 1
    )
    ph = jnp.sum(jnp.where(lane_oh, ph_all, 0), axis=1)
    ph = jnp.where(lane >= 0, ph, 0)
    applicable = enabled & (ph != 0)

    prows = P.pair_rows(slots_f, ph, cfg.param_depth, cfg.param_width)  # [N, depth]
    wtab = P.class_tables(
        state.pcms, state.pcms_epochs, jnp.asarray(rules.param.class_k), now_ms, cfg
    )
    if _use_fused(cfg):
        est = P.estimate_fused(cfg, wtab, prows, cls)
    else:
        est = P.estimate(cfg, wtab, prows, cls)
    # the concurrency gathers only run when a THREAD-grade rule exists
    any_thread = jnp.any(
        jnp.asarray(rules.param.enabled)
        & (jnp.asarray(rules.param.grade) == GRADE_THREAD)
    )
    conc_est = jax.lax.cond(
        any_thread,
        lambda: P.conc_estimate(cfg, state.pconc, prows),
        lambda: jnp.zeros((prows.shape[0],), jnp.float32),
    )

    # per-value exception items (ParamFlowItem): hashes are raw int32 bits,
    # so they go through the exact int gather; thresholds pack as f32
    ih = T.small_gather_int(cfg, rules.param.item_hash, slots_f)  # [N, KI]
    it = T.small_gather_fields(
        cfg, jnp.asarray(rules.param.item_threshold, jnp.float32), slots_f
    )
    is_item = (ih == ph[:, None]) & (ih != 0)
    has_item = is_item.any(axis=1)
    item_thr = jnp.max(jnp.where(is_item, it, 0.0), axis=1)
    thr = jnp.where(has_item, item_thr, pg[:, 1])

    cnt = _fan(acq.count, KP).astype(jnp.float32)
    elig_f = _fan(eligible, KP) & applicable
    # within-tick rank keyed by the exact (value, rule) pair — the int32
    # wrap of the mix only ever MERGES groups, which over-counts
    # conservatively (sort-based rank: the key space is unbounded)
    key = ph * jnp.int32(KP + 1) + slots_f
    (rank,) = grouped_exclusive_cumsum(key, [cnt], elig_f)
    is_thread = grade == GRADE_THREAD
    over = jnp.where(is_thread, conc_est, est) + rank + cnt > thr
    blocked_f = applicable & over
    blocked = (blocked_f & elig_f).reshape(b, KP).any(axis=1)
    qps_add = applicable & ~is_thread
    thread_add = applicable & is_thread
    return blocked, prows, qps_add, thread_add


def _fold_occupied(cfg: EngineConfig, state: EngineState, now_ms):
    """Borrowed-ahead tokens whose target bucket has arrived land as
    PASS in the current column of their NODE row — the batched form of
    FutureBucketLeapArray's buckets becoming current
    (occupy/OccupiableBucketLeapArray.java:29-43).

    The occupy state is keyed by node row, so the fold is a pure
    elementwise land: no histogram, no rule lookup — RELATE/CHAIN/origin-
    metered grants fold exactly like DIRECT ones."""
    cur_wid = W.wid_of(now_ms, cfg.second_window_ms)
    # modular age (wrap-safe) — occ_epoch is at most one bucket ahead
    due = (cur_wid - state.occ_epoch >= 0) & (state.occ_tokens > 0)
    # debt whose target bucket already rolled OUT of the sliding window
    # (idle gap longer than the interval) is discarded, not charged — the
    # borrowed-against budget expired unused
    chargeable = due & (cur_wid - state.occ_epoch < cfg.second_sample_count)
    tok = jnp.round(jnp.where(chargeable, state.occ_tokens, 0.0)).astype(jnp.int32)
    any_due = jnp.any(due)

    def fold(s):
        # OCCUPIED was already counted once at grant time — only the
        # deferred PASS lands now
        delta = jnp.zeros((cfg.node_rows, W.NUM_EVENTS), jnp.int32)
        delta = delta.at[:, W.EV_PASS].set(tok)
        sec_cfg = W.WindowConfig(cfg.second_sample_count, cfg.second_window_ms)
        win_sec = W.add_dense(s.win_sec, now_ms, delta, None, sec_cfg)
        win_min = s.win_min
        if cfg.enable_minute_window:
            min_cfg = W.WindowConfig(cfg.minute_sample_count, cfg.minute_window_ms)
            win_min = W.add_dense(s.win_min, now_ms, delta, None, min_cfg)
        return s._replace(
            win_sec=win_sec,
            win_min=win_min,
            occ_tokens=jnp.where(due, 0.0, s.occ_tokens),
        )

    return jax.lax.cond(any_due, fold, lambda s: s, state)


def _sync_warmup(
    cfg: EngineConfig, state: EngineState, rules: RuleSet, now_ms
) -> EngineState:
    """Per-second warm-up token refill, vectorized over all flow rules
    (WarmUpController.syncToken/coolDownTokens)."""
    f = rules.flow
    cur_s = (now_ms // 1000).astype(jnp.int32)
    is_warm = (
        (f.behavior == CONTROL_WARM_UP) | (f.behavior == CONTROL_WARM_UP_RATE_LIMITER)
    ) & f.enabled
    elapsed = cur_s - state.warmup_last_s
    first = state.warmup_last_s < 0
    sync_time = (elapsed > 0) | first  # every slot tracks seconds + resets acc
    do_sync = is_warm & sync_time

    # exact passQps: the PREVIOUS full second's per-slot admitted counts,
    # accumulated by the tick effects (a sliding-window read taken at the
    # second boundary sees only the surviving half-bucket and systematically
    # underestimates, freezing the bucket cold).  After an idle gap
    # (elapsed > 1) the accumulator belongs to a long-past second — the
    # recent rate is 0 and the bucket must be allowed to refill to cold.
    pass_qps = jnp.where(elapsed == 1, state.warm_acc, 0.0)

    tokens = state.warmup_tokens
    refill_ok = (tokens < f.warning_token) | (
        pass_qps < f.count / jnp.maximum(f.cold_factor, 1.0)
    )
    dt = jnp.where(first, 1.0, jnp.minimum(elapsed.astype(jnp.float32), 1.0e6))
    grown = jnp.minimum(tokens + dt * f.count, f.max_token)
    new_tokens = jnp.where(refill_ok, grown, tokens)
    # start cold: on first sync fill to max (cold system has full bucket)
    new_tokens = jnp.where(first & is_warm, f.max_token, new_tokens)
    new_tokens = jnp.maximum(new_tokens - pass_qps, 0.0)

    tokens = jnp.where(do_sync, new_tokens, tokens)
    # second tracking + accumulator reset apply to EVERY slot (a plain rule
    # flipped to warm-up at runtime must not inherit a historical total)
    last_s = jnp.where(sync_time, cur_s, state.warmup_last_s)
    warm_acc = jnp.where(sync_time, 0.0, state.warm_acc)
    return state._replace(
        warmup_tokens=tokens, warmup_last_s=last_s, warm_acc=warm_acc
    )


@jax.named_scope("stage.flow")
def _check_flow(
    cfg: EngineConfig,
    state: EngineState,
    rules: RuleSet,
    acq: AcquireBatch,
    now_ms,
    eligible,
    occupy: bool = True,
):
    """FlowSlot: per-resource QPS/thread limiting with the three traffic
    shapers (FlowRuleChecker.java:42-176, Default/RateLimiter/WarmUp
    controllers) plus prioritized occupy-ahead (DefaultController
    :49-68 tryOccupyNext).  Returns (blocked[B], wait_ms[B],
    latest_passed_update-or-None, occupying[B], occ_grant, slots_f,
    (rl_ok, cost)); latest is None on the fused path, where the
    (cost, count) sums ride the acquire-effects kernel instead."""
    K = cfg.flow_rules_per_resource
    b = acq.res.shape[0]
    f = rules.flow
    sec_cfg = W.WindowConfig(cfg.second_sample_count, cfg.second_window_ms)

    res_l = jnp.minimum(acq.res, cfg.max_resources)
    slots = T.big_gather(cfg, f.res_rules, res_l, cfg.max_resources + 1, max_int=cfg.max_flow_rules)  # [B, K]
    slots_f = slots.reshape(-1)  # [N]
    item = jnp.repeat(jnp.arange(b), K)

    # ONE packed matmul replaces a dozen serialized per-field gathers; the
    # dynamic warm-up token state rides in the same matrix, packed fresh
    # each tick (a [F+1, 12] stack — free)
    # the count crosses whole, as its three bfloat16 parts (T.bf16_parts):
    # a pacing cost is round(1000 / count), and 400 for 401 is another ms
    count_parts = T.bf16_parts(f.count)
    fg = T.small_gather_fields(
        cfg,
        T.pack_fields(
            [
                f.enabled,  # 0
                f.limit_app,  # 1
                f.strategy,  # 2
                f.ref_node,  # 3
                f.ref_ctx,  # 4
                f.grade,  # 5
                count_parts[0],  # 6
                f.behavior,  # 7
                f.max_queue_ms,  # 8
                f.warning_token,  # 9
                f.slope,  # 10
                state.warmup_tokens,  # 11
                count_parts[1],  # 12
                count_parts[2],  # 13
            ]
        ),
        slots_f,
    )
    # latestPassedTime is absolute engine-ms in int32, as now_ms is: its
    # magnitude outgrows the matmul's bf16x3 precision (~2^-22 relative)
    # and, past 2^24 ms (4.66 h), float32 itself, so it takes the
    # bit-exact integer gather and is made relative to now BEFORE it
    # becomes a float (latest_rel_ms)
    m_rl = latest_rel_ms(T.small_gather_int(cfg, state.latest_passed_ms, slots_f), now_ms)
    enabled = fg[:, 0] > 0
    la = fg[:, 1].astype(jnp.int32)
    origin = _fan(acq.origin_id, K)
    la_all = la.reshape(b, K)  # [B, K]
    named = ((la_all >= 0) & (la_all == acq.origin_id[:, None])).any(axis=1)  # [B]
    match = (
        (la == RT.LIMIT_ANY)
        | ((la >= 0) & (la == origin))
        | ((la == RT.LIMIT_OTHER) & (origin >= 0) & ~_fan(named, K))
    )
    applicable = enabled & match

    # --- node selection (FlowRuleChecker.selectNodeByRequesterAndStrategy:115)
    strategy = fg[:, 2].astype(jnp.int32)
    ref_node = fg[:, 3].astype(jnp.int32)
    ref_ctx = fg[:, 4].astype(jnp.int32)
    direct_node = jnp.where(la == RT.LIMIT_ANY, _fan(acq.res, K), _fan(acq.origin_node, K))
    chain_ok = (ref_ctx >= 0) & (ref_ctx == _fan(acq.ctx_name, K))
    chain_node = jnp.where(chain_ok, _fan(acq.ctx_node, K), -1)
    node = jnp.where(
        strategy == STRATEGY_DIRECT,
        direct_node,
        jnp.where(strategy == STRATEGY_RELATE, ref_node, chain_node),
    )
    node_ok = (node >= 0) & (node != cfg.trash_row)
    applicable = applicable & node_ok
    node_safe = jnp.where(node_ok, node, cfg.trash_row)

    grade = fg[:, 5].astype(jnp.int32)
    rcount = fg[:, 6] + fg[:, 12] + fg[:, 13]
    behavior = jnp.where(grade == GRADE_QPS, fg[:, 7].astype(jnp.int32), CONTROL_DEFAULT)
    cnt = _fan(acq.count, K).astype(jnp.float32)

    # --- per-entry warm-up threshold (WarmUpController.canPass)
    rest = fg[:, 11]
    warning = fg[:, 9]
    above = jnp.maximum(rest - warning, 0.0)
    warm_qps = jnp.floor(
        1.0 / (above * fg[:, 10] + 1.0 / jnp.maximum(rcount, 1e-9)) + 0.5
    )
    warm_qps = jnp.where(rest >= warning, warm_qps, rcount)

    is_warm = (behavior == CONTROL_WARM_UP) | (behavior == CONTROL_WARM_UP_RATE_LIMITER)
    is_rl = (behavior == CONTROL_RATE_LIMITER) | (
        behavior == CONTROL_WARM_UP_RATE_LIMITER
    )
    # pacing rate: plain RL paces at rule count, warm-up RL paces at the
    # current warm-up threshold (WarmUpRateLimiterController)
    pace_qps = jnp.where(
        behavior == CONTROL_WARM_UP_RATE_LIMITER, warm_qps, jnp.maximum(rcount, 1e-9)
    )
    cost = jnp.where(is_rl, pace_cost_ms(cnt, pace_qps), 0.0)

    # --- within-tick ranks (key: decision node; RL keys by rule slot)
    key = jnp.where(is_rl, jnp.int32(cfg.node_rows) + slots_f, node_safe)
    elig_f = _fan(eligible, K) & applicable
    rank_tok, rank_thr, rank_cost = _rank(
        cfg,
        key,
        [cnt, jnp.ones_like(cnt), cost],
        elig_f,
        cfg.node_rows + cfg.max_flow_rules + 1,
    )

    # occupy borrow pool already booked against the NEXT bucket, keyed by
    # node row (the reference's FutureBucket lives on the node, so RELATE/
    # CHAIN/origin-metered rules can borrow too — the deferred PASS lands
    # on whatever row the grant recorded)
    cur_wid = W.wid_of(now_ms, cfg.second_window_ms)
    pool_dense = jnp.where(state.occ_epoch == cur_wid + 1, state.occ_tokens, 0.0)
    if cfg.use_mxu_tables:
        # per-row windowed pass totals straight off the running sums
        # (exact: completions refreshed at this now_ms before checks run;
        # the old masked [rows, nb] reduction per tick is gone), then ONE
        # one-hot gather for (pass, concurrency, borrow pool)
        wsum = W.window_event_run(state.win_sec, W.EV_PASS)
        tab = jnp.stack(
            [wsum, state.concurrency, jnp.round(pool_dense).astype(jnp.int32)],
            axis=1,
        )
        if _use_fused(cfg):
            cap = jnp.int32((1 << 24) - 1)
            (both,) = FU.gather_many(
                [FU.GatherJob("wsum", node_safe, jnp.minimum(tab, cap), (3, 3, 3))]
            )
        else:
            both = T.big_gather(
                cfg,
                tab,
                node_safe,
                cfg.node_rows,
                max_int=(1 << 24),
            )
        wp = both[:, 0].astype(jnp.float32)
        conc = both[:, 1].astype(jnp.float32)
        pool = both[:, 2].astype(jnp.float32)
    else:
        wp = W.gather_window_event_run(state.win_sec, node_safe, W.EV_PASS)
        wp = wp.astype(jnp.float32)
        conc = state.concurrency[node_safe].astype(jnp.float32)
        pool = pool_dense[node_safe]

    # DefaultController.canPass:31-49
    thr_eff = jnp.where(is_warm, warm_qps, rcount)
    qps_block = wp + rank_tok + cnt > thr_eff
    thread_block = conc + rank_thr + cnt > rcount
    basic_block = jnp.where(grade == GRADE_QPS, qps_block, thread_block)

    # RateLimiterController.canPass:50-105 (exact batched leaky bucket),
    # every term relative to now: expected - now
    csum_incl = rank_cost + cost
    wait = jnp.maximum(m_rl + csum_incl, csum_incl - cost)
    rl_block = wait > fg[:, 8]

    entry_block = jnp.where(is_rl, rl_block, basic_block) & applicable
    # warm-up RL blocks on either the pace or the warm-up threshold
    entry_block = entry_block | (
        (behavior == CONTROL_WARM_UP_RATE_LIMITER) & applicable & qps_block
    )

    blocked = (entry_block & elig_f).reshape(b, K).any(axis=1)

    # --- prioritized occupy-ahead (DefaultController.canPass:49-68) -------
    # a prioritized request rejected by the QPS check may borrow from the
    # NEXT bucket's budget (up to one full bucket per rule) and enter after
    # waiting for that bucket to start
    occupying = jnp.zeros((b,), bool)
    occ_wait = jnp.zeros((b,), jnp.float32)
    occ_grant = None
    if occupy:
        # any DEFAULT/QPS rule can borrow ahead regardless of strategy or
        # limitApp: the grant records its metered NODE row, and the fold
        # lands the deferred PASS there (FutureBucketLeapArray lives on
        # the node in the reference too — tryOccupyNext on the selected
        # node, DefaultController.java:49-68)
        cand = (
            (_fan(acq.prio, K) > 0)
            & (behavior == CONTROL_DEFAULT)
            & (grade == GRADE_QPS)
            & applicable
            & elig_f
            & qps_block
        )

        # the occupy rank pass only runs when the batch carries prioritized
        # items at all (lax.cond skips the rank work for the common
        # all-normal batch); contention is per NODE bucket.  Keying by node
        # means a second rule watching the same node sees the first rule's
        # pending borrow — exactly the reference, where tryOccupyNext
        # checks the node's currentWaiting against each rule's own count
        # (DefaultController.java:49-68).  Note the key space is node_rows,
        # so large configs take the sort-based rank here (prioritized
        # batches only).
        def _occ_rank(cand):
            (rank_occ,) = _rank(cfg, node_safe, [cnt], cand, cfg.node_rows)
            return cand & (pool + rank_occ + cnt <= rcount)  # maxOccupyRatio=1

        granted = jax.lax.cond(
            jnp.any(cand),
            _occ_rank,
            lambda cand: jnp.zeros_like(cand),
            cand,
        )
        # an item occupies iff its ONLY failure was the occupiable QPS check
        still_blocked = (entry_block & ~granted & elig_f).reshape(b, K).any(axis=1)
        occupying = (granted & elig_f).reshape(b, K).any(axis=1) & ~still_blocked
        blocked = still_blocked
        occ_wait_v = (cfg.second_window_ms - (now_ms % cfg.second_window_ms)).astype(
            jnp.float32
        )
        occ_wait = jnp.where(occupying, occ_wait_v, 0.0)
        # booking is deferred to the tick (after degrade): a later stage may
        # still block the item, and its grant must not be committed.  Book
        # ONE lane per item (first granted) — one request borrows once even
        # when several rules on the node granted it.  (Deliberate
        # divergence: the reference books addOccupiedPass once per GRANTING
        # RULE, so one request with two same-node rules charges the future
        # bucket twice and folds two passes for one real request; charging
        # once keeps the folded pass count equal to admitted traffic.)
        grant_mtx = (granted & elig_f).reshape(b, K)
        first_lane = grant_mtx & (jnp.cumsum(grant_mtx, axis=1) == 1)
        occ_grant = (first_lane.reshape(-1), node_safe, cnt)

    # pacing delay for admitted rate-limited entries
    rl_ok = is_rl & applicable & ~entry_block & elig_f & ~_fan(blocked, K)
    wait_ms_entry = jnp.where(rl_ok, jnp.maximum(wait, 0.0), 0.0)
    wait_ms = jnp.maximum(jnp.max(wait_ms_entry.reshape(b, K), axis=1), occ_wait)

    # advance latestPassedTime for admitted entries (even if a later slot
    # blocks the request, matching the reference's side-effect order).
    #
    # Closed form instead of a per-item scatter-max (which costs ~10 ms at
    # B=128K): replaying RateLimiterController.canPass:50-105 sequentially
    # over this tick's admitted items, latestPassedTime can reset to `now`
    # at most once (after the first reset it only grows by costs), so
    #     L' = l0 + T                 if the bucket stays busy
    #     L' = now + (T - C_reset)    if item with inclusive prefix C_reset
    #                                 found the bucket idle (l0 + C <= now)
    # with T = sum of admitted costs.  The reset item is the FIRST admitted
    # one, so C_reset ≈ T/n * 1 — we use the per-slot mean admitted cost,
    # which is exact whenever a slot's within-tick costs are uniform (same
    # rule + count, the overwhelmingly common case) and off by at most one
    # cost spread otherwise.  One packed scatter-add replaces the max —
    # or, on the fused path, the (cost, 1) sums ride the acquire-effects
    # megakernel and the closed form is applied there (_apply_latest).
    if _use_fused(cfg):
        latest = None
    else:
        sums = T.small_scatter_add(
            cfg,
            jnp.zeros((cfg.max_flow_rules + 1, 2), jnp.float32),
            jnp.where(rl_ok, slots_f, jnp.int32(-1)),
            jnp.stack(
                [jnp.where(rl_ok, cost, 0.0), jnp.where(rl_ok, 1.0, 0.0)], axis=1
            ),
        )
        latest = _apply_latest(state.latest_passed_ms, sums[:, 0], sums[:, 1], now_ms)

    return (
        blocked,
        wait_ms.astype(jnp.int32),
        latest,
        occupying,
        occ_grant,
        slots_f,
        (rl_ok, cost),
    )


def pace_cost_ms(cnt, pace_qps):
    """``Math.round(1000 * count / qps)`` (RateLimiterController.java:57: a
    half rounds up), clamped to the fused effects path's 3-digit envelope
    (~4.6 h of pacing per item — larger is unreal and would overflow the
    int32 segmented ranks; the clamped item still blocks via its wait).

    A float32 divide that is a last bit short turns a quotient standing on an
    exact half (1000 / 80 = 12.5) into the millisecond below, and a
    backend's divide need not be correctly rounded (the TPU's is a
    reciprocal and refinement steps).  So the rounded quotient ``c`` is held
    to what defines it, ``(c - 0.5) * qps <= 1000 * count < (c + 0.5) * qps``,
    by two products, which are exact for whole counts and whole qps."""
    num = 1000.0 * cnt
    c = jnp.floor(num / pace_qps + 0.5)
    c = c + ((c + 0.5) * pace_qps <= num) - ((c - 0.5) * pace_qps > num)
    return jnp.minimum(c, float((1 << 24) - 1))


#: latestPassedTime of a rule that has never admitted anything
LATEST_IDLE_MS = -(10**9)
#: how far behind now a latestPassedTime is still told apart: further back
#: the bucket is idle whatever the rule's cost (costs are capped at 2^24 - 1)
_LATEST_REL_FLOOR = -(1 << 24)


def latest_rel_ms(latest_ms, now_ms):
    """``latestPassedTime - now`` as float32, exact: the difference is taken
    in int32 (both are whole engine-ms) and floored at -2^24, which also
    keeps LATEST_IDLE_MS from wrapping.  A float32 plane beside the int32
    now_ms held whole milliseconds only up to 2^24 ms = 4.66 h of engine
    time: past it a 1 ms cost vanished in the rounding (PERF.md, PR 42)."""
    now_ms = now_ms.astype(jnp.int32)
    far = latest_ms < now_ms + _LATEST_REL_FLOOR
    return jnp.where(far, _LATEST_REL_FLOOR, latest_ms - now_ms).astype(jnp.float32)


def _apply_latest(latest_passed_ms, T_s, n_s, now_ms):
    """Closed-form latestPassedTime advance from per-slot (cost, count)
    sums — see the comment block in _check_flow.  The plane is int32
    engine-ms; the arithmetic runs relative to now in float32 (exact while
    a slot's summed cost stays under 2^24) and lands as a whole number.

    Drift bound vs the reference's per-request CAS
    (RateLimiterController.java:50-105), pinned by
    tests/test_rate_limiter_drift.py: with MIXED within-tick costs the
    reset anchor uses the mean admitted cost instead of the first
    admitted item's, so |latest - sequential| <= one maximum item cost at
    every tick.  The error does NOT compound: the busy branch
    (latest + T) is exact, and every idle reset re-anchors to `now`.
    Admission divergence stays within a few items per tick and its
    running total is conservative (slight under-admission, never a
    sustained burst past the configured rate)."""
    mean_cost = T_s / jnp.maximum(n_s, 1.0)
    cand = jnp.maximum(latest_rel_ms(latest_passed_ms, now_ms) + T_s, T_s - mean_cost)
    landed = now_ms.astype(jnp.int32) + jnp.round(cand).astype(jnp.int32)
    return jnp.where(n_s > 0, landed, latest_passed_ms)


@jax.named_scope("stage.tail_flow")
def _check_tail_flow(
    cfg: EngineConfig,
    state: EngineState,
    rules: RuleSet,
    acq: AcquireBatch,
    now_ms,
    eligible,
):
    """Approximate QPS enforcement for SKETCH-TAIL resources: the rule's
    north star demands rule checks across 1M resources, far beyond the
    exact row space.  Hot ruled resources PROMOTE into exact rows
    (Registry.promote_resource); the remainder enforce here from the
    observability sketch's windowed pass CMS against depth-hashed
    threshold cells (rule_tensors.TailFlowTensors — (eps, delta) bounds
    documented there).  Reference semantics: FlowRuleChecker.java:85 with
    bounded approximation instead of the 6,000-chain cap."""
    is_tail = acq.res >= cfg.node_rows
    elig = eligible & is_tail
    thr_tab = jnp.asarray(rules.tail.thr)

    def _run():
        # thresholds: max over depth of hashed cells (+inf = unruled) —
        # ONE flat gather across all depths (tables.depth_gather_1col;
        # float table, so the MXU path rides the lane-packed gather)
        cols = P.cms_cell(acq.res, cfg.sketch_depth, cfg.sketch_width)
        t = T.depth_gather_1col(cfg, thr_tab, cols, cfg.sketch_width)
        # invalid ids gather 0 — restore the unruled sentinel for them
        thr = jnp.max(
            jnp.where(elig[None, :], t, RT.TAIL_UNRULED), axis=0
        )
        # sentinel is FINITE (2e38): +inf would ride the one-hot matmul as
        # 0*inf = NaN on the MXU path and kill enforcement silently
        ruled = elig & (thr < RT.TAIL_UNRULED / 2)

        est = _sketch(cfg).estimate_plane_mxu(
            cfg, state.gs, now_ms, acq.res, W.EV_PASS, sketch_config(cfg)
        )
        cnt = acq.count.astype(jnp.float32)
        # within-tick arrival rank keyed by the exact tail id (sort-based:
        # the id space is the sketch capacity, far beyond dense ranking)
        (rank,) = grouped_exclusive_cumsum(acq.res, [cnt], ruled)
        return ruled & (est + rank + cnt > thr)

    # runtime skip when no tail rules exist at all (the table scan is
    # trivial against the per-item gathers + sort it gates).  Under the
    # SPMD mesh the partitioner hoists _run's salsa read reshard (the
    # flatten in tables.depth_gather_1col, pinned in the ledger) out of
    # the branch, so its all-gather is attributed to this line.
    # stlint: disable-next-line=implicit-reshard — known salsa read reshard, hoisted to the cond boundary
    return jax.lax.cond(
        jnp.any(thr_tab < RT.TAIL_UNRULED / 2) & jnp.any(elig),
        _run,
        lambda: jnp.zeros_like(elig),
    )


@jax.named_scope("stage.degrade")
def _check_degrade(
    cfg: EngineConfig,
    state: EngineState,
    rules: RuleSet,
    acq: AcquireBatch,
    now_ms,
    eligible,
):
    """DegradeSlot entry: CB gate + half-open probe election
    (DegradeSlot.java:37-53, AbstractCircuitBreaker.tryPass).
    Returns (blocked[B], new_cb_state)."""
    KD = cfg.degrade_rules_per_resource
    b = acq.res.shape[0]
    res_l = jnp.minimum(acq.res, cfg.max_resources)
    slots = T.big_gather(cfg, rules.degrade.res_cbs, res_l, cfg.max_resources + 1, max_int=cfg.max_degrade_rules)
    slots_f = slots.reshape(-1)
    item = jnp.repeat(jnp.arange(b), KD)
    dg = T.small_gather_fields(
        cfg, T.pack_fields([rules.degrade.enabled, state.cb_state]), slots_f
    )
    enabled = dg[:, 0] > 0
    st = dg[:, 1].astype(jnp.int32)
    # retry deadlines are absolute engine-ms — int-exact gather (f32 packing
    # would drift by several ms once uptime passes 2^24 ms ≈ 4.6 h)
    retry_due = now_ms >= T.small_gather_int(cfg, state.cb_retry_ms, slots_f)
    open_wait = (st == D.CB_OPEN) & ~retry_due
    open_due = (st == D.CB_OPEN) & retry_due
    half = st == D.CB_HALF_OPEN

    probe_cand = open_due & enabled & _fan(eligible, KD)

    # one probe per rule: first eligible candidate by rank — the rank pass
    # only runs when some breaker is actually due (lax.cond: the all-closed
    # steady state pays nothing)
    def _probe_rank(cand):
        (p_rank,) = _rank(
            cfg,
            jnp.minimum(slots_f, cfg.max_degrade_rules),
            [jnp.ones_like(slots_f, dtype=jnp.float32)],
            cand,
            cfg.max_degrade_rules + 1,
        )
        return cand & (p_rank < 0.5)

    probe = jax.lax.cond(
        jnp.any(probe_cand), _probe_rank, lambda cand: jnp.zeros_like(cand), probe_cand
    )

    entry_block = enabled & (open_wait | (open_due & ~probe) | half)
    blocked = (entry_block & _fan(eligible, KD)).reshape(b, KD).any(axis=1)

    # elected probes flip their breaker OPEN → HALF_OPEN; a probe whose item
    # is blocked by another CB on the same resource must not flip.  The
    # scatter only runs when a probe was actually elected — the all-closed
    # steady state pays nothing (the unconditional form cost ~0.6 ms/tick)
    probe_ok = probe & ~_fan(blocked, KD)
    Dn1 = cfg.max_degrade_rules + 1
    flip = jax.lax.cond(
        jnp.any(probe_ok),
        lambda: T.small_scatter_or(
            cfg,
            jnp.zeros((Dn1,), jnp.int32),
            jnp.minimum(slots_f, cfg.max_degrade_rules),
            probe_ok,
        ),
        lambda: jnp.zeros((Dn1,), jnp.int32),
    )
    cb_state = jnp.where(
        (flip > 0) & (state.cb_state == D.CB_OPEN), D.CB_HALF_OPEN, state.cb_state
    )
    return blocked, cb_state


# ---------------------------------------------------------------------------


#: every optional tick stage; make_tick compiles only what the rule set
#: needs (the SPI slot-chain analog: absent slots cost nothing)
ALL_FEATURES = frozenset(
    {
        "authority",
        "system",
        "param",
        "flow",
        "degrade",
        "warmup",
        "nodes",
        "occupy",
        "tail_flow",
    }
)


def _run_checks_plain(
    cfg: EngineConfig,
    state: EngineState,
    rules: RuleSet,
    acq: AcquireBatch,
    now_ms,
    sys_load,
    sys_cpu,
    valid,
    forced,
    features: frozenset,
):
    """The per-item check phase (Authority -> System -> ParamFlow -> Flow
    (+tail) -> Degrade, first-fail order), extracted so the segment engine
    can lax.cond against it.  Returns

      (auth_block, sys_block, param_block, param_state, flow_block,
       wait_ms, occupying, occ_grant, fslots, rl_info, degrade_block,
       cb_state, latest_passed)

    with param_state = (prows, qps_add, thread_add) or None, and every
    *_block already masked by its stage's eligibility."""
    b = acq.res.shape[0]
    zero_block = jnp.zeros((b,), bool)

    if "authority" in features:
        auth_block = _check_authority(cfg, rules, acq) & valid & ~forced
    else:
        auth_block = zero_block
    eligible = valid & ~auth_block & ~forced

    if "system" in features:
        sys_block = _check_system(
            cfg, state, rules, acq, now_ms, sys_load, sys_cpu, eligible
        )
    else:
        sys_block = zero_block
    eligible = eligible & ~sys_block

    if "param" in features:
        param_block, prows, p_qps_add, p_thread_add = _check_param(
            cfg, state, rules, acq, now_ms, eligible
        )
        param_block = param_block & eligible
        param_state = (prows, p_qps_add, p_thread_add)
    else:
        param_block = zero_block
        param_state = None
    eligible = eligible & ~param_block

    if "flow" in features:
        (
            flow_block,
            wait_ms,
            latest_passed,
            occupying,
            occ_grant,
            fslots,
            rl_info,
        ) = _check_flow(
            cfg, state, rules, acq, now_ms, eligible, occupy="occupy" in features
        )
        flow_block = flow_block & eligible
        occupying = occupying & eligible
    else:
        flow_block = zero_block
        occupying = zero_block
        occ_grant = None
        fslots = None
        rl_info = None
        latest_passed = None
        wait_ms = jnp.zeros((b,), jnp.int32)
    if "tail_flow" in features and cfg.sketch_stats:
        tail_block = _check_tail_flow(cfg, state, rules, acq, now_ms, eligible)
        flow_block = flow_block | (tail_block & eligible)
    eligible = eligible & ~flow_block

    if "degrade" in features:
        degrade_block, cb_state = _check_degrade(
            cfg, state, rules, acq, now_ms, eligible
        )
        degrade_block = degrade_block & eligible
    else:
        degrade_block = zero_block
        cb_state = state.cb_state

    return (
        auth_block,
        sys_block,
        param_block,
        param_state,
        flow_block,
        wait_ms,
        occupying,
        occ_grant,
        fslots,
        rl_info,
        degrade_block,
        cb_state,
        latest_passed,
    )


def _cb_moves(before, exited, after):
    """The STAT_CB_* counts of a tick from the breakers' state as the tick
    found it, after its exits and after its checks: five reductions over the
    rule axis (the pad slot is never enabled and stays CLOSED)."""

    def n(was, frm, now, to):
        return jnp.sum((was == frm) & (now == to))

    return (
        n(before, D.CB_CLOSED, exited, D.CB_OPEN),
        n(exited, D.CB_OPEN, after, D.CB_HALF_OPEN),
        n(before, D.CB_HALF_OPEN, exited, D.CB_CLOSED),
        n(before, D.CB_HALF_OPEN, exited, D.CB_OPEN),
        jnp.sum(after != D.CB_CLOSED),
    )


def _telemetry_and_output(
    cfg, state, rules, acq, verdict, wait_ms, valid, forced, fslots, now_ms,
    seg_dropped, n_seg, cb_moves=None,
) -> TickOutput:
    """The tick's last two stages, shared by the fused and the plain path:
    the device telemetry reads, then the output (packed wire or columns)."""
    with jax.named_scope("stage.telemetry"):
        stats = None
        res_stats = None
        if cfg.device_telemetry:
            stats = _device_stats(
                cfg, state, rules, acq, verdict, valid, now_ms, seg_dropped, n_seg,
                cb_moves,
            )
            if timeline_k(cfg) > 0:
                res_stats = _device_res_stats(cfg, state, now_ms)
        hot = None
        if hotset_k(cfg) > 0:
            hot = _device_hot_candidates(cfg, state, acq, valid, now_ms)
        expl = None
        if explain_k(cfg) > 0:
            expl = _device_explain(
                cfg, state, rules, acq, verdict, valid, forced, fslots, now_ms
            )
    with jax.named_scope("stage.pack"):
        return _tick_output(
            cfg, verdict, wait_ms, seg_dropped, stats, res_stats, hot, expl
        )


def tick(
    state: EngineState,
    rules: RuleSet,
    acq: AcquireBatch,
    comp: CompleteBatch,
    now_ms: jax.Array,  # int32 scalar, engine epoch ms
    sys_load: jax.Array,  # float32 scalar — host-sampled load average
    sys_cpu: jax.Array,  # float32 scalar — host-sampled CPU usage [0,1]
    cfg: EngineConfig,
    features: frozenset = ALL_FEATURES,
) -> Tuple[EngineState, TickOutput]:
    """One engine tick: completions, then batched decisions, then effects."""
    b = acq.res.shape[0]
    now_ms = now_ms.astype(jnp.int32)
    if cfg.packed_wire:
        # narrow uploads (ops/wire.py) widen here, before anything else
        # touches the batch — every stage below sees the classic int32
        # columns, so the packed and classic ticks share one code path
        with jax.named_scope("stage.widen"):
            acq = WIRE.widen_acquire(acq)
            comp = WIRE.widen_complete(comp)
    zero_block = jnp.zeros((b,), bool)

    # segment-compacted effects (ops/engine_seg.py): build the key-run
    # structure once per side; each effects phase lax.cond-falls back to
    # the per-item kernels when live segments exceed capacity
    use_seg = cfg.seg_effects and _use_fused(cfg)
    if use_seg:
        # binds ES for every use_seg-guarded block below (checks, effects)
        from sentinel_tpu.ops import engine_seg as ES

        with jax.named_scope("stage.seg_prepare"):
            ctx_c, carry_c = ES.prepare_completions(cfg, comp, features)
            ctx_a, carry_a = ES.prepare_acquire(cfg, acq)

    # 1. exits first: they release concurrency and update breakers
    seg_dropped = jnp.int32(0)
    cb_before = state.cb_state
    with jax.named_scope("stage.exits"):
        if use_seg:
            if cfg.seg_fallback:
                state = jax.lax.cond(
                    ctx_c.ok,
                    lambda: ES.process_completions_seg(
                        cfg, state, rules, comp, now_ms, features, ctx_c, carry_c
                    ),
                    lambda: _process_completions_fused(
                        cfg, state, rules, comp, now_ms, features
                    ),
                )
            else:
                state = ES.process_completions_seg(
                    cfg, state, rules, comp, now_ms, features, ctx_c, carry_c
                )
                seg_dropped = seg_dropped + ES.dropped_items(
                    ctx_c, comp.res != cfg.trash_row
                )
        elif _use_fused(cfg):
            state = _process_completions_fused(cfg, state, rules, comp, now_ms, features)
        else:
            state = _process_completions(cfg, state, rules, comp, now_ms, features)

    # 2. warm-up token sync (per second, vectorized over rules)
    with jax.named_scope("stage.warmup"):
        if "warmup" in features:
            state = _sync_warmup(cfg, state, rules, now_ms)
        if "occupy" in features and "flow" in features:
            state = _fold_occupied(cfg, state, now_ms)

    valid = acq.res != cfg.trash_row
    forced = valid & (acq.pre_verdict > 0)

    # the hot-parameter store's stale bucket is cleared, and further down its
    # current one written, HERE and not inside the check and effects phases:
    # a lax.cond branch that updates its operand copies it whole, and a wide
    # store is 256 MiB (PERF.md section 6, PR 33: four such copies a tick)
    if "param" in features:
        with jax.named_scope("stage.param_refresh"):
            pcms, pcms_epochs, pcms_idx = P.refresh(
                state.pcms, state.pcms_epochs, now_ms, cfg
            )
            state = state._replace(pcms=pcms, pcms_epochs=pcms_epochs)

    # 3. rule checks in reference slot order; each stage's blocks remove
    #    the item from later stages' rank accounting.  With segmented
    #    effects + single-rule lanes the whole phase switches between the
    #    segment-level implementation (ops/engine_seg.run_checks_seg) and
    #    this per-item one — verdicts are exact in both.
    seg_checks = (
        use_seg
        and cfg.flow_rules_per_resource == 1
        and cfg.degrade_rules_per_resource == 1
        and cfg.param_rules_per_resource == 1
    )
    with jax.named_scope("stage.checks"):
        if seg_checks and not cfg.seg_fallback:
            # presorting callers (seg_fallback=False): run the segment check
            # phase UNCONDITIONALLY — the lax.cond boundary alone cost ~1.4 ms
            # at B=128K (operand/result copies) plus the whole plain branch's
            # compile.  Items in segments past seg_u FAIL CLOSED (sys_block
            # inside run_checks_seg) and are already counted in seg_dropped.
            checks = ES.run_checks_seg(
                cfg, state, rules, acq, now_ms, sys_load, sys_cpu,
                valid, forced, ctx_a, carry_a, features,
            )
        elif seg_checks:
            checks = jax.lax.cond(
                ctx_a.ok,
                lambda: ES.run_checks_seg(
                    cfg, state, rules, acq, now_ms, sys_load, sys_cpu,
                    valid, forced, ctx_a, carry_a, features,
                ),
                lambda: _run_checks_plain(
                    cfg, state, rules, acq, now_ms, sys_load, sys_cpu,
                    valid, forced, features,
                ),
            )
        else:
            checks = _run_checks_plain(
                cfg, state, rules, acq, now_ms, sys_load, sys_cpu,
                valid, forced, features,
            )
    (
        auth_block,
        sys_block,
        param_block,
        param_state,
        flow_block,
        wait_ms,
        occupying,
        occ_grant,
        fslots,
        rl_info,
        degrade_block,
        cb_state,
        latest_passed,
    ) = checks
    cb_moves = None
    if "degrade" in features and cfg.device_telemetry:
        cb_moves = _cb_moves(cb_before, state.cb_state, cb_state)
    state = state._replace(cb_state=cb_state)
    if latest_passed is not None:
        state = state._replace(latest_passed_ms=latest_passed)
    if "param" in features:
        prows, p_qps_add, p_thread_add = param_state

    with jax.named_scope("stage.verdict"):
        passed = valid & ~forced & ~(
            auth_block | sys_block | param_block | flow_block | degrade_block
        )
        # occupy grants only COMMIT for items that finally pass — a grant
        # revoked by a later slot (e.g. an open circuit breaker) books nothing
        occupying = occupying & passed
        fused = _use_fused(cfg)
        if occ_grant is not None and not fused:
            grant_lane, onodes, ocnt = occ_grant
            b_k = grant_lane.shape[0] // b
            commit = grant_lane & _fan(occupying, b_k)
            # node-keyed booking (FutureBucket lives on the node): one
            # histogram over the node table
            add = T.histogram(
                cfg,
                jnp.where(commit, onodes, jnp.int32(-1)),
                jnp.where(commit, jnp.round(ocnt).astype(jnp.int32), 0),
                cfg.node_rows,
            ).astype(jnp.float32)
            cur_wid = W.wid_of(now_ms, cfg.second_window_ms)
            pool_vec = jnp.where(state.occ_epoch == cur_wid + 1, state.occ_tokens, 0.0)
            state = state._replace(
                occ_tokens=pool_vec + add,
                occ_epoch=jnp.where(add > 0, cur_wid + 1, state.occ_epoch),
            )

        verdict = jnp.full((b,), PASS, dtype=jnp.int8)
        verdict = jnp.where(forced, acq.pre_verdict.astype(jnp.int8), verdict)
        verdict = jnp.where(auth_block, jnp.int8(BLOCK_AUTHORITY), verdict)
        verdict = jnp.where(sys_block, jnp.int8(BLOCK_SYSTEM), verdict)
        verdict = jnp.where(param_block, jnp.int8(BLOCK_PARAM), verdict)
        verdict = jnp.where(flow_block, jnp.int8(BLOCK_FLOW), verdict)
        verdict = jnp.where(degrade_block, jnp.int8(BLOCK_DEGRADE), verdict)
        verdict = jnp.where(passed & (wait_ms > 0), jnp.int8(PASS_WAIT), verdict)
        wait_ms = jnp.where(passed, wait_ms, 0)

    # 4. effects: pass/block statistics (StatisticSlot.java:54-123).
    # Occupying entries count OCCUPIED now; their PASS lands when the
    # borrowed bucket becomes current (_fold_occupied), so the next
    # window's budget is reduced by exactly the borrowed amount.
    if fused:
        with jax.named_scope("stage.effects"):
            param_ctx = None
            if "param" in features:
                param_ctx = (prows, p_qps_add, p_thread_add)
            if use_seg:
                if cfg.seg_fallback:
                    state, p_upd = jax.lax.cond(
                        ctx_a.ok,
                        lambda: ES.acquire_effects_seg(
                            cfg, state, rules, acq, now_ms, features, passed,
                            occupying, valid, fslots, occ_grant, rl_info,
                            param_ctx, ctx_a, carry_a,
                        ),
                        lambda: _acquire_effects_fused(
                            cfg, state, rules, acq, now_ms, features, passed,
                            occupying, valid, fslots, occ_grant, rl_info,
                            param_ctx,
                        ),
                    )
                else:
                    state, p_upd = ES.acquire_effects_seg(
                        cfg, state, rules, acq, now_ms, features, passed,
                        occupying, valid, fslots, occ_grant, rl_info,
                        param_ctx, ctx_a, carry_a,
                    )
                    seg_dropped = seg_dropped + ES.dropped_items(ctx_a, valid)
            else:
                state, p_upd = _acquire_effects_fused(
                    cfg, state, rules, acq, now_ms, features, passed,
                    occupying, valid, fslots, occ_grant, rl_info, param_ctx,
                )
            if p_upd is not None:
                counts, conc = p_upd  # int32 P.conc_shape(cfg) each
                state = state._replace(
                    pcms=P.land(cfg, state.pcms, pcms_idx, counts),
                    pconc=jnp.maximum(state.pconc + conc, 0),
                )
        return state, _telemetry_and_output(
            cfg, state, rules, acq, verdict, wait_ms, valid, forced, fslots,
            now_ms, seg_dropped, ctx_a.n_seg if use_seg else 0, cb_moves,
        )

    with jax.named_scope("stage.effects"):
        with_nodes = "nodes" in features
        rows = _stat_rows(cfg, acq.res, acq.ctx_node, acq.origin_node, with_nodes)
        # planes (PASS, BLOCK, OCCUPIED) only — the entry path writes no others
        pass_c, block_c, occ_c, entry_deltas = _acquire_entry_stats(
            cfg, acq, valid, passed, occupying
        )
        deltas1 = jnp.stack([pass_c, block_c, occ_c], axis=1)

        def _land_acq(fanned: bool):
            rws = _stat_rows(cfg, acq.res, acq.ctx_node, acq.origin_node, fanned)
            f = 3 if fanned else 1
            return _stat_update(
                cfg,
                state,
                now_ms,
                rws,
                jnp.tile(deltas1, (f, 1)) if fanned else deltas1,
                None,
                entry_deltas,
                None,
                None,
                plane_idx=(W.EV_PASS, W.EV_BLOCK, W.EV_OCCUPIED),
            )

        if with_nodes:
            any_fan = jnp.any(
                valid
                & ((acq.ctx_node != cfg.trash_row) | (acq.origin_node != cfg.trash_row))
            )
            state, hist = jax.lax.cond(
                any_fan, lambda: _land_acq(True), lambda: _land_acq(False)
            )
        else:
            state, hist = _land_acq(False)
        if cfg.sketch_stats:
            gvals = jnp.stack(
                [
                    jnp.where(passed, acq.count, 0),
                    jnp.where(valid & ~passed, acq.count, 0),
                ],
                axis=1,
            )
            # completion phase already refreshed this now_ms's bucket — skip
            # the second masked-multiply copy of the whole counts tensor
            state = state._replace(
                gs=_sketch(cfg).add(
                    state.gs,
                    now_ms,
                    acq.res,
                    gvals,
                    (W.EV_PASS, W.EV_BLOCK),
                    valid,
                    sketch_config(cfg),
                    pre_refreshed=True,
                    ecfg=cfg,
                )
            )

        if hist is not None:  # MXU: concurrency rides the pass+occupied histogram
            # (the histogram already carries the ENTRY-row reduction; occupying
            # entries hold a concurrency slot even though their PASS lands later)
            concurrency = state.concurrency + hist[:, W.EV_PASS] + hist[:, W.EV_OCCUPIED]
        else:
            fan = 3 if with_nodes else 1
            rows = _stat_rows(cfg, acq.res, acq.ctx_node, acq.origin_node, with_nodes)
            inc = jnp.tile(jnp.where(passed, acq.count, 0), (fan,))
            concurrency = state.concurrency.at[rows].add(inc, mode="drop")
            concurrency = concurrency.at[cfg.entry_node_row].add(
                entry_deltas[W.EV_PASS] + entry_deltas[W.EV_OCCUPIED]
            )
        state = state._replace(concurrency=concurrency)

        # warm-up drain accounting: exact per-slot admitted counts this second
        # (pad-slot lanes drop — row F is never read, and dropping keeps this
        # bit-identical with the fused path's row masking)
        if "warmup" in features and fslots is not None:
            K = cfg.flow_rules_per_resource
            adm = _fan(passed, K) & (fslots < cfg.max_flow_rules)
            acc_add = T.small_scatter_add(
                cfg,
                jnp.zeros((cfg.max_flow_rules + 1,), jnp.float32),
                jnp.where(adm, fslots, jnp.int32(-1)),
                jnp.where(adm, _fan(acq.count, K).astype(jnp.float32), 0.0),
            )
            state = state._replace(warm_acc=state.warm_acc + acc_add)

        # param pass counting + THREAD concurrency (only admitted traffic
        # consumes the per-value budget, like the token bucket decrement in
        # ParamFlowChecker.passDefaultLocalCheck; ParamFlowSlot entry thread++)
        if "param" in features:
            KP = cfg.param_rules_per_resource
            adm = _fan(passed, KP)
            pcms = P.add(
                state.pcms,
                pcms_idx,
                jnp.where((p_qps_add & adm)[:, None], prows, -1),
                _fan(acq.count, KP),
                cfg,
            )
            thread_mask = p_thread_add & adm
            pconc = jax.lax.cond(
                jnp.any(thread_mask),
                lambda: P.conc_add(
                    cfg,
                    state.pconc,
                    jnp.where(thread_mask[:, None], prows, -1),
                    _fan(acq.count, KP),
                    jnp.zeros_like(_fan(acq.count, KP)),
                ),
                lambda: state.pconc,
            )
            state = state._replace(pcms=pcms, pconc=pconc)

    return state, _telemetry_and_output(
        cfg, state, rules, acq, verdict, wait_ms, valid, forced, fslots,
        now_ms, 0, 0, cb_moves,
    )


def replace_system_columns(ruleset: RuleSet, system: RT.SystemTensors) -> RuleSet:
    """Swap ONLY the system-threshold columns of a live ruleset — the
    adaptive controller's upload path (sentinel_tpu/adaptive).

    The SystemTensors leaves are ordinary traced arguments of the jitted
    tick, so publishing new VALUES (five scalars, same shapes/dtypes) is
    a plain device transfer: no retrace, no recompile, jaxpr
    fingerprints untouched.  Each leaf is device_put as its own buffer —
    two leaves must never share one (the XLA argument-dedup hazard
    documented on _empty_batch)."""
    return ruleset._replace(system=jax.device_put(system))


def compile_ruleset(
    cfg: EngineConfig,
    registry,
    flow_rules=(),
    degrade_rules=(),
    param_rules=(),
    authority_rules=(),
    system_rules=(),
    param_lanes=None,
) -> RuleSet:
    """Host-side: compile rule objects into a device-resident RuleSet.

    ``param_lanes``: optional resource -> ordered param_idx list from
    rule_tensors.param_lanes — pass the host client's map so engine lanes
    match the hashes the client computes per entry.

    QPS flow rules whose resource resolves to a SKETCH id (exact row space
    exhausted, promotion failed) compile into the tail threshold tables;
    other grades/behaviors on tail resources cannot be enforced and log a
    warning."""
    # materialize BEFORE anything reads them: callers may pass one-shot
    # iterables, and a drained generator here would silently compile an
    # empty (fail-open) ruleset
    flow_rules = list(flow_rules)
    degrade_rules = list(degrade_rules)
    param_rules = list(param_rules)
    _span = OT.TRACER.begin(
        "engine.compile_ruleset",
        flow=len(flow_rules),
        degrade=len(degrade_rules),
        param=len(param_rules),
    )
    # span ends in finally: a rule push that raises mid-compile (device
    # OOM, malformed rule) is exactly the slow event worth seeing traced
    try:
        rs = _compile_ruleset(
            cfg, registry, flow_rules, degrade_rules, param_rules,
            authority_rules, system_rules, param_lanes,
        )
        # memory ledger: compiled rule tensors are the "rules" pool (the
        # latest compile at this site replaces the previous claim)
        PROF.LEDGER.track("rules", "engine.compile_ruleset", rs)
        return rs
    finally:
        OT.TRACER.end(_span)


def _compile_ruleset(
    cfg: EngineConfig,
    registry,
    flow_rules,
    degrade_rules,
    param_rules,
    authority_rules,
    system_rules,
    param_lanes,
) -> RuleSet:
    tail = []
    exact_flow = []
    for r in flow_rules:
        rid = registry.resource_id(r.resource) if r.resource else None
        if rid is not None and rid >= cfg.node_rows:
            from sentinel_tpu.core.rules import (
                CONTROL_DEFAULT as _CD,
                GRADE_QPS as _GQ,
                STRATEGY_DIRECT as _SD,
            )

            if (
                r.grade == _GQ
                and r.control_behavior == _CD
                and r.strategy == _SD
                # the tail table has no origin dimension: an origin-scoped
                # rule compiled there would throttle EVERY origin
                and (r.limit_app or "default") == "default"
                and cfg.sketch_stats
            ):
                tail.append((rid, float(r.count)))
            else:
                from sentinel_tpu.utils.record_log import record_log

                record_log().warning(
                    "flow rule on tail resource %r needs exact windows "
                    "(grade/behavior/strategy/limitApp unsupported in the "
                    "tail) and will NOT be enforced; free exact rows or "
                    "simplify it",
                    r.resource,
                )
        else:
            exact_flow.append(r)
    rs = RuleSet(
        flow=RT.compile_flow_rules(exact_flow, cfg, registry),
        degrade=RT.compile_degrade_rules(degrade_rules, cfg, registry),
        param=RT.compile_param_rules(
            param_rules, cfg, registry, lanes=param_lanes
        ),
        auth=RT.compile_authority_rules(list(authority_rules), cfg, registry),
        system=RT.compile_system_rules(list(system_rules), cfg),
        tail=RT.compile_tail_flow_rules(tail, cfg),
    )
    return jax.device_put(rs)


def migrate_state(
    state: EngineState,
    old_cfg: EngineConfig,
    new_cfg: EngineConfig,
    now_ms: int,
) -> EngineState:
    """Carry engine state across a WINDOW-SHAPE change (the live analog of
    IntervalProperty/SampleCountProperty, node/IntervalProperty.java —
    which the reference handles by resetting node metrics; here the
    current windowed totals MIGRATE so admission budgets don't reopen).

    Only OPERATING-POINT knobs may differ: window shapes (second/minute
    sample counts + lengths), batch shapes (``batch_size`` /
    ``complete_batch_size`` — safe because no ``init_state`` leaf is
    batch-shaped; only the traced tick signature changes) and the sketch
    window shape (``sketch_sample_count`` / ``sketch_window_ms`` /
    ``sketch_slack_frac`` — gs restarts fresh below when its grid
    changes, the same dashboard-only transient a window reshape has).
    Capacity knobs must match — the callers (SentinelClient.
    update_window_shape / apply_operating_point) guarantee it.  Sliding
    detail below bucket granularity is coarsened: the old window's
    TOTALS land in the new shape's current bucket, so the new window
    initially sees the whole old window (budgets stay conservative) and
    decays after one new interval.

    gs/rtq observability re-initializes when their bucket grid changes —
    a transient visible only to dashboards, never to rule checks."""
    import dataclasses

    same_caps = dataclasses.replace(
        old_cfg,
        second_sample_count=new_cfg.second_sample_count,
        second_window_ms=new_cfg.second_window_ms,
        minute_sample_count=new_cfg.minute_sample_count,
        minute_window_ms=new_cfg.minute_window_ms,
        batch_size=new_cfg.batch_size,
        complete_batch_size=new_cfg.complete_batch_size,
        sketch_sample_count=new_cfg.sketch_sample_count,
        sketch_window_ms=new_cfg.sketch_window_ms,
        sketch_slack_frac=new_cfg.sketch_slack_frac,
    )
    if same_caps != new_cfg:
        raise ValueError(
            "migrate_state only supports operating-point changes "
            "(window/batch/sketch shapes)"
        )

    now = jnp.int32(now_ms)
    out = init_state(new_cfg)

    def carry(old_win, o_cfg: W.WindowConfig, n_cfg: W.WindowConfig, new_win):
        counts = W.window_counts(old_win, now, o_cfg)  # [rows, NE]
        rt_tot, rt_min = W.window_rt(old_win, now, o_cfg)
        wid = W.wid_of(now, n_cfg.window_ms)
        idx = W.current_index(now, n_cfg)
        return W.WindowState(
            counts=new_win.counts.at[:, idx, :].set(counts.astype(jnp.int32)),
            rt_sum=new_win.rt_sum.at[:, idx].set(rt_tot),
            rt_min=new_win.rt_min.at[:, idx].set(rt_min),
            epochs=new_win.epochs.at[idx].set(wid),
            # running sums mirror the single carried bucket exactly
            run=counts.astype(jnp.int32),
            run_rt=rt_tot,
            run_rt_min=rt_min,
            rot_wid=jnp.asarray(wid, jnp.int32),
        )

    o_sec = W.WindowConfig(old_cfg.second_sample_count, old_cfg.second_window_ms)
    n_sec = W.WindowConfig(new_cfg.second_sample_count, new_cfg.second_window_ms)
    win_sec = carry(state.win_sec, o_sec, n_sec, out.win_sec)
    win_min = out.win_min
    if new_cfg.enable_minute_window and old_cfg.enable_minute_window:
        o_min = W.WindowConfig(old_cfg.minute_sample_count, old_cfg.minute_window_ms)
        n_min = W.WindowConfig(new_cfg.minute_sample_count, new_cfg.minute_window_ms)
        win_min = carry(state.win_min, o_min, n_min, out.win_min)

    # shape-stable fields carry over verbatim; gs/rtq keep their state when
    # the grid is unchanged, else restart fresh (gs is impl-polymorphic —
    # GS.SketchState or sketch/salsa.SalsaState — so compare leaf shapes)
    gs = (
        state.gs
        if type(out.gs) is type(state.gs)
        and all(
            a.shape == b.shape
            for a, b in zip(
                jax.tree_util.tree_leaves(out.gs),
                jax.tree_util.tree_leaves(state.gs),
            )
        )
        else out.gs
    )
    rtq = state.rtq if out.rtq.counts.shape == state.rtq.counts.shape else out.rtq
    return out._replace(
        win_sec=win_sec,
        win_min=win_min,
        concurrency=state.concurrency,
        latest_passed_ms=state.latest_passed_ms,
        warmup_tokens=state.warmup_tokens,
        warmup_last_s=state.warmup_last_s,
        warm_acc=state.warm_acc,
        # occupy epochs are denominated in second-window ids: a changed
        # bucket length invalidates them, so pending borrowed-ahead grants
        # drop (their holders already got PASS_WAIT; only the deferred
        # PASS statistic is lost — bounded by one bucket's borrow pool)
        occ_tokens=state.occ_tokens
        if old_cfg.second_window_ms == new_cfg.second_window_ms
        else out.occ_tokens,
        occ_epoch=state.occ_epoch
        if old_cfg.second_window_ms == new_cfg.second_window_ms
        else out.occ_epoch,
        cb_state=state.cb_state,
        cb_retry_ms=state.cb_retry_ms,
        cb_counts=state.cb_counts,
        cb_epochs=state.cb_epochs,
        pcms=state.pcms,
        pcms_epochs=state.pcms_epochs,
        pconc=state.pconc,
        gs=gs,
        rtq=rtq,
    )


def tick_wire_in(
    state: EngineState,
    rules: RuleSet,
    wire_in: jax.Array,  # uint32 [InputLayout.total] — ops/wire.py
    cfg: EngineConfig,
    features: frozenset = ALL_FEATURES,
) -> Tuple[EngineState, TickOutput]:
    """The tick as the packed client calls it: the whole per-tick input
    is ONE buffer, unpacked here into the batches and scalars ``tick``
    takes, so everything below sees the classic int32 columns."""
    with jax.named_scope("stage.unpack"):
        lo = WIRE.input_layout_of(cfg, wire_in.shape[0])
        acq, comp, now_ms, sys_load, sys_cpu = WIRE.unpack_tick_input(wire_in, lo)
    return tick(
        state, rules, acq, comp, now_ms, sys_load, sys_cpu, cfg=cfg,
        features=features,
    )


_TICK_CACHE: dict = {}
_TICK_CACHE_LOCK = threading.Lock()
#: the jitted tick's program name (``jit_sentinel_tick`` in a trace)
TICK_PROGRAM = "sentinel_tick"

#: distinct compiled-tick builds this process created (each is a future
#: XLA compile; a climbing count in steady state means config churn)
_C_TICK_BUILDS = _OBS.counter(
    "sentinel_engine_tick_builds_total",
    "distinct (config, features) tick callables built (each = one XLA compile)",
)


def make_tick(
    cfg: EngineConfig,
    donate: bool = True,
    jit: bool = True,
    features: frozenset = ALL_FEATURES,
    wire_in: bool = False,
):
    """Build the compiled tick for a given engine config.

    Cached per (cfg, donate, features, wire_in) — EngineConfig is
    frozen/hashable — so multiple clients with the same config share one
    compiled executable (compile is the expensive part, especially on the
    first call).

    ``features`` compiles only the stages the rule set needs — the SPI
    slot-chain analog; a flow-only service pays nothing for param/degrade/
    authority machinery, and "nodes" off drops the ctx/origin stat fan-out.

    ``wire_in`` gives the packed client's form, ``(state, rules, wire_in)
    -> (state, out)`` (:func:`tick_wire_in`), under the same program name;
    the default is :func:`tick`'s own signature.
    """
    key = (cfg, donate, jit, features, wire_in)
    # check-then-act under the cache lock: the background seg_u-resize
    # thread and the serving thread race here on a rule reload, and two
    # distinct jitted callables for one key would each pay the multi-
    # second XLA compile (jax.jit itself is lazy, so holding the lock
    # across it costs microseconds)
    with _TICK_CACHE_LOCK:
        fn = _TICK_CACHE.get(key)
        if fn is None:
            fn = functools.partial(
                tick_wire_in if wire_in else tick, cfg=cfg, features=features
            )
            if jit:
                # a bare partial compiles as ``jit__unknown``: the name is
                # what a profiler trace lists the tick program under
                fn.__name__ = TICK_PROGRAM
                fn = jax.jit(fn, donate_argnums=(0,) if donate else ())
            _TICK_CACHE[key] = fn
            # a fresh tick build is a hot-swap/recompile PRECURSOR worth
            # seeing in traces: the XLA compile itself lands at first call
            _C_TICK_BUILDS.inc()
            OT.event(
                "engine.make_tick",
                attrs={"features": ",".join(sorted(features)), "seg_u": cfg.seg_u},
            )
            # retrace observatory (obs/profile.py): the miss is journaled
            # with its CAUSE — the key diff against the previous build —
            # and counted expected/surprise.  Cache hits never reach here.
            PROF.RETRACE.observe(
                "engine.tick", cfg=cfg, donate=donate, jit=jit,
                features=features, wire_in=wire_in,
            )
    return fn
