"""Segment-compacted effect phases (round-4 aggregation primitive).

Drop-in replacements for the fused effects megakernel phases of
ops/engine.py (_process_completions_fused / _acquire_effects_fused) that
contract ONE entry per batch *segment* instead of one per item.  A
segment is a maximal run of items sharing every scatter-relevant key
(resource, ctx/origin nodes, origin id), capped at 256 items
(ops/segment.py) — Zipf traffic at B=128K compacts ~11x, and the one-hot
digit-dot cost of every scatter kernel shrinks proportionally.

Dataflow per side (built for exactly two compaction passes):
  1. prepare_*: everything known at batch arrival (stat digit cumsums,
     row columns, the rowmin running minimum) rides the ONE build sort
     as payload operands — compaction costs nothing beyond the sort.
  2. values that exist only after rule checks (acquire pass/block masks,
     degrade event masks) pack into ONE [N, cols] matrix and take a
     single row gather at seg_end.

Correctness does NOT require the batch to be sorted: segments are runs of
EQUAL keys, and all landed quantities are order-independent (integer
digit-plane sums; f32 minima).  An unsorted batch merely produces more
segments; when the live segment count exceeds the static capacity
(cfg.seg_u), the engine either lax.cond-falls back to the per-item fused
path (seg_fallback=True, always exact) or drops overflow segments'
effects and reports TickOutput.seg_dropped (seg_fallback=False).

Hot-parameter scatters key on (rule, value-hash) — not segment-constant —
so they stay on the item axis in a second, small kernel call.

Reference map: same per-request semantics as StatisticSlot.java:54-164 /
DegradeSlot.exit:60-75 / ParamFlowSlot — this file only changes the
aggregation schedule, not what is counted.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from sentinel_tpu.core.config import EngineConfig
from sentinel_tpu.ops import fused as FU
from sentinel_tpu.ops import gsketch as GS
from sentinel_tpu.sketch import impl_for as _sketch
from sentinel_tpu.ops import param as P
from sentinel_tpu.ops import rowmin as RM
from sentinel_tpu.ops import rtq as RQ
from sentinel_tpu.ops import segment as SG
from sentinel_tpu.ops import segscan as SC
from sentinel_tpu.ops import tables as T
from sentinel_tpu.ops import window as W
from sentinel_tpu.ops import wire as WIRE

#: rowmin sentinel (> any valid rt; replaced by drop row before scatter)
_RT_ABSENT = 3.0e38


def seg_capacity(cfg: EngineConfig, b: int, full: Optional[int] = None) -> int:
    """Static compacted-axis capacity of a side of ``b`` rows, ``full`` at
    the full tick shape (default: the acquire side's, cfg.batch_size):
    explicit cfg.seg_u, else sized for Zipf-like traffic (distinct keys
    ~9-17% of a full batch, measured) plus the 256-block split overhead,
    with headroom.  A middle tick shape (ops/wire.tick_shapes) holds a
    smaller sample of the same stream, whose keys repeat less (16,384 rows
    of Zipf(1.3) read 2,560-2,860 segments, 16-17% of the rows, and the
    middle ticks of 32,768 up to 4,696: PERF.md section 6), and no seg_u
    resize recovers its overflow (runtime/client._note_seg_count), so it
    gets a quarter of its rows."""
    if cfg.seg_u:
        return cfg.seg_u
    share = 4 if WIRE.LIGHT_ROWS < b < (full or cfg.batch_size) else 8
    return min(b, b // share + b // SG.BLOCK + 64)


def dropped_items(ctx: SG.SegCtx, valid: Optional[jax.Array] = None) -> jax.Array:
    """Items whose effects a no-fallback compacted pass dropped: segments
    are item-contiguous in sid order, so everything past the last kept
    segment's end is dropped when capacity overflows.  ``valid`` (when
    given) excludes trash-row padding from the count — a short batch
    padded to shape would otherwise report dropped "items" whose effects
    were no-ops anyway."""
    n = ctx.head.shape[0]
    kept = ctx.seg_end[-1] + 1
    if valid is None:
        late = jnp.int32(n) - kept
    else:
        iota = jnp.arange(n, dtype=jnp.int32)
        late = jnp.sum((valid & (iota >= kept)).astype(jnp.int32))
    return jnp.where(ctx.ok, jnp.int32(0), late)


class CompCarry(NamedTuple):
    """Sort-carried compacted payloads of one completion batch."""

    ce: list  # cumsum-at-tail cols for (success, error, rt_q)
    split: list
    min_rt: jax.Array  # [U] per-segment min rt (or _RT_ABSENT)
    res: jax.Array  # [U]
    ctx_node: jax.Array
    origin_node: jax.Array


class AcqCarry(NamedTuple):
    res: jax.Array  # [U]
    ctx_node: jax.Array
    origin_node: jax.Array
    origin_id: jax.Array
    ctx_name: jax.Array
    res_sorted: jax.Array  # bool scalar — res nondecreasing over the batch


def prepare_completions(cfg: EngineConfig, comp, features: frozenset):
    """Build the completion-side SegCtx with every batch-known payload
    riding the compaction sort."""
    valid = comp.res != cfg.trash_row
    succ_w = jnp.where(valid, comp.success, 0)
    err_w = jnp.where(valid, comp.error, 0)
    rt1 = jnp.where(valid, comp.rt, 0.0)
    rt_q = jnp.round(
        jnp.minimum(rt1, float(cfg.statistic_max_rt)) * 8.0
    ).astype(jnp.int32)
    # the fused kernels' documented count envelope (cfg.max_batch_count,
    # cd=1 digit) applies to completion success/error exactly like the
    # per-item fused path; rt_q spans two digit planes
    cm = cfg.max_batch_count
    rtm = int(cfg.statistic_max_rt) * 8
    C_rows, split = SG.cum_cols([succ_w, err_w, rt_q], [cm, cm, rtm])
    head = SG.heads_from_keys(comp.res, comp.ctx_node, comp.origin_node)
    inc_min = SC.seg_incl_min_pl(
        head,
        jnp.where(valid & (rt1 > 0), rt1, jnp.float32(_RT_ABSENT)),
        _RT_ABSENT,
    )
    U = seg_capacity(cfg, comp.res.shape[0], cfg.complete_batch_size)
    ctx, carried = SG.build_from_head(
        head,
        U,
        payloads=list(C_rows)
        + [inc_min, comp.res, comp.ctx_node, comp.origin_node],
    )
    nC = len(C_rows)
    carry = CompCarry(
        ce=carried[:nC],
        split=split,
        min_rt=jnp.where(ctx.live, carried[nC], jnp.float32(_RT_ABSENT)),
        res=carried[nC + 1],
        ctx_node=carried[nC + 2],
        origin_node=carried[nC + 3],
    )
    return ctx, carry


def prepare_acquire(cfg: EngineConfig, acq):
    """Acquire-side SegCtx; only row sources are batch-known (values come
    after the checks via one packed gather)."""
    U = seg_capacity(cfg, acq.res.shape[0])
    ctx, carried = SG.build(
        [acq.res, acq.ctx_node, acq.origin_node, acq.origin_id, acq.ctx_name],
        U,
        payloads=[
            acq.res, acq.ctx_node, acq.origin_node, acq.origin_id, acq.ctx_name
        ],
    )
    return ctx, AcqCarry(
        res=carried[0],
        ctx_node=carried[1],
        origin_node=carried[2],
        origin_id=carried[3],
        ctx_name=carried[4],
        res_sorted=jnp.all(acq.res[1:] >= acq.res[:-1]),
    )


def _chunks_to_planes(chunk_lists):
    """sums_from_ce output -> (vals [P2, U], digits tuple, spec per plane)."""
    vals, digits, spec = [], [], []
    for chunks in chunk_lists:
        s = []
        for arr, w, dig in chunks:
            s.append((len(vals), w))
            vals.append(arr)
            digits.append(dig)
        spec.append(s)
    return jnp.stack(vals), tuple(digits), spec


def _recombine(out, spec):
    """Scatter output [n, P2] -> one exact int32 [n] column per plane."""
    o = jnp.round(out).astype(jnp.int32)
    return [sum(o[:, i] * w for i, w in s) for s in spec]


def _packed_seg_values(ctx: SG.SegCtx, planes, maxes, extra_rows=()):
    """Post-check compaction: ONE [N, cols] pack + ONE row gather at
    seg_end.  planes -> sums chunks (exact); extra_rows (segment-constant
    int32 row ids) -> compacted [U] columns appended verbatim."""
    C_rows, split = SG.cum_cols(planes, maxes)
    cols = list(C_rows) + [r.astype(jnp.int32) for r in extra_rows]
    M = jnp.stack(cols, axis=1)  # [N, X]
    G = M[ctx.seg_end]  # [U, X]
    nC = len(C_rows)
    chunks = SG.sums_from_ce(ctx, [G[:, i] for i in range(nC)], split)
    rows = [
        jnp.where(ctx.live, G[:, nC + i], -1) for i in range(len(extra_rows))
    ]
    return chunks, rows


def _clean_rows_u(cfg: EngineConfig, x, live):
    return jnp.where(
        live & (x != cfg.trash_row) & (x >= 0), x, jnp.int32(2**30)
    )


def _stat_rows_u(cfg, ctx, carry, with_nodes: bool):
    res_u = _clean_rows_u(cfg, carry.res, ctx.live)
    if not with_nodes:
        return res_u[None, :]
    c_u = _clean_rows_u(cfg, carry.ctx_node, ctx.live)
    o_u = _clean_rows_u(cfg, carry.origin_node, ctx.live)
    return jnp.stack([res_u, c_u, o_u])


def _bits(x):
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)


def _unbits(x):
    return jax.lax.bitcast_convert_type(x, jnp.float32)


class _Expander:
    """Collects per-segment int32 columns, then performs ONE [B]-row
    gather by sid plus ONE transpose so every per-item column reads as a
    contiguous row.  Separate per-check expansions cost 0.3-1.3 ms EACH
    at B=128K (bool gathers and strided column slices are the worst); the
    shared pack amortizes all of it into ~0.5 ms."""

    def __init__(self, ctx: SG.SegCtx):
        self.ctx = ctx
        self.cols = []
        self.R = None

    def add(self, col) -> int:
        assert self.R is None, "expander already ran"
        self.cols.append(col.astype(jnp.int32))
        return len(self.cols) - 1

    def add_f(self, col) -> int:
        return self.add(_bits(col))

    def run(self):
        if not self.cols:  # feature sets with no segment-level columns
            self.R = jnp.zeros((0, self.ctx.sid.shape[0]), jnp.int32)
            return
        G = jnp.stack(self.cols, axis=1)[self.ctx.sid]  # [B, C]
        self.R = G.T  # [C, B] — row reads are free views

    def get(self, i):
        return self.R[i]

    def get_f(self, i):
        return _unbits(self.R[i])


def run_checks_seg(
    cfg: EngineConfig,
    state,
    rules,
    acq,
    now_ms,
    sys_load,
    sys_cpu,
    valid,
    forced,
    ctx: SG.SegCtx,
    carry: AcqCarry,
    features: frozenset,
):
    """The whole acquire check phase with every per-item table read hoisted
    to the segment level: rule slots, packed fields, window/concurrency/
    pool reads, CB state, authority lists and tail thresholds happen once
    per SEGMENT, and all per-item context expands back through ONE shared
    monotone gather (_Expander).  Item-level logic (ranks, comparisons,
    verdict masks) is bit-identical to engine's per-stage checks —
    AuthoritySlot -> SystemSlot -> ParamFlowSlot -> FlowSlot(+tail) ->
    DegradeSlot, first-fail order preserved.

    Ranks switch at runtime between head-run segmented integer scans
    (valid when the batch is res-sorted and, for flow, all enabled rules
    are DIRECT + limitApp ANY so equal rank keys are contiguous) and the
    batch-order rank kernels.  Requires *_rules_per_resource == 1 for the
    active features (engine checks statically).

    Exactness note: comparisons use the margin rearrangement
    (rank + cnt > thr - wp instead of wp + rank + cnt > thr), identical
    to the per-item forms whenever the operands are f32-exact integers
    (< 2^24 — the same envelope as the window counters themselves).  At
    magnitudes beyond that, the two lax.cond branches may round verdicts
    differently by one ulp.

    Returns the same tuple engine._run_checks_plain produces.
    """
    from sentinel_tpu.ops import engine as E
    from sentinel_tpu.ops import degrade as D
    from sentinel_tpu.core import rule_tensors as RT
    from sentinel_tpu.core.rules import (
        CONTROL_DEFAULT,
        CONTROL_RATE_LIMITER,
        CONTROL_WARM_UP,
        CONTROL_WARM_UP_RATE_LIMITER,
        GRADE_QPS,
        GRADE_THREAD,
        STRATEGY_DIRECT,
        STRATEGY_RELATE,
    )
    from sentinel_tpu.ops.rank import grouped_exclusive_cumsum

    b = acq.res.shape[0]
    cnt = acq.count.astype(jnp.float32)
    zero_block = jnp.zeros((b,), bool)
    live = ctx.live
    res_u = jnp.where(live & (carry.res >= 0), carry.res, cfg.max_resources)
    res_l = jnp.minimum(res_u, cfg.max_resources)
    exp = _Expander(ctx)

    # ================= segment-level phase =================
    with jax.named_scope("stage.segment_reads"):
        with_auth = "authority" in features
        with_param = "param" in features
        with_flow = "flow" in features
        with_degrade = "degrade" in features

        # all four per-resource slot tables are read at the SAME index — one
        # shared 8-lane row gather serves them (tables.lane_gather_multi; a
        # separate lane gather each cost ~0.1 ms apiece at U~16K).  Keyed by
        # NAME so the gather list and the consumers can never fall out of
        # order.
        n_res1 = cfg.max_resources + 1
        slot_tabs = []
        if with_auth:
            slot_tabs.append(("auth", jnp.asarray(rules.auth.mode)))
        if with_param:
            slot_tabs.append(("param", jnp.asarray(rules.param.res_params)[:, 0]))
        if with_flow:
            slot_tabs.append(("flow", jnp.asarray(rules.flow.res_rules)[:, 0]))
        if with_degrade:
            slot_tabs.append(("degrade", jnp.asarray(rules.degrade.res_cbs)[:, 0]))
        slot_vals = {
            name: g.astype(jnp.int32)
            for (name, _t), g in zip(
                slot_tabs,
                T.lane_gather_multi(cfg, [t for _n, t in slot_tabs], res_l, n_res1)
                if slot_tabs
                else [],
            )
        }

        if with_auth:
            n = n_res1
            mode = slot_vals["auth"]
            origins = T.big_gather(cfg, rules.auth.origins, res_l, n)
            listed = (
                (origins == carry.origin_id[:, None]) & (origins != RT.AUTH_EMPTY)
            ).any(axis=1)
            auth_u = ((mode == 1) & ~listed) | ((mode == 2) & listed)

        if with_param:
            # KP == 1 statically (the seg_checks gate) -> shared slot gather
            pslot_u = slot_vals["param"]
            pgu = T.small_gather_fields(
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
                pslot_u,
            )
            ih_u = T.small_gather_int(cfg, rules.param.item_hash, pslot_u)  # [U, KI]
            it_u = T.small_gather_fields(
                cfg, jnp.asarray(rules.param.item_threshold, jnp.float32), pslot_u
            )
            KI = ih_u.shape[1]
            p_en_u = (pgu[:, 0] > 0) & live
            p_thread_u = pgu[:, 2].astype(jnp.int32) == GRADE_THREAD
            i_pflags = exp.add(
                p_en_u.astype(jnp.int32) | (p_thread_u.astype(jnp.int32) << 1)
            )
            i_plane = exp.add(jnp.clip(pgu[:, 4].astype(jnp.int32), -1, cfg.param_dims - 1))
            i_pslot = exp.add(jnp.where(live, pslot_u, cfg.max_param_rules))
            i_pcls = exp.add(
                jnp.clip(pgu[:, 3].astype(jnp.int32), 0, max(cfg.param_classes - 1, 0))
            )
            i_pthr = exp.add_f(pgu[:, 1])
            i_ih = [exp.add(ih_u[:, k]) for k in range(KI)]
            i_it = [exp.add_f(it_u[:, k]) for k in range(KI)]

        if with_flow:
            f = rules.flow
            sec_cfg = W.WindowConfig(cfg.second_sample_count, cfg.second_window_ms)
            slot_u = slot_vals["flow"]
            count_parts = T.bf16_parts(f.count)  # the count crosses whole (engine._check_flow)
            fg = T.small_gather_fields(
                cfg,
                T.pack_fields(
                    [
                        f.enabled, f.limit_app, f.strategy, f.ref_node, f.ref_ctx,
                        f.grade, count_parts[0], f.behavior, f.max_queue_ms,
                        f.warning_token, f.slope, state.warmup_tokens,
                        count_parts[1], count_parts[2],
                    ]
                ),
                slot_u,
            )
            # relative to now while still int32: exact past 2^24 ms of engine time
            latest_rel_u = E.latest_rel_ms(
                T.small_gather_int(cfg, state.latest_passed_ms, slot_u), now_ms
            )
            enabled = fg[:, 0] > 0
            la = fg[:, 1].astype(jnp.int32)
            named = (la >= 0) & (la == carry.origin_id)
            match = (
                (la == RT.LIMIT_ANY)
                | ((la >= 0) & (la == carry.origin_id))
                | ((la == RT.LIMIT_OTHER) & (carry.origin_id >= 0) & ~named)
            )
            applicable_u = enabled & match & live
            strategy = fg[:, 2].astype(jnp.int32)
            ref_node = fg[:, 3].astype(jnp.int32)
            ref_ctx = fg[:, 4].astype(jnp.int32)
            direct_node = jnp.where(la == RT.LIMIT_ANY, carry.res, carry.origin_node)
            chain_ok = (ref_ctx >= 0) & (ref_ctx == carry.ctx_name)
            node = jnp.where(
                strategy == STRATEGY_DIRECT,
                direct_node,
                jnp.where(
                    strategy == STRATEGY_RELATE,
                    ref_node,
                    jnp.where(chain_ok, carry.ctx_node, -1),
                ),
            )
            node_ok = (node >= 0) & (node != cfg.trash_row)
            applicable_u = applicable_u & node_ok
            node_safe_u = jnp.where(node_ok & (node < cfg.node_rows), node, cfg.trash_row)
            grade = fg[:, 5].astype(jnp.int32)
            rcount = fg[:, 6] + fg[:, 12] + fg[:, 13]
            behavior = jnp.where(
                grade == GRADE_QPS, fg[:, 7].astype(jnp.int32), CONTROL_DEFAULT
            )
            rest = fg[:, 11]
            warning = fg[:, 9]
            above = jnp.maximum(rest - warning, 0.0)
            warm_qps = jnp.floor(
                1.0 / (above * fg[:, 10] + 1.0 / jnp.maximum(rcount, 1e-9)) + 0.5
            )
            warm_qps = jnp.where(rest >= warning, warm_qps, rcount)
            is_warm = (behavior == CONTROL_WARM_UP) | (
                behavior == CONTROL_WARM_UP_RATE_LIMITER
            )
            is_rl = (behavior == CONTROL_RATE_LIMITER) | (
                behavior == CONTROL_WARM_UP_RATE_LIMITER
            )
            pace_qps = jnp.where(
                behavior == CONTROL_WARM_UP_RATE_LIMITER,
                warm_qps,
                jnp.maximum(rcount, 1e-9),
            )
            thr_eff = jnp.where(is_warm, warm_qps, rcount)
            cur_wid = W.wid_of(now_ms, cfg.second_window_ms)
            pool_dense = jnp.where(
                state.occ_epoch == cur_wid + 1, state.occ_tokens, 0.0
            )
            # running sums are exact here: completions refreshed this now_ms
            # before checks (ops/window.py Option-B read contract)
            wsum = W.window_event_run(state.win_sec, W.EV_PASS)
            tab = jnp.stack(
                [wsum, state.concurrency, jnp.round(pool_dense).astype(jnp.int32)],
                axis=1,
            )
            g = tab[node_safe_u]
            wp = g[:, 0].astype(jnp.float32)
            conc = g[:, 1].astype(jnp.float32)
            pool = g[:, 2].astype(jnp.float32)
            i_fflags = exp.add(
                applicable_u.astype(jnp.int32)
                | (is_rl.astype(jnp.int32) << 1)
                | ((behavior == CONTROL_WARM_UP_RATE_LIMITER).astype(jnp.int32) << 2)
                | ((grade == GRADE_QPS).astype(jnp.int32) << 3)
                | ((behavior == CONTROL_DEFAULT).astype(jnp.int32) << 4)
            )
            i_node = exp.add(node_safe_u)
            i_fslot = exp.add(jnp.where(live, slot_u, cfg.max_flow_rules))
            i_mq = exp.add_f(thr_eff - wp)
            i_mt = exp.add_f(rcount - conc)
            i_mrl = exp.add_f(latest_rel_u)
            i_maxq = exp.add_f(fg[:, 8])
            i_pace = exp.add_f(pace_qps)
            i_mo = exp.add_f(rcount - pool)

        with_tail = "tail_flow" in features and cfg.sketch_stats
        if with_tail:
            # UNCONDITIONAL under the feature: "tail_flow" is only compiled in
            # when sketch-id flow rules exist (client._select_features), so a
            # lax.cond on any_tail_rules would buy nothing on real workloads
            # while its boundary copies cost ~0.3-1.4 ms at B=128K (STATUS
            # cond-boundary measurements).  With no rules loaded the gathers
            # read UNRULED thresholds and nothing blocks — semantics identical.
            thr_tab = jnp.asarray(rules.tail.thr)
            tres_u = jnp.where(live, carry.res, -1)
            tail_u = live & (tres_u >= cfg.node_rows)
            tcols = P.cms_cell(tres_u, cfg.sketch_depth, cfg.sketch_width)
            # ONE flat gather across all depths (tables.depth_gather_1col)
            t = T.depth_gather_1col(cfg, thr_tab, tcols, cfg.sketch_width)
            thr_u = jnp.max(
                jnp.where(tail_u[None, :], t, RT.TAIL_UNRULED), axis=0
            )
            est_u = _sketch(cfg).estimate_plane_mxu(
                cfg, state.gs, now_ms, tres_u, W.EV_PASS, E.sketch_config(cfg)
            )
            i_tthr = exp.add_f(thr_u)
            i_test = exp.add_f(est_u)

        if with_degrade:
            dslot_u = slot_vals["degrade"]
            dgu = T.small_gather_fields(
                cfg, T.pack_fields([rules.degrade.enabled, state.cb_state]), dslot_u
            )
            d_en = (dgu[:, 0] > 0) & live
            st_u = dgu[:, 1].astype(jnp.int32)
            retry_due = now_ms >= T.small_gather_int(cfg, state.cb_retry_ms, dslot_u)
            open_wait = (st_u == D.CB_OPEN) & ~retry_due
            open_due = (st_u == D.CB_OPEN) & retry_due
            half = st_u == D.CB_HALF_OPEN
            i_dflags = exp.add(
                d_en.astype(jnp.int32)
                | (open_wait.astype(jnp.int32) << 1)
                | (open_due.astype(jnp.int32) << 2)
                | (half.astype(jnp.int32) << 3)
            )
            i_dslot = exp.add(
                jnp.minimum(
                    jnp.where(live, dslot_u, cfg.max_degrade_rules),
                    cfg.max_degrade_rules,
                )
            )

        if with_auth:
            i_auth = exp.add(auth_u.astype(jnp.int32))

        exp.run()

    # ================= item-level phase (slot order) =================
    # Items in segments past the compacted capacity have no segment-level
    # data (their expansions clamp to slot U-1 — garbage): FAIL CLOSED.
    # Empty whenever ctx.ok (sid < U for every item), so this is a no-op
    # on the seg_fallback=True path, where the lax.cond guards capacity;
    # with seg_fallback=False these items are counted by dropped_items and
    # block as system rejections rather than pass unchecked.
    overflow = valid & (ctx.sid >= ctx.U)

    with jax.named_scope("stage.authority"):
        if with_auth:
            # ~overflow: garbage expansions must not mislabel the fail-closed
            # block as BLOCK_AUTHORITY (it lands as a system rejection below)
            auth_block = (exp.get(i_auth) > 0) & valid & ~forced & ~overflow
        else:
            auth_block = zero_block
    eligible = valid & ~auth_block & ~forced & ~overflow

    if "system" in features:
        sys_block = E._check_system(
            cfg, state, rules, acq, now_ms, sys_load, sys_cpu, eligible
        )
        sys_block = sys_block | overflow
    else:
        sys_block = zero_block | overflow
    eligible = eligible & ~sys_block

    with jax.named_scope("stage.param"):
        if with_param:
            fl = exp.get(i_pflags)
            p_en_i = (fl & 1) > 0
            p_thread_i = (fl & 2) > 0
            lane_i = exp.get(i_plane)
            pslot_i = exp.get(i_pslot)
            cls_i = exp.get(i_pcls)
            pthr_i = exp.get_f(i_pthr)
            lane_oh = jnp.clip(lane_i, 0, cfg.param_dims - 1)[
                :, None
            ] == jax.lax.broadcasted_iota(jnp.int32, (1, cfg.param_dims), 1)
            ph = jnp.sum(jnp.where(lane_oh, acq.param_hash, 0), axis=1)
            ph = jnp.where(lane_i >= 0, ph, 0)
            p_app = p_en_i & (ph != 0)
            prows = P.pair_rows(pslot_i, ph, cfg.param_depth, cfg.param_width)
            wtab = P.class_tables(
                state.pcms, state.pcms_epochs, jnp.asarray(rules.param.class_k),
                now_ms, cfg,
            )
            est = P.estimate_fused(cfg, wtab, prows, cls_i)
            any_thread = jnp.any(
                jnp.asarray(rules.param.enabled)
                & (jnp.asarray(rules.param.grade) == GRADE_THREAD)
            )
            conc_est = jax.lax.cond(
                any_thread,
                lambda: P.conc_estimate(cfg, state.pconc, prows),
                lambda: jnp.zeros((prows.shape[0],), jnp.float32),
            )
            is_item = jnp.zeros((b,), bool)
            item_thr = jnp.zeros((b,), jnp.float32)
            for k in range(KI):
                ihk = exp.get(i_ih[k])
                itk = exp.get_f(i_it[k])
                hit = (ihk == ph) & (ihk != 0)
                item_thr = jnp.where(hit, jnp.maximum(item_thr, itk), item_thr)
                is_item = is_item | hit
            pthr = jnp.where(is_item, item_thr, pthr_i)
            elig_p = eligible & p_app
            key = ph * jnp.int32(2) + pslot_i  # KP == 1
            (p_rank,) = grouped_exclusive_cumsum(key, [cnt], elig_p)
            over = jnp.where(p_thread_i, conc_est, est) + p_rank + cnt > pthr
            param_block = p_app & over & elig_p & eligible
            param_state = (prows, p_app & ~p_thread_i, p_app & p_thread_i)
        else:
            param_block = zero_block
            param_state = None
    eligible = eligible & ~param_block

    with jax.named_scope("stage.flow"):
        occupy = "occupy" in features
        if with_flow:
            fl = exp.get(i_fflags)
            app_i = (fl & 1) > 0
            rl_i = (fl & 2) > 0
            wurl_i = (fl & 4) > 0
            qps_i = (fl & 8) > 0
            def_i = (fl & 16) > 0
            node_i = exp.get(i_node)
            slot_i = exp.get(i_fslot)
            margin_q = exp.get_f(i_mq)
            margin_t = exp.get_f(i_mt)
            m_rl = exp.get_f(i_mrl)
            mq_i = exp.get_f(i_maxq)
            pace_i = exp.get_f(i_pace)
            margin_o = exp.get_f(i_mo)
            cost = jnp.where(rl_i, E.pace_cost_ms(cnt, pace_i), 0.0)
            elig_f = eligible & app_i
            rank_key = jnp.where(rl_i, jnp.int32(cfg.node_rows) + slot_i, node_i)
            direct_any = ~jnp.any(
                jnp.asarray(f.enabled)
                & (
                    (jnp.asarray(f.strategy) != STRATEGY_DIRECT)
                    | (jnp.asarray(f.limit_app) != RT.LIMIT_ANY)
                )
            )
            seg_rank_ok = carry.res_sorted & direct_any

            def _ranks_seg():
                head_k = jnp.concatenate(
                    [jnp.ones((1,), bool), rank_key[1:] != rank_key[:-1]]
                )
                r = SC.seg_excl_cumsum_pl(
                    head_k,
                    jnp.stack(
                        [jnp.where(elig_f, acq.count, 0), elig_f.astype(jnp.int32)]
                    ),
                )
                rc = SC.seg_excl_cumsum_wide_pl(
                    head_k, jnp.where(elig_f, cost, 0.0).astype(jnp.int32)
                )
                return r[0].astype(jnp.float32), r[1].astype(jnp.float32), rc

            def _ranks_sort():
                return E._rank(
                    cfg,
                    rank_key,
                    [cnt, jnp.ones_like(cnt), cost],
                    elig_f,
                    cfg.node_rows + cfg.max_flow_rules + 1,
                )

            if cfg.seg_static_ranks:
                # scans only (cfg contract: sorted + DIRECT/ANY rules); if the
                # contract breaks at runtime, ranks are garbage — fail closed
                # below by blocking every applicable item rather than
                # misranking silently
                rank_tok, rank_thr, rank_cost = _ranks_seg()
                rank_guard = ~seg_rank_ok
            else:
                rank_tok, rank_thr, rank_cost = jax.lax.cond(
                    seg_rank_ok, _ranks_seg, _ranks_sort
                )
                rank_guard = jnp.zeros((), bool)
            qps_block = rank_tok + cnt > margin_q
            thread_block = rank_thr + cnt > margin_t
            basic_block = jnp.where(qps_i, qps_block, thread_block)
            csum_incl = rank_cost + cost
            rl_wait = jnp.maximum(m_rl + csum_incl, csum_incl - cost)
            rl_block = rl_wait > mq_i
            entry_block = jnp.where(rl_i, rl_block, basic_block) & app_i
            entry_block = entry_block | (wurl_i & app_i & qps_block)
            entry_block = entry_block | (rank_guard & app_i)
            flow_block = entry_block & elig_f

            occupying = jnp.zeros((b,), bool)
            occ_wait = jnp.zeros((b,), jnp.float32)
            occ_grant = None
            if occupy:
                cand = (acq.prio > 0) & def_i & qps_i & app_i & elig_f & qps_block
                if cfg.seg_static_ranks:
                    # under a broken static-rank contract nothing may occupy
                    # ahead (a garbage grant would bypass the fail-closed
                    # entry_block above)
                    cand = cand & ~rank_guard

                def _occ_rank(cand):
                    def _seg():
                        head_n = jnp.concatenate(
                            [jnp.ones((1,), bool), node_i[1:] != node_i[:-1]]
                        )
                        (r,) = SC.seg_excl_cumsum_pl(
                            head_n, jnp.where(cand, acq.count, 0)[None, :]
                        )
                        return r.astype(jnp.float32)

                    def _sort():
                        (r,) = E._rank(cfg, node_i, [cnt], cand, cfg.node_rows)
                        return r

                    if cfg.seg_static_ranks:
                        # contract break -> rank_guard already blocks the
                        # entry, so a garbage occupy rank cannot grant
                        rank_occ = _seg()
                    else:
                        rank_occ = jax.lax.cond(seg_rank_ok, _seg, _sort)
                    return cand & (rank_occ + cnt <= margin_o)

                granted = jax.lax.cond(
                    jnp.any(cand), _occ_rank, lambda c: jnp.zeros_like(c), cand
                )
                still_blocked = entry_block & ~granted & elig_f
                occupying = granted & elig_f & ~still_blocked
                flow_block = still_blocked
                occ_wait_v = (
                    cfg.second_window_ms - (now_ms % cfg.second_window_ms)
                ).astype(jnp.float32)
                occ_wait = jnp.where(occupying, occ_wait_v, 0.0)
                occ_grant = (granted & elig_f, node_i, cnt)

            rl_ok = rl_i & app_i & ~entry_block & elig_f & ~flow_block
            wait_ms_entry = jnp.where(rl_ok, jnp.maximum(rl_wait, 0.0), 0.0)
            wait_ms = jnp.maximum(wait_ms_entry, occ_wait).astype(jnp.int32)
            fslots = slot_i
            rl_info = (rl_ok, cost)
        else:
            flow_block = zero_block
            occupying = zero_block
            occ_grant = None
            fslots = None
            rl_info = None
            wait_ms = jnp.zeros((b,), jnp.int32)

    with jax.named_scope("stage.tail_flow"):
        if with_tail:
            # unconditional (see the segment-level tail phase above): the rank
            # scan + compare interior is cheap next to the cond boundary it
            # replaced, and with no ruled tail items `ruled` is all-False
            thr = jnp.where(
                eligible & (acq.res >= cfg.node_rows),
                exp.get_f(i_tthr),
                RT.TAIL_UNRULED,
            )
            est_t = exp.get_f(i_test)
            ruled = thr < RT.TAIL_UNRULED / 2

            def _tail_seg():
                head_r = jnp.concatenate(
                    [jnp.ones((1,), bool), acq.res[1:] != acq.res[:-1]]
                )
                (r,) = SC.seg_excl_cumsum_pl(
                    head_r, jnp.where(ruled, acq.count, 0)[None, :]
                )
                return r.astype(jnp.float32)

            def _tail_sort():
                (r,) = grouped_exclusive_cumsum(acq.res, [cnt], ruled)
                return r

            if cfg.seg_static_ranks:
                # unsorted batch under the static contract: block ruled
                # tail items outright (fail closed, loud) — t_rank would
                # be garbage
                t_rank = _tail_seg()
                tail_block = ruled & (
                    (est_t + t_rank + cnt > thr) | ~carry.res_sorted
                )
            else:
                t_rank = jax.lax.cond(carry.res_sorted, _tail_seg, _tail_sort)
                tail_block = ruled & (est_t + t_rank + cnt > thr)
            flow_block = flow_block | (tail_block & eligible)
    eligible = eligible & ~flow_block

    with jax.named_scope("stage.degrade"):
        if with_degrade:
            fl = exp.get(i_dflags)
            en_i = (fl & 1) > 0
            ow_i = (fl & 2) > 0
            od_i = (fl & 4) > 0
            hf_i = (fl & 8) > 0
            dslot_i = exp.get(i_dslot)
            probe_cand = od_i & en_i & eligible

            def _probe_rank(cand):
                def _seg():
                    head_s = jnp.concatenate(
                        [jnp.ones((1,), bool), dslot_i[1:] != dslot_i[:-1]]
                    )
                    (r,) = SC.seg_excl_cumsum_pl(head_s, cand.astype(jnp.int32)[None, :])
                    return r.astype(jnp.float32)

                def _sort():
                    (r,) = E._rank(
                        cfg,
                        dslot_i,
                        [jnp.ones_like(dslot_i, dtype=jnp.float32)],
                        cand,
                        cfg.max_degrade_rules + 1,
                    )
                    return r

                if cfg.seg_static_ranks:
                    # unsorted under the static contract: elect NO probes
                    # (conservative — the breaker simply stays open a tick)
                    p_rank = _seg()
                    return cand & (p_rank < 0.5) & carry.res_sorted
                p_rank = jax.lax.cond(carry.res_sorted, _seg, _sort)
                return cand & (p_rank < 0.5)

            probe = jax.lax.cond(
                jnp.any(probe_cand),
                _probe_rank,
                lambda c: jnp.zeros_like(c),
                probe_cand,
            )
            entry_blk_d = en_i & (ow_i | (od_i & ~probe) | hf_i)
            degrade_block = entry_blk_d & eligible
            probe_ok = probe & ~degrade_block
            Dn1 = cfg.max_degrade_rules + 1
            flip = jax.lax.cond(
                jnp.any(probe_ok),
                lambda: T.small_scatter_or(
                    cfg, jnp.zeros((Dn1,), jnp.int32), dslot_i, probe_ok
                ),
                lambda: jnp.zeros((Dn1,), jnp.int32),
            )
            cb_state = jnp.where(
                (flip > 0) & (state.cb_state == D.CB_OPEN),
                D.CB_HALF_OPEN,
                state.cb_state,
            )
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
        None,  # latest_passed: the fused paths land it via the effects kernel
    )


def process_completions_seg(
    cfg: EngineConfig,
    state,
    rules,
    comp,
    now_ms,
    features: frozenset,
    ctx: SG.SegCtx,
    carry: CompCarry,
):
    """_process_completions_fused with segment-compacted scatters.

    Bit-identical state updates (ints sum order-free; minima order-free);
    see engine._process_completions_fused for the per-plane semantics and
    reference citations."""
    from sentinel_tpu.ops import engine as E

    b = comp.res.shape[0]
    U = ctx.U
    valid = comp.res != cfg.trash_row
    with_nodes = "nodes" in features
    sec_cfg = W.WindowConfig(cfg.second_sample_count, cfg.second_window_ms)
    min_cfg = W.WindowConfig(cfg.minute_sample_count, cfg.minute_window_ms)
    erow = cfg.entry_node_row
    inb, entry_deltas, entry_rt, entry_rt_min = E._completion_entry_stats(
        cfg, comp, valid
    )

    vals3_u, digits3, spec3 = _chunks_to_planes(
        SG.sums_from_ce(ctx, carry.ce, carry.split)
    )
    stat_rows = _stat_rows_u(cfg, ctx, carry, with_nodes)
    jobs = [FU.Job("stat", cfg.max_nodes, stat_rows, vals3_u, digits3)]

    # --- exact per-row windowed minRt over compacted per-segment minima --
    RMIN = stat_rows.shape[0]
    seg_min = jnp.where(carry.min_rt < 1.0e38, carry.min_rt, -1.0)
    mh_rows, mh_vals = RM.min_heads(
        jnp.where(stat_rows < cfg.max_nodes, stat_rows, -1).reshape(-1),
        jnp.tile(seg_min, (RMIN,)),
        jnp.ones((RMIN * U,), bool),
        cfg.max_nodes,
    )
    jobs.append(
        FU.Job(
            "rowmin",
            cfg.max_nodes,
            mh_rows.reshape(RMIN, U),
            mh_vals.T.reshape(3, RMIN, U).transpose(1, 0, 2),
            (2, 2, 1),
        )
    )

    if cfg.sketch_stats:
        res_u = jnp.where(ctx.live, carry.res, -1)
        cols_u = P.cms_cell(res_u, cfg.sketch_depth, cfg.sketch_width)
        valid_u = ctx.live & (res_u != cfg.trash_row) & (res_u >= 0)
        for d in range(cfg.sketch_depth):
            jobs.append(
                FU.Job(
                    f"sketch{d}",
                    cfg.sketch_width,
                    jnp.where(valid_u, cols_u[:, d], -1)[None, :],
                    vals3_u,
                    digits3,
                )
            )

    # --- circuit-breaker columns + probe flags ---------------------------
    with_degrade = "degrade" in features
    if with_degrade:
        KD = cfg.degrade_rules_per_resource
        slots_f, cb_counts, cb_epochs, active, is_err, is_slow, g_idx, half_open = (
            E._degrade_completion_masks(cfg, state, rules, comp, valid, now_ms)
        )
        nbd = cfg.cb_sample_count
        Dn = cfg.max_degrade_rules
        probe_done = active & half_open
        probe_fail = probe_done & (is_err | is_slow)
        planes = []
        rows_src = []
        for d in range(KD):
            sl = lambda x: x.reshape(b, KD)[:, d]
            planes += [
                sl(jnp.where(active, 1, 0)),
                sl(jnp.where(is_err, 1, 0)),
                sl(jnp.where(is_slow, 1, 0)),
                sl(probe_done.astype(jnp.int32)),
                sl(probe_fail.astype(jnp.int32)),
            ]
            flat = jnp.where(slots_f < Dn, slots_f * nbd + g_idx, -1)
            rows_src += [sl(flat), sl(jnp.where(slots_f < Dn, slots_f, -1))]
        # per-ITEM plane bound is 1 (event flags); seg sums stay <= BLOCK
        # and ride single 2-digit chunks
        chunks, crows = _packed_seg_values(
            ctx, planes, [1] * len(planes), extra_rows=rows_src
        )
        cbp_vals, cbp_digits, cbp_spec = _chunks_to_planes(
            [chunks[5 * d + k] for d in range(KD) for k in range(3)]
        )
        prp_vals, prp_digits, prp_spec = _chunks_to_planes(
            [chunks[5 * d + k] for d in range(KD) for k in range(3, 5)]
        )
        P2c = cbp_vals.shape[0] // KD
        P2p = prp_vals.shape[0] // KD
        jobs.append(
            FU.Job(
                "cb",
                Dn * nbd,
                jnp.stack([crows[2 * d] for d in range(KD)]),
                cbp_vals.reshape(KD, P2c, U),
                cbp_digits[:P2c],
            )
        )
        jobs.append(
            FU.Job(
                "probe",
                Dn,
                jnp.stack([crows[2 * d + 1] for d in range(KD)]),
                prp_vals.reshape(KD, P2p, U),
                prp_digits[:P2p],
            )
        )

    outs = FU.scatter_many(jobs)
    oi = 0
    stat_out = outs[oi]
    oi += 1
    min_out = outs[oi]
    oi += 1
    sk_out = None
    if cfg.sketch_stats:
        sk_out = jnp.stack(outs[oi : oi + cfg.sketch_depth])
        oi += cfg.sketch_depth
    if with_degrade:
        cb_out = outs[oi]
        probe_out = outs[oi + 1]

    # --- THREAD-grade param release: item-axis kernel, skipped when no
    # lane releases (the common QPS-only workload pays nothing) -----------
    with_param = "param" in features
    if with_param:
        cd = cfg.count_digits
        KPp = cfg.param_rules_per_resource
        rel, prows_c, rel_cnt_f = E._param_release_ctx(cfg, rules, comp, valid)
        pr = jnp.where(rel[:, None], prows_c, -1).reshape(b, KPp, cfg.param_depth)
        rel_cnt = rel_cnt_f.reshape(b, KPp).T[:, None, :]

        def _rel_scatter():
            pjobs = [
                FU.Job(f"prel{d}", cfg.param_width, pr[:, :, d].T, rel_cnt, (cd,))
                for d in range(cfg.param_depth)
            ]
            if P.wide(cfg):
                return E._param_tiles(pjobs)[:, 0]
            return jnp.stack([o[:, 0] for o in FU.scatter_many(pjobs)])

        prel_out = jax.lax.cond(
            jnp.any(rel),
            _rel_scatter,
            lambda: jnp.zeros(P.conc_shape(cfg), jnp.float32),
        )

    # --- land (same tail as the per-item fused path) ---------------------
    succ_h, err_h, rtq_h = _recombine(stat_out, spec3)
    pad_tail = cfg.node_rows - cfg.max_nodes
    hist = jnp.zeros((cfg.node_rows, W.NUM_EVENTS), jnp.int32)
    hist = hist.at[: cfg.max_nodes, W.EV_SUCCESS].set(succ_h)
    hist = hist.at[: cfg.max_nodes, W.EV_EXCEPTION].set(err_h)
    hist = hist.at[erow].add(entry_deltas)
    rt_hist = jnp.concatenate(
        [rtq_h.astype(jnp.float32) / 8.0, jnp.zeros((pad_tail,), jnp.float32)]
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
        rtq=RQ.add(state.rtq, now_ms, comp.rt, inb & (comp.rt > 0), E.rtq_config(cfg))
    )
    if sk_out is not None:
        upd = jnp.stack(
            [
                jnp.stack(_recombine(sk_out[d], spec3), axis=1)
                for d in range(cfg.sketch_depth)
            ]
        )  # [depth, width, 3]
        state = state._replace(
            gs=_sketch(cfg).add_dense(
                state.gs,
                now_ms,
                upd,
                (W.EV_SUCCESS, W.EV_EXCEPTION, GS.RT_PLANE),
                E.sketch_config(cfg),
            )
        )

    concurrency = jnp.maximum(state.concurrency - hist[:, W.EV_SUCCESS], 0)

    if with_param:
        dec = jnp.round(prel_out).astype(jnp.int32)
        state = state._replace(pconc=jnp.maximum(state.pconc - dec, 0))

    if not with_degrade:
        return state._replace(concurrency=concurrency)

    cb_cols = _recombine(cb_out, cbp_spec[:3])
    cb_upd = jnp.stack(cb_cols, axis=1).reshape(Dn, nbd, 3)
    cb_counts = cb_counts.at[:Dn].add(cb_upd)
    pr_cols = _recombine(probe_out, prp_spec[:2])
    sf = jnp.concatenate(
        [jnp.stack(pr_cols, axis=1), jnp.zeros((1, 2), jnp.int32)]
    )
    cb_counts, cb_state, cb_retry = E._cb_transitions(
        cfg, state, rules, cb_counts, cb_epochs, sf[:, 0], sf[:, 1], now_ms
    )
    return state._replace(
        concurrency=concurrency,
        cb_counts=cb_counts,
        cb_epochs=cb_epochs,
        cb_state=cb_state,
        cb_retry_ms=cb_retry,
    )


def acquire_effects_seg(
    cfg: EngineConfig,
    state,
    rules,
    acq,
    now_ms,
    features: frozenset,
    passed,
    occupying,
    valid,
    fslots,
    occ_grant,
    rl_info,
    param_ctx,
    ctx: SG.SegCtx,
    carry: AcqCarry,
):
    """_acquire_effects_fused with segment-compacted scatters (same
    semantics; see that function for the reference map).  All post-check
    value planes and per-lane rows compact through ONE packed gather."""
    from sentinel_tpu.ops import engine as E

    b = acq.res.shape[0]
    U = ctx.U
    with_nodes = "nodes" in features
    sec_cfg = W.WindowConfig(cfg.second_sample_count, cfg.second_window_ms)
    min_cfg = W.WindowConfig(cfg.minute_sample_count, cfg.minute_window_ms)
    erow = cfg.entry_node_row
    cd = cfg.count_digits
    K = cfg.flow_rules_per_resource
    CMAX = cfg.max_batch_count  # fused path clamps per-item counts

    pass_c, block_c, occ_c, entry_deltas = E._acquire_entry_stats(
        cfg, acq, valid, passed, occupying
    )

    # --- assemble the one packed post-check compaction -------------------
    planes = [pass_c, block_c, occ_c]
    maxes = [CMAX, CMAX, CMAX]
    rows_src = []
    if cfg.sketch_stats:
        planes.append(jnp.where(passed, acq.count, 0))
        maxes.append(CMAX)
    slot_planes = []
    if fslots is not None:
        F = cfg.max_flow_rules
        cnt_f = E._fan(acq.count, K)
        w = c = n1 = None
        if "warmup" in features:
            adm = E._fan(passed, K)
            w = jnp.where(adm, cnt_f, 0).reshape(b, K)
            slot_planes.append("warm")
        if rl_info is not None:
            rl_ok, cost = rl_info
            c = jnp.where(rl_ok, jnp.round(cost).astype(jnp.int32), 0).reshape(b, K)
            n1 = jnp.where(rl_ok, 1, 0).reshape(b, K)
            slot_planes.append("latest")
        # LANE-MAJOR: the chunk slicing below walks chunks per lane
        for d in range(K):
            if w is not None:
                planes.append(w[:, d])
                maxes.append(CMAX)
            if c is not None:
                planes += [c[:, d], n1[:, d]]
                maxes += [(1 << 24) - 1, 255]
        fs = jnp.where(fslots < F, fslots, -1).reshape(b, K)
        rows_src += [fs[:, d] for d in range(K)]
    if occ_grant is not None:
        grant_lane, onodes, ocnt = occ_grant
        commit = grant_lane & E._fan(occupying, K)
        cm = jnp.where(commit, jnp.round(ocnt).astype(jnp.int32), 0).reshape(b, K)
        on = jnp.where(onodes < cfg.max_nodes, onodes, -1).reshape(b, K)
        for d in range(K):
            planes.append(cm[:, d])
            maxes.append(CMAX)
            rows_src.append(on[:, d])

    chunks, crows = _packed_seg_values(ctx, planes, maxes, extra_rows=rows_src)
    pi = 0
    ri = 0
    vals3_u, digits3, spec3 = _chunks_to_planes(chunks[pi : pi + 3])
    pi += 3
    stat_rows = _stat_rows_u(cfg, ctx, carry, with_nodes)
    jobs = [FU.Job("stat", cfg.max_nodes, stat_rows, vals3_u, digits3)]

    if cfg.sketch_stats:
        sk_vals, sk_digits, sk_spec = _chunks_to_planes(
            [chunks[pi], chunks[1]]  # (admitted count, block)
        )
        pi += 1
        res_u = jnp.where(ctx.live, carry.res, -1)
        cols_u = P.cms_cell(res_u, cfg.sketch_depth, cfg.sketch_width)
        valid_u = ctx.live & (res_u != cfg.trash_row) & (res_u >= 0)
        for d in range(cfg.sketch_depth):
            jobs.append(
                FU.Job(
                    f"sketch{d}",
                    cfg.sketch_width,
                    jnp.where(valid_u, cols_u[:, d], -1)[None, :],
                    sk_vals,
                    sk_digits,
                )
            )

    n_flow_jobs = 0
    if fslots is not None and slot_planes:
        per_lane = (1 if "warm" in slot_planes else 0) + (
            2 if "latest" in slot_planes else 0
        )
        lane_chunks = []
        for d in range(K):
            lane_chunks.extend(chunks[pi + d * per_lane : pi + (d + 1) * per_lane])
        f_vals, f_digits, f_spec = _chunks_to_planes(lane_chunks)
        pi += K * per_lane
        P2f = f_vals.shape[0] // K
        jobs.append(
            FU.Job(
                "fslots",
                cfg.max_flow_rules,
                jnp.stack(crows[ri : ri + K]),
                f_vals.reshape(K, P2f, U),
                f_digits[:P2f],
            )
        )
        ri += K
        n_flow_jobs = 1
    elif fslots is not None:
        ri += K

    n_occ_jobs = 0
    if occ_grant is not None:
        o_vals, o_digits, o_spec = _chunks_to_planes(chunks[pi : pi + K])
        pi += K
        P2o = o_vals.shape[0] // K
        jobs.append(
            FU.Job(
                "occ",
                cfg.max_nodes,
                jnp.stack(crows[ri : ri + K]),
                o_vals.reshape(K, P2o, U),
                o_digits[:P2o],
            )
        )
        ri += K
        n_occ_jobs = 1

    outs = FU.scatter_many(jobs)
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
        occ_out = outs[oi]
        oi += 1

    # --- param pass + THREAD concurrency: item-axis kernel ---------------
    p_out = None
    if param_ctx is not None:
        prows, q_add, thread_add = param_ctx
        KP = cfg.param_rules_per_resource
        adm = E._fan(passed, KP)
        cnt_p = E._fan(acq.count, KP)
        p_vals = jnp.stack(
            [
                jnp.where(q_add & adm, cnt_p, 0),
                jnp.where(thread_add & adm, cnt_p, 0),
            ]
        )
        p_vals_r = p_vals.reshape(2, b, KP).transpose(2, 0, 1)
        pjobs = [
            FU.Job(
                f"param{d}",
                cfg.param_width,
                prows[:, d].reshape(b, KP).T,
                p_vals_r,
                (cd, cd),
            )
            for d in range(cfg.param_depth)
        ]
        if P.wide(cfg):
            p_out = E._param_tiles(pjobs)  # [depth, 2, Q/128, 128]
        else:
            p_out = jnp.stack(FU.scatter_many(pjobs))  # [depth, Q, 2]

    # --- land (same tail as the per-item fused path) ---------------------
    pass_h, block_h, occ_h = _recombine(stat_out, spec3)
    hist = jnp.zeros((cfg.node_rows, W.NUM_EVENTS), jnp.int32)
    hist = hist.at[: cfg.max_nodes, W.EV_PASS].set(pass_h)
    hist = hist.at[: cfg.max_nodes, W.EV_BLOCK].set(block_h)
    hist = hist.at[: cfg.max_nodes, W.EV_OCCUPIED].set(occ_h)
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
        upd = jnp.stack(
            [
                jnp.stack(_recombine(sk_out[d], sk_spec), axis=1)
                for d in range(cfg.sketch_depth)
            ]
        )
        # the completion phase already refreshed this now_ms's sketch
        # bucket (its write is unconditional under sketch_stats)
        state = state._replace(
            gs=_sketch(cfg).add_dense(
                state.gs,
                now_ms,
                upd,
                (W.EV_PASS, W.EV_BLOCK),
                E.sketch_config(cfg),
                pre_refreshed=True,
            )
        )

    if f_out is not None:
        # lanes are row-vectors of one job, so f_out [F, P2] is already
        # summed over lanes; recombine with lane 0's spec (lanes share it)
        cols = _recombine(f_out, f_spec[: len(f_spec) // K])
        fi = 0
        pad1 = jnp.zeros((1,), jnp.float32)
        if "warm" in slot_planes:
            acc_add = jnp.concatenate([cols[fi].astype(jnp.float32), pad1])
            state = state._replace(warm_acc=state.warm_acc + acc_add)
            fi += 1
        if "latest" in slot_planes:
            T_s = jnp.concatenate([cols[fi].astype(jnp.float32), pad1])
            n_s = jnp.concatenate([cols[fi + 1].astype(jnp.float32), pad1])
            state = state._replace(
                latest_passed_ms=E._apply_latest(
                    state.latest_passed_ms, T_s, n_s, now_ms
                )
            )

    if occ_out is not None:
        add = jnp.concatenate(
            [
                _recombine(occ_out, o_spec[: len(o_spec) // K])[0].astype(
                    jnp.float32
                ),
                jnp.zeros((cfg.node_rows - cfg.max_nodes,), jnp.float32),
            ]
        )
        cur_wid = W.wid_of(now_ms, cfg.second_window_ms)
        pool_vec = jnp.where(state.occ_epoch == cur_wid + 1, state.occ_tokens, 0.0)
        state = state._replace(
            occ_tokens=pool_vec + add,
            occ_epoch=jnp.where(add > 0, cur_wid + 1, state.occ_epoch),
        )

    return state, E._param_upd(cfg, p_out)
