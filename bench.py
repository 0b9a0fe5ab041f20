"""Benchmark: rule-check decisions/sec across 1M resources (BASELINE north star).

Honest full-feature configuration (round-2 revision):
  - features = ALL engine stages (authority/system/param/flow/degrade/
    warmup/nodes/occupy) — nothing compiled out
  - 10,000 RULED resources: every one carries a flow rule AND a slow-ratio
    circuit breaker; 128 of them carry hot-param rules; plus system +
    authority rules.  Rule capacity sized to hold them (no 4095-rule
    flattery).
  - minute window ON
  - ~1M total resource ids: Zipf traffic; ids beyond the ruled hot set go
    to the global CMS sketch, and the hottest 2,048 of them carry ACTIVE
    approximate-QPS tail rules enforced in the measured tick
    (engine._check_tail_flow) — the rest of the tail is observability
  - a slice of traffic carries origins and param values so the
    origin/param paths do real work
  - batches are presorted host-side by (resource, has-origin) so the
    segment-compacted engine (ops/engine_seg.py) aggregates per key-run
    segment (~10x compaction on this traffic); host sort cost is reported
    (it overlaps the device tick in the pipelined runtime) and the engine
    is exact either way (per-item fallback for unsorted callers)

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "decisions/s", "vs_baseline": N/5e7,
   "features": "ALL", "ruled_resources": 10000, ...,
   "req_latency_vs_tick_size": [...tick-size/latency table...]}

Baseline: >= 50M decisions/sec @ 1M resources on one v5e-1, p99 < 2 ms
(BASELINE.md).  The reference publishes no numbers; its envelope is a JMH
harness and a 6,000-resource design cap (Constants.java:37).

The full benchmark (no arguments) runs on the chip or not at all: it
exits non-zero unless the JAX backend is ``tpu``.  The named modes
(``--smoke``, ``--wire-compare``, ...) are the CPU-reproducible rows.

Timing notes:
  - throughput comes from a long pipelined run with one readback;
  - per-tick device time uses the K-slope of scan-packed ticks (per-call
    dispatch + sync overhead cancels);
  - request-level latency is modeled as device tick time + half the tick
    interval (arrivals uniform over the interval) and reported per tick
    size.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import sys
import time
from typing import Optional

import numpy as np


N_RULED = 10000
N_TAIL_RULED = 2048  # tail ids carrying ACTIVE approximate-QPS rules
N_TOTAL = 1 << 20


def build(B: int, on_tpu: bool):
    import jax
    import jax.numpy as jnp

    from sentinel_tpu.core import rule_tensors as RT
    from sentinel_tpu.core.config import EngineConfig
    from sentinel_tpu.core.rules import (
        AuthorityRule,
        DegradeRule,
        FlowRule,
        ParamFlowRule,
        SystemRule,
        AUTHORITY_BLACK,
    )
    from sentinel_tpu.ops import engine as E
    from sentinel_tpu.ops import segment as SG
    from sentinel_tpu.runtime.registry import Registry

    # --- traffic first: the segment-compacted engine (ops/engine_seg.py)
    # needs a static compacted-axis capacity (cfg.seg_u), sized here from
    # the EXACT per-batch key-run counts of the deterministic traffic.
    # Batches are presorted host-side by (resource, has-origin) — batch
    # assembly is host work that overlaps the previous device tick in the
    # pipelined runtime, and the engine stays exact (slower per-item
    # fallback) for unsorted callers.
    node_rows = 16376 + 8  # must match cfg.node_rows (asserted below)
    rng = np.random.default_rng(0)
    n_batches = 8
    raw_batches = []
    max_segs = 0
    sort_ms = []
    for i in range(n_batches):
        z = rng.zipf(1.3, size=B).astype(np.int64)
        raw = (z - 1) % (N_TOTAL - 1) + 1
        ids_np = np.where(raw <= N_RULED, raw, node_rows + raw).astype(np.int32)
        with_origin = rng.random(B) < 0.125
        ph0 = np.where(
            ids_np <= 128, rng.integers(1, 1 << 20, B), 0
        ).astype(np.int32)
        inbound_a = (rng.random(B) < 0.5).astype(np.int32)
        inbound_c = (rng.random(B) < 0.5).astype(np.int32)
        rt = np.abs(rng.normal(3.0, 1.0, B)).astype(np.float32)
        t0 = time.perf_counter()
        order = np.lexsort((with_origin, ids_np))
        sort_ms.append((time.perf_counter() - t0) * 1000.0)
        ids_np = ids_np[order]
        with_origin = with_origin[order]
        ph0, inbound_a, inbound_c, rt = (
            ph0[order], inbound_a[order], inbound_c[order], rt[order]
        )
        # exact key-run count with ops/segment.heads_from_keys semantics:
        # synthetic heads sit at every GLOBAL BLOCK-aligned position (not
        # every 256th item of a run), so count them the same way
        head = np.ones(B, bool)
        head[1:] = (ids_np[1:] != ids_np[:-1]) | (
            with_origin[1:] != with_origin[:-1]
        )
        head |= (np.arange(B) % SG.BLOCK) == 0
        segs = int(head.sum())
        max_segs = max(max_segs, segs)
        raw_batches.append((ids_np, with_origin, ph0, inbound_a, inbound_c, rt))
    seg_u = -(-(int(max_segs * 1.15) + 128) // 128) * 128  # headroom, aligned

    # capacities sit just UNDER the 128x128 MXU tile boundary: every fused
    # dot streams the item axis once per ceil(table/16384) tile, so 16376
    # node rows (node_rows = +8 = 16384) and 16368-capacity rule tables
    # (+pad row) cost HALF of 16384/16385-row ones (ops/fused.py cost model)
    cfg = EngineConfig(
        max_resources=16368,
        max_nodes=16376,
        max_flow_rules=16368,
        max_degrade_rules=16368,  # cb table = 2*16368 rows -> 2 tiles (vs 3)
        max_param_rules=256,
        param_classes=1,  # one distinct rule duration in this config

        flow_rules_per_resource=1,
        degrade_rules_per_resource=1,
        param_rules_per_resource=1,
        batch_size=B,
        complete_batch_size=B,
        enable_minute_window=True,
        use_mxu_tables=on_tpu,
        fused_effects=on_tpu,  # Pallas effects megakernels (ops/fused.py)
        sketch_stats=True,
        # segment-compacted effects+checks: presorted batches compact
        # ~10x; capacity from the exact count above, so nothing drops
        # (asserted on TickOutput.seg_dropped in main)
        seg_effects=on_tpu,
        seg_fallback=False,
        seg_u=seg_u,
        # every flow rule below is DIRECT + limitApp default and batches
        # are presorted -> compile only the segmented-scan ranks
        seg_static_ranks=on_tpu,
        # param thresholds here are 500/window << 65535: 2 estimate digit
        # planes stay exact (EngineConfig.param_est_digits docs) and cut
        # a third of the per-item param-estimate gather kernel
        param_est_digits=2,
    )
    assert cfg.node_rows == node_rows, (cfg.node_rows, node_rows)
    reg = Registry(cfg)
    flow_rules, degrade_rules, param_rules, auth_rules = [], [], [], []
    for i in range(N_RULED):
        name = f"res-{i+1}"
        assert reg.resource_id(name) == i + 1
        flow_rules.append(FlowRule(resource=name, count=1000.0))
        degrade_rules.append(
            DegradeRule(resource=name, grade=0, count=200.0, time_window=10)
        )
        if i < 128:
            param_rules.append(ParamFlowRule(resource=name, param_idx=0, count=500.0))
        if i < 16:
            auth_rules.append(
                AuthorityRule(resource=name, limit_app="banned", strategy=AUTHORITY_BLACK)
            )
    ruleset = E.compile_ruleset(
        cfg,
        reg,
        flow_rules=flow_rules,
        degrade_rules=degrade_rules,
        param_rules=param_rules,
        authority_rules=auth_rules,
        system_rules=[SystemRule(qps=1e9)],
    )
    # ACTIVE tail enforcement (VERDICT r3 weak #3): the hottest
    # N_TAIL_RULED ids past the exact row space carry approximate-QPS
    # rules enforced from the observability sketch (engine._check_tail_flow
    # / rule_tensors.TailFlowTensors) — the measured tick includes this
    # work, so the "@1M resources" label covers ruled tail traffic too
    tail_rules = [
        (node_rows + r, 20.0)
        for r in range(N_RULED + 1, N_RULED + 1 + N_TAIL_RULED)
    ]
    ruleset = ruleset._replace(
        tail=jax.device_put(RT.compile_tail_flow_rules(tail_rules, cfg))
    )

    origin_row = reg.origin_node_row("res-1", "peer-app")
    origin_id = reg.origin_id("peer-app")
    acqs, comps = [], []
    for ids_np, with_origin, ph0, inbound_a, inbound_c, rt in raw_batches:
        ids = jnp.asarray(ids_np)
        # 1/8 of traffic carries an origin (origin-node stat fan-out), all
        # param-ruled hits carry a param value, 1/2 is inbound
        ph = np.stack([ph0, np.zeros(B, np.int32)], axis=1)
        acqs.append(
            E.empty_acquire(cfg)._replace(
                res=ids,
                count=jnp.ones((B,), jnp.int32),
                origin_id=jnp.asarray(
                    np.where(with_origin, origin_id, -1).astype(np.int32)
                ),
                origin_node=jnp.asarray(
                    np.where(with_origin, origin_row, cfg.trash_row).astype(np.int32)
                ),
                inbound=jnp.asarray(inbound_a),
                param_hash=jnp.asarray(ph),
            )
        )
        comps.append(
            E.empty_complete(cfg)._replace(
                res=ids,
                rt=jnp.asarray(rt),
                success=jnp.ones((B,), jnp.int32),
                inbound=jnp.asarray(inbound_c),
                param_hash=jnp.asarray(ph),
            )
        )
    info = {
        "seg_u": seg_u,
        "max_segments": max_segs,
        "host_presort_ms": round(float(np.median(sort_ms)), 2),
    }
    return cfg, E, ruleset, acqs, comps, info


def device_tick_ms(cfg, E, ruleset, acqs, comps, k1=8, k2=40) -> float:
    """Per-tick device time via the K-slope of scan-packed ticks."""
    import jax
    import jax.numpy as jnp

    KS = 4
    stacked_acq = jax.tree.map(
        lambda *xs: jnp.stack(xs), *(acqs[i % len(acqs)] for i in range(KS))
    )
    stacked_comp = jax.tree.map(
        lambda *xs: jnp.stack(xs), *(comps[i % len(comps)] for i in range(KS))
    )
    state0 = E.init_state(cfg)
    load = jnp.float32(0.0)
    cpu = jnp.float32(0.0)

    def make(K):
        def many(state, base, sacq, scomp):
            def body(s, t):
                a = jax.tree.map(lambda x: x[t % KS], sacq)
                c = jax.tree.map(lambda x: x[t % KS], scomp)
                s, o = E.tick(
                    s, ruleset, a, c, base + t * 7, load, cpu,
                    cfg=cfg, features=E.ALL_FEATURES,
                )
                return s, o.verdict[0]

            state, vs = jax.lax.scan(body, state, jnp.arange(K, dtype=jnp.int32))
            return state, vs

        return jax.jit(many)

    m1, m2 = make(k1), make(k2)
    jax.block_until_ready(m1(state0, jnp.int32(0), stacked_acq, stacked_comp))
    jax.block_until_ready(m2(state0, jnp.int32(0), stacked_acq, stacked_comp))

    def samples(n):
        slopes = []
        for s in range(n):
            t0 = time.perf_counter()
            jax.block_until_ready(
                m1(state0, jnp.int32(999 * s), stacked_acq, stacked_comp)
            )
            t1 = time.perf_counter() - t0
            t0 = time.perf_counter()
            jax.block_until_ready(
                m2(state0, jnp.int32(999 * s), stacked_acq, stacked_comp)
            )
            t2 = time.perf_counter() - t0
            slopes.append((t2 - t1) / (k2 - k1) * 1000.0)
        return slopes

    # median of per-sample slopes, NOT min-of-mins: per-call variance can
    # make a min-based slope collapse to ~0 and report a nonsense tick
    # time; retry once if the result is implausible
    sl = sorted(samples(4))
    d = sl[len(sl) // 2]
    if d < 0.05:
        sl = sorted(samples(6))
        d = sl[len(sl) // 2]
    return max(d, 0.001)


@dataclasses.dataclass(frozen=True)
class ServedScale:
    """Size of the served 1 M-resource scenario.  The defaults ARE the
    benchmark deployment (BASELINE.json config 2 at the north star's 1 M
    resources; capacities sit just under the MXU tile boundary, see
    ``build``) and are the only size a measurement may use; a smaller
    instance exists solely so ``chip_smoke.py --rehearse-cpu`` can walk
    the same code on CPU."""

    n_ruled: int = N_RULED
    n_tail_ruled: int = N_TAIL_RULED
    n_total: int = N_TOTAL
    n_param_ruled: int = 128
    n_authority_ruled: int = 16
    max_resources: int = 16368
    max_nodes: int = 16376
    max_rules: int = 16368  # flow and degrade rule capacity
    max_param_rules: int = 256
    batch: int = 1 << 17
    flow_qps: float = 1000.0  # every ruled resource's FlowRule count
    tail_qps: float = 20.0  # every tail rule's count


def served_config(scale: ServedScale = ServedScale(), **overrides):
    """The served deployment's engine config: ``platform_engine_config``
    (the product's platform detection) with only capacity shape + the
    documented ``param_est_digits`` workload knob set."""
    from sentinel_tpu.core.config import platform_engine_config

    base = dict(
        max_resources=scale.max_resources,
        max_nodes=scale.max_nodes,
        max_flow_rules=scale.max_rules,
        max_degrade_rules=scale.max_rules,
        max_param_rules=scale.max_param_rules,
        param_classes=1,
        flow_rules_per_resource=1,
        degrade_rules_per_resource=1,
        param_rules_per_resource=1,
        batch_size=scale.batch,
        complete_batch_size=scale.batch,
        enable_minute_window=True,
        sketch_stats=True,
        param_est_digits=2,  # thresholds << 65535 (EngineConfig docs)
    )
    base.update(overrides)
    return platform_engine_config(**base)


def served_scenario(
    scale: ServedScale = ServedScale(),
    seed: int = 1,
    n_batches: int = 6,
    cfg_overrides: Optional[dict] = None,
    **client_kw,
):
    """The served 1 M-resource deployment, set up through the PUBLIC
    client surface only: a ``SentinelClient`` on ``served_config``,
    resources interned through the registry, rules loaded
    through the managers (incl. tail-rule promotion), and seeded
    Zipf(1.3) traffic as ``submit_block`` column tuples.  The one
    definition ``client_bench`` and ``chip_smoke.py`` share.

    Returns ``(client, traffic, info)``: ``traffic`` is ``n_batches``
    tuples ``(ids, origin_node, origin_id, param_hash, inbound, rt)`` of
    ``scale.batch`` items each; the client is NOT started."""
    from sentinel_tpu.core.rules import (
        AuthorityRule,
        DegradeRule,
        FlowRule,
        ParamFlowRule,
        SystemRule,
        AUTHORITY_BLACK,
    )
    from sentinel_tpu.runtime.client import SentinelClient

    B = scale.batch
    cfg = served_config(scale, **(cfg_overrides or {}))
    node_rows = cfg.node_rows
    c = SentinelClient(cfg=cfg, **client_kw)

    # resources + rules through the PUBLIC surface
    ruled = [f"res-{i+1}" for i in range(scale.n_ruled)]
    for i, name in enumerate(ruled):
        rid = c.registry.resource_id(name)
        assert rid == i + 1
    # exhaust the organic exact space so tail names intern as sketch ids
    while True:
        rid = c.registry.resource_id(f"burn-{c.registry.num_resources}")
        if c.registry.is_sketch_id(rid):
            break
    tail_names = [f"tail-{r}" for r in range(scale.n_tail_ruled)]
    for n in tail_names:
        c.registry.resource_id(n)  # intern -> sequential sketch ids
    c.flow_rules.load(
        [FlowRule(resource=n, count=scale.flow_qps) for n in ruled]
        + [FlowRule(resource=n, count=scale.tail_qps) for n in tail_names]
    )
    c.degrade_rules.load(
        [
            DegradeRule(resource=n, grade=0, count=200.0, time_window=10)
            for n in ruled
        ]
    )
    c.param_flow_rules.load(
        [
            ParamFlowRule(resource=n, param_idx=0, count=500.0)
            for n in ruled[: scale.n_param_ruled]
        ]
    )
    c.authority_rules.load(
        [
            AuthorityRule(resource=n, limit_app="banned", strategy=AUTHORITY_BLACK)
            for n in ruled[: scale.n_authority_ruled]
        ]
    )
    c.system_rules.load([SystemRule(qps=1e9)])
    if c.cfg.seg_effects:
        assert c.cfg.seg_static_ranks, "client should self-specialize here"
    # rule load may promote tail resources into freed exact rows — traffic
    # must follow the registry's CURRENT ids (the product contract)
    tail_ids = np.array(
        [c.registry.peek_resource_id(n) for n in tail_names], np.int64
    )

    rng = np.random.default_rng(seed)
    origin_row = c.registry.origin_node_row("res-1", "peer-app")
    origin_id = c.registry.origin_id("peer-app")
    traffic = []
    for _ in range(n_batches):
        z = rng.zipf(1.3, size=B).astype(np.int64)
        raw = (z - 1) % (scale.n_total - 1) + 1
        tail_k = raw - scale.n_ruled - 1  # >= 0 for tail traffic
        ids = np.where(
            raw <= scale.n_ruled,
            raw,
            np.where(
                tail_k < scale.n_tail_ruled,
                tail_ids[np.clip(tail_k, 0, scale.n_tail_ruled - 1)],
                node_rows + tail_k,
            ),
        ).astype(np.int32)
        with_origin = rng.random(B) < 0.125
        onode = np.where(with_origin, origin_row, cfg.trash_row).astype(np.int32)
        oid = np.where(with_origin, origin_id, -1).astype(np.int32)
        ph = np.zeros((B, cfg.param_dims), np.int32)
        ph[:, 0] = np.where(
            ids <= scale.n_param_ruled, rng.integers(1, 1 << 20, B), 0
        )
        inb = (rng.random(B) < 0.5).astype(np.int32)
        rt = np.abs(rng.normal(3.0, 1.0, B)).astype(np.float32)
        traffic.append((ids, onode, oid, ph, inb, rt))
    info = {
        "tail_ids": tail_ids,
        "tail_rules_promoted_to_exact_rows": int((tail_ids < node_rows).sum()),
    }
    return c, traffic, info


def client_bench(B: int, n_blocks: int = 32, depth: int = 4) -> dict:
    """END-TO-END product path: the served 1M-resource scenario
    (``served_scenario``) through ``SentinelClient`` — registry interning,
    rule-manager loads (incl. tail-rule promotion), host batch assembly,
    presort, engine tick, and pipelined verdict readback (submit_block
    futures).  The client auto-specializes seg_static_ranks itself when
    the loaded ruleset qualifies.

    Latency numbers are MEASURED wall-clock from submit_block to future
    resolution."""
    from sentinel_tpu.core.errors import PASS
    from sentinel_tpu.runtime.client import SentinelClient

    c, traffic, info = served_scenario(
        ServedScale(batch=B), mode="threaded", pipeline_depth=depth
    )
    n_tr = len(traffic)
    # capacity sizing (operator knowledge of the workload, like the
    # engine section): exact post-sort key-run count of each batch
    max_segs = 0
    for ids, onode, oid, _ph, _inb, _rt in traffic:
        order = np.lexsort((oid, onode, ids))
        segs = SentinelClient._host_seg_count(
            (ids[order], onode[order], oid[order])
        )
        max_segs = max(max_segs, segs)
    # explicit headroom so the auto-resize never kicks in mid-measurement
    # (a background recompile would pollute the timing run); the resize
    # path compiles + hot-swaps the tick synchronously here
    want_u = min(B, -(-int(max_segs * 1.3 + 256) // 128) * 128)
    from sentinel_tpu.ops import engine_seg as _ES

    if want_u > _ES.seg_capacity(c.cfg, B):
        c._seg_resizing = True
        c._resize_seg_u(want_u)

    # warm the two batch shapes (the threaded start() path does this for
    # servers; here the loop is driven manually)
    c._warm_shapes()

    # per-stage decomposition of req_p99_ms via the obs span tracer
    # (assemble / presort / dispatch / device / readback / resolve): the
    # tracer is enabled only for the measured run so warmup ticks don't
    # pollute the percentiles.  Overhead is ~6 clock reads + ring stores
    # per tick — noise against a >10 ms device tick.
    from sentinel_tpu import obs

    obs.TRACER.reset()
    obs.enable()

    import threading

    feed_lock = threading.Lock()
    state = {"done": 0, "next": 0}
    lat = []
    t_submit = {}
    results = []

    def feed():
        with feed_lock:
            k = state["next"]
            if k >= n_blocks:
                return
            state["next"] = k + 1
        ids, onode, oid, ph, inb, rt = traffic[k % n_tr]
        t_submit[k] = time.perf_counter()
        fut = c.submit_block(
            ids, origin_node=onode, origin_id=oid, param_hash=ph, inbound=inb
        )
        c.submit_completion_block(ids, rt, inbound=inb, param_hash=ph)

        def on_done(f, k=k):
            # runs on resolver-pool threads — everything shared is locked
            with feed_lock:
                lat.append(time.perf_counter() - t_submit[k])
                state["done"] += 1
                results.append(f.result()[0])
            feed()

        fut.add_done_callback(on_done)

    # measured wire bytes (sentinel_wire_bytes_total deltas): the actual
    # host<->device transfer per tick — the number ROADMAP item 1 must
    # shrink — next to the modeled transport_mb_per_tick estimate
    def _wire_snapshot() -> dict:
        out_w = {}
        for path_l in ("device", "cluster", "timeline"):
            for d in ("tx", "rx"):
                m = obs.REGISTRY.get(
                    "sentinel_wire_bytes_total",
                    {"path": path_l, "direction": d},
                )
                out_w[f"{path_l}_{d}"] = float(m.value) if m is not None else 0.0
        return out_w

    wire0 = _wire_snapshot()
    inflight = depth + 4
    t0 = time.perf_counter()
    for _ in range(min(inflight, n_blocks)):
        feed()
    while state["done"] < n_blocks:
        c.tick_once()
    wall = time.perf_counter() - t0
    obs.disable()
    wire1 = _wire_snapshot()
    wire_bytes = {k: round(wire1[k] - wire0[k]) for k in wire1}
    wire_bytes["device_mb_per_tick"] = round(
        (wire_bytes["device_tx"] + wire_bytes["device_rx"]) / max(n_blocks, 1) / 1e6,
        3,
    )
    # the new per-resource timeline channel's wire cost, separated out so
    # ROADMAP item 1's transport work sees it (rx = device readback of the
    # top-K matrix, tx = metric-log bytes written behind the tick)
    timeline_bytes = wire_bytes["timeline_rx"] + wire_bytes["timeline_tx"]
    # {stage: {count, p50_ms, p99_ms, ...}} — decomposes req_p99_ms into
    # where each millisecond goes (BENCH_r0N consumers read this directly)
    stage_breakdown = obs.summarize(obs.TRACER.snapshot(), prefix="tick.")

    # transport decomposition: per-tick bytes actually uploaded (constant
    # columns ride the device-resident cache) + verdict readback
    up_mb = (
        # acquire: res, origin_node, origin_id, inbound + ph lane0 (int32)
        5 * 4 * B
        # completion: res, rt, inbound, success(1s≠pad 0s) + ph lane0
        + 5 * 4 * B
    ) / 1e6
    down_mb = B / 1e6  # int8 verdicts (wait skipped: no PASS_WAIT here)
    if c.cfg.packed_wire:
        # packed transport: the MEASURED bytes are the model — narrow
        # dirty-column uploads, one fused wire readback (ops/wire.py)
        up_mb = wire_bytes["device_tx"] / max(n_blocks, 1) / 1e6
        down_mb = (
            wire_bytes["device_rx"] + timeline_bytes
        ) / max(n_blocks, 1) / 1e6

    verd = np.concatenate(results[-3:])
    lat_ms = np.sort(np.array(lat[inflight:] or lat)) * 1000.0
    out = {
        "batch": B,
        "blocks": n_blocks,
        "dps": round(n_blocks * B / wall),
        "effective_tick_ms": round(wall / n_blocks * 1000.0, 3),
        "req_p50_ms": round(float(lat_ms[len(lat_ms) // 2]), 1),
        "req_p99_ms": round(float(lat_ms[int(len(lat_ms) * 0.99)]), 1),
        "pipeline_depth": depth,
        "host_build_ms_avg": round(c.host_build_ms_avg, 2),
        "stage_breakdown_ms": stage_breakdown,
        "wire_bytes": wire_bytes,
        "timeline_bytes": timeline_bytes,
        "transport_mb_per_tick": round(up_mb + down_mb, 2),
        "tail_rules_promoted_to_exact_rows": info[
            "tail_rules_promoted_to_exact_rows"
        ],
        "seg_dropped_total": c.seg_dropped_total,
        "seg_static_ranks": bool(c.cfg.seg_static_ranks),
        "pass_sample": int((verd == PASS).sum()),
        "block_sample": int((verd != PASS).sum()),
    }
    assert c.seg_dropped_total == 0
    assert (verd != PASS).any() and (verd == PASS).any()
    return out


def adaptive_overload_bench() -> dict:
    """ISSUE-7 row: closed-loop adaptive protection under a 2×-capacity
    flash crowd (adaptive/simload.py — real sync client on virtual time,
    fixed-capacity FIFO backend).  Controller ON vs OFF at the identical
    offered schedule: ON must keep storm p99 bounded and goodput near
    capacity while the ladder climbs and recovers; OFF demonstrates the
    queue collapse the controller exists to prevent.  Engine-time pure —
    the same numbers reproduce on any host."""
    from sentinel_tpu.adaptive.simload import (
        run_overload_sim,
        storm_controller_preset,
    )

    on = run_overload_sim(adaptive=True, adaptive_cfg=storm_controller_preset())
    off = run_overload_sim(adaptive=False)
    return {
        "offered_x_capacity": 2.0,
        "controller_on": on.to_dict(),
        "controller_off": off.to_dict(),
        "p99_collapse_ratio_off": round(
            off.p99_storm_ms / max(off.p99_healthy_ms, 1e-9), 2
        ),
        "p99_ratio_on": round(on.p99_storm_ms / max(on.p99_healthy_ms, 1e-9), 2),
        "goodput_held_frac_on": round(
            on.goodput_storm / max(on.goodput_healthy, 1e-9), 3
        ),
        "ladder_path": [
            (frm, to) for _t, frm, to in on.ladder_transitions
        ],
    }


def cluster_sharded_bench(n_requests: int = 2000, workers: int = 8) -> dict:
    """ISSUE-6 satellite: the sharded cluster token fleet (cluster/shard.py)
    at N=1 vs N=4 shards — routed decisions/s, decision p50/p99, and the
    failover blip (kill one shard → time until its flows are being served
    again from the bounded-slack lease fallback).  Host-path numbers: the
    work here is the TCP round-trip + the decision engine's micro-batched
    tick, so this row measures the FLEET overhead, not the kernels."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from sentinel_tpu.cluster import constants as CC
    from sentinel_tpu.cluster.shard import ShardFleet
    from sentinel_tpu.core import rules as R
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.runtime.client import SentinelClient

    made = []

    def factory():
        c = SentinelClient(cfg=small_engine_config(), mode="sync")
        c.start()
        made.append(c)
        return c

    flows = list(range(1001, 1017))  # 16 flows spread over the ring
    out: dict = {
        "flows": len(flows),
        "requests": n_requests,
        "workers": workers,
        "note": (
            "in-process fleet: all shards' decision engines share this "
            "host's cores, so N=4 measures fleet-protocol overhead and "
            "the failover blip, not capacity scaling — deployed shards "
            "run on separate hosts/devices"
        ),
    }
    try:
        for n_shards in (1, 4):
            fleet = ShardFleet(
                factory,
                n_shards=n_shards,
                lease_slack=0.25,
                retry_interval_s=300.0,
                lease_ttl_ms=600_000,
                timeout_ms=5000,
                reconnect_interval_s=0.0,
            )
            try:
                fleet.load_flow_rules(
                    "default",
                    [
                        R.FlowRule(
                            resource=f"res-{fid}",
                            count=1e9,  # measure routing, not admission
                            cluster_mode=True,
                            cluster_flow_id=fid,
                            cluster_threshold_type=1,
                        )
                        for fid in flows
                    ],
                )
                for fid in flows:  # warm connections + leases off the clock
                    fleet.client.request_token(fid)
                lat: list = []
                lat_lock = threading.Lock()

                def one(i):
                    t0 = time.perf_counter()
                    r = fleet.client.request_token(flows[i % len(flows)])
                    dt = time.perf_counter() - t0
                    with lat_lock:
                        lat.append(dt)
                    return r.status

                t0 = time.perf_counter()
                with ThreadPoolExecutor(max_workers=workers) as pool:
                    statuses = list(pool.map(one, range(n_requests)))
                wall = time.perf_counter() - t0
                lat_ms = np.sort(np.array(lat)) * 1000.0
                row = {
                    "shards": n_shards,
                    "dps": round(n_requests / wall),
                    "decision_p50_ms": round(float(lat_ms[len(lat_ms) // 2]), 3),
                    "decision_p99_ms": round(
                        float(lat_ms[int(len(lat_ms) * 0.99)]), 3
                    ),
                    "non_ok": int(sum(1 for s in statuses if s != CC.STATUS_OK)),
                }
                if n_shards > 1:
                    # failover blip: kill one flow's owner and time until a
                    # decision for that flow is served again (lease fallback)
                    victim_fid = flows[0]
                    victim = fleet.client.owner_of(victim_fid)
                    t_kill = time.perf_counter()
                    fleet.kill(victim)
                    blip_deadline = t_kill + 30.0
                    recovered = False
                    while time.perf_counter() < blip_deadline:
                        if fleet.client.request_token(victim_fid).status == CC.STATUS_OK:
                            recovered = True
                            break
                    row["failover_blip_ms"] = round(
                        (time.perf_counter() - t_kill) * 1000.0, 1
                    )
                    if not recovered:
                        # deadline exhaustion, NOT a measured blip — mark
                        # it so ~30000 ms can't read as a real recovery
                        row["failover_timed_out"] = True
                    row["degraded_shard"] = victim
                out[f"n{n_shards}"] = row
            finally:
                fleet.stop()
        if out["n1"]["dps"]:
            out["speedup_n4_vs_n1"] = round(out["n4"]["dps"] / out["n1"]["dps"], 2)
    finally:
        for c in made:
            c.stop()
    return out


# -- multihost fleet curve (--multihost → MULTIHOST_r13.json) ----------------


def _fleet_point(
    fleet, fids, duration_s: float, workers: int, count: int = 1
) -> dict:
    """Hammer an already-warmed fleet for ``duration_s`` and report the
    steady-state lease-phase shape: tokens/s, sampled call p50/p99, and
    RPCs-per-decision (routed singles + batch frames over decisions)."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from sentinel_tpu.obs.registry import REGISTRY as OBS

    def _frames_tx() -> float:
        m = OBS.get("sentinel_cluster_batch_frames_total", {"direction": "tx"})
        return float(m.value) if m is not None else 0.0

    shards = list(fleet.client._shards.values())
    req0 = sum(st.c_requests.value for st in shards)
    adm0 = sum(st.c_local_admits.value for st in shards)
    fr0 = _frames_tx()
    lat: list = []
    lat_lock = threading.Lock()
    n_done = [0] * workers
    end_t = [0.0]

    def worker(wi: int) -> None:
        rng = np.random.default_rng(wi)
        order = [int(x) for x in rng.permutation(fids)]
        i = n = 0
        loc = []
        end = end_t[0]
        while time.perf_counter() < end:
            t0 = time.perf_counter()
            fleet.client.request_token(order[i % len(order)], count)
            if n % 64 == 0:  # sample: timing every call would dominate it
                loc.append(time.perf_counter() - t0)
            i += 1
            n += 1
        with lat_lock:
            lat.extend(loc)
        n_done[wi] = n

    end_t[0] = time.perf_counter() + duration_s
    cpu0, t0 = time.process_time(), time.perf_counter()
    with ThreadPoolExecutor(max_workers=workers) as pool:
        list(pool.map(worker, range(workers)))
    wall = time.perf_counter() - t0
    cpu = time.process_time() - cpu0
    fleet.client.flush_lease_refresh(5.0)
    decisions = sum(n_done)
    routed = sum(st.c_requests.value for st in shards) - req0
    local = sum(st.c_local_admits.value for st in shards) - adm0
    frames = _frames_tx() - fr0
    la = np.sort(np.asarray(lat)) * 1000.0
    return {
        "routed_tokens_per_s": round(decisions * count / wall),
        "decisions": decisions,
        "call_p50_ms": round(float(la[len(la) // 2]), 4),
        "call_p99_ms": round(float(la[int(len(la) * 0.99)]), 4),
        "rpcs_per_decision": round((routed + frames) / max(decisions, 1), 5),
        "local_admit_share": round(local / max(decisions, 1), 4),
        "routed_rpcs": int(routed),
        "batch_frames": int(frames),
        "cpu_core_share": round(cpu / wall, 2),
    }


def multihost_fleet_bench(
    duration_s: float = 3.0, workers: int = 8, flows: int = 32
) -> dict:
    """The MULTIHOST curve, r13 revision: the cluster token fleet under
    protocol v2's lease-first admission at 1/2/4 shards.  The seed curve
    (MULTIHOST_BENCH.json) anti-scaled — 28.9k → 15.2k routed tokens/s
    with call_p50 280 ms — because every decision was one synchronous
    RPC.  Lease-first makes the steady state RPC-free: decisions admit
    locally against standing leases topped up ahead of exhaustion by
    batched LEASE frames, so tokens/s is bounded by the admitting hosts,
    not the socket.

    Environment honesty (same note as the seed bench): every shard AND
    the driving workers share this container's single core, so the curve
    cannot show CAPACITY scaling — adding in-process shards only splits
    the same core.  What it shows is that shards no longer COST
    throughput (the seed lost 47% going 1 → 4): the per-decision RPC
    that made fan-out anti-scale is gone, and the residual per-shard
    overhead is a handful of lease frames per thousand decisions.
    Deployed shards on separate hosts multiply capacity by the host
    count exactly because the client-side cost per decision no longer
    grows with the fleet."""
    from sentinel_tpu.cluster.shard import ShardFleet
    from sentinel_tpu.core import rules as R
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.runtime.client import SentinelClient

    made = []

    def factory():
        c = SentinelClient(cfg=small_engine_config(), mode="sync")
        c.start()
        made.append(c)
        return c

    fids = list(range(1001, 1001 + flows))
    out: dict = {
        "metric": "multihost_routed_tokens_per_s",
        "revision": "r13",
        "flows": flows,
        "workers": workers,
        "duration_s": duration_s,
        "seed_points": {"1": 28886, "2": 22740, "4": 15237},
        "points": [],
        "environment": (
            "in-process fleet on ONE core: shards and workers split the "
            "same cycles, so the curve documents that shard fan-out no "
            "longer costs throughput (seed: −47% at 4 shards) — not "
            "multi-host capacity, which needs one host per shard"
        ),
    }
    try:
        for n_shards in (1, 2, 4):
            fleet = ShardFleet(
                factory,
                n_shards=n_shards,
                lease_slack=0.25,
                retry_interval_s=300.0,
                lease_ttl_ms=600_000,
                timeout_ms=5000,
                reconnect_interval_s=0.0,
            )
            try:
                fleet.load_flow_rules(
                    "default",
                    [
                        R.FlowRule(
                            resource=f"res-{fid}",
                            count=1e9,  # measure the protocol, not admission
                            cluster_mode=True,
                            cluster_flow_id=fid,
                            cluster_threshold_type=1,
                        )
                        for fid in fids
                    ],
                )
                for fid in fids:  # warm: connections + bootstrap leases
                    fleet.client.request_token(fid)
                fleet.client.flush_lease_refresh(5.0)
                row = _fleet_point(fleet, fids, duration_s, workers)
                row["shards"] = n_shards
                out["points"].append(row)
            finally:
                fleet.stop()
        by = {p["shards"]: p for p in out["points"]}
        out["scaling_4_vs_1"] = round(
            by[4]["routed_tokens_per_s"] / max(by[1]["routed_tokens_per_s"], 1), 2
        )
        out["seed_scaling_4_vs_1"] = round(15237 / 28886, 2)
    finally:
        for c in made:
            c.stop()
    return out


def _cluster_smoke_metrics() -> dict:
    """The perf sentry's fleet-path sample: a 2-shard fleet hammered
    briefly at per-decision grain.  ``cluster_rpcs_per_decision`` trips
    if the lease-first fast path stops absorbing steady-state traffic
    (every decision turning back into an RPC measures ~1.0 against a
    0.05 ceiling); ``cluster_call_p50_ms`` trips if the common-case
    admission stops being a local debit."""
    from sentinel_tpu.cluster.shard import ShardFleet
    from sentinel_tpu.core import rules as R
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.runtime.client import SentinelClient

    made = []

    def factory():
        c = SentinelClient(cfg=small_engine_config(), mode="sync")
        c.start()
        made.append(c)
        return c

    fids = list(range(1001, 1017))
    fleet = ShardFleet(
        factory,
        n_shards=2,
        lease_slack=0.25,
        retry_interval_s=300.0,
        lease_ttl_ms=600_000,
        timeout_ms=5000,
        reconnect_interval_s=0.0,
    )
    try:
        fleet.load_flow_rules(
            "default",
            [
                R.FlowRule(
                    resource=f"res-{fid}",
                    count=1e9,
                    cluster_mode=True,
                    cluster_flow_id=fid,
                    cluster_threshold_type=1,
                )
                for fid in fids
            ],
        )
        for fid in fids:
            fleet.client.request_token(fid)
        fleet.client.flush_lease_refresh(5.0)
        row = _fleet_point(fleet, fids, duration_s=1.5, workers=4)
        return {
            "cluster_rpcs_per_decision": row["rpcs_per_decision"],
            "cluster_call_p50_ms": row["call_p50_ms"],
        }
    finally:
        fleet.stop()
        for c in made:
            c.stop()


# -- sketch statistics tier @ 1M ruled resources (--sketch-tier) -------------


def sketch_tier_bench(B: int = 2048, n_ticks: int = 12) -> dict:
    """The BENCH ``sketch_tier`` row: ONE MILLION ruled tail resources
    enforced by the salsa sketch tier (sentinel_tpu/sketch) on a
    minute-scale window, reporting decisions/s, persistent HBM bytes vs
    the exact tier and the seed int32 CMS, and the MEASURED per-resource
    estimate error against an exact host shadow of the same stream.

    CPU-reproducible (plain path): the tick runs the real tail-rule
    check (threshold gathers + O(1) running-sum estimates + within-tick
    rank) and both sketch write sides, with a Zipf stream over the 1 M
    ruled ids."""
    import jax
    import jax.numpy as jnp

    from sentinel_tpu.core import rule_tensors as RT
    from sentinel_tpu.core.config import EngineConfig
    from sentinel_tpu.core.errors import BLOCK_FLOW
    from sentinel_tpu.obs import profile as PROF
    from sentinel_tpu.ops import engine as E
    from sentinel_tpu.ops import gsketch as GS
    from sentinel_tpu.ops import window as W
    from sentinel_tpu.sketch import salsa as SA

    N_TAIL = 1_000_000
    cfg = EngineConfig(
        max_resources=16368,
        max_nodes=16376,
        batch_size=B,
        complete_batch_size=B,
        enable_minute_window=False,  # the sketch carries the minute scale
        sketch_stats=True,
        sketch_salsa=True,
        sketch_depth=2,
        sketch_width=1 << 16,
        sketch_capacity=1 << 21,
        sketch_sample_count=60,
        sketch_window_ms=1000,
        hotset_k=64,
    )
    scfg = E.sketch_config(cfg)

    class _Reg:
        def resource_id(self, n):
            return 1

    ruleset = E._compile_ruleset(cfg, _Reg(), [], [], [], [], [], None)
    # per-second limit, scaled to the 60 s interval at compile; low
    # enough that the Zipf head crosses it mid-run — the reported
    # tail_blocked_sample proves the enforcement path produces verdicts
    qps_limit = 2.0
    t0 = time.perf_counter()
    tail_rules = [(cfg.node_rows + 1 + r, qps_limit) for r in range(N_TAIL)]
    with PROF.ledger_owner("bench.sketch_tier"):
        ruleset = ruleset._replace(
            tail=jax.device_put(RT.compile_tail_flow_rules(tail_rules, cfg))
        )
        # this harness calls E._compile_ruleset directly (bypassing the
        # ledgered wrapper), so claim the rule tensors explicitly — the
        # BENCH ledger breakdown must cover every pool it reports
        PROF.LEDGER.track("rules", "bench.ruleset", ruleset)
    compile_rules_s = time.perf_counter() - t0

    features = frozenset({"tail_flow"})
    # donate=True is the production configuration (runtime/client.py builds
    # every tick with donated engine state); without it XLA re-copies the
    # packed sketch ring on every functional column update
    tick = E.make_tick(cfg, donate=True, features=features)
    with PROF.ledger_owner("bench.sketch_tier"):
        state = E.init_state(cfg)
    rng = np.random.default_rng(5)
    batches = []
    exact = np.zeros(N_TAIL + 1, np.int64)  # host shadow: exact attempts
    n_batches = 6
    for _ in range(n_batches):
        z = rng.zipf(1.1, size=B).astype(np.int64)
        k = (z - 1) % N_TAIL + 1
        batches.append(
            E.empty_acquire(cfg)._replace(
                res=jnp.asarray(cfg.node_rows + k, jnp.int32),
                count=jnp.ones(B, jnp.int32),
            )
        )
    comp = E.empty_complete(cfg)
    zf = jnp.float32(0.0)
    for w in range(2):  # compile + warm (outside the shadow accounting)
        state, out = tick(
            state, ruleset, batches[w], comp, jnp.int32(w), zf, zf
        )
    jax.block_until_ready(out.verdict)

    with PROF.ledger_owner("bench.sketch_tier"):
        state = E.init_state(cfg)
    blocks = 0
    t0 = time.perf_counter()
    for t in range(n_ticks):
        a = batches[t % n_batches]
        state, out = tick(
            state, ruleset, a, comp, jnp.int32(1_000 + 37 * t), zf, zf
        )
    jax.block_until_ready(out.verdict)
    wall = time.perf_counter() - t0
    # shadow the same stream on the host (attempts per ruled id)
    for t in range(n_ticks):
        ids = np.asarray(batches[t % n_batches].res) - cfg.node_rows
        np.add.at(exact, ids, 1)
    blocks = int(np.asarray(out.verdict == BLOCK_FLOW).sum())

    # measured error: sketch windowed attempts (pass + block estimates)
    # vs the exact shadow, over the hottest 2k + 2k random touched ids
    touched = np.flatnonzero(exact)
    hot = touched[np.argsort(exact[touched])[-2000:]]
    cold = rng.choice(touched, size=min(2000, len(touched)), replace=False)
    sample = np.unique(np.concatenate([hot, cold]))
    est = np.asarray(
        SA.estimate(
            state.gs,
            jnp.int32(1_000 + 37 * n_ticks),
            jnp.asarray(cfg.node_rows + sample, jnp.int32),
            scfg,
        )
    )
    attempts_est = est[:, W.EV_PASS] + est[:, W.EV_BLOCK]
    errs = attempts_est - exact[sample]
    V = float(exact.sum())
    eps_bound = math.e / cfg.sketch_width * V
    exact_tier_bytes = N_TAIL * scfg.sample_count * (W.NUM_EVENTS * 4 + 8)
    seed_cms_bytes = 4 * scfg.sample_count * scfg.depth * scfg.width * GS.PLANES
    lv = np.asarray(SA.level_histogram(state.gs, scfg))
    # HBM memory ledger (obs/profile.py): the MEASURED per-pool device
    # bytes the plane accounts at allocation time, next to the formulaic
    # salsa footprint — the PR 15 acceptance bound is agreement on the
    # sketch pool within 10%
    snap = PROF.LEDGER.snapshot()
    pools: dict = {}
    for k, v in snap["entries"].items():
        if "/bench.sketch_tier:" in k:
            p = k.split("/", 1)[0]
            pools[p] = pools.get(p, 0) + int(v)
    sketch_pool = pools.get("sketch", 0)
    ledger = {
        "pools": dict(sorted(pools.items())),
        "total_bytes": sum(pools.values()),
        "sketch_pool_vs_salsa_hbm": round(
            sketch_pool / max(SA.hbm_bytes(scfg), 1), 4
        ),
    }
    PROF.LEDGER.drop_owner("bench.sketch_tier")
    return {
        "resources_ruled": N_TAIL,
        "window": f"{scfg.sample_count}x{scfg.window_ms}ms",
        "width_x_depth": [cfg.sketch_width, cfg.sketch_depth],
        "batch": B,
        "dps": round(n_ticks * B / wall),
        "tick_ms": round(wall / n_ticks * 1000.0, 3),
        "tail_rule_compile_s": round(compile_rules_s, 2),
        "tail_blocked_sample": blocks,
        "hbm_bytes": {
            "salsa_tier": SA.hbm_bytes(scfg),
            "seed_cms_int32": seed_cms_bytes,
            "exact_tier_equivalent": exact_tier_bytes,
        },
        "ledger": ledger,
        "merged_words": [int(x) for x in lv],
        "error_vs_exact": {
            "stream_volume": V,
            "sampled_resources": int(len(sample)),
            "underestimates": int((errs < 0).sum()),  # must be 0
            "mean_abs": round(float(errs.mean()), 3),
            "max_abs": int(errs.max()),
            "mean_pct_of_volume": round(float(errs.mean()) / V * 100.0, 5),
            "max_pct_of_volume": round(float(errs.max()) / V * 100.0, 5),
            "eps_bound_abs": round(eps_bound, 1),
            "within_eps_bound_frac": round(float((errs <= eps_bound).mean()), 4),
        },
        "platform": jax.devices()[0].platform,
    }


# -- exact-tier window op before/after (BENCH_r14 --window-compare) ----------


def _window_op_rate(
    rows: int,
    op,
    n_ticks: int,
    mode: str,
    step_ms: int = 37,
    span: str = "",
    repeats: int = 3,
) -> float:
    """decisions/s through ONE jitted window-op step at the shape the
    engine tick pays every tick: an ``add_batch`` (scatter write + the
    rotation it triggers) plus the two reads every tick consumes — the
    per-entry [B] gather and the fleet-wide [rows] flow sum.

    ``op`` is a shared ``workload.OperatingPoint`` (the BENCH_WINDOW_*
    presets) carrying the batch and window-shape knobs that used to be
    hard-coded per bench row — the tuner, the simulator preset and
    these rows now read ONE definition.

    ``mode="masked"`` is the pre-r14 read shape (epoch-masked reductions
    over the bucket axis on every read, O(rows*nb) per tick);
    ``mode="run"`` is the O(1) running-sum path (expiry folds into the
    bucket rotation, reads are single gathers).  ``now_ms`` advances by
    ``step_ms`` per tick so rotation cost is IN the measurement."""
    import jax
    import jax.numpy as jnp

    from sentinel_tpu import obs
    from sentinel_tpu.ops import window as W

    B = op.batch_size
    cfg = W.WindowConfig(
        sample_count=op.sketch_sample_count,
        window_ms=op.sketch_window_ms,
        slack_frac=op.sketch_slack_frac,
    )
    rng = np.random.default_rng(11)
    slots = jnp.asarray(rng.integers(0, rows, B), jnp.int32)
    deltas = jnp.zeros((B, W.NUM_EVENTS), jnp.int32).at[:, W.EV_PASS].set(1)
    rt = jnp.asarray(np.abs(rng.normal(3.0, 1.0, B)), jnp.float32)

    if mode == "masked":

        @jax.jit
        def step(win, now):
            win = W.add_batch(win, now, slots, deltas, rt=rt, cfg=cfg)
            used = W.gather_window_event(win, now, slots, cfg, W.EV_PASS)
            fleet = W.window_event(win, now, cfg, W.EV_PASS)
            return win, used.sum() + fleet.sum()

    else:

        @jax.jit
        def step(win, now):
            win = W.add_batch(win, now, slots, deltas, rt=rt, cfg=cfg)
            used = W.gather_window_event_run(win, slots, W.EV_PASS)
            fleet = W.window_event_run(win, W.EV_PASS)
            return win, used.sum() + fleet.sum()

    state = W.init_window(rows, cfg)
    state, chk = step(state, jnp.int32(1_000))  # compile + warm
    jax.block_until_ready(chk)

    def once() -> float:
        nonlocal state
        with obs.span(f"winop.{span or mode}", ticks=n_ticks):
            t0 = time.perf_counter()
            for t in range(n_ticks):
                state, chk = step(state, jnp.int32(2_000 + step_ms * t))
            jax.block_until_ready(chk)
            return n_ticks * B / (time.perf_counter() - t0)

    return _best_of(once, repeats=repeats)


def window_compare_bench(rows: int = 16384, B: int = 4096, n_ticks: int = 240) -> dict:
    """BENCH_r14 before/after: the exact-tier window math at the shapes
    the engine tick pays.

    - ``before_masked`` vs ``after_run``: the same write + rotation +
      per-entry + fleet-wide reads at the second-window shape, through
      the old epoch-masked O(rows*nb) reductions vs the O(1) running
      sums (expiry folds into the bucket rotation; reads are single
      gathers — arXiv 1604.02450's running-sum bucket ring);
    - ``slack_rotation``: minute-scale (60x1000 ms) rotation maintenance
      with slack OFF vs ON — slack_frac=0.05 rounds to g=3 buckets, so
      the batched purge runs every 3rd bucket boundary (arXiv
      1703.01166's slack windows) for a bounded overestimate.  now
      advances one full bucket per tick: every tick crosses a boundary,
      the worst case for rotation and the best case for slack batching.
    """
    import jax

    from sentinel_tpu import obs

    from sentinel_tpu.workload.operating_point import (
        BENCH_WINDOW_EXACT,
        BENCH_WINDOW_MINUTE,
        BENCH_WINDOW_MINUTE_SLACK,
    )

    # the shared operating-point presets, re-batched to this run's B —
    # no more per-row literal knobs (they lived here pre-r19)
    op_exact = BENCH_WINDOW_EXACT.replace(batch_size=B, complete_batch_size=B)
    op_minute = BENCH_WINDOW_MINUTE.replace(batch_size=B, complete_batch_size=B)
    op_slack = BENCH_WINDOW_MINUTE_SLACK.replace(
        batch_size=B, complete_batch_size=B
    )
    obs.TRACER.reset()
    obs.enable()
    dps_before = _window_op_rate(rows, op_exact, n_ticks, "masked")
    dps_after = _window_op_rate(rows, op_exact, n_ticks, "run")
    rot_exact = _window_op_rate(
        rows, op_minute, n_ticks, "run", step_ms=1000, span="rotate_exact",
    )
    rot_slack = _window_op_rate(
        rows, op_slack, n_ticks, "run", step_ms=1000, span="rotate_slack",
    )
    obs.disable()
    g = max(
        1, math.ceil(op_slack.sketch_slack_frac * op_slack.sketch_sample_count)
    )
    rotations = -(-n_ticks // g)  # ceil: the cond purge fires every g-th

    def _row(dps: float, **extra) -> dict:
        return {
            "window_op_dps": round(dps),
            "tick_us": round(1e6 * B / max(dps, 1.0), 1),
            **extra,
        }

    return {
        "rows": rows,
        "batch": B,
        "ticks": n_ticks,
        "window": "10x100ms",
        "before_masked": _row(dps_before),
        "after_run": _row(dps_after),
        "speedup": round(dps_after / max(dps_before, 1.0), 2),
        "slack_rotation": {
            "window": "60x1000ms",
            "exact": _row(rot_exact, rotations=n_ticks, slack_skips=0),
            "slack_0.05": _row(
                rot_slack,
                slack_buckets=g,
                rotations=rotations,
                slack_skips=n_ticks - rotations,
            ),
            "rotation_speedup": round(rot_slack / max(rot_exact, 1.0), 2),
        },
        "stage_breakdown_ms": obs.summarize(
            obs.TRACER.snapshot(), prefix="winop."
        ),
        "platform": jax.devices()[0].platform,
    }


# -- continuous profiling plane (--profile-plane + BENCH_r15.json) -----------


def _profile_overhead_pct(B: int = 1024) -> float:
    """Ambient cost of the ARMED profiling plane — the memory ledger
    plus the rotating sketch-accuracy audit at its default cadence — vs
    the identical client with the audit off.  The ledger has no per-tick
    sites (allocation events only), so the audit's observe hook and its
    periodic K-row estimate readback are the whole serving-path cost;
    the PR 15 acceptance ceiling is <= 2% of ambient throughput."""
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.core.rules import FlowRule
    from sentinel_tpu.runtime.client import SentinelClient

    def make(audit_k: int):
        c = SentinelClient(
            cfg=small_engine_config(
                batch_size=B, max_resources=16, max_nodes=32,
                sketch_stats=True, sketch_width=1024,
            ),
            mode="sync",
            sketch_audit_k=audit_k,
        )
        c.start()
        # 64 names over 16 exact rows: most of the stream rides the
        # sketched tail, so the audit genuinely samples and re-folds
        names = [f"prof-{i}" for i in range(64)]
        ids = np.asarray([c.registry.resource_id(n) for n in names], np.int32)
        c.flow_rules.load([FlowRule(resource=n, count=1e9) for n in names[:8]])
        rng = np.random.default_rng(3)
        res = ids[rng.integers(0, len(ids), B)].astype(np.int32)
        # warm both shapes AND the audit's jit-cached estimate reader
        # (first audit fires at tick `period`) before any timed window
        for _ in range(20):
            c.submit_block(res)
            c.tick_once()
        return c, res

    def once(c, res) -> float:
        t0 = time.perf_counter()
        for _ in range(16):
            c.submit_block(res)
            c.tick_once()
        return 16 * B / (time.perf_counter() - t0)

    c_off, res_off = make(0)
    c_on, res_on = make(8)
    try:
        # interleave the samples: a noisy-box phase slows BOTH sides of
        # the ratio instead of landing on one, so best-of stays honest
        # (scheduler spikes here are 3-4x, so both sides need enough
        # rounds to land at least one clean peak each)
        d_off = d_on = 0.0
        for _ in range(8):
            d_off = max(d_off, once(c_off, res_off))
            d_on = max(d_on, once(c_on, res_on))
    finally:
        c_off.stop()
        c_on.stop()
    return max((d_off / max(d_on, 1.0) - 1.0) * 100.0, 0.0)


def online_audit_bench(n_rounds: int = 200, B: int = 256) -> dict:
    """BENCH_r15: the ONLINE sketch-accuracy audit (the rotating shadow
    sampler inside the serving client, obs/profile.SketchAudit) must
    reproduce the posture BENCH_r14 measured OFFLINE from a host shadow
    of the whole stream: zero underestimates, and an eps-bound pass rate
    consistent with within_eps_bound_frac ≈ 0.99."""
    from sentinel_tpu import obs
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.runtime.client import SentinelClient
    from sentinel_tpu.utils.time_source import VirtualTimeSource

    def _ctr(name: str) -> float:
        m = obs.REGISTRY.get(name)
        return float(m.value) if m is not None else 0.0

    names_c = (
        "sentinel_sketch_audit_checks_total",
        "sentinel_sketch_underestimates_total",
        "sentinel_sketch_eps_violations_total",
        "sentinel_sketch_audit_failures_total",
    )
    before = {n: _ctr(n) for n in names_c}
    vt = VirtualTimeSource()
    c = SentinelClient(
        app_name="bench-audit",
        cfg=small_engine_config(
            batch_size=B, max_resources=16, max_nodes=32,
            sketch_stats=True, sketch_width=1024,
        ),
        time_source=vt,
        mode="sync",
        sketch_audit_k=8,
        sketch_audit_period=4,
    )
    c.start()
    try:
        # a Zipf stream over 256 names on 16 exact rows: the hot head and
        # the long tail both land in the sketch, like the offline row
        names = [f"tail-{i}" for i in range(256)]
        ids = np.asarray([c.registry.resource_id(n) for n in names], np.int32)
        rng = np.random.default_rng(15)
        for _ in range(n_rounds):
            z = rng.zipf(1.3, size=B).astype(np.int64)
            res = ids[(z - 1) % len(ids)].astype(np.int32)
            c.submit_block(res)
            c.tick_once()
            vt.advance(25)
        au = c._audit
        section = au.flight_section()
    finally:
        c.stop()
    delta = {n: _ctr(n) - before[n] for n in names_c}
    checks = delta["sentinel_sketch_audit_checks_total"]
    eps = delta["sentinel_sketch_eps_violations_total"]
    return {
        "rounds": n_rounds,
        "batch": B,
        "checks": int(checks),
        "underestimates": int(delta["sentinel_sketch_underestimates_total"]),
        "eps_violations": int(eps),
        "audit_failures": int(delta["sentinel_sketch_audit_failures_total"]),
        "within_eps_frac": round(1.0 - eps / max(checks, 1.0), 4),
        "audit": section,
    }


# -- perf-regression sentry (--smoke + PERF_BASELINE.json) -------------------
#
# A fast, CPU-reproducible measurement of the serving path's throughput
# shape, pinned against committed tolerances so the r01→r07 perf
# trajectory cannot silently regress while the hot path is rewritten.
# `python bench.py --smoke` measures; `--update-baseline` re-pins after an
# INTENTIONAL perf change; tests/test_perf_sentry.py runs the comparison
# as a slow-marked test (and a fast synthetic-regression check).

PERF_BASELINE_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "PERF_BASELINE.json"
)

#: default tolerance per metric: min_ratio flags measured/baseline below
#: it (throughput floors), max_ratio flags above (latency/overhead
#: ceilings), max_abs flags an absolute ceiling.  0.6 catches a 2x
#: regression (ratio 0.5) with CPU-timing headroom; best-of-K sampling
#: keeps honest runs well above it.
DEFAULT_TOLERANCES = {
    "engine_tick_dps": {"min_ratio": 0.6},
    "client_path_dps": {"min_ratio": 0.6},
    # wall-clock mean over few ticks — the noisiest metric here (a busy
    # CI box doubles it without any code change), so the ceiling only
    # catches gross host-path regressions
    "host_build_ms": {"max_ratio": 2.5},
    "telemetry_overhead_pct": {"max_abs": 5.0},
    "stats_readback_bytes": {"max_abs": 256.0},
    # the per-resource timeline matrix (top-K selection + bucket gather,
    # ops/engine._device_res_stats) at K=128 — the PR 9 acceptance bound
    "timeline_overhead_pct": {"max_abs": 5.0},
    "timeline_readback_bytes": {"max_abs": 4096.0},
    # sketch tier (sentinel_tpu/sketch): full salsa path — CMS writes on
    # both tick sides, tail-rule threshold reads, and the hot-candidate
    # top-K — vs the same config with the sketch off.  r14 collapsed this
    # (~235% → <25%) by dispatching the digit-plane contractions per
    # backend and reading O(1) running sums, so the ceiling is pinned to
    # the PRE-r14 measurement via ``ref`` (0.5 x 234.03 ≈ 117%): the
    # collapse cannot silently unwind, while the re-pinned baseline
    # metric tracks the new, far smaller (and noisier) value
    "sketch_overhead_pct": {"max_ratio": 0.5, "ref": 234.03},
    # exact-tier window op (scatter add + rotation + per-entry and
    # fleet-wide reads) through the r14 O(1) running-sum path — a read
    # quietly reverting to the masked bucket-axis reduction trips this
    "window_op_dps": {"min_ratio": 0.6},
    # mean salsa overestimate as % of stream volume on a seeded Zipf
    # stream — must stay inside the CMS bound e/width (≈0.27% at 1024)
    "sketch_estimate_err_pct": {"max_abs": 100.0 * math.e / 1024},
    # packed-wire transport (PR 12): steady-state bytes/tick over EVERY
    # wire path.  rx ceiling = the ONE fused readback (header + verdict
    # bitmap + wait sidecar + stats row + timeline top-K at B=1024,
    # ~5.1 KiB) + slack; a second readback creeping into the resolve
    # phase blows through it.  tx ceiling: identical columns are skipped
    # entirely (dirty tracking), so steady-state uploads are ~0 — any
    # full-column re-upload (~4 KiB/column at B=1024 int32) trips it.
    "wire_bytes_per_tick_rx": {"max_abs": 6656.0},
    "wire_bytes_per_tick_tx": {"max_abs": 2048.0},
    # cluster fleet path (PR 13 lease-first admission): steady-state
    # decisions must be absorbed locally by standing leases — the ratio
    # measures ~0.001 when healthy and ~1.0 if every decision turns back
    # into a synchronous RPC; p50 is a local debit (µs), so the 30 ms
    # ceiling catches the fast path collapsing to the transport
    "cluster_rpcs_per_decision": {"max_abs": 0.05},
    "cluster_call_p50_ms": {"max_abs": 30.0},
    # continuous profiling plane (PR 15): the ARMED memory ledger +
    # rotating sketch-accuracy audit vs the identical ambient client —
    # the plane must stay always-on-cheap, so the ceiling is absolute
    "profile_overhead_pct": {"max_abs": 2.0},
    # closed-loop autotuner (PR 19): the tuned run's whole-run SLO-bad
    # fraction over the static default's on the seeded flash-crowd shape
    # — virtual-time arithmetic, so the ratio is DETERMINISTIC and the
    # ceiling is tight: a tuner that stops converging (ratio → 1.0)
    # fails CI.  Surprise retraces during tuning are an exact invariant.
    "workload_smoke_bad_frac_ratio": {"max_abs": 0.75},
    "workload_smoke_surprise_retraces": {"max_abs": 0.0},
    # wall-clock drive at the converged point — noisy, loose floor only
    "workload_smoke_dps": {"min_ratio": 0.3},
    # verdict provenance plane (PR 20): the device explain section
    # (explain_k record gathers + checksum packed into the fused wire
    # buffer) vs the identical packed tick with the section off, on
    # all-blocked traffic — the acceptance bound is absolute: the
    # always-on explain records must stay under 2%
    "explain_overhead_pct": {"max_abs": 2.0},
}


def _wire_totals() -> dict:
    """Sum of sentinel_wire_bytes_total across every path label, per
    direction — the choke-point accounting the client/wire layer feeds."""
    from sentinel_tpu import obs

    tot = {"tx": 0.0, "rx": 0.0}
    for path_l in ("device", "cluster", "timeline"):
        for d in ("tx", "rx"):
            m = obs.REGISTRY.get(
                "sentinel_wire_bytes_total", {"path": path_l, "direction": d}
            )
            if m is not None:
                tot[d] += float(m.value)
    return tot


def _best_of(fn, repeats: int = 3) -> float:
    """max over repeats — scheduler noise only ever slows a run down, so
    the best sample is the least-noisy throughput estimate."""
    return max(fn() for _ in range(repeats))


def smoke_bench(B: int = 4096, n_ticks: int = 12) -> dict:
    """The sentry's measurement set (CPU-reproducible, ~tens of seconds):

    - ``engine_tick_dps``: jitted engine-only tick throughput at a small
      plain-path config (the kernel-shape guard);
    - ``telemetry_overhead_pct``: device_telemetry off vs the scalar
      stats row alone — the acceptance bound for the PR 8 row (<= 5%);
    - ``timeline_overhead_pct``: the scalar row alone vs + the K=128
      per-resource timeline matrix — the PR 9 acceptance bound (<= 5%;
      the config widens max_resources to 256 so K is genuinely 128);
    - ``stats_readback_bytes`` / ``timeline_readback_bytes``: added
      readback per tick of each channel;
    - ``client_path_dps`` / ``host_build_ms``: decisions/s through the
      public SentinelClient bulk path (registry + assembly + readback).
    """
    import jax
    import jax.numpy as jnp

    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.core.rules import FlowRule
    from sentinel_tpu.ops import engine as E
    from sentinel_tpu.runtime.client import SentinelClient

    def engine_dps(telemetry: bool, timeline_k: int = 0, sketch: bool = False) -> float:
        cfg = small_engine_config(
            batch_size=B,
            complete_batch_size=B,
            device_telemetry=telemetry,
            timeline_k=timeline_k,
            max_resources=256,
            max_nodes=512,
            sketch_stats=sketch,
            sketch_width=1024,
        )
        tick = E.make_tick(cfg, donate=False, features=E.ALL_FEATURES)

        class _Reg:
            def resource_id(self, n):
                return 1

        rules = E._compile_ruleset(cfg, _Reg(), [], [], [], [], [], None)
        state = E.init_state(cfg)
        rng = np.random.default_rng(0)
        res = rng.integers(1, 64, B).astype(np.int32)
        if sketch:
            # half the traffic rides the sketched tail, so the measured
            # tick pays the real CMS write + hot-candidate top-K work
            tail = cfg.node_rows + rng.integers(0, 4096, B)
            res = np.where(rng.random(B) < 0.5, tail, res).astype(np.int32)
        acq = E.empty_acquire(cfg)._replace(
            res=jnp.asarray(res),
            count=jnp.ones(B, jnp.int32),
            inbound=jnp.ones(B, jnp.int32),
        )
        comp = E.empty_complete(cfg)
        z = jnp.float32(0.0)
        for w in range(2):  # compile + warm
            state, out = tick(state, rules, acq, comp, jnp.int32(w), z, z)
        jax.block_until_ready(out.verdict)

        def once() -> float:
            nonlocal state
            t0 = time.perf_counter()
            for t in range(n_ticks):
                state, out = tick(
                    state, rules, acq, comp, jnp.int32(1000 + 7 * t), z, z
                )
            jax.block_until_ready(out.verdict)
            return n_ticks * B / (time.perf_counter() - t0)

        # the overhead percentages divide two of these runs, so scheduler
        # noise in EITHER direction doubles; extra repeats keep the
        # telemetry/timeline bounds honest rather than flaky
        return _best_of(once, repeats=5)

    dps_off = engine_dps(False)
    dps_on = engine_dps(True)
    dps_tl = engine_dps(True, timeline_k=128)
    dps_sk = engine_dps(True, sketch=True)
    overhead_pct = max((dps_off / max(dps_on, 1.0) - 1.0) * 100.0, 0.0)
    tl_overhead_pct = max((dps_on / max(dps_tl, 1.0) - 1.0) * 100.0, 0.0)
    sk_overhead_pct = max((dps_on / max(dps_sk, 1.0) - 1.0) * 100.0, 0.0)
    sk_err_pct = _sketch_estimate_err_pct()
    # the exact-tier window op through the O(1) running-sum path — the
    # r14 floor (the full before/after row lives in --window-compare)
    from sentinel_tpu.workload.operating_point import BENCH_WINDOW_EXACT

    window_op_dps = _window_op_rate(
        8192, BENCH_WINDOW_EXACT.replace(batch_size=B, complete_batch_size=B),
        60, "run",
    )

    # client path: public bulk API on a sync client (one process, CPU)
    c = SentinelClient(cfg=small_engine_config(batch_size=1024), mode="sync")
    c.start()
    try:
        names = [f"smoke-{i}" for i in range(32)]
        ids = np.asarray([c.registry.resource_id(n) for n in names], np.int32)
        c.flow_rules.load([FlowRule(resource=n, count=1e9) for n in names])
        rng = np.random.default_rng(1)
        res = ids[rng.integers(0, len(ids), 1024)].astype(np.int32)
        fut = c.submit_block(res)  # warm both shapes
        c.tick_once()

        def once() -> float:
            t0 = time.perf_counter()
            for _ in range(8):
                f = c.submit_block(res)
                c.tick_once()
                assert f is None or f.done()
            return 8 * len(res) / (time.perf_counter() - t0)

        client_dps = _best_of(once)

        # steady-state wire bytes/tick (sentinel_wire_bytes_total deltas,
        # all paths): rx is THE single fused readback; tx is the dirty-
        # column residual — repeat traffic uploads nothing.  host_build_ms
        # is averaged over the SAME window: the client's lifetime average
        # folds in the first tick's one-time staging/transfer setup
        # (~100ms), which is not the serving-path cost being sentried.
        w0 = _wire_totals()
        b_sum0, b_n0 = c._build_ms_sum, c._build_ticks
        n_wt = 8
        for _ in range(n_wt):
            c.submit_block(res)
            c.tick_once()
        w1 = _wire_totals()
        wire_rx = (w1["rx"] - w0["rx"]) / n_wt
        wire_tx = (w1["tx"] - w0["tx"]) / n_wt
        host_build_ms = (c._build_ms_sum - b_sum0) / max(
            c._build_ticks - b_n0, 1
        )
    finally:
        c.stop()

    return {
        "metrics": {
            "engine_tick_dps": round(dps_on),
            "engine_tick_dps_telemetry_off": round(dps_off),
            "engine_tick_dps_timeline_k128": round(dps_tl),
            "telemetry_overhead_pct": round(overhead_pct, 2),
            "timeline_overhead_pct": round(tl_overhead_pct, 2),
            "stats_readback_bytes": E.N_STATS * 4,
            "timeline_readback_bytes": 128 * E.TL_COLS * 4,
            "client_path_dps": round(client_dps),
            "host_build_ms": round(host_build_ms, 3),
            "sketch_overhead_pct": round(sk_overhead_pct, 2),
            "sketch_estimate_err_pct": sk_err_pct,
            "window_op_dps": round(window_op_dps),
            "wire_bytes_per_tick_rx": round(wire_rx),
            "wire_bytes_per_tick_tx": round(wire_tx),
            "profile_overhead_pct": round(_profile_overhead_pct(), 2),
            "explain_overhead_pct": round(_explain_overhead_pct(), 2),
            **_cluster_smoke_metrics(),
            **_workload_smoke_metrics(),
        },
        "batch": B,
        "platform": jax.devices()[0].platform,
    }


def _sketch_estimate_err_pct(width: int = 1024, volume: int = 4096) -> float:
    """Mean salsa-tier overestimate on a seeded Zipf stream, as % of the
    stream volume — the sentry's accuracy guard (must stay inside the
    CMS bound e/width; see DEFAULT_TOLERANCES)."""
    import jax.numpy as jnp

    from sentinel_tpu.ops import gsketch as GS
    from sentinel_tpu.ops import window as W
    from sentinel_tpu.sketch import salsa as SA

    scfg = GS.SketchConfig(sample_count=2, window_ms=500, depth=2, width=width)
    s = SA.init_sketch(scfg)
    rng = np.random.default_rng(7)
    ids = (rng.zipf(1.2, size=volume).astype(np.int64) - 1) % 50_000 + 1_000_000
    exact: dict = {}
    for lo in range(0, len(ids), 512):
        chunk = ids[lo : lo + 512]
        s = SA.add(
            s,
            jnp.int32(100),
            jnp.asarray(chunk, jnp.int32),
            jnp.ones((len(chunk), 1), jnp.int32),
            (W.EV_PASS,),
            jnp.ones((len(chunk),), bool),
            scfg,
        )
        for i in chunk:
            exact[int(i)] = exact.get(int(i), 0) + 1
    qs = sorted(exact)
    est = np.asarray(
        SA.estimate(s, jnp.int32(100), jnp.asarray(qs, jnp.int32), scfg)
    )[:, W.EV_PASS]
    errs = np.asarray([e - exact[q] for q, e in zip(qs, est)], np.float64)
    return round(float(errs.mean()) / volume * 100.0, 4)


def wire_compare_bench(B: int = 4096, n_blocks: int = 48) -> dict:
    """BENCH_r12 before/after: the identical smoke-scale client workload
    on the CLASSIC transport (packed_wire=False — full int32 column
    uploads every tick, separate verdict/stats/timeline readbacks) vs the
    PACKED transport (the default — narrow dirty-column delta uploads,
    ONE fused readback), with the span tracer's per-stage breakdown for
    each.  Two workloads per transport:

    - ``steady``: the same block (acquire + completion) every tick — the
      smoke sentry's shape, where the dirty-column skip eliminates the
      upload entirely and the wire carries only the fused readback;
    - ``churn``: blocks repeat twice then change (A,A,B,B,C,C,...) — half
      the ticks re-upload their changed columns, the repeats skip.
    """
    from sentinel_tpu import obs
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.core.rules import FlowRule
    from sentinel_tpu.runtime.client import SentinelClient

    rng = np.random.default_rng(3)
    rows = {}
    for label, packed in (("classic", False), ("packed", True)):
        c = SentinelClient(
            cfg=small_engine_config(
                batch_size=B, complete_batch_size=B, packed_wire=packed
            ),
            mode="sync",
        )
        c.start()
        try:
            names = [f"wc-{i}" for i in range(32)]
            ids = np.asarray(
                [c.registry.resource_id(n) for n in names], np.int32
            )
            c.flow_rules.load([FlowRule(resource=n, count=1e9) for n in names])
            traffic = [
                ids[rng.integers(0, len(ids), B)].astype(np.int32)
                for _ in range(3)
            ]
            rts = [
                np.abs(rng.normal(3.0, 1.0, B)).astype(np.float32)
                for _ in range(3)
            ]
            # warm both shapes off the clock
            c.submit_block(traffic[0])
            c.submit_completion_block(traffic[0], rts[0])
            c.tick_once()
            obs.TRACER.reset()
            obs.enable()
            row = {"packed_wire": packed, "batch": B, "blocks": n_blocks}
            for phase, pick in (
                ("steady", lambda t: 0),
                ("churn", lambda t: (t // 2) % 3),
            ):
                w0 = _wire_totals()
                t0 = time.perf_counter()
                for t in range(n_blocks):
                    k = pick(t)
                    f = c.submit_block(traffic[k])
                    c.submit_completion_block(traffic[k], rts[k])
                    c.tick_once()
                    assert f is None or f.done()
                wall = time.perf_counter() - t0
                w1 = _wire_totals()
                row[phase] = {
                    "dps": round(n_blocks * B / wall),
                    "wire_bytes_per_tick_tx": round(
                        (w1["tx"] - w0["tx"]) / n_blocks
                    ),
                    "wire_bytes_per_tick_rx": round(
                        (w1["rx"] - w0["rx"]) / n_blocks
                    ),
                }
            obs.disable()
            row["host_build_ms_avg"] = round(c.host_build_ms_avg, 3)
            row["stage_breakdown_ms"] = obs.summarize(
                obs.TRACER.snapshot(), prefix="tick."
            )
            rows[label] = row
        finally:
            c.stop()

    def _wire(r, phase):
        return (
            r[phase]["wire_bytes_per_tick_tx"]
            + r[phase]["wire_bytes_per_tick_rx"]
        )

    cl, pk = rows["classic"], rows["packed"]
    for phase in ("steady", "churn"):
        rows[f"wire_bytes_ratio_classic_over_packed_{phase}"] = round(
            _wire(cl, phase) / max(_wire(pk, phase), 1), 2
        )
        rows[f"dps_ratio_packed_over_classic_{phase}"] = round(
            pk[phase]["dps"] / max(cl[phase]["dps"], 1), 3
        )
    return rows


# -- workload engine + closed-loop autotuner (--workload + BENCH_r19) --------


def workload_bench(steps: int = 300, seed: int = 7, small: bool = False) -> dict:
    """BENCH_r19: the closed-loop autotuner against the static seed
    default on the seeded flash-crowd-at-2× shape (workload/).

    Three runs of the SAME offered stream through a real sync client on
    virtual time: (1) static at the seed-default operating point, (2)
    tuned — the autotuner walks its candidate grid live against the
    ``workload_latency`` SLO-burn objective, guarded by the PR-15
    instruments, (3) a wall-clock drive at the converged point for dps.
    The burn comparison is virtual-time arithmetic — deterministic and
    CPU-reproducible; only the dps row is wall-clock."""
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.obs import profile as PROF
    from sentinel_tpu.runtime.client import SentinelClient
    from sentinel_tpu.utils.time_source import VirtualTimeSource
    import sentinel_tpu.workload as WL

    def mk(op):
        c = SentinelClient(
            cfg=op.apply_to_config(small_engine_config()),
            time_source=VirtualTimeSource(start_ms=1_000),
            mode="sync",
            pipeline_depth=op.pipeline_depth,
        )
        c.start()
        return c

    spec = WL.flash_crowd_2x(seed=seed, steps=steps)
    op0 = WL.sim_default_op()
    cands = [
        op0.replace(batch_size=16, complete_batch_size=16),
        op0.replace(batch_size=8, complete_batch_size=8),
    ]
    if not small:
        cands += [
            op0.replace(batch_size=16, complete_batch_size=16, pipeline_depth=2),
            op0.replace(audit_period=8),
            op0.replace(pipeline_depth=2),
        ]

    c = mk(op0)
    static = WL.run_closed_loop(c, spec, op0, tune=False)
    c.stop()
    surprises0 = PROF.RETRACE.surprise_count()
    c = mk(op0)
    tuned = WL.run_closed_loop(c, spec, op0, cands, tune=True)
    c.stop()
    surprises = PROF.RETRACE.surprise_count() - surprises0

    # wall-clock decisions/s through the driven client path AT the
    # converged point (fresh client so compile cost stays off the clock
    # for neither side — both pay first-tick compiles in the drive)
    conv = tuned.converged_op
    c = mk(conv)
    gen = WL.TrafficGenerator(spec, start_ms=c.time.now_ms())
    t0 = time.perf_counter()
    drive = WL.drive_client(c, gen)
    wall = time.perf_counter() - t0
    c.stop()

    sb, tb = static.bad_frac(), tuned.bad_frac()
    return {
        "shape": "flash_crowd_2x",
        "seed": seed,
        "steps": steps,
        "static_op": op0.describe(),
        "converged_op": conv.describe(),
        "candidates": len(cands),
        "decisions": tuned.decisions,
        "static_bad_frac": round(sb, 4),
        "tuned_bad_frac": round(tb, 4),
        "bad_frac_ratio_tuned_over_static": round(tb / max(sb, 1e-9), 4),
        "static_p99_ms": round(static.p99_ms(), 2),
        "tuned_p99_ms": round(tuned.p99_ms(), 2),
        "final_burn_static": round(static.objective_burn, 4),
        "final_burn_tuned": round(tuned.objective_burn, 4),
        "surprise_retraces_during_tuning": surprises,
        "converged_dps": round(drive.submitted / max(wall, 1e-9)),
        "platform": _platform_name(),
    }


def _platform_name() -> str:
    import jax

    return jax.devices()[0].platform


def _workload_smoke_metrics(steps: int = 160, seed: int = 7) -> dict:
    """Autotuner convergence sentry: the seeded flash-crowd loop must
    keep converging to a lower-SLO-burn point than the static default
    (the bad-frac ratio is virtual-time arithmetic — deterministic), and
    the driven client path at the converged point must hold wall-clock
    throughput."""
    row = workload_bench(steps=steps, seed=seed, small=True)
    return {
        "workload_smoke_bad_frac_ratio": row["bad_frac_ratio_tuned_over_static"],
        "workload_smoke_surprise_retraces": row["surprise_retraces_during_tuning"],
        "workload_smoke_dps": row["converged_dps"],
    }


def _explain_dps_pair(B: int = 4096, n_ticks: int = 12) -> tuple:
    """Packed-wire engine tick dps with the device explain section OFF
    vs ON (cfg.explain_k), on traffic where the flow window keeps most
    of the batch genuinely BLOCKED — empty-section ticks would measure
    nothing.  Returns ``(dps_off, dps_on)``."""
    import jax
    import jax.numpy as jnp

    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.core.rules import FlowRule
    from sentinel_tpu.ops import engine as E

    class _Reg:
        def resource_id(self, n):
            return 1

    def dps(explain_k: int) -> float:
        cfg = small_engine_config(
            batch_size=B,
            complete_batch_size=B,
            device_telemetry=True,
            packed_wire=True,
            explain_k=explain_k,
        )
        tick = E.make_tick(cfg, donate=False, features=E.ALL_FEATURES)
        # one tight QPS rule on the single traffic resource: the window
        # fills during warmup and every later decision blocks, so the
        # explain_k gathers run against real blocked rows every tick
        rules = E._compile_ruleset(
            cfg, _Reg(), [FlowRule(resource="bench/expl", count=64.0)],
            [], [], [], [], None,
        )
        state = E.init_state(cfg)
        acq = E.empty_acquire(cfg)._replace(
            res=jnp.full((B,), 1, jnp.int32),
            count=jnp.ones(B, jnp.int32),
            inbound=jnp.ones(B, jnp.int32),
        )
        comp = E.empty_complete(cfg)
        z = jnp.float32(0.0)
        for w in range(2):  # compile + warm (fills the flow window)
            state, out = tick(state, rules, acq, comp, jnp.int32(w), z, z)
        jax.block_until_ready(out.wire)

        def once() -> float:
            nonlocal state
            t0 = time.perf_counter()
            for t in range(n_ticks):
                state, out = tick(
                    state, rules, acq, comp, jnp.int32(1000 + 7 * t), z, z
                )
            jax.block_until_ready(out.wire)
            return n_ticks * B / (time.perf_counter() - t0)

        return _best_of(once, repeats=5)

    return dps(0), dps(32)


def _explain_overhead_pct(B: int = 4096, n_ticks: int = 12) -> float:
    """BENCH_r20 sentry metric: % tick-throughput cost of packing the
    device provenance records (clamped at 0 — noise can make ON faster)."""
    dps_off, dps_on = _explain_dps_pair(B, n_ticks)
    return max((dps_off / max(dps_on, 1.0) - 1.0) * 100.0, 0.0)


def _explain_coverage_row(ticks: int = 24, B: int = 128) -> dict:
    """End-to-end explainability under a flash crowd: a sync client on
    virtual time drives 2x-limit traffic and the host plane must explain
    (nearly) every blocked decision.  ``explain_k`` is sized to the
    batch — the operator knob for block-heavy workloads; the default 32
    covers ordinary block rates."""
    import dataclasses

    from sentinel_tpu.core import errors as ERR
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.core.rules import FlowRule
    from sentinel_tpu.runtime.client import SentinelClient
    from sentinel_tpu.utils.time_source import VirtualTimeSource

    cfg = dataclasses.replace(small_engine_config(), explain_k=B)
    c = SentinelClient(
        cfg=cfg, mode="sync", time_source=VirtualTimeSource(start_ms=1_000)
    )
    c.start()
    try:
        names = [f"crowd-{i}" for i in range(8)]
        # 2x flash crowd: each tick offers twice what the windows admit
        c.flow_rules.load(
            [FlowRule(resource=n, count=B // (2 * len(names))) for n in names]
        )
        blocked = 0
        for t in range(ticks):
            got = c.check_batch([names[i % len(names)] for i in range(B)])
            blocked += sum(
                1 for v, _ in got if v not in (ERR.PASS, ERR.PASS_WAIT)
            )
            c.time.advance(40)
        cov = c.explain_coverage()
    finally:
        c.stop()
    return {
        "ticks": ticks,
        "batch": B,
        "blocked_decisions": blocked,
        "explained": cov["explained"],
        "explained_frac": round(cov["frac"], 4),
    }


def explain_bench() -> dict:
    """BENCH_r20: the verdict provenance plane — packed-tick throughput
    with the device explain section off vs on (the <2% acceptance row),
    the section's added wire bytes, and end-to-end flash-crowd
    explainability through the host plane."""
    from sentinel_tpu.ops import wire as WIRE

    dps_off, dps_on = _explain_dps_pair()
    return {
        "engine_dps_explain_off": round(dps_off),
        "engine_dps_explain_on": round(dps_on),
        "explain_overhead_pct": round(
            max((dps_off / max(dps_on, 1.0) - 1.0) * 100.0, 0.0), 2
        ),
        "explain_wire_bytes_k32": (2 + 32 * WIRE.EXPLAIN_WORDS) * 4,
        "flash_crowd": _explain_coverage_row(),
    }


def compare_to_baseline(measured: dict, baseline: dict) -> list:
    """Tolerance check: measured smoke metrics vs the committed baseline.
    Returns a list of human-readable regression strings (empty = pass).
    Metrics present in only one side are ignored — adding a metric must
    not fail old baselines, and a re-pin picks it up."""
    out = []
    mm = measured.get("metrics", measured)
    bm = baseline.get("metrics", {})
    tols = baseline.get("tolerances", DEFAULT_TOLERANCES)
    for key, tol in tols.items():
        m = mm.get(key)
        b = bm.get(key)
        if m is None:
            continue
        if "max_abs" in tol and m > tol["max_abs"]:
            out.append(
                f"{key}: measured {m} exceeds absolute ceiling {tol['max_abs']}"
            )
        # a tolerance may pin its own reference denominator ("ref") — a
        # historical measurement a one-off collapse was measured against —
        # so a tightened ratio (< 1.0) can coexist with a re-pinned
        # baseline value tracking the new level
        b = tol.get("ref", b)
        if b in (None, 0):
            continue
        ratio = m / b
        if "min_ratio" in tol and ratio < tol["min_ratio"]:
            out.append(
                f"{key}: measured {m} is {ratio:.2f}x baseline {b} "
                f"(floor {tol['min_ratio']}x) — perf regression"
            )
        if "max_ratio" in tol and ratio > tol["max_ratio"]:
            out.append(
                f"{key}: measured {m} is {ratio:.2f}x baseline {b} "
                f"(ceiling {tol['max_ratio']}x) — perf regression"
            )
    return out


def load_perf_baseline(path: str = PERF_BASELINE_PATH) -> dict:
    with open(path) as f:
        return json.load(f)


def _smoke_main(update_baseline: bool) -> int:
    measured = smoke_bench()
    if update_baseline:
        doc = {
            "metrics": measured["metrics"],
            "tolerances": DEFAULT_TOLERANCES,
            "platform": measured["platform"],
        }
        with open(PERF_BASELINE_PATH, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        print(json.dumps({"perf_smoke": measured, "baseline_written": True}))
        return 0
    regressions = []
    have_baseline = os.path.exists(PERF_BASELINE_PATH)
    if have_baseline:
        regressions = compare_to_baseline(measured, load_perf_baseline())
    print(
        json.dumps(
            {
                "perf_smoke": measured,
                "baseline": have_baseline,
                "regressions": regressions,
            }
        )
    )
    return 1 if regressions else 0


def main() -> None:
    from sentinel_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    platform = jax.default_backend()
    if platform != "tpu":
        # a measurement path that finds no chip fails; the CPU-reproducible
        # rows are the named modes (--smoke, --wire-compare, ...)
        sys.exit(
            f"bench.py: the full benchmark needs the tpu backend, found "
            f"{platform!r}; nothing measured"
        )
    B = 1 << 17

    from sentinel_tpu.ops import engine as E_mod

    cfg, E, ruleset, acqs, comps, seg_info = build(B, True)
    n_batches = len(acqs)
    tick = E.make_tick(cfg, donate=True, features=E.ALL_FEATURES)
    state = E.init_state(cfg)
    load = jnp.float32(0.0)
    cpu = jnp.float32(0.0)

    # warm up over EVERY distinct batch and verify none of them overflows
    # the compacted capacity (seg_dropped is per-tick; checking one batch
    # would let another's overflow degrade the measured run silently)
    for w in range(n_batches):
        state, out = tick(state, ruleset, acqs[w % n_batches], comps[w % n_batches],
                          jnp.int32(w), load, cpu)
        dropped = int(out.seg_dropped)
        assert dropped == 0, f"seg overflow dropped {dropped} items (batch {w})"
    _ = float(out.verdict[0])

    # --- throughput: long pipelined run, one readback ----------------------
    n_ticks = 150
    t0 = time.perf_counter()
    for t in range(n_ticks):
        state, out = tick(state, ruleset, acqs[t % n_batches], comps[t % n_batches],
                          jnp.int32(1000 + t * 7), load, cpu)
    _ = float(out.verdict[0])
    dt = time.perf_counter() - t0
    decisions_per_sec = n_ticks * B / dt
    pipelined_tick_ms = dt / n_ticks * 1000.0

    # ruled-tail enforcement really fires in the measured config: after a
    # window's worth of traffic, tail ids with ~>20 QPS block (code
    # BLOCK_FLOW on a sketch-tail id can ONLY come from _check_tail_flow)
    from sentinel_tpu.core.errors import BLOCK_FLOW

    verd = np.asarray(out.verdict)
    res_last = np.asarray(acqs[(n_ticks - 1) % n_batches].res)
    tail_blocked = int(((verd == BLOCK_FLOW) & (res_last >= cfg.node_rows)).sum())
    # the 'active tail rules' headline must describe ENFORCED rules: if
    # compile_tail_flow_rules or the ruleset._replace silently stopped
    # taking effect, fail the benchmark rather than print a dead label
    assert tail_blocked > 0, (
        "tail rules present but no tail id blocked in the sampled tick"
    )

    # --- device tick time (slope; per-call overhead cancels) ---------------
    dev_ms = device_tick_ms(cfg, E_mod, ruleset, acqs, comps)
    device_decisions_per_sec = B / dev_ms * 1000.0

    # --- request-level latency vs tick size --------------------------------
    # model: a request arriving uniformly within a tick interval waits on
    # average interval/2 for its tick, then the device tick time; p99 adds
    # a full interval.  Device tick time per B from the slope harness.
    lat_table = []
    # 10240/12288 probe the joint (throughput, p99<2ms) frontier
    # between the 8K and 16K points — the tick-size knob is the real
    # deployment tradeoff this table exists to expose
    for Bl in (4096, 8192, 10240, 12288, 16384, 65536):
        cfg_l, E_l, ruleset_l, acqs_l, comps_l, _info_l = build(Bl, True)
        # small ticks need a long slope window: per-call variance must be
        # small against (k2-k1) x tick_ms.  576 scan steps at a ~0.8 ms
        # tick ≈ 0.46 s per sample — the joint p99<2ms point rides on
        # sub-0.1ms precision here, so spend the extra wall clock (two
        # tick sizes gate the contract)
        k2 = 576 if Bl <= 16384 else 40
        d = device_tick_ms(cfg_l, E_l, ruleset_l, acqs_l, comps_l, k1=8, k2=k2)
        if d < 0.1:  # implausible slope: one full retry
            d = device_tick_ms(
                cfg_l, E_l, ruleset_l, acqs_l, comps_l, k1=8, k2=k2
            )
        interval = max(d, 1.0)  # ticking back-to-back at device rate
        lat_table.append(
            {
                "batch": Bl,
                "device_tick_ms": round(d, 3),
                "req_p50_ms": round(d + interval / 2, 3),
                "req_p99_ms": round(d + interval, 3),
                "throughput_Mdps": round(Bl / d / 1000.0, 2),
            }
        )
    # --- end-to-end product path (SentinelClient) --------------------------
    client_path = client_bench(B)
    client_path["vs_engine_only"] = round(
        client_path["dps"] / device_decisions_per_sec, 3
    )

    best_p99 = min(r["req_p99_ms"] for r in lat_table)
    # the BASELINE contract is BOTH at once: the best throughput among tick
    # sizes whose modeled p99 stays under 2 ms (VERDICT r2 weak #2)
    joint = max(
        (r for r in lat_table if r["req_p99_ms"] < 2.0),
        key=lambda r: r["throughput_Mdps"],
        default=None,
    )

    print(
        json.dumps(
            {
                "metric": "rule_check_decisions_per_sec@1M_resources",
                "value": round(device_decisions_per_sec),
                "unit": "decisions/s",
                "vs_baseline": round(device_decisions_per_sec / 50e6, 4),
                "features": "ALL",
                "ruled_resources": N_RULED,
                "tail_ruled_resources": N_TAIL_RULED,
                "tail_blocked_sample": tail_blocked,
                "flow_rules": N_RULED,
                "degrade_rules": N_RULED,
                "param_rules": 128,
                "minute_window": True,
                "segments": seg_info,
                "batch": B,
                "device_tick_ms": round(dev_ms, 3),
                "pipelined_tick_ms": round(pipelined_tick_ms, 3),
                "pipelined_dps": round(decisions_per_sec),
                "req_latency_vs_tick_size": lat_table,
                "req_p99_ms_best": best_p99,
                "joint_point_p99_under_2ms": joint,
                "client_path": client_path,
                "cluster_sharded": cluster_sharded_bench(),
                "adaptive_overload": adaptive_overload_bench(),
                "platform": platform,
                "device_kind": jax.devices()[0].device_kind,
                "device_count": len(jax.devices()),
            }
        )
    )


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        # the perf-regression sentry: fast CPU-reproducible measurements
        # compared against PERF_BASELINE.json (exit 1 on regression);
        # --update-baseline re-pins after an intentional perf change
        sys.exit(_smoke_main("--update-baseline" in sys.argv))
    if "--multihost" in sys.argv:
        # the fleet scaling curve under protocol v2 lease-first admission
        # (host path only — CPU-reproducible); writes MULTIHOST_r13.json
        doc = multihost_fleet_bench()
        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "MULTIHOST_r13.json"
        )
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(json.dumps({"multihost": doc, "written": path}))
    elif "--window-compare" in sys.argv:
        # the exact-tier window-op before/after row (CPU-reproducible —
        # how BENCH_r14 captured the running-sum collapse); merged into
        # BENCH_r14.json alongside the sketch-tier and smoke rows
        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_r14.json"
        )
        doc = {}
        if os.path.exists(path):
            with open(path) as f:
                doc = json.load(f)
        doc["window_compare"] = window_compare_bench()
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(
            json.dumps(
                {"window_compare": doc["window_compare"], "written": path}
            )
        )
    elif "--wire-compare" in sys.argv:
        # the packed-wire before/after row alone (CPU-reproducible —
        # how BENCH_r12 captured the transport collapse)
        print(json.dumps({"wire_compare": wire_compare_bench()}))
    elif "--profile-plane" in sys.argv:
        # the PR 15 continuous-profiling-plane rows (CPU-reproducible):
        # the 1 M sketch-tier point with its HBM ledger breakdown, the
        # online audit posture vs BENCH_r14's offline shadow, and the
        # ambient overhead of the armed plane; writes BENCH_r15.json
        doc = {
            "sketch_tier": sketch_tier_bench(),
            "online_audit": online_audit_bench(),
            "profile_overhead_pct": round(_profile_overhead_pct(), 2),
        }
        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_r15.json"
        )
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(json.dumps({"profile_plane": doc, "written": path}))
    elif "--sketch-tier" in sys.argv:
        # the 1 M-ruled-resource sketch-tier row alone (plain path —
        # CPU-reproducible; how BENCH_r10 captured it)
        print(json.dumps({"sketch_tier": sketch_tier_bench()}))
    elif "--cluster-sharded" in sys.argv:
        # the fleet row alone (host path only — no device build): fast
        # enough to run on CPU, which is how BENCH_r06 captured it
        print(json.dumps({"cluster_sharded": cluster_sharded_bench()}))
    elif "--adaptive-overload" in sys.argv:
        # the adaptive row alone (engine-time pure — CPU-reproducible;
        # how BENCH_r07 captured it)
        print(json.dumps({"adaptive_overload": adaptive_overload_bench()}))
    elif "--explain-plane" in sys.argv:
        # the verdict-provenance-plane row (PR 20): packed-tick dps with
        # the device explain section off vs on (<2% acceptance), the
        # section's wire bytes, flash-crowd end-to-end explainability
        # (CPU-reproducible); writes BENCH_r20.json
        doc = {"explain": explain_bench()}
        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_r20.json"
        )
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(json.dumps({"explain": doc["explain"], "written": path}))
    elif "--workload" in sys.argv:
        # the closed-loop autotuner row (PR 19): converged-vs-static SLO
        # burn on the seeded flash-crowd shape + dps at the converged
        # point (burn math is virtual-time pure — CPU-reproducible);
        # writes BENCH_r19.json
        doc = {"workload": workload_bench()}
        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_r19.json"
        )
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(json.dumps({"workload": doc["workload"], "written": path}))
    else:
        main()
