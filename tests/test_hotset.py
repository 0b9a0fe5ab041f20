"""sentinel_tpu.sketch.hotset — promotion loop, demotion, hysteresis, and
the runtime.hotset.promote failure contract (stats fail OPEN, tail-rule
verdicts fail CLOSED)."""

import numpy as np
import pytest

import jax.numpy as jnp

import sentinel_tpu as st
from sentinel_tpu.chaos import failpoints as FP
from sentinel_tpu.chaos.plans import FaultPlan, FaultSpec
from sentinel_tpu.core.config import small_engine_config
from sentinel_tpu.ops import engine as E
from sentinel_tpu.runtime.registry import Registry
from sentinel_tpu.sketch.hotset import (
    _C_PROMOTE_FAIL,
    _C_PROMOTIONS,
    guarded_promote,
)


def _hot_cfg(**kw):
    base = dict(
        max_resources=8,
        max_nodes=16,
        sketch_stats=True,
        sketch_width=256,
        hotset_k=8,
        hotset_promote_qps=3.0,
        hotset_demote_qps=1.0,
        hotset_cooldown_s=30.0,
    )
    base.update(kw)
    return small_engine_config(**base)


def _burn_exact(c):
    i = 0
    while not c.registry.is_sketch_id(c.registry.resource_id(f"burn-{i}")):
        i += 1


# -- device candidate emission ----------------------------------------------


def test_tick_emits_hot_candidates(client_factory, vt):
    c = client_factory(cfg=_hot_cfg())
    _burn_exact(c)
    rid = c.registry.resource_id("hot-svc")
    assert c.registry.is_sketch_id(rid)
    for _ in range(6):
        e = c.try_entry("hot-svc")
        if e is not None:
            e.exit()
        vt.advance(5)
    assert c.hotset is not None
    cand = dict(c.hotset._cand)
    assert cand.get(rid, 0.0) >= 3.0  # folded windowed pass estimate


def test_hot_output_off_when_disabled():
    cfg = _hot_cfg(hotset_k=0)
    assert E.hotset_k(cfg) == 0
    state = E.init_state(cfg)
    rules = E.compile_ruleset(cfg, Registry(cfg))
    z = jnp.float32(0.0)
    _, out = E.tick(
        state, rules, E.empty_acquire(cfg), E.empty_complete(cfg),
        jnp.int32(1_000), z, z, cfg=cfg,
    )
    assert out.hot is None


def test_fold_normalizes_windowed_counts_to_qps(client_factory, vt):
    """TickOutput.hot carries WINDOWED pass sums; the manager must fold
    them as QPS so a minute-window sketch (interval 60 s) is not 60x too
    eager against hotset_promote_qps (same unit as the demote side)."""
    cfg = _hot_cfg(sketch_sample_count=60, sketch_window_ms=1000)
    c = client_factory(cfg=cfg)
    rid = cfg.node_rows + 7
    c.hotset.fold(np.asarray([[float(rid), 120.0]], np.float32))
    assert abs(c.hotset._cand[rid] - 2.0) < 1e-6  # 120 events / 60 s


# -- promotion / demotion loop ----------------------------------------------


def test_manager_promotes_hot_tail_resource(client_factory, vt):
    c = client_factory(cfg=_hot_cfg())
    _burn_exact(c)
    rid = c.registry.resource_id("hot-svc")
    assert c.registry.is_sketch_id(rid)
    for _ in range(8):
        e = c.try_entry("hot-svc")
        if e is not None:
            e.exit()
        vt.advance(5)
    c.hotset.evaluate_now()
    new_rid = c.registry.peek_resource_id("hot-svc")
    assert not c.registry.is_sketch_id(new_rid)
    assert c.hotset.promoted["hot-svc"] == new_rid
    # exact tier serves it now: stats come from real windows
    e = c.try_entry("hot-svc")
    assert e is not None
    e.exit()


def test_cold_promoted_row_demotes_with_hysteresis(client_factory, vt):
    c = client_factory(cfg=_hot_cfg())
    _burn_exact(c)
    c.registry.resource_id("fades")
    for _ in range(8):
        e = c.try_entry("fades")
        if e is not None:
            e.exit()
        vt.advance(5)
    c.hotset.evaluate_now()
    assert not c.registry.is_sketch_id(c.registry.peek_resource_id("fades"))
    # traffic stops; the window slides past -> two cold evaluations demote
    vt.advance(2_000)
    c.tick_once()  # the cadence is due on the client's clock: one cold pass
    assert "fades" in c.hotset.promoted  # one cold eval holds
    c.hotset.evaluate_now()
    rid = c.registry.peek_resource_id("fades")
    assert c.registry.is_sketch_id(rid)  # demoted back to the tail
    assert "fades" not in c.hotset.promoted
    # hysteresis: re-promotion is refused while the cooldown runs
    hys = c.hotset._cool["fades"]
    assert hys.cooling
    c.hotset._cand[rid] = 100.0
    c.hotset.evaluate_now()
    assert c.registry.is_sketch_id(c.registry.peek_resource_id("fades"))


def test_demoted_row_quarantines_then_recycles():
    cfg = _hot_cfg()
    reg = Registry(cfg)
    i = 0
    while not reg.is_sketch_id(reg.resource_id(f"b{i}")):
        i += 1
    assert reg.promote_resource(f"b{i}") is not None
    row = reg.peek_resource_id(f"b{i}")
    # demote with zero quarantine: the row must be reusable immediately
    new_id = reg.demote_resource(f"b{i}", quarantine_s=0.0)
    assert reg.is_sketch_id(new_id)
    assert reg.resource_name(new_id) == f"b{i}"
    reg.resource_id("next-hot")
    got = reg.promote_resource("next-hot")
    assert got == row  # recycled, not burned from the reserve
    # long quarantine keeps the row OUT of rotation
    reg.demote_resource("next-hot", quarantine_s=3600.0)
    reg.resource_id("later")
    got2 = reg.promote_resource("later")
    assert got2 != row


# -- failure contract --------------------------------------------------------


def test_promote_failures_fail_open_for_stats_closed_for_verdicts(
    client_factory, vt
):
    """Injected runtime.hotset.promote failures: the ruled tail resource
    stays sketched (stats keep flowing = OPEN) and its rule enforces via
    the tail tables (blocks still fire = CLOSED)."""
    c = client_factory(cfg=_hot_cfg())
    _burn_exact(c)
    rid = c.registry.resource_id("guarded")
    assert c.registry.is_sketch_id(rid)
    fails0 = _C_PROMOTE_FAIL.value
    plan = FaultPlan(
        name="hotset_promote_fail",
        seed=1,
        faults=[
            FaultSpec(
                "runtime.hotset.promote", "raise",
                burst_start=0, burst_len=1000, exc="RuntimeError",
            )
        ],
    )
    st_armed = FP.arm(plan)
    try:
        c.flow_rules.load([st.FlowRule(resource="guarded", count=2)])
    finally:
        FP.disarm()
    assert st_armed.injected().get("runtime.hotset.promote:raise", 0) >= 1
    assert _C_PROMOTE_FAIL.value > fails0
    # CLOSED for verdicts: the un-promoted rule still blocks from the tail
    assert c.registry.is_sketch_id(c.registry.peek_resource_id("guarded"))
    got = sum(1 for _ in range(8) if c.try_entry("guarded"))
    assert 1 <= got <= 2
    # OPEN for stats: the sketch keeps observing the resource
    snap = c.stats.resource("guarded")
    assert snap["passQps"] >= 1


def test_guarded_promote_counts_transitions():
    cfg = _hot_cfg()
    reg = Registry(cfg)
    i = 0
    while not reg.is_sketch_id(reg.resource_id(f"b{i}")):
        i += 1
    p0 = _C_PROMOTIONS.value
    assert guarded_promote(reg, f"b{i}") is not None
    assert _C_PROMOTIONS.value == p0 + 1
    # idempotent: promoting an already-exact resource is not a transition
    assert guarded_promote(reg, f"b{i}") is not None
    assert _C_PROMOTIONS.value == p0 + 1
