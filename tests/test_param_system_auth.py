"""ParamFlowSlot / SystemSlot / AuthoritySlot integration tests.

Counterparts of the reference's ParamFlowCheckerTest,
SystemGuardIntegrationTest and AuthoritySlotTest (SURVEY.md §4.3),
exercised through the public API with virtual time.
"""


import sentinel_tpu as st
from sentinel_tpu.core.rules import ParamFlowItem


# ---------------- param flow ----------------


def test_param_flow_per_value_budget(client, vt):
    client.param_flow_rules.load(
        [st.ParamFlowRule(resource="api", count=2, duration_in_sec=1)]
    )
    # value "a": budget 2/s
    got_a = sum(1 for _ in range(5) if client.try_entry("api", args=["a"]))
    # value "b" has its own bucket
    got_b = sum(1 for _ in range(5) if client.try_entry("api", args=["b"]))
    assert got_a == 2
    assert got_b == 2
    # no param → param rule does not apply
    assert client.try_entry("api") is not None

    vt.advance(1100)
    assert client.try_entry("api", args=["a"]) is not None


def test_param_flow_item_exception(client, vt):
    client.param_flow_rules.load(
        [
            st.ParamFlowRule(
                resource="api2",
                count=1,
                duration_in_sec=1,
                param_flow_item_list=[ParamFlowItem(object="vip", count=5)],
            )
        ]
    )
    got_vip = sum(1 for _ in range(8) if client.try_entry("api2", args=["vip"]))
    got_x = sum(1 for _ in range(8) if client.try_entry("api2", args=["x"]))
    assert got_vip == 5
    assert got_x == 1


def test_param_flow_burst(client, vt):
    client.param_flow_rules.load(
        [st.ParamFlowRule(resource="api3", count=2, duration_in_sec=1, burst_count=3)]
    )
    got = sum(1 for _ in range(10) if client.try_entry("api3", args=[7]))
    assert got == 5  # count*duration + burst


def test_param_flow_thread_grade(client, vt):
    """GRADE_THREAD param rules bound per-VALUE concurrency and release on
    exit (ParamFlowChecker.passLocalCheck THREAD branch,
    ParamFlowSlot.exit decreaseThreadCount)."""
    client.param_flow_rules.load(
        [st.ParamFlowRule(resource="papi", count=2, grade=st.GRADE_THREAD)]
    )
    e1 = client.try_entry("papi", args=["k"])
    e2 = client.try_entry("papi", args=["k"])
    assert e1 and e2
    # third concurrent holder of value "k" is rejected...
    assert client.try_entry("papi", args=["k"]) is None
    # ...but another value has its own concurrency budget
    e3 = client.try_entry("papi", args=["other"])
    assert e3
    # releasing one "k" holder frees a slot
    e1.exit()
    e4 = client.try_entry("papi", args=["k"])
    assert e4
    for e in (e2, e3, e4):
        e.exit()


def test_param_flow_multi_index(client, vt):
    """Two rules with different paramIdx on one resource enforce their own
    argument lanes (ParamFlowChecker.java:78 paramIdx dispatch)."""
    client.param_flow_rules.load(
        [
            st.ParamFlowRule(resource="mapi", count=50, param_idx=0),
            st.ParamFlowRule(resource="mapi", count=2, param_idx=1),
        ]
    )
    # distinct idx-0 values keep rule 0 out of the way; idx-1 value "y" is
    # capped at 2 by the second rule
    got = sum(
        1 for i in range(6) if client.try_entry("mapi", args=[f"x{i}", "y"])
    )
    assert got == 2
    # a fresh idx-1 value has its own budget even under one idx-0 value
    got2 = sum(
        1 for i in range(6) if client.try_entry("mapi", args=["x0", f"z{0}"])
    )
    assert got2 == 2


def test_param_flow_four_distinct_indices(client_factory, vt):
    """Four rules with four DISTINCT paramIdx on one resource all enforce
    (ParamFlowChecker.java:78 dispatches on arbitrary paramIdx; the ring
    transport carries four release lanes — sx_event.aux0..aux3).  The
    r2/r3 "unenforced rule" warning path must be unreachable here."""
    from sentinel_tpu.core.config import small_engine_config

    client = client_factory(
        cfg=small_engine_config(param_dims=4, param_rules_per_resource=4)
    )
    client.param_flow_rules.load(
        [
            st.ParamFlowRule(resource="papi4", count=50, param_idx=0),
            st.ParamFlowRule(resource="papi4", count=2, param_idx=1),
            st.ParamFlowRule(resource="papi4", count=3, param_idx=2),
            st.ParamFlowRule(
                resource="papi4", count=2, param_idx=3, grade=st.GRADE_THREAD
            ),
        ]
    )
    # every index got a hash lane (nothing dropped to the warning path)
    assert sorted(
        client.param_lane("papi4", k) for k in range(4)
    ) == [0, 1, 2, 3]

    # idx-1 value "y" capped at 2 while idx 0/2/3 stay distinct
    got = sum(
        1
        for i in range(6)
        if client.try_entry("papi4", args=[f"a{i}", "y", f"c{i}", f"d{i}"])
    )
    assert got == 2
    # idx-2 value "w" capped at 3 under fresh values elsewhere
    got2 = sum(
        1
        for i in range(6)
        if client.try_entry("papi4", args=[f"e{i}", f"f{i}", "w", f"g{i}"])
    )
    assert got2 == 3
    vt.advance(1100)
    # idx-3 THREAD grade: per-value concurrency 2, released on exit
    # through the ring's third release lane
    e1 = client.try_entry("papi4", args=["p", "q", "r", "t"])
    e2 = client.try_entry("papi4", args=["p2", "q2", "r2", "t"])
    assert e1 and e2
    assert client.try_entry("papi4", args=["p3", "q3", "r3", "t"]) is None
    e1.exit()
    e4 = client.try_entry("papi4", args=["p4", "q4", "r4", "t"])
    assert e4
    for e in (e2, e4):
        e.exit()


# ---------------- system rules ----------------


def test_system_qps_gate(client, vt):
    client.system_rules.load([st.SystemRule(qps=5)])
    got = sum(1 for _ in range(10) if client.try_entry("in-svc", inbound=True))
    assert got == 5
    # outbound traffic unaffected (SystemSlot guards inbound only)
    assert client.try_entry("out-svc") is not None
    vt.advance(1100)
    assert client.try_entry("in-svc", inbound=True) is not None


def test_system_thread_gate(client, vt):
    client.system_rules.load([st.SystemRule(max_thread=2)])
    e1 = client.try_entry("s1", inbound=True)
    e2 = client.try_entry("s1", inbound=True)
    assert e1 and e2
    assert client.try_entry("s1", inbound=True) is None
    e1.exit()
    assert client.try_entry("s1", inbound=True) is not None


def test_system_avg_rt_gate(client, vt):
    client.system_rules.load([st.SystemRule(avg_rt=10)])
    # one slow completion drives the global average RT over the threshold
    e = client.entry("slow", inbound=True)
    vt.advance(100)
    e.exit()
    assert client.try_entry("anything", inbound=True) is None
    # the slow sample ages out of the second window → gate reopens
    vt.advance(1100)
    assert client.try_entry("anything", inbound=True) is not None


# ---------------- authority ----------------


def test_authority_white_list(client, vt):
    client.authority_rules.load(
        [st.AuthorityRule(resource="guarded", limit_app="appA,appB", strategy=st.AUTHORITY_WHITE)]
    )
    with client.context("ctx", "appA"):
        assert client.try_entry("guarded") is not None
    with client.context("ctx", "appC"):
        assert client.try_entry("guarded") is None
    # no origin: not on the white list → blocked? The reference requires a
    # matching origin for white-listed resources; empty origin doesn't match
    with client.context("ctx", ""):
        assert client.try_entry("guarded") is None


def test_authority_black_list(client, vt):
    client.authority_rules.load(
        [st.AuthorityRule(resource="g2", limit_app="evil", strategy=st.AUTHORITY_BLACK)]
    )
    with client.context("ctx", "evil"):
        assert client.try_entry("g2") is None
    with client.context("ctx", "good"):
        assert client.try_entry("g2") is not None


# ---------------- origin-scoped flow rules ----------------


def test_flow_rule_limit_app_specific_and_other(client, vt):
    client.flow_rules.load(
        [
            st.FlowRule(resource="mix", count=2, limit_app="appA"),
            st.FlowRule(resource="mix", count=5, limit_app="other"),
        ]
    )
    with client.context("c", "appA"):
        got_a = sum(1 for _ in range(8) if client.try_entry("mix"))
    with client.context("c", "appZ"):
        got_z = sum(1 for _ in range(8) if client.try_entry("mix"))
    assert got_a == 2  # specific rule
    assert got_z == 5  # "other" rule


# ---------------- a wide hot-parameter store ----------------


def test_param_width_past_the_fused_tables_reach_fails_at_construction_in_one_line():
    import pytest

    from sentinel_tpu.core.config import PARAM_MAX_WIDTH, PARAM_NARROW_WIDTH, EngineConfig

    assert EngineConfig().param_width == PARAM_NARROW_WIDTH  # today's store: narrow
    assert EngineConfig(param_width=1 << 20).param_width == 1 << 20
    for width in (PARAM_NARROW_WIDTH + 128, 3 * PARAM_NARROW_WIDTH // 2, 2 * PARAM_MAX_WIDTH):
        with pytest.raises(ValueError, match=f"param_width {width}") as e:
            EngineConfig(param_width=width, use_mxu_tables=True, fused_effects=True)
        assert "\n" not in str(e.value)


def test_param_flow_holds_its_budgets_over_a_wide_store(client_factory, vt):
    """The per-value budget, the exception item and the window, with the
    store 2^15 cells wide: laid out [depth, bucket, cell / 128, 128]."""
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.obs import profile as PROF

    client = client_factory(cfg=small_engine_config(param_width=1 << 15))
    assert client._state.pcms.shape == (2, 8, (1 << 15) // 128, 128)
    client.param_flow_rules.load([
        st.ParamFlowRule(resource="api", count=2, duration_in_sec=1,
                         param_flow_item_list=[ParamFlowItem(object="vip", count=5)])
    ])
    assert sum(1 for _ in range(6) if client.try_entry("api", args=["a"])) == 2
    assert sum(1 for _ in range(8) if client.try_entry("api", args=["vip"])) == 5
    assert client.try_entry("api") is not None
    occupied = client.param_store_occupancy()
    assert occupied == {"store_cells": 1 << 15, "store_cells_counting": [2, 2]}
    vt.advance(1100)
    assert client.try_entry("api", args=["a"]) is not None
    # the store is a pool of its own in the memory ledger: counts and concurrency
    assert PROF.LEDGER.pool_bytes("param_store") >= (2 * 8 + 2) * (1 << 15) * 4


def test_tick_resolve_carries_param_rows_and_param_blocked(client_factory, vt):
    import numpy as np

    from sentinel_tpu import obs
    from sentinel_tpu.core.rule_tensors import hash_param
    from sentinel_tpu.obs.registry import REGISTRY

    client = client_factory()
    client.param_flow_rules.load([st.ParamFlowRule(resource="api", count=3)])
    api, other = client.registry.resource_id("api"), client.registry.resource_id("plain")
    blocked_before = REGISTRY.get("sentinel_param_blocked_total").value
    obs.TRACER.reset()
    obs.enable()
    try:
        ph = np.zeros((10, client.cfg.param_dims), np.int32)
        ph[:8, 0] = hash_param("a")  # 7 under the rule with a value, 1 elsewhere, 2 without
        res = np.array([api] * 7 + [other] + [api] * 2, np.int32)
        fut = client.submit_block(res, param_hash=ph)
        client.tick_once()
        verdicts = fut.result(timeout=5)[0]
    finally:
        obs.disable()
    assert (verdicts == 3).sum() == 4 and (verdicts == 0).sum() == 6
    resolves = [s for s in obs.TRACER.snapshot() if s["name"] == "tick.resolve"]
    assert [(s["attrs"]["param_rows"], s["attrs"]["param_blocked"]) for s in resolves] == [(7, 4)]
    assert REGISTRY.get("sentinel_param_blocked_total").value == blocked_before + 4
