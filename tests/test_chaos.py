"""sentinel_tpu.chaos — failpoints, plans, invariants, scenarios.

Covers the ISSUE-4 contracts: the failpoint catalog (site names unique,
registered, scheme-conformant — mirroring obs's single-site clock
assertion), the disarmed-site overhead guard (<5 µs/site-call, the obs
bound), seeded plan JSON round-trips and schedule determinism, the
fail-closed resolve hardening, the RemoteShard mid-window partition
driven through the new failpoint sites (no monkeypatching), the
front-door unenforceable-rule counter satellite, the labeled cluster
RPC failure kinds satellite, and the tier-1 scenario subset.  The full
scenario matrix and the two-run determinism contract run under
``@pytest.mark.slow``.
"""

from __future__ import annotations

import ast
import os
import re
import time

import pytest

from sentinel_tpu.chaos import failpoints as FP
from sentinel_tpu.chaos.plans import FaultPlan, FaultSpec
from sentinel_tpu.core import errors as ERR

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the token services built here are closed when the module ends (tests/conftest.py)
pytestmark = pytest.mark.usefixtures("closes_token_services")


@pytest.fixture(autouse=True)
def _disarm_guard():
    """No test may leak an armed plan into the rest of the suite."""
    yield
    FP.disarm()


def _import_instrumented_modules():
    """Import every module that registers failpoints (idempotent)."""
    import sentinel_tpu.analysis.concurrency.witness  # noqa: F401
    import sentinel_tpu.chaos.runner  # noqa: F401
    import sentinel_tpu.cluster.client  # noqa: F401
    import sentinel_tpu.cluster.front_door  # noqa: F401
    import sentinel_tpu.cluster.server  # noqa: F401
    import sentinel_tpu.cluster.shard  # noqa: F401
    import sentinel_tpu.datasource.stores  # noqa: F401
    import sentinel_tpu.obs.profile  # noqa: F401
    import sentinel_tpu.obs.timeline  # noqa: F401
    import sentinel_tpu.parallel.remote_shard  # noqa: F401
    import sentinel_tpu.runtime.client  # noqa: F401
    import sentinel_tpu.sketch.hotset  # noqa: F401
    import sentinel_tpu.transport.heartbeat  # noqa: F401
    import sentinel_tpu.transport.http_server  # noqa: F401
    import sentinel_tpu.workload.generator  # noqa: F401
    import sentinel_tpu.workload.tuner  # noqa: F401


# ---------------------------------------------------------------------------
# failpoint catalog
# ---------------------------------------------------------------------------

_SCHEME = re.compile(r"^[a-z][a-z0-9_]*\.[a-z][a-z0-9_]*\.[a-z][a-z0-9_]*$")
_LAYERS = {
    "transport", "cluster", "runtime", "parallel", "datasource", "obs",
    "sketch", "workload",
}


def test_catalog_sites_unique_registered_and_scheme_conformant():
    """Every registered site follows <layer>.<component>.<operation>, the
    layer set is closed, and the source's register() literals match the
    live catalog exactly — a renamed site cannot drift from its docs."""
    _import_instrumented_modules()
    cat = FP.catalog()
    assert len(cat) >= 15, f"expected the documented ~15-20 sites, got {len(cat)}"
    for name, site in cat.items():
        assert _SCHEME.match(name), f"{name!r} violates the naming scheme"
        assert name.split(".")[0] in _LAYERS
        assert site.kinds, f"{name!r} registered without action kinds"

    # source scan: FP.register("<literal>", ...) across the package
    registered_in_source = set()
    pkg = os.path.join(REPO_ROOT, "sentinel_tpu")
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn)) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "register"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "FP"
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                ):
                    registered_in_source.add(node.args[0].value)
    assert registered_in_source == set(cat), (
        "source register() literals and the live catalog diverge: "
        f"{registered_in_source ^ set(cat)}"
    )


def test_register_rejects_bad_names_and_conflicts():
    with pytest.raises(ValueError):
        FP.register("cluster.rpc")  # two segments
    with pytest.raises(ValueError):
        FP.register("kitchen.sink.op")  # unknown layer
    with pytest.raises(ValueError):
        FP.register("cluster.rpc.send", "different", ("drop",))  # conflict
    # identical re-registration is idempotent (module re-import)
    site = FP.catalog()["cluster.rpc.send"]
    assert FP.register("cluster.rpc.send", site.desc, site.kinds) == "cluster.rpc.send"


def test_disarmed_overhead_guard():
    """A disarmed site costs one flag check: 20k hit() probes must stay
    under 5 µs/call — the same bound the obs tracer guards."""
    from sentinel_tpu.utils.time_source import mono_s

    assert not FP._ARMED
    n = 20_000
    t0 = mono_s()
    for _ in range(n):
        FP.hit("cluster.rpc.send")
    elapsed = mono_s() - t0
    assert elapsed / n < 5e-6, f"disarmed-site cost {elapsed / n * 1e9:.0f} ns/call"


# ---------------------------------------------------------------------------
# plans: JSON round-trip, validation, schedules
# ---------------------------------------------------------------------------


def test_plan_json_roundtrip():
    plan = FaultPlan(
        name="demo",
        seed=42,
        faults=[
            FaultSpec("cluster.rpc.send", "raise", burst_start=2, burst_len=3),
            FaultSpec("cluster.rpc.recv", "corrupt", probability=0.25),
            FaultSpec("runtime.tick.clock", "clock_skew", every_nth=4, skew_ms=500),
        ],
    )
    assert FaultPlan.from_json(plan.to_json()) == plan


def test_plan_validation_rejects_unknown_site_action_and_exc():
    with pytest.raises(ValueError):
        FaultPlan(faults=[FaultSpec("cluster.rpc.nope", "raise")]).validate(FP.catalog())
    with pytest.raises(ValueError):
        # hit-style site does not honor byte mangling
        FaultPlan(faults=[FaultSpec("cluster.token.decide", "drop")]).validate(
            FP.catalog()
        )
    with pytest.raises(ValueError):
        FaultPlan(
            faults=[FaultSpec("cluster.token.decide", "raise", exc="KeyboardInterrupt")]
        ).validate(FP.catalog())
    with pytest.raises(ValueError):
        # a lone burst_start would fire every hit, not a window
        FaultPlan(
            faults=[FaultSpec("cluster.token.decide", "raise", burst_start=5)]
        ).validate(FP.catalog())


def test_schedule_gates_and_actions():
    site = "cluster.token.decide"
    plan = FaultPlan(
        seed=9,
        faults=[FaultSpec(site, "raise", every_nth=3, max_fires=2, exc="ValueError")],
    )
    fired = []
    with FP.armed(plan) as st:
        for i in range(12):
            try:
                FP.hit(site)
            except ValueError:
                fired.append(i)
        assert st.hit_counts()[site] == 12
    assert fired == [2, 5]  # every 3rd hit, capped at 2 fires
    assert st.injected() == {f"{site}:raise": 2}
    # the event log records each fire's (site, action, site-hit index) —
    # the replay-confirmation trail a failing chaos run is debugged from
    assert st.events == [(site, "raise", 2), (site, "raise", 5)]


def test_pipe_actions_drop_corrupt_short_read_and_skew():
    data = bytes(range(32))
    with FP.armed(
        FaultPlan(seed=1, faults=[FaultSpec("cluster.rpc.send", "drop", max_fires=1)])
    ):
        assert FP.pipe("cluster.rpc.send", data) == b""
        assert FP.pipe("cluster.rpc.send", data) == data  # max_fires spent
    with FP.armed(
        FaultPlan(seed=1, faults=[FaultSpec("cluster.rpc.send", "corrupt")])
    ):
        mangled = FP.pipe("cluster.rpc.send", data)
        assert len(mangled) == len(data) and mangled != data
    with FP.armed(
        FaultPlan(seed=1, faults=[FaultSpec("cluster.rpc.send", "short_read")])
    ):
        short = FP.pipe("cluster.rpc.send", data)
        assert 1 <= len(short) < len(data)
        assert short == data[: len(short)]
    with FP.armed(
        FaultPlan(
            seed=1,
            faults=[FaultSpec("runtime.tick.clock", "clock_skew", skew_ms=1500)],
        )
    ):
        assert FP.skew_ms("runtime.tick.clock") == 1500
    assert FP.skew_ms("runtime.tick.clock") == 0  # disarmed


def test_probability_schedule_replays_from_seed():
    site = "cluster.token.decide"

    def pattern(seed: int):
        plan = FaultPlan(
            seed=seed, faults=[FaultSpec(site, "raise", probability=0.5)]
        )
        out = []
        with FP.armed(plan):
            for i in range(64):
                try:
                    FP.hit(site)
                    out.append(0)
                except OSError:
                    out.append(1)
        return out

    a, b = pattern(123), pattern(123)
    assert a == b, "same seed must replay the exact decision stream"
    assert 0 < sum(a) < 64  # actually probabilistic, not constant


def test_arm_is_exclusive_and_disarm_idempotent():
    plan = FaultPlan(seed=0, faults=[])
    st = FP.arm(plan)
    with pytest.raises(RuntimeError):
        FP.arm(plan)
    assert FP.disarm() is st
    assert FP.disarm() is None


# ---------------------------------------------------------------------------
# fail-closed resolve hardening (runtime/client._fail_tick)
# ---------------------------------------------------------------------------


def test_resolve_failure_fails_entries_closed_not_stranded(client_factory):
    """An injected fan-out failure must surface as an immediate
    SystemBlockException — never an entry_timeout_s hang."""
    c = client_factory()
    c.registry.resource_id("chaos/ft")
    f = c.submit_acquire("chaos/ft")
    if f is not None:
        f.result(timeout=60.0)  # prime the compile outside the plan
    plan = FaultPlan(
        seed=2,
        faults=[FaultSpec("runtime.resolve.fanout", "raise", max_fires=1)],
    )
    t0 = time.perf_counter()
    with FP.armed(plan):
        with pytest.raises(ERR.SystemBlockException):
            c.entry("chaos/ft")
    assert time.perf_counter() - t0 < c.entry_timeout_s, "fail-closed, not timeout"
    # the engine recovered: the next entry serves normally
    e = c.entry("chaos/ft")
    e.exit()


# ---------------------------------------------------------------------------
# satellite: RemoteShard mid-window partition via failpoint sites
# ---------------------------------------------------------------------------


class _MarkerFallback:
    """Fallback whose verdicts carry wait_ms=7 so remote vs degraded
    decisions are distinguishable in the combined result."""

    def __init__(self):
        self.batches = []

    def check_batch(self, resources, **kw):
        self.batches.append(list(resources))
        return [(ERR.PASS, 7)] * len(resources)


def test_remote_shard_mid_window_partition_no_replay():
    """Socket drop between chunk dispatch and reply, through the REAL
    transport and the new failpoint sites (no monkeypatching): answered
    chunks keep their remote verdicts, written-but-unanswered chunks
    degrade to the fallback, and the shard host never sees a chunk
    twice."""
    from sentinel_tpu.chaos.runner import _make_token_server
    from sentinel_tpu.obs.registry import REGISTRY
    from sentinel_tpu.parallel.remote_shard import RemoteShard

    decision, svc, server = _make_token_server(flow_count=100.0)
    fb = _MarkerFallback()
    shard = RemoteShard(
        "127.0.0.1", server.port, timeout_s=2.0, fallback=fb, retry_interval_s=60.0
    )
    shard.CHUNK = 4
    names = [f"chaos/part{i}" for i in range(12)]
    answered0 = REGISTRY.counter("sentinel_shard_chunks_total").value
    degraded0 = REGISTRY.counter("sentinel_shard_chunks_degraded_total").value

    def _server_chunks(st, want, deadline_s=10.0):
        from sentinel_tpu.utils.time_source import mono_s

        deadline = mono_s() + deadline_s
        while (
            st.hit_counts().get("cluster.server.process", 0) < want
            and mono_s() < deadline
        ):
            time.sleep(0.01)
        return st.hit_counts().get("cluster.server.process", 0)

    try:
        # healthy window: 3 chunks served remotely
        with FP.armed(FaultPlan(seed=0, faults=[])) as st:
            out_a = shard.check_batch(names)
            seen_a = _server_chunks(st, 3)
        # partition mid-window: first reply read drops -> peer-closed ->
        # every in-flight chunk forfeited, degraded, NOT re-sent
        plan = FaultPlan(
            seed=0,
            faults=[FaultSpec("parallel.shard.recv", "drop", max_fires=1)],
        )
        with FP.armed(plan) as st:
            out_b = shard.check_batch(names)
            seen_b = _server_chunks(st, 3)
    finally:
        shard.close()
        server.stop()
        decision.stop()

    assert [w for _v, w in out_a] == [0] * 12  # remote verdicts, no marker
    assert [w for _v, w in out_b] == [7] * 12  # every span degraded locally
    assert fb.batches == [names[0:4], names[4:8], names[8:12]]
    # no replay: the server processed each written chunk at most once
    assert seen_a == 3 and seen_b == 3
    answered = REGISTRY.counter("sentinel_shard_chunks_total").value - answered0
    degraded = (
        REGISTRY.counter("sentinel_shard_chunks_degraded_total").value - degraded0
    )
    assert (answered, degraded) == (3, 3)
    assert shard._down_until > 0.0  # mid-exchange death armed the cool-down


# ---------------------------------------------------------------------------
# satellite: front-door unenforceable-rule counter
# ---------------------------------------------------------------------------


def test_front_door_unenforceable_param_rule_counts(client_factory):
    """A decision param rule whose param_idx 0 lost its hash lane (lanes
    claimed by gateway rules) must increment the registry counter, not
    only log; a healthy rule maps without counting."""
    from sentinel_tpu.cluster.front_door import _C_UNENFORCEABLE, resolve_param_lane
    from sentinel_tpu.cluster.rules import param_resource
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.core import rules as R
    from sentinel_tpu.obs.registry import REGISTRY

    decision = client_factory()
    svc = DefaultTokenService(decision)
    name = param_resource(7)
    # gateway rules claim both hash lanes of the shared resource first,
    # so the cluster decision rule's param_idx 0 gets none
    decision.gateway_param_rules.load(
        [
            R.ParamFlowRule(resource=name, count=5.0, param_idx=1),
            R.ParamFlowRule(resource=name, count=5.0, param_idx=2),
        ]
    )
    svc.param_rules.load(
        "default",
        [
            R.ParamFlowRule(
                resource="res-7", count=3.0, cluster_mode=True, cluster_flow_id=7
            )
        ],
    )
    before = _C_UNENFORCEABLE.value
    assert resolve_param_lane(svc, 7, name) is None
    assert _C_UNENFORCEABLE.value == before + 1
    # visible on the /metrics surface
    assert "sentinel_front_door_unenforceable_rules" in REGISTRY.exposition()

    # healthy service: lane resolves, nothing counted
    decision2 = client_factory()
    svc2 = DefaultTokenService(decision2)
    svc2.param_rules.load(
        "default",
        [
            R.ParamFlowRule(
                resource="res-8", count=3.0, cluster_mode=True, cluster_flow_id=8
            )
        ],
    )
    before2 = _C_UNENFORCEABLE.value
    assert resolve_param_lane(svc2, 8, param_resource(8)) == 0
    assert _C_UNENFORCEABLE.value == before2


# ---------------------------------------------------------------------------
# satellite: labeled cluster RPC failure kinds
# ---------------------------------------------------------------------------


def test_rpc_failure_kind_connect_refused():
    from sentinel_tpu.cluster import constants as C
    from sentinel_tpu.cluster.client import ClusterTokenClient
    from sentinel_tpu.obs.registry import REGISTRY

    c_connect = REGISTRY.counter(
        "sentinel_cluster_rpc_failures_total", labels={"kind": "connect"}
    )
    before = c_connect.value
    tok = ClusterTokenClient("127.0.0.1", 1, timeout_ms=200)  # nothing listens
    try:
        assert tok.request_token(5).status == C.STATUS_FAIL
    finally:
        tok.close()
    assert c_connect.value == before + 1


def test_rpc_failure_kind_send_via_failpoint():
    """An injected send failure lands on kind=send — the label chaos
    scenarios assert to prove WHICH fault fired."""
    from sentinel_tpu.chaos.runner import _make_token_server
    from sentinel_tpu.cluster import constants as C
    from sentinel_tpu.cluster.client import ClusterTokenClient
    from sentinel_tpu.obs.registry import REGISTRY

    c_send = REGISTRY.counter(
        "sentinel_cluster_rpc_failures_total", labels={"kind": "send"}
    )
    decision, svc, server = _make_token_server(flow_count=100.0)
    tok = ClusterTokenClient("127.0.0.1", server.port, timeout_ms=3000)
    tok.reconnect_interval_s = 0.0  # no throttle: reconnect right after the fault
    tok.start()
    before = c_send.value
    plan = FaultPlan(
        seed=0, faults=[FaultSpec("cluster.rpc.send", "raise", max_fires=1)]
    )
    try:
        with FP.armed(plan):
            assert tok.request_token(101).status == C.STATUS_FAIL
        assert tok.request_token(101).status == C.STATUS_OK  # reconnects
    finally:
        tok.close()
        server.stop()
        decision.stop()
    assert c_send.value == before + 1


# ---------------------------------------------------------------------------
# scenarios: tier-1 fast subset + determinism; full matrix under slow
# ---------------------------------------------------------------------------

def _fast_scenarios():
    # single source of truth: the Scenario.fast flags in the runner —
    # the CLI --fast subset and the tier-1 subset can never diverge
    from sentinel_tpu.chaos.runner import SCENARIOS

    return [n for n, s in SCENARIOS.items() if s.fast]


_FAST_SCENARIOS = _fast_scenarios()


@pytest.mark.parametrize("name", _FAST_SCENARIOS)
def test_fast_scenario_invariants_green(name):
    from sentinel_tpu.chaos.runner import report, run_scenario

    r = run_scenario(name, seed=7)
    assert r.ok, report([r])


def test_scenario_determinism_fast():
    """Two same-seed runs of a scenario inject identical event counts."""
    from sentinel_tpu.chaos.runner import run_scenario

    a = run_scenario("datasource_flap", seed=11)
    b = run_scenario("datasource_flap", seed=11)
    assert a.injected == b.injected and a.injected


def test_cli_list_and_sites(capsys):
    from sentinel_tpu.chaos.__main__ import main

    assert main(["--list"]) == 0
    out = capsys.readouterr().out
    for name in ("rpc_error_burst", "seg_overflow_storm", "shard_reconnect"):
        assert name in out
    assert main(["--sites"]) == 0
    out = capsys.readouterr().out
    assert "cluster.rpc.send" in out and "runtime.resolve.readback" in out


@pytest.mark.slow
def test_full_scenario_matrix_and_determinism():
    from sentinel_tpu.chaos.runner import report, run_all

    first = run_all(seed=7)
    assert len(first) >= 6
    assert all(r.ok for r in first), report([r for r in first if not r.ok])
    again = run_all(seed=7)
    assert [r.injected for r in first] == [r.injected for r in again]
