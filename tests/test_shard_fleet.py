"""Sharded cluster token fleet (cluster/shard.py) — the ISSUE-6 tentpole
contracts: ring-routed token decisions across N real token servers,
per-shard failover with the degrade-hysteresis shape, bounded-slack
budget leases (fallback passes are pre-debited, exhaustion fails
CLOSED), the LEASE wire extension, the RLS front door governing traffic
through the fleet, the ``/api/shards`` exposition, and the one-trace
client → RLS → shard timeline.
"""

import pytest

from sentinel_tpu.cluster import constants as C
from sentinel_tpu.cluster import protocol as P
from sentinel_tpu.cluster.shard import ShardFleet, describe_fleets
from sentinel_tpu.core import rules as R

pytestmark = pytest.mark.jitted  # TCP servers need real (cached) jit programs


def flow_rule(fid, count=100.0):
    return R.FlowRule(
        resource=f"res-{fid}",
        count=count,
        cluster_mode=True,
        cluster_flow_id=fid,
        cluster_threshold_type=1,  # GLOBAL
    )


@pytest.fixture()
def fleet(client_factory):
    f = ShardFleet(
        client_factory,
        n_shards=2,
        lease_slack=0.5,
        retry_interval_s=300.0,  # failover heals explicitly in tests
        lease_ttl_ms=600_000,
        timeout_ms=5000,
        reconnect_interval_s=0.0,
        lease_refresh_async=False,  # top-ups run inline: exact sequences below
    )
    yield f
    f.stop()


def owned_flow(fleet, shard_name, lo=101, hi=900):
    return next(f for f in range(lo, hi) if fleet.client.owner_of(f) == shard_name)


# ---------------------------------------------------------------------------
# routing + budgets
# ---------------------------------------------------------------------------


def test_fleet_routes_flows_to_ring_owners_and_enforces(fleet):
    fid_a = owned_flow(fleet, "shard-0")
    fid_b = owned_flow(fleet, "shard-1")
    fleet.load_flow_rules("default", [flow_rule(fid_a, 3.0), flow_rule(fid_b, 3.0)])
    # rules landed ONLY on their owners (partitioned, not broadcast)
    assert fleet.services["shard-0"].flow_rules.get_by_id(fid_a) is not None
    assert fleet.services["shard-0"].flow_rules.get_by_id(fid_b) is None
    assert fleet.services["shard-1"].flow_rules.get_by_id(fid_b) is not None
    # leasing off for exact budget arithmetic in this test
    fleet.client.lease_slack = 0.0
    ok_a = sum(fleet.client.request_token(fid_a).ok for _ in range(5))
    ok_b = sum(fleet.client.request_token(fid_b).ok for _ in range(5))
    assert (ok_a, ok_b) == (3, 3)  # independent per-shard budgets


def test_unknown_flow_is_no_rule(fleet):
    assert fleet.client.request_token(999_999).status == C.STATUS_NO_RULE


def test_concurrent_token_roundtrips_through_owner(fleet):
    fid = owned_flow(fleet, "shard-1")
    fleet.load_flow_rules("default", [flow_rule(fid, 2.0)])
    r1 = fleet.client.request_concurrent_token(fid)
    r2 = fleet.client.request_concurrent_token(fid)
    assert r1.ok and r2.ok and r1.token_id != r2.token_id
    assert fleet.client.request_concurrent_token(fid).blocked  # limit 2
    # composite ids route the release back to the grantor
    assert fleet.client.release_concurrent_token(r1.token_id).status == C.STATUS_RELEASE_OK
    assert fleet.client.request_concurrent_token(fid).ok


# ---------------------------------------------------------------------------
# failover + leases
# ---------------------------------------------------------------------------


def test_shard_kill_degrades_only_its_flows_and_lease_fails_closed(fleet):
    fid_a = owned_flow(fleet, "shard-0")
    fid_b = owned_flow(fleet, "shard-1")
    fleet.load_flow_rules(
        "default", [flow_rule(fid_a, 4.0), flow_rule(fid_b, 100.0)]
    )
    # healthy traffic establishes the slack lease (ceil(4 * 0.5) = 2)
    assert fleet.client.request_token(fid_a).ok
    lease = fleet.client._shards["shard-0"].leases[fid_a]
    assert lease.granted == 2 and lease.used == 0

    fleet.kill("shard-0")
    import time

    time.sleep(0.2)
    # failover: the first dead-socket request enters degraded and serves
    # from the lease; capacity 2, then FAIL-CLOSED — never an unmetered pass
    statuses = [fleet.client.request_token(fid_a).status for _ in range(4)]
    assert statuses == [
        C.STATUS_OK,
        C.STATUS_OK,
        C.STATUS_BLOCKED,
        C.STATUS_BLOCKED,
    ]
    assert fleet.client.shard_degraded("shard-0")
    # the OTHER shard's flows are untouched by the failover
    assert fleet.client.request_token(fid_b).ok
    assert not fleet.client.shard_degraded("shard-1")

    # rejoin on the original port + explicit cooldown expiry: the next
    # request probes and exits degraded within one hysteresis window
    fleet.rejoin("shard-0")
    st = fleet.client._shards["shard-0"]
    with st.lock:
        st.degraded_until = 0.0
    r = fleet.client.request_token(fid_a)
    assert r.status in (C.STATUS_OK, C.STATUS_BLOCKED)  # a real engine verdict
    assert not fleet.client.shard_degraded("shard-0")


def test_param_flows_fail_closed_while_degraded(fleet):
    fid = owned_flow(fleet, "shard-0")
    fleet.load_flow_rules("default", [flow_rule(fid)])
    fleet.kill("shard-0")
    import time

    time.sleep(0.2)
    assert fleet.client.request_param_token(fid, 1, ["u1"]).status == C.STATUS_BLOCKED


def test_no_lease_means_fail_closed(client_factory):
    f = ShardFleet(
        client_factory,
        n_shards=1,
        lease_slack=0.0,  # leasing disabled entirely
        retry_interval_s=300.0,
        timeout_ms=5000,
        reconnect_interval_s=0.0,
    )
    try:
        fid = owned_flow(f, "shard-0")
        f.load_flow_rules("default", [flow_rule(fid)])
        assert f.client.request_token(fid).ok
        f.kill("shard-0")
        import time

        time.sleep(0.2)
        assert f.client.request_token(fid).status == C.STATUS_BLOCKED
    finally:
        f.stop()


# ---------------------------------------------------------------------------
# LEASE wire extension
# ---------------------------------------------------------------------------


def test_lease_request_roundtrips_on_the_wire(fleet):
    fid = owned_flow(fleet, "shard-0")
    fleet.load_flow_rules("default", [flow_rule(fid, 10.0)])
    st = fleet.client._shards["shard-0"]
    r = st.client.request_lease(fid, 4)
    assert r.status == C.STATUS_OK
    assert r.remaining == 4
    assert r.wait_ms == 600_000  # the fleet's configured lease TTL
    # leased units were debited from the same global window
    fleet.client.lease_slack = 0.0
    ok = sum(fleet.client.request_token(fid).ok for _ in range(10))
    assert ok == 6


def test_lease_units_are_capped_both_sides(fleet):
    """An uncapped lease against a huge-threshold rule (slack × 1e9)
    would build a 250M-item engine batch and stall every flow on the
    shard.  Both the client sizing
    and the server grant clamp to MAX_LEASE_UNITS."""
    fid = owned_flow(fleet, "shard-0")
    fleet.load_flow_rules("default", [flow_rule(fid, 1e9)])
    assert fleet.client._lease_units(fid) == C.MAX_LEASE_UNITS
    st = fleet.client._shards["shard-0"]
    r = st.client.request_lease(fid, 10_000_000)  # hostile oversize ask
    assert r.status == C.STATUS_OK
    assert 0 < r.remaining <= C.MAX_LEASE_UNITS


def test_lease_frame_codec_roundtrip():
    req = P.ClusterRequest(xid=7, type=C.MSG_TYPE_LEASE, flow_id=12345, count=16)
    body = P.encode_request(req)[2:]
    back = P.decode_request(body)
    assert (back.type, back.flow_id, back.count) == (C.MSG_TYPE_LEASE, 12345, 16)
    rsp = P.ClusterResponse(
        xid=7, type=C.MSG_TYPE_LEASE, status=C.STATUS_OK, remaining=12, wait_ms=1000
    )
    back_r = P.decode_response(P.encode_response(rsp)[2:])
    assert (back_r.status, back_r.remaining, back_r.wait_ms) == (C.STATUS_OK, 12, 1000)


def test_dropped_rule_evicts_standing_lease(fleet):
    """A rule push that drops a flow must drop its standing lease too —
    otherwise a dead shard's fallback keeps admitting deleted-rule
    traffic until the lease TTL runs out."""
    fid = owned_flow(fleet, "shard-0")
    fleet.load_flow_rules("default", [flow_rule(fid, 10.0)])
    fleet.client.request_token(fid)  # establishes the lease
    st = fleet.client._shards["shard-0"]
    assert fid in st.leases
    fleet.load_flow_rules("default", [])  # rule dropped
    assert fid not in st.leases
    fleet.kill("shard-0")
    assert fleet.client.request_token(fid).status == C.STATUS_BLOCKED


def test_lease_transport_fail_is_not_cached_as_denial(fleet):
    """STATUS_FAIL from the LEASE RPC is a transport failure, not an
    admission denial: caching it as a zero-unit lease would pin the
    flow's failover slack at zero for a whole TTL window."""
    from sentinel_tpu.cluster.token_service import TokenResult

    fid = owned_flow(fleet, "shard-0")
    fleet.load_flow_rules("default", [flow_rule(fid, 10.0)])
    st = fleet.client._shards["shard-0"]
    orig = st.client.request_lease
    st.client.request_lease = lambda f, u: TokenResult(C.STATUS_FAIL)
    try:
        assert fleet.client.request_token(fid).status == C.STATUS_OK
        assert fid not in st.leases  # FAIL left uncached
    finally:
        st.client.request_lease = orig
    fleet.client.request_token(fid)  # next request re-leases normally
    assert st.leases[fid].granted > 0


def test_bare_client_flow_rules_facade(fleet):
    """A hand-built ShardedTokenClient (no fleet) must work behind the
    RLS rule manager: the built-in ``_ClientFlowRules`` facade learns
    thresholds (lease sizing) instead of crashing on ``load``, and
    forgets flows a later push drops."""
    from sentinel_tpu.cluster.shard import ShardedTokenClient
    from sentinel_tpu.rls import (
        EnvoyRlsRule,
        EnvoyRlsRuleManager,
        RlsKeyValue,
        RlsResourceDescriptor,
    )

    members = {n: ("127.0.0.1", fleet._ports[n]) for n in fleet.names}
    bare = ShardedTokenClient(members, lease_slack=0.5, reconnect_interval_s=0.0)
    try:
        mgr = EnvoyRlsRuleManager(bare)
        mgr.load(
            [
                EnvoyRlsRule(
                    domain="d",
                    descriptors=[
                        RlsResourceDescriptor(
                            key_values=[RlsKeyValue("k", "v")], count=8.0
                        )
                    ],
                )
            ]
        )
        fid = mgr.lookup_flow_id("d", [("k", "v")])
        assert fid is not None
        assert bare._lease_units(fid) == 4  # ceil(8 × 0.5)
        mgr.load([])  # dropping the domain forgets the threshold
        assert bare._lease_units(fid) == 0
    finally:
        bare.close()


def test_set_to_sharded_client_routes_through_fleet(fleet):
    """The runtime-facing entry point (ClusterStateManager): flip to
    fleet mode, teach thresholds through the client's facade, and get
    ring-routed decisions with sized leases."""
    from sentinel_tpu.cluster.state import CLUSTER_CLIENT, ClusterStateManager

    state = ClusterStateManager()
    state.set_to_sharded_client(
        {n: ("127.0.0.1", fleet._ports[n]) for n in fleet.names},
        timeout_ms=5000,  # must not collide with the explicit default
        reconnect_interval_s=0.0,
    )
    try:
        assert state.mode == CLUSTER_CLIENT
        tc = state.token_service()
        fid = owned_flow(fleet, "shard-1")
        fleet.load_flow_rules("default", [flow_rule(fid, 8.0)])
        tc.flow_rules.load("default", [flow_rule(fid, 8.0)])
        assert tc._lease_units(fid) == 2  # default lease_slack 0.25
        assert tc.request_token(fid).status == C.STATUS_OK
    finally:
        state.token_service().close()


# ---------------------------------------------------------------------------
# RLS front door over the fleet
# ---------------------------------------------------------------------------


def test_rls_routes_descriptors_through_the_ring(fleet):
    grpc = pytest.importorskip("grpc")  # noqa: F841
    from sentinel_tpu.rls import rls_pb2 as pb
    from sentinel_tpu.rls.rules import (
        EnvoyRlsRule,
        RlsKeyValue,
        RlsResourceDescriptor,
        descriptor_identifier,
        identifier_flow_id,
    )
    from sentinel_tpu.rls.server import SentinelEnvoyRlsService

    fleet.client.lease_slack = 0.0  # exact budget arithmetic below
    rls = SentinelEnvoyRlsService(fleet.client)
    rules = [
        EnvoyRlsRule(
            domain="mesh",
            descriptors=[
                RlsResourceDescriptor(
                    key_values=[RlsKeyValue("dest", f"svc-{i}")], count=2.0
                )
                for i in range(6)
            ],
        )
    ]
    rls.rules.load(rules)
    # every descriptor's flow id landed on its ring owner's shard service
    for i in range(6):
        fid = identifier_flow_id(
            descriptor_identifier("mesh", [("dest", f"svc-{i}")])
        )
        owner = fleet.client.owner_of(fid)
        assert fleet.services[owner].flow_rules.get_by_id(fid) is not None
        other = next(n for n in fleet.names if n != owner)
        assert fleet.services[other].flow_rules.get_by_id(fid) is None

    def ask(value):
        req = pb.RateLimitRequest(domain="mesh", hits_addend=1)
        d = req.descriptors.add()
        e = d.entries.add()
        e.key, e.value = "dest", value
        return rls.should_rate_limit(req).overall_code

    codes = [ask("svc-0") for _ in range(4)]
    assert codes.count(pb.RateLimitResponse.OK) == 2
    assert codes.count(pb.RateLimitResponse.OVER_LIMIT) == 2
    # a different descriptor has its own (possibly other-shard) budget
    assert ask("svc-1") == pb.RateLimitResponse.OK


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------


def test_one_trace_spans_client_rls_and_shard(fleet):
    """The acceptance timeline: one ShouldRateLimit request produces
    rls.should_rate_limit → cluster.rpc → token.decision spans sharing a
    single trace id with parent links — exactly what
    ``python -m sentinel_tpu.obs --merge`` joins into one Perfetto flow
    when the tiers run as separate processes."""
    pytest.importorskip("grpc")
    from sentinel_tpu import obs
    from sentinel_tpu.rls import rls_pb2 as pb
    from sentinel_tpu.rls.rules import EnvoyRlsRule, RlsKeyValue, RlsResourceDescriptor
    from sentinel_tpu.rls.server import SentinelEnvoyRlsService

    rls = SentinelEnvoyRlsService(fleet.client)
    rls.rules.load(
        [
            EnvoyRlsRule(
                domain="mesh",
                descriptors=[
                    RlsResourceDescriptor(
                        key_values=[RlsKeyValue("dest", "svc-t")], count=50.0
                    )
                ],
            )
        ]
    )
    req = pb.RateLimitRequest(domain="mesh", hits_addend=1)
    d = req.descriptors.add()
    e = d.entries.add()
    e.key, e.value = "dest", "svc-t"

    obs.TRACER.reset()
    obs.enable()
    try:
        assert rls.should_rate_limit(req).overall_code == pb.RateLimitResponse.OK
        import time

        time.sleep(0.1)  # server-side decision span lands async
    finally:
        obs.disable()
    spans = obs.TRACER.snapshot()
    rls_spans = [s for s in spans if s["name"] == "rls.should_rate_limit"]
    assert rls_spans, [s["name"] for s in spans]
    root = rls_spans[0]
    trace = root["trace"]
    assert trace != 0
    rpc = [s for s in spans if s["name"] == "cluster.rpc" and s["trace"] == trace]
    assert rpc, "cluster RPC span missing from the request's trace"
    # the RPC span parents to the RLS front-door span...
    assert rpc[0]["attrs"].get("parent") == root["attrs"]["span_id"]
    # ...and the shard's decision span joined the same trace over the wire
    decision = [s for s in spans if s["name"] == "token.decision" and s["trace"] == trace]
    assert decision, "shard-side decision span did not adopt the wire trace"
    assert decision[0]["attrs"].get("parent") == rpc[0]["attrs"]["span_id"]


def test_four_shard_fleet_grpc_end_to_end(client_factory):
    """The acceptance topology: a REAL gRPC ShouldRateLimit front door
    over a 4-shard fleet — Envoy-shaped requests resolve to flow ids,
    route through the ring to their owning shards, and come back
    governed."""
    pytest.importorskip("grpc")
    from sentinel_tpu.rls import rls_pb2 as pb
    from sentinel_tpu.rls.rules import (
        EnvoyRlsRule,
        RlsKeyValue,
        RlsResourceDescriptor,
        descriptor_identifier,
        identifier_flow_id,
    )
    from sentinel_tpu.rls.server import SentinelRlsGrpcServer, make_channel_stub

    f = ShardFleet(
        client_factory,
        n_shards=4,
        lease_slack=0.0,  # exact budgets below
        retry_interval_s=300.0,
        timeout_ms=5000,
        reconnect_interval_s=0.0,
    )
    server = None
    try:
        server = SentinelRlsGrpcServer(f.client, host="127.0.0.1", port=0)
        values = [f"svc-{i}" for i in range(8)]
        server.rules.load(
            [
                EnvoyRlsRule(
                    domain="mesh",
                    descriptors=[
                        RlsResourceDescriptor(
                            key_values=[RlsKeyValue("dest", v)], count=2.0
                        )
                        for v in values
                    ],
                )
            ]
        )
        server.start()
        fids = [
            identifier_flow_id(descriptor_identifier("mesh", [("dest", v)]))
            for v in values
        ]
        owners = {f.client.owner_of(fid) for fid in fids}
        assert len(owners) >= 2, "8 descriptors should spread over the ring"
        channel, call = make_channel_stub(f"127.0.0.1:{server.port}")

        def ask(value):
            req = pb.RateLimitRequest(domain="mesh", hits_addend=1)
            d = req.descriptors.add()
            e = d.entries.add()
            e.key, e.value = "dest", value
            return call(req).overall_code

        # every descriptor gets its own owner-enforced budget of 2
        for v in values:
            codes = [ask(v) for _ in range(3)]
            assert codes.count(pb.RateLimitResponse.OK) == 2, v
            assert codes.count(pb.RateLimitResponse.OVER_LIMIT) == 1, v
        channel.close()
    finally:
        if server is not None:
            server.stop()
        f.stop()


def test_merged_perfetto_trace_links_the_timeline(fleet, tmp_path):
    """``obs --merge`` on the dumped trace produces Chrome flow events
    (``ph: s``/``f``) binding the request's rls → cluster.rpc →
    token.decision spans — the acceptance's one-request timeline."""
    pytest.importorskip("grpc")
    import json

    from sentinel_tpu import obs
    from sentinel_tpu.obs.__main__ import merge_traces
    from sentinel_tpu.rls import rls_pb2 as pb
    from sentinel_tpu.rls.rules import EnvoyRlsRule, RlsKeyValue, RlsResourceDescriptor
    from sentinel_tpu.rls.server import SentinelEnvoyRlsService

    rls = SentinelEnvoyRlsService(fleet.client)
    rls.rules.load(
        [
            EnvoyRlsRule(
                domain="mesh",
                descriptors=[
                    RlsResourceDescriptor(
                        key_values=[RlsKeyValue("dest", "svc-m")], count=50.0
                    )
                ],
            )
        ]
    )
    req = pb.RateLimitRequest(domain="mesh", hits_addend=1)
    d = req.descriptors.add()
    e = d.entries.add()
    e.key, e.value = "dest", "svc-m"
    obs.TRACER.reset()
    obs.enable()
    try:
        rls.should_rate_limit(req)
        import time

        time.sleep(0.1)
    finally:
        obs.disable()
    dump = tmp_path / "proc.json"
    dump.write_text(json.dumps(obs.TRACER.chrome_trace()))
    merged = merge_traces([str(dump)])
    events = merged["traceEvents"]
    names = {ev.get("name") for ev in events if ev.get("ph") == "X"}
    assert {"rls.should_rate_limit", "cluster.rpc", "token.decision"} <= names
    flow_ids = {ev.get("id") for ev in events if ev.get("ph") in ("s", "f")}
    # the rls→rpc and rpc→decision parent links each became a flow pair
    assert len(flow_ids) >= 2, merged["otherData"]


def test_api_shards_exposition(fleet):
    from sentinel_tpu.transport.command import CommandRequest
    from sentinel_tpu.transport.handlers import build_default_handlers

    fid = owned_flow(fleet, "shard-0")
    fleet.load_flow_rules("default", [flow_rule(fid)])
    registry = build_default_handlers(fleet.services["shard-0"].client)
    rsp = registry.handle("api/shards", CommandRequest())
    assert rsp.success
    ours = [
        f
        for f in rsp.result
        if {s["name"] for s in f["shards"]} == {"shard-0", "shard-1"}
    ]
    assert ours, "fleet missing from /api/shards"
    desc = ours[0]
    assert desc["vnodes"] > 0 and desc["flows_registered"] >= 1
    for s in desc["shards"]:
        assert set(s) >= {"name", "addr", "connected", "degraded", "leases"}
    assert describe_fleets()  # module surface the handler rides


def test_shard_metrics_are_labeled(fleet):
    from sentinel_tpu.obs import REGISTRY

    fid = owned_flow(fleet, "shard-1")
    fleet.load_flow_rules("default", [flow_rule(fid)])
    assert fleet.client.request_token(fid).ok
    snap = REGISTRY.snapshot()
    assert snap['sentinel_shard_requests_total{shard="shard-1"}'] >= 1
    assert 'sentinel_shard_degraded{shard="shard-1"}' in snap


# ---------------------------------------------------------------------------
# lease-first admission (protocol v2)
# ---------------------------------------------------------------------------


def test_lease_first_steady_state_is_rpc_free(fleet):
    """After the bootstrap round-trip a healthy flow admits locally
    against its standing lease: zero routed RPCs per decision."""
    fid = owned_flow(fleet, "shard-0")
    fleet.load_flow_rules("default", [flow_rule(fid, 100.0)])
    st = fleet.client._shards["shard-0"]
    assert fleet.client.request_token(fid).ok  # remote + lease bootstrap
    base = st.c_requests.value
    lease = st.leases[fid]
    assert (lease.granted, lease.used) == (50, 0)  # slack 0.5 × count 100
    admits0 = st.c_local_admits.value
    for _ in range(10):
        assert fleet.client.request_token(fid).ok
    assert st.c_requests.value == base  # no further routed requests
    assert st.c_local_admits.value == admits0 + 10
    assert st.leases[fid].used == 10


def test_lease_tops_up_ahead_of_exhaustion(fleet):
    """Once the spendable remainder dips to refresh_frac of the grant
    the top-up (inline here: the fixture sets async off) refills the
    lease before it empties — the flow never pays a remote decision."""
    fid = owned_flow(fleet, "shard-0")
    fleet.load_flow_rules("default", [flow_rule(fid, 100.0)])
    st = fleet.client._shards["shard-0"]
    fleet.client.request_token(fid)  # bootstrap: granted 50, used 0
    base = st.c_requests.value
    for _ in range(25):  # 25th admit leaves remaining == 25 → top-up fires
        assert fleet.client.request_token(fid).ok
    lease = st.leases[fid]
    assert (lease.granted, lease.used) == (50, 0)  # refilled, carry folded in
    assert st.c_requests.value == base  # top-up was a LEASE frame, not a route


def test_async_refresher_tops_up_in_background(client_factory):
    """The default configuration hands top-ups to the background
    refresher thread; flush_lease_refresh() sequences the assertion."""
    f = ShardFleet(
        client_factory,
        n_shards=2,
        lease_slack=0.5,
        retry_interval_s=300.0,
        lease_ttl_ms=600_000,
        timeout_ms=5000,
        reconnect_interval_s=0.0,
    )
    try:
        fid = owned_flow(f, "shard-0")
        f.load_flow_rules("default", [flow_rule(fid, 100.0)])
        st = f.client._shards["shard-0"]
        f.client.request_token(fid)
        base = st.c_requests.value
        for _ in range(25):
            assert f.client.request_token(fid).ok
        assert f.client.flush_lease_refresh(5.0)
        lease = st.leases[fid]
        assert (lease.granted, lease.used) == (50, 0)
        assert st.c_requests.value == base
    finally:
        f.stop()


def test_request_token_many_one_exchange_per_owner(fleet):
    """Multi-flow admission groups by ring owner and rides one batched
    exchange per shard, preserving per-entry order semantics."""
    fid_a = owned_flow(fleet, "shard-0")
    fid_b = owned_flow(fleet, "shard-1")
    fleet.load_flow_rules("default", [flow_rule(fid_a, 3.0), flow_rule(fid_b, 3.0)])
    fleet.client.lease_slack = 0.0  # exact budgets: every decision remote
    rs = fleet.client.request_token_many(
        [(fid_a, 1), (fid_b, 1), (fid_a, 1), (999_999, 1), (fid_a, 2)]
    )
    assert [r.status for r in rs] == [
        C.STATUS_OK,
        C.STATUS_OK,
        C.STATUS_OK,
        C.STATUS_NO_RULE,
        C.STATUS_BLOCKED,  # 2 more against count 3 with 2 spent
    ]


def test_request_token_many_admits_locally_against_leases(fleet):
    fid = owned_flow(fleet, "shard-0")
    fleet.load_flow_rules("default", [flow_rule(fid, 100.0)])
    st = fleet.client._shards["shard-0"]
    fleet.client.request_token(fid)  # bootstrap lease
    base = st.c_requests.value
    rs = fleet.client.request_token_many([(fid, 1)] * 5)
    assert all(r.ok for r in rs)
    assert st.c_requests.value == base  # all five admitted off the lease


def test_request_token_many_fails_over_per_shard(fleet):
    """A dead owner degrades only its own entries; with leasing off the
    fallback fails CLOSED, and the other shard's entries are untouched."""
    fid_a = owned_flow(fleet, "shard-0")
    fid_b = owned_flow(fleet, "shard-1")
    fleet.load_flow_rules("default", [flow_rule(fid_a, 4.0), flow_rule(fid_b, 4.0)])
    fleet.client.lease_slack = 0.0
    fleet.kill("shard-0")
    rs = fleet.client.request_token_many([(fid_a, 1), (fid_b, 1)])
    assert rs[0].status == C.STATUS_BLOCKED
    assert rs[1].status == C.STATUS_OK
    assert fleet.client.shard_degraded("shard-0")
