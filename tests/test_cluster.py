"""Cluster token backend tests.

Mirrors the reference's cluster test strategy (SURVEY.md §4.4): checker
logic against in-memory state, codec round-trips, connection bookkeeping —
plus a real localhost TCP server/client end-to-end loop the reference never
had.
"""

import threading
import time

import pytest

from sentinel_tpu.cluster import constants as C
from sentinel_tpu.cluster import protocol as P
from sentinel_tpu.cluster.client import ClusterTokenClient
from sentinel_tpu.cluster.rules import ClusterServerConfigManager, ServerFlowConfig
from sentinel_tpu.cluster.server import ClusterTokenServer
from sentinel_tpu.cluster.state import ClusterStateManager
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.core import errors as ERR
from sentinel_tpu.core import rules as R
from sentinel_tpu.utils.host_window import HostWindow

# the token services built here are closed when the module ends (tests/conftest.py)
pytestmark = pytest.mark.usefixtures("closes_token_services")


def cluster_flow_rule(flow_id=101, count=5.0, threshold_type=C.FLOW_THRESHOLD_GLOBAL):
    return R.FlowRule(
        resource=f"res-{flow_id}",
        count=count,
        cluster_mode=True,
        cluster_flow_id=flow_id,
        cluster_threshold_type=threshold_type,
    )


# ---------------------------------------------------------------------------
# codec round-trips (ParamFlowRequestDataWriterTest / FlowResponseDataDecoderTest)
# ---------------------------------------------------------------------------


def test_protocol_flow_roundtrip():
    req = P.ClusterRequest(xid=7, type=C.MSG_TYPE_FLOW, flow_id=12345678901, count=3, priority=True)
    frames = P.FrameReader().feed(P.encode_request(req))
    assert len(frames) == 1
    got = P.decode_request(frames[0])
    assert (got.xid, got.type, got.flow_id, got.count, got.priority) == (
        7, C.MSG_TYPE_FLOW, 12345678901, 3, True,
    )


def test_protocol_param_roundtrip():
    params = [42, 2**40, 3.5, "user-x", True]
    req = P.ClusterRequest(xid=9, type=C.MSG_TYPE_PARAM_FLOW, flow_id=5, count=1, params=params)
    got = P.decode_request(P.FrameReader().feed(P.encode_request(req))[0])
    assert got.params == params


def test_protocol_response_and_partial_frames():
    rsp = P.ClusterResponse(xid=3, type=C.MSG_TYPE_FLOW, status=C.STATUS_SHOULD_WAIT,
                            remaining=10, wait_ms=250)
    raw = P.encode_response(rsp)
    r = P.FrameReader()
    assert r.feed(raw[:3]) == []  # partial frame buffers
    frames = r.feed(raw[3:])
    got = P.decode_response(frames[0])
    assert (got.status, got.wait_ms, got.remaining) == (C.STATUS_SHOULD_WAIT, 250, 10)


def test_protocol_concurrent_roundtrip():
    req = P.ClusterRequest(xid=1, type=C.MSG_TYPE_CONCURRENT_RELEASE, token_id=99)
    assert P.decode_request(P.FrameReader().feed(P.encode_request(req))[0]).token_id == 99
    rsp = P.ClusterResponse(xid=1, type=C.MSG_TYPE_CONCURRENT_ACQUIRE,
                            status=C.STATUS_OK, token_id=77)
    assert P.decode_response(P.FrameReader().feed(P.encode_response(rsp))[0]).token_id == 77


# ---------------------------------------------------------------------------
# trace-context tail: version tolerance both ways
# ---------------------------------------------------------------------------

import struct  # noqa: E402 — the back-compat tests re-implement the legacy reader


def test_traced_frames_roundtrip_all_types():
    """(trace_id, span_id) survives encode→decode for every request type
    that carries it and for responses (echoed server-side)."""
    tid, sid = 0xABCDEF0123456789, 0x1122334455667788
    for req in (
        P.ClusterRequest(xid=1, type=C.MSG_TYPE_FLOW, flow_id=5, count=2,
                         priority=True, trace_id=tid, span_id=sid),
        P.ClusterRequest(xid=2, type=C.MSG_TYPE_FLOW_BATCH, flow_id=5, count=9,
                         trace_id=tid, span_id=sid),
        P.ClusterRequest(xid=3, type=C.MSG_TYPE_PARAM_FLOW, flow_id=5, count=1,
                         params=[42, "user-x", True], trace_id=tid, span_id=sid),
        P.ClusterRequest(xid=4, type=C.MSG_TYPE_CONCURRENT_ACQUIRE, flow_id=5,
                         trace_id=tid, span_id=sid),
        P.ClusterRequest(xid=5, type=C.MSG_TYPE_CONCURRENT_RELEASE, token_id=7,
                         trace_id=tid, span_id=sid),
        P.ClusterRequest(xid=6, type=C.MSG_TYPE_RES_CHECK,
                         params=["r", 1, False, "", ""], trace_id=tid, span_id=sid),
    ):
        got = P.decode_request(P.FrameReader().feed(P.encode_request(req))[0])
        assert (got.trace_id, got.span_id) == (tid, sid), req.type
        assert got.params == req.params and got.flow_id == req.flow_id
    rsp = P.ClusterResponse(xid=9, type=C.MSG_TYPE_FLOW, status=C.STATUS_OK,
                            remaining=3, wait_ms=10, trace_id=tid, span_id=sid)
    got = P.decode_response(P.FrameReader().feed(P.encode_response(rsp))[0])
    assert (got.trace_id, got.span_id) == (tid, sid)
    assert (got.remaining, got.wait_ms) == (3, 10)


def test_untraced_frames_are_byte_identical_to_legacy_format():
    """With no trace context the wire format is bit-exact the pre-trace
    encoding — a tracing-off deployment interoperates with ANY version."""
    req = P.ClusterRequest(xid=7, type=C.MSG_TYPE_FLOW, flow_id=12, count=3,
                           priority=True)
    legacy = struct.pack(">iB", 7, C.MSG_TYPE_FLOW) + struct.pack(">qiB", 12, 3, 1)
    assert P.encode_request(req) == struct.pack(">H", len(legacy)) + legacy
    rsp = P.ClusterResponse(xid=7, type=C.MSG_TYPE_FLOW, status=C.STATUS_OK,
                            remaining=2, wait_ms=0)
    legacy_r = struct.pack(">iBb", 7, C.MSG_TYPE_FLOW, C.STATUS_OK) + struct.pack(">ii", 2, 0)
    assert P.encode_response(rsp) == struct.pack(">H", len(legacy_r)) + legacy_r
    # and legacy frames (no tail) decode on the new reader with ctx == 0
    got = P.decode_request(P.FrameReader().feed(P.encode_request(req))[0])
    assert (got.trace_id, got.span_id) == (0, 0)
    got_r = P.decode_response(P.FrameReader().feed(P.encode_response(rsp))[0])
    assert (got_r.trace_id, got_r.span_id) == (0, 0)


def test_legacy_reader_skips_trace_tail_on_fixed_and_response_frames():
    """A pre-trace reader parsed fixed-size payloads by offset and
    count-bounded item lists — both skip the appended tail untouched.
    (Re-implemented here exactly as the legacy decoder read the wire.)"""
    tid, sid = 0x1234, 0x5678
    raw = P.encode_request(
        P.ClusterRequest(xid=3, type=C.MSG_TYPE_FLOW, flow_id=11, count=4,
                         priority=False, trace_id=tid, span_id=sid)
    )
    body = P.FrameReader().feed(raw)[0]
    xid, t = struct.unpack_from(">iB", body, 0)
    flow_id, count, prio = struct.unpack_from(">qiB", body[5:], 0)  # legacy parse
    assert (xid, t, flow_id, count, prio) == (3, C.MSG_TYPE_FLOW, 11, 4, 0)

    rsp = P.ClusterResponse(xid=4, type=C.MSG_TYPE_RES_CHECK, status=C.STATUS_OK,
                            items=[(0, 0), (4, 9)], trace_id=tid, span_id=sid)
    body = P.FrameReader().feed(P.encode_response(rsp))[0]
    xid, t, status = struct.unpack_from(">iBb", body, 0)
    p = body[6:]
    (n,) = struct.unpack_from(">i", p, 0)
    items, off = [], 4
    for _ in range(n):  # the legacy count-bounded item loop
        v, w = struct.unpack_from(">bi", p, off)
        off += 5
        items.append((v, w))
    assert items == [(0, 0), (4, 9)]


def test_tcp_roundtrip_carries_trace_context_end_to_end(tcp_cluster, tmp_path):
    """ISSUE-5 acceptance over the REAL wire: tracing on both ends of a
    SentinelClient↔ClusterTokenServer round-trip, the client's
    cluster.rpc span and the server's token.decision span share one wire
    trace id (parent = the RPC span id), and the per-endpoint dumps
    --merge into one Chrome trace with a flow event linking them."""
    import json as _json

    from sentinel_tpu import obs
    from sentinel_tpu.obs.__main__ import merge_traces

    server, tok, svc = tcp_cluster
    obs.TRACER.reset()
    obs.enable()
    try:
        assert tok.request_token(101).status in (C.STATUS_OK, C.STATUS_BLOCKED)
    finally:
        obs.disable()
    spans = obs.TRACER.snapshot()
    rpc = [s for s in spans if s["name"] == "cluster.rpc"]
    dec = [s for s in spans if s["name"] == "token.decision"]
    assert rpc and dec
    links = [
        (r, d)
        for r in rpc
        for d in dec
        if d["attrs"].get("parent") == r["attrs"].get("span_id")
    ]
    assert links, f"no parent link: rpc={rpc} dec={dec}"
    r, d = links[0]
    assert r["trace"] == d["trace"] != 0

    # the context crossed a real socket (client and server halves run in
    # one test process but share NOTHING except the wire frames) — dump
    # each endpoint's spans as its own process and merge
    client_doc = obs.TRACER.chrome_trace(rpc)
    server_doc = obs.TRACER.chrome_trace(dec)
    for e in server_doc["traceEvents"]:
        e["pid"] += 1  # the server's own dump would carry its own pid
    a, b = tmp_path / "client.json", tmp_path / "server.json"
    a.write_text(_json.dumps(client_doc))
    b.write_text(_json.dumps(server_doc))
    doc = merge_traces([str(a), str(b)])
    assert doc["otherData"]["flow_links"] >= 1
    flow_ids = {e["id"] for e in doc["traceEvents"] if e.get("ph") in ("s", "f")}
    assert r["attrs"]["span_id"] in flow_ids


# ---------------------------------------------------------------------------
# host window / namespace guard
# ---------------------------------------------------------------------------


def test_host_window_try_pass_and_expiry():
    w = HostWindow(10, 1000)
    t = 10_000
    for _ in range(5):
        assert w.try_pass(t, limit_qps=5.0)
    assert not w.try_pass(t, limit_qps=5.0)
    # window slides: 1.1 s later all buckets expired
    assert w.try_pass(t + 1100, limit_qps=5.0)


# ---------------------------------------------------------------------------
# token service decisions (ClusterFlowCheckerTest analog)
# ---------------------------------------------------------------------------


def test_request_token_blocks_over_global_threshold(client, vt):
    svc = DefaultTokenService(client)
    svc.flow_rules.load("default", [cluster_flow_rule(count=5.0)])
    got = [svc.request_token(101).status for _ in range(7)]
    assert got.count(C.STATUS_OK) == 5
    assert got.count(C.STATUS_BLOCKED) == 2
    vt.advance(1100)  # window rolls → tokens replenish
    assert svc.request_token(101).status == C.STATUS_OK


def test_request_token_no_rule(client):
    svc = DefaultTokenService(client)
    assert svc.request_token(999).status == C.STATUS_NO_RULE


def test_avg_local_threshold_scales_with_connections(client):
    svc = DefaultTokenService(client)
    svc.connected_count_fn = lambda ns: 3
    svc.flow_rules.load(
        "default", [cluster_flow_rule(count=2.0, threshold_type=C.FLOW_THRESHOLD_AVG_LOCAL)]
    )
    svc.refresh_connected_count()
    got = [svc.request_token(101).status for _ in range(8)]
    assert got.count(C.STATUS_OK) == 6  # 2 × 3 connections


def test_namespace_guard_too_many(client):
    cfgm = ClusterServerConfigManager()
    cfgm.set_flow_config("default", ServerFlowConfig(max_allowed_qps=3.0))
    svc = DefaultTokenService(client, config=cfgm)
    svc.flow_rules.load("default", [cluster_flow_rule(count=100.0)])
    got = [svc.request_token(101).status for _ in range(5)]
    assert got.count(C.STATUS_OK) == 3
    assert got.count(C.STATUS_TOO_MANY_REQUEST) == 2


def test_param_token(client, vt):
    svc = DefaultTokenService(client)
    rule = R.ParamFlowRule(
        resource="p", count=2.0, cluster_mode=True, cluster_flow_id=55, duration_in_sec=1
    )
    svc.param_rules.load("default", [rule])
    assert svc.request_param_token(55, 1, ["alice"]).status == C.STATUS_OK
    assert svc.request_param_token(55, 1, ["alice"]).status == C.STATUS_OK
    assert svc.request_param_token(55, 1, ["alice"]).status == C.STATUS_BLOCKED
    # different value has its own budget
    assert svc.request_param_token(55, 1, ["bob"]).status == C.STATUS_OK


def test_concurrent_tokens_and_expiry(client, vt):
    svc = DefaultTokenService(client, concurrent_ttl_ms=1000)
    svc.flow_rules.load("default", [cluster_flow_rule(count=2.0)])
    r1 = svc.request_concurrent_token(101)
    r2 = svc.request_concurrent_token(101)
    assert r1.ok and r2.ok and r1.token_id != r2.token_id
    assert svc.request_concurrent_token(101).blocked
    assert svc.release_concurrent_token(r1.token_id).status == C.STATUS_RELEASE_OK
    assert svc.release_concurrent_token(r1.token_id).status == C.STATUS_ALREADY_RELEASE
    assert svc.request_concurrent_token(101).ok
    # TTL sweep frees leaked tokens (RegularExpireStrategy)
    vt.advance(1500)
    svc.concurrent.expire(vt.now_ms())
    assert svc.concurrent.current(101) == 0
    assert svc.request_concurrent_token(101).ok


# ---------------------------------------------------------------------------
# TCP end-to-end (server + client over localhost)
# ---------------------------------------------------------------------------


@pytest.fixture()
def tcp_cluster(client_factory):
    decision = client_factory()
    svc = DefaultTokenService(decision)
    svc.flow_rules.load("default", [cluster_flow_rule(count=3.0)])
    server = ClusterTokenServer(svc, host="127.0.0.1", port=0)
    server.start()
    tok = ClusterTokenClient("127.0.0.1", server.port, namespace="default", timeout_ms=5000)
    tok.start()
    yield server, tok, svc
    tok.close()
    server.stop()


def test_tcp_token_roundtrip(tcp_cluster):
    server, tok, svc = tcp_cluster
    got = [tok.request_token(101).status for _ in range(5)]
    assert got.count(C.STATUS_OK) == 3
    assert got.count(C.STATUS_BLOCKED) == 2
    assert tok.request_token(31337).status == C.STATUS_NO_RULE


def test_tcp_connection_census(tcp_cluster):
    server, tok, svc = tcp_cluster
    deadline = time.monotonic() + 2
    while server.connections.connected_count("default") < 1:
        assert time.monotonic() < deadline, "PING registration not observed"
        time.sleep(0.01)


def test_tcp_token_batch_partial_grant(tcp_cluster):
    """FLOW_BATCH: one roundtrip, server grants k of n units (limit 3)."""
    server, tok, svc = tcp_cluster
    r = tok.request_token_batch(101, 5)
    assert r.status == C.STATUS_OK and r.remaining == 3
    r2 = tok.request_token_batch(101, 5)
    assert r2.status == C.STATUS_BLOCKED and r2.remaining == 0


def test_tcp_concurrent_roundtrip(tcp_cluster):
    server, tok, svc = tcp_cluster
    r = tok.request_concurrent_token(101)
    assert r.ok and r.token_id > 0
    assert tok.release_concurrent_token(r.token_id).status == C.STATUS_RELEASE_OK


def test_client_fail_fast_when_server_down():
    tok = ClusterTokenClient("127.0.0.1", 1, timeout_ms=100, reconnect_interval_s=0.0)
    assert tok.request_token(1).status == C.STATUS_FAIL


# ---------------------------------------------------------------------------
# runtime integration: embedded server + degrade-to-local
# ---------------------------------------------------------------------------


def test_embedded_cluster_entry_flow(client_factory):
    app = client_factory()
    decision = client_factory()
    svc = DefaultTokenService(decision)
    svc.flow_rules.load("default", [cluster_flow_rule(flow_id=101, count=2.0)])

    mgr = ClusterStateManager()
    mgr.set_to_server(svc, serve_network=False)
    app.set_cluster(mgr)
    rule = cluster_flow_rule(flow_id=101, count=2.0)
    app.flow_rules.load([rule])

    ok = blocked = 0
    for _ in range(5):
        try:
            e = app.entry("res-101")
            e.exit()
            ok += 1
        except ERR.FlowException:
            blocked += 1
    assert ok == 2 and blocked == 3
    # blocks were recorded into the app's own stat windows (pre_verdict path)
    s = app.stats.resource("res-101")
    assert s["blockQps"] > 0


def test_cluster_degrades_to_local_when_unavailable(client_factory):
    app = client_factory()
    mgr = ClusterStateManager()  # NOT_STARTED: no token service
    app.set_cluster(mgr)
    app.flow_rules.load([cluster_flow_rule(flow_id=7, count=2.0)])

    ok = blocked = 0
    for _ in range(5):
        try:
            app.entry("res-7").exit()
            ok += 1
        except ERR.FlowException:
            blocked += 1
    # degraded → the cluster rule enforces locally (fallbackToLocalOrPass)
    assert ok == 2 and blocked == 3


def test_check_batch_enforces_cluster_rules(client_factory):
    """The bulk API must consult the token service too (not just entry())."""
    app = client_factory()
    decision = client_factory()
    svc = DefaultTokenService(decision)
    svc.flow_rules.load("default", [cluster_flow_rule(flow_id=501, count=2.0)])
    mgr = ClusterStateManager()
    mgr.set_to_server(svc, serve_network=False)
    app.set_cluster(mgr)
    app.flow_rules.load([cluster_flow_rule(flow_id=501, count=2.0)])

    results = app.check_batch(["res-501"] * 5)
    verdicts = [v for v, _ in results]
    assert verdicts.count(ERR.PASS) == 2
    assert verdicts.count(ERR.BLOCK_FLOW) == 3


def test_too_many_request_degrades_to_local(client_factory):
    """Namespace-guard overload must fall back to local enforcement, not
    hard-block everything (applyTokenResult groups TOO_MANY with FAIL)."""
    from sentinel_tpu.cluster.token_service import TokenResult, TokenService

    class OverloadedService(TokenService):
        def request_token(self, flow_id, count=1, prioritized=False):
            return TokenResult(C.STATUS_TOO_MANY_REQUEST)

    class FakeMgr:
        def token_service(self):
            return OverloadedService()

    app = client_factory()
    app.set_cluster(FakeMgr())
    app.flow_rules.load([cluster_flow_rule(flow_id=9, count=2.0)])

    ok = blocked = 0
    for _ in range(5):
        try:
            app.entry("res-9").exit()
            ok += 1
        except ERR.FlowException:
            blocked += 1
    # local fallback enforces count=2, nothing hard-blocks on TOO_MANY itself
    assert ok == 2 and blocked == 3


def test_degraded_probe_recovers_without_unenforced_window(client_factory):
    """While degraded, fallback rules stay compiled through probes; a probe
    response flips back to remote enforcement."""
    from sentinel_tpu.cluster.token_service import TokenResult, TokenService

    class FlappingService(TokenService):
        def __init__(self):
            self.up = False
            self.calls = 0

        def request_token(self, flow_id, count=1, prioritized=False):
            self.calls += 1
            return TokenResult(C.STATUS_OK if self.up else C.STATUS_FAIL)

    svc = FlappingService()

    class Mgr:
        def token_service(self):
            return svc

    app = client_factory()
    app.set_cluster(Mgr())
    app.cluster_retry_interval_s = 0.0  # every entry re-probes
    app.flow_rules.load([cluster_flow_rule(flow_id=11, count=100.0)])

    app.entry("res-11").exit()  # FAIL → degraded
    assert app._cluster_degraded_active
    app.entry("res-11").exit()  # probe still failing → stays degraded
    assert app._cluster_degraded_active
    svc.up = True
    app.entry("res-11").exit()  # probe succeeds → back to remote
    assert not app._cluster_degraded_active


def test_cluster_no_fallback_passes_when_unavailable(client_factory):
    app = client_factory()
    app.set_cluster(ClusterStateManager())
    r = cluster_flow_rule(flow_id=8, count=1.0)
    r.cluster_fallback_to_local = False
    app.flow_rules.load([r])
    for _ in range(4):
        app.entry("res-8").exit()  # no fallback → pass-through


def test_authority_blocked_request_consumes_no_cluster_token(client_factory):
    """Slot-order parity with the reference (FlowRuleChecker.java:64-72 —
    cluster tokens are requested inside FlowSlot, AFTER AuthoritySlot): a
    blacklisted-origin request must be rejected WITHOUT consuming a
    cluster token (VERDICT r4 weak #6)."""
    from sentinel_tpu.cluster.token_service import TokenResult, TokenService

    class CountingService(TokenService):
        def __init__(self):
            self.calls = 0

        def request_token(self, flow_id, count=1, prioritized=False):
            self.calls += 1
            return TokenResult(C.STATUS_OK)

        def request_token_batch(self, flow_id, count=1):
            self.calls += 1
            r = TokenResult(C.STATUS_OK)
            r.remaining = count
            return r

    svc = CountingService()

    class Mgr:
        def token_service(self):
            return svc

    app = client_factory()
    app.set_cluster(Mgr())
    app.flow_rules.load([cluster_flow_rule(flow_id=77, count=100.0)])
    app.authority_rules.load(
        [R.AuthorityRule(resource="res-77", limit_app="badcaller",
                         strategy=R.AUTHORITY_BLACK)]
    )

    # blacklisted origin: engine rejects, token service never consulted
    with pytest.raises(ERR.AuthorityException):
        app.entry("res-77", origin="badcaller")
    assert svc.calls == 0

    # allowed origin: token consumed as usual
    app.entry("res-77", origin="goodcaller").exit()
    assert svc.calls == 1

    # bulk path: the doomed item is excluded from the group's token count
    out = app.check_batch(
        ["res-77", "res-77"], origins=["badcaller", "goodcaller"]
    )
    assert out[0][0] == ERR.BLOCK_AUTHORITY and out[1][0] == ERR.PASS
    assert svc.calls == 2

    # white-list form: an unlisted origin is equally doomed -> no token
    app.authority_rules.load(
        [R.AuthorityRule(resource="res-77", limit_app="goodcaller",
                         strategy=R.AUTHORITY_WHITE)]
    )
    with pytest.raises(ERR.AuthorityException):
        app.entry("res-77", origin="stranger")
    assert svc.calls == 2


def test_authority_mirror_two_rules_last_wins(client_factory):
    """ADVICE r5 medium, case (1): two authority rules on one resource —
    compile_authority_rules must apply TRUE last-wins (zero the origin
    slots before each write) so the device matches exactly the rule the
    host mirror keeps, not the union of both rules' origins."""
    from sentinel_tpu.core.rule_tensors import AUTH_EMPTY, compile_authority_rules

    app = client_factory()
    rules = [
        R.AuthorityRule(resource="res-au", limit_app="alpha,beta",
                        strategy=R.AUTHORITY_WHITE),
        R.AuthorityRule(resource="res-au", limit_app="gamma",
                        strategy=R.AUTHORITY_WHITE),
    ]
    rid = app.registry.resource_id("res-au")
    for o in ("alpha", "beta", "gamma"):
        app.registry.origin_id(o)
    t = compile_authority_rules(rules, app.cfg, app.registry)
    live = sorted(int(x) for x in t.origins[rid] if x != AUTH_EMPTY)
    assert live == [app.registry.origin_id("gamma")], (
        "first rule's origins must be cleared, not unioned"
    )

    # behavioral check through the engine: alpha (only in the OVERWRITTEN
    # rule) must now be rejected, gamma passes — and the mirror agrees,
    # so neither side opens a device-pass/mirror-block divergence
    app.authority_rules.load(rules)
    with pytest.raises(ERR.AuthorityException):
        app.entry("res-au", origin="alpha")
    app.entry("res-au", origin="gamma").exit()
    assert app._authority_pre_blocks("res-au", "alpha") is True
    assert app._authority_pre_blocks("res-au", "gamma") is False


def test_authority_mirror_unintered_origin_never_preblocks(client_factory):
    """ADVICE r5 medium, case (2): a rule origin past the intern cap is
    stored as -1 device-side, where it matches every un-interned request
    origin (device-LENIENT under WHITE).  The host mirror must therefore
    never pre-block for such a rule — otherwise a WHITE request the
    device passes would skip _cluster_check, opening an unenforced
    cluster-limit window."""
    app = client_factory()
    # exhaust the origin intern space so the NEXT origin fails to intern
    app.registry.MAX_ORIGINS = len(app.registry._origin_names) + 1
    app.registry.origin_id("filler-origin")
    assert app.registry.origin_id("vip-app") == -1  # past the cap

    app.authority_rules.load(
        [R.AuthorityRule(resource="res-au2", limit_app="vip-app",
                         strategy=R.AUTHORITY_WHITE)]
    )
    # device side: request origin "someone-else" is also un-interned (-1),
    # matches the rule's -1 slot -> device passes; the mirror must agree
    assert app._authority_pre_blocks("res-au2", "someone-else") is False
    assert app._authority_pre_blocks("res-au2", "vip-app") is False
    app.entry("res-au2", origin="someone-else").exit()

    # and the cluster token service still gets consulted for that traffic
    from sentinel_tpu.cluster.token_service import TokenResult, TokenService

    class CountingService(TokenService):
        def __init__(self):
            self.calls = 0

        def request_token(self, flow_id, count=1, prioritized=False):
            self.calls += 1
            return TokenResult(C.STATUS_OK)

    svc = CountingService()

    class Mgr:
        def token_service(self):
            return svc

    app.set_cluster(Mgr())
    app.flow_rules.load([R.FlowRule(resource="res-au2", count=100.0,
                                    cluster_mode=True, cluster_flow_id=909)])
    app.entry("res-au2", origin="someone-else").exit()
    assert svc.calls == 1, (
        "mirror pre-blocked device-passing traffic: cluster limit unenforced"
    )


# ---------------------------------------------------------------------------
# protocol v2: batched frames, HELLO negotiation, fail-closed framing
# ---------------------------------------------------------------------------

import socket  # noqa: E402

import numpy as np  # noqa: E402


def _batch_req(xid=11, tid=0, sid=0):
    return P.ClusterBatchRequest(
        xid=xid,
        kinds=np.array([C.BATCH_KIND_FLOW, C.BATCH_KIND_FLOW_BATCH, C.BATCH_KIND_LEASE], np.uint8),
        ids=np.array([101, 2**40, -7], np.int64),
        counts=np.array([1, 500, 32], np.int32),
        flags=np.array([C.BATCH_FLAG_PRIORITIZED, 0, 0], np.uint8),
        trace_id=tid,
        span_id=sid,
    )


def test_batch_frame_roundtrip_with_and_without_trace_tail():
    for tid, sid in ((0, 0), (0xABCDEF0123456789, 0x1122334455667788)):
        got = P.decode_batch_request(P.FrameReader().feed(
            P.encode_batch_request(_batch_req(tid=tid, sid=sid)))[0])
        want = _batch_req(tid=tid, sid=sid)
        assert got.xid == 11 and (got.trace_id, got.span_id) == (tid, sid)
        for f in ("kinds", "ids", "counts", "flags"):
            assert np.array_equal(getattr(got, f), getattr(want, f)), f
        rsp = P.ClusterBatchResponse(
            xid=11, status=C.STATUS_OK,
            statuses=np.array([C.STATUS_OK, C.STATUS_BLOCKED, C.STATUS_FAIL], np.int8),
            remainings=np.array([3, 0, -1], np.int32),
            waits=np.array([0, 250, 0], np.int32),
            token_ids=np.array([0, 0, 2**50], np.int64),
            trace_id=tid, span_id=sid,
        )
        got_r = P.decode_batch_response(P.FrameReader().feed(P.encode_batch_response(rsp))[0])
        assert (got_r.status, got_r.trace_id, got_r.span_id) == (C.STATUS_OK, tid, sid)
        for f in ("statuses", "remainings", "waits", "token_ids"):
            assert np.array_equal(getattr(got_r, f), getattr(rsp, f)), f


def test_batch_frame_golden_bytes():
    """Pin the v2 wire layout byte-for-byte: a future refactor that
    shifts a field breaks THIS test, not a live fleet mid-upgrade."""
    req = P.ClusterBatchRequest(
        xid=7,
        kinds=np.array([C.BATCH_KIND_FLOW], np.uint8),
        ids=np.array([12], np.int64),
        counts=np.array([3], np.int32),
        flags=np.array([1], np.uint8),
    )
    body = struct.pack(">iBH", 7, C.MSG_TYPE_BATCH, 1) + struct.pack(">BqiB", C.BATCH_KIND_FLOW, 12, 3, 1)
    assert P.encode_batch_request(req) == struct.pack(">H", len(body)) + body
    rsp = P.ClusterBatchResponse(
        xid=7, status=C.STATUS_OK,
        statuses=np.array([C.STATUS_BLOCKED], np.int8),
        remainings=np.array([2], np.int32),
        waits=np.array([9], np.int32),
        token_ids=np.array([5], np.int64),
    )
    body_r = struct.pack(">iBbH", 7, C.MSG_TYPE_BATCH, C.STATUS_OK, 1) + struct.pack(
        ">biiq", C.STATUS_BLOCKED, 2, 9, 5
    )
    assert P.encode_batch_response(rsp) == struct.pack(">H", len(body_r)) + body_r
    # and the type byte sits where peek_type reads it, on BOTH frames
    assert P.peek_type(body) == C.MSG_TYPE_BATCH == P.peek_type(body_r)


def test_batch_frame_strict_length_rejects_whole_frame():
    """_batch_payload: any length that is not exactly n entries (plus an
    optional well-formed trace block) rejects the WHOLE frame — a
    corrupted count byte or short read never yields partial entries."""
    raw = P.encode_batch_request(_batch_req())
    body = bytearray(P.FrameReader().feed(raw)[0])
    with pytest.raises(ValueError):
        P.decode_batch_request(bytes(body[:-1]))  # short read
    mangled = bytearray(body)
    mangled[6] ^= 0xFF  # count byte bit-flip -> slab length mismatch
    with pytest.raises(ValueError):
        P.decode_batch_request(bytes(mangled))
    with pytest.raises(ValueError):
        P.decode_batch_request(bytes(body) + b"x")  # trailing garbage


def test_hello_negotiation_flips_client_to_v2(tcp_cluster):
    server, tok, svc = tcp_cluster
    assert tok.request_token(101).status in (C.STATUS_OK, C.STATUS_BLOCKED)
    deadline = time.monotonic() + 2
    while tok.peer_version < 2:
        assert time.monotonic() < deadline, "HELLO response not observed"
        time.sleep(0.01)


def test_request_batch_v2_end_to_end(tcp_cluster):
    """One BATCH frame, many flows: per-entry verdicts match the
    sequential semantics of the device column batcher (limit 3)."""
    server, tok, svc = tcp_cluster
    assert tok.request_token(101).ok  # also completes HELLO negotiation
    results = tok.request_batch([
        (C.BATCH_KIND_FLOW, 101, 1),
        (C.BATCH_KIND_FLOW_BATCH, 101, 5),
        (C.BATCH_KIND_FLOW, 101, 1),
        (C.BATCH_KIND_FLOW, 31337, 1),
    ])
    assert tok.peer_version == C.PROTOCOL_VERSION
    assert results[0].status == C.STATUS_OK
    # partial grant: 1 unit already spent above, 1 by entry 0 -> 1 left
    assert results[1].status == C.STATUS_OK and results[1].remaining == 1
    assert results[2].status == C.STATUS_BLOCKED
    # v3: the deny explains itself (_T_PROV rode the response)
    assert results[2].prov_kind == ERR.BLOCK_FLOW
    assert results[2].prov_rule == 101
    assert results[2].prov_limit == 3.0
    assert results[2].prov_observed is not None
    assert results[3].status == C.STATUS_NO_RULE


def test_batch_frames_carry_trace_context(tcp_cluster):
    """The 17-byte trace tail rides batched frames end to end: the
    client's cluster.rpc span for a BATCH exchange carries the ambient
    trace id, and the server echoes the context on the response."""
    from sentinel_tpu import obs
    from sentinel_tpu.obs import trace as OT

    server, tok, svc = tcp_cluster
    assert tok.request_token(101).ok  # negotiate v2 first
    obs.TRACER.reset()
    obs.enable()
    try:
        tid, sid = OT.new_trace_id(), OT.new_span_id()
        with OT.trace_ctx(tid, sid):
            tok.request_batch([(C.BATCH_KIND_FLOW, 101, 1)])
    finally:
        obs.disable()
    rpc = [s for s in obs.TRACER.snapshot() if s["name"] == "cluster.rpc"]
    assert rpc and rpc[-1]["trace"] == tid
    assert rpc[-1]["attrs"].get("type") == C.MSG_TYPE_BATCH


class _V1Server(threading.Thread):
    """Hand-rolled LEGACY token server: answers PING and FLOW frames and
    silently drops anything it does not know — exactly how the v1
    decoder treats a type-15 HELLO (decode error -> frame dropped)."""

    def __init__(self):
        super().__init__(daemon=True)
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(1)
        self.port = self.sock.getsockname()[1]
        self.seen_types = []
        self._halt = threading.Event()

    def run(self):
        self.sock.settimeout(2.0)
        try:
            conn, _ = self.sock.accept()
        except OSError:
            return
        reader = P.FrameReader()
        conn.settimeout(0.1)
        while not self._halt.is_set():
            try:
                data = conn.recv(4096)
            except socket.timeout:
                continue
            except OSError:
                break
            if not data:
                break
            for body in reader.feed(data):
                xid, t = struct.unpack_from(">iB", body, 0)
                self.seen_types.append(t)
                if t == C.MSG_TYPE_PING:
                    rsp = P.ClusterResponse(xid, t, C.STATUS_OK)
                elif t == C.MSG_TYPE_FLOW:
                    rsp = P.ClusterResponse(xid, t, C.STATUS_OK, remaining=1)
                else:
                    continue  # v1: unknown frame type is dropped
                try:
                    conn.sendall(P.encode_response(rsp))
                except OSError:
                    return
        conn.close()

    def stop(self):
        self._halt.set()
        self.sock.close()
        self.join(timeout=3)


def test_v1_server_keeps_client_on_v1_and_batches_pipeline():
    """Negotiation back-compat, server side: a legacy peer drops the
    HELLO, the client's reaper resolves it to v1 after timeout_ms, and
    request_batch transparently degrades to PIPELINED legacy frames on
    the same multiplexed socket — correct answers, no v2 frames sent."""
    v1 = _V1Server()
    v1.start()
    tok = ClusterTokenClient("127.0.0.1", v1.port, timeout_ms=300,
                             reconnect_interval_s=0.0)
    try:
        assert tok.request_token(5).status == C.STATUS_OK
        time.sleep(0.5)  # past the HELLO reaper: negotiation settled
        assert tok.peer_version == 1
        results = tok.request_batch([
            (C.BATCH_KIND_FLOW, 5, 1),
            (C.BATCH_KIND_FLOW, 6, 1),
        ])
        assert [r.status for r in results] == [C.STATUS_OK, C.STATUS_OK]
        assert C.MSG_TYPE_BATCH not in v1.seen_types
        assert C.MSG_TYPE_HELLO in v1.seen_types  # offered, ignored
    finally:
        tok.close()
        v1.stop()


def test_corrupt_batch_frame_fails_closed(tcp_cluster):
    """cluster.batch.frame chaos site: a structurally corrupted or
    truncated BATCH frame fails the WHOLE exchange closed — every entry
    STATUS_FAIL, no partial answers applied — and the connection keeps
    working after.  (The wire carries no checksum, so a flip inside the
    entry slab just decodes as a different ask; the strict-length
    contract is about frame STRUCTURE: header, count, slab size.  The
    seed is picked so the deterministic fault lands structurally.)"""
    from sentinel_tpu.chaos import failpoints as FP
    from sentinel_tpu.chaos.plans import FaultPlan, FaultSpec

    server, tok, svc = tcp_cluster
    assert tok.request_token(101).ok  # negotiate v2 first
    body_len = 7 + 2 * 14  # [xid:4][type:1][n:2] + 2 entries, no trace tail

    def _plan(action, seed):
        return FaultPlan(
            name=f"batch-{action}", seed=seed,
            faults=[FaultSpec("cluster.batch.frame", action, max_fires=1)],
        )

    def _pick_seed(action):
        for s in range(500):
            rng = _plan(action, s).spec_rng(0)
            if action == "corrupt":
                # flip must land in the header (type/count bytes) to be
                # structurally detectable
                if rng.randrange(body_len) in (4, 5, 6):
                    return s
            else:
                # cut must keep the xid readable so the server can send
                # the frame-level FAIL instead of forcing a 5 s timeout
                if rng.randrange(1, body_len) >= 4:
                    return s
        raise AssertionError(f"no structural seed for {action}")

    for action in ("corrupt", "short_read"):
        plan = _plan(action, _pick_seed(action))
        with FP.armed(plan) as st:
            results = tok.request_batch([
                (C.BATCH_KIND_FLOW, 101, 1),
                (C.BATCH_KIND_FLOW, 101, 1),
            ])
        assert st.injected().get(f"cluster.batch.frame:{action}") == 1
        assert all(r.status == C.STATUS_FAIL for r in results), action
    # the frame-level reject did not poison the connection or the budget
    r = tok.request_batch([(C.BATCH_KIND_FLOW, 101, 1)])
    assert r[0].status in (C.STATUS_OK, C.STATUS_BLOCKED)
