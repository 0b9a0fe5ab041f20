"""Continuous profiling plane (obs/profile.py): HBM memory ledger,
retrace observatory, bounded deep-profile capture, and the online
sketch-accuracy audit — plus the flight/postmortem rendering of the new
provider sections and the protocol-v2 wire byte-accounting regression."""

from __future__ import annotations

import dataclasses
import json
import time

import numpy as np
import pytest

import sentinel_tpu as st
from sentinel_tpu.chaos import failpoints as FP
from sentinel_tpu.chaos.plans import FaultPlan, FaultSpec
from sentinel_tpu.core.config import small_engine_config
from sentinel_tpu.obs import REGISTRY
from sentinel_tpu.obs import flight as FL
from sentinel_tpu.obs import profile as PROF
from sentinel_tpu.obs import slo as S
from sentinel_tpu.obs import trace as OT
from sentinel_tpu.obs.flight import FlightRecorder
from sentinel_tpu.obs.registry import MetricRegistry
from sentinel_tpu.ops import engine as E


def _metric(name, **labels):
    m = REGISTRY.get(name, labels or None)
    return float(m.value) if m is not None else 0.0


# -- memory ledger -----------------------------------------------------------


def test_ledger_set_track_drop_and_gauges():
    reg = MetricRegistry()
    led = PROF.MemoryLedger(registry=reg)
    with PROF.ledger_owner("unit-a"):
        led.set("rules", "tbl", 1024)
        n = led.track("windows", "gs", {"a": np.zeros((4, 8), np.float32)})
    assert n == 4 * 8 * 4
    assert led.pool_bytes("rules") == 1024
    assert led.pool_bytes("windows") == n
    assert led.total_bytes() == 1024 + n
    g = reg.get("sentinel_hbm_bytes", {"pool": "windows"})
    assert g is not None and float(g.value) == n
    # per-owner entries show up namespaced in the snapshot
    snap = led.snapshot()
    assert snap["entries"]["rules/unit-a:tbl"] == 1024
    assert snap["pools"]["windows"] == n
    with PROF.ledger_owner("unit-a"):
        led.drop("rules", "tbl")
    assert led.pool_bytes("rules") == 0
    assert float(reg.get("sentinel_hbm_bytes", {"pool": "rules"}).value) == 0


def test_ledger_drop_owner_scopes_by_owner_only():
    led = PROF.MemoryLedger(registry=MetricRegistry())
    with PROF.ledger_owner("owner-x"):
        led.set("sketch", "s", 100)
    with PROF.ledger_owner("owner-y"):
        led.set("sketch", "s", 200)
    assert led.pool_bytes("sketch") == 300
    led.drop_owner("owner-x")
    assert led.pool_bytes("sketch") == 200
    assert "sketch/owner-y:s" in led.snapshot()["entries"]


def test_ledger_capacity_checks_and_breaches():
    reg = MetricRegistry()
    led = PROF.MemoryLedger(registry=reg)

    def _c(name):
        m = reg.get(name)
        return float(m.value) if m is not None else 0.0

    # no capacity configured -> mutations don't count as checks
    led.set("wire", "a", 10)
    assert _c("sentinel_hbm_capacity_checks_total") == 0
    led.set_capacity(100)
    led.set("wire", "b", 20)  # 30 <= 100: check, no breach
    assert _c("sentinel_hbm_capacity_checks_total") == 1
    assert _c("sentinel_hbm_capacity_breaches_total") == 0
    led.set("tokens", "big", 500)  # 530 > 100: breach
    assert _c("sentinel_hbm_capacity_breaches_total") == 1
    snap = led.snapshot()
    assert snap["capacity_bytes"] == 100 and snap["in_breach"] is True


def test_ledger_reconcile_fails_open_and_has_fields():
    led = PROF.MemoryLedger(registry=MetricRegistry())
    led.set("rules", "r", 64)
    rec = led.reconcile()
    # must never raise on CPU-only processes; fields present even when
    # the backend offers no memory stats
    assert rec["total_bytes"] == 64
    assert "live_array_bytes" in rec and "unaccounted_bytes" in rec
    assert "device_memory_stats" in rec
    sect = led.flight_section()
    assert sect["pools"]["rules"] == 64


def test_tree_nbytes_counts_leaves():
    tree = {"a": np.zeros(10, np.int32), "b": (np.zeros(3, np.float64), 7)}
    assert PROF.tree_nbytes(tree) == 10 * 4 + 3 * 8


def test_client_ledger_pools_match_salsa_and_release_on_stop(client_factory):
    import sentinel_tpu.sketch.salsa as SA

    cfg = small_engine_config(
        max_resources=4, max_nodes=8, sketch_stats=True, sketch_width=256
    )
    c = client_factory(cfg=cfg, sketch_audit_k=4)
    snap = PROF.LEDGER.snapshot()
    mine = {
        k: v
        for k, v in snap["entries"].items()
        if f"/{c._ledger_name}:" in k
    }
    pools = {k.split("/", 1)[0] for k in mine}
    assert {"windows", "sketch"} <= pools
    # acceptance: the ledger's sketch pool agrees with the analytic
    # salsa footprint within 10%
    sketch_bytes = sum(v for k, v in mine.items() if k.startswith("sketch/"))
    want = SA.hbm_bytes(E.sketch_config(cfg))
    assert abs(sketch_bytes - want) <= 0.1 * want
    c.stop()
    snap2 = PROF.LEDGER.snapshot()
    assert not any(f"/{c._ledger_name}:" in k for k in snap2["entries"])


# -- retrace observatory -----------------------------------------------------


def test_retrace_names_the_changed_field():
    reg = MetricRegistry()
    ro = PROF.RetraceObservatory(registry=reg)
    rec = ro.observe("unit.fn", width=256, donate=True)
    assert rec["expected"] is True and rec["cause"] == "warmup"
    rec = ro.observe("unit.fn", width=512, donate=True)
    assert rec["expected"] is False
    assert "width" in rec["cause"] and "256" in rec["cause"]
    assert "512" in rec["cause"]
    assert ro.surprise_count() == 1
    m = reg.get(
        "sentinel_retraces_total", {"entry": "unit.fn", "expected": "false"}
    )
    assert m is not None and float(m.value) == 1


def test_retrace_diffs_frozen_dataclass_fields():
    ro = PROF.RetraceObservatory(registry=MetricRegistry())
    a = small_engine_config(sketch_stats=True, sketch_width=256)
    b = dataclasses.replace(a, sketch_width=512)
    ro.observe("unit.cfg", cfg=a)
    rec = ro.observe("unit.cfg", cfg=b)
    assert not rec["expected"]
    assert "sketch_width" in rec["cause"]


def test_retrace_expected_context_suppresses_surprise():
    ro = PROF.RetraceObservatory(registry=MetricRegistry())
    ro.observe("unit.ctx", n=1)
    with PROF.expected_retrace("test-resize"):
        rec = ro.observe("unit.ctx", n=2)
    assert rec["expected"] is True and rec["reason"] == "test-resize"
    assert ro.surprise_count() == 0


def test_retrace_compile_ms_histogram_and_flight_section():
    reg = MetricRegistry()
    ro = PROF.RetraceObservatory(registry=reg)
    ro.observe("unit.h", x=1)
    ro.observe_compile_ms("unit.h", 12.5)
    h = reg.get("sentinel_compile_ms", {"entry": "unit.h"})
    assert h is not None
    sect = ro.flight_section()
    assert sect["total_seen"] == 1 and sect["surprises"] == 0
    assert sect["recent"][-1]["entry"] == "unit.h"


def test_engine_tick_retrace_journal_steady_state_and_config_change(client):
    """Acceptance: a warmed client shows zero surprise retraces under
    steady-state ticks; an induced config change journals exactly one
    surprise whose cause names the changed field."""
    base = PROF.RETRACE.surprise_count()
    for i in range(8):
        with client.entry(f"rt-{i % 3}"):
            pass
    assert PROF.RETRACE.surprise_count() == base
    # induced: same entry key, one changed EngineConfig field.  Two
    # expected warmups (unique shapes), then the surprise.
    cfg_a = small_engine_config(max_resources=7, max_nodes=13)
    cfg_b = dataclasses.replace(
        cfg_a, second_window_ms=cfg_a.second_window_ms + 500
    )
    with PROF.expected_retrace("test-setup"):
        E.make_tick(cfg_a)
    E.make_tick(cfg_b)
    assert PROF.RETRACE.surprise_count() == base + 1
    last = [r for r in PROF.RETRACE.recent() if not r["expected"]][-1]
    assert last["entry"] == "engine.tick"
    assert "second_window_ms" in last["cause"]


#: a ladder of tick shapes -> (batch rows a side, its shapes, the acquire and
#: completion rows that take each rung).  "uneven": the acquire side is too
#: narrow for a middle rung of its own (1,024 // 4 = 256, the light one's)
#: and the completion side is not, so only completions reach the middle shape
_LADDERS = {
    "even": (
        (4096, 4096), ((256, 256), (1024, 1024), (4096, 4096)),
        ((4, 4), (300, 300), (4096, 4096)),
    ),
    "uneven": (
        (1024, 2048), ((256, 256), (256, 512), (1024, 2048)),
        ((4, 4), (4, 300), (300, 600)),
    ),
}


@pytest.fixture(scope="module", params=list(_LADDERS))
def ladder_client(request):
    """A started threaded client whose batch is wide enough for the whole
    ladder of tick shapes: light, middle, full."""
    from sentinel_tpu.runtime.client import SentinelClient

    (b, b2), _shapes, _rows = _LADDERS[request.param]
    c = SentinelClient(
        cfg=small_engine_config(batch_size=b, complete_batch_size=b2),
        mode="threaded", entry_timeout_s=60.0,
    )
    c.start()
    yield c, request.param
    c.stop()


@pytest.mark.parametrize("rung", [0, 1, 2], ids=["light", "middle", "full"])
def test_start_compiles_every_tick_shape_and_serving_compiles_none(ladder_client, rung):
    """``start()`` leaves the tick compiled for every shape of the ladder,
    also where a rung is wider than the one below on one side only; a tick
    of each shape, acquire and completion side, then serves from those
    executables, at the smallest shape that holds it, with no retrace
    journalled."""
    from sentinel_tpu.ops import wire as WIRE

    c, ladder = ladder_client
    _batch, want, rows = _LADDERS[ladder]
    shapes = WIRE.tick_shapes(c.cfg)
    assert shapes == want
    assert c._tick._cache_size() == len(shapes)
    journalled = len(PROF.RETRACE.recent())
    n, n2 = rows[rung]
    ids = np.full(max(n, n2), c.registry.resource_id("shapes/r"), np.int32)
    OT.TRACER.reset()
    OT.TRACER.enable()
    try:
        verdicts, _waits = c.submit_block(ids[:n]).result(timeout=60.0)
        assert len(verdicts) == n
        c.submit_completion_block(ids[:n2], np.ones(n2, np.float32))
        c.submit_block(ids[:1]).result(timeout=60.0)  # the completions are in by now
    finally:
        OT.TRACER.disable()
    ran = {
        (s["attrs"]["b"], s["attrs"]["b2"])
        for s in OT.TRACER.snapshot() if s["name"] == "tick.assemble"
    }
    # the block's tick and the completions' (one tick or two); the rest light
    assert shapes[rung] in ran and ran <= {shapes[0], shapes[rung]}
    assert c._tick._cache_size() == len(shapes)
    assert len(PROF.RETRACE.recent()) == journalled


# -- deep-profile capture ----------------------------------------------------


def _reset_capture_clock():
    PROF._LAST_CAPTURE[0] = 0.0


def test_capture_profile_ok_and_clamped():
    _reset_capture_clock()
    assert not OT.TRACER.enabled
    before = _metric("sentinel_profile_captures_total", result="ok")

    def _sleep(s):
        # the tracer must be live inside the window
        assert OT.TRACER.enabled
        with OT.TRACER.span("unit.captured"):
            time.sleep(0.001)

    cap = PROF.capture_profile(ms=0.0, min_interval_s=0.0, sleep=_sleep)
    assert cap["ms"] == PROF.MIN_CAPTURE_MS  # clamped up
    assert cap["span_count"] >= 1
    trace = json.loads(cap["chrome_trace"]) if isinstance(
        cap["chrome_trace"], str
    ) else cap["chrome_trace"]
    assert trace  # non-empty chrome payload
    assert not OT.TRACER.enabled  # prior state restored
    assert _metric("sentinel_profile_captures_total", result="ok") == before + 1


def test_capture_profile_rate_limited():
    _reset_capture_clock()
    before = _metric("sentinel_profile_captures_total", result="rate_limited")
    ok = PROF.capture_profile(ms=1.0, min_interval_s=0.0, sleep=lambda s: None)
    assert "chrome_trace" in ok
    cap = PROF.capture_profile(ms=1.0, min_interval_s=60.0, sleep=lambda s: None)
    assert cap["error"] == "rate_limited" and cap["retry_after_s"] > 0
    assert (
        _metric("sentinel_profile_captures_total", result="rate_limited")
        == before + 1
    )
    _reset_capture_clock()


def test_capture_profile_fails_open_and_restores_tracer():
    _reset_capture_clock()
    before = _metric("sentinel_profile_captures_total", result="error")
    assert not OT.TRACER.enabled
    plan = FaultPlan(
        name="capture-fail",
        seed=1,
        faults=[
            FaultSpec(
                "obs.profile.capture",
                "raise",
                burst_start=0,
                burst_len=1,
                exc="RuntimeError",
            )
        ],
    )
    with FP.armed(plan):
        cap = PROF.capture_profile(
            ms=1.0, min_interval_s=0.0, sleep=lambda s: None
        )
    assert "error" in cap and cap["error"] != "rate_limited"
    assert not OT.TRACER.enabled  # fail OPEN: prior state restored
    assert (
        _metric("sentinel_profile_captures_total", result="error") == before + 1
    )


def test_api_profile_and_memory_endpoints(client):
    from sentinel_tpu.transport import build_default_handlers
    from sentinel_tpu.transport.command import CommandRequest

    _reset_capture_clock()
    registry = build_default_handlers(client)
    rsp = registry.handle(
        "api/profile", CommandRequest(parameters={"ms": "1"})
    )
    assert rsp.success and "chrome_trace" in rsp.result
    rsp = registry.handle("api/memory", CommandRequest(parameters={}))
    assert rsp.success and "pools" in rsp.result
    _reset_capture_clock()


# -- online sketch-accuracy audit --------------------------------------------


def _audit(k=2, period=1, **kw):
    kw.setdefault("node_rows", 8)
    kw.setdefault("window_ms", 1000)
    kw.setdefault("sample_count", 2)
    kw.setdefault("slack_buckets", 1)
    kw.setdefault("width", 256)
    kw.setdefault("registry", MetricRegistry())
    return PROF.SketchAudit(k=k, period=period, **kw)


def _vals(a):
    return {
        "checks": int(a._c_checks.value),
        "under": int(a._c_under.value),
        "eps": int(a._c_eps.value),
        "fail": int(a._c_fail.value),
    }


def test_audit_tracks_sketch_ids_only_and_counts_checks():
    a = _audit(k=4)
    res = np.asarray([2, 9, 10, 9], np.int32)  # row 2 is exact-tier
    cnt = np.asarray([5, 3, 7, 1], np.int32)
    a.observe(1_000, res, cnt)  # fold only (nothing tracked at audit time)
    assert set(a._tracked) == {9, 10}
    a.observe(1_050, res, cnt, reader=lambda rids, t: [100, 100])
    v = _vals(a)
    assert v["checks"] == 2 and v["fail"] == 0
    # volume counts ALL valid rows, exact tier included
    assert a._vol[1] == 2 * (5 + 3 + 7 + 1)


def test_audit_underestimate_detected():
    a = _audit(k=1)
    res = np.asarray([9], np.int32)
    cnt = np.asarray([10], np.int32)
    a.observe(1_000, res, cnt)
    a.observe(1_100, res, cnt)
    # shadow has 20 in-window; a reader at 5 breaks overestimate-only
    a.observe(1_200, res, cnt, reader=lambda rids, t: [5])
    v = _vals(a)
    assert v["under"] == 1 and v["checks"] == 1 and v["eps"] == 0


def test_audit_slack_only_overestimate_is_not_eps_violation():
    """Regression (slack windows, PR 14): an estimate above the bare
    window but inside the slack-widened exact bound + eps budget is
    journaled as overestimate magnitude, NOT as an eps violation."""
    a = _audit(k=1)  # slack_buckets stored = 1 + 1 guard = 2
    res = np.asarray([9], np.int32)
    cnt = np.asarray([10], np.int32)
    for t in (1_000, 2_000, 3_000):  # buckets w=1,2,3 get 10 each
        a.observe(t, res, cnt)
    # audit at w=4: bare window (2,4] holds only w3 = 10; slack span
    # (0,4] holds w1+w2+w3 = 30.  A reader at 30 models a sketch that
    # hasn't expired the slack buckets yet: overestimate vs the bare
    # window, legal vs the slack bound.
    a.observe(4_500, res, cnt, reader=lambda rids, t: [30])
    v = _vals(a)
    assert v["eps"] == 0 and v["under"] == 0 and v["checks"] == 1
    assert a._last_audit["eps_violations"] == 0
    # the magnitude IS observed (30 - 10 = 20 lands in the histogram)
    h = a._h_err
    assert h.count >= 1


def test_audit_eps_violation_beyond_slack_and_budget():
    a = _audit(k=1)
    res = np.asarray([9], np.int32)
    cnt = np.asarray([10], np.int32)
    for t in (1_000, 2_000, 3_000):
        a.observe(t, res, cnt)
    # slack bound 30, eps budget = e/256 * 30 ~ 0.32 -> 500 violates
    a.observe(4_500, res, cnt, reader=lambda rids, t: [500])
    v = _vals(a)
    assert v["eps"] == 1 and v["under"] == 0
    assert a._last_audit["eps_violations"] == 1


def test_audit_uncovered_resource_skips_eps_check():
    # stale sketch state: shadow may be incomplete for ids seen before
    a = _audit(k=1, fresh_state=False)
    res = np.asarray([9], np.int32)
    cnt = np.asarray([10], np.int32)
    a.observe(1_000, res, cnt)
    # first fold at w=1 > hi_min -> not covered; a huge estimate could
    # be pre-tracking history, so no eps verdict (underestimates still
    # impossible to hit here: est >= 0 never < shadow when shadow small)
    a.observe(1_100, res, cnt, reader=lambda rids, t: [10_000])
    v = _vals(a)
    assert v["eps"] == 0 and v["checks"] == 1


def test_audit_trash_row_excluded_from_volume():
    a = _audit(k=2, trash_row=63)
    res = np.asarray([63, 2, 9], np.int32)
    cnt = np.asarray([5, 7, 11], np.int32)
    a.observe(1_000, res, cnt)
    assert a._vol[1] == 7 + 11  # trash row's 5 excluded, exact row kept
    assert set(a._tracked) == {9}


def test_audit_rotation_retires_oldest():
    a = _audit(k=1, period=4, rotate_every=4)
    res_a = np.asarray([9], np.int32)
    res_b = np.asarray([10], np.int32)
    one = np.asarray([1], np.int32)
    for i in range(3):
        a.observe(1_000 + i, res_a, one)
    assert set(a._tracked) == {9}
    # 4th tick: k is full, ticks % rotate_every == 0 -> 10 replaces 9
    a.observe(1_003, res_b, one)
    assert set(a._tracked) == {10}


def test_audit_fails_open_on_raising_reader():
    a = _audit(k=1)
    res = np.asarray([9], np.int32)
    cnt = np.asarray([1], np.int32)
    a.observe(1_000, res, cnt)

    def boom(rids, t):
        raise RuntimeError("reader exploded")

    a.observe(1_100, res, cnt, reader=boom)  # must not raise
    v = _vals(a)
    assert v["fail"] == 1 and v["checks"] == 0
    # and the audit keeps working afterwards
    a.observe(1_200, res, cnt, reader=lambda rids, t: [100])
    assert _vals(a)["checks"] == 1


def test_audit_shadow_failpoint_fails_open():
    a = _audit(k=1)
    res = np.asarray([9], np.int32)
    cnt = np.asarray([1], np.int32)
    plan = FaultPlan(
        name="audit-fail",
        seed=1,
        faults=[
            FaultSpec(
                "sketch.audit.shadow",
                "raise",
                burst_start=0,
                burst_len=2,
                exc="RuntimeError",
            )
        ],
    )
    with FP.armed(plan):
        a.observe(1_000, res, cnt)
        a.observe(1_100, res, cnt)
    assert _vals(a)["fail"] == 2
    assert not a._tracked  # folds were skipped, nothing admitted
    a.observe(1_200, res, cnt)  # heals once disarmed
    assert set(a._tracked) == {9}


def test_audit_disabled_mode_under_five_micros():
    a = _audit(k=0)
    res = np.asarray([9], np.int32)
    cnt = np.asarray([1], np.int32)
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        a.observe(1_000, res, cnt)
    elapsed = time.perf_counter() - t0
    assert elapsed / n < 5e-6, f"disarmed audit costs {elapsed / n * 1e6:.2f}us"
    assert a._ticks == 0  # truly disarmed: no state mutated


def test_client_online_audit_end_to_end(client_factory, vt):
    """The wired path: sketch-tier client with the audit on — checks
    accumulate, the overestimate-only and eps invariants hold, and the
    flight bundle carries the audit section."""
    cfg = small_engine_config(
        max_resources=4, max_nodes=8, sketch_stats=True, sketch_width=256
    )
    c = client_factory(cfg=cfg, sketch_audit_k=4, sketch_audit_period=2)
    before = {
        "checks": _metric("sentinel_sketch_audit_checks_total"),
        "under": _metric("sentinel_sketch_underestimates_total"),
        "eps": _metric("sentinel_sketch_eps_violations_total"),
        "fail": _metric("sentinel_sketch_audit_failures_total"),
    }
    for i in range(40):
        with c.entry(f"audit-res-{i % 12}"):
            vt.advance(5)
    assert _metric("sentinel_sketch_audit_checks_total") > before["checks"]
    assert _metric("sentinel_sketch_underestimates_total") == before["under"]
    assert _metric("sentinel_sketch_eps_violations_total") == before["eps"]
    assert _metric("sentinel_sketch_audit_failures_total") == before["fail"]
    b = FL.FLIGHT.dump_bundle(reason="unit-audit")
    sect = b["providers"]["audit"]
    assert sect["k"] == 4 and sect["tracked"] >= 1
    assert sect["checks"] >= 1 and sect["underestimates"] == 0


# -- flight bundles + postmortem rendering -----------------------------------


def test_flight_bundle_has_memory_and_retrace_sections(client):
    b = FL.FLIGHT.dump_bundle(reason="unit-profile")
    mem = b["providers"]["memory"]
    assert set(mem["pools"]) <= set(PROF.MemoryLedger.POOLS)
    assert {"rules", "windows"} <= set(mem["pools"])
    assert mem["total_bytes"] >= 0
    rt = b["providers"]["retrace"]
    assert "surprises" in rt and "recent" in rt


def test_postmortem_renders_profiling_provider_sections(tmp_path, capsys):
    from sentinel_tpu.obs.__main__ import main

    fr = FlightRecorder(capacity=8)
    fr.register_provider("memory", PROF.LEDGER.flight_section)
    fr.register_provider("retrace", PROF.RETRACE.flight_section)
    a = _audit(k=1)
    fr.register_provider("audit", a.flight_section)
    a.observe(1_000, np.asarray([9], np.int32), np.asarray([3], np.int32))
    b = fr.dump_bundle(reason="unit-postmortem")
    p = tmp_path / "bundle.json"
    p.write_text(json.dumps(b))
    assert main(["--postmortem", str(p)]) == 0
    out = capsys.readouterr().out
    assert "provider [memory]" in out
    assert "provider [retrace]" in out
    assert "provider [audit]" in out
    assert "unit-postmortem" in out


def test_eps_violation_slo_alert_bundles_with_profiling_sections():
    """Satellite: a firing sketch_eps SLO burn auto-bundles, and the
    bundle carries the memory/retrace sections alongside the slo one."""
    reg, greg = MetricRegistry(), MetricRegistry()
    fl = FlightRecorder()
    fl.register_provider("memory", PROF.LEDGER.flight_section)
    fl.register_provider("retrace", PROF.RETRACE.flight_section)
    checks = reg.counter("sentinel_sketch_audit_checks_total", "c")
    eps = reg.counter("sentinel_sketch_eps_violations_total", "e")
    spec = [s for s in S.default_slos() if s.name == "sketch_eps"][0]
    eng = S.SloEngine(
        specs=(spec,), registry=reg, flight=fl, gauge_registry=greg
    )
    checks.inc(100)
    st0 = eng.step(0)[0]
    assert not st0.alerting
    checks.inc(1000)
    eng.step(60_000)
    # 40% violation rate >> the 1% budget -> both windows burn
    checks.inc(1000)
    eps.inc(400)
    st1 = eng.step(120_000)[0]
    assert st1.fired and st1.alerting
    b = fl.last_bundle()
    assert b is not None and b["reason"] == "slo-burn-sketch_eps"
    assert b["providers"]["slo"]["sketch_eps"]["alerting"] is True
    assert "memory" in b["providers"] and "retrace" in b["providers"]
    eng.close()


# -- protocol-v2 wire byte accounting ----------------------------------------


def _wire(direction):
    return _metric(
        "sentinel_wire_bytes_total", path="cluster", direction=direction
    )


def _frames(direction):
    return _metric("sentinel_cluster_batch_frames_total", direction=direction)


def test_wire_bytes_account_every_v2_frame_kind_exactly():
    """Coverage audit (PR 13 protocol v2): every encode/decode on the
    cluster path moves sentinel_wire_bytes_total by exactly len(frame)
    — prefix included — for request, response, batch-request and
    batch-response frames, traced variants included."""
    from sentinel_tpu.cluster import constants as C
    from sentinel_tpu.cluster import protocol as P

    reqs = [
        P.ClusterRequest(xid=1, type=C.MSG_TYPE_PING),
        P.ClusterRequest(
            xid=2, type=C.MSG_TYPE_FLOW, flow_id=77, count=3, priority=True
        ),
        P.ClusterRequest(
            xid=3,
            type=C.MSG_TYPE_PARAM_FLOW,
            flow_id=9,
            count=1,
            params=["user", "42"],
        ),
        # traced variant: the 17-byte trace tail must be accounted too
        P.ClusterRequest(
            xid=4,
            type=C.MSG_TYPE_LEASE,
            flow_id=5,
            count=2,
            trace_id=0xDEADBEEF,
            span_id=0xFEED,
        ),
    ]
    for req in reqs:
        tx0, rx0 = _wire("tx"), _wire("rx")
        f = P.encode_request(req)
        assert _wire("tx") - tx0 == len(f)
        got = P.decode_request(f[2:])
        assert _wire("rx") - rx0 == len(f)
        assert (got.xid, got.type, got.flow_id, got.count) == (
            req.xid,
            req.type,
            req.flow_id,
            req.count,
        )
        assert got.params == req.params and got.trace_id == req.trace_id

    rsps = [
        P.ClusterResponse(xid=1, type=C.MSG_TYPE_FLOW, status=C.STATUS_OK),
        P.ClusterResponse(
            xid=2,
            type=C.MSG_TYPE_FLOW,
            status=C.STATUS_OK,
            remaining=41,
            wait_ms=7,
            trace_id=0xBEEF,
            span_id=0x17,
        ),
    ]
    for rsp in rsps:
        tx0, rx0 = _wire("tx"), _wire("rx")
        f = P.encode_response(rsp)
        assert _wire("tx") - tx0 == len(f)
        got = P.decode_response(f[2:])
        assert _wire("rx") - rx0 == len(f)
        assert (got.xid, got.status, got.remaining, got.wait_ms) == (
            rsp.xid,
            rsp.status,
            rsp.remaining,
            rsp.wait_ms,
        )


def test_wire_bytes_account_batch_frames_and_frame_counters():
    from sentinel_tpu.cluster import constants as C
    from sentinel_tpu.cluster import protocol as P

    n = 3
    breq = P.ClusterBatchRequest(
        xid=11,
        kinds=np.asarray(
            [C.BATCH_KIND_FLOW, C.BATCH_KIND_FLOW_BATCH, C.BATCH_KIND_LEASE],
            np.uint8,
        ),
        ids=np.asarray([101, 102, 103], np.int64),
        counts=np.asarray([1, 4, 2], np.int32),
        flags=np.asarray([0, 1, 0], np.uint8),
        trace_id=0xABCD,
        span_id=0x99,
    )
    tx0, rx0 = _wire("tx"), _wire("rx")
    ftx0, frx0 = _frames("tx"), _frames("rx")
    f = P.encode_batch_request(breq)
    assert _wire("tx") - tx0 == len(f)
    got = P.decode_batch_request(f[2:])
    assert _wire("rx") - rx0 == len(f)
    assert _frames("tx") - ftx0 == 1 and _frames("rx") - frx0 == 1
    assert got.xid == breq.xid and got.trace_id == breq.trace_id
    np.testing.assert_array_equal(got.kinds, breq.kinds)
    np.testing.assert_array_equal(got.ids, breq.ids)
    np.testing.assert_array_equal(got.counts, breq.counts)

    brsp = P.ClusterBatchResponse(
        xid=11,
        status=C.STATUS_OK,
        statuses=np.zeros(n, np.int8),
        remainings=np.asarray([9, 8, 7], np.int32),
        waits=np.zeros(n, np.int32),
        token_ids=np.asarray([0, 0, 555], np.int64),
    )
    tx0, rx0 = _wire("tx"), _wire("rx")
    ftx0, frx0 = _frames("tx"), _frames("rx")
    f = P.encode_batch_response(brsp)
    assert _wire("tx") - tx0 == len(f)
    got = P.decode_batch_response(f[2:])
    assert _wire("rx") - rx0 == len(f)
    assert _frames("tx") - ftx0 == 1 and _frames("rx") - frx0 == 1
    np.testing.assert_array_equal(got.remainings, brsp.remainings)
    np.testing.assert_array_equal(got.token_ids, brsp.token_ids)
