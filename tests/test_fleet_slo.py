"""Fleet metric aggregation (obs/fleet.py), histogram exemplars, and the
SLO burn-rate engine (obs/slo.py) — incl. the cross-process registry
merge contract: counter sums, correct merged-histogram quantiles, and no
double-count of the scraping process."""

from __future__ import annotations

import re

import numpy as np
import pytest

from sentinel_tpu.obs import fleet as F
from sentinel_tpu.obs import slo as S
from sentinel_tpu.obs.flight import FlightRecorder
from sentinel_tpu.obs.registry import MetricRegistry

# the token services built here are closed when the module ends (tests/conftest.py)
pytestmark = pytest.mark.usefixtures("closes_token_services")

#: the exposition-lines grammar the repo pins (tests/test_obs.py)
_LINE_PAT = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9][0-9a-zA-Z+.e-]*$"
)


def _assert_wellformed(text: str) -> None:
    for line in text.strip().split("\n"):
        if line.startswith("#"):
            assert line.startswith(("# HELP ", "# TYPE ", "# EXEMPLAR ")), line
        else:
            assert _LINE_PAT.match(line), line


def _member_registry(i: int, hot: int = 0) -> MetricRegistry:
    """A synthetic per-process registry: scrape id, per-shard counters,
    a shared counter, a histogram (``hot`` samples land at 100 ms)."""
    r = MetricRegistry()
    r.gauge("sentinel_scrape_id", "id", labels={"id": f"proc-{i}"}).set(1)
    r.counter(
        "sentinel_shard_requests_total", "reqs", labels={"shard": f"shard-{i}"}
    ).inc(100 * (i + 1))
    r.counter("sentinel_token_decisions_total", "dec").inc(7)
    h = r.histogram("sentinel_cluster_rpc_ms", "rpc")
    for _ in range(100 - hot):
        h.observe(1.0)
    for _ in range(hot):
        h.observe(100.0)
    r.gauge("sentinel_pipeline_occupancy", "occ").set(float(i))
    return r


# ---------------------------------------------------------------------------
# exemplars
# ---------------------------------------------------------------------------


def test_histogram_exemplar_in_exposition_and_snapshot():
    r = MetricRegistry()
    h = r.histogram("sentinel_tick_device_ms", "dev")
    for _ in range(99):
        h.observe(1.0)
    h.observe(200.0, exemplar="deadbeef123")
    text = r.exposition()
    ex = [l for l in text.splitlines() if l.startswith("# EXEMPLAR ")]
    assert len(ex) == 1
    assert "trace_id=deadbeef123" in ex[0]
    assert "sentinel_tick_device_ms_bucket" in ex[0]
    _assert_wellformed(text)
    e = h.p99_exemplar()
    assert e is not None and e["trace_id"] == "deadbeef123"
    assert e["value"] == 200.0
    snap = r.snapshot()
    assert snap["sentinel_tick_device_ms"]["p99_exemplar"]["trace_id"] == (
        "deadbeef123"
    )


def test_histogram_without_exemplars_emits_no_comment():
    """No exemplar recorded => exposition byte-identical to the golden
    shape (guards test_prometheus_exposition_golden)."""
    r = MetricRegistry()
    h = r.histogram("plain_ms", "p")
    h.observe(1.0)
    assert "# EXEMPLAR" not in r.exposition()
    assert h.p99_exemplar() is None


def test_stage_helpers_thread_trace_id_as_exemplar():
    from sentinel_tpu import obs
    from sentinel_tpu.obs import trace as OT

    r = MetricRegistry()
    h = r.histogram("sentinel_tick_device_ms", "dev")
    was = OT.TRACER.enabled
    obs.enable()
    try:
        t = OT.t0()
        OT.stage_ns("tick.device", t, 2_000_000, h, trace=0xABC123)
    finally:
        if not was:
            obs.disable()
    e = h.p99_exemplar()
    assert e is not None and e["trace_id"] == "abc123"


def test_postmortem_prints_p99_exemplars(tmp_path):
    """A flight bundle whose metrics carry a p99 exemplar surfaces the
    trace id in --postmortem output (the Perfetto jump-off point)."""
    import io
    import json

    from sentinel_tpu.obs.__main__ import _print_postmortem

    bundle = {
        "kind": "sentinel-flight-bundle",
        "reason": "test",
        "pid": 1,
        "captured_wall_ms": 0,
        "captured_mono_ns": 0,
        "journal": [],
        "metrics": {
            "sentinel_tick_device_ms": {
                "count": 100,
                "sum": 300.0,
                "p50": 1.0,
                "p99": 256.0,
                "p99_exemplar": {"le": "256", "value": 200.0, "trace_id": "feed1"},
            }
        },
        "spans": [],
        "providers": {},
    }
    p = tmp_path / "b.json"
    p.write_text(json.dumps(bundle))
    out = io.StringIO()
    _print_postmortem(str(p), out=out)
    text = out.getvalue()
    assert "p99 exemplars" in text and "trace_id=feed1" in text


# ---------------------------------------------------------------------------
# fleet merge (cross-process registry merge contract)
# ---------------------------------------------------------------------------


def test_fleet_merge_counter_sums_and_histogram_quantiles():
    texts = [_member_registry(i, hot=50 * i).exposition() for i in range(3)]
    merged = F.merge_scrapes([F.parse_exposition(t) for t in texts])
    assert merged.members == 3 and merged.duplicates == 0
    out = F.render_exposition(merged)
    _assert_wellformed(out)
    # per-shard labels preserved, per-series counters intact
    assert 'sentinel_shard_requests_total{shard="shard-0"} 100' in out
    assert 'sentinel_shard_requests_total{shard="shard-2"} 300' in out
    # same-series counters sum across processes
    assert "sentinel_token_decisions_total 21" in out
    # gauges: conservative max
    assert "sentinel_pipeline_occupancy 2" in out
    # histogram quantile over the MERGED buckets: 300 samples, 150 slow
    # -> p50 in the 1 ms bucket region, p99 in the 100 ms region
    back = F.parse_exposition(out)
    h = back.hists[("sentinel_cluster_rpc_ms", ())]
    assert h["count"] == 300
    assert h["sum"] == pytest.approx(150 * 1.0 + 150 * 100.0)
    # merged cumulative buckets: ~half the mass sits at/below 1 ms, all
    # of it at/below the top bucket — the quantile split survived
    by_bound = sorted(h["buckets"].items(), key=lambda kv: F._le_sort_key(kv[0]))
    le_1ms = next(cum for le, cum in by_bound if float(le) >= 1.0)
    assert le_1ms == 150
    assert by_bound[-1][1] == 300


def test_fleet_merge_drops_same_process_duplicate():
    """The scraping process's own exposition listed as a fleet member
    must merge exactly once (scrape-id dedupe)."""
    t = _member_registry(0).exposition()
    merged = F.merge_scrapes([F.parse_exposition(t), F.parse_exposition(t)])
    assert merged.members == 1 and merged.duplicates == 1
    out = F.render_exposition(merged)
    assert 'sentinel_shard_requests_total{shard="shard-0"} 100' in out
    assert "sentinel_scrape_id" not in out


def test_fleet_exposition_counts_errors_and_members():
    t1 = _member_registry(1).exposition()

    def fetch(url):
        if "dead" in url:
            raise OSError("connection refused")
        return t1

    text = F.fleet_exposition(targets=["peer:1", "dead:2"], fetch=fetch)
    _assert_wellformed(text)
    assert "sentinel_fleet_members 2" in text  # local + peer
    assert "sentinel_fleet_scrape_errors 1" in text


def test_fleet_target_registry_and_env(monkeypatch):
    F.set_fleet_targets([])
    F.add_fleet_target("a:1")
    F.add_fleet_target("a:1")  # idempotent
    monkeypatch.setenv("SENTINEL_FLEET_TARGETS", "b:2, a:1")
    assert F.fleet_targets() == ["a:1", "b:2"]
    F.set_fleet_targets([])
    assert F._normalize_url("a:1") == "http://a:1/metrics"
    assert F._normalize_url("http://a:1/metrics") == "http://a:1/metrics"


def test_metrics_fleet_param_over_live_n4_fleet(client_factory):
    """Acceptance: GET /metrics?fleet=1 over a live N=4 ShardFleet
    returns ONE well-formed exposition with per-shard labels preserved
    and remote histograms merged in."""
    from sentinel_tpu.cluster.shard import ShardFleet
    from sentinel_tpu.core.rules import FlowRule
    from sentinel_tpu.transport.command import CommandRequest
    from sentinel_tpu.transport.handlers import build_default_handlers

    f = ShardFleet(
        client_factory,
        n_shards=4,
        retry_interval_s=300.0,
        timeout_ms=5000,
        reconnect_interval_s=0.0,
    )
    try:
        f.load_flow_rules(
            "default",
            [
                FlowRule(
                    resource=f"res-{fid}",
                    count=1000.0,
                    cluster_mode=True,
                    cluster_flow_id=fid,
                    cluster_threshold_type=1,
                )
                for fid in (101, 202, 303, 404)
            ],
        )
        for fid in (101, 202, 303, 404):
            f.client.request_token(fid)
        # a "remote engine host" target answers with its own registry
        remote = _member_registry(9, hot=10).exposition()
        from sentinel_tpu.obs import fleet as FM

        FM.set_fleet_targets(["remote-host:8719"])
        try:
            registry = build_default_handlers(f.services["shard-0"].client)
            orig_fetch = FM._http_fetch
            FM._http_fetch = lambda url, timeout_s=2.0: remote
            try:
                rsp = registry.handle(
                    "metrics", CommandRequest(parameters={"fleet": "1"})
                )
            finally:
                FM._http_fetch = orig_fetch
        finally:
            FM.set_fleet_targets([])
        assert rsp.success
        text = rsp.result
        _assert_wellformed(text)
        assert "sentinel_fleet_members 2" in text
        # per-shard labels from all four LIVE shards survive the merge
        for name in ("shard-0", "shard-1", "shard-2", "shard-3"):
            assert f'shard="{name}"' in text, name
        # the remote member's shard label and histogram merged in
        assert 'shard="shard-9"' in text
        assert "sentinel_cluster_rpc_ms_bucket" in text
        # live topology decoration from /api/shards
        assert "sentinel_fleet_shard_info" in text
    finally:
        f.stop()


# ---------------------------------------------------------------------------
# SLO engine
# ---------------------------------------------------------------------------


def _shed_spec() -> S.SloSpec:
    return S.SloSpec(
        "shed_ratio",
        objective=0.99,
        bad=S.CounterSum(("sentinel_shed_total",)),
        total=S.CounterSum(
            ("sentinel_shed_total", "sentinel_device_verdicts_total")
        ),
    )


def test_slo_burn_alert_fires_bundles_and_clears():
    reg, greg = MetricRegistry(), MetricRegistry()
    fl = FlightRecorder()
    good = reg.counter(
        "sentinel_device_verdicts_total", "v", labels={"verdict": "pass"}
    )
    shed = reg.counter(
        "sentinel_shed_total", "s", labels={"stage": "admit", "reason": "queue_full"}
    )
    eng = S.SloEngine(
        specs=(_shed_spec(),), registry=reg, flight=fl, gauge_registry=greg
    )
    good.inc(100)
    st = eng.step(0)[0]
    assert not st.alerting and st.budget_remaining == 1.0
    good.inc(1000)
    st = eng.step(60_000)[0]
    assert not st.alerting and not st.fired
    # storm: 40% shed >> the 1% budget -> both windows burn >= 14.4
    good.inc(600)
    shed.inc(400)
    st = eng.step(120_000)[0]
    assert st.fired and st.alerting
    assert max(st.burn.values()) > 14.4
    assert st.budget_remaining < 1.0
    # journal + auto bundle + provider section
    b = fl.last_bundle()
    assert b is not None and b["reason"] == "slo-burn-shed_ratio"
    assert "slo" in b["providers"]
    assert b["providers"]["slo"]["shed_ratio"]["alerting"] is True
    assert [e for e in fl.events() if e["kind"] == "slo.alert"]
    # a second breached step must NOT re-fire (alert is a transition)
    good.inc(60)
    shed.inc(40)
    st = eng.step(180_000)[0]
    assert st.alerting and not st.fired
    # calm traffic clears on the short windows
    good.inc(5000)
    st = eng.step(4_000_000)[0]
    assert not st.alerting
    assert [e for e in fl.events() if e["kind"] == "slo.alert.clear"]
    # gauges on the (injected) gauge registry
    burn = greg.get(
        "sentinel_slo_burn_rate", {"slo": "shed_ratio", "window": "300s"}
    )
    assert burn is not None
    assert greg.get("sentinel_slo_budget_remaining", {"slo": "shed_ratio"}) is not None
    eng.close()


def test_slo_latency_spec_histogram_over():
    reg, greg = MetricRegistry(), MetricRegistry()
    fl = FlightRecorder()
    h = reg.histogram("sentinel_tick_device_ms", "d")
    spec = S.SloSpec(
        "req_p99",
        objective=0.99,
        latency=S.HistogramOver("sentinel_tick_device_ms", 10.0),
        auto_bundle=False,
    )
    eng = S.SloEngine(specs=(spec,), registry=reg, flight=fl, gauge_registry=greg)
    eng.step(0)
    for _ in range(50):
        h.observe(1.0)
    for _ in range(50):
        h.observe(100.0)
    st = eng.step(60_000)[0]
    assert st.alerting and st.fired
    assert fl.last_bundle() is None  # auto_bundle=False respected
    eng.close()


def test_slo_default_specs_cover_the_six_objectives():
    names = {s.name for s in S.default_slos()}
    assert names == {
        "req_p99",
        "shed_ratio",
        "fail_closed",
        "fleet_error_budget",
        "sketch_eps",
        "hbm_capacity",
    }
    for s in S.default_slos():
        assert 0.0 < s.objective < 1.0 and s.windows


def test_slo_no_total_traffic_means_no_burn():
    reg, greg = MetricRegistry(), MetricRegistry()
    eng = S.SloEngine(
        specs=(_shed_spec(),), registry=reg, flight=FlightRecorder(),
        gauge_registry=greg,
    )
    eng.step(0)
    st = eng.step(60_000)[0]
    assert not st.alerting and st.budget_remaining == 1.0
    assert all(v == 0.0 for v in st.burn.values())
    eng.close()
