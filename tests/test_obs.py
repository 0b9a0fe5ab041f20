"""sentinel_tpu.obs — span tracer ring, metrics registry, exposition, CLI.

Covers the ISSUE-3 contracts: ring wraparound and concurrent writers,
power-of-two histogram bucket boundaries + merge, Prometheus exposition
(golden text), the tracer-disabled overhead guard, the extension
error-counter satellite, and the ``python -m sentinel_tpu.obs --summary``
self-capture printing p50/p99 for all six tick stages.
"""

from __future__ import annotations

import json
import os
import threading

import numpy as np
import pytest

from sentinel_tpu import obs
from sentinel_tpu.obs.registry import Counter, Gauge, Histogram, MetricRegistry
from sentinel_tpu.obs.trace import SpanTracer


@pytest.fixture(autouse=True)
def _tracer_state():
    """Never leak an enabled/poisoned global tracer into other tests."""
    was = obs.TRACER.enabled
    yield
    obs.disable()
    obs.TRACER.reset()
    if was:  # pragma: no cover — the suite never leaves it on
        obs.TRACER.enable()


# ---------------------------------------------------------------------------
# span tracer ring
# ---------------------------------------------------------------------------


def test_ring_records_in_order_and_snapshot_is_sorted():
    tr = SpanTracer(capacity=64)
    tr.enable()
    for i in range(10):
        tr.record(f"s{i}", t0_ns=1000 + i, dur_ns=5, trace=7)
    snap = tr.snapshot()
    assert [s["name"] for s in snap] == [f"s{i}" for i in range(10)]
    assert all(s["trace"] == 7 for s in snap)
    assert tr.recorded_total == 10


def test_ring_wraparound_keeps_newest():
    tr = SpanTracer(capacity=8)  # already a power of two
    tr.enable()
    for i in range(20):
        tr.record("s", t0_ns=i, dur_ns=1)
    snap = tr.snapshot()
    assert len(snap) == 8
    # the survivors are exactly the last capacity records, oldest first
    assert [s["t0_ns"] for s in snap] == list(range(12, 20))
    assert tr.recorded_total == 20


def test_capacity_rounds_up_to_power_of_two():
    assert SpanTracer(capacity=100).capacity == 128
    assert SpanTracer(capacity=1).capacity == 2


def test_concurrent_writers_land_on_distinct_slots():
    tr = SpanTracer(capacity=4096)
    tr.enable()
    n_threads, per = 8, 200

    def work(k):
        for i in range(per):
            tr.record(f"t{k}", t0_ns=i, dur_ns=1)

    ts = [threading.Thread(target=work, args=(k,)) for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = tr.snapshot()
    assert len(snap) == n_threads * per  # nothing lost below capacity
    seqs = [s["seq"] for s in snap]
    assert len(set(seqs)) == len(seqs)  # no slot ever shared a sequence
    by_name = {}
    for s in snap:
        by_name.setdefault(s["name"], 0)
        by_name[s["name"]] += 1
    assert all(v == per for v in by_name.values())


def test_span_context_manager_and_disabled_noop():
    tr = SpanTracer(capacity=16)
    with tr.span("off"):  # disabled: shared no-op, nothing recorded
        pass
    assert tr.snapshot() == []
    tr.enable()
    with tr.span("on", trace=3, stage="x"):
        pass
    (s,) = tr.snapshot()
    assert s["name"] == "on" and s["trace"] == 3 and s["attrs"] == {"stage": "x"}
    assert s["dur_ns"] >= 0


def test_begin_end_crosses_threads():
    tr = SpanTracer(capacity=16)
    tr.enable()
    h = tr.begin("xthread", trace=9, chunk=1)
    done = threading.Event()

    def finisher():
        tr.end(h, ok=True)
        done.set()

    threading.Thread(target=finisher).start()
    assert done.wait(5.0)
    (s,) = tr.snapshot()
    assert s["name"] == "xthread" and s["trace"] == 9
    assert s["attrs"] == {"chunk": 1, "ok": True}
    # disabled begin returns None and end(None) is a no-op
    tr.disable()
    assert tr.begin("nope") is None
    tr.end(None)


def test_chrome_trace_export_shape(tmp_path):
    tr = SpanTracer(capacity=16)
    tr.enable()
    tr.record("a", t0_ns=2_000, dur_ns=1_000, trace=1, attrs={"k": "v"})
    doc = tr.chrome_trace()
    (ev,) = doc["traceEvents"]
    assert ev["ph"] == "X"
    assert ev["ts"] == 2.0 and ev["dur"] == 1.0  # microseconds
    assert ev["args"]["k"] == "v" and ev["args"]["trace"] == 1
    p = tmp_path / "trace.json"
    tr.dump(str(p))
    spans = obs.load_spans(str(p))
    assert spans[0]["name"] == "a" and spans[0]["dur_ns"] == 1_000.0


def test_summarize_percentiles():
    spans = [
        {"name": "tick.device", "dur_ns": d * 1e6, "t0_ns": 0, "tid": 0}
        for d in (1.0, 2.0, 3.0, 4.0, 100.0)
    ] + [{"name": "other", "dur_ns": 5e6, "t0_ns": 0, "tid": 0}]
    summ = obs.summarize(spans, prefix="tick.")
    assert list(summ) == ["tick.device"]
    s = summ["tick.device"]
    assert s["count"] == 5
    assert s["p50_ms"] == 3.0
    assert 4.0 < s["p99_ms"] <= 100.0


def test_disabled_overhead_guard():
    """The disabled fast path is a single flag check: 20k t0() probes must
    cost microseconds each at worst — no clock read, no allocation."""
    from sentinel_tpu.obs import trace as OT
    from sentinel_tpu.utils.time_source import mono_s

    assert not OT.TRACER.enabled
    n = 20_000
    t_start = mono_s()
    acc = 0
    for _ in range(n):
        t = OT.t0()
        if t:  # pragma: no cover — disabled: never taken
            acc += t
    elapsed = mono_s() - t_start
    assert acc == 0
    # ~100 ns/call in CPython; 5 µs/call is a 50x safety margin for CI
    assert elapsed / n < 5e-6, f"disabled-path cost {elapsed / n * 1e9:.0f} ns/call"


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------


def test_histogram_power_of_two_bucket_boundaries():
    h = Histogram("h", start=1.0, buckets=4)  # bounds 1, 2, 4, 8, +Inf
    assert list(h.bounds) == [1.0, 2.0, 4.0, 8.0]
    for v, want in [
        (0.1, 0),  # below start -> first bucket
        (1.0, 0),  # boundary is INCLUSIVE (le semantics)
        (1.0001, 1),
        (2.0, 1),
        (2.0001, 2),
        (4.0, 2),
        (8.0, 3),
        (8.0001, 4),  # overflow -> +Inf slot
        (1e9, 4),
    ]:
        assert h._index(v) == want, (v, want, h._index(v))
    h.observe(1.5)
    h.observe(3.0)
    h.observe(100.0)
    assert h.count == 3 and h.sum == pytest.approx(104.5)


def test_histogram_merge_and_quantile():
    a = Histogram("h", start=1.0, buckets=8)
    b = Histogram("h", start=1.0, buckets=8)
    for v in (1.0, 1.0, 2.0, 4.0):
        a.observe(v)
    for v in (64.0, 128.0):
        b.observe(v)
    a.merge(b)
    assert a.count == 6
    assert a.quantile(0.5) == 2.0  # 3rd of 6 samples sits in the le=2 bucket
    assert a.quantile(1.0) == 128.0
    c = Histogram("h", start=2.0, buckets=8)
    with pytest.raises(ValueError):
        a.merge(c)


def test_histogram_quantile_empty_is_zero():
    assert Histogram("h").quantile(0.99) == 0.0


# ---------------------------------------------------------------------------
# registry + Prometheus exposition
# ---------------------------------------------------------------------------


def test_registry_get_or_create_identity_and_type_conflict():
    reg = MetricRegistry()
    c1 = reg.counter("x_total", "help one")
    c2 = reg.counter("x_total")
    assert c1 is c2
    assert reg.counter("x_total", labels={"a": "1"}) is not c1  # new series
    with pytest.raises(ValueError):
        reg.gauge("x_total")
    assert isinstance(reg.gauge("g"), Gauge)
    assert reg.get("x_total") is c1
    assert reg.get("missing") is None


def test_prometheus_exposition_golden():
    reg = MetricRegistry()
    reg.counter("demo_requests_total", "requests served").inc(3)
    reg.counter("demo_requests_total", labels={"kind": "bulk"}).inc(2)
    reg.gauge("demo_depth", "queue depth").set(1.5)
    h = reg.histogram("demo_ms", "latency", start=1.0, buckets=3)
    h.observe(0.5)
    h.observe(3.0)
    h.observe(99.0)
    golden = "\n".join(
        [
            "# HELP demo_depth queue depth",
            "# TYPE demo_depth gauge",
            "demo_depth 1.5",
            "# HELP demo_ms latency",
            "# TYPE demo_ms histogram",
            'demo_ms_bucket{le="1"} 1',
            'demo_ms_bucket{le="2"} 1',
            'demo_ms_bucket{le="4"} 2',
            'demo_ms_bucket{le="+Inf"} 3',
            "demo_ms_sum 102.5",
            "demo_ms_count 3",
            "# HELP demo_requests_total requests served",
            "# TYPE demo_requests_total counter",
            "demo_requests_total 3",
            'demo_requests_total{kind="bulk"} 2',
            "",
        ]
    )
    assert reg.exposition() == golden


def test_exposition_lines_are_well_formed():
    """Every non-comment line of the GLOBAL registry (fully populated by
    the instrumented modules' imports) parses as `name{labels} value`."""
    import re

    import sentinel_tpu.runtime.client  # noqa: F401 — registers tick metrics

    text = obs.REGISTRY.exposition()
    assert "sentinel_tick_device_ms" in text
    assert "sentinel_pipeline_occupancy" in text
    pat = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9][0-9a-zA-Z+.e-]*$"
    )
    for line in text.strip().split("\n"):
        if line.startswith("#"):
            assert line.startswith(("# HELP ", "# TYPE ", "# EXEMPLAR "))
        else:
            assert pat.match(line), line


def test_label_values_are_escaped():
    reg = MetricRegistry()
    reg.counter("esc_total", labels={"k": 'a"b\\c\nd'}).inc()
    line = [
        l for l in reg.exposition().splitlines() if not l.startswith("#")
    ][0]
    assert line == 'esc_total{k="a\\"b\\\\c\\nd"} 1'


def test_gauges_zero_when_pipeline_drains(client_factory):
    """Occupancy/resolver-queue gauges must not stay stale after the loop
    goes idle (scrapes happen while idle)."""
    import sentinel_tpu as st
    from sentinel_tpu.runtime import client as RC

    obs.enable()
    try:
        c = client_factory()
        c.flow_rules.load([st.FlowRule(resource="g-res", count=100)])
        with c.entry("g-res"):
            pass
    finally:
        obs.disable()
    assert RC.OBS.get("sentinel_pipeline_occupancy").value == 0
    assert RC.OBS.get("sentinel_resolver_queue_depth").value == 0


def test_registry_snapshot_shape():
    reg = MetricRegistry()
    reg.counter("c_total").inc(4)
    h = reg.histogram("h_ms", start=1.0, buckets=4)
    h.observe(3.0)
    snap = reg.snapshot()
    assert snap["c_total"] == 4
    assert snap["h_ms"]["count"] == 1 and snap["h_ms"]["p50"] == 4.0


# ---------------------------------------------------------------------------
# extension error counting satellite
# ---------------------------------------------------------------------------


def test_safe_dispatch_counts_errors_and_rate_limits_log(monkeypatch):
    from sentinel_tpu.metrics import extension as MEXT

    class Boom(MEXT.MetricExtension):
        def on_pass(self, *a, **kw):
            raise RuntimeError("boom")

    logged = []

    class _FakeLog:
        def exception(self, msg, *args):
            logged.append(msg % args if args else msg)

    import sentinel_tpu.utils.record_log as RL

    monkeypatch.setattr(RL, "record_log", lambda: _FakeLog())
    clock = {"t": 100.0}
    monkeypatch.setattr(MEXT, "mono_s", lambda: clock["t"])
    MEXT._warn_state.clear()

    ext = Boom()
    MEXT.register_extension(ext)
    try:
        before = MEXT._C_EXT_ERRORS.value
        for _ in range(5):
            MEXT.safe_dispatch("on_pass", "res", 1, "")
        assert MEXT._C_EXT_ERRORS.value == before + 5  # every failure counted
        assert len(logged) == 1  # ...but only one log line inside the window
        clock["t"] += MEXT._WARN_INTERVAL_S + 1
        MEXT.safe_dispatch("on_pass", "res", 1, "")
        assert MEXT._C_EXT_ERRORS.value == before + 6
        assert len(logged) == 2
        assert "+4 more" in logged[1]  # the suppressed count surfaces
    finally:
        MEXT.unregister_extension(ext)


# ---------------------------------------------------------------------------
# end-to-end: instrumented client + CLI summary
# ---------------------------------------------------------------------------

#: the six pipelined tick stages the ISSUE-3 acceptance names
_SIX = (
    "tick.assemble",
    "tick.presort",
    "tick.dispatch",
    "tick.device",
    "tick.readback",
    "tick.resolve",
)


def test_cli_self_capture_prints_all_six_stages(capsys):
    """`python -m sentinel_tpu.obs --summary` (self-capture path): a
    SentinelClient run with pipeline_depth>0 yields p50/p99 for all six
    tick stages."""
    from sentinel_tpu.obs.__main__ import main

    obs.TRACER.reset()
    assert main(["--summary", "--blocks", "3"]) == 0
    out = capsys.readouterr().out
    for name in _SIX:
        assert name in out, f"{name} missing from CLI summary:\n{out}"
    assert "p50 ms" in out and "p99 ms" in out
    assert "absent from this trace" not in out


def test_client_run_populates_stage_histograms_and_gauges(client_factory):
    """Tick-stage histograms and the occupancy gauge fill from a traced
    sync-mode client run (the /metrics acceptance surface)."""
    import sentinel_tpu as st
    from sentinel_tpu.runtime import client as RC

    before = {n: RC.OBS.get(f"sentinel_tick_{n}_ms").count for n in
              ("assemble", "dispatch", "device", "readback", "resolve")}
    obs.enable()
    try:
        c = client_factory()
        c.flow_rules.load([st.FlowRule(resource="obs-res", count=100)])
        for _ in range(3):
            with c.entry("obs-res"):
                pass
    finally:
        obs.disable()
    for n, b in before.items():
        assert RC.OBS.get(f"sentinel_tick_{n}_ms").count > b, n
    # tick spans carry matching trace ids across stages
    spans = [s for s in obs.TRACER.snapshot() if s["name"].startswith("tick.")]
    by_trace = {}
    for s in spans:
        by_trace.setdefault(s["trace"], set()).add(s["name"])
    assert any(
        {"tick.assemble", "tick.dispatch", "tick.device", "tick.resolve"} <= v
        for v in by_trace.values()
    )


def test_wraparound_loss_is_accounted_not_silent():
    """ISSUE-5 satellite: overwrite loss is exposed (spans_dropped_total
    + a registry counter synced on the read side), never silent."""
    from sentinel_tpu.obs.registry import MetricRegistry

    reg = MetricRegistry()
    c = reg.counter("sentinel_trace_spans_dropped_total")
    tr = SpanTracer(capacity=8, drop_counter=c)
    tr.enable()
    for i in range(20):
        tr.record("s", t0_ns=i, dur_ns=1)
    assert tr.spans_dropped_total() == 12  # 20 recorded, 8 retained
    snap = tr.snapshot()  # read side syncs the counter
    assert len(snap) == 8
    assert c.value == 12
    # further records keep the accounting monotonic, no double count
    for i in range(4):
        tr.record("s", t0_ns=100 + i, dur_ns=1)
    tr.snapshot()
    assert tr.spans_dropped_total() == 16 and c.value == 16
    tr.snapshot()
    assert c.value == 16
    # below-capacity tracers never report drops
    small = SpanTracer(capacity=64, drop_counter=reg.counter("other_total"))
    small.enable()
    small.record("x", 0, 1)
    small.snapshot()
    assert small.spans_dropped_total() == 0


def test_global_tracer_drop_counter_registered():
    assert obs.REGISTRY.get("sentinel_trace_spans_dropped_total") is not None


# ---------------------------------------------------------------------------
# distributed trace context
# ---------------------------------------------------------------------------


def test_trace_ctx_adoption_and_ids():
    from sentinel_tpu.obs import trace as OT

    t1, t2 = OT.new_trace_id(), OT.new_trace_id()
    assert t1 != t2 and t1 != 0 and t1 < 2**64
    tr = SpanTracer(capacity=16)
    tr.enable()
    with OT.trace_ctx(t1, 42):
        with tr.span("child"):
            pass
        h = tr.begin("xchild")
    tr.end(h)
    with tr.span("orphan"):
        pass
    by_name = {s["name"]: s for s in tr.snapshot()}
    assert by_name["child"]["trace"] == t1
    assert by_name["child"]["attrs"]["parent"] == 42
    assert by_name["xchild"]["trace"] == t1
    assert by_name["xchild"]["attrs"]["parent"] == 42
    assert by_name["orphan"]["trace"] == 0  # no ambient ctx -> unchanged
    # explicit trace beats ambient; ctx restores on exit
    with OT.trace_ctx(t1, 42):
        with tr.span("explicit", trace=7):
            pass
    assert OT.current_ctx() == (0, 0)
    assert {s["name"]: s for s in tr.snapshot()}["explicit"]["trace"] == 7


def test_maybe_ctx_noop_when_disabled():
    from sentinel_tpu.obs import trace as OT

    assert not OT.TRACER.enabled
    with OT.maybe_ctx(123, 456):
        assert OT.current_ctx() == (0, 0)  # disabled: nothing installed


def test_trace_context_plumbing_disabled_overhead_guard():
    """The wire-trace plumbing's disabled path (maybe_ctx on the server,
    the enabled-flag check before minting ids on the client) stays in
    the same <5 µs/call budget as every other disarmed obs site."""
    from sentinel_tpu.obs import trace as OT
    from sentinel_tpu.utils.time_source import mono_s

    assert not OT.TRACER.enabled
    n = 20_000
    t_start = mono_s()
    for _ in range(n):
        with OT.maybe_ctx(0, 0):
            pass
    elapsed = mono_s() - t_start
    assert elapsed / n < 5e-6, f"maybe_ctx cost {elapsed / n * 1e9:.0f} ns/call"


def test_golden_cross_process_merge_links_rpc_to_decision(tmp_path, capsys):
    """ISSUE-5 acceptance: client + server dumps --merge into ONE chrome
    trace where a cluster.rpc span and the server decision span share a
    trace id and are linked by flow events."""
    from sentinel_tpu.obs import trace as OT
    from sentinel_tpu.obs.__main__ import main, merge_traces

    tid, sid = OT.new_trace_id(), OT.new_span_id()
    # client process: the RPC span carrying its span id on the wire
    cl = SpanTracer(capacity=16)
    cl.enable()
    cl.record("cluster.rpc", t0_ns=1_000_000, dur_ns=900_000, trace=tid,
              attrs={"span_id": sid, "ok": True, "type": 1})
    client_doc = cl.chrome_trace()
    # server process: the decision span that adopted (tid, sid)
    sv = SpanTracer(capacity=16)
    sv.enable()
    with OT.trace_ctx(tid, sid):
        sv.record("token.decision", t0_ns=5_000_000, dur_ns=400_000, trace=tid,
                  attrs={"parent": sid, "flow_id": 101})
    server_doc = sv.chrome_trace()
    for e in server_doc["traceEvents"]:
        e["pid"] = e["pid"] + 1  # distinct process
    a, b = tmp_path / "client.json", tmp_path / "server.json"
    a.write_text(json.dumps(client_doc))
    b.write_text(json.dumps(server_doc))

    doc = merge_traces([str(a), str(b)])
    ev = doc["traceEvents"]
    rpc = [e for e in ev if e.get("name") == "cluster.rpc"]
    dec = [e for e in ev if e.get("name") == "token.decision"]
    assert rpc and dec
    assert rpc[0]["args"]["trace"] == dec[0]["args"]["trace"] == tid
    assert rpc[0]["pid"] != dec[0]["pid"]  # separate lanes survived
    starts = [e for e in ev if e.get("ph") == "s"]
    ends = [e for e in ev if e.get("ph") == "f"]
    assert len(starts) == 1 and len(ends) == 1
    assert starts[0]["id"] == ends[0]["id"] == sid
    # flow endpoints bind inside their spans' (pid, ts) lanes
    assert starts[0]["pid"] == rpc[0]["pid"] and ends[0]["pid"] == dec[0]["pid"]
    # the CLI writes the same document
    out = tmp_path / "merged.json"
    assert main(["--merge", str(a), str(b), "-o", str(out)]) == 0
    written = json.loads(out.read_text())
    assert written["otherData"]["flow_links"] == 1
    assert "1 flow links" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_journal_ring_and_events():
    from sentinel_tpu.obs.flight import FlightRecorder

    fr = FlightRecorder(capacity=8)
    for i in range(12):
        fr.note("k", i=i)
    evs = fr.events()
    assert len(evs) == 8  # bounded: oldest overwritten
    assert [e["fields"]["i"] for e in evs] == list(range(4, 12))
    assert fr.recorded_total() == 12
    assert [e["fields"]["i"] for e in fr.events(last=3)] == [9, 10, 11]
    assert evs[0]["kind"] == "k" and evs[0]["t_ns"] > 0


def test_flight_bundle_contents_and_providers():
    from sentinel_tpu.obs.flight import FlightRecorder

    fr = FlightRecorder(capacity=32)
    fr.note("cluster.degrade.enter", cooldown_s=5.0)
    fr.register_provider("good", lambda: {"x": 1})
    fr.register_provider("bad", lambda: 1 / 0)
    b = fr.dump_bundle("unit")
    assert b["kind"] == "sentinel-flight-bundle" and b["reason"] == "unit"
    assert b["journal"][-1]["kind"] == "cluster.degrade.enter"
    assert isinstance(b["metrics"], dict) and "captured_wall_ms" in b
    assert b["providers"]["good"] == {"x": 1}
    assert "ZeroDivisionError" in b["providers"]["bad"]["error"]
    # unregister honors identity
    keeper = lambda: {}  # noqa: E731
    fr.register_provider("good", keeper)
    fr.unregister_provider("good", lambda: {})  # not the registered fn
    assert "good" in fr.dump_bundle("u2")["providers"]
    fr.unregister_provider("good", keeper)
    assert "good" not in fr.dump_bundle("u3")["providers"]


def test_flight_trigger_rate_limit_and_keep_k(tmp_path, monkeypatch):
    from sentinel_tpu.obs.flight import FlightRecorder

    monkeypatch.setenv("SENTINEL_FLIGHT_DIR", str(tmp_path))
    fr = FlightRecorder(capacity=8, keep=2, min_interval_s=3600.0)
    assert fr.trigger("breach") is not None
    assert fr.trigger("breach") is None  # inside the window
    fr.reset_rate_limit()
    assert fr.trigger("degrade") is not None
    fr.reset_rate_limit()
    assert fr.trigger("third") is not None
    reasons = [b["reason"] for b in fr.bundles()]
    assert reasons == ["degrade", "third"]  # keep=2, oldest evicted
    assert fr.last_bundle()["reason"] == "third"
    files = sorted(tmp_path.glob("flight_*.json"))
    assert len(files) == 3  # disk keeps everything the process triggered
    from sentinel_tpu.obs.flight import load_bundle

    assert load_bundle(str(files[0]))["kind"] == "sentinel-flight-bundle"
    rl = obs.REGISTRY.get("sentinel_flight_bundles_rate_limited_total")
    assert rl is None or rl.value >= 0  # registered lazily per instance


def test_flight_first_trigger_fires_on_a_freshly_booted_host(monkeypatch):
    """"Never fired" is not "fired at boot": a host whose monotonic clock
    reads less than ``min_interval_s`` still captures its first bundle."""
    from sentinel_tpu.obs import flight

    up_ns = [1_000_000_000]  # one second after boot
    monkeypatch.setattr(flight.OT, "now_ns", lambda: up_ns[0])
    fr = flight.FlightRecorder(capacity=8, keep=2, min_interval_s=3600.0)
    assert fr.trigger("breach")["reason"] == "breach"
    up_ns[0] += 1_000_000_000
    assert fr.trigger("breach") is None  # inside the window
    fr.reset_rate_limit()
    assert fr.trigger("again")["reason"] == "again"


def test_flight_note_disarmed_overhead_guard():
    """The journal append is the black box's hot hook: it must stay in
    the same <5 µs/call budget as t0() and disarmed failpoints."""
    from sentinel_tpu.obs.flight import FlightRecorder
    from sentinel_tpu.utils.time_source import mono_s

    fr = FlightRecorder(capacity=1024)
    n = 20_000
    t_start = mono_s()
    for i in range(n):
        fr.note("overhead.guard.event")
    elapsed = mono_s() - t_start
    assert elapsed / n < 5e-6, f"note() cost {elapsed / n * 1e9:.0f} ns/call"


def test_postmortem_cli_prints_timeline(tmp_path, capsys):
    from sentinel_tpu.obs.__main__ import main
    from sentinel_tpu.obs.flight import FlightRecorder

    fr = FlightRecorder(capacity=16)
    fr.note("failpoint.fire", site="cluster.rpc.send", action="raise", hit=2)
    fr.note("cluster.degrade.enter", cooldown_s=5.0)
    b = fr.dump_bundle("unit-test")
    p = tmp_path / "bundle.json"
    p.write_text(json.dumps(b))
    assert main(["--postmortem", str(p)]) == 0
    out = capsys.readouterr().out
    assert "reason='unit-test'" in out
    assert "failpoint.fire" in out and "cluster.degrade.enter" in out
    # non-bundles are rejected loudly
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"nope": 1}))
    with pytest.raises(ValueError):
        main(["--postmortem", str(bad)])


def test_build_info_gauge_in_exposition():
    text = obs.REGISTRY.exposition()
    line = [l for l in text.splitlines() if l.startswith("sentinel_build_info")]
    assert line, "sentinel_build_info missing from exposition"
    assert line[0].endswith(" 1")
    assert 'sentinel_version="' in line[0] and 'jax_version="' in line[0]
    assert 'backend="' in line[0]


def test_chrome_roundtrip_through_summarize(tmp_path):
    obs.TRACER.reset()
    obs.enable()
    try:
        with obs.span("tick.device", trace=1):
            pass
    finally:
        obs.disable()
    p = tmp_path / "t.json"
    obs.TRACER.dump(str(p))
    spans = obs.load_spans(str(p))
    assert "tick.device" in obs.summarize(spans)
    # files that are neither format are rejected
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"nope": 1}))
    with pytest.raises(ValueError):
        obs.load_spans(str(bad))


# ---------------------------------------------------------------------------
# one timeline from submit to verdict (PR 24)
# ---------------------------------------------------------------------------

_TICK_THREAD = ("tick.drain", "tick.assemble", "tick.dispatch", "tick.handoff")
_PER_TICK = ("tick.resident", "tick.wait", "tick.device", "tick.readback", "tick.resolve")


def _load(c):
    import sentinel_tpu as st

    c.flow_rules.load([st.FlowRule(resource="tl-res", count=100)])
    return c


def _serve(c, n_entries=3, block=48):
    for _ in range(n_entries):
        with c.entry("tl-res"):
            pass
    rid = c.registry.resource_id("tl-res")
    return c.submit_block(np.full(block, rid, np.int32)).result(timeout=30)


def test_tracing_off_a_served_tick_records_nothing_and_reads_no_clock(
    client_factory, monkeypatch
):
    """The contract of obs/trace.py at every site PR 24 added: with the
    tracer off a served tick costs flag checks only.  The tracer's one clock
    read raises here, so a site that read it would fail the serving path."""
    from sentinel_tpu.obs import trace as OT
    from sentinel_tpu.runtime import client as RC

    def no_clock():
        raise AssertionError("a tracing site read the clock with tracing off")

    c = _load(client_factory(pipeline_depth=2))  # the flight journal stamps a rule load
    obs.TRACER.reset()
    assert not OT.TRACER.enabled
    monkeypatch.setattr(OT, "now_ns", no_clock)
    seen = []
    real_block = RC.ArrayBlock

    def spy_block(*a, **kw):
        blk = real_block(*a, **kw)
        seen.append(blk)
        return blk

    monkeypatch.setattr(RC, "ArrayBlock", spy_block)
    verdicts, _waits = _serve(c)
    assert len(verdicts) == 48
    assert obs.TRACER.snapshot() == []
    assert [b.submitted_ns for b in seen] == [0]
    assert c._idle_since == 0


def _seg_client(client_factory):
    """A client whose ticks take the host presort (segment effects behind
    the fused path), as the served deployments do."""
    from sentinel_tpu.core.config import small_engine_config

    cfg = small_engine_config(
        use_mxu_tables=True, fused_effects=True, seg_effects=True,
        flow_rules_per_resource=1, degrade_rules_per_resource=1,
        param_rules_per_resource=1)
    return _load(client_factory(cfg=cfg, pipeline_depth=2))


def test_tracing_off_the_presort_reads_no_clock_and_builds_no_attrs(
    client_factory, monkeypatch
):
    """PR 25's site under the same contract: off, the presort runs and the
    span machinery is never entered (so its attrs dict is never built)."""
    import sentinel_tpu.native.ring as RM
    from sentinel_tpu.obs import trace as OT

    def never(*_a, **_kw):
        raise AssertionError("the presort site entered the tracer with tracing off")

    c = _seg_client(client_factory)
    obs.TRACER.reset()
    assert not OT.TRACER.enabled
    monkeypatch.setattr(OT, "now_ns", never)
    monkeypatch.setattr(OT, "stage_ns", never)
    sorted_rows = []
    real = RM.presort
    monkeypatch.setattr(
        RM, "presort", lambda keys, n, *a, **kw: sorted_rows.append(n) or real(keys, n, *a, **kw)
    )
    verdicts, _waits = _serve(c)
    assert len(verdicts) == 48 and 48 in sorted_rows
    assert obs.TRACER.snapshot() == []


@pytest.mark.parametrize("path", ["small", "numpy"])
def test_traced_presort_says_how_many_rows_it_sorted_and_by_which_path(
    client_factory, monkeypatch, path
):
    """tick.presort carries n_a / n_c (live rows sorted a side) and path;
    obs.summarize counts the paths, which --summary and the benchmark's
    span_summary print."""
    import sentinel_tpu.native.ring as RM

    c = _seg_client(client_factory)
    if path == "numpy":
        monkeypatch.setattr(RM, "load_native", lambda: None)
    obs.TRACER.reset()
    obs.enable()
    try:
        _serve(c)
    finally:
        obs.disable()
    spans = [s for s in obs.TRACER.snapshot() if s["name"] == "tick.presort"]
    assert spans and all(
        set(s["attrs"]) == {"n_a", "n_c", "path", "segs", "seg_cap"} for s in spans
    )  # at test size every tick's segments are counted
    assert all(0 < s["attrs"]["segs"] <= s["attrs"]["seg_cap"] for s in spans)
    assert {s["attrs"]["path"] for s in spans} == {path}
    assert 48 in [s["attrs"]["n_a"] for s in spans]  # the block's tick
    assert all(0 <= s["attrs"]["n_c"] <= c.cfg.complete_batch_size for s in spans)
    assert obs.summarize(spans)["tick.presort"]["path"] == {path: len(spans)}


@pytest.mark.parametrize("depth", [0, 2])
def test_traced_ticks_carry_one_id_from_queue_to_resolve(client_factory, depth):
    """req.queue, the tick thread's spans and the resolver's spans of one
    tick share its id; tick.resident + tick.wait is tick.device exactly."""
    c = _load(client_factory(pipeline_depth=depth))
    obs.TRACER.reset()
    obs.enable()
    try:
        _serve(c)
    finally:
        obs.disable()
    spans = obs.TRACER.snapshot()
    by_tick = {}
    for s in spans:
        by_tick.setdefault(s["trace"], {}).setdefault(s["name"], []).append(s)
    dispatched = {t for t, found in by_tick.items() if t and "tick.dispatch" in found}
    assert len(dispatched) >= 4
    queued = [s for s in spans if s["name"] == "req.queue"]
    assert len(queued) == 4  # three entries and one block
    assert {s["trace"] for s in queued} <= dispatched
    assert sorted(s["attrs"]["kind"] for s in queued) == ["block", "entry", "entry", "entry"]
    assert [s["attrs"]["n"] for s in queued if s["attrs"]["kind"] == "block"] == [48]
    assert all(s["dur_ns"] >= 0 for s in queued)
    for t in dispatched:
        found = by_tick[t]
        for name in _TICK_THREAD + _PER_TICK:
            assert len(found.get(name, [])) == 1, (t, name, sorted(found))
        res, wait, dev = (found[n][0] for n in ("tick.resident", "tick.wait", "tick.device"))
        assert res["t0_ns"] == dev["t0_ns"] == (
            found["tick.dispatch"][0]["t0_ns"] + found["tick.dispatch"][0]["dur_ns"])
        assert wait["t0_ns"] == res["t0_ns"] + res["dur_ns"]
        assert res["dur_ns"] + wait["dur_ns"] == dev["dur_ns"]
        assert 0 <= found["tick.dispatch"][0]["attrs"]["call_ns"] <= found["tick.dispatch"][0]["dur_ns"]
        drain = found["tick.drain"][0]["attrs"]
        assert {"n_obj", "n_blk", "n_comp", "blocks", "left_blocks", "left_items"} <= set(drain)
        assert res["attrs"]["handed_ns"] >= res["t0_ns"]
    # the caller's own share of an entry() carries the serving tick's id too
    for name in ("req.admit", "req.wake"):
        own = [s for s in spans if s["name"] == name]
        assert len(own) == 3 and {s["trace"] for s in own} <= dispatched


def test_a_block_that_spans_ticks_records_one_queue_span_per_piece(client_factory):
    c = _load(client_factory())
    obs.TRACER.reset()
    obs.enable()
    try:
        n = c.cfg.batch_size + 40
        verdicts, _w = _serve(c, n_entries=0, block=n)
    finally:
        obs.disable()
    assert len(verdicts) == n
    pieces = [s for s in obs.TRACER.snapshot() if s["name"] == "req.queue"]
    assert sorted(s["attrs"]["n"] for s in pieces) == [40, c.cfg.batch_size]
    assert len({s["trace"] for s in pieces}) == 2 and len({s["t0_ns"] for s in pieces}) == 1


def test_the_lowered_tick_names_its_program_kernels_and_stages():
    """Names on the device side are metadata a profiler trace is read by:
    the program, each Pallas kernel and each stage of ops/engine.py:tick."""
    import re

    import jax
    import jax.numpy as jnp

    from sentinel_tpu.core.config import EngineConfig
    from sentinel_tpu.ops import engine as E

    # the configuration the chip serves (MXU tables, fused kernels, segment
    # effects behind the client's presort, packed wire, sketch tier) at the
    # size of the perfbench rehearsal; the kernels lower interpreted here
    cfg = EngineConfig(
        max_resources=112, max_nodes=120, max_flow_rules=112, max_degrade_rules=112,
        max_param_rules=8, batch_size=512, complete_batch_size=512, sketch_stats=True,
        flow_rules_per_resource=1, degrade_rules_per_resource=1, param_rules_per_resource=1,
        use_mxu_tables=True, fused_effects=True, seg_effects=True, seg_fallback=False,
        packed_wire=True)
    tick = E.make_tick(cfg, donate=False)
    args = (E.init_state(cfg), E.compile_ruleset(cfg, _Reg()), E.empty_acquire(cfg),
            E.empty_complete(cfg), jnp.int32(1000), jnp.float32(0.0), jnp.float32(0.0))
    lowered = tick.lower(*args)
    text = lowered.as_text(debug_info=True)
    assert f"module @jit_{E.TICK_PROGRAM} " in text
    scopes = set(re.findall(r"stage\.[a-z_]+", text))
    assert cfg.packed_wire and cfg.seg_effects and cfg.sketch_stats
    assert scopes == {
        "stage.widen", "stage.seg_prepare", "stage.exits", "stage.warmup", "stage.param_refresh",
        "stage.checks", "stage.segment_reads", "stage.authority", "stage.system", "stage.param", "stage.flow",
        "stage.tail_flow", "stage.degrade", "stage.verdict", "stage.effects", "stage.sketch",
        "stage.telemetry", "stage.pack"}, sorted(scopes)

    def pallas_names(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn.params["name"])
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None:
                        pallas_names(getattr(inner, "jaxpr", inner), out)
        return out

    names = pallas_names(jax.make_jaxpr(tick)(*args).jaxpr, [])
    assert set(names) == {"seg_excl_cumsum", "seg_incl_min", "scatter_many"}
    # the fourth kernel serves the per-item flow check, which this tick skips
    from sentinel_tpu.ops import fused as FU

    job = FU.GatherJob("t", jnp.zeros(256, jnp.int32), jnp.zeros((64, 1), jnp.int32), (1,))
    assert pallas_names(jax.make_jaxpr(lambda: FU.gather_many([job]))().jaxpr, []) == [
        "gather_many"]


class _Reg:
    """The registry face compile_ruleset needs for an empty rule set."""

    def resource_id(self, name):
        return None


def test_an_idle_tick_thread_records_nothing_and_one_idle_span_closes_the_stretch():
    """With tracing on, an idle server must not wrap the ring: the polls
    that find nothing record no span, and the whole stretch becomes one
    tick.idle when work next arrives."""
    import time

    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.runtime.client import SentinelClient

    c = SentinelClient(cfg=small_engine_config(), mode="threaded", tick_interval_ms=1.0)
    c.start()
    try:
        _load(c)
        with c.entry("tl-res"):  # first traffic compiles; the client is idle again after it
            pass
        time.sleep(0.05)
        obs.TRACER.reset()
        obs.enable()
        time.sleep(0.15)  # some hundred empty iterations of the 1 ms loop
        quiet = obs.TRACER.snapshot()
        with c.entry("tl-res"):
            pass
    finally:
        obs.disable()
        c.stop()
    # (the process's own beat, proc.wake, is recorded whenever the tracer is on)
    assert [s for s in quiet if not s["name"].startswith("proc.")] == []
    spans = obs.TRACER.snapshot()
    drains = [s for s in spans if s["name"] == "tick.drain" and s["attrs"]["n_obj"]]
    assert len(drains) == 1
    # the stretch ran from a poll that found nothing to the drain that found the
    # entry (its exit may open and close a second, short one before the tracer is off)
    idle = [s for s in spans if s["name"] == "tick.idle" and s["attrs"]["why"] == "interval"
            and s["t0_ns"] + s["dur_ns"] == drains[0]["t0_ns"]]
    assert len(idle) == 1 and idle[0]["trace"] == 0 and idle[0]["dur_ns"] > 100e6
    # the wait for the tick mutex is named, with the id of the tick it preceded
    locks = [s for s in spans if s["name"] == "tick.lock" and s["trace"] == drains[0]["trace"]]
    assert len(locks) == 1 and locks[0]["t0_ns"] + locks[0]["dur_ns"] == drains[0]["t0_ns"]
    assert all(s["trace"] for s in spans if s["name"] == "tick.drain")


def test_a_sync_callers_absence_is_not_recorded_as_idleness(client_factory):
    c = _load(client_factory())
    obs.TRACER.reset()
    obs.enable()
    try:
        c.tick_once()  # nothing queued: an iteration that finds nothing
        _serve(c)
    finally:
        obs.disable()
    assert c._idle_since == 0
    names = {s["name"] for s in obs.TRACER.snapshot()}
    assert "tick.drain" in names and "tick.lock" in names
    assert not [s for s in obs.TRACER.snapshot()
                if s["name"] == "tick.idle" and s["attrs"]["why"] == "interval"]


def test_the_hot_set_cadence_runs_on_the_clients_clock(client_factory, vt):
    """A pass is due ``hotset_eval_s`` after the last one on the client's
    clock: wall time that passes under a frozen virtual clock makes none
    due, so a slow host never folds a promote/demote pass into a test."""
    import time

    from sentinel_tpu.core.config import small_engine_config

    c = client_factory(cfg=small_engine_config(sketch_stats=True, hotset_eval_s=0.05))
    assert c.hotset.maybe_evaluate() is False  # the cadence starts at construction
    vt.advance(50)
    assert c.hotset.maybe_evaluate() is True
    time.sleep(0.08)
    assert c.hotset.maybe_evaluate() is False
    vt.advance(49)
    assert c.hotset.maybe_evaluate() is False
    vt.advance(1)
    assert c.hotset.maybe_evaluate() is True


@pytest.mark.parametrize("due", [True, False])
def test_a_hot_set_pass_on_the_tick_thread_gets_a_span_and_the_cadence_check_none(
    client_factory, due
):
    from sentinel_tpu.core.config import small_engine_config

    c = _load(client_factory(cfg=small_engine_config(sketch_stats=True)))
    assert c.hotset is not None
    _serve(c, n_entries=1, block=8)
    c.hotset._last_eval = -1e18 if due else 1e18
    obs.TRACER.reset()
    obs.enable()
    try:
        c.tick_once()
    finally:
        obs.disable()
    passes = [s for s in obs.TRACER.snapshot() if s["name"] == "tick.hotset"]
    assert len(passes) == (1 if due else 0)
    assert c.hotset.maybe_evaluate() is False  # stamped, or not yet due


# ---------------------------------------------------------------------------
# what a thread waited for (PR 36): proc.wake, proc.gc, the hot-set pass by
# its parts, tick.wait's copy_ns, the overflow counter
# ---------------------------------------------------------------------------


def _wake_threads():
    return [t for t in threading.enumerate() if t.name == "sentinel-obs-wake"]


def test_the_wake_thread_lives_exactly_as_long_as_tracing_is_on():
    import gc

    from sentinel_tpu.obs import proc

    assert not _wake_threads() and proc._on_gc not in gc.callbacks  # after import, off
    obs.enable()
    try:
        (first,) = _wake_threads()
        assert first.daemon
        obs.enable()
        assert _wake_threads() == [first]
        assert gc.callbacks.count(proc._on_gc) == 1
    finally:
        obs.disable()
    assert not first.is_alive()  # joined, not left to die
    assert not _wake_threads() and proc._on_gc not in gc.callbacks
    obs.disable()  # twice is harmless


@pytest.mark.parametrize("env, threads", [("", 0), ("0", 0), ("1", 1)])
def test_after_import_the_recorders_exist_only_where_the_environment_turned_tracing_on(env, threads):
    """A fresh interpreter: ``SENTINEL_TRACE=1`` has the tracer on from
    import, and its recorders with it; otherwise no thread, no callback."""
    import subprocess
    import sys

    code = (
        "import gc, threading\n"
        "from sentinel_tpu import obs\n"
        "from sentinel_tpu.obs import proc\n"
        "n = sum(t.name == 'sentinel-obs-wake' for t in threading.enumerate())\n"
        "print(int(obs.enabled()), n, gc.callbacks.count(proc._on_gc))\n"
        "obs.disable()\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120,
        env={**os.environ, "SENTINEL_TRACE": env},
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(threads)] * 3


def test_a_beat_records_how_late_it_woke_and_a_late_one_the_cpu_time(monkeypatch):
    """The clock is stubbed: it jumps ``hold`` ahead once, between a beat's
    due instant and its wake-up, as a thread kept from running would see."""
    from sentinel_tpu.obs import proc
    from sentinel_tpu.obs import trace as OT

    hold = 3 * proc.WAKE_CPU_NS
    real, reads, shift = OT.now_ns, [0], [0]

    def clock():
        reads[0] += 1
        if reads[0] == 6:  # the third beat's wake-up read
            shift[0] = hold
        return real() + shift[0]

    monkeypatch.setattr(OT, "now_ns", clock)
    obs.TRACER.reset()
    obs.enable()
    try:
        deadline = real() + 5_000_000_000
        while reads[0] < 12 and real() < deadline:
            threading.Event().wait(0.005)
    finally:
        obs.disable()
    beats = [s for s in obs.TRACER.snapshot() if s["name"] == "proc.wake"]
    assert len(beats) >= 5
    (held,) = [s for s in beats if s["dur_ns"] >= hold]
    assert 0 <= held["attrs"]["cpu_ns"] < hold  # the process did not burn the hold
    assert all("cpu_ns" not in s["attrs"] for s in beats if s["dur_ns"] <= proc.WAKE_CPU_NS)
    # a beat's t0 is the instant it was due: a period after the read before it
    assert all(s["dur_ns"] >= 0 for s in beats)
    assert obs.REGISTRY.get("sentinel_proc_wake_late_ms").count >= len(beats)


def test_a_full_collection_records_a_span_and_the_callback_goes_with_tracing():
    import gc

    obs.TRACER.reset()
    obs.enable()
    try:
        junk = [[] for _ in range(1000)]
        for a in junk:
            a.append(a)  # cycles: the collector has something to find
        del junk, a
        gc.collect(2)
    finally:
        obs.disable()
    pauses = [s for s in obs.TRACER.snapshot() if s["name"] == "proc.gc"]
    full = [s for s in pauses if s["attrs"]["gen"] == 2]
    assert full and full[-1]["attrs"]["collected"] >= 1000 and full[-1]["dur_ns"] > 0
    # a quick young collection records nothing; one of a millisecond would
    from sentinel_tpu.obs import proc

    assert all(s["attrs"]["gen"] == 2 or s["dur_ns"] >= proc.GC_SLOW_NS for s in pauses)
    obs.TRACER.reset()
    gc.collect(2)
    assert obs.TRACER.snapshot() == []  # the callback is gone


def _hotset_pass(client_factory, **cfg_kw):
    from sentinel_tpu.core.config import small_engine_config

    c = _load(client_factory(cfg=small_engine_config(sketch_stats=True, **cfg_kw)))
    _serve(c, n_entries=1, block=8)
    c.hotset._last_eval = -1e18
    obs.TRACER.reset()
    obs.enable()
    try:
        c.tick_once()
    finally:
        obs.disable()
    spans = obs.TRACER.snapshot()
    (whole,) = [s for s in spans if s["name"] == "tick.hotset"]
    return c, whole, [s for s in spans if s["name"].startswith("hotset.")]


@pytest.mark.parametrize("salsa", [True, False])
def test_the_hot_set_pass_is_tiled_by_its_parts(client_factory, salsa):
    c, whole, parts = _hotset_pass(client_factory, sketch_salsa=salsa)
    assert whole["attrs"] == {"pass": c.hotset._eval_n}
    assert [s["name"] for s in parts] == ["hotset.scan", "hotset.demote", "hotset.health"]
    assert {s["trace"] for s in parts} == {whole["attrs"]["pass"]}
    for a, b in zip(parts, parts[1:]):  # end to start, on one clock read
        assert a["t0_ns"] + a["dur_ns"] == b["t0_ns"]
    assert parts[0]["t0_ns"] >= whole["t0_ns"]
    assert parts[-1]["t0_ns"] + parts[-1]["dur_ns"] <= whole["t0_ns"] + whole["dur_ns"]
    # nine tenths of the pass (with the tier off the pass is some 40 us, and
    # the cadence gate's two locks outside the parts get 0.2 ms of grace)
    uncovered = whole["dur_ns"] - sum(s["dur_ns"] for s in parts)
    assert uncovered <= max(0.1 * whole["dur_ns"], 200_000)
    scan, demote, health = (s["attrs"] for s in parts)
    assert set(scan) == {"candidates", "promoted"}
    assert demote == {"rows": 0, "stats_reads": 0, "demoted": 0}
    if salsa:
        assert health["read_ns"] > 0 and health["lock_ns"] >= 0
    else:  # the tier is off: recorded all the same, with zeros
        assert health == {"lock_ns": 0, "read_ns": 0}


def test_a_recompiling_pass_records_the_recompile_between_demote_and_health(
    client_factory, monkeypatch
):
    from sentinel_tpu.core.config import small_engine_config

    c = _load(client_factory(cfg=small_engine_config(sketch_stats=True)))
    monkeypatch.setattr(c.hotset, "_demote_cold", lambda: (True, 3, 2, 1))
    obs.TRACER.reset()
    obs.enable()
    try:
        c.hotset.evaluate_now()
    finally:
        obs.disable()
    parts = [s for s in obs.TRACER.snapshot() if s["name"].startswith("hotset.")]
    assert [s["name"] for s in parts] == [
        "hotset.scan", "hotset.demote", "hotset.recompile", "hotset.health"]
    assert parts[1]["attrs"] == {"rows": 3, "stats_reads": 2, "demoted": 1}


@pytest.mark.parametrize("packed", [True, False])
def test_tick_wait_says_how_much_of_it_lay_after_the_buffer_was_ready(client_factory, packed):
    from sentinel_tpu.core.config import small_engine_config

    c = _load(client_factory(cfg=small_engine_config(packed_wire=packed), pipeline_depth=2))
    obs.TRACER.reset()
    obs.enable()
    try:
        _serve(c)
    finally:
        obs.disable()
    waits = [s for s in obs.TRACER.snapshot() if s["name"] == "tick.wait"]
    assert waits and all(0 < s["attrs"]["copy_ns"] <= s["dur_ns"] for s in waits)


def test_tracing_off_a_tick_and_a_hot_set_pass_read_no_clock_at_the_new_sites(
    client_factory, monkeypatch
):
    """PR 36's sites under PR 24's contract: the resolver's hand-over wait,
    the pass's parts and the health read are flag checks with tracing off.
    A read is counted as well as refused: the resolver and the health read
    fail closed, and would swallow the refusal."""
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.core.errors import BLOCK_SYSTEM
    from sentinel_tpu.obs import trace as OT

    reads = []

    def no_clock():
        reads.append(1)
        raise AssertionError("a tracing site read the clock with tracing off")

    c = _load(client_factory(cfg=small_engine_config(sketch_stats=True), pipeline_depth=2))
    obs.TRACER.reset()
    assert not OT.TRACER.enabled
    monkeypatch.setattr(OT, "now_ns", no_clock)
    c.hotset._last_eval = -1e18
    verdicts, _waits = _serve(c)  # its tick_once runs the pass that is due
    assert len(verdicts) == 48 and int(BLOCK_SYSTEM) not in set(verdicts.tolist())
    assert c.hotset._eval_n >= 1
    assert not reads and obs.TRACER.snapshot() == []
    assert not _wake_threads()


def _overflow_count(shape):
    m = obs.REGISTRY.get("sentinel_seg_overflow_ticks_total", {"shape": str(shape)})
    return m.value if m is not None else 0.0


@pytest.mark.parametrize("side", ["acquire", "completion"])
def test_an_overflowing_tick_moves_its_shapes_counter_by_one(client_factory, side):
    from sentinel_tpu.ops import engine_seg as ES

    c = _seg_client(client_factory)
    b = c.cfg.batch_size if side == "acquire" else c.cfg.complete_batch_size
    full = b
    cap = ES.seg_capacity(c.cfg, b, full)
    before = _overflow_count(b)
    assert c._note_seg_count(cap, b, full) == (cap, cap)
    assert _overflow_count(b) == before  # at the capacity: not over it
    assert c._note_seg_count(cap + 1, b, full) == (cap + 1, cap)
    assert _overflow_count(b) == before + 1
    _serve(c)  # a served tick of 48 rows of one resource overflows nothing
    assert _overflow_count(b) == before + 1
