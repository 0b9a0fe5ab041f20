"""Circuit-breaker integration tests under virtual time.

Counterpart of the reference's CircuitBreakingIntegrationTest and the
ResponseTime/ExceptionCircuitBreaker unit tests (SURVEY.md §4.3): full
entry/exit loops against DegradeRules, state transitions driven by the
virtual clock.
"""

import sentinel_tpu as st


def _roundtrip(client, vt, resource, rt_ms, error=False):
    """One entry+exit taking rt_ms of virtual time. Returns verdict ok."""
    try:
        e = client.entry(resource)
    except st.BlockException:
        return False
    vt.advance(rt_ms)
    if error:
        e.trace(RuntimeError("biz"))
    e.exit()
    return True


def test_slow_ratio_trips_and_recovers(client, vt):
    client.degrade_rules.load(
        [
            st.DegradeRule(
                resource="svc",
                grade=st.CB_STRATEGY_SLOW_REQUEST_RATIO,
                count=10,  # max RT ms
                slow_ratio_threshold=0.5,
                stat_interval_ms=1000,
                time_window=2,  # retry after 2 s
                min_request_amount=5,
            )
        ]
    )
    # 5 slow requests (60 > 10 ms) → at the 5th completion total=5 ≥
    # minRequestAmount and ratio 1.0 > 0.5 → OPEN
    for _ in range(5):
        assert _roundtrip(client, vt, "svc", 60)
    assert not _roundtrip(client, vt, "svc", 1)  # breaker open

    # before the retry window: still open
    vt.advance(1000)
    assert not _roundtrip(client, vt, "svc", 1)

    # after retry timeout: exactly one probe is let through
    vt.advance(2500)
    probe = client.try_entry("svc")
    assert probe is not None
    assert client.try_entry("svc") is None  # half-open: probe in flight
    # fast probe completion closes the breaker
    vt.advance(2)
    probe.exit()
    assert _roundtrip(client, vt, "svc", 1)


def test_half_open_regression(client, vt):
    client.degrade_rules.load(
        [
            st.DegradeRule(
                resource="svc2",
                grade=st.CB_STRATEGY_SLOW_REQUEST_RATIO,
                count=10,
                slow_ratio_threshold=0.5,
                stat_interval_ms=1000,
                time_window=1,
                min_request_amount=3,
            )
        ]
    )
    for _ in range(3):
        assert _roundtrip(client, vt, "svc2", 50)
    assert not _roundtrip(client, vt, "svc2", 1)
    vt.advance(1500)
    # probe admitted but SLOW again → breaker re-opens
    assert _roundtrip(client, vt, "svc2", 80)
    assert not _roundtrip(client, vt, "svc2", 1)


def test_error_ratio(client, vt):
    client.degrade_rules.load(
        [
            st.DegradeRule(
                resource="err",
                grade=st.CB_STRATEGY_ERROR_RATIO,
                count=0.5,
                stat_interval_ms=1000,
                time_window=5,
                min_request_amount=4,
            )
        ]
    )
    for _ in range(3):
        assert _roundtrip(client, vt, "err", 1, error=True)
    assert _roundtrip(client, vt, "err", 1, error=False)
    # 3/4 errors > 0.5 → open
    assert not _roundtrip(client, vt, "err", 1)


def test_error_count(client, vt):
    client.degrade_rules.load(
        [
            st.DegradeRule(
                resource="ec",
                grade=st.CB_STRATEGY_ERROR_COUNT,
                count=3,
                stat_interval_ms=1000,
                time_window=5,
                min_request_amount=1,
            )
        ]
    )
    assert _roundtrip(client, vt, "ec", 1, error=True)
    assert _roundtrip(client, vt, "ec", 1, error=True)
    assert _roundtrip(client, vt, "ec", 1, error=True)
    assert not _roundtrip(client, vt, "ec", 1)


def test_window_expiry_resets_ratio(client, vt):
    client.degrade_rules.load(
        [
            st.DegradeRule(
                resource="w",
                grade=st.CB_STRATEGY_SLOW_REQUEST_RATIO,
                count=10,
                slow_ratio_threshold=0.5,
                stat_interval_ms=1000,
                time_window=1,
                min_request_amount=5,
            )
        ]
    )
    # 4 slow requests — under minRequestAmount, no trip
    for _ in range(4):
        assert _roundtrip(client, vt, "w", 30)
    # window slides past them
    vt.advance(2000)
    # fresh fast traffic keeps it closed
    for _ in range(6):
        assert _roundtrip(client, vt, "w", 1)


def test_a_breaker_that_moves_tells_the_host(client, vt):
    """The tick's stats row carries the breakers that moved and those open
    after it: with the tracer off they feed the transition counters, the
    open gauge and the flight journal; with it on, ``tick.resolve``."""
    from sentinel_tpu import obs
    from sentinel_tpu.obs.registry import REGISTRY

    def moved(to):
        return REGISTRY.get("sentinel_breaker_transitions_total", labels={"to": to}).value

    client.degrade_rules.load([st.DegradeRule(
        resource="svc9", grade=st.CB_STRATEGY_SLOW_REQUEST_RATIO, count=10,
        slow_ratio_threshold=0.5, stat_interval_ms=1000, time_window=1, min_request_amount=3)])
    before = {to: moved(to) for to in ("open", "half_open", "closed", "reopen")}
    assert not obs.enabled()
    for _ in range(3):
        assert _roundtrip(client, vt, "svc9", 50)
    assert not _roundtrip(client, vt, "svc9", 1)  # open
    assert moved("open") == before["open"] + 1
    assert REGISTRY.get("sentinel_breakers_open").value == 1
    vt.advance(1500)
    assert _roundtrip(client, vt, "svc9", 80)  # the probe, slow: reopens
    assert moved("half_open") == before["half_open"] + 1
    assert not _roundtrip(client, vt, "svc9", 1)
    assert moved("reopen") == before["reopen"] + 1
    vt.advance(1500)
    obs.TRACER.reset()
    obs.enable()
    try:
        assert _roundtrip(client, vt, "svc9", 2)  # the second probe, fast: closes
        assert _roundtrip(client, vt, "svc9", 2)
    finally:
        obs.disable()
    assert moved("closed") == before["closed"] + 1 and moved("half_open") == before["half_open"] + 2
    assert REGISTRY.get("sentinel_breakers_open").value == 0
    resolves = [s["attrs"] for s in obs.TRACER.snapshot() if s["name"] == "tick.resolve"]
    assert resolves and all({"items", "degrade_blocked", "cb_opened", "cb_half_opened", "cb_closed",
                             "cb_reopened", "cb_open_now"} <= set(a) for a in resolves)
    assert sum(a["cb_half_opened"] for a in resolves) == 1 and sum(a["cb_closed"] for a in resolves) == 1
    kinds = [e["kind"] for e in obs.FLIGHT.events()]
    assert "breaker.trip" in kinds and "breaker.close" in kinds
