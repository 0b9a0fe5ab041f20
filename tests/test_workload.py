"""Workload engine + closed-loop autotuner (ROADMAP item 3).

Covers the offered half (shapes are pure arithmetic, the generator
replays bit-identically from its seed, emit faults drop exactly), the
shared ``OperatingPoint`` definition all three consumers ride, the
service model's tradeoff surface, and the tuner itself: live
``apply_operating_point`` swaps under expected-retrace journaling,
fail-open on raising steps, the HBM guardrail, and the acceptance
claim — the tuned loop beats the static default on SLO-bad fraction
with a bit-replayable decision journal.
"""

import math

import pytest

import sentinel_tpu as st
from sentinel_tpu import workload as WL
from sentinel_tpu.chaos import failpoints as FP
from sentinel_tpu.chaos.plans import FaultPlan, FaultSpec
from sentinel_tpu.core.config import small_engine_config
from sentinel_tpu.obs import profile as PROF
from sentinel_tpu.obs.registry import REGISTRY
from sentinel_tpu.obs.slo import SloEngine
from sentinel_tpu.utils.time_source import VirtualTimeSource


def _cval(name, labels=None):
    m = REGISTRY.get(name, labels)
    return float(m.value) if m is not None else 0.0


# -- shapes ------------------------------------------------------------------


def test_flash_crowd_envelope_is_pure_arithmetic():
    fc = WL.FlashCrowd(peak=8.0, start_step=10, ramp_steps=4, hold_steps=6, decay_steps=2)
    assert fc.rate_at(9) == 0.0
    assert fc.rate_at(10) == pytest.approx(2.0)  # ramp: peak*(t+1)/ramp
    assert fc.rate_at(13) == pytest.approx(8.0)
    assert fc.rate_at(14) == 8.0 and fc.rate_at(19) == 8.0  # hold
    assert fc.rate_at(20) == pytest.approx(8.0)  # decay start
    assert fc.rate_at(21) == pytest.approx(4.0)
    assert fc.rate_at(22) == 0.0
    d = WL.Diurnal(base=4.0, amplitude=0.5, period_steps=8)
    assert d.rate_at(0) == pytest.approx(4.0)
    assert d.rate_at(2) == pytest.approx(6.0)  # sin peak
    # pure functions: re-evaluation is identical, no hidden state
    assert [d.rate_at(s) for s in range(16)] == [d.rate_at(s) for s in range(16)]
    hp = WL.HotParamFlood(rate=5.0, start_step=2, duration_steps=3, key="wl/t")
    assert [hp.rate_at(s) for s in range(6)] == [0.0, 0.0, 5.0, 5.0, 5.0, 0.0]
    assert hp.keys.key_for(0, 0.3, hp.keys._cdf()) == "wl/t"


def test_zipf_churn_rotates_hot_set():
    z = WL.ZipfKeys(n_keys=8, churn_every_steps=10, churn_shift=3, prefix="k")
    cdf = z._cdf()
    # rank 0 (hottest) rotates by churn_shift each churn epoch
    assert z.key_for(0, 0.0, cdf) == "k0"
    assert z.key_for(10, 0.0, cdf) == "k3"
    assert z.key_for(20, 0.0, cdf) == "k6"
    sk = WL.SkewedKeys(keys=(("hot", 0.9), ("cold", 0.1)))
    c2 = sk._cdf()
    assert sk.key_for(0, 0.5, c2) == "hot"
    assert sk.key_for(0, 0.95, c2) == "cold"


# -- generator ---------------------------------------------------------------


def test_generator_bit_replay_and_seed_divergence():
    spec = WL.flash_crowd_2x(seed=11, base=2.0, steps=40, start_step=10)
    a = WL.TrafficGenerator(spec).all_events()
    b = WL.TrafficGenerator(spec).all_events()
    assert a == b and len(a) > 0
    assert WL.TrafficGenerator(spec.with_seed(12)).all_events() != a
    # error-diffusion accounting: per-shape event counts are exactly the
    # floor of the shape's cumulative rate — zero entropy in the counts
    for shape in spec.shapes:
        want = math.floor(sum(shape.rate_at(s) for s in range(spec.steps)))
        got = sum(1 for ev in a if ev.shape == shape.name)
        assert got == want


def test_gen_emit_failpoint_drops_steps_exactly():
    spec = WL.flash_crowd_2x(seed=5, base=2.0, steps=30, start_step=8)
    baseline = WL.TrafficGenerator(spec).all_events()
    drops0 = _cval("sentinel_workload_emit_drops_total")
    plan = FaultPlan(
        seed=3,
        faults=[
            FaultSpec(
                "workload.gen.emit",
                "raise",
                every_nth=7,
                max_fires=2,
                exc="RuntimeError",
            )
        ],
    )
    with FP.armed(plan) as armed:
        got = WL.TrafficGenerator(spec).all_events()
    assert armed.injected() == {"workload.gen.emit:raise": 2}
    assert _cval("sentinel_workload_emit_drops_total") - drops0 == 2.0
    # a fault drops whole steps, nothing else: the survivor stream is the
    # baseline minus the dropped steps' events
    dropped = {ev.step for ev in baseline} - {ev.step for ev in got}
    assert 0 < len(got) < len(baseline)
    assert got == [ev for ev in baseline if ev.step not in dropped]


# -- the shared OperatingPoint -----------------------------------------------


def test_operating_point_is_the_shared_definition():
    cfg = small_engine_config()
    op = WL.sim_default_op()
    # identity against the small config — seeded sim/chaos goldens safe
    assert op.engine_changes(cfg) == {}
    assert op.apply_to_config(cfg) is cfg
    op2 = op.replace(batch_size=16, complete_batch_size=16)
    assert op2.engine_changes(cfg) == {"batch_size": 16, "complete_batch_size": 16}
    cfg2 = op2.apply_to_config(cfg)
    assert (cfg2.batch_size, cfg2.complete_batch_size) == (16, 16)
    assert op2.describe().startswith("b16/c16/")
    # the simulator preset derives its queue bound from the same point
    from sentinel_tpu.adaptive.simload import storm_controller_preset

    assert storm_controller_preset().queue_max == int(op.pipeline_depth)
    assert storm_controller_preset(op.replace(pipeline_depth=3)).queue_max == 3


def test_service_model_has_a_real_tradeoff_surface():
    m = WL.ServiceModel()
    small = WL.OperatingPoint(batch_size=2, complete_batch_size=2)
    mid = WL.OperatingPoint(batch_size=16, complete_batch_size=16)
    big = WL.OperatingPoint(batch_size=64, complete_batch_size=64)
    # bigger batches cost more per tick and earn fewer ticks per step
    assert m.tick_us(small) < m.tick_us(mid) < m.tick_us(big)
    assert m.ticks_per_step(small) >= m.ticks_per_step(mid) >= m.ticks_per_step(big)
    # pipelining buys tick budget but charges readback latency
    piped = mid.replace(pipeline_depth=2)
    assert m.ticks_per_step(piped) >= m.ticks_per_step(mid)
    assert m.extra_wait_ms(piped) > m.extra_wait_ms(mid) == 0.0
    # audit cadence and slack windows amortize tick cost
    assert m.tick_us(mid.replace(audit_period=4)) > m.tick_us(mid.replace(audit_period=64))
    slacked = mid.replace(sketch_sample_count=60, sketch_slack_frac=0.1)
    exact = mid.replace(sketch_sample_count=60, sketch_slack_frac=0.0)
    assert m.tick_us(slacked) < m.tick_us(exact)


def test_service_backend_batches_and_flushes():
    m = WL.ServiceModel(flush_steps=3)
    b = WL.ServiceBackend(m, WL.OperatingPoint(batch_size=4, complete_batch_size=4))
    b.submit(0, 1)
    # a lone item waits for the batch to fill (the big-batch cost)...
    assert b.advance(1) == [] and b.depth() == 1
    # ...until flush age forces the tick
    assert b.advance(3) == []  # fired into service, due next step
    done = b.advance(4)
    assert len(done) == 1
    lat, rid = done[0]
    assert rid == 1 and lat > 4 * m.step_ms  # queue wait dominates
    assert b.depth() == 0


# -- live apply + tuner ------------------------------------------------------


def test_apply_operating_point_live_swap(client):
    surprise0 = PROF.RETRACE.surprise_count()
    op0 = WL.OperatingPoint.from_engine_config(client.cfg)
    assert client.apply_operating_point(op0) == {"engine": False, "host": []}
    # host-only knob: attribute write, no compiled-program impact
    out = client.apply_operating_point(op0.replace(pipeline_depth=2))
    assert out == {"engine": False, "host": ["pipeline_depth"]}
    # engine knob: compile-then-swap, journaled as ONE expected retrace
    op1 = op0.replace(batch_size=16, complete_batch_size=16, pipeline_depth=2)
    out = client.apply_operating_point(op1)
    assert out["engine"] is True
    assert client.cfg.batch_size == 16 and client.cfg.complete_batch_size == 16
    # decisions keep flowing through the swapped engine
    verdicts = client.check_batch(["wl/after-swap"] * 3, inbound=True)
    assert len(verdicts) == 3
    assert PROF.RETRACE.surprise_count() == surprise0


def test_tuner_step_fail_open_rolls_back_to_last_good(client):
    slo = SloEngine(specs=WL.workload_slos(), registry=REGISTRY)
    try:
        op0 = WL.OperatingPoint.from_engine_config(client.cfg)
        cand = op0.replace(batch_size=16, complete_batch_size=16)
        t = WL.AutoTuner(
            client,
            slo,
            op0,
            [cand],
            seed=3,
            tcfg=WL.TunerConfig(settle_steps=1, warmup_steps=0),
        )
        fails0 = _cval("sentinel_tuner_step_failures_total")
        t.step(client.time.now_ms())  # measures the incumbent, moves to cand
        assert t.current == cand and t.best == op0
        plan = FaultPlan(
            seed=1,
            faults=[
                FaultSpec("workload.tuner.step", "raise", max_fires=1, exc="RuntimeError")
            ],
        )
        with FP.armed(plan) as armed:
            t.step(client.time.now_ms())
        assert armed.injected() == {"workload.tuner.step:raise": 1}
        assert _cval("sentinel_tuner_step_failures_total") - fails0 == 1.0
        # failed OPEN: back on the last-good point, client included
        assert t.current == op0 and t.best == op0
        assert client.cfg.batch_size == op0.batch_size
        assert t.decisions[-1]["action"] == "fail_open"
        # serving continues after the fail-open
        assert len(client.check_batch(["wl/post-fail"] * 2, inbound=True)) == 2
    finally:
        slo.close()


def test_tuner_rejects_candidate_that_would_breach_hbm(client_factory):
    client = client_factory(cfg=small_engine_config(sketch_stats=True))
    slo = SloEngine(specs=WL.workload_slos(), registry=REGISTRY)
    cap0 = int(PROF.LEDGER.snapshot().get("capacity_bytes") or 0)
    PROF.LEDGER.set_capacity(PROF.LEDGER.total_bytes() + 1)
    try:
        op0 = WL.OperatingPoint.from_engine_config(client.cfg)
        grown = op0.replace(sketch_sample_count=max(8, op0.sketch_sample_count) * 8)
        t = WL.AutoTuner(
            client,
            slo,
            op0,
            [grown],
            seed=3,
            tcfg=WL.TunerConfig(settle_steps=1, warmup_steps=0),
        )
        breach0 = _cval("sentinel_hbm_capacity_breaches_total")
        t.step(client.time.now_ms())
        acts = [d["action"] for d in t.decisions]
        assert "rejected_hbm" in acts and "converged" in acts
        # never applied: the client still runs the incumbent point
        assert t.current == op0 and t.best == op0 and t.converged
        assert client.cfg.sketch_sample_count == op0.sketch_sample_count
        assert _cval("sentinel_hbm_capacity_breaches_total") == breach0
    finally:
        PROF.LEDGER.set_capacity(cap0)
        slo.close()


# -- the closed loop (acceptance) --------------------------------------------


def _fresh_client(client_factory):
    return client_factory(time_source=VirtualTimeSource(start_ms=1_000))


def test_closed_loop_tuner_beats_static_default(client_factory):
    """ISSUE 19 acceptance: under the seeded flash-crowd-at-2× shape the
    tuner converges to an operating point with a LOWER SLO-bad fraction
    than the static default, with zero surprise retraces."""
    spec = WL.flash_crowd_2x(seed=7, steps=160)  # the perf-smoke shape
    surprise0 = PROF.RETRACE.surprise_count()

    def run(tune):
        c = _fresh_client(client_factory)
        op0 = WL.OperatingPoint.from_engine_config(c.cfg)  # static b64
        cands = [
            op0.replace(batch_size=16, complete_batch_size=16),
            op0.replace(batch_size=8, complete_batch_size=8),
        ]
        out = WL.run_closed_loop(
            c, spec, op0, candidates=cands if tune else (), tune=tune
        )
        c.stop()
        return op0, out

    op0, static = run(False)
    _, tuned = run(True)
    for r in (static, tuned):
        assert r.submitted == r.passed + r.blocked > 0
        assert len(r.latencies_ms) == r.passed  # every admit completed
    assert static.decisions == [] and static.converged_op == op0
    # the tuner moved off the default and earned a lower bad fraction
    assert tuned.converged_op != op0
    assert any(d["action"] == "applied" for d in tuned.decisions)
    assert tuned.decisions[-1]["action"] in ("converged", "rollback")
    assert tuned.bad_frac() < static.bad_frac()
    # retrace guardrail: every move was an EXPECTED retrace
    assert PROF.RETRACE.surprise_count() == surprise0


@pytest.mark.slow
def test_closed_loop_decisions_replay_bit_identically(client_factory):
    """Two tuned runs at one seed produce IDENTICAL offered streams,
    decision journals and latency sequences (the replay half of the
    acceptance)."""
    spec = WL.flash_crowd_2x(seed=7, base=3.0, steps=60, start_step=10)
    assert (
        WL.TrafficGenerator(spec).all_events()
        == WL.TrafficGenerator(spec).all_events()
    )

    def run():
        c = _fresh_client(client_factory)
        op0 = WL.OperatingPoint.from_engine_config(c.cfg)
        out = WL.run_closed_loop(
            c,
            spec,
            op0,
            candidates=[
                op0.replace(batch_size=16, complete_batch_size=16),
                op0.replace(batch_size=8, complete_batch_size=8),
            ],
            tune=True,
            tune_every=4,
            tcfg=WL.TunerConfig(settle_steps=3, warmup_steps=1),
        )
        c.stop()
        return out

    a, b = run(), run()
    assert a.decisions == b.decisions and len(a.decisions) > 0
    assert a.latencies_ms == b.latencies_ms
    assert (a.submitted, a.passed, a.blocked) == (b.submitted, b.passed, b.blocked)
    assert a.converged_op == b.converged_op
