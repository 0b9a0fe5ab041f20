"""Verdict provenance plane (obs/explain.py + the wire's explain section):
device-packed "explain" records for every blocked decision.

Covers the ISSUE-20 acceptance surface: the packed record round-trips
under jit at the 1M-resource (sketch) config; explain-section corruption
drops provenance but never touches a verdict (the main section still
fails CLOSED on its own checksum); a flash-crowd run stays >=99%
explainable; the block log's 5-field legacy and 7-field provenance line
formats both parse; and cluster v3 deny frames carry the same tuple.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from sentinel_tpu.chaos import FaultPlan, FaultSpec
from sentinel_tpu.chaos import failpoints as FP
from sentinel_tpu.core import errors as ERR
from sentinel_tpu.core.config import small_engine_config
from sentinel_tpu.core.rules import FlowRule
from sentinel_tpu.metrics.block_log import BlockLogger, parse_line
from sentinel_tpu.obs import REGISTRY
from sentinel_tpu.obs import explain as EX
from sentinel_tpu.ops import engine as E
from sentinel_tpu.ops import wire as WIRE


class _Reg:
    def resource_id(self, n):
        return 1


def _metric(name, **labels):
    m = REGISTRY.get(name, labels or None)
    return float(m.value) if m is not None else 0.0


def _rec(resource, kind, rule=None, sketch=False, forced=False,
         observed=None, threshold=None):
    """Build one 4-word wire record (the _device_explain layout)."""
    w1 = (
        int(kind)
        | (0x8 if sketch else 0)
        | (0x10 if forced else 0)
        | (((rule + 1) if rule is not None else 0) << 16)
    )
    return [int(resource), w1, EX.fx_encode(observed), EX.fx_encode(threshold)]


def _section(records, n_blocked=None):
    """Raw uint32 explain words [n_blocked, sec_sum, K*4 ...] with a
    CORRECT checksum — the shape ops/wire.py hands to obs/explain.py."""
    recs = np.asarray(records, np.uint32).reshape(-1)
    n = len(records) if n_blocked is None else n_blocked
    sec = (
        WIRE.EXPLAIN_MAGIC + n + int(np.sum(recs, dtype=np.uint64))
    ) & 0xFFFFFFFF
    return np.concatenate([np.asarray([n, sec], np.uint32), recs])


# -- fixed-point codec + layout ----------------------------------------------


def test_fx_codec_round_trip():
    assert EX.fx_encode(None) == EX.FX_UNKNOWN
    assert EX.fx_decode(EX.FX_UNKNOWN) is None
    # 1/256 resolution values survive exactly
    for v in (0.0, 1.0, 12.5, 3.00390625, 1e6):
        assert EX.fx_decode(EX.fx_encode(v)) == v
    # clamps: negatives to 0, overflow to the uint32-safe ceiling
    assert EX.fx_decode(EX.fx_encode(-5.0)) == 0.0
    assert EX.fx_encode(1e12) == int(EX.FX_MAX)


def test_wire_layout_explain_section_and_gate():
    # the gate: provenance rides ONLY the packed wire
    assert E.explain_k(small_engine_config()) == 0  # packed_wire unset
    assert E.explain_k(small_engine_config(packed_wire=True)) == 32
    assert E.explain_k(small_engine_config(packed_wire=True, explain_k=0)) == 0
    # layout: the section trails the hot block, main checksum stops at it
    cfg = small_engine_config(packed_wire=True)
    lo = WIRE.layout_for(cfg, 64)
    assert lo.expl_k == 32
    assert lo.total == lo.off_expl + 2 + lo.expl_k * WIRE.EXPLAIN_WORDS
    assert (lo.total - lo.off_expl) * 4 == 520  # the explain section's wire cost at K = 32
    # off: layout (and so the traced program) is unchanged
    lo_off = WIRE.layout_for(small_engine_config(packed_wire=True, explain_k=0), 64)
    assert lo_off.expl_k == 0 and lo_off.total == lo_off.off_expl


# -- device round-trip under jit at the 1M-resource config -------------------


@pytest.mark.jitted
def test_engine_packed_explain_round_trip_jit_1m_config():
    """A jitted packed tick at the sketch (1M+ resource id space) config:
    every blocked row's record decodes back with the right resource,
    kind, blamed rule slot, and fixed-point observed/threshold."""
    cfg = small_engine_config(
        packed_wire=True,
        explain_k=8,
        sketch_stats=True,
        sketch_width=256,
        sketch_capacity=1 << 20,  # 1M sketch-tier resources
    )
    rules = E._compile_ruleset(
        cfg, _Reg(), [FlowRule(resource="r", count=3.0)], [], [], [], [], None
    )
    b = 8
    wd = WIRE.acquire_wire_dtypes(cfg)
    acq = E.empty_acquire(cfg, b=b)._replace(
        res=jnp.ones((b,), jnp.int32),
        count=jnp.ones((b,), dtype=wd.get("count", np.int32)),
    )
    st = E.init_state(cfg)
    tick = E.make_tick(cfg, donate=False)
    z = jnp.float32(0.0)
    _st, out = tick(
        st, rules, acq, E.empty_complete(cfg, b=b), jnp.int32(1000), z, z
    )
    lo = WIRE.layout_for(cfg, b)
    frame = WIRE.unpack(np.asarray(out.wire).tobytes(), lo)
    verdict = np.asarray(frame.verdict)
    blocked_rows = np.flatnonzero(verdict == ERR.BLOCK_FLOW)
    assert len(blocked_rows) > 0  # count=3.0 over 8 requests must block
    assert frame.expl is not None
    n_blocked, rows = EX.decode_section(frame.expl)
    assert n_blocked == len(blocked_rows)
    recs = [EX.decode_record(r) for r in rows[:n_blocked]]
    assert all(r is not None for r in recs)
    for r in recs:
        assert r.resource == 1
        assert r.kind_name == "flow" and r.kind == ERR.BLOCK_FLOW
        assert r.rule == 0  # the single compiled flow slot
        assert not r.sketch_tier and not r.forced
        assert r.threshold == 3.0  # exact at 1/256 resolution
        assert r.observed is not None and r.observed >= 3.0
        assert r.margin is not None and r.margin >= 0.0
    # rows past n_blocked are zero padding
    assert not np.asarray(rows[n_blocked:]).any()


# -- decode integrity (fail-open contract) -----------------------------------


def test_decode_section_rejects_any_single_byte_corruption():
    words = _section(
        [_rec(7, ERR.BLOCK_FLOW, rule=2, observed=9.0, threshold=4.0),
         _rec(9, ERR.BLOCK_DEGRADE, rule=0, observed=1.0, threshold=0.5)]
    )
    n, rows = EX.decode_section(words)
    assert n == 2 and rows.shape == (2, WIRE.EXPLAIN_WORDS)
    good = words.tobytes()
    for pos in range(len(good)):
        bad = bytearray(good)
        bad[pos] ^= 0xFF
        with pytest.raises(EX.ExplainDecodeError):
            EX.decode_section(np.frombuffer(bytes(bad), np.uint32))


def test_decode_record_padding_unknown_kind_and_flags():
    # a zero padding row and an undecodable kind both drop, never raise
    assert EX.decode_record([0, 0, 0, 0]) is None
    assert EX.decode_record([5, 7, 0, 0]) is None  # kind 7 unknown
    r = EX.decode_record(
        _rec(3, ERR.BLOCK_FLOW, rule=None, sketch=True, forced=True,
             observed=None, threshold=2.0),
        ts_ms=123, origin="cluster",
    )
    assert r.rule is None and r.sketch_tier and r.forced
    assert r.observed is None and r.threshold == 2.0 and r.margin is None
    assert r.ts_ms == 123 and r.origin == "cluster"


def test_plane_counts_unexplained_beyond_capacity():
    plane = EX.ExplainPlane()
    # 5 blocked, section capacity carried only 2 records
    folded = plane.ingest_section(
        _section(
            [_rec(1, ERR.BLOCK_FLOW, rule=0, observed=5.0, threshold=2.0),
             _rec(2, ERR.BLOCK_PARAM, rule=1, threshold=3.0)],
            n_blocked=5,
        )
    )
    assert folded == 2
    cov = plane.coverage()
    assert cov == {"blocked": 5, "explained": 2, "frac": 0.4}
    # a pre-v3 remote deny has no provenance at all
    plane.count_unexplained(1)
    assert plane.coverage()["blocked"] == 6
    causes = plane.top_causes()
    assert sum(c["count"] for c in causes) == 2
    assert plane.latest_rule(2, ERR.BLOCK_PARAM) == 1
    assert plane.latest_rule(2, ERR.BLOCK_FLOW) is None


def test_plane_eps_annotation_flags_possibly_false_sketch_blocks():
    """A sketch-tier block whose margin is within the audit eps budget is
    the exact signature of a CMS-overestimate false block."""
    pf0 = _metric("sentinel_explain_possibly_false_total")
    plane = EX.ExplainPlane(eps_source=lambda: 10.0)
    within = plane.fold(EX.decode_record(
        _rec(4, ERR.BLOCK_FLOW, rule=0, sketch=True, observed=105.0,
             threshold=100.0)
    ))
    assert within.eps == 10.0 and within.possibly_false
    beyond = plane.fold(EX.decode_record(
        _rec(4, ERR.BLOCK_FLOW, rule=0, sketch=True, observed=150.0,
             threshold=100.0)
    ))
    assert beyond.possibly_false is False
    # exact-tier records carry no eps annotation at all
    exact = plane.fold(EX.decode_record(
        _rec(4, ERR.BLOCK_FLOW, rule=0, observed=101.0, threshold=100.0)
    ))
    assert exact.eps is None and not exact.possibly_false
    assert _metric("sentinel_explain_possibly_false_total") == pf0 + 1


# -- client path -------------------------------------------------------------


def test_client_explains_blocked_decisions(client_factory):
    c = client_factory()
    c.flow_rules.load([FlowRule(resource="expl/r", count=2.0)])
    got = [v for v, _ in c.check_batch(["expl/r"] * 5)]
    assert got.count(int(ERR.BLOCK_FLOW)) == 3
    recs = c.explain("expl/r")
    assert len(recs) == 3
    top = recs[0]
    assert top.kind_name == "flow" and top.rule is not None
    assert top.threshold == 2.0 and top.name == "expl/r"
    assert top.observed is not None and top.origin == "local"
    causes = c.explain_top_causes()
    assert causes and causes[0]["name"] == "expl/r"
    assert causes[0]["count"] == 3 and causes[0]["kind"] == "flow"
    cov = c.explain_coverage()
    assert cov["blocked"] == 3 and cov["frac"] == 1.0
    # unknown resource / plane-off answers stay shaped
    assert c.explain("never-seen") == []


@pytest.mark.parametrize("action", ["corrupt", "short_read", "drop", "raise"])
def test_explain_fault_drops_provenance_never_verdicts(client_factory, action):
    """obs.explain.decode faults: the tick's explanations are lost and
    counted; the verdicts are bit-identical to the unfaulted ticks."""
    c = client_factory()
    c.flow_rules.load([FlowRule(resource="ef/r", count=2.0)])
    c.check_batch(["ef/r"] * 4)  # fill the window
    base = [v for v, _ in c.check_batch(["ef/r"] * 4)]
    assert base == [int(ERR.BLOCK_FLOW)] * 4
    dec0 = _metric("sentinel_explain_decode_failures_total")
    pkd0 = _metric("sentinel_packed_decode_failures_total")
    exp0 = c.explain_coverage()["explained"]
    plan = FaultPlan(
        name=f"expl-{action}", seed=5,
        faults=[FaultSpec("obs.explain.decode", action, max_fires=1)],
    )
    with FP.armed(plan) as st:
        got = [v for v, _ in c.check_batch(["ef/r"] * 4)]
        assert st.injected().get(f"obs.explain.decode:{action}") == 1
    assert got == base  # verdicts untouched by the provenance fault
    assert _metric("sentinel_explain_decode_failures_total") == dec0 + 1
    assert _metric("sentinel_packed_decode_failures_total") == pkd0
    assert c.explain_coverage()["explained"] == exp0  # nothing folded
    # recovery: the next tick's provenance folds again
    c.check_batch(["ef/r"] * 2)
    assert c.explain_coverage()["explained"] == exp0 + 2


def test_main_section_still_fails_closed_with_explain_on(client_factory):
    """The split contract's other half: a mangled MAIN section fails the
    tick CLOSED exactly as before the explain section existed, and the
    failed tick contributes no provenance records."""
    c = client_factory()
    assert E.explain_k(c.cfg) > 0
    c.flow_rules.load([FlowRule(resource="mc/r", count=100.0)])
    c.check_batch(["mc/r"] * 2)
    dec0 = _metric("sentinel_explain_decode_failures_total")
    rec0 = _metric("sentinel_explain_records_total")
    plan = FaultPlan(
        name="main-corrupt", seed=11,
        faults=[FaultSpec("transport.packed.decode", "corrupt", max_fires=1)],
    )
    with FP.armed(plan):
        got = [v for v, _ in c.check_batch(["mc/r"] * 4)]
    assert got == [int(ERR.BLOCK_SYSTEM)] * 4
    assert _metric("sentinel_explain_decode_failures_total") == dec0
    assert _metric("sentinel_explain_records_total") == rec0


def test_flash_crowd_stays_explainable(client_factory):
    """The acceptance bar: >=99% of blocked decisions resolve through
    explain() in a flash-crowd run (explain_k sized to the batch — the
    operator knob for block-heavy workloads)."""
    cfg = small_engine_config(explain_k=64)
    c = client_factory(cfg=cfg)
    names = [f"crowd/r{i}" for i in range(8)]
    c.flow_rules.load([FlowRule(resource=n, count=4.0) for n in names])
    for _ in range(10):
        c.check_batch(names * 8)  # 64 decisions/tick, mostly blocked
        c.time.advance(40)
    cov = c.explain_coverage()
    assert cov["blocked"] > 300
    assert cov["frac"] >= 0.99
    # every resource can answer "why?", and the leaderboard adds up
    for n in names:
        recs = c.explain(n, limit=4)
        assert recs and all(r.kind_name == "flow" for r in recs)
    causes = c.explain_top_causes(len(names))
    assert sum(cz["count"] for cz in causes) == cov["explained"]


def test_sketch_tier_block_explains_with_sketch_flag(client_factory):
    """A block enforced from the SALSA/CMS estimate carries the
    sketch-tier flag — the hook the eps annotation keys off."""
    cfg = small_engine_config(
        max_resources=4, max_nodes=8, sketch_stats=True, sketch_width=256
    )
    c = client_factory(cfg=cfg)
    for i in range(5):  # exhaust the exact row space
        c.registry.resource_id(f"sk-{i}")
    c.flow_rules.load([FlowRule(resource="sk-tail", count=0)])
    rid = c.registry.peek_resource_id("sk-tail")
    if rid is not None and not c.registry.is_sketch_id(rid):
        pytest.skip("promotion found an exact row for the ruled resource")
    with pytest.raises(ERR.BlockException):
        with c.entry("sk-tail"):
            pass
    recs = c.explain("sk-tail")
    assert recs
    assert recs[0].kind_name == "flow" and recs[0].sketch_tier


def test_flight_bundle_carries_explain_section(client_factory):
    from sentinel_tpu.obs import flight as FL

    c = client_factory()
    c.flow_rules.load([FlowRule(resource="fb/r", count=1.0)])
    c.check_batch(["fb/r"] * 3)
    bundle = FL.FLIGHT.dump_bundle(reason="test")
    sec = bundle["providers"].get("explain")
    assert sec is not None
    assert sec["coverage"]["explained"] >= 2
    assert any(r["kind"] == "flow" for r in sec["recent"])
    assert sec["top_causes"][0]["count"] >= 2


# -- block log: 7-field provenance lines, legacy lines still parse -----------


def test_block_log_parses_both_line_formats(tmp_path):
    legacy = parse_line("5000|res1|FlowException|100|web")
    assert legacy == {
        "ts": 5000, "resource": "res1", "exception": "FlowException",
        "count": 100, "origin": "web", "kind": None, "rule": None,
    }
    expl = parse_line("5000|res1|FlowException|100|web|flow|3")
    assert expl["kind"] == "flow" and expl["rule"] == 3
    unattr = parse_line("5000|res1|FlowException|1|||")
    assert unattr["kind"] is None and unattr["rule"] is None
    assert parse_line("garbage") is None
    assert parse_line("a|b|c|d|e|f") is None  # 6 fields: neither format
    assert parse_line("x|res|E|nan|o") is None
    assert parse_line("5000|r|E|1|o|flow|notanint") is None
    # the writer emits legacy lines without provenance, 7-field with
    bl = BlockLogger(str(tmp_path))
    bl.log(5000, "r1", "FlowException", "web")
    bl.log(5000, "r2", "FlowException", "web", kind="flow", rule=2)
    bl.flush()
    lines = open(bl.path).read().strip().split("\n")
    assert "5000|r1|FlowException|1|web" in lines
    assert "5000|r2|FlowException|1|web|flow|2" in lines
    assert all(parse_line(ln) is not None for ln in lines)


def test_client_block_log_line_carries_provenance_key(
    client_factory, tmp_path, monkeypatch
):
    import sentinel_tpu.metrics.block_log as BL

    monkeypatch.setattr(BL, "_default", None)
    monkeypatch.setenv("CSP_SENTINEL_LOG_DIR", str(tmp_path))
    c = client_factory(block_log=True)
    c.flow_rules.load([FlowRule(resource="blk2", count=0)])
    with pytest.raises(ERR.BlockException):
        c.entry("blk2")
    c.block_log.flush()
    rows = [parse_line(ln) for ln in open(c.block_log.path)]
    row = next(r for r in rows if r and r["resource"] == "blk2")
    assert row["exception"] == "FlowException"
    assert row["kind"] == "flow" and row["rule"] == 0
    monkeypatch.setattr(BL, "_default", None)


# -- cluster v3 deny provenance ----------------------------------------------


def test_cluster_deny_provenance_round_trips():
    from sentinel_tpu.cluster import protocol as CP

    rsp = CP.ClusterBatchResponse(
        xid=7, status=0,
        statuses=np.asarray([0, 2, 0], np.int8),
        remainings=np.asarray([1, 0, 1], np.int32),
        waits=np.zeros(3, np.int32),
        token_ids=np.zeros(3, np.int64),
        prov=[None, (ERR.BLOCK_FLOW, 3, 12.5, 10.0), None],
    )
    frame = CP.encode_batch_response(rsp)
    out = CP.decode_batch_response(frame[2:])
    assert out.prov == [None, (ERR.BLOCK_FLOW, 3, 12.5, 10.0), None]
    # unknown observed/limit survive as None (FX_UNKNOWN on the wire)
    rsp2 = dataclasses.replace(
        rsp, prov=[None, (ERR.BLOCK_PARAM, 0, None, None), None]
    )
    out2 = CP.decode_batch_response(CP.encode_batch_response(rsp2)[2:])
    assert out2.prov[1] == (ERR.BLOCK_PARAM, 0, None, None)
    # no provenance at all: the frame is byte-identical to v2
    plain = CP.encode_batch_response(dataclasses.replace(rsp, prov=None))
    empty = CP.encode_batch_response(
        dataclasses.replace(rsp, prov=[None, None, None])
    )
    assert plain == empty
    assert CP.decode_batch_response(plain[2:]).prov is None


def test_plane_folds_remote_deny_provenance():
    plane = EX.ExplainPlane()
    rec = plane.fold_remote(
        resource=42, kind=ERR.BLOCK_FLOW, rule=3, observed=12.5,
        threshold=10.0, ts_ms=999,
    )
    assert rec.origin == "cluster" and rec.kind_name == "flow"
    assert rec.rule == 3 and rec.margin == 2.5
    assert plane.coverage() == {"blocked": 1, "explained": 1, "frac": 1.0}
    assert plane.top_causes()[0]["origin"] == "cluster"
    # an unknown kind from a newer peer drops cleanly
    assert plane.fold_remote(1, kind=99, rule=0, observed=None,
                             threshold=None) is None
