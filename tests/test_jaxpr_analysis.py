"""sentinel_tpu.analysis.jaxpr — the tier-2 semantic analyzer.

Three jobs:

1. unit-test every jaxpr pass on tiny traced fixtures, one triggering
   and one non-triggering per rule — including THE demonstration the
   tier exists for: a module-level ``jnp`` const (the documented
   rowmin/rank/segment hazard class) is caught here and invisible to
   the AST tier;
2. golden-file mechanics: fingerprint mismatch/missing, budget breach,
   and the update round-trip;
3. THE CI GATE: trace the real engine/ops entry points and require both
   tiers clean vs the checked-in goldens — this is what keeps hoisted
   consts, timestamp wraps, smuggled callbacks, silent program drift,
   and cost regressions off the admission path.

Runs under JAX_PLATFORMS=cpu (tests/conftest.py); pallas kernels trace
via abstract eval — nothing here executes a tick.
"""

from __future__ import annotations

import ast
import json
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sentinel_tpu.analysis import ALL_PASSES, REPO_ROOT
from sentinel_tpu.analysis.framework import ParsedModule, parse_suppressions
from sentinel_tpu.analysis.jaxpr import (
    entry_signature,
    load_golden,
    run_jaxpr_analysis,
    save_golden,
)
from sentinel_tpu.analysis.jaxpr.framework import TracedEntry, walk_eqns
from sentinel_tpu.analysis.jaxpr.passes import (
    ConstHoistPass,
    CostBudgetPass,
    DtypeOverflowPass,
    FingerprintPass,
    TransferGuardPass,
)

# module-level jnp const — the EXACT hazard the ops comments guard by
# hand (rowmin.py:36 "numpy scalar, NOT jnp"); hoisted into the jaxpr of
# any function closing over it
_BAD_DEVICE_CONST = jnp.float32(-3.0e38)
_GOOD_NP_CONST = np.float32(-3.0e38)


def _entry(fn, *args, name="fixture", time_invars=(), **kw) -> TracedEntry:
    return TracedEntry(
        name=name,
        path="sentinel_tpu/ops/engine.py",
        closed_jaxpr=jax.make_jaxpr(fn)(*args),
        time_invars=time_invars,
        **kw,
    )


# ---------------------------------------------------------------------------
# const-hoist
# ---------------------------------------------------------------------------


def test_const_hoist_catches_module_jnp_const():
    """The rowmin/rank/segment hazard class: a module-level jnp scalar
    becomes a device-array const of the traced program."""
    e = _entry(lambda x: jnp.maximum(x, _BAD_DEVICE_CONST), jnp.zeros((4,)))
    got = list(ConstHoistPass().run(e))
    assert len(got) == 1
    assert got[0].rule == "const-hoist"
    assert "np.int32" in got[0].message  # the fix is named in the message


def test_const_hoist_np_scalar_is_clean():
    e = _entry(lambda x: jnp.maximum(x, _GOOD_NP_CONST), jnp.zeros((4,)))
    assert list(ConstHoistPass().run(e)) == []


def test_const_hoist_invisible_to_ast_tier():
    """The AST tier cannot distinguish the two spellings — both are
    module-level assignments feeding jnp.maximum; only the jaxpr shows
    the const's concrete type.  This is the gap the tier-2 analyzer
    closes."""
    source = textwrap.dedent(
        """
        import jax.numpy as jnp

        _NEG = jnp.float32(-3.0e38)

        def fill(x):
            return jnp.maximum(x, _NEG)
        """
    )
    line_disables, file_disables = parse_suppressions(source)
    mod = ParsedModule(
        path="sentinel_tpu/ops/rank.py",
        abspath="/sentinel_tpu/ops/rank.py",
        source=source,
        tree=ast.parse(source),
        line_disables=line_disables,
        file_disables=file_disables,
    )
    ast_findings = [f for p in ALL_PASSES for f in p.run(mod)]
    assert ast_findings == [], [f.message for f in ast_findings]


def test_const_hoist_warns_on_large_numpy_const():
    big = np.ones((1 << 15,), np.float32)  # 128 KiB > the 64 KiB bound
    e = _entry(lambda x: x + big, jnp.zeros((1 << 15,), jnp.float32))
    got = list(ConstHoistPass().run(e))
    assert len(got) == 1 and got[0].severity == "warning"


# ---------------------------------------------------------------------------
# transfer-guard
# ---------------------------------------------------------------------------


def test_transfer_guard_catches_pure_callback():
    def leaky(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v) * 2, jax.ShapeDtypeStruct((4,), jnp.float32), x
        )
        return y + 1

    e = _entry(leaky, jnp.zeros((4,), jnp.float32))
    got = list(TransferGuardPass().run(e))
    assert len(got) == 1 and "callback" in got[0].message


def test_transfer_guard_clean_tensor_program():
    e = _entry(lambda x: jnp.cumsum(x) * 2, jnp.zeros((8,), jnp.float32))
    assert list(TransferGuardPass().run(e)) == []


def test_transfer_guard_flags_readbacks_outside_fused_wire():
    """A packed-wire tick whose TickOutput still carries a live stats (or
    verdict) array has silently un-fused the transport: _resolve_tick
    would sync that array separately from the single wire transfer."""
    e = _entry(
        lambda x: x + 1,
        jnp.zeros((4,), jnp.float32),
        packed_wire=True,
        readback_fields=("wait_ms", "seg_dropped", "stats", "wire"),
    )
    got = list(TransferGuardPass().run(e))
    assert len(got) == 1 and "'stats'" in got[0].message

    # ...and a packed entry that lost the wire buffer itself is flagged
    e = _entry(
        lambda x: x + 1,
        jnp.zeros((4,), jnp.float32),
        packed_wire=True,
        readback_fields=("verdict", "wait_ms"),
    )
    msgs = [f.message for f in TransferGuardPass().run(e)]
    assert any("no fused 'wire' buffer" in m for m in msgs)
    assert any("'verdict'" in m for m in msgs)

    # ...and so is a client-facing program that takes its batch as columns
    # again: every leaf after (state, rules) is an upload a tick
    e = _entry(
        lambda x: x + 1,
        jnp.zeros((4,), jnp.float32),
        packed_wire=True,
        readback_fields=("wait_ms", "seg_dropped", "wire"),
        client_inputs=(7, 21),
    )
    (got,) = list(TransferGuardPass().run(e))
    assert "7 arguments with 21 batch-input leaves" in got.message


def test_transfer_guard_packed_allowance_is_clean():
    e = _entry(
        lambda x: x + 1,
        jnp.zeros((4,), jnp.float32),
        packed_wire=True,
        readback_fields=("wait_ms", "seg_dropped", "wire"),
        client_inputs=(3, 1),
    )
    assert list(TransferGuardPass().run(e)) == []


def test_packed_wire_entry_readback_surface_is_fused():
    """The REAL tick/packed-wire entry: eval_shape-observed live outputs
    must be exactly the fused buffer + the sidecar escape hatch — this is
    the acceptance invariant 'four readbacks fused to one' as a gate."""
    from sentinel_tpu.analysis.jaxpr.entrypoints import trace_entries

    ents = {e.name: e for e in trace_entries()}
    e = ents["tick/packed-wire"]
    assert e.packed_wire and e.readback_fields is not None
    assert "wire" in e.readback_fields
    assert set(e.readback_fields) <= {"wire", "wait_ms", "seg_dropped"}
    # ...and one upload: the program the packed client calls takes state,
    # rules and one input buffer, and is fingerprinted under its own name
    assert e.client_inputs == (3, 1)
    assert len(ents["tick/wire-in"].closed_jaxpr.jaxpr.invars) == (
        len(e.closed_jaxpr.jaxpr.invars) - 21 + 1
    )
    # the classic entries keep the multi-array form and are not gated
    assert ents["tick/plain"].packed_wire is False


# ---------------------------------------------------------------------------
# dtype-overflow
# ---------------------------------------------------------------------------


def test_dtype_overflow_flags_ms_scale_up():
    e = _entry(lambda t: t * 1000, jnp.int32(1_000), time_invars=(0,))
    got = list(DtypeOverflowPass().run(e))
    assert len(got) == 1
    assert "1000x" in got[0].message


def test_dtype_overflow_flags_narrowing_and_traced_mul():
    e1 = _entry(lambda t: t.astype(jnp.int16), jnp.int32(1_000), time_invars=(0,))
    assert any("narrowed" in f.message for f in DtypeOverflowPass().run(e1))
    e2 = _entry(
        lambda t, v: t * v, jnp.int32(1_000), jnp.int32(7), time_invars=(0,)
    )
    got = list(DtypeOverflowPass().run(e2))
    assert len(got) == 1 and "traced value" in got[0].message


def test_dtype_overflow_flags_pow_and_int_dot():
    """t**2 is the same wrap class as t*t (integer_pow must not slip
    through the unknown-primitive fallback), and an integer dot_general
    over tainted values is length-scaled accumulation."""
    e = _entry(lambda t: t**2, jnp.int32(1_000), time_invars=(0,))
    got = list(DtypeOverflowPass().run(e))
    assert len(got) == 1 and "power 2" in got[0].message
    e2 = _entry(
        lambda t: jnp.dot(jnp.full((4,), t), jnp.ones((4,), jnp.int32)),
        jnp.int32(1_000),
        time_invars=(0,),
    )
    got2 = list(DtypeOverflowPass().run(e2))
    assert len(got2) == 1 and "dot_general" in got2[0].message


def test_dtype_overflow_scans_while_loop_condition():
    """Deadline/spin conditions live in the while COND jaxpr — tainted
    arithmetic there must not escape the gate."""
    fn = lambda t: jax.lax.while_loop(  # noqa: E731
        lambda s: s * 1000 < 10_000_000, lambda s: s + 1, t
    )
    e = _entry(fn, jnp.int32(1), time_invars=(0,))
    got = list(DtypeOverflowPass().run(e))
    assert len(got) == 1 and "1000x" in got[0].message


def test_dtype_overflow_window_math_is_legal():
    """The operations the engine actually does with now_ms: bucket id,
    phase, round-trip to epoch start, deadline offsets, comparisons —
    none change the ms scale class."""

    def window_math(t):
        wid = t // 500
        idx = t % 500
        start = wid * 500
        deadline = t + 3_000
        fresh = (t - start) < 250
        return wid, idx, start, deadline, fresh

    e = _entry(window_math, jnp.int32(1_000), time_invars=(0,))
    assert list(DtypeOverflowPass().run(e)) == []


def test_dtype_overflow_untainted_counters_are_ignored():
    # length-scaled int accumulation of NON-timestamp values is the
    # engine's bread and butter (histograms); no taint, no finding
    e = _entry(lambda c: jnp.cumsum(c), jnp.ones((64,), jnp.int32))
    assert list(DtypeOverflowPass().run(e)) == []


# ---------------------------------------------------------------------------
# recompile-fingerprint
# ---------------------------------------------------------------------------


def test_fingerprint_roundtrip_and_drift(tmp_path):
    golden_path = str(tmp_path / "fingerprints.json")
    e = _entry(lambda x: x * 2 + 1, jnp.zeros((4,), jnp.float32), name="fp/probe")

    p = FingerprintPass(golden_path=golden_path)
    got = list(p.run(e))
    assert len(got) == 1 and "no golden fingerprint" in got[0].message

    save_golden(
        golden_path,
        {"jax_version": jax.__version__, "entries": {"fp/probe": entry_signature(e)}},
    )
    assert list(FingerprintPass(golden_path=golden_path).run(e)) == []

    # the same NAME tracing to a different program = drift
    e2 = _entry(
        lambda x: x * 2.0 + jnp.sum(x), jnp.zeros((4,), jnp.float32), name="fp/probe"
    )
    got = list(FingerprintPass(golden_path=golden_path).run(e2))
    assert len(got) == 1 and "traced program changed" in got[0].message


def test_fingerprint_is_weak_type_sensitive():
    """Weak-type drift on an entry INPUT is a real recompile hazard (one
    extra executable specialization per call site) — str(aval) hides
    weak_type, so the signature must encode it explicitly."""
    strong = _entry(lambda x, s: x * s, jnp.zeros((4,)), jnp.float32(2.0))
    weak = _entry(lambda x, s: x * s, jnp.zeros((4,)), 2.0)
    assert entry_signature(strong)["hash"] != entry_signature(weak)["hash"]


# ---------------------------------------------------------------------------
# flops-bytes-budget
# ---------------------------------------------------------------------------


def _budget_entry(flops, byts, name="bud/probe"):
    return _entry(
        lambda x: x * 2,
        jnp.zeros((4,), jnp.float32),
        name=name,
        cost_eligible=True,
        cost={"flops": flops, "bytes": byts},
    )


def test_budget_breach_missing_and_pass(tmp_path):
    path = str(tmp_path / "budgets.json")
    e = _budget_entry(2_000.0, 64_000.0)

    got = list(CostBudgetPass(budget_path=path).run(e))
    assert len(got) == 1 and "no cost budget" in got[0].message

    save_golden(
        path, {"entries": {"bud/probe": {"flops": 2_500, "bytes": 80_000}}}
    )
    assert list(CostBudgetPass(budget_path=path).run(e)) == []

    hot = _budget_entry(9_999.0, 64_000.0)
    got = list(CostBudgetPass(budget_path=path).run(hot))
    assert len(got) == 1 and "exceeds the checked-in ceiling" in got[0].message

    exempt = _entry(lambda x: x, jnp.zeros((4,)), name="bud/exempt")
    assert list(CostBudgetPass(budget_path=path).run(exempt)) == []


# ---------------------------------------------------------------------------
# the CI gate: real entry points vs checked-in goldens
# ---------------------------------------------------------------------------


def test_jaxpr_tier_clean_on_real_entry_points():
    """THE tier-2 gate: trace `ops.engine.tick` (plain/MXU/fused-seg and
    the cluster token-decision feature set), the segscan/fused/rank/
    window kernels, and run all five semantic passes.  A failure means a
    PR hoisted a device const, scaled a timestamp, smuggled a callback,
    changed a traced program without --update-fingerprints, or breached
    a cost ceiling."""
    findings = run_jaxpr_analysis()
    assert findings == [], "jaxpr-tier findings:\n" + "\n".join(
        f"{f.path}:{f.line}: [{f.rule}] {f.message}" for f in findings
    )


def test_goldens_cover_every_entry_point():
    """fingerprints.json tracks the live entry list — a new entry point
    without a golden (or a stale golden naming a removed entry) fails
    here rather than surfacing as a confusing missing-fingerprint
    finding in an unrelated PR."""
    from sentinel_tpu.analysis.jaxpr import FINGERPRINTS_PATH
    from sentinel_tpu.analysis.jaxpr.entrypoints import trace_entries

    live = {e.name for e in trace_entries()}
    golden = set(load_golden(FINGERPRINTS_PATH).get("entries", {}))
    assert golden == live


def test_tick_jaxpr_has_no_pallas_on_plain_config():
    """Sanity on the entry list itself: the plain-config tick must stay
    pallas-free (interpret-mode kernels on the scatter path would mean
    the config gating broke), while fused-seg must contain pallas_call."""
    from sentinel_tpu.analysis.jaxpr.entrypoints import trace_entries

    by_name = {e.name: e for e in trace_entries()}
    plain_prims = {eq.primitive.name for eq in walk_eqns(by_name["tick/plain"].closed_jaxpr)}
    seg_prims = {eq.primitive.name for eq in walk_eqns(by_name["tick/fused-seg"].closed_jaxpr)}
    assert "pallas_call" not in plain_prims
    assert "pallas_call" in seg_prims
