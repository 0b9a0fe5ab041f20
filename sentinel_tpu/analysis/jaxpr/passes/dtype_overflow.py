"""dtype-overflow: i32 timestamps scaled or accumulated past wraparound.

The engine keeps time as int32 engine-epoch milliseconds by design
(2^31 ms ≈ 24.8 days of uptime, rolled by the host clock discipline).
That budget survives division, remainder, comparison, and small offsets
— the operations the window/breaker math actually needs — but NOT
multiplication or unbounded accumulation: one `ms * 1000` (a µs
conversion someone "just needed") wraps in 35 minutes and the verdicts
silently corrupt, the classic sketch-datapath width bug (SALSA's
correctness argument is exactly about these placement/width properties).

Mechanism: forward taint over the jaxpr.  Entry points declare which
flat invars carry ms-scale timestamps (`TracedEntry.time_invars`);
every tainted integer value carries a **net scale factor** relative to
raw ms.  Propagation:

* ``div`` by a literal d divides the factor; ``mul`` by a literal m
  multiplies it — so ``(now // w) * w`` nets out at 1 and stays legal;
* ``rem`` by a small literal BOUNDS the value and clears the taint
  (bucket indices are safe by construction);
* add/sub/min/max/select keep the max operand factor (offsets don't
  change scale class);
* casting to float or bool clears the taint (floats have their own,
  different, precision hazard — out of scope here);
* casting a tainted value to a NARROWER int is flagged immediately;
* ``mul`` of a tainted int by a non-literal is flagged (unbounded
  scale), as is `reduce_sum`/`cumsum` over a tainted axis (length-scaled
  accumulation).

A finding fires when an equation first pushes the factor above
``MAX_SCALE`` (4x ms — wrap inside 6.2 days), anchored to the source
line recorded in the equation's trace frames, so a deliberate wrap can
be suppressed in place with ``# stlint: disable=dtype-overflow`` and a
rationale.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Iterable, List, Optional, Tuple

from sentinel_tpu.analysis.framework import ERROR, Finding
from sentinel_tpu.analysis.jaxpr.framework import JaxprPass, TracedEntry, eqn_source

#: max tolerated net scale-up of a raw-ms value (4x ms wraps in ~6 days)
MAX_SCALE = 4.0

_PASSTHROUGH_MAXES = frozenset(
    {
        "add",
        "sub",
        "max",
        "min",
        "clamp",
        "select_n",
        "broadcast_in_dim",
        "reshape",
        "squeeze",
        "slice",
        "dynamic_slice",
        "dynamic_update_slice",
        "gather",
        "scatter",
        "scatter-add",
        "scatter-max",
        "scatter-min",
        "transpose",
        "concatenate",
        "pad",
        "rev",
        "sort",
        "expand_dims",
        "abs",
        "neg",
        "sign",
        "stop_gradient",
        "copy",
        "reduce_max",
        "reduce_min",
        "where",
        "tie_in",
    }
)

_COMPARES = frozenset({"eq", "ne", "lt", "le", "gt", "ge", "and", "or", "not", "xor", "reduce_or", "reduce_and", "is_finite"})

#: rem by a literal at or below this bound clears taint (the result is a
#: bucket index / phase, not a timestamp)
_REM_BOUND = float(1 << 24)

#: primitives whose output carries only their DATA operands' taint — a
#: timestamp-derived BUCKET INDEX used to address a count table must not
#: taint the counts (the values written/read are not time-scaled)
_DATA_OPERANDS = {
    "gather": (0,),
    "dynamic_slice": (0,),
    "scatter": (0, 2),
    "scatter-add": (0, 2),
    "scatter-max": (0, 2),
    "scatter-min": (0, 2),
    "scatter-mul": (0, 2),
    "dynamic_update_slice": (0, 1),
}


def _is_int(aval) -> bool:
    dt = getattr(aval, "dtype", None)
    return dt is not None and dt.kind in ("i", "u")


def _int_width(aval) -> int:
    dt = getattr(aval, "dtype", None)
    return dt.itemsize * 8 if dt is not None else 0


def _literal_mag(v, const_env: Dict[Any, Any]) -> Optional[float]:
    """max |value| when the operand is a trace-time constant, else None."""
    import numpy as np

    val = None
    if hasattr(v, "val"):  # jax.core.Literal
        val = v.val
    elif v in const_env:
        val = const_env[v]
    if val is None:
        return None
    try:
        arr = np.asarray(val)
        if arr.size == 0:
            return 0.0
        return float(np.max(np.abs(arr.astype(np.float64))))
    except (TypeError, ValueError, OverflowError):
        return None


class _Ctx:
    """One traversal's shared state: findings (deduped by source) and the
    pass handle for constructing them."""

    def __init__(self, outer: "DtypeOverflowPass", entry: TracedEntry, root: str):
        self.outer = outer
        self.entry = entry
        self.root = root
        self.findings: List[Finding] = []
        self._sites = set()

    def flag(self, eqn, message: str) -> None:
        src = eqn_source(eqn, self.root)
        key = (src, message[:60])
        if key in self._sites:
            return
        self._sites.add(key)
        self.findings.append(
            self.outer.finding(self.entry, message, source=src)
        )


def _sub_closed(params: Dict[str, Any], key: str):
    v = params.get(key)
    return v if v is not None and hasattr(v, "jaxpr") else None


def _run_body(
    ctx,
    closed,
    in_factors: List[Optional[float]],
    in_mags: Optional[List[Optional[float]]] = None,
) -> List[Optional[float]]:
    """Propagate factors through a ClosedJaxpr body (consts untainted).

    ``in_mags``: known constant magnitudes of the call's operands — a
    literal divisor crossing a jit boundary (``t // 500`` traces to
    ``jit[name=floor_divide] t 500``) must stay a known constant inside the
    body or the division never shrinks the scale factor."""
    jx = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    consts = list(getattr(closed, "consts", ()))
    const_env: Dict[Any, Any] = dict(zip(jx.constvars, consts))
    if in_mags:
        for var, mag in zip(jx.invars, in_mags):
            if mag is not None:
                const_env[var] = mag
    env: Dict[Any, float] = {}
    for var, f in zip(jx.invars, in_factors):
        if f is not None:
            env[var] = f
    _scan_eqns(ctx, jx, env, const_env)
    out: List[Optional[float]] = []
    for v in jx.outvars:
        out.append(env.get(v) if not hasattr(v, "val") else None)
    return out


def _factor_of(env, v) -> Optional[float]:
    if hasattr(v, "val"):  # Literal
        return None
    return env.get(v)


def _scan_eqns(ctx: _Ctx, jx, env: Dict[Any, float], const_env: Dict[Any, Any]) -> None:
    for eqn in jx.eqns:
        name = eqn.primitive.name
        fins = [_factor_of(env, v) for v in eqn.invars]
        data_ops = _DATA_OPERANDS.get(name)
        if data_ops is not None:
            fins = [
                f if i in data_ops else None for i, f in enumerate(fins)
            ]
        tainted = [f for f in fins if f is not None]
        out_f: Optional[float] = None

        # track scalar trace-time constants through shape/dtype wrappers so
        # `x // 500` sees "500" even when XLA broadcast it first
        if name in ("broadcast_in_dim", "convert_element_type", "reshape", "squeeze"):
            mag = _literal_mag(eqn.invars[0], const_env)
            if mag is not None:
                for var in eqn.outvars:
                    const_env[var] = mag

        # -- control flow: recurse with positional mapping ------------------
        mags = [_literal_mag(v, const_env) for v in eqn.invars]
        if name in ("jit", "closed_call", "core_call", "xla_call", "custom_jvp_call", "custom_vjp_call", "remat", "checkpoint"):
            closed = _sub_closed(eqn.params, "jaxpr") or _sub_closed(
                eqn.params, "call_jaxpr"
            )
            if closed is not None:
                outs = _run_body(ctx, closed, fins, mags)
                for var, f in zip(eqn.outvars, outs):
                    if f is not None:
                        env[var] = f
                continue
        if name == "cond":
            branches = eqn.params.get("branches", ())
            outs_acc: List[Optional[float]] = [None] * len(eqn.outvars)
            for br in branches:
                outs = _run_body(ctx, br, fins[1:], mags[1:])
                for i, f in enumerate(outs[: len(outs_acc)]):
                    if f is not None:
                        outs_acc[i] = max(outs_acc[i] or 0.0, f)
            for var, f in zip(eqn.outvars, outs_acc):
                if f is not None:
                    env[var] = f
            continue
        if name == "scan":
            closed = _sub_closed(eqn.params, "jaxpr")
            if closed is not None:
                # run twice so a taint entering the carry reaches the body's
                # second-order uses (fixpoint for monotone factors in 2 steps
                # unless the body amplifies per step, which mul-flagging
                # catches anyway)
                ins = list(fins)
                for _ in range(2):
                    outs = _run_body(ctx, closed, ins, mags)
                    nc = eqn.params.get("num_consts", 0)
                    ncar = eqn.params.get("num_carry", 0)
                    ins = list(fins)
                    for i in range(ncar):
                        if i < len(outs) and outs[i] is not None:
                            prev = ins[nc + i]
                            ins[nc + i] = max(prev or 0.0, outs[i])
                for var, f in zip(eqn.outvars, outs):
                    if f is not None:
                        env[var] = f
            continue
        if name == "while":
            body = _sub_closed(eqn.params, "body_jaxpr")
            if body is not None:
                cn = eqn.params.get("cond_nconsts", 0)
                bn = eqn.params.get("body_nconsts", 0)
                bins = fins[cn:]
                for _ in range(2):
                    outs = _run_body(ctx, body, bins, mags[cn:])
                    bins = fins[cn:]
                    for i, f in enumerate(outs):
                        if f is not None and bn + i < len(bins):
                            bins[bn + i] = max(bins[bn + i] or 0.0, f)
                # the CONDITION sees the same (amplified) carry — deadline
                # / spin conditions computed from now_ms live exactly here
                # and must not escape the gate.  cond invars = cond_consts
                # + carry.
                cond = _sub_closed(eqn.params, "cond_jaxpr")
                if cond is not None:
                    _run_body(
                        ctx,
                        cond,
                        fins[:cn] + bins[bn:],
                        mags[:cn] + mags[cn + bn:],
                    )
                for var, f in zip(eqn.outvars, outs):
                    if f is not None:
                        env[var] = f
            continue

        if not tainted:
            continue
        f_in = max(tainted)
        out_aval = eqn.outvars[0].aval if eqn.outvars else None
        flagged = False

        # -- arithmetic on tainted timestamps -------------------------------
        if name in _COMPARES:
            continue
        if name == "convert_element_type":
            if out_aval is not None and not _is_int(out_aval):
                continue  # float/bool: taint class ends here
            in_aval = eqn.invars[0].aval
            if (
                _is_int(out_aval)
                and _is_int(in_aval)
                and _int_width(out_aval) < _int_width(in_aval)
            ):
                ctx.flag(
                    eqn,
                    f"timestamp-derived i{_int_width(in_aval)} narrowed to "
                    f"i{_int_width(out_aval)} — silent truncation of a "
                    "time-scale value; widen the accumulator or bound the "
                    "value (rem/min) before the cast",
                )
                flagged = True
            out_f = f_in
        elif name == "mul":
            lit = None
            for v, f in zip(eqn.invars, fins):
                if f is None:
                    lit = _literal_mag(v, const_env)
                    break
            if lit is None and len(tainted) < len(fins):
                ctx.flag(
                    eqn,
                    "timestamp-derived i32 multiplied by a traced value — "
                    "unbounded scale-up of a time-scale quantity; rescale "
                    "in float or bound the factor explicitly",
                )
                flagged = True
                out_f = math.inf
            elif len(tainted) == len(fins):
                ctx.flag(
                    eqn,
                    "product of two timestamp-derived i32 values — wraps "
                    "for any epoch past ~46 s; compute durations (sub) "
                    "before multiplying",
                )
                flagged = True
                out_f = math.inf
            else:
                out_f = f_in * max(lit, 1.0)
        elif name == "div":
            lit = _literal_mag(eqn.invars[1], const_env) if len(eqn.invars) > 1 else None
            out_f = f_in / max(lit, 1.0) if lit else f_in
        elif name == "rem":
            lit = _literal_mag(eqn.invars[1], const_env) if len(eqn.invars) > 1 else None
            if lit is not None and 0 < lit <= _REM_BOUND:
                out_f = None  # bounded: a bucket index, not a timestamp
            else:
                out_f = f_in
        elif name in ("reduce_sum", "cumsum", "cummax", "cumlogsumexp", "reduce_window_sum"):
            if out_aval is not None and _is_int(out_aval):
                ctx.flag(
                    eqn,
                    f"'{name}' accumulates timestamp-derived i32 values — "
                    "length-scaled accumulation wraps; sum durations, not "
                    "epochs, or widen/bound first",
                )
                flagged = True
                out_f = math.inf
            else:
                out_f = None
        elif name == "integer_pow":
            y = eqn.params.get("y", 1)
            if y >= 2:
                ctx.flag(
                    eqn,
                    f"timestamp-derived i32 raised to power {y} — wraps "
                    "for any epoch past ~46 s (same class as t*t); compute "
                    "durations (sub) before squaring",
                )
                flagged = True
                out_f = math.inf
            else:
                out_f = f_in
        elif name == "dot_general":
            if out_aval is not None and _is_int(out_aval):
                ctx.flag(
                    eqn,
                    "'dot_general' contracts timestamp-derived i32 values — "
                    "length-scaled accumulation wraps; contract durations "
                    "or widen/bound first",
                )
                flagged = True
                out_f = math.inf
            else:
                out_f = None
        elif name == "shift_left":
            lit = _literal_mag(eqn.invars[1], const_env) if len(eqn.invars) > 1 else None
            out_f = f_in * float(2 ** int(lit)) if lit is not None else math.inf
        elif name in _PASSTHROUGH_MAXES:
            out_f = f_in
        else:
            # unknown primitive: keep the taint flowing without amplifying
            out_f = f_in

        if out_f is not None and out_aval is not None and not _is_int(out_aval):
            out_f = None  # left the integer domain
        if out_f is not None:
            if not flagged and out_f > MAX_SCALE and f_in <= MAX_SCALE:
                ctx.flag(
                    eqn,
                    f"'{name}' scales a timestamp-derived i32 by net factor "
                    f"{out_f:.0f}x ms — int32 wraps within "
                    f"{2**31 / out_f / 86_400_000:.1f} days of engine "
                    "uptime; keep ms scale (divide, don't multiply) or "
                    "widen deliberately with a suppression rationale",
                )
            for var in eqn.outvars:
                if _is_int(var.aval):
                    env[var] = out_f


class DtypeOverflowPass(JaxprPass):
    name = "dtype-overflow"
    description = (
        "i32 timestamp lineage must not be scaled/accumulated past wrap"
    )
    severity = ERROR

    def run(self, entry: TracedEntry) -> Iterable[Finding]:
        if not entry.time_invars:
            return []
        from sentinel_tpu.analysis import REPO_ROOT

        cj = entry.closed_jaxpr
        ctx = _Ctx(self, entry, REPO_ROOT)
        in_factors: List[Optional[float]] = [None] * len(cj.jaxpr.invars)
        for i in entry.time_invars:
            if i < len(in_factors):
                in_factors[i] = 1.0
        _run_body(ctx, cj, in_factors)
        return ctx.findings
