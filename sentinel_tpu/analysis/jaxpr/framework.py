"""Jaxpr-tier analysis framework.

The AST tier (PR 1) sees source; this tier sees the PROGRAM — each
engine/ops entry point traced to a ClosedJaxpr under a canonical config
(entrypoints.py), with semantic passes walking the equations.  Hazards
that only exist after tracing (a module-level jnp const hoisted into the
executable's parameter list, an i32 timestamp scaled past wrap, a
callback smuggled into the tick, a silently-changed traced program)
cannot be seen by any source linter; here they are first-class objects.

Findings reuse the tier-1 :class:`Finding`/baseline machinery.  Where an
equation carries usable source info the finding lands on the real
``file:line`` (so tier-1 ``# stlint: disable=`` comments apply); whole-
program findings (fingerprints, budgets, consts) anchor on the entry's
pseudo-path ``jaxpr://<entry-name>``.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional, Tuple

from sentinel_tpu.analysis.framework import (
    ERROR,
    Finding,
    parse_suppressions,
)

#: directory of the golden files (fingerprints.json, budgets.json)
JAXPR_DIR = os.path.dirname(os.path.abspath(__file__))
FINGERPRINTS_PATH = os.path.join(JAXPR_DIR, "fingerprints.json")
BUDGETS_PATH = os.path.join(JAXPR_DIR, "budgets.json")


@dataclass
class TracedEntry:
    """One traced entry point: the unit every jaxpr pass runs over."""

    name: str  # e.g. "tick/plain"
    path: str  # repo-relative path of the DEFINING module (for findings)
    closed_jaxpr: Any  # jax.core.ClosedJaxpr
    #: indices (into the FLAT jaxpr invars) of ms-scale timestamp inputs —
    #: dtype-overflow taint seeds
    time_invars: Tuple[int, ...] = ()
    #: True when the entry participates in cost budgeting.  Pallas-bearing
    #: entries are exempt: XLA's CPU cost model prices the INTERPRETER
    #: loop, not the Mosaic kernel — those numbers would gate noise
    #: (see entrypoints.py)
    cost_eligible: bool = False
    #: cost_analysis dict ({"flops", "bytes"}) from the lowered
    #: computation; None when exempt OR when this jaxlib exposes no cost
    #: model (the budget pass reports eligible-but-unmeasured entries)
    cost: Optional[Dict[str, float]] = None
    #: True when the entry's config runs the packed-wire transport
    #: (cfg.packed_wire) — the transfer-guard pass then pins the tick's
    #: readback surface to the single fused wire transfer
    packed_wire: bool = False
    #: TickOutput field names that are LIVE outputs of the traced tick
    #: (fields the pack step None'd out are absent) — observed from the
    #: program via eval_shape, not re-derived from config.  Populated
    #: only for packed-wire tick entries; None elsewhere.
    readback_fields: Optional[Tuple[str, ...]] = None
    #: (positional arguments, batch-input leaves after state and rules) of
    #: the program the packed client calls, observed likewise; the
    #: transfer-guard pass pins it to (3, 1): one upload a tick.  Populated
    #: only for packed-wire tick entries; None elsewhere.
    client_inputs: Optional[Tuple[int, int]] = None

    @property
    def pseudo_path(self) -> str:
        return f"jaxpr://{self.name}"


class JaxprPass:
    """One semantic pass over a traced entry point."""

    name: str = ""
    description: str = ""
    severity: str = ERROR

    def run(self, entry: TracedEntry) -> Iterable[Finding]:  # pragma: no cover
        raise NotImplementedError

    def finding(
        self,
        entry: TracedEntry,
        message: str,
        severity: Optional[str] = None,
        source: Optional[Tuple[str, int]] = None,
    ) -> Finding:
        path, line = source if source else (entry.pseudo_path, 1)
        return Finding(
            rule=self.name,
            path=path,
            line=line,
            col=0,
            message=f"[{entry.name}] {message}",
            severity=severity or self.severity,
        )


# -- jaxpr walking -----------------------------------------------------------


def _sub_jaxprs(params: Dict[str, Any]) -> Iterator[Any]:
    """Every Jaxpr/ClosedJaxpr nested in an equation's params (cond
    branches, scan/while bodies, pjit calls, pallas kernels, ...)."""
    for v in params.values():
        stack = [v]
        while stack:
            x = stack.pop()
            if hasattr(x, "jaxpr") and hasattr(x, "consts"):  # ClosedJaxpr
                yield x
            elif hasattr(x, "eqns") and hasattr(x, "invars"):  # raw Jaxpr
                yield x
            elif isinstance(x, (tuple, list)):
                stack.extend(x)


def walk_eqns(closed_jaxpr) -> Iterator[Any]:
    """Depth-first over every equation, including nested sub-jaxprs."""
    stack = [closed_jaxpr.jaxpr]
    seen = set()
    while stack:
        jx = stack.pop()
        if hasattr(jx, "jaxpr"):  # ClosedJaxpr -> Jaxpr
            jx = jx.jaxpr
        if id(jx) in seen:
            continue
        seen.add(id(jx))
        for eqn in jx.eqns:
            yield eqn
            for sub in _sub_jaxprs(eqn.params):
                stack.append(sub)


def walk_closed(closed_jaxpr) -> Iterator[Any]:
    """Every ClosedJaxpr reachable from the root (root included) — the
    const-hoist pass inspects each one's ``consts``."""
    yield closed_jaxpr
    for eqn in walk_eqns(closed_jaxpr):
        for sub in _sub_jaxprs(eqn.params):
            if hasattr(sub, "consts"):
                yield sub


def eqn_source(eqn, repo_root: str) -> Optional[Tuple[str, int]]:
    """(repo-relative path, line) of the innermost sentinel_tpu frame
    that created ``eqn``, or None when source info is unavailable.
    Frames inside the analysis package itself are skipped (the tracer's
    own frames are not user code)."""
    src = getattr(eqn, "source_info", None)
    tb = getattr(src, "traceback", None)
    if tb is None:
        return None
    try:
        frames = list(tb.frames)  # jaxlib Traceback
    except AttributeError:
        return None
    sep = os.sep
    for fr in frames:
        fn = getattr(fr, "file_name", "") or ""
        if f"sentinel_tpu{sep}" not in fn or f"{sep}analysis{sep}" in fn:
            continue
        try:
            rel = os.path.relpath(fn, repo_root).replace(os.sep, "/")
        except ValueError:
            continue
        if rel.startswith(".."):
            continue
        return rel, int(getattr(fr, "line_num", 1) or 1)
    return None


# -- fingerprints ------------------------------------------------------------

_ADDR_RE = re.compile(r" at 0x[0-9a-fA-F]+")
_ID_RE = re.compile(r"\bid=\d+\b")


def _norm_param(v: Any) -> Any:
    """Normalize one equation param into something deterministic across
    processes: jaxprs recurse structurally, arrays reduce to shape/dtype,
    callables to their name, everything else to an address-stripped repr."""
    if hasattr(v, "jaxpr") and hasattr(v, "consts"):  # ClosedJaxpr
        return {"jaxpr": _norm_jaxpr(v.jaxpr), "consts": len(v.consts)}
    if hasattr(v, "eqns") and hasattr(v, "invars"):  # raw Jaxpr
        return {"jaxpr": _norm_jaxpr(v)}
    if isinstance(v, (tuple, list)):
        return [_norm_param(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _norm_param(x) for k, x in sorted(v.items())}
    if hasattr(v, "shape") and hasattr(v, "dtype"):  # ndarray-likes
        return f"array[{v.dtype}{tuple(v.shape)}]"
    if callable(v) and hasattr(v, "__name__"):
        return f"fn:{v.__name__}"
    return _ID_RE.sub("id=?", _ADDR_RE.sub("", repr(v)))


def _aval_str(v) -> str:
    """dtype[shape] plus an explicit weak-type marker — ``str(aval)``
    hides weak_type, and weak-type drift on an entry input is exactly
    the one-extra-executable-per-callsite hazard the fingerprints exist
    to catch."""
    a = getattr(v, "aval", v)
    s = str(a)
    if getattr(a, "weak_type", False):
        s += "~weak"
    return s


def _norm_jaxpr(jx) -> List[Any]:
    out: List[Any] = [
        [_aval_str(v) for v in jx.invars],
        [_aval_str(v) for v in jx.outvars],
    ]
    for eqn in jx.eqns:
        out.append(
            [
                eqn.primitive.name,
                [_aval_str(v) for v in eqn.invars],
                [_aval_str(v) for v in eqn.outvars],
                {str(k): _norm_param(v) for k, v in sorted(eqn.params.items())},
            ]
        )
    return out


def entry_signature(entry: TracedEntry) -> Dict[str, Any]:
    """Stable structural signature of a traced entry point.

    Hashes the normalized equation stream (primitive names, operand/
    result avals, structure-relevant params) — NOT the pretty-printed
    jaxpr, whose variable naming is an implementation detail.  Weak-type
    drift changes avals, a new static-arg specialization changes the
    equation list, a swapped kernel changes primitive params: all show
    up as a hash change."""
    cj = entry.closed_jaxpr
    norm = {
        "in": [_aval_str(v) for v in cj.jaxpr.invars],
        "out": [_aval_str(v) for v in cj.jaxpr.outvars],
        "consts": [_norm_param(c) for c in cj.consts],
        "eqns": _norm_jaxpr(cj.jaxpr),
    }
    blob = json.dumps(norm, sort_keys=True, separators=(",", ":"))
    n_eqns = sum(1 for _ in walk_eqns(cj))
    return {
        "hash": hashlib.sha256(blob.encode()).hexdigest()[:16],
        "eqns": n_eqns,
        "invars": len(cj.jaxpr.invars),
        "outvars": len(cj.jaxpr.outvars),
    }


def load_golden(path: str) -> Dict[str, Any]:
    try:
        with open(path, "r", encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def save_golden(path: str, data: Dict[str, Any]) -> None:
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")


# -- runner ------------------------------------------------------------------


def _source_suppressed(
    repo_root: str, cache: Dict[str, Any], f: Finding
) -> bool:
    """Honor tier-1 ``# stlint: disable=`` comments for jaxpr findings
    that landed on a real source line."""
    if f.path.startswith("jaxpr://"):
        return False
    table = cache.get(f.path)
    if table is None:
        try:
            with open(os.path.join(repo_root, f.path), "r", encoding="utf-8") as fh:
                table = parse_suppressions(fh.read())
        except OSError:
            table = ({}, set())
        cache[f.path] = table
    line_disables, file_disables = table
    if "*" in file_disables or f.rule in file_disables:
        return True
    at = line_disables.get(f.line, ())
    return "*" in at or f.rule in at


def run_jaxpr_passes(
    entries: Iterable[TracedEntry],
    passes: Iterable[JaxprPass],
    repo_root: str,
) -> List[Finding]:
    findings: List[Finding] = []
    sup_cache: Dict[str, Any] = {}
    for entry in entries:
        for p in passes:
            for f in p.run(entry):
                if not _source_suppressed(repo_root, sup_cache, f):
                    findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return findings
