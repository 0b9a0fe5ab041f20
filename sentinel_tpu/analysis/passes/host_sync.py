"""host-sync: device↔host synchronization inside the tick hot path.

One stray ``.item()`` or ``np.asarray(device_value)`` inside the tick
loop turns an async dispatch into a blocking host↔device round-trip and
caps throughput at the transfer latency.  The designed architecture syncs in exactly one place —
the verdict readback in ``_resolve_tick`` — and everything else
dispatches asynchronously.

Hot zones:

* functions that end up inside ``jax.jit`` (detected from decorators,
  direct ``jax.jit(fn)`` calls, and the two-step partial-then-jit idiom)
  plus their same-module callees — STRICT: any ``numpy`` call, ``.item``,
  ``float()/int()`` on non-trivial expressions, ``block_until_ready``
  forces a trace-time constant or a host round-trip;
* configured host-side dispatch roots (the client tick loop) plus their
  same-module callees — flags only the unambiguous sync primitives
  (``.item()``, ``block_until_ready``, ``jax.device_get``,
  ``np.asarray``/``np.array``); plain host-numpy batch assembly in the
  dispatch path is the design, so ``float``/``int``/other np calls stay
  legal there.

``_resolve_tick`` is deliberately NOT a root: it is the architecture's
single readback point.  New readbacks added elsewhere must either move
into it or carry an explicit suppression rationale.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, Set

from sentinel_tpu.analysis import astutil as A
from sentinel_tpu.analysis.framework import ERROR, Finding, ParsedModule, Pass

#: file-glob -> host-side hot-path root functions (same-module closure)
HOST_ROOTS = {
    "*sentinel_tpu/runtime/client.py": (
        "_tick_loop",
        "tick_once",
        "_tick_once_locked",
        "_run_tick",
    ),
    "*sentinel_tpu/cluster/token_service.py": ("_tick_loop", "_drain"),
}

_SYNC_CALLS = {
    "jax.block_until_ready",
    "jax.device_get",
    "numpy.asarray",
    "numpy.array",
}

#: host helpers that are fine even in jit zones (static shape math)
_JIT_OK_CALLS = {"len", "min", "max", "sum", "abs", "range", "sorted", "round"}

#: names whose attributes are static under jit (partial-bound config)
_STATIC_ROOTS = {"cfg", "config", "self", "cls"}


def _static_expr(expr: ast.AST) -> bool:
    """True when every Name the expression references is a static-config
    root — ``float(cfg.statistic_max_rt)`` is trace-time constant math,
    not a host coercion of a traced value.  Expressions with no Names at
    all (``float((1 << 24) - 1)``) are static by construction."""
    for node in ast.walk(expr):
        if isinstance(node, ast.Name) and node.id not in _STATIC_ROOTS:
            return False
    return True


def _call_findings(self, mod, fn, aliases, strict, zone):
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = A.resolve_call(node, aliases)
        tail = name.rsplit(".", 1)[-1] if name else None
        if tail == "item" and not node.args:
            yield self.finding(
                mod,
                node,
                f".item() in {zone} '{fn.name}' forces a device→host "
                "sync per call — keep values on device or batch the "
                "readback through the resolve path",
            )
            continue
        if name in _SYNC_CALLS or tail == "block_until_ready":
            # host zone: np.asarray/np.array on a bare local (host batch
            # assembly) is the design — only attribute chains (tick
            # outputs, engine state) look like device readbacks there
            materializing = name in ("numpy.asarray", "numpy.array")
            if (
                not strict
                and materializing
                and not (
                    node.args and isinstance(node.args[0], ast.Attribute)
                )
            ):
                continue
            yield self.finding(
                mod,
                node,
                f"{name or tail}() in {zone} '{fn.name}' blocks on "
                "device→host transfer — move it to the resolve/readback "
                "path or suppress with a rationale",
            )
            continue
        if not strict:
            continue
        # jit zone extras: numpy use and host coercions force trace-time
        # constants (stale state) or fail under tracing
        if name and (name.startswith("numpy.") or name.startswith("np.")):
            yield self.finding(
                mod,
                node,
                f"numpy call {name}() inside jitted code '{fn.name}' — "
                "use jax.numpy (a np.* call materializes a host constant "
                "at trace time and goes stale across calls)",
            )
            continue
        if (
            isinstance(node.func, ast.Name)
            and node.func.id in ("float", "int", "bool")
            and node.args
            and not isinstance(
                node.args[0], (ast.Constant, ast.Name)
            )
            and not _static_expr(node.args[0])
        ):
            yield self.finding(
                mod,
                node,
                f"host {node.func.id}() coercion inside jitted code "
                f"'{fn.name}' — traced values cannot be coerced; compute "
                "in jnp or hoist to the host side",
            )


class HostSyncPass(Pass):
    name = "host-sync"
    description = "no device↔host sync inside tick-reachable functions"
    severity = ERROR

    def run(self, mod: ParsedModule) -> Iterable[Finding]:
        aliases = A.import_aliases(mod.tree)
        jit_roots = A.jitted_root_names(mod.tree, aliases)
        host_roots: Set[str] = set()
        for glob, roots in HOST_ROOTS.items():
            if A.path_matches(mod.path, (glob,)):
                host_roots |= set(roots)
        if not jit_roots and not host_roots:
            return

        jit_zone = A.reachable_funcs(mod.tree, jit_roots)
        host_zone = A.reachable_funcs(mod.tree, host_roots)
        emitted: Set[int] = set()
        for name, fn in sorted(jit_zone.items()):
            for f in _call_findings(self, mod, fn, aliases, True, "jitted code"):
                if (f.line, f.col) not in emitted:
                    emitted.add((f.line, f.col))
                    yield f
        for name, fn in sorted(host_zone.items()):
            if name in jit_zone:
                continue
            for f in _call_findings(
                self, mod, fn, aliases, False, "tick hot path"
            ):
                if (f.line, f.col) not in emitted:
                    emitted.add((f.line, f.col))
                    yield f
