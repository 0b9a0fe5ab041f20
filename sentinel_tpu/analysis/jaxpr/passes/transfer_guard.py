"""transfer-guard: no host round-trips inside a traced tick program.

The admission path's whole performance model is "one dispatch, zero
host↔device syncs per tick" (SURVEY.md §4.1): timestamps and system load
enter as explicit tensor inputs, verdicts leave as tensors, and the one
designed readback point lives OUTSIDE the jitted program
(`_resolve_tick`).  A `pure_callback`/`io_callback` smuggled into tick
code — usually via an innocent-looking helper that calls back to Python
— serializes every batch on a host trip and silently caps throughput at
callback latency.  The AST tier can't see these when the callback enters
through a library wrapper; the jaxpr names the primitive directly.

Flagged primitives: the callback family (`pure_callback`, `io_callback`,
`debug_callback`, anything containing "callback"), `infeed`/`outfeed`,
and `device_put` (a placement op inside a traced program — the operand
should have been an input or a trace-time constant).

Packed-wire readback surface: under ``cfg.packed_wire`` the resolve
phase performs ONE fused device→host transfer (the wire buffer), so the
traced tick must not leave any OTHER TickOutput array live — a stats or
verdict leaf that survives packing re-opens a per-array sync in
`_resolve_tick` and silently un-fuses the transport.  Entrypoints
records the live output fields (observed via eval_shape, not re-derived
from config); this pass flags any field outside the allowance: the wire
buffer itself, ``wait_ms`` (the sidecar-overflow escape hatch, read only
on a tick whose PASS_WAIT rows overflow the fixed sidecar: rare without
pacing rules, every tick under them, and counted by
``sentinel_wire_wait_overflow_ticks_total`` either way), and
``seg_dropped`` (a plain-int trace constant, never read back packed).

Packed-wire upload surface: the program the packed client calls takes
the engine state, the rules and ONE input buffer (ops/wire.py).  A second
batch-input leaf is a second host→device transfer on every tick, which is
what the one buffer replaced; entrypoints records what the program
accepts, and this pass flags anything but three arguments and one leaf.
"""

from __future__ import annotations

from typing import Iterable

from sentinel_tpu.analysis.framework import ERROR, Finding
from sentinel_tpu.analysis.jaxpr.framework import (
    JaxprPass,
    TracedEntry,
    eqn_source,
    walk_eqns,
)

_EXACT = frozenset({"infeed", "outfeed", "device_put", "copy_to_host_async"})

#: the ONLY TickOutput fields a packed-wire tick may leave live (see
#: module docstring for why each is allowed)
_PACKED_READBACK_OK = frozenset({"wire", "wait_ms", "seg_dropped"})


def _repo_root() -> str:
    from sentinel_tpu.analysis import REPO_ROOT

    return REPO_ROOT


class TransferGuardPass(JaxprPass):
    name = "transfer-guard"
    description = "no callback/infeed/placement primitives inside tick jaxprs"
    severity = ERROR

    def run(self, entry: TracedEntry) -> Iterable[Finding]:
        root = _repo_root()
        if entry.packed_wire and entry.readback_fields is not None:
            fields = set(entry.readback_fields)
            if "wire" not in fields:
                yield self.finding(
                    entry,
                    "packed-wire tick emits no fused 'wire' buffer — the "
                    "resolve phase would fall back to per-array readbacks",
                )
            for f in sorted(fields - _PACKED_READBACK_OK):
                yield self.finding(
                    entry,
                    f"TickOutput field '{f}' is still a live output of the "
                    "packed-wire tick — packed mode must fold every "
                    "readback into the single fused wire transfer "
                    "(ops/wire.pack_tick_output); an extra output array "
                    "re-opens a per-array device->host sync in "
                    "_resolve_tick",
                )
        if entry.packed_wire and entry.client_inputs not in (None, (3, 1)):
            n_args, n_leaves = entry.client_inputs
            yield self.finding(
                entry,
                f"the packed client's tick takes {n_args} arguments with "
                f"{n_leaves} batch-input leaves — it must take (state, "
                "rules, wire_in) with the whole per-tick input in ONE "
                "buffer (ops/wire.unpack_tick_input); every further leaf "
                "is one more host->device transfer a tick in _run_tick",
            )
        for eqn in walk_eqns(entry.closed_jaxpr):
            pname = eqn.primitive.name
            if "callback" in pname or pname in _EXACT:
                yield self.finding(
                    entry,
                    f"primitive '{pname}' in the traced program — the tick "
                    "must stay free of host round-trips; pass data as "
                    "explicit inputs (timestamps, sys load) or move the "
                    "readback outside the jitted program (_resolve_tick is "
                    "THE designed sync point)",
                    source=eqn_source(eqn, root),
                )
