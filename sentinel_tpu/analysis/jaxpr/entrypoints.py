"""Canonical traced entry points for the jaxpr analyzer tier.

Each entry imports a REAL engine/ops entry point (`ops.engine.tick`,
`ops.fused.scatter_many`, `ops.segscan`, the cluster token-decision
tick), builds canonical example inputs on a small config, and traces it
to a ClosedJaxpr on CPU.  The semantic passes and the golden
fingerprints/budgets key off the entry NAME — keep names stable; add new
names rather than repurposing old ones.

Configs are deliberately SMALL (`small_engine_config`) so CI tracing
stays in seconds: every hazard class the passes guard (hoisted device
consts, callback primitives, timestamp scaling, program drift) is
config-size-invariant — a jnp module const is hoisted into the jaxpr at
any batch size.

Cost budgeting (``cost=True``) lowers the entry and records XLA's
cost_analysis.  Pallas-bearing entries are fingerprinted but NOT
budgeted: on CPU their kernels lower in interpret mode, and XLA prices
the interpreter's scan-over-grid loop (~1000x the real Mosaic kernel) —
a budget on that number would gate noise, not the datapath.
"""

from __future__ import annotations

import functools
import inspect
import os
import threading
from typing import Dict, List, Optional

from sentinel_tpu.analysis.jaxpr.framework import TracedEntry

#: entry names -> defining module (repo-relative), for finding paths
_ENTRY_MODULES = {
    "tick/plain": "sentinel_tpu/ops/engine.py",
    "tick/mxu": "sentinel_tpu/ops/engine.py",
    "tick/fused-seg": "sentinel_tpu/ops/engine.py",
    "tick/packed-wire": "sentinel_tpu/ops/engine.py",
    "tick/wire-in": "sentinel_tpu/ops/engine.py",
    "tick/sketch-salsa": "sentinel_tpu/sketch/salsa.py",
    "tick/cluster-token": "sentinel_tpu/cluster/token_service.py",
    "segscan/excl-cumsum": "sentinel_tpu/ops/segscan.py",
    "segscan/incl-min": "sentinel_tpu/ops/segscan.py",
    "fused/scatter-many": "sentinel_tpu/ops/fused.py",
    "rank/grouped-cumsum": "sentinel_tpu/ops/rank.py",
    "rank/grouped-cumsum-small": "sentinel_tpu/ops/rank.py",
    "window/add-batch": "sentinel_tpu/ops/window.py",
    "cluster/token-col": "sentinel_tpu/ops/token_col.py",
}

#: entries whose jaxpr contains pallas_call — exempt from cost budgets
#: (interpret-mode lowering prices the interpreter, not the kernel)
PALLAS_ENTRIES = frozenset(
    {"tick/fused-seg", "segscan/excl-cumsum", "segscan/incl-min", "fused/scatter-many"}
)

_CACHE: Optional[List[TracedEntry]] = None
_CACHE_LOCK = threading.Lock()


def _force_cpu() -> None:
    """Trace on CPU regardless of the ambient backend (the analyzer also
    runs on hosts with a chip attached): jaxpr structure is what we pin,
    and the goldens are traced with the Pallas kernels in interpret mode,
    which is the CPU choice (ops/fused.interpret_mode).  Must run before
    backends initialize; a no-op afterwards."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
    except Exception:  # stlint: disable=fail-open — backends already initialized: trace on whatever platform is live rather than refusing to analyze
        pass


def _mk_tick_inputs(cfg, n_resources: int = 8):
    """Canonical (state, rules, acq, comp, now, load, cpu) for a config.

    The rule set touches every stage class (flow incl. rate-limiter and
    warm-up controllers, degrade both grades, param, authority, system)
    so the traced program contains every check the features enable."""
    import jax.numpy as jnp

    from sentinel_tpu.core import rules as R
    from sentinel_tpu.ops import engine as E
    from sentinel_tpu.runtime.registry import Registry

    reg = Registry(cfg)
    for i in range(1, n_resources + 1):
        reg.resource_id(f"r{i}")
    reg.origin_id("caller-a")
    ruleset = E.compile_ruleset(
        cfg,
        reg,
        flow_rules=[
            R.FlowRule(resource="r1", count=5),
            R.FlowRule(
                resource="r2", count=3, control_behavior=R.CONTROL_RATE_LIMITER
            ),
            R.FlowRule(resource="r3", count=8, control_behavior=R.CONTROL_WARM_UP),
            R.FlowRule(resource="r4", count=100, grade=R.GRADE_THREAD),
        ],
        degrade_rules=[
            R.DegradeRule(
                resource="r5",
                grade=R.CB_STRATEGY_ERROR_COUNT,
                count=2,
                time_window=3,
            ),
            R.DegradeRule(
                resource="r6",
                grade=R.CB_STRATEGY_SLOW_REQUEST_RATIO,
                count=50,
                slow_ratio_threshold=0.5,
                time_window=2,
            ),
        ],
        param_rules=[R.ParamFlowRule(resource="r7", count=2, param_idx=0)],
        authority_rules=[
            R.AuthorityRule(
                resource="r8", limit_app="caller-a", strategy=R.AUTHORITY_BLACK
            )
        ],
        system_rules=[R.SystemRule(qps=1000)],
    )
    state = E.init_state(cfg)
    acq = E.empty_acquire(cfg)
    comp = E.empty_complete(cfg)
    return (
        state,
        ruleset,
        acq,
        comp,
        jnp.int32(1_000),
        jnp.float32(0.1),
        jnp.float32(0.1),
    )


def _time_invar_indices(args, time_arg: int) -> tuple:
    """Flat invar indices covering positional arg ``time_arg`` — the
    dtype-overflow taint seeds (jaxpr invars are the flattened args)."""
    import jax

    off = 0
    for i, a in enumerate(args):
        n = len(jax.tree_util.tree_leaves(a))
        if i == time_arg:
            return tuple(range(off, off + n))
        off += n
    return ()


def _trace(name, fn, args, time_arg: Optional[int] = None, cost: bool = False):
    import jax

    cost_dict: Optional[Dict[str, float]] = None
    if cost:
        # one trace serves both jaxpr and lowering: jit(fn).trace gives a
        # Traced whose .jaxpr and .lower() share the trace — re-tracing
        # the tick configs for cost_analysis would double the tier's wall
        # time
        traced = jax.jit(fn).trace(*args)
        closed = traced.jaxpr
        analysis = traced.lower().cost_analysis()
        cost_dict = {
            "flops": float(analysis.get("flops", 0.0)),
            "bytes": float(analysis.get("bytes accessed", 0.0)),
        }
    else:
        closed = jax.make_jaxpr(fn)(*args)
    time_invars = _time_invar_indices(args, time_arg) if time_arg is not None else ()
    return TracedEntry(
        name=name,
        path=_ENTRY_MODULES[name],
        closed_jaxpr=closed,
        time_invars=time_invars,
        cost_eligible=cost,
        cost=cost_dict,
    )


def _build_entries() -> List[TracedEntry]:
    _force_cpu()
    import jax.numpy as jnp

    from sentinel_tpu.cluster.token_service import DECISION_FEATURES
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.ops import engine as E
    from sentinel_tpu.ops import fused as FU
    from sentinel_tpu.ops import rank as RK
    from sentinel_tpu.ops import segscan as SS
    from sentinel_tpu.ops import window as W
    from sentinel_tpu.ops import wire as WIRE

    entries: List[TracedEntry] = []

    # -- the tick under its three memory-access strategies ------------------
    tick_args_by_cfg = {}

    def tick_entry(name, cfg, features, time_arg=4, cost=True):
        args = tick_args_by_cfg.get(cfg)
        if args is None:
            args = tick_args_by_cfg[cfg] = _mk_tick_inputs(cfg)
        fn = functools.partial(E.tick, cfg=cfg, features=features)
        ent = _trace(name, fn, args, time_arg=time_arg, cost=cost)
        if cfg.packed_wire:
            # observe (not re-derive) the packed tick's readback surface:
            # the TickOutput fields the pack step left live.  The
            # transfer-guard pass pins this set to the fused wire buffer
            # plus the sidecar-overflow escape hatch.
            import jax

            out_struct = jax.eval_shape(fn, *args)[1]
            ent.packed_wire = True
            ent.readback_fields = tuple(
                f
                for f in out_struct._fields
                if getattr(out_struct, f) is not None
            )
            # ...and its upload surface: the program the packed client
            # calls (E.make_tick(wire_in=True)) takes state, rules and ONE
            # input buffer, observed the same way
            wire_fn, wire_args = _wire_in_program(cfg, features, args)
            jax.eval_shape(wire_fn, *wire_args)
            ent.client_inputs = (
                sum(
                    p.default is p.empty
                    for p in inspect.signature(wire_fn).parameters.values()
                ),
                len(jax.tree_util.tree_leaves(wire_args[2:])),
            )
        return ent

    def _wire_in_program(cfg, features, args):
        lo = WIRE.input_layout_for(cfg, *WIRE.tick_shapes(cfg)[-1])
        return (
            functools.partial(E.tick_wire_in, cfg=cfg, features=features),
            (args[0], args[1], jnp.zeros((lo.total,), jnp.uint32)),
        )

    cfg_plain = small_engine_config()
    cfg_mxu = small_engine_config(use_mxu_tables=True)
    cfg_seg = small_engine_config(
        use_mxu_tables=True, fused_effects=True, seg_effects=True
    )
    entries.append(tick_entry("tick/plain", cfg_plain, E.ALL_FEATURES))
    entries.append(tick_entry("tick/mxu", cfg_mxu, E.ALL_FEATURES))
    # the sketch statistics tier: salsa packed counters + O(1) running
    # sums + tail-rule enforcement + hot-candidate top-K, all in-trace
    cfg_sketch = small_engine_config(
        sketch_stats=True, sketch_width=256, hotset_k=8
    )
    entries.append(tick_entry("tick/sketch-salsa", cfg_sketch, E.ALL_FEATURES))
    entries.append(
        tick_entry("tick/fused-seg", cfg_seg, E.ALL_FEATURES, cost=False)
    )
    # the packed-wire transport: every readback block (verdict bitmap,
    # wait sidecar, telemetry row, timeline top-K, hot-set) folded into
    # ONE fused uint32 buffer on-device (ops/wire.py) — all blocks
    # enabled so the trace pins the full wire layout
    cfg_packed = small_engine_config(
        packed_wire=True,
        sketch_stats=True,
        sketch_width=256,
        hotset_k=8,
        timeline_k=8,
    )
    entries.append(tick_entry("tick/packed-wire", cfg_packed, E.ALL_FEATURES))
    # the same tick as the packed client calls it: the whole per-tick input
    # as one uint32 buffer, unpacked at the entry (ops/wire.py).  The
    # timestamp rides in the buffer's header, so there is no time invar to
    # seed; tick/packed-wire keeps that coverage for the same program body
    entries.append(
        _trace(
            "tick/wire-in",
            *_wire_in_program(
                cfg_packed, E.ALL_FEATURES, tick_args_by_cfg[cfg_packed]
            ),
            cost=True,
        )
    )
    # the cluster token-decision engine: same tick, the feature set the
    # DefaultTokenService's dedicated decision client needs
    entries.append(tick_entry("tick/cluster-token", cfg_plain, DECISION_FEATURES))

    # -- standalone kernels -------------------------------------------------
    n = 512
    head = jnp.zeros((n,), jnp.int32).at[0].set(1)
    vals_f = jnp.ones((n,), jnp.float32)
    entries.append(
        _trace("segscan/excl-cumsum", SS.seg_excl_cumsum_pl, (head, vals_f))
    )
    entries.append(
        _trace(
            "segscan/incl-min",
            functools.partial(SS.seg_incl_min_pl, fill=1.0e9),
            (head, vals_f),
        )
    )

    def _scatter_two_jobs(rows, values):
        jobs = [
            FU.Job("stat", 128, rows, values, (1, 1)),
            FU.Job("cb", 64, rows, values, (1, 1)),
        ]
        return FU.scatter_many(jobs, interpret=True)

    rows = jnp.zeros((1, 256), jnp.int32)
    values = jnp.ones((2, 256), jnp.int32)
    entries.append(_trace("fused/scatter-many", _scatter_two_jobs, (rows, values)))

    keys = jnp.zeros((n,), jnp.int32)
    elig = jnp.ones((n,), bool)
    entries.append(
        _trace(
            "rank/grouped-cumsum",
            lambda k, v, e: RK.grouped_exclusive_cumsum(k, [v], e),
            (keys, vals_f, elig),
            cost=True,
        )
    )
    entries.append(
        _trace(
            "rank/grouped-cumsum-small",
            lambda k, v, e: RK.grouped_exclusive_cumsum_small(k, [v], e, 64),
            (keys, vals_f, elig),
            cost=True,
        )
    )

    # the cluster decision-batch column (protocol v2): one call answers a
    # coalesced BATCH frame — slot-run prefix rebase + window charge —
    # entirely on device (cluster/token_service.TokenColumnBatcher)
    from sentinel_tpu.ops import token_col as TC

    tc_state = TC.init_state(16)
    tcn = 64
    tc_slots = jnp.zeros((tcn,), jnp.int32)
    tc_units = jnp.ones((tcn,), jnp.int32)
    tc_heads = jnp.zeros((tcn,), jnp.int32)
    tc_flag = jnp.zeros((tcn,), bool)
    entries.append(
        _trace(
            "cluster/token-col",
            functools.partial(TC.decide_batch, cfg=TC.DEFAULT_CFG),
            (
                tc_state,
                jnp.int32(1_000),
                tc_slots,
                tc_units,
                tc_heads,
                tc_flag,
                tc_flag,
            ),
            time_arg=1,
            cost=True,
        )
    )

    wcfg = W.WindowConfig(2, 500)
    wstate = W.init_window(64, wcfg)
    wrows = jnp.zeros((256,), jnp.int32)
    wdeltas = jnp.ones((256, W.NUM_EVENTS), jnp.int32)
    wrt = jnp.ones((256,), jnp.float32)
    entries.append(
        _trace(
            "window/add-batch",
            functools.partial(W.add_batch, cfg=wcfg),
            (wstate, jnp.int32(1_000), wrows, wdeltas, wrt),
            time_arg=1,
            cost=True,
        )
    )
    return entries


def trace_entries(refresh: bool = False) -> List[TracedEntry]:
    """The canonical entry list, traced once per process (tracing is
    pure; the cache only saves re-trace time for in-process callers like
    the test suite running several jaxpr-tier tests)."""
    global _CACHE
    with _CACHE_LOCK:
        if _CACHE is None or refresh:
            _CACHE = _build_entries()
        return list(_CACHE)
