"""On-device three-path equivalence check (run on a real TPU).

Drives randomized full-feature ticks through the engine's three memory
paths — XLA scatter (use_mxu_tables=False), one-hot MXU matmuls, and the
fused Pallas megakernels — ON THE REAL CHIP, asserting bit-identical
verdicts and state.  This is what actually pins the bf16 digit-plane
exactness claims of ops/tables.py / ops/mxu_table.py / ops/fused.py on
hardware: the CPU tests (tests/test_engine_backends.py, tests/
test_fused.py) compare the same paths where matmuls are f32-exact, so a
wrong digit decomposition could only be caught here.

Exit code 0 = all paths agree.  Run it by hand on the chip (one process:
``python benchmarks/tpu_equivalence.py``); it is not part of the pytest
suite, whose outcome must not depend on whether a chip is reachable.  The
full-width served-vs-plain comparison (seg, SALSA, packed wire, explain)
is chip_smoke.py's equivalence phase.
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_batches(cfg, reg, seed: int):
    import jax.numpy as jnp

    from sentinel_tpu.ops import engine as E

    rng = np.random.default_rng(seed)
    B = cfg.batch_size
    n_res = 48
    origin_row = reg.origin_node_row("res-1", "peer")
    ctx_row = reg.ctx_node_row("res-2", "ctx-a")
    ctx_id = reg.context_id("ctx-a")
    batches = []
    for t in range(6):
        ids_np = rng.integers(1, n_res + 40, B).astype(np.int32)  # incl. tail ids
        ids_np = np.where(ids_np <= n_res, ids_np, cfg.node_rows + ids_np)
        witho = rng.random(B) < 0.25
        withc = rng.random(B) < 0.2
        ph = np.stack(
            [rng.integers(1, 9, B), np.zeros(B)], axis=1
        ).astype(np.int32)
        acq = E.empty_acquire(cfg)._replace(
            res=jnp.asarray(ids_np),
            count=jnp.asarray(rng.integers(1, 4, B).astype(np.int32)),
            prio=jnp.asarray((rng.random(B) < 0.3).astype(np.int32)),
            origin_id=jnp.asarray(
                np.where(witho, reg.origin_id("peer"), -1).astype(np.int32)
            ),
            origin_node=jnp.asarray(
                np.where(witho, origin_row, cfg.trash_row).astype(np.int32)
            ),
            ctx_node=jnp.asarray(
                np.where(withc, ctx_row, cfg.trash_row).astype(np.int32)
            ),
            ctx_name=jnp.asarray(np.where(withc, ctx_id, -1).astype(np.int32)),
            inbound=jnp.asarray((rng.random(B) < 0.5).astype(np.int32)),
            param_hash=jnp.asarray(ph),
        )
        comp = E.empty_complete(cfg)._replace(
            res=jnp.asarray(ids_np),
            origin_node=jnp.asarray(
                np.where(witho, origin_row, cfg.trash_row).astype(np.int32)
            ),
            ctx_node=jnp.asarray(
                np.where(withc, ctx_row, cfg.trash_row).astype(np.int32)
            ),
            inbound=jnp.asarray((rng.random(B) < 0.5).astype(np.int32)),
            # multiples of 1/8 ms: the MXU path quantizes RT to the 1/8 ms
            # grid (documented), so on-grid inputs make all three paths
            # bit-comparable including rt_sum/rt_min
            rt=jnp.asarray((rng.integers(4, 240, B) / 8.0).astype(np.float32)),
            success=jnp.asarray(rng.integers(1, 3, B).astype(np.int32)),
            error=jnp.asarray((rng.random(B) < 0.25).astype(np.int32)),
            param_hash=jnp.asarray(ph),
        )
        batches.append((acq, comp))
    return batches


def run_path(use_mxu: bool, fused: bool):
    import jax
    import jax.numpy as jnp

    from sentinel_tpu.core.config import EngineConfig
    from sentinel_tpu.core.rules import (
        CONTROL_RATE_LIMITER,
        CONTROL_WARM_UP,
        AuthorityRule,
        DegradeRule,
        FlowRule,
        ParamFlowRule,
        SystemRule,
        AUTHORITY_BLACK,
    )
    from sentinel_tpu.ops import engine as E
    from sentinel_tpu.runtime.registry import Registry

    cfg = EngineConfig(
        max_resources=2048,
        max_nodes=2040,  # node_rows = 2048
        max_flow_rules=256,
        max_degrade_rules=128,
        max_param_rules=32,
        batch_size=4096,
        complete_batch_size=4096,
        enable_minute_window=True,
        use_mxu_tables=use_mxu,
        fused_effects=fused,
        sketch_stats=True,
        sketch_width=2048,
        param_width=2048,
    )
    reg = Registry(cfg)
    flow, deg, par, auth = [], [], [], []
    for i in range(48):
        name = f"res-{i+1}"
        reg.resource_id(name)
        behavior = (
            CONTROL_RATE_LIMITER
            if i % 4 == 1
            else (CONTROL_WARM_UP if i % 4 == 2 else 0)
        )
        flow.append(
            FlowRule(
                resource=name,
                count=40.0 + i,
                control_behavior=behavior,
                max_queueing_time_ms=30,
            )
        )
        deg.append(
            DegradeRule(resource=name, grade=i % 3, count=10.0, time_window=5)
        )
        if i < 12:
            par.append(
                ParamFlowRule(
                    resource=name, param_idx=0, count=6.0, grade=1 if i % 2 else 0
                )
            )
        if i < 6:
            auth.append(
                AuthorityRule(
                    resource=name, limit_app="peer", strategy=AUTHORITY_BLACK
                )
            )
    rules = E.compile_ruleset(
        cfg,
        reg,
        flow_rules=flow,
        degrade_rules=deg,
        param_rules=par,
        authority_rules=auth,
        system_rules=[SystemRule(qps=1e8)],
    )
    state = E.init_state(cfg)
    tick = E.make_tick(cfg, donate=False, features=E.ALL_FEATURES)
    verdicts = []
    for t, (acq, comp) in enumerate(build_batches(cfg, reg, seed=11)):
        state, out = tick(
            state,
            rules,
            acq,
            comp,
            jnp.int32(1000 + 311 * t),
            jnp.float32(0.1),
            jnp.float32(0.1),
        )
        verdicts.append(np.asarray(out.verdict))
    return jax.tree.map(np.asarray, state), verdicts


def main() -> int:
    import jax

    platform = jax.devices()[0].platform
    print(f"platform: {platform}")
    if platform == "cpu":
        print("WARNING: running on CPU — this only re-checks what CI covers")

    global jnp
    import jax.numpy as jnp

    ref_state, ref_v = run_path(use_mxu=False, fused=False)
    paths = [("mxu", True, False), ("fused", True, True)]
    ok = True
    for name, um, fu in paths:
        st, vs = run_path(use_mxu=um, fused=fu)
        for t, (a, b) in enumerate(zip(ref_v, vs)):
            if not np.array_equal(a, b):
                n_diff = int((a != b).sum())
                print(f"FAIL [{name}] tick {t}: {n_diff} verdict mismatches")
                ok = False
        leaves_a = jax.tree_util.tree_flatten_with_path(ref_state)[0]
        leaves_b = jax.tree.leaves(st)
        for (path, x), y in zip(leaves_a, leaves_b):
            if not np.array_equal(x, y):
                print(f"FAIL [{name}] state mismatch at {jax.tree_util.keystr(path)}")
                ok = False
        print(f"[{name}] {'OK' if ok else 'MISMATCH'} — 6 ticks, verdicts + state")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
