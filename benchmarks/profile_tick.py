"""Profile the engine tick: dispatch overhead vs device time, per-stage cost.

Usage:  python benchmarks/profile_tick.py [--features flow|all|none] [--batch 131072]

Two measurements per configuration:
  - "dispatch": N pipelined single-tick dispatches, one readback (what
    bench.py measured in round 1 — includes per-launch dispatch cost).
  - "scanned": K ticks inside ONE jitted lax.scan, so per-launch overhead
    is amortized K x and the number approaches true device time per tick.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build(features: frozenset, B: int, n_ruled: int, use_scan_k: int):
    import jax
    import jax.numpy as jnp

    from sentinel_tpu.core.config import EngineConfig
    from sentinel_tpu.core.rules import FlowRule, DegradeRule, ParamFlowRule
    from sentinel_tpu.ops import engine as E
    from sentinel_tpu.runtime.registry import Registry

    platform = jax.devices()[0].platform
    on_tpu = platform != "cpu"
    n_total = 1 << 20
    cfg = EngineConfig(
        max_resources=16384,
        max_nodes=16384,
        max_flow_rules=16384,
        max_degrade_rules=4096,
        max_param_rules=64,
        flow_rules_per_resource=1,
        degrade_rules_per_resource=1,
        param_rules_per_resource=1,
        batch_size=B,
        complete_batch_size=B,
        enable_minute_window=False,
        use_mxu_tables=on_tpu,
        sketch_stats=True,
    )
    reg = Registry(cfg)
    flow_rules, degrade_rules, param_rules = [], [], []
    for i in range(n_ruled):
        name = f"res-{i+1}"
        reg.resource_id(name)
        flow_rules.append(FlowRule(resource=name, count=1000.0))
        if "degrade" in features:
            degrade_rules.append(
                DegradeRule(resource=name, grade=0, count=50.0, time_window=10)
            )
        if "param" in features and i < 60:
            param_rules.append(
                ParamFlowRule(resource=name, param_idx=0, count=100.0)
            )
    ruleset = E.compile_ruleset(
        cfg,
        reg,
        flow_rules=flow_rules,
        degrade_rules=degrade_rules,
        param_rules=param_rules,
    )

    rng = np.random.default_rng(0)
    n_batches = 4
    acqs, comps = [], []
    for i in range(n_batches):
        z = rng.zipf(1.3, size=B).astype(np.int64)
        raw = (z - 1) % (n_total - 1) + 1
        ids_np = np.where(raw <= n_ruled, raw, cfg.node_rows + raw).astype(np.int32)
        ids = jnp.asarray(ids_np)
        ph = jnp.asarray(rng.integers(1, 1 << 20, (B, cfg.param_dims), dtype=np.int32))
        acqs.append(
            E.empty_acquire(cfg)._replace(
                res=ids, count=jnp.ones((B,), jnp.int32), param_hash=ph
            )
        )
        comps.append(
            E.empty_complete(cfg)._replace(
                res=ids,
                rt=jnp.abs(jnp.asarray(rng.normal(3.0, 1.0, B), dtype=np.float32)),
                success=jnp.ones((B,), jnp.int32),
            )
        )
    return jax, jnp, cfg, E, ruleset, acqs, comps, platform


def measure(features: frozenset, B: int, n_ruled: int, label: str):
    import jax
    import jax.numpy as jnp

    jax_, jnp_, cfg, E, ruleset, acqs, comps, platform = build(
        features, B, n_ruled, 0
    )
    n_batches = len(acqs)

    tick = E.make_tick(cfg, donate=True, features=features)
    state0 = E.init_state(cfg)
    load = jnp.float32(0.0)
    cpu = jnp.float32(0.0)

    # scanned ticks, slope-timed: device ms/tick = (T(K2)-T(K1))/(K2-K1)
    KS = 4  # distinct stacked batches reused cyclically inside the scan
    stacked_acq = jax.tree.map(lambda *xs: jnp.stack(xs), *(acqs[i % n_batches] for i in range(KS)))
    stacked_comp = jax.tree.map(lambda *xs: jnp.stack(xs), *(comps[i % n_batches] for i in range(KS)))

    def make_many(K):
        def many(state, base, sacq, scomp):
            def body(s, t):
                a = jax.tree.map(lambda x: x[t % KS], sacq)
                c = jax.tree.map(lambda x: x[t % KS], scomp)
                s, o = E.tick(s, ruleset, a, c, base + t * 7, load, cpu, cfg=cfg,
                              features=features)
                return s, o.verdict[0]
            state, vs = jax.lax.scan(body, state, jnp.arange(K, dtype=jnp.int32))
            return state, vs
        return jax.jit(many)

    import time as _time
    k1, k2 = 8, 72
    m1, m2 = make_many(k1), make_many(k2)
    jax.block_until_ready(m1(state0, jnp.int32(0), stacked_acq, stacked_comp))
    jax.block_until_ready(m2(state0, jnp.int32(0), stacked_acq, stacked_comp))
    t1s, t2s = [], []
    for s in range(3):
        t0 = _time.perf_counter()
        jax.block_until_ready(m1(state0, jnp.int32(1000 * s), stacked_acq, stacked_comp))
        t1s.append(_time.perf_counter() - t0)
        t0 = _time.perf_counter()
        jax.block_until_ready(m2(state0, jnp.int32(1000 * s), stacked_acq, stacked_comp))
        t2s.append(_time.perf_counter() - t0)
    scan_ms = (min(t2s) - min(t1s)) / (k2 - k1) * 1000.0

    print(
        f"{label:28s} B={B} device={scan_ms:8.3f} ms/tick"
        f"  -> {B / scan_ms * 1000 / 1e6:8.2f} M dec/s device"
    )
    return scan_ms, scan_ms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=131072)
    ap.add_argument("--ruled", type=int, default=10000)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--ablate", action="store_true")
    args = ap.parse_args()
    B = args.batch

    suites = [
        ("stats only", frozenset()),
        ("flow", frozenset({"flow"})),
        ("flow+degrade", frozenset({"flow", "degrade"})),
        ("flow+param", frozenset({"flow", "param"})),
        ("ALL", None),  # engine.ALL_FEATURES
    ]
    if args.quick:
        suites = [("flow", frozenset({"flow"})), ("ALL", None)]
    if args.ablate:
        from sentinel_tpu.ops import engine as E2
        suites = [(f"ALL-{f}", E2.ALL_FEATURES - {f}) for f in
                  ("nodes", "occupy", "warmup", "authority", "system")]

    from sentinel_tpu.ops import engine as E

    for label, feats in suites:
        feats = E.ALL_FEATURES if feats is None else feats
        measure(feats, B, args.ruled, label)


if __name__ == "__main__":
    main()
