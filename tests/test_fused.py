"""Fused Pallas effects kernels (ops/fused.py): exactness vs oracles and
engine-path equivalence.

On CPU the kernels run in Pallas interpret mode — semantics only; the
device path is exercised on the chip by chip_smoke.py (full-width
served-vs-plain verdict equivalence) and perfbench/run.py."""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
import pytest

from sentinel_tpu.ops import fused as FU


def test_scatter_many_exact_vs_numpy():
    rng = np.random.default_rng(7)
    N = 700
    rows1 = rng.integers(-5, 320, (3, N)).astype(np.int32)
    vals1 = np.stack(
        [
            rng.integers(0, 60000, N),
            rng.integers(0, 2, N),
            rng.integers(0, 40000, N),
        ]
    ).astype(np.int32)
    rows2 = rng.integers(-2, 90, (2, N)).astype(np.int32)
    vals2 = rng.integers(0, 200, (2, 2, N)).astype(np.int32)

    o1, o2 = FU.scatter_many(
        [
            FU.Job("a", 300, jnp.asarray(rows1), jnp.asarray(vals1), (2, 1, 2)),
            FU.Job("b", 77, jnp.asarray(rows2), jnp.asarray(vals2), (1, 1)),
        ],
        tb=256,
        interpret=True,
    )
    ref1 = np.zeros((300, 3), np.int64)
    for r in range(3):
        ok = (rows1[r] >= 0) & (rows1[r] < 300)
        for p in range(3):
            np.add.at(ref1[:, p], rows1[r][ok], vals1[p][ok])
    assert np.array_equal(np.asarray(o1).astype(np.int64), ref1)
    ref2 = np.zeros((77, 2), np.int64)
    for r in range(2):
        ok = (rows2[r] >= 0) & (rows2[r] < 77)
        for p in range(2):
            np.add.at(ref2[:, p], rows2[r][ok], vals2[r, p][ok])
    assert np.array_equal(np.asarray(o2).astype(np.int64), ref2)


def test_gather_many_exact_vs_numpy():
    rng = np.random.default_rng(8)
    N = 500
    ids = rng.integers(-3, 310, N).astype(np.int32)
    tab = rng.integers(0, 1 << 24, (300, 2)).astype(np.int32)
    (g,) = FU.gather_many(
        [FU.GatherJob("g", jnp.asarray(ids), jnp.asarray(tab), (3, 3))],
        tb=256,
        interpret=True,
    )
    ok = (ids >= 0) & (ids < 300)
    ref = np.zeros((N, 2), np.int64)
    ref[ok] = tab[ids[ok]]
    assert np.array_equal(np.asarray(g).astype(np.int64), ref)


def _tick_once(cfg, seed=0, sort_batches=False):
    """Run a few full-feature ticks exercising every fused plane: default +
    rate-limiter + warm-up flow rules, prioritized occupy-ahead, ctx/origin
    stat fan, QPS + THREAD param rules, slow-ratio breakers.  Returns
    (state, outputs).

    sort_batches: stably presort each batch by resource id (the segment
    engine's fast-rank precondition) and report verdicts in arrival
    order."""
    import jax

    from sentinel_tpu.core.rules import (
        CONTROL_RATE_LIMITER,
        CONTROL_WARM_UP,
        DegradeRule,
        FlowRule,
        ParamFlowRule,
    )
    from sentinel_tpu.ops import engine as E
    from sentinel_tpu.runtime.registry import Registry

    reg = Registry(cfg)
    flow, deg, par = [], [], []
    for i in range(12):
        name = f"r{i}"
        reg.resource_id(name)
        behavior = (
            CONTROL_RATE_LIMITER
            if i % 3 == 1
            else (CONTROL_WARM_UP if i % 3 == 2 else 0)
        )
        flow.append(
            FlowRule(
                resource=name,
                count=5.0,
                control_behavior=behavior,
                max_queueing_time_ms=40 if behavior == CONTROL_RATE_LIMITER else 0,
            )
        )
        deg.append(DegradeRule(resource=name, grade=0, count=2.0, time_window=5))
        if i < 4:
            par.append(
                ParamFlowRule(resource=name, param_idx=0, count=3.0, grade=1 if i % 2 else 0)
            )
    rules = E.compile_ruleset(cfg, reg, flow_rules=flow, degrade_rules=deg, param_rules=par)
    state = E.init_state(cfg)
    rng = np.random.default_rng(seed)
    B = cfg.batch_size
    outs = []
    origin_row = reg.origin_node_row("r0", "peer")
    ctx_row = reg.ctx_node_row("r1", "ctx-a")
    ctx_id = reg.context_id("ctx-a")
    for t in range(4):
        ids = rng.integers(1, 14, B).astype(np.int32)
        witho = rng.random(B) < 0.3
        withc = rng.random(B) < 0.25
        prio = (rng.random(B) < 0.3).astype(np.int32)
        a_inb = (rng.random(B) < 0.5).astype(np.int32)
        a_ph = np.stack([rng.integers(1, 5, B), np.zeros(B)], axis=1).astype(np.int32)
        rt = rng.uniform(0.5, 8.0, B).astype(np.float32)
        err = (rng.random(B) < 0.3).astype(np.int32)
        c_inb = (rng.random(B) < 0.5).astype(np.int32)
        c_ph = np.stack([rng.integers(1, 5, B), np.zeros(B)], axis=1).astype(np.int32)
        if sort_batches:
            order = np.lexsort((np.arange(B), ids))
            inv = np.empty(B, np.int64)
            inv[order] = np.arange(B)
            ids, witho, withc, prio = ids[order], witho[order], withc[order], prio[order]
            a_inb, a_ph, rt, err = a_inb[order], a_ph[order], rt[order], err[order]
            c_inb, c_ph = c_inb[order], c_ph[order]
        acq = E.empty_acquire(cfg)._replace(
            res=jnp.asarray(ids),
            count=jnp.ones((B,), jnp.int32),
            prio=jnp.asarray(prio),
            origin_node=jnp.asarray(
                np.where(witho, origin_row, cfg.trash_row).astype(np.int32)
            ),
            ctx_node=jnp.asarray(
                np.where(withc, ctx_row, cfg.trash_row).astype(np.int32)
            ),
            ctx_name=jnp.asarray(
                np.where(withc, ctx_id, -1).astype(np.int32)
            ),
            inbound=jnp.asarray(a_inb),
            param_hash=jnp.asarray(a_ph),
        )
        comp = E.empty_complete(cfg)._replace(
            res=jnp.asarray(ids),
            rt=jnp.asarray(rt),
            success=jnp.ones((B,), jnp.int32),
            error=jnp.asarray(err),
            inbound=jnp.asarray(c_inb),
            param_hash=jnp.asarray(c_ph),
        )
        state, out = E.tick(
            state,
            rules,
            acq,
            comp,
            jnp.int32(1000 + 333 * t),
            jnp.float32(0.0),
            jnp.float32(0.0),
            cfg=cfg,
        )
        v = np.asarray(out.verdict)
        outs.append(v[inv] if sort_batches else v)
    return jax.tree.map(np.asarray, state), outs


@pytest.mark.slow  # full-tick equivalence: ~minutes on a 1-core host; see test_engine_seg.py note
@pytest.mark.parametrize("sketch", [False, True])
def test_fused_tick_matches_mxu_path(sketch):
    """Full ticks through the fused-effects path must be bit-identical to
    the unfused MXU path (which test_engine_backends pins to the scatter
    oracle)."""
    from sentinel_tpu.core.config import small_engine_config

    base = dict(
        batch_size=96,
        complete_batch_size=96,
        use_mxu_tables=True,
        sketch_stats=sketch,
        enable_minute_window=True,
    )
    cfg_mxu = small_engine_config(**base)
    cfg_fused = small_engine_config(**base, fused_effects=True)
    st1, out1 = _tick_once(cfg_mxu)
    st2, out2 = _tick_once(cfg_fused)
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(a, b)
    import jax

    l1, treedef = jax.tree.flatten(st1)
    l2 = jax.tree.leaves(st2)
    paths = [str(p) for p, _ in jax.tree_util.tree_flatten_with_path(st1)[0]]
    for p, x, y in zip(paths, l1, l2):
        np.testing.assert_array_equal(x, y, err_msg=p)


# -- scatter_sorted: a table too wide for scatter_many's resident form ------


def _ref_scatter(n, rows, vals):
    """numpy oracle for one Job: rows [R, N], vals [P, N] or [R, P, N]."""
    R, P = rows.shape[0], vals.shape[-2]
    ref = np.zeros((n, P), np.int64)
    for r in range(R):
        ok = (rows[r] >= 0) & (rows[r] < n)
        for p in range(P):
            v = vals[r, p] if vals.ndim == 3 else vals[p]
            np.add.at(ref[:, p], rows[r][ok], v[ok])
    return ref


@pytest.mark.parametrize(
    "n,N,R,per_row,digits,tb",
    [
        (1 << 15, 700, 1, False, (1, 1), 256),  # two stretches, a sparse tick
        (1 << 16, 3000, 2, True, (1, 1), 256),  # per-row-vector values
        (1 << 15, 256, 1, False, (2,), 256),  # one tile spans every stretch
        (1 << 20, 4096, 1, True, (1, 1), 2048),  # the gateway's width
        ((1 << 15) + 640, 900, 1, False, (1, 2), 512),  # a last stretch part-filled
    ],
)
def test_scatter_sorted_exact_vs_numpy(n, N, R, per_row, digits, tb):
    rng = np.random.default_rng(n % 1000 + N)
    rows = rng.integers(-5, n + 5, (R, N)).astype(np.int32)
    rows[:, : N // 4] = rows[0, 0]  # a hot cell, hit hundreds of times
    P = len(digits)
    shape = (R, P, N) if per_row else (P, N)
    vals = np.stack(
        [rng.integers(0, 256 ** d, shape[:-2] + (N,)) for d in digits], axis=-2
    ).astype(np.int32)
    job = FU.Job("wide", n, jnp.asarray(rows), jnp.asarray(vals), digits)
    got = np.asarray(FU.scatter_sorted(job, tb=tb, interpret=True))
    assert got.shape == (n, P)
    assert np.array_equal(got.astype(np.int64), _ref_scatter(n, rows, vals))


def test_scatter_sorted_of_nothing_is_zero():
    rows = np.full((1, 300), -1, np.int32)
    job = FU.Job("idle", 1 << 15, jnp.asarray(rows), jnp.ones((1, 300), jnp.int32), (1,))
    assert not np.asarray(FU.scatter_sorted(job, tb=256, interpret=True)).any()


def test_scatter_many_sends_a_wide_job_to_scatter_sorted_and_keeps_the_order():
    rng = np.random.default_rng(3)
    N = 400
    narrow = rng.integers(-2, 90, (1, N)).astype(np.int32)
    wide = rng.integers(-2, 1 << 15, (1, N)).astype(np.int32)
    v = rng.integers(0, 200, (1, N)).astype(np.int32)
    jobs = [
        FU.Job("n0", 77, jnp.asarray(narrow), jnp.asarray(v), (1,)),
        FU.Job("w", 1 << 15, jnp.asarray(wide), jnp.asarray(v), (1,)),
        FU.Job("n1", 90, jnp.asarray(narrow), jnp.asarray(v), (1,)),
    ]
    assert FU.MAX_RESIDENT_ROWS < 1 << 15
    outs = FU.scatter_many(jobs, tb=256, interpret=True)
    for out, (n, rows) in zip(outs, ((77, narrow), (1 << 15, wide), (90, narrow))):
        assert np.array_equal(np.asarray(out).astype(np.int64), _ref_scatter(n, rows, v))


def test_a_wide_param_store_ticks_alike_on_the_plain_and_the_seg_path():
    """Whole ticks with QPS and THREAD hot-parameter rules over a store of
    2^15 cells a depth (wide: [depth, bucket, cell / 128, 128], written by
    scatter_sorted on the fused paths): the verdicts and the store equal the
    plain scatter path's.  (RT sums differ between the two by the fused
    paths' 1/8 ms quantum, as at any width.)"""
    from sentinel_tpu.core.config import small_engine_config

    base = dict(batch_size=96, complete_batch_size=96, param_width=1 << 15, param_rules_per_resource=1)
    st0, out0 = _tick_once(small_engine_config(**base), sort_batches=True)
    assert st0.pcms.shape == (2, 8, (1 << 15) // 128, 128) and st0.pcms.any() and st0.pconc.any()
    assert any((v == 3).any() for v in out0)  # BLOCK_PARAM was produced
    seg = small_engine_config(**base, use_mxu_tables=True, fused_effects=True, seg_effects=True)
    st1, out1 = _tick_once(seg, sort_batches=True)
    for a, b in zip(out0, out1):
        np.testing.assert_array_equal(a, b)
    for leaf in ("pcms", "pcms_epochs", "pconc"):
        np.testing.assert_array_equal(getattr(st0, leaf), getattr(st1, leaf), err_msg=leaf)
