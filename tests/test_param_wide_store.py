"""A wide hot-parameter store (ops/param.py: every plane's cell axis kept as
(Q / 128, 128) tiles) held cell for cell to a NumPy count-min.

A seeded stream of QPS- and THREAD-grade hot-parameter traffic is served
through whole ticks over a store of 2^15 cells a depth, across bucket
roll-overs and once around the bucket ring, on each effects path.  After
every tick ``pcms.reshape(depth, nb, Q)`` has to equal a count-min built from
``P.pair_rows`` of the items the tick admitted (the landing adds to the
current bucket and to nothing else; the refresh clears the stale bucket and
nothing else), and ``pconc.reshape(depth, Q)`` the admitted THREAD-grade
entries less the exits, held at zero.  No benchmark cell loads a THREAD-grade
rule, so the concurrency plane's tiled form is held here alone."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from sentinel_tpu.core.config import small_engine_config
from sentinel_tpu.core.rules import GRADE_QPS, GRADE_THREAD, ParamFlowRule
from sentinel_tpu.ops import engine as E
from sentinel_tpu.ops import param as P
from sentinel_tpu.runtime.registry import Registry

pytestmark = pytest.mark.jitted

Q = 1 << 15
B = 128
PATHS = {
    "plain": {},
    "fused": dict(use_mxu_tables=True, fused_effects=True),
    "seg": dict(use_mxu_tables=True, fused_effects=True, seg_effects=True),
}
#: tick instants: steps inside a 500 ms bucket, over its edge, a gap that
#: skips buckets, and past the ring's 8 x 500 ms so that indices come round
NOW_MS = (1000, 1170, 1340, 1510, 1900, 2300, 2800, 3400, 4100, 4900, 5200, 5700, 6300, 7100)


@functools.cache
def _served(path: str):
    """Serve the stream on ``path``; a record a tick of how the store stood
    against the reference (compared here, so that no tick's 2 MiB planes
    outlive it): cells of ``pcms`` and of ``pconc`` that differ, which
    buckets hold counts, the concurrency held, the verdicts."""
    cfg = small_engine_config(
        batch_size=B, complete_batch_size=B, param_width=Q, param_rules_per_resource=1,
        **PATHS[path],
    )
    assert P.wide(cfg) and P.store_shape(cfg) == (2, 8, Q // 128, 128)
    reg = Registry(cfg)
    names = [f"route-{i}" for i in range(6)]
    rids = np.array([reg.resource_id(n) for n in names], np.int32)
    grades = [GRADE_THREAD if i == 5 else GRADE_QPS for i in range(6)]
    rules = E.compile_ruleset(cfg, reg, param_rules=[
        ParamFlowRule(resource=n, param_idx=0, count=4.0 if g == GRADE_THREAD else 3.0, grade=g, duration_in_sec=1)
        for n, g in zip(names, grades)
    ])
    slot_of = {int(r): int(np.asarray(rules.param.res_params)[r, 0]) for r in rids}
    grade_of = {int(r): g for r, g in zip(rids, grades)}
    assert set(np.asarray(rules.param.lane)[list(slot_of.values())].tolist()) == {0}

    tick = E.make_tick(cfg, donate=False, features=frozenset({"param"}))
    state = E.init_state(cfg)
    assert state.pconc.shape == (2, Q // 128, 128)
    depth, nb = cfg.param_depth, cfg.param_sample_count
    ref = np.zeros((depth, nb, Q), np.int64)
    ref_epochs = np.full((nb,), -(nb + 1), np.int64)
    ref_conc = np.zeros((depth, Q), np.int64)
    rng = np.random.default_rng(35)

    def draw():
        """A batch sorted by resource (the segment path's precondition):
        a few padding rows, some items without a value, values that repeat."""
        res = rids[np.minimum(rng.zipf(1.5, B) - 1, len(rids) - 1)]
        res = np.where(rng.random(B) < 0.05, cfg.trash_row, res).astype(np.int32)
        value = np.where(rng.random(B) < 0.1, 0, 7001 + rng.integers(0, 12, B)).astype(np.int32)
        order = np.lexsort((np.arange(B), res))
        return res[order], value[order]

    def rows_of(res, value):
        slots = np.array([slot_of.get(int(r), 0) for r in res], np.int32)
        return np.asarray(P.pair_rows(jnp.asarray(slots), jnp.asarray(value), depth, Q))

    def lanes(value):  # the value's hash in lane 0 (param_idx 0), nothing in lane 1
        return jnp.asarray(np.stack([value, np.zeros_like(value)], axis=1))

    def graded(res, value, grade):
        return np.array([grade_of.get(int(r)) == grade for r in res]) & (value != 0)

    seen = []
    for now in NOW_MS:
        a_res, a_val = draw()
        c_res, c_val = draw()
        c_succ = rng.integers(0, 3, B).astype(np.int32)
        acq = E.empty_acquire(cfg)._replace(
            res=jnp.asarray(a_res), count=jnp.ones((B,), jnp.int32), param_hash=lanes(a_val))
        comp = E.empty_complete(cfg)._replace(
            res=jnp.asarray(c_res), success=jnp.asarray(c_succ), param_hash=lanes(c_val))
        state, out = tick(state, rules, acq, comp, jnp.int32(now), jnp.float32(0), jnp.float32(0))
        admitted = (np.asarray(out.verdict) == E.PASS) & (a_res != cfg.trash_row)

        # the reference: exits release first, then the stale bucket is cleared,
        # then what the tick admitted lands in the current bucket
        rel = graded(c_res, c_val, GRADE_THREAD)
        rows_c = rows_of(c_res, c_val)
        for d in range(depth):
            np.subtract.at(ref_conc[d], rows_c[rel, d], c_succ[rel])
        np.maximum(ref_conc, 0, out=ref_conc)
        wid = now // cfg.param_bucket_ms
        idx = wid % nb
        if ref_epochs[idx] != wid:
            ref[:, idx, :] = 0
            ref_epochs[idx] = wid
        rows_a = rows_of(a_res, a_val)
        qps = admitted & graded(a_res, a_val, GRADE_QPS)
        thr = admitted & graded(a_res, a_val, GRADE_THREAD)
        for d in range(depth):
            np.add.at(ref[d, idx], rows_a[qps, d], 1)
            np.add.at(ref_conc[d], rows_a[thr, d], 1)
        pcms = np.asarray(state.pcms).reshape(depth, nb, Q)
        pconc = np.asarray(state.pconc).reshape(depth, Q)
        seen.append(dict(
            pcms_off=int(np.count_nonzero(pcms != ref)), pconc_off=int(np.count_nonzero(pconc != ref_conc)),
            counting=pcms.any(axis=(0, 2)), held=int(pconc.sum()), verdict=np.asarray(out.verdict),
        ))
    return seen


@pytest.mark.parametrize("path", list(PATHS))
def test_a_wide_store_equals_a_numpy_count_min_cell_for_cell(path):
    seen = _served(path)
    assert [s["pcms_off"] for s in seen] == [0] * len(NOW_MS)  # cells off the reference, a tick
    # the stream did what the test is for: both verdicts, more than two
    # roll-overs, a bucket index that came round and was cleared
    verdicts = np.concatenate([s["verdict"] for s in seen])
    assert (verdicts == E.PASS).any() and (verdicts == E.BLOCK_PARAM).any()
    assert len({now // 500 for now in NOW_MS}) > 3
    assert NOW_MS[-1] // 500 - NOW_MS[0] // 500 >= 8
    first_bucket = (NOW_MS[0] // 500) % 8
    assert seen[0]["counting"][first_bucket]
    assert seen[-1]["counting"].sum() >= 2  # several buckets hold counts at the end


@pytest.mark.parametrize("path", list(PATHS))
def test_a_wide_stores_concurrency_plane_follows_entries_and_exits(path):
    seen = _served(path)
    assert [s["pconc_off"] for s in seen] == [0] * len(NOW_MS)  # cells off the reference, a tick
    held = [s["held"] for s in seen]
    assert max(held) > 0 and any(b < a for a, b in zip(held, held[1:]))  # entries and exits both moved it


def test_the_paths_agree_on_every_verdict():
    plain = _served("plain")
    for path in ("fused", "seg"):
        for t, (a, b) in enumerate(zip(plain, _served(path))):
            np.testing.assert_array_equal(a["verdict"], b["verdict"], err_msg=f"{path}, tick {t}")
