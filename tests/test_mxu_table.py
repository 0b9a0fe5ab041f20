"""MXU table ops vs numpy scatter/gather oracle — exactness, not approximation
(the one-hot contraction touches exactly one nonzero per selection)."""

import numpy as np
import pytest

import jax.numpy as jnp

from sentinel_tpu.ops import mxu_table as M


@pytest.mark.parametrize("n,b", [(1000, 257), (70_000, 4096), (131, 64)])
def test_scatter_add_matches_oracle(n, b):
    rng = np.random.default_rng(0)
    idx = rng.integers(-5, n + 5, b).astype(np.int32)  # include OOB → dropped
    vals = rng.integers(0, 100, (b, 3)).astype(np.int32)
    table = rng.integers(0, 1000, (n, 3)).astype(np.int32)

    oracle = table.copy()
    for i in range(b):
        if 0 <= idx[i] < n:
            oracle[idx[i]] += vals[i]

    plan = M.make_plan(n)
    Hi, Lo = M.onehots(jnp.asarray(idx), plan)
    out = np.asarray(M.scatter_add(jnp.asarray(table), plan, Hi, Lo, jnp.asarray(vals)))
    np.testing.assert_array_equal(out, oracle)


def test_scatter_add_float_plane():
    rng = np.random.default_rng(1)
    n, b = 5000, 1024
    idx = rng.integers(0, n, b).astype(np.int32)
    rt = rng.uniform(0, 5000, b).astype(np.float32)
    table = np.zeros((n,), np.float32)
    oracle = table.copy()
    for i in range(b):
        oracle[idx[i]] += rt[i]
    plan = M.make_plan(n)
    Hi, Lo = M.onehots(jnp.asarray(idx), plan)
    out = np.asarray(M.scatter_add(jnp.asarray(table), plan, Hi, Lo, jnp.asarray(rt)))
    np.testing.assert_allclose(out, oracle, rtol=1e-5, atol=1e-2)


@pytest.mark.parametrize("planes", [(), (5,), (2, 5)])
def test_gather_matches_oracle(planes):
    rng = np.random.default_rng(2)
    n, b = 33_000, 2048
    idx = rng.integers(-3, n + 3, b).astype(np.int32)
    table = rng.integers(0, 1 << 20, (n,) + planes).astype(np.int32)
    plan = M.make_plan(n)
    Hi, Lo = M.onehots(jnp.asarray(idx), plan)
    out = np.asarray(M.gather(jnp.asarray(table), plan, Hi, Lo))
    oracle = np.zeros((b,) + planes, np.int32)
    for i in range(b):
        if 0 <= idx[i] < n:
            oracle[i] = table[idx[i]]
    np.testing.assert_array_equal(out, oracle)


def test_gather_respects_valid_mask():
    n = 100
    idx = jnp.asarray([1, 2, 3], jnp.int32)
    table = jnp.arange(n, dtype=jnp.int32) * 10
    plan = M.make_plan(n)
    Hi, Lo = M.onehots(idx, plan, valid=jnp.asarray([True, False, True]))
    out = np.asarray(M.gather(table, plan, Hi, Lo))
    np.testing.assert_array_equal(out, [10, 0, 30])


def test_scatter_or():
    n, b = 4097, 512
    rng = np.random.default_rng(3)
    idx = rng.integers(0, n, b).astype(np.int32)
    flag = (rng.random(b) < 0.3)
    table = np.zeros((n,), np.int32)
    oracle = table.copy()
    for i in range(b):
        if flag[i]:
            oracle[idx[i]] = 1
    plan = M.make_plan(n)
    Hi, Lo = M.onehots(jnp.asarray(idx), plan)
    out = np.asarray(M.scatter_or(jnp.asarray(table), plan, Hi, Lo, jnp.asarray(flag)))
    np.testing.assert_array_equal(out, oracle)


def test_lane_gather_1col_matches_big_gather():
    """The lane-packed 1-column gather (pad to 8 lanes + data-dependent
    select) must match big_gather exactly — including out-of-range ids
    (zeros), n not a multiple of 8, and large f32 sentinels — on both the
    mxu and plain backends."""
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.ops import tables as T

    rng = np.random.default_rng(9)
    for n in (4093, 4096, 16384):
        idx = rng.integers(-3, n + 5, 777).astype(np.int32)  # incl. OOB
        for table in (
            rng.integers(0, (1 << 24) - 1, n).astype(np.int32),
            np.where(
                rng.random(n) < 0.5, 2.0e38, rng.random(n) * 100
            ).astype(np.float32),
        ):
            for mxu in (False, True):
                cfg = small_engine_config(use_mxu_tables=mxu)
                got = np.asarray(
                    T.lane_gather_1col(cfg, jnp.asarray(table), jnp.asarray(idx), n)
                )
                ok = (idx >= 0) & (idx < n)
                want = np.where(ok, table[np.clip(idx, 0, n - 1)], 0).astype(
                    np.float32
                )
                np.testing.assert_array_equal(got, want)
    # int variant restores exact small ints
    cfg = small_engine_config(use_mxu_tables=True)
    tab = rng.integers(0, 4096, 1000).astype(np.int32)
    ids = rng.integers(0, 1000, 256).astype(np.int32)
    got = np.asarray(T.lane_gather_1col_int(cfg, jnp.asarray(tab), jnp.asarray(ids), 1000))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, tab[ids])


@pytest.mark.parametrize(
    "n,n_lo",
    [
        (1, 512), (7, 512), (127, 64), (128, 512), (129, 512), (131, 128),
        (255, 1000), (4093, 512), (1 << 14, 512), ((1 << 14) + 1, 512),
        (70_000, 384),
    ],
)
def test_make_plan_clamp_invariants(n, n_lo):
    """The clamp must hold for ANY (n, n_lo): the padded id space covers
    every logical id, the Lo axis is lane-friendly, and small tables never
    keep a caller's wide default (minimal padding, one Hi row)."""
    plan = M.make_plan(n, n_lo)
    assert plan.n_lo % 128 == 0
    assert plan.n_lo >= 128
    assert plan.padded >= n, (plan, n)
    # the Lo axis never exceeds the smallest lane multiple covering n
    assert plan.n_lo <= max(128, ((n + 127) // 128) * 128)
    assert plan.n_hi >= 1


def test_make_plan_small_n_full_coverage():
    """Scatter then gather across EVERY id of an awkward small size (the
    default n_lo=512 must clamp down, not truncate the id space)."""
    n = 131
    plan = M.make_plan(n)
    assert plan.padded >= n
    idx = jnp.arange(n, dtype=jnp.int32)
    Hi, Lo = M.onehots(idx, plan)
    vals = jnp.arange(1, n + 1, dtype=jnp.int32)
    tab = M.scatter_add(jnp.zeros((n,), jnp.int32), plan, Hi, Lo, vals)
    np.testing.assert_array_equal(np.asarray(tab), np.arange(1, n + 1))
    got = np.asarray(M.gather(tab, plan, Hi, Lo))
    np.testing.assert_array_equal(got, np.arange(1, n + 1))


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_depth_histogram_mxu_native_parity(depth):
    """tables.depth_histogram: the flat [depth*width] MXU contraction must
    be BIT-equal to the native scatter path and the per-event oracle —
    including invalid rows and out-of-range columns (dropped)."""
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.ops import tables as T

    rng = np.random.default_rng(17 + depth)
    width, N, P = 1 << 10, 513, 3
    cols = rng.integers(-2, width + 2, (N, depth)).astype(np.int32)
    vals = rng.integers(0, 50, (N, P)).astype(np.int32)
    valid = rng.random(N) < 0.8
    args = (jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(valid), depth, width)
    oracle = np.zeros((depth, width, P), np.int64)
    for i in range(N):
        if not valid[i]:
            continue
        for d in range(depth):
            c = cols[i, d]
            if 0 <= c < width:
                oracle[d, c] += vals[i]
    native = np.asarray(T.depth_histogram(None, *args))
    mxu = np.asarray(
        T.depth_histogram(small_engine_config(use_mxu_tables=True), *args)
    )
    np.testing.assert_array_equal(native, oracle)
    np.testing.assert_array_equal(mxu, oracle)


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("kind", ["int", "float"])
def test_depth_gather_1col_mxu_native_parity(depth, kind):
    """tables.depth_gather_1col: one flat contraction (int digit planes) /
    one lane gather (float) per batch must match the native gather and the
    oracle exactly for both table dtypes, depths 1–3, out-of-range ids."""
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.ops import tables as T

    rng = np.random.default_rng(23 + depth)
    width, N = 1 << 10, 777
    if kind == "int":
        tab = rng.integers(0, (1 << 24) - 1, (depth, width)).astype(np.int32)
        max_int = (1 << 24) - 1
    else:
        tab = (rng.random((depth, width)) * 5000.0).astype(np.float32)
        max_int = None
    cols = rng.integers(-2, width + 2, (N, depth)).astype(np.int32)
    oracle = np.zeros((depth, N), np.float32)
    for d in range(depth):
        ok = (cols[:, d] >= 0) & (cols[:, d] < width)
        oracle[d] = np.where(ok, tab[d, np.clip(cols[:, d], 0, width - 1)], 0)
    native = np.asarray(
        T.depth_gather_1col(None, jnp.asarray(tab), jnp.asarray(cols), width,
                            max_int=max_int)
    )
    mxu = np.asarray(
        T.depth_gather_1col(
            small_engine_config(use_mxu_tables=True),
            jnp.asarray(tab), jnp.asarray(cols), width, max_int=max_int,
        )
    )
    np.testing.assert_array_equal(native, oracle)
    np.testing.assert_array_equal(mxu, oracle)


def test_lane_gather_multi_matches_oracle():
    """tables.lane_gather_multi: k tables, one shared row gather — exact
    vs numpy for odd/even n, k=1..4, out-of-range ids."""
    import jax.numpy as jnp

    from sentinel_tpu.core.config import EngineConfig
    from sentinel_tpu.ops import tables as T

    rng = np.random.default_rng(31)
    cfg = EngineConfig(use_mxu_tables=True)
    for n in (7, 16, 333):
        for k in (1, 2, 3, 4):
            tabs = [
                rng.integers(0, 1 << 20, n).astype(np.int32) for _ in range(k)
            ]
            idx = rng.integers(-3, n + 3, 257).astype(np.int32)
            got = T.lane_gather_multi(
                cfg, [jnp.asarray(t) for t in tabs], jnp.asarray(idx), n
            )
            ok = (idx >= 0) & (idx < n)
            for c in range(k):
                want = np.where(ok, tabs[c][np.clip(idx, 0, n - 1)], 0)
                np.testing.assert_array_equal(
                    np.asarray(got[c]).astype(np.int64), want,
                    err_msg=f"n={n} k={k} col={c}",
                )


@pytest.mark.parametrize("n_rows", [257, 2049])  # the flat one-hot, and the Hi/Lo plan
def test_small_gather_int_survives_the_chips_bfloat16_matmul(monkeypatch, n_rows):
    """On the chip a matmul at tables.PRECISION rounds both sides to
    bfloat16 (8 bits).  With the table's side rounded the same way here,
    raw int32 hashes must still come back bit for bit: the table crosses as
    bytes.  (As 16-bit halves no ParamFlowItem hash matched on the chip.)"""
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.ops import tables as T

    real = T.small_gather_fields

    def as_the_chip(cfg, packed, slots):
        return real(cfg, packed.astype(jnp.bfloat16).astype(jnp.float32), slots)

    monkeypatch.setattr(T, "small_gather_fields", as_the_chip)
    rng = np.random.default_rng(11)
    table = rng.integers(-(2**31), 2**31 - 1, (n_rows, 3)).astype(np.int32)
    table[0] = (0, -1, 2**31 - 1)
    slots = rng.integers(0, n_rows, 500).astype(np.int32)
    cfg = small_engine_config(use_mxu_tables=True)
    got = np.asarray(T.small_gather_int(cfg, jnp.asarray(table), jnp.asarray(slots)))
    assert got.dtype == np.int32 and (got == table[slots]).all()
