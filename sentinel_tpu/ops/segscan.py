"""Pallas segmented exclusive prefix sums — the rank-scan hot path.

`segment.seg_excl_cumsum` computed segmented sums as int32 cumsum +
two-level running-max + subtract: exact, but ~1.2 ms of XLA scan ops at
B=128K (profiled: associative_scan slices + reduce-windows dominate the
check phase's rank costs).  This kernel computes the segmented sums
directly in one sequential-grid pass:

  - the exclusive segmented sum equals the INCLUSIVE segmented scan of
    the right-shifted values (sv[i] = head[i] ? 0 : v[i-1]) with the
    heads as reset flags;
  - per 256-item tile, that scan is 8 log-steps of the classic segmented
    combine — s[i] += f[i] ? 0 : s[i-d]; f[i] |= f[i-d] — pure int32
    VPU rolls/selects/adds, bit-exact by construction.  (An earlier
    masked-matmul formulation spent ~0.3 ms/call building [256,256]
    masks on the VPU and LOST to the XLA scans it replaced — measured.)
  - a carry per value row rides VMEM scratch across tiles (sequential
    "arbitrary" grid).  After the within-tile scan, the open segment's
    sum is simply s[TB-1] + v[TB-1], and items before the tile's first
    head add the incoming carry.  int32 wraparound cannot occur within
    the caller contract (per-segment totals < 2^31).

Interpret mode runs the identical kernel on CPU for tests; the public
entries fall back to segment.seg_excl_cumsum when Pallas is unavailable
(SENTINEL_NO_PALLAS).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sentinel_tpu.ops import fused as FU

#: tile length: the grid is SEQUENTIAL (carry), so per-tile overhead is
#: the dominant cost — 2048-item tiles keep the step count low (64 tiles
#: at B=128K; 256-item tiles cost ~0.4 ms/call in pure grid overhead,
#: measured) while the log-step count only grows to 11
TB = 2048


def _kernel(head_ref, vals_ref, out_ref, carry):
    from jax.experimental import pallas as pl

    t = pl.program_id(0)
    V = vals_ref.shape[0]

    @pl.when(t == 0)
    def _():
        carry[...] = jnp.zeros_like(carry)

    h = head_ref[:, :]  # int32 [1, TB] 0/1
    v = vals_ref[:, :]  # int32 [V, TB]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, TB), 1)

    def shift(x, d, fill):
        r = jnp.roll(x, d, axis=-1)
        return jnp.where(iota >= d, r, fill)

    # sv[i] = head[i] ? 0 : v[i-1]  (out-of-tile v treated as 0: the
    # cross-tile contribution rides the carry instead)
    s = jnp.where(h > 0, 0, shift(v, 1, 0))
    f = h
    d = 1
    while d < TB:
        s = s + jnp.where(f > 0, 0, shift(s, d, 0))
        f = jnp.maximum(f, shift(f, d, 0))
        d *= 2
    # s: within-tile EXCLUSIVE segmented sums; f[i]: any head at <= i

    c = carry[0:V, 0:1]  # [V, 1]
    out_ref[:, :] = s + jnp.where(f > 0, 0, c)

    # open segment's within-tile sum = s[last] + v[last]; a head-free tile
    # extends the previous carry
    open_sum = s[:, TB - 1 : TB] + v[:, TB - 1 : TB]  # [V, 1]
    any_head = f[0:1, TB - 1 : TB]  # [1, 1]
    carry[0:V, 0:1] = open_sum + jnp.where(any_head > 0, 0, c)


def seg_excl_cumsum_pl(head: jax.Array, values: jax.Array) -> jax.Array:
    """Drop-in for segment.seg_excl_cumsum: head [N] bool (head[0] True),
    values [V, N] or [N] nonnegative int32 with per-row segment totals
    < 2^31.  Exact; Pallas on TPU, XLA-scan fallback otherwise."""
    from sentinel_tpu.ops import segment as SG

    if not FU.available():
        return SG.seg_excl_cumsum(head, values)

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    squeeze = values.ndim == 1
    v = values[None, :] if squeeze else values
    V, n = v.shape
    v = v.astype(jnp.int32)
    pad = (-n) % TB
    if pad:
        v = jnp.concatenate([v, jnp.zeros((V, pad), jnp.int32)], axis=1)
        head = jnp.concatenate([head, jnp.ones((pad,), bool)])
    Np = v.shape[1]
    nT = Np // TB

    out = FU.run_pallas(pl.pallas_call(
        _kernel,
        grid=(nT,),
        in_specs=[
            pl.BlockSpec((1, TB), lambda t: (0, t), memory_space=pltpu.VMEM),
            pl.BlockSpec((V, TB), lambda t: (0, t), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (V, TB), lambda t: (0, t), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((V, Np), jnp.int32),
        scratch_shapes=[pltpu.VMEM((8, 128), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=FU.interpret_mode(),
        name="seg_excl_cumsum",
    ), head.astype(jnp.int32)[None, :], v,
        key=("seg_excl_cumsum", V, Np))

    res = out[:, :n]
    return res[0] if squeeze else res


def _kernel_min(head_ref, vals_ref, out_ref, carry):
    from jax.experimental import pallas as pl

    t = pl.program_id(0)
    V = vals_ref.shape[0]

    @pl.when(t == 0)
    def _():
        carry[...] = jnp.full_like(carry, jnp.float32(3.0e38))

    h = head_ref[:, :]
    v = vals_ref[:, :]  # f32 [V, TB]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, TB), 1)
    BIG = jnp.float32(3.0e38)

    def shift(x, d):
        r = jnp.roll(x, d, axis=-1)
        return jnp.where(iota >= d, r, BIG)

    def shift_f(x, d):
        r = jnp.roll(x, d, axis=-1)
        return jnp.where(iota >= d, r, 0)

    # inclusive segmented running MIN of v (resets at heads)
    m = v
    f = h
    d = 1
    while d < TB:
        m = jnp.minimum(m, jnp.where(f > 0, BIG, shift(m, d)))
        f = jnp.maximum(f, shift_f(f, d))
        d *= 2

    c = carry[0:V, 0:1]
    res = jnp.minimum(m, jnp.where(f > 0, BIG, c))
    out_ref[:, :] = res
    carry[0:V, 0:1] = res[:, TB - 1 : TB]


def seg_incl_min_pl(head: jax.Array, values: jax.Array, fill: float) -> jax.Array:
    """Within-segment inclusive running minimum — the pallas form of
    segment.block_min_inclusive.  f32 min is order-free → bit-exact vs
    the associative-scan path.

    CALLER CONTRACT: heads must include segment.BLOCK-aligned synthetic
    boundaries (heads_from_keys produces them).  The pallas kernel is a
    true segmented min (cross-tile carry) and would ALSO handle longer
    runs, but the SENTINEL_NO_PALLAS fallback is block_min_inclusive,
    which resets at every BLOCK boundary regardless of heads — the two
    paths agree only under the block-capped contract."""
    from sentinel_tpu.ops import segment as SG

    if not FU.available():
        return SG.block_min_inclusive(head, values, fill)

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = values.shape[0]
    v = values.astype(jnp.float32)[None, :]
    pad = (-n) % TB
    if pad:
        v = jnp.concatenate([v, jnp.full((1, pad), fill, jnp.float32)], axis=1)
        head = jnp.concatenate([head, jnp.ones((pad,), bool)])
    Np = v.shape[1]

    out = FU.run_pallas(pl.pallas_call(
        _kernel_min,
        grid=(Np // TB,),
        in_specs=[
            pl.BlockSpec((1, TB), lambda t: (0, t), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, TB), lambda t: (0, t), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, TB), lambda t: (0, t), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=FU.interpret_mode(),
        name="seg_incl_min",
    ), head.astype(jnp.int32)[None, :], v,
        key=("seg_incl_min", Np))
    # sentinel BIG never leaks: every segment has >= 1 item, and heads
    # reset the min to that item's value; fill only pads
    return out[0, :n]


def seg_excl_cumsum_wide_pl(head: jax.Array, values: jax.Array) -> jax.Array:
    """segment.seg_excl_cumsum_wide on the Pallas path: values <= 2^24
    (pacing costs) whose batch TOTAL may overflow int32.

    Exactly the original's scheme — two 12-bit digit lanes through the
    integer scan (per-lane totals <= 4095 * 2^23 < 2^31, int32-safe),
    recombined in f32 AFTER the exact integer differences — so results
    are bit-identical to segment.seg_excl_cumsum_wide.  (A first cut cast
    one int32 scan to f32 and WRAPPED once a segment's total crossed
    2^31 — caught on hardware by review; the rate-limiter rank path
    feeds exactly such totals on slow-pace rules over large batches.)"""
    from sentinel_tpu.ops import segment as SG

    if not FU.available():
        return SG.seg_excl_cumsum_wide(head, values)
    v = values.astype(jnp.int32)
    r = seg_excl_cumsum_pl(head, jnp.stack([v & 0xFFF, v >> 12]))
    return r[1].astype(jnp.float32) * 4096.0 + r[0].astype(jnp.float32)
