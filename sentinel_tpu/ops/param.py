"""Hot-parameter statistics: hashed (rule, value) rows on a global window.

The reference tracks per-parameter-value token buckets in LRU CacheMaps
capped at 4000×duration / 200k keys per rule (ParameterMetric.java:35-118).
That design — pointer-chasing hash maps with per-key CAS — cannot batch.

v1 here kept one small CMS *per rule* with per-rule time buckets; reading
it required a per-item advanced-indexing gather that XLA serializes
(~21 ms/tick at B=128K, measured).  v2 inverts the layout so every op is a
dense contraction:

    pcms   : int32 [depth, Q, nb]   windowed counts; row = hash_d(rule, value)
    epochs : int32 [nb]             ONE global bucket grid (param_bucket_ms)
    pconc  : int32 [depth, Q]       per-(rule,value) concurrency (THREAD grade)

A WIDE store (``wide(cfg)``: more than PARAM_NARROW_WIDTH cells a depth, e.g.
the 2^22 an API gateway's million (route, client) pairs take) keeps every
plane's cell axis as (Q / 128, 128) TILES:

    pcms   : int32 [depth, nb, Q/128, 128]
    pconc  : int32 [depth, Q/128, 128]

The chip tiles an array's LAST TWO axes (8 sublanes x 128 lanes).  Laid out
[depth, Q, nb], every cell's 8 buckets pad to a lane tile and a column
update walks the whole table; laid out [depth, nb, Q], the 8 buckets are one
tile's 8 sublanes, so a bucket's row is a sublane of EVERY tile and its
refresh and landing each pass over the whole store (measured on the chip,
PERF.md section 6, PR 33: 1.1 ms each at 256 MiB).  With the cell axis split
a tile is 1,024 consecutive cells of ONE bucket, a bucket's row is
contiguous memory, and the two updates touch that row and nothing else.
Cell q lies at [q // 128, q % 128]: a row-major reshape, which is the form
ops/fused.scatter_sorted writes in ([planes, Q/128, 128]) and the estimate's
lane-packed gathers read at (WIDE_LANES), so no plane changes form between
the kernel and the store.  Reads stay per-item lane-packed gathers; writes
leave the one-hot kernels (rows x width multiply-adds a plane) for
scatter_sorted.

- All rules share the global bucket grid, so the current column is a single
  dense histogram target (ops/tables.py MXU path) and stale-column reset is
  the same epoch scheme as ops/window.py.
- A rule's window is its ``durationInSec`` expressed in buckets
  (win_k = duration*1000 / param_bucket_ms, capped at nb; longer durations
  clamp to the nb-bucket window with the threshold scaled to preserve the
  RATE — divergence documented in compile_param_rules).
- Distinct win_k values are grouped into ≤ param_classes "duration
  classes"; the windowed table per class is a masked sum over recent
  buckets (elementwise), and an item reads its rule's class plane.
- Estimates take min over depth rows — classic CMS: collisions only
  overestimate, so enforcement over-blocks with probability bounded by
  eps = e/Q per depth, delta = e^-depth (the conservative direction for a
  limiter).  THREAD concurrency uses the same row structure.

Reference: ParamFlowChecker.passLocalCheck:78-188 (QPS + THREAD dispatch),
ParamFlowSlot.java:60-75 (entry/exit thread count).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from sentinel_tpu.core.config import PARAM_NARROW_WIDTH, EngineConfig
from sentinel_tpu.ops import tables as T

# depth-row hash multipliers (odd constants, splitmix-ish)
_MULTS = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F, 0x165667B1, 0x9E3779B9)


def cms_cell(h: jax.Array, depth: int, width: int) -> jax.Array:
    """int32 [N, depth] — column index per depth row for hashes h [N]."""
    hu = h.astype(jnp.uint32)
    cols = []
    for d in range(depth):
        x = hu * jnp.uint32(_MULTS[d % len(_MULTS)]) + jnp.uint32(
            (d * 0x7F4A7C15) & 0xFFFFFFFF
        )
        x = x ^ (x >> 15)
        x = x * jnp.uint32(0x2C1B3C6D)
        x = x ^ (x >> 12)
        cols.append((x % jnp.uint32(width)).astype(jnp.int32))
    return jnp.stack(cols, axis=-1)


def pair_rows(slots: jax.Array, hashes: jax.Array, depth: int, width: int) -> jax.Array:
    """int32 [N, depth] — pcms row per depth for (rule slot, value hash).

    The slot is folded into the hash input so distinct rules' identical
    values land on independent rows."""
    mixed = hashes.astype(jnp.uint32) * jnp.uint32(0x01000193) ^ (
        slots.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
    )
    return cms_cell(mixed.astype(jnp.int32), depth, width)


#: lanes of a wide store's row gathers (ops/tables.lane_gather_1col)
WIDE_LANES = 128


def wide(cfg: EngineConfig) -> bool:
    """True for a store kept in tiles (see the module docstring)."""
    return cfg.param_width > PARAM_NARROW_WIDTH


def plane_shape(cfg: EngineConfig) -> Tuple[int, ...]:
    """One depth's cell axis: (Q,), or the (Q / 128, 128) tiles of a wide
    store (EngineConfig holds a wide width to a multiple of 2^14)."""
    if wide(cfg):
        return (cfg.param_width // WIDE_LANES, WIDE_LANES)
    return (cfg.param_width,)


def store_shape(cfg: EngineConfig) -> Tuple[int, ...]:
    if wide(cfg):
        return (cfg.param_depth, cfg.param_sample_count) + plane_shape(cfg)
    return (cfg.param_depth, cfg.param_width, cfg.param_sample_count)


def conc_shape(cfg: EngineConfig) -> Tuple[int, ...]:
    return (cfg.param_depth,) + plane_shape(cfg)


def _wid(now_ms, cfg: EngineConfig):
    return (now_ms // cfg.param_bucket_ms).astype(jnp.int32)


def refresh(
    pcms: jax.Array, epochs: jax.Array, now_ms, cfg: EngineConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Zero the global current bucket if stale; returns (pcms, epochs, idx).

    Masked column update, not lax.cond — a cond's identity branch copies
    the whole pcms tensor every tick (see ops/window.refresh)."""
    nb = cfg.param_sample_count
    wid = _wid(now_ms, cfg)
    idx = wid % nb
    keep = (epochs[idx] == wid).astype(pcms.dtype)
    if wide(cfg):
        return pcms.at[:, idx].multiply(keep), epochs.at[idx].set(wid), idx
    return pcms.at[:, :, idx].multiply(keep), epochs.at[idx].set(wid), idx


def class_tables(
    pcms: jax.Array,  # [depth, Q, nb] — already refreshed
    epochs: jax.Array,  # [nb]
    class_k: jax.Array,  # int32 [C] — window length in buckets per class
    now_ms,
    cfg: EngineConfig,
) -> jax.Array:
    """f32 [depth, Q, C] ([depth, C, Q/128, 128] of a wide store): windowed
    totals per duration class.

    Class c sums buckets whose epoch lies in (wid - k_c, wid] — the k_c
    most recent grid positions (masked elementwise; stale columns excluded
    by their epoch, identical to ops/window.py validity)."""
    wid = _wid(now_ms, cfg)
    # [C, nb] validity masks
    valid = (epochs[None, :] > wid - class_k[:, None]) & (epochs[None, :] <= wid)
    return jnp.einsum(
        "dbrl,cb->dcrl" if wide(cfg) else "dqb,cb->dqc",
        pcms.astype(jnp.float32),
        valid.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


def estimate(
    cfg: EngineConfig,
    wtab: jax.Array,  # [depth, Q, C] from class_tables
    rows: jax.Array,  # [N, depth] from pair_rows
    cls: jax.Array,  # int32 [N] — rule's duration class per item
) -> jax.Array:
    """f32 [N] — windowed CMS estimate (min over depth) for each item."""
    if wide(cfg):
        return _estimate_wide(cfg, wtab, rows, cls)
    C = wtab.shape[2]
    # class selection as a tiny one-hot contraction — take_along_axis lowers
    # to a serialized per-item gather on TPU
    cls_oh = (
        jnp.clip(cls, 0, C - 1)[:, None]
        == jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    ).astype(jnp.float32)
    ests = []
    cap_i = 256**cfg.param_est_digits - 1
    cap = jnp.int32(cap_i)
    for d in range(wtab.shape[0]):
        # saturate at the configured digit bound before the digit-plane
        # gather: values beyond would WRAP (dropping high bits) and flip
        # the CMS overestimate into an underestimate; saturation keeps
        # enforcement conservative for any threshold below the cap
        # (thresholds above it cannot trip — cfg.param_est_digits)
        g = T.big_gather(
            cfg,
            jnp.minimum(wtab[d].astype(jnp.int32), cap),
            rows[:, d],
            cfg.param_width,
            max_int=cap_i,
        )  # [N, C]
        ests.append(jnp.sum(g.astype(jnp.float32) * cls_oh, axis=1))
    return jnp.min(jnp.stack(ests, axis=0), axis=0).astype(jnp.float32)


def estimate_fused(
    cfg: EngineConfig,
    wtab: jax.Array,  # [depth, Q, C] from class_tables
    rows: jax.Array,  # [N, depth] from pair_rows
    cls: jax.Array,  # int32 [N]
) -> jax.Array:
    """estimate() via LANE-PACKED native row gathers.

    A 1-column gather from a [Q] plane is pathological on TPU (~0.9 ms at
    B=128K — and simply padding the table is undone by the compiler, which
    narrows the gather to the columns actually read).  Reshaping the flat
    (row, class) plane to [QC/8, 8] and selecting the lane with a
    DATA-DEPENDENT one-hot keeps every row read 8 lanes wide: the lane is
    unknown at compile time, so the gather cannot be narrowed.  Replaces
    the pallas one-hot digit kernel (~1.3 ms at B=128K).  Saturation at
    256**param_est_digits - 1 and min-over-depth are bit-identical to
    estimate(), so every cross-path equivalence suite holds unchanged."""
    if wide(cfg):
        return _estimate_wide(cfg, wtab, rows, cls)
    depth, Q, C = wtab.shape
    cap = jnp.int32(256**cfg.param_est_digits - 1)
    idx = jnp.clip(rows, 0, Q - 1) * C + jnp.clip(cls, 0, C - 1)[:, None]
    ests = []
    for d in range(depth):
        flat = jnp.minimum(wtab[d].reshape(-1).astype(jnp.int32), cap)
        ests.append(T.lane_gather_1col(cfg, flat, idx[:, d], Q * C))
    return jnp.min(jnp.stack(ests, axis=0), axis=0).astype(jnp.float32)


def _estimate_wide(cfg: EngineConfig, wtab: jax.Array, rows: jax.Array, cls: jax.Array):
    """estimate() of a wide store, whose class tables are [depth, C, Q/128,
    128]: one lane-packed row gather a depth, whatever the backend, at the
    tiles' own width, so the view it reads is the table itself.  Nothing is
    saturated: no digit plane carries the value, and a windowed total stays
    f32-exact far past any threshold."""
    depth, C = wtab.shape[:2]
    Q = cfg.param_width
    idx = jnp.clip(cls, 0, C - 1)[:, None] * Q + jnp.clip(rows, 0, Q - 1)
    ests = [
        T.lane_gather_1col(cfg, wtab[d].reshape(-1), idx[:, d], C * Q, lanes=WIDE_LANES)
        for d in range(depth)
    ]
    return jnp.min(jnp.stack(ests, axis=0), axis=0)


def conc_estimate(
    cfg: EngineConfig, pconc: jax.Array, rows: jax.Array
) -> jax.Array:
    """f32 [N] — current concurrency estimate (min over depth)."""
    if wide(cfg):
        # the one-hot gather below is rows x width multiply-adds a digit
        ests = [
            T.lane_gather_1col(
                cfg, pconc[d].reshape(-1), rows[:, d], cfg.param_width, lanes=WIDE_LANES
            )
            for d in range(pconc.shape[0])
        ]
        return jnp.min(jnp.stack(ests, axis=0), axis=0)
    ests = []
    cap = jnp.int32((1 << 24) - 1)
    for d in range(pconc.shape[0]):
        g = T.big_gather(
            cfg,
            jnp.minimum(pconc[d], cap),
            rows[:, d],
            cfg.param_width,
            max_int=(1 << 24) - 1,
        )
        ests.append(g)
    return jnp.min(jnp.stack(ests, axis=0), axis=0).astype(jnp.float32)


def add(
    pcms: jax.Array,  # [depth, Q, nb] — refreshed this tick
    cur_idx,  # int32 — global current bucket
    rows: jax.Array,  # [N, depth]
    counts: jax.Array,  # int32 [N] (0 for no-op)
    cfg: EngineConfig,
) -> jax.Array:
    """Histogram admitted counts into every depth row of the current bucket."""
    hists = [
        T.histogram(cfg, rows[:, d], counts, cfg.param_width) for d in range(pcms.shape[0])
    ]
    upd = jnp.stack(hists).astype(pcms.dtype).reshape(conc_shape(cfg))
    return land(cfg, pcms, cur_idx, upd)


def land(cfg: EngineConfig, pcms: jax.Array, cur_idx, upd: jax.Array) -> jax.Array:
    """Add ``upd`` (``conc_shape(cfg)``: [depth, Q], in tiles for a wide
    store) to the current bucket of every depth."""
    if wide(cfg):
        return pcms.at[:, cur_idx].add(upd)
    return pcms.at[:, :, cur_idx].add(upd)


def conc_add(
    cfg: EngineConfig,
    pconc: jax.Array,  # conc_shape(cfg)
    rows: jax.Array,  # [N, depth]
    inc: jax.Array,  # int32 [N] nonnegative acquire counts (0 no-op)
    dec: jax.Array,  # int32 [N] nonnegative release counts (0 no-op)
) -> jax.Array:
    """Apply concurrency deltas; clamped at zero (releases may race ahead
    of their acquires across host restarts, like curThreadNum clamps).
    Increments and decrements ride separate nonnegative histograms — the
    MXU digit planes assume unsigned payloads."""
    for d in range(pconc.shape[0]):
        delta = jnp.stack([inc, dec], axis=1)
        hist = T.histogram(cfg, rows[:, d], delta, cfg.param_width, max_int=65535)
        net = (hist[:, 0] - hist[:, 1]).astype(pconc.dtype)
        pconc = pconc.at[d].add(net.reshape(plane_shape(cfg)))
    return jnp.maximum(pconc, 0)
