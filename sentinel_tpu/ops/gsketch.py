"""Global per-resource statistics sketch — observability beyond capacity.

The north star (SURVEY §0, BASELINE): serve MILLIONS of resources per chip.
Exact per-row windows cost one histogram plane of B×node_rows MACs per
tick, so the exact space is kept small (ruled + hot resources) and the
long tail of unruled resources is tracked in a windowed count-min sketch:

    gs_counts : int32 [nbp, depth, width, PLANES]
    gs_epochs : int32 [nbp]

Each tick scatter-adds every valid event (pass/block on acquire;
success/exception/rt on completion) into the current time bucket at the
resource's hashed column per depth — one flat MXU one-hot contraction
over depth×WIDTH (ops/tables.depth_histogram), so cost is
B×width×depth MACs, independent of how many resources exist.
Reads take min over depth of the windowed column sums: a classic CMS
overestimate with eps = e/width, delta = e^-depth — at width 64K and real
(Zipf) traffic the per-resource error is a fraction of a percent of total
volume.  The reference's analog is nothing: beyond 6,000 chains it stops
tracking entirely (Constants.java:37).  Time bucketing mirrors
ops/window.py's epoch scheme, including the unsigned-wid continuity at
the int32 engine-ms wrap and the slack-window bucket geometry (the extra
``slack_buckets - 1`` physical columns are allocated here too so this
exact-reference tier shares the salsa tier's cursor arithmetic; its
masked reads stay exact regardless — stale columns just fail the age
test).

Plane layout: [EV_PASS, EV_BLOCK, EV_EXCEPTION, EV_SUCCESS, EV_OCCUPIED,
RT_Q] — the window event enum plus quantized RT (1/8 ms units).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from sentinel_tpu.obs import profile as PROF
from sentinel_tpu.ops import window as W
from sentinel_tpu.ops.param import cms_cell

PLANES = W.NUM_EVENTS + 1  # + quantized RT sum
RT_PLANE = W.NUM_EVENTS
RT_SCALE = 8.0  # 1/8 ms resolution


class SketchConfig(NamedTuple):
    sample_count: int
    window_ms: int
    depth: int
    width: int
    # slack fraction (arXiv 1703.01166) — consumed by the salsa tier's
    # batched expiry; see ops/window.WindowConfig.slack_frac
    slack_frac: float = 0.0

    @property
    def interval_ms(self) -> int:
        return self.sample_count * self.window_ms

    @property
    def slack_buckets(self) -> int:
        """Buckets between batched expiries (g) — 1 means no slack."""
        if self.slack_frac <= 0.0:
            return 1
        return max(1, math.ceil(self.slack_frac * self.sample_count))

    @property
    def phys_buckets(self) -> int:
        """Physical ring columns (nb + g - 1): the slack margin that keeps
        the write cursor off columns the last batched expiry missed."""
        return self.sample_count + self.slack_buckets - 1


class SketchState(NamedTuple):
    counts: jax.Array  # int32 [nbp, depth, width, PLANES]
    epochs: jax.Array  # int32 [nbp]


def init_sketch(cfg: SketchConfig) -> SketchState:
    nbp = cfg.phys_buckets
    state = SketchState(
        counts=jnp.zeros((nbp, cfg.depth, cfg.width, PLANES), jnp.int32),
        epochs=jnp.full((nbp,), -(cfg.sample_count + 1), jnp.int32),
    )
    # memory ledger (obs/profile.py): seed CMS tier under the same
    # "sketch" pool the salsa tier reports to
    PROF.LEDGER.track("sketch", "gsketch.init_sketch", state)
    return state


def _wid(now_ms, cfg: SketchConfig):
    # unsigned engine-ms: the window id stays continuous across the int32
    # clock wrap at 2^31 (~24.8 days of 1 ms) — see ops/window._wid
    u = jnp.asarray(now_ms).astype(jnp.uint32)
    return (u // jnp.uint32(cfg.window_ms)).astype(jnp.int32)


def _index(now_ms, cfg: SketchConfig):
    u = jnp.asarray(now_ms).astype(jnp.uint32)
    return ((u // jnp.uint32(cfg.window_ms)) % jnp.uint32(cfg.phys_buckets)).astype(
        jnp.int32
    )


def _valid(epochs: jax.Array, wid, cfg: SketchConfig) -> jax.Array:
    """bool [nbp] — wraparound-safe modular window membership."""
    age = wid - epochs
    return (age >= 0) & (age < cfg.sample_count)


def refresh(state: SketchState, now_ms, cfg: SketchConfig) -> SketchState:
    # masked column update, not lax.cond — a cond's identity branch copies
    # the whole counts tensor every tick (see ops/window.refresh)
    wid = _wid(now_ms, cfg)
    idx = _index(now_ms, cfg)
    keep = (state.epochs[idx] == wid).astype(state.counts.dtype)
    return SketchState(
        counts=state.counts.at[idx].multiply(keep),
        epochs=state.epochs.at[idx].set(wid),
    )


@jax.named_scope("stage.sketch")
def add(
    state: SketchState,
    now_ms,
    res: jax.Array,  # int32 [N] resource ids (any id space; OOB-safe)
    values: jax.Array,  # int32 [N, len(plane_idx)] deltas for the named planes
    plane_idx: Tuple[int, ...],  # which PLANES columns these values land in
    valid: jax.Array,  # bool [N]
    cfg: SketchConfig,
    max_int: int = 65535,
    pre_refreshed: bool = False,
    ecfg=None,  # EngineConfig — tables.py backend dispatch (None = native)
) -> SketchState:
    """Only the named planes are contracted — the acquire path lands
    (pass, block), the completion path (success, exception, rt_q); paying
    for all PLANES on both would double the sketch's MAC bill.

    The histogram build dispatches through ops/tables.depth_histogram on
    ``ecfg``: native flat scatter on CPU/small configs, ONE flat
    digit-plane MXU contraction across all depths on TPU (the seed looped
    per-depth MXU contractions unconditionally — ~2.7 GMAC/tick of CPU
    matmuls at the 1M point).

    ``pre_refreshed``: the caller guarantees a sketch write with the SAME
    ``now_ms`` already ran this trace (the tick lands completions before
    acquire effects), so the current bucket's epoch is already stamped and
    the masked-multiply copy of the whole counts tensor in ``refresh`` can
    be skipped — the second write per tick becomes a pure column add."""
    from sentinel_tpu.ops import tables as T

    if not pre_refreshed:
        state = refresh(state, now_ms, cfg)
    idx = _index(now_ms, cfg)
    cols = cms_cell(res, cfg.depth, cfg.width)  # [N, depth]
    upd = T.depth_histogram(
        ecfg, cols, values.astype(jnp.int32), valid, cfg.depth, cfg.width,
        max_int=max_int,
    )  # [depth, width, len(plane_idx)]
    new_col = state.counts[idx].at[:, :, jnp.asarray(plane_idx)].add(upd)
    return state._replace(counts=state.counts.at[idx].set(new_col))


@jax.named_scope("stage.sketch")
def add_dense(
    state: SketchState,
    now_ms,
    upd: jax.Array,  # int32 [depth, width, len(plane_idx)] — precomputed histogram
    plane_idx: Tuple[int, ...],
    cfg: SketchConfig,
    pre_refreshed: bool = False,
) -> SketchState:
    """Land a precomputed per-cell delta (from the fused effects kernel,
    ops/fused.py) into the current bucket — the dense companion of add().
    ``pre_refreshed``: see add()."""
    if not pre_refreshed:
        state = refresh(state, now_ms, cfg)
    idx = _index(now_ms, cfg)
    new_col = state.counts[idx].at[:, :, jnp.asarray(plane_idx)].add(upd)
    return state._replace(counts=state.counts.at[idx].set(new_col))


@jax.named_scope("stage.sketch")
def estimate_plane_mxu(
    ecfg,  # EngineConfig — tables.py dispatch
    state: SketchState,
    now_ms,
    res: jax.Array,  # int32 [N]
    plane: int,
    cfg: SketchConfig,
) -> jax.Array:
    """f32 [N]: windowed min-over-depth estimate of ONE plane, through the
    MXU table layer (the dense-indexing ``estimate`` serializes on TPU —
    this is the decision-path variant used by tail-rule enforcement).
    All depths read in ONE flat contraction (tables.depth_gather_1col)."""
    from sentinel_tpu.ops import tables as T

    wid = _wid(now_ms, cfg)
    valid = _valid(state.epochs, wid, cfg)
    windowed = jnp.sum(
        state.counts[:, :, :, plane] * valid[:, None, None], axis=0
    )  # [depth, width]
    cols = cms_cell(res, cfg.depth, cfg.width)
    cap = jnp.int32((1 << 24) - 1)
    g = T.depth_gather_1col(
        ecfg, jnp.minimum(windowed, cap), cols, cfg.width, max_int=(1 << 24) - 1
    )  # [depth, N]
    return jnp.min(g, axis=0).astype(jnp.float32)


def estimate(
    state: SketchState, now_ms, res: jax.Array, cfg: SketchConfig
) -> jax.Array:
    """int32 [N, PLANES]: windowed min-over-depth estimates per resource."""
    wid = _wid(now_ms, cfg)
    valid = _valid(state.epochs, wid, cfg)
    windowed = jnp.sum(
        state.counts * valid[:, None, None, None], axis=0
    )  # [depth, width, PLANES]
    cols = cms_cell(res, cfg.depth, cfg.width)  # [N, depth]
    per_depth = jnp.stack(
        [windowed[d][cols[:, d]] for d in range(cfg.depth)], axis=0
    )  # [depth, N, PLANES]
    return jnp.min(per_depth, axis=0)
