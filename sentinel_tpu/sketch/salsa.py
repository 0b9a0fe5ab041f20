"""SALSA-style self-adjusting windowed count-min sketch (TPU-batched).

The seed tail sketch (ops/gsketch.py) spends one int32 per
(bucket, depth, column, plane) cell and sums all ``sample_count`` buckets
on every windowed read.  At minute windows over 1 M+ resources that is
the whole HBM bill, so this module replaces both sides:

STORAGE — self-adjusting counters (arXiv 2102.12531, "SALSA"): logical
columns start as int8 cells, FOUR packed into each int32 word.  When a
cell saturates its current width, the word's cells merge with their
neighbors (sums — the CMS overestimate direction) and the word re-packs
one level wider:

    level 0   4 x int8   (cell cap 255)        — the steady state
    level 1   2 x int16  (cell cap 65535)      — lanes {0,1} / {2,3} merge
    level 2   1 x int32  (clamped, see _cap2)  — all four lanes merge

A per-word 2-bit level rides a packed width bitmap (16 words per int32).
Light columns — almost all of them under Zipf traffic — stay at int8, so
the per-bucket plane costs W bytes instead of 4W: width x depth stretches
~4x at the same HBM and error target.  Merging only ever widens a
counter's coverage, so estimates stay upper bounds (min-over-depth CMS
semantics intact; heavy neighborhoods degrade toward width/4, the
documented SALSA trade).

The CURRENT bucket is the exception: it accumulates UNPACKED in ``cur``
(one int32 plane set, ~4W bytes) so the per-tick write is a plain
clamped vector add — no packed-word decode/escalate arithmetic, and no
functional update of the O(nbp * W) ring tensors, which would copy tens
of MB per tick on backends without buffer donation.  The SALSA packing
runs ONCE per bucket, when refresh lands the finished ``cur`` into its
ring column (amortized ~window_ms per pack instead of per tick).
Intra-bucket estimates read exact values; the merge overestimate enters
only at landing — strictly tighter than packing eagerly.

READS — O(1) windowed sums (arXiv 1604.02450): ``run`` holds the decoded
window total per logical column, maintained INCREMENTALLY — adds land
their decoded delta, and expired buckets subtract their decoded contents
exactly once, at a batched rotation.  Reads gather ``run`` directly; no
per-read sum over sample_count buckets, and the estimate cost is
independent of the window shape.

ROTATION — batched expiry under slack (arXiv 1703.01166 +
2305.16513-style vectorized kernel): every ``slack_buckets`` buckets (1
when ``cfg.slack_frac`` is 0), ONE masked decode-and-subtract pass
expires every out-of-window bucket from ``run`` at once, inside a
lax.cond whose outputs are only the O(depth·P·W) running sums + epochs —
the big packed-word tensors never cross the cond, so steady-state ticks
inside a bucket pay a scalar predicate, not a decode.  Expired columns
are stamped ``window.PURGED`` (subtract-once) and their storage is zeroed
lazily when the write cursor next lands on them; the ring carries
``slack_buckets - 1`` extra physical columns so the cursor only reaches
already-purged columns.  Under slack, expired-but-unpurged buckets remain
counted for at most ``slack_buckets - 1`` bucket lengths — a bounded
OVERESTIMATE, the enforcement-safe direction.

Lazy expiry (documented transient): after an idle gap longer than the
window interval, buckets that expired WITHOUT a rotation running still
sit in ``run`` until the next write triggers one.  Until then estimates
OVERESTIMATE by at most one pre-gap window volume — the conservative
direction for enforcement (blocks fire early, never late).
``sweep_expired`` purges them eagerly for callers that care (tests,
post-idle maintenance).

Every estimate here is >= the true windowed count: CMS collision, SALSA
merge, slack, and lazy expiry all err upward.  Tail-rule enforcement
built on it therefore fails CLOSED (tests/test_salsa.py pins the
invariant).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from sentinel_tpu.obs import profile as PROF
from sentinel_tpu.ops import window as W
from sentinel_tpu.ops.gsketch import (
    PLANES,
    RT_PLANE,
    RT_SCALE,
    SketchConfig,
    _wid,
)
from sentinel_tpu.ops.param import cms_cell

#: words per packed int32 of the width bitmap (2 bits per word level)
_BMP = 16


def _cap2(cfg: SketchConfig) -> int:
    """Level-2 cell clamp, sized so the OVERFLOW-FREE invariant holds by
    construction: ``run`` sums at most phys_buckets decoded buckets, each
    cell <= cap2, so run <= phys_buckets * cap2 <= int32 max — the
    running sums can never wrap negative and silently invert the
    fail-closed bias to fail-open for the heaviest cell.  At the minute
    window (nb=60) this still allows ~33 M token-weighted events per
    cell per SECOND-long bucket, far past the device's total peak."""
    return ((1 << 31) - 1) // max(cfg.phys_buckets, 2)


class SalsaState(NamedTuple):
    words: jax.Array  # int32 [nbp, depth, PLANES, Wp]  packed counter words
    lvlmap: jax.Array  # int32 [nbp, depth, PLANES, Wp // 16]  2-bit width bitmap
    run: jax.Array  # int32 [depth, PLANES, W]  O(1) running window sums
    epochs: jax.Array  # int32 [nbp]  window-id per bucket column
    rot_wid: jax.Array  # int32 []  wid of the last batched expiry
    cur: jax.Array  # int32 [depth, PLANES, W]  UNPACKED current bucket
    cur_wid: jax.Array  # int32 []  wid the cur buffer belongs to


def _wp(cfg: SketchConfig) -> int:
    if cfg.width % (4 * _BMP):
        raise ValueError(
            f"salsa sketch width must be a multiple of {4 * _BMP} "
            f"(4 int8 lanes/word, {_BMP} words/bitmap-int32); got {cfg.width}"
        )
    return cfg.width // 4


def init_sketch(cfg: SketchConfig) -> SalsaState:
    wp = _wp(cfg)
    nbp = cfg.phys_buckets
    state = SalsaState(
        words=jnp.zeros((nbp, cfg.depth, PLANES, wp), jnp.int32),
        lvlmap=jnp.zeros((nbp, cfg.depth, PLANES, wp // _BMP), jnp.int32),
        run=jnp.zeros((cfg.depth, PLANES, cfg.width), jnp.int32),
        epochs=jnp.full((nbp,), -(cfg.sample_count + 1), jnp.int32),
        rot_wid=jnp.int32(-(cfg.sample_count + 1)),
        cur=jnp.zeros((cfg.depth, PLANES, cfg.width), jnp.int32),
        cur_wid=jnp.int32(-(cfg.sample_count + 1)),
    )
    # memory ledger (obs/profile.py): the measured live counterpart of
    # the static hbm_bytes(cfg) claim — the two must agree within 10%
    PROF.LEDGER.track("sketch", "salsa.init_sketch", state)
    return state


def _index_of(wid, cfg: SketchConfig):
    """Ring column of a window id (same modular view as gsketch._index)."""
    return (
        jnp.asarray(wid).astype(jnp.uint32) % jnp.uint32(cfg.phys_buckets)
    ).astype(jnp.int32)


# -- width bitmap ------------------------------------------------------------


def pack_levels(lvl: jax.Array) -> jax.Array:
    """int32 levels [..., Wp] in {0,1,2} -> packed bitmap [..., Wp//16]
    (2-bit fields, word k at bits [2k, 2k+2))."""
    g = lvl.reshape(lvl.shape[:-1] + (-1, _BMP)).astype(jnp.int32)
    out = jnp.zeros(g.shape[:-1], jnp.int32)
    for k in range(_BMP):
        out = out | (g[..., k] << (2 * k))
    return out


def unpack_levels(packed: jax.Array, wp: int) -> jax.Array:
    """Packed bitmap [..., Wp//16] -> int32 levels [..., Wp]."""
    lanes = jnp.stack([(packed >> (2 * k)) & 3 for k in range(_BMP)], axis=-1)
    return lanes.reshape(packed.shape[:-1] + (wp,))


# -- packed-word arithmetic --------------------------------------------------


def _decode(words: jax.Array, lvl: jax.Array) -> jax.Array:
    """words/lvl int32 [..., Wp] -> logical column values int32 [..., 4*Wp].

    Merged cells report the SHARED counter for every logical column they
    cover — the decoded value is an upper bound per column by
    construction (width-bitmap round-trip pinned by tests)."""
    b0 = jnp.stack([(words >> (8 * k)) & 0xFF for k in range(4)], axis=-1)
    h = jnp.stack([(words >> (16 * k)) & 0xFFFF for k in range(2)], axis=-1)
    b1 = jnp.repeat(h, 2, axis=-1)  # lanes {0,1} <- half0, {2,3} <- half1
    b2 = jnp.broadcast_to(words[..., None], words.shape + (4,))
    lv = lvl[..., None]
    out = jnp.where(lv == 0, b0, jnp.where(lv == 1, b1, b2))
    return out.reshape(out.shape[:-2] + (out.shape[-2] * 4,))


def _land_words(words: jax.Array, lvl: jax.Array, upd: jax.Array, cap2: int):
    """Add logical deltas ``upd`` [..., W] (>= 0) into packed words
    [..., Wp], escalating word levels on saturation (the self-adjusting
    merge).  Returns (words', lvl', decoded_before, decoded_after) — the
    decoded pair is what the caller folds into the running window sum.
    ``cap2`` bounds level-2 cells so run never overflows (_cap2)."""
    u = upd.reshape(upd.shape[:-1] + (-1, 4))  # [..., Wp, 4]
    dec_before = _decode(words, lvl)
    # stored sums at each coarser granularity, from the STORED
    # representation (an expanded decode would double-count merged cells)
    l0 = jnp.stack([(words >> (8 * k)) & 0xFF for k in range(4)], axis=-1)
    l1 = jnp.stack([(words >> (16 * k)) & 0xFFFF for k in range(2)], axis=-1)
    s1 = jnp.where(
        lvl[..., None] == 0, l0[..., 0::2] + l0[..., 1::2], l1
    )  # [..., Wp, 2]
    s2 = jnp.where(
        lvl == 0, jnp.sum(l0, axis=-1), jnp.where(lvl == 1, jnp.sum(l1, axis=-1), words)
    )
    u1 = u[..., 0::2] + u[..., 1::2]
    u2 = jnp.sum(u, axis=-1)
    t0 = l0 + u  # candidate int8 lanes (meaningful only at level 0)
    t1 = s1 + u1
    t2 = jnp.minimum(s2 + u2, cap2)
    fit0 = (lvl == 0) & jnp.all(t0 <= 255, axis=-1)
    fit1 = ~fit0 & (lvl <= 1) & jnp.all(t1 <= 65535, axis=-1)
    new_lvl = jnp.where(fit0, 0, jnp.where(fit1, 1, 2))
    w0 = t0[..., 0] | (t0[..., 1] << 8) | (t0[..., 2] << 16) | (t0[..., 3] << 24)
    w1 = t1[..., 0] | (t1[..., 1] << 16)
    new_words = jnp.where(new_lvl == 0, w0, jnp.where(new_lvl == 1, w1, t2))
    da = jnp.where(
        new_lvl[..., None] == 0,
        t0,
        jnp.where(new_lvl[..., None] == 1, jnp.repeat(t1, 2, axis=-1), t2[..., None]),
    )
    dec_after = da.reshape(dec_before.shape)
    return new_words, new_lvl, dec_before, dec_after


# -- window maintenance ------------------------------------------------------


def refresh(state: SalsaState, now_ms, cfg: SketchConfig) -> SalsaState:
    """Rotate: batched expiry of the running sums + landing of the
    finished bucket into the packed ring.

    The current bucket lives UNPACKED in ``cur`` (adds are a plain
    vector add — no packed-word arithmetic, no touch of the big ring
    tensors), so the per-tick steady state here is two scalar predicates
    and one single-column write-back of unchanged values.  When the
    bucket id advances, ``cur`` is packed ONCE (the SALSA escalation,
    amortized from every tick to every bucket) and landed into its ring
    column; ``run`` absorbs the encode delta (decode >= exact per cell —
    the merge overestimate enters only at landing, never mid-bucket).

    The expiry (decode every column once, subtract all expired buckets
    from ``run`` in one masked pass — the 1604.02450 subtract-expired
    step, vectorized over the whole ring) runs under lax.cond, gated on
    the bucket id advancing ``slack_buckets`` past the last expiry or the
    landing cursor reaching a column whose contents are still in ``run``
    (the safety net that makes leaks impossible even across the 2^32
    engine-clock horizon).  Only ``run`` + ``epochs`` + ``rot_wid`` cross
    that cond, and only column-sized tensors cross the landing cond — the
    big packed ring tensors cross neither (an identity branch would copy
    them every tick).  Expired columns are stamped ``window.PURGED`` so
    they subtract exactly once; landing OVERWRITES its (always purged)
    target column, which retires the seed's per-tick lazy zeroing."""
    wp = _wp(cfg)
    nb = cfg.sample_count
    nbp = cfg.phys_buckets
    g = cfg.slack_buckets
    wid = _wid(now_ms, cfg)
    land = state.cur_wid != wid
    land_idx = _index_of(state.cur_wid, cfg)
    tgt_epoch = state.epochs[land_idx]
    due = (wid - state.rot_wid >= g) | (land & (tgt_epoch != W.PURGED))
    land_onehot = jax.lax.broadcasted_iota(jnp.int32, (nbp,), 0) == land_idx

    def _expire(run, epochs):
        age = wid - epochs
        live = (age >= 0) & (age < nb) & (epochs != W.PURGED)
        doomed = (~live | (land_onehot & land)) & (epochs != W.PURGED)
        lvl = unpack_levels(state.lvlmap, wp)
        dec = _decode(state.words, lvl)  # [nbp, depth, P, W]
        gone = jnp.sum(dec * doomed.astype(jnp.int32)[:, None, None, None], axis=0)
        return run - gone, jnp.where(doomed, W.PURGED, epochs), wid

    def _skip(run, epochs):
        return run, epochs, state.rot_wid

    run, epochs, rot_wid = jax.lax.cond(
        due, _expire, _skip, state.run, state.epochs
    )

    col_w = state.words[land_idx]
    col_l = state.lvlmap[land_idx]

    def _land(run, epochs, cur):
        # pack the finished bucket into an empty column (the target is
        # purged by construction — the expiry cond above guarantees it)
        nw, nl, _, dec_a = _land_words(
            jnp.zeros_like(col_w),
            jnp.zeros((cfg.depth, PLANES, wp), jnp.int32),
            cur,
            _cap2(cfg),
        )
        return (
            nw,
            pack_levels(nl),
            run + (dec_a - cur),
            epochs.at[land_idx].set(state.cur_wid),
            jnp.zeros_like(cur),
        )

    def _stay(run, epochs, cur):
        return col_w, col_l, run, epochs, cur

    ncw, ncl, run, epochs, cur = jax.lax.cond(
        land, _land, _stay, run, epochs, state.cur
    )
    return SalsaState(
        words=state.words.at[land_idx].set(ncw),
        lvlmap=state.lvlmap.at[land_idx].set(ncl),
        run=run,
        epochs=epochs,
        rot_wid=jnp.asarray(rot_wid, jnp.int32),
        cur=cur,
        cur_wid=jnp.asarray(wid, jnp.int32),
    )


def sweep_expired(state: SalsaState, now_ms, cfg: SketchConfig) -> SalsaState:
    """Eagerly purge EVERY expired bucket from the running sums and zero
    their storage.  O(nbp * W) — the cost refresh amortizes over
    slack_buckets; callers use it after known idle gaps or in tests to
    collapse the lazy-expiry overestimate immediately."""
    wp = _wp(cfg)
    wid = _wid(now_ms, cfg)
    age = wid - state.epochs
    live = (age >= 0) & (age < cfg.sample_count) & (state.epochs != W.PURGED)
    # PURGED columns already left run — zero their storage, subtract nothing
    doomed = ~live & (state.epochs != W.PURGED)
    lvl = unpack_levels(state.lvlmap, wp)
    dec = _decode(state.words, lvl)  # [nbp, depth, P, W]
    gone = jnp.sum(dec * doomed.astype(jnp.int32)[:, None, None, None], axis=0)
    keep = live.astype(jnp.int32)[:, None, None, None]
    # the unpacked current bucket expires with its wid like any column
    cage = wid - state.cur_wid
    cur_live = (cage >= 0) & (cage < cfg.sample_count)
    ckeep = cur_live.astype(jnp.int32)
    return SalsaState(
        words=state.words * keep,
        lvlmap=state.lvlmap * keep,
        run=state.run - gone - (1 - ckeep) * state.cur,
        epochs=jnp.where(live, state.epochs, W.PURGED),
        rot_wid=jnp.asarray(wid, jnp.int32),
        cur=state.cur * ckeep,
        cur_wid=jnp.where(cur_live, state.cur_wid, wid).astype(jnp.int32),
    )


# -- writes ------------------------------------------------------------------


@jax.named_scope("stage.sketch")
def add_dense(
    state: SalsaState,
    now_ms,
    upd: jax.Array,  # int32 [depth, width, len(plane_idx)] logical histogram
    plane_idx: Tuple[int, ...],
    cfg: SketchConfig,
    pre_refreshed: bool = False,
) -> SalsaState:
    """Land a precomputed logical-width histogram into the current bucket
    accumulator — a plain clamped vector add on the UNPACKED ``cur``
    buffer, mirrored into the running window sums.  The packed-word
    escalation happens once per bucket, at refresh's landing step, not
    here.  ``pre_refreshed``: see ops/gsketch.add."""
    if not pre_refreshed:
        state = refresh(state, now_ms, cfg)
    # scatter the touched planes into a full-plane update: untouched
    # planes land zeros — simpler than plane-sliced advanced indexing
    u_full = jnp.zeros((cfg.depth, PLANES, cfg.width), jnp.int32)
    u_full = u_full.at[:, jnp.asarray(plane_idx), :].set(
        jnp.swapaxes(upd, 1, 2).astype(jnp.int32)
    )
    # cap2 clamp per cell keeps the bucket's run contribution bounded, so
    # the _cap2 overflow-free invariant holds exactly as it did when the
    # clamp sat in the per-tick packed landing
    new_cur = jnp.minimum(state.cur + u_full, _cap2(cfg))
    return state._replace(
        cur=new_cur,
        run=state.run + (new_cur - state.cur),
    )


@jax.named_scope("stage.sketch")
def add(
    state: SalsaState,
    now_ms,
    res: jax.Array,  # int32 [N] resource ids (any id space; OOB-safe)
    values: jax.Array,  # int32 [N, len(plane_idx)]
    plane_idx: Tuple[int, ...],
    valid: jax.Array,  # bool [N]
    cfg: SketchConfig,
    max_int: int = 65535,
    pre_refreshed: bool = False,
    ecfg=None,  # EngineConfig — tables.py backend dispatch (None = native)
) -> SalsaState:
    """Batched event ingest: ONE flat histogram at LOGICAL width across
    all depths (ops/tables.depth_histogram — native scatter on CPU, a
    single digit-plane MXU contraction on TPU; the packed storage only
    changes how the histogram lands, not how it is built)."""
    from sentinel_tpu.ops import tables as T

    if not pre_refreshed:
        state = refresh(state, now_ms, cfg)
    cols = cms_cell(res, cfg.depth, cfg.width)  # [N, depth]
    upd = T.depth_histogram(
        ecfg, cols, values.astype(jnp.int32), valid, cfg.depth, cfg.width,
        max_int=max_int,
    )  # [depth, width, len(plane_idx)]
    return add_dense(state, now_ms, upd, plane_idx, cfg, pre_refreshed=True)


# -- reads -------------------------------------------------------------------


@jax.named_scope("stage.sketch")
def estimate_plane_mxu(
    ecfg,  # EngineConfig — tables.py dispatch
    state: SalsaState,
    now_ms,
    res: jax.Array,  # int32 [N]
    plane: int,
    cfg: SketchConfig,
) -> jax.Array:
    """f32 [N]: min-over-depth windowed estimate of ONE plane, read
    straight from the running sums — O(1) in the window shape, and ONE
    flat gather/contraction across all depths (tables.depth_gather_1col;
    the seed looped a lane gather per depth)."""
    from sentinel_tpu.ops import tables as T

    cols = cms_cell(res, cfg.depth, cfg.width)
    cap = jnp.int32((1 << 24) - 1)
    g = T.depth_gather_1col(
        ecfg,
        jnp.minimum(state.run[:, plane, :], cap),
        cols,
        cfg.width,
        max_int=(1 << 24) - 1,
    )  # [depth, N]
    return jnp.min(g, axis=0).astype(jnp.float32)


def estimate(
    state: SalsaState, now_ms, res: jax.Array, cfg: SketchConfig
) -> jax.Array:
    """int32 [N, PLANES]: min-over-depth windowed estimates per resource
    (host observability path — plain gathers from the running sums)."""
    cols = cms_cell(res, cfg.depth, cfg.width)  # [N, depth]
    per_depth = jnp.stack(
        [state.run[d, :, cols[:, d]] for d in range(cfg.depth)], axis=0
    )  # [depth, N, PLANES]
    return jnp.min(per_depth, axis=0)


# -- introspection -----------------------------------------------------------


def level_histogram(state: SalsaState, cfg: SketchConfig) -> jax.Array:
    """int32 [3]: how many counter words sit at each width level across
    the whole sketch — the saturation/merge telemetry the hot-set manager
    exports (``sentinel_sketch_merged_words``).  Effective width for the
    error bound degrades with merged share: eps ~ e / (W * (n0 + n1/2 +
    n2/4) / (n0 + n1 + n2)).  The unpacked current bucket reports the
    levels it WILL land at (its ring column — stale until landing — is
    replaced by that virtual view)."""
    wp = _wp(cfg)
    lvl = unpack_levels(state.lvlmap, wp)
    u = state.cur.reshape(cfg.depth, PLANES, wp, 4)
    u1 = u[..., 0::2] + u[..., 1::2]
    fit0 = jnp.all(u <= 255, axis=-1)
    fit1 = ~fit0 & jnp.all(u1 <= 65535, axis=-1)
    vlvl = jnp.where(fit0, 0, jnp.where(fit1, 1, 2)).astype(jnp.int32)
    lvl = lvl.at[_index_of(state.cur_wid, cfg)].set(vlvl)
    return jnp.stack([jnp.sum(lvl == k) for k in range(3)]).astype(jnp.int32)


def hbm_bytes(cfg: SketchConfig) -> int:
    """Persistent HBM bytes of a SalsaState at this config (words + bitmap
    + running sums + unpacked current bucket + epochs + watermarks) — the
    BENCH sketch_tier row's storage number."""
    wp = cfg.width // 4
    nbp, d = cfg.phys_buckets, cfg.depth
    return 4 * (
        nbp * d * PLANES * wp  # words
        + nbp * d * PLANES * (wp // _BMP)  # width bitmap
        + d * PLANES * cfg.width  # running sums
        + d * PLANES * cfg.width  # unpacked current bucket
        + nbp  # epochs
        + 2  # rot_wid + cur_wid
    )
