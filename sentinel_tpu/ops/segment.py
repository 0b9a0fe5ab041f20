"""Sorted-batch segment machinery: the round-4 aggregation primitive.

The fused one-hot digit-dot kernels (ops/fused.py) stream the whole item
axis through the MXU for every destination table — cost LINEAR in batch
size with no amortization (the round-3 cost model).  Real traffic is
Zipf-skewed: a 128K-item tick touches ~12K distinct resources (9%), so
almost all of that streaming is redundant.

This module exploits a batch that arrives SORTED by a composite key
(resource id first): equal-key items form contiguous *segments*, and

  - per-table scatters contract SEGMENT SUMS over a short compacted axis
    (U entries) instead of per-item payloads over the full batch,
  - per-item table reads (rule fields, window totals) happen once per
    segment and expand back with ONE monotone gather,
  - within-tick FCFS ranks (ops/rank.py) become segmented prefix sums on
    the already-sorted order — no per-rank sort networks.

Sorting stably by key preserves arrival order within each segment, so
every rank/verdict is bit-identical to the unsorted engine (integer
digit-plane sums are order-independent; see tests/test_segment.py and
the engine equivalence suite).

Exactness scheme: segments are capped at BLOCK=256 items by synthetic
breaks at block boundaries, so a segment never spans two 256-item blocks.
Per-item payloads are split into base-256 digit planes (<= 255 each),
prefix-summed in int32 (exact: 255 * 2^23 < 2^31), and differenced at
segment ends; a digit-plane segment sum is <= 255*256 = 65280 and two
adjacent digit sums recombine to < 2^24 — inside the bf16 digit-dot
exactness envelope of ops/fused.py.

Reference map: this replaces the per-request LongAdder adds of
StatisticSlot.java:54-164 and the CAS ranking of
RateLimiterController.java:50-105 with sort + segmented scans — the
batched form of "group requests by resource, then admit in arrival
order".
"""

from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

import jax
import numpy as np
import jax.numpy as jnp

#: segments never span a BLOCK-item boundary (synthetic heads), capping
#: segment length so digit-plane sums stay exact (see module docstring)
BLOCK = 256

_INT_MIN = np.int32(-(2**31) + 1)  # numpy scalar, NOT jnp: a module-level device array becomes a hoisted jaxpr const (extra executable parameter) and this jaxlib's dispatch fastpath drops consts when sibling cfg-variant executables coexist.  Enforced structurally by the jaxpr analyzer's const-hoist pass (sentinel_tpu/analysis/jaxpr)
_INT_MAX = np.int32(2**31 - 1)  # numpy scalar, NOT jnp: same hazard class; see _INT_MIN above and analysis/jaxpr/passes/const_hoist.py


class SegCtx(NamedTuple):
    """Segment structure of one sorted batch (item axis N, capacity U)."""

    head: jax.Array  # bool [N] — first item of its segment
    sid: jax.Array  # int32 [N] — segment id, 0-based, nondecreasing
    n_seg: jax.Array  # int32 scalar — live segment count
    ok: jax.Array  # bool scalar — n_seg <= U (compacted outputs valid)
    seg_end: jax.Array  # int32 [U] — last item position per live segment
    live: jax.Array  # bool [U] — segment slot holds a live segment

    @property
    def U(self) -> int:
        return self.seg_end.shape[0]


def heads_from_keys(*cols: jax.Array) -> jax.Array:
    """Segment-start marks from sorted key columns + BLOCK boundaries."""
    n = cols[0].shape[0]
    change = jnp.zeros((n,), bool)
    for c in cols:
        change = change | jnp.concatenate(
            [jnp.ones((1,), bool), c[1:] != c[:-1]]
        )
    pos = jax.lax.broadcasted_iota(jnp.int32, (n,), 0)
    return change | (pos % BLOCK == 0)


def build(key_cols: Sequence[jax.Array], U: int, payloads: Sequence[jax.Array] = ()):
    """Segment structure for a batch sorted by ``key_cols`` (stably).

    One sort compacts segment-end positions into [U]; when the live
    segment count exceeds U, ``ok`` is False and the caller must take its
    uncompacted fallback (compacted outputs would drop segments).

    ``payloads``: per-item columns to compact THROUGH the sort — the
    returned [U] arrays hold each segment's value at its last item
    (exactly ``compact(ctx, p)`` but without the extra per-column [U]
    gathers, which cost ~0.11 ms each at B=128K).  Dead slots carry junk;
    mask with ctx.live.  Returns (ctx, compacted_payloads).
    """
    head = heads_from_keys(*key_cols)
    return build_from_head(head, U, payloads)


def build_from_head(head: jax.Array, U: int, payloads: Sequence[jax.Array] = ()):
    """build() for a precomputed head vector (see heads_from_keys)."""
    n = head.shape[0]
    sid = jnp.cumsum(head.astype(jnp.int32)) - 1
    n_seg = sid[-1] + 1
    ok = n_seg <= U
    tail = jnp.concatenate([head[1:], jnp.ones((1,), bool)])
    pos = jax.lax.broadcasted_iota(jnp.int32, (n,), 0)
    skey = jnp.where(tail & (sid < U), sid, _INT_MAX)
    out = jax.lax.sort(
        [skey, pos] + [p for p in payloads], num_keys=1, is_stable=False
    )
    skeys, spos = out[0], out[1]
    comp = list(out[2:])
    if U > n:  # short batches still produce [U]-shaped compacted outputs
        skeys = jnp.concatenate([skeys, jnp.full((U - n,), _INT_MAX, jnp.int32)])
        spos = jnp.concatenate([spos, jnp.zeros((U - n,), jnp.int32)])
        comp = [
            jnp.concatenate([c, jnp.zeros((U - n,), c.dtype)]) for c in comp
        ]
    seg_end = spos[:U]
    live = skeys[:U] != _INT_MAX
    ctx = SegCtx(
        head=head, sid=sid, n_seg=n_seg, ok=ok, seg_end=seg_end, live=live
    )
    return ctx, [c[:U] for c in comp]


def compact(ctx: SegCtx, arr: jax.Array, fill=0) -> jax.Array:
    """Per-segment value (constant within each segment): [N(,P)] -> [U(,P)].

    Reads each segment's LAST item; dead slots get ``fill``.
    """
    g = arr[ctx.seg_end]
    mask = ctx.live if g.ndim == 1 else ctx.live[:, None]
    return jnp.where(mask, g, fill)


def cum_cols(planes: Sequence[jax.Array], maxes: Sequence[int]):
    """Digit-split payload planes + exact int32 prefix sums.

    Returns (C_rows: list of [N] int32 inclusive cumsums, split: list of
    (plane_idx, weight)).  Planes wider than 255 are digit-split BEFORE
    the prefix sum so the int32 cumsum stays exact (item axis <= 2^23).
    Feed the C_rows through build()'s payload sort (or gather them at
    seg_end) and hand the per-segment values to sums_from_ce."""
    n = planes[0].shape[0]
    assert n <= (1 << 23), "item axis too long for exact int32 digit cumsum"
    split: list = []  # (plane_idx, weight)
    cols = []
    for p, (v, m) in enumerate(zip(planes, maxes)):
        v = v.astype(jnp.int32)
        if m <= 255:
            cols.append(v)
            split.append((p, 1))
        else:
            d = max(1, (int(m).bit_length() + 7) // 8)
            for k in range(d):
                cols.append((v >> (8 * k)) & 0xFF)
                split.append((p, 1 << (8 * k)))
    C = jnp.cumsum(jnp.stack(cols, axis=0), axis=1)  # [Pd, N]
    return [C[i] for i in range(C.shape[0])], split


def sums_from_ce(ctx: SegCtx, ce_cols: Sequence[jax.Array], split) -> list:
    """Per-segment sums from compacted cumsum columns (each [U] int32,
    the cumsum value at each segment's last item).

    Returns, per input plane, a list of (sums [U] int32, weight, digits):
    the plane's segment sum is sum(weight_k * sums_k), each sums_k < 2^24
    and scatter-able with ``digits`` base-256 digit planes (ops/fused.Job).
    """
    Ce = jnp.stack(ce_cols, axis=1)  # [U, Pd]
    prev = jnp.concatenate([jnp.zeros((1, Ce.shape[1]), jnp.int32), Ce[:-1]])
    sums_d = jnp.where(ctx.live[:, None], Ce - prev, 0)  # each <= 255*BLOCK

    n_planes = max(p for p, _ in split) + 1
    out: list = [[] for _ in range(n_planes)]
    j = 0
    while j < len(split):
        p, w = split[j]
        if (
            j + 1 < len(split)
            and split[j + 1][0] == p
            and split[j + 1][1] == w * 256
        ):
            s = sums_d[:, j] + sums_d[:, j + 1] * 256
            out[p].append((s, w, 3))
            j += 2
        else:
            out[p].append((sums_d[:, j], w, 2))
            j += 1
    return out


def seg_sums(
    ctx: SegCtx,
    planes: Sequence[jax.Array],  # each int32 [N], values in [0, maxes[p]]
    maxes: Sequence[int],
) -> list:
    """Exact per-segment sums of int32 payload planes (cum_cols +
    ONE packed row gather at seg_end + sums_from_ce).  Callers that know
    their planes before build() should carry the cum_cols through the
    build sort instead (cheaper)."""
    C_rows, split = cum_cols(planes, maxes)
    CT = jnp.stack(C_rows, axis=1)  # [N, Pd] — one packed row gather
    Ce = CT[ctx.seg_end]
    return sums_from_ce(ctx, [Ce[:, i] for i in range(Ce.shape[1])], split)


def _two_level_max(x: jax.Array) -> jax.Array:
    """Inclusive running max along the last axis via block scan + cross-
    block offsets (both lane-parallel associative scans)."""
    *lead, n = x.shape
    pad = (-n) % BLOCK
    if pad:
        x = jnp.concatenate(
            [x, jnp.full((*lead, pad), _INT_MIN, x.dtype)], axis=-1
        )
    nb = x.shape[-1] // BLOCK
    r = x.reshape(*lead, nb, BLOCK)
    within = jax.lax.associative_scan(jnp.maximum, r, axis=len(lead) + 1)
    blast = within[..., -1]
    cross = jax.lax.associative_scan(jnp.maximum, blast, axis=len(lead))
    cross_excl = jnp.concatenate(
        [jnp.full((*lead, 1), _INT_MIN, x.dtype), cross[..., :-1]], axis=-1
    )
    out = jnp.maximum(within, cross_excl[..., None]).reshape(*lead, -1)
    return out[..., :n]


def seg_excl_cumsum(head: jax.Array, values: jax.Array) -> jax.Array:
    """Segmented EXCLUSIVE prefix sums over sorted items, int32-exact.

    ``head`` marks segment starts (head[0] must be True); ``values`` is
    [V, N] (or [N]) nonnegative int32 with sum(values) < 2^31 per row.
    Item i receives the sum of earlier same-segment items — the batched
    arrival-order rank of ops/rank.py, without the sort (the batch IS the
    sorted order here).  Segments may span BLOCK boundaries (two-level
    scan); use this for node-run ranks where runs aren't block-capped.
    """
    squeeze = values.ndim == 1
    v = values[None, :] if squeeze else values
    v = v.astype(jnp.int32)
    C = jnp.cumsum(v, axis=1)
    E = C - v
    base = _two_level_max(jnp.where(head[None, :], E, _INT_MIN))
    out = E - base
    return out[0] if squeeze else out


def seg_excl_cumsum_wide(head: jax.Array, values: jax.Array) -> jax.Array:
    """seg_excl_cumsum for values whose batch total may overflow int32
    (e.g. rate-limiter pacing costs, <= 2^24 each): two 12-bit digit
    lanes, recombined as f32 AFTER the exact integer differences — one
    rounding instead of the accumulated rounding of an f32 prefix sum."""
    v = values.astype(jnp.int32)
    lo = v & 0xFFF
    hi = v >> 12
    r = seg_excl_cumsum(head, jnp.stack([lo, hi]))
    return r[1].astype(jnp.float32) * 4096.0 + r[0].astype(jnp.float32)


class _MinCarry(NamedTuple):
    m: jax.Array
    flag: jax.Array


def block_min_inclusive(head: jax.Array, v: jax.Array, fill: float) -> jax.Array:
    """Within-segment inclusive running minimum, [N] -> [N].

    Requires segments that never span BLOCK boundaries (build() inserts
    synthetic heads), so one within-block composite scan suffices: the
    carry resets at each head.  f32 min is order-free, so this is
    bit-exact.  The value at each segment's LAST item is the segment min
    — carry this through build()'s payload sort or read it at seg_end."""
    n = v.shape[0]
    pad = (-n) % BLOCK
    vp = jnp.concatenate([v, jnp.full((pad,), fill, v.dtype)]) if pad else v
    hp = jnp.concatenate([head, jnp.ones((pad,), bool)]) if pad else head
    nb = vp.shape[0] // BLOCK
    m = vp.reshape(nb, BLOCK)
    f = hp.reshape(nb, BLOCK)

    def op(a: _MinCarry, b: _MinCarry) -> _MinCarry:
        return _MinCarry(
            m=jnp.where(b.flag, b.m, jnp.minimum(a.m, b.m)),
            flag=a.flag | b.flag,
        )

    scanned = jax.lax.associative_scan(op, _MinCarry(m=m, flag=f), axis=1)
    return scanned.m.reshape(-1)[:n]


def seg_min_f32(ctx: SegCtx, v: jax.Array, fill: float) -> jax.Array:
    """Per-segment minimum of a float32 plane, compacted to [U]."""
    inc = block_min_inclusive(ctx.head, v, fill)
    return jnp.where(ctx.live, inc[ctx.seg_end], fill)


def expand(ctx: SegCtx, seg_vals: jax.Array) -> jax.Array:
    """Broadcast per-segment values back to items: [U(,P)] -> [N(,P)].

    One monotone gather (sid is sorted) — pack every per-segment quantity
    into seg_vals' columns so the whole tick pays this once per side.
    """
    return seg_vals[ctx.sid]


def sort_batch(key_cols: Sequence[jax.Array], payloads: Sequence[jax.Array]):
    """Device-side stable sort fallback for callers without a presorted
    batch: returns (perm, sorted_payloads).  The runtime client presorts
    on the host instead (native/ring.presort over the segment keys in
    runtime/client._run_tick, verdicts mapped back through the inverse
    permutation) and never calls this."""
    n = key_cols[0].shape[0]
    pos = jax.lax.broadcasted_iota(jnp.int32, (n,), 0)
    ops = list(key_cols) + [pos] + [p for p in payloads]
    out = jax.lax.sort(ops, num_keys=len(key_cols), is_stable=True)
    perm = out[len(key_cols)]
    return perm, list(out[len(key_cols) + 1 :])


def unsort(perm: jax.Array, cols: Sequence[jax.Array]):
    """Restore batch order for output planes (device-side fallback)."""
    out = jax.lax.sort(
        [perm] + [c for c in cols], num_keys=1, is_stable=False
    )
    return list(out[1:])
