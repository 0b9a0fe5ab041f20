"""Cluster token decision column — the shard's device-answered batch.

Protocol v2 coalesces BATCH frames from many connections into one
decision batch (cluster/token_service.TokenColumnBatcher).  This module
is the jitted kernel that answers it: every cluster flow owns one row
("slot") of a shared sliding-window tensor (ops/window.py — the same
epoch-validated O(1) running-sum shape as the engine tier, arXiv
1604.02450), and one call decides B entries against their per-flow
global thresholds in a single gather + prefix-sum + scatter-add.

Within-batch ordering: entries arrive PRESORTED by slot (host presort,
native batch_sort3), and ``heads[i]`` is the index of the first entry of
entry *i*'s slot run.  An exclusive prefix sum of requested units,
rebased at each head, charges every entry with the units requested by
SAME-slot entries ahead of it in the batch — so one coalesced batch
admits exactly what sequential requests would have.  The prefix charges
*requested* (not granted) units: a denied all-or-nothing entry still
reserves its ask against later same-slot entries of the SAME batch.
That slack is bounded by one batch and errs toward under-admission —
the fail-closed direction.

Decision semantics per entry (matching the engine's GlobalRequestLimiter
``used + units <= threshold``):

  all-or-nothing (partial=False): granted = units if avail >= units else 0
  partial-grant  (partial=True):  granted = clip(floor(avail), 0, units)
  forced         (forced=True):   granted = units unconditionally — the
      occupy-ahead emulation: a prioritized over-limit ask charges its
      units anyway (against the CURRENT bucket, one bucket earlier than
      the engine's tryOccupyNext — the conservative direction) and the
      host answers SHOULD_WAIT with the time to the next bucket.

Granted units land in the window as EV_PASS, denied as EV_BLOCK, so the
window IS the budget ledger — replenishment is bucket expiry, identical
to the engine tier.  Everything is a pure function of (state, now_ms);
nothing reads a clock.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from sentinel_tpu.ops import window as W

#: shard decision window: DEFAULT_SAMPLE_COUNT buckets over one
#: DEFAULT_INTERVAL_MS accounting interval (cluster/constants.py values,
#: restated literally to keep ops/ free of cluster imports)
DEFAULT_CFG = W.WindowConfig(sample_count=10, window_ms=100)


class TokenColState(NamedTuple):
    win: W.WindowState  # per-slot pass/block ledger
    limits: jax.Array  # float32 [slots] — global threshold per flow slot


def init_state(slots: int, cfg: W.WindowConfig = DEFAULT_CFG) -> TokenColState:
    return TokenColState(
        win=W.init_window(slots, cfg),
        limits=jnp.zeros((slots,), dtype=jnp.float32),
    )


def decide_batch(
    state: TokenColState,
    now_ms: jax.Array,  # int32/int64 scalar — host-stamped batch time
    slots: jax.Array,  # int32 [B] — flow slot per entry (slot-sorted)
    units: jax.Array,  # int32 [B] — requested units (0 = padding)
    heads: jax.Array,  # int32 [B] — index of entry's slot-run head
    partial: jax.Array,  # bool [B] — partial-grant vs all-or-nothing
    forced: jax.Array,  # bool [B] — unconditional charge (occupy-ahead)
    cfg: W.WindowConfig = DEFAULT_CFG,
) -> Tuple[jax.Array, jax.Array, TokenColState]:
    """(granted int32 [B], observed float32 [B], updated ledger state).

    ``observed`` is the window usage each entry was decided against
    (used + same-batch prefix) — the deny-provenance value the protocol
    v3 _T_PROV block ships back to clients, so a remote block can report
    "observed N of limit M" like a local one (obs/explain.py)."""
    # rotate once up front so the O(1) running sums are exact at this
    # now_ms, then the ledger read is a single [B] gather instead of the
    # old masked [B, nb] reduction per batch
    win = W.refresh(state.win, now_ms, cfg)
    used = W.gather_window_event_run(win, slots, W.EV_PASS)
    # per-entry ask clipped so an int32 cumsum over MAX_BATCH_ENTRIES
    # cannot overflow (2048 × 2^20 < 2^31); a single ask beyond 1M units
    # is already past every sane threshold and the lease ceiling
    units = jnp.minimum(units, jnp.int32(1 << 20))
    # exclusive prefix of requested units, rebased per slot run
    ex = jnp.cumsum(units) - units
    prefix = ex - ex[heads]
    observed = used.astype(jnp.float32) + prefix.astype(jnp.float32)
    avail = state.limits[slots] - observed
    units_f = units.astype(jnp.float32)
    grant_partial = jnp.clip(jnp.floor(avail), 0.0, units_f)
    grant_strict = jnp.where(avail >= units_f, units_f, 0.0)
    granted = jnp.where(partial, grant_partial, grant_strict).astype(jnp.int32)
    granted = jnp.where(forced, units, granted)
    deltas = jnp.zeros((slots.shape[0], W.NUM_EVENTS), dtype=jnp.int32)
    deltas = deltas.at[:, W.EV_PASS].set(granted)
    deltas = deltas.at[:, W.EV_BLOCK].set(units - granted)
    win = W.add_batch(win, now_ms, slots, deltas, cfg=cfg)
    return granted, observed, TokenColState(win=win, limits=state.limits)


def ms_to_next_bucket(now_ms: int, cfg: W.WindowConfig = DEFAULT_CFG) -> int:
    """Host helper: ms until the next bucket boundary — the SHOULD_WAIT
    horizon for the occupy-ahead emulation.  Always in [1, window_ms]."""
    return int(cfg.window_ms - (now_ms % cfg.window_ms))


def set_limits(state: TokenColState, limits: jax.Array) -> TokenColState:
    """Replace the per-slot thresholds (rule push / census reprojection)
    without disturbing the standing window ledger."""
    return TokenColState(win=state.win, limits=limits.astype(jnp.float32))


#: the jitted column's program name (``jit_sentinel_token_col`` in a trace)
COLUMN_PROGRAM = "sentinel_token_col"


@functools.lru_cache(maxsize=None)
def jitted_decide(cfg: W.WindowConfig = DEFAULT_CFG):
    """Process-shared jitted decide_batch for one window config — every
    TokenColumnBatcher instance reuses the same compiled executables
    (keyed by shape and by the device the state is committed to), so a
    test suite constructing many services pays XLA compilation once per
    (slots, batch) shape, not per service."""
    fn = functools.partial(decide_batch, cfg=cfg)
    # a bare partial compiles as ``jit__unknown``: the name is what a
    # profiler trace lists the column's program under, on every chip
    fn.__name__ = COLUMN_PROGRAM
    return jax.jit(fn)
