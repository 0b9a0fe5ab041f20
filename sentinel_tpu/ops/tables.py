"""Backend-selectable table primitives for the engine.

Every random-access table op in the tick goes through this layer, so the
engine logic is written once and the memory-access strategy is chosen by
``cfg.use_mxu_tables``:

- **cpu / small** (False): plain XLA gather / scatter-add.  Optimal on CPU
  and fine for small test configs.
- **mxu** (True): one-hot matmul contractions (ops/mxu_table.py) for
  big per-row tables, and a single packed-matrix matmul for per-rule-slot
  field gathers.  On TPU this replaces XLA's serialized ~65 ns/element
  scatter/gather loops with MXU work at B×N MACs — the difference between
  ~0.3M and tens of M decisions/s (measured on v5e).

Exactness: both paths are bit-identical for integer payloads through the
bf16 digit planes; float payloads go through Precision.DEFAULT matmuls,
which on TPU lower to a bf16x3 decomposition (measured exact for values
below ~2^22; ~2^-22 relative beyond).  Payloads whose magnitude outgrows
that — absolute engine-ms timestamps, raw 32-bit hashes — use the
bit-exact integer gathers (small_gather_int / digit planes) instead.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from sentinel_tpu.core.config import EngineConfig
from sentinel_tpu.ops import mxu_table as MX

PRECISION = jax.lax.Precision.DEFAULT  # exact: one side is a 0/1 one-hot


# ---------------------------------------------------------------------------
# big tables: [n_rows, ...planes] indexed by dynamic ids
# ---------------------------------------------------------------------------


def big_gather(
    cfg: EngineConfig,
    table: jax.Array,
    idx: jax.Array,
    n: int,
    max_int: int = None,
) -> jax.Array:
    """table[idx] with zeros for ids outside [0, n).

    ``max_int``: for NONNEGATIVE int tables, the max cell value — enables
    exact bf16 digit-plane matmuls on the MXU path (several× faster than
    the f32 fallback)."""
    if not cfg.use_mxu_tables:
        safe = jnp.clip(idx, 0, n - 1)
        out = table[safe]
        ok = (idx >= 0) & (idx < n)
        return jnp.where(ok.reshape(ok.shape + (1,) * (out.ndim - 1)), out, 0)
    plan = MX.make_plan(n, cfg.mxu_n_lo)
    Hi, Lo = MX.onehots(idx, plan)
    return MX.gather(table, plan, Hi, Lo, max_int=max_int)


def lane_gather_1col(
    cfg: EngineConfig, table: jax.Array, idx: jax.Array, n: int, lanes: int = 8
) -> jax.Array:
    """f32 table[idx] for a ONE-COLUMN table, zeros for ids outside [0, n).

    Direct 1-column gathers are pathological on TPU (~0.9 ms at 128K
    indices — and padding the table is undone by the compiler narrowing
    the gather to the used columns); the MXU one-hot gather pays a full
    index-axis pass per digit plane.  Packing the column as [n/8, 8] and
    selecting the lane with a DATA-DEPENDENT one-hot keeps the row read
    8 lanes wide and cannot be narrowed.  Exact: native row gather +
    multiply by exact 0/1 (same trick as param.estimate_fused).

    ``lanes`` (a power of two): a [n/8, 8] view of a table of millions of
    cells is materialized padded to the 128-lane tile, sixteen times the
    table, every call; such a table is read at ``lanes=128``, where the view
    is the table itself."""
    ok = (idx >= 0) & (idx < n)
    safe = jnp.clip(idx, 0, n - 1)
    if not cfg.use_mxu_tables:
        return jnp.where(ok, table[safe].astype(jnp.float32), 0.0)
    t = table.astype(jnp.float32)
    pad = (-n) % lanes
    if pad:
        t = jnp.concatenate([t, jnp.zeros((pad,), jnp.float32)])
    g = t.reshape(-1, lanes)[safe >> (lanes.bit_length() - 1)]  # [N, lanes] row gather
    oh = (
        (safe & (lanes - 1))[:, None] == jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    ).astype(jnp.float32)
    return jnp.where(ok, jnp.sum(g * oh, axis=1), 0.0)


def lane_gather_1col_int(
    cfg: EngineConfig, table: jax.Array, idx: jax.Array, n: int
) -> jax.Array:
    """lane_gather_1col for small-int tables (slot ids, modes): values are
    f32-exact (< 2^24), so a plain cast restores them."""
    return lane_gather_1col(cfg, table, idx, n).astype(jnp.int32)


def lane_gather_multi(
    cfg: EngineConfig, tables: Sequence[jax.Array], idx: jax.Array, n: int
) -> list:
    """Up to FOUR 1-column tables read at the SAME index with ONE gather.

    Interleaves the k tables two-rows-per-8-lane-row ([n/2, 8]: row r
    holds tables[0..3] of ids 2r and 2r+1), gathers rows at idx>>1, and
    selects each table's value with a data-dependent one-hot on
    (idx&1)*4+col — the same cannot-be-narrowed trick as
    lane_gather_1col, but k tables share the single row gather instead of
    paying one each (the check phase reads four per-resource slot tables
    at the same res index; ~0.1 ms per gather at U~16K adds up).
    f32-exact values (< 2^24) only."""
    k = len(tables)
    assert 1 <= k <= 4
    ok = (idx >= 0) & (idx < n)
    safe = jnp.clip(idx, 0, n - 1)
    if not cfg.use_mxu_tables:
        return [
            jnp.where(ok, t[safe].astype(jnp.float32), 0.0) for t in tables
        ]
    n2 = n + (n % 2)
    cols = []
    for t in tables:
        t = t.astype(jnp.float32)
        if n2 != n:
            t = jnp.concatenate([t, jnp.zeros((1,), jnp.float32)])
        cols.append(t.reshape(-1, 2))  # [n2/2, 2] (even, odd)
    while len(cols) < 4:
        cols.append(jnp.zeros_like(cols[0]))
    # lane layout: [t0@even, t1@even, t2@even, t3@even, t0@odd, ...]
    packed = jnp.concatenate(
        [c[:, 0:1] for c in cols] + [c[:, 1:2] for c in cols], axis=1
    )  # [n2/2, 8]
    g = packed[safe >> 1]  # [N, 8] row gather
    half = (safe & 1)[:, None] * 4
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (1, 8), 1)
    out = []
    for c in range(k):
        oh = ((half + c) == lane_iota).astype(jnp.float32)
        out.append(jnp.where(ok, jnp.sum(g * oh, axis=1), 0.0))
    return out


def big_scatter_add(
    cfg: EngineConfig,
    table: jax.Array,
    idx: jax.Array,
    values: jax.Array,
    n: int,
    max_int: int = 65535,
) -> jax.Array:
    """table.at[idx].add(values), dropping ids outside [0, n).

    ``max_int`` bounds each integer VALUE (not the cell) for the bf16
    digit decomposition; 65535 covers per-item counts."""
    if not cfg.use_mxu_tables:
        ok = (idx >= 0) & (idx < n)
        v = values
        okb = ok.reshape(ok.shape + (1,) * (v.ndim - 1))
        return table.at[jnp.where(ok, idx, jnp.int32(2**30))].add(
            jnp.where(okb, v, 0), mode="drop"
        )
    # scatter contractions tile best with a narrow Lo axis (measured on
    # v5e: n_lo=128 beats 512 by ~30%+ for multi-plane histograms, while
    # gathers prefer the wide plan — see big_gather)
    plan = MX.make_plan(n, min(cfg.mxu_n_lo, 128))
    Hi, Lo = MX.onehots(idx, plan)
    return MX.scatter_add(table, plan, Hi, Lo, values, max_int=max_int)


def histogram(
    cfg: EngineConfig, idx: jax.Array, values: jax.Array, n: int, max_int: int = 65535
) -> jax.Array:
    """Dense [n, ...planes] sum of values grouped by id (dropped if OOB).

    The MXU-native replacement for scatter-into-state: compute the dense
    per-row delta once, then apply it with an elementwise add."""
    planes = values.shape[1:]
    dtype = values.dtype if jnp.issubdtype(values.dtype, jnp.floating) else jnp.int32
    zeros = jnp.zeros((n,) + planes, dtype)
    return big_scatter_add(cfg, zeros, idx, values, n, max_int=max_int)


def depth_histogram(
    cfg: EngineConfig,
    cols: jax.Array,  # int32 [N, depth] — per-depth column per event
    values: jax.Array,  # int32 [N, P] — deltas, landed at EVERY depth
    valid: jax.Array,  # bool [N]
    depth: int,
    width: int,
    max_int: int = 65535,
) -> jax.Array:
    """Dense [depth, width, P] histogram of a CMS-style batch — every event
    lands its value row at one column PER depth.

    The sketch tier's write kernel.  All depths share ONE flat
    [depth*width] id space (column + d*width), so the MXU path is a single
    digit-plane contraction over the whole flat table instead of a
    per-depth loop of narrower ones (same MACs, 1/depth the pass count —
    and one plan, so tick-identity holds across depths).  The CPU path is
    one native scatter-add on the same flat ids; ``cfg=None`` forces it
    (hosts without an EngineConfig in reach, e.g. cluster token columns).
    """
    N = cols.shape[0]
    P = values.shape[1]
    off = jax.lax.broadcasted_iota(jnp.int32, (1, depth), 1) * width
    ok = valid[:, None] & (cols >= 0) & (cols < width)
    flat_idx = jnp.where(ok, cols + off, jnp.int32(-1)).T.reshape(-1)  # [depth*N]
    flat_val = jnp.broadcast_to(values[None], (depth, N, P)).reshape(depth * N, P)
    if cfg is None or not cfg.use_mxu_tables:
        hist = (
            jnp.zeros((depth * width, P), jnp.int32)
            .at[jnp.where(flat_idx >= 0, flat_idx, jnp.int32(2**30))]
            .add(jnp.where(flat_idx[:, None] >= 0, flat_val, 0), mode="drop")
        )
        return hist.reshape(depth, width, P)
    plan = MX.plan_for(depth * width, min(cfg.mxu_n_lo, 128))
    Hi, Lo = MX.onehots(flat_idx, plan)
    hist = MX.scatter_add(
        jnp.zeros((depth * width, P), jnp.int32), plan, Hi, Lo, flat_val,
        max_int=max_int,
    )
    return hist.reshape(depth, width, P)


def depth_gather_1col(
    cfg: EngineConfig,
    tab: jax.Array,  # [depth, width] — one table column per depth
    cols: jax.Array,  # int32 [N, depth]
    width: int,
    max_int: int = None,
) -> jax.Array:
    """f32 [depth, N] = tab[d, cols[:, d]] for every depth at once, zeros
    for ids outside [0, width).

    The sketch tier's read kernel (min-over-depth runs on the result).
    Same flat [depth*width] id trick as depth_histogram: the MXU path is
    ONE digit-plane contraction (pass ``max_int`` — the max CELL value —
    for nonnegative int tables) or one lane-packed gather for float
    tables; the CPU path one native gather."""
    depth = tab.shape[0]
    N = cols.shape[0]
    off = jax.lax.broadcasted_iota(jnp.int32, (1, depth), 1) * width
    ok = (cols >= 0) & (cols < width)
    flat_idx = (jnp.where(ok, cols, 0) + off).T.reshape(-1)  # [depth*N]
    flat_ok = ok.T.reshape(-1)
    # The flatten destroys the width sharding, so under the SPMD mesh
    # XLA all-gathers the full [depth, width] slice of the salsa running
    # sums before the gather (pinned in analysis/spmd/collectives.json:
    # 2 x s32[2,512] per tick at the CI config, scaling to 2 x 512 KiB
    # per device per tick at the 1M tier).  The shard-local fix —
    # partial gather on each width shard + all-reduce of the [depth, N]
    # result — is scoped to MULTICHIP_r06 (ROADMAP open item 1); any
    # NEW gather through this line still fails the collective-ledger pass.
    # stlint: disable-next-line=implicit-reshard — known salsa read reshard, pinned in the ledger
    flat_tab = tab.reshape(depth * width)
    if cfg is None or not cfg.use_mxu_tables:
        g = jnp.where(flat_ok, flat_tab[flat_idx].astype(jnp.float32), 0.0)
        return g.reshape(depth, N)
    if max_int is not None and jnp.issubdtype(flat_tab.dtype, jnp.integer):
        plan = MX.plan_for(depth * width, cfg.mxu_n_lo)
        Hi, Lo = MX.onehots(jnp.where(flat_ok, flat_idx, jnp.int32(-1)), plan)
        g = MX.gather(flat_tab, plan, Hi, Lo, max_int=max_int).astype(jnp.float32)
    else:
        g = lane_gather_1col(
            cfg, flat_tab, jnp.where(flat_ok, flat_idx, jnp.int32(-1)), depth * width
        )
    return g.reshape(depth, N)


# ---------------------------------------------------------------------------
# small tables: per-rule-slot field rows, S <= a few thousand
# ---------------------------------------------------------------------------


def pack_fields(fields: Sequence[jax.Array]) -> jax.Array:
    """[S, F] f32 matrix from per-slot field vectors (bool/int/float)."""
    cols = [jnp.asarray(f).astype(jnp.float32) for f in fields]
    return jnp.stack(cols, axis=1)


def bf16_parts(x) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A float32 column as three addends, each exact in bfloat16, whose sum
    is the column exactly (3 x 8 significant bits).  At PRECISION the chip
    rounds a table that crosses the MXU to bfloat16: a FlowRule count of 401
    arrived as 400, and its pacing cost as 3 ms where 1000 / 401 rounds to 2
    (PERF.md, PR 42).  A value that has to arrive whole crosses as its parts
    and is summed on the far side.  The parts are cut by masking the low 16
    bits, not by a convert to bfloat16 and back, which a compiler that may
    keep excess precision is free to drop."""

    def head(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32) & jnp.uint32(0xFFFF0000)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    x = jnp.asarray(x).astype(jnp.float32)
    p0 = head(x)
    p1 = head(x - p0)
    return p0, p1, x - p0 - p1


#: above this, a flat [N, S] one-hot's memory traffic dominates — switch to
#: the two-level decomposition (same MACs, B×(n_hi+n_lo) memory)
_FLAT_ONEHOT_LIMIT = 1024


def small_gather_fields(
    cfg: EngineConfig, packed: jax.Array, slots: jax.Array
) -> jax.Array:
    """[N, F] f32 = packed[slots] — ONE matmul on the MXU path, replacing F
    separate serialized gathers."""
    S = packed.shape[0]
    if not cfg.use_mxu_tables:
        safe = jnp.clip(slots, 0, S - 1)
        return packed[safe]
    safe = jnp.clip(slots, 0, S - 1)
    if S > _FLAT_ONEHOT_LIMIT:
        # many-plane f32 gathers tile best at a mid-width Lo axis (measured)
        plan = MX.make_plan(S, min(cfg.mxu_n_lo, 256))
        Hi, Lo = MX.onehots(safe, plan)
        return MX.gather(packed, plan, Hi, Lo)
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
    onehot = (safe[:, None] == iota).astype(jnp.float32)
    return jnp.matmul(onehot, packed, precision=PRECISION)


def small_gather_int(cfg: EngineConfig, table: jax.Array, slots: jax.Array) -> jax.Array:
    """Exact int32 gather from a small table via f32 matmuls.

    A raw int32 (e.g. a param hash) does not survive the matmul whole: at
    PRECISION the chip rounds BOTH sides to bfloat16, which holds 8 bits, so
    the table crosses as its four bytes, each exact, and the int32
    recombination restores the original bits.  (16-bit halves were rounded
    on the chip: no ParamFlowItem hash ever matched there.)"""
    if not cfg.use_mxu_tables:
        S = table.shape[0]
        return table[jnp.clip(slots, 0, S - 1)]
    t = jnp.asarray(table)
    flat = t.reshape(t.shape[0], -1).astype(jnp.uint32)
    F = flat.shape[1]
    shifts = (24, 16, 8, 0)
    packed = jnp.concatenate(
        [((flat >> s) & 0xFF).astype(jnp.float32) for s in shifts], axis=1
    )
    g = jnp.round(small_gather_fields(cfg, packed, slots)).astype(jnp.uint32)
    out = jnp.zeros((slots.shape[0], F), jnp.uint32)
    for i, s in enumerate(shifts):
        out = out | (g[:, i * F : (i + 1) * F] << s)
    return out.astype(jnp.int32).reshape((slots.shape[0],) + t.shape[1:])


def small_scatter_add(
    cfg: EngineConfig, table: jax.Array, slots: jax.Array, values: jax.Array,
    max_int: int = 65535,
) -> jax.Array:
    """table [S, ...planes] .at[slots].add(values) — one-hot matmul on MXU.
    Out-of-range slots are dropped.  ``max_int`` bounds integer VALUES for
    the digit decomposition (pass 1 for 0/1 flags — one bf16 plane)."""
    S = table.shape[0]
    if not cfg.use_mxu_tables:
        return table.at[jnp.where((slots >= 0) & (slots < S), slots, 2**30)].add(
            values, mode="drop"
        )
    ok = (slots >= 0) & (slots < S)
    if S > _FLAT_ONEHOT_LIMIT:
        plan = MX.make_plan(S, min(cfg.mxu_n_lo, 128))
        Hi, Lo = MX.onehots(slots, plan, valid=ok)
        return MX.scatter_add(table, plan, Hi, Lo, values, max_int=max_int)
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
    onehot = ((jnp.where(ok, slots, 0)[:, None] == iota) & ok[:, None]).astype(
        jnp.float32
    )
    v = values.astype(jnp.float32)
    squeeze = v.ndim == 1
    if squeeze:
        v = v[:, None]
    upd = jnp.einsum("ns,np->sp", onehot, v, precision=PRECISION)
    if squeeze:
        upd = upd[:, 0]
    out = table.astype(jnp.float32) + upd.reshape(table.shape)
    return out.astype(table.dtype) if jnp.issubdtype(table.dtype, jnp.integer) else out


def small_scatter_or(
    cfg: EngineConfig, table: jax.Array, slots: jax.Array, flag: jax.Array
) -> jax.Array:
    """Boolean OR-scatter into [S] (0/1 semantics) — rides a single-digit
    integer histogram (flags are 0/1)."""
    hist = small_scatter_add(
        cfg, jnp.zeros(table.shape, jnp.int32), slots, flag.astype(jnp.int32),
        max_int=1,
    )
    return (table.astype(jnp.bool_) | (hist > 0)).astype(table.dtype)


def small_scatter_max(
    cfg: EngineConfig, table: jax.Array, slots: jax.Array, values: jax.Array, neutral: float
) -> jax.Array:
    """table [S] = elementwise max with per-slot max of values [N].

    MXU path: masked one-hot substitution + column max — O(N*S) VPU ops,
    fine for S <= a few thousand."""
    S = table.shape[0]
    if not cfg.use_mxu_tables:
        return table.at[jnp.where((slots >= 0) & (slots < S), slots, 2**30)].max(
            values, mode="drop"
        )
    ok = (slots >= 0) & (slots < S)
    safe = jnp.where(ok, slots, 0)
    n = slots.shape[0]
    chunk = 8192
    pad = (-n) % chunk
    if pad:
        safe = jnp.concatenate([safe, jnp.zeros((pad,), safe.dtype)])
        ok = jnp.concatenate([ok, jnp.zeros((pad,), bool)])
        values = jnp.concatenate([values, jnp.full((pad,), neutral, values.dtype)])
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)

    def body(carry, xs):
        s, o, v = xs
        onehot = (s[:, None] == iota) & o[:, None]  # [chunk, S]
        cand = jnp.where(onehot, v[:, None], neutral)
        return jnp.maximum(carry, jnp.max(cand, axis=0)), None

    C = safe.shape[0] // chunk
    init = jnp.full((S,), neutral, jnp.float32)
    colmax, _ = jax.lax.scan(
        body,
        init,
        (safe.reshape(C, chunk), ok.reshape(C, chunk), values.astype(jnp.float32).reshape(C, chunk)),
    )
    return jnp.maximum(table, colmax.astype(table.dtype))
