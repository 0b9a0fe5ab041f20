"""Fused Pallas effects-phase megakernels.

Why this exists (measured on v5e in round 2, before the ledger; the
probe is gone): the XLA one-hot-matmul table path (ops/mxu_table.py) pays
~0.3-0.9 ms PER OP at B=128K regardless of FLOPs — every scatter/gather
materializes [B, n_lo] one-hot tensors in HBM and takes its own fusion,
and the tick makes ~25 such calls (19 ms total).  The fused formulation
runs ONE Pallas kernel per tick phase: each grid step loads a tile of
items into VMEM, builds the one-hot factors there, and contracts them
into EVERY destination table's accumulator (stat windows, circuit-breaker
columns, CMS sketch, per-rule scatters) without ever writing a one-hot to
HBM.  Measured: the 3B-item stat landing drops 5.0 ms -> ~1.3 ms; the
full set of effect scatters collapses from ~11 ms of serial fusions to
~2-3 ms of mostly-MXU work.

Exactness matches ops/mxu_table.py bit for bit: integer payloads are
decomposed into base-256 digit planes (bf16 represents 0..255 exactly, so
a DEFAULT-precision one-pass bf16 dot with a 0/1 one-hot side is exact),
accumulated in f32, and recombined with integer arithmetic outside the
kernel.  The same value bounds apply (counts <= 65535 via 2 digits,
rt_q <= 2^16, cells < 2^24 before f32 accumulation loses integers).

Reference map: this is the batched replacement for the reference's
per-request LongAdder writes in StatisticSlot.java:54-164 and the
LeapArray bucket adds (slots/statistic/base/LeapArray.java:41) — one
kernel landing a whole micro-batch of slot-chain side effects at once.
"""

from __future__ import annotations

import functools
import os
import threading
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from sentinel_tpu.core.config import PARAM_NARROW_WIDTH

#: default items per grid step.  Multi-job kernels unroll one [tb, N_LO]
#: LoV temporary per digit-dot; ~25 dots x tb=2048 x 128 x 2B ~= 13 MB
#: stays inside Mosaic's 16 MB scoped-vmem stack (tb=4096 overflows on
#: some job mixes) and measured within noise of 4096 at the served shapes.
TILE = 2048
#: gather kernels hold [tb, N_LO] f32 select products per unrolled digit
TILE_GATHER = 2048

#: one-hot minor-axis width — 128 lanes exactly, so Lo is a single vreg
#: column and the dot's N dim never pads
N_LO = 128


#: jitted pallas wrappers for EAGER callers, keyed by the call site's
#: static plan (kernel structure + shapes).  Under ``jax.disable_jit()``
#: (the test suite's eager-heavy fixture, tests/conftest.py) every eager
#: pallas call re-traces its interpreted kernel; wrapping the built
#: pallas_call in a key-cached jit pays one small compile per distinct
#: kernel and runs compiled thereafter.  The eager modules need it:
#: tests/test_fused.py takes 97 s with the cache and 120 s without
#: (CPU, measured), and tier-1 has no such slack.
_EAGER_PALLAS_CACHE: dict = {}
_EAGER_PALLAS_LOCK = threading.Lock()


def run_pallas(call, *args, key=None):
    """Invoke a built pallas_call; inside a jit trace (and eagerly with
    jit enabled) this is a plain call.

    ``key``: hashable static plan of the call site (kernel structure,
    shapes, tiling).  Two calls with equal keys MUST be equivalent
    pallas programs up to traced inputs — the first caller's kernel is
    the one that stays cached."""
    if key is None or not jax.config.jax_disable_jit:
        return call(*args)
    with _EAGER_PALLAS_LOCK:
        fn = _EAGER_PALLAS_CACHE.get(key)
        if fn is None:
            fn = jax.jit(call)
            _EAGER_PALLAS_CACHE[key] = fn
    with jax.disable_jit(False):
        return fn(*args)


@functools.cache
def interpret_mode() -> bool:
    """True when the kernels run interpreted because the backend has no
    Mosaic (tests on CPU).  Decided once per process from the live
    backend; a backend that fails to initialise raises here instead of
    silently selecting the interpreter."""
    return jax.default_backend() != "tpu"


@functools.cache
def available() -> bool:
    """Fused kernels compile on TPU (Mosaic); interpret elsewhere."""
    if os.environ.get("SENTINEL_NO_PALLAS"):
        return False
    return True


class Job(NamedTuple):
    """One scatter destination processed by a fused kernel.

    rows:   int32 [R, N] — R row-vectors per item (e.g. the res/ctx/origin
            stat fan of StatisticSlot.java:54-123 is R=3); ids outside
            [0, n) are dropped (the trash-row / drop-mode analog).
    values: int32 [P, N] value planes shared by every row-vector, or
            [R, P, N] for per-row-vector values.
    digits: per-plane base-256 digit counts; plane p must satisfy
            0 <= value < 256**digits[p] (matching mxu_table max_int).
    n:      logical table rows.
    """

    name: str
    n: int
    rows: jax.Array
    values: jax.Array
    digits: tuple


def _pad_axis(x: jax.Array, axis: int, to: int, fill) -> jax.Array:
    pad = to - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


#: table rows past which a job leaves scatter_many for scatter_sorted: the
#: kernel here keeps the job's whole f32 table and a [rows / N_LO, tile]
#: one-hot in fast memory (32 bytes a row at the default tile), and contracts
#: every item against all of it
MAX_RESIDENT_ROWS = PARAM_NARROW_WIDTH

#: max digit-dot units per pallas call — Mosaic's 16 MB scoped-vmem stack
#: holds ~25-30 unrolled [tb, N_LO] temporaries at tb=2048; larger job
#: mixes (e.g. rules_per_resource > 1 configs) split across calls
_MAX_UNITS_PER_CALL = 28


def _job_units(j: "Job") -> int:
    return j.rows.shape[0] * sum(j.digits)


def scatter_many(jobs: Sequence[Job], tb: int = TILE, interpret: Optional[bool] = None):
    """Run every job's scatter in ONE Pallas kernel over a shared item axis.

    All jobs must share the item-axis length N (pad shorter vectors with
    row id -1 upstream).  Returns one f32 [n_j, P_j] histogram per job —
    digit planes already recombined; integer-exact within the documented
    bounds.  The caller lands these into window/sketch state with plain
    elementwise adds (ops/window.add_dense etc.).

    Job lists whose total digit-dot count exceeds the scoped-vmem budget
    are transparently split across several pallas calls (per-call overhead
    is small against the per-dot cost).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = interpret_mode()

    def sorted_path(j: Job) -> bool:
        # a table this wide costs items x rows here whatever the items hold;
        # scatter_sorted takes it if its digit planes pack into one word
        return j.n > MAX_RESIDENT_ROWS and sum(j.digits) <= 4

    if any(sorted_path(j) for j in jobs):
        rest = [j for j in jobs if not sorted_path(j)]
        done = iter(scatter_many(rest, tb=tb, interpret=interpret) if rest else ())
        return [
            scatter_sorted(j, interpret=interpret) if sorted_path(j) else next(done)
            for j in jobs
        ]

    total_units = sum(_job_units(j) for j in jobs)
    if total_units > _MAX_UNITS_PER_CALL and len(jobs) > 1:
        chunks: list = [[]]
        acc = 0
        for j in jobs:
            u = _job_units(j)
            if chunks[-1] and acc + u > _MAX_UNITS_PER_CALL:
                chunks.append([])
                acc = 0
            chunks[-1].append(j)
            acc += u
        out: list = []
        for ch in chunks:
            out.extend(scatter_many(ch, tb=tb, interpret=interpret))
        return out

    N = jobs[0].rows.shape[-1]
    for j in jobs:
        assert j.rows.shape[-1] == N, f"job {j.name}: item axis mismatch"
        assert j.values.shape[-1] == N, f"job {j.name}: values item axis mismatch"

    nT = max((N + tb - 1) // tb, 1)
    Np = nT * tb

    # --- static plan per job ------------------------------------------------
    # ALL jobs' row-vectors and value planes pack into TWO stacked inputs
    # (one pad+reshape+transpose each) instead of two per job — at small
    # batches the ~3 XLA prep ops per job were a measurable fixed cost
    plans = []  # (R, P, per_row_vals, n_hi, pd_total, digits, n, roff, voff)
    row_stack = []
    val_stack = []
    roff = voff = 0
    out_shapes = []
    out_specs = []
    for j in jobs:
        rows = j.rows
        assert rows.ndim == 2, f"job {j.name}: rows must be [R, N]"
        R = rows.shape[0]
        per_row = j.values.ndim == 3
        P = j.values.shape[-2]
        assert len(j.digits) == P, f"job {j.name}: digits/planes mismatch"
        n_hi = (j.n + N_LO - 1) // N_LO
        pd = sum(j.digits)
        plans.append(
            (R, P, per_row, n_hi, pd, tuple(j.digits), j.n, roff, voff)
        )
        roff += R
        voff += R * P if per_row else P
        row_stack.append(rows.astype(jnp.int32))
        vals = j.values.astype(jnp.int32)
        val_stack.append(vals.reshape(-1, N))
        out_shapes.append(jax.ShapeDtypeStruct((pd, n_hi, N_LO), jnp.float32))
        out_specs.append(
            pl.BlockSpec((pd, n_hi, N_LO), lambda t: (0, 0, 0), memory_space=pltpu.VMEM)
        )

    rows_all = _pad_axis(jnp.concatenate(row_stack, axis=0), 1, Np, -1)
    vals_all = _pad_axis(jnp.concatenate(val_stack, axis=0), 1, Np, 0)
    SR = rows_all.shape[0]
    SV = vals_all.shape[0]
    # 2-D blocks over the natural [S, Np] stacks: the tile axis is sliced
    # by the index map, so kernel inputs need no layout transpose — the
    # old [nT, S, tb] form cost a ~0.1 ms HBM copy per stacked input at
    # B=128K (profiled)
    ins = [rows_all, vals_all]
    in_specs = [
        pl.BlockSpec((SR, tb), lambda t: (0, t), memory_space=pltpu.VMEM),
        pl.BlockSpec((SV, tb), lambda t: (0, t), memory_space=pltpu.VMEM),
    ]

    def kernel(*refs):
        rows_ref, vals_ref = refs[0], refs[1]
        orefs = refs[2:]
        t = pl.program_id(0)

        for o in orefs:

            @pl.when(t == 0)
            def _(o=o):
                o[...] = jnp.zeros_like(o)

        iota_l = jax.lax.broadcasted_iota(jnp.int32, (tb, N_LO), 1)
        for ji, (R, P, per_row, n_hi, pd, digits, n, roff, voff) in enumerate(plans):
            iota_h = jax.lax.broadcasted_iota(jnp.int32, (n_hi, tb), 0)
            for r in range(R):
                k = rows_ref[roff + r, :]
                ok = (k >= 0) & (k < n)
                safe = jnp.where(ok, k, 0)
                hi = safe // N_LO
                lo = safe - hi * N_LO
                oki = ok.astype(jnp.int32)
                HiT = ((hi[None, :] == iota_h) & (oki[None, :] > 0)).astype(
                    jnp.bfloat16
                )
                Lo = (lo[:, None] == iota_l).astype(jnp.bfloat16)
                # ONE wide dot per row: every digit plane rides as N_LO
                # extra N-columns — [n_hi, tb] x [tb, pd*N_LO] keeps the
                # MXU fed, where the old per-digit [.,tb]x[tb,N_LO] dots
                # were too narrow to utilize it (the digit loop was ~10x
                # off the roofline, measured).  Same products, same
                # per-column f32 accumulation order — bit-identical.
                cols = []
                for p in range(P):
                    v = vals_ref[voff + (r * P + p if per_row else p), :]
                    for d in range(digits[p]):
                        dig = ((v >> (8 * d)) & 0xFF)[:, None].astype(jnp.bfloat16)
                        cols.append(Lo * dig)
                wide = jnp.concatenate(cols, axis=1)  # [tb, pd*N_LO]
                res = jax.lax.dot(
                    HiT, wide, preferred_element_type=jnp.float32
                )  # [n_hi, pd*N_LO]
                for k2 in range(pd):
                    orefs[ji][k2, :, :] += res[:, k2 * N_LO : (k2 + 1) * N_LO]

    grid = (nT,)
    outs = run_pallas(pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
        name="scatter_many",
        # Mosaic's default 16 MB scoped-vmem stack is marginal for the
        # ~28-unit job mixes (observed 16.24 MB on a 27-val-row mix at
        # B=4096 after the 2-D block-spec change); v5e has 128 MB VMEM
        # per core, so double the scope rather than split finer
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=32 * 1024 * 1024
        ),
    ), *ins,
        key=("scatter_many", tuple(plans), SR, SV, nT, tb, bool(interpret)))

    # --- digit recombination (XLA elementwise; exact integer weights) ------
    results = []
    for out, (R, P, per_row, n_hi, pd, digits, n, _roff, _voff) in zip(outs, plans):
        flat = out.reshape(pd, n_hi * N_LO)[:, :n]  # [pd, n]
        cols = []
        off = 0
        for p in range(P):
            acc = flat[off]
            for d in range(1, digits[p]):
                acc = acc + flat[off + d] * float(1 << (8 * d))
            cols.append(acc)
            off += digits[p]
        results.append(jnp.stack(cols, axis=1))  # [n, P]
    return results


# ---------------------------------------------------------------------------
# fused gather suite: chained per-item reads sharing one item axis
# ---------------------------------------------------------------------------


class GatherJob(NamedTuple):
    """One gather source read by a fused gather kernel.

    ids:    int32 [N] — row per item; out-of-range ids read 0.
    table:  int32 [n, P] — NONNEGATIVE integer table; each plane p bounded
            by 256**digits[p] (digit-plane exactness, like mxu_table
            gather with max_int).
    digits: per-plane digit counts.
    """

    name: str
    ids: jax.Array
    table: jax.Array
    digits: tuple


def gather_many(
    jobs: Sequence[GatherJob], tb: int = TILE_GATHER, interpret: Optional[bool] = None
):
    """Per-item gathers from several tables in ONE kernel.

    Returns one f32 [N, P] per job.  The table rides in VMEM as bf16 digit
    planes ([digits_total, n_hi, N_LO], built XLA-side — cheap elementwise)
    and each tile contracts Hi @ plane then selects with Lo — the gather
    formulation of ops/mxu_table.py:137-184 without HBM one-hots.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = interpret_mode()

    N = jobs[0].ids.shape[0]
    for j in jobs:
        assert j.ids.shape[0] == N, f"gather job {j.name}: item axis mismatch"
    nT = max((N + tb - 1) // tb, 1)
    Np = nT * tb

    plans = []
    ins = []
    in_specs = []
    out_shapes = []
    out_specs = []
    for j in jobs:
        n, P = j.table.shape
        assert len(j.digits) == P
        n_hi = (n + N_LO - 1) // N_LO
        pd = sum(j.digits)
        plans.append((P, n_hi, pd, tuple(j.digits), n))

        ids_p = _pad_axis(j.ids.astype(jnp.int32)[None, :], 1, Np, -1)
        ins.append(ids_p)
        in_specs.append(
            pl.BlockSpec((1, tb), lambda t: (0, t), memory_space=pltpu.VMEM)
        )
        # digit planes of the table: [pd, n_hi, N_LO] bf16
        t32 = j.table.astype(jnp.int32)
        pad_rows = n_hi * N_LO - n
        if pad_rows:
            t32 = jnp.concatenate([t32, jnp.zeros((pad_rows, P), jnp.int32)])
        planes = []
        for p in range(P):
            for d in range(j.digits[p]):
                planes.append((t32[:, p] >> (8 * d)) & 0xFF)
        tabd = jnp.stack(planes, 0).astype(jnp.bfloat16).reshape(pd, n_hi, N_LO)
        ins.append(tabd)
        in_specs.append(
            pl.BlockSpec((pd, n_hi, N_LO), lambda t: (0, 0, 0), memory_space=pltpu.VMEM)
        )
        out_shapes.append(jax.ShapeDtypeStruct((nT, P, tb), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, P, tb), lambda t: (t, 0, 0), memory_space=pltpu.VMEM)
        )

    def kernel(*refs):
        nrefs = refs[: len(ins)]
        orefs = refs[len(ins) :]
        iota_l = jax.lax.broadcasted_iota(jnp.int32, (tb, N_LO), 1)
        ri = 0
        for ji, (P, n_hi, pd, digits, n) in enumerate(plans):
            ids_ref = nrefs[ri]
            tab_ref = nrefs[ri + 1]
            ri += 2
            k = ids_ref[0, :]
            ok = (k >= 0) & (k < n)
            safe = jnp.where(ok, k, 0)
            hi = safe // N_LO
            lo = safe - hi * N_LO
            oki = ok.astype(jnp.int32)
            iota_h = jax.lax.broadcasted_iota(jnp.int32, (tb, n_hi), 1)
            Hi = ((hi[:, None] == iota_h) & (oki[:, None] > 0)).astype(jnp.bfloat16)
            Lo = (lo[:, None] == iota_l).astype(jnp.bfloat16)
            off = 0
            for p in range(P):
                acc = None
                for d in range(digits[p]):
                    sel = jax.lax.dot(
                        Hi, tab_ref[off], preferred_element_type=jnp.float32
                    )  # [tb, N_LO]
                    part = jnp.sum(sel * Lo.astype(jnp.float32), axis=1)
                    acc = part * float(1 << (8 * d)) if acc is None else acc + part * float(1 << (8 * d))
                    off += 1
                orefs[ji][0, p, :] = acc

    outs = run_pallas(pl.pallas_call(
        kernel,
        grid=(nT,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
        name="gather_many",
        # same scoped-vmem headroom as scatter_many (see comment there)
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=32 * 1024 * 1024
        ),
    ), *ins,
        key=("gather_many", tuple(plans), Np, tb, bool(interpret)))

    results = []
    for out, (P, n_hi, pd, digits, n) in zip(outs, plans):
        results.append(out.transpose(1, 0, 2).reshape(P, Np)[:, :N].T)  # [N, P]
    return results


# ---------------------------------------------------------------------------
# wide tables: sort a job's cells, visit only the stretches they fall in
# ---------------------------------------------------------------------------

#: table rows of one stretch a grid step contracts against: N_LO lanes by as
#: many sublane rows, so a stretch is one square one-hot product
STRETCH = N_LO * N_LO


def scatter_sorted(job: Job, tb: int = TILE, interpret: Optional[bool] = None):
    """One job's scatter for a table too wide for scatter_many: f32
    [n, P], the same digit-plane exactness (scatter_many's form; a caller
    that keeps its table in tiles takes scatter_sorted_tiles)."""
    tiles = scatter_sorted_tiles(job, tb=tb, interpret=interpret)
    return tiles.reshape(tiles.shape[0], -1)[:, : job.n].T


def scatter_sorted_tiles(job: Job, tb: int = TILE, interpret: Optional[bool] = None):
    """scatter_sorted's table PLANE-MAJOR IN TILES: f32 [P, n_hi, N_LO], row
    r of the table at [r // N_LO, r % N_LO], the form the kernel writes in,
    so nothing is laid out anew between it and a store kept in the same
    tiles (ops/param.py); n_hi * N_LO is n rounded up to a whole STRETCH,
    and the rows past n read 0.

    The one-hot contraction costs items x table rows, which at 2^20 rows is
    a thousand times the items' own work.  Sorted by row, a tile of items
    touches only a short stretch of the table: the kernel walks the tiles in
    order and contracts each against the STRETCH-row windows between its
    first and its last row, whose count it is handed ahead of the grid
    (scalar prefetch).  A full tile of a dense tick spans one or two windows,
    a sparse tick's tile many short ones; an all-padding tile none.  The cost
    follows the items and the spread of their rows, not the table: what is
    fixed is the sort, and zeroing and writing back the resident table
    (n x planes x 4 bytes of fast memory: core/config.PARAM_MAX_WIDTH).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = interpret_mode()
    R, N0 = job.rows.shape
    per_row = job.values.ndim == 3
    P = job.values.shape[-2]
    digits = tuple(job.digits)
    pd = sum(digits)
    if pd > 4:
        raise ValueError(f"job {job.name}: {pd} digit planes do not pack into one sort payload")
    n = job.n
    n_win = (n + STRETCH - 1) // STRETCH
    n_hi = n_win * N_LO
    sentinel = n_win * STRETCH  # sorts last, matches no window

    rows = job.rows.astype(jnp.int32).reshape(-1)
    vals = job.values.astype(jnp.int32)
    vals = vals.transpose(1, 0, 2) if per_row else jnp.broadcast_to(vals[:, None, :], (P, R, N0))
    packed, shift = jnp.zeros((R * N0,), jnp.int32), 0
    for p in range(P):  # every plane's digits side by side in one word
        packed = packed | (vals[p].reshape(-1) << shift)
        shift += 8 * digits[p]
    N = R * N0
    tb = min(tb, max(256, -(-N // 256) * 256))  # a light tick's one tile is its own size
    nT = max((N + tb - 1) // tb, 1)
    rows = _pad_axis(jnp.where((rows >= 0) & (rows < n), rows, sentinel), 0, nT * tb, sentinel)
    packed = _pad_axis(packed, 0, nT * tb, 0)
    rows, packed = jax.lax.sort([rows, packed], num_keys=1, is_stable=False)

    # the windows each tile spans: from its first row's to its last live row's
    r2 = rows.reshape(nT, tb)
    last = jnp.max(jnp.where(r2 < n, r2, -1), axis=1)
    first_win = jnp.minimum(r2[:, 0], n - 1) // STRETCH
    windows = jnp.where(last >= 0, last // STRETCH - first_win + 1, 0).astype(jnp.int32)

    def kernel(first_ref, windows_ref, rows_ref, vals_ref, out_ref):
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        k = rows_ref[0, :]
        v = vals_ref[0, :]
        hi = k // N_LO
        lo = k - hi * N_LO
        iota_l = jax.lax.broadcasted_iota(jnp.int32, (tb, N_LO), 1)
        iota_h = jax.lax.broadcasted_iota(jnp.int32, (N_LO, tb), 0)
        Lo = (lo[:, None] == iota_l).astype(jnp.bfloat16)
        wide = jnp.concatenate(
            [Lo * ((v >> (8 * d)) & 0xFF)[:, None].astype(jnp.bfloat16) for d in range(pd)],
            axis=1,
        )  # [tb, pd*N_LO]

        def window(w, carry):
            base = pl.multiple_of((first_ref[t] + w) * N_LO, N_LO)
            # a sentinel row's hi is past every window: it matches nothing
            HiT = ((hi[None, :] - base) == iota_h).astype(jnp.bfloat16)
            res = jax.lax.dot(HiT, wide, preferred_element_type=jnp.float32)
            for k2 in range(pd):
                out_ref[k2, pl.ds(base, N_LO), :] += res[:, k2 * N_LO : (k2 + 1) * N_LO]
            return carry

        jax.lax.fori_loop(0, windows_ref[t], window, 0)

    out = run_pallas(pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nT,),
            in_specs=[
                pl.BlockSpec((1, tb), lambda t, *_: (0, t), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, tb), lambda t, *_: (0, t), memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (pd, n_hi, N_LO), lambda t, *_: (0, 0, 0), memory_space=pltpu.VMEM
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((pd, n_hi, N_LO), jnp.float32),
        interpret=interpret,
        name="scatter_sorted",
        compiler_params=pltpu.CompilerParams(
            # the resident table, twice (the pipeline's two buffers), and room
            # for a tile's temporaries; v5e has 128 MB a core
            vmem_limit_bytes=min(2 * pd * n_hi * N_LO * 4 + 24 * 1024 * 1024, 100 * 1024 * 1024)
        ),
    ), first_win.astype(jnp.int32), windows, rows[None, :], packed[None, :],
        key=("scatter_sorted", R, P, per_row, digits, n, nT, tb, bool(interpret)))

    planes, off = [], 0
    for p in range(P):
        acc = out[off]
        for d in range(1, digits[p]):
            acc = acc + out[off + d] * float(1 << (8 * d))
        planes.append(acc)
        off += digits[p]
    return jnp.stack(planes)  # [P, n_hi, N_LO]
