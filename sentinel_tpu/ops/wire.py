"""Packed host↔device wire format for the client tick path.

ROADMAP item 1: the engine decides at ~19 M dps pipelined but the client
path ships ~5 MB of full-width columns per tick and reads verdicts,
telemetry, timeline and hot-set rows back in FOUR separate transfers.
This module is the wire, both ways:

Readback — ONE flat uint32 buffer per tick (``TickOutput.wire``),
packed on-device so only packed bytes ever cross the transport::

    word 0            WIRE_MAGIC (layout/version tag)
    word 1            n_wait   — count of PASS_WAIT rows with wait > 0
    word 2            seg_dropped — fail-closed seg-overflow item count
    word 3            checksum — uint32 sum of words {0,1,2} ∪ payload
    [bitmap]          ceil(B / 10) words; 10 verdicts per word, 3 bits
                      each (verdict codes are 0..6 — core/errors.py)
    [sidecar]         EXC_K row indices then EXC_K wait values (uint32):
                      the top-EXC_K rows of wait_ms.  Covers every
                      PASS_WAIT row whenever n_wait <= EXC_K; a tick
                      with more falls back to reading the full
                      TickOutput.wait_ms column (the one escape hatch:
                      every tick where pacing rules admit most items
                      with a wait — see EXC_K).
    [stats]           N_STATS words — float32 telemetry row, bitcast
    [timeline]        timeline_k * TL_COLS words — float32, bitcast
    [hot]             hotset_k * 2 words — float32, bitcast
    [explain]         2 + explain_k * EXPLAIN_WORDS words — verdict
                      provenance records for up to explain_k BLOCKED
                      rows (obs/explain.py owns the record encoding):
                      ``[n_blocked, sec_sum, records...]`` with its OWN
                      additive checksum ``sec_sum`` seeded with
                      EXPLAIN_MAGIC.  The section sits OUTSIDE the main
                      checksum: a corrupt explain section drops the
                      tick's explanations only (fail-OPEN for the
                      provenance), while main-section corruption still
                      fails every verdict CLOSED.

Optional blocks appear iff the config emits them, so the layout is a
pure function of (EngineConfig, batch shape) — the host unpacks by a
static offset table, no per-tick negotiation.  The additive checksum
detects any single-flipped-byte corruption (the chaos ``corrupt``
action's exact fault model) plus truncation/drop via the length check;
``unpack`` raises :class:`WireDecodeError` and the client fails the tick
CLOSED (runtime/client._resolve_tick).  ``unpack`` validates the main
section ONLY and hands the explain words back raw — decode + sec_sum
validation live in obs/explain.py behind their own chaos failpoint.

Upload — ONE flat uint32 buffer per tick too (``input_layout_for``)::

    word 0            WIRE_IN_MAGIC (layout/version tag)
    word 1..3         now_ms (int32), sys_load, sys_cpu (float32), bitcast
    [acquire]         every AcquireBatch column, in field order
    [complete]        every CompleteBatch column, in field order

Each column starts on an ``IN_ALIGN``-word boundary after the header's
``IN_HDR_SPAN`` and travels at its wire dtype: batch columns whose value range is statically bounded are
narrow (prio/inbound are 0/1 flags, pre_verdict is a verdict code, and
count/success/error are clamped to ``cfg.max_batch_count`` at the
client's batch-build choke point whenever the fused path is active),
four int8 (two int16) rows to a word, which on the host is nothing but a
view of the buffer; ``rt`` is float32 bitcast; the rest is int32, and
``param_hash`` lies lane by lane (``[param_dims, rows]``: the order the
device keeps it in, so nothing is transposed there).  The
client's presort gathers straight into those views, the buffer crosses
in one transfer, and ``unpack_tick_input`` rebuilds the classic int32
batches and the three scalars at the tick's entry.  Dtypes are STATIC
per config (a value-dependent encoding would change the jitted tick's
signature and recompile mid-serving), so this layout too is a pure
function of (EngineConfig, tick shape).  The classic per-column tick
signature still takes narrow columns and widens them (``widen_acquire``
/ ``widen_complete``).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from sentinel_tpu.core.config import EngineConfig

#: layout/version tag — bump when the word layout changes
WIRE_MAGIC = 0x53_E1_71_12
#: verdict codes are 0..6 (core/errors.py) — 3 bits, 10 per uint32 word
VERDICT_BITS = 3
VERDICTS_PER_WORD = 10
_VMASK = (1 << VERDICT_BITS) - 1
#: header words: magic, n_wait, seg_dropped, checksum
HDR_WORDS = 4
#: PASS_WAIT sidecar capacity: 64 rows = 512 B.  That covers a tick whose
#: PASS_WAIT rows are the exception (prioritized occupy, a pacing rule on a
#: resource or two).  A tick with more reads the full wait column instead, in
#: a second blocking transfer (4 B a row of the tick's shape): under a
#: RATE_LIMITER rule on every resource that is EVERY tick (perfbench's
#: rate-limiter-pacing: nine items in ten are PASS_WAIT).  The client counts
#: those ticks in sentinel_wire_wait_overflow_ticks_total and, tracer on,
#: times each read in tick.readback's wait_read_ns / wait_read_bytes beside
#: the header's n_wait as wait_rows
EXC_K = 64
#: seed of the explain section's own checksum — distinct from the main
#: checksum so a flip in either section is attributed to that section
EXPLAIN_MAGIC = 0x0B_5E_CF_A1
#: uint32 words per explain record (obs/explain.py packs/unpacks them)
EXPLAIN_WORDS = 4


class WireDecodeError(Exception):
    """The fused readback failed validation (bad magic, wrong length, or
    checksum mismatch).  The client turns this into a fail-CLOSED tick."""


class WireLayout(NamedTuple):
    """Static offset table for one (config, batch shape) pair."""

    b: int  # batch rows the bitmap covers
    exc_k: int  # sidecar rows (min(EXC_K, b))
    n_stats: int  # telemetry words (0 = block absent)
    tl_rows: int  # timeline rows (0 = block absent)
    tl_cols: int
    hot_rows: int  # hot-candidate rows (0 = block absent)
    expl_k: int  # explain record rows (0 = block absent)
    off_bitmap: int
    n_bitmap: int
    off_exc: int
    off_stats: int
    off_tl: int
    off_hot: int
    off_expl: int  # == total when the explain block is absent
    total: int  # whole-buffer length in words


def layout_for(cfg: EngineConfig, b: int) -> WireLayout:
    """The wire layout this config emits at batch shape ``b`` — must
    mirror the engine's emission conditions exactly (ops/engine.tick)."""
    from sentinel_tpu.ops import engine as E

    n_stats = E.N_STATS if cfg.device_telemetry else 0
    tl_rows = E.timeline_k(cfg) if cfg.device_telemetry else 0
    # hot candidates clamp to the batch shape (engine._device_hot_candidates)
    hot_rows = min(E.hotset_k(cfg), b)
    expl_k = min(E.explain_k(cfg), b)
    exc_k = min(EXC_K, b)
    n_bitmap = -(-b // VERDICTS_PER_WORD)
    off_bitmap = HDR_WORDS
    off_exc = off_bitmap + n_bitmap
    off_stats = off_exc + 2 * exc_k
    off_tl = off_stats + n_stats
    off_hot = off_tl + tl_rows * E.TL_COLS
    off_expl = off_hot + hot_rows * 2
    total = off_expl + (2 + expl_k * EXPLAIN_WORDS if expl_k else 0)
    return WireLayout(
        b=b,
        exc_k=exc_k,
        n_stats=n_stats,
        tl_rows=tl_rows,
        tl_cols=E.TL_COLS,
        hot_rows=hot_rows,
        expl_k=expl_k,
        off_bitmap=off_bitmap,
        n_bitmap=n_bitmap,
        off_exc=off_exc,
        off_stats=off_stats,
        off_tl=off_tl,
        off_hot=off_hot,
        off_expl=off_expl,
        total=total,
    )


# -- device side (inside the jitted tick) -----------------------------------


def pack_tick_output(
    cfg: EngineConfig,
    verdict,  # int8 [B]
    wait_ms,  # int32 [B]
    seg_dropped,  # int32 scalar or plain 0
    stats,  # float32 [N_STATS] or None
    res_stats,  # float32 [K, TL_COLS] or None
    hot,  # float32 [K, 2] or None
    expl=None,  # (n_blocked uint32 scalar, records uint32 [K, 4]) or None
):
    """Pack one tick's outputs into the flat uint32 wire buffer.

    Pure jnp (element-wise shifts + one top_k + concatenates) — cheap on
    any backend against a tick that already streamed the full batch, and
    it keeps the single-readback property on CPU tests and TPU alike."""
    b = verdict.shape[0]
    lo = layout_for(cfg, b)
    v = verdict.astype(jnp.uint32)
    v = jnp.pad(v, (0, lo.n_bitmap * VERDICTS_PER_WORD - b))
    shifts = (jnp.arange(VERDICTS_PER_WORD, dtype=jnp.uint32) * VERDICT_BITS)
    # lanes occupy disjoint bit ranges, so the OR-fold is a plain sum
    bitmap = jnp.sum(
        v.reshape(lo.n_bitmap, VERDICTS_PER_WORD) << shifts[None, :],
        axis=1,
        dtype=jnp.uint32,
    )
    n_wait = jnp.sum(wait_ms > 0).astype(jnp.uint32)
    # top-K by wait value: whenever n_wait <= exc_k this captures EVERY
    # wait row (the rest read 0 and the host filters them out)
    wv, wi = jax.lax.top_k(wait_ms, lo.exc_k)
    parts = [bitmap, wi.astype(jnp.uint32), wv.astype(jnp.uint32)]
    if lo.n_stats:
        parts.append(jax.lax.bitcast_convert_type(stats, jnp.uint32))
    if lo.tl_rows:
        parts.append(
            jax.lax.bitcast_convert_type(res_stats, jnp.uint32).reshape(-1)
        )
    if lo.hot_rows:
        parts.append(jax.lax.bitcast_convert_type(hot, jnp.uint32).reshape(-1))
    payload = jnp.concatenate(parts)
    magic = jnp.uint32(WIRE_MAGIC)
    dropped = jnp.asarray(seg_dropped).astype(jnp.uint32).reshape(())
    # the MAIN checksum stops at off_expl: the explain section carries
    # its own sec_sum so its corruption fails OPEN (provenance dropped)
    # without poisoning the verdict path's fail-CLOSED check
    cksum = (
        magic
        + n_wait
        + dropped
        + jnp.sum(payload, dtype=jnp.uint32)
    )
    out = [jnp.stack([magic, n_wait, dropped, cksum]), payload]
    if lo.expl_k:
        n_blocked, records = expl
        n_blocked = jnp.asarray(n_blocked).astype(jnp.uint32).reshape(())
        flat = records.astype(jnp.uint32).reshape(-1)
        sec_sum = (
            jnp.uint32(EXPLAIN_MAGIC)
            + n_blocked
            + jnp.sum(flat, dtype=jnp.uint32)
        )
        out.append(jnp.stack([n_blocked, sec_sum]))
        out.append(flat)
    return jnp.concatenate(out)


# -- host side (resolver thread) --------------------------------------------


class WireFrame(NamedTuple):
    """One decoded tick readback (host numpy)."""

    verdict: np.ndarray  # int8 [B]
    wait: Optional[np.ndarray]  # int32 [B]; None = sidecar overflowed
    n_wait: int
    seg_dropped: int
    stats: Optional[np.ndarray]  # float32 [N_STATS]
    res_stats: Optional[np.ndarray]  # float32 [K, TL_COLS]
    hot: Optional[np.ndarray]  # float32 [K, 2]
    expl: Optional[np.ndarray]  # RAW uint32 explain words (unvalidated)


def unpack(data: bytes, lo: WireLayout) -> WireFrame:
    """Validate and unpack one fused readback.

    Raises :class:`WireDecodeError` on any integrity failure — length
    (drop/short_read), magic, or checksum (any single-byte corruption);
    the caller fails the tick CLOSED rather than fanning out garbage
    verdicts."""
    if len(data) != lo.total * 4:
        raise WireDecodeError(
            f"wire length {len(data)} B != layout {lo.total * 4} B"
        )
    buf = np.frombuffer(data, dtype=np.uint32)
    if int(buf[0]) != WIRE_MAGIC:
        raise WireDecodeError(f"bad wire magic {int(buf[0]):#x}")
    # main checksum stops at off_expl — the explain section fails open
    # on its own sec_sum (obs/explain.decode_records), never the tick
    expect = (
        int(buf[0]) + int(buf[1]) + int(buf[2])
        + int(np.sum(buf[HDR_WORDS : lo.off_expl], dtype=np.uint64))
    ) & 0xFFFFFFFF
    if int(buf[3]) != expect:
        raise WireDecodeError(
            f"wire checksum mismatch ({int(buf[3]):#x} != {expect:#x})"
        )
    n_wait = int(buf[1])
    seg_dropped = int(buf[2])
    words = buf[lo.off_bitmap : lo.off_bitmap + lo.n_bitmap]
    shifts = np.arange(VERDICTS_PER_WORD, dtype=np.uint32) * VERDICT_BITS
    verdict = (
        ((words[:, None] >> shifts[None, :]) & _VMASK)
        .reshape(-1)[: lo.b]
        .astype(np.int8)
    )
    wait: Optional[np.ndarray]
    if n_wait == 0:
        wait = np.zeros(lo.b, np.int32)
    elif n_wait <= lo.exc_k:
        idx = buf[lo.off_exc : lo.off_exc + lo.exc_k].astype(np.int64)
        vals = buf[lo.off_exc + lo.exc_k : lo.off_stats].astype(np.int32)
        live = vals > 0
        if int(idx[live].max(initial=0)) >= lo.b:
            raise WireDecodeError("wait sidecar row index out of range")
        wait = np.zeros(lo.b, np.int32)
        wait[idx[live]] = vals[live]
    else:
        wait = None  # overflow: caller reads the full wait_ms column
    stats = res_stats = hot = None
    if lo.n_stats:
        stats = buf[lo.off_stats : lo.off_tl].view(np.float32)
    if lo.tl_rows:
        res_stats = (
            buf[lo.off_tl : lo.off_hot].view(np.float32)
            .reshape(lo.tl_rows, lo.tl_cols)
        )
    if lo.hot_rows:
        hot = (
            buf[lo.off_hot : lo.off_expl].view(np.float32)
            .reshape(lo.hot_rows, 2)
        )
    expl = buf[lo.off_expl : lo.total].copy() if lo.expl_k else None
    return WireFrame(
        verdict=verdict,
        wait=wait,
        n_wait=n_wait,
        seg_dropped=seg_dropped,
        stats=stats,
        res_stats=res_stats,
        hot=hot,
        expl=expl,
    )


# -- narrow upload dtypes ----------------------------------------------------


def _count_dtype(cfg: EngineConfig):
    """Narrowest dtype that carries count-valued columns exactly.  The
    client clamps counts to cfg.max_batch_count at batch build ONLY when
    the fused path is active (engine._use_fused — static per process),
    so narrowing is sound exactly then; the unfused paths stay exact to
    65535 and keep int32."""
    from sentinel_tpu.ops.engine import _use_fused

    if not _use_fused(cfg):
        return np.int32
    if cfg.max_batch_count <= 0xFF:
        return np.uint8
    if cfg.max_batch_count <= 0x7FFF:
        return np.int16
    return np.int32


def acquire_wire_dtypes(cfg: EngineConfig) -> dict:
    """field -> numpy dtype for AcquireBatch columns narrower than int32
    under packed_wire.  prio/inbound are 0/1 flags and pre_verdict is a
    verdict code (0..6) — always int8-safe; count follows the clamp."""
    if not cfg.packed_wire:
        return {}
    out = {
        "prio": np.int8,
        "inbound": np.int8,
        "pre_verdict": np.int8,
    }
    cd = _count_dtype(cfg)
    if cd is not np.int32:
        out["count"] = cd
    return out


def complete_wire_dtypes(cfg: EngineConfig) -> dict:
    """field -> numpy dtype for CompleteBatch columns narrower than int32
    under packed_wire (same envelope as the acquire side)."""
    if not cfg.packed_wire:
        return {}
    out = {"inbound": np.int8}
    cd = _count_dtype(cfg)
    if cd is not np.int32:
        out["success"] = cd
        out["error"] = cd
    return out


def _widen(batch, fields):
    reps = {}
    for f in fields:
        x = getattr(batch, f)
        if x.dtype != jnp.int32:
            reps[f] = x.astype(jnp.int32)
    return batch._replace(**reps) if reps else batch


def widen_acquire(acq):
    """Restore int32 for narrow-uploaded acquire columns at tick entry —
    everything downstream of tick() sees the classic dtypes."""
    return _widen(acq, ("count", "prio", "inbound", "pre_verdict"))


def widen_complete(comp):
    return _widen(comp, ("inbound", "success", "error"))


# -- the input wire: one upload a tick ----------------------------------------

#: layout/version tag of the input buffer — bump when its layout changes
WIRE_IN_MAGIC = 0x53_E1_71_1A
#: header words: magic, now_ms, sys_load, sys_cpu
IN_HDR_WORDS = 4
#: the header has the buffer's first (8, 128) tile of the device's 1-D
#: 32-bit layout to itself, and every column starts on a multiple of
#: IN_ALIGN words after it: at the served shape (131,072 rows) every
#: column then starts on a tile, and the slice the unpack takes of it
#: needs no shifting copy.  IN_ALIGN divides the light shape's 256 rows,
#: so a side of more rows is longer and the buffer's length alone tells
#: the tick shapes apart (input_layout_of)
IN_HDR_SPAN = 1024
IN_ALIGN = 256


class InputColumn(NamedTuple):
    """Where one batch column lies in the input buffer."""

    field: str  # AcquireBatch / CompleteBatch field name
    off: int  # first word
    words: int  # words its rows occupy
    dtype: np.dtype  # wire dtype (the host view's dtype)
    shape: tuple  # the batch column's: (rows,) or (rows, param_dims)
    fill: float  # what a padding row, and every row of an idle side, holds


class InputLayout(NamedTuple):
    """Static offset table of one tick's input for (config, tick shape)."""

    b: int  # acquire rows
    b2: int  # completion rows
    acq: Tuple[InputColumn, ...]  # in AcquireBatch field order
    comp: Tuple[InputColumn, ...]  # in CompleteBatch field order
    total: int  # whole-buffer length in words

    @property
    def nbytes(self) -> int:
        return self.total * 4


#: rows a side of the light tick shape, and the share of the batch a side
#: of the middle one has (see tick_shapes)
LIGHT_ROWS = 256
MIDDLE_DIV = 4


def tick_shapes(cfg: EngineConfig) -> Tuple[Tuple[int, int], ...]:
    """The (acquire rows, completion rows) shapes a client ticks at, in
    rising order: light, middle (a quarter of the batch), full.  A tick
    runs at the smallest that holds its live rows, since the device pays
    for the padded rows and not for the live ones.  A fixed ladder, both
    sides sized together, so every shape compiles during warm-up and
    serving compiles none; duplicates are dropped, so a batch under
    ``LIGHT_ROWS * MIDDLE_DIV`` rows has the two shapes light and full,
    and one of at most ``LIGHT_ROWS`` has one."""
    b, b2 = cfg.batch_size, cfg.complete_batch_size
    light = (min(LIGHT_ROWS, b), min(LIGHT_ROWS, b2))
    middle = (max(light[0], b // MIDDLE_DIV), max(light[1], b2 // MIDDLE_DIV))
    return tuple(dict.fromkeys((light, middle, (b, b2))))


def tick_shape_for(cfg: EngineConfig, n_acq: int, n_comp: int) -> Tuple[int, int]:
    """The smallest tick shape that holds ``n_acq`` acquire rows and
    ``n_comp`` completion rows: what the tick holds decides, and nothing
    remembered from the tick before."""
    shapes = tick_shapes(cfg)
    return next(
        (s for s in shapes if n_acq <= s[0] and n_comp <= s[1]), shapes[-1]
    )


def acquire_fills(cfg: EngineConfig) -> tuple:
    """``(field, fill)`` in AcquireBatch field order: what a padding row
    holds, and every row of an idle side (``engine.empty_acquire``)."""
    trash = cfg.trash_row
    return (
        ("res", trash), ("count", 0), ("prio", 0), ("origin_id", -1),
        ("origin_node", trash), ("ctx_node", trash), ("ctx_name", -1),
        ("inbound", 0), ("param_hash", 0), ("pre_verdict", 0),
    )


def complete_fills(cfg: EngineConfig) -> tuple:
    """``(field, fill)`` in CompleteBatch field order."""
    trash = cfg.trash_row
    return (
        ("res", trash), ("origin_node", trash), ("ctx_node", trash),
        ("inbound", 0), ("rt", 0.0), ("success", 0), ("error", 0),
        ("param_hash", 0),
    )


def input_layout_for(cfg: EngineConfig, b: int, b2: int) -> InputLayout:
    """The input buffer's layout at tick shape ``(b, b2)``: the header,
    then each side's columns at their wire dtypes, each aligned."""
    m = cfg.param_dims
    off = IN_HDR_SPAN

    def side(rows, wide, fills):
        nonlocal off
        cols = []
        for field, fill in fills:
            dt = np.dtype(np.float32 if field == "rt" else wide.get(field, np.int32))
            shape = (rows, m) if field == "param_hash" else (rows,)
            words = -(-rows * (m if field == "param_hash" else 1) * dt.itemsize // 4)
            cols.append(InputColumn(field, off, words, dt, shape, fill))
            off += -(-words // IN_ALIGN) * IN_ALIGN
        return tuple(cols)

    acq = side(b, acquire_wire_dtypes(cfg), acquire_fills(cfg))
    comp = side(b2, complete_wire_dtypes(cfg), complete_fills(cfg))
    return InputLayout(b=b, b2=b2, acq=acq, comp=comp, total=off)


def input_layout_of(cfg: EngineConfig, words: int) -> InputLayout:
    """The layout of an input buffer of ``words`` words: one of the tick
    shapes'.  The jitted tick finds its shape by the buffer alone."""
    for b, b2 in tick_shapes(cfg):
        lo = input_layout_for(cfg, b, b2)
        if lo.total == words:
            return lo
    raise ValueError(
        f"input wire of {words} words fits no tick shape of this config"
    )


class InputBuffer:
    """One tick's input on the host: the flat buffer and a view of it a
    column, at the column's wire dtype.  Whoever builds the tick writes
    the views; the buffer is what crosses to the device.  A two-
    dimensional column (param_hash) lies lane by lane, so its view is the
    batch column's transpose, ``(param_dims, rows)``."""

    __slots__ = ("layout", "buf", "acq", "comp", "_now", "_sys")

    def __init__(self, lo: InputLayout):
        self.layout = lo
        self.buf = np.zeros(lo.total, np.uint32)
        self.buf[0] = WIRE_IN_MAGIC
        self._now = self.buf[1:2].view(np.int32)
        self._sys = self.buf[2:IN_HDR_WORDS].view(np.float32)

        def views(cols):
            return {
                c.field: self.buf[c.off : c.off + c.words]
                .view(c.dtype)[: int(np.prod(c.shape))]
                .reshape(c.shape[::-1])
                for c in cols
            }

        self.acq = views(lo.acq)
        self.comp = views(lo.comp)

    def idle_acquire(self) -> None:
        """An idle side is its fill."""
        for c in self.layout.acq:
            self.acq[c.field].fill(c.fill)

    def idle_complete(self) -> None:
        for c in self.layout.comp:
            self.comp[c.field].fill(c.fill)

    def set_header(self, now_ms: int, sys_load: float, sys_cpu: float) -> None:
        self._now[0] = now_ms
        self._sys[0] = sys_load
        self._sys[1] = sys_cpu


def unpack_tick_input(buf, lo: InputLayout):
    """The device half: one flat uint32 buffer -> ``(AcquireBatch,
    CompleteBatch, now_ms, sys_load, sys_cpu)`` as ``engine.tick`` takes
    them, every narrow column widened to int32 — slices and bitcasts
    only."""
    from sentinel_tpu.ops.engine import AcquireBatch, CompleteBatch

    def col(c: InputColumn):
        w = jax.lax.slice(buf, (c.off,), (c.off + c.words,))
        x = jax.lax.bitcast_convert_type(w, c.dtype)
        if c.dtype.itemsize < 4:
            # [words, rows a word] in the host's byte order -> rows
            x = x.reshape(-1)[: c.shape[0]].astype(jnp.int32)
        if len(c.shape) == 2:
            # lane by lane on the wire: the device keeps [rows, few] with
            # the rows along its lanes too, so this moves nothing, where
            # row-major rows would have to be de-interleaved (1 ms a tick)
            x = x.reshape(c.shape[::-1])
            x = jnp.stack([x[k] for k in range(c.shape[1])], axis=1)
        return x

    hdr = buf[:IN_HDR_WORDS]
    return (
        AcquireBatch(**{c.field: col(c) for c in lo.acq}),
        CompleteBatch(**{c.field: col(c) for c in lo.comp}),
        jax.lax.bitcast_convert_type(hdr[1], jnp.int32),
        jax.lax.bitcast_convert_type(hdr[2], jnp.float32),
        jax.lax.bitcast_convert_type(hdr[3], jnp.float32),
    )
