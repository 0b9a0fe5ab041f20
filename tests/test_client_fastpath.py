"""Production client on the fast engine path (VERDICT r4 #1).

The client presorts batches by the segment keys host-side (one call of
native/ring.presort in _run_tick, bit-identical to np.lexsort + np.take) and
maps verdicts back through the inverse permutation; seg_u
grows automatically when traffic overflows the compacted capacity; fail-
closed overflow drops are surfaced loudly.  On CPU the fused kernels run
in Pallas interpret mode — semantics only (device speed is
perfbench/run.py's job).
"""

from __future__ import annotations

import threading
from types import SimpleNamespace

import numpy as np
import pytest

from sentinel_tpu.core import errors as ERR
from sentinel_tpu.core.config import small_engine_config
from sentinel_tpu.core.rules import FlowRule
from sentinel_tpu.ops import wire as WIRE
from sentinel_tpu.runtime.client import SentinelClient
from sentinel_tpu.utils.time_source import TimeSource, VirtualTimeSource

# single-rule lanes so the segment CHECK phase engages too (engine gates
# seg_checks on *_rules_per_resource == 1)
SEG = dict(
    use_mxu_tables=True,
    fused_effects=True,
    seg_effects=True,
    flow_rules_per_resource=1,
    degrade_rules_per_resource=1,
    param_rules_per_resource=1,
)


def _mk(vt, **kw):
    cfg = small_engine_config(**{**SEG, **kw})
    return SentinelClient(cfg=cfg, time_source=vt, mode="sync")


def test_presorted_verdicts_map_back_to_submission_order(vt):
    """Verdicts must return to the REQUEST that submitted them, not to the
    sorted position — intern ids out of submission order so the presort
    permutation is nontrivial."""
    c = _mk(vt)
    # intern in an order unrelated to the submission order below
    for name in ("zz", "blocked", "open", "aa"):
        c.registry.resource_id(name)
    c.flow_rules.load(
        [
            FlowRule(resource="blocked", count=0.0),
            FlowRule(resource="open", count=1000.0),
        ]
    )
    resources = ["open", "blocked", "zz", "blocked", "open", "aa", "blocked"]
    out = c.check_batch(resources)
    for name, (v, _w) in zip(resources, out):
        if name == "blocked":
            assert v == ERR.BLOCK_FLOW, (name, v)
        else:
            assert v == ERR.PASS, (name, v)


@pytest.mark.jitted  # many small ticks: execution-bound, compiles amortize
def test_seg_client_matches_plain_client():
    """Same shuffled workload (origins + counts) through the seg-path
    client and the plain-path client: identical verdict sequences."""
    rng = np.random.default_rng(11)
    names = [f"res-{i}" for i in range(24)]
    batches = []
    for _ in range(4):
        k = rng.integers(8, 40)
        rs = [names[i] for i in rng.integers(0, len(names), k)]
        og = [("peer" if rng.random() < 0.3 else "") for _ in rs]
        cn = [int(rng.integers(1, 3)) for _ in rs]
        batches.append((rs, og, cn))

    def run(seg: bool):
        vt = VirtualTimeSource(start_ms=5_000)
        kw = dict(SEG) if seg else {}
        c = SentinelClient(
            cfg=small_engine_config(**kw), time_source=vt, mode="sync"
        )
        # shuffled interning order -> nontrivial presort permutation
        for n in reversed(names):
            c.registry.resource_id(n)
        c.flow_rules.load(
            [FlowRule(resource=n, count=3.0) for n in names[:12]]
        )
        got = []
        for rs, og, cn in batches:
            got.append(c.check_batch(rs, origins=og, counts=cn))
            vt.advance(50)
        return got

    assert run(seg=True) == run(seg=False)


def test_seg_u_auto_resize_grows_capacity(vt):
    """Persistent segment-capacity overflow grows seg_u (tick hot-swap);
    verdicts stay exact throughout via the seg_fallback safety net."""
    c = _mk(vt, seg_u=8, seg_fallback=True)
    names = [f"r{j}" for j in range(40)]
    for i in range(6):
        out = c.check_batch(names)
        assert all(v == ERR.PASS for v, _ in out), f"tick {i}"
        vt.advance(10)
    assert c.cfg.seg_u > 8, "seg_u should have grown past the observed peak"
    # the swapped tick keeps serving correctly
    out = c.check_batch(names)
    assert all(v == ERR.PASS for v, _ in out)


def test_seg_overflow_drop_surfaced_and_fails_closed(vt):
    """seg_fallback=False + undersized seg_u: overflow items BLOCK (never
    pass unchecked), the drop counter advances, and the block log gets the
    loud __seg_overflow__ row.  Resize inhibited to observe the drop path
    itself (normally the first overflow triggers the resize)."""
    c = _mk(vt, seg_u=8, seg_fallback=False)
    c._seg_resizing = True  # pin capacity for this test

    logged = []

    class _BL:
        def log(self, ts, res, exc, origin="", count=1):
            logged.append((res, exc, count))

        def flush(self):
            pass

    c.block_log = _BL()
    out = c.check_batch([f"r{j}" for j in range(40)])
    vs = [v for v, _ in out]
    assert c.seg_dropped_total > 0
    assert any(v == ERR.BLOCK_SYSTEM for v in vs), "overflow must fail closed"
    assert any(r == "__seg_overflow__" for r, _e, _n in logged)
    # low-id segments fit the capacity and keep passing
    assert vs[0] == ERR.PASS


def test_block_api_matches_object_api(vt):
    """check_batch_ids (column arrays, zero per-item Python) must decide
    exactly like the per-object check_batch on the same workload — and the
    block path rides the presorted seg engine here."""
    c = _mk(vt)
    names = [f"b{i}" for i in range(20)]
    ids = np.array([c.registry.resource_id(n) for n in names], np.int32)
    c.flow_rules.load([FlowRule(resource=n, count=2.0) for n in names[:10]])

    rng = np.random.default_rng(3)
    idx = rng.integers(0, len(names), 50)
    obj_out = c.check_batch([names[i] for i in idx])

    vt2 = VirtualTimeSource(start_ms=1_000)
    c2 = _mk(vt2)
    for n in names:
        c2.registry.resource_id(n)
    c2.flow_rules.load([FlowRule(resource=n, count=2.0) for n in names[:10]])
    verd, wait = c2.check_batch_ids(ids[idx])
    assert [int(v) for v in verd] == [v for v, _ in obj_out]
    assert all(int(w) == 0 for w in wait)


def test_block_api_spans_multiple_ticks(vt):
    """Blocks larger than the batch size stream through several ticks and
    still resolve one future with every verdict in submission order."""
    c = _mk(vt)  # batch_size = 64
    names = [f"s{i}" for i in range(8)]
    ids = np.array([c.registry.resource_id(n) for n in names], np.int32)
    c.flow_rules.load([FlowRule(resource=names[0], count=0.0)])
    res = np.tile(ids, 40)  # 320 items > 64-batch
    verd, _w = c.check_batch_ids(res)
    assert len(verd) == 320
    blocked = verd[res == ids[0]]
    passed = verd[res != ids[0]]
    assert (blocked == ERR.BLOCK_FLOW).all()
    assert (passed == ERR.PASS).all()


@pytest.mark.jitted  # many small ticks: execution-bound, compiles amortize
def test_pipelined_resolution_matches_inline():
    """pipeline_depth > 0 defers verdict readback behind dispatch; the
    resolved verdicts must be identical to depth-0 operation."""
    names = [f"p{i}" for i in range(12)]

    def run(depth):
        vt = VirtualTimeSource(start_ms=2_000)
        c = SentinelClient(
            cfg=small_engine_config(**SEG),
            time_source=vt,
            mode="sync",
            pipeline_depth=depth,
        )
        ids = np.array([c.registry.resource_id(n) for n in names], np.int32)
        c.flow_rules.load([FlowRule(resource=names[0], count=3.0)])
        outs = []
        for t in range(3):
            # several blocks queued at once so the drain loop actually
            # runs multiple ticks back-to-back (where deferral engages)
            futs = [
                c.submit_block(np.tile(ids, 8))  # 96 items
                for _ in range(3)
            ]
            outs.append([tuple(map(int, f.result(timeout=30)[0][:8])) for f in futs])
            vt.advance(25)
        return outs

    assert run(0) == run(2)


def test_seg_static_ranks_auto_specialization(vt):
    """The client flips seg_static_ranks on when every flow rule is
    DIRECT/default-limitApp (the presort makes the contract hold), and
    back off when a rule stops qualifying."""
    from sentinel_tpu.core.rules import STRATEGY_RELATE

    c = _mk(vt)
    names = ["sa", "sb"]
    for n in names:
        c.registry.resource_id(n)
    c.flow_rules.load([FlowRule(resource="sa", count=5.0)])
    assert c.cfg.seg_static_ranks
    out = c.check_batch(["sa", "sb", "sa"])
    assert [v for v, _ in out] == [0, 0, 0]  # ERR.PASS == 0
    c.flow_rules.load(
        [FlowRule(resource="sa", count=5.0, strategy=STRATEGY_RELATE,
                  ref_resource="sb")]
    )
    assert not c.cfg.seg_static_ranks
    out = c.check_batch(["sa", "sb"])
    assert all(v == ERR.PASS for v, _ in out)


def test_platform_engine_config_detects_backend(monkeypatch):
    import sentinel_tpu.core.config as C

    monkeypatch.setattr(C, "_backend_is_tpu", lambda: True)
    cfg = C.platform_engine_config()
    assert cfg.use_mxu_tables and cfg.fused_effects and cfg.seg_effects
    assert cfg.seg_fallback  # safety net stays ON by default
    # explicit overrides win over detection
    cfg_o = C.platform_engine_config(seg_effects=False, fused_effects=False)
    assert cfg_o.use_mxu_tables and not cfg_o.seg_effects

    monkeypatch.setattr(C, "_backend_is_tpu", lambda: False)
    cfg2 = C.platform_engine_config()
    assert not (cfg2.use_mxu_tables or cfg2.fused_effects or cfg2.seg_effects)


def test_a_wire_buffer_is_its_ticks_own_until_the_tick_has_resolved(vt):
    """The tick's input crosses from its host buffer uncopied, and the
    transfer may read that buffer after the dispatch (on the CPU backend an
    aligned buffer IS the device array): so with pipeline_depth ticks
    dispatched and unresolved no buffer is written again, a resolved tick's
    buffer is the next one lent, and a tick the watchdog failed over, which
    may still run on the device, never gives its buffer back."""
    depth = 3
    c = SentinelClient(
        cfg=small_engine_config(**SEG), time_source=vt, mode="sync",
        pipeline_depth=depth,
    )
    try:  # never started: the ticks below are driven by hand
        rng = np.random.default_rng(30)

        def tick():
            acq, blocks, comp = _mixed_tick(c.cfg, rng, 4, 30, 30)
            return c._run_tick(acq, comp, None, blocks=blocks)

        ticks = [tick() for _ in range(depth)]
        bufs = [p.wire_in for p in ticks]
        sent = [wb.buf.copy() for wb in bufs]
        for i, x in enumerate(bufs):
            assert x.buf.nbytes == x.layout.nbytes
            assert not any(np.shares_memory(x.buf, y.buf) for y in bufs[:i])
        # one more while all of them are unresolved: a buffer of its own,
        # and not a word of theirs was written
        extra = tick()
        assert not any(extra.wire_in is wb for wb in bufs)
        for wb, was in zip(bufs, sent):
            assert wb.buf.tobytes() == was.tobytes()
        # resolved: its buffer is the next one lent
        first = bufs[0]
        c._resolve_tick(ticks[0])
        assert ticks[0].wire_in is None
        assert c._wire_free[first.layout] == [first]
        again = tick()
        assert again.wire_in is first and c._wire_free[first.layout] == []
        # failed over by the watchdog while its resolver is still out: the
        # resolver comes home later, loses the claim, and the buffer stays
        # with the tick
        failed = ticks[1]
        assert c._claim_tick(failed, "failed")
        c._fail_tick(failed)
        c._resolve_tick(failed)
        assert failed.wire_in is bufs[1]
        for p in (*ticks[2:], extra, again):
            c._resolve_tick(p)
        free = c._wire_free[first.layout]
        assert bufs[1] not in free and len(free) == depth
    finally:
        c.stop()


@pytest.mark.jitted  # the POINT: no disable_jit — pin jit-only buffer behavior
def test_jitted_const_column_cache_and_empty_batches(vt):
    """ADVICE r5 low #4: the jit-only buffer failure class ('Execution
    supplied N buffers but compiled program expected N+1', and an input
    buffer lent again while a tick still reads it) only manifests under
    REAL jit dispatch, which the eager-heavy fixture normally bypasses.
    Interleave empty ticks (both sides their fill), all-default batches and
    distinct-value batches through one jitted tick, every one out of a
    reused input buffer, and require exact verdicts throughout."""
    c = _mk(vt)
    names = [f"j{i}" for i in range(8)]
    for n in names:
        c.registry.resource_id(n)
    c.flow_rules.load(
        [FlowRule(resource=names[0], count=0.0),
         FlowRule(resource=names[1], count=1000.0)]
    )

    # repeated EMPTY batches: tick_once with nothing queued sends both
    # sides as their fill, call after call
    for _ in range(3):
        c.tick_once()
        vt.advance(10)

    for round_ in range(3):
        # all-default columns (count=1, no origin/ctx/params): every
        # column except res and count equals its fill
        out = c.check_batch([names[0], names[1], names[2]])
        assert [v for v, _ in out] == [
            ERR.BLOCK_FLOW, ERR.PASS, ERR.PASS,
        ], f"round {round_}"
        # distinct values in the same columns of the same executable
        out2 = c.check_batch(
            [names[1], names[1]], counts=[2, 3], origins=["peer", ""]
        )
        assert [v for v, _ in out2] == [ERR.PASS, ERR.PASS]
        # back to empty: nothing of the last batch may be left behind
        c.tick_once()
        vt.advance(25)

    # completions ride the jitted tick too (exit path buffers)
    e = c.entry(names[3])
    e.exit()
    c.tick_once()


# -- the presort of _run_tick against the parent's (PR 25) -------------------
#
# The parent sorted all B padded rows with a comparison sort and permuted each
# column with np.take (completions: x[order] over the n drained rows).  Its few
# lines are the reference here; the one-call presort must upload the same bytes.


def _parent_presort_acquire(keys, cols, ph):
    order = np.lexsort(tuple(reversed(keys))).astype(np.int32)
    inv = np.empty_like(order)
    inv[order] = np.arange(order.shape[0], dtype=np.int32)
    return inv, [np.take(x, order) for x in cols], np.take(ph, order, axis=0)


def _mixed_tick(cfg, rng, n_obj, n_blk, n_comp):
    """Object requests + two array blocks + completions, seeded: ids out of
    order, origins, contexts, counts over the clamp, hot-param lanes, a few
    live rows on the trash row."""
    from sentinel_tpu.runtime.client import AcquireRequest, ArrayBlock

    trash, M = cfg.trash_row, cfg.param_dims

    def ids(n):
        x = rng.integers(1, 48, n).astype(np.int32)
        x[rng.random(n) < 0.05] = trash
        return x

    acq = [
        AcquireRequest(
            res=int(r), count=int(rng.integers(1, 4)), prio=int(rng.integers(0, 2)),
            origin_id=int(rng.integers(-1, 3)), origin_node=int(rng.integers(50, 54)),
            ctx_node=int(rng.integers(54, 58)), ctx_name=int(rng.integers(-1, 2)),
            inbound=int(rng.integers(0, 2)),
            param_hash=tuple(int(v) for v in rng.integers(0, 9, M)),
        )
        for r in ids(n_obj)
    ]
    half = n_blk // 2
    blocks = []
    for take in (half, n_blk - half):
        blk = ArrayBlock(
            res=ids(take + 3),
            count=rng.integers(1, 70000, take + 3).astype(np.int32),
            origin_id=rng.integers(-1, 3, take + 3).astype(np.int32),
            origin_node=rng.integers(50, 54, take + 3).astype(np.int32),
            inbound=rng.integers(0, 2, take + 3).astype(np.int32),
            param_hash=rng.integers(0, 9, (take + 3, M)).astype(np.int32),
            unresolved=take, verdicts=np.zeros(take + 3, np.int8),
            waits=np.zeros(take + 3, np.int32),
        )
        blocks.append((blk, 3, take))  # a piece: offset 3 into the block
    i32 = lambda lo, hi: rng.integers(lo, hi, n_comp).astype(np.int32)
    comp = (
        ids(n_comp), i32(1, 70000), i32(50, 54), i32(54, 58), i32(4, 6),
        rng.random(n_comp).astype(np.float32), i32(0, 3), np.zeros(n_comp, np.int32),
        i32(0, 9), i32(0, 9), i32(0, 9), i32(0, 9),
    )
    return acq, blocks, comp


@pytest.mark.parametrize("fill", ["third", "full"])
def test_run_tick_uploads_the_parents_presorted_columns(vt, fill, monkeypatch):
    """A seeded mixed tick, a third full (the comparison path on both sides)
    and full (radix): every uploaded acquire and completion column and inv_a
    are byte-equal to what the parent's presort gives for the same inputs."""
    import sentinel_tpu.native.ring as RM
    from sentinel_tpu.native.ring import FLAG_INBOUND

    B = 3072
    c = _mk(vt, batch_size=B, complete_batch_size=B)  # never started: no compile
    cfg, trash, M = c.cfg, c.cfg.trash_row, c.cfg.param_dims
    n_blk = (B // 3 if fill == "third" else B) - 40
    acq, blocks, comp = _mixed_tick(cfg, np.random.default_rng(25), 40, n_blk, n_blk)

    seen = []  # what each side handed the presort, copied before it ran
    real = RM.presort

    def spy(keys, n_live, order, inv, scratch, src=(), dst=(), wide=None, wide_dst=None):
        seen.append(([k.copy() for k in keys], [x.copy() for x in src],
                     None if wide is None else wide.copy()))
        return real(keys, n_live, order, inv, scratch, src, dst, wide, wide_dst)

    uploaded = {}

    def fake_tick(state, rules, wire_in):
        # what crossed, unpacked as the tick's entry unpacks it (narrow
        # columns widened to int32; nothing else runs on the device)
        lo = WIRE.input_layout_of(cfg, wire_in.shape[0])
        uploaded["a"], uploaded["c"], *_hdr = WIRE.unpack_tick_input(wire_in, lo)
        return state, None  # nothing is resolved

    monkeypatch.setattr(RM, "presort", spy)
    monkeypatch.setattr(c, "_tick", fake_tick)
    p = c._run_tick(acq, comp, 1_000, blocks=blocks)

    (keys_a, src_a, ph_a), (keys_c, src_c, _none) = seen
    assert len(keys_a[0]) == B and len(keys_c[0]) == n_blk
    inv, cols, ph = _parent_presort_acquire(keys_a, src_a, ph_a)
    assert p.inv_a.tobytes() == inv.tobytes()
    a = uploaded["a"]
    got = (a.res, a.count, a.prio, a.origin_id, a.origin_node, a.ctx_node,
           a.ctx_name, a.inbound, a.pre_verdict)
    wd = WIRE.acquire_wire_dtypes(cfg)
    names = ("res", "count", "prio", "origin_id", "origin_node", "ctx_node",
             "ctx_name", "inbound", "pre_verdict")
    for name, g, want in zip(names, got, cols):
        # through the wire dtype and back, as the parent's narrow upload went
        want = want.astype(wd.get(name, np.int32)).astype(np.int32)
        assert np.asarray(g).tobytes() == want.tobytes(), name
    assert np.asarray(a.param_hash).tobytes() == ph.tobytes()
    assert int(np.asarray(a.count).max()) == cfg.max_batch_count  # clamped first

    # completions, the parent's lines: sort the n drained rows, then pad
    res_a, cnt_a, org_a, ctx_a, flags_a, rt_a, err_a = src_c[:7]
    order = np.lexsort((org_a, ctx_a, res_a))
    res_a, cnt_a, org_a, ctx_a, flags_a, rt_a, err_a = (
        x[order] for x in (res_a, cnt_a, org_a, ctx_a, flags_a, rt_a, err_a)
    )
    aux = [x[order] for x in comp[8:]]

    def pad(x, fill_v, like):
        out = np.full(B, fill_v, like.dtype)
        out[:n_blk] = x
        return out

    cb = {k: np.asarray(v) for k, v in uploaded["c"]._asdict().items()}
    want_ph = np.zeros((B, M), np.int32)
    for k in range(M):
        want_ph[:n_blk, k] = aux[k]
    want = dict(
        res=pad(res_a, trash, cb["res"]),
        origin_node=pad(org_a, trash, cb["origin_node"]),
        ctx_node=pad(ctx_a, trash, cb["ctx_node"]),
        inbound=pad(flags_a & FLAG_INBOUND, 0, cb["inbound"]),
        rt=pad(rt_a, 0.0, cb["rt"]),
        success=pad(np.minimum(cnt_a, cfg.max_batch_count), 0, cb["success"]),
        error=pad(np.minimum(err_a, cfg.max_batch_count), 0, cb["error"]),
        param_hash=want_ph,
    )
    for name, w in want.items():
        assert cb[name].tobytes() == w.tobytes(), name


class _FrozenTime(TimeSource):
    """Engine time stands still, and the client stays threaded (a
    VirtualTimeSource makes it sync): verdicts do not depend on how long
    a tick thread took."""

    def now_ms(self):
        return 2_000


# -- absent columns stay absent ------------------------------------------------
#
# A column that no row of a tick carries is never materialised (client.py
# _run_tick, _join_completions): the tests below hold the bytes that cross to
# what the same rows give with every column passed at its default.

_ABSENT_B = 2048  # rehearsal size

_L, _M, _F = (256, 256), (512, 512), (_ABSENT_B, _ABSENT_B)

#: case -> (acquire pieces' rows, of which carry counts, object requests,
#: completions through the ring, what a piece carries, the ticks' shapes)
_ABSENT_CASES = {
    "light": ((100, 100), (), 0, 0, "generator", [_L]),  # n < B
    "middle": ((150, 150, 150), (), 0, 0, "generator", [_M]),
    "full-part": ((500, 500, 500), (), 0, 0, "generator", [_F]),
    # 2,100 rows a side: a tick with n == B whose third piece is cut, then
    # a light one of the remainder
    "full": ((700, 700, 700), (), 0, 0, "generator", [_F, _L]),
    "counts-beside": ((300, 300), (0,), 0, 0, "generator", [_F]),
    "objects-beside": ((200, 200), (), 5, 0, "generator", [_M]),
    "ring-and-blocks": ((120, 120), (), 0, 20, "generator", [_M]),
    "bare": ((90, 400), (), 0, 0, "nothing", [_M]),  # res alone, (res, rt) alone
}


def _absent_traffic(cfg, rng, case):
    """The case's rows, seeded: per acquire piece and per completion piece
    the keyword columns a caller passes (``some``) and the same with every
    optional column passed at its default (``every``)."""
    from sentinel_tpu.runtime.client import AcquireRequest, Completion

    pieces, with_counts, n_obj, n_ring, carries, _shapes = _ABSENT_CASES[case]
    trash, M = cfg.trash_row, cfg.param_dims
    i32 = lambda lo, hi, n: rng.integers(lo, hi, n).astype(np.int32)
    acq, comp = [], []
    for k, n in enumerate(pieces):
        ids = i32(1, 48, n)
        ids[rng.random(n) < 0.05] = trash  # a few live rows on the trash row
        some = {}
        if carries == "generator":
            some = dict(
                origin_node=i32(50, 54, n), origin_id=i32(-1, 3, n),
                inbound=i32(0, 2, n), param_hash=i32(0, 9, (n, M)),
            )
        if k in with_counts:
            some["counts"] = i32(1, 70000, n)  # over the clamp
        every = dict(
            counts=np.ones(n, np.int32), prio=np.zeros(n, np.int32),
            origin_id=np.full(n, -1, np.int32), origin_node=np.full(n, trash, np.int32),
            ctx_node=np.full(n, trash, np.int32), ctx_name=np.full(n, -1, np.int32),
            inbound=np.zeros(n, np.int32), param_hash=np.zeros((n, M), np.int32),
            pre_verdict=np.zeros(n, np.int32),
        )
        acq.append((ids, some, {**every, **some}))
        c_some = {f: some[f] for f in ("inbound", "param_hash") if f in some}
        c_every = dict(
            success=np.ones(n, np.int32), error=np.zeros(n, np.int32),
            inbound=np.zeros(n, np.int32), origin_node=np.full(n, trash, np.int32),
            ctx_node=np.full(n, trash, np.int32), param_hash=np.zeros((n, M), np.int32),
        )
        comp.append((ids, rng.random(n).astype(np.float32), c_some, {**c_every, **c_some}))
    objs = [
        dict(
            res=int(r), count=int(rng.integers(1, 4)), prio=int(rng.integers(0, 2)),
            origin_id=int(rng.integers(-1, 3)), origin_node=int(rng.integers(50, 54)),
            ctx_node=int(rng.integers(54, 58)), ctx_name=int(rng.integers(-1, 2)),
            inbound=int(rng.integers(0, 2)),
            param_hash=tuple(int(v) for v in rng.integers(0, 9, M)),
        )
        for r in i32(1, 48, n_obj)
    ]
    ring = [
        Completion(
            res=int(r), origin_node=int(rng.integers(50, 54)),
            ctx_node=int(rng.integers(54, 58)), inbound=int(rng.integers(0, 2)),
            rt=float(rng.integers(1, 90)), success=int(rng.integers(1, 70000)),
            error=int(rng.integers(0, 3)),
            param_hash=tuple(int(v) for v in rng.integers(0, 9, M)),
        )
        for r in i32(1, 48, n_ring)
    ]
    return acq, comp, [lambda o=o: AcquireRequest(**o) for o in objs], ring


def _absent_client(monkeypatch, cfg_kw=None, **kw):
    """A threaded client that is never started and whose ticks go nowhere:
    ``tick_once`` drains and builds, the spy keeps every built tick."""
    c = SentinelClient(
        cfg=small_engine_config(
            **SEG, batch_size=_ABSENT_B, complete_batch_size=_ABSENT_B,
            **(cfg_kw or {}),
        ),
        time_source=_FrozenTime(), mode="threaded", pipeline_depth=0, **kw,
    )
    built = []
    real = c._run_tick

    def run_tick(*a, **kw):
        built.append(real(*a, **kw))
        return built[-1]

    monkeypatch.setattr(c, "_run_tick", run_tick)
    monkeypatch.setattr(c, "_tick", lambda state, rules, wire_in: (state, None))
    monkeypatch.setattr(c, "_resolve_tick", lambda p: None)
    # the header carries the load and the CPU share: held still
    monkeypatch.setattr(c, "_sys", SimpleNamespace(sample=lambda: (0.5, 0.25)))
    return c, built


@pytest.mark.parametrize("lib", ["native", "numpy"])
@pytest.mark.parametrize("case", list(_ABSENT_CASES))
def test_a_tick_of_absent_columns_uploads_the_bytes_of_explicit_defaults(
    monkeypatch, case, lib
):
    """Pieces that leave their optional columns out against the same pieces
    with every column passed at its default: every input buffer is byte-equal
    (into views poisoned beforehand, so each is written whole) and inv_a too;
    light, middle and full shape, n < B and n == B, a piece with counts beside
    one without, object requests beside blocks, completions from the ring and
    from blocks at once, with the native library and without."""
    import sentinel_tpu.native.ring as RM

    if lib == "numpy":
        monkeypatch.setattr(RM, "load_native", lambda: None)
    elif RM.load_native() is None:
        pytest.skip("no native library here")
    c, built = _absent_client(monkeypatch)
    try:
        acq, comp, objs, ring = _absent_traffic(c.cfg, np.random.default_rng(43), case)
        sent = {}
        for which in ("some", "every"):
            if which == "some":
                # the first side builds into views that hold something else
                for b, b2 in WIRE.tick_shapes(c.cfg):
                    wb = c._input_buffer(c.cfg, b, b2)
                    for v in (*wb.acq.values(), *wb.comp.values()):
                        v.view(np.uint8).fill(0xA5)
                    c._wire_free[wb.layout].append(wb)
            for r in ring:
                c._submit_completion(r)
            c._acquires.extend(mk() for mk in objs)
            for (ids, some, every), (_ids, rt, c_some, c_every) in zip(acq, comp):
                c.submit_block(ids, **(some if which == "some" else every))
                c.submit_completion_block(
                    ids, rt, **(c_some if which == "some" else c_every)
                )
            del built[:]
            c.tick_once(now_ms=1_000)
            sent[which] = [
                (p.wire_in.layout, p.wire_in.buf.tobytes(), p.inv_a.tobytes())
                for p in built
            ]
        shapes = [(lo.b, lo.b2) for lo, _b, _i in sent["some"]]
        assert shapes == _ABSENT_CASES[case][-1]
        assert sent["some"] == sent["every"]
    finally:
        c.stop()


def test_the_audits_shadow_folds_an_absent_count_as_its_constant(monkeypatch):
    """The sketch audit's shadow reads the tick's res and clamped count
    columns: a tick whose pieces carry no counts gives it the constant, and
    it folds the volume it folds for the same pieces with counts of one."""
    folded = {}
    for which in ("some", "every"):
        c, _built = _absent_client(
            monkeypatch, cfg_kw=dict(sketch_stats=True, sketch_width=256),
            sketch_audit_k=4,
        )
        try:
            acq, _comp, _objs, _ring = _absent_traffic(
                c.cfg, np.random.default_rng(45), "full-part"
            )
            for ids, some, every in acq:
                c.submit_block(ids, **(some if which == "some" else every))
            c.tick_once(now_ms=1_000)
            assert ("a.count" in {k[0] for k in c._stage}) is (which == "every")
            folded[which] = (dict(c._audit._vol), dict(c._audit._tracked))
        finally:
            c.stop()
    live = sum(int((ids != c.cfg.trash_row).sum()) for ids, _s, _e in acq)
    assert folded["some"] == folded["every"]
    assert list(folded["some"][0].values()) == [live]


def test_block_traffic_as_the_generators_send_it_stages_the_carried_columns_only(
    monkeypatch,
):
    """Counts that hold on the CPU: blocks with res, the origin, inbound and
    the hot-param lanes and nothing else leave no staging slot for the five
    columns they do not carry, the presort gathers four acquire columns and
    five completion columns, a queued completion block holds None where the
    caller passed nothing, and the spans say so (absent_a, absent_c, joined)."""
    import sentinel_tpu.native.ring as RM
    from sentinel_tpu import obs

    c, built = _absent_client(monkeypatch)
    calls = []
    real = RM.presort

    def spy(keys, n_live, order, inv, scratch, src=(), dst=(), wide=None, wide_dst=None):
        calls.append((len(keys), len(src), wide is not None))
        return real(keys, n_live, order, inv, scratch, src, dst, wide, wide_dst)

    monkeypatch.setattr(RM, "presort", spy)
    rng = np.random.default_rng(44)
    M = c.cfg.param_dims
    i32 = lambda lo, hi, n: rng.integers(lo, hi, n).astype(np.int32)

    def feed(n):
        ids, ph, inb = i32(1, 48, n), i32(0, 9, (n, M)), i32(0, 2, n)
        c.submit_block(
            ids, origin_node=i32(50, 54, n), origin_id=i32(-1, 3, n),
            param_hash=ph, inbound=inb,
        )
        c.submit_completion_block(ids, rng.random(n).astype(np.float32),
                                  inbound=inb, param_hash=ph)

    obs.TRACER.reset()
    obs.enable()
    try:
        feed(300)
        (queued,) = c._comp_blocks
        res, success, origin_node, ctx_node, flags, rt, error, tag, *aux = queued
        assert [x is None for x in (success, origin_node, ctx_node, error, tag)] == [True] * 5
        assert [x is None for x in aux] == [False] * M + [True] * (4 - M)
        assert flags.dtype == np.int32 and rt.dtype == np.float32
        c.tick_once(now_ms=1_000)  # one piece a side
        feed(200)
        feed(200)
        c.tick_once(now_ms=1_001)  # two pieces a side
    finally:
        obs.disable()
        c.stop()
    assert len(built) == 2
    slots = {name for name, _shape, _dt in c._stage}
    assert slots >= {"a.res", "a.origin_id", "a.origin_node", "a.inbound", "a.ph"}
    assert not slots & {
        "a.count", "a.prio", "a.ctx_node", "a.ctx_name", "a.pre_verdict",
        "s.count", "s.prio", "s.pre_verdict", "sc.1", "sc.6",
    }
    # (keys, columns, the wide one): res, origin_node and origin_id order the
    # acquire side, res alone the completions
    assert calls == [(3, 4, True), (1, 3 + M, False)] * 2
    spans = obs.TRACER.snapshot()
    asm = [s["attrs"] for s in spans if s["name"] == "tick.assemble"]
    assert [(a["absent_a"], a["absent_c"]) for a in asm] == [(5, 4)] * 2
    drains = [s["attrs"] for s in spans if s["name"] == "tick.drain"]
    # res, flags, rt and the M lanes of two pieces; one piece goes on as it is
    assert [d["joined"] for d in drains] == [0, 3 + M]
    # counted where the benchmark's span_summary prints it
    summary = obs.summarize(spans)
    assert summary["tick.assemble"]["absent_a"] == {5: 2}
    assert summary["tick.assemble"]["absent_c"] == {4: 2}
    assert summary["tick.drain"]["joined"] == {0: 1, 3 + M: 1}


class _Door:
    """A front door's response ring: keeps what the resolver answers."""

    def __init__(self):
        self.answers = []

    def respond(self, corr, verdicts, waits):
        self.answers.append((corr.copy(), verdicts.copy(), waits.copy()))


_MIXED_B = 96  # small, so that the ticks cost little here; one shape


@pytest.fixture(scope="module")
def mixed_clients():
    """The packed client and the packed_wire=False reference, same rules,
    same clock; module-scoped: the second tick runs on the first's state."""
    from sentinel_tpu.core.rules import CONTROL_RATE_LIMITER

    out = {}
    for packed in (True, False):
        # seg_u with room for a full tick's segments: no resize, no recompile
        c = _mk(VirtualTimeSource(start_ms=1_000), batch_size=_MIXED_B,
                complete_batch_size=_MIXED_B, seg_u=_MIXED_B, packed_wire=packed)
        assert c.cfg.packed_wire is packed
        names = [f"m{i}" for i in range(1, 48)]
        assert [c.registry.resource_id(n) for n in names] == list(range(1, 48))
        c.flow_rules.load(
            [FlowRule(resource=n, count=2.0 + i) for i, n in enumerate(names[:20])]
            + [FlowRule(resource=names[20], count=5.0, max_queueing_time_ms=2_000,
                        control_behavior=CONTROL_RATE_LIMITER)]
        )
        out[packed] = c
    return out


def _serve_mixed_tick(c, fill: str):
    """One seeded mixed tick (object requests + array blocks + front-door
    items + completions) through a client, resolved inline: every verdict
    and wait it answered, by consumer, in order."""
    from concurrent.futures import Future

    rng = np.random.default_rng(30)
    n_obj, n_front = 8, 8
    n_blk = (_MIXED_B // 3 if fill == "third" else _MIXED_B) - n_obj - n_front
    acq, blocks, comp = _mixed_tick(c.cfg, rng, n_obj, n_blk, n_blk)
    for r in acq:
        r.future = Future()
    door = _Door()
    i32 = lambda lo, hi: rng.integers(lo, hi, n_front).astype(np.int32)
    front = (i32(1, 48), i32(1, 4), i32(0, 2), np.arange(n_front), i32(0, 9), i32(0, 9))
    p = c._run_tick(acq, comp, None, fronts=[(door, front)], blocks=blocks)
    assert (p.wire_in is not None) is bool(c.cfg.packed_wire)
    assert p.out.wait_ms.shape == (_MIXED_B,)
    c._resolve_tick(p)
    c.time.advance(300)
    return (
        [r.future.result(timeout=0) for r in acq],
        [(b.verdicts[3:].tolist(), b.waits[3:].tolist()) for b, _o, _t in blocks],
        [(v.tolist(), w.tolist()) for _c, v, w in door.answers],
    )


@pytest.mark.parametrize("fill", ["third", "full"])
def test_a_mixed_tick_is_bit_identical_packed_and_classic(mixed_clients, fill):
    """The one-buffer upload against the packed_wire=False reference client,
    which sends every column on its own on the classic signature: the same
    verdicts and waits for every consumer of the same seeded tick."""
    got = _serve_mixed_tick(mixed_clients[True], fill)
    want = _serve_mixed_tick(mixed_clients[False], fill)
    assert got == want
    objs, blks, doors = got
    verdicts = {v for v, _w in objs} | {v for vs, _ws in blks for v in vs}
    assert {int(ERR.PASS), int(ERR.BLOCK_FLOW)} <= verdicts
    assert len(doors) == 1 and len(doors[0][0]) == 8


def test_pipelined_ticks_do_not_share_presort_storage(vt, monkeypatch):
    """order and inv of a tick stay its own while it is unresolved: the next
    tick gets other storage; a resolved tick's inv is lent again, so steady
    serving allocates none."""
    import sentinel_tpu.native.ring as RM

    c = _mk(vt)
    c.start()
    try:
        orders = []
        real = RM.presort

        def spy(keys, n_live, order, inv, *rest, **kw):
            if inv is not None:  # the acquire side
                orders.append(order)
            return real(keys, n_live, order, inv, *rest, **kw)

        monkeypatch.setattr(RM, "presort", spy)
        rng = np.random.default_rng(3)
        ticks = []
        for _ in range(2):
            acq, blocks, _comp = _mixed_tick(c.cfg, rng, 4, 30, 0)
            ticks.append(c._run_tick(acq, None, None, blocks=blocks))
        p1, p2 = ticks
        inv1 = p1.inv_a.copy()
        assert not np.shares_memory(p1.inv_a, p2.inv_a)
        assert not np.shares_memory(orders[0], orders[1])
        assert sorted(inv1) == list(range(c.cfg.batch_size))
        lent = p1.inv_a
        c._resolve_tick(p1)
        assert p1.inv_a is None and c._inv_free[c.cfg.batch_size] == [lent]
        acq, blocks, _comp = _mixed_tick(c.cfg, rng, 4, 30, 0)
        p3 = c._run_tick(acq, None, None, blocks=blocks)
        assert p3.inv_a is lent and p2.inv_a is not lent
        c._resolve_tick(p2)
        c._resolve_tick(p3)
        assert len(c._inv_free[c.cfg.batch_size]) == 2
    finally:
        c.stop()


# -- pipeline residency: handed over at dispatch, pipeline_depth as the cap --


def _traced_ticks(spans):
    """Per dispatched tick, in dispatch order: its tick.dispatch,
    tick.resident and tick.handoff spans."""
    by_tick = {}
    for s in spans:
        if s["name"] in ("tick.dispatch", "tick.resident", "tick.handoff"):
            by_tick.setdefault(s["trace"], {})[s["name"]] = s
    ticks = [t for t in by_tick.values() if len(t) == 3]
    return sorted(ticks, key=lambda t: t["tick.dispatch"]["t0_ns"])


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_a_tick_is_handed_to_its_resolver_before_the_next_is_dispatched(vt, depth):
    """With more work queued across ticks, a tick goes to the resolver pool
    when it is dispatched, not pipeline_depth ticks later; and the unresolved
    count a tick sees at hand-over never passes the depth."""
    from sentinel_tpu import obs

    c = _mk(vt)
    c._pipeline_depth = depth
    ids = np.array([c.registry.resource_id(f"h{i}") for i in range(12)], np.int32)
    c.flow_rules.load([FlowRule(resource="h0", count=3.0)])
    c.start()
    obs.TRACER.reset()
    obs.enable()
    try:
        # one block over seven ticks: `more` stays true from tick to tick
        n = 6 * c.cfg.batch_size + 40
        verdicts, _w = c.submit_block(np.resize(ids, n)).result(timeout=60)
    finally:
        obs.disable()
        c.stop()
    assert len(verdicts) == n
    ticks = _traced_ticks(obs.TRACER.snapshot())
    assert len(ticks) == 7
    for this, nxt in zip(ticks, ticks[1:]):
        handed = this["tick.resident"]["attrs"]["handed_ns"]
        d_this, d_nxt = this["tick.dispatch"], nxt["tick.dispatch"]
        assert d_this["t0_ns"] + d_this["dur_ns"] <= handed
        assert handed <= d_nxt["t0_ns"] + d_nxt["dur_ns"]
    for t in ticks:
        at = t["tick.handoff"]["attrs"]
        assert 0 <= at["pending"] <= depth and at["resolvers"] == at["pending"]
    assert c._pending_ticks == []


class _Gate:
    """Stands in for the sleep of a failpoint's ``delay``: a resolver that
    fires it is held until the test opens the gate, so the test asserts
    orderings and counts and never a duration."""

    def __init__(self):
        self.open = threading.Event()
        self.held = threading.Semaphore(0)  # one release per resolver held

    def sleep(self, _seconds):
        self.held.release()
        assert self.open.wait(60), "the test never opened the gate"


def _stalled_client(monkeypatch, depth, **kw):
    """A threaded client whose every readback stalls on a gate (the
    stand-in for a hung device: runtime.watchdog.stall), with a spy on the
    tick thread's wait for the cap."""
    from sentinel_tpu.chaos import failpoints as FP
    from sentinel_tpu.chaos.plans import FaultPlan, FaultSpec

    gate = _Gate()
    monkeypatch.setattr(FP, "_time", SimpleNamespace(sleep=gate.sleep))
    c = SentinelClient(
        cfg=small_engine_config(**SEG), time_source=_FrozenTime(), mode="threaded",
        tick_interval_ms=1.0, entry_timeout_s=30.0, pipeline_depth=depth, **kw,
    )
    ids = np.array([c.registry.resource_id(f"s{i}") for i in range(12)], np.int32)
    c.flow_rules.load([FlowRule(resource="s0", count=3.0)])
    c.start()
    at_cap = threading.Event()
    real = c._await_resolved

    def spy(n, why):
        if why["why"] == "depth":
            at_cap.set()
        return real(n, why)

    monkeypatch.setattr(c, "_await_resolved", spy)
    plan = FaultPlan(
        name="stall", seed=5,
        faults=[FaultSpec("runtime.watchdog.stall", "delay", delay_ms=1000)],
    )
    return c, ids, gate, at_cap, FP.armed(plan)


def _drained(c):
    from sentinel_tpu.chaos import invariants as INV

    # the loop goes idle once nothing is queued: wait for it, then check
    with c._tick_mutex:
        c._drain_resolves()
    return INV.pipeline_drained(SimpleNamespace(client=c))


def test_pipeline_depth_caps_the_unresolved_ticks(monkeypatch):
    """Resolvers held at the readback: the tick thread dispatches
    pipeline_depth ticks and waits at the cap (tick.idle why="depth");
    released, every block resolves as a depth-0 run resolves it."""
    from sentinel_tpu import obs

    depth, n_ticks = 2, 5
    c, ids, gate, at_cap, armed = _stalled_client(monkeypatch, depth)
    items = np.resize(ids, n_ticks * c.cfg.batch_size)
    obs.TRACER.reset()
    obs.enable()
    try:
        with armed:
            fut = c.submit_block(items)
            assert at_cap.wait(60), "the tick thread never met the cap"
            for _ in range(depth):
                assert gate.held.acquire(timeout=60)
            # depth resolvers are held and no third was started: the tick
            # that would be the third unresolved one was not dispatched
            assert not gate.held.acquire(blocking=False)
            assert len(c._pending_ticks) == depth
            assert not fut.done()
            gate.open.set()
            verdicts, _w = fut.result(timeout=60)
            assert _drained(c).ok
    finally:
        gate.open.set()
        obs.disable()
        c.stop()
    spans = obs.TRACER.snapshot()
    assert len([s for s in spans if s["name"] == "tick.dispatch"]) == n_ticks
    # counted where the benchmark's span_summary prints it
    assert obs.summarize(spans)["tick.idle"]["why"]["depth"] >= 1
    inline = SentinelClient(
        cfg=small_engine_config(**SEG), time_source=_FrozenTime(), mode="sync",
        pipeline_depth=0,
    )
    for i in range(12):
        inline.registry.resource_id(f"s{i}")
    inline.flow_rules.load([FlowRule(resource="s0", count=3.0)])
    want, _w = inline.submit_block(items).result(timeout=60)
    assert verdicts.tolist() == want.tolist()
    assert ERR.BLOCK_SYSTEM not in set(verdicts.tolist())


@pytest.mark.parametrize("fill", ["part", "full"])
def test_a_part_filled_tick_goes_behind_one_unresolved_tick_at_most(vt, monkeypatch, fill):
    """Before its drain the tick thread waits until a dispatch would leave at
    most pipeline_depth ticks unresolved; and a tick that would not be full,
    which costs the device as much as a full one, until it goes behind at
    most ONE unresolved tick (one running, one queued: the device cannot go
    idle, and a third would only wait there).  A depth under 2 stays the cap."""
    from sentinel_tpu.runtime.client import ArrayBlock

    waits = []
    for depth in (4, 2, 1):
        c = SentinelClient(
            cfg=small_engine_config(**SEG), time_source=vt, mode="sync",
            pipeline_depth=depth,
        )
        n = c.cfg.batch_size if fill == "full" else c.cfg.batch_size - 1
        c._acq_blocks.append(ArrayBlock(res=np.ones(n + 5, np.int32), taken=5))
        monkeypatch.setattr(
            c, "_await_resolved", lambda k, why, d=depth: waits.append((d, k, why["why"]))
        )
        for unresolved in range(depth + 1):
            c._pending_ticks = [
                SimpleNamespace(settled=threading.Event()) for _ in range(unresolved)
            ]
            c._await_room()
    cap4 = 4 if fill == "full" else 2
    assert waits == (
        [(4, u - cap4 + 1, "depth") for u in range(cap4, 5)]
        + [(2, 1, "depth"), (1, 1, "depth")]
    )


def test_the_watchdog_releases_the_cap_and_the_loop_moves_on(monkeypatch):
    """Watchdog armed, resolvers held: each stalled tick fails closed and
    the tick thread, waiting at the cap for it, dispatches the next while
    the resolvers are still held."""
    from sentinel_tpu.obs.registry import REGISTRY as OBS

    depth, n_ticks = 2, 4
    c, ids, gate, at_cap, armed = _stalled_client(
        monkeypatch, depth, watchdog_timeout_s=0.2
    )
    fired = OBS.counter("sentinel_watchdog_fired_total")
    before = fired.value
    try:
        with armed:
            fut = c.submit_block(np.resize(ids, n_ticks * c.cfg.batch_size))
            assert at_cap.wait(60), "the tick thread never met the cap"
            # every tick is failed over while the gate is still shut
            verdicts, _w = fut.result(timeout=60)
            assert not gate.open.is_set()
            assert set(verdicts.tolist()) == {int(ERR.BLOCK_SYSTEM)}
            assert fired.value == before + n_ticks
            gate.open.set()
            assert _drained(c).ok
        # the loop is sound afterwards; the stalls are over, so the watchdog
        # gets the patience a sound tick needs on a starved host (read at
        # every dispatch), not the 0.2 s that made it fire above
        c.watchdog_timeout_s = 30.0
        ok, _w = c.submit_block(ids[1:]).result(timeout=60)
        assert set(ok.tolist()) == {int(ERR.PASS)}
        assert _drained(c).ok
    finally:
        gate.open.set()
        c.stop()


def test_a_resolution_that_raises_is_logged_and_frees_the_loop(vt, monkeypatch):
    """_resolve_tick fails its tick closed and does not raise; should it
    ever, the loss is logged from the pool future's done-callback and the
    tick leaves the books, so neither the cap nor the idle drain waits out
    its deadline for it."""
    from sentinel_tpu.utils import record_log as RL

    logged = []
    monkeypatch.setattr(
        RL.record_log(), "error", lambda msg, *a, **kw: logged.append(msg % a)
    )
    c = _mk(vt)
    c._pipeline_depth = 1
    rid = c.registry.resource_id("lost")
    c.start()
    try:
        lost = []

        def broken(p):
            lost.append(p)
            raise RuntimeError("resolver broke")

        monkeypatch.setattr(c, "_resolve_tick", broken)
        # three ticks at depth 1: the second and third meet the cap
        fut = c.submit_block(np.full(2 * c.cfg.batch_size + 8, rid, np.int32))
        assert len(lost) == 3 and c._pending_ticks == []
        assert not fut.done()  # stranded, which is why it must be loud
        assert len([m for m in logged if "tick resolution failed" in m]) == 3
    finally:
        monkeypatch.undo()
        for p in lost:
            c._fail_tick(p)
        c.stop()
    assert fut.done()


@pytest.mark.parametrize("shape,batch", [("full", 512), ("middle", 2048)])
def test_a_full_tick_reads_back_exactly_its_layouts_bytes(client_factory, shape, batch):
    """tests/test_wire.py holds a light tick to its packed layout's total;
    the full shape and the middle one are layouts of their own and are held
    to them here: one fused read-back a tick (the timeline rows on their
    own path), not four."""
    from sentinel_tpu.obs.registry import REGISTRY as OBS

    def rx(path):
        return OBS.get("sentinel_wire_bytes_total", {"path": path, "direction": "rx"}).value

    c = client_factory(cfg=small_engine_config(batch_size=batch, complete_batch_size=batch))
    # over 256 rows: the full shape of a batch of 512, the middle one (512) of 2,048
    assert WIRE.tick_shape_for(c.cfg, 300, 0) == (512, 512)
    assert (WIRE.tick_shapes(c.cfg)[-1] == (512, 512)) is (shape == "full")
    ids = np.full(300, c.registry.resource_id("full/r"), np.int32)
    c.submit_block(ids).result(timeout=60)  # compile this shape / const cols
    dev0, tl0 = rx("device"), rx("timeline")
    c.submit_block(ids).result(timeout=60)
    lo = c._wire_layout(c.cfg, 512)
    tl_bytes = lo.tl_rows * lo.tl_cols * 4
    assert rx("device") - dev0 == lo.total * 4 - tl_bytes
    assert rx("timeline") - tl0 == tl_bytes


# -- the ladder of tick shapes ------------------------------------------------


def test_a_tick_takes_the_smallest_shape_that_holds_both_sides():
    """ops/wire.tick_shape_for at its edges, on the served batch of 131,072
    (light 256, middle 32,768): a row more than a shape holds takes the
    next; the longer side decides; a batch of at most 1,027 rows keeps
    two shapes and one of at most 256 rows one."""
    cfg = small_engine_config(batch_size=131072, complete_batch_size=131072)
    light, middle, full = WIRE.tick_shapes(cfg)
    assert (light, middle, full) == ((256, 256), (32768, 32768), (131072, 131072))
    for n_acq, n_comp, want in [
        (0, 0, light), (256, 256, light), (257, 0, middle), (0, 257, middle),
        (32768, 32768, middle), (32769, 0, full), (0, 32769, full),
        (4, 9000, middle), (9000, 4, middle),  # a long side beside a short one
        (4, 131072, full), (131072, 131072, full),
        (131073, 0, full),  # more than a tick holds is the drain's fault, not a new shape
    ]:
        assert WIRE.tick_shape_for(cfg, n_acq, n_comp) == want, (n_acq, n_comp)

    def ladder(b, b2):
        return WIRE.tick_shapes(small_engine_config(batch_size=b, complete_batch_size=b2))

    assert ladder(1023, 1023) == ((256, 256), (1023, 1023))  # 1023 // 4 < 256
    assert ladder(1027, 1027) == ((256, 256), (1027, 1027))
    assert ladder(1028, 1028) == ((256, 256), (257, 257), (1028, 1028))
    assert ladder(4096, 300) == ((256, 256), (1024, 256), (4096, 300))
    assert ladder(64, 64) == ((64, 64),)
    # every shape of a ladder is a buffer length of its own: the jitted tick
    # finds its layout by the buffer alone
    for cfg in (cfg, small_engine_config(batch_size=1028, complete_batch_size=1028)):
        totals = [WIRE.input_layout_for(cfg, b, b2).total for b, b2 in WIRE.tick_shapes(cfg)]
        assert len(set(totals)) == len(totals)
        for (b, b2), total in zip(WIRE.tick_shapes(cfg), totals):
            assert WIRE.input_layout_of(cfg, total)[:2] == (b, b2)


def test_the_middle_shapes_capacity_and_the_resize_guard(vt):
    """engine_seg.seg_capacity leaves the light and the full shape what they
    were and gives the middle one a quarter of its rows; a middle tick's
    overflow that the full shape's capacity covers starts no seg_u resize,
    however often it comes."""
    from sentinel_tpu.ops import engine_seg as ES

    cfg = small_engine_config(**SEG, batch_size=131072, complete_batch_size=131072)
    assert ES.seg_capacity(cfg, 256) == 97
    assert ES.seg_capacity(cfg, 131072) == 131072 // 8 + 512 + 64
    assert ES.seg_capacity(cfg, 32768) == 32768 // 4 + 128 + 64
    # a side is part-filled against its OWN full shape
    assert ES.seg_capacity(cfg, 32768, 32768) == 32768 // 8 + 128 + 64
    assert ES.seg_capacity(small_engine_config(seg_u=40), 32768) == 40

    B = 16384  # middle 4,096
    c = _mk(vt, batch_size=B, complete_batch_size=B)  # never started: no compile
    started = []
    c._resize_seg_u = started.append
    mid = ES.seg_capacity(c.cfg, B // 4, B)
    assert mid < ES.seg_capacity(c.cfg, B)
    for _ in range(16):
        c._note_seg_count(mid + 40, B // 4, B)
    assert c._seg_over_ticks == 16 and started == []
    # the full shape's own overflow still does
    for _ in range(4):
        c._note_seg_count(ES.seg_capacity(c.cfg, B) + 1, B, B)
    assert len(started) == 1 and started[0] > ES.seg_capacity(c.cfg, B)


def test_a_batch_is_decided_alike_at_the_middle_and_at_the_full_shape(vt, monkeypatch):
    """One seeded mixed tick of some thousand rows (objects, blocks,
    completions), then a second on the first's state, through two clients
    of one configuration: one pads it to the middle shape, the other (the
    ladder without its middle rung, as before the middle shape existed) to
    the full one.  Padding rows are trash rows, engine no-ops: every
    verdict and wait, the whole decoded TickOutput and every leaf of the
    state are equal; but for the telemetry row's count of live segments,
    which counts the padding run's 256-row block heads by definition."""
    import jax

    from sentinel_tpu.ops import engine as E
    from sentinel_tpu.ops import segment as SG

    B = 8192  # light 256, middle 2,048

    def serve(with_middle: bool):
        real = WIRE.tick_shapes
        if not with_middle:
            monkeypatch.setattr(
                WIRE, "tick_shapes", lambda cfg: (real(cfg)[0], real(cfg)[-1])
            )
        c = _mk(VirtualTimeSource(start_ms=1_000), batch_size=B, complete_batch_size=B)
        names = [f"m{i}" for i in range(1, 48)]
        assert [c.registry.resource_id(n) for n in names] == list(range(1, 48))
        c.flow_rules.load(
            [FlowRule(resource=n, count=20.0 + 9 * i) for i, n in enumerate(names[:24])]
        )
        rng = np.random.default_rng(32)
        got = []
        for n_blk in (1900, 1400):
            acq, blocks, comp = _mixed_tick(c.cfg, rng, 8, n_blk, n_blk - 100)
            p = c._run_tick(acq, comp, None, blocks=blocks)
            rows = int(p.out.wait_ms.shape[0])
            inv = p.inv_a.copy()
            frame = WIRE.unpack(
                np.asarray(p.out.wire).tobytes(), c._wire_layout(c.cfg, rows)
            )
            c._resolve_tick(p)
            n = 8 + n_blk
            stats = frame.stats.copy()
            seg_live, stats[E.STAT_SEG_LIVE] = int(stats[E.STAT_SEG_LIVE]), 0
            got.append((
                rows, seg_live,
                [(b.verdicts[3:].tolist(), b.waits[3:].tolist()) for b, _o, _t in blocks],
                frame.verdict[inv][:n].tolist(), frame.wait[inv][:n].tolist(),
                frame.n_wait, frame.seg_dropped,
                *(None if x is None else x.tolist()
                  for x in (stats, frame.res_stats, frame.hot, frame.expl)),
            ))
            c.time.advance(300)
        state = [np.asarray(x) for x in jax.tree_util.tree_leaves(c._state)]
        monkeypatch.undo()
        return got, state

    (mid1, mid2), mid_state = serve(with_middle=True)
    (full1, full2), full_state = serve(with_middle=False)
    assert (mid1[0], mid2[0]) == (2048, 2048) and (full1[0], full2[0]) == (B, B)
    assert mid1[2:] == full1[2:] and mid2[2:] == full2[2:]
    for mid, full in ((mid1, full1), (mid2, full2)):
        assert full[1] - mid[1] == (B - 2048) // SG.BLOCK
    verdicts = set(mid1[3]) | set(mid2[3])
    assert {int(ERR.PASS), int(ERR.BLOCK_FLOW)} <= verdicts
    assert len(mid_state) == len(full_state)
    for a, b in zip(mid_state, full_state):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
