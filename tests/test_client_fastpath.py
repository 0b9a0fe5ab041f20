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


def test_dev_col_never_aliases_the_staging_slot(vt):
    """jnp.asarray is zero-copy for a 64-byte-aligned host buffer on the
    CPU backend, so a column uploaded straight from a staging slot would
    change under a queued (pipelined) tick when the slot is rewritten —
    chip_smoke's equivalence phase caught exactly that.  Both _dev_col
    paths must hand the tick a buffer nothing rewrites."""
    c = _mk(vt)
    n = c.cfg.batch_size
    raw = np.empty(n + 16, np.int32)
    off = (-raw.ctypes.data % 64) // 4
    slot = raw[off:off + n]  # what a lucky np.empty hands _sbuf
    assert slot.ctypes.data % 64 == 0

    slot[:] = np.arange(n)
    varying = c._dev_col("t.varying", slot, -1)
    slot[:] = 5
    const = c._dev_col("t.const", slot, 5)
    slot[:] = 9  # the slot's next use
    assert np.array_equal(np.asarray(varying), np.arange(n))
    assert (np.asarray(const) == 5).all()
    assert (np.asarray(c._dev_col("t.const", np.full(n, 5, np.int32), 5)) == 5).all()


@pytest.mark.jitted  # the POINT: no disable_jit — pin jit-only buffer behavior
def test_jitted_const_column_cache_and_empty_batches(vt):
    """ADVICE r5 low #4: the jit-only buffer-dedup failure class (per-leaf
    empty_acquire buffers, the field-keyed _dev_col constant cache —
    'Execution supplied N buffers but compiled program expected N+1')
    only manifests under REAL jit dispatch, which the eager-heavy fixture
    normally bypasses.  Interleave empty ticks (every column a cached
    device constant), all-default batches (most columns hit the _dev_col
    cache), and distinct-value batches (cache misses) through one jitted
    tick and require exact verdicts throughout."""
    c = _mk(vt)
    names = [f"j{i}" for i in range(8)]
    for n in names:
        c.registry.resource_id(n)
    c.flow_rules.load(
        [FlowRule(resource=names[0], count=0.0),
         FlowRule(resource=names[1], count=1000.0)]
    )

    # repeated EMPTY batches: tick_once with nothing queued reuses the
    # empty_acquire/empty_complete constants call after call
    for _ in range(3):
        c.tick_once()
        vt.advance(10)

    for round_ in range(3):
        # all-default columns (count=1, no origin/ctx/params): every
        # column except res equals its fill -> _dev_col cache round-trips
        out = c.check_batch([names[0], names[1], names[2]])
        assert [v for v, _ in out] == [
            ERR.BLOCK_FLOW, ERR.PASS, ERR.PASS,
        ], f"round {round_}"
        # distinct values force fresh uploads on the same executable
        out2 = c.check_batch(
            [names[1], names[1]], counts=[2, 3], origins=["peer", ""]
        )
        assert [v for v, _ in out2] == [ERR.PASS, ERR.PASS]
        # back to empty: the cached constants must still be aliasing-safe
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

    def fake_tick(state, rules, a, cb, *_rest):
        uploaded["a"], uploaded["c"] = a, cb
        return state, None  # the device never runs; nothing is resolved

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
    for g, want in zip(got, cols):
        g = np.asarray(g)
        assert g.tobytes() == want.astype(g.dtype).tobytes()
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


class _FrozenTime(TimeSource):
    """Engine time stands still, and the client stays threaded (a
    VirtualTimeSource makes it sync): verdicts do not depend on how long
    a tick thread took."""

    def now_ms(self):
        return 2_000


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
        # the loop is sound afterwards
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


def test_a_full_tick_reads_back_exactly_its_layouts_bytes(client_factory):
    """tests/test_wire.py holds a light tick to its packed layout's total;
    the full shape is a layout of its own and is held to it here: one fused
    read-back a tick (the timeline rows on their own path), not four."""
    from sentinel_tpu.obs.registry import REGISTRY as OBS

    def rx(path):
        return OBS.get("sentinel_wire_bytes_total", {"path": path, "direction": "rx"}).value

    c = client_factory(cfg=small_engine_config(batch_size=512, complete_batch_size=512))
    ids = np.full(300, c.registry.resource_id("full/r"), np.int32)  # over 256: the full shape
    c.submit_block(ids).result(timeout=60)  # compile this shape / const cols
    dev0, tl0 = rx("device"), rx("timeline")
    c.submit_block(ids).result(timeout=60)
    lo = c._wire_layout(c.cfg, c.cfg.batch_size)
    tl_bytes = lo.tl_rows * lo.tl_cols * 4
    assert rx("device") - dev0 == lo.total * 4 - tl_bytes
    assert rx("timeline") - tl0 == tl_bytes
