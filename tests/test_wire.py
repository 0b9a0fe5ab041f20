"""Packed host↔device wire format (ops/wire.py) and the client's fused
readback / one-buffer upload path: codec round-trips bit-exactly for
every verdict code, padding rows and the PASS_WAIT sidecar (incl.
overflow); the input wire unpacks on the device to the batches the classic
path builds; the packed engine tick and the packed client are
bit-identical to the unpacked reference on the same traffic; a packed tick
makes one upload; and a mangled fused readback fails the tick CLOSED."""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from sentinel_tpu.chaos import FaultPlan, FaultSpec
from sentinel_tpu.chaos import failpoints as FP
from sentinel_tpu.core import errors as ERR
from sentinel_tpu.core.config import small_engine_config
from sentinel_tpu.core.rules import FlowRule
from sentinel_tpu.obs import REGISTRY
from sentinel_tpu.ops import engine as E
from sentinel_tpu.ops import wire as WIRE


class _Reg:
    def resource_id(self, n):
        return 1


def _metric(name, **labels):
    m = REGISTRY.get(name, labels or None)
    return float(m.value) if m is not None else 0.0


# -- codec goldens -----------------------------------------------------------


def _pack_unpack(cfg, verdict, wait, dropped=0):
    """Round-trip synthetic outputs through the device packer."""
    b = len(verdict)
    lo = WIRE.layout_for(cfg, b)
    rng = np.random.default_rng(42)
    stats = (
        rng.standard_normal(lo.n_stats).astype(np.float32)
        if lo.n_stats
        else None
    )
    res_stats = (
        rng.standard_normal((lo.tl_rows, lo.tl_cols)).astype(np.float32)
        if lo.tl_rows
        else None
    )
    hot = (
        rng.standard_normal((lo.hot_rows, 2)).astype(np.float32)
        if lo.hot_rows
        else None
    )
    buf = WIRE.pack_tick_output(
        cfg,
        jnp.asarray(verdict, jnp.int8),
        jnp.asarray(wait, jnp.int32),
        jnp.int32(dropped),
        None if stats is None else jnp.asarray(stats),
        None if res_stats is None else jnp.asarray(res_stats),
        None if hot is None else jnp.asarray(hot),
    )
    raw = np.asarray(buf)
    assert raw.dtype == np.uint32 and raw.shape == (lo.total,)
    frame = WIRE.unpack(raw.tobytes(), lo)
    return lo, raw, frame, stats, res_stats, hot


def test_codec_round_trip_all_verdict_codes():
    """Every verdict code 0..6 survives the 3-bit bitmap, including at
    word boundaries and with non-multiple-of-10 padding."""
    cfg = small_engine_config()
    codes = [
        ERR.PASS, ERR.BLOCK_FLOW, ERR.BLOCK_DEGRADE, ERR.BLOCK_PARAM,
        ERR.BLOCK_SYSTEM, ERR.BLOCK_AUTHORITY, ERR.PASS_WAIT,
    ]
    for b in (1, 7, 10, 11, 64, 257):
        verdict = np.array([codes[i % len(codes)] for i in range(b)], np.int8)
        wait = np.where(verdict == ERR.PASS_WAIT, 25, 0).astype(np.int32)
        lo, _raw, frame, stats, res_stats, hot = _pack_unpack(
            cfg, verdict, wait, dropped=3
        )
        assert np.array_equal(frame.verdict, verdict)
        assert frame.seg_dropped == 3
        if frame.n_wait <= lo.exc_k:
            assert np.array_equal(frame.wait, wait)
        if stats is not None:
            assert frame.stats.tobytes() == stats.tobytes()
        if res_stats is not None:
            assert frame.res_stats.tobytes() == res_stats.tobytes()
        if hot is not None:
            assert frame.hot.tobytes() == hot.tobytes()


def test_codec_wait_sidecar_exact_and_overflow():
    cfg = small_engine_config()
    b = 256
    assert WIRE.EXC_K < b
    # exactly EXC_K scattered wait rows: the sidecar covers them all
    verdict = np.zeros(b, np.int8)
    wait = np.zeros(b, np.int32)
    idx = np.arange(0, b, b // WIRE.EXC_K)[: WIRE.EXC_K]
    verdict[idx] = ERR.PASS_WAIT
    wait[idx] = 10 + np.arange(len(idx))
    _lo, _raw, frame, *_ = _pack_unpack(cfg, verdict, wait)
    assert frame.n_wait == WIRE.EXC_K
    assert np.array_equal(frame.wait, wait)
    # EXC_K + 1 rows: overflow — wait is None, the client falls back to
    # the full TickOutput.wait_ms column
    verdict[:] = ERR.PASS_WAIT
    wait[:] = 9
    _lo, _raw, frame, *_ = _pack_unpack(cfg, verdict, wait)
    assert frame.n_wait == b
    assert frame.wait is None
    # zero wait rows: no sidecar decode at all
    _lo, _raw, frame, *_ = _pack_unpack(
        cfg, np.zeros(b, np.int8), np.zeros(b, np.int32)
    )
    assert frame.n_wait == 0 and not frame.wait.any()


def test_codec_rejects_corruption_truncation_and_bad_magic():
    cfg = small_engine_config()
    verdict = np.array([0, 1, 6, 2, 0, 5, 3, 4], np.int8)
    wait = np.where(verdict == 6, 7, 0).astype(np.int32)
    lo, raw, _frame, *_ = _pack_unpack(cfg, verdict, wait)
    good = raw.tobytes()
    # any single flipped byte is caught (the chaos `corrupt` fault model)
    for pos in (0, 5, 17, len(good) // 2, len(good) - 1):
        bad = bytearray(good)
        bad[pos] ^= 0xFF
        with pytest.raises(WIRE.WireDecodeError):
            WIRE.unpack(bytes(bad), lo)
    # truncation / drop
    with pytest.raises(WIRE.WireDecodeError):
        WIRE.unpack(good[:-4], lo)
    with pytest.raises(WIRE.WireDecodeError):
        WIRE.unpack(b"", lo)
    # checksum fixed up but magic wrong is still rejected
    words = np.frombuffer(good, np.uint32).copy()
    words[0] ^= 1
    words[3] = (
        int(words[0]) + int(words[1]) + int(words[2])
        + int(np.sum(words[4:], dtype=np.uint64))
    ) & 0xFFFFFFFF
    with pytest.raises(WIRE.WireDecodeError):
        WIRE.unpack(words.tobytes(), lo)
    # the untouched buffer still decodes (guards the fixtures above)
    WIRE.unpack(good, lo)


def test_engine_packed_tick_bit_identical_to_unpacked():
    """The same inputs through a packed_wire tick and a classic tick must
    decode to bit-identical verdict/wait/stats/timeline outputs."""
    base = small_engine_config()
    packed = dataclasses.replace(base, packed_wire=True)
    rules_b = E._compile_ruleset(
        base, _Reg(), [FlowRule(resource="r", count=3.0)], [], [], [], [], None
    )
    res = np.array([1, 1, 1, 1, 1, base.trash_row, 1, 1], np.int32)
    outs = {}
    for cfg, rules in ((base, rules_b), (packed, None)):
        if rules is None:
            rules = E._compile_ruleset(
                cfg, _Reg(), [FlowRule(resource="r", count=3.0)],
                [], [], [], [], None,
            )
        st = E.init_state(cfg)
        tick = E.make_tick(cfg, donate=False)
        acq = E.empty_acquire(cfg, b=len(res))._replace(
            res=jnp.asarray(res, jnp.int32)
        )
        z = jnp.float32(0.0)
        _st, out = tick(
            st, rules, acq, E.empty_complete(cfg, b=len(res)),
            jnp.int32(1000), z, z,
        )
        outs[bool(cfg.packed_wire)] = out
    ref, pk = outs[False], outs[True]
    assert pk.verdict is None and pk.stats is None and pk.wire is not None
    lo = WIRE.layout_for(packed, len(res))
    frame = WIRE.unpack(np.asarray(pk.wire).tobytes(), lo)
    assert np.array_equal(frame.verdict, np.asarray(ref.verdict))
    assert frame.n_wait <= lo.exc_k
    assert np.array_equal(frame.wait, np.asarray(ref.wait_ms))
    assert frame.stats.tobytes() == np.asarray(ref.stats).tobytes()
    if ref.res_stats is not None:
        assert frame.res_stats.tobytes() == np.asarray(ref.res_stats).tobytes()


def test_empty_batch_dtypes_match_wire_uploads():
    """empty_acquire/empty_complete must carry the same narrow dtypes the
    client uploads, or warmup compiles a signature no real tick uses."""
    cfg = dataclasses.replace(small_engine_config(), packed_wire=True)
    acq = E.empty_acquire(cfg, b=8)
    wd = WIRE.acquire_wire_dtypes(cfg)
    for f in ("prio", "inbound", "pre_verdict", "count"):
        want = np.dtype(wd.get(f, np.int32))
        assert np.dtype(getattr(acq, f).dtype) == want, f
    comp = E.empty_complete(cfg, b=8)
    wdc = WIRE.complete_wire_dtypes(cfg)
    for f in ("inbound", "success", "error"):
        want = np.dtype(wdc.get(f, np.int32))
        assert np.dtype(getattr(comp, f).dtype) == want, f


# -- client path: packed vs reference, one upload, fail-closed ---------------


def _drive(c, rules, rounds=6):
    """Deterministic mixed traffic; returns the flat verdict/wait lists."""
    c.flow_rules.load(rules)
    got = []
    for i in range(rounds):
        names = [f"wiretest/r{j % 3}" for j in range(4 + (i % 3))]
        got.extend(c.check_batch(names, inbound=True))
        # completions exercise the c.* upload columns too
        rids = np.array(
            [c.registry.resource_id(n) for n in names[:3]], np.int32
        )
        c.submit_completion_block(
            rids, rt=np.full(3, 1.0 + i, np.float32),
            inbound=np.ones(3, np.int32),
        )
        c.time.advance(50)
        c.tick_once()
    return got


def test_packed_client_bit_identical_to_reference_client(client_factory, vt):
    """The packed client (fused readback + one narrow-column upload) must
    produce bit-identical verdicts and waits to a packed_wire=False
    reference client over identical traffic."""
    from sentinel_tpu.utils.time_source import VirtualTimeSource

    rules = [
        FlowRule(resource="wiretest/r0", count=3.0),
        FlowRule(
            resource="wiretest/r1", count=2.0,
            control_behavior=2, max_queueing_time_ms=400,
        ),  # RATE_LIMITER: produces PASS_WAIT rows through the sidecar
    ]
    ref_c = client_factory(
        cfg=small_engine_config(packed_wire=False),
        time_source=VirtualTimeSource(start_ms=1_000),
    )
    pk_c = client_factory(
        cfg=small_engine_config(packed_wire=True),
        time_source=VirtualTimeSource(start_ms=1_000),
    )
    assert pk_c.cfg.packed_wire is True
    ref = _drive(ref_c, rules)
    got = _drive(pk_c, rules)
    assert got == ref
    assert any(v == ERR.PASS_WAIT and w > 0 for v, w in ref)


#: rung of ops/wire.tick_shapes -> (its index, batch rows, rows a tick of
#: that shape carries here): a batch of 1,200 has the ladder 256 / 300 / 1,200
_RUNGS = {"light": (0, 64, 6), "middle": (1, 1200, 300), "full": (-1, 1200, 301)}


@pytest.mark.parametrize("shape", list(_RUNGS))
def test_client_defaults_to_packed_and_makes_one_upload_a_tick(
    client_factory, monkeypatch, shape
):
    """Tri-state default: the client resolves packed_wire=None to True.
    A packed tick of any shape makes exactly one host-to-device transfer,
    of exactly its input layout's bytes, says so on tick.assemble and
    counts it; and changed traffic changes the verdicts (every tick
    crosses whole: there is no cache a stale column could be served
    from)."""
    from sentinel_tpu import obs

    rung, batch, n = _RUNGS[shape]
    client = client_factory(
        cfg=small_engine_config(batch_size=batch, complete_batch_size=batch)
    )
    assert client.cfg.packed_wire is True
    client.flow_rules.load([FlowRule(resource="delta/r", count=4.0)])
    names = ["delta/r"] * n
    first = client.check_batch(names, inbound=True)

    sent = []  # every numpy array handed to the device while spying

    def spy(real):
        def f(x, *a, **kw):
            if isinstance(x, np.ndarray):
                sent.append(x.nbytes)
            return real(x, *a, **kw)
        return f

    monkeypatch.setattr(jax, "device_put", spy(jax.device_put))
    monkeypatch.setattr(jnp, "asarray", spy(jnp.asarray))
    tx0 = _metric("sentinel_wire_bytes_total", path="device", direction="tx")
    obs.TRACER.reset()
    obs.enable()
    try:
        second = client.check_batch(names, inbound=True)
    finally:
        obs.disable()
        monkeypatch.undo()
    b, b2 = WIRE.tick_shapes(client.cfg)[rung]  # the smallest that holds n rows
    assert b >= n and (rung == 0 or WIRE.tick_shapes(client.cfg)[rung - 1][0] < n)
    nbytes = WIRE.input_layout_for(client.cfg, b, b2).nbytes
    assert sent == [nbytes]
    assert _metric(
        "sentinel_wire_bytes_total", path="device", direction="tx"
    ) - tx0 == nbytes
    spans = obs.TRACER.snapshot()
    (asm,) = [s for s in spans if s["name"] == "tick.assemble"]
    assert asm["attrs"]["puts"] == 1 and asm["attrs"]["tx_bytes"] == nbytes
    assert (asm["attrs"]["b"], asm["attrs"]["b2"]) == (b, b2)
    # counted where the benchmark's span_summary prints it
    assert obs.summarize(spans)["tick.assemble"]["puts"] == {1: 1}
    # identical traffic, window used up
    assert [v for v, _ in second].count(int(ERR.PASS)) == 0
    assert len(first) == len(second) == n
    assert [v for v, _ in first].count(int(ERR.PASS)) == 4
    # changed traffic: the verdicts track it
    client.time.advance(client.cfg.second_window_ms * client.cfg.second_sample_count + 10)
    third = client.check_batch(["delta/r"] * 2 + ["delta/other"] * 4)
    assert [v for v, _ in third] == [int(ERR.PASS)] * 6


# -- the input wire: host pack -> device unpack ------------------------------

_INPUT_CASES = {
    # name: (engine overrides, the dtype the clamped counts cross at)
    "uint8-counts": (dict(param_dims=1, max_batch_count=255), np.uint8),
    "int16-counts": (dict(param_dims=2, max_batch_count=0x7FFF), np.int16),
    "int32-counts": (dict(param_dims=3, max_batch_count=70000), np.int32),
    "unfused": (dict(param_dims=4, fused_effects=False, seg_effects=False), np.int32),
}


@pytest.mark.parametrize("shape", list(_RUNGS))
@pytest.mark.parametrize("case", sorted(_INPUT_CASES))
def test_input_wire_round_trip(case, shape):
    """Host pack -> unpack_tick_input on the device gives, leaf for leaf
    (values and dtypes), the batches the classic path builds from the same
    narrow columns and widens at the tick's entry; the header's now_ms,
    load and cpu come back exact; an idle side is its fill, each way."""
    over, count_dt = _INPUT_CASES[case]
    cfg = small_engine_config(**{
        **dict(packed_wire=True, use_mxu_tables=True, fused_effects=True,
               seg_effects=True, batch_size=1200, complete_batch_size=1120),
        **over,
    })
    assert WIRE._count_dtype(cfg) is count_dt
    assert WIRE.tick_shapes(cfg) == ((256, 256), (300, 280), (1200, 1120))
    b, b2 = WIRE.tick_shapes(cfg)[_RUNGS[shape][0]]
    lo = WIRE.input_layout_for(cfg, b, b2)
    assert WIRE.input_layout_of(cfg, lo.total) == lo
    assert [c.field for c in lo.acq] == list(E.AcquireBatch._fields)
    assert [c.field for c in lo.comp] == list(E.CompleteBatch._fields)
    rng = np.random.default_rng(30)

    def column(c):  # a batch column, at its wire dtype
        if c.dtype == np.float32:
            return rng.random(c.shape).astype(np.float32) * 100
        info = np.iinfo(c.dtype)
        return rng.integers(
            max(info.min, -(2**31)), min(info.max, 2**31 - 1), c.shape,
            dtype=np.int64, endpoint=True,
        ).astype(c.dtype)

    def host(x):  # as the wire carries it: a 2-D column lane by lane
        return x.T if x.ndim == 2 else x

    unpack = jax.jit(WIRE.unpack_tick_input, static_argnums=1)
    for idle in (None, "acq", "comp"):
        wb = WIRE.InputBuffer(lo)
        want_a = {c.field: column(c) for c in lo.acq}
        want_c = {c.field: column(c) for c in lo.comp}
        if idle == "acq":
            wb.idle_acquire()
            want_a = {c.field: np.full(c.shape, c.fill, c.dtype) for c in lo.acq}
        else:
            for f, x in want_a.items():
                wb.acq[f][...] = host(x)
        if idle == "comp":
            wb.idle_complete()
            want_c = {c.field: np.full(c.shape, c.fill, c.dtype) for c in lo.comp}
        else:
            for f, x in want_c.items():
                wb.comp[f][...] = host(x)
        now, load, cpu = int(rng.integers(-(2**31), 2**31)), 0.1 + rng.random(), rng.random()
        wb.set_header(now, load, cpu)
        assert wb.buf.nbytes == lo.nbytes and int(wb.buf[0]) == WIRE.WIRE_IN_MAGIC
        got_a, got_c, t, ld, cp = unpack(jnp.asarray(wb.buf), lo)
        ref_a = WIRE.widen_acquire(
            E.AcquireBatch(**{f: jnp.asarray(x) for f, x in want_a.items()})
        )
        ref_c = WIRE.widen_complete(
            E.CompleteBatch(**{f: jnp.asarray(x) for f, x in want_c.items()})
        )
        for got, ref in ((got_a, ref_a), (got_c, ref_c)):
            for f in ref._fields:
                g, r = getattr(got, f), getattr(ref, f)
                assert g.dtype == r.dtype and g.shape == r.shape, (f, idle)
                assert np.asarray(g).tobytes() == np.asarray(r).tobytes(), (f, idle)
        assert t.dtype == jnp.int32 and int(t) == now
        assert ld.dtype == jnp.float32 and float(ld) == float(np.float32(load))
        assert cp.dtype == jnp.float32 and float(cp) == float(np.float32(cpu))
        if idle == "acq":
            ref = E.empty_acquire(cfg, b=b)
            for f in ref._fields:
                assert np.array_equal(np.asarray(getattr(got_a, f)), np.asarray(getattr(ref, f)))
        if idle == "comp":
            ref = E.empty_complete(cfg, b=b2)
            for f in ref._fields:
                assert np.array_equal(np.asarray(getattr(got_c, f)), np.asarray(getattr(ref, f)))


def test_corrupt_fused_readback_fails_tick_closed(client_factory):
    """chaos transport.packed.decode corrupt: the decoder must DETECT the
    mangled buffer (checksum), count it, and the tick must fail CLOSED —
    every caller gets BLOCK_SYSTEM, nothing hangs or passes.  The site
    pipes only the fail-CLOSED main section (the trailing explain block
    fails OPEN via its own obs.explain.decode site — test_explain.py),
    so this holds with the explain section present."""
    c = client_factory()
    c.flow_rules.load([FlowRule(resource="fc/r", count=100.0)])
    assert [v for v, _ in c.check_batch(["fc/r"] * 4)] == [int(ERR.PASS)] * 4
    dec0 = _metric("sentinel_packed_decode_failures_total")
    plan = FaultPlan(
        name="wire-corrupt", seed=11,
        faults=[FaultSpec("transport.packed.decode", "corrupt", max_fires=1)],
    )
    with FP.armed(plan) as st:
        got = c.check_batch(["fc/r"] * 4)
        assert st.injected().get("transport.packed.decode:corrupt") == 1
    assert [v for v, _ in got] == [int(ERR.BLOCK_SYSTEM)] * 4
    assert _metric("sentinel_packed_decode_failures_total") == dec0 + 1
    # recovery: the next tick decodes clean again
    assert [v for v, _ in c.check_batch(["fc/r"] * 2)] == [int(ERR.PASS)] * 2


def test_short_read_fused_readback_fails_tick_closed(client_factory):
    """A dropped/truncated fused buffer trips the length check."""
    c = client_factory()
    c.flow_rules.load([FlowRule(resource="fs/r", count=100.0)])
    c.check_batch(["fs/r"] * 2)
    dec0 = _metric("sentinel_packed_decode_failures_total")
    plan = FaultPlan(
        name="wire-short", seed=3,
        faults=[FaultSpec("transport.packed.decode", "short_read", max_fires=1)],
    )
    with FP.armed(plan):
        got = c.check_batch(["fs/r"] * 3)
    assert [v for v, _ in got] == [int(ERR.BLOCK_SYSTEM)] * 3
    assert _metric("sentinel_packed_decode_failures_total") == dec0 + 1


def test_single_fused_readback_accounting(client_factory):
    """Packed rx accounting: one tick moves exactly the layout's bytes
    (minus timeline, accounted on its own path) — not four transfers."""
    c = client_factory()
    c.registry.resource_id("acct/r")
    c.check_batch(["acct/r"] * 4)  # warm both shapes / const cols
    rx0 = _metric("sentinel_wire_bytes_total", path="device", direction="rx")
    tl0 = _metric("sentinel_wire_bytes_total", path="timeline", direction="rx")
    c.check_batch(["acct/r"] * 4)
    lo = c._wire_layout(c.cfg, min(256, c.cfg.batch_size))
    d_rx = _metric(
        "sentinel_wire_bytes_total", path="device", direction="rx"
    ) - rx0
    d_tl = _metric(
        "sentinel_wire_bytes_total", path="timeline", direction="rx"
    ) - tl0
    tl_bytes = lo.tl_rows * lo.tl_cols * 4
    assert d_rx == lo.total * 4 - tl_bytes
    assert d_tl == tl_bytes
