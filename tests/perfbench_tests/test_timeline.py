"""The readers and reductions of ``perfbench/timeline.py`` on hand-built
spans and traces whose answers are known, on the slice recorded on the chip
that keeps ``op_name`` and the step events (``data/timeline_slice.json``),
and on the old slice, which must reduce to what it always did."""

import json
import os

import numpy as np
import pytest

from perfbench import manifest as M
from perfbench import timeline, xplane
from perfbench.deployments import single_client
from perfbench.readers import Context, span_each, span_stat, span_unnamed

US = 1_000
MS = 1_000_000
TICK, POOL = 7, 8  # thread ids
DATA = os.path.join(os.path.dirname(__file__), "data")
NEW_METRICS = ("queue_wait_ms.lat", "resident_ms.lat", "resolver_wait_ms.lat",
               "tick_unnamed_pct.flood", "tick_unnamed_pct.lat")


def span(name, t0_us, dur_us, trace=0, tid=TICK, **attrs):
    return {"name": name, "t0_ns": t0_us * US, "dur_ns": dur_us * US, "trace": trace,
            "tid": tid, "attrs": attrs}


def one_tick(i, t0_us, presort_us=300, gap_us=0):
    """The spans of tick ``i`` as the client records them: a period of 2 ms
    on the tick thread, tiled but for ``gap_us`` before the drain."""
    t = t0_us + gap_us
    asm = 500
    return [
        span("tick.lock", t0_us - 20, 20, i),
        span("tick.drain", t, 100, i, n_blk=64),
        span("req.queue", t - 400, 450, i, tid=99, n=64, kind="block"),
        # assemble is recorded with its own duration, presort inside it
        span("tick.assemble", t + 100, asm, i),
        span("tick.presort", t + 200, presort_us, i),
        span("tick.dispatch", t + 100 + asm + presort_us, 200, i, call_ns=150_000),
        span("tick.handoff", t + 800 + presort_us, 100, i, pending=1, resolvers=1),
        span("tick.idle", t + 900 + presort_us, 1080 - presort_us - gap_us, why="interval"),
        span("tick.resident", t + 800 + presort_us, 3000, i, tid=POOL, handed_ns=0),
        span("tick.wait", t + 3800 + presort_us, 400, i, tid=POOL),
        span("tick.device", t + 800 + presort_us, 3400, i, tid=POOL),
        span("tick.readback", t + 4200 + presort_us, 50, i, tid=POOL),
        span("tick.resolve", t + 4250 + presort_us, 100, i, tid=POOL, n_blk=64),
    ]


def test_unnamed_share_of_a_tiled_thread_is_zero_and_a_gap_shows():
    tiled = [s for i in range(5) for s in one_tick(i + 1, 10_000 + 2000 * i)]
    assert timeline.unnamed_share(tiled) == pytest.approx(0.0, abs=1e-9)
    # 100 us, and 20 us without the wait for the tick mutex, under no span in
    # each of the four periods
    holed = [s for i in range(5) for s in one_tick(i + 1, 10_000 + 2000 * i, gap_us=100)]
    holed = [s for s in holed if s["name"] != "tick.lock"]
    assert timeline.unnamed_share(holed) == pytest.approx(100.0 * 120 / 2000, rel=1e-6)
    ctx = Context(window=None, setup_s=0.0, batch=512, spans=holed)
    assert span_unnamed.read(ctx) == timeline.unnamed_share(holed)
    # and the holes are placed: between which two spans, how many, how long
    assert timeline.unnamed_between(tiled) == []
    assert timeline.unnamed_between(holed) == [
        ["tick.idle", "tick.drain", 4, pytest.approx(4 * 120e-6)]]


def test_assemble_and_presort_together_reach_the_dispatch():
    """Without the presort folded into the assemble interval the thread would
    show a hole where the upload after the presort ran."""
    spans = [s for i in range(3) for s in one_tick(i + 1, 10_000 + 2000 * i, presort_us=300)]
    assert timeline.unnamed_share(spans) == pytest.approx(0.0, abs=1e-9)
    no_presort = [dict(s, trace=0) if s["name"] == "tick.presort" else s for s in spans]
    assert timeline.unnamed_share(no_presort) > 5.0


def test_spans_of_other_threads_do_not_cover_the_tick_thread():
    spans = [s for i in range(3) for s in one_tick(i + 1, 10_000 + 2000 * i)]
    spans = [s for s in spans if s["name"] != "tick.idle"]
    spans.append(span("tick.wait", 10_000, 6000, 1, tid=POOL))
    assert timeline.unnamed_share(spans) > 30.0


def test_a_program_without_the_new_spans_reads_nothing():
    old = [s for i in range(3) for s in one_tick(i + 1, 10_000 + 2000 * i)
           if s["name"] in ("tick.assemble", "tick.presort", "tick.dispatch", "tick.device",
                            "tick.readback", "tick.resolve")]
    ctx = Context(window=None, setup_s=0.0, batch=512, spans=old)
    assert span_unnamed.read(ctx) is None
    assert span_each.read(ctx, span="req.queue") is None
    assert span_stat.read(ctx, spans=["tick.resident"]) is None
    assert timeline.closure(old, np.array([5.0]), np.zeros(0)) is None


def test_span_each_reads_every_span_of_a_name():
    spans = [span("req.queue", 0, 100, 1), span("req.queue", 10, 300, 1),
             span("req.queue", 20, 500, 2), span("tick.wait", 0, 9999, 1)]
    ctx = Context(window=None, setup_s=0.0, batch=512, spans=spans)
    assert span_each.read(ctx, span="req.queue") == pytest.approx(0.3)
    assert span_each.read(ctx, span="req.queue", stat="p50") == pytest.approx(0.3)
    # span_stat keeps one span per tick id: the reason for the new reader
    assert span_stat.read(ctx, spans=["req.queue"]) == pytest.approx(0.4)


def test_closure_sums_the_path_of_each_request():
    spans = [s for i in range(4) for s in one_tick(i + 1, 10_000 + 2000 * i)]
    # queue 450 + assemble 500 + presort 300 + dispatch 200 + resident 3000
    # + wait 400 + readback 50 + resolve 100
    got = timeline.closure(spans, np.array([5.2, 5.2, 5.2]), np.array([0.1, 0.1]))
    assert got["requests"] == 4
    assert got["sum_of_spans_p50_ms"] == pytest.approx(5.0)
    assert got["latency_less_lateness_p50_ms"] == pytest.approx(5.1)
    assert got["residual_ms"] == pytest.approx(0.1)
    assert got["residual_pct"] == pytest.approx(100 * 0.1 / 5.1)
    # submit (queue start) to the end of resolve, by the timestamps
    assert got["submit_to_resolved_p50_ms"] == pytest.approx((4250 + 300 + 100 + 400) / 1000)
    # the caller's own spans of an entry() are added as means
    spans += [span("req.admit", 0, 30, 1, tid=50), span("req.wake", 0, 170, 1, tid=50)]
    got = timeline.closure(spans, np.array([5.2]), np.zeros(0))
    assert got["sum_of_spans_p50_ms"] == pytest.approx(5.2)
    assert got["mean_ms"]["req.wake"] == pytest.approx(0.17)


def test_covering_names_what_each_side_was_under():
    spans = [s for i in range(3) for s in one_tick(i + 1, 10_000 + 2000 * i)]
    got = timeline.covering(spans, 10_000 * US, 12_000 * US)
    assert got["tick_thread"]["unnamed_s"] == pytest.approx(0.0, abs=1e-9)
    assert got["tick_thread"]["seconds"]["tick.idle"] == pytest.approx(780e-6)
    assert got["tick_thread"]["longest"][0] == "tick.idle"
    assert got["resolvers"]["longest"][0] == "tick.device"


# -- the profiler's side --------------------------------------------------------

KERNEL = ('%seg_excl_cumsum.3 = s32[1,4096]{1,0} custom-call(s32[1,4096]{1,0} %p), '
          'custom_call_target="tpu_custom_call", metadata={op_name="jit(sentinel_tick)/'
          'stage.exits/seg_excl_cumsum/pallas_call" stack_frame_id=7}')
BARE_KERNEL = '%call.9 = f32[8]{0} custom-call(f32[8]{0} %p), custom_call_target="tpu_custom_call"'
SORT = ('%sort.2 = s32[8]{0} sort(s32[8]{0} %b), metadata={op_name="jit(sentinel_tick)/'
        'stage.seg_prepare/sort"}')
FLOW = ('%fusion.1 = s32[8]{0} fusion(s32[8]{0} %a), kind=kLoop, metadata={op_name='
        '"jit(sentinel_tick)/stage.checks/stage.flow/add"}')
SKETCH = ('%fusion.5 = s32[8]{0} fusion(s32[8]{0} %a), kind=kLoop, metadata={op_name='
          '"jit(sentinel_tick)/stage.effects/stage.sketch/stage.sketch/mul"}')
PLAIN = "%copy.7 = s32[8]{0} copy(s32[8]{0} %a)"


def test_scope_of_reads_stage_and_kernel_from_the_op_name():
    assert timeline.scope_of(KERNEL) == ("exits", "seg_excl_cumsum")
    assert timeline.scope_of(SORT) == ("seg_prepare", None)
    assert timeline.scope_of(FLOW) == ("checks/flow", None)
    assert timeline.scope_of(SKETCH) == ("effects/sketch", None)
    assert timeline.scope_of(PLAIN) == ("-", None)
    assert timeline.scope_of(BARE_KERNEL) == ("-", None)


# tsl's xplane.proto by hand: just enough of an encoder to check the reader


def _vi(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _ld(number, body):
    return _vi(number << 3 | 2) + _vi(len(body)) + body


def _int(number, n):
    return _vi(number << 3) + _vi(n)


def _plane(name, events, stat_names):
    body = _int(1, 7) + _ld(2, name.encode())
    body += _ld(3, _ld(2, b"XLA Ops") + _ld(4, _int(1, 1) + _int(3, 5)))  # a line: skipped
    for i, (text, stats) in enumerate(events, 1):
        meta = _int(1, i) + _ld(2, text.encode()) + _ld(3, b"\x00\x01") + b"".join(
            _ld(5, stat) for stat in stats)
        body += _ld(4, _int(1, i) + _ld(2, meta))
    for i, n in stat_names.items():
        body += _ld(5, _int(1, i) + _ld(2, _int(1, i) + _ld(2, n.encode())))
    return _ld(1, body)


def test_event_metadata_reads_the_stats_the_python_reader_leaves_out(tmp_path):
    import struct

    path = "jit(sentinel_tick)/stage.exits/seg_excl_cumsum/pallas_call"
    names = {1: "tf_op", 2: "flops", 3: "hlo_category", 4: "custom-call", 5: "occupancy"}
    stats = [_int(1, 1) + _ld(5, (path + ":").encode()), _int(1, 2) + _int(3, 4096),
             _int(1, 3) + _int(7, 4), _int(1, 5) + _vi(2 << 3 | 1) + struct.pack("<d", 0.5)]
    space = (_plane("/device:TPU:0", [(BARE_KERNEL, stats), (PLAIN, [])], names)
             + _plane("/host:CPU", [("python", [])], {}) + _ld(4, b"host-1"))
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(space)
    meta = timeline.event_metadata(str(f))
    assert list(meta) == ["/device:TPU:0"]
    assert meta["/device:TPU:0"] == {
        BARE_KERNEL: {"tf_op": path + ":", "flops": 4096, "hlo_category": "custom-call",
                      "occupancy": 0.5},
        PLAIN: {},
    }
    # the operation's text holds no op_name: its metadata does
    plane = meta["/device:TPU:0"]
    assert timeline.op_path(BARE_KERNEL) == "" and timeline.op_path(BARE_KERNEL, plane) == path + ":"
    assert timeline.scope_of(BARE_KERNEL, plane) == ("exits", "seg_excl_cumsum")
    assert timeline.scope_of(PLAIN, plane) == ("-", None)
    spans, pd = traced_ticks()
    got = timeline.device_stages(pd, meta)
    assert got["unnamed_kernel_ops"] == 0
    assert got["kernels"]["seg_excl_cumsum"] == pytest.approx(4 * 150e-6 + 4 * 20e-6)
    # and a slice written with it keeps the path in the text
    back = timeline.from_json(timeline.to_json(pd, 0.004, meta))
    texts = [ev.name for ev in back.planes[1].lines[0].events]
    assert timeline.scope_of(texts[3]) == ("exits", "seg_excl_cumsum")


def trace(ops, modules, steps, mark=(0, 10_000 * US)):
    def events(rows):
        return [{"name": n, "start_ns": s * US, "duration_ns": d * US} for n, s, d in rows]

    return timeline.from_json({"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                {"name": xplane.WINDOW_MARK, "start_ns": mark[0],
                 "duration_ns": mark[1] - mark[0]}]},
            {"name": "tick", "events": [
                {"name": timeline.STEP_MARK, "start_ns": at * US, "duration_ns": 900 * US,
                 "stats": [["_r", 1], ["step_num", t]]} for t, at in steps]},
        ]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": events(ops)},
            {"name": "XLA Modules", "events": events(modules)},
        ]},
    ]})


#: the file's clock runs 1 s behind monotonic_ns
OFFSET_US = 1_000_000


def traced_ticks(n=4, jitter_us=(0, 2, -2, 0)):
    """``n`` ticks 2 ms apart; each execution starts 50 us after its
    dispatch span ends and runs 400 us; tick ids start at 11."""
    spans, ops, modules, steps = [], [], [], []
    for i in range(n):
        t = 1000 + 2000 * i  # file clock, us
        steps.append((11 + i, t + 100 + jitter_us[i % len(jitter_us)]))
        spans += [dict(s, t0_ns=s["t0_ns"] + OFFSET_US * US) for s in one_tick(11 + i, t)]
        start = t + 100 + 500 + 300 + 200 + 50  # drain, assemble, presort, dispatch
        modules.append((f"jit_sentinel_tick({i})", start, 400))
        ops += [(SORT, start, 100), (KERNEL, start + 100, 150), (FLOW, start + 260, 100),
                (BARE_KERNEL, start + 370, 20)]
    modules.append(("jit_and(5)", 9000, 3))
    ops.append((PLAIN, 9000, 3))
    return spans, trace(ops, modules, steps, mark=(0, max(10_000, 2000 * n + 2000) * US))


def test_clock_tie_takes_the_median_over_the_ticks_and_reports_the_spread():
    spans, pd = traced_ticks()
    tie = timeline.clock_tie(pd, spans, mark_mono_ns=123)
    # drain ends and assemble starts at the same instant here, t + 100
    assert tie.points == 4 and tie.offset_ns == OFFSET_US * US
    assert tie.spread_max_us == pytest.approx(2.0) and tie.spread_p50_us == pytest.approx(1.0)
    assert tie.bracket_p50_us == pytest.approx(0.0)


def test_without_step_events_the_window_mark_ties_the_clocks():
    spans, pd = traced_ticks()
    pd.planes[0].lines.pop()
    tie = timeline.clock_tie(pd, spans, mark_mono_ns=5 * MS)
    assert tie.points == 0 and tie.offset_ns == 5 * MS and tie.spread_p50_us is None


def test_every_execution_is_joined_to_the_tick_that_dispatched_it():
    spans, pd = traced_ticks()
    e0, e1 = timeline.executions(pd)
    assert len(e0) == 4  # jit_and holds the device for less
    joined = timeline.join(e0, e1, OFFSET_US * US, spans)
    assert joined.tick_id.tolist() == [11, 12, 13, 14] and joined.unjoined == 0
    assert (joined.end_ns - joined.start_ns).tolist() == [400 * US] * 4
    # an execution whose tick was dispatched before the window: no tick id
    late = [s for s in spans if s["trace"] != 11]
    joined = timeline.join(e0, e1, OFFSET_US * US, late)
    assert joined.tick_id.tolist() == [12, 13, 14] and joined.unjoined == 1
    # a tick whose execution fell past the window's end: no execution
    joined = timeline.join(e0[:3], e1[:3], OFFSET_US * US, spans)
    assert joined.tick_id.tolist() == [11, 12, 13] and joined.unjoined == 0
    # one pair out of order among many does not shift the alignment: it is counted
    many_spans, many = traced_ticks(n=60)
    e0, e1 = timeline.executions(many)
    e0[7] -= 1500 * US
    joined = timeline.join(e0, e1, OFFSET_US * US, many_spans)
    assert joined.tick_id.tolist() == list(range(11, 71))
    assert joined.unjoined == 0 and joined.early == 1


def test_ready_unread_is_execution_end_to_the_start_of_the_wait():
    spans, pd = traced_ticks()
    e0, e1 = timeline.executions(pd)
    joined = timeline.join(e0, e1, OFFSET_US * US, spans)
    unread = timeline.ready_unread_ms(joined, spans)
    # the wait starts 3800 + 300 us into the tick, the execution ends 1550 in
    assert unread.tolist() == pytest.approx([(4100 - 1550) / 1000] * 4)
    # a resolver that came before the device had finished counts 0
    early = [dict(s, t0_ns=s["t0_ns"] - 3000 * US) if s["name"] == "tick.wait" else s
             for s in spans]
    assert timeline.ready_unread_ms(joined, early).tolist() == [0.0] * 4


def test_device_stages_sums_leaf_operations_by_stage_and_kernel():
    spans, pd = traced_ticks()
    got = timeline.device_stages(pd)
    assert got["stages"]["seg_prepare"] == pytest.approx(4 * 100e-6)
    assert got["stages"]["exits"] == pytest.approx(4 * 150e-6)
    assert got["stages"]["checks/flow"] == pytest.approx(4 * 100e-6)
    assert got["stages"]["-"] == pytest.approx(4 * 20e-6 + 3e-6)
    assert got["kernels"] == {"seg_excl_cumsum": pytest.approx(4 * 150e-6)}
    assert got["unnamed_kernel_ops"] == 4


def test_reduce_prints_one_object_and_splits_host_other():
    spans, pd = traced_ticks()
    win = type("W", (), {"open_ns": OFFSET_US * US, "latency_ms": np.array([5.0]),
                         "late_ms": np.zeros(0)})()
    got = timeline.reduce(pd, win, spans)
    json.dumps(got)
    assert got["clock_tie_points"] == 4 and got["unjoined"] == 0 and got["joined"] == 4
    assert got["tick_program_seen"] is True
    assert got["execution_end_after_dispatch_end_ms"]["p50"] == pytest.approx(0.45)
    assert got["ready_unread_ms"]["mean"] == pytest.approx(2.55)
    assert set(got["idle_by_span_s"]) >= {"tick.idle", "tick.drain", "tick.handoff"}
    assert sum(got["idle_by_span_s"].values()) == pytest.approx(
        10_000e-6 - 4 * 370e-6 - 3e-6)


# -- recorded slices ------------------------------------------------------------


def test_a_slice_keeps_what_this_module_reads():
    spans, pd = traced_ticks()
    data = timeline.to_json(pd, 0.004)
    back = timeline.from_json(data)
    assert timeline.step_marks(back) == {11: 1100 * US, 12: 3102 * US}
    ops = [ev.name for ev in back.planes[1].lines[0].events]
    assert timeline.scope_of(ops[1]) == ("exits", "seg_excl_cumsum") and xplane.KERNEL in ops[1]


def test_the_old_slice_reduces_to_what_it_did():
    """The recorded slice of PR 23 still gives the device numbers written down
    when it was taken.  Its idle seconds are attributed as ``idle_by_span_s``
    always was (PR 27): assemble's interval reaches its tick's dispatch, so
    0.0076 of the 0.0098 s that read ``host_other`` are the uploads after the
    presort, and the presort keeps what it had."""
    with open(os.path.join(DATA, "trace_slice.json")) as f:
        rec = json.load(f)
    s = xplane.summarize(xplane.from_json(rec["trace"]), rec["open_ns"],
                         single_client.host_intervals(rec["spans"]))
    assert s.window_s == 0.08 and s.busy_s == 0.025188909
    assert s.tick_busy_ms.tolist() == [8.396657, 8.396199, 8.396053]
    assert s.tick_kernels_ms.tolist() == [1.815539, 1.815542, 1.815545]
    assert s.clock_offset_ns == 1370953155124
    assert s.idle_gaps == [
        ("tick.presort", 0.035699949), ("tick.assemble", 0.012803766),
        ("tick.resolve", 0.002239379), ("host_other", 0.002136775),
        ("tick.readback", 0.001886839), ("in_program", 4.4383e-05)]
    assert 0.012803766 + 0.002136775 == pytest.approx(0.005189998 + 0.009750543)
    assert [n for n, _ in s.device_ops[:3]] == [
        "branch_1_fun.32__mosaic", "sort.217", "branch_1_fun.30__mosaic"]
    # the old slice holds no step event: the one tie point is the window mark's
    pd = timeline.from_json(rec["trace"])
    tie = timeline.clock_tie(pd, rec["spans"], rec["open_ns"])
    assert tie.points == 0 and tie.offset_ns == s.clock_offset_ns
    # and under one tie the two callers of the one attribution read the same
    by_span = xplane.idle_by(pd, tie.offset_ns, single_client.host_intervals(rec["spans"]))
    assert {n: v for n, v in by_span.items() if v > 0} == dict(s.idle_gaps)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "timeline_slice.json")) as f:
        return json.load(f)


def test_the_recorded_slice_ties_joins_and_names(recorded):
    """A slice of a traced window on a TPU v5e, recorded by
    ``perfbench/timeline.py trace --slice-out``."""
    pd = timeline.from_json(recorded["trace"])
    spans = recorded["spans"]
    tie = timeline.clock_tie(pd, spans, recorded["open_ns"])
    ticks = sum(s["name"] == "tick.dispatch" for s in spans)
    assert tie.points >= ticks - 2 and tie.points >= 2
    assert tie.spread_max_us < 100.0
    # the per-tick tie and the window mark's agree to well under a tick
    w0, _w1 = xplane.window_mark(pd)
    assert abs(tie.offset_ns - (recorded["open_ns"] - int(w0))) < 2 * MS
    e0, e1 = timeline.executions(pd)
    joined = timeline.join(e0, e1, tie.offset_ns, spans)
    assert len(joined.tick_id) >= 1 and joined.unjoined <= 1
    starts = {s["trace"]: s["t0_ns"] for s in spans if s["name"] == "tick.dispatch"}
    for t, at in zip(joined.tick_id, joined.start_ns):
        assert at >= starts[int(t)] - 50_000
    stages = timeline.device_stages(pd)
    assert stages["unnamed_kernel_ops"] == 0
    assert set(stages["kernels"]) <= {"seg_excl_cumsum", "seg_incl_min", "scatter_many",
                                      "gather_many"}
    assert {"checks", "effects"} & {k.split("/")[0] for k in stages["stages"]}
    names = {xplane.program(ev.name) for ev in pd.planes[1].lines[0].events
             } | {xplane.program(ev.name) for ev in pd.planes[1].lines[1].events}
    assert timeline.TICK_PROGRAM in names
    # breakdown.idle_gaps and idle_by_span_s are one function under two ties of
    # the clocks: the same names, the same seconds within 1 % of the window
    host = single_client.host_intervals(spans)
    gaps = dict(xplane.summarize(pd, recorded["open_ns"], host).idle_gaps)
    by_span = xplane.idle_by(pd, tie.offset_ns, host)
    assert set(gaps) == {n for n, v in by_span.items() if v > 0} > {"tick.idle", "tick.drain"}
    assert all(abs(by_span[n] - v) < 0.01 * 0.02 for n, v in gaps.items())
    assert max(gaps, key=gaps.get) == "tick.idle"  # an entry cell: was host_other


def test_the_manifest_is_sound_with_the_new_metrics():
    manifest = M.load()
    assert M.problems(manifest) == []
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert name in per_layer and per_layer[name]["source"] == "program_span"
        assert os.path.exists(os.path.join(M.ROOT, M.HERE, "readers",
                                           M.metric(name)["reader"] + ".py"))
    # in the order they were appended in; what a later PR appends comes after
    assert [n for n in per_layer if n in NEW_METRICS] == list(NEW_METRICS)


def test_the_window_mark_carries_the_hosts_clock_at_its_opening():
    """The mark opens a wake-up and a few statements after the window was due
    to open: the tie is read beside the mark, not taken from the schedule."""
    import time

    from perfbench import run
    from sentinel_tpu import obs

    hooks = run._Hooks(True)
    before = time.monotonic_ns()
    hooks.opened()
    try:
        assert before <= hooks.mark_ns <= time.monotonic_ns() and obs.enabled()
    finally:
        hooks.closed()
    assert not obs.enabled()
    plain = run._Hooks(False)
    plain.opened()
    plain.closed()
    assert plain.mark_ns is None and plain.setup_s > 0 and not obs.enabled()


@pytest.mark.parametrize("cmd", ["trace", "spans"])
def test_a_cell_of_another_kind_is_refused_in_one_line(cmd, monkeypatch, capsys):
    """This module knows one kind's spans by name; the harness proper knows
    none, and takes a kind's from its module."""
    monkeypatch.setattr(timeline.M, "config", lambda name: {"deployment": "token_mesh"})
    cell = M.load()["workloads"][0]["name"]
    assert timeline.main([cmd, "--workload", cell, "--seed", "1"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and out.err.count("\n") == 1
    assert f"cells of kind 'single_client'; {cell} is of kind 'token_mesh'" in out.err


# -- the served path rehearsed on the CPU with the spans on ----------------------


@pytest.fixture()
def big_ring(monkeypatch):
    """The harness's ring (``SENTINEL_TRACE_CAPACITY`` is read at import,
    long before a test runs): a fast host records more spans in the
    rehearsal's window than the default 8,192 hold."""
    from sentinel_tpu import obs
    from sentinel_tpu.obs import trace as OT

    ring = OT.SpanTracer(1 << 18)
    monkeypatch.setattr(OT, "TRACER", ring)
    monkeypatch.setattr(obs, "TRACER", ring)
    return ring


@pytest.mark.jitted
@pytest.mark.parametrize("cell", ["zipf-1m.paced", "zipf-10k.entry"])
def test_rehearsed_with_spans_on_the_tick_thread_is_tiled_and_the_ids_join(cell, big_ring):
    """The real threaded client under the cell's own generator, at the tiny
    size of ``test_rehearsal.py``, spans on and profiler off."""
    from tests.perfbench_tests import rehearsal

    entry = M.cell(M.load(), cell)
    sizes, params, _names = rehearsal.of(cell)
    out = timeline.spans_run(cell, 2**31 + 17, 1.5, sizes=sizes, require_tpu=False,
                             params_override=params, untraced_first=False)
    spans, on = out["spans"], out["spans_on"]
    assert on["failed"] == 0 and on["attempted"] > 0
    assert on["ring_capacity"] == big_ring.capacity and on["ring_wrapped"] is False
    # the tick thread is tiled: under 5 % of its wall time lies under no span
    assert 0.0 <= on["tick_unnamed_pct"] < 5.0
    # every request's span carries the id of a tick that was dispatched
    dispatched = {s["trace"] for s in spans if s["name"] == "tick.dispatch"}
    queued = [s for s in spans if s["name"] == "req.queue"]
    assert len(queued) >= on["attempted"] // 2
    late = max(dispatched)  # a tick dispatched after the window closed is not in the spans
    assert all(s["trace"] in dispatched or s["trace"] > late for s in queued)
    assert {s["attrs"]["kind"] for s in queued} == {
        "entry" if entry["traffic"] == "entry-8t" else "block"}
    # for every tick, resident + wait is device, exactly
    per_tick = {}
    for s in spans:
        if s["name"] in ("tick.resident", "tick.wait", "tick.device"):
            per_tick.setdefault(s["trace"], {})[s["name"]] = s
    whole = [t for t in per_tick.values() if len(t) == 3]
    assert len(whole) >= 20
    for t in whole:
        assert t["tick.resident"]["dur_ns"] + t["tick.wait"]["dur_ns"] == t["tick.device"]["dur_ns"]
        assert t["tick.resident"]["attrs"]["handed_ns"] <= t["tick.wait"]["t0_ns"]
    # the request path closes on the CPU too, if loosely: a sum of spans on one clock
    assert abs(on["closure"]["residual_pct"]) < 25.0
    # the readers of the five new metrics find something to read
    ctx = Context(window=None, setup_s=0.0, batch=512, spans=spans)
    for name in NEW_METRICS:
        if name.endswith(".flood") != (entry["traffic"] == "flood-128k"):
            continue
        spec = M.metric(name)
        reader = __import__(f"perfbench.readers.{spec['reader']}", fromlist=["read"])
        assert reader.read(ctx, **spec["args"]) >= 0.0
