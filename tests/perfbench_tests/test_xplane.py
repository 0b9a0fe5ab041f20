"""The reduction from a profiler trace to device numbers: on hand-made
traces whose answers are known, and on a slice recorded on the chip
(``data/trace_slice.json``, the first 80 ms of a traced zipf-1m.paced
window on a TPU v5e) against a plain loop over the same events."""

import json
import os

import numpy as np
import pytest

from perfbench import xplane

US = 1_000


def trace(ops, modules, mark=(0, 1000 * US)):
    """A one-chip trace from ``(name, start_us, dur_us)`` tuples."""
    def events(rows):
        return [{"name": n, "start_ns": s * US, "duration_ns": d * US} for n, s, d in rows]

    return xplane.from_json({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            {"name": xplane.WINDOW_MARK, "start_ns": mark[0], "duration_ns": mark[1] - mark[0]}]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": events(ops)},
            {"name": "XLA Modules", "events": events(modules)},
        ]},
    ]})


KERNEL = '%branch_1_fun.3 = f32[2,128]{1,0} custom-call(s32[4]{0} %p), custom_call_target="tpu_custom_call"'
OPS = [
    ("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %a), kind=kLoop", 100, 50),
    (KERNEL, 150, 20),
    ("%sort.2 = s32[8]{0} sort(s32[8]{0} %b)", 200, 20),
    # a container: its time is its children's, and the 10 us between them
    ("%conditional.4 = (s32[8]{0}) conditional(s32[] %i, (s32[8]{0}) %t, (s32[8]{0}) %f)", 600, 100),
    ("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %a), kind=kLoop", 600, 50),
    (KERNEL, 660, 40),
    ("%and.9 = s32[2]{0} and(s32[2]{0} %x, s32[2]{0} %y)", 900, 1),
]
MODULES = [("jit_tick(111)", 90, 140), ("jit_tick(222)", 590, 120), ("jit_and(5)", 899, 3)]


def test_names_are_shortened_and_kernels_marked():
    assert xplane.short(OPS[0][0]) == "fusion.1"
    assert xplane.short(KERNEL) == "branch_1_fun.3__mosaic"
    assert xplane.program("jit_tick(111)") == "jit_tick"


def test_union_and_overlap():
    s, e = xplane.union(np.array([0., 5., 20., 21.]), np.array([10., 8., 30., 25.]))
    assert s.tolist() == [0., 20.] and e.tolist() == [10., 30.]
    got = xplane._overlap(np.array([5., 0., 12.]), np.array([25., 40., 18.]), s, e)
    assert got.tolist() == [10., 20., 0.]


def host(*named):
    """``(name, start_us, dur_us)`` in the spans' clock, 7 ms ahead of the
    file's, as ``deployments.host_intervals`` hands them over: in the order
    they are to be asked."""
    return [(n, np.array([7_000_000.0 + t0 * US]), np.array([7_000_000.0 + (t0 + dur) * US]))
            for n, t0, dur in named]


def test_hand_made_trace():
    spans = host(("tick.presort", 300, 200), ("tick.assemble", 250, 300))
    s = xplane.summarize(trace(OPS, MODULES), 7_000_000, spans)
    assert s.window_s == pytest.approx(1e-3)
    assert s.clock_offset_ns == 7_000_000
    # 100-170, 200-220, 600-700, 900-901
    assert s.busy_s == pytest.approx((70 + 20 + 100 + 1) * 1e-6)
    # the tick program is the one that holds the device longest
    assert s.tick_busy_ms.tolist() == pytest.approx([0.090, 0.100])
    assert s.tick_kernels_ms.tolist() == pytest.approx([0.020, 0.040])
    assert dict(s.device_ops)["fusion.1"] == pytest.approx(100e-6)
    assert dict(s.device_ops)["branch_1_fun.3__mosaic"] == pytest.approx(60e-6)
    assert "conditional.4" not in dict(s.device_ops)
    gaps = dict(s.idle_gaps)
    # inside the two tick programs: 90-100, 170-200, 220-230; 590-600, 700-710
    assert gaps["in_program"] == pytest.approx(70e-6)
    # 230-590 is idle between programs: presort covers 300-500, assemble the
    # rest of 250-550, nothing 0-90, 230-250, 550-590, 710-900 and 901-1000
    assert gaps["tick.presort"] == pytest.approx(200e-6)
    assert gaps["tick.assemble"] == pytest.approx(100e-6)
    assert gaps["host_other"] == pytest.approx((90 + 20 + 40 + 190 + 99) * 1e-6)
    assert sum(gaps.values()) + s.busy_s == pytest.approx(s.window_s, rel=1e-3)
    # the attribution behind it is the one function, under whatever offset
    assert xplane.idle_by(trace(OPS, MODULES), 7_000_000, spans) == pytest.approx(gaps)
    # the order given is the order asked: assemble first leaves the presort nothing
    swapped = dict(xplane.summarize(trace(OPS, MODULES), 7_000_000, spans[::-1]).idle_gaps)
    assert swapped["tick.assemble"] == pytest.approx(300e-6) and "tick.presort" not in swapped


def test_a_kind_that_names_no_span_reads_in_program_and_host_other():
    s = xplane.summarize(trace(OPS, MODULES), 7_000_000)
    assert dict(s.idle_gaps) == {"in_program": pytest.approx(70e-6),
                                 "host_other": pytest.approx((1000 - 191 - 70) * 1e-6)}
    # and so does one whose spans are not the first kind's
    doors = host(("door.answer", 230, 360))
    assert dict(xplane.summarize(trace(OPS, MODULES), 7_000_000, doors).idle_gaps) == {
        "in_program": pytest.approx(70e-6), "door.answer": pytest.approx(360e-6),
        "host_other": pytest.approx((1000 - 191 - 70 - 360) * 1e-6)}


def test_each_chips_own_busy_seconds_stand_beside_their_mean():
    """The result's ``busy_s`` is the mean over the chips the cell asks for;
    beside it the harness prints each plane's own.  (A chip on which nothing
    ran has no plane, and counts as idle: the next test.)"""
    one = xplane.summarize(trace(OPS, MODULES), 0, [])
    assert one.chip_busy_s == {"/device:TPU:0": pytest.approx(191e-6)}
    assert one.busy_s == one.chip_busy_s["/device:TPU:0"]
    two = trace(OPS, MODULES)
    two.planes.append(trace(OPS[:3], MODULES[:1]).planes[1])
    two.planes[-1].name = "/device:TPU:1"
    s = xplane.summarize(two, 0, [], chips=2)
    assert s.chip_busy_s == {"/device:TPU:0": pytest.approx(191e-6),
                             "/device:TPU:1": pytest.approx(90e-6)}
    assert s.busy_s == pytest.approx((191e-6 + 90e-6) / 2)
    assert len(s.tick_busy_ms) == 3
    assert dict(s.device_ops)["fusion.1"] == pytest.approx((100e-6 + 50e-6) / 2)
    assert sum(dict(s.idle_gaps).values()) + s.busy_s == pytest.approx(s.window_s, rel=1e-3)


def test_events_are_clipped_to_the_marked_window():
    s = xplane.summarize(trace(OPS, MODULES, mark=(150 * US, 620 * US)), 0, [])
    assert s.busy_s == pytest.approx((20 + 20 + 20) * 1e-6)
    assert len(s.tick_busy_ms) == 0  # no tick program lies whole in the window


def test_a_trace_without_the_mark_or_a_device_is_an_error():
    no_mark = trace(OPS, MODULES)
    no_mark.planes[0].lines[0].events[0].name = "something else"
    with pytest.raises(ValueError, match="annotation"):
        xplane.summarize(no_mark, 0, [])
    no_device = trace(OPS, MODULES)
    no_device.planes[1].name = "/device:GPU:0"
    with pytest.raises(ValueError, match="TPU"):
        xplane.summarize(no_device, 0, [])
    no_ops = trace(OPS, MODULES)
    no_ops.planes[1].lines[0].name = "Ops"
    with pytest.raises(ValueError, match="/device:TPU:0 has no 'XLA Ops' line"):
        xplane.summarize(no_ops, 0, [])


# -- the recorded slice -------------------------------------------------------

SLICE = os.path.join(os.path.dirname(__file__), "data", "trace_slice.json")


@pytest.fixture(scope="module")
def recorded():
    with open(SLICE) as f:
        return json.load(f)


def _loop_busy(events, w0, w1):
    """Union of intervals the slow way: sort, then walk."""
    busy, reach = 0.0, w0
    for s, e in sorted((max(ev["start_ns"], w0), min(ev["start_ns"] + ev["duration_ns"], w1))
                       for ev in events):
        if e <= reach:
            continue
        busy += e - max(s, reach)
        reach = e
    return busy


def test_recorded_slice_against_a_plain_loop(recorded):
    data = recorded["trace"]
    mark = data["planes"][0]["lines"][0]["events"][0]
    w0, w1 = mark["start_ns"], mark["start_ns"] + mark["duration_ns"]
    device = [p for p in data["planes"] if p["name"].startswith("/device:TPU")]
    assert len(device) == 1
    lines = {ln["name"]: ln["events"] for ln in device[0]["lines"]}
    ops = [e for e in lines["XLA Ops"] if e["start_ns"] + e["duration_ns"] > w0 and e["start_ns"] < w1]
    assert len(ops) > 1000

    from perfbench.deployments import single_client

    s = xplane.summarize(xplane.from_json(data), recorded["open_ns"],
                         single_client.host_intervals(recorded["spans"]))
    assert s.window_s == pytest.approx(0.08)
    assert s.busy_s * 1e9 == pytest.approx(_loop_busy(ops, w0, w1), rel=1e-9)
    assert 0 < s.busy_s < s.window_s

    # per tick, by the loop: the program that holds the device longest
    held = {}
    for m in lines["XLA Modules"]:
        if m["start_ns"] >= w0 and m["start_ns"] + m["duration_ns"] <= w1:
            held.setdefault(m["name"].split("(")[0], []).append(m)
    ticks = max(held.values(), key=lambda ms: sum(m["duration_ns"] for m in ms))
    assert len(ticks) == len(s.tick_busy_ms) >= 2
    for m, busy_ms, kern_ms in zip(sorted(ticks, key=lambda m: m["start_ns"]),
                                   s.tick_busy_ms, s.tick_kernels_ms):
        m0, m1 = m["start_ns"], m["start_ns"] + m["duration_ns"]
        inside = [e for e in ops if e["start_ns"] + e["duration_ns"] > m0 and e["start_ns"] < m1]
        assert busy_ms * 1e6 == pytest.approx(_loop_busy(inside, m0, m1), rel=1e-6)
        kern = sum(min(e["start_ns"] + e["duration_ns"], w1) - max(e["start_ns"], w0) for e in ops
                   if "tpu_custom_call" in e["name"] and m0 <= e["start_ns"] < m1)
        assert kern_ms * 1e6 == pytest.approx(kern, rel=1e-6)
        assert 0 < kern_ms <= busy_ms <= m["duration_ns"] / 1e6 + 1e-9
    # what is busy and what is idle make up the window
    assert s.busy_s + sum(v for _n, v in s.idle_gaps) == pytest.approx(s.window_s, rel=0.01)
    assert all("__mosaic" in n or "tpu_custom_call" not in n for n, _v in s.device_ops)


def test_a_chip_without_a_device_plane_counts_as_idle(recorded):
    """A chip on which nothing ran has no plane in the trace (PERF.md, PR
    26), so the mean is taken over the chips the cell asks for, not over the
    planes there are: the recorded chip three times over and a fourth that is
    missing read three quarters of its busy seconds for a four-chip cell, and
    the recorded trace as it is reads the one plane's own for a one-chip cell."""
    one = xplane.summarize(xplane.from_json(recorded["trace"]), recorded["open_ns"])
    own = one.chip_busy_s["/device:TPU:0"]
    assert one.busy_s == own == 0.025188909
    data = dict(recorded["trace"], planes=list(recorded["trace"]["planes"]))
    data["planes"] += [dict(data["planes"][1], name=f"/device:TPU:{i}") for i in (1, 2)]
    four = xplane.summarize(xplane.from_json(data), recorded["open_ns"], chips=4)
    assert four.chip_busy_s == {f"/device:TPU:{i}": own for i in range(3)}
    assert four.busy_s == pytest.approx(3 * own / 4)
    assert len(four.tick_busy_ms) == 3 * len(one.tick_busy_ms)
    # its idle seconds count the missing chip's whole window, under the same names
    idle, was = dict(four.idle_gaps), dict(one.idle_gaps)
    assert four.busy_s + sum(idle.values()) == pytest.approx(four.window_s, rel=1e-9)
    assert idle["in_program"] == pytest.approx(3 * was["in_program"] / 4)
    assert idle["host_other"] == pytest.approx((3 * was["host_other"] + four.window_s) / 4)
    assert dict(four.device_ops) == pytest.approx({n: 3 * v / 4 for n, v in one.device_ops})
