"""From the profiler's trace (``.xplane.pb``) to device numbers.

Device planes are named ``/device:TPU:<n>``.  Their ``XLA Ops`` line holds
one event per device operation, their ``XLA Modules`` line one event per
execution of a compiled program.  Times inside the file count from the start
of the trace; the harness brackets its window with one ``perfbench.window``
annotation on its own thread, which places the window in the file and ties
the file's clock to ``time.monotonic_ns()``, the clock of the program's spans.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW_MARK = "perfbench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
KERNEL = "tpu_custom_call"  # a Mosaic (Pallas) kernel's custom-call target
OP_NAME = re.compile(r"%?([\w.\-]+)")
#: host spans that can explain a device gap, most specific first; tick.device
#: is the wait for the device itself and explains nothing
HOST_SPANS = ("tick.presort", "tick.assemble", "tick.dispatch", "tick.readback", "tick.resolve")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # union of device-op intervals in the window, mean over the chips with a plane
    tick_busy_ms: np.ndarray  # per execution of the tick program
    tick_kernels_ms: np.ndarray
    device_ops: List[Tuple[str, float]]  # top operations by seconds in the window
    idle_gaps: List[Tuple[str, float]]  # idle seconds by what the host was doing
    clock_offset_ns: int  # monotonic_ns = file time + offset
    #: device plane -> its own busy seconds; ``busy_s`` is their mean.  A chip
    #: on which nothing ran has no device plane at all (a four-chip host with
    #: one client on chip 0 gave ``/device:TPU:0`` alone; PERF.md, PR 26)
    chip_busy_s: Dict[str, float]


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def short(name: str) -> str:
    """An operation's event carries its whole HLO text; its name is the part
    before `` = ``.  Mosaic kernels are marked, since their HLO names
    (``branch_1_fun.32``) say nothing."""
    m = OP_NAME.match(name)
    base = m.group(1) if m else name[:64]
    return base + "__mosaic" if KERNEL in name else base


def program(name: str) -> str:
    """``jit_tick(123456)`` -> ``jit_tick``: one name for every execution
    and every compiled shape of a program."""
    return name.split("(", 1)[0]


def describe(pd, limit: int = 3, top: int = 8) -> str:
    """Planes, lines, a few events of each and the names that take most
    time: read this before trusting the reduction on a new installation."""
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name}: {len(events)} events")
            for ev in events[:limit]:
                out.append(
                    f"    {ev.name[:120]} start={ev.start_ns:.0f} dur={ev.duration_ns:.0f} "
                    f"stats={list(ev.stats)[:6]}"
                )
            total: Dict[str, List[float]] = {}
            for ev in events:
                t = total.setdefault(program(short(ev.name)), [0, 0.0])
                t[0] += 1
                t[1] += ev.duration_ns
            for n, (k, ns) in sorted(total.items(), key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    TOP {n}: {k} events, {ns / 1e6:.3f} ms")
    return "\n".join(out)


def _line_arrays(line):
    names, starts, durs = [], [], []
    for ev in line.events:
        names.append(ev.name)
        starts.append(ev.start_ns)
        durs.append(ev.duration_ns)
    order = np.argsort(starts, kind="stable")
    return (
        np.asarray(names, object)[order],
        np.asarray(starts, np.float64)[order],
        np.asarray(durs, np.float64)[order],
    )


def union(starts: np.ndarray, ends: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Merge intervals sorted by start into disjoint ones."""
    if not len(starts):
        return starts, ends
    reach = np.maximum.accumulate(ends)
    new = np.concatenate([[True], starts[1:] > reach[:-1]])
    first = np.flatnonzero(new)
    last = np.concatenate([first[1:] - 1, [len(starts) - 1]])
    return starts[first], reach[last]


def _sorted(starts: np.ndarray, ends: np.ndarray):
    order = np.argsort(starts, kind="stable")
    return starts[order], ends[order]


def window_mark(pd) -> Optional[Tuple[float, float]]:
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_MARK:
                    return ev.start_ns, ev.start_ns + ev.duration_ns
    return None


def _overlap(a0, a1, b0, b1) -> np.ndarray:
    """Seconds of each interval ``a`` covered by the disjoint sorted
    intervals ``b``."""
    if not len(b0):
        return np.zeros(len(a0))
    cum = np.concatenate([[0.0], np.cumsum(b1 - b0)])

    def covered(t):  # length of b before time t
        i = np.searchsorted(b0, t, side="right")
        inside = np.where(i > 0, np.minimum(t, b1[np.maximum(i - 1, 0)]) - b0[np.maximum(i - 1, 0)], 0.0)
        return np.where(i > 0, cum[np.maximum(i - 1, 0)] + np.maximum(inside, 0.0), 0.0)

    return covered(a1) - covered(a0)


def summarize(pd, open_mono_ns: int, spans: List[dict]) -> Summary:
    """Reduce a trace to the window the harness marked in it.  ``spans`` are
    the program's host spans (monotonic ns) of the same window.  The tick
    program is the one that holds the device longest in the window, under
    whatever name the program gives it."""
    mark = window_mark(pd)
    if mark is None:
        raise ValueError(f"the trace has no {WINDOW_MARK} annotation")
    w0, w1 = mark
    offset = open_mono_ns - int(w0)
    planes = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    if not planes:
        raise ValueError("the trace has no /device:TPU plane")
    busy_s: Dict[str, float] = {}
    totals: Dict[str, float] = {}
    tick_busy, tick_kern = [], []
    gap_by: Dict[str, float] = {}
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            raise ValueError(f"{plane.name} has no {OPS_LINE!r} line: {sorted(lines)}")
        names, s, d = _line_arrays(lines[OPS_LINE])
        e = s + d
        keep = (e > w0) & (s < w1)
        names, s, e = names[keep], np.maximum(s[keep], w0), np.minimum(e[keep], w1)
        u0, u1 = union(s, e)
        busy_s[plane.name] = float((u1 - u0).sum()) / 1e9
        # an operation that the next one starts inside is a container
        # (a conditional, a loop): its time is its children's, listed anyway
        leaf = np.concatenate([s[1:] >= e[:-1], [True]]) if len(s) else np.zeros(0, bool)
        for n, dur in zip(names[leaf], (e - s)[leaf]):
            n = short(n)
            totals[n] = totals.get(n, 0.0) + dur / 1e9
        is_kernel = np.array([KERNEL in n for n in names], bool)
        m0 = m1 = np.zeros(0)
        if MODULES_LINE in lines:
            mn, ms, md = _line_arrays(lines[MODULES_LINE])
            whole = (ms >= w0) & (ms + md <= w1)
            mn = np.array([program(n) for n in mn], object)
            held: Dict[str, float] = {}
            for n, dur in zip(mn[whole], md[whole]):
                held[n] = held.get(n, 0.0) + dur
            tick_program = max(held, key=held.get) if held else None
            pick = whole & (mn == tick_program)
            m0, m1 = ms[pick], (ms + md)[pick]
            tick_busy.extend(_overlap(m0, m1, u0, u1) / 1e6)
            lo, hi = np.searchsorted(s, m0), np.searchsorted(s, m1)
            kcum = np.concatenate([[0.0], np.cumsum(np.where(is_kernel, e - s, 0.0))])
            tick_kern.extend((kcum[hi] - kcum[lo]) / 1e6)
        # idle gaps of this chip, by what covers them: first the tick
        # program itself (gaps between its operations), then the host's
        # spans, most specific first; each layer gets what the ones before
        # it left uncovered
        g0 = np.concatenate([[w0], u1])
        g1 = np.concatenate([u0, [w1]])
        a0, a1 = union(*_sorted(m0, m1))
        layers = [("in_program", a0, a1)]
        for name in HOST_SPANS:
            iv = [(sp["t0_ns"] - offset, sp["t0_ns"] + sp["dur_ns"] - offset)
                  for sp in spans if sp["name"] == name]
            layers.append((name, np.array([a for a, _ in iv], np.float64),
                           np.array([b for _, b in iv], np.float64)))
        c0 = c1 = np.zeros(0)
        covered = 0.0
        for name, h0, h1 in layers:
            c0, c1 = union(*_sorted(np.concatenate([c0, h0]), np.concatenate([c1, h1])))
            now = float(_overlap(g0, g1, c0, c1).sum())
            gap_by[name] = gap_by.get(name, 0.0) + (now - covered) / 1e9
            covered = now
        gap_by["host_other"] = gap_by.get("host_other", 0.0) + (float((g1 - g0).sum()) - covered) / 1e9
    k = len(planes)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(((n, v / k) for n, v in gap_by.items() if v > 0), key=lambda kv: -kv[1])[:10]
    return Summary(
        window_s=(w1 - w0) / 1e9,
        busy_s=float(np.mean(list(busy_s.values()))),
        tick_busy_ms=np.asarray(tick_busy),
        tick_kernels_ms=np.asarray(tick_kern),
        device_ops=[(n, v / k) for n, v in top],
        idle_gaps=gaps,
        clock_offset_ns=offset,
        chip_busy_s=busy_s,
    )


# -- a recorded slice, small enough to check in with the tests ---------------


def to_json(pd, seconds: float) -> dict:
    """The first ``seconds`` of the marked window as plain data: the device
    planes' two lines and the mark, clipped to the slice."""
    w0, _w1 = window_mark(pd)
    w1 = w0 + seconds * 1e9
    planes = [{"name": "/host:CPU", "lines": [{"name": "perfbench", "events": [
        {"name": WINDOW_MARK, "start_ns": w0, "duration_ns": w1 - w0}]}]}]
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            lines.append({"name": line.name, "events": [
                # an operation's name is its whole HLO text: keep its head, and
                # the mark of a Mosaic kernel
                {"name": ev.name[:96] + (" " + KERNEL if KERNEL in ev.name[96:] else ""),
                 "start_ns": ev.start_ns, "duration_ns": ev.duration_ns}
                for ev in line.events
                if ev.start_ns + ev.duration_ns > w0 and ev.start_ns < w1
            ]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


class _Node:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def from_json(data: dict):
    """A recorded slice with the attributes ``summarize`` reads."""
    return _Node(planes=[
        _Node(name=p["name"], lines=[
            _Node(name=ln["name"], events=[_Node(stats=[], **ev) for ev in ln["events"]])
            for ln in p["lines"]
        ])
        for p in data["planes"]
    ])
