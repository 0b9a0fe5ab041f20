"""From the profiler's trace (``.xplane.pb``) to device numbers.

Device planes are named ``/device:TPU:<n>``.  Their ``XLA Ops`` line holds
one event per device operation, their ``XLA Modules`` line one event per
execution of a compiled program.  Times inside the file count from the start
of the trace; the harness brackets its window with one ``perfbench.window``
annotation on its own thread, which places the window in the file and, with
the host's clock read on both sides of its opening (``run._Hooks.mark_ns``),
ties the file's clock to ``time.monotonic_ns()``, the clock of the program's
spans.
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
#: what ``perfbench.deployments.host_intervals`` gives: the host's named
#: intervals that can explain an idle device, most specific first, as
#: ``(name, starts, ends)`` in ``monotonic_ns``
HostIntervals = List[Tuple[str, np.ndarray, np.ndarray]]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # union of device-op intervals in the window, mean over the cell's chips
    tick_busy_ms: np.ndarray  # per execution of the tick program
    tick_kernels_ms: np.ndarray
    device_ops: List[Tuple[str, float]]  # top operations by seconds in the window
    idle_gaps: List[Tuple[str, float]]  # idle seconds by what the host was doing
    clock_offset_ns: int  # monotonic_ns = file time + offset
    #: device plane -> its own busy seconds; ``busy_s`` is their sum over the
    #: chips the cell asks for.  A chip on which nothing ran has no device
    #: plane at all (a four-chip host with one client on chip 0 gave
    #: ``/device:TPU:0`` alone; PERF.md, PR 26) and counts as idle
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


@dataclasses.dataclass
class _Chip:
    """One device plane clipped to the window."""

    name: str
    ops: np.ndarray  # the operations' names, by start
    s: np.ndarray
    e: np.ndarray
    u0: np.ndarray  # their union: when the chip was busy
    u1: np.ndarray
    m0: np.ndarray  # executions of the tick program wholly inside the window
    m1: np.ndarray


def tick_executions(plane, w0: float, w1: float) -> Tuple[np.ndarray, np.ndarray]:
    """Start and end of every execution of the tick program that lies wholly
    inside ``[w0, w1]`` on one device plane.  The tick program is the one
    that holds the plane longest in the window, under whatever name the
    program under test gives it."""
    lines = {ln.name: ln for ln in plane.lines}
    if MODULES_LINE not in lines:
        return np.zeros(0), np.zeros(0)
    mn, ms, md = _line_arrays(lines[MODULES_LINE])
    whole = (ms >= w0) & (ms + md <= w1)
    mn = np.array([program(n) for n in mn], object)
    held: Dict[str, float] = {}
    for n, dur in zip(mn[whole], md[whole]):
        held[n] = held.get(n, 0.0) + dur
    if not held:
        return np.zeros(0), np.zeros(0)
    pick = whole & (mn == max(held, key=held.get))
    return ms[pick], (ms + md)[pick]


def _chips(pd, w0: float, w1: float) -> List[_Chip]:
    out = []
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            raise ValueError(f"{plane.name} has no {OPS_LINE!r} line: {sorted(lines)}")
        names, s, d = _line_arrays(lines[OPS_LINE])
        e = s + d
        keep = (e > w0) & (s < w1)
        names, s, e = names[keep], np.maximum(s[keep], w0), np.minimum(e[keep], w1)
        out.append(_Chip(plane.name, names, s, e, *union(s, e), *tick_executions(plane, w0, w1)))
    if not out:
        raise ValueError("the trace has no /device:TPU plane")
    return out


def _idle(found: List[_Chip], w0: float, w1: float, offset_ns: int, host: HostIntervals,
          chips: int) -> Dict[str, float]:
    out: Dict[str, float] = {}
    none = np.zeros(0)
    # a chip on which nothing ran has no plane: idle from end to end
    gaps = [(np.concatenate([[w0], c.u1]), np.concatenate([c.u0, [w1]]),
             *union(*_sorted(c.m0, c.m1))) for c in found]
    gaps += [(np.array([w0]), np.array([w1]), none, none)] * (chips - len(found))
    for g0, g1, a0, a1 in gaps:
        c0 = c1 = none
        covered = 0.0
        for name, h0, h1 in [("in_program", a0, a1)] + [
                (n, np.asarray(t0, np.float64) - offset_ns, np.asarray(t1, np.float64) - offset_ns)
                for n, t0, t1 in host]:
            c0, c1 = union(*_sorted(np.concatenate([c0, h0]), np.concatenate([c1, h1])))
            now = float(_overlap(g0, g1, c0, c1).sum())
            out[name] = out.get(name, 0.0) + (now - covered) / 1e9
            covered = now
        out["host_other"] = out.get("host_other", 0.0) + (float((g1 - g0).sum()) - covered) / 1e9
    return {n: v / chips for n, v in sorted(out.items(), key=lambda kv: -kv[1])}


def idle_by(pd, offset_ns: int, host: HostIntervals, chips: int = 1) -> Dict[str, float]:
    """The device's idle seconds in the marked window by what covers them,
    mean over the cell's ``chips``, largest first: the one attribution, behind
    ``Summary.idle_gaps`` (the result's ``breakdown.idle_gaps``) and
    ``timeline.py``'s ``idle_by_span_s`` alike.  First the tick program itself
    (``in_program``: the gaps between its operations), then the host's
    intervals in the order the deployment kind gives them, most specific
    first; each layer gets what the ones before it left uncovered, and
    ``host_other`` the rest, so a kind that names no span reads those two.
    ``host`` is in ``monotonic_ns`` and ``offset_ns`` places it in the
    file's clock (``monotonic_ns = file time + offset``): the window mark's
    one offset in ``summarize``, the per-tick tie in ``timeline.py``."""
    w0, w1 = window_mark(pd)
    return _idle(_chips(pd, w0, w1), w0, w1, offset_ns, host, chips)


def summarize(pd, mark_mono_ns: int, host: HostIntervals = (), chips: int = 1) -> Summary:
    """Reduce a trace to the window the harness marked in it.  ``mark_mono_ns``
    is ``monotonic_ns`` when the mark opened (``run._Hooks.mark_ns``), ``host``
    what the deployment kind says the host was doing in the same window
    (``perfbench.deployments.host_intervals``) and ``chips`` the number the
    cell asks for: device seconds are means over those, so a chip without a
    device plane counts as idle."""
    mark = window_mark(pd)
    if mark is None:
        raise ValueError(f"the trace has no {WINDOW_MARK} annotation")
    w0, w1 = mark
    offset = mark_mono_ns - int(w0)
    found = _chips(pd, w0, w1)
    busy_s: Dict[str, float] = {}
    totals: Dict[str, float] = {}
    tick_busy, tick_kern = [], []
    for c in found:
        busy_s[c.name] = float((c.u1 - c.u0).sum()) / 1e9
        # an operation that the next one starts inside is a container
        # (a conditional, a loop): its time is its children's, listed anyway
        leaf = np.concatenate([c.s[1:] >= c.e[:-1], [True]]) if len(c.s) else np.zeros(0, bool)
        for n, dur in zip(c.ops[leaf], (c.e - c.s)[leaf]):
            n = short(n)
            totals[n] = totals.get(n, 0.0) + dur / 1e9
        is_kernel = np.array([KERNEL in n for n in c.ops], bool)
        tick_busy.extend(_overlap(c.m0, c.m1, c.u0, c.u1) / 1e6)
        lo, hi = np.searchsorted(c.s, c.m0), np.searchsorted(c.s, c.m1)
        kcum = np.concatenate([[0.0], np.cumsum(np.where(is_kernel, c.e - c.s, 0.0))])
        tick_kern.extend((kcum[hi] - kcum[lo]) / 1e6)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    idle = _idle(found, w0, w1, offset, host, chips)
    return Summary(
        window_s=(w1 - w0) / 1e9,
        busy_s=sum(busy_s.values()) / chips,
        tick_busy_ms=np.asarray(tick_busy),
        tick_kernels_ms=np.asarray(tick_kern),
        device_ops=[(n, v / chips) for n, v in top],
        idle_gaps=[(n, v) for n, v in idle.items() if v > 0][:10],
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
