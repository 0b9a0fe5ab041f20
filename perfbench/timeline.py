"""One timeline from submit to verdict, read from the program's spans and the
profiler's trace: a study tool for the cells of one deployment kind,
``single_client``.

    python3 perfbench/timeline.py trace --workload zipf-1m.paced --seed 5
    python3 perfbench/timeline.py spans --workload zipf-1m.paced --seed 5 --seconds 30

Everything here knows ``SentinelClient``'s spans and its tick program by
name, which the harness proper (``run.py``, ``xplane.py``) does not: both
commands refuse a cell of another kind in one line, and a kind that wants
such a study brings a tool of its own.  What the two share is shared as code:
the set-up (``run.set_up``) and the attribution of the device's idle seconds
(``xplane.idle_by`` over the kind's ``host_intervals``), so ``idle_by_span_s``
here and the result's ``breakdown.idle_gaps`` differ only in how the two
clocks are tied.

The reductions here read what ``sentinel_tpu`` records while its tracer is on
(``runtime/client.py``): ``req.queue`` per request, ``tick.drain``,
``tick.assemble``, ``tick.presort``, ``tick.dispatch``, ``tick.handoff``,
``tick.lock``, ``tick.hotset`` and ``tick.idle`` on the tick thread,
``tick.resident``, ``tick.wait``, ``tick.readback`` and ``tick.resolve`` per
tick, and one ``sentinel.tick`` step event per tick in the profiler's own file.  A program that records none
of them (an older commit) gives ``None`` everywhere, never an error.

``trace`` is a traced window of the cell, taken as ``run.py --trace 1`` takes
it, that prints one ``phase="timeline"`` object: how tightly the two clocks
are tied, every execution of the tick program joined to the tick id that
dispatched it, how long finished verdicts lay unread, device seconds per
stage and per kernel, the tick thread's unnamed share and the closure of the
request path.  It asks ``run.set_up`` for whole locations, because stage
scopes reach a device operation's ``op_name`` only while JAX's locations are
whole and ``run.py`` cuts them to one frame to keep its compile-cache key
still (the traced program is the same; the Mosaic kernels compile to code
that reads 1.7 % faster with whole locations, PERF.md section 7).  The window
and the other reductions are still this module's own, beside ``run.py``'s
``phase="trace"`` line (ROADMAP D13).
``spans`` is one set-up and two windows of the same seed, the
first with everything off and the second with the program's spans on and the
profiler off: what tracing costs, and which spans cover each slow episode.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from perfbench import manifest as M  # noqa: E402
from perfbench import xplane  # noqa: E402
from perfbench.deployments import single_client  # noqa: E402
from perfbench.readers import by_tick  # noqa: E402

KIND = "single_client"  # the one deployment kind whose spans this module knows

STEP_MARK = "sentinel.tick"  # the program's StepTraceAnnotation, one per tick
RING_MARK = "perfbench.ring"  # recorded first after a reset: still there = nothing was lost
TICK_PROGRAM = "jit_sentinel_tick"
OP_NAME = re.compile(r'op_name="([^"]*)"')
STAGE = "stage."  # jax.named_scope prefix of the tick's stages (ops/engine.py)
#: the spans of one request's path, in order; the first is per request, the
#: rest per tick, joined on the tick id the request's span carries
PATH = ("req.queue", "tick.assemble", "tick.presort", "tick.dispatch", "tick.resident",
        "tick.wait", "tick.readback", "tick.resolve")
#: the caller's own share of an ``entry()``, on its thread: one span of each
#: per request, carrying the id of the tick that served it.  ``submit_block``
#: has neither: its caller does not wait, and its verdicts are handed over
#: inside ``tick.resolve``.
CALLER = ("req.admit", "req.wake")


# -- the program's spans alone ------------------------------------------------


def tick_thread(spans: List[dict]) -> Optional[int]:
    """The thread that records ``tick.assemble``: the tick thread."""
    tids = [s["tid"] for s in spans if s["name"] == "tick.assemble"]
    return max(set(tids), key=tids.count) if tids else None


def _tick_thread_cover(spans: List[dict], tid: int) -> Tuple[np.ndarray, np.ndarray]:
    """Disjoint intervals of the tick thread that lie under a ``tick.*``
    span.  ``tick.assemble`` is recorded with its own duration, the time
    between its start and the start of ``tick.dispatch`` less the presort
    inside it, and ``tick.presort`` with the summed duration of its parts: the
    two together cover exactly assemble's start to dispatch's start, so the
    assemble interval is lengthened by its tick's presort."""
    presort = {s["trace"]: s["dur_ns"] for s in spans if s["name"] == "tick.presort"}
    a, b = [], []
    for s in spans:
        if s["tid"] != tid or not s["name"].startswith("tick."):
            continue
        dur = s["dur_ns"]
        if s["name"] == "tick.assemble":
            dur += presort.get(s["trace"], 0)
        a.append(s["t0_ns"])
        b.append(s["t0_ns"] + dur)
    return xplane.union(*xplane._sorted(np.asarray(a, np.float64), np.asarray(b, np.float64)))


def unnamed_share(spans: List[dict]) -> Optional[float]:
    """Per cent of the tick thread's wall time, between the first and the
    last ``tick.assemble`` start, that lies under no ``tick.*`` span.  None
    where the program does not record ``tick.drain``."""
    tid = tick_thread(spans)
    if tid is None or not any(s["name"] == "tick.drain" for s in spans):
        return None
    starts = [s["t0_ns"] for s in spans if s["name"] == "tick.assemble" and s["tid"] == tid]
    w0, w1 = float(min(starts)), float(max(starts))
    if w1 <= w0:
        return None
    c0, c1 = _tick_thread_cover(spans, tid)
    covered = float(xplane._overlap(np.array([w0]), np.array([w1]), c0, c1)[0])
    return 100.0 * (1.0 - covered / (w1 - w0))


def unnamed_between(spans: List[dict], top: int = 5) -> Optional[list]:
    """Where the tick thread's unnamed time lies: ``[[span that ended before
    the hole, span that started after it, holes, seconds], ...]`` over the
    same stretch as ``unnamed_share``, largest first.  A hole between the
    same two names every time is a span that is missing there."""
    tid = tick_thread(spans)
    if tid is None or not any(s["name"] == "tick.drain" for s in spans):
        return None
    starts = [s["t0_ns"] for s in spans if s["name"] == "tick.assemble" and s["tid"] == tid]
    c0, c1 = _tick_thread_cover(spans, tid)
    presort = {s["trace"]: s["dur_ns"] for s in spans if s["name"] == "tick.presort"}
    began, ended = {}, {}
    for s in spans:
        if s["tid"] == tid and s["name"].startswith("tick."):
            more = presort.get(s["trace"], 0) if s["name"] == "tick.assemble" else 0
            began[s["t0_ns"]] = s["name"]
            ended[s["t0_ns"] + s["dur_ns"] + more] = s["name"]
    holes: Dict[Tuple[str, str], List[float]] = {}
    for a, b in zip(c1[:-1], c0[1:]):
        if a >= min(starts) and b <= max(starts):
            holes.setdefault((ended.get(int(a), "?"), began.get(int(b), "?")), []).append(b - a)
    return [[a, b, len(v), sum(v) / 1e9]
            for (a, b), v in sorted(holes.items(), key=lambda kv: -sum(kv[1]))[:top]]


def closure(spans: List[dict], latency_ms, late_ms) -> Optional[dict]:
    """Does a request's latency equal the sum of its spans?  Per ``req.queue``
    span whose tick recorded every stage: the summed durations of ``PATH``,
    and the same request from its submit to the end of its tick's
    ``tick.resolve`` by the two timestamps.  Where the caller waits in
    ``entry()``, the mean of each ``CALLER`` span is added to the sum (they
    are per request, on other threads, and join on the tick id alone).  The
    median stands beside the generator's median latency less its median
    lateness."""
    ticks = by_tick(spans, set(PATH[1:]))
    sums, ends, parts = [], [], {n: [] for n in PATH}
    for s in spans:
        if s["name"] != "req.queue":
            continue
        t = ticks.get(s["trace"], {})
        if any(n not in t for n in PATH[1:] if n != "tick.presort"):
            continue
        durs = [s["dur_ns"]] + [t[n]["dur_ns"] if n in t else 0 for n in PATH[1:]]
        for n, d in zip(PATH, durs):
            parts[n].append(d / 1e6)
        sums.append(sum(durs) / 1e6)
        r = t["tick.resolve"]
        ends.append((r["t0_ns"] + r["dur_ns"] - s["t0_ns"]) / 1e6)
    if not sums or not len(latency_ms):
        return None
    mean_ms = {n: float(np.mean(v)) for n, v in parts.items()}
    for n in CALLER:
        own = [s["dur_ns"] / 1e6 for s in spans if s["name"] == n]
        if own:
            mean_ms[n] = float(np.mean(own))
    target = float(np.median(latency_ms)) - (float(np.median(late_ms)) if len(late_ms) else 0.0)
    summed = float(np.median(sums)) + sum(mean_ms.get(n, 0.0) for n in CALLER)
    return {
        "requests": len(sums),
        "sum_of_spans_p50_ms": summed,
        "submit_to_resolved_p50_ms": float(np.median(ends)),
        "latency_less_lateness_p50_ms": target,
        "residual_ms": target - summed,
        "residual_pct": 100.0 * (target - summed) / target,
        "mean_ms": mean_ms,
    }


def covering(spans: List[dict], t0: float, t1: float) -> dict:
    """What the tick thread and the resolvers were under during ``[t0, t1]``
    (monotonic ns): seconds per span name clipped to the interval, the tick
    thread's seconds under no span, and the longest span that touches it."""
    tid = tick_thread(spans)
    out = {}
    for side in ("tick_thread", "resolvers"):
        on = [s for s in spans
              if s["name"].startswith("tick.") and (s["tid"] == tid) == (side == "tick_thread")
              and s["t0_ns"] < t1 and s["t0_ns"] + s["dur_ns"] > t0]
        by: Dict[str, float] = {}
        for s in on:
            clipped = min(s["t0_ns"] + s["dur_ns"], t1) - max(s["t0_ns"], t0)
            by[s["name"]] = by.get(s["name"], 0.0) + clipped / 1e9
        longest = max(on, key=lambda s: s["dur_ns"], default=None)
        out[side] = {
            "seconds": {k: round(v, 6) for k, v in sorted(by.items(), key=lambda kv: -kv[1])},
            "longest": [longest["name"], round(longest["dur_ns"] / 1e6, 2),
                        longest["attrs"]] if longest else None,
        }
    if tid is not None:
        c0, c1 = _tick_thread_cover(spans, tid)
        under = float(xplane._overlap(np.array([t0]), np.array([t1]), c0, c1)[0])
        out["tick_thread"]["unnamed_s"] = round((t1 - t0 - under) / 1e9, 6)
    return out


# -- the profiler's trace beside them ------------------------------------------


@dataclasses.dataclass
class Tie:
    offset_ns: int  # monotonic_ns = file time + offset
    points: int  # ticks that gave a pair; 0 means the window mark's one
    spread_p50_us: Optional[float]  # distance of a pair's offset from the median
    spread_max_us: Optional[float]
    bracket_p50_us: Optional[float]  # drain end to assemble start: a pair's own width


def step_marks(pd) -> Dict[int, float]:
    """``{tick id: start in the file's clock}`` of the program's step events."""
    out = {}
    for plane in pd.planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == STEP_MARK:
                    step = dict(ev.stats).get("step_num")
                    if step is not None:
                        out[int(step)] = ev.start_ns
    return out


def clock_tie(pd, spans: List[dict], mark_mono_ns: int) -> Tie:
    """The file's clock against ``monotonic_ns``, from one pair per tick: the
    step event opens between the end of the tick's ``tick.drain`` and the start
    of its ``tick.assemble``, two reads of ``monotonic_ns`` a few microseconds
    apart, so their midpoint less the event's start is that tick's offset.
    The median over the ticks is taken.  Without step events the window mark
    gives the one offset ``xplane.summarize`` uses."""
    marks = step_marks(pd)
    found = by_tick(spans, {"tick.drain", "tick.assemble"})
    offsets, brackets = [], []
    for tick_id, at in marks.items():
        t = found.get(tick_id, {})
        if "tick.drain" in t and "tick.assemble" in t:
            lo = t["tick.drain"]["t0_ns"] + t["tick.drain"]["dur_ns"]
            hi = t["tick.assemble"]["t0_ns"]
            offsets.append((lo + hi) / 2.0 - at)
            brackets.append(hi - lo)
    if not offsets:
        w0, _w1 = xplane.window_mark(pd)
        return Tie(mark_mono_ns - int(w0), 0, None, None, None)
    offsets = np.asarray(offsets)
    mid = float(np.median(offsets))
    away = np.abs(offsets - mid) / 1e3
    return Tie(int(round(mid)), len(offsets), float(np.median(away)), float(away.max()),
               float(np.median(brackets)) / 1e3)


def executions(pd) -> Tuple[np.ndarray, np.ndarray]:
    """Start and end, in the file's clock, of every execution of the tick
    program that lies wholly inside the marked window, over all chips, in
    order: the executions ``xplane.summarize`` counts."""
    w0, w1 = xplane.window_mark(pd)
    found = [xplane.tick_executions(plane, w0, w1) for plane in pd.planes
             if xplane.DEVICE_PLANE.match(plane.name)]
    starts = np.concatenate([s for s, _e in found] + [np.zeros(0)])
    ends = np.concatenate([e for _s, e in found] + [np.zeros(0)])
    order = np.argsort(starts, kind="stable")
    return starts[order], ends[order]


@dataclasses.dataclass
class Joined:
    tick_id: np.ndarray  # of each joined execution
    start_ns: np.ndarray  # its start and end on the device, in monotonic_ns
    end_ns: np.ndarray
    unjoined: int  # executions in the window with no tick id
    early: int = 0  # joined executions that start before their dispatch span does


def join(exec_start: np.ndarray, exec_end: np.ndarray, offset_ns: int, spans: List[dict],
         slack_ns: float = 50_000.0, share: float = 0.98) -> Joined:
    """Each execution of the tick program and the tick that dispatched it, by
    order and checked by the clock.  The device runs the ticks in the order
    the tick thread dispatched them, so the two lists differ only by where
    they start: the alignment taken is the latest one under which (all but a
    few, ``share``, of) the executions start no earlier than their own
    ``tick.dispatch`` span does; those few are counted as ``early``."""
    disp = sorted((s for s in spans if s["name"] == "tick.dispatch"), key=lambda s: s["t0_ns"])
    e0, e1 = exec_start + offset_ns, exec_end + offset_ns
    d0 = np.array([s["t0_ns"] for s in disp], np.float64)
    ids = np.array([s["trace"] for s in disp], np.int64)
    n, m = len(d0), len(e0)
    for shift in range(n - 1, -m, -1):  # execution j <-> dispatch j + shift
        j = np.arange(max(0, -shift), min(m, n - shift))
        if not len(j):
            continue
        ok = e0[j] >= d0[j + shift] - slack_ns
        if ok.mean() >= share:
            return Joined(ids[j + shift], e0[j], e1[j], m - len(j), int((~ok).sum()))
    return Joined(np.zeros(0, np.int64), np.zeros(0), np.zeros(0), m)


def ready_unread_ms(joined: Joined, spans: List[dict]) -> Optional[np.ndarray]:
    """Per tick, how long its finished verdicts lay on the device before a
    resolver began to read them: the end of its execution to the start of its
    ``tick.wait``.  A resolver that came first waits instead (``tick.wait``),
    and counts 0 here."""
    waits = {s["trace"]: s["t0_ns"] for s in spans if s["name"] == "tick.wait"}
    lags = [max(0.0, waits[int(t)] - end) / 1e6
            for t, end in zip(joined.tick_id, joined.end_ns) if int(t) in waits]
    return np.asarray(lags) if lags else None


# -- what the Python reader of the trace leaves out ------------------------------
#
# ``jax.profiler.ProfileData`` gives an event's name, times and own stats, not
# the stats of its metadata, and a device operation's ``op_name`` (its path of
# ``jax.named_scope``s) is one of those.  The file is a protobuf (tsl's
# ``xplane.proto``); the few fields needed are read from the wire here.

_FIELD = {  # message -> {field number: name}, of tsl/profiler/protobuf/xplane.proto
    "XSpace": {1: "planes"},
    "XPlane": {2: "name", 4: "event_metadata", 5: "stat_metadata"},
    "MapEntry": {1: "key", 2: "value"},
    "XEventMetadata": {1: "id", 2: "name", 4: "display_name", 5: "stats"},
    "XStatMetadata": {1: "id", 2: "name"},
    "XStat": {1: "metadata_id", 2: "double", 3: "uint64", 4: "int64", 5: "str", 7: "ref"},
}


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, message: str):
    """``(name, value)`` of the wanted fields of one message: ints for
    varints, bytes for length-delimited fields; the rest is skipped."""
    want = _FIELD[message]
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in {message}")
        if number in want:
            yield want[number], value


def event_metadata(path: str) -> Dict[str, Dict[str, Dict[str, object]]]:
    """``{plane name: {event name: {stat name: value}}}`` for the device
    planes of an ``.xplane.pb``: the stats an operation's metadata carries."""
    import struct

    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for _n, plane in _fields(space, "XSpace"):
        got = dict.fromkeys(("name",), b"")
        events, stat_names = [], {}
        for name, value in _fields(plane, "XPlane"):
            if name == "name":
                got["name"] = value
            else:
                entry = dict(_fields(value, "MapEntry"))
                if name == "stat_metadata":
                    meta = dict(_fields(entry["value"], "XStatMetadata"))
                    stat_names[meta.get("id", entry.get("key"))] = meta.get("name", b"").decode()
                else:
                    events.append(entry["value"])
        plane_name = got["name"].decode()
        if not xplane.DEVICE_PLANE.match(plane_name):
            continue
        per_event = {}
        for raw_event in events:
            name, stats = "", {}
            for field, value in _fields(raw_event, "XEventMetadata"):
                if field == "name":
                    name = value.decode(errors="replace")
                elif field == "stats":
                    stat = dict(_fields(value, "XStat"))
                    key = stat_names.get(stat.get("metadata_id"), "?")
                    if "str" in stat:
                        stats[key] = stat["str"].decode(errors="replace")
                    elif "ref" in stat:
                        stats[key] = stat_names.get(stat["ref"], "")
                    elif "double" in stat:
                        stats[key] = struct.unpack("<d", stat["double"])[0]
                    else:
                        stats[key] = stat.get("int64", stat.get("uint64"))
            per_event[name] = stats
        out[plane_name] = per_event
    return out


Meta = Optional[Dict[str, Dict[str, object]]]  # one plane of event_metadata()


def op_path(text: str, meta: Meta = None) -> str:
    """A device operation's ``op_name``, its path of scopes: from its event
    text where that holds it (a recorded slice), else from the stats of its
    metadata (the file itself); empty where neither has one."""
    m = OP_NAME.search(text)
    if m:
        return m.group(1)
    stats = (meta or {}).get(text, {})
    for key in ("tf_op", "op_name"):
        if isinstance(stats.get(key), str):
            return stats[key]
    return next((v for v in stats.values() if isinstance(v, str) and v.startswith("jit(")), "")


def scope_of(text: str, meta: Meta = None) -> Tuple[str, Optional[str]]:
    """``(stage, kernel)`` of one device operation from its ``op_name``: the
    ``stage.*`` scopes on its path joined by ``/`` (``-`` where it lies under
    none), and for a Mosaic kernel the name it was given (``None`` where it
    was given none)."""
    # the file writes "<op_name>:<op type>", and JAX gives no type
    path = op_path(text, meta).rstrip(":").split("/")
    stages = [p[len(STAGE):] for p in path if p.startswith(STAGE)]
    stages = [p for i, p in enumerate(stages) if i == 0 or p != stages[i - 1]]
    kernel = None
    if "pallas_call" in path[1:]:
        kernel = path[path.index("pallas_call", 1) - 1]
        if kernel.startswith((STAGE, "jit(")):
            kernel = None
    return "/".join(stages) or "-", kernel


def device_stages(pd, meta=None) -> dict:
    """Seconds of device time in the marked window per stage of the tick and
    per named kernel, over the leaf operations ``xplane.summarize`` counts
    (mean over chips), and how many Mosaic operations carry no name."""
    w0, w1 = xplane.window_mark(pd)
    stages: Dict[str, float] = {}
    kernels: Dict[str, float] = {}
    unnamed = chips = 0
    for plane in pd.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if xplane.OPS_LINE not in lines:
            continue
        chips += 1
        names, s, d = xplane._line_arrays(lines[xplane.OPS_LINE])
        e = s + d
        keep = (e > w0) & (s < w1)
        names, s, e = names[keep], np.maximum(s[keep], w0), np.minimum(e[keep], w1)
        leaf = np.concatenate([s[1:] >= e[:-1], [True]]) if len(s) else np.zeros(0, bool)
        for text, dur in zip(names[leaf], (e - s)[leaf]):
            stage, kernel = scope_of(text, (meta or {}).get(plane.name))
            stages[stage] = stages.get(stage, 0.0) + dur / 1e9
            if xplane.KERNEL in text:
                if kernel is None:
                    unnamed += 1
                else:
                    kernels[kernel] = kernels.get(kernel, 0.0) + dur / 1e9
    k = max(chips, 1)
    return {
        "stages": {n: v / k for n, v in sorted(stages.items(), key=lambda kv: -kv[1])},
        "kernels": {n: v / k for n, v in sorted(kernels.items(), key=lambda kv: -kv[1])},
        "unnamed_kernel_ops": unnamed,
    }


def reduce(pd, win, spans: List[dict], meta=None, chips: int = 1, mark_ns=None) -> dict:
    """Everything above for one traced window, as one printable object.
    ``meta`` is ``event_metadata()`` of the trace's file, ``chips`` the
    number the cell asks for, ``mark_ns`` the host's clock when the window
    mark opened (``run._Hooks.mark_ns``; a recorded slice keeps it as
    ``open_ns``)."""
    mark_ns = win.open_ns if mark_ns is None else mark_ns
    tie = clock_tie(pd, spans, mark_ns)
    e0, e1 = executions(pd)
    joined = join(e0, e1, tie.offset_ns, spans)
    unread = ready_unread_ms(joined, spans)
    w0, _w1 = xplane.window_mark(pd)
    programs = set()
    for plane in pd.planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == xplane.MODULES_LINE:
                    programs.update(xplane.program(ev.name) for ev in line.events)
    return {
        "clock_tie_points": tie.points,
        "clock_spread_us": {"p50": tie.spread_p50_us, "max": tie.spread_max_us},
        "clock_bracket_p50_us": tie.bracket_p50_us,
        "clock_offset_minus_window_mark_us": (tie.offset_ns - (mark_ns - int(w0))) / 1e3,
        "ticks_dispatched": sum(s["name"] == "tick.dispatch" for s in spans),
        "executions": int(len(e0)),
        "joined": int(len(joined.tick_id)),
        "unjoined": joined.unjoined,
        "joined_but_early": joined.early,
        "execution_start_after_dispatch_start_ms": _stat(
            (joined.start_ns - _edge(spans, "tick.dispatch", joined.tick_id, end=False)) / 1e6),
        "execution_end_after_dispatch_end_ms": _stat(
            (joined.end_ns - _edge(spans, "tick.dispatch", joined.tick_id, end=True)) / 1e6),
        "ready_unread_ms": _stat(unread),
        "resolver_came_first_share": float(np.mean(unread == 0.0)) if unread is not None else None,
        "tick_program_seen": TICK_PROGRAM in programs,
        "device_stages": device_stages(pd, meta),
        # the harness's one attribution, under the per-tick tie of the clocks
        "idle_by_span_s": xplane.idle_by(pd, tie.offset_ns,
                                         single_client.host_intervals(spans), chips),
        "tick_unnamed_pct": unnamed_share(spans),
        "tick_unnamed_between": unnamed_between(spans),
        "closure": closure(spans, win.latency_ms, win.late_ms),
    }


def _edge(spans: List[dict], name: str, tick_ids, end: bool) -> np.ndarray:
    at = {s["trace"]: s["t0_ns"] + (s["dur_ns"] if end else 0) for s in spans if s["name"] == name}
    return np.array([at.get(int(t), np.nan) for t in tick_ids], np.float64)


def _stat(v) -> Optional[dict]:
    if v is None or not len(v):
        return None
    return {"mean": float(np.nanmean(v)), "p50": float(np.nanmedian(v)),
            "max": float(np.nanmax(v)), "n": int(len(v))}


# -- the two commands ----------------------------------------------------------


class OtherKind(ValueError):
    pass


def its_cell(workload: str) -> None:
    """Refuse a cell whose deployment kind records other spans than these."""
    kind = M.config(M.cell(M.load(), workload)["config"]).get("deployment")
    if kind != KIND:
        raise OtherKind(f"timeline.py studies cells of kind {KIND!r}; {workload} is of kind "
                        f"{kind!r}, whose spans it does not know")


def traced(workload: str, seed: int, slice_to: Optional[str] = None,
           slice_s: float = 0.08) -> dict:
    """A traced window of the cell, as ``run.py --trace 1`` takes it (the
    same hooks, the same profiler options, the same reduction for the device
    numbers) but set up here, so that the locations stay whole."""
    import shutil

    import jax

    from perfbench import run as R
    from sentinel_tpu import obs

    its_cell(workload)
    # whole locations: stage scopes reach an operation's name only with them
    cell = R.set_up(workload, seed, whole_locations=True)
    dep, generator, params, device = cell.dep, cell.generator, cell.params, cell.device
    shutil.rmtree(R.TRACE_DIR, ignore_errors=True)
    obs.TRACER.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(R.TRACE_DIR, profiler_options=opts)
    try:
        hooks = R._Hooks(True)
        win = generator.run(dep, params, seed, params["trace_seconds"], hooks)
    finally:
        jax.profiler.stop_trace()
        dep.stop()
    spans = [s for s in obs.TRACER.snapshot() if win.open_ns <= s["t0_ns"] < win.close_ns]
    path = xplane.find(R.TRACE_DIR)
    profile = xplane.load(path)
    meta = event_metadata(path)
    if slice_to:
        os.makedirs(os.path.dirname(slice_to) or ".", exist_ok=True)
        cut = win.open_ns + int(slice_s * 1e9)
        with open(slice_to, "w") as f:
            json.dump({"trace": to_json(profile, slice_s, meta), "open_ns": hooks.mark_ns,
                       "spans": [s for s in spans if s["t0_ns"] < cut]}, f)
    chips = cell.entry["chips"]
    summary = xplane.summarize(profile, hooks.mark_ns, single_client.host_intervals(spans), chips)
    shutil.rmtree(R.TRACE_DIR, ignore_errors=True)
    return {
        "phase": "timeline", "workload": workload, "seed": seed,
        "device": device, "failed": win.failed, "attempted": win.attempted,
        "latency_ms": R._percentiles(win.latency_ms),
        "window_s": summary.window_s, "busy_s": summary.busy_s,
        "device_tick_ms": _stat(summary.tick_busy_ms), "kernel_ms": _stat(summary.tick_kernels_ms),
        "spans": len(spans), "span_summary": obs.summarize(spans),
        # what run.py prints as breakdown.idle_gaps: the window mark's one tie
        "idle_gaps": [list(kv) for kv in summary.idle_gaps],
        **reduce(profile, win, spans, meta, chips, hooks.mark_ns),
    }


def to_json(pd, seconds: float, meta=None) -> dict:
    """The first ``seconds`` of the marked window as plain data, like
    ``xplane.to_json`` but with what this module reads kept: of each
    operation its name, the mark of a Mosaic kernel and its ``op_name``, and
    the step events of the slice."""
    w0, _w1 = xplane.window_mark(pd)
    w1 = w0 + seconds * 1e9

    def op_text(text: str, plane_meta: Meta) -> str:
        path = op_path(text, plane_meta)
        return ("%" + xplane.short(text).replace("__mosaic", "") + " ="
                + (f' custom_call_target="{xplane.KERNEL}"' if xplane.KERNEL in text else "")
                + (f' metadata={{op_name="{path}"}}' if path else ""))

    steps = [{"name": STEP_MARK, "start_ns": at, "duration_ns": 0.0, "stats": [["step_num", t]]}
             for t, at in sorted(step_marks(pd).items()) if w0 <= at < w1]
    planes = [{"name": "/host:CPU", "lines": [
        {"name": "perfbench", "events": [
            {"name": xplane.WINDOW_MARK, "start_ns": w0, "duration_ns": w1 - w0}]},
        {"name": "steps", "events": steps},
    ]}]
    for plane in pd.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        lines = []
        for line in plane.lines:
            if line.name not in (xplane.OPS_LINE, xplane.MODULES_LINE):
                continue
            lines.append({"name": line.name, "events": [
                {"name": op_text(ev.name, (meta or {}).get(plane.name))
                 if line.name == xplane.OPS_LINE else ev.name,
                 "start_ns": ev.start_ns, "duration_ns": ev.duration_ns}
                for ev in line.events
                if ev.start_ns + ev.duration_ns > w0 and ev.start_ns < w1
            ]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def from_json(data: dict):
    """A recorded slice with the attributes this module reads: what
    ``xplane.from_json`` gives, and each event's ``stats`` where the slice
    kept them."""

    def event(ev: dict):
        ev = dict(ev)
        return xplane._Node(stats=[tuple(kv) for kv in ev.pop("stats", [])], **ev)

    return xplane._Node(planes=[
        xplane._Node(name=p["name"], lines=[
            xplane._Node(name=ln["name"], events=[event(ev) for ev in ln["events"]])
            for ln in p["lines"]
        ])
        for p in data["planes"]
    ])


class _SpanHooks:
    """Switches the program's spans on for exactly the window."""

    def __init__(self, on: bool):
        self.on = on

    def opened(self) -> None:
        if self.on:
            from sentinel_tpu import obs

            obs.enable()

    def closed(self) -> None:
        if self.on:
            from sentinel_tpu import obs

            obs.disable()


def spans_run(workload: str, seed: int, seconds: float, *, sizes=None, require_tpu: bool = True,
              params_override=None, untraced_first: bool = True) -> dict:
    """One set-up, then the cell's window with everything off and the same
    window again (same seed) with the program's spans on and the profiler
    off.  ``sizes`` and ``require_tpu`` are for the CPU rehearsal in the
    tests, as in ``run.run_cell``."""
    from perfbench import run as R
    from sentinel_tpu import obs

    its_cell(workload)
    cell = R.set_up(workload, seed, sizes=sizes, require_tpu=require_tpu,
                    params_override=params_override)
    dep, generator, params = cell.dep, cell.generator, cell.params
    out = {"workload": workload, "seed": seed, "seconds": seconds, "device": cell.device}
    for on in ((False, True) if untraced_first else (True,)):
        obs.TRACER.reset()
        obs.TRACER.record(RING_MARK, 0, 0)  # the ring's first span: gone if the ring wrapped
        win = generator.run(dep, params, seed, seconds, _SpanHooks(on))
        side = {
            "attempted": win.attempted, "failed": win.failed,
            "latency_ms": R._percentiles(win.latency_ms),
            "decisions_per_s": win.visible_items / win.seconds,
            "late_p50_ms": float(np.median(win.late_ms)) if len(win.late_ms) else 0.0,
            "slow_episodes": R.slow_episodes(win),
        }
        if on:
            ring = obs.TRACER.snapshot()
            spans = [s for s in ring if win.open_ns <= s["t0_ns"] < win.close_ns]
            side["spans_per_s"] = len(spans) / win.seconds
            side["ring_wrapped"] = ring[0]["name"] != RING_MARK
            side["ring_capacity"] = obs.TRACER.capacity
            side["tick_unnamed_pct"] = unnamed_share(spans)
            side["tick_unnamed_between"] = unnamed_between(spans)
            side["closure"] = closure(spans, win.latency_ms, win.late_ms)
            side["span_summary"] = obs.summarize(spans)
            side["episodes_covered_by"] = [
                {"episode": ep, **covering(
                    spans, win.open_ns + ep[0] * 1e9, win.open_ns + ep[1] * 1e9 + ep[3] * 1e6)}
                for ep in side["slow_episodes"]
            ]
            out["spans"] = spans
        out["spans_on" if on else "spans_off"] = side
    dep.stop()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    t = sub.add_parser("trace")
    t.add_argument("--workload", required=True)
    t.add_argument("--seed", type=int, required=True)
    t.add_argument("--slice-out", help="also write the window's first --slice-s seconds as JSON")
    t.add_argument("--slice-s", type=float, default=0.08)
    s = sub.add_parser("spans")
    s.add_argument("--workload", required=True)
    s.add_argument("--seed", type=int, required=True)
    s.add_argument("--seconds", type=float, default=30.0)
    s.add_argument("--spans-only", action="store_true", help="skip the window with the spans off")
    a = ap.parse_args(argv)
    # the program's span ring is sized when sentinel_tpu is first imported
    os.environ.setdefault("SENTINEL_TRACE_CAPACITY", str(1 << 18))
    try:
        if a.cmd == "trace":
            print(json.dumps(traced(a.workload, a.seed, a.slice_out, a.slice_s)))
        else:
            out = spans_run(a.workload, a.seed, a.seconds, untraced_first=not a.spans_only)
            out.pop("spans", None)
            print(json.dumps(out))
    except OtherKind as e:
        print(f"perfbench: {e}; nothing was run", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
