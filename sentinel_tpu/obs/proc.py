"""What every thread of the process shares, measured while the tracer is on.

Two recorders on ``obs.TRACER``'s ring, started by ``obs.enable()`` (and at
import where ``SENTINEL_TRACE=1`` has the tracer on already) and stopped by
``obs.disable()``; with tracing off neither exists (no thread, no
``gc.callbacks`` entry):

``proc.wake``  one span a beat of the ``sentinel-obs-wake`` thread, which
    waits ``WAKE_PERIOD_NS`` at a time: ``t0_ns`` is the instant the beat
    was due, ``dur_ns`` how late the thread ran again.  A runnable thread
    waits for two things, the host's scheduler and the interpreter lock,
    and a beat waits for both like any thread of the hot path.  A beat
    later than ``WAKE_CPU_NS`` also carries ``cpu_ns``, the process's CPU
    time since the beat before: about the wall time means code of this
    process held the interpreter lock, next to none means the machine had
    the cores.  Feeds ``sentinel_proc_wake_late_ms``.
``proc.gc``    one span a collection of generation 2, and a collection of
    any generation that took ``GC_SLOW_NS`` or more (``gen``,
    ``collected``): a collection stops every thread of the process.

Both periods are constants: nothing selects them.
"""

from __future__ import annotations

import gc
import threading
import time as _time
from typing import Optional

from sentinel_tpu.obs import trace as OT
from sentinel_tpu.obs.registry import REGISTRY

WAKE_PERIOD_NS = 5_000_000
WAKE_CPU_NS = 20_000_000
GC_SLOW_NS = 1_000_000

_H_WAKE = REGISTRY.histogram(
    "sentinel_proc_wake_late_ms",
    "how late a thread that waits 5 ms at a time ran again: the wait for the "
    "host's scheduler and the interpreter lock (fed while the tracer is on)",
)


class _WakeThread(threading.Thread):
    def __init__(self):
        super().__init__(name="sentinel-obs-wake", daemon=True)
        self._stop_evt = threading.Event()

    def run(self) -> None:
        cpu = _time.process_time_ns()
        while True:
            due = OT.now_ns() + WAKE_PERIOD_NS
            if self._stop_evt.wait(WAKE_PERIOD_NS / 1e9):
                return
            late = max(OT.now_ns() - due, 0)
            cpu, cpu_before = _time.process_time_ns(), cpu
            OT.TRACER.record(
                "proc.wake", due, late, 0,
                {"cpu_ns": cpu - cpu_before} if late > WAKE_CPU_NS else None,
            )
            _H_WAKE.observe(late / 1e6)

    def stop(self) -> None:
        self._stop_evt.set()
        self.join()


_lock = threading.Lock()  # guards start/stop, never the recorders
_wake: Optional[_WakeThread] = None
_gc_t0 = 0  # a collection's start; collections do not nest


def _on_gc(phase: str, info: dict) -> None:
    global _gc_t0
    if phase == "start":
        _gc_t0 = OT.now_ns()
    elif _gc_t0:
        dur = OT.now_ns() - _gc_t0
        if info["generation"] == 2 or dur >= GC_SLOW_NS:
            OT.TRACER.record(
                "proc.gc", _gc_t0, dur, 0,
                {"gen": info["generation"], "collected": info["collected"]},
            )
        _gc_t0 = 0


def start() -> None:
    """Start both recorders; a second call keeps the ones that run."""
    global _wake
    with _lock:
        if _wake is None:
            _wake = _WakeThread()
            _wake.start()
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)


def stop() -> None:
    """Stop both, the thread joined; harmless when neither runs."""
    global _wake, _gc_t0
    with _lock:
        if _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)
        _gc_t0 = 0
        wake, _wake = _wake, None
    if wake is not None:
        wake.stop()
