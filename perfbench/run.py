"""Run one cell of the benchmark.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json`` and its configuration, traffic
mix and metrics in the files those names lead to (``perfbench/manifest.py``).
The last line of standard output is the result object: the keys the driver
reads, then ``beside`` (what stood beside a window that lost requests: the
longest gap between replies, the witness's late wake-ups and stalls, the
program's journal; empty in a sound run) and, last, ``compared``: every
number compared beside its limit, which are also the last lines of standard
error.  Everything else a reader may want (sample counts, slices) goes on
earlier lines.  Without a TPU, or with fewer chips than the
cell asks for, the command prints a reason to standard error, no result, and
exits with code 2.
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()  # as early as this process can read a clock

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from perfbench import manifest as M  # noqa: E402
from perfbench.generators import Hooks  # noqa: E402
from perfbench.xplane import WINDOW_MARK  # noqa: E402

#: scratch of a traced run, inside the checkout and listed in .gitignore
TRACE_DIR = os.path.join(M.ROOT, ".perfbench_trace")
SLICE_S = 5.0


class NoChip(RuntimeError):
    pass


def _say(**kw) -> None:
    print(json.dumps(kw), flush=True)


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if require_tpu:
        if info["platform"] != "tpu":
            raise NoChip(f"needs a TPU, JAX found platform {info['platform']!r}")
        if info["count"] < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found {info['count']}")
        with open(os.path.join(M.ROOT, M.HERE, "peaks.json")) as f:
            if info["kind"] not in json.load(f):
                raise NoChip(f"device kind {info['kind']!r} is not in perfbench/peaks.json")
    return info


def memory_peaks() -> list:
    """Peak bytes in use on every chip, in ``jax.devices()`` order; the
    result's ``memory_peak_bytes`` is the fullest."""
    import jax

    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.devices()]


class SetupClock:
    """Where set-up went: stage times by the host's clock, and JAX's own
    compile events (seconds in the backend compiler or loading from the
    cache, seconds tracing and lowering, cache hits)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    TRACE = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
    )
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = self.trace_s = 0.0
        self.cache_hits = self.compiles = 0
        self.stages = {}
        self._last = _T_PROCESS
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == self.COMPILE:
            self.compile_s += duration
            self.compiles += 1
        elif event in self.TRACE:
            self.trace_s += duration

    def _event(self, event, **kw):
        if event == self.HIT:
            self.cache_hits += 1

    def stage(self, name: str) -> None:
        now = time.monotonic()
        self.stages[name] = round(now - self._last, 3)
        self._last = now

    def line(self) -> dict:
        return dict(stages_s=self.stages, compile_s=round(self.compile_s, 3),
                    trace_lower_s=round(self.trace_s, 3), compiles=self.compiles,
                    cache_hits=self.cache_hits)


class _Hooks(Hooks):
    """Stamps the set-up time when the window opens; in a traced run also
    switches the program's spans on for exactly the window and marks the
    window in the profiler's trace.  ``mark_ns`` is ``monotonic_ns`` at the
    instant the mark opens, read on both sides of it: what ties the file's
    clock to the spans'.  (The window's own ``open_ns`` is when it was due to
    open; the mark opens a wake-up and this method's first lines later,
    0.2 to 2 ms, which is a whole stage of a light tick.)"""

    def __init__(self, trace: bool):
        self.trace = trace
        self.setup_s = None
        self.mark_ns = None
        self.opened_ns = self.closed_ns = None  # as the generator called; the witness reads them
        self._mark = None

    def opened(self) -> None:
        self.setup_s = time.monotonic() - _T_PROCESS
        self.opened_ns = time.monotonic_ns()
        if self.trace:
            import jax
            from sentinel_tpu import obs

            before = time.monotonic_ns()
            self._mark = jax.profiler.TraceAnnotation(WINDOW_MARK)
            self._mark.__enter__()
            self.mark_ns = (before + time.monotonic_ns()) // 2
            obs.enable()

    def closed(self) -> None:
        self.closed_ns = time.monotonic_ns()
        if self.trace:
            from sentinel_tpu import obs

            obs.disable()
            self._mark.__exit__(None, None, None)


class Witness(threading.Thread):
    """What a stalled window looked like from beside it, so that a run that
    comes out not correct says why (PERF.md, PR 27: one run of the driver's
    check lost 12 s of a flood window and every block in flight).  A thread
    that sleeps ``PERIOD_S`` at a time and notes how late it wakes, and the
    CPU seconds all threads of the process used meanwhile.  Late by seconds,
    the whole process stood still: with next to no CPU used, the host had the
    cores; with as much as the wall time, code of this process held the
    interpreter lock.  On time while the generator's ``hooks.progress()``
    does not move for ``STALL_S`` of the window, the serving path alone stood
    still, and the other threads' innermost frames are kept, once.  It costs
    ten wake-ups a second and reads no clock of the program."""

    PERIOD_S = 0.1
    STALL_S = 2.0
    LATE_S = 0.03  # a wake-up later than this is kept

    def __init__(self, hooks):
        super().__init__(name="perfbench-witness", daemon=True)
        self.hooks = hooks
        self.late = []  # [monotonic_ns woken, seconds late, process CPU seconds meanwhile]
        self.stalls = []  # [monotonic_ns when seen, seconds without progress so far]
        self.stacks = None
        self._stop_evt = threading.Event()
        self._proc0 = time.process_time()

    def run(self):
        last, moved_ns, seen = None, time.monotonic_ns(), False
        while True:
            due = time.monotonic_ns() + int(self.PERIOD_S * 1e9)
            cpu = time.process_time()
            if self._stop_evt.wait(self.PERIOD_S):
                return
            now = time.monotonic_ns()
            if now - due > self.LATE_S * 1e9:
                self.late.append([now, (now - due) / 1e9, time.process_time() - cpu])
                if len(self.late) > 64:
                    self.late.remove(min(self.late, key=lambda x: x[1]))
            probe = self.hooks.progress
            if probe is None or self.hooks.opened_ns is None or self.hooks.closed_ns is not None:
                continue  # requests stop by design outside the window
            at = probe()
            if at != last:
                last, moved_ns, seen = at, now, False
            elif (now - moved_ns) / 1e9 >= self.STALL_S:
                if not seen:
                    seen = True
                    self.stalls.append([now, 0.0])
                    if self.stacks is None:
                        self.stacks = self._frames()
                self.stalls[-1][1] = round((now - moved_ns) / 1e9, 3)

    @staticmethod
    def _frames(limit: int = 4000) -> list:
        """Every other thread's innermost five frames, shortest first, cut to
        ``limit`` characters in all."""
        names = {t.ident: t.name for t in threading.enumerate()}
        me = threading.get_ident()
        out = []
        for ident, frame in sys._current_frames().items():
            if ident == me:
                continue
            where = [f"{os.path.basename(f.filename)}:{f.lineno}:{f.name}"
                     for f in traceback.extract_stack(frame)[-5:]]
            out.append(f"{names.get(ident, ident)}: " + " < ".join(reversed(where)))
        out.sort(key=len)
        kept, used = [], 0
        for line in out:
            if used + len(line) > limit:
                break
            kept.append(line)
            used += len(line)
        return kept

    def close(self, win) -> dict:
        """Stop, and say what was seen: the four latest wake-ups inside the
        window as ``[seconds into the window, seconds late, process CPU
        seconds meanwhile]``, the stalls as ``[seconds into the window when
        seen, seconds without progress]``."""
        self._stop_evt.set()
        self.join(timeout=2.0)

        def at(ns):
            return round((ns - win.open_ns) / 1e9, 3)

        inside = [x for x in self.late if win.open_ns <= x[0] < win.close_ns + int(1e9)]
        inside.sort(key=lambda x: -x[1])
        line = {
            "witness_late": [[at(ns), round(late, 4), round(cpu, 4)] for ns, late, cpu in inside[:4]],
            "witness_late_wakeups": len(inside),
            "process_cpu_s": round(time.process_time() - self._proc0, 3),
            "stalls": [[at(ns), s] for ns, s in self.stalls],
        }
        if self.stacks:
            line["stalled_threads"] = self.stacks
        return line


def _percentiles(v) -> dict:
    if not len(v):
        return {}
    qs = (50, 90, 95, 99, 100)
    return {f"p{q}": round(float(x), 3) for q, x in zip(qs, np.percentile(v, qs))}


def slow_episodes(win, factor: float = 1.5, apart_s: float = 0.5, limit: int = 6) -> list:
    """Where in the window the slow requests sit: runs of samples slower
    than ``factor`` times the median, as ``[start s, end s, samples, worst
    ms, second of the wall-clock minute]``.  A tail that one stall makes
    shows here as one episode."""
    lat = win.latency_ms
    if not len(lat):
        return []
    at = (win.due_ns - win.open_ns) / 1e9
    order = np.argsort(at)
    at, lat = at[order], lat[order]
    slow = np.flatnonzero(lat > factor * np.median(lat))
    if not len(slow):
        return []
    wall0 = time.time() - (time.monotonic_ns() - win.open_ns) / 1e9
    groups = np.split(slow, np.flatnonzero(np.diff(at[slow]) > apart_s) + 1)
    groups.sort(key=len, reverse=True)
    return [
        [round(float(at[g[0]]), 2), round(float(at[g[-1]]), 2), int(len(g)),
         round(float(lat[g].max()), 1), round((wall0 + float(at[g[0]])) % 60, 1)]
        for g in groups[:limit]
    ]


def slices(win) -> list:
    """Median latency per ``SLICE_S`` of the window, by due time."""
    if not len(win.latency_ms):
        return []
    idx = ((win.due_ns - win.open_ns) / 1e9 // SLICE_S).astype(int)
    return [
        round(float(np.median(win.latency_ms[idx == i])), 4)
        for i in range(int(idx.max()) + 1)
        if (idx == i).any()
    ]


@dataclasses.dataclass
class Cell:
    """A cell set up: looked up, its deployment built, started and primed."""

    manifest: dict
    entry: dict  # the cell's entry of ``workloads``
    params: dict  # the traffic mix's parameters, then the cell's own
    generator: object  # module of perfbench.generators
    check: object  # module of perfbench.checks
    kind: object  # module of perfbench.deployments: the configuration's deployment kind
    dep: object  # what that kind built
    device: dict
    clock: SetupClock
    at_setup: dict  # the clock's line when set-up ended
    root: str  # where BENCHMARK.json and the data files were read


def set_up(
    workload: str,
    seed: int,
    *,
    sizes=None,
    require_tpu: bool = True,
    params_override=None,
    whole_locations: bool = False,
    root: str = M.ROOT,
) -> Cell:
    """The one set-up: the cell looked up in ``BENCHMARK.json`` and the data
    files, its deployment built from the seed, started and primed with a
    moment of the cell's own traffic.  ``sizes`` and ``require_tpu`` exist
    for the CPU rehearsal in the tests (no compile cache, no device check);
    ``whole_locations`` is ``timeline.py trace``'s, which needs the stage
    scopes in its operations' names."""
    manifest = M.load(root)
    bad = M.problems(manifest, root)
    if bad:
        raise ValueError("BENCHMARK.json: " + "; ".join(bad))
    entry = M.cell(manifest, workload)
    cfg = M.config(entry["config"], root)
    params = M.traffic(entry, root)
    params.update(params_override or {})
    generator = M.module("generators", params["generator"])

    cache_dir = None
    if require_tpu:
        from sentinel_tpu.utils.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
    import jax

    # A Mosaic kernel's payload carries its debug locations into the compile
    # cache's key, and with full tracebacks those name every caller's line:
    # the same tick would compile again under another entry point.  Whole,
    # they put a ``jax.named_scope`` into an operation's ``op_name``; cut to
    # one frame, the path of every operation outside a nested ``jit`` goes.
    jax.config.update("jax_include_full_tracebacks_in_locations", bool(whole_locations))
    clock = SetupClock()
    device = device_info(entry["chips"], require_tpu)
    clock.stage("import_and_device")

    kind = M.module("deployments", cfg["deployment"])
    dep = kind.build(cfg, seed, sizes)
    clock.stage("deployment")
    try:
        dep.start()
        clock.stage("start")
        # The deployment's own warm-up runs empty ticks.  The first ticks that
        # carry items compile some thirty small programs more (wire unpack,
        # telemetry folds), seconds of stall that belong to set-up: so the
        # cell's own traffic runs for a moment here, until it is idle again.
        generator.run(dep, dict(params, preroll_s=0.0, postroll_s=0.0), seed,
                      params["prime_seconds"], Hooks())
    except BaseException:
        dep.stop()  # whoever called has no deployment to stop
        raise
    clock.stage("prime")
    gc.collect()
    gc.freeze()
    at_setup = clock.line()
    _say(phase="setup", cache_dir=cache_dir, **at_setup)
    return Cell(manifest, entry, params, generator, M.module("checks", cfg["check"]),
                kind, dep, device, clock, at_setup, root)


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    sizes=None,
    require_tpu: bool = True,
    params_override=None,
    on_profile=None,
    root: str = M.ROOT,
) -> dict:
    """One run of one cell; returns the result object.  ``sizes`` and
    ``require_tpu`` are ``set_up``'s, ``params_override`` and ``on_profile``
    (called with the loaded trace, the window, its spans and the host's
    clock at the window mark) for the noise
    study and a first look at a trace (``perfbench/study.py``); the command
    passes none of them."""
    if trace:
        # the program's span ring (read at import): room for a whole window
        os.environ.setdefault("SENTINEL_TRACE_CAPACITY", str(1 << 18))
    cell = set_up(workload, seed, sizes=sizes, require_tpu=require_tpu,
                  params_override=params_override, root=root)
    try:
        return _measure(cell, seed, seconds, trace, on_profile)
    finally:
        cell.dep.stop()


def _measure(cell: Cell, seed: int, seconds: float, trace: bool, on_profile) -> dict:
    """The window, its metrics and the check, on a cell that is set up."""
    from perfbench.readers import Context

    params, generator, dep, device = cell.params, cell.generator, cell.dep, cell.device
    hooks = _Hooks(trace)
    if trace:
        import jax
        from sentinel_tpu import obs

        seconds = min(seconds, params["trace_seconds"])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        obs.TRACER.reset()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    witness = Witness(hooks)
    witness.start()
    try:
        win = generator.run(dep, params, seed, seconds, hooks)
    finally:
        witness._stop_evt.set()
        if trace:
            jax.profiler.stop_trace()
    # what stood beside the window: the generator's own account, the
    # witness's, and the kind's journal of rare state changes (a rule
    # recompile, a capacity resize, a tick failed closed) where it keeps one
    beside = dict(answered_late=win.late, **win.extra, **witness.close(win))
    journal = getattr(cell.kind, "journal", None)
    if journal:
        beside["journal"] = [
            [round((t_ns - win.open_ns) / 1e9, 3), what, json.loads(json.dumps(fields, default=str))]
            for t_ns, what, fields in journal(win.open_ns - 5_000_000_000, win.close_ns + 1_000_000_000)
        ]
    peaks = memory_peaks()
    device["memory_peak_bytes"] = max(peaks)
    at_setup, since_setup = cell.at_setup, cell.clock.line()
    _say(
        phase="window", compiles_since_setup=since_setup["compiles"] - at_setup["compiles"],
        compile_s_since_setup=round(since_setup["compile_s"] - at_setup["compile_s"], 3), samples=int(len(win.latency_ms)), attempted=win.attempted,
        failed=win.failed, codes=win.codes, span_s=round(win.span_s, 3),
        p50_ms_per_slice=slices(win), latency_ms=_percentiles(win.latency_ms),
        slow_episodes=slow_episodes(win), memory_peak_bytes_per_chip=peaks,
        **beside,
    )

    ctx = Context(window=win, setup_s=hooks.setup_s, batch=dep.batch)
    result = {}
    if trace:
        from perfbench import deployments, xplane

        ctx.spans = [
            s for s in obs.TRACER.snapshot()
            if win.open_ns <= s["t0_ns"] < win.close_ns
        ]
        profile = xplane.load(xplane.find(TRACE_DIR))
        if on_profile:
            on_profile(profile, win, ctx.spans, hooks.mark_ns)
        summary = xplane.summarize(profile, hooks.mark_ns,
                                   deployments.host_intervals(cell.kind, ctx.spans),
                                   cell.entry["chips"])
        ctx.trace = summary
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [list(kv) for kv in summary.device_ops],
            "idle_gaps": [list(kv) for kv in summary.idle_gaps],
        }
        # the span that opens once a tick is the kind's to name; a kind that
        # names none still has its ticks counted, from the trace alone
        tick_span = getattr(cell.kind, "TICK_SPAN", None)
        starts = np.sort([s["t0_ns"] for s in ctx.spans if s["name"] == tick_span])
        _say(phase="trace", spans=len(ctx.spans), ticks=int(len(summary.tick_busy_ms)),
             longest_tick_gap_ms=float(np.diff(starts).max() / 1e6) if len(starts) > 1 else None,
             mark_late_ms=(hooks.mark_ns - win.open_ns) / 1e6,  # the mark after the due opening
             busy_s_per_chip=summary.chip_busy_s,
             chips_without_a_device_plane=device["count"] - len(summary.chip_busy_s),
             span_summary=obs.summarize(ctx.spans))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    metrics = {}
    group = "per_layer" if trace else "end_to_end"
    for m in M.metrics_of(cell.manifest, cell.entry["name"], group):
        spec = M.metric(m["name"], cell.root)
        value = M.module("readers", spec["reader"]).read(ctx, **spec["args"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    correct, numbers, replayed = cell.check.decide(dep, generator, params, seed, win)
    for n in numbers:
        _say(compared=n.name, value=n.value, limit=n.limit,
             rule="at least" if n.at_least else "at most", ok=n.ok)
    _say(phase="replay", **replayed)
    result.update(
        # what stood beside the window, in a run that lost requests only: a
        # sound run's line stays short
        beside=beside if win.failed or win.unresolved or not correct else {},
        # every number compared beside its limit; last, so that the end of
        # the line always holds it
        compared={n.name: {"value": n.value, "at least" if n.at_least else "at most": n.limit,
                           "ok": n.ok} for n in numbers},
    )
    return {
        "correct": bool(correct),
        "attempted": int(win.attempted),
        "failed": int(win.failed),
        "metrics": metrics,
        "device": device,
        **result,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"perfbench: {e}; nothing was run", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    # the same numbers as the last lines of standard error
    if result["beside"]:
        print("beside the window: " + json.dumps(result["beside"]), file=sys.stderr)
    for name, n in result["compared"].items():
        print(f"compared {name}: " + json.dumps(n), file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
