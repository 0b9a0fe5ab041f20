"""chip_smoke.py — does the served path start, compile and answer on the chip?

One process (the only one that touches JAX) drives the system's main path
once, end to end, through the entry points a user would call, at the full
width of the deployment the repo benchmarks: ``perfbench/configs/
zipf-1m.json``, built by the deployment kind that file names, exactly as
``perfbench/run.py`` builds it for the ledger's cells (16,368-row tables,
10,000 ruled resources each with a QPS FlowRule and a slow-ratio
DegradeRule, 128 ParamFlowRules, 16 AuthorityRules, one SystemRule, 2,048
tail rules on sketch ids, Zipf(1.3) over 2^20 ids, batch 131,072).  Data
and traffic come from ``--seed``.

Phases (each reported with ok, wall seconds and, separately, XLA compile
seconds — on a warm persistent cache that is the cache-load time):

  environment   versions, backend, device, compile-cache directory, native
                host library.  Exits non-zero unless the backend is ``tpu``.
  serve         a threaded ``SentinelClient`` on ``platform_engine_config``,
                rules through the public managers, ``start()``; then the
                HelloWorld 20/s demo through ``entry()``/``exit()``, full
                131,072-item blocks through ``submit_block`` +
                ``submit_completion_block`` against the running tick thread,
                and token requests over loopback TCP through
                ``ClusterTokenServer`` (its decision engine is a second
                client, as in demos/demo_cluster.py: the token service
                takes over its decision client's flow rules).  The client's
                fail-closed paths turn a broken device tick into "some
                requests were blocked", so the phase also requires zero
                BLOCK_SYSTEM verdicts and zero failure-counter deltas.
  evidence      the device path is the one that ran: the fast-path flags
                were resolved by ``platform_engine_config`` alone, kernels
                are not interpreted, and the compiled served tick contains
                Mosaic custom calls.
  equivalence   two sync-mode clients on one virtual clock, identical
                seeded full-width blocks: the served configuration (with
                ``pipeline_depth=4``, dispatch running ahead of readback)
                against the plain scatter path on the same chip — verdict
                arrays bit-identical (batches are handed over in the
                client's presort order, so both engines see one order).
                The clock advances 5 ms per round so that a window holds
                enough ticks for the ~0.2-per-batch tail ids to cross their
                20/s rules: tail ids must come back blocked here (on the
                real clock of the serve phase the host cannot offer them
                20/s).  Every round also sends a part-filled batch, a
                seeded sample of the first one that just fills the tick
                shape below the full one (the middle shape of the ladder,
                ops/wire.tick_shapes; at rehearsal size the light one),
                held bit-identical like the full ones, and by the bytes
                the served client uploads for it to that shape.

  param_store   the benchmark's second ``SentinelClient`` deployment,
                ``perfbench/configs/param-1m-hot-keys.json`` (256
                ParamFlowRules over 1,024,000 (route, client) pairs, the
                hot-parameter store 2^22 cells wide), built by the kind
                that file names; a short replay of its own traffic at
                virtual times through ``submit_block`` and the compiled
                tick, held to the exact shadow ``perfbench/reference/
                param_shadow.py`` by the cell's own check: nothing admitted
                that an exact count would block, the share refused wrongly
                under the check's limit, both verdict codes and no third.

``--rehearse-cpu`` (together with ``JAX_PLATFORMS=cpu``) walks the same code
at a tiny size (``REHEARSAL_SIZES`` over the same file) with the fast-path
flags forced on, so the kernels run
interpreted through the same call sites; it reports ``"platform": "cpu"``
and ``"rehearsal": true`` and is never what a bare run does.

Stdout is JSON lines: one per phase, one ``{"summary": {...}}`` (versions,
seed, cache directory, compile totals, ``rehearsal``), and as the LAST line
exactly ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count":
...}}`` with the device as JAX reports it — no other key, because the
driver's chip check reads that line.  The exit code is 0 only if every
phase passed.  Nothing is printed to stdout when no accelerator is found.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import threading
import time
import traceback

#: XLA compile (or persistent-cache load) of one program
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: tracing + lowering to MLIR: host work no cache removes
_TRACE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: failure counters whose delta over the serve phase must be zero
_ZERO_COUNTERS = (
    "sentinel_resolve_failures_total",
    "sentinel_packed_decode_failures_total",
    "sentinel_explain_decode_failures_total",
    "sentinel_seg_dropped_total",
    "sentinel_watchdog_fired_total",
)

#: the benchmark's configuration this smoke vouches for
CONFIG = "zipf-1m"
#: ``--rehearse-cpu``: the same file cut to what interpreted kernels walk in
#: minutes, with the fast-path flags forced on (on the CPU
#: ``platform_engine_config`` leaves them off); the futures get room, so a
#: slow host cannot fail the walk
REHEARSAL_SIZES = {
    "engine": {
        "max_resources": 112, "max_nodes": 120, "max_flow_rules": 112,
        "max_degrade_rules": 112, "max_param_rules": 8, "batch_size": 512,
        "complete_batch_size": 512, "use_mxu_tables": True,
        "fused_effects": True, "seg_effects": True,
    },
    "resources": {"n_ruled": 48, "id_universe": 4095, "n_tail_ruled": 16},
    "rules": {"flow_qps": 100.0, "tail_qps": 2.0, "n_param_ruled": 8,
              "n_authority_ruled": 4},
    "traffic": {"pool_batches": 8},
    "client": {"entry_timeout_s": 30.0},
}
#: the second deployment this smoke vouches for, and its rehearsal cut (a
#: store of 2^15 cells is still a wide one: ops/param.wide)
PARAM_CONFIG = "param-1m-hot-keys"
PARAM_REHEARSAL_SIZES = {
    "engine": {
        "max_resources": 112, "max_nodes": 120, "max_flow_rules": 112,
        "max_degrade_rules": 112, "max_param_rules": 16, "batch_size": 512,
        "complete_batch_size": 512, "param_width": 1 << 15,
        "use_mxu_tables": True, "fused_effects": True, "seg_effects": True,
    },
    "resources": {"n_routes": 16, "clients_per_route": 50, "universe_pairs": 800},
    "traffic": {"pool_batches": 4, "client_rotation": 7},
    "client": {"entry_timeout_s": 30.0},
}
#: the equivalence phase's reference: the same deployment on the plain
#: scatter engine, no pipelining
_PLAIN = {
    "engine": {"use_mxu_tables": False, "fused_effects": False,
               "seg_effects": False},
    "client": {"pipeline_depth": 0},
}


def build(seed: int, *sizes: dict, config: str = CONFIG):
    """``config`` as ``perfbench/run.py`` builds it (the file's deployment
    kind, not started), with the groups of ``sizes`` laid over the file in
    order; none on the chip, where the serve phase runs the file as it is."""
    from perfbench import manifest
    from perfbench.deployments import with_sizes

    cfg = manifest.config(config)
    for s in sizes:
        cfg = with_sizes(cfg, s)
    return manifest.module("deployments", cfg["deployment"]).build(cfg, seed, None)

#: the third: circuit breakers on more rows than the other tables hold
BREAKER_CONFIG = "degrade-100k-slow-ratio"
BREAKER_REHEARSAL_SIZES = {
    "engine": {
        "max_resources": 160, "max_nodes": 168, "max_flow_rules": 16,
        "max_degrade_rules": 160, "max_param_rules": 8, "batch_size": 512,
        "complete_batch_size": 512, "use_mxu_tables": True,
        "fused_effects": True, "seg_effects": True,
    },
    "resources": {"n_services": 96},
    "traffic": {"pool_batches": 1},
    "check_params": {"rows_past": 40},
    "client": {"entry_timeout_s": 30.0},
}


class CompileClock:
    """Sums JAX's own compile-time events (jax.monitoring listeners)."""

    def __init__(self):
        import jax.monitoring as M

        self.compile_s = 0.0
        self.trace_s = 0.0
        self.cache_hits = 0
        self.phase = None  # set by Report.run
        self.big = []  # [phase, program, seconds] of every compile >= 1 s
        self._lock = threading.Lock()
        M.register_event_duration_secs_listener(self._on_duration)
        M.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        with self._lock:
            if event == _COMPILE_EVENT:
                self.compile_s += duration
                if duration >= 1.0:
                    self.big.append(
                        [self.phase, kw.get("fun_name", "?"), round(duration, 3)]
                    )
            elif event in _TRACE_EVENTS:
                self.trace_s += duration

    def _on_event(self, event, **kw):
        if event == _CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        with self._lock:
            return self.compile_s, self.trace_s, self.cache_hits


class Report:
    """Phase results in order; a failed phase ends the run."""

    def __init__(self, clock: CompileClock):
        self.clock = clock
        self.phases = {}

    def run(self, name, fn) -> bool:
        self.clock.phase = name
        c0, t0, h0 = self.clock.snapshot()
        w0 = time.perf_counter()
        try:
            detail, failures = fn()
        except Exception:
            detail = {}
            failures = ["raised: " + traceback.format_exc(limit=12)]
        c1, t1, h1 = self.clock.snapshot()
        row = {
            "ok": not failures,
            "wall_s": round(time.perf_counter() - w0, 3),
            "compile_s": round(c1 - c0, 3),
            "trace_lower_s": round(t1 - t0, 3),
            "cache_hits": h1 - h0,
            **detail,
        }
        if failures:
            row["failures"] = failures
        self.phases[name] = row
        print(json.dumps({"phase": name, **row}), flush=True)
        return not failures


def metric_total(name: str, **labels) -> float:
    """Sum of every series of one obs-registry metric matching ``labels``."""
    from sentinel_tpu import obs

    want = set(labels.items())
    return float(
        sum(
            m.value
            for m in obs.REGISTRY.series(name)
            if want <= set(m.labels)
        )
    )


def library_versions() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def hello_world(c, seconds: float = 4.4, threads: int = 4):
    """The upstream demo: one resource pinned to 20 pass/s under a few
    request threads.  Passes are counted per WHOLE engine second (window
    buckets align to engine time, so each whole second holds exactly one
    burst of the budget).  The exact rows are all taken in this
    deployment, so HelloWorld is a sketch-tier resource: after the idle
    gap of start-up its budget stays conservatively closed until traffic
    rotates the stale buckets (at most one window interval), hence one
    leading second without passes is allowed."""
    from sentinel_tpu.core.errors import BlockException, FlowException
    from sentinel_tpu.core.rules import FlowRule

    c.flow_rules.load(
        c.flow_rules.get() + [FlowRule(resource="HelloWorld", count=20.0)]
    )
    lock = threading.Lock()
    passes = collections.Counter()  # engine second -> passes
    out = {"flow_blocked": 0, "other_blocked": 0, "timeouts": 0, "errors": []}
    t_start = c.time.now_ms()
    t_end = t_start + int(seconds * 1000)

    def worker():
        while c.time.now_ms() < t_end:
            try:
                e = c.entry("HelloWorld")
            except FlowException:
                with lock:
                    out["flow_blocked"] += 1
            except BlockException as exc:
                with lock:
                    out["other_blocked"] += 1
                    out["errors"].append(type(exc).__name__)
            except TimeoutError:
                with lock:
                    out["timeouts"] += 1
            else:
                with lock:
                    passes[c.time.now_ms() // 1000] += 1
                e.exit()

    ts = [threading.Thread(target=worker, name=f"hello-{i}") for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=seconds + 4 * c.entry_timeout_s)
    stuck = [t.name for t in ts if t.is_alive()]
    whole = list(range(t_start // 1000 + 1, t_end // 1000))
    per_second = [passes.get(s, 0) for s in whole]
    failures = []
    if stuck:
        failures.append(f"request threads never returned: {stuck}")
    if out["timeouts"]:
        failures.append(f"{out['timeouts']} entry timeout(s)")
    if out["other_blocked"]:
        failures.append(
            f"{out['other_blocked']} non-flow block(s): {sorted(set(out['errors']))}"
        )
    settled = per_second[1:] if per_second[:1] == [0] else per_second
    # a slow offered rate (the CPU rehearsal) lets a burst straddle a
    # second boundary, so the mean carries the rate and each second a cap
    if (
        len(settled) < 2
        or not 15 <= sum(settled) / len(settled) <= 25
        or max(settled) > 30
    ):
        failures.append(f"passes per whole second {per_second}, want ~20 each")
    if not out["flow_blocked"]:
        failures.append("no FlowException under overload")
    detail = {
        "passes_per_whole_second": per_second,
        "passes_total": sum(passes.values()),
        "flow_blocked": out["flow_blocked"],
        "entry_timeouts": out["timeouts"],
    }
    return detail, failures


def full_blocks(c, traffic, n_blocks: int, inflight: int = 4):
    """Full-width blocks through the bulk surface while the tick thread
    runs; every future must resolve inside ``entry_timeout_s``."""
    import numpy as np

    from sentinel_tpu.core.errors import BLOCK_FLOW, BLOCK_SYSTEM, PASS

    node_rows = c.cfg.node_rows
    pending = collections.deque()
    lat, mix = [], collections.Counter()
    tail_blocked = 0
    failures = []

    def submit(k):
        ids, onode, oid, ph, inb, rt = traffic[k % len(traffic)]
        fut = c.submit_block(
            ids, origin_node=onode, origin_id=oid, param_hash=ph, inbound=inb
        )
        c.submit_completion_block(ids, rt, inbound=inb, param_hash=ph)
        pending.append((k, time.perf_counter(), fut, ids))

    def settle():
        nonlocal tail_blocked
        k, t0, fut, ids = pending.popleft()
        try:
            verd, _wait = fut.result(timeout=c.entry_timeout_s)
        except TimeoutError:
            failures.append(
                f"block {k} unresolved after entry_timeout_s={c.entry_timeout_s}"
            )
            return
        lat.append(time.perf_counter() - t0)
        vals, counts = np.unique(verd, return_counts=True)
        mix.update(dict(zip(vals.tolist(), counts.tolist())))
        tail_blocked += int(((verd == BLOCK_FLOW) & (ids >= node_rows)).sum())

    nxt = 0
    while nxt < n_blocks and not failures:
        while nxt < n_blocks and len(pending) < inflight:
            submit(nxt)
            nxt += 1
        settle()
    while pending and not failures:
        settle()

    if mix.get(BLOCK_SYSTEM):
        failures.append(
            f"{mix[BLOCK_SYSTEM]} BLOCK_SYSTEM verdict(s): a tick failed closed"
        )
    if not mix.get(PASS) or not mix.get(BLOCK_FLOW):
        failures.append(f"verdict mix lacks PASS or BLOCK_FLOW: {dict(mix)}")
    detail = {
        "blocks": len(lat),
        "block_items": int(len(traffic[0][0])),
        "verdict_mix": {str(k): v for k, v in sorted(mix.items())},
        "tail_blocked_real_clock": tail_blocked,
        "block_latency_s_max": round(max(lat), 3) if lat else None,
    }
    return detail, failures


def token_requests(cfg, n: int = 12, budget: int = 5):
    """A handful of token requests over loopback TCP: the device column
    kernel (ops/token_col.py) must compile and answer."""
    from sentinel_tpu.cluster import constants as C
    from sentinel_tpu.cluster.client import ClusterTokenClient
    from sentinel_tpu.cluster.server import ClusterTokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.core.rules import FlowRule
    from sentinel_tpu.runtime.client import SentinelClient

    flow_id = 7001
    decided0 = metric_total("sentinel_cluster_batched_decisions_total")
    engine = SentinelClient(cfg=cfg, mode="threaded")
    engine.start()
    svc = DefaultTokenService(engine)
    server = tc = None
    try:
        svc.flow_rules.load(
            "smoke-ns",
            [
                FlowRule(
                    resource="tokenApi",
                    count=float(budget),
                    cluster_mode=True,
                    cluster_flow_id=flow_id,
                    cluster_threshold_type=C.FLOW_THRESHOLD_GLOBAL,
                )
            ],
        )
        server = ClusterTokenServer(svc, host="127.0.0.1", port=0)
        server.start()
        tc = ClusterTokenClient(
            "127.0.0.1", server.port, namespace="smoke-ns", timeout_ms=5000
        )
        tc.start()
        statuses = collections.Counter(
            tc.request_token(flow_id, 1).status for _ in range(n)
        )
    finally:
        if tc is not None:
            tc.close()
        if server is not None:
            server.stop()
        svc.close()
        engine.stop()
    decided = metric_total("sentinel_cluster_batched_decisions_total") - decided0
    failures = []
    ok, blocked = statuses.get(C.STATUS_OK, 0), statuses.get(C.STATUS_BLOCKED, 0)
    if ok + blocked != n:
        failures.append(f"token statuses {dict(statuses)}: not all OK/BLOCKED")
    if not 1 <= ok <= 2 * budget or not blocked:
        failures.append(
            f"{ok} granted / {blocked} blocked of {n} against a budget of {budget}"
        )
    if decided < n:
        failures.append(f"device column decided {decided} of {n} entries")
    detail = {"token_granted": ok, "token_blocked": blocked,
              "token_device_decided": int(decided)}
    return detail, failures


def wait_for_seg_resize(timeout_s: float) -> bool:
    """Join the client's background seg_u-resize compile, if one runs."""
    deadline = time.monotonic() + timeout_s
    for t in threading.enumerate():
        if t.name == "sentinel-seg-resize":
            t.join(timeout=max(0.0, deadline - time.monotonic()))
            if t.is_alive():
                return False
    return True


def serve_phase(seed, sizes, n_blocks, state):
    from sentinel_tpu.core.config import platform_engine_config

    counters0 = {k: metric_total(k) for k in _ZERO_COUNTERS}
    surprise0 = metric_total("sentinel_retraces_total", expected="false")
    resizes0 = metric_total("sentinel_seg_resizes_total")

    dep = build(seed, *sizes)
    c, traffic = dep.client, dep.pool
    detail = {"tail_rules_promoted": int((dep.tail_ids < dep.sketch_base).sum())}
    t0 = time.perf_counter()
    c.start()  # warms every tick shape, then starts the tick thread
    detail["start_s"] = round(time.perf_counter() - t0, 3)
    try:
        seg_u0 = c.cfg.seg_u
        d, failures = hello_world(c)
        detail.update(d)
        if not failures:
            d, failures = full_blocks(c, traffic, n_blocks)
            detail.update(d)
        if not wait_for_seg_resize(600.0):
            failures.append("background seg_u resize still compiling after 600 s")
        resizes = metric_total("sentinel_seg_resizes_total") - resizes0
        detail["seg_resizes"] = int(resizes)
        detail["seg_u"] = [seg_u0, c.cfg.seg_u]
        if resizes and c.cfg.seg_u <= seg_u0:
            failures.append(
                "a seg_u resize started but the capacity did not grow: its "
                "background compile failed (see the record log)"
            )
    finally:
        c.stop()
    state["served_client"] = c
    if not failures:
        width = min(2048, dep.batch)
        d, f = token_requests(platform_engine_config(**{
            **dep.config["engine"], "batch_size": width,
            "complete_batch_size": width,
        }))
        detail.update(d)
        failures += f
    deltas = {k: metric_total(k) - v for k, v in counters0.items()}
    deltas["sentinel_retraces_total{expected=false}"] = (
        metric_total("sentinel_retraces_total", expected="false") - surprise0
    )
    detail["failure_counter_deltas"] = {k: int(v) for k, v in deltas.items()}
    failures += [f"{k} moved by {int(v)}" for k, v in deltas.items() if v]
    return detail, failures


def compiled_tick_text(c) -> str:
    """Compiled (post-XLA) text of the tick the (stopped) client served,
    at its full batch shape.  Reads the client's private tick, state and
    ruleset: the evidence wanted is about exactly that executable, and the
    persistent cache makes re-requesting it a load, not a compile."""
    import jax

    from sentinel_tpu.ops import wire as WIRE

    def spec(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
        )

    cfg = c.cfg
    # the packed client's tick takes its whole input as one buffer
    lo = WIRE.input_layout_for(cfg, cfg.batch_size, cfg.complete_batch_size)
    lowered = c._tick.lower(
        spec(c._state),
        spec(c._rules_dev),
        jax.ShapeDtypeStruct((lo.total,), "uint32"),
    )
    return lowered.compile().as_text()


# ---------------------------------------------------------------------------
# evidence
# ---------------------------------------------------------------------------


def evidence_phase(state, rehearsal: bool, sizes):
    from sentinel_tpu.ops import fused

    c = state["served_client"]
    flags = {
        f: bool(getattr(c.cfg, f))
        for f in ("use_mxu_tables", "fused_effects", "seg_effects")
    }
    mosaic_calls = compiled_tick_text(c).count(
        'custom_call_target="tpu_custom_call"'
    )
    detail = {
        "fast_path_flags": flags,
        "flags_overridden_by_smoke": sorted(
            k for s in sizes for k in s.get("engine", {}) if k in flags
        ),
        "interpret_mode": fused.interpret_mode(),
        "fused_available": fused.available(),
        "mosaic_custom_calls_in_served_tick": mosaic_calls,
        "packed_wire": bool(c.cfg.packed_wire),
        "seg_static_ranks": bool(c.cfg.seg_static_ranks),
    }
    failures = []
    if not all(flags.values()):
        failures.append(f"fast-path flags not all on: {flags}")
    if not fused.available():
        failures.append("fused kernels switched off (SENTINEL_NO_PALLAS)")
    if rehearsal:
        if not fused.interpret_mode():
            failures.append("rehearsal expected interpreted kernels")
    else:
        if sizes:
            failures.append("the smoke changed the benchmark's configuration on the chip")
        if fused.interpret_mode():
            failures.append("Pallas kernels ran INTERPRETED on the chip")
        if mosaic_calls == 0:
            failures.append("no Mosaic custom call in the served tick")
    return detail, failures


# ---------------------------------------------------------------------------
# equivalence
# ---------------------------------------------------------------------------

def equivalence_phase(seed, sizes, rounds, per_round=4):
    """Served configuration vs the plain scatter path, both on this
    backend, same virtual clock, same seeded traffic, full width and one
    part-filled batch a round."""
    import numpy as np

    from sentinel_tpu.core.errors import BLOCK_FLOW, BLOCK_SYSTEM, PASS
    from sentinel_tpu.ops import wire as WIRE
    from sentinel_tpu.utils.time_source import VirtualTimeSource

    vt = VirtualTimeSource(start_ms=1_000)
    # the pool is seeded batch by batch: its first per_round batches are
    # the serve phase's, and no more are drawn
    sync = {"client": {"mode": "sync", "time_source": vt},
            "traffic": {"pool_batches": per_round}}
    dep_s = build(seed, *sizes, sync)
    dep_p = build(seed, *sizes, sync, _PLAIN)
    served, plain = dep_s.client, dep_p.client
    traffic, traffic_p = dep_s.pool, dep_p.pool
    failures = []
    for a, b in zip(traffic, traffic_p):
        if not all(np.array_equal(x, y) for x, y in zip(a, b)):
            failures.append("the two clients were handed different traffic")
            break
    # Each batch goes in already ordered by the client's segment keys.
    # The served client presorts every batch by them and the plain one
    # does not, so otherwise the two ENGINES would see one resource's
    # same-tick requests in different orders and admit different ones of
    # them (equal counts, different items) — a property of the presort,
    # not of the device path this phase compares.
    traffic = [
        tuple(x[np.lexsort((t[2], t[1], t[0]))] for x in t) for t in traffic
    ]
    # ONE block of per_round batches: in sync mode a submission ticks at
    # once, and a block wider than the batch spans that many back-to-back
    # ticks — the only way dispatch runs pipeline_depth ahead of readback
    cols = [np.concatenate([t[i] for t in traffic]) for i in range(6)]
    ids, onode, oid, ph, inb, rt = cols
    # the MXU path carries RT on a 1/8 ms grid (documented); on-grid
    # inputs keep both paths bit-comparable
    rt = np.round(rt * 8.0) / 8.0
    # the part-filled batch: a sample of the first batch, still in segment-
    # key order, as many rows as the tick shape below the full one holds
    # (the served client's ladder; the plain one takes what its own gives)
    shapes = WIRE.tick_shapes(served.cfg)
    part_shape = shapes[-2] if len(shapes) > 1 else shapes[-1]
    pick = np.sort(np.random.default_rng(seed).choice(
        served.cfg.batch_size, min(part_shape), replace=False
    ))
    full = [ids, onode, oid, ph, inb, rt]
    part = [x[pick] for x in full]
    part_bytes = WIRE.input_layout_for(served.cfg, *part_shape).nbytes
    node_rows = served.cfg.node_rows
    mismatched = ticks = tail_blocked = items = 0
    mix = collections.Counter()
    t0 = time.perf_counter()

    def upload_bytes():
        return metric_total("sentinel_wire_bytes_total", path="device", direction="tx")

    try:
        for _ in range(rounds):
            for ids, onode, oid, ph, inb, rt in (full, part):
                if failures:
                    break
                verdicts = []
                for cl in (served, plain):
                    tx0 = upload_bytes()
                    fut = cl.submit_block(
                        ids, origin_node=onode, origin_id=oid, param_hash=ph,
                        inbound=inb,
                    )
                    cl.submit_completion_block(ids, rt, inbound=inb, param_hash=ph)
                    verdicts.append(fut.result(timeout=60.0)[0])
                    if cl is served and ids is part[0]:
                        # one tick for its acquires, one for its completions
                        if upload_bytes() - tx0 != 2 * part_bytes:
                            failures.append(
                                f"the part-filled batch of {len(ids)} rows uploaded "
                                f"{upload_bytes() - tx0} B, not two ticks of shape "
                                f"{part_shape} ({part_bytes} B each)"
                            )
                vs, vp = verdicts
                n_ticks = -(-len(ids) // served.cfg.batch_size)
                ticks += 2 * n_ticks
                items += len(ids)
                bad = int((vs != vp).sum())
                if bad:
                    mismatched += bad
                    i = int(np.flatnonzero(vs != vp)[0])
                    failures.append(
                        f"verdicts of a block of {len(ids)} differ at {bad} item(s); first: "
                        f"item {i} res {int(ids[i])} served {int(vs[i])} plain {int(vp[i])}"
                    )
                vals, counts = np.unique(vs, return_counts=True)
                mix.update(dict(zip(vals.tolist(), counts.tolist())))
                tail_blocked += int(((vs == BLOCK_FLOW) & (ids >= node_rows)).sum())
            vt.advance(5)
    finally:
        served.stop()
        plain.stop()
    if not part_shape < shapes[-1]:
        failures.append(f"the served ladder {shapes} has no shape under the full one")
    if mix.get(BLOCK_SYSTEM):
        failures.append(f"{mix[BLOCK_SYSTEM]} BLOCK_SYSTEM verdict(s)")
    if not mix.get(PASS) or not mix.get(BLOCK_FLOW):
        failures.append(f"verdict mix lacks PASS or BLOCK_FLOW: {dict(mix)}")
    if not tail_blocked:
        failures.append("tail rules loaded but no tail id came back blocked")
    detail = {
        "ticks_per_client": ticks,
        "items_compared": items,
        "part_filled_rows": len(pick),
        "part_filled_shape": list(part_shape),
        "items_mismatched": mismatched,
        "verdict_mix": {str(k): v for k, v in sorted(mix.items())},
        "tail_blocked": tail_blocked,
        "served_seg_u": served.cfg.seg_u,
        "run_s": round(time.perf_counter() - t0, 3),
    }
    return detail, failures


# ---------------------------------------------------------------------------


def param_store_phase(seed, sizes, ticks):
    """``PARAM_CONFIG`` built as its cell builds it and a short replay of its
    traffic held to the exact shadow by the cell's own check."""
    from perfbench.checks import param_replay
    from perfbench.generators import open_loop_param_blocks

    dep = build(seed, *sizes, config=PARAM_CONFIG)
    c = dep.client
    block = min(4096, dep.batch // 8)
    params = {"block_items": block,
              "replay": {"ticks": ticks, "step_ms": 25, "blocks_per_tick": [4]}}
    try:
        replayed = open_loop_param_blocks.replay(dep, params, seed)
        numbers, summary = param_replay.compare_replay(dep, replayed)
        summary.update(param_replay.store_occupancy(dep))
    finally:
        c.stop()
    held = ("replay_pairs_compared", "replay_blocked_items", "replay_param_over_admitted",
            "replay_param_false_block_share", "replay_other_codes",
            "replay_item_keys_past_the_rules_count")
    detail = {"param_width": c.cfg.param_width, "rules": len(c.param_flow_rules.get()),
              "universe_pairs": dep.universe, "pool_pairs": dep.pool_pairs,
              **{n.name: n.value for n in numbers}, **summary}
    failures = [f"{n.name} is {n.value}, limit {n.limit}" for n in numbers
                if n.name in held and not n.ok]
    return detail, failures


def breaker_phase(seed, sizes):
    """``BREAKER_CONFIG`` built as its cell builds it, and six services whose
    rows lie past the other configurations' tables (``check_params.
    rows_past``) driven by hand at virtual times through what a breaker can
    do, each step held to ``perfbench/reference/plain_breaker.py``: four slow
    exits of five trip, three of five (exactly the threshold) do not, an exit
    while OPEN counts and moves nothing, one probe at the retry time, a slow
    probe reopens with a new deadline, a fast one closes, a closed breaker
    admits again."""
    import numpy as np

    from perfbench.checks import breaker_replay

    dep = build(seed, *sizes, config=BREAKER_CONFIG)
    c = dep.client
    r = dep.config["rules"]
    slow, fast, retry = r["count"] + 1, r["count"], r["time_window"] * 1000
    far = np.flatnonzero(dep.ids > dep.config["check_params"]["rows_past"])[:6]
    trip, tie, idle = far[:2], far[2:4], far[4:]
    ref = breaker_replay.reference_of(dep)
    # (ms after the start, {rank: its exits' rts}, entries a service)
    steps = [
        (0, {}, 5),
        (25, {**{k: [slow] * 4 + [fast] for k in trip}, **{k: [slow] * 3 + [fast] * 2 for k in tie}}, 5),
        (50, {k: [slow, fast] for k in trip}, 3),  # exits while OPEN
        (25 + retry - 1, {}, 3),  # a millisecond early: no probe
        (25 + retry, {}, 3),  # one probe each
        (50 + retry, {trip[0]: [slow], trip[1]: [fast]}, 3),  # one reopens, one closes
        (75 + retry, {}, 3),
        (50 + 2 * retry, {}, 3),  # the reopened breaker's second probe
        (75 + 2 * retry, {trip[0]: [fast]}, 3),
    ]
    failures, t0 = [], c.time.now_ms() + 10_000
    try:
        for at, exits, n in steps:
            x_rank = np.array([k for k, rts in exits.items() for _ in rts], np.int64)
            x_rt = np.array([rt for rts in exits.values() for rt in rts], np.float32)
            if len(x_rank):
                c.submit_completion_block(dep.ids[x_rank].astype(np.int32), x_rt)
            ranks = np.repeat(far, n)
            fut = c.submit_block(dep.ids[ranks].astype(np.int32))
            c.tick_once(now_ms=t0 + at)
            verdicts = fut.result(timeout=c.entry_timeout_s)[0]
            uniq, _n, want = ref.tick(t0 + at, x_rank, x_rt, ranks)
            got = np.bincount(np.searchsorted(uniq, ranks), weights=verdicts == 0).astype(np.int64)
            state = dep.breaker_states()
            if (got != want).any() or (state[far] != ref.state[far]).any():
                failures.append(f"at +{at} ms admitted {got.tolist()} for {want.tolist()}, "
                                f"states {state[far].tolist()} for {ref.state[far].tolist()}")
    finally:
        c.stop()
    seen = ref.seen
    for what in ("opened", "half_opened", "closed_again", "reopened", "exits_while_open", "ratio_ties"):
        if not seen[what]:
            failures.append(f"the script never reached {what}")
    detail = {"services": len(dep.ids), "max_resources": c.cfg.max_resources,
              "rows": dep.ids[far].tolist(), "steps": len(steps), **seen}
    return detail, failures


def result_line(ok: bool, device: dict) -> str:
    """The last line of stdout: exactly the keys the chip check reads."""
    return json.dumps({
        "ok": bool(ok),
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    })


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument(
        "--rehearse-cpu",
        action="store_true",
        help="tiny-size walk of the same code on CPU (needs JAX_PLATFORMS=cpu)",
    )
    args = ap.parse_args(argv)

    if os.environ.get("SENTINEL_NO_PALLAS"):
        print("chip_smoke: SENTINEL_NO_PALLAS is set; the fused kernels are "
              "the path under test", file=sys.stderr)
        return 2
    if args.rehearse_cpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("chip_smoke: --rehearse-cpu needs JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2

    from sentinel_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    backend = jax.default_backend()
    want = "cpu" if args.rehearse_cpu else "tpu"
    if backend != want:
        print(f"chip_smoke: needs the {want} backend, JAX found {backend!r}; "
              "nothing was run", file=sys.stderr)
        return 2

    from sentinel_tpu.native import native_available

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    clock = CompileClock()
    report = Report(clock)
    state = {}

    if args.rehearse_cpu:
        sizes, n_blocks, rounds = (REHEARSAL_SIZES,), 8, 3
        param_sizes, param_ticks = (PARAM_REHEARSAL_SIZES,), 12
        breaker_sizes = (BREAKER_REHEARSAL_SIZES,)
    else:
        sizes, n_blocks, rounds = (), 12, 40
        param_sizes, param_ticks = (), 24
        breaker_sizes = ()

    def environment():
        native = native_available()
        detail = {
            **library_versions(),
            "backend": backend,
            "device": device,
            "compile_cache_dir": cache_dir,
            "native": native,
            "seed": args.seed,
        }
        # without g++ the C++ ring, interner and presort silently become
        # Python: a different host path from the one a deployment runs
        return detail, [] if native else ["native host library unavailable"]

    phases = (
        ("environment", environment),
        ("serve", lambda: serve_phase(args.seed, sizes, n_blocks, state)),
        ("evidence", lambda: evidence_phase(state, args.rehearse_cpu, sizes)),
        ("equivalence", lambda: equivalence_phase(args.seed, sizes, rounds)),
        ("param_store", lambda: param_store_phase(args.seed, param_sizes, param_ticks)),
        ("breaker", lambda: breaker_phase(args.seed, breaker_sizes)),
    )
    ok = all(report.run(name, fn) for name, fn in phases)
    compile_s, trace_s, hits = clock.snapshot()
    print(json.dumps({"summary": {
        "ok": ok,
        "rehearsal": args.rehearse_cpu,
        "versions": library_versions(),
        "seed": args.seed,
        "compile_cache_dir": cache_dir,
        "compile_s_total": round(compile_s, 3),
        "trace_lower_s_total": round(trace_s, 3),
        "cache_hits": hits,
        "compiles_over_1s": clock.big,
        "phases": {
            k: {f: v[f] for f in ("ok", "wall_s", "compile_s")}
            for k, v in report.phases.items()
        },
    }}), flush=True)
    print(result_line(ok, device), flush=True)  # nothing after it
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
