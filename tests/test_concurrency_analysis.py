"""sentinel_tpu.analysis.concurrency — the tier-3 concurrency analyzer.

Three jobs:

1. unit-test every pass on fixture trees — one triggering and one clean
   per rule (a seeded two-lock cycle, a blocking call routed through a
   helper that intra-procedural scanning would miss, an unjoined
   non-daemon thread), plus the golden round-trip and the
   ``--update-lock-order`` scoping contract;
2. THE CI GATE: run the whole tier over the real ``sentinel_tpu/`` tree
   and require zero findings — the committed ``lock_order.json`` must be
   acyclic and exactly match the tree, and every blocking-under-lock /
   thread-lifecycle site must be fixed or carry a written rationale;
3. check the static claims against reality: a witness-instrumented
   threaded ``SentinelClient`` run must record zero order violations and
   no dynamic edge the static graph missed, and the concurrency fixes
   this tier motivated (non-blocking cluster connect, bounded resolver
   drain, timeout-carrying worker waits) each keep a regression test.

The fixture tests are pure AST work; the gate builds one whole-package
summary DB (~2 s); only the witness smoke and drain tests import jax.
"""

from __future__ import annotations

import ast
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from sentinel_tpu.analysis import REPO_ROOT, rule_catalog
from sentinel_tpu.analysis.concurrency import (
    LOCK_ORDER_PATH,
    current_edges,
    load_lock_order,
    run_concurrency_analysis,
    save_lock_order,
    update_lock_order,
)
from sentinel_tpu.analysis.concurrency.passes import (
    ALL_CONCURRENCY_PASSES,
    GRAPH_PATH,
    BlockingUnderLockPass,
    LockOrderCyclePass,
    LockOrderNewEdgePass,
    ThreadLifecyclePass,
    _sccs,
)
from sentinel_tpu.analysis.concurrency.summaries import build_db


def _db(tmp_path, files):
    """Summary DB over an inline fixture tree (uncached)."""
    for name, src in files.items():
        p = tmp_path / name
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return build_db([str(tmp_path)], str(tmp_path), cached=False)


def _run(p, db, golden=None):
    return list(p.run(db, golden))


# ---------------------------------------------------------------------------
# lock-order-cycle
# ---------------------------------------------------------------------------

CYCLE_SRC = """
    import threading

    A = threading.Lock()
    B = threading.Lock()

    def ab():
        with A:
            with B:
                pass

    def ba():
        with B:
            with A:
                pass
"""


def test_two_lock_cycle_is_reported_with_both_stacks(tmp_path):
    db = _db(tmp_path, {"twist.py": CYCLE_SRC})
    found = _run(LockOrderCyclePass(), db)
    assert len(found) == 1, found
    f = found[0]
    assert f.rule == "lock-order-cycle"
    assert f.path == GRAPH_PATH
    # both acquisition chains are named so the report is actionable
    assert "twist.A" in f.message and "twist.B" in f.message
    assert "ab" in f.message and "ba" in f.message


def test_consistent_order_is_clean(tmp_path):
    db = _db(
        tmp_path,
        {
            "calm.py": """
            import threading

            A = threading.Lock()
            B = threading.Lock()

            def one():
                with A:
                    with B:
                        pass

            def two():
                with A:
                    with B:
                        pass
            """
        },
    )
    assert _run(LockOrderCyclePass(), db) == []


def test_interprocedural_cycle_through_helper(tmp_path):
    """A cycle whose A→B edge only exists through a helper call — the
    point of summary propagation: no single function shows both orders."""
    db = _db(
        tmp_path,
        {
            "twist.py": """
            import threading

            A = threading.Lock()
            B = threading.Lock()

            def _grab_b():
                with B:
                    pass

            def outer():
                with A:
                    _grab_b()

            def reverse():
                with B:
                    with A:
                        pass
            """
        },
    )
    found = _run(LockOrderCyclePass(), db)
    assert len(found) == 1, found
    assert "twist.A" in found[0].message and "twist.B" in found[0].message
    assert "_grab_b" in found[0].message  # the chain names the helper


# ---------------------------------------------------------------------------
# lock-order-new-edge + golden workflow
# ---------------------------------------------------------------------------


def test_new_edge_vs_golden_fails_with_site(tmp_path):
    db = _db(
        tmp_path,
        {
            "fresh.py": """
            import threading

            A = threading.Lock()
            B = threading.Lock()

            def pair():
                with A:
                    with B:
                        pass
            """
        },
    )
    found = _run(LockOrderNewEdgePass(), db, golden=set())
    assert len(found) == 1
    f = found[0]
    assert f.rule == "lock-order-new-edge"
    assert f.severity == "error"
    assert f.path == "fresh.py"  # anchored at the real acquisition site
    assert "fresh.A -> fresh.B" in f.message


def test_stale_golden_edge_warns_and_blessed_edge_is_silent(tmp_path):
    db = _db(
        tmp_path,
        {
            "fresh.py": """
            import threading

            A = threading.Lock()
            B = threading.Lock()

            def pair():
                with A:
                    with B:
                        pass
            """
        },
    )
    golden = {"fresh.A -> fresh.B", "fresh.GONE -> fresh.B"}
    found = _run(LockOrderNewEdgePass(), db, golden=golden)
    assert len(found) == 1
    f = found[0]
    assert f.severity == "warning" and "fresh.GONE" in f.message
    assert f.path == GRAPH_PATH


def test_no_golden_skips_the_edge_diff(tmp_path):
    db = _db(tmp_path, {"fresh.py": CYCLE_SRC})
    assert _run(LockOrderNewEdgePass(), db, golden=None) == []


def test_golden_round_trip(tmp_path):
    path = str(tmp_path / "lock_order.json")
    edges = ["m.B -> m.C", "m.A -> m.B", "m.A -> m.B"]  # dupes collapse
    save_lock_order(edges, path)
    assert load_lock_order(path) == {"m.A -> m.B", "m.B -> m.C"}
    # the file is reviewable: sorted, commented, newline-terminated
    raw = open(path).read()
    assert raw.endswith("\n")
    data = json.loads(raw)
    assert data["edges"] == sorted(set(edges))
    assert "--update-lock-order" in data["comment"]


def test_load_lock_order_missing_file_is_none(tmp_path):
    assert load_lock_order(str(tmp_path / "absent.json")) is None


def test_update_lock_order_scoping(tmp_path):
    """--update-lock-order over a SUBTREE writes only that subtree's
    edges — a scoped re-bless must not silently drop the rest of the
    repo's constraints from a golden it then overwrites."""
    path = str(tmp_path / "lock_order.json")
    sub = os.path.join(REPO_ROOT, "sentinel_tpu", "cluster")
    n = update_lock_order(path=path, roots=[sub])
    scoped = load_lock_order(path)
    assert n == len(scoped) > 0
    full = set(current_edges())
    # every scoped edge exists in the full graph under the same ids
    # (canonicalization must not depend on which roots were scanned)
    assert scoped <= full
    assert scoped < full  # and scoping genuinely narrowed the set


# ---------------------------------------------------------------------------
# blocking-under-lock
# ---------------------------------------------------------------------------


def test_blocking_call_through_helper_is_found(tmp_path):
    """The trigger an intra-procedural lint cannot see: the lock is in
    one function, the socket connect two calls away."""
    db = _db(
        tmp_path,
        {
            "svc.py": """
            import socket
            import threading

            L = threading.Lock()

            def _dial(host):
                return socket.create_connection((host, 80))

            def _fetch(host):
                return _dial(host)

            def serve(host):
                with L:
                    return _fetch(host)
            """
        },
    )
    found = _run(BlockingUnderLockPass(), db)
    assert len(found) == 1, found
    f = found[0]
    assert f.rule == "blocking-under-lock"
    assert f.path == "svc.py"
    assert "svc.L" in f.message
    # the call chain to the blocking op is reconstructed for the report
    assert "_fetch" in f.message and "_dial" in f.message


def test_blocking_without_lock_is_clean(tmp_path):
    db = _db(
        tmp_path,
        {
            "svc.py": """
            import socket
            import threading

            L = threading.Lock()

            def _dial(host):
                return socket.create_connection((host, 80))

            def serve(host):
                with L:
                    pass
                return _dial(host)
            """
        },
    )
    assert _run(BlockingUnderLockPass(), db) == []


def test_source_site_suppression_kills_transitive_findings(tmp_path):
    """A rationale ON the blocking call removes it from the summary —
    callers holding locks stop reporting it too."""
    db = _db(
        tmp_path,
        {
            "svc.py": """
            import socket
            import threading

            L = threading.Lock()

            def _dial(host):
                return socket.create_connection((host, 80))  # stlint: disable=blocking-under-lock — fixture rationale

            def serve(host):
                with L:
                    return _dial(host)
            """
        },
    )
    assert _run(BlockingUnderLockPass(), db) == []


def test_timeoutless_future_result_under_lock(tmp_path):
    db = _db(
        tmp_path,
        {
            "pool.py": """
            import threading

            L = threading.Lock()

            def wait_all(futs):
                with L:
                    return [f.result() for f in futs]
            """
        },
    )
    found = _run(BlockingUnderLockPass(), db)
    assert len(found) == 1
    assert "future-result" in found[0].message


# ---------------------------------------------------------------------------
# thread-lifecycle
# ---------------------------------------------------------------------------


def test_unjoined_non_daemon_thread_is_reported(tmp_path):
    db = _db(
        tmp_path,
        {
            "svc.py": """
            import threading

            class Svc:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def _run(self):
                    pass
            """
        },
    )
    found = _run(ThreadLifecyclePass(), db)
    assert len(found) == 1, found
    assert found[0].rule == "thread-lifecycle"
    assert found[0].path == "svc.py"


def test_daemon_or_joined_threads_are_clean(tmp_path):
    db = _db(
        tmp_path,
        {
            "svc.py": """
            import threading

            class Daemonic:
                def start(self):
                    self._t = threading.Thread(target=self._run, daemon=True)
                    self._t.start()

                def _run(self):
                    pass

            class Joined:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def _run(self):
                    pass

                def stop(self):
                    self._t.join()
            """
        },
    )
    assert _run(ThreadLifecyclePass(), db) == []


def test_timeoutless_wait_under_lock_is_reported(tmp_path):
    db = _db(
        tmp_path,
        {
            "svc.py": """
            import threading

            class W:
                def __init__(self):
                    self._cv = threading.Condition()

                def pump(self):
                    with self._cv:
                        self._cv.wait()
            """
        },
    )
    found = _run(ThreadLifecyclePass(), db)
    assert len(found) == 1
    assert "timeout" in found[0].message

    db2 = _db(
        tmp_path / "b",
        {
            "svc.py": """
            import threading

            class W:
                def __init__(self):
                    self._cv = threading.Condition()

                def pump(self):
                    with self._cv:
                        self._cv.wait(timeout=1.0)
            """
        },
    )
    assert _run(ThreadLifecyclePass(), db2) == []


# ---------------------------------------------------------------------------
# CLI / reporting integration
# ---------------------------------------------------------------------------


def test_rule_catalog_spans_three_tiers():
    cat = rule_catalog()
    for p in ALL_CONCURRENCY_PASSES:
        assert p.name in cat and cat[p.name]


def test_sarif_carries_tier3_findings(tmp_path):
    from sentinel_tpu.analysis.framework import format_sarif

    db = _db(tmp_path, {"twist.py": CYCLE_SRC})
    findings = []
    for p in ALL_CONCURRENCY_PASSES:
        findings.extend(p.run(db, None))
    assert findings
    doc = json.loads(format_sarif(findings, findings, rule_catalog()))
    run = doc["runs"][0]
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert "lock-order-cycle" in rule_ids
    locs = [
        r["locations"][0]["physicalLocation"]["artifactLocation"]
        for r in run["results"]
    ]
    # the concurrency:// pseudo-path must not claim the repo uriBaseId
    pseudo = [l for l in locs if l["uri"].startswith("concurrency://")]
    assert pseudo and all("uriBaseId" not in l for l in pseudo)


def test_cli_tier_concurrency_gate():
    env = {**os.environ, "PYTHONPATH": REPO_ROOT, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, "-m", "sentinel_tpu.analysis", "--tier", "concurrency"],
        capture_output=True,
        text=True,
        env=env,
        timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 finding(s)" in r.stdout


# ---------------------------------------------------------------------------
# THE repo gate
# ---------------------------------------------------------------------------


def test_repo_gate_zero_findings_and_acyclic_blessed_graph():
    """The CI contract for this tier: the committed golden exists, is
    exactly the current tree's edge set (any new edge fails until
    reviewed and re-blessed), the graph is acyclic, and every finding
    across all four passes is fixed or carries a written rationale."""
    golden = load_lock_order(LOCK_ORDER_PATH)
    assert golden, "lock_order.json missing or empty — re-bless and commit"
    assert set(current_edges()) == golden

    succ = {}
    for e in golden:
        a, _, b = e.partition(" -> ")
        succ.setdefault(a, set()).add(b)
        succ.setdefault(b, set())
    assert _sccs(set(succ), succ) == []

    findings = run_concurrency_analysis()
    assert findings == [], "\n".join(
        f"{f.path}:{f.line} [{f.rule}] {f.message}" for f in findings
    )


def test_tier3_baseline_is_empty():
    """Tier 3 launched with ZERO accepted debt: no concurrency rule may
    appear in baseline.json — new hazards get fixed or a written
    rationale, never a baseline bump."""
    from sentinel_tpu.analysis import DEFAULT_BASELINE, load_baseline

    conc_rules = {p.name for p in ALL_CONCURRENCY_PASSES}
    offenders = [
        k for k in load_baseline(DEFAULT_BASELINE) if k.split(":")[0] in conc_rules
    ]
    assert offenders == []


def test_canonical_runtime_order_tick_mutex_outer():
    """The ordering fix this tier landed: ``_tick_mutex`` is the OUTER
    runtime lock, ``_cluster_lock`` inner.  The reverse edge coming back
    (recompile warming inside the cluster lock again) re-creates the
    mode-partitioned deadlock hazard PR 16 removed."""
    edges = set(current_edges())
    tm = "runtime.client.SentinelClient._tick_mutex"
    cl = "runtime.client.SentinelClient._cluster_lock"
    assert f"{tm} -> {cl}" in edges
    assert f"{cl} -> {tm}" not in edges


# ---------------------------------------------------------------------------
# regression tests for the concurrency fixes this tier motivated
# ---------------------------------------------------------------------------


def test_ensure_connected_does_not_queue_behind_a_connect():
    """While one thread owns the connect lock, other admission threads
    must get an instant False (degraded fallback), not block for the
    2 s connect window."""
    from sentinel_tpu.cluster.client import ClusterTokenClient

    c = ClusterTokenClient("127.0.0.1", 1, reconnect_interval_s=0.0)
    assert c._lock.acquire(blocking=False)
    try:
        t0 = time.monotonic()
        assert c._ensure_connected() is False
        assert time.monotonic() - t0 < 0.5
    finally:
        c._lock.release()


def test_drain_resolves_abandons_wedged_ticks(monkeypatch):
    """A resolver that never returns (wedged device readback) must not
    hang stop() under _tick_mutex forever: the drain shares one deadline
    and abandons what is still running."""
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.runtime import client as RC

    def pending(resolved):
        p = RC._PendingTick(
            acq=[], blocks=[], fronts=[], inv_a=None, out=None,
            check_dropped=False, n_obj=0, n_blk=0,
        )
        if resolved:
            p.settled.set()
        return p

    c = RC.SentinelClient(cfg=small_engine_config(), mode="sync")
    done, wedged = pending(True), pending(False)  # wedged: never resolved
    c._pending_ticks = [done, wedged]

    # virtual clock: the first mono_s() sets the deadline, every later
    # read is past it — the drain must take the timeout path instantly
    ticks = iter([100.0] + [1000.0] * 10)
    monkeypatch.setattr(RC, "mono_s", lambda: next(ticks))
    c._drain_resolves()
    assert c._pending_ticks == []
    assert wedged.state == "pending"  # abandoned, not claimed into a fake result


def test_worker_waits_carry_timeouts():
    """The lost-notify fix: every Condition.wait on the lease-refresher
    and token-batcher worker loops must carry a timeout (a missed
    notify degrades to a bounded poll instead of a parked-forever
    thread).  Source-level so a revert cannot hide behind scheduling."""
    for rel in ("sentinel_tpu/cluster/shard.py", "sentinel_tpu/cluster/token_service.py"):
        tree = ast.parse(open(os.path.join(REPO_ROOT, rel)).read())
        bare = [
            node.lineno
            for node in ast.walk(tree)
            if isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "wait"
            and not node.args
            and not node.keywords
        ]
        assert bare == [], f"{rel}: timeout-less wait() at lines {bare}"


# ---------------------------------------------------------------------------
# runtime lock witness
# ---------------------------------------------------------------------------


@pytest.fixture()
def witness():
    from sentinel_tpu.analysis.concurrency import witness as W

    W.install()
    W.reset()
    yield W
    W.uninstall()
    W.reset()


def test_witness_records_and_inverts(witness):
    W = witness
    a = W.WitnessLock(W._REAL_LOCK(), "fix.A._lock", reentrant=False)
    b = W.WitnessLock(W._REAL_LOCK(), "fix.B._lock", reentrant=False)
    with a:
        with b:
            pass
    assert ("fix.A._lock", "fix.B._lock") in W.dynamic_edges()
    assert W.violations() == []
    with b:
        with a:
            pass
    assert any("order inversion" in v for v in W.violations())
    ok, detail = W.verdict()
    assert not ok and "violation" in detail


def test_witness_same_instance_reacquire_raises(witness):
    W = witness
    a = W.WitnessLock(W._REAL_LOCK(), "fix.A._lock", reentrant=False)
    with a:
        with pytest.raises(RuntimeError, match="self-deadlock"):
            a.acquire()
    assert any("re-acquire" in v for v in W.violations())


def test_witness_rlock_reentry_and_condition_are_clean(witness):
    W = witness
    r = W.WitnessRLock(W._REAL_RLOCK(), "fix.C._rlock")
    with r:
        with r:
            pass
    cv = threading.Condition(r)

    def poke():
        with cv:
            cv.notify()

    with cv:
        t = threading.Thread(target=poke)
        t.start()
        cv.wait(timeout=2.0)
        t.join()
    assert W.violations() == []
    assert W._held_stack() == []


def test_witnessed_client_smoke_no_violations(witness):
    """The acceptance run: a real threaded SentinelClient under the
    witness — zero violations, zero dynamic edges the static graph
    missed."""
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.core.rules import FlowRule
    from sentinel_tpu.runtime.client import SentinelClient

    W = witness
    c = SentinelClient(
        cfg=small_engine_config(), mode="threaded", tick_interval_ms=2.0
    )
    c.flow_rules.load([FlowRule(resource="res-w", count=100.0)])
    c.start()
    try:
        for _ in range(3):
            with c.entry("res-w"):
                pass
            time.sleep(0.01)
    finally:
        c.stop()
    assert W.violations() == []
    assert W.edges_unknown_to_static() == []
    # the run actually exercised witnessed locks — this is not a vacuous
    # pass on an uninstrumented client
    assert any(
        "runtime.client.SentinelClient" in a or "runtime.client.SentinelClient" in b
        for a, b in W.dynamic_edges()
    )
    ok, detail = W.verdict()
    assert ok, detail


def test_chaos_invariant_is_universal_and_green_when_inactive():
    from sentinel_tpu.chaos.invariants import (
        CATALOG,
        MetricsDelta,
        ScenarioContext,
        evaluate,
    )

    assert "no-order-violations" in CATALOG
    out = evaluate(["verdict-accounting"], ScenarioContext(metrics=MetricsDelta()))
    names = [v.name for v in out]
    assert "no-order-violations" in names  # appended without being asked
    v = next(v for v in out if v.name == "no-order-violations")
    assert v.ok and "inactive" in v.detail
