"""sentinel_tpu.analysis — the TPU-hazard linter.

Two jobs:

1. unit-test every pass on fixture snippets, one triggering and one
   non-triggering per rule (plus the suppression syntaxes);
2. THE CI GATE: run all five passes over the real ``sentinel_tpu/`` tree
   and require zero findings beyond the checked-in baseline — this is
   what keeps fail-open/host-sync/jit-recompile/time-source/unguarded-
   global hazards from riding in on future PRs.

Pure AST work — no jax, no engine compiles; this file is cheap.
"""

from __future__ import annotations

import ast
import json
import os
import subprocess
import sys
import textwrap

import pytest

from sentinel_tpu.analysis import (
    ALL_PASSES,
    DEFAULT_BASELINE,
    REPO_ROOT,
    load_baseline,
    new_findings,
    run_passes,
)
from sentinel_tpu.analysis.framework import (
    ParsedModule,
    parse_suppressions,
)
from sentinel_tpu.analysis.passes import (
    FailOpenPass,
    HostSyncPass,
    JitRecompilePass,
    TimeSourcePass,
    UnguardedGlobalPass,
)


def _mod(source: str, path: str = "sentinel_tpu/runtime/client.py") -> ParsedModule:
    """ParsedModule from an inline snippet; ``path`` controls which
    file-scoped rules engage."""
    source = textwrap.dedent(source)
    line_disables, file_disables = parse_suppressions(source)
    return ParsedModule(
        path=path,
        abspath="/" + path,
        source=source,
        tree=ast.parse(source),
        line_disables=line_disables,
        file_disables=file_disables,
    )


def _run(p, mod):
    # mirrors the runner's filter (framework.run_passes): the suppression
    # check covers the finding's whole anchor span, not just line 1 of it
    return [f for f in p.run(mod) if not mod.suppressed(f.rule, *f.span())]


# ---------------------------------------------------------------------------
# time-source
# ---------------------------------------------------------------------------


def test_time_source_triggers_on_raw_clock_and_aliases():
    mod = _mod(
        """
        import time as _time
        from time import monotonic as mono

        def deadline():
            return _time.time() + mono()
        """
    )
    got = _run(TimeSourcePass(), mod)
    assert len(got) == 2
    assert all(f.rule == "time-source" for f in got)


def test_time_source_allows_helpers_perf_counter_and_own_module():
    clean = _mod(
        """
        import time
        from sentinel_tpu.utils.time_source import mono_s

        def f():
            t0 = time.perf_counter()  # profiling-only: allowed
            time.sleep(0.01)          # not a clock READ
            return mono_s() - t0
        """
    )
    assert _run(TimeSourcePass(), clean) == []
    own = _mod(
        "import time\n\ndef now():\n    return time.time()\n",
        path="sentinel_tpu/utils/time_source.py",
    )
    assert _run(TimeSourcePass(), own) == []


def test_time_source_allowlists_tracer_read_point_only():
    """obs/trace.py holds the span tracer's single sanctioned monotonic
    read (ISSUE 3 satellite); every other obs module stays banned."""
    src = "import time\n\ndef now_ns():\n    return time.monotonic_ns()\n"
    assert _run(TimeSourcePass(), _mod(src, path="sentinel_tpu/obs/trace.py")) == []
    got = _run(TimeSourcePass(), _mod(src, path="sentinel_tpu/obs/registry.py"))
    assert len(got) == 1 and got[0].rule == "time-source"
    # the chaos failpoint registry is the fault-injection plane's single
    # sanctioned home for time manipulation (ISSUE 4 satellite): its
    # delay/clock_skew actions may touch the clock there, and NOWHERE
    # else in the chaos package
    assert (
        _run(TimeSourcePass(), _mod(src, path="sentinel_tpu/chaos/failpoints.py"))
        == []
    )
    got = _run(TimeSourcePass(), _mod(src, path="sentinel_tpu/chaos/runner.py"))
    assert len(got) == 1 and got[0].rule == "time-source"
    # the REAL tracer module keeps exactly ONE raw-clock call site
    real = os.path.join(REPO_ROOT, "sentinel_tpu", "obs", "trace.py")
    with open(real) as f:
        tree = ast.parse(f.read())
    from sentinel_tpu.analysis import astutil as A

    aliases = A.import_aliases(tree)
    raw_reads = [
        n
        for n in ast.walk(tree)
        if isinstance(n, ast.Call)
        and A.resolve_call(n, aliases)
        in ("time.monotonic_ns", "time.monotonic", "time.time", "time.time_ns")
    ]
    assert len(raw_reads) == 1, "obs/trace.py must keep ONE sanctioned clock read"


# ---------------------------------------------------------------------------
# fail-open
# ---------------------------------------------------------------------------


def test_fail_open_triggers_on_broad_swallow_in_admission_path():
    mod = _mod(
        """
        def check(item):
            try:
                return engine_verdict(item)
            except Exception:
                return PASS
        """
    )
    got = _run(FailOpenPass(), mod)
    assert len(got) == 1 and got[0].rule == "fail-open"


def test_fail_open_ignores_reraise_cleanup_and_out_of_scope_files():
    mod = _mod(
        """
        def check(item):
            try:
                return engine_verdict(item)
            except Exception:
                log()
                raise

        def teardown(sock):
            try:
                sock.close()
            except Exception:
                pass
        """
    )
    assert _run(FailOpenPass(), mod) == []
    # same swallow in a NON-admission file: out of scope
    other = _mod(
        """
        def render(x):
            try:
                return fmt(x)
            except Exception:
                return ""
        """,
        path="sentinel_tpu/dashboard/ui.py",
    )
    assert _run(FailOpenPass(), other) == []


def test_fail_open_suppression_with_rationale():
    mod = _mod(
        """
        def check(item):
            try:
                return consult_token_service(item)
            except Exception:  # stlint: disable=fail-open — degrades to local rules
                return degrade_to_local(item)
        """
    )
    assert _run(FailOpenPass(), mod) == []


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------


def test_host_sync_triggers_in_jit_zone_and_hot_path():
    mod = _mod(
        """
        import jax
        import numpy as np

        @jax.jit
        def kernel(state, x):
            bad = np.asarray(x)
            return state.sum() + float(x[0])

        def _run_tick(self, acq):
            v = self._tick(acq)
            return v.verdict.item()
        """
    )
    got = _run(HostSyncPass(), mod)
    rules = sorted(set(f.rule for f in got))
    assert rules == ["host-sync"]
    msgs = " | ".join(f.message for f in got)
    assert "numpy.asarray" in msgs  # np materialization inside jit
    assert "float()" in msgs  # traced coercion inside jit
    assert ".item()" in msgs  # sync in the client hot path


def test_host_sync_jit_zone_extends_to_callees_and_allows_static_cfg():
    mod = _mod(
        """
        import functools
        import jax
        import numpy as np

        def tick(state, acq, *, cfg):
            if cfg.seg_effects:          # static branch: fine
                state = _land(state, acq)
            return state

        def _land(state, acq):
            return state + np.asarray(acq)   # callee of a jitted root

        def make_tick(cfg):
            fn = functools.partial(tick, cfg=cfg)
            fn = jax.jit(fn, donate_argnums=(0,))
            return fn

        def host_prep(cols):
            return np.asarray(cols)      # not reachable from any root
        """,
        path="sentinel_tpu/ops/engine.py",
    )
    got = _run(HostSyncPass(), mod)
    assert len(got) == 1, [f.message for f in got]
    assert "_land" in got[0].message


def test_host_sync_clean_dispatch_is_clean():
    mod = _mod(
        """
        import numpy as np

        def _run_tick(self, acq):
            cols = np.zeros(len(acq), np.int32)   # host batch assembly: fine
            return self._tick(self._dev(cols))
        """
    )
    assert _run(HostSyncPass(), mod) == []


# ---------------------------------------------------------------------------
# jit-recompile
# ---------------------------------------------------------------------------


def test_jit_recompile_triggers_on_callsite_jit_loop_jit_and_traced_branch():
    mod = _mod(
        """
        import jax

        def per_call(x):
            return jax.jit(lambda y: y + 1)(x)

        def in_loop(xs):
            out = []
            for x in xs:
                out.append(jax.jit(step))
            return out

        @jax.jit
        def branchy(state, now_ms, *, cfg):
            if now_ms > 0:
                return state
            return state * 2
        """,
        path="sentinel_tpu/ops/engine.py",
    )
    got = _run(JitRecompilePass(), mod)
    msgs = " | ".join(f.message for f in got)
    assert "invoked at its own call site" in msgs
    assert "inside a loop" in msgs
    assert "traced parameter 'now_ms'" in msgs


def test_jit_recompile_flags_mutable_module_closure():
    mod = _mod(
        """
        import jax

        _REGISTRY = {}

        @jax.jit
        def kernel(x):
            return x * len(_REGISTRY)
        """,
        path="sentinel_tpu/ops/engine.py",
    )
    got = _run(JitRecompilePass(), mod)
    assert any("_REGISTRY" in f.message for f in got)


def test_jit_recompile_clean_cached_factory_is_clean():
    mod = _mod(
        """
        import functools
        import threading
        import jax

        _CACHE = {}
        _LOCK = threading.Lock()

        def tick(state, acq, *, cfg):
            return state if cfg.flag else state * 2

        def make_tick(cfg):
            with _LOCK:
                fn = _CACHE.get(cfg)
                if fn is None:
                    fn = functools.partial(tick, cfg=cfg)
                    fn = jax.jit(fn)
                    _CACHE[cfg] = fn
            return fn
        """,
        path="sentinel_tpu/ops/engine.py",
    )
    got = _run(JitRecompilePass(), mod)
    # `tick` is jitted via the two-step idiom; its cfg branch is static
    # and the cache write is lock-guarded -> nothing to report
    assert got == [], [f.message for f in got]


# ---------------------------------------------------------------------------
# unguarded-global
# ---------------------------------------------------------------------------


def test_unguarded_global_triggers_on_lockless_registry_write():
    mod = _mod(
        """
        _HANDLERS = {}
        _ORDER: list = []

        def register(name, fn):
            _HANDLERS[name] = fn
            _ORDER.append(name)
        """
    )
    got = _run(UnguardedGlobalPass(), mod)
    assert len(got) == 2
    assert all(f.rule == "unguarded-global" for f in got)


def test_unguarded_global_lock_guarded_and_local_shadows_are_clean():
    mod = _mod(
        """
        import threading

        _HANDLERS = {}
        _lock = threading.Lock()

        def register(name, fn):
            with _lock:
                _HANDLERS[name] = fn

        def local_work():
            tmp = {}
            tmp["k"] = 1      # local, not the module global
            return tmp
        """
    )
    assert _run(UnguardedGlobalPass(), mod) == []


def test_unguarded_global_catches_global_rebind():
    mod = _mod(
        """
        _EXTS: list = []

        def clear():
            global _EXTS
            _EXTS = []
        """
    )
    got = _run(UnguardedGlobalPass(), mod)
    assert len(got) == 1 and "rebound" in got[0].message


def test_unguarded_global_lockset_mismatch_reports_both_sites():
    """Lock PRESENCE is not enough: writes under _LOCK_A and _LOCK_B
    both 'hold a lock' but serialize against nothing.  Every site of the
    disjoint lockset is reported, each naming the other."""
    mod = _mod(
        """
        import threading

        _CACHE = {}
        _LOCK_A = threading.Lock()
        _LOCK_B = threading.Lock()

        def put(k, v):
            with _LOCK_A:
                _CACHE[k] = v

        def evict(k):
            with _LOCK_B:
                _CACHE.pop(k, None)
        """
    )
    got = _run(UnguardedGlobalPass(), mod)
    assert len(got) == 2
    assert all("disjoint locksets" in f.message for f in got)
    # each site names the other's lock
    assert "_LOCK_B" in got[0].message and "_LOCK_A" in got[1].message


def test_unguarded_global_consistent_lock_and_nesting_are_clean():
    mod = _mod(
        """
        import threading

        _CACHE = {}
        _LOCK = threading.Lock()
        _OTHER = threading.Lock()

        def put(k, v):
            with _LOCK:
                _CACHE[k] = v

        def evict(k):
            with _OTHER:
                with _LOCK:          # nested: _LOCK still held
                    _CACHE.pop(k, None)
        """
    )
    assert _run(UnguardedGlobalPass(), mod) == []


def test_unguarded_global_single_guarded_site_never_mismatches():
    """One guarded site has nothing to be inconsistent WITH — the
    lockset check needs two sites."""
    mod = _mod(
        """
        import threading

        _CACHE = {}
        _only_lock = threading.Lock()

        def put(k, v):
            with _only_lock:
                _CACHE[k] = v
        """
    )
    assert _run(UnguardedGlobalPass(), mod) == []


def test_unguarded_global_entry_locks_are_never_another_trees():
    """The helper-inherits-its-callers'-lock cache is keyed by the tree's
    id, and a freed tree's id is handed out again: an entry answers only for
    the tree it was computed from."""
    from sentinel_tpu.analysis.concurrency import summaries

    mod = _mod(
        """
        import threading
        _REG = {}
        _LOCK = threading.Lock()

        def _store(k):
            _REG[k] = 1

        def put(k):
            with _LOCK:
                _store(k)
        """
    )
    stale = ast.parse("x = 1")
    summaries._MOD_ENTRY_CACHE[id(mod.tree)] = (stale, {})  # as if the id had been another tree's
    try:
        assert summaries.module_entry_locks(mod) == {"_store": frozenset({"_LOCK"})}
        assert _run(UnguardedGlobalPass(), mod) == []
    finally:
        summaries.invalidate_cache()


# ---------------------------------------------------------------------------
# suppression machinery
# ---------------------------------------------------------------------------


def test_suppression_next_line_and_file_scope():
    mod = _mod(
        """
        # stlint: disable-file=time-source reason: fixture file
        import time

        def a():
            return time.time()

        def b():
            try:
                return check()
            # stlint: disable-next-line=fail-open
            except Exception:
                return 0
        """
    )
    assert _run(TimeSourcePass(), mod) == []
    assert _run(FailOpenPass(), mod) == []


def test_suppression_shares_comment_with_noqa():
    mod = _mod(
        """
        import time

        def f():
            return time.time()  # noqa: X100  # stlint: disable=time-source — fixture
        """
    )
    assert _run(TimeSourcePass(), mod) == []


def test_suppression_anchors_on_multiline_statement_tail():
    """A trailing directive naturally lands on the CLOSING line of a
    multi-line statement; the finding anchors on the first line.  The
    anchor span must cover the whole statement."""
    mod = _mod(
        """
        import time

        def f():
            return time.time(
            )  # stlint: disable=time-source — fixture: multi-line call
        """
    )
    assert _run(TimeSourcePass(), mod) == []
    # ... and a directive on a line BELOW the statement does nothing
    unrelated = _mod(
        """
        import time

        def f():
            t = time.time()
            # stlint: disable=time-source
            return t
        """
    )
    assert len(_run(TimeSourcePass(), unrelated)) == 1


def test_suppression_anchors_on_decorator_and_def_line():
    """For findings anchored at a decorated def, the directive works on
    the decorator line (where the @jax.jit that makes it hazardous
    lives) AND on the def line — both are the statement's header."""
    from sentinel_tpu.analysis.framework import Pass

    class DefPass(Pass):
        name = "def-probe"

        def run(self, mod):
            import ast as _ast

            for node in _ast.walk(mod.tree):
                if isinstance(node, _ast.FunctionDef):
                    yield self.finding(mod, node, "probe")

    on_decorator = _mod(
        """
        import functools

        @functools.cache  # stlint: disable=def-probe — fixture
        def f():
            return 1
        """
    )
    assert _run(DefPass(), on_decorator) == []

    on_def = _mod(
        """
        import functools

        @functools.cache
        def f():  # stlint: disable=def-probe — fixture
            return 1
        """
    )
    assert _run(DefPass(), on_def) == []

    in_body = _mod(
        """
        import functools

        @functools.cache
        def f():
            return 1  # stlint: disable=def-probe — body lines are NOT the header
        """
    )
    assert len(_run(DefPass(), in_body)) == 1


def test_suppression_span_does_not_leak_across_statements():
    """The span of statement N must not swallow a directive intended
    for statement N+1 sharing the same line region."""
    mod = _mod(
        """
        import time

        def f():
            a = time.time()
            # stlint: disable-next-line=time-source — only the SECOND read
            b = time.time()
            return a + b
        """
    )
    got = _run(TimeSourcePass(), mod)
    assert len(got) == 1 and got[0].line == 5


# ---------------------------------------------------------------------------
# the CI gate + CLI contract
# ---------------------------------------------------------------------------


def test_repo_is_clean_vs_baseline():
    """THE gate: all five passes over the real tree, zero findings beyond
    the checked-in baseline.  A failure here means a PR introduced a
    fail-open/host-sync/jit-recompile/time-source/unguarded-global hazard
    (fix it or suppress WITH a rationale; see sentinel_tpu/analysis/README.md)."""
    findings = run_passes(
        [os.path.join(REPO_ROOT, "sentinel_tpu")], ALL_PASSES, rel_to=REPO_ROOT
    )
    new = new_findings(findings, load_baseline(DEFAULT_BASELINE))
    assert new == [], "NEW lint findings:\n" + "\n".join(
        f"{f.path}:{f.line}: [{f.rule}] {f.message}" for f in new
    )


def test_cli_exit_codes(tmp_path):
    """Non-zero on a seeded violation, zero on the clean repo."""
    env = {**os.environ, "PYTHONPATH": REPO_ROOT}
    bad = tmp_path / "sentinel_tpu" / "runtime"
    bad.mkdir(parents=True)
    snippet = bad / "client.py"
    snippet.write_text("import time\n\ndef f():\n    return time.time()\n")

    r = subprocess.run(
        [sys.executable, "-m", "sentinel_tpu.analysis", str(snippet), "--json"],
        capture_output=True,
        text=True,
        env=env,
        timeout=120,
    )
    assert r.returncode == 1, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["new"] == 1
    assert report["findings"][0]["rule"] == "time-source"

    r2 = subprocess.run(
        [sys.executable, "-m", "sentinel_tpu.analysis"],
        capture_output=True,
        text=True,
        env=env,
        timeout=300,
    )
    assert r2.returncode == 0, r2.stdout + r2.stderr


def test_cli_sarif_output(tmp_path):
    """--sarif: valid SARIF 2.1.0 with NEW findings as results (the
    GitHub code-scanning inline-annotation contract); exit code still 1."""
    env = {**os.environ, "PYTHONPATH": REPO_ROOT}
    bad = tmp_path / "sentinel_tpu" / "runtime"
    bad.mkdir(parents=True)
    snippet = bad / "client.py"
    snippet.write_text("import time\n\ndef f():\n    return time.time()\n")

    r = subprocess.run(
        [sys.executable, "-m", "sentinel_tpu.analysis", str(snippet), "--sarif"],
        capture_output=True,
        text=True,
        env=env,
        timeout=120,
    )
    assert r.returncode == 1, r.stdout + r.stderr
    sarif = json.loads(r.stdout)
    assert sarif["version"] == "2.1.0"
    run = sarif["runs"][0]
    assert run["tool"]["driver"]["name"] == "stlint"
    results = run["results"]
    assert len(results) == 1
    assert results[0]["ruleId"] == "time-source"
    assert results[0]["level"] == "error"
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["region"]["startLine"] == 4
    # the rule metadata block names every rule that fired
    assert [ru["id"] for ru in run["tool"]["driver"]["rules"]] == ["time-source"]

    # --sarif and --json are mutually exclusive (usage error)
    r2 = subprocess.run(
        [
            sys.executable, "-m", "sentinel_tpu.analysis", str(snippet),
            "--sarif", "--json",
        ],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r2.returncode == 2


def test_unguarded_global_call_rooted_lock_still_counts():
    """A lock reached through a call has no stable dotted name but must
    still count as a held lock (pre-lockset behavior) — not a false
    'without the owning lock' error."""
    mod = _mod(
        """
        _CACHE = {}

        def put(reg, k, v):
            with reg().lock:
                _CACHE[k] = v
        """
    )
    assert _run(UnguardedGlobalPass(), mod) == []


def test_cli_zero_pass_selection_is_usage_error(tmp_path):
    """--rules naming only the OTHER tier's passes must exit 2, not
    masquerade as a clean run with zero passes executed."""
    env = {**os.environ, "PYTHONPATH": REPO_ROOT}
    snippet = tmp_path / "probe.py"
    snippet.write_text("import time\n\ndef f():\n    return time.time()\n")
    # explicit path pins tier=ast; const-hoist is jaxpr-tier only
    r = subprocess.run(
        [
            sys.executable, "-m", "sentinel_tpu.analysis",
            str(snippet), "--rules", "const-hoist",
        ],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r.returncode == 2, r.stdout + r.stderr
    assert "no pass selected for tier(s) ast" in r.stderr


def test_scoped_update_baseline_preserves_out_of_scope_debt(tmp_path):
    """--update-baseline on a SCOPED run (explicit path) re-measures only
    that scope; accepted entries elsewhere must survive the rewrite or
    the next full run reports old debt as NEW."""
    env = {**os.environ, "PYTHONPATH": REPO_ROOT}
    tree = tmp_path / "sentinel_tpu" / "runtime"
    tree.mkdir(parents=True)
    a = tree / "a.py"
    b = tree / "b.py"
    a.write_text("import time\n\ndef f():\n    return time.time()\n")
    b.write_text("import time\n\ndef g():\n    return time.time()\n")
    base = tmp_path / "baseline.json"

    # accept both files' debt
    r = subprocess.run(
        [
            sys.executable, "-m", "sentinel_tpu.analysis", str(a), str(b),
            "--baseline", str(base), "--update-baseline",
        ],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    accepted = json.loads(base.read_text())["accepted"]
    assert len(accepted) == 2

    # re-update scoped to a.py only: b.py's entry must be preserved
    r2 = subprocess.run(
        [
            sys.executable, "-m", "sentinel_tpu.analysis", str(a),
            "--baseline", str(base), "--update-baseline",
        ],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert json.loads(base.read_text())["accepted"] == accepted

    # the full (two-path) run still sees nothing new
    r3 = subprocess.run(
        [
            sys.executable, "-m", "sentinel_tpu.analysis", str(a), str(b),
            "--baseline", str(base),
        ],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r3.returncode == 0, r3.stdout + r3.stderr


def test_rule_catalog_spans_both_tiers():
    """The CLI's SARIF rule metadata and the README catalog are driven
    by rule_catalog(); it must name the AST rules AND the jaxpr rules
    (importing the tier-2 pass classes must NOT trigger a trace)."""
    from sentinel_tpu.analysis import rule_catalog

    cat = rule_catalog()
    assert {
        "fail-open",
        "host-sync",
        "jit-recompile",
        "time-source",
        "unguarded-global",
        "transfer-guard",
        "dtype-overflow",
        "const-hoist",
        "recompile-fingerprint",
        "flops-bytes-budget",
    } <= set(cat)
    assert all(desc for desc in cat.values())


def test_cli_update_baseline_roundtrip(tmp_path):
    env = {**os.environ, "PYTHONPATH": REPO_ROOT}
    bad = tmp_path / "sentinel_tpu" / "runtime"
    bad.mkdir(parents=True)
    snippet = bad / "client.py"
    snippet.write_text("import time\n\ndef f():\n    return time.time()\n")
    base = tmp_path / "baseline.json"

    r = subprocess.run(
        [
            sys.executable, "-m", "sentinel_tpu.analysis", str(snippet),
            "--baseline", str(base), "--update-baseline",
        ],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    # accepted into the baseline -> the same tree now exits 0...
    r2 = subprocess.run(
        [
            sys.executable, "-m", "sentinel_tpu.analysis", str(snippet),
            "--baseline", str(base),
        ],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r2.returncode == 0, r2.stdout + r2.stderr
    # ...but --no-baseline still sees the debt
    r3 = subprocess.run(
        [
            sys.executable, "-m", "sentinel_tpu.analysis", str(snippet),
            "--baseline", str(base), "--no-baseline",
        ],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r3.returncode == 1


def test_only_the_benchmark_times_a_tick():
    """The ledger's harness (``perfbench/``) reads a tick's host time from
    the tracer's spans.  Nothing imports the deleted ``bench`` module or
    ``benchmarks/`` package, and the client keeps no meter of its own."""
    importers = []
    for root, dirs, files in os.walk(REPO_ROOT):
        dirs[:] = [d for d in dirs if not d.startswith(".") and d != "chiprun_out"]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path) as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Import):
                    mods = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom) and not node.level:
                    mods = [node.module]
                else:
                    continue
                if any(m.split(".")[0] in ("bench", "benchmarks") for m in mods):
                    importers.append(f"{os.path.relpath(path, REPO_ROOT)}:{node.lineno}")
    assert importers == []
    with open(os.path.join(REPO_ROOT, "sentinel_tpu", "runtime", "client.py")) as f:
        client = ast.parse(f.read())
    cls = next(n for n in client.body
               if isinstance(n, ast.ClassDef) and n.name == "SentinelClient")
    methods = {n.name: n for n in cls.body if isinstance(n, ast.FunctionDef)}
    assert "host_build_ms_avg" not in methods
    clock_reads = [n.lineno for n in ast.walk(methods["_run_tick"])
                   if isinstance(n, ast.Attribute) and n.attr == "perf_counter"]
    assert clock_reads == []
