"""chip_smoke.py refuses to run without a chip and builds the deployment
the benchmark's cells measure, the compile-cache helper places the cache from
outside, and (slow) the CPU rehearsal walks the whole smoke."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

import chip_smoke
from sentinel_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*argv, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, *argv], cwd=REPO, env=env, timeout=timeout,
        capture_output=True, text=True,
    )


def test_no_chip_means_nonzero_exit_and_no_result():
    r = _run("chip_smoke.py", timeout=120)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr  # names the platform it found
    assert r.stdout.strip() == ""  # no JSON line a reader could take for a result


def test_no_probe_subprocess():
    src = open(os.path.join(REPO, "chip_smoke.py")).read()
    assert not re.search(r"\bsubprocess\b|\bPopen\b|os\.system|multiprocessing", src)


def _file_with(*sizes):
    """perfbench/configs/zipf-1m.json, read here and not through the code
    under test, with the groups of ``sizes`` laid over it."""
    with open(os.path.join(REPO, "perfbench", "configs", "zipf-1m.json")) as f:
        cfg = json.load(f)
    for s in sizes:
        for group, keys in s.items():
            cfg[group] = {**cfg[group], **keys}
    return cfg


@pytest.mark.parametrize(
    "sizes", [(), (chip_smoke.REHEARSAL_SIZES,)], ids=["chip", "rehearsal"]
)
def test_the_smoke_builds_the_deployment_the_cells_measure(sizes):
    """On the chip the file as it is; in the rehearsal the same file, cut."""
    want = _file_with(*sizes)
    dep = chip_smoke.build(3, *sizes)
    c = dep.client
    try:
        assert dep.config == want
        assert {k: getattr(c.cfg, k) for k in want["engine"]} == want["engine"]
        assert (c.mode, c._pipeline_depth, c.tick_interval_ms, c.entry_timeout_s) == tuple(
            want["client"][k]
            for k in ("mode", "pipeline_depth", "tick_interval_ms", "entry_timeout_s")
        )
        res, rules = want["resources"], want["rules"]
        assert dep.ruled_names == [f"res-{i + 1}" for i in range(res["n_ruled"])]
        flow = c.flow_rules.get()
        assert [r.count for r in flow] == (
            [rules["flow_qps"]] * res["n_ruled"] + [rules["tail_qps"]] * res["n_tail_ruled"]
        )
        assert len(c.degrade_rules.get()) == res["n_ruled"]
        assert len(c.param_flow_rules.get()) == rules["n_param_ruled"]
        assert len(c.authority_rules.get()) == rules["n_authority_ruled"]
        assert [r.qps for r in c.system_rules.get()] == [rules["system_qps"]]
        assert len(dep.pool) == want["traffic"]["pool_batches"]
        assert {len(col) for batch in dep.pool for col in batch} == {c.cfg.batch_size}
    finally:
        c.stop()


def test_compile_cache_dir_follows_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)


def test_compile_cache_dir_default_is_fixed(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.compile_cache_dir()
    assert path == os.path.join(REPO, ".jax_cache")
    # the path is part of the cache key: nothing run-specific in it
    assert str(os.getpid()) not in path and "tmp" not in path.lower()
    assert compile_cache.compile_cache_dir() == path


def test_enabling_the_compile_cache_keeps_the_small_programs_too(monkeypatch, tmp_path):
    """A start compiles some sixty programs of a tenth of a second beside the
    tick's shapes: the cache takes them all, not only what took a second."""
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", was)


@pytest.mark.parametrize(
    "sizes", [(), (chip_smoke.PARAM_REHEARSAL_SIZES,)], ids=["chip", "rehearsal"]
)
def test_the_smoke_builds_the_param_deployment_its_cell_measures(sizes):
    with open(os.path.join(REPO, "perfbench", "configs", "param-1m-hot-keys.json")) as f:
        want = json.load(f)
    for s in sizes:
        for group, keys in s.items():
            want[group] = {**want[group], **keys}
    dep = chip_smoke.build(3, *sizes, config=chip_smoke.PARAM_CONFIG)
    c = dep.client
    try:
        assert dep.config == want
        assert {k: getattr(c.cfg, k) for k in want["engine"]} == want["engine"]
        rules = c.param_flow_rules.get()
        assert len(rules) == want["resources"]["n_routes"] == len(dep.route_ids)
        assert {(r.count, r.param_flow_item_list[0].count) for r in rules} == {(5, 10)}
        assert not c.flow_rules.get() and not c.degrade_rules.get()
        assert dep.universe == want["resources"]["universe_pairs"]
        assert len(dep.pool) == want["traffic"]["pool_batches"]
    finally:
        c.stop()


def test_the_param_store_phase_holds_a_replay_to_the_exact_shadow():
    """The phase itself at rehearsal size, on the plain CPU path (the
    rehearsal's forced fast-path flags are the slow test's)."""
    sizes = dict(chip_smoke.PARAM_REHEARSAL_SIZES)
    sizes["engine"] = {k: v for k, v in sizes["engine"].items()
                       if k not in ("use_mxu_tables", "fused_effects", "seg_effects")}
    detail, failures = chip_smoke.param_store_phase(5, (sizes,), 12)
    assert failures == []
    assert detail["param_width"] == 1 << 15 and detail["rules"] == 16
    assert detail["replay_param_over_admitted"] == 0 and detail["replay_blocked_items"] >= 1
    assert detail["store_cells"] == 1 << 15 and all(n > 0 for n in detail["store_cells_counting"])


def test_the_breaker_phase_walks_six_far_rows_through_every_transition():
    """The phase itself at rehearsal size, on the plain CPU path."""
    sizes = dict(chip_smoke.BREAKER_REHEARSAL_SIZES)
    sizes["engine"] = {k: v for k, v in sizes["engine"].items()
                       if k not in ("use_mxu_tables", "fused_effects", "seg_effects")}
    detail, failures = chip_smoke.breaker_phase(5, (sizes,))
    assert failures == []
    assert len(detail["rows"]) == 6 and min(detail["rows"]) > 40
    assert (detail["opened"], detail["reopened"], detail["closed_again"]) == (2, 1, 2)
    assert detail["half_opened"] == 3 and detail["exits_while_open"] == 4 and detail["ratio_ties"] == 2


def test_result_line_has_exactly_the_contract_keys():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = chip_smoke.result_line(True, {**device, "extra": 0})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": device}


@pytest.mark.slow  # ~4 min: the fused+seg tick traces and compiles on CPU
def test_cpu_rehearsal_walks_every_phase():
    r = _run("chip_smoke.py", "--rehearse-cpu", "--seed", "3", timeout=1500)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = [json.loads(x) for x in r.stdout.strip().splitlines()]
    # the result line is the last one and has exactly the keys the chip
    # check reads; everything else is in the summary line before it
    final, summary = lines[-1], lines[-2]["summary"]
    assert set(final) == {"ok", "device"} and final["ok"] is True
    assert set(final["device"]) == {"platform", "kind", "count"}
    assert final["device"]["platform"] == "cpu"
    assert summary["ok"] is True and summary["rehearsal"] is True
    assert list(summary["phases"]) == [
        "environment", "serve", "evidence", "equivalence", "param_store", "breaker",
    ]
    assert all(p["ok"] for p in summary["phases"].values())
