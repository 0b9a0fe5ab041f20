"""chip_smoke.py / bench.py refuse to run without a chip, the compile-cache
helper places the cache from outside, and (slow) the CPU rehearsal walks
the whole smoke."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

from sentinel_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*argv, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, *argv], cwd=REPO, env=env, timeout=timeout,
        capture_output=True, text=True,
    )


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_no_chip_means_nonzero_exit_and_no_result(script):
    r = _run(script, timeout=120)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr  # names the platform it found
    assert r.stdout.strip() == ""  # no JSON line a reader could take for a result


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_no_probe_subprocess(script):
    src = open(os.path.join(REPO, script)).read()
    assert not re.search(r"\bsubprocess\b|\bPopen\b|os\.system|multiprocessing", src)


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


def test_result_line_has_exactly_the_contract_keys():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
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
        "environment", "serve", "evidence", "equivalence",
    ]
    assert all(p["ok"] for p in summary["phases"].values())
