"""The fit test: a deployment kind with two stand-in doors, its own check,
its own plain reference and its own generator, brought as a later PR would
bring them (the files under ``fit/`` and the entries of
``fit/BENCHMARK.add.json``), run through the same ``run_cell`` with no edit
to any file the harness has.

The harness is copied beside the new files and run there in a process of its
own, because a module is found by its name in the ``perfbench`` package: in
this process that package is the repository's, which must not gain files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench import manifest as M

FIT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fit")
CELL = "pair.round-robin"
DRIVER = """
import contextlib, json, sys
sys.path.insert(0, {root!r})
from perfbench import manifest, run
assert manifest.ROOT == {root!r}, manifest.ROOT
kind = manifest.config(manifest.cell(manifest.load(), {cell!r})["config"])["deployment"]
control = manifest.module("deployments", kind).control if {control} else contextlib.nullcontext
with control():
    result = run.run_cell({cell!r}, {seed}, 1.0, False, require_tpu=False)
print(json.dumps(result))
"""


def _files(top):
    return {
        os.path.relpath(os.path.join(d, f), top): open(os.path.join(d, f), "rb").read()
        for d, _dirs, files in os.walk(top) for f in files
    }


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout's ``perfbench/`` and ``BENCHMARK.json`` with the fit's
    files and entries added, and the proof that adding was all it took."""
    root = str(tmp_path_factory.mktemp("fit"))
    shutil.copytree(os.path.join(M.ROOT, "perfbench"), os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(os.path.join(root, "perfbench"))
    new = _files(os.path.join(FIT, "perfbench"))
    assert not set(new) & set(before), "the fit may only add files"
    shutil.copytree(os.path.join(FIT, "perfbench"), os.path.join(root, "perfbench"),
                    dirs_exist_ok=True)
    after = _files(os.path.join(root, "perfbench"))
    assert {k: after[k] for k in before} == before and set(after) == set(before) | set(new)

    manifest = M.load()
    with open(os.path.join(FIT, "BENCHMARK.add.json")) as f:
        add = json.load(f)
    for group in ("configs", "workloads", "per_layer"):
        manifest[group] = manifest[group] + add[group]
    for m in manifest["end_to_end"]:
        if m["name"] in add["end_to_end_workloads"]:
            m["workloads"] = m["workloads"] + add["end_to_end_workloads"][m["name"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def rehearse(root, control=False, seed=2**31 + 23):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "-c", DRIVER.format(root=root, cell=CELL, seed=seed, control=control)],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return [json.loads(line) for line in done.stdout.splitlines() if line.startswith("{")]


def test_the_manifest_with_the_fit_added_is_sound(root):
    assert M.problems(M.load(root), root) == []


def test_a_kind_the_harness_has_never_seen_runs_through_run_cell(root):
    lines = rehearse(root)
    result = lines[-1]
    assert result["correct"] is True, lines
    # the result line has the shape of the others (test_rehearsal.py)
    assert sorted(result) == ["attempted", "correct", "device", "failed", "metrics"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] >= 1
    assert {"kind", "memory_peak_bytes"} <= set(result["device"])
    want = {m["name"]: m["unit"] for m in M.metrics_of(M.load(root), CELL, "end_to_end")}
    assert sorted(want) == ["decisions_per_s", "setup_s"]
    assert {k: v["unit"] for k, v in result["metrics"].items()} == want
    assert all(isinstance(v["value"], float) and v["value"] > 0 for v in result["metrics"].values())
    # and so have the lines before it
    assert [l["phase"] for l in lines if "phase" in l] == ["setup", "window", "replay"]
    window = next(l for l in lines if l.get("phase") == "window")
    assert window["compiles_since_setup"] == 0
    assert len(window["memory_peak_bytes_per_chip"]) == result["device"]["count"]
    # a verdict code the FlowRule cells' check refuses, judged by this kind's own
    assert set(window["codes"]) == {"0", "9"}
    numbers = {l["compared"]: l for l in lines if "compared" in l}
    assert numbers["ids_granted_otherwise_than_the_plain_rule"]["limit"] == 0
    assert numbers["spent_answers"]["value"] >= 1
    assert all(n["ok"] for n in numbers.values())


def test_its_own_check_bites_when_its_guarantee_is_broken(root):
    """The run under the kind's own ``control()``, as ``study.py control``
    enters it: both doors loaded with two grants a request where the
    configuration states one."""
    lines = rehearse(root, control=True)
    numbers = {l["compared"]: l for l in lines if "compared" in l}
    assert lines[-1]["correct"] is False
    assert numbers["ids_granted_otherwise_than_the_plain_rule"]["value"] >= 1


def test_the_kind_is_refused_under_a_mix_that_does_not_drive_it(root):
    manifest = M.load(root)
    M.cell(manifest, CELL)["traffic"] = "paced-4k"
    bad = M.problems(manifest, root)
    assert len(bad) == 1 and "drives ['single_client'], not config pair's deployment kind 'two_doors'" in bad[0]
