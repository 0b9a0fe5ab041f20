"""The fit test: a deployment kind with two stand-in doors, its own check,
its own plain reference and its own generator, brought as a later PR would
bring them (the files under ``fit/`` and the entries of
``fit/BENCHMARK.add.json``), run through the same ``run_cell`` with no edit
to any file the harness has.  Since PR 27 it brings what a cell of a new kind
on four chips brings: a second cell with ``"chips": 4``, a per-layer metric
appended after the last there is, host-span names of its own, its rehearsal
data and its own run that must come out as not correct; and the harness's own
tests are run over the manifest so grown.

The harness is copied beside the new files and run there in a process of its
own, because a module is found by its name in the ``perfbench`` package: in
this process that package is the repository's, which must not gain files."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from perfbench import manifest as M
from perfbench import xplane

FIT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fit")
TESTS = os.path.join("tests", "perfbench_tests")
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
        for d, _dirs, files in os.walk(top) for f in files if "__pycache__" not in d
    }


def _add():
    with open(os.path.join(FIT, "BENCHMARK.add.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout's two benchmark directories and ``BENCHMARK.json`` with the
    fit's files and entries added, and the proof that adding was all it took.
    (Where the fit is already in place, as in a copy of the tree that a
    reviewer grew by hand, adding it again changes nothing.)"""
    root = str(tmp_path_factory.mktemp("fit"))
    for rel in (M.HERE, TESTS):
        shutil.copytree(os.path.join(M.ROOT, rel), os.path.join(root, rel),
                        ignore=shutil.ignore_patterns("__pycache__"))
    # around them, what lets the tests there import each other, and no more
    open(os.path.join(root, "tests", "__init__.py"), "w").close()
    with open(os.path.join(root, "pytest.ini"), "w") as f:
        f.write("[pytest]\nmarkers =\n    jitted: as in the repository's own pytest.ini\n")
    before = _files(root)
    new = {rel: body for rel, body in _files(FIT).items() if rel.startswith((M.HERE, "tests"))}
    assert all(before[rel] == new[rel] for rel in set(new) & set(before)), "the fit may only add files"
    for rel in (M.HERE, "tests"):
        shutil.copytree(os.path.join(FIT, rel), os.path.join(root, rel), dirs_exist_ok=True)
    after = _files(root)
    assert {k: after[k] for k in before} == before and set(after) == set(before) | set(new)

    manifest, add = M.load(), _add()
    for group in ("configs", "workloads", "per_layer"):
        have = {e["name"] for e in manifest[group]}
        manifest[group] = manifest[group] + [e for e in add[group] if e["name"] not in have]
    for m in manifest["end_to_end"]:
        for cell in add["end_to_end_workloads"].get(m["name"], []):
            if cell not in m["workloads"]:
                m["workloads"] = m["workloads"] + [cell]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def _env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1")
    return env


def rehearse(root, control=False, seed=2**31 + 23):
    done = subprocess.run(
        [sys.executable, "-c", DRIVER.format(root=root, cell=CELL, seed=seed, control=control)],
        cwd=root, env=_env(), capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return [json.loads(line) for line in done.stdout.splitlines() if line.startswith("{")]


def test_the_manifest_with_the_fit_added_is_sound(root):
    grown = M.load(root)
    assert M.problems(grown, root) == []
    # a four-chip cell and a per-layer metric after the last there was
    assert [w["chips"] for w in grown["workloads"]][-2:] == [1, 4]
    assert grown["per_layer"][-1]["name"] == _add()["per_layer"][-1]["name"]


def test_the_harness_own_tests_pass_over_the_grown_manifest(root):
    """What a ``model_config`` PR meets: it may edit no file under the
    benchmark's two directories, and the driver runs the harness's own tests
    over the manifest it grew.  So those tests are run here over the grown
    manifest, in the copy, in a process of their own: the manifest's tests
    whole, the fit cells' cases of the rehearsal (their sizes found by name
    under ``rehearsal/``) and the not-correct run the kind brings; exit 0,
    and no file that was in the copy differs afterwards.

    With ``test_manifest.py`` as it was before PR 27 this fails: it asserted
    ``chips == 1`` for every cell.  So did the rehearsal, on a ``KeyError``
    (sizes and parameters were two dicts in the test file) and on
    ``flow_replay``'s three comparison names, and ``test_timeline.py`` on the
    per-layer metric appended after the five it held to be last."""
    before = _files(root)
    cases = "test_rehearsal.py::test_cell_rehearses_correct_with_the_result_lines_shape"
    cells = [w["name"] for w in _add()["workloads"]]
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", "-p", "no:xdist",
         os.path.join(TESTS, "test_manifest.py"), os.path.join(TESTS, "test_two_doors.py"),
         os.path.join(TESTS, "test_timeline.py") + "::test_the_manifest_is_sound_with_the_new_metrics",
         *[f"{os.path.join(TESTS, cases)}[{cell}]" for cell in cells]],
        cwd=root, env=_env(), capture_output=True, text=True, timeout=240)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-1000:]
    # a node id that selects nothing is exit 4; and none was skipped
    assert " passed in " in done.stdout and "skipped" not in done.stdout, done.stdout[-500:]
    after = _files(root)
    assert {k: after[k] for k in before} == before
    assert not [k for k in after if k not in before], "the tests left files behind"


def test_a_cell_without_rehearsal_data_names_the_file_to_add(root):
    """One line, with the path, whichever of the three is missing."""
    done = subprocess.run(
        [sys.executable, "-c", "from tests.perfbench_tests import rehearsal\n"
         "for group, name in (('configs', 'trio'), ('traffic', 'ring'), ('checks', 'held')):\n"
         "    try: rehearsal.read(group, name)\n"
         "    except LookupError as e: print(e)\n"],
        cwd=root, env=_env(), capture_output=True, text=True, timeout=60)
    assert done.stdout.splitlines() == [
        "configuration 'trio' has no rehearsal data: add tests/perfbench_tests/rehearsal/configs/trio.json",
        "traffic mix 'ring' has no rehearsal data: add tests/perfbench_tests/rehearsal/traffic/ring.json",
        "check 'held' has no rehearsal data: add tests/perfbench_tests/rehearsal/checks/held.json",
    ], done.stderr[-1000:]


def test_the_kinds_own_span_names_explain_its_idle_device():
    """``two_doors`` names ``door.*`` spans, none of them a ``tick.*``: the
    one attribution asks them, in the kind's order, as it asks the first
    kind's ten."""
    from perfbench import deployments

    spec = importlib.util.spec_from_file_location(
        "fit_two_doors", os.path.join(FIT, M.HERE, "deployments", "two_doors.py"))
    kind = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = kind  # a dataclass looks its module up there
    try:
        spec.loader.exec_module(kind)
    finally:
        del sys.modules[spec.name]
    spans = [{"name": n, "t0_ns": t0 * 1000, "dur_ns": d * 1000, "trace": 0}
             for n, t0, d in (("door.queue", 0, 500), ("door.answer", 400, 300),
                              ("tick.assemble", 0, 1000))]
    host = deployments.host_intervals(kind, spans)
    assert [n for n, _a, _b in host] == ["door.answer", "door.queue"] and kind.TICK_SPAN == "door.answer"
    pd = xplane.from_json({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            {"name": xplane.WINDOW_MARK, "start_ns": 0, "duration_ns": 1_000_000}]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            {"name": "%copy.1 = s32[8]{0} copy(s32[8]{0} %a)", "start_ns": 600_000,
             "duration_ns": 100_000}]}]},
    ]})
    # idle 0-600 and 700-1000 us: the answer covers 400-600, the queue the rest of 0-500
    assert xplane.idle_by(pd, 0, host) == {
        "host_other": pytest.approx(300e-6), "door.queue": pytest.approx(400e-6),
        "door.answer": pytest.approx(200e-6), "in_program": 0.0}
    # and a module that names none is asked nothing
    assert deployments.host_intervals(np, spans) == []


def test_a_kind_the_harness_has_never_seen_runs_through_run_cell(root):
    lines = rehearse(root)
    result = lines[-1]
    assert result["correct"] is True, lines
    # the result line has the shape of the others (test_rehearsal.py)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "beside", "compared"]
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
    numbers = {l["compared"]: l for l in lines if "rule" in l}
    assert numbers["ids_granted_otherwise_than_the_plain_rule"]["limit"] == 0
    assert numbers["spent_answers"]["value"] >= 1
    assert all(n["ok"] for n in numbers.values())


def test_its_own_check_bites_when_its_guarantee_is_broken(root):
    """The run under the kind's own ``control()``, as ``study.py control``
    enters it: both doors loaded with two grants a request where the
    configuration states one."""
    lines = rehearse(root, control=True)
    numbers = {l["compared"]: l for l in lines if "rule" in l}
    assert lines[-1]["correct"] is False
    assert numbers["ids_granted_otherwise_than_the_plain_rule"]["value"] >= 1


def test_the_kind_is_refused_under_a_mix_that_does_not_drive_it(root):
    manifest = M.load(root)
    M.cell(manifest, CELL)["traffic"] = "paced-4k"
    bad = M.problems(manifest, root)
    assert len(bad) == 1 and "drives ['single_client'], not config pair's deployment kind 'two_doors'" in bad[0]
