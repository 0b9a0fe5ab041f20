"""``BENCHMARK.json`` and the files it names: the committed manifest is
sound, faults are caught, and a configuration, a mix, a cell and a per-layer
metric can each be added as new files and entries only."""

import copy
import json
import os
import re
import shutil

import pytest

from perfbench import manifest as M

MANIFEST = M.load()


def test_the_committed_manifest_is_sound():
    assert M.problems(MANIFEST) == []


def test_contract_keys_and_limits():
    assert sorted(MANIFEST) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"]
    )
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    for w in MANIFEST["workloads"]:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    for c in MANIFEST["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
        assert len(c["source"]) <= 200
    assert all(not w.startswith("/") and ".." not in w for w in MANIFEST["command"])


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_resolves_to_files_and_reports_enough(cell):
    entry = M.cell(MANIFEST, cell)
    assert M.config(entry["config"])["name"] == entry["config"]
    params = M.traffic(entry)
    assert os.path.exists(os.path.join(M.ROOT, "perfbench", "generators", params["generator"] + ".py"))
    # an offered rate is a literal in a data file, never worked out at run time
    rate = params.get(params.get("rate_key", "rate_items_per_s"))
    assert rate is None or type(rate) in (int, float)
    e2e = [m["name"] for m in M.metrics_of(MANIFEST, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = M.metrics_of(MANIFEST, cell, "per_layer")
    assert layer
    for m in layer:
        assert m["moves"] in e2e, f"{m['name']} moves {m['moves']}, which {cell} does not report"
        assert M.metric(m["name"])["reader"]


def test_one_layer_one_spelling():
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    assert len({l.lower().split(" (")[0] for l in layers}) == len(layers)


def named(m, group, name):
    return next(e for e in m[group] if e["name"] == name)


def _broken(edit):
    m = copy.deepcopy(MANIFEST)
    edit(m)
    return M.problems(m)


@pytest.mark.parametrize("edit, word", [
    (lambda m: m["workloads"][0].update(name="zipf 1m"), "not a permitted name"),
    (lambda m: m["end_to_end"][0].update(unit="decisions per s"), "unit"),
    (lambda m: m["per_layer"][0].update(moves="nothing"), "moves unknown"),
    (lambda m: named(m, "per_layer", "tick_fill_pct.flood").update(workloads=["zipf-1m.paced"]), "does not report"),
    (lambda m: m.update(workloads=m["workloads"][:2]), "has no cell"),
    (lambda m: m["workloads"][1].update(traffic="no-such-mix"), "traffic/no-such-mix.json"),
    (lambda m: m["per_layer"].append(dict(m["per_layer"][0], name="no_such_metric")), "metrics/no_such_metric.json"),
    (lambda m: m["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda m: m["end_to_end"].pop(), "setup_s"),
    (lambda m: m["workloads"].append(dict(m["workloads"][0], name="again")), "pair appears twice"),
    (lambda m: m["configs"][0].update(source="elsewhere"), "source differs"),
    (lambda m: m["workloads"][0].update(chips=2), "chips 2"),
])
def test_faults_are_named(edit, word):
    assert any(word in p for p in _broken(edit)), _broken(edit)


def _with_cells(m, keep, four):
    """The manifest cut to its first ``keep`` cells, the first ``four`` of
    them asking for four chips, and still sound otherwise."""
    m["workloads"] = m["workloads"][:keep]
    for w in m["workloads"][:four]:
        w["chips"] = 4
    cells = {w["name"] for w in m["workloads"]}
    m["configs"] = [c for c in m["configs"] if any(w["config"] == c["name"] for w in m["workloads"])]
    for group in ("end_to_end", "per_layer"):
        for e in m[group]:
            if "workloads" in e:
                e["workloads"] = [w for w in e["workloads"] if w in cells]
        m[group] = [e for e in m[group] if e.get("workloads", True)]


@pytest.mark.parametrize("keep, four, most", [
    (1, 1, None),  # one cell always may
    (4, 2, None),  # half, rounded down
    (3, 1, None),
    (4, 3, 2),
    (3, 2, 1),
])
def test_at_most_half_the_cells_may_ask_for_four_chips(keep, four, most):
    """The driver's rule, which ``problems()`` meets first: refused in one
    line that names the cells, or not at all."""
    names = ", ".join(w["name"] for w in MANIFEST["workloads"][:four])
    line = f"{four} of {keep} cells ask for four chips ({names}); at most {most} may"
    assert _broken(lambda m: _with_cells(m, keep, four)) == ([line] if most else [])


def _edit_file(root, rel, edit):
    path = os.path.join(root, "perfbench", rel)
    with open(path) as f:
        body = json.load(f)
    edit(body)
    with open(path, "w") as f:
        json.dump(body, f)


@pytest.mark.parametrize("rel, edit, lines", [
    ("configs/zipf-1m.json", lambda c: c.pop("deployment"),
     ["config zipf-1m: names no deployment"]),
    ("configs/zipf-1m.json", lambda c: c.update(deployment="token_mesh"),
     ["config zipf-1m: no deployment 'token_mesh' (perfbench/deployments/token_mesh.py)",
      "workload zipf-1m.flood: traffic flood-128k drives ['single_client'], not config zipf-1m's deployment kind 'token_mesh'",
      "workload zipf-1m.paced: traffic paced-4k drives ['single_client'], not config zipf-1m's deployment kind 'token_mesh'"]),
    ("configs/zipf-10k.json", lambda c: c.update(check="no_such_check"),
     ["config zipf-10k: no check 'no_such_check' (perfbench/checks/no_such_check.py)"]),
    ("configs/zipf-10k.json", lambda c: c.update(reference="../check"),
     ["config zipf-10k: no reference '../check' (perfbench/reference/../check.py)"]),
    ("configs/zipf-10k.json", lambda c: c.update(reference="plain"),
     ["config zipf-10k: check 'flow_replay' does not import perfbench.reference.plain"]),
    ("traffic/entry-8t.json", lambda t: t.update(drives=["token_mesh"]),
     ["workload zipf-10k.entry: traffic entry-8t drives ['token_mesh'], not config zipf-10k's deployment kind 'single_client'"]),
    ("traffic/entry-8t.json", lambda t: t.pop("drives"),
     ["workload zipf-10k.entry: traffic entry-8t drives [], not config zipf-10k's deployment kind 'single_client'"]),
])
def test_a_configurations_modules_and_a_mixs_kinds_are_each_named_in_one_line(tmp_path, rel, edit, lines):
    """What a configuration names (deployment, check, reference) and what a
    mix says it drives are held against the files before anything is built."""
    from perfbench import run

    root = str(tmp_path)
    shutil.copytree(os.path.join(M.ROOT, "perfbench"), os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(M.ROOT, "BENCHMARK.json"), root)
    with open(os.path.join(root, "perfbench", "reference", "plain.py"), "w") as f:
        f.write("# a reference that no check imports\n")
    assert M.problems(M.load(root), root) == []
    _edit_file(root, rel, edit)
    assert M.problems(M.load(root), root) == lines
    # and a run stops there, in one line, before it builds a thing
    with pytest.raises(ValueError, match="^BENCHMARK.json: " + re.escape(lines[0])):
        run.run_cell("zipf-10k.entry", 1, 1.0, False, require_tpu=False, root=root)


def test_one_of_each_can_be_added_without_editing_a_file(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(M.ROOT, "perfbench"), os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {
        os.path.join(d, f): open(os.path.join(d, f), "rb").read()
        for d, _dirs, files in os.walk(os.path.join(root, "perfbench")) for f in files
    }
    cfg = M.config("zipf-10k", root)
    cfg.update(name="zipf-2k", source="a later PR's own source")
    cfg["resources"].update(n_ruled=2000, id_universe=2000)
    mix = dict(M.traffic(M.cell(MANIFEST, "zipf-1m.paced"), root), block_items=1024)
    new = {
        "configs/zipf-2k.json": cfg,
        "traffic/paced-1k.json": mix,
        "cells/zipf-2k.paced.json": {"rate_items_per_s": 1000000},
        "metrics/presort_ms.lat.json": {"reader": "span_stat", "args": {"spans": ["tick.presort"]}},
    }
    for rel, body in new.items():
        with open(os.path.join(root, "perfbench", rel), "w") as f:
            json.dump(body, f)
    m = copy.deepcopy(MANIFEST)
    m["configs"].append({"name": "zipf-2k", "source": cfg["source"], "reduced": [],
                         "file": "perfbench/configs/zipf-2k.json", "why": "smaller"})
    m["workloads"].append({"name": "zipf-2k.paced", "config": "zipf-2k", "traffic": "paced-1k",
                           "chips": 1, "why": "a later PR's cell"})
    for e in m["end_to_end"]:
        if "workloads" in e and e["name"] != "decisions_per_s":
            e["workloads"] = e["workloads"] + ["zipf-2k.paced"]
    m["per_layer"].append({"name": "presort_ms.lat", "unit": "ms", "better": "lower",
                           "source": "program_span",
                           "layer": named(m, "per_layer", "host_build_ms.lat")["layer"],
                           "moves": "decision_p50_ms", "workloads": ["zipf-2k.paced"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    assert M.problems(M.load(root), root) == []
    entry = M.cell(M.load(root), "zipf-2k.paced")
    assert M.traffic(entry, root)["rate_items_per_s"] == 1000000
    assert M.traffic(entry, root)["block_items"] == 1024
    assert [x["name"] for x in M.metrics_of(m, "zipf-2k.paced", "per_layer")] == ["presort_ms.lat"]
    # and no file that was there has changed
    for path, body in before.items():
        assert open(path, "rb").read() == body
