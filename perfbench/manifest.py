"""``BENCHMARK.json`` and the data files it names.

A cell names a configuration and a traffic mix; each is a file found by that
name, and so is every metric.  Adding one is adding files and entries:

- ``configs/<config>.json``: the deployment's sizes, rules and guarantees,
  and the three modules it is built and judged by: ``deployment`` (a module
  of ``perfbench.deployments``), ``check`` (``perfbench.checks``) and the
  plain ``reference`` that check imports (``perfbench.reference``)
- ``traffic/<traffic>.json``: the mix's generator (a module of
  ``perfbench.generators``), its parameters, and under ``drives`` the
  deployment kinds that generator can drive
- ``cells/<cell>.json`` (optional): parameters of the mix that belong to
  this cell alone, such as the offered rate found by the sweep
- ``metrics/<metric>.json``: the metric's reader (a module of
  ``perfbench.readers``) and its arguments
"""

from __future__ import annotations

import importlib
import json
import os
import re
from typing import List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = "perfbench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
#: what a configuration file names, and the package each is a module of
CONFIG_MODULES = (("deployment", "deployments"), ("check", "checks"), ("reference", "reference"))


def _read(root: str, *parts: str) -> dict:
    with open(os.path.join(root, HERE, *parts)) as f:
        return json.load(f)


def module(group: str, name: str):
    """The code a data file names: ``perfbench/<group>/<name>.py``."""
    return importlib.import_module(f"{HERE}.{group}.{name}")


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(
        f"no workload {name!r} in BENCHMARK.json; it has "
        + ", ".join(w["name"] for w in manifest["workloads"])
    )


def config(name: str, root: str = ROOT) -> dict:
    return _read(root, "configs", f"{name}.json")


def traffic(cell_entry: dict, root: str = ROOT) -> dict:
    """The cell's traffic parameters: its mix, then what is the cell's own."""
    params = _read(root, "traffic", f"{cell_entry['traffic']}.json")
    own = os.path.join(root, HERE, "cells", f"{cell_entry['name']}.json")
    if os.path.exists(own):
        params.update(_read(root, "cells", f"{cell_entry['name']}.json"))
    return params


def metric(name: str, root: str = ROOT) -> dict:
    return _read(root, "metrics", f"{name}.json")


def metrics_of(manifest: dict, cell_name: str, group: str) -> List[dict]:
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [
        m for m in manifest[group]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def problems(manifest: dict, root: str = ROOT) -> List[str]:
    """Everything wrong with the manifest and its files; empty when sound."""
    out: List[str] = []

    def name_ok(what: str, n: str) -> None:
        if not NAME.match(n):
            out.append(f"{what} {n!r} is not a permitted name")

    def exists(*parts: str) -> Optional[dict]:
        try:
            return _read(root, *parts)
        except (OSError, ValueError) as e:
            out.append(f"{'/'.join(parts)}: {e}")
            return None

    def code(group: str, name) -> Optional[str]:
        """The text of ``<group>/<name>.py``, if the name can be one."""
        if not (isinstance(name, str) and NAME.match(name)):
            return None
        try:
            with open(os.path.join(root, HERE, group, f"{name}.py")) as f:
                return f.read()
        except OSError:
            return None

    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    kinds = {}  # configuration -> the deployment kind its file names
    for n, c in configs.items():
        name_ok("config", n)
        if c["file"] != f"{HERE}/configs/{n}.json":
            out.append(f"config {n}: file is {c['file']}")
        body = exists("configs", f"{n}.json")
        if body is not None:
            if body.get("source") != c["source"]:
                out.append(f"config {n}: source differs from its file's")
            if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
                out.append(f"config {n}: reduced differs from its file's")
            if not body.get("guarantees"):
                out.append(f"config {n}: states no guarantees")
            kinds[n] = body.get("deployment")
            text = {}
            for key, group in CONFIG_MODULES:
                if key not in body:
                    out.append(f"config {n}: names no {key}")
                    continue
                text[key] = code(group, body[key])
                if text[key] is None:
                    out.append(f"config {n}: no {key} {body[key]!r} ({HERE}/{group}/{body[key]}.py)")
            if text.get("check") and text.get("reference") is not None:
                wanted = f"{HERE}.reference.{body['reference']}"
                if wanted not in text["check"]:
                    out.append(f"config {n}: check {body['check']!r} does not import {wanted}")
        if not any(w["config"] == n for w in cells.values()):
            out.append(f"config {n} has no cell")
    pairs = set()
    for n, w in cells.items():
        name_ok("workload", n)
        name_ok("traffic", w["traffic"])
        if w["config"] not in configs:
            out.append(f"workload {n}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            out.append(f"workload {n}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200:
            out.append(f"workload {n}: why has {len(w['why'])} characters")
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"workload {n}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        mix = exists("traffic", f"{w['traffic']}.json")
        if mix is not None:
            gen = os.path.join(root, HERE, "generators", f"{mix.get('generator')}.py")
            if not os.path.exists(gen):
                out.append(f"traffic {w['traffic']}: no generator {mix.get('generator')!r}")
            kind = kinds.get(w["config"])
            if kind is not None and kind not in mix.get("drives", []):
                out.append(f"workload {n}: traffic {w['traffic']} drives {mix.get('drives', [])}, "
                           f"not config {w['config']}'s deployment kind {kind!r}")
    # the driver's rule, met here first: a four-chip cell costs four times
    # the chip time in every later check, so at most half the cells, rounded
    # down, may ask for four chips, and one always may
    four = [n for n, w in cells.items() if w["chips"] == 4]
    if len(four) > max(1, len(cells) // 2):
        out.append(f"{len(four)} of {len(cells)} cells ask for four chips ({', '.join(four)}); "
                   f"at most {max(1, len(cells) // 2)} may")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        out.append("no end-to-end metric setup_s")
    seen = set()
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            n = m["name"]
            name_ok("metric", n)
            if n in seen:
                out.append(f"metric {n} appears twice")
            seen.add(n)
            if not UNIT.match(m["unit"]):
                out.append(f"metric {n}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                out.append(f"metric {n}: better {m['better']!r}")
            if m["source"] not in SOURCES:
                out.append(f"metric {n}: source {m['source']!r}")
            for w in m.get("workloads", []):
                if w not in cells:
                    out.append(f"metric {n}: unknown workload {w}")
            body = exists("metrics", f"{n}.json")
            if body is not None:
                rd = os.path.join(root, HERE, "readers", f"{body.get('reader')}.py")
                if not os.path.exists(rd):
                    out.append(f"metric {n}: no reader {body.get('reader')!r}")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"end-to-end metric {m['name']}: source {m['source']}")
        if not 0 < m["bound"] <= 0.25:
            out.append(f"end-to-end metric {m['name']}: bound {m['bound']}")
    for m in manifest["per_layer"]:
        moved = e2e.get(m["moves"])
        if moved is None:
            out.append(f"metric {m['name']}: moves unknown {m['moves']}")
            continue
        if "workloads" in m and "workloads" in moved:
            for w in m["workloads"]:
                if w not in moved["workloads"]:
                    out.append(f"metric {m['name']}: cell {w} does not report {m['moves']}")
    for n in cells:
        own = [m["name"] for m in metrics_of(manifest, n, "end_to_end")]
        if "setup_s" not in own or len(own) < 2:
            out.append(f"workload {n}: reports {own}")
        if not metrics_of(manifest, n, "per_layer"):
            out.append(f"workload {n}: reports no per-layer metric")
    return out
