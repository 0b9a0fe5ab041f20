"""What a cell is rehearsed with on the CPU, found by name like the cell's
other files (``perfbench/manifest.py``), so that a later PR's cell brings its
own as new files:

- ``configs/<config>.json``: the tiny sizes, the ``sizes`` argument of
  ``run.run_cell`` (keys of the configuration's groups, replaced)
- ``traffic/<traffic>.json``: the short parameters, its ``params_override``
- ``checks/<check>.json``: which of the check's comparisons keep a rehearsal
  from passing with nothing compared.  ``equal_to_the_reference`` names those
  held to the plain reference with the limit 0, ``at_least_one`` those that
  count what was compared and must reach 1; each list names at least one.
"""

import json
import os

from perfbench import manifest as M

HERE = os.path.dirname(os.path.abspath(__file__))
WHAT = {"configs": "configuration", "traffic": "traffic mix", "checks": "check"}


def read(group: str, name: str) -> dict:
    rel = f"tests/perfbench_tests/rehearsal/{group}/{name}.json"
    try:
        with open(os.path.join(HERE, group, f"{name}.json")) as f:
            return json.load(f)
    except FileNotFoundError:
        raise LookupError(f"{WHAT[group]} {name!r} has no rehearsal data: add {rel}") from None


def of(cell: str) -> tuple:
    """``(sizes, params_override, the check's names)`` of a cell of the
    committed manifest."""
    entry = M.cell(M.load(), cell)
    check = M.config(entry["config"])["check"]
    return read("configs", entry["config"]), read("traffic", entry["traffic"]), read("checks", check)
