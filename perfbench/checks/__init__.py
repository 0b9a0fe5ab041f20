"""What decides ``correct``.  A configuration file names one of these
modules under ``check`` and, under ``reference``, the plain reference of
``perfbench/reference/`` that the check holds the system to; each check has
``decide(dep, generator, params, seed, win) -> (correct, numbers, replayed)``:
every number compared as a ``Compared``, and a summary of what was driven
after the window for the run's ``phase="replay"`` line.

``decide`` is handed the deployment as the window left it, still serving,
and stops it when its own comparison needs that; the harness stops it again
afterwards, which then does nothing.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Compared:
    name: str
    value: float
    limit: float
    at_least: bool = False  # the value must reach the limit, not stay under it

    @property
    def ok(self) -> bool:
        return self.value >= self.limit if self.at_least else self.value <= self.limit
