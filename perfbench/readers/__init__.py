"""Metric readers.  ``metrics/<metric>.json`` names one of these modules
under ``reader`` and gives its arguments; each has
``read(ctx, **args) -> float | None``.  A reader that finds nothing to read
returns ``None`` and the harness leaves the metric out of the result line.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Context:
    """What a run hands its readers."""

    window: object  # perfbench.generators.Window
    setup_s: float
    batch: int  # the configuration's tick width in items
    spans: List[dict] = dataclasses.field(default_factory=list)  # obs spans of the window
    trace: Optional[object] = None  # perfbench.xplane.Summary of the traced run


def by_tick(spans: List[dict], names) -> Dict[int, Dict[str, dict]]:
    """``{tick id: {span name: span}}`` over the named spans."""
    out: Dict[int, Dict[str, dict]] = {}
    for s in spans:
        if s["name"] in names and s["trace"]:
            out.setdefault(s["trace"], {})[s["name"]] = s
    return out


def statistic(values, stat: str) -> Optional[float]:
    v = np.asarray(values, np.float64)
    if not len(v):
        return None
    if stat == "mean":
        return float(v.mean())
    if stat.startswith("p"):
        return float(np.percentile(v, float(stat[1:])))
    raise ValueError(f"unknown statistic {stat!r}")
