"""Deployment kinds.  A configuration file names one of these modules under
``deployment``; each has ``build(cfg, seed, sizes) -> Deployment`` and
``control()``, a context manager under which ``build`` breaks one guarantee
the configuration states, so that a run has to come out as not correct
(``study.py control``; the benchmark's own runs never enter it).

The harness asks four things of what ``build`` returns: ``start()`` (serve;
every rule is loaded by then), ``stop()`` (nothing of it runs on after; a
second call, or one before ``start()``, does nothing), ``batch`` (the tick's
width in items) and ``config`` (the configuration as it was built, ``sizes``
applied).  What else it carries is between the kind, the generators that
drive it (a traffic file lists the kinds its generator can drive under
``drives``) and the check that its configuration names.

A kind may also say how a traced run of its cells is read, since the spans a
program records are its own: ``TICK_SPAN``, the name of the span that opens
once a tick, and ``host_intervals(spans)``, the host spans that can explain
an idle device, most specific first, each as the interval it covers (which
need not be the span's recorded duration).  A kind that says neither is
traced all the same: its idle seconds read ``in_program`` and ``host_other``.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np


def with_sizes(cfg: dict, sizes: Optional[dict]) -> dict:
    """``cfg`` with keys of its groups replaced for a CPU rehearsal at a tiny
    size (``{"engine": {...}, "resources": {...}, ...}``); a measurement
    never passes ``sizes``."""
    if not sizes:
        return cfg
    return {
        k: ({**v, **sizes[k]} if k in sizes and isinstance(v, dict) else v)
        for k, v in cfg.items()
    }


def host_intervals(kind, spans: List[dict]) -> List[Tuple[str, np.ndarray, np.ndarray]]:
    """What the deployment kind (its module) says the host was doing under
    ``spans``: ``[(name, starts, ends), ...]`` in the spans' own clock, in
    the order ``xplane.idle_by`` is to ask them; empty for a kind that names
    no span."""
    named = getattr(kind, "host_intervals", None)
    return named(spans) if named else []


def intervals(spans: List[dict], name: str, longer=None) -> Tuple[str, np.ndarray, np.ndarray]:
    """Every span of ``name`` as the interval it was recorded with,
    lengthened by ``longer[span's tick id]`` nanoseconds where given."""
    own = [s for s in spans if s["name"] == name]
    ends = [s["t0_ns"] + s["dur_ns"] + (longer.get(s["trace"], 0) if longer else 0) for s in own]
    return name, np.array([s["t0_ns"] for s in own], np.float64), np.array(ends, np.float64)
