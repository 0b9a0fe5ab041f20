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
"""

from __future__ import annotations

from typing import Optional


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
