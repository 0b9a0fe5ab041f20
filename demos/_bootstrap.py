"""Shared demo setup: repo-root imports + platform default.

QPS-based demos assume entries are much faster than the 1 s statistic
window; on very slow hosts (cold XLA compiles) a demo may show fewer
blocks than advertised — each demo warms the engine first to avoid the
worst of it.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# demos are documentation: they default to CPU (demos/README.md); set
# JAX_PLATFORMS yourself to run one elsewhere
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def warm(client, resource: str = "__warmup__") -> None:
    """Run one entry end-to-end so rule-reload recompiles are paid before
    the demo's timed loops (a cold tick can exceed entry_timeout_s)."""
    try:
        with client.entry(resource):
            pass
    except Exception:
        pass
