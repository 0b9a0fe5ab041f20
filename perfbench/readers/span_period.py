"""Milliseconds from the start of one span to the start of the next of the
same name: the tick period when the span opens every tick."""

import numpy as np

from perfbench.readers import statistic


def read(ctx, span: str, stat: str = "mean"):
    starts = np.sort([s["t0_ns"] for s in ctx.spans if s["name"] == span])
    return statistic(np.diff(starts) / 1e6, stat)
