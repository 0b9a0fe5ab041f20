"""Host milliseconds per tick spent in the named spans, summed per tick."""

from perfbench.readers import by_tick, statistic


def read(ctx, spans, stat: str = "mean"):
    per_tick = [
        sum(s["dur_ns"] for s in found.values()) / 1e6
        for found in by_tick(ctx.spans, set(spans)).values()
    ]
    return statistic(per_tick, stat)
