"""Milliseconds from an edge of one span to an edge of another span of the
same tick, joined on the tick id both carry."""

from perfbench.readers import by_tick, statistic


def _edge(span: dict, edge: str) -> int:
    return span["t0_ns"] + (span["dur_ns"] if edge == "end" else 0)


def read(ctx, start, end, stat: str = "mean"):
    (a, a_edge), (b, b_edge) = start, end
    lags = [
        (_edge(found[b], b_edge) - _edge(found[a], a_edge)) / 1e6
        for found in by_tick(ctx.spans, {a, b}).values()
        if a in found and b in found
    ]
    return statistic(lags, stat)
