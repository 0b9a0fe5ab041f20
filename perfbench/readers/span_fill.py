"""Items per tick as a share of the tick's width, from the item counts the
named span carries as attributes."""

from perfbench.readers import statistic


def read(ctx, span: str, attrs, stat: str = "mean"):
    fills = [
        100.0 * sum(s["attrs"].get(a, 0) for a in attrs) / ctx.batch
        for s in ctx.spans
        if s["name"] == span
    ]
    return statistic(fills, stat)
