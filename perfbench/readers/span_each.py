"""Milliseconds of every span of a name.  ``span_stat`` keeps one span per
tick id; a tick serves many requests, each with a span of its own."""

from perfbench.readers import statistic


def read(ctx, span: str, stat: str = "mean"):
    return statistic([s["dur_ns"] / 1e6 for s in ctx.spans if s["name"] == span], stat)
