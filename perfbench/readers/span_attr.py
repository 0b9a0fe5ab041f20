"""A number every span of a name carries as an attribute, times ``scale``
(``1e-6`` turns an attribute in nanoseconds into milliseconds)."""

from perfbench.readers import statistic


def read(ctx, span: str, attr: str, stat: str = "mean", scale: float = 1.0):
    return statistic([s["attrs"][attr] * scale for s in ctx.spans
                      if s["name"] == span and attr in s["attrs"]], stat)
