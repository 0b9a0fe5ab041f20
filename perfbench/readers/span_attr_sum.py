"""The sum of several attributes of a span, per span that carries them all
(a tick's four kinds of breaker transition on its ``tick.resolve``); ``None``
where no span carries them (a program from before they were recorded)."""

from perfbench.readers import statistic


def read(ctx, span: str, attrs, stat: str = "mean"):
    return statistic([sum(s["attrs"][a] for a in attrs) for s in ctx.spans
                      if s["name"] == span and all(a in s["attrs"] for a in attrs)], stat)
