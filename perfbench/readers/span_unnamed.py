"""Per cent of the tick thread's wall time, between the first and the last
``tick.assemble`` start of the window, that lies under no ``tick.*`` span."""

from perfbench.timeline import unnamed_share


def read(ctx):
    return unnamed_share(ctx.spans)
