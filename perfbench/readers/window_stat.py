"""A number the load generator took itself, by the host's clock."""

from perfbench.readers import statistic


def read(ctx, stat: str, field: str = ""):
    if stat == "setup_s":
        return ctx.setup_s
    if stat == "rate":  # all the work of the window over all its time
        return getattr(ctx.window, field) / ctx.window.seconds
    return statistic(getattr(ctx.window, field), stat)
