"""Device milliseconds per tick from the profiler's trace: ``busy`` is the
union of the device-operation intervals inside one execution of the tick
program, ``kernels`` the summed durations of its Mosaic custom calls."""

from perfbench.readers import statistic


def read(ctx, what: str, stat: str = "mean"):
    if ctx.trace is None:
        return None
    return statistic(getattr(ctx.trace, f"tick_{what}_ms"), stat)
