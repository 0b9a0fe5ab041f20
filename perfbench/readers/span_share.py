"""Per cent of the window that lay under spans of a name (each cut at the
window's close).  ``0.0`` where spans were recorded and none bears the name:
a span that is recorded only when something happens, as a collector's pause
is, reads as no time, not as a metric done away with.  ``None`` where no
span at all was recorded (an untraced run)."""


def read(ctx, span: str):
    if not ctx.spans:
        return None
    win = ctx.window
    under = sum(min(s["t0_ns"] + s["dur_ns"], win.close_ns) - s["t0_ns"]
                for s in ctx.spans if s["name"] == span)
    return 100.0 * under / (win.close_ns - win.open_ns)
