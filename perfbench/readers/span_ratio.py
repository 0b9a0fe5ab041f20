"""One attribute of the named spans as a share (per cent) of another, both
summed over the window; ``None`` where no span carries both, or the whole is
zero (a program from before the attributes were recorded)."""


def read(ctx, span: str, part: str, whole: str):
    both = [s["attrs"] for s in ctx.spans
            if s["name"] == span and part in s["attrs"] and whole in s["attrs"]]
    total = sum(a[whole] for a in both)
    return 100.0 * sum(a[part] for a in both) / total if total else None
