"""Seconds per whole-circuit simulation: the window's length to the end of
its last simulation over the number it completed."""


def read(ctx):
    w = ctx.window
    return (w.end - w.start) / w.count if w.count else None
