"""Device milliseconds per simulation in every op that is not a Pallas
kernel: routing transposes, planar copies, diagonals, remaps, the input's
making and the logical-order reshape, from the traced window."""


def read(ctx):
    tr = ctx.trace
    if not tr:
        return None
    return tr["other_s"] / ctx.window.count * 1e3
