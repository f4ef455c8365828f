"""Device milliseconds per simulation in the Pallas kernels (the ops that
XLA runs as ``tpu_custom_call``), from the traced window."""


def read(ctx):
    tr = ctx.trace
    if not tr or not tr["kernel_calls"]:
        return None
    return tr["kernel_s"] / ctx.window.count * 1e3
