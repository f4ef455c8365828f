"""Host seconds of set-up that JAX spent tracing functions to jaxprs and
lowering them to MLIR, Pallas kernels to Mosaic included: the simulator's
record of JAX's compile events (``repro.sim.trace.compile_seconds``,
phase ``trace``) up to the window's start. None for a simulator that keeps
no such record."""


def read(ctx):
    try:
        from repro.sim import trace
    except ImportError:
        return None
    return trace.compile_seconds(until=ctx.window.start)["trace"]
