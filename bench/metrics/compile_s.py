"""Host seconds of set-up in the backend's compiles, or in their load from
the persistent compilation cache: the simulator's record of JAX's compile
events (``repro.sim.trace.compile_seconds``, phase ``backend``) up to the
window's start. None for a simulator that keeps no such record."""


def read(ctx):
    try:
        from repro.sim import trace
    except ImportError:
        return None
    return trace.compile_seconds(until=ctx.window.start)["backend"]
