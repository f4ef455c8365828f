"""Host seconds of set-up in the compiler's peephole pass: the simulator's
span ``build/compile_plan/peephole`` in its process table
(``repro.sim.trace``; every pass of the engine's build, summed). None for a
simulator that records no spans."""


def read(ctx):
    try:
        from repro.sim import trace
    except ImportError:
        return None
    span = trace.snapshot().get("build/compile_plan/peephole")
    return span["total_s"] if span else None
