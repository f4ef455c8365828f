"""Device milliseconds per simulation in the collectives of the remaps
(the XLA ``all-to-all`` and ``collective-permute`` ops), averaged over the
chips, from the traced window (``bench/comm.py``). None unless the trace's
ten largest ops hold every collective that the simulator's record of its
remaps says a simulation runs."""


def read(ctx):
    from bench import comm

    stats = comm.remap_stats()
    if not ctx.trace or stats is None:
        return None
    s = comm.collective_seconds(ctx.trace, stats)
    return None if s is None else s / ctx.window.count * 1e3
