"""Percent of the Pallas kernels' device time that the chip's roofline
needs for their work: the sum over one simulation's kernel calls of the
least time (``work.least_time``: bytes over HBM bandwidth, or flops over
peak, whichever is larger) over the kernels' device time per simulation.

Read only where the plan's count of kernel calls agrees with the
simulator's call-site counters and with the calls in the trace."""


def read(ctx):
    from bench.work import least_time

    tr = ctx.trace
    calls = ctx.kernel_calls
    if not tr or not tr["kernel_s"] or not calls or ctx.peaks is None:
        return None
    sites = ctx.kernel_counts["fused"] + ctx.kernel_counts["shm"]
    per_sim = tr["kernel_calls"] / ctx.window.count
    if sites != len(calls) or abs(per_sim - len(calls)) > 0.5:
        return None
    least = sum(least_time(c, ctx.peaks)[0] for c in calls)
    return 100.0 * least * ctx.window.count / tr["kernel_s"]
