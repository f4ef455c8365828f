"""Percent of the remaps' collective device time that the chip's
interconnect needs for their bytes: the bytes each chip sends in one
simulation's all-to-alls (``bench/comm.py``) at the chip's peak
(``bench/ici.json``), over the collectives' device time per simulation
(``a2a_ms``). The peak, 1,600 Gbps per v5e chip, is read as egress, the
larger of its two readings, so the share can only come out low. None where
``a2a_ms`` is, off the chip, or where a ``ppermute`` runs (whose bytes
depend on which chips move)."""


def read(ctx):
    import jax

    from bench import comm

    stats = comm.remap_stats()
    if (not ctx.trace or stats is None or ctx.peaks is None
            or any(r["ppermute"] for r in stats["remaps"])):
        return None
    s = comm.collective_seconds(ctx.trace, stats)
    if not s:
        return None
    peak = comm.ici_peaks(jax.devices()[0].device_kind)["ici_bytes_per_s"]
    least = comm.sent_bytes(stats) / peak
    return 100.0 * least * ctx.window.count / s
