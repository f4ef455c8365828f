"""The profiler trace of a run's window, and its reduction to numbers.

``capture`` records the window with JAX's profiler; ``load`` reads the
``.xplane.pb`` it wrote into a plain dict (small, so a trimmed copy is kept
in ``testdata/`` and checked); ``reduce`` turns that dict into the device's
busy time, its idle gaps labelled by what the host was doing, and the
device time of the Pallas kernels against all other ops.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re

# the benchmark's own host spans around each simulation of the window
HOST_SPANS = ("prepare", "dispatch", "wait")


@contextlib.contextmanager
def capture(log_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def is_kernel(hlo: str) -> bool:
    """A Pallas (Mosaic) kernel: XLA calls it as a ``tpu_custom_call``
    (other custom calls, such as XLA's own complex packing, are not)."""
    m = _TARGET.search(hlo)
    return bool(m) and m.group(1) == "tpu_custom_call"


def short_name(hlo: str) -> str:
    """``%name`` of an op from its HLO text (a TPU trace names each op by
    its whole instruction), with a custom call's target."""
    head = hlo.split(" = ", 1)[0]
    m = _TARGET.search(hlo)
    return f"{head} {m.group(1)}" if m else head


def load(log_dir: str) -> dict:
    """``{"device_ops": [[name, start_ns, dur_ns, kernel], ...] per device,
    "host_spans": [[name, start_ns, end_ns], ...]}`` from the newest trace
    under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                devices.setdefault(plane.name, []).extend(
                    [short_name(e.name), float(e.start_ns),
                     float(e.duration_ns), int(is_kernel(e.name))]
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns),
                             float(e.start_ns + e.duration_ns)]
                            for e in line.events if e.name in HOST_SPANS)
    return {"device_ops": devices, "host_spans": sorted(host, key=lambda s: s[1])}


def read(path: str) -> dict:
    """A ``load`` result stored as gzipped JSON (``testdata/``)."""
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(t: float, spans) -> str:
    """The host span in progress at ``t`` (the latest started), or
    ``host`` when none is."""
    best = None
    for name, s, e in spans:
        if s > t:
            break
        if e >= t:
            best = name
    return best or "host"


def reduce(tr: dict) -> dict:
    """Busy and idle time of each device over the window, averaged over the
    devices: the window runs from the first host span's start to the last
    one's end (the whole of the timed loop). Returns seconds."""
    spans = tr["host_spans"]
    if not spans or not tr["device_ops"]:
        return {}
    w0, w1 = spans[0][1], max(e for _, _, e in spans)
    per = []
    for ops in tr["device_ops"].values():
        ops = [o for o in ops if o[1] < w1 and o[1] + o[2] > w0]
        if not ops:
            continue
        busy = _union([max(s, w0), min(s + d, w1)] for _, s, d, _k in ops)
        gaps, prev = [], w0
        for s, e in busy + [[w1, w1]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        by_label, by_op = {}, {}
        for s, e in gaps:
            lab = _label((s + e) / 2, spans)
            by_label[lab] = by_label.get(lab, 0.0) + (e - s)
        for name, _s, d, _k in ops:
            by_op[name] = by_op.get(name, 0.0) + d
        per.append({
            "busy": sum(e - s for s, e in busy),
            "kernel": sum(d for _n, _s, d, k in ops if k),
            "other": sum(d for _n, _s, d, k in ops if not k),
            "kernel_calls": sum(k for *_x, k in ops),
            "by_label": by_label, "by_op": by_op,
        })
    if not per:
        return {}
    m = len(per)

    def mean(key):
        return sum(p[key] for p in per) / m / 1e9

    def top(key):
        tot = {}
        for p in per:
            for k, v in p[key].items():
                tot[k] = tot.get(k, 0.0) + v / m / 1e9
        return sorted(([k, v] for k, v in tot.items()), key=lambda kv: -kv[1])[:10]

    return {"window_s": (w1 - w0) / 1e9, "busy_s": mean("busy"),
            "kernel_s": mean("kernel"), "other_s": mean("other"),
            "kernel_calls": sum(p["kernel_calls"] for p in per) / m,
            "device_ops": top("by_op"), "idle_gaps": top("by_label")}
