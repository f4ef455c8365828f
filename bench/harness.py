"""One run of one benchmark cell: set-up, the timed window, the check
against the plain reference, and the result line.

Everything particular to a cell is data or a file found by name (see
``bench/__init__.py``); this module only walks through them.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _check_name(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"bad name {name!r}")
    return name


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_file_module(path: str):
    """Import the module at ``path`` (metric names may hold dots)."""
    name = "bench_file_" + re.sub(r"\W", "_", os.path.relpath(path, HERE))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(kind: str, name: str, ext: str) -> str:
    path = os.path.join(HERE, kind, _check_name(name) + ext)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {os.path.relpath(path, ROOT)}")
    return path


class Cell:
    """A workload of ``BENCHMARK.json`` with its configuration and traffic."""

    def __init__(self, name: str, bench: dict = None):
        bench = bench or load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.bench = bench
        self.spec = cells[name]
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = load_json(os.path.join(ROOT, configs[self.spec["config"]]["file"]))
        self.traffic = load_json(find("traffic", self.spec["traffic"], ".json"))
        self.chips = int(self.spec["chips"])

    def metrics(self, per_layer: bool) -> list:
        """The metric entries this cell reports: end-to-end ones, or (with
        ``per_layer``) the per-layer ones."""
        mine = [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]
        if not per_layer:
            return mine
        moved = {m["name"] for m in mine}
        return [m for m in self.bench["per_layer"]
                if self.name in m.get("workloads", [self.name])
                and m["moves"] in moved]

    def gates(self) -> list:
        circ = dict(self.config["circuit"])
        family = circ.pop("family")
        mod = importlib.import_module(f"bench.circuits.{_check_name(family)}")
        return mod.gates(**circ)

    def reference(self):
        return importlib.import_module(
            f"bench.references.{_check_name(self.config['reference'])}")

    def system(self, gates: list, spans: "Spans"):
        """The simulator built as the configuration states, by the system
        module its ``engine.backend`` names (``systems/<backend>.py``)."""
        mod = importlib.import_module(
            f"bench.systems.{_check_name(self.config['engine']['backend'])}")
        return mod.System(self.config, gates, spans)


class Spans:
    """Host-clock spans of set-up (seconds, by name)."""

    def __init__(self):
        self.seconds = {}

    @contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = self.seconds.get(name, 0.0) + time.perf_counter() - t


class CompileCounter:
    """Counts JAX traces and backend compiles (a listener on JAX's
    monitoring events), so that a window holding one is caught."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.count += 1


def _device_info(devs, chips: int) -> dict:
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips, "memory_peak_bytes": peak}


def worst(readings: list) -> dict:
    """The largest reading of each number over the samples (NaN wins)."""
    return {k: max((r[k] for r in readings), key=lambda v: (v != v, v))
            for k in readings[0]} if readings else {}


def _metric(name: str, ctx):
    return load_file_module(find("metrics", name, ".py")).read(ctx)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
             *, log=print) -> dict:
    """Run ``cell`` once and return the result object (the contract's last
    line). ``t0`` is the process's start on the host clock."""
    import jax
    from repro.kernels import ops as kops

    from bench import devtrace, work
    from bench.compare import errors, judge, passed

    devs = jax.devices()
    spans = Spans()
    compiles = CompileCounter()
    gates = cell.gates()
    kops.reset_kernel_counters()
    system = cell.system(gates, spans)
    driver = importlib.import_module(
        f"bench.drivers.{_check_name(cell.traffic['driver'])}")
    loop = driver.Loop(system, cell.traffic, seed)
    with spans("warmup"):
        loop.warm_up()
    kernel_counts = kops.kernel_call_counts()
    tmp = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        setup_s = time.perf_counter() - t0
        before = compiles.count
        if trace:
            with devtrace.capture(tmp):
                window = loop.window(seconds)
            tr = devtrace.reduce(devtrace.load(tmp))
        else:
            window = loop.window(seconds)
            tr = None
        window_compiles = compiles.count - before
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    device = _device_info(devs, cell.chips)
    calls = system.kernel_calls()
    provenance = dict(system.engine.provenance)
    del system, loop.system
    gc.collect()

    # the check, once the window has closed and the program's state is freed
    ref = cell.reference()
    readings, failed = [], 0
    limits = cell.config["limits"]
    for sample in window.samples:
        want = ref.state(gates, cell.config["circuit"]["n_qubits"], sample.x)
        readings.append(errors(sample.out, want))
        del want
        failed += not passed(judge(readings[-1], limits))
    checks = judge(worst(readings), limits)
    checks["degraded"] = {"value": int(bool(provenance.get("degraded"))), "limit": 0}
    checks["window_compiles"] = {"value": window_compiles, "limit": 0}
    checks["interpreted_kernels"] = {"value": kernel_counts["interpreted"], "limit": 0}
    correct = passed(checks) and not failed and bool(window.samples)

    # what the metric readers read (see ``metrics/__init__.py``)
    ctx = SimpleNamespace(setup_s=setup_s, spans=spans.seconds, window=window,
                          trace=tr, kernel_calls=calls,
                          kernel_counts=kernel_counts,
                          peaks=(work.peaks(device["kind"])
                                 if device["platform"] == "tpu" else None))
    metrics = {}
    for m in cell.metrics(per_layer=trace):
        value = _metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct, "attempted": window.count, "failed": failed,
              "metrics": metrics, "device": device}
    if trace and tr:
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    return result
