"""CPU self-checks of the metric readers that read the simulator's own
record (``repro.sim.trace``): ``peephole_s``, ``trace_s`` and ``compile_s``.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/test_program_metrics.py

Small sizes only (n = 10); no number here is a device measurement.
"""

from __future__ import annotations

import os
import sys
import time
from types import SimpleNamespace

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["REPRO_CALIBRATION"] = "off"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.drivers import Window  # noqa: E402
from bench.harness import Cell, _metric, run_cell  # noqa: E402

METRICS = ("peephole_s", "trace_s", "compile_s")


@pytest.mark.parametrize("name", ["qft28.state", "su2random28.state"])
def test_traced_run_reports_the_programs_set_up(name):
    from repro.sim import trace

    trace.SPANS.clear()
    cell = Cell(name)
    cell.config["circuit"]["n_qubits"] = cell.config["engine"]["L"] = 10
    res = run_cell(cell, 2**31 + 17, 0.5, True, time.perf_counter(),
                   log=lambda *a, **k: None)
    got = {m: res["metrics"][m]["value"] for m in METRICS}
    assert all(v > 0 for v in got.values()), got
    spans = sum(res["metrics"][m]["value"] for m in ("plan_s", "build_s", "warmup_s"))
    assert got["trace_s"] + got["compile_s"] <= spans
    assert got["peephole_s"] <= trace.snapshot()["build"]["total_s"]


def test_compiles_after_the_window_start_are_left_out():
    import jax
    import jax.numpy as jnp

    ctx = SimpleNamespace(window=Window(start=time.perf_counter(), end=0.0))
    before = [_metric(m, ctx) for m in ("trace_s", "compile_s")]
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
    assert [_metric(m, ctx) for m in ("trace_s", "compile_s")] == before


def test_a_simulator_without_the_record_reads_none(monkeypatch):
    """An older simulator has no ``repro.sim.trace``: each reader leaves its
    metric out instead of failing."""
    import repro.sim

    monkeypatch.delattr(repro.sim, "trace", raising=False)
    monkeypatch.setitem(sys.modules, "repro.sim.trace", None)
    ctx = SimpleNamespace(window=Window(start=time.perf_counter(), end=0.0))
    assert [_metric(m, ctx) for m in METRICS] == [None] * 3
