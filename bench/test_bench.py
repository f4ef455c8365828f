"""CPU self-checks of the benchmark's yardstick.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/test_bench.py

They check the arithmetic the harness reports (work counts, the trace
reduction on a recorded chip trace, the metric readers), that a cell's files
are found by name, that a run on a CPU exits non-zero with no result, and
that the check refuses the control and each fault of the timed path a
one-chip state cell can have. Small sizes only (n = 10 to 14); no number here
is a device measurement.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["REPRO_CALIBRATION"] = "off"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import devtrace, work  # noqa: E402
from bench.compare import judge, passed  # noqa: E402
from bench.drivers import Window  # noqa: E402
from types import SimpleNamespace  # noqa: E402

from bench.harness import Cell, Spans, run_cell, _metric  # noqa: E402
from bench.systems.pjit import System  # noqa: E402

CELLS = ("qft28.state", "su2random28.state")


def small(name: str, n: int) -> Cell:
    """The cell at ``n`` qubits (one stage, L = n)."""
    cell = Cell(name)
    cell.config["circuit"]["n_qubits"] = n
    cell.config["engine"]["L"] = n
    return cell


# ------------------------------------------------------------- by name


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_found_by_name(name):
    cell = Cell(name)
    assert cell.traffic["driver"] == "closed_loop"
    assert len(cell.gates()) == cell.config["gates"]
    for m in cell.metrics(False) + cell.metrics(True):
        assert os.path.isfile(os.path.join(HERE, "metrics", m["name"] + ".py"))
    assert {m["name"] for m in cell.metrics(False)} >= {"setup_s", "sim_s"}


def test_qft_gate_list_matches_the_simulators_generator():
    from repro.core import generators as gen

    assert small("qft28.state", 12).gates() == [
        (g.name, tuple(g.qubits), tuple(g.params)) for g in gen.qft(12).gates]


def test_su2random_is_efficient_su2():
    """EfficientSU2, full entanglement, reps 3: four rotation layers (one
    u3 per qubit) with a full round of cx between each two."""
    n = 12
    gates = small("su2random28.state", n).gates()
    pairs = [("cx", (j, i), ()) for i in range(n) for j in range(i + 1, n)]
    layer = n + len(pairs)
    assert len(gates) == 4 * n + 3 * len(pairs)
    for r in range(4):
        rot = gates[r * layer:r * layer + n]
        assert [(g[0], g[1]) for g in rot] == [("u3", (q,)) for q in range(n)]
        assert all(0 <= t < 2 * np.pi and 0 <= p < 2 * np.pi and lam == 0.0
                   for _, _, (t, p, lam) in rot)
        if r < 3:
            assert gates[r * layer + n:(r + 1) * layer] == pairs
    # every layer draws fresh angles
    assert len({g[2] for g in gates if g[0] == "u3"}) == 4 * n


def test_system_found_by_backend():
    cell = small("qft28.state", 8)
    assert isinstance(cell.system(cell.gates(), Spans()), System)
    cell.config["engine"]["backend"] = "no_such_backend"
    with pytest.raises(ModuleNotFoundError):
        cell.system(cell.gates(), Spans())


def test_inputs_follow_the_seed():
    import importlib

    class Fake:
        n = 28

    loop = importlib.import_module("bench.drivers.closed_loop")
    a = [loop.Loop(Fake, {"initial_state": "basis"}, 2**31 + 5).next_x() for _ in range(2)]
    b = loop.Loop(Fake, {"initial_state": "basis"}, 2**31 + 6).next_x()
    assert a[0] == a[1] != b and 0 <= b < 2**28


# ---------------------------------------------------------- work counts


def test_work_counts_qft10():
    cell = small("qft28.state", 10)
    calls = cell.system(cell.gates(), Spans()).kernel_calls()
    # one shm group: h on each of the 10 qubits (dense, k = 1: 8 * 2^10 * 2
    # flops each) and 9 folded cp diagonals (6 * 2^10 each); one read and
    # one write of 2^10 complex64 amplitudes
    assert calls == [{"kind": "shm", "bytes": 16 * 1024,
                      "flops": 10 * 8 * 1024 * 2 + 9 * 6 * 1024}]


def test_work_counts_su2random10():
    cell = small("su2random28.state", 10)
    calls = cell.system(cell.gates(), Spans()).kernel_calls()
    # one shm group over all 10 qubits: the 4 * 10 u3 gates (dense, k = 1:
    # 8 * 2^10 * 2 flops each) and the 3 * 45 cx gates (dense, k = 2:
    # 8 * 2^10 * 4 each)
    assert calls == [{"kind": "shm", "bytes": 16384,
                      "flops": 40 * 8 * 1024 * 2 + 135 * 8 * 1024 * 4}]


def test_least_time_and_peaks():
    peak = work.peaks("TPU v5 lite")
    assert peak == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    # a 7-qubit dense gate over 2^28 amplitudes: 64 flop/B, under the ridge
    t, bound = work.least_time({"bytes": 16 << 28, "flops": 8 * 128 << 28}, peak)
    assert bound == "bytes" and t == pytest.approx((16 << 28) / 819e9)
    with pytest.raises(KeyError):
        work.peaks("TPU v9")


# ------------------------------------------------------- trace reduction


def test_reduce_synthetic():
    ms = 1e6  # ns
    tr = {"host_spans": [["prepare", 0, 1 * ms], ["dispatch", 1 * ms, 3 * ms],
                         ["wait", 3 * ms, 10 * ms]],
          "device_ops": {"/device:TPU:0": [
              ["fusion.1", 2 * ms, 2 * ms, 0],   # 2-4
              ["custom-call.1", 3 * ms, 3 * ms, 1],  # 3-6 (overlaps)
              ["fusion.2", 8 * ms, 1 * ms, 0],   # 8-9
              ["fusion.3", 20 * ms, 1 * ms, 0],  # outside the window
          ]}}
    r = devtrace.reduce(tr)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.005)  # [2, 6] and [8, 9]
    assert r["kernel_s"] == pytest.approx(0.003)
    assert r["other_s"] == pytest.approx(0.003)
    assert r["kernel_calls"] == 1
    # gaps [0, 2] (mid 1: dispatch starts), [6, 8] and [9, 10] (wait)
    assert dict(r["idle_gaps"]) == pytest.approx({"dispatch": 0.002, "wait": 0.003})
    assert r["device_ops"][0] == ["custom-call.1", pytest.approx(0.003)]


def test_reduce_recorded_chip_trace():
    """A trimmed trace of ``qft28.state`` recorded on one v5e: three
    simulations' ops and spans (its provenance is the expected file's
    ``about``)."""
    tr = devtrace.read(os.path.join(HERE, "testdata", "qft28_trace.json.gz"))
    with open(os.path.join(HERE, "testdata", "qft28_trace.expected.json")) as f:
        want = json.load(f)
    r = devtrace.reduce(tr)
    for k in ("window_s", "busy_s", "kernel_s", "other_s", "kernel_calls"):
        assert r[k] == pytest.approx(want[k], rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["kernel_calls"] == 3 * want["simulations"]
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


# ------------------------------------------------------- metric readers


def test_metric_arithmetic():
    w = Window(start=10.0, end=12.0, durations=[0.1] * 20)
    calls = [{"kind": "shm", "bytes": 16 << 28, "flops": 0}] * 3
    tr = {"window_s": 2.0, "busy_s": 1.5, "kernel_s": 0.06, "other_s": 1.0,
          "kernel_calls": 3 * w.count}
    ctx = SimpleNamespace(setup_s=42.0, spans={"plan": 1.0, "build": 2.0, "warmup": 3.0},
                  window=w, trace=tr, kernel_calls=calls,
                  kernel_counts={"fused": 0, "shm": 3, "interpreted": 0},
                  peaks=work.peaks("TPU v5 lite"))
    assert _metric("sim_s", ctx) == pytest.approx(2.0 / 20)
    assert _metric("setup_s", ctx) == 42.0
    assert [_metric(m, ctx) for m in ("plan_s", "build_s", "warmup_s")] == [1.0, 2.0, 3.0]
    assert _metric("idle_share", ctx) == pytest.approx(25.0)
    assert _metric("kernel_ms", ctx) == pytest.approx(3.0)
    assert _metric("xla_op_ms", ctx) == pytest.approx(50.0)
    least = 3 * (16 << 28) / 819e9  # per simulation
    assert _metric("kernel_roofline", ctx) == pytest.approx(100 * least / 0.003)
    # the plan's calls disagree with the trace: the roofline stays silent
    ctx.trace = dict(tr, kernel_calls=2 * w.count)
    assert _metric("kernel_roofline", ctx) is None
    ctx.trace = None
    assert all(_metric(m, ctx) is None
               for m in ("kernel_ms", "kernel_roofline", "xla_op_ms", "idle_share"))


# ------------------------------------------------------------- the run


def test_run_without_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "qft28.state",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def _run(cell):
    import time

    return run_cell(cell, 2**31 + 17, 0.5, False, time.perf_counter(),
                    log=lambda *a, **k: None)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """On a CPU every Pallas call is interpreted; the runs that stand for a
    sound chip run report the counts a chip gives: none interpreted."""
    from repro.kernels import ops as kops

    orig = kops.kernel_call_counts
    monkeypatch.setattr(kops, "kernel_call_counts",
                        lambda: dict(orig(), interpreted=0))


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(compiled_kernels, name):
    res = _run(small(name, 10))
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) >= {"sim_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["max_err"]["value"] <= res["checks"]["max_err"]["limit"]


def _broken(monkeypatch, fault):
    orig = System.run

    def run(self, psi0):
        if fault == "unchanged":  # the step returns its state unchanged
            return psi0.reshape(-1)
        out = orig(self, psi0)
        # an answer altered where it is produced: one amplitude negated
        return out.at[12345 % out.size].multiply(-1)

    monkeypatch.setattr(System, "run", run)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_fault_is_not_correct(compiled_kernels, monkeypatch, name, fault):
    _broken(monkeypatch, fault)
    res = _run(small(name, 10))
    assert not res["correct"] and res["failed"] >= 1


def test_interpreted_kernels_are_not_correct():
    """On a CPU every Pallas call is interpreted: the run fails on that
    check alone."""
    res = _run(small("qft28.state", 10))
    assert not res["correct"]
    assert res["checks"]["interpreted_kernels"]["value"] > 0
    assert passed({k: v for k, v in res["checks"].items()
                   if k != "interpreted_kernels"})


def test_degraded_engine_is_not_correct(compiled_kernels, monkeypatch):
    orig = System.__init__

    def init(self, *a, **k):
        orig(self, *a, **k)
        self.engine.provenance["degraded"] = True

    monkeypatch.setattr(System, "__init__", init)
    assert not _run(small("qft28.state", 10))["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limits(name):
    """The reference at three bf16 passes per product, in the simulator's
    place, fails the cell's limits; the simulator passes them."""
    from bench.control import readings

    cell = small(name, 12)
    out = readings(cell, [1], [2, 3], emit=lambda *a, **k: None)
    limits = cell.config["limits"]
    assert passed(judge(out["program"][0], limits))
    assert not any(passed(judge(r, limits)) for r in out["control"])


def test_references_agree_with_the_simulators_oracle():
    from repro.core.circuit import Circuit
    from repro.sim.statevector import simulate_np

    from bench.references import qft_closed_form, statevector

    n, x = 12, 2741
    psi0 = np.zeros(1 << n, np.complex128)
    psi0[x] = 1
    for name in CELLS:
        gates = small(name, n).gates()
        circ = Circuit(n)
        for g, qubits, params in gates:
            circ.add(g, *qubits, params=params)
        want = simulate_np(circ, psi0)
        got = np.asarray(statevector.state(gates, n, x)).reshape(-1)
        assert np.abs(got - want).max() < 1e-6
        if name == "qft28.state":
            got = np.asarray(qft_closed_form.state(None, n, x)).reshape(-1)
            assert np.abs(got - want).max() < 1e-6
