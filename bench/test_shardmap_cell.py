"""CPU self-checks of the four-chip cell ``qft30.shardmap.x4``.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/test_shardmap_cell.py

The cell's files are found by name; at n = 12 (L = 10, R = 2) over four
virtual CPU devices, each in a subprocess of its own, a sound run reads
``correct``, each fault of the timed path and the control do not, and the
sharded reference agrees with the one-device closed form and the
simulator's oracle. The readers of ``a2a_ms`` and ``a2a_roofline`` are
checked on a synthetic reduced trace. No number here is a device
measurement.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["REPRO_CALIBRATION"] = "off"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import comm  # noqa: E402
from bench.drivers import Window  # noqa: E402
from bench.harness import Cell, _metric  # noqa: E402

CELL = "qft30.shardmap.x4"

# the child's prelude: the cell at 12 qubits, one run of it, and the
# counts a chip gives (no kernel interpreted) for the runs that stand for
# a sound chip run
PRELUDE = f"""
import json, sys, time
sys.path[:0] = [{ROOT!r}, {os.path.join(ROOT, "src")!r}]
from bench.harness import Cell, run_cell
from bench.systems.shardmap import System
from repro.kernels import ops as kops

def small(n=12):
    cell = Cell({CELL!r})
    cell.config["circuit"]["n_qubits"] = n
    cell.config["engine"]["L"] = n - 2
    return cell

def run(cell):
    return run_cell(cell, 2**31 + 17, 0.5, False, time.perf_counter(),
                    log=lambda *a, **k: None)

def compiled_kernels():
    orig = kops.kernel_call_counts
    kops.kernel_call_counts = lambda: dict(orig(), interpreted=0)
"""


def on_four_devices(code: str) -> dict:
    """Run ``code`` after :data:`PRELUDE` over four virtual CPU devices;
    its last stdout line is JSON."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_CALIBRATION="off",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", PRELUDE + textwrap.dedent(code)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


# ------------------------------------------------------------- by name


def test_cell_files_found_by_name():
    cell = Cell(CELL)
    assert cell.chips == 4 and cell.traffic["driver"] == "closed_loop"
    assert len(cell.gates()) == cell.config["gates"] == 30 + 30 * 29 // 2
    e = cell.config["engine"]
    assert (e["L"], e["R"], e["G"], e["backend"], e["use_pallas"]) == (
        28, 2, 0, "shardmap", True)
    assert cell.config["reference"] == cell.config["control"] == "qft_closed_form_x4"
    for m in cell.metrics(False) + cell.metrics(True):
        assert os.path.isfile(os.path.join(HERE, "metrics", m["name"] + ".py"))
    assert {m["name"] for m in cell.metrics(False)} == {"setup_s", "sim_s"}
    assert {m["name"] for m in cell.metrics(True)} == {"a2a_ms", "a2a_roofline"}
    for kind, name in (("systems", "shardmap"), ("references", "qft_closed_form_x4")):
        assert os.path.isfile(os.path.join(HERE, kind, name + ".py"))


# ------------------------------------------------------------- the run


def test_sound_run_is_correct():
    res = on_four_devices("""
        compiled_kernels()
        res = run(small())
        res["remaps"] = kops.remap_stats()
        print(json.dumps(res))
    """)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"sim_s", "setup_s"}
    # qft(12) over L = 10: two all-to-alls of two bits, then one of one bit
    # and a ppermute
    assert [(r["slot"], r["m"], r["ppermute"]) for r in res["remaps"]["remaps"]] == [
        ("init", 2, False), (0, 2, False), ("final", 1, True)]


def test_interpreted_kernels_are_not_correct():
    res = on_four_devices("print(json.dumps(run(small())))")
    assert not res["correct"]
    assert res["checks"]["interpreted_kernels"]["value"] > 0
    assert all(c["value"] <= c["limit"] for k, c in res["checks"].items()
               if k != "interpreted_kernels")


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_fault_is_not_correct(fault):
    res = on_four_devices(f"""
        compiled_kernels()
        orig = System.run

        def broken(self, psi0):
            if {fault!r} == "unchanged":  # the step returns its state unchanged
                return psi0
            out = orig(self, psi0)
            # an answer altered where it is produced: one amplitude negated
            return out.at[12345 % out.size].multiply(-1)

        System.run = broken
        print(json.dumps(run(small())))
    """)
    assert not res["correct"] and res["failed"] >= 1


def test_control_fails_the_limits():
    """The sharded gate-by-gate reference at three bf16 passes per product,
    in the simulator's place, fails both of the cell's limits; the
    simulator passes them."""
    out = on_four_devices("""
        from bench.control import readings
        print(json.dumps(readings(small(), [1], [2, 3], emit=lambda *a, **k: None)))
    """)
    limits = Cell(CELL).config["limits"]
    assert all(out["program"][0][k] <= v for k, v in limits.items())
    assert all(r[k] > v for r in out["control"] for k, v in limits.items())


def test_sharded_reference_agrees_with_the_oracles():
    out = on_four_devices("""
        import numpy as np
        from bench.circuits import qft
        from bench.references import qft_closed_form, qft_closed_form_x4 as x4
        from repro.core.circuit import Circuit
        from repro.sim.statevector import simulate_np

        n, x = 12, 2741
        gates = qft.gates(n)
        circ = Circuit(n)
        for g, qubits, params in gates:
            circ.add(g, *qubits, params=params)
        psi0 = np.zeros(1 << n, np.complex128)
        psi0[x] = 1
        want = simulate_np(circ, psi0)
        got = x4.state(gates, n, x)
        one = np.asarray(qft_closed_form.state(gates, n, x))
        res = {
            "shape": list(got.shape),
            "blocks": sorted([s.device.id, s.index[0].start, s.data.shape[0]]
                             for s in got.addressable_shards),
            "vs_closed_form": float(np.abs(np.asarray(got) - one).max()),
            "vs_oracle": float(np.abs(np.asarray(got).reshape(-1) - want).max()),
            "passes_vs_oracle": float(np.abs(
                np.asarray(x4.passes(gates, n, x, "highest")).reshape(-1) - want).max()),
        }
        print(json.dumps(res))
    """)
    assert out["shape"] == [32, 128]
    # block d (rows 8d to 8d + 7) on the d-th device
    assert out["blocks"] == [[d, 8 * d, 8] for d in range(4)]
    assert out["vs_closed_form"] == 0.0
    assert out["vs_oracle"] < 1e-6 and out["passes_vs_oracle"] < 1e-6


# ------------------------------------------------------- metric readers


def _stats(ppermute=False):
    rec = [{"slot": s, "m": m, "L": 28, "dtype": "complex64", "itemsize": 8,
            "ppermute": ppermute and s == "final"}
           for s, m in (("init", 2), (0, 2), ("final", 0))]
    return {"programs": 1, "remaps": rec}


def test_a2a_readers(monkeypatch):
    w = Window(start=0.0, end=10.0, durations=[0.5] * 20)
    ops = [["%shm_group.3 tpu_custom_call", 4.0], ["%all_to_all.12", 0.2],
           ["%all_to_all.13", 0.2], ["%fusion.7", 0.3], ["%all_to_all.14", 0.1],
           ["%all_to_all.15", 0.1]]
    ctx = SimpleNamespace(window=w, trace={"device_ops": ops},
                          peaks={"flops_per_s": 1.0})
    monkeypatch.setattr(comm, "remap_stats", _stats)
    monkeypatch.setattr(comm, "ici_peaks", lambda kind: {"ici_bytes_per_s": 200e9})
    # two all-to-alls of two bits: each chip sends 3/4 of its 2 GiB twice,
    # in four f32 ops (two planes each)
    assert comm.device_ops(_stats()) == 4
    assert comm.sent_bytes(_stats()) == 2 * 3 * (8 << 28) // 4
    assert _metric("a2a_ms", ctx) == pytest.approx(0.6 / 20 * 1e3)
    least = 2 * 0.75 * (8 << 28) / 200e9
    assert _metric("a2a_roofline", ctx) == pytest.approx(100 * least / (0.6 / 20))
    # a collective missing from the trace's ops: both stay silent
    ctx.trace = {"device_ops": ops[:-1]}
    assert _metric("a2a_ms", ctx) is None and _metric("a2a_roofline", ctx) is None
    # a ppermute: two ops more; its bytes depend on which chips move
    monkeypatch.setattr(comm, "remap_stats", lambda: _stats(ppermute=True))
    ctx.trace = {"device_ops": ops + [["%collective-permute.2", 0.1],
                                      ["%collective-permute.3", 0.1]]}
    assert _metric("a2a_ms", ctx) == pytest.approx(0.8 / 20 * 1e3)
    assert _metric("a2a_roofline", ctx) is None
    # off the chip, or with no record of the program's remaps
    ctx.peaks = None
    assert _metric("a2a_roofline", ctx) is None
    monkeypatch.setattr(comm, "remap_stats", lambda: None)
    assert _metric("a2a_ms", ctx) is None


def test_byte_count_agrees_with_the_simulators_record():
    from repro.kernels import ops as kops

    for m in range(4):
        rec = kops.remap_record("init", m, 28, "complex64", False)
        assert comm.a2a_bytes(m, 28, 8) == rec["a2a_bytes"]
    assert comm.a2a_bytes(2, 28, 8) == 1610612736  # 1.61 GB
