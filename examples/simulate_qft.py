"""Distributed quantum circuit simulation with explicit collectives.

Runs a 20-qubit QFT across 8 (virtual) devices through ``engine_for`` and
compares the staged backends (pjit, shard_map, host-offloaded) against the
dense reference.

    PYTHONPATH=src python examples/simulate_qft.py
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time

import numpy as np

from repro.core.generators import qft
from repro.sim.engine import engine_for
from repro.sim.measure import expectation_np, marginal_np, simulate_and_measure
from repro.sim.statevector import fidelity, simulate


def timed(name, fn):
    t0 = time.time()
    out = fn()
    out = np.asarray(out)
    print(f"  {name:28s} {time.time() - t0:6.2f}s")
    return out


def main():
    n, L, R, G = 20, 17, 2, 1
    circuit = qft(n)
    pjit = engine_for(circuit, L, R, G)
    plan = pjit.plan
    print(f"qft({n}): {circuit.n_gates} gates -> {plan.n_stages} stages, "
          f"{sum(len(s.kernels) for s in plan.stages)} kernels "
          f"(2^{L} amps/shard on {1 << (R + G)} devices)")

    engines = {
        "pjit (GSPMD)": pjit,
        "shard_map": engine_for(circuit, L, R, G, plan=plan,
                                backend="shardmap"),
        "shard_map+pallas": engine_for(circuit, L, R, G, plan=plan,
                                       backend="shardmap", use_pallas=True),
        "offloaded": engine_for(circuit, L, n - L, 0, backend="offload"),
    }
    ref = np.asarray(simulate(circuit))
    outs = {name: timed(name, eng.run) for name, eng in engines.items()}

    for name, out in outs.items():
        f = fidelity(out, ref)
        print(f"  fidelity[{name}] = {f:.8f}")
        assert f > 0.9999, name

    # --- measurement API: consume the state through shots / marginals /
    # Pauli expectations instead of gathering 2^n amplitudes. The planned
    # backends measure in the final stage's layout (no closing remap).
    print("\nmeasurement (512 shots, marginal over qubits 0-2, <Z0 Z1 + 0.5*X0>):")
    obs = "Z0 Z1 + 0.5*X0"
    e_ref = expectation_np(ref, obs)
    m_ref = marginal_np(ref, (0, 1, 2))
    for backend in ("shardmap", "pjit", "offload"):
        res = simulate_and_measure(
            circuit, backend=backend, plan=plan if backend != "offload" else None,
            L=L, R=(R if backend != "offload" else n - L),
            G=(G if backend != "offload" else 0),
            shots=512, seed=0, marginals=[(0, 1, 2)], observables=obs)
        e = res.expectation(obs)  # accessor canonicalizes the key
        m = res.marginal((0, 1, 2))
        top = ", ".join(f"{b}:{c}" for b, c in res.top(3))
        print(f"  {backend:9s} <obs>={e:+.6f} (ref {e_ref:+.6f})  top: {top}")
        assert abs(e - e_ref) < 1e-4, backend
        assert np.abs(m - m_ref).max() < 1e-5, backend
    print("OK")


if __name__ == "__main__":
    main()
