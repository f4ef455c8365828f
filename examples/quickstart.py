"""Quickstart: the full Atlas pipeline on one machine in under a minute.

Builds a 12-qubit QFT circuit, partitions it hierarchically (ILP staging +
DP kernelization) and builds its staged engine in one call to
``engine_for``, simulates it, and verifies the result against the dense
reference simulator.

    PYTHONPATH=src python examples/quickstart.py
"""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core.generators import qft
from repro.sim.engine import engine_for
from repro.sim.statevector import fidelity, simulate


def main():
    n = 12
    circuit = qft(n)
    print(f"qft({n}): {circuit.n_gates} gates")

    # Hierarchical partitioning for a (virtual) 1-pod machine with
    # 2^2 = 4 accelerators (R=2) x 2 pods (G=1), 2^9 amplitudes per shard.
    engine = engine_for(circuit, L=n - 3, R=2, G=1)
    plan = engine.plan
    print(f"staging: {plan.n_stages} stages "
          f"(ILP objective = {plan.staging_objective} qubit moves)")
    for i, st in enumerate(plan.stages):
        kinds = {0: "fusion", 1: "shm", 2: "insular"}
        ks = ", ".join(f"{kinds[k.kind]}({k.n_qubits}q x{len(k.gate_ids)}g)"
                       for k in st.kernels)
        print(f"  stage {i}: {len(st.gate_ids)} gates -> {ks}")
    print(f"modeled kernel cost: {plan.total_kernel_cost:,.0f} us/shard")

    out = engine.run()
    ref = simulate(circuit)
    f = fidelity(out, ref)
    print(f"fidelity vs dense reference: {f:.8f}")
    assert f > 0.9999
    print("OK")


if __name__ == "__main__":
    main()
