"""EfficientSU2 ansatz with random angles and full entanglement (MQT Bench
``su2random``; Atlas, arXiv:2408.09055, Table I), built as Qiskit's
``EfficientSU2(n, entanglement="full", reps=reps)`` builds it: ``reps + 1``
rotation layers with an entangling round between each two. A rotation
layer is ``ry(theta)`` then ``rz(phi)`` on every qubit, here merged into one
``u3(theta, phi, 0) = e^{i phi / 2} rz(phi) ry(theta)`` per qubit, as the
paper's count has it; an entangling round is ``cx`` on every pair
``(i, j)``, ``i < j`` (control ``i``, target ``j``). That makes
``(reps + 1) n + reps n(n-1)/2`` gates, 1,246 at n = 28 and reps = 3. Each
layer's angles are uniform on [0, 2 pi), drawn from
``numpy.random.default_rng(seed)``: the ``ry`` angles of every qubit, then
the ``rz`` angles."""

from __future__ import annotations

import math

import numpy as np


def gates(n_qubits: int, reps: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    out = []

    def rotations():
        theta = rng.uniform(0, 2 * math.pi, n_qubits)
        phi = rng.uniform(0, 2 * math.pi, n_qubits)
        out.extend(("u3", (q,), (float(theta[q]), float(phi[q]), 0.0))
                   for q in range(n_qubits))

    for _ in range(reps):
        rotations()
        out.extend(("cx", (j, i), ()) for i in range(n_qubits)
                   for j in range(i + 1, n_qubits))
    rotations()
    return out
