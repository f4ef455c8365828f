"""Quantum Fourier transform without the final swaps (MQT Bench ``qft``;
Atlas, arXiv:2408.09055, Table I): ``n + n(n-1)/2`` gates, an ``h`` on each
qubit followed by controlled phases ``cp(pi / 2^(i-j))``."""

from __future__ import annotations

import math


def gates(n_qubits: int) -> list:
    out = []
    for i in range(n_qubits - 1, -1, -1):
        out.append(("h", (i,), ()))
        for j in range(i - 1, -1, -1):
            out.append(("cp", (j, i), (math.pi / (2 ** (i - j)),)))
    return out
