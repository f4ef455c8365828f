"""The closed form of the QFT without final swaps on a basis state:
``QFT |x> = sum_y exp(2 pi i x rev(y) / 2^n) / 2^(n/2) |y>``, where ``rev``
reverses the n bits of y. Computed on the device in one pass; the phase
``x * rev(y)`` is exact modulo 2^n in uint32 arithmetic."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import statevector

LANES = statevector.LANES


def bit_reverse(y, n: int):
    """n-bit reversal of uint32 ``y`` (traceable)."""
    y = y.astype(jnp.uint32)
    for s, m in ((1, 0x55555555), (2, 0x33333333), (4, 0x0F0F0F0F),
                 (8, 0x00FF00FF)):
        y = ((y >> s) & m) | ((y & m) << s)
    y = (y >> 16) | (y << 16)
    return y >> (32 - n)


@partial(jax.jit, static_argnames=("n_qubits",))
def _closed_form(x, *, n_qubits: int):
    shape = (1 << (n_qubits - statevector.LANE_BITS), LANES)
    y = ((jax.lax.broadcasted_iota(jnp.uint32, shape, 0) << statevector.LANE_BITS)
         | jax.lax.broadcasted_iota(jnp.uint32, shape, 1))
    ph = (x.astype(jnp.uint32) * bit_reverse(y, n_qubits)) & jnp.uint32(
        (1 << n_qubits) - 1)
    ang = ph.astype(jnp.float32) * jnp.float32(2 * 3.141592653589793 / (1 << n_qubits))
    return jax.lax.complex(jnp.cos(ang), jnp.sin(ang)) * jnp.float32(
        2.0 ** (-n_qubits / 2))


def state(gates, n_qubits: int, x: int, precision: str = "highest"):
    """``gates`` must be the QFT of ``circuits/qft.py``; only the gate-by-gate
    reference has a lower-precision form."""
    if precision != "highest":
        return statevector.state(gates, n_qubits, x, precision)
    return _closed_form(jnp.uint32(x), n_qubits=n_qubits)
