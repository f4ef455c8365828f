"""Pure-jnp oracles for the Pallas kernels."""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax.numpy as jnp


def fused_matmul_ref(
    sre: jnp.ndarray, sim: jnp.ndarray, ure: jnp.ndarray, uim: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Apply U (planar complex [K, K]) to state rows [M, K]:
    out[m, r] = sum_c U[r, c] * s[m, c]  (i.e. s @ U^T)."""
    out_re = sre @ ure.T - sim @ uim.T
    out_im = sre @ uim.T + sim @ ure.T
    return out_re, out_im


def shm_apply_ref(
    sre: jnp.ndarray,
    sim: jnp.ndarray,
    gates: Sequence[Tuple[Tuple[int, ...], jnp.ndarray]],
    window_bits: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Apply a sequence of small gates to state rows [M, 2^a] (a = window_bits).

    ``gates``: list of (bits, mat) where ``bits`` are index-bit positions
    within the window (bit j of the matrix index binds to bits[j]) and ``mat``
    is a complex matrix [2^kg, 2^kg].
    """
    a = window_bits
    x = (sre + 1j * sim).astype(jnp.complex64)
    m = x.shape[0]
    view = x.reshape((m,) + (2,) * a)
    for bits, mat in gates:
        # the trailing a axes are the window bits, highest first
        k = len(bits)
        mat = jnp.asarray(mat, dtype=jnp.complex64)
        mat_t = mat.reshape((2,) * (2 * k))
        state_axes = [1 + (a - 1 - b) for b in bits]
        in_axes = [2 * k - 1 - j for j in range(k)]
        out = jnp.tensordot(mat_t, view, axes=(in_axes, state_axes))
        dest = [state_axes[k - 1 - i] for i in range(k)]
        view = jnp.moveaxis(out, list(range(k)), dest)
    out = view.reshape(m, 1 << a)
    return jnp.real(out), jnp.imag(out)
