"""Gate application primitives on dense state-vector views.

Conventions (used across repro.sim):

* flat state ``psi[2^n]``: index bit ``p`` (0 = least significant) is
  *physical* qubit ``p``;
* view ``psi.reshape((2,)*n)``: array axis ``i`` corresponds to bit ``n-1-i``;
* a gate's matrix index bit ``j`` (see repro.core.gates) binds to
  ``gate.qubits[j]``.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..kernels.layout import gather_bits, scatter_bits


def axis_of_bit(n: int, p: int) -> int:
    return n - 1 - p


def apply_matrix(psi_view: jnp.ndarray, mat: jnp.ndarray, bits: Sequence[int]) -> jnp.ndarray:
    """Apply a ``2^k x 2^k`` matrix to the view on index bits ``bits`` (bit j of
    the matrix index binds to bits[j])."""
    n = psi_view.ndim
    k = len(bits)
    mat_t = mat.reshape((2,) * (2 * k))
    # mat_t axes: (out_{k-1}..out_0, in_{k-1}..in_0)
    state_axes = [axis_of_bit(n, b) for b in bits]  # axis for gate bit j
    in_axes = [2 * k - 1 - j for j in range(k)]
    out = jnp.tensordot(mat_t, psi_view, axes=(in_axes, state_axes))
    # output axes: (out_{k-1}..out_0) + remaining state axes (orig order)
    dest = [state_axes[k - 1 - i] for i in range(k)]
    return jnp.moveaxis(out, list(range(k)), dest)


def apply_diag(psi_view: jnp.ndarray, diag: jnp.ndarray, bits: Sequence[int]) -> jnp.ndarray:
    """Elementwise multiply by ``diag[2^k]`` indexed by the values of ``bits``."""
    n = psi_view.ndim
    k = len(bits)
    d = diag.reshape((2,) * k)  # axis j' = bit bits[k-1-j'] (C-order: high first)
    shape = [1] * n
    perm_axes = [axis_of_bit(n, b) for b in bits]  # state axis for gate bit j
    # build broadcastable weight: put d's axes at the right state positions
    src = list(range(k))  # d axis i corresponds to gate bit k-1-i
    dst = [perm_axes[k - 1 - i] for i in range(k)]
    w = jnp.moveaxis(d.reshape((2,) * k + (1,) * (n - k)), src, dst)
    return psi_view * w


def embed_matrix(mat: np.ndarray, positions: Sequence[int], k: int) -> np.ndarray:
    """Embed a matrix over ``len(positions)`` bits into a ``2^k``-bit space.

    ``positions[j]`` is the target bit (within the k-bit space) for matrix
    index bit ``j``. Pure numpy index arithmetic (host-side kernel building).
    """
    kk = len(positions)
    dim, DIM = 2**kk, 2**k
    rest = [b for b in range(k) if b not in positions]
    base = scatter_bits(np.arange(1 << len(rest)), rest)  # identity sub-space
    sub = scatter_bits(np.arange(dim), positions)  # embedded matrix indices
    rows = base[:, None, None] | sub[None, :, None]
    cols = base[:, None, None] | sub[None, None, :]
    out = np.zeros((DIM, DIM), dtype=np.complex128)
    out[rows, cols] = np.asarray(mat, dtype=np.complex128)[None, :, :]
    return out


def specialize_gate(
    mat: np.ndarray,
    nonlocal_bits: Sequence[int],
    values: Sequence[int],
    classify: np.ndarray = None,
) -> Tuple[np.ndarray, Tuple[int, ...]]:
    """Restrict a gate matrix on its non-local index bits.

    For each non-local matrix bit ``j`` with effective input value ``v``:
    * diagonal-in-j  -> keep entries with r_j == c_j == v;
    * antidiag-in-j  -> keep entries with c_j == v, r_j == 1-v, and report the
      bit as *flipped* (the caller toggles its lazy flip state).

    ``classify`` (optional) supplies the nonzero pattern used for the
    diagonal/antidiagonal branch decisions while entry *values* still come
    from ``mat``. The parametric compile pipeline passes the gate's
    structural (generic-probe) matrix here so that specialization takes the
    same branches — and reports the same flips — for every binding, even at
    special angles where ``mat`` entries vanish (the probe pattern is a
    superset of every binding's pattern, so extra positions only contribute
    zeros to the reduced matrix).

    Returns (reduced matrix over the remaining bits in ascending original
    order, tuple of flipped non-local bit positions).
    """
    k = int(round(np.log2(mat.shape[0])))
    pattern = mat if classify is None else classify
    rows, cols = np.nonzero(np.abs(pattern) > 1e-14)
    flipped = []
    keep = np.ones(len(rows), dtype=bool)
    for j, v in zip(nonlocal_bits, values):
        rb, cb = (rows >> j) & 1, (cols >> j) & 1
        if np.all(rb[keep] == cb[keep]):
            keep &= (cb == v) & (rb == v)
        elif np.all(rb[keep] != cb[keep]):
            keep &= (cb == v) & (rb == (1 - v))
            flipped.append(j)
        else:
            raise ValueError(f"matrix bit {j} is not insular; staging bug")
    local_bits = [j for j in range(k) if j not in nonlocal_bits]
    dim = 2 ** len(local_bits)
    out = np.zeros((dim, dim), dtype=np.complex128)
    r_kept, c_kept = rows[keep], cols[keep]
    out[gather_bits(r_kept, local_bits), gather_bits(c_kept, local_bits)] = mat[
        r_kept, c_kept
    ]
    return out, tuple(flipped)
