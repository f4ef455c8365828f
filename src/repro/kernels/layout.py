"""What the TPU's tiling asks of a state and of a kernel block.

A TPU tiles every array (8, 128) on its two minor dimensions, so every
device path keeps a state lane-dense: the ``LANE_BITS`` lowest index bits
are the 128 lanes, the higher bits index rows (see :mod:`repro.sim.lanes`).
This module holds the pieces both the kernels and the simulator's lane
primitives build on — the lane width, the matmul precision, the bit-index
algebra that embeds a gate in the lane space — and the scoped-VMEM budget a
Pallas block is sized against. It imports nothing from the rest of the
package.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

LANE_BITS = 7

# f32/complex64 contractions run at HIGHEST on purpose: at DEFAULT a TPU runs
# an f32 matmul as one bf16 pass, and the error of ~1,000 gate contractions
# (su2random at n = 28 has 1,246 gates) would break the 1e-4 fidelity bounds.
PRECISION = jax.lax.Precision.HIGHEST

# Block sizing plans for 12 MiB of the 16 MiB of scoped VMEM a Pallas
# kernel may use on a TPU v5e by default (the rest is the compiler's own
# headroom); a kernel that needs more asks for it (:func:`vmem_limit`).
VMEM_BUDGET_BYTES = 12 << 20


def lane_bits(L: int) -> int:
    return min(LANE_BITS, L)


# ------------------------------------------------------------ bit algebra


def scatter_bits(values: np.ndarray, positions: Sequence[int]) -> np.ndarray:
    """Vectorized bit scatter: deposit bit ``j`` of each value at position
    ``positions[j]`` of the result (numpy index arithmetic, no Python loop
    over values)."""
    out = np.zeros_like(np.asarray(values, dtype=np.int64))
    for j, p in enumerate(positions):
        out |= ((values >> j) & 1) << p
    return out


def gather_bits(values: np.ndarray, positions: Sequence[int]) -> np.ndarray:
    """Vectorized bit gather: bit ``j`` of the result is bit ``positions[j]``
    of each value (inverse of :func:`scatter_bits`)."""
    out = np.zeros_like(np.asarray(values, dtype=np.int64))
    for j, p in enumerate(positions):
        out |= ((values >> p) & 1) << j
    return out


def embed(U, positions: Sequence[int], l: int):
    """Embed ``U`` (lead + [2^k, 2^k]) acting on lane slots ``positions``
    into the full ``2^l`` lane space (identity elsewhere). Traceable: the
    gather indices and mask are static."""
    dim = 1 << l
    ridx = gather_bits(np.arange(dim), positions)
    m = sum(1 << p for p in positions)
    r = np.arange(dim)
    mask = ((r[:, None] & ~m) == (r[None, :] & ~m)).astype(np.float32)
    U = jnp.asarray(U)
    return U[..., ridx[:, None], ridx[None, :]] * mask


def ascending(U, bits: Sequence[int]):
    """``U`` re-indexed so that its bit j binds to ``sorted(bits)[j]``."""
    bits = list(bits)
    srt = sorted(bits)
    if bits == srt:
        return U, bits
    k = len(bits)
    idx = np.arange(1 << k)
    old = np.zeros_like(idx)  # old index of each new index
    for j, b in enumerate(srt):
        old |= ((idx >> j) & 1) << bits.index(b)
    return U[..., old[:, None], old[None, :]], srt


# ------------------------------------------------------------ VMEM blocks


def choose_block_m(m: int, row_bytes: int, *, planes: int, fixed: int = 0,
                   min_rows: int = 8, budget: int = VMEM_BUDGET_BYTES) -> int:
    """Largest power-of-two row block dividing ``m`` whose VMEM footprint
    ``planes * bm * row_bytes + fixed`` fits ``budget``. ``planes`` counts
    every block-sized buffer: inputs and outputs twice (the pipeline double
    buffers them) plus the kernel's block-sized temporaries; ``fixed`` the
    whole-grid operands (also double buffered). Never below ``min_rows``
    (or ``m`` when the array is smaller)."""
    bm = min_rows
    while bm * 2 <= m and planes * bm * 2 * row_bytes + fixed <= budget:
        bm *= 2
    bm = min(bm, m)
    while m % bm:
        bm //= 2
    return max(bm, 1)


def vmem_limit(need: int) -> int:
    """``vmem_limit_bytes`` for a kernel needing ``need`` bytes: 0 (the
    default scoped limit) when it fits, else the need plus headroom."""
    return 0 if need <= VMEM_BUDGET_BYTES else need + (4 << 20)
