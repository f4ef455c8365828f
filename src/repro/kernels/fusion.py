"""Pallas TPU kernel: fused-unitary application (cuQuantum-fusion analogue).

Applies a fused ``2^k``-qubit unitary to a state shard whose k target qubits
have been transposed to the lowest index bits, i.e. a planar-complex matmul

    out[m, r] = sum_c U[r, c] * s[m, c]        (s: [M, K], K = 2^k)

TPU mapping:
* K = 128 (k = 7) makes the contraction a native MXU tile — this is why the
  cost model's sweet spot sits at 7 qubits (see core/cost_model.py);
* the state streams through VMEM in ``(BLOCK_M, K)`` tiles (double-buffered by
  the Pallas pipeline); U stays VMEM-resident across the whole grid;
* complex arithmetic is planar fp32: 4 real matmuls;
* every dot runs at ``Precision.HIGHEST`` (see :data:`repro.kernels.layout.PRECISION`):
  at the default precision a TPU computes an f32 dot in one bf16 pass.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .layout import PRECISION


def _dot(a, b):
    return jnp.dot(a, b, precision=PRECISION, preferred_element_type=jnp.float32)


def _kernel(sre_ref, sim_ref, ure_ref, uim_ref, ore_ref, oim_ref):
    sre = sre_ref[...]
    sim = sim_ref[...]
    ure_t = ure_ref[...].T
    uim_t = uim_ref[...].T
    ore_ref[...] = _dot(sre, ure_t) - _dot(sim, uim_t)
    oim_ref[...] = _dot(sre, uim_t) + _dot(sim, ure_t)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def fused_matmul(
    sre: jnp.ndarray,
    sim: jnp.ndarray,
    ure: jnp.ndarray,
    uim: jnp.ndarray,
    *,
    block_m: int = 512,
    interpret: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """sre/sim: [M, K] fp32; ure/uim: [K, K] fp32. Returns planar result."""
    m, k = sre.shape
    bm = min(block_m, m)
    assert m % bm == 0, f"M={m} must be divisible by block_m={bm}"
    grid = (m // bm,)
    state_spec = pl.BlockSpec((bm, k), lambda i: (i, 0))
    u_spec = pl.BlockSpec((k, k), lambda i: (0, 0))
    out_shape = [
        jax.ShapeDtypeStruct((m, k), jnp.float32),
        jax.ShapeDtypeStruct((m, k), jnp.float32),
    ]
    return tuple(
        pl.pallas_call(
            _kernel,
            grid=grid,
            in_specs=[state_spec, state_spec, u_spec, u_spec],
            out_specs=[state_spec, state_spec],
            out_shape=out_shape,
            interpret=interpret,
        )(sre, sim, ure, uim)
    )
