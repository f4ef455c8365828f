"""jit-traceable wrappers dispatching lane-dense state shards to the Pallas
kernels.

Whether a kernel runs compiled or interpreted is decided when it is traced
(:func:`interpret_mode`): interpreted on a CPU backend, compiled by Mosaic on
a TPU, refused anywhere else. Nothing is decided at import time.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from .fusion import fused_matmul
from .layout import choose_block_m
from .shm import shm_apply

# Trace-time pallas_call emission counters: each wrapper bumps its counter
# once per call site traced, so after `jit`-tracing an executor the counts
# equal the number of kernel launches (= HBM read+write passes) in the
# compiled program. "interpreted" counts call sites traced in interpret mode.
KERNEL_CALLS = {"fused": 0, "shm": 0, "interpreted": 0}


def reset_kernel_counters() -> None:
    for k in KERNEL_CALLS:
        KERNEL_CALLS[k] = 0


def kernel_call_counts() -> dict:
    return dict(KERNEL_CALLS)


def interpret_mode() -> bool:
    """Trace-time choice: interpret on ``cpu``, compile on ``tpu``."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels have no path on platform {platform!r}")


def _interpret() -> bool:
    interp = interpret_mode()
    if interp:
        KERNEL_CALLS["interpreted"] += 1
    return interp


def _to_planar(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    return jnp.real(x).astype(jnp.float32), jnp.imag(x).astype(jnp.float32)


def fused_block_m(m: int, k: int = 128) -> int:
    """Row block of :func:`fused_matmul` for an ``[m, k]`` planar state: 2
    inputs + 2 outputs double buffered (8 planes) and 4 dot temporaries;
    the two ``[k, k]`` gate planes double buffered."""
    return choose_block_m(m, 4 * k, planes=12, fixed=2 * 2 * 4 * k * k)


def lane_matmul(x: jnp.ndarray, E, l: int) -> jnp.ndarray:
    """``x @ E^T`` over the ``2^l`` lanes of one state through the Pallas
    fused kernel. ``E``: ``[2^l, 2^l]``, or ``[G, 2^l, 2^l]`` with variant g
    on the g-th row block (one kernel call per block)."""
    KERNEL_CALLS["fused"] += 1
    interp = _interpret()
    cols = 1 << l
    E = jnp.asarray(E)

    def one(v, e):
        rows = v.size // cols
        sre, sim = _to_planar(v.reshape(rows, cols))
        ure, uim = _to_planar(e)
        ore, oim = fused_matmul(sre, sim, ure, uim,
                                block_m=fused_block_m(rows, cols),
                                interpret=interp)
        return jax.lax.complex(ore, oim).astype(x.dtype)

    if E.ndim == 2:
        return one(x, E).reshape(x.shape)
    blocks = x.reshape(E.shape[0], -1)
    return jax.vmap(one)(blocks, E).reshape(x.shape)


def shm_kernel(x: jnp.ndarray,
               gates: Sequence[Tuple[Tuple[int, ...], jnp.ndarray]],
               window_bits: int) -> jnp.ndarray:
    """ONE shm ``pallas_call`` over a lane-dense state ``x`` ``[rows,
    2^l]`` whose group window is its lowest ``window_bits`` index bits (the
    lanes and the lowest row bits); member ``bits`` are index positions
    inside it. :func:`repro.sim.lanes.apply_shm_group` routes a window
    there first."""
    KERNEL_CALLS["shm"] += 1
    interp = _interpret()
    sre, sim = _to_planar(x)
    ore, oim = shm_apply(sre, sim, gates, window_bits, interpret=interp)
    return jax.lax.complex(ore, oim).astype(x.dtype)
