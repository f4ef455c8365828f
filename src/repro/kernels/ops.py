"""jit-traceable wrappers dispatching lane-dense state shards to the Pallas
kernels.

Whether a kernel runs compiled or interpreted is decided when it is traced
(:func:`interpret_mode`): interpreted on a CPU backend, compiled by Mosaic on
a TPU, refused anywhere else. Nothing is decided at import time.
"""

from __future__ import annotations

from typing import Dict, Hashable, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from .fusion import fused_matmul
from .layout import choose_block_m
from .shm import ShmBlock, shm_apply

# Trace-time pallas_call emission counters: each wrapper bumps its counter
# once per call site traced, so after `jit`-tracing an executor the counts
# equal the number of kernel launches (= HBM read+write passes) in the
# compiled program. "interpreted" counts call sites traced in interpret mode.
KERNEL_CALLS = {"fused": 0, "shm": 0, "interpreted": 0}

# Trace-time record of each shm call site's VMEM layout
# (:class:`repro.kernels.shm.ShmBlock`), in trace order.
SHM_BLOCKS: List[ShmBlock] = []


def reset_kernel_counters() -> None:
    for k in KERNEL_CALLS:
        KERNEL_CALLS[k] = 0
    _clear_records()


def kernel_call_counts() -> dict:
    return dict(KERNEL_CALLS)


def shm_block_stats() -> dict:
    """The traced shm calls' block rows, chunk rows and operand bytes; how
    many got another block than sizing operands and blocks against one
    budget gives (``resized``); and the calls' lane ``products`` (in
    whole-chunk dots) and ``mixed`` lane/row members, summed."""
    return {
        "calls": len(SHM_BLOCKS),
        "resized": sum(b.block != b.shared_block for b in SHM_BLOCKS),
        "products": sum(b.products for b in SHM_BLOCKS),
        "mixed": sum(b.mixed for b in SHM_BLOCKS),
        "blocks": [b.block for b in SHM_BLOCKS],
        "chunks": [b.chunk for b in SHM_BLOCKS],
        "operand_bytes": [b.operand_bytes for b in SHM_BLOCKS],
    }


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
    with jax.named_scope("planar"):
        return jnp.real(x).astype(jnp.float32), jnp.imag(x).astype(jnp.float32)


def _from_planar(re: jnp.ndarray, im: jnp.ndarray, dtype) -> jnp.ndarray:
    with jax.named_scope("planar"):
        return jax.lax.complex(re, im).astype(dtype)


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
        return _from_planar(ore, oim, x.dtype)

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
    ore, oim = shm_apply(sre, sim, gates, window_bits, interpret=interp,
                         record=SHM_BLOCKS.append)
    return _from_planar(ore, oim, x.dtype)


# Trace-time record of the remaps of each traced shard_map program, by
# program: a second trace of one program (its ``lower()`` beside its run)
# replaces the first record and adds nothing. (It and its reset sit below
# the wrappers above: a Pallas call carries the source lines of the frames
# that traced it, so lines added above them change every program's text.)
REMAPS: Dict[Hashable, List[dict]] = {}


def _clear_records() -> None:
    SHM_BLOCKS.clear()
    REMAPS.clear()


def remap_record(slot, m: int, L: int, dtype, ppermute: bool) -> dict:
    """One traced remap of a shard of ``2^L`` amplitudes of ``dtype``: its
    ``slot`` (``"init"``, a stage index or ``"final"``), the ``m`` bits its
    all-to-all exchanges, the bytes each device sends in it
    (``(1 - 2^-m) * itemsize * 2^L``: all of its ``2^m`` chunks but its
    own) and whether a ``ppermute`` follows."""
    dt = jnp.dtype(dtype)
    return {"slot": slot, "m": m, "L": L, "dtype": dt.name,
            "itemsize": dt.itemsize,
            "a2a_bytes": dt.itemsize * ((1 << L) - (1 << (L - m))),
            "ppermute": ppermute}


def remap_stats() -> dict:
    """How many shard_map ``programs`` were traced, and their ``remaps``
    (:func:`remap_record`, in stage order)."""
    return {"programs": len(REMAPS),
            "remaps": [r for rs in REMAPS.values() for r in rs]}
