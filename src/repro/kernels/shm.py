"""Pallas TPU kernel: VMEM-resident multi-gate application (the GPU
shared-memory kernel of HyQuas/Atlas, re-targeted at the TPU memory
hierarchy).

The state arrives lane-dense as planar fp32 ``[M, C]`` (``C = 2^c`` lanes,
``c <= 7``; 128 at every real size). The group's *window* covers the ``c``
lane bits and the lowest ``r`` row bits, so a ``(BR, C)`` block with ``BR``
a multiple of ``2^r`` holds whole windows. The block is loaded into VMEM
once and every member gate runs on it — one HBM read+write pass in total,
whatever the gate count (the ``alpha + sum_g cost(g)`` regime of the cost
model):

* a gate that touches lane bits is a complex MXU matmul against its lane
  blocks (the gate embedded in the ``c``-bit lane space, one block per pair
  of row-bit values); runs of lane-only gates are pre-multiplied into one
  matrix;
* a gate on row bits only is row-pair arithmetic: the partner rows come from
  sublane rolls, the coefficients from periodic ``(P, C)`` tiles;
* a diagonal member is one elementwise multiply by its periodic tile; runs
  of diagonals are pre-multiplied into one tile.

Gate *structure* (bits, kinds) is static; gate *values* are kernel operands
built from the (possibly traced, dep-selected) member tensors outside the
kernel, so one compiled kernel serves every binding.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layout import (PRECISION, ascending, choose_block_m, embed, gather_bits,
                     vmem_limit)

LANES = 128

# block-sized VMEM buffers of one shm kernel: the two input and two output
# planes, double buffered, and the body's block-sized temporaries
BLOCK_PLANES = 16


def _cdot(ar, ai, br, bi):
    d = lambda u, v: jnp.dot(u, v, precision=PRECISION,  # noqa: E731
                             preferred_element_type=jnp.float32)
    return d(ar, br) - d(ai, bi), d(ar, bi) + d(ai, br)


def _tile_mul(xr, xi, tr, ti):
    """Complex multiply of a (BR, C) block by a periodic (P, C) tile."""
    br, c = xr.shape
    p = tr.shape[0]
    x3r, x3i = xr.reshape(br // p, p, c), xi.reshape(br // p, p, c)
    yr = x3r * tr[None] - x3i * ti[None]
    yi = x3r * ti[None] + x3i * tr[None]
    return yr.reshape(br, c), yi.reshape(br, c)


def _flip_row(y, rid, j):
    """Rows with bit ``j`` toggled (sublane/row roll + select)."""
    n = y.shape[0]
    s = 1 << j
    return jnp.where(((rid >> j) & 1) == 1, pltpu.roll(y, s, 0),
                     pltpu.roll(y, n - s, 0))


def make_shm_kernel(program: Sequence[Tuple], n_mats: int, n_tiles: int):
    """Kernel body for a static member program. Entries:
    ``("tile", J, tidx)`` — out = sum_d tile[tidx[d]] * x(rows ^ d);
    ``("mat", J, midx)`` — out = sum_d sum_v [rowval == v] x(rows ^ d) @
    mat[midx[d][v]]; ``J`` are the member's row bits (block-relative)."""

    def body(sre_ref, sim_ref, *refs):
        mats_ref = refs[0] if n_mats else None
        tiles_ref = refs[1 if n_mats else 0] if n_tiles else None
        ore_ref, oim_ref = refs[-2], refs[-1]
        xr, xi = sre_ref[...], sim_ref[...]
        rid = jax.lax.broadcasted_iota(jnp.int32, xr.shape, 0)
        for kind, J, idx in program:
            h = len(J)
            parts = [(xr, xi)]
            for d in range(1, 1 << h):
                j = (d & -d).bit_length() - 1  # lowest set bit of d
                pr, pi = parts[d & (d - 1)]
                parts.append((_flip_row(pr, rid, J[j]), _flip_row(pi, rid, J[j])))
            acc_r = acc_i = None
            if kind == "tile":
                for d in range(1 << h):
                    t = tiles_ref[idx[d]]
                    yr, yi = _tile_mul(*parts[d], t[0], t[1])
                    acc_r = yr if acc_r is None else acc_r + yr
                    acc_i = yi if acc_i is None else acc_i + yi
            else:
                rowval = 0
                for i, jb in enumerate(J):
                    rowval = rowval + (((rid >> jb) & 1) << i)
                for d in range(1 << h):
                    for v in range(1 << h):
                        m = mats_ref[idx[d][v]]
                        yr, yi = _cdot(*parts[d], m[0], m[1])
                        if h:
                            sel = rowval == v
                            yr = jnp.where(sel, yr, 0.0)
                            yi = jnp.where(sel, yi, 0.0)
                        acc_r = yr if acc_r is None else acc_r + yr
                        acc_i = yi if acc_i is None else acc_i + yi
            xr, xi = acc_r, acc_i
        ore_ref[...] = xr
        oim_ref[...] = xi

    return body


def _lower_members(gates, c: int, p_rows: int):
    """Turn (bits, op) members into the kernel program plus stacked lane
    matrices ``[n, 2, C, C]`` (already transposed for ``x @ m``) and
    periodic tiles ``[n, 2, P, C]``. Runs of lane-only matrices and runs of
    diagonals fold into one operand each."""
    C = 1 << c
    flat = (np.arange(p_rows)[:, None] << c) | np.arange(C)[None, :]
    program: List[Tuple] = []
    mats: List[jnp.ndarray] = []
    tiles: List[jnp.ndarray] = []
    for bits, op in gates:
        op = jnp.asarray(op)
        bits = tuple(bits)
        if op.ndim == 1:  # diagonal: one periodic tile
            t = op[gather_bits(flat, bits)]
            if program and program[-1][0] == "tile" and not program[-1][1]:
                tiles[-1] = tiles[-1] * t
            else:
                program.append(("tile", (), (len(tiles),)))
                tiles.append(t)
            continue
        op, bits = ascending(op, bits)  # lane bits = low index bits
        lane = [b for b in bits if b < c]
        J = tuple(b - c for b in bits if b >= c)
        h, klo = len(J), len(lane)
        blk = op.reshape(1 << h, 1 << klo, 1 << h, 1 << klo)  # [r, lo, c, lo]
        if not lane:  # row-only gate: coefficient tiles per row XOR d
            rv = gather_bits(np.arange(p_rows), J)
            tidx = []
            for d in range(1 << h):
                col = blk[rv, 0, rv ^ d, 0]  # (P,)
                tidx.append(len(tiles))
                tiles.append(jnp.broadcast_to(col[:, None], (p_rows, C)))
            program.append(("tile", J, tuple(tidx)))
            continue
        # lane blocks: E_{v, v^d} embedded on the lane bits, transposed
        midx = []
        for d in range(1 << h):
            row = []
            for v in range(1 << h):
                e = embed(blk[v, :, v ^ d, :], lane, c).T
                row.append(len(mats))
                mats.append(e)
            midx.append(tuple(row))
        if not h and program and program[-1][0] == "mat" and not program[-1][1]:
            prev = program[-1][2][0][0]
            mats[prev] = jnp.matmul(mats[prev], mats.pop(), precision=PRECISION)
            continue
        program.append(("mat", J, tuple(midx)))
    planar = lambda xs: (  # noqa: E731
        jnp.stack([jnp.stack([jnp.real(x), jnp.imag(x)]) for x in xs])
        .astype(jnp.float32) if xs else None)
    return program, planar(mats), planar(tiles)


def shm_apply(
    sre: jnp.ndarray,
    sim: jnp.ndarray,
    gates: Sequence[Tuple[Tuple[int, ...], jnp.ndarray]],
    window_bits: int,
    *,
    block_m: int = 0,
    interpret: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Apply ``gates`` inside ONE ``pallas_call``.

    ``sre``/``sim``: planar fp32 state. Lane-dense ``[M, C]`` (``C <=
    128``), or ``[M, 2^a]`` with ``a > 7``, which is read as the same flat
    order in ``[M * 2^(a-7), 128]``. Gate bits are positions in the
    flattened (row, lane) index: bits below ``log2(C)`` are lanes, the rest
    are the lowest row bits and must lie inside ``window_bits``.

    ``gates``: (bits, op) pairs; a 2-D ``op`` is a unitary on ``bits`` (bit
    j of its index binds to bits[j]), a 1-D ``op`` a diagonal indexed by the
    values of ``bits``. ``block_m`` is the block's row count, a multiple of
    the window's row period; 0 picks the largest block whose buffers and
    lowered gate operands fit the VMEM budget.
    """
    shape = sre.shape
    if shape[1] > LANES:
        sre, sim = sre.reshape(-1, LANES), sim.reshape(-1, LANES)
    m, C = sre.shape
    c = C.bit_length() - 1
    period = 1 << max(0, window_bits - c)
    # the tiles' row period: whole windows and at least one sublane tile,
    # never taller than a block (a chosen block is at least this tall)
    p_rows = max(period, min(8, block_m or m))
    program, mats, tiles = _lower_members(gates, c, p_rows)
    operands = [x for x in (mats, tiles) if x is not None]
    fixed = 2 * sum(x.size * x.dtype.itemsize for x in operands)
    bm = min(block_m or choose_block_m(m, 4 * C, planes=BLOCK_PLANES,
                                       fixed=fixed, min_rows=max(8, period)), m)
    assert m % bm == 0 and bm % period == 0, (m, bm, period)
    body = make_shm_kernel(program, 0 if mats is None else len(mats),
                           0 if tiles is None else len(tiles))
    spec = pl.BlockSpec((bm, C), lambda i: (i, 0))
    op_specs = [pl.BlockSpec(x.shape, lambda i, _n=x.ndim: (0,) * _n)
                for x in operands]
    limit = vmem_limit(BLOCK_PLANES * bm * 4 * C + fixed)
    params = ({"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=limit)} if limit else {})
    ore, oim = pl.pallas_call(
        body,
        grid=(m // bm,),
        in_specs=[spec, spec] + op_specs,
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((m, C), jnp.float32)] * 2,
        interpret=interpret,
        **params,
    )(sre, sim, *operands)
    return ore.reshape(shape), oim.reshape(shape)
