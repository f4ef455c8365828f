"""Pallas TPU kernel: VMEM-resident multi-gate application (the GPU
shared-memory kernel of HyQuas/Atlas, re-targeted at the TPU memory
hierarchy).

The state arrives lane-dense as planar fp32 ``[M, C]`` (``C = 2^c`` lanes,
``c <= 7``; 128 at every real size). The group's *window* covers the ``c``
lane bits and the lowest ``r`` row bits, so a ``(BR, C)`` block with ``BR``
a multiple of ``P = 2^r`` holds whole windows. The block is loaded into
VMEM once and every member gate runs on it — one HBM read+write pass in
total, whatever the gate count (the ``alpha + sum_g cost(g)`` regime of the
cost model). The members run on the rows grouped by window row value: slab
``u`` holds the rows whose ``r`` window row bits read ``u`` (one strided
load and store each), and the slabs lie side by side in ``u`` order:

* a gate that touches lane bits is a complex MXU matmul against its lane
  blocks (the gate embedded in the ``c``-bit lane space, one block per pair
  of row-bit values): for each value of its ``h`` row bits, the slabs that
  hold it go through the MXU side by side, so it runs ``4^h`` dots on
  ``1/2^h`` of the rows each; runs of lane-only gates are pre-multiplied
  into one matrix;
* a gate on row bits only is slab arithmetic: the partner rows are the
  slabs whose index differs in those bits, the coefficients rows of
  periodic ``(P, C)`` tiles, each broadcast over its slab;
* a diagonal member is one elementwise multiply by its periodic tile; runs
  of diagonals are pre-multiplied into one tile.

The block is sized from its own buffers and the operands get VMEM of their
own; a tall block runs the members on row chunks (:func:`shm_block`).

Gate *structure* (bits, kinds) is static; gate *values* are kernel operands
built from the (possibly traced, dep-selected) member tensors outside the
kernel, so one compiled kernel serves every binding.
"""

from __future__ import annotations

from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layout import (PRECISION, VMEM_CAP_BYTES, ascending, choose_block_m,
                     embed, gather_bits, scatter_bits, vmem_limit)

LANES = 128

# block-sized VMEM buffers of one shm kernel: the two input and two output
# planes, double buffered, and the body's block-sized temporaries
BLOCK_PLANES = 16

# VMEM copies of the lowered lane matrices and tiles: their block index never
# changes, so the pipeline holds one buffer of each (``pl.Buffered(1)``)
OPERAND_BUFFERS = 1

# the fewest rows the member program runs on at a time: each lane product
# loads a 128 x 128 matrix into the MXU, and a shorter chunk streams too few
# rows through it per load
MIN_CHUNK_ROWS = 128


class ShmBlock(NamedTuple):
    """How one shm call is laid out in VMEM (rows of ``C`` lanes)."""
    block: int          # rows of a grid step
    chunk: int          # rows the member program runs on at a time
    operand_bytes: int  # lowered lane matrices and tiles
    shared_block: int   # the block when operands and blocks share the budget
    vmem_limit: int     # ``vmem_limit_bytes`` asked for (0: the default)
    products: int = 0   # lane products a call runs, in whole-chunk dots
    mixed: int = 0      # lane matrix members on window row bits too


def shm_block(m: int, C: int, period: int, operand_bytes: int,
              block_m: int = 0) -> ShmBlock:
    """Size an shm call over ``[m, C]`` planes whose window repeats every
    ``period`` rows and whose lowered operands take ``operand_bytes``.

    The block is the largest whose block-sized buffers fit the scoped VMEM
    budget; the grid-constant operands get VMEM of their own on top, up to
    the chip's cap (if they would not fit beside it, the block is sized
    against both together, as the chunk is). The body's code grows with
    the rows it runs on times its member program, and the operands' bytes
    grow with the program, so the program runs on chunks of the block that
    operands and blocks would share the budget for, never fewer than
    ``MIN_CHUNK_ROWS`` nor than eight rows a window row value (whole
    ``(8, 128)`` tiles in each of the body's ``period`` slabs); where that
    is the whole block, unchunked. An explicit ``block_m`` is the block,
    run unchunked."""
    row = 4 * C
    min_rows = max(8, period)
    limit = lambda bm: vmem_limit(  # noqa: E731
        BLOCK_PLANES * bm * row + OPERAND_BUFFERS * operand_bytes)
    shared = choose_block_m(m, row, planes=BLOCK_PLANES,
                            fixed=2 * operand_bytes, min_rows=min_rows)
    if block_m:
        bm = chunk = min(block_m, m)
    else:
        bm = choose_block_m(m, row, planes=BLOCK_PLANES, min_rows=min_rows)
        if limit(bm) > VMEM_CAP_BYTES:
            bm = shared
        chunk = min(bm, max(shared, MIN_CHUNK_ROWS, 8 * period))
    return ShmBlock(bm, chunk, operand_bytes, shared, limit(bm))


def _cdot(ar, ai, br, bi):
    d = lambda u, v: lax.dot_general(  # noqa: E731
        u, v, (((1,), (0,)), ((), ())), precision=PRECISION,
        preferred_element_type=jnp.float32)
    return (lax.sub(d(ar, br), d(ai, bi)), lax.add(d(ar, bi), d(ai, br)))


# The body is traced slab by slab, so it binds ``lax`` primitives: a
# ``jnp`` operator would add a nested trace per slab and per member.


def _rows(parts):
    """Slabs side by side along rows."""
    return parts[0] if len(parts) == 1 else lax.concatenate(parts, 0)


def _split(x, k: int):
    """``x`` cut along rows into ``k`` equal slabs."""
    return [x] if k == 1 else lax.split(x, (x.shape[0] // k,) * k, 0)


def _add(acc, y):
    return y if acc is None else lax.add(acc, y)


def _per_slab(t, P: int, S: int):
    """Rows ``0..P-1`` of a periodic ``(p, C)`` tile, row ``u`` broadcast
    over slab ``u``'s ``S`` rows, slabs side by side."""
    C = t.shape[1]
    t = lax.slice(t, (0, 0), (P, C))
    return lax.reshape(lax.broadcast_in_dim(t, (P, S, C), (0, 2)), (P * S, C))


def make_shm_kernel(program: Sequence[Tuple], n_mats: int, n_tiles: int,
                    chunk: int, period: int):
    """Kernel body for a static member program. The member program runs on
    ``chunk`` rows (a multiple of the window's period ``P`` dividing the
    block) at a time, in a loop unless that is the block, grouped by
    window row value: slab ``u`` holds the chunk's rows whose window row
    value is ``u`` (rows ``u, u + P, ...``; one strided load and store),
    and the slabs lie side by side in ``u`` order. Entries, with ``J`` the
    member's row bits (block-relative) and ``D(d)`` the slab offset of
    ``d``'s bits on them:
    ``("tile", J, tidx)`` — slab u = sum_d tile[tidx[d]][u] * slab(u ^ D(d));
    ``("mat", J, midx)`` — for each value ``v`` of the bits ``J``, the slabs
    holding it, side by side: sum_d slabs(^ D(d)) @ mat[midx[d][v]]."""
    P = period

    def run(xr, xi, mats_ref, tiles_ref):
        S = xr.shape[0] // P
        for kind, J, idx in program:
            h = len(J)
            flips = scatter_bits(np.arange(1 << h), J).tolist()
            parts = (_split(xr, P), _split(xi, P)) if h else None

            def pick(us, f):
                """Slabs ``u ^ f`` for ``u`` in ``us``, side by side."""
                if not f and len(us) == P:
                    return xr, xi
                return tuple(_rows([p[u ^ f] for u in us]) for p in parts)

            if kind == "tile":
                acc_r = acc_i = None
                for d, i in enumerate(idx):
                    ar, ai = pick(range(P), flips[d])
                    tr, ti = (_per_slab(tiles_ref[i, k], P, S) for k in (0, 1))
                    acc_r = _add(acc_r, lax.sub(lax.mul(ar, tr),
                                                lax.mul(ai, ti)))
                    acc_i = _add(acc_i, lax.add(lax.mul(ar, ti),
                                                lax.mul(ai, tr)))
                xr, xi = acc_r, acc_i
                continue
            if not h:  # lane-only: one product over all slabs
                m = mats_ref[idx[0][0]]
                xr, xi = _cdot(xr, xi, m[0], m[1])
                continue
            vals = gather_bits(np.arange(P), J)
            out_r, out_i = [None] * P, [None] * P
            for v in range(1 << h):
                us = np.flatnonzero(vals == v).tolist()
                acc_r = acc_i = None
                for d in range(1 << h):
                    m = mats_ref[idx[d][v]]
                    yr, yi = _cdot(*pick(us, flips[d]), m[0], m[1])
                    acc_r, acc_i = _add(acc_r, yr), _add(acc_i, yi)
                for u, yr, yi in zip(us, _split(acc_r, len(us)),
                                     _split(acc_i, len(us))):
                    out_r[u], out_i[u] = yr, yi
            xr, xi = _rows(out_r), _rows(out_i)
        return xr, xi

    def body(sre_ref, sim_ref, *refs):
        mats_ref = refs[0] if n_mats else None
        tiles_ref = refs[1 if n_mats else 0] if n_tiles else None
        ore_ref, oim_ref = refs[-2], refs[-1]
        bm = sre_ref.shape[0]

        def step(base):
            rows = [pl.ds(base + u, chunk // P, stride=P) for u in range(P)]
            yr, yi = run(_rows([sre_ref[r, :] for r in rows]),
                         _rows([sim_ref[r, :] for r in rows]),
                         mats_ref, tiles_ref)
            for r, sr, si in zip(rows, _split(yr, P), _split(yi, P)):
                ore_ref[r, :], oim_ref[r, :] = sr, si

        if chunk == bm:
            step(0)
            return

        def loop(k, carry):
            step(pl.multiple_of(k * chunk, chunk))
            return carry

        jax.lax.fori_loop(0, bm // chunk, loop, 0)

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
    record: Optional[Callable[[ShmBlock], None]] = None,
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
    the window's row period; 0 sizes it by :func:`shm_block`. ``record``
    receives the call's :class:`ShmBlock`.
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
    # built from the op tensors inside the traced program, so in every run
    with jax.named_scope("operands"):
        program, mats, tiles = _lower_members(gates, c, p_rows)
    operands = [x for x in (mats, tiles) if x is not None]
    sizing = shm_block(m, C, period,
                       sum(x.size * x.dtype.itemsize for x in operands),
                       block_m)._replace(
        products=sum(1 << len(J) for kind, J, _ in program if kind == "mat"),
        mixed=sum(kind == "mat" and bool(J) for kind, J, _ in program))
    if record is not None:
        record(sizing)
    bm, chunk = sizing.block, sizing.chunk
    assert m % bm == 0 and bm % chunk == 0 and chunk % period == 0, (
        m, bm, chunk, period)
    body = make_shm_kernel(program, 0 if mats is None else len(mats),
                           0 if tiles is None else len(tiles), chunk, period)
    spec = pl.BlockSpec((bm, C), lambda i: (i, 0))
    op_specs = [pl.BlockSpec(x.shape, lambda i, _n=x.ndim: (0,) * _n,
                             pipeline_mode=pl.Buffered(OPERAND_BUFFERS))
                for x in operands]
    params = ({"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=sizing.vmem_limit)} if sizing.vmem_limit else {})
    ore, oim = pl.pallas_call(
        body,
        grid=(m // bm,),
        in_specs=[spec, spec] + op_specs,
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((m, C), jnp.float32)] * 2,
        interpret=interpret,
        name="shm_group",
        **params,
    )(sre, sim, *operands)
    return ore.reshape(shape), oim.reshape(shape)
