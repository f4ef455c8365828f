"""Lane-dense state-vector primitives for the device paths.

A TPU tiles every array (8, 128) on its two minor dimensions, so a state
viewed as ``(2,)*L`` pads each size-2 minor axis up to a whole tile and a
transpose of such a view materializes copies hundreds of times the state's
size. The device paths therefore keep every state as ``(2^(n-l), 2^l)``:
the ``l`` lowest index bits (7 at every real size) are the 128 *lanes*,
every higher bit indexes *rows*. Views only split the rows; the lane axis
moves only as a whole block.

* a gate on lane bits only is one ``[rows, 128] x [128, 128]`` contraction
  against the gate embedded in the lane space;
* a gate of at most two qubits on row bits only is slice arithmetic on
  ``(rows, 2, 2^(b-l), 2^l)`` views (one elementwise pass);
* any other gate first routes its target bits into the lanes with
  :func:`plan_lanes` — row transposes plus whole-block exchanges of the
  lanes with ``l`` row bits — applies one lane contraction, and routes back;
* a remap (a bit permutation with flips) is the same route ending in the
  requested arrangement, with a permutation contraction for reordering bits
  inside the lanes.

Every function takes ONE state (any shape of ``2^n`` amplitudes; batches
come from ``jax.vmap``) and returns it in the same shape. Only the lowest
``L`` bits — one shard's local bits — ever move; the bits above them are
*groups*: a gate tensor with a leading axis of size ``G`` applies its
variant ``g`` to the ``g``-th of ``G`` equal blocks of the state (one
variant per shard for dep-batched ops).

Bit conventions are those of :mod:`repro.sim.apply`: index bit ``p`` (0 =
least significant) is physical qubit ``p``, and bit ``j`` of a gate matrix
index binds to ``bits[j]``. An *arrangement* lists, for each slot ``p`` of
the current array, the original bit that sits there.

Shards smaller than two lane blocks (``L < 2*l``) cannot route through an
exchange; there the route is a single grouped transpose, which is exact
everywhere and only costs padding on tiny states.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.layout import (LANE_BITS, PRECISION, ascending, embed,
                              gather_bits, lane_bits)


def _nbits(x) -> int:
    return int(x.size).bit_length() - 1


# ----------------------------------------------------------------- routing


def put_rows(arr: List[int], bits: Sequence[int], l: int) -> List[int]:
    """Row slots ``l, l+1, ...`` take ``bits`` in order; the other row bits
    keep their relative order above them; lanes are untouched."""
    rest = [b for b in arr[l:] if b not in bits]
    return arr[:l] + list(bits) + rest


def _exchange(arr: List[int], l: int) -> List[int]:
    """Swap the lane block with row slots ``l..2l-1`` as whole blocks."""
    return arr[l:2 * l] + arr[:l] + arr[2 * l:]


def plan_lanes(cur: Sequence[int], lanes: Sequence[int],
               rows: Optional[Sequence[int]] = None,
               L: Optional[int] = None) -> List[List[int]]:
    """Arrangements leading from ``cur`` to one whose lane slots hold
    ``lanes`` (in that order) and, if given, whose row slots hold ``rows``.
    Only the lowest ``L`` slots (default: all) take part; the slots above
    keep their bits.

    Each consecutive pair differs by one lane-dense step: a row transpose
    (lanes fixed), a lane permutation (rows fixed) or an exchange of the
    lane block with the ``l`` row bits just above it. Lane bits that must
    leave while row bits enter take two exchanges: fillers go into the lanes
    first, then every wanted bit is gathered in the rows and exchanged in."""
    cur = list(cur)
    L = len(cur) if L is None else L
    l = len(lanes)
    want = list(lanes)
    arrs = [cur]

    def push(a):
        if a != arrs[-1]:
            arrs.append(a)

    fillers = [b for b in cur[l:L] if b not in want][:l]
    if set(cur[:l]) == set(want):
        push(want + cur[l:])
    elif L < 2 * l or (set(cur[:l]) & set(want) and len(fillers) < l):
        push(want + [b for b in cur if b not in want])  # direct (tiny L)
    else:
        if set(cur[:l]) & set(want):
            push(put_rows(arrs[-1], fillers, l))
            push(_exchange(arrs[-1], l))
        push(put_rows(arrs[-1], want, l))
        push(_exchange(arrs[-1], l))
    if rows is not None:
        push(arrs[-1][:l] + list(rows))
    return arrs


def _grouped_transpose(x: jnp.ndarray, cur: Sequence[int],
                       new: Sequence[int]) -> jnp.ndarray:
    """Rearrange ``x`` from arrangement ``cur`` to ``new`` with one transpose
    over maximal runs of bits that move together."""
    n = len(cur)
    slot = {b: q for q, b in enumerate(cur)}
    src = [slot[new[p]] for p in range(n)]  # old slot feeding new slot p
    groups = []  # (old lowest slot, length), in new order from the top
    p = n - 1
    while p >= 0:
        q, k = src[p], 1
        while p - k >= 0 and src[p - k] == q - k:
            k += 1
        groups.append((q - k + 1, k))
        p -= k
    old = sorted(groups, key=lambda g: -g[0])
    view = tuple(1 << g[1] for g in old)
    perm = tuple(old.index(g) for g in groups)
    return jnp.transpose(x.reshape(view), perm).reshape(x.shape)


def _lane_perm_matrix(cur_lanes: Sequence[int], new_lanes: Sequence[int],
                      flip_mask: int = 0) -> np.ndarray:
    """P with ``x @ P`` moving lane contents from ``cur_lanes`` order to
    ``new_lanes`` order, XOR-ing the lane index by ``flip_mask`` (old slot
    bits) first."""
    l = len(cur_lanes)
    old_slot = {b: q for q, b in enumerate(cur_lanes)}
    idx = np.arange(1 << l)
    src = np.zeros_like(idx)
    for p, b in enumerate(new_lanes):
        src |= ((idx >> p) & 1) << old_slot[b]
    src ^= flip_mask
    P = np.zeros((1 << l, 1 << l), dtype=np.float32)
    P[src, idx] = 1.0
    return P


def lane_contract(x: jnp.ndarray, M, l: int) -> jnp.ndarray:
    """``x @ M`` over the lanes; ``M`` is ``[2^l, 2^l]`` or ``[G, 2^l,
    2^l]`` (variant g on the g-th row block)."""
    x2 = x.reshape(-1, 1 << l)
    M = jnp.asarray(M).astype(x.dtype)
    if M.ndim == 2:
        y = jnp.matmul(x2, M, precision=PRECISION)
    else:
        y = jnp.einsum("grl,glm->grm", x2.reshape((M.shape[0], -1, 1 << l)),
                       M, precision=PRECISION)
    return y.reshape(x.shape)


def step(x: jnp.ndarray, a: Sequence[int], b: Sequence[int],
         l: int) -> jnp.ndarray:
    """One lane-dense step from arrangement ``a`` to ``b``."""
    a, b = list(a), list(b)
    if a == b:
        return x
    if a[l:] == b[l:] and set(a[:l]) == set(b[:l]):
        return lane_contract(x, _lane_perm_matrix(a[:l], b[:l]), l)
    return _grouped_transpose(x, a, b)


def run_steps(x: jnp.ndarray, arrs: Sequence[Sequence[int]], l: int,
              reverse: bool = False) -> jnp.ndarray:
    seq = list(arrs)[::-1] if reverse else list(arrs)
    for a, b in zip(seq, seq[1:]):
        x = step(x, a, b, l)
    return x


def flip_bits(x: jnp.ndarray, bits: Sequence[int], l: int) -> jnp.ndarray:
    """Reverse ``x`` along each index bit in ``bits``: row bits flip on a
    row-split view, lane bits through one permutation contraction."""
    if not bits:
        return x
    n = _nbits(x)
    lane_mask = sum(1 << b for b in bits if b < l)
    rows = sorted((b for b in bits if b >= l), reverse=True)
    if rows:
        dims, axes, top = [], [], n
        for b in rows:
            if top - b - 1:
                dims.append(1 << (top - b - 1))
            axes.append(len(dims))
            dims.append(2)
            top = b
        dims.append(1 << top)
        x = jnp.flip(x.reshape(tuple(dims)), axis=axes).reshape(x.shape)
    if lane_mask:
        ident = list(range(l))
        x = lane_contract(x, _lane_perm_matrix(ident, ident, lane_mask), l)
    return x


def permute(x: jnp.ndarray, src_bit_of: Sequence[int],
            flips: Sequence[int] = (), l: Optional[int] = None) -> jnp.ndarray:
    """Bit permutation: flip old bits ``flips``, then new bit ``p`` takes old
    bit ``src_bit_of[p]`` (the :class:`repro.sim.compile.RemapSpec`
    convention) over all ``n`` bits of ``x``; ``l`` lane bits (default
    ``min(7, n)``)."""
    n = len(src_bit_of)
    l = lane_bits(n) if l is None else l
    x = flip_bits(x, flips, l)
    want = list(src_bit_of)
    return run_steps(x, plan_lanes(list(range(n)), want[:l], want[l:]), l)


# ------------------------------------------------------------ gate algebra


def _row_combo(x: jnp.ndarray, U, bits: Sequence[int], l: int) -> jnp.ndarray:
    """Shared gate on at most two row bits (``bits`` ascending): out_r =
    sum_c U[r, c] * x_c over the 2^h row-slices (one elementwise pass). Each
    row bit ``b`` splits a ``(rows, 2, 2^(b-l), 2^l)`` view, so the lane
    axis stays whole and no view splits the minor dimension (XLA:TPU
    relayouts such splits and compiles chains of them very slowly). A lane
    bit among ``bits`` would cost one lane contraction per block; such
    gates take the lane route instead, which compiles faster."""
    row = [b for b in bits if b >= l]
    lane = [b for b in bits if b < l]
    h = len(row)
    desc = row[::-1]
    blk = jnp.asarray(U).reshape(1 << h, 1 << len(lane), 1 << h, 1 << len(lane))

    def split(v, level):  # -> {key: slice}; key bit i <-> row[i]
        b = desc[level]
        w = v.reshape(-1, 2, 1 << (b - l), 1 << l)
        halves = (w[:, 0], w[:, 1])
        if level == h - 1:
            return {0: halves[0], 1: halves[1]}
        out = {}
        for hv, half in enumerate(halves):
            for c, sl in split(half, level + 1).items():
                out[c | (hv << (h - 1 - level))] = sl
        return out

    sub = split(x, 0)

    def term(r, c):
        if not lane:
            return blk[r, 0, c, 0] * sub[c]
        E = embed(blk[r, :, c, :], lane, l)
        return jnp.matmul(sub[c], E.T.astype(x.dtype), precision=PRECISION)

    outs = {r: sum(term(r, c) for c in range(1 << h)) for r in range(1 << h)}

    def join(level, key):
        if level == h:
            return outs[key]
        b = desc[level]
        parts = [join(level + 1, key | (hv << (h - 1 - level)))
                 .reshape(-1, 1 << (b - l), 1 << l) for hv in (0, 1)]
        return jnp.stack(parts, axis=1)

    # the barrier keeps each gate one pass: fused into the next gate's
    # slices, a chain of m row gates would inline 2^m input reads (and XLA's
    # code for such fusions grows past hundreds of MB)
    return jax.lax.optimization_barrier(join(0, 0).reshape(x.shape))


def apply_unitary(x: jnp.ndarray, U, bits: Sequence[int], L: int,
                  lane_matmul=None) -> jnp.ndarray:
    """Apply ``U`` ([2^k, 2^k], or [G, 2^k, 2^k] per row block) on index
    bits ``bits`` (all below ``L``) of ``x``.

    ``lane_matmul(x, E, l)`` overrides the lane contraction (the Pallas
    fused kernel plugs in here); it receives ``E`` already embedded in the
    lane space and applies ``x @ E^T`` over the lanes."""
    l = lane_bits(L)
    U, bits = ascending(jnp.asarray(U), bits)
    contract = lane_matmul or (
        lambda y, E, l_: lane_contract(y, jnp.swapaxes(E, -1, -2), l_))
    if all(b < l for b in bits):
        return contract(x, embed(U, bits, l), l)
    if all(b >= l for b in bits) and len(bits) <= 2 and U.ndim == 2:
        return _row_combo(x, U, bits, l)
    cur = list(range(_nbits(x)))
    # fillers: current lane bits when a target already sits in the lanes
    # (two exchanges), local row bits otherwise (one exchange)
    order = cur[:L] if any(b < l for b in bits) else cur[l:L] + cur[:l]
    fill = [b for b in order if b not in bits]
    arrs = plan_lanes(cur, sorted(bits + fill[: l - len(bits)]), L=L)
    y = run_steps(x, arrs, l)
    pos = [arrs[-1].index(b) for b in bits]
    y = contract(y, embed(U, pos, l), l)
    return run_steps(y, arrs, l, reverse=True)


def apply_diag(x: jnp.ndarray, d, bits: Sequence[int], l: int) -> jnp.ndarray:
    """Elementwise multiply by ``d`` ([2^k], or [G, 2^k] per row block)
    indexed by the values of ``bits``. The weight is built at the view's
    shape: one axis per run of target row bits, a full lane axis only when
    a lane bit is a target."""
    n = _nbits(x)
    bits = list(bits)
    d = jnp.asarray(d)
    if d.ndim == 2:  # per row block: the block index is the top bits
        g = d.shape[0].bit_length() - 1
        bits = bits + list(range(n - g, n))
        d = d.reshape(-1)
    lane_t = [b for b in bits if b < l]
    row_t = [b for b in bits if b >= l]
    w = d.reshape(1 << len(row_t), 1 << len(lane_t))
    if lane_t:
        w = w[:, gather_bits(np.arange(1 << l), lane_t)]
    dims, wdims = [], []
    row_set = set(row_t)
    p = n - 1
    while p >= l:
        t = p in row_set
        q = p
        while q - 1 >= l and ((q - 1) in row_set) == t:
            q -= 1
        dims.append(1 << (p - q + 1))
        wdims.append(1 << (p - q + 1) if t else 1)
        p = q - 1
    dims.append(1 << l)
    wdims.append((1 << l) if lane_t else 1)
    v = x.reshape(tuple(dims)) * w.reshape(tuple(wdims)).astype(x.dtype)
    return jax.lax.optimization_barrier(v.reshape(x.shape))


# ------------------------------------------------------------- shm groups

# window row bits kept in the rows: more would make the kernel's row period
# (and so its smallest block) too tall; the window is exchanged into the
# lanes instead
MAX_WINDOW_ROW_BITS = LANE_BITS - 1


def apply_shm_group(x: jnp.ndarray, gates, window: Sequence[int]) -> jnp.ndarray:
    """Apply an shm group to one shard (any shape of 2^L amplitudes) in the
    Pallas shm kernel. ``window`` is the group's active bit set; member gate
    ``bits`` are shard bit positions inside it.

    The window's row bits are gathered just above the lanes (after an
    exchange of the lanes with seven window row bits when the window holds
    too many), ONE shm ``pallas_call`` runs the whole group on lane-dense
    blocks, and the route is undone — one HBM pass for the kernel however
    many gates the group holds."""
    from ..kernels import ops as kops

    L = _nbits(x)
    l = lane_bits(L)
    cur = list(range(L))
    arrs = [cur]
    win = sorted(window)
    win_rows = [b for b in win if b >= l]
    if len(win_rows) > MAX_WINDOW_ROW_BITS and L >= 2 * l:
        arrs = plan_lanes(cur, sorted(win_rows[:l]))
    now = arrs[-1]
    in_rows = [b for b in now[l:] if b in set(win)]
    gathered = put_rows(now, in_rows, l)
    if gathered != now:
        arrs = arrs + [gathered]
    slot = {b: q for q, b in enumerate(arrs[-1])}
    rel = [(tuple(slot[b] for b in bits), mat) for bits, mat in gates]
    y = run_steps(x, arrs, l).reshape(-1, 1 << l)
    out = kops.shm_kernel(y, rel, l + len(in_rows)).reshape(x.shape)
    return run_steps(out, arrs, l, reverse=True)
