"""The references of a state spread over four devices: ``qft_closed_form``'s
closed form and, at ``precision="high"``, ``statevector``'s gate-by-gate
passes, with the lane-dense rows ``[2^(n-7), 128]`` split in four equal
blocks over ``jax.devices()[:4]``: block d, the amplitudes whose top two
index bits read d, on the d-th device. That is how the simulator's
shard_map bit-mesh places a state of R = 2 (its devices in
``jax.devices()`` order, the top bit the major mesh axis), so comparing
the two moves no amplitude between devices. Each device computes only its
own block (``shard_map``), so no device ever holds the whole state.

Gate-by-gate: a gate on one of the lowest n - 2 bits is ``statevector``'s
pass on each block, its control read from the block's index or, on a top
bit, from the device's; a gate on a top bit pairs each device with the one
whose block differs in that bit, which sends it its amplitudes
(``ppermute``), and combines them as ``statevector`` combines two halves.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from . import qft_closed_form, statevector

DEVICE_BITS = 2
DEVICES = 1 << DEVICE_BITS
AXIS = "d"
LANE_BITS = statevector.LANE_BITS
LANES = statevector.LANES


@lru_cache(maxsize=None)
def mesh() -> Mesh:
    """The first ``DEVICES`` devices in order, one row block each."""
    devs = jax.devices()
    if len(devs) < DEVICES:
        raise RuntimeError(f"the reference needs {DEVICES} devices, has {len(devs)}")
    return Mesh(np.array(devs[:DEVICES]), (AXIS,))


def _rows(n_qubits: int) -> int:
    rows = 1 << (n_qubits - LANE_BITS)
    if rows % DEVICES:
        raise ValueError(f"{n_qubits} qubits leave fewer rows than devices")
    return rows // DEVICES


def _global_index(rows: int):
    """Each amplitude's index in the whole state, on this device's block."""
    shape = (rows, LANES)
    row = (lax.broadcasted_iota(jnp.uint32, shape, 0)
           + lax.axis_index(AXIS).astype(jnp.uint32) * jnp.uint32(rows))
    return (row << LANE_BITS) | lax.broadcasted_iota(jnp.uint32, shape, 1)


@partial(jax.jit, static_argnames=("n_qubits",))
def _closed_form(x, *, n_qubits: int):
    rows = _rows(n_qubits)

    def block(x):
        y = _global_index(rows)
        ph = (x * qft_closed_form.bit_reverse(y, n_qubits)) & jnp.uint32(
            (1 << n_qubits) - 1)
        ang = ph.astype(jnp.float32) * jnp.float32(
            2 * 3.141592653589793 / (1 << n_qubits))
        return lax.complex(jnp.cos(ang), jnp.sin(ang)) * jnp.float32(
            2.0 ** (-n_qubits / 2))

    return jax.shard_map(block, mesh=mesh(), in_specs=P(),
                         out_specs=P(AXIS, None), check_vma=False)(x)


@partial(jax.jit, static_argnames=("n_qubits",))
def _basis(x, *, n_qubits: int):
    rows = _rows(n_qubits)

    def block(x):
        xr = (_global_index(rows) == x).astype(jnp.float32)
        return xr, jnp.zeros_like(xr)

    return jax.shard_map(block, mesh=mesh(), in_specs=P(),
                         out_specs=(P(AXIS, None),) * 2, check_vma=False)(x)


def _device_gate(st, c, u, *, q: int, local: int, precision: str):
    """Gate ``u`` ([2, 2, 2]: rows, columns, re/im) on target ``q`` where
    control ``c`` (-1: none) is 1, on this device's block of ``2^local``
    amplitudes."""
    d = lax.axis_index(AXIS).astype(jnp.int32)
    top = jnp.maximum(c - local, 0)
    dev_on = (c < local) | (((d >> top) & 1) == 1)  # a top-bit control
    c_local = jnp.where(c < local, c, -1)
    if q < local:
        new = statevector._apply(q, precision, st, c_local, u)
    else:
        k = q - local
        perm = [(s, s ^ (1 << k)) for s in range(DEVICES)]
        partner = tuple(lax.ppermute(v, AXIS, perm) for v in st)
        b = (d >> k) & 1
        # where bit q is b: out = U[b, b] x + U[b, 1 - b] partner
        a = statevector._cmul(u[b, b], *st, precision)
        p = statevector._cmul(u[b, 1 - b], *partner, precision)
        shape = st[0].shape
        on = statevector._control(c_local, lax.broadcasted_iota(jnp.int32, shape, 0),
                                  lax.broadcasted_iota(jnp.int32, shape, 1))
        new = tuple(jnp.where(on, a_ + p_, v) for a_, p_, v in zip(a, p, st))
    return tuple(jnp.where(dev_on, v, s) for v, s in zip(new, st))


@partial(jax.jit, static_argnames=("q", "local", "precision"),
         donate_argnums=(0,))
def _gate(st, c, u, i, *, q: int, local: int, precision: str):
    fn = partial(_device_gate, q=q, local=local, precision=precision)
    return jax.shard_map(
        lambda st, c, u: fn(st, c, u), mesh=mesh(),
        in_specs=((P(AXIS, None),) * 2, P(), P()),
        out_specs=(P(AXIS, None),) * 2, check_vma=False)(st, c[i], u[i])


@jax.jit
def _complex(st):
    return lax.complex(*st)


def state(gates, n_qubits: int, x: int, precision: str = "highest"):
    """The final state, lane-dense ``[2^(n-7), 128]`` complex64 in logical
    order, its rows split over the four devices: the closed form of
    ``gates`` (the QFT of ``circuits/qft.py``) or, at ``"high"``, the
    gate-by-gate :func:`passes` at three bf16 products per product."""
    if precision == "highest":
        return _closed_form(jnp.uint32(x), n_qubits=n_qubits)
    return passes(gates, n_qubits, x, precision)


def passes(gates, n_qubits: int, x: int, precision: str):
    """``gates`` applied to |x> one pass per gate, dispatched in gate order;
    the state is donated from pass to pass."""
    local = n_qubits - DEVICE_BITS
    if local < LANE_BITS + 1:
        raise ValueError("the reference needs more than 9 qubits")
    t, c, u = statevector.gate_table(gates)
    c, u = jnp.asarray(c), jnp.asarray(u)
    st = _basis(jnp.uint32(x), n_qubits=n_qubits)
    for i, q in enumerate(t.tolist()):
        st = _gate(st, c, u, jnp.int32(i), q=q, local=local, precision=precision)
    return _complex(st)
