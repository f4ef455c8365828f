"""Gate-by-gate state-vector simulation on the device.

Every gate the benchmark's circuits use is a one-qubit matrix ``U`` on a
target qubit, applied where an optional control qubit is 1 (``h``, ``ry``,
``rz``, ``u3``; ``cx`` and ``cp`` with their control). Each target qubit
has one jitted pass, whatever the gate's matrix and control, so a circuit
of n qubits compiles n programs. The state is planar float32, lane-dense
``[2^(n-7), 128]`` real and imaginary parts, and each gate is one
elementwise pass over it:

* a target row bit splits the rows into ``(.., 2, stride, 128)`` halves,
  which the pass combines;
* a target lane bit finds each amplitude's partner by a product with the
  128 x 128 permutation that flips the bit (exact at ``HIGHEST``: every
  term is an amplitude times 1 or 0), then combines.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LANE_BITS = 7
LANES = 1 << LANE_BITS

_S = 1.0 / math.sqrt(2.0)


def _one_qubit(name: str, params) -> np.ndarray:
    if name == "h":
        return np.array([[_S, _S], [_S, -_S]], np.complex128)
    if name == "ry":
        c, s = math.cos(params[0] / 2), math.sin(params[0] / 2)
        return np.array([[c, -s], [s, c]], np.complex128)
    if name == "rz":
        return np.diag([np.exp(-0.5j * params[0]), np.exp(0.5j * params[0])])
    if name == "u3":
        theta, phi, lam = params
        c, s = math.cos(theta / 2), math.sin(theta / 2)
        return np.array([[c, -np.exp(1j * lam) * s],
                         [np.exp(1j * phi) * s, np.exp(1j * (phi + lam)) * c]])
    if name == "cx":
        return np.array([[0, 1], [1, 0]], np.complex128)
    if name == "cp":
        return np.diag([1.0, np.exp(1j * params[0])]).astype(np.complex128)
    raise ValueError(f"the reference has no gate {name!r}")


def gate_table(gates):
    """(targets, controls (-1: none), matrices [G, 2, 2, 2] float32 with
    the real and imaginary parts on the last axis)."""
    t, c, u = [], [], []
    for name, qubits, params in gates:
        if len(qubits) not in (1, 2) or (len(qubits) == 2) != (name in ("cx", "cp")):
            raise ValueError(f"the reference cannot apply {name} on {qubits}")
        t.append(qubits[0])
        c.append(qubits[1] if len(qubits) == 2 else -1)
        m = _one_qubit(name, params)
        u.append(np.stack([m.real, m.imag], axis=-1))
    return (np.asarray(t, np.int32), np.asarray(c, np.int32),
            np.asarray(u, np.float32))


def _split(v):
    """bf16 head and tail of an f32 array: v ~ head + tail to ~16 bits."""
    head = v.astype(jnp.bfloat16).astype(jnp.float32)
    return head, (v - head).astype(jnp.bfloat16).astype(jnp.float32)


def _mul(a, b, precision: str):
    """a * b in f32 (``highest``), or with three bf16 products as a TPU's
    ``Precision.HIGH`` computes it (``high``)."""
    if precision == "highest":
        return a * b
    if precision != "high":
        raise ValueError(f"precision {precision!r}")
    ah, al = _split(a)
    bh, bl = _split(b)
    return ah * bh + (ah * bl + al * bh)


def _cmul(u, xr, xi, precision: str):
    """Planar complex product of the matrix entry ``u`` ([2]: re, im) with
    the amplitudes ``(xr, xi)``."""
    return (_mul(u[0], xr, precision) - _mul(u[1], xi, precision),
            _mul(u[0], xi, precision) + _mul(u[1], xr, precision))


def _control(c, row, lane):
    """Where control bit ``c`` (traced; ``c < 0``: none) is 1."""
    cc = jnp.maximum(c, 0)
    bit = jnp.where(cc < LANE_BITS, (lane >> cc) & 1,
                    (row >> jnp.maximum(cc - LANE_BITS, 0)) & 1)
    return (c < 0) | (bit == 1)


def _apply(q: int, precision: str, st, c, u):
    """Gate ``u`` on target ``q`` where control ``c`` is 1."""
    xr, xi = st
    rows = xr.shape[0]
    if q >= LANE_BITS:
        s = 1 << (q - LANE_BITS)
        view = (rows // (2 * s), 2, s, LANES)
        ar, ai = xr.reshape(view), xi.reshape(view)
        x0, x1 = (ar[:, 0], ai[:, 0]), (ar[:, 1], ai[:, 1])
        shape = ar[:, 0].shape
        row = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * (2 * s)
               + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
        on = _control(c, row, jax.lax.broadcasted_iota(jnp.int32, shape, 2))
        outs = []
        for r in (0, 1):
            a = _cmul(u[r, 0], *x0, precision)
            b = _cmul(u[r, 1], *x1, precision)
            keep = x0 if r == 0 else x1
            outs.append(tuple(jnp.where(on, p + q_, k)
                              for p, q_, k in zip(a, b, keep)))
        return tuple(jnp.stack([o0, o1], axis=1).reshape(rows, LANES)
                     for o0, o1 in zip(*outs))
    idx = np.arange(LANES)
    perm = np.zeros((LANES, LANES), np.float32)
    perm[idx ^ (1 << q), idx] = 1.0
    pr, pi = (jnp.matmul(v, perm, precision=jax.lax.Precision.HIGHEST)
              for v in (xr, xi))
    row = jax.lax.broadcasted_iota(jnp.int32, xr.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, xr.shape, 1)
    bt = ((lane >> q) & 1) == 1
    on = _control(c, row, lane)
    # where bit q is r: out = U[r, r] x + U[r, 1 - r] partner
    outs = []
    for r in (0, 1):
        a = _cmul(u[r, r], xr, xi, precision)
        b = _cmul(u[r, 1 - r], pr, pi, precision)
        outs.append((a[0] + b[0], a[1] + b[1]))
    return tuple(jnp.where(on, jnp.where(bt, o1, o0), k)
                 for o0, o1, k in zip(outs[0], outs[1], (xr, xi)))


@partial(jax.jit, static_argnames=("q", "precision"), donate_argnums=(0,))
def _gate(st, c, u, i, *, q: int, precision: str):
    return _apply(q, precision, st, c[i], u[i])


@partial(jax.jit, static_argnames=("n_qubits",))
def _basis(x, *, n_qubits: int):
    rows = 1 << (n_qubits - LANE_BITS)
    xr = jnp.zeros((rows, LANES), jnp.float32).at[
        x >> LANE_BITS, x & (LANES - 1)].set(1)
    return xr, jnp.zeros_like(xr)


@jax.jit
def _complex(st):
    return jax.lax.complex(*st)


def state(gates, n_qubits: int, x: int, precision: str = "highest"):
    """One jitted pass per gate, dispatched in gate order; the state is
    donated from pass to pass, so the device holds it once plus one pass's
    temporaries."""
    if n_qubits < LANE_BITS + 1:
        raise ValueError("the reference needs more than 7 qubits")
    t, c, u = gate_table(gates)
    c, u = jnp.asarray(c), jnp.asarray(u)
    st = _basis(jnp.int32(x), n_qubits=n_qubits)
    for i, q in enumerate(t.tolist()):
        st = _gate(st, c, u, jnp.int32(i), q=q, precision=precision)
    return _complex(st)
