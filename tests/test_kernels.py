"""Pallas kernel tests: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode on CPU; same code lowers to Mosaic on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean env: deterministic fallback sweep
    from _hypothesis_compat import given, settings, st

from repro.core import gates as G
from repro.kernels.fusion import fused_matmul
from repro.kernels.ops import lane_matmul
from repro.kernels.ref import fused_matmul_ref, shm_apply_ref
from repro.kernels.shm import shm_apply
from repro.sim.apply import apply_matrix
from repro.sim.lanes import apply_shm_group, apply_unitary


def _rand_unitary(rng, k):
    q, _ = np.linalg.qr(rng.normal(size=(2**k, 2**k)) + 1j * rng.normal(size=(2**k, 2**k)))
    return q


@pytest.mark.parametrize("k", [1, 2, 3, 5, 7])
@pytest.mark.parametrize("block_m", [32, 128])
def test_fused_matmul_sweep(k, block_m):
    rng = np.random.default_rng(k)
    M, K = 128, 2**k
    sre = rng.normal(size=(M, K)).astype(np.float32)
    sim = rng.normal(size=(M, K)).astype(np.float32)
    u = _rand_unitary(rng, k)
    ure, uim = np.real(u).astype(np.float32), np.imag(u).astype(np.float32)
    o_re, o_im = fused_matmul(
        jnp.array(sre), jnp.array(sim), jnp.array(ure), jnp.array(uim),
        block_m=block_m, interpret=True,
    )
    r_re, r_im = fused_matmul_ref(jnp.array(sre), jnp.array(sim),
                                  jnp.array(ure), jnp.array(uim))
    np.testing.assert_allclose(np.asarray(o_re), np.asarray(r_re), atol=1e-4)
    np.testing.assert_allclose(np.asarray(o_im), np.asarray(r_im), atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(
    k=st.integers(1, 4),
    logm=st.integers(3, 7),
    block_log=st.integers(3, 5),
    seed=st.integers(0, 100),
)
def test_fused_matmul_property(k, logm, block_log, seed):
    rng = np.random.default_rng(seed)
    M, K = 2**logm, 2**k
    bm = min(2**block_log, M)
    sre = rng.normal(size=(M, K)).astype(np.float32)
    sim = rng.normal(size=(M, K)).astype(np.float32)
    u = _rand_unitary(rng, k)
    o_re, o_im = fused_matmul(
        jnp.array(sre), jnp.array(sim),
        jnp.array(np.real(u), dtype=jnp.float32), jnp.array(np.imag(u), dtype=jnp.float32),
        block_m=bm, interpret=True,
    )
    r_re, r_im = fused_matmul_ref(
        jnp.array(sre), jnp.array(sim),
        jnp.array(np.real(u), dtype=jnp.float32), jnp.array(np.imag(u), dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(o_re), np.asarray(r_re), atol=1e-4)
    np.testing.assert_allclose(np.asarray(o_im), np.asarray(r_im), atol=1e-4)


def test_shm_kernel_vs_ref():
    rng = np.random.default_rng(1)
    a = 5
    gates = [
        ((0,), G.H), ((1, 3), G.CX), ((2,), G.T),
        ((0, 4), G.gate_matrix("cp", [0.7])), ((1,), G.X), ((2, 4), G.SWAP),
    ]
    M = 32
    sre = rng.normal(size=(M, 1 << a)).astype(np.float32)
    sim = rng.normal(size=(M, 1 << a)).astype(np.float32)
    o_re, o_im = shm_apply(jnp.array(sre), jnp.array(sim), gates, a,
                           block_m=8, interpret=True)
    r_re, r_im = shm_apply_ref(jnp.array(sre), jnp.array(sim), gates, a)
    np.testing.assert_allclose(np.asarray(o_re), np.asarray(r_re), atol=1e-4)
    np.testing.assert_allclose(np.asarray(o_im), np.asarray(r_im), atol=1e-4)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 1000))
def test_apply_fused_shard_property(seed):
    rng = np.random.default_rng(seed)
    L, k = 15, 3  # targets in the lanes or routed in from the rows
    psi = (rng.normal(size=2**L) + 1j * rng.normal(size=2**L)).astype(np.complex64)
    bits = sorted(rng.choice(L, size=k, replace=False).tolist())
    u = _rand_unitary(rng, k).astype(np.complex64)
    view = jnp.asarray(psi).reshape((2,) * L)
    # the engine's Pallas path: targets routed into the lanes, MXU kernel
    out = apply_unitary(jnp.asarray(psi).reshape(-1, 1 << min(L, 7)),
                        jnp.asarray(u), bits, L, lane_matmul=lane_matmul)
    ref = apply_matrix(view, jnp.asarray(u), bits)
    np.testing.assert_allclose(np.asarray(out).reshape(-1),
                               np.asarray(ref).reshape(-1), atol=1e-4)


def test_apply_shm_shard_matches_sequential():
    rng = np.random.default_rng(2)
    L, a = 8, 4
    psi = (rng.normal(size=2**L) + 1j * rng.normal(size=2**L)).astype(np.complex64)
    gates = [((0,), G.H), ((1, 2), G.CX), ((3,), G.gate_matrix("rz", [0.3]))]
    view = jnp.asarray(psi).reshape((2,) * L)
    out = apply_shm_group(jnp.asarray(psi).reshape(-1, 1 << min(L, 7)),
                          [(b, jnp.asarray(m)) for b, m in gates], range(a))
    ref = view
    for bits, mat in gates:
        ref = apply_matrix(ref, jnp.asarray(np.asarray(mat).astype(np.complex64)),
                           list(bits))
    np.testing.assert_allclose(np.asarray(out).reshape(-1),
                               np.asarray(ref).reshape(-1), atol=1e-4)
