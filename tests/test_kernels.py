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


# --------------------------------------------------------- shm block sizing

MAT_BYTES = 2 * 128 * 128 * 4  # one lowered lane matrix, planar f32


@pytest.mark.parametrize("n_mats,period", [(90, 8), (175, 64)])
def test_shm_block_lane_heavy_group_gets_a_tall_block(n_mats, period):
    """Lane matrices that crowd the block out of the shared budget (as in
    su2random at n = 28) get VMEM of their own: a tall block, chunks of at
    least MIN_CHUNK_ROWS, and a VMEM request under the chip's cap."""
    from repro.kernels.layout import VMEM_CAP_BYTES
    from repro.kernels.shm import MIN_CHUNK_ROWS, shm_block
    s = shm_block(1 << 21, 128, period, n_mats * MAT_BYTES)
    assert s.shared_block <= 64
    assert s.block >= 512 and s.block % s.chunk == 0
    assert s.chunk >= MIN_CHUNK_ROWS and s.chunk % period == 0
    assert s.vmem_limit >= s.operand_bytes
    assert s.vmem_limit <= VMEM_CAP_BYTES


@pytest.mark.parametrize("operand_mb,period", [(1.0, 8), (1.3, 32), (1.5, 8)])
def test_shm_block_light_group_keeps_its_block(operand_mb, period):
    """A group whose operands fit beside its block (qft at n = 28: 1.0-1.3
    MB, up to 3 MB double buffered) keeps the block the shared budget
    gives, run unchunked."""
    from repro.kernels.shm import shm_block
    s = shm_block(1 << 21, 128, period, int(operand_mb * 2**20))
    assert s.block == s.shared_block == s.chunk == 1024


@pytest.mark.parametrize("block_m", [8, 64, 256])
def test_shm_block_explicit_block_is_honoured(block_m):
    from repro.kernels.shm import shm_block
    s = shm_block(1 << 21, 128, 8, 90 * MAT_BYTES, block_m=block_m)
    assert s.block == s.chunk == block_m


def test_shm_block_operands_past_the_cap_share_the_budget():
    """Operands too large to sit beside the tall block fall back to the
    block sized against operands and blocks together."""
    from repro.kernels.layout import VMEM_CAP_BYTES
    from repro.kernels.shm import shm_block
    s = shm_block(1 << 21, 128, 8, VMEM_CAP_BYTES - (2 << 20))
    assert s.block == s.chunk == s.shared_block == 8


def _chunked_group(rng, r, n_mixed):
    """Members on a window of 7 lanes and r row bits: lane/row pairs (the
    operand load that chunks the block), lane-only runs, row-only gates and
    diagonals."""
    top = 7 + r - 1
    gates = [((0,), G.H), ((1,), G.gate_matrix("ry", [0.3])),
             ((7, top), G.CX), ((top,), G.gate_matrix("rx", [0.9])),
             ((2, top), np.exp(1j * rng.uniform(0, 6, 4)).astype(np.complex64)),
             ((7,), np.exp(1j * rng.uniform(0, 6, 2)).astype(np.complex64))]
    for i in range(n_mixed):
        gates.append(((i % 7, 7 + i % r), _rand_unitary(rng, 2)))
        if i % 4 == 3:
            gates.append(((i % 7,), _rand_unitary(rng, 1)))
    return [(b, jnp.asarray(np.asarray(m), jnp.complex64)) for b, m in gates]


@pytest.mark.parametrize("r", [3, 6])
def test_shm_chunked_block_matches_unchunked_and_ref(r):
    """A block run in several chunks gives exactly the state of the same
    group run one chunk per block, and matches the reference. A chunk holds
    at least eight rows of each of the window's 2^r row values."""
    from repro.kernels.shm import MIN_CHUNK_ROWS
    rng = np.random.default_rng(r)
    chunk = max(MIN_CHUNK_ROWS, 8 << r)
    a, M = 7 + r, 4 * chunk
    gates = _chunked_group(rng, r, 12)
    sre = jnp.asarray(rng.normal(size=(M, 128)).astype(np.float32))
    sim = jnp.asarray(rng.normal(size=(M, 128)).astype(np.float32))
    seen = []
    o_re, o_im = shm_apply(sre, sim, gates, a, interpret=True,
                           record=seen.append)
    (s,) = seen
    # several chunks in a block: four at r = 3, two in each of two blocks
    # at r = 6 (the block's VMEM budget stops at 1024 rows)
    assert s.block == min(M, 1024) and s.chunk == chunk, s
    u_re, u_im = shm_apply(sre, sim, gates, a, block_m=s.chunk,
                           interpret=True, record=seen.append)
    assert seen[-1].block == seen[-1].chunk == s.chunk
    np.testing.assert_array_equal(np.asarray(o_re), np.asarray(u_re))
    np.testing.assert_array_equal(np.asarray(o_im), np.asarray(u_im))
    dense = [(b, jnp.diag(m) if m.ndim == 1 else m) for b, m in gates]
    r_re, r_im = shm_apply_ref(sre.reshape(-1, 1 << a), sim.reshape(-1, 1 << a),
                               dense, a)
    np.testing.assert_allclose(np.asarray(o_re).reshape(r_re.shape),
                               np.asarray(r_re), atol=1e-4)
    np.testing.assert_allclose(np.asarray(o_im).reshape(r_im.shape),
                               np.asarray(r_im), atol=1e-4)


# ------------------------------------------- shm members by window row value


def _row_value_group(rng, r, J, h, n_mixed):
    """Members on a window of 7 lanes and r row bits around ``n_mixed``
    lane/row members with h row bits (row bit ``J`` first): lane-only runs
    between them, row-only gates and diagonals."""
    rows = [J, (J + 1) % r][:h]
    gates = [((0,), G.H), ((1,), G.gate_matrix("ry", [0.3])),
             ((3, 7 + J), np.exp(1j * rng.uniform(0, 6, 4)).astype(np.complex64))]
    if r > 1:
        gates.append(((7 + J, 7 + (J + 1) % r), G.CX))
    for i in range(n_mixed):
        gates.append(((i % 7,) + tuple(7 + b for b in rows),
                      _rand_unitary(rng, 1 + h)))
        gates.append(((7 + (J + i) % r,), G.gate_matrix("rx", [0.4 + i])))
        gates.append((((i + 2) % 7,), _rand_unitary(rng, 1)))
    gates.append(((7 + r - 1,), np.exp(1j * rng.uniform(0, 6, 2)).astype(np.complex64)))
    return [(b, jnp.asarray(np.asarray(m), jnp.complex64)) for b, m in gates]


@pytest.mark.parametrize("r,J,h", [(r, J, h) for r in (1, 3, 6)
                                   for J in sorted({0, r - 1})
                                   for h in (1, 2) if h <= r])
def test_shm_row_value_members_match_ref(r, J, h):
    """Lane/row members on the lowest and the highest window row bit, with
    one and two row bits, run on the rows of each row value: chunked and
    unchunked blocks give the same state, and it matches the reference."""
    rng = np.random.default_rng(10 * r + J + h)
    a, M = 7 + r, 1024
    gates = _row_value_group(rng, r, J, h, 6 if h == 1 else 2)
    sre = jnp.asarray(rng.normal(size=(M, 128)).astype(np.float32))
    sim = jnp.asarray(rng.normal(size=(M, 128)).astype(np.float32))
    seen = []
    o_re, o_im = shm_apply(sre, sim, gates, a, interpret=True,
                           record=seen.append)
    assert seen[0].chunk < seen[0].block, seen[0]  # chunked
    u_re, u_im = shm_apply(sre, sim, gates, a, block_m=seen[0].chunk,
                           interpret=True, record=seen.append)
    assert seen[1].chunk == seen[1].block  # unchunked
    np.testing.assert_array_equal(np.asarray(o_re), np.asarray(u_re))
    np.testing.assert_array_equal(np.asarray(o_im), np.asarray(u_im))
    dense = [(b, jnp.diag(m) if m.ndim == 1 else m) for b, m in gates]
    r_re, r_im = shm_apply_ref(sre.reshape(-1, 1 << a), sim.reshape(-1, 1 << a),
                               dense, a)
    np.testing.assert_allclose(np.asarray(o_re).reshape(r_re.shape),
                               np.asarray(r_re), atol=1e-4)
    np.testing.assert_allclose(np.asarray(o_im).reshape(r_im.shape),
                               np.asarray(r_im), atol=1e-4)


def test_shm_block_stats_count_products_and_mixed_members():
    """Four lane/row members with one row bit (two whole-chunk products
    each) and one lane-only run (one) make 9 products and 4 mixed members;
    row-only gates and diagonals run none."""
    from repro.kernels import ops as kops

    rng = np.random.default_rng(3)
    gates = [((0,), G.H), ((1,), G.T), ((8,), G.H), ((7, 8), G.CX),
             ((2, 7), np.exp(1j * rng.uniform(0, 6, 4)).astype(np.complex64))]
    gates += [((i, 7 + i % 2), _rand_unitary(rng, 2)) for i in range(4)]
    x = jnp.asarray((rng.normal(size=(64, 128)) + 1j * rng.normal(size=(64, 128)))
                    .astype(np.complex64))
    kops.reset_kernel_counters()
    kops.shm_kernel(x, [(b, jnp.asarray(np.asarray(m), jnp.complex64))
                        for b, m in gates], 9)
    stats = kops.shm_block_stats()
    assert (stats["calls"], stats["products"], stats["mixed"]) == (1, 9, 4)
