"""Simulation tests: oracle goldens, staged engines vs reference, offloading."""

import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean env: deterministic fallback sweep
    from _hypothesis_compat import given, settings, st

from repro.core import gates as G
from repro.core import generators as gen
from repro.core.partition import partition
from repro.sim.apply import apply_matrix, embed_matrix, specialize_gate
from conftest import assert_states_close, planned_engine
from repro.sim.statevector import fidelity, simulate, simulate_np, zero_state


def test_ghz_golden():
    psi = np.asarray(simulate(gen.ghz(4)))
    expect = np.zeros(16, complex)
    expect[0] = expect[15] = 2**-0.5
    np.testing.assert_allclose(psi, expect, atol=1e-6)


def test_qft_uniform():
    psi = np.asarray(simulate(gen.qft(5)))
    np.testing.assert_allclose(np.abs(psi), 2**-2.5, atol=1e-6)


def test_wstate_golden():
    n = 5
    psi = np.asarray(simulate(gen.wstate(n)))
    onehot = [1 << q for q in range(n)]
    np.testing.assert_allclose(np.abs(psi[onehot]), n**-0.5, atol=1e-6)
    rest = np.delete(psi, onehot)
    assert np.abs(rest).max() < 1e-6


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_simulator_matches_unitary(seed):
    c = gen.random_circuit(5, 25, seed=seed)
    psi = simulate_np(c)
    np.testing.assert_allclose(psi, c.unitary()[:, 0], atol=1e-10)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_staged_executor_matches_reference(seed):
    c = gen.random_circuit(8, 40, seed=seed)
    ref = simulate(c)
    plan = partition(c, 5, 2, 1)
    out = planned_engine(c, plan).run()
    assert_states_close(out, ref)


@pytest.mark.parametrize("fam", ["qft", "qsvm", "ising", "ae", "dj", "graphstate"])
def test_staged_executor_families(fam):
    c = gen.FAMILIES[fam](9)
    ref = simulate(c)
    plan = partition(c, 6, 2, 1)
    out = planned_engine(c, plan).run()
    assert_states_close(out, ref)


def test_offload_matches_reference_and_saves_traffic():
    c = gen.qft(9)
    ref = np.asarray(simulate(c))
    plan = partition(c, 6, 3, 0)
    ex = planned_engine(c, plan, backend="offload")
    out = ex.run()
    assert_states_close(out, ref)
    # staged offloading must move far fewer shards than a per-gate
    # (QDAO-style) schedule, which streams every shard once per gate
    assert ex.stats["shard_transfers"] * 5 < c.n_gates * 2**plan.R


def test_specialize_gate_control():
    # CX with control bit non-local: v=0 -> identity, v=1 -> X
    m0, f0 = specialize_gate(G.CX, [1], [0])
    m1, f1 = specialize_gate(G.CX, [1], [1])
    np.testing.assert_allclose(m0, np.eye(2), atol=1e-12)
    np.testing.assert_allclose(m1, G.X, atol=1e-12)
    assert f0 == f1 == ()


def test_specialize_gate_antidiagonal_flip():
    m, flipped = specialize_gate(G.X, [0], [0])
    assert flipped == (0,)
    np.testing.assert_allclose(m, [[1.0]], atol=1e-12)
    # Y: |0> -> i|1>; stored bit 0 holds a = M[1,0] = i
    m, flipped = specialize_gate(G.Y, [0], [0])
    assert flipped == (0,)
    np.testing.assert_allclose(m, [[1j]], atol=1e-12)


def test_embed_matrix_matches_full():
    rng = np.random.default_rng(0)
    q, _ = np.linalg.qr(rng.normal(size=(4, 4)) + 1j * rng.normal(size=(4, 4)))
    emb = embed_matrix(q, [0, 2], 3)
    psi = rng.normal(size=8) + 1j * rng.normal(size=8)
    out = emb @ psi
    # compare against apply_matrix on the view
    view = jnp.asarray(psi).reshape(2, 2, 2)
    ref = apply_matrix(view, jnp.asarray(q), [0, 2]).reshape(-1)
    np.testing.assert_allclose(out, np.asarray(ref), atol=1e-6)


def test_plan_roundtrip_and_executor():
    from repro.core.partition import SimulationPlan

    c = gen.ising(9)
    plan = partition(c, 6, 2, 1)
    plan2 = SimulationPlan.from_json(plan.to_json())
    out = planned_engine(c, plan2).run()
    assert_states_close(out, simulate(c))
