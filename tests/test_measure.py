"""Measurement subsystem: cross-backend equivalence of samples, marginals
and Pauli expectations against the complex128 `simulate_np` oracle."""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import gates as G
from repro.core import generators as gen
from repro.core.partition import partition
from repro.sim import measure as M
from repro.sim.result import SimulationResult, index_to_bitstring
from repro.sim.statevector import simulate_np
from conftest import planned_engine

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

OBS = "Z0 Z1 + 0.5*X2 Y6 - 1.5*Y0 X3 + 2.0"
MARGINALS = [(0, 3, 5), (7, 1), (2,)]


def _flip_circuit(n=7, seed=5):
    """Random circuit ending in X/Y on every qubit: whichever qubits end
    non-local in the last stage carry pending lazy flips into measurement."""
    c = gen.random_circuit(n, 25, seed=seed)
    for q in range(n):
        c.add("x", q)
    c.add("y", 3)
    return c


FAMILY_CASES = {
    # name -> (circuit, n, L, R, G): qft + supremacy-style random + ZZ feature
    # map, all 3 tiers populated so the frame permutation is non-trivial
    "qft": (lambda: gen.qft(8), 8, 5, 2, 1),
    "random": (lambda: gen.random_circuit(8, 40, seed=3), 8, 5, 2, 1),
    "qsvm": (lambda: gen.FAMILIES["qsvm"](8), 8, 5, 2, 1),
    "flips": (_flip_circuit, 7, 4, 2, 1),
}


# ---------------------------------------------------------------- parsing
def test_pauli_parse():
    ps = M.PauliSum.parse("Z0 Z1 + 0.5*X2 Y3 - 2.0")
    assert len(ps.terms) == 3
    assert ps.terms[0] == M.PauliTerm(1.0, ((0, "Z"), (1, "Z")))
    assert ps.terms[1] == M.PauliTerm(0.5, ((2, "X"), (3, "Y")))
    assert ps.terms[2] == M.PauliTerm(-2.0, ())
    # bare pauli, sign-only coeff, I ops, case-insensitive
    assert M.PauliSum.parse("-X0").terms[0].coeff == -1.0
    assert M.PauliSum.parse("y2 I0").terms[0] == M.PauliTerm(1.0, ((2, "Y"),))
    with pytest.raises(ValueError):
        M.PauliSum.parse("Z0 Z0")
    with pytest.raises(ValueError):
        M.PauliSum.parse("Q3")


def _kron_expectation(psi, ps, n):
    I2 = np.eye(2)
    total = 0.0
    for t in ps.terms:
        mats = {q: {"X": G.X, "Y": G.Y, "Z": G.Z}[p] for q, p in t.ops}
        U = np.array([[1.0]])
        for q in range(n - 1, -1, -1):
            U = np.kron(U, mats.get(q, I2))
        total += t.coeff * float(np.real(np.vdot(psi, U @ psi)))
    return total


def test_expectation_np_matches_kron():
    n = 4
    psi = simulate_np(gen.random_circuit(n, 15, seed=1))
    for txt in ["Z0", "X1 Y2", "Z0 X2 Y3", "0.7*Z1 Z2 + 0.3*X3 - 1.0"]:
        ps = M.PauliSum.parse(txt)
        assert abs(M.expectation_np(psi, ps) - _kron_expectation(psi, ps, n)) < 1e-10


# ------------------------------------------------------------------ frame
def test_frame_roundtrip():
    frame = M.Frame(n=6, L=3, layout=(4, 0, 5, 2, 1, 3), flip_bits=(1, 4))
    idx = np.arange(64, dtype=np.int64)
    logical = frame.phys_to_logical(idx)
    assert sorted(logical.tolist()) == list(range(64))  # a bijection
    np.testing.assert_array_equal(frame.logical_to_phys(logical), idx)


# ------------------------------------------------------- dense vs oracles
def test_dense_measurer_matches_oracles():
    psi = simulate_np(gen.random_circuit(6, 30, seed=7))
    dm = M.DenseMeasurer(psi)
    assert abs(dm.expectation(OBS.replace("6", "5")) -
               M.expectation_np(psi, OBS.replace("6", "5"))) < 1e-10
    for qs in [(0, 2, 4), (5, 1), (3,)]:
        np.testing.assert_allclose(dm.marginal(qs), M.marginal_np(psi, qs),
                                   atol=1e-12)
    s1, s2 = dm.sample(128, seed=9), dm.sample(128, seed=9)
    np.testing.assert_array_equal(s1, s2)
    assert (dm.sample(128, seed=10) != s1).any()


# ------------------------------------------- cross-backend equivalence
@pytest.mark.parametrize("case", sorted(FAMILY_CASES))
def test_backend_equivalence(case):
    mk, n, L, R, Gb = FAMILY_CASES[case]
    c = mk()
    psi = simulate_np(c)
    plan = partition(c, L, R, Gb)
    obs = OBS if n > 6 else OBS.replace("6", "5")

    ex = planned_engine(c, plan)
    frame = ex.measurement_frame
    measurers = {
        "pjit": M.ShardedMeasurer(ex.run_packed(), frame),
    }
    off = planned_engine(c, plan, backend="offload")
    measurers["offload"] = M.StreamingMeasurer(
        off.run_packed(), off.measurement_frame
    )
    # dense oracle re-stored in the same frame: bit-for-bit comparable
    measurers["oracle"] = M.DenseMeasurer.with_frame(psi, frame)
    if case == "flips":
        assert frame.flip_bits, "flip case must exercise pending lazy flips"

    # expectations within 1e-5 of the complex128 pairing-identity oracle
    e_ref = M.expectation_np(psi, obs)
    for name, m in measurers.items():
        assert abs(m.expectation(obs) - e_ref) < 1e-5, name

    # marginals within 1e-5 (logical order, arbitrary subset order)
    for qs in MARGINALS:
        qs = tuple(q for q in qs if q < n)
        ref = M.marginal_np(psi, qs)
        for name, m in measurers.items():
            np.testing.assert_allclose(m.marginal(qs), ref, atol=1e-5,
                                       err_msg=f"{name} {qs}")

    # samples: reproducible under a fixed key; backends sharing the frame
    # produce the same stream (tiny tolerance for float32 CDF boundaries)
    samples = {k: m.sample(256, seed=0) for k, m in measurers.items()}
    np.testing.assert_array_equal(samples["pjit"],
                                  measurers["pjit"].sample(256, seed=0))
    for name in ("offload", "oracle"):
        assert (samples["pjit"] == samples[name]).mean() > 0.98, name

    # chi-square sanity of the sampled distribution vs oracle marginal
    ref3 = M.marginal_np(psi, (0, 1, 2))
    hist = np.bincount(samples["pjit"] & 7, minlength=8).astype(float)
    exp = 256 * ref3
    chi2 = float((((hist - exp) ** 2) / np.maximum(exp, 1e-12)).sum())
    assert chi2 < 40, chi2  # df=7; deterministic given the fixed key


def test_no_global_probability_vector_on_device_path():
    """Sampling must touch only shard masses + the locally sampled rows."""
    c = gen.qft(8)
    plan = partition(c, 5, 2, 1)
    ex = planned_engine(c, plan)
    m = M.ShardedMeasurer(ex.run_packed(), ex.measurement_frame)
    calls = []
    orig = m._local_probs
    m._local_probs = lambda s: (calls.append(s), orig(s))[1]
    m.sample(64, seed=0)
    assert len(calls) <= m.frame.n_shards  # one row per *distinct* shard
    assert len(set(calls)) == len(calls)


# ------------------------------------------------------------- entry point
def test_simulate_and_measure_api():
    res = M.simulate_and_measure(
        gen.qft(8), backend="pjit", L=5, R=2, G=1,
        shots=64, seed=7, marginals=[(0, 1, 2)],
        observables=["Z0 Z1 + 0.5*X2", "X0"])
    assert isinstance(res, SimulationResult)
    assert res.samples.shape == (64,)
    assert set(res.expectations) == {"1*Z0 Z1 + 0.5*X2", "1*X0"}
    # qft of |0..0> is the uniform superposition: every <Z...>=0, <X q>=1
    assert abs(res.expectations["1*Z0 Z1 + 0.5*X2"] - 0.5) < 1e-5
    assert abs(res.expectations["1*X0"] - 1.0) < 1e-5
    np.testing.assert_allclose(res.marginal((0, 1, 2)), np.full(8, 0.125),
                               atol=1e-5)
    bs = res.bitstrings()
    assert len(bs) == 64 and all(len(b) == 8 for b in bs)
    assert sum(res.counts().values()) == 64
    assert res.meta["n_stages"] >= 1


def test_result_helpers():
    r = SimulationResult(n_qubits=3, backend="ref", shots=4,
                         samples=np.array([5, 5, 2, 0]))
    assert index_to_bitstring(5, 3) == "101"
    assert r.counts() == {"101": 2, "010": 1, "000": 1}
    assert r.top(1) == [("101", 2)]
    assert r.probability_of("101") == 0.5


@pytest.mark.slow
def test_shardmap_measurement_equivalence():
    """shard_map backend measured in a subprocess with 8 virtual devices."""
    code = """
import numpy as np
from repro.core import generators as gen
from repro.core.partition import partition
from repro.sim import measure as M
from repro.sim.engine import engine_for
from repro.sim.statevector import simulate_np

c = gen.random_circuit(8, 40, seed=3)
psi = simulate_np(c)
plan = partition(c, 5, 2, 1)
ex = engine_for(c, 5, 2, 1, plan=plan, backend="shardmap", degrade=False)
m = M.ShardedMeasurer(ex.run_packed(), ex.measurement_frame)
obs = "Z0 Z1 + 0.5*X2 Y6 - 1.5*Y0 X3 + 2.0"
assert abs(m.expectation(obs) - M.expectation_np(psi, obs)) < 1e-5
np.testing.assert_allclose(m.marginal((0, 3, 5)), M.marginal_np(psi, (0, 3, 5)),
                           atol=1e-5)
s = m.sample(128, seed=0)
s_or = M.DenseMeasurer.with_frame(psi, ex.measurement_frame).sample(128, seed=0)
assert (s == s_or).mean() > 0.98
print('OK')
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, f"subprocess failed:\n{r.stdout}\n{r.stderr[-3000:]}"
    assert "OK" in r.stdout


# ---------------------------------------------------------- shot determinism
def test_shot_streams_deterministic_across_measurers():
    """Same seed + same state array => bit-identical shot streams from the
    Dense, Sharded and Streaming measurers (and stable across reruns).

    This pins the fix for a real divergence: shard masses / local CDFs used
    to be computed with jnp float32 reductions on some measurers and numpy
    float64 on others, so a uniform draw landing between the two CDFs picked
    different outcomes. All measurers now share one mass kernel and one
    host-side float64 probability path.
    """
    import jax.numpy as jnp

    c, n, L, R, Gq = (lambda: gen.random_circuit(8, 40, seed=3))(), 8, 5, 2, 1
    plan = partition(c, L, R, Gq)
    from repro.sim.engine import ExecutionEngine

    eng = ExecutionEngine(c, plan, backend="offload")
    state = np.ascontiguousarray(eng.run_packed())  # complex64 host array
    frame = eng.measurement_frame

    dense = M.DenseMeasurer(state.copy(), frame)
    sharded = M.ShardedMeasurer(jnp.asarray(state), frame)
    streaming = M.StreamingMeasurer(state.copy(), frame)

    # the CDF inputs must be BIT-identical, not merely close: a uniform draw
    # landing between two almost-equal CDFs silently picks different outcomes
    m_ref = dense._shard_masses()
    np.testing.assert_array_equal(sharded._shard_masses(), m_ref)
    np.testing.assert_array_equal(streaming._shard_masses(), m_ref)
    for s in range(frame.n_shards):
        lp_ref = dense._local_probs(s)
        np.testing.assert_array_equal(sharded._local_probs(s), lp_ref)
        np.testing.assert_array_equal(streaming._local_probs(s), lp_ref)

    shots = 4096
    ref = dense.sample(shots, seed=123)
    np.testing.assert_array_equal(sharded.sample(shots, seed=123), ref)
    np.testing.assert_array_equal(streaming.sample(shots, seed=123), ref)
    # rerun determinism
    np.testing.assert_array_equal(dense.sample(shots, seed=123), ref)
    # different seed => (overwhelmingly) different stream
    assert (dense.sample(shots, seed=124) != ref).any()


def test_measure_batch_shot_determinism():
    """measure_batch element b must reproduce a direct measurer on the same
    packed state with seed+b — across reruns and measurer kinds."""
    from repro.sim.engine import ExecutionEngine

    c = gen.qft(8)
    plan = partition(c, 5, 2, 1)
    eng = ExecutionEngine(c, plan, backend="offload")
    B = 3
    psi0s = np.zeros((B, 2**8), dtype=np.complex64)
    psi0s[np.arange(B), np.arange(B)] = 1.0
    results = M.measure_batch(eng, psi0s, shots=256, seed=7)
    again = M.measure_batch(eng, psi0s, shots=256, seed=7)
    states = eng.run_batch(psi0s, apply_final=False)
    frame = eng.measurement_frame
    for b in range(B):
        np.testing.assert_array_equal(results[b].samples, again[b].samples)
        direct = M.measurer_for(np.ascontiguousarray(states[b]), frame)
        np.testing.assert_array_equal(
            direct.sample(256, seed=7 + b), results[b].samples)
