"""Parameterized circuits: structure/parameter split through the whole stack.

Covers the PR's acceptance criteria directly:

* rebinding parameters on a cached engine performs ZERO ILP/DP solves and
  ZERO new XLA traces (asserted via ``staging.SOLVER_CALLS`` /
  ``kernelization.SOLVER_CALLS`` / ``engine.xla_compiles``);
* bound-parameter execution is oracle-equivalent to eagerly-built circuits
  across backends (pallas on/off), including under ``run_sweep`` batching;
* `Param` algebra, `Circuit.bind`, structural fingerprints, JSON round-trips
  and the structural `CircuitKey`/`engine_for` rebinding path.
"""

import jax
import numpy as np
import pytest

from conftest import assert_states_close

from repro.core import generators as gen
from repro.core import kernelization, staging
from repro.core.circuit import Circuit
from repro.core.gates import Param, UnboundParameterError
from repro.core.partition import partition
from repro.sim import measure as M
from repro.sim.compile import bind_tensors, compile_plan
from repro.sim.engine import CircuitKey, CompileCache, ExecutionEngine, engine_for
from repro.sim.statevector import simulate_np

from strategies import SHM_CM  # shared shm-forcing cost model


def _ansatz(n, vals=None):
    """Small entangling ansatz; symbolic when ``vals`` is None. Uses affine
    Param reuse (0.5 * t_q) so sharing/scaling goes through the whole stack."""
    c = Circuit(n)
    for q in range(n):
        c.add("ry", q, params=[Param(f"t{q}") if vals is None else vals[q]])
    for q in range(n - 1):
        c.add("cx", q + 1, q)
    for q in range(n):
        c.add("rz", q,
              params=[Param(f"t{q}") * 0.5 if vals is None else 0.5 * vals[q]])
    c.add("h", 0)
    return c


def _vals(n, seed):
    return list(np.random.default_rng(seed).uniform(0.0, 2 * np.pi, n))


def _solve_counts():
    return (staging.SOLVER_CALLS["ilp"], staging.SOLVER_CALLS["greedy"],
            kernelization.SOLVER_CALLS["dp"])


# ------------------------------------------------------------- core/ Param
def test_param_algebra_and_bind():
    p = -Param("t") * 0.5 + 1.0
    assert p.resolve({"t": 2.0}) == 0.0
    c = _ansatz(3)
    assert c.param_names == ("t0", "t1", "t2")
    assert not c.is_bound
    with pytest.raises(UnboundParameterError):
        c.gates[0].matrix
    with pytest.raises(UnboundParameterError):
        c.bind({"t0": 1.0})  # missing values
    with pytest.raises(ValueError):
        c.bind({"t0": 1.0, "t1": 1.0, "t2": 1.0, "nope": 2.0})
    b1 = c.bind({"t0": 0.1, "t1": 0.2, "t2": 0.3})
    b2 = c.bind([0.1, 0.2, 0.3])  # flat vector, param_names order
    assert b1.is_bound and b1.binding_signature() == b2.binding_signature()
    assert b1.gates[c.n_gates - 2].params[0] == pytest.approx(0.15)  # 0.5*t2


def test_structure_fingerprint_ignores_angles():
    a, b = _ansatz(4, _vals(4, 0)), _ansatz(4, _vals(4, 1))
    sym = _ansatz(4)
    assert a.structure_fingerprint() == b.structure_fingerprint() \
        == sym.structure_fingerprint()
    other = _ansatz(4, _vals(4, 0))
    other.add("h", 1)
    assert other.structure_fingerprint() != a.structure_fingerprint()


def test_symbolic_json_roundtrip():
    c = _ansatz(3)
    c2 = Circuit.from_json(c.to_json())
    assert c2.param_names == c.param_names
    assert c2.to_json() == c.to_json()
    # scale survives the round trip
    vals = {"t0": 0.3, "t1": 0.5, "t2": 0.7}
    assert c2.bind(vals).binding_signature() == c.bind(vals).binding_signature()


# --------------------------------------------------- compile: binding pass
def test_bind_tensors_matches_eager_compile():
    sym = _ansatz(5)
    plan = partition(sym, 4, 1, 0)
    cc = compile_plan(sym, plan)
    assert cc.needs_binding
    vals = dict(zip(sym.param_names, _vals(5, 2)))
    table = bind_tensors(sym.bind(vals), plan, expect=cc)
    eager = compile_plan(sym.bind(vals), plan)
    assert not eager.needs_binding
    for prog in eager.programs:
        for op in prog.ops:
            for o in (op,) + op.gates:
                if o.tensor.size:
                    np.testing.assert_array_equal(table[o.uid], o.tensor)


def test_bind_tensors_rejects_structure_mismatch():
    sym = _ansatz(5)
    plan = partition(sym, 4, 1, 0)
    cc = compile_plan(sym, plan)
    other = _ansatz(5, _vals(5, 3))
    other.add("h", 2)
    other_plan = partition(other, 4, 1, 0)
    with pytest.raises(ValueError):
        bind_tensors(other, other_plan, expect=cc)


# ------------------------------------------ serving: rebind without recompile
@pytest.mark.parametrize("backend", ["pjit", "offload", "dense"])
def test_rebind_zero_solves_zero_xla(backend):
    """THE acceptance bar: a structural cache hit with new angles re-runs
    neither ILP staging, nor DP kernelization, nor XLA tracing."""
    n = 6
    cache = CompileCache()
    e1 = engine_for(_ansatz(n, _vals(n, 0)), 4, 2, 0, backend=backend,
                    cache=cache)
    outA = np.asarray(e1.run())
    solves0, xla0 = _solve_counts(), e1.xla_compiles
    for seed in (1, 2):
        vals = _vals(n, seed)
        e2 = engine_for(_ansatz(n, vals), 4, 2, 0, backend=backend, cache=cache)
        assert e2 is e1, "same structure must hit the cache"
        out = np.asarray(e2.run())
        assert_states_close(out, simulate_np(_ansatz(n, vals)),
                            msg=f"{backend} seed={seed}")
    assert _solve_counts() == solves0, "rebinding re-ran ILP/DP"
    assert e1.xla_compiles == xla0, "rebinding re-traced XLA"
    assert cache.misses == 1 and cache.hits == 2
    # first binding still correct after rebinds (no aliasing of tensors)
    assert_states_close(outA, simulate_np(_ansatz(n, _vals(n, 0))))


def test_rebind_pallas_shm_operands():
    """Rebinding flows through Pallas shm-group operands too (tensors are
    pallas_call inputs, not trace constants)."""
    n = 7
    sym = _ansatz(n)
    plan = partition(sym, 5, 2, 0, cost_model=SHM_CM)
    eng = ExecutionEngine(sym, plan, backend="pjit", use_pallas=True)
    assert any(op.kind == "shm" for p in eng.cc.programs for op in p.ops), \
        "test must exercise the shm path"
    vals1, vals2 = _vals(n, 4), _vals(n, 5)
    eng.bind(dict(zip(sym.param_names, vals1)))
    out1 = np.asarray(eng.run())
    xla0 = eng.xla_compiles
    eng.bind(dict(zip(sym.param_names, vals2)))
    out2 = np.asarray(eng.run())
    assert eng.xla_compiles == xla0
    assert_states_close(out1, simulate_np(_ansatz(n, vals1)))
    assert_states_close(out2, simulate_np(_ansatz(n, vals2)))


def test_unbound_engine_refuses_to_run():
    sym = _ansatz(4)
    plan = partition(sym, 4, 0, 0)
    eng = ExecutionEngine(sym, plan, backend="pjit")
    with pytest.raises(UnboundParameterError):
        eng.run()
    eng.bind(dict(zip(sym.param_names, _vals(4, 6))))
    eng.run()  # now fine


# -------------------------------------------------------------- run_sweep
@pytest.mark.parametrize("backend", ["pjit", "offload", "dense"])
def test_run_sweep_oracle_equivalence(backend):
    n = 6
    sym = _ansatz(n)
    plan = partition(sym, 4, 2, 0)
    eng = ExecutionEngine(sym, plan, backend=backend)
    P = 3
    batch = np.stack([_vals(n, s) for s in (7, 8, 9)])
    batch[2] = 0.0  # special angles: identity rotations must stay valid
    outs = np.asarray(eng.run_sweep(None, batch))
    assert outs.shape == (P, 2**n)
    for p in range(P):
        assert_states_close(outs[p], simulate_np(_ansatz(n, list(batch[p]))),
                            msg=f"{backend} point={p}")
    # sweeping after a sweep re-traces nothing
    xla0 = eng.xla_compiles
    eng.run_sweep(None, batch + 0.1)
    assert eng.xla_compiles == xla0


def test_run_sweep_pallas():
    n = 7
    sym = _ansatz(n)
    plan = partition(sym, 5, 2, 0, cost_model=SHM_CM)
    eng = ExecutionEngine(sym, plan, backend="pjit", use_pallas=True)
    batch = np.stack([_vals(n, s) for s in (10, 11)])
    outs = np.asarray(eng.run_sweep(None, batch))
    for p in range(2):
        assert_states_close(outs[p], simulate_np(_ansatz(n, list(batch[p]))))


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs 4 devices (multi-device CI job)")
def test_run_sweep_pjit_with_mesh_falls_back():
    """With a real mesh, vmapping the sharding-constrained loop is invalid —
    the engine must take the sequential-rebind path (and stay correct)."""
    n = 6
    sym = _ansatz(n)
    plan = partition(sym, 4, 2, 0)
    mesh = jax.make_mesh((1, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    eng = ExecutionEngine(sym, plan, backend="pjit", mesh=mesh)
    assert not eng.backend.supports_fused_sweep()
    batch = np.stack([_vals(n, s) for s in (20, 21)])
    outs = np.asarray(eng.run_sweep(None, batch))
    for p in range(2):
        assert_states_close(outs[p], simulate_np(_ansatz(n, list(batch[p]))))


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="shardmap needs 4 devices (multi-device CI job)")
def test_run_sweep_shardmap():
    n = 6
    sym = _ansatz(n)
    plan = partition(sym, 4, 2, 0)
    eng = ExecutionEngine(sym, plan, backend="shardmap")
    batch = np.stack([_vals(n, s) for s in (12, 13)])
    outs = np.asarray(eng.run_sweep(None, batch))
    xla0 = eng.xla_compiles
    for p in range(2):
        assert_states_close(outs[p], simulate_np(_ansatz(n, list(batch[p]))))
    eng.run_sweep(None, batch + 0.2)
    assert eng.xla_compiles == xla0


def test_measure_sweep_and_params_kwarg():
    n = 6
    sym = _ansatz(n)
    plan = partition(sym, 4, 2, 0)
    eng = ExecutionEngine(sym, plan, backend="offload")
    batch = np.stack([_vals(n, s) for s in (14, 15)])
    results = M.measure_sweep(eng, batch, shots=128, seed=3,
                              observables=["Z0 Z1"])
    assert len(results) == 2
    for p in range(2):
        psi = simulate_np(_ansatz(n, list(batch[p])))
        assert results[p].expectations["1*Z0 Z1"] == pytest.approx(
            M.expectation_np(psi, "Z0 Z1"), abs=1e-4)
    # determinism across reruns
    again = M.measure_sweep(eng, batch, shots=128, seed=3)
    for p in range(2):
        np.testing.assert_array_equal(again[p].samples, results[p].samples)
    # simulate_and_measure binds via the params kwarg
    res = M.simulate_and_measure(sym, backend="offload", L=4, R=2,
                                 params=dict(zip(sym.param_names, batch[0])),
                                 observables=["Z0 Z1"])
    psi = simulate_np(_ansatz(n, list(batch[0])))
    assert res.expectations["1*Z0 Z1"] == pytest.approx(
        M.expectation_np(psi, "Z0 Z1"), abs=1e-4)


# ------------------------------------------------- structural key + upgrade
def test_structural_key_and_symbolic_upgrade():
    n = 5
    cache = CompileCache()
    vals = _vals(n, 16)
    e1 = engine_for(_ansatz(n, vals), 4, 1, 0, backend="offload", cache=cache)
    # symbolic request with the same structure: same entry, upgraded skeleton
    e2 = engine_for(_ansatz(n), 4, 1, 0, backend="offload", cache=cache)
    assert e2 is e1 and cache.misses == 1
    assert e2.param_names == _ansatz(n).param_names
    out = np.asarray(e2.run(params=dict(zip(e2.param_names, _vals(n, 17)))))
    assert_states_close(out, simulate_np(_ansatz(n, _vals(n, 17))))
    # key includes structure: an extra gate is a different engine
    other = _ansatz(n, vals)
    other.add("h", 2)
    k1 = CircuitKey.make(_ansatz(n, vals), 4, 1, 0)
    assert CircuitKey.make(other, 4, 1, 0) != k1
    assert CircuitKey.make(_ansatz(n), 4, 1, 0) == k1


def test_symbolic_hit_adopts_requested_skeleton():
    """The structural key is blind to Param names AND affine coefficients,
    so a symbolic request hitting a symbolic-built entry must adopt the
    REQUESTED skeleton — otherwise run(params=...) silently resolves angles
    with the first request's scales (or rejects its names)."""
    n = 4
    cache = CompileCache()

    def skel(scale=1.0, prefix="t"):
        c = Circuit(n)
        for q in range(n):
            c.add("ry", q, params=[Param(f"{prefix}{q}") * scale])
        for q in range(n - 1):
            c.add("cx", q + 1, q)
        return c

    e1 = engine_for(skel(1.0), n, 0, 0, backend="dense", cache=cache)
    # same wiring, doubled affine scale: same cache entry, NEW skeleton
    e2 = engine_for(skel(2.0), n, 0, 0, backend="dense", cache=cache)
    assert e2 is e1 and cache.misses == 1
    vals = {f"t{q}": 0.2 + 0.1 * q for q in range(n)}
    out = np.asarray(e2.run(params=vals))
    ref = simulate_np(skel(2.0).bind(vals))
    assert_states_close(out, ref, msg="scale-variant skeleton not adopted")
    # renamed params: the request's names must resolve
    e3 = engine_for(skel(1.0, prefix="b"), n, 0, 0, backend="dense", cache=cache)
    assert e3 is e1
    out = np.asarray(e3.run(params={f"b{q}": 0.5 for q in range(n)}))
    assert_states_close(out, simulate_np(skel(1.0, "b").bind(
        {f"b{q}": 0.5 for q in range(n)})))
