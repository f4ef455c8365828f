"""Unified ExecutionEngine tests.

Covers: oracle equivalence (backend x use_pallas x batch), legacy-shim
bit-identicality to the engine path, packed-layout agreement across backends,
the CircuitKey/CompileCache serving path, the bounded per-backend jit cache,
SimulationPlan JSON round-trips, and per-batch measurement.
"""

import os
import subprocess
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import generators as gen
from repro.core.partition import SimulationPlan, partition
from repro.sim import measure as M
from repro.sim.engine import (
    BACKENDS,
    CircuitKey,
    CompileCache,
    ExecutionEngine,
    HostOffloadBackend,
    JitCache,
    engine_for,
)
from conftest import assert_states_close
from repro.sim.statevector import fidelity, simulate_np

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

from strategies import SHM_CM  # shared shm-forcing cost model


def _basis_batch(n: int, B: int) -> np.ndarray:
    out = np.zeros((B, 2**n), dtype=np.complex64)
    out[np.arange(B), np.arange(B)] = 1.0
    return out


@pytest.fixture(scope="module")
def qft_case():
    c = gen.qft(8)
    return c, partition(c, 5, 2, 1)


@pytest.fixture(scope="module")
def shm_case():
    c = gen.qft(8)
    return c, partition(c, 6, 2, 0, cost_model=SHM_CM)


# ------------------------------------------------- oracle equivalence sweep
@pytest.mark.parametrize("backend", ["pjit", "offload", "dense"])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_engine_oracle_equivalence(qft_case, shm_case, backend, use_pallas):
    """Every backend, with and without the Pallas shm path, matches the
    complex128 dense oracle — for the default state AND a batch of initial
    states through run_batch."""
    c, plan = shm_case if use_pallas else qft_case
    eng = ExecutionEngine(c, plan, backend=backend, use_pallas=use_pallas)
    ref = simulate_np(c)
    assert_states_close(eng.run(), ref)

    B = 3
    psi0s = _basis_batch(8, B)
    outs = eng.run_batch(psi0s)
    assert outs.shape == (B, 2**8)
    for b in range(B):
        assert_states_close(outs[b], simulate_np(c, psi0s[b]),
                            msg=f"{backend} pallas={use_pallas} b={b}")


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 devices (multi-device CI job)")
def test_engine_shardmap_in_process(qft_case):
    """shard_map backend through the engine API, including run_batch (this
    runs in the XLA_FLAGS=--xla_force_host_platform_device_count=8 CI job)."""
    c, plan = qft_case
    eng = ExecutionEngine(c, plan, backend="shardmap")
    ref = simulate_np(c)
    assert_states_close(eng.run(), ref)
    psi0s = _basis_batch(8, 2)
    outs = eng.run_batch(psi0s)
    for b in range(2):
        assert_states_close(outs[b], simulate_np(c, psi0s[b]))


@pytest.mark.slow
def test_engine_shardmap_subprocess():
    """Same sweep on 8 virtual devices when the main process has only one."""
    code = """
import numpy as np
from repro.core import generators as gen
from repro.core.partition import partition
from repro.sim.engine import ExecutionEngine
from repro.sim.statevector import fidelity, simulate_np
c = gen.qft(8)
plan = partition(c, 5, 2, 1)
eng = ExecutionEngine(c, plan, backend="shardmap")
assert fidelity(np.asarray(eng.run()), simulate_np(c)) > 0.9999
psi0s = np.zeros((2, 2**8), np.complex64); psi0s[[0, 1], [0, 1]] = 1.0
outs = eng.run_batch(psi0s)
for b in range(2):
    assert fidelity(np.asarray(outs[b]), simulate_np(c, psi0s[b])) > 0.9999
print('OK')
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, f"subprocess failed:\n{r.stdout}\n{r.stderr[-3000:]}"
    assert "OK" in r.stdout


def test_packed_layouts_agree_across_backends(qft_case):
    """run_packed leaves every backend in the SAME physical layout (the
    dense oracle re-stores the logical state in the compiled frame)."""
    c, plan = qft_case
    pk_pjit = np.asarray(ExecutionEngine(c, plan, backend="pjit").run_packed())
    pk_off = ExecutionEngine(c, plan, backend="offload").run_packed()
    pk_dense = ExecutionEngine(c, plan, backend="dense").run_packed()
    np.testing.assert_allclose(pk_pjit.reshape(-1), pk_off, atol=1e-5)
    np.testing.assert_allclose(pk_pjit.reshape(-1), pk_dense, atol=1e-5)


# -------------------------------------------------------- compile cache
def test_circuit_key_stability():
    k1 = CircuitKey.make(gen.qft(8), 5, 2, 1)
    k2 = CircuitKey.make(gen.qft(8), 5, 2, 1)
    assert k1 == k2  # structurally identical circuits -> same key

    # the key is STRUCTURAL: perturbing a gate angle must NOT change it (the
    # serving cache rebinds tensors instead of recompiling) ...
    c3 = gen.qft(8)
    gi = next(i for i, g in enumerate(c3.gates) if g.params)
    g = c3.gates[gi]
    c3.gates[gi] = replace(g, params=(g.params[0] + 1e-3,) + g.params[1:])
    assert CircuitKey.make(c3, 5, 2, 1) == k1
    # ... while perturbing the structure (wiring) must change it
    c4 = gen.qft(8)
    g4 = c4.gates[gi]
    c4.gates[gi] = replace(g4, qubits=(g4.qubits[0], (g4.qubits[1] + 1) % 8)
                           if len(g4.qubits) > 1 else g4.qubits)
    assert CircuitKey.make(c4, 5, 2, 1) != k1

    # every knob that changes the compiled artifact changes the key
    base = dict(backend="pjit", use_pallas=False, peephole=True,
                staging_method="ilp", kernelize_method="dp")
    c = gen.qft(8)
    ref = CircuitKey.make(c, 5, 2, 1, **base)
    assert CircuitKey.make(c, 6, 1, 1, **base) != ref
    for knob, val in [("backend", "offload"), ("use_pallas", True),
                      ("peephole", False), ("kernelize_method", "greedy")]:
        assert CircuitKey.make(c, 5, 2, 1, **{**base, knob: val}) != ref


def test_compile_cache_hit_and_eviction():
    cache = CompileCache(maxsize=2)
    c = gen.qft(7)
    e1 = engine_for(c, 5, 2, 0, backend="offload", cache=cache)
    e2 = engine_for(c, 5, 2, 0, backend="offload", cache=cache)
    assert e2 is e1, "identical request must return the cached engine"
    assert cache.hits == 1 and cache.misses == 1
    # the cached engine still answers correctly (serving: run many)
    assert_states_close(e2.run(), simulate_np(c))

    engine_for(c, 4, 3, 0, backend="offload", cache=cache)
    engine_for(gen.ising(7), 5, 2, 0, backend="offload", cache=cache)
    assert len(cache) == 2, "LRU must stay bounded at maxsize"
    # the oldest entry (e1) was evicted: same request now misses
    misses = cache.misses
    e4 = engine_for(c, 5, 2, 0, backend="offload", cache=cache)
    assert e4 is not e1 and cache.misses == misses + 1


def test_compile_cache_is_placement_aware():
    """backend_kw (mesh/devices/placement knobs) is part of the key: two
    requests with different placements must never share a cached engine."""
    cache = CompileCache()
    c = gen.qft(7)
    e1 = engine_for(c, 5, 2, 0, backend="offload", cache=cache,
                    backend_kw={"jit_cache_size": 8})
    e2 = engine_for(c, 5, 2, 0, backend="offload", cache=cache,
                    backend_kw={"jit_cache_size": 16})
    assert e1 is not e2 and cache.misses == 2
    e3 = engine_for(c, 5, 2, 0, backend="offload", cache=cache,
                    backend_kw={"jit_cache_size": 8})
    assert e3 is e1 and cache.hits == 1


def test_engine_for_explicit_plan_bypasses_cache(qft_case):
    c, plan = qft_case
    cache = CompileCache()
    e1 = engine_for(c, 5, 2, 1, plan=plan, cache=cache)
    e2 = engine_for(c, 5, 2, 1, plan=plan, cache=cache)
    assert e1 is not e2 and len(cache) == 0


# ------------------------------------------------------ bounded jit cache
def test_jit_cache_bounded_lru():
    jc = JitCache(maxsize=2)
    built = []
    for key in ["a", "b", "a", "c"]:
        jc.get(key, lambda key=key: built.append(key) or key.upper())
    assert built == ["a", "b", "c"] and len(jc) == 2
    # "b" was LRU at the time "c" was inserted -> rebuilding "b" misses
    jc.get("b", lambda: built.append("b2") or "B2")
    assert built[-1] == "b2"
    assert jc.hits == 1 and jc.misses == 4


def test_offload_backend_jit_cache_is_instance_bounded(qft_case):
    """The old module-level lru_cache(maxsize=None) is gone: each offload
    backend owns a bounded cache that dies with the engine."""
    c, plan = qft_case
    ex1 = ExecutionEngine(c, plan, backend=HostOffloadBackend(jit_cache_size=3))
    ex2 = ExecutionEngine(c, plan, backend="offload")
    ex1.run()
    assert 0 < len(ex1.backend.jit_cache) <= 3
    assert len(ex2.backend.jit_cache) == 0, "caches must not be shared"
    assert ex2.backend.jit_cache.maxsize == 64


# ----------------------------------------------------- plan serialization
def test_plan_json_roundtrip(qft_case):
    c, plan = qft_case
    s = plan.to_json()
    plan2 = SimulationPlan.from_json(s)
    assert plan2.to_json() == s, "to_json(from_json(s)) must be stable"
    assert plan2.n_stages == plan.n_stages
    assert [st.layout for st in plan2.stages] == [st.layout for st in plan.stages]
    # a round-tripped plan compiles to a bit-identical execution
    a = np.asarray(ExecutionEngine(c, plan, backend="pjit").run())
    b = np.asarray(ExecutionEngine(c, plan2, backend="pjit").run())
    np.testing.assert_array_equal(a, b)


# ------------------------------------------------------ batched measurement
def test_measure_batch_per_state_results(qft_case):
    c, plan = qft_case
    eng = ExecutionEngine(c, plan, backend="offload")
    B = 3
    psi0s = _basis_batch(8, B)
    results = M.measure_batch(eng, psi0s, shots=128, seed=11,
                              marginals=[(0, 1)], observables=["Z0 Z1"])
    assert len(results) == B
    for b, res in enumerate(results):
        psi = simulate_np(c, psi0s[b])
        assert abs(res.expectations["1*Z0 Z1"]
                   - M.expectation_np(psi, "Z0 Z1")) < 1e-5
        np.testing.assert_allclose(res.marginals[(0, 1)],
                                   M.marginal_np(psi, (0, 1)), atol=1e-5)
        assert res.samples.shape == (128,)
        assert res.meta["batch_index"] == b
    # per-element seeds differ -> independent shot streams
    assert (results[0].samples != results[1].samples).any() or \
        (results[0].samples == results[0].samples[0]).all()
    # deterministic: rerunning the batch reproduces the sample streams
    again = M.measure_batch(eng, psi0s, shots=128, seed=11)
    for b in range(B):
        np.testing.assert_array_equal(again[b].samples, results[b].samples)


def test_backend_registry_complete():
    assert set(BACKENDS) == {"pjit", "shardmap", "offload", "dense"}


# ------------------------------------------- offload sweep-state hygiene
def test_on_rebind_clears_stale_sweep_state():
    """Regression: a raced/interrupted fused sweep leaves per-binding sweep
    tables (``_sweep_consts``/``_sweep_slices``) on the offload backend;
    ``on_rebind`` must drop them, or the next plain ``run`` resolves
    ``[P, ...]`` sweep slices into a non-sweep shard stream."""
    from test_params import _ansatz, _vals

    n = 6
    sym = _ansatz(n)
    plan = partition(sym, 4, 2, 0)
    eng = ExecutionEngine(sym, plan, backend="offload")
    batch = np.stack([_vals(n, s) for s in (7, 8)])

    captured = {}
    orig = eng.backend._stream_stage

    def spy(state, prog):
        if eng.backend._sweep_consts is not None and not captured:
            captured["consts"] = eng.backend._sweep_consts
            captured["slices"] = dict(eng.backend._sweep_slices)
        return orig(state, prog)

    eng.backend._stream_stage = spy
    eng.run_sweep(None, batch)
    del eng.backend._stream_stage
    assert "consts" in captured, "sweep never went through the spy"

    # simulate the race: the sweep's tables are still parked on the backend
    # when a rebind lands (pre-fix, on_rebind left them in place)
    eng.backend._sweep_consts = captured["consts"]
    eng.backend._sweep_slices.update(captured["slices"])
    vals2 = _vals(n, 9)
    eng.bind(dict(zip(sym.param_names, vals2)))
    assert eng.backend._sweep_consts is None
    assert not eng.backend._sweep_slices
    assert_states_close(np.asarray(eng.run()),
                        simulate_np(_ansatz(n, vals2)))


def test_concurrent_sweep_and_run_stay_correct():
    """run/run_sweep on one engine from two threads: the engine lock must
    serialize them (the fused sweep parks shared per-binding state on the
    backend; unserialized, the plain run reads the sweep's tensors)."""
    import threading

    from test_params import _ansatz, _vals

    n = 6
    sym = _ansatz(n)
    plan = partition(sym, 4, 2, 0)
    eng = ExecutionEngine(sym, plan, backend="offload")
    vals = _vals(n, 3)
    eng.bind(dict(zip(sym.param_names, vals)))
    ref_run = simulate_np(_ansatz(n, vals))
    batch = np.stack([_vals(n, s) for s in (7, 8)])
    refs_sweep = [simulate_np(_ansatz(n, list(batch[p]))) for p in range(2)]

    for _ in range(3):
        results, errs = {}, []

        def worker(name, fn):
            try:
                results[name] = np.asarray(fn())
            except Exception as e:  # noqa: BLE001 - surfaced via errs
                errs.append(e)

        ts = [threading.Thread(target=worker,
                               args=("sweep", lambda: eng.run_sweep(None, batch))),
              threading.Thread(target=worker, args=("run", eng.run))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not errs, errs
        for p in range(2):
            assert_states_close(results["sweep"][p], refs_sweep[p],
                                msg=f"sweep point {p}")
        assert_states_close(results["run"], ref_run, msg="plain run")


def test_overlap_ratio_single_shard_is_vacuous_one():
    """With one shard per stage no dispatch can overlap the previous one:
    the ratio must report a vacuous 1.0, not a misleading 0.0."""
    c = gen.random_circuit(6, 16, seed=2)
    eng = engine_for(c, 6, 0, 0, backend="offload", cache=None)
    out = np.asarray(eng.run())
    assert eng.stats["shard_transfers"] > 0
    assert eng.stats["overlapped_dispatches"] == 0
    assert eng.backend.overlap_ratio == 1.0
    assert_states_close(out, simulate_np(c))
