"""Pre-staging circuit optimizer (repro.core.optimize) — pass-level unit
tests, dense unitary-equivalence verification, commutation-predicate
soundness, engine/cache integration and the satellite validation fixes.

Every rewrite claim is backed by one of two equivalence checks:

* small-n dense ``unitaries_equivalent`` (global-phase-insensitive) — the
  strongest check, used for every seeded pipeline case here;
* end-to-end state equivalence on every backend — the optimizer cross-check
  in ``tests/test_fuzz_differential.py``.
"""

import math

import numpy as np
import pytest

import strategies as strat

from repro.core import gates as G
from repro.core import kernelization, staging
from repro.core.circuit import Circuit
from repro.core.gates import Param
from repro.core.optimize import (
    ALL_PASSES,
    OptimizerConfig,
    gates_commute,
    optimize_circuit,
    optimize_fingerprint,
    resolve_config,
    unitaries_equivalent,
)


def _c(n):
    return Circuit(n)


# ---------------------------------------------------------------------------
# pipeline: seeded unitary equivalence (the tentpole contract)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(25))
def test_pipeline_unitary_equivalence_concrete(seed):
    """optimize(c) implements the same unitary as c, up to global phase."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 5))
    c = strat.build_cancellation_circuit(n, int(rng.integers(3, 9)), seed)
    res = optimize_circuit(c)
    assert res.circuit.n_gates <= c.n_gates
    assert unitaries_equivalent(c, res.circuit), \
        f"seed={seed}: optimizer changed the unitary\n{c.to_json()}"


@pytest.mark.parametrize("seed", range(15))
def test_pipeline_commutes_with_binding(seed):
    """optimize(c).bind(v) == optimize(c.bind(v)) up to global phase, and
    the free-parameter surface survives the rewrite."""
    rng = np.random.default_rng(1000 + seed)
    n = int(rng.integers(2, 5))
    c = strat.build_cancellation_circuit(n, int(rng.integers(3, 9)), seed,
                                         param_mode="mixed")
    res = optimize_circuit(c)
    assert set(res.circuit.param_names) == set(c.param_names)
    binding = strat.random_binding(c, seed + 7)
    assert unitaries_equivalent(c.bind(binding), res.circuit.bind(binding)), \
        f"seed={seed}: optimize/bind do not commute"


@pytest.mark.parametrize("passes", [("cancel",), ("merge",), ("drop",),
                                    ("reorder",), ("cancel", "merge")])
def test_each_pass_alone_preserves_unitary(passes):
    for seed in range(8):
        c = strat.build_cancellation_circuit(3, 6, 400 + seed)
        res = optimize_circuit(c, passes)
        assert unitaries_equivalent(c, res.circuit), \
            f"pass subset {passes} broke seed {seed}"


# ---------------------------------------------------------------------------
# gates_commute: structural predicate, numerically sound
# ---------------------------------------------------------------------------


def _gate(name, *qubits, params=()):
    c = _c(max(qubits) + 1)
    c.add(name, *qubits, params=params)
    return c.gates[0]


def test_gates_commute_positives():
    # disjoint support
    assert gates_commute(_gate("h", 0), _gate("h", 1))
    # diagonal/diagonal sharing qubits
    assert gates_commute(_gate("cz", 0, 1), _gate("rz", 1, params=(0.3,)))
    assert gates_commute(_gate("cp", 0, 1, params=(0.2,)),
                         _gate("rzz", 1, 2, params=(0.4,)))
    # control bit is a diagonal bit: cx control vs rz commute (controls are
    # the most-significant gate bits, i.e. the LAST entries of the tuple —
    # cx(0, 1) controls on qubit 1)
    assert gates_commute(_gate("cx", 0, 1), _gate("rz", 1, params=(0.3,)))
    # same one-generator family, same wiring, ANY angles
    assert gates_commute(_gate("rx", 0, params=(0.1,)),
                         _gate("rx", 0, params=(2.2,)))
    assert gates_commute(_gate("crx", 0, 1, params=(0.1,)),
                         _gate("crx", 0, 1, params=(1.1,)))


def test_gates_commute_negatives():
    # cx TARGET (first tuple entry) is not a diagonal bit
    assert not gates_commute(_gate("cx", 0, 1), _gate("rz", 0, params=(0.3,)))
    # different axes on the same qubit
    assert not gates_commute(_gate("rx", 0, params=(0.1,)),
                             _gate("rz", 0, params=(0.2,)))
    # u3 is excluded from the same-family rule (two u3s need not commute)
    assert not gates_commute(_gate("u3", 0, params=(0.1, 0.2, 0.3)),
                             _gate("u3", 0, params=(0.4, 0.5, 0.6)))
    assert not gates_commute(_gate("h", 0), _gate("x", 0))


@pytest.mark.parametrize("seed", range(10))
def test_gates_commute_numerically_sound(seed):
    """Whenever the predicate says True, the dense matrices over the union
    support must commute — for random gates at random angles."""
    rng = np.random.default_rng(seed)
    names = list(G.GATE_DEFS)
    for _ in range(40):
        c = _c(4)
        for _k in range(2):
            name = names[int(rng.integers(len(names)))]
            gd = G.GATE_DEFS[name]
            qs = tuple(int(q) for q in rng.choice(4, gd.n_qubits,
                                                  replace=False))
            params = tuple(float(rng.uniform(0.05, 2 * math.pi))
                           for _ in range(gd.n_params))
            c.add(name, *qs, params=params)
        a, b = c.gates
        if not gates_commute(a, b):
            continue
        ab = c.unitary()
        c2 = _c(4)
        c2.add(b.name, *b.qubits, params=b.params)
        c2.add(a.name, *a.qubits, params=a.params)
        assert np.allclose(ab, c2.unitary(), atol=1e-9), \
            f"predicate unsound for {a.name}{a.qubits} vs {b.name}{b.qubits}"


# ---------------------------------------------------------------------------
# cancel pass
# ---------------------------------------------------------------------------


def test_cancel_cascade():
    c = _c(2)
    c.add("h", 0)
    c.add("x", 0)
    c.add("x", 0)
    c.add("h", 0)
    c.add("cx", 0, 1)
    c.add("cx", 0, 1)
    res = optimize_circuit(c, ("cancel",))
    assert res.circuit.n_gates == 0
    assert res.pass_counts()["cancel"] == 6
    assert sorted(res.dropped_gids) == [0, 1, 2, 3, 4, 5]


def test_cancel_through_disjoint_gates():
    # DAG-adjacency: the h(1) between the two cz gates does not block
    c = _c(3)
    c.add("cz", 0, 2)
    c.add("h", 1)
    c.add("cz", 2, 0)  # symmetric gate: qubit-set match suffices
    res = optimize_circuit(c, ("cancel",))
    assert [g.name for g in res.circuit.gates] == ["h"]


def test_cancel_blocked_by_intervening_gate():
    c = _c(2)
    c.add("h", 0)
    c.add("rz", 0, params=(0.3,))
    c.add("h", 0)
    res = optimize_circuit(c, ("cancel",))
    assert res.circuit.n_gates == 3  # rz blocks: h·rz·h is not rz


def test_cancel_inverse_name_pairs():
    c = _c(1)
    c.add("s", 0)
    c.add("sdg", 0)
    c.add("tdg", 0)
    c.add("t", 0)
    res = optimize_circuit(c, ("cancel",))
    assert res.circuit.n_gates == 0


# ---------------------------------------------------------------------------
# merge pass
# ---------------------------------------------------------------------------


def test_merge_concrete_and_param_shift():
    c = _c(1)
    c.add("rz", 0, params=(0.4,))
    c.add("rz", 0, params=(0.5,))
    res = optimize_circuit(c, ("merge",))
    assert res.circuit.n_gates == 1
    assert res.circuit.gates[0].params[0] == pytest.approx(0.9)
    assert res.provenance == ((0, 1),)

    c = _c(1)
    c.add("rx", 0, params=(Param("a"),))
    c.add("rx", 0, params=(0.25,))
    g = optimize_circuit(c, ("merge",)).circuit.gates[0]
    p = g.params[0]
    assert isinstance(p, Param) and p.name == "a"
    assert p.scale == 1.0 and p.shift == pytest.approx(0.25)


def test_merge_same_name_affine_fold():
    c = _c(1)
    c.add("rz", 0, params=(Param("a"),))
    c.add("rz", 0, params=(Param("a", 2.0, 0.1),))
    p = optimize_circuit(c, ("merge",)).circuit.gates[0].params[0]
    assert (p.name, p.scale, p.shift) == ("a", 3.0, pytest.approx(0.1))


def test_merge_zero_scale_keeps_param_surface():
    c = _c(1)
    c.add("rz", 0, params=(Param("a"),))
    c.add("rz", 0, params=(Param("a", -1.0, 0.0),))
    opt = optimize_circuit(c, ("merge",)).circuit
    assert opt.n_gates == 1
    p = opt.gates[0].params[0]
    assert isinstance(p, Param) and p.scale == 0.0
    assert set(opt.param_names) == {"a"}  # binding dicts keep working


def test_merge_bails_on_different_names():
    c = _c(1)
    c.add("rz", 0, params=(Param("a"),))
    c.add("rz", 0, params=(Param("b"),))
    assert optimize_circuit(c, ("merge",)).circuit.n_gates == 2


def test_merge_symmetric_vs_directed_qubit_order():
    # cp is qubit-symmetric: (0,1) merges with (1,0)
    c = _c(2)
    c.add("cp", 0, 1, params=(0.3,))
    c.add("cp", 1, 0, params=(0.4,))
    assert optimize_circuit(c, ("merge",)).circuit.n_gates == 1
    # crz is NOT symmetric: control/target order matters
    c = _c(2)
    c.add("crz", 0, 1, params=(0.3,))
    c.add("crz", 1, 0, params=(0.4,))
    assert optimize_circuit(c, ("merge",)).circuit.n_gates == 2


# ---------------------------------------------------------------------------
# drop pass
# ---------------------------------------------------------------------------


def test_drop_identities():
    c = _c(2)
    c.add("i", 0)
    c.add("rz", 0, params=(0.0,))
    c.add("rz", 0, params=(4 * math.pi,))
    c.add("rz", 1, params=(2 * math.pi,))  # rz(2π) = -I: pure global phase
    res = optimize_circuit(c, ("drop",))
    assert res.circuit.n_gates == 0
    assert unitaries_equivalent(c, res.circuit)


def test_drop_keeps_controlled_phase_and_symbolic():
    c = _c(2)
    # crz(2π) = diag(1,1,-1,-1): NOT a global phase — must be kept
    c.add("crz", 0, 1, params=(2 * math.pi,))
    c.add("rz", 0, params=(Param("a"),))  # symbolic: never value-dropped
    assert optimize_circuit(c, ("drop",)).circuit.n_gates == 2


# ---------------------------------------------------------------------------
# reorder pass
# ---------------------------------------------------------------------------


def test_reorder_exposes_merge():
    # rz · h(other) · rz: reorder sinks the diagonals together, the merge
    # rerun folds them — full pipeline ends at 2 gates
    c = _c(2)
    c.add("rz", 0, params=(0.3,))
    c.add("h", 1)
    c.add("rz", 0, params=(0.4,))
    res = optimize_circuit(c)
    assert res.circuit.n_gates == 2
    assert unitaries_equivalent(c, res.circuit)


def test_reorder_emits_equivalent_order():
    c = strat.build_cancellation_circuit(4, 8, 77)
    res = optimize_circuit(c, ("reorder",))
    assert res.circuit.n_gates == c.n_gates
    # reorder-only provenance is a permutation of the source gids, and the
    # order is accepted by the commutation-aware validity check
    order = [srcs[0] for srcs in res.provenance]
    assert sorted(order) == list(range(c.n_gates))
    assert c.is_equivalent_order(order)
    assert unitaries_equivalent(c, res.circuit)


def test_reorder_pair_cap_skips():
    c = _c(2)
    for _ in range(30):
        c.add("rz", 0, params=(0.1,))
        c.add("h", 0)
    cfg = OptimizerConfig(passes=("reorder",), reorder_pair_cap=1)
    res = optimize_circuit(c, cfg)
    assert [s for s in res.stats if s["pass"] == "reorder"][0]["skipped"]
    assert [g.name for g in res.circuit.gates] == \
        [g.name for g in c.gates]


# ---------------------------------------------------------------------------
# config / fingerprint / result surface
# ---------------------------------------------------------------------------


def test_resolve_config_and_fingerprint():
    assert resolve_config(False) is None and resolve_config(None) is None
    assert resolve_config(True).passes == ALL_PASSES
    assert resolve_config(["cancel"]).passes == ("cancel",)
    with pytest.raises(ValueError, match="unknown optimizer passes"):
        OptimizerConfig(passes=("cancel", "nope"))
    with pytest.raises(TypeError):
        resolve_config("cancel")
    assert optimize_fingerprint(False) == ("off",)
    assert optimize_fingerprint(True) != optimize_fingerprint(False)
    assert optimize_fingerprint(("cancel",)) != optimize_fingerprint(True)


def test_result_to_dict_and_off_identity():
    c = strat.build_cancellation_circuit(3, 5, 9)
    d = optimize_circuit(c).to_dict()
    assert set(d) == {"gates_before", "gates_after", "gates_removed",
                      "pass_counts", "dropped_gids"}
    assert d["gates_before"] - d["gates_after"] == d["gates_removed"]
    off = optimize_circuit(c, False)
    assert off.circuit is c and off.gates_removed == 0
    assert off.dropped_gids == ()


# ---------------------------------------------------------------------------
# engine / cache integration
# ---------------------------------------------------------------------------


def test_circuit_key_separates_optimized_and_literal():
    from repro.sim.engine import circuit_key_for

    c = strat.build_cancellation_circuit(3, 5, 11)
    k_off = circuit_key_for(c, 3, 0, 0, backend="dense")
    k_on = circuit_key_for(c, 3, 0, 0, backend="dense", optimize=True)
    assert k_off.digest != k_on.digest
    # and pass subsets key differently from the full pipeline
    k_sub = circuit_key_for(c, 3, 0, 0, backend="dense",
                            optimize=("cancel",))
    assert k_sub.digest not in (k_off.digest, k_on.digest)


@pytest.mark.parametrize("backend", ["dense", "pjit", "offload"])
def test_engine_optimize_state_equivalence(backend):
    from repro.sim.engine import CompileCache, engine_for
    from repro.sim.statevector import simulate_np

    c = strat.build_cancellation_circuit(4, 7, 21)
    eng = engine_for(c, 3, 1, 0, backend=backend, optimize=True,
                     cache=CompileCache(maxsize=4))
    got = np.asarray(eng.run())
    oracle = simulate_np(c)
    fid = abs(np.vdot(got, oracle)) / (
        np.linalg.norm(got) * np.linalg.norm(oracle))
    assert 1.0 - fid < 1e-5
    prov = eng.provenance["optimize"]
    assert prov["gates_before"] == c.n_gates
    assert prov["gates_after"] == prov["gates_before"] - prov["gates_removed"]


@pytest.mark.parametrize("family", ["redundant", "qft", "su2random"])
def test_optimizer_bars_on_families(family):
    """On the generator families the optimizer never adds gates and the
    optimized engine reproduces the literal engine's state; on the
    cancellation-rich ``redundant`` family it removes gates and the plan
    has strictly fewer stages (qft and su2random have nothing to remove)."""
    from repro.core.generators import FAMILIES
    from repro.sim.engine import CompileCache, engine_for

    n, L, R = 10, 8, 2
    c = FAMILIES[family](n)
    cache = CompileCache(maxsize=4)
    lit = engine_for(c, L, R, 0, cache=cache, degrade=False)
    opt = engine_for(c, L, R, 0, cache=cache, optimize=True, degrade=False)
    prov = opt.provenance["optimize"]
    assert prov["gates_before"] == c.n_gates
    assert prov["gates_after"] <= prov["gates_before"]
    a = np.asarray(lit.run(), dtype=np.complex128)
    b = np.asarray(opt.run(), dtype=np.complex128)
    infidelity = 1.0 - abs(np.vdot(a, b)) / (np.linalg.norm(a)
                                             * np.linalg.norm(b))
    assert infidelity < 1e-6, f"{family}: infidelity {infidelity:.3e}"
    if family == "redundant":
        assert prov["gates_removed"] > 0
        assert opt.plan.n_stages < lit.plan.n_stages, \
            (lit.plan.n_stages, opt.plan.n_stages)


def test_optimized_symbolic_warm_rebind_zero_solves_zero_retraces():
    from repro.sim.engine import CompileCache, engine_for
    from repro.sim.statevector import simulate_np

    c = _c(3)
    c.add("h", 0)
    c.add("h", 0)  # cancels: the optimized structure differs from literal
    for q in range(3):
        c.add("rz", q, params=(Param(f"a{q}"),))
        c.add("rz", q, params=(Param(f"a{q}", 1.0, 0.2),))
    c.add("cx", 0, 1)
    c.add("cx", 1, 2)
    cache = CompileCache(maxsize=4)
    e1 = engine_for(c, 3, 0, 0, backend="dense", optimize=True, cache=cache)
    e1.bind({"a0": 0.1, "a1": 0.2, "a2": 0.3})
    e1.run()

    solves0 = (staging.SOLVER_CALLS["ilp"], staging.SOLVER_CALLS["greedy"],
               kernelization.SOLVER_CALLS["dp"])
    xla0 = e1.xla_compiles
    e2 = engine_for(c, 3, 0, 0, backend="dense", optimize=True, cache=cache)
    binding = {"a0": 0.7, "a1": 0.9, "a2": 1.1}
    e2.bind(binding)
    got = np.asarray(e2.run())
    assert e2 is e1, "warm request must hit the cached optimized engine"
    assert (staging.SOLVER_CALLS["ilp"], staging.SOLVER_CALLS["greedy"],
            kernelization.SOLVER_CALLS["dp"]) == solves0, \
        "warm rebind of an optimized symbolic circuit re-ran ILP/DP"
    assert e2.xla_compiles == xla0, "warm rebind retraced XLA"
    oracle = simulate_np(c.bind(binding))
    fid = abs(np.vdot(got, oracle)) / (
        np.linalg.norm(got) * np.linalg.norm(oracle))
    assert 1.0 - fid < 1e-5


def test_autotune_alias_guard_serves_fresh_literal_requests():
    """An optimized engine aliased under the DEFAULT key (what autotune's
    winner installation does) must still answer literal requests correctly —
    including a request whose angles optimize differently."""
    from repro.core.autotune import PlanCandidate, autotune_engine, \
        clear_tuned
    from repro.core.cost_model import DEFAULT_COST_MODEL
    from repro.sim.engine import CompileCache, engine_for
    from repro.sim.statevector import simulate_np

    clear_tuned()
    c = _c(3)
    c.add("h", 0)
    c.add("h", 0)
    c.add("rz", 1, params=(0.4,))
    c.add("rz", 1, params=(0.5,))
    c.add("cx", 0, 1)
    c.add("cx", 1, 2)
    cache = CompileCache(maxsize=8)
    res = autotune_engine(
        c, 3, 0, 0, backend="dense", cache=cache, repeats=1, warmup=1,
        candidates=[PlanCandidate("optimize", DEFAULT_COST_MODEL,
                                  optimize=True)])
    assert res.engine.provenance.get("optimize"), \
        "winner should be the optimized engine"

    # same literal circuit, DIFFERENT angles: rz pair no longer sums to 0.9
    # — the aliased engine must not serve its stale structure blindly
    c2 = _c(3)
    c2.add("h", 0)
    c2.add("h", 0)
    c2.add("rz", 1, params=(1.1,))
    c2.add("rz", 1, params=(2.2,))
    c2.add("cx", 0, 1)
    c2.add("cx", 1, 2)
    eng2 = engine_for(c2, 3, 0, 0, backend="dense", cache=cache)
    got = np.asarray(eng2.run())
    oracle = simulate_np(c2)
    fid = abs(np.vdot(got, oracle)) / (
        np.linalg.norm(got) * np.linalg.norm(oracle))
    assert 1.0 - fid < 1e-5
    clear_tuned()


# ---------------------------------------------------------------------------
# satellite fixes: validation, subcircuit provenance, order equivalence
# ---------------------------------------------------------------------------


def test_unknown_gate_raises_typed_error():
    c = _c(2)
    with pytest.raises(ValueError, match=r"unknown gate 'hadamard'"):
        c.add("hadamard", 0)
    with pytest.raises(ValueError, match=r"known gates: .*cx.*"):
        c.add("nope", 0)
    bad = ('{"n_qubits": 1, "gates": '
           '[{"name": "bogus", "qubits": [0], "params": []}]}')
    with pytest.raises(ValueError, match=r"unknown gate 'bogus'"):
        Circuit.from_json(bad)


def test_subcircuit_records_parent_gids():
    c = _c(3)
    c.add("h", 0)
    c.add("cx", 0, 1)
    c.add("rz", 2, params=(0.3,))
    sub = c.subcircuit([2, 0])
    assert sub.parent_gids == (2, 0)
    assert [g.name for g in sub.gates] == ["rz", "h"]
    assert c.parent_gids is None  # only set on extracted views


def test_is_equivalent_order_vs_topological():
    c = _c(2)
    c.add("rz", 0, params=(0.3,))
    c.add("cz", 0, 1)
    swapped = [1, 0]
    # exact per-qubit order check rejects the swap...
    assert not c.is_topologically_equivalent(swapped)
    # ...but rz/cz commute, so the commutation-aware check accepts it
    assert c.is_equivalent_order(swapped)

    c2 = _c(2)
    c2.add("h", 0)
    c2.add("cx", 0, 1)
    # h and cx share qubit 0 non-diagonally: neither check accepts the swap
    assert not c2.is_topologically_equivalent([1, 0])
    assert not c2.is_equivalent_order([1, 0])
    # non-permutations are rejected outright
    assert not c2.is_equivalent_order([0, 0])
