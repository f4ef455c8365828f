"""Profile-guided planning: calibration round-trips, fingerprint-gated
auto-loading, and the plan autotuner's cached-winner contract."""

import json
import math

import numpy as np
import pytest

from conftest import assert_states_close
from repro.core import kernelization, staging
from repro.core.autotune import (
    PlanCandidate,
    TUNED,
    autotune_engine,
    clear_tuned,
    default_candidates,
    tuned_outcomes,
)
from repro.core.cost_model import (
    CostModel,
    DEFAULT_COST_MODEL,
    DegenerateCostModelError,
    offload_pass_us,
    stage_pass_us,
)
from repro.core.generators import qft, su2random
from repro.core.partition import partition
from repro.sim import profiler
from repro.sim.engine import CompileCache, circuit_key_for, engine_for
from repro.sim.statevector import simulate


MEASURED = {
    "pass_us": 1234.5,
    "mxu_us_per_2k": 17.25,
    "launch_us": 4.0,
    "shm_gate_us": 150.0,
    "shm_diag_gate_us": 60.0,
    "host_link_gbps": 12.5,
    "comm_weight": 2.0,
}


def _calib(fingerprint=None, measurements=MEASURED):
    return {
        "version": profiler.CALIBRATION_VERSION,
        "fingerprint": fingerprint or profiler.device_fingerprint(),
        "measurements": dict(measurements),
        "cost_model": CostModel.from_calibration(measurements).to_dict(),
        "meta": {"fast": True},
    }


@pytest.fixture(autouse=True)
def _clean_resolution(monkeypatch):
    """Pin resolution to 'no calibration' unless a test opts in, and leave
    no memoized state behind."""
    monkeypatch.delenv("REPRO_CALIBRATION", raising=False)
    monkeypatch.setenv("REPRO_CALIBRATION_DIR", "/nonexistent-calib-dir")
    profiler.clear_resolved_cache()
    clear_tuned()
    yield
    profiler.clear_resolved_cache()
    clear_tuned()


# ======================================================================
# CostModel: folded offload constants + hardening
# ======================================================================


class TestCostModelFields:
    def test_offload_shims_match_dataclass(self):
        assert offload_pass_us(26) == DEFAULT_COST_MODEL.offload_pass_us(26)
        assert stage_pass_us(4, 24) == DEFAULT_COST_MODEL.stage_pass_us(4, 24)

    def test_offload_cost_varies_with_model(self):
        fast_link = CostModel(host_link_gbps=64.0)
        assert fast_link.offload_pass_us(28) == pytest.approx(
            DEFAULT_COST_MODEL.offload_pass_us(28) / 2)

    def test_degenerate_best_fusion_size_raises(self):
        with pytest.raises(DegenerateCostModelError):
            CostModel(max_fusion_qubits=0).best_fusion_size()
        with pytest.raises(ValueError):  # typed subclass of ValueError
            CostModel(max_fusion_qubits=-3).best_fusion_size()

    def test_all_inf_costs_raise(self):
        cm = CostModel(pass_us=math.inf, mxu_us_per_2k=math.inf,
                       launch_us=math.inf)
        with pytest.raises(DegenerateCostModelError):
            cm.best_fusion_size()

    def test_comm_weight_defaults_into_partition(self):
        circ = qft(8)
        p_default = partition(circ, 6, 2, 0)
        p_low = partition(circ, 6, 2, 0,
                          cost_model=CostModel(comm_weight=1.0))
        assert p_default.meta["comm_weight"] == DEFAULT_COST_MODEL.comm_weight
        assert p_low.meta["comm_weight"] == 1.0
        # explicit c still wins over the model
        p_explicit = partition(circ, 6, 2, 0, c=5.0,
                               cost_model=CostModel(comm_weight=1.0))
        assert p_explicit.meta["comm_weight"] == 5.0


class TestFromCalibration:
    def test_merge_and_floors(self):
        cm = CostModel.from_calibration(MEASURED)
        assert cm.pass_us == MEASURED["pass_us"]
        assert cm.comm_weight == 2.0
        assert cm.max_fusion_qubits == DEFAULT_COST_MODEL.max_fusion_qubits
        # degenerate zero timer measurements are floored, never zero
        floored = CostModel.from_calibration({"shm_gate_us": 0.0})
        assert floored.shm_gate_us > 0

    def test_nan_inf_measurements_keep_base(self):
        cm = CostModel.from_calibration(
            {"pass_us": float("nan"), "mxu_us_per_2k": float("inf")})
        assert cm.pass_us == DEFAULT_COST_MODEL.pass_us
        assert cm.mxu_us_per_2k == DEFAULT_COST_MODEL.mxu_us_per_2k

    def test_capacity_fields_stay_integral(self):
        cm = CostModel.from_calibration({"max_fusion_qubits": 5.0,
                                         "io_qubits": 2.0})
        assert cm.max_fusion_qubits == 5 and isinstance(
            cm.max_fusion_qubits, int)
        assert cm.io_qubits == 2

    def test_degenerate_calibration_rejected(self):
        with pytest.raises(DegenerateCostModelError):
            CostModel.from_calibration({"max_fusion_qubits": 0})


# ======================================================================
# Calibration persistence + fingerprint-gated auto-load
# ======================================================================


class TestCalibrationRoundTrip:
    def test_write_load_identical_cost_model(self, tmp_path):
        path = str(tmp_path / "calibration.json")
        calib = _calib()
        profiler.save_calibration(path, calib)
        loaded = profiler.load_calibration(path)
        assert loaded == calib
        cm_a = CostModel.from_calibration(calib["measurements"])
        cm_b = CostModel.from_dict(loaded["cost_model"])
        assert cm_a == cm_b

    def test_resolve_matching_fingerprint(self, tmp_path):
        path = str(tmp_path / "calibration.json")
        profiler.save_calibration(path, _calib())
        cm, info = profiler.resolve_calibration(path, refresh=True)
        assert info["source"] == "calibrated"
        assert cm == CostModel.from_calibration(MEASURED)

    def test_resolve_fingerprint_mismatch_falls_back(self, tmp_path):
        path = str(tmp_path / "calibration.json")
        wrong_fp = dict(profiler.device_fingerprint(),
                        device_kind="TPU v5e", platform="tpu")
        profiler.save_calibration(path, _calib(fingerprint=wrong_fp))
        cm, info = profiler.resolve_calibration(path, refresh=True)
        assert cm == DEFAULT_COST_MODEL
        assert info["source"] == "mismatch"

    def test_resolve_missing_file_is_analytic(self, tmp_path):
        cm, info = profiler.resolve_calibration(
            str(tmp_path / "nope.json"), refresh=True)
        assert cm == DEFAULT_COST_MODEL
        assert info["source"] == "analytic"

    def test_resolve_corrupt_file_is_analytic(self, tmp_path):
        path = tmp_path / "calibration.json"
        path.write_text("{not json")
        cm, info = profiler.resolve_calibration(str(path), refresh=True)
        assert cm == DEFAULT_COST_MODEL
        assert info["source"] == "error"

    def test_env_off_disables(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_CALIBRATION", "off")
        profiler.clear_resolved_cache()
        cm, info = profiler.resolve_calibration()
        assert cm == DEFAULT_COST_MODEL and info["source"] == "disabled"

    def test_env_path_auto_loads_into_engine_for(self, tmp_path, monkeypatch):
        path = str(tmp_path / "calibration.json")
        profiler.save_calibration(path, _calib())
        monkeypatch.setenv("REPRO_CALIBRATION", path)
        profiler.clear_resolved_cache()
        assert profiler.resolve_cost_model() == CostModel.from_calibration(
            MEASURED)
        # engine_for with cost_model=None plans under the calibrated model
        # and records the provenance
        eng = engine_for(qft(6), 4, 2, 0, cache=None)
        assert eng.provenance["calibration"]["source"] == "calibrated"
        assert_states_close(eng.run(), simulate(qft(6)))

    def test_resolution_is_memoized(self, tmp_path, monkeypatch):
        path = str(tmp_path / "calibration.json")
        profiler.save_calibration(path, _calib())
        monkeypatch.setenv("REPRO_CALIBRATION", path)
        profiler.clear_resolved_cache()
        first = profiler.resolve_cost_model()
        # a rewrite is NOT picked up until the memo is dropped: every key
        # computed in one process must see one consistent model
        profiler.save_calibration(path, _calib(
            measurements={**MEASURED, "pass_us": 9999.0}))
        assert profiler.resolve_cost_model() == first
        profiler.clear_resolved_cache()
        assert profiler.resolve_cost_model() != first


class TestDeterministicPlans:
    def test_pinned_calibration_gives_identical_plans(self, tmp_path,
                                                      monkeypatch):
        path = str(tmp_path / "calibration.json")
        profiler.save_calibration(path, _calib())
        monkeypatch.setenv("REPRO_CALIBRATION", path)
        profiler.clear_resolved_cache()
        circ = su2random(8)
        cm = profiler.resolve_cost_model()
        p1 = partition(circ, 6, 2, 0, cost_model=cm)
        p2 = partition(circ, 6, 2, 0, cost_model=cm)

        def structural(p):
            d = json.loads(p.to_json())
            d.pop("preprocess_time_s")  # wall time, not plan content
            return d

        assert structural(p1) == structural(p2)
        k1 = circuit_key_for(circ, 6, 2, 0)
        k2 = circuit_key_for(circ, 6, 2, 0)
        assert k1 == k2

    def test_key_depends_on_cost_model_fields(self):
        circ = qft(6)
        base = circuit_key_for(circ, 4, 2, 0,
                               cost_model=DEFAULT_COST_MODEL)
        tweaked = circuit_key_for(
            circ, 4, 2, 0,
            cost_model=DEFAULT_COST_MODEL.with_overrides(comm_weight=1.5))
        assert base != tweaked


# ======================================================================
# Profiler measurement machinery (device-independent pieces)
# ======================================================================


class TestProfiler:
    def test_fingerprint_digest_stable_and_sensitive(self):
        fp = profiler.device_fingerprint()
        assert profiler.fingerprint_digest(fp) == \
            profiler.fingerprint_digest(dict(fp))
        other = dict(fp, platform="tpu")
        assert profiler.fingerprint_digest(fp) != \
            profiler.fingerprint_digest(other)

    def test_fast_profile_feeds_cost_model(self):
        # the tiniest real measurement pass: structure must be complete and
        # the resulting model usable by the planner
        calib = profiler.run_profile(fast=True, L=6, repeats=1)
        cm = CostModel.from_calibration(calib["measurements"])
        assert cm.best_fusion_size() >= 1
        for field in ("pass_us", "mxu_us_per_2k", "launch_us",
                      "shm_gate_us", "shm_diag_gate_us", "host_link_gbps"):
            assert calib["measurements"][field] > 0
        plan = partition(qft(6), 4, 2, 0, cost_model=cm)
        assert plan.n_stages >= 1

    def test_engine_timings_recorded(self):
        eng = engine_for(qft(6), 4, 2, 0, backend="offload", cache=None)
        eng.run()
        snap = eng.timing_snapshot()
        assert snap["engine.run"]["count"] == 1
        assert snap["engine.run"]["mean_s"] > 0
        # eager offload backend records each stage individually
        assert snap["engine.run/execute/offload_stage"]["count"] == eng.plan.n_stages


# ======================================================================
# Autotuner
# ======================================================================


def _solves():
    return (staging.SOLVER_CALLS["ilp"], staging.SOLVER_CALLS["greedy"],
            kernelization.SOLVER_CALLS["dp"])


class TestAutotune:
    def test_candidates_default_first_and_unique(self):
        cands = default_candidates(R=2, G=0)
        assert cands[0].name == "default"
        names = [c.name for c in cands]
        assert len(names) == len(set(names))
        # comm-weight variants only exist when a non-local tier exists
        local_only = default_candidates(R=0, G=0)
        assert not any(c.name.startswith("comm_weight")
                       for c in local_only)

    def test_winner_cached_zero_solves_zero_retraces(self):
        circ = su2random(8)
        cache = CompileCache(maxsize=8)
        res = autotune_engine(circ, 6, 2, 0, repeats=2, cache=cache)
        assert res.chosen in res.replay_us
        s0 = _solves()
        eng = engine_for(circ, 6, 2, 0, cache=cache)
        assert _solves() == s0, "tuned hit must not re-solve ILP/DP"
        assert eng is res.engine
        x0 = eng.xla_compiles
        out = eng.run()
        assert eng.xla_compiles == x0, "tuned replay must not retrace"
        assert_states_close(out, simulate(circ))
        assert eng.provenance["autotune"]["chosen"] == res.chosen

    def test_memoized_retune_is_free(self):
        circ = qft(7)
        cache = CompileCache(maxsize=8)
        cands = [PlanCandidate("default", DEFAULT_COST_MODEL),
                 PlanCandidate("greedy", DEFAULT_COST_MODEL,
                               kernelize_method="greedy")]
        autotune_engine(circ, 5, 2, 0, candidates=cands, repeats=1,
                        cache=cache)
        s0 = _solves()
        res2 = autotune_engine(circ, 5, 2, 0, candidates=cands, repeats=1,
                               cache=cache)
        assert res2.cached
        assert _solves() == s0, "memoized retune must not replan anything"
        assert len(tuned_outcomes()) == 1

    def test_hysteresis_keeps_default_on_marginal_win(self):
        circ = qft(7)
        cache = CompileCache(maxsize=8)
        res = autotune_engine(
            circ, 5, 2, 0, cache=cache, repeats=2,
            candidates=[PlanCandidate("default", DEFAULT_COST_MODEL),
                        PlanCandidate("same", DEFAULT_COST_MODEL.
                                      with_overrides(launch_us=10.001))],
            min_speedup=1e9)  # nothing can clear this bar
        assert res.chosen == "default"

    def test_symbolic_circuit_tunable(self):
        from repro.core.generators import PARAM_FAMILIES

        sym = PARAM_FAMILIES["su2param"](8)
        cache = CompileCache(maxsize=8)
        res = autotune_engine(sym, 6, 2, 0, repeats=1, cache=cache,
                              candidates=default_candidates(R=2, G=0)[:2])
        theta = {n: 0.3 for n in sym.param_names}
        eng = engine_for(sym.bind(theta), 6, 2, 0, cache=cache)
        assert eng is res.engine  # structural hit rebinds the tuned engine
        assert_states_close(eng.run(), simulate(sym.bind(theta)))

    def test_empty_candidates_raise(self):
        with pytest.raises(ValueError):
            autotune_engine(qft(6), 4, 2, 0, candidates=[])


# ======================================================================
# Serving surface
# ======================================================================


class TestServingSurface:
    def test_metrics_info_blob(self):
        from repro.serve.metrics import Metrics

        m = Metrics()
        m.set_info("autotune", [{"chosen": "default"}])
        snap = m.snapshot()
        assert snap["info"]["autotune"][0]["chosen"] == "default"
        assert "info" not in Metrics().snapshot()

    def test_service_stats_expose_planning_provenance(self):
        import asyncio

        from repro.serve.service import ServeConfig, SimRequest, \
            SimulationService

        async def go():
            async with SimulationService(ServeConfig()) as svc:
                await svc.submit(SimRequest(circuit=qft(6)))
                return svc.stats()

        stats = asyncio.run(go())
        assert stats["calibration"]["source"] in (
            "analytic", "calibrated", "disabled", "mismatch", "error")
        assert isinstance(stats["autotune"], list)
        assert stats["warm_pool"]["engine_timings"]
