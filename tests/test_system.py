"""End-to-end behaviour tests for the paper's system.

The paper's pipeline: PARTITION (ILP staging + DP kernelization) -> staged
execution == dense reference, with communication confined to stage
boundaries, plus the hlo-analysis roofline machinery used by the dry-run.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import generators as gen
from repro.core.partition import partition
from repro.launch import hlo_analysis as ha
from conftest import assert_states_close, planned_engine
from repro.sim.statevector import fidelity, simulate


def test_end_to_end_paper_pipeline():
    """Full Atlas pipeline on a 10-qubit qft: ILP stages it in fewer stages
    than greedy, kernelizes cheaper than greedy packing, simulates exactly."""
    c = gen.qft(10)
    plan_dp = partition(c, 7, 2, 1, kernelize_method="dp")
    plan_greedy = partition(c, 7, 2, 1, staging_method="greedy",
                            kernelize_method="greedy")
    assert plan_dp.n_stages <= plan_greedy.n_stages
    assert plan_dp.total_kernel_cost < plan_greedy.total_kernel_cost
    out = planned_engine(c, plan_dp).run()
    assert_states_close(out, simulate(c))


def test_communication_only_between_stages():
    """Within-stage ops touch only local axes: the single-device program of
    the whole execution contains no collective ops."""
    import re

    c = gen.qft(10)
    plan = partition(c, 7, 2, 1)
    ex = planned_engine(c, plan, backend_kw={"donate": False})
    hlo = ex.lower().compile().as_text()
    assert not re.search(r"all-to-all|all-reduce|all-gather", hlo)


def test_hlo_analyzer_on_known_program():
    """Trip-count-aware analyzer: a scan of 5 matmuls must count 5x flops."""
    import jax

    def f(x, w):
        def body(c, _):
            return c @ w, ()

        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    m, n = 64, 64
    hlo = (
        jax.jit(f)
        .lower(jnp.zeros((m, n), jnp.float32), jnp.zeros((n, n), jnp.float32))
        .compile()
        .as_text()
    )
    a = ha.analyze_hlo(hlo)
    want = 5 * 2 * m * n * n
    assert abs(a["flops"] - want) / want < 0.05, (a["flops"], want)


def test_active_params_sane():
    from repro.configs.registry import get_arch

    # deepseek-v3: ~37B active of 671B total (public figure)
    act = ha.active_params(get_arch("deepseek-v3-671b"))
    assert 25e9 < act < 50e9, act
    # qwen2-1.5b: ~1.5B dense
    q = ha.active_params(get_arch("qwen2-1.5b"))
    assert 1.0e9 < q < 2.5e9, q
    # mistral-nemo ~12B
    mn = ha.active_params(get_arch("mistral-nemo-12b"))
    assert 9e9 < mn < 15e9, mn


def test_collective_census_parses_real_hlo():
    import jax

    hlo = jax.jit(lambda x: x @ x).lower(jnp.zeros((8, 8))).compile().as_text()
    a = ha.analyze_hlo(hlo)
    assert a["collectives"] == {}
    assert a["flops"] == 2 * 8 * 8 * 8
