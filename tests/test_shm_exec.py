"""Single-pass stage execution tests: SHM groups through the Pallas VMEM
kernel, compile-time op-stream fusion (peephole), and the double-buffered
offload path.

The cost model below makes fusion kernels expensive so the kernelizer picks
shared-memory kernels — the compiled programs then contain ``shm`` ops with
multi-gate member lists, which is the regime these tests exercise. The oracle
is always ``simulate_np`` (complex128 dense numpy).
"""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import generators as gen
from repro.core.partition import partition
from repro.kernels import ops as kops
from repro.sim.compile import compile_plan
from conftest import assert_states_close, planned_engine
from repro.sim.statevector import fidelity, simulate_np

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

from strategies import SHM_CM  # shared shm-forcing cost model


def _n_shm_ops(cc):
    return sum(1 for p in cc.programs for op in p.ops if op.kind == "shm")


def test_compile_emits_single_shm_op_per_group():
    c = gen.qft(8)
    plan = partition(c, 6, 2, 0, cost_model=SHM_CM)
    cc = compile_plan(c, plan)
    shm_ops = [op for p in cc.programs for op in p.ops if op.kind == "shm"]
    assert shm_ops, "forced-shm plan must compile to shm ops"
    for op in shm_ops:
        assert len(op.gates) >= 2
        assert op.local_bits == tuple(
            sorted({b for m in op.gates for b in m.local_bits})
        )
    # a stage's pass count is its op count, NOT its gate count
    assert cc.total_passes < cc.total_gates
    # every gate lands in exactly one op
    per_stage_gids = {
        si: sorted(g for op in p.ops for g in op.gate_ids)
        for si, p in enumerate(cc.programs)
    }
    all_gids = sorted(g for gids in per_stage_gids.values() for g in gids)
    assert all_gids == sorted(set(all_gids))


def test_peephole_reduces_passes_and_preserves_state():
    c = gen.qft(8)
    plan = partition(c, 6, 2, 0)
    fused = compile_plan(c, plan, peephole=True)
    raw = compile_plan(c, plan, peephole=False)
    assert fused.total_passes <= raw.total_passes
    assert fused.total_gates == raw.total_gates
    ref = simulate_np(c)
    for peep in (True, False):
        ex = planned_engine(c, plan, backend="offload", peephole=peep)
        assert_states_close(ex.run(), ref)


def test_shm_group_is_one_pallas_call():
    """An shm group of g gates must trace to exactly ONE pallas_call."""
    c = gen.qft(7)
    plan = partition(c, 7, 0, 0, cost_model=SHM_CM)
    kops.reset_kernel_counters()
    ex = planned_engine(c, plan, backend="shardmap", use_pallas=True)
    ex.lower()  # trace without executing
    counts = kops.kernel_call_counts()
    n_shm = _n_shm_ops(ex.cc)
    assert n_shm >= 1
    assert counts["shm"] == n_shm, (counts, n_shm)
    # the group bundles several gates into that single call
    shm_gates = sum(
        op.n_gates for p in ex.cc.programs for op in p.ops if op.kind == "shm"
    )
    assert shm_gates > counts["shm"]


def test_shardmap_pallas_shm_matches_oracle_single_device():
    c = gen.qft(7)
    plan = partition(c, 7, 0, 0, cost_model=SHM_CM)
    ref = jnp.asarray(simulate_np(c))
    ex = planned_engine(c, plan, backend="shardmap", use_pallas=True)
    assert _n_shm_ops(ex.cc) >= 1
    assert_states_close(ex.run(), ref)


def test_staged_executor_pallas_shm_dep_batched():
    """Packed pjit-path executor with R=2: shm members carry dep-batched
    tensors resolved per shard (vmapped pallas_call)."""
    c = gen.qft(8)
    plan = partition(c, 6, 2, 0, cost_model=SHM_CM)
    ref = jnp.asarray(simulate_np(c))
    ex = planned_engine(c, plan, use_pallas=True)
    shm_ops = [op for p in ex.cc.programs for op in p.ops if op.kind == "shm"]
    assert shm_ops
    assert any(m.dep_bits for op in shm_ops for m in op.gates), \
        "test must exercise dep-batched shm members"
    assert_states_close(ex.run(), ref)


@pytest.mark.parametrize("seed", [0, 1])
def test_staged_executor_pallas_shm_random_with_flips(seed):
    """Random circuits (X/Y gates -> lazy flips) through the Pallas shm path."""
    c = gen.random_circuit(8, 40, seed=seed)
    plan = partition(c, 5, 2, 1, cost_model=SHM_CM)
    ref = jnp.asarray(simulate_np(c))
    ex = planned_engine(c, plan, use_pallas=True)
    assert_states_close(ex.run(), ref)


@pytest.mark.slow
def test_shardmap_pallas_shm_distributed():
    """shard_map path on 4 devices: dep selection via lax.axis_index inside
    the shm group, one pallas_call per group, oracle equivalence."""
    code = """
from repro.core import generators as gen
from repro.core.cost_model import CostModel
from repro.core.partition import partition
from repro.kernels import ops as kops
from repro.sim.engine import engine_for
from repro.sim.statevector import simulate, fidelity
cm = CostModel(mxu_us_per_2k=1e7, shm_gate_us=1.0, shm_diag_gate_us=0.5)
c = gen.qft(8)
plan = partition(c, 6, 2, 0, cost_model=cm)
kops.reset_kernel_counters()
ex = engine_for(c, 6, 2, 0, plan=plan, backend='shardmap', use_pallas=True,
               degrade=False)
f = fidelity(ex.run(), simulate(c))
assert f > 0.9999, f
n_shm = sum(1 for p in ex.cc.programs for op in p.ops if op.kind == 'shm')
assert n_shm >= 1
assert kops.kernel_call_counts()['shm'] == n_shm
c2 = gen.random_circuit(8, 45, seed=3)
plan2 = partition(c2, 5, 2, 1, cost_model=cm)
ex2 = engine_for(c2, 5, 2, 1, plan=plan2, backend='shardmap', use_pallas=True,
                degrade=False)
f2 = fidelity(ex2.run(), simulate(c2))
assert f2 > 0.9999, f2
print('OK')
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, f"subprocess failed:\n{r.stdout}\n{r.stderr[-3000:]}"
    assert "OK" in r.stdout


def test_offload_prestages_tensors_and_overlaps():
    c = gen.qft(9)
    plan = partition(c, 6, 3, 0)
    ref = jnp.asarray(simulate_np(c))
    ex = planned_engine(c, plan, backend="offload")
    out = ex.run()
    assert_states_close(out, ref)
    st = ex.stats
    n_stages = len(ex.cc.programs)
    n_shards = 1 << ex.n_nonlocal
    assert st["shard_transfers"] == n_stages * n_shards
    # no per-shard tensor re-upload: one upload per op, slices reused
    n_ops = sum(
        len(op.gates) if op.kind == "shm" else 1
        for p in ex.cc.programs for op in p.ops
    )
    assert st["tensor_uploads"] <= n_ops
    assert st["tensor_uploads"] < st["shard_transfers"] or n_ops >= st["shard_transfers"]
    # double buffering: every dispatch except one drain per stage overlaps
    assert st["overlapped_dispatches"] == st["shard_transfers"] - n_stages
    assert ex.overlap_ratio > 0.5


def test_offload_shm_plan_matches_oracle():
    c = gen.qft(8)
    plan = partition(c, 6, 2, 0, cost_model=SHM_CM)
    ref = jnp.asarray(simulate_np(c))
    ex = planned_engine(c, plan, backend="offload")
    assert _n_shm_ops(ex.cc) >= 1
    assert_states_close(ex.run(), ref)
