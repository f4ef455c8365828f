"""The shard_map backend with the Pallas kernels (interpreted on a CPU) over
four virtual devices at L = 10, R = 2: its states, the record of its remaps
(``kops.remap_stats``) and the named scopes of its program. Each test runs
in a subprocess with its own XLA_FLAGS, so the main pytest process keeps a
single device."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str) -> dict:
    """Run ``code`` over four virtual CPU devices; its last stdout line is
    JSON."""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               REPRO_CALIBRATION="off",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"subprocess failed:\n{r.stdout}\n{r.stderr[-3000:]}"
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("circuit", ["qft(12)", "random_circuit(12, 80, seed=0)",
                                     "random_circuit(12, 80, seed=5)"])
def test_shardmap_pallas_matches_oracle(circuit):
    """qft(12), and seeded random circuits whose remaps flip a device bit
    (seed 0 between the stages, seed 5 in the final remap), against
    ``simulate_np``; every shm group runs as a Pallas call."""
    out = run_sub(f"""
        import json
        import numpy as np
        from repro.core import generators as gen
        from repro.core.partition import partition
        from repro.kernels import ops as kops
        from repro.sim.engine import engine_for
        from repro.sim.statevector import simulate_np

        c = gen.{circuit}
        plan = partition(c, 10, 2, 0, staging_method="ilp", kernelize_method="dp")
        kops.reset_kernel_counters()
        eng = engine_for(c, 10, 2, 0, backend="shardmap", use_pallas=True,
                         degrade=False, plan=plan)
        psi = eng.run()
        shm = sum(op.kind == "shm" for p in eng.cc.programs for op in p.ops)
        print(json.dumps({{
            "err": float(np.abs(np.asarray(psi) - simulate_np(c)).max()),
            "devices": len(psi.sharding.device_set),
            "flips": sorted({{b for s in [eng.cc.initial_remap, eng.cc.final_remap]
                              + [p.remap_after for p in eng.cc.programs]
                              if s is not None for b in s.flip_bits}}),
            "shm": shm, "calls": kops.kernel_call_counts(),
            "degraded": bool(eng.provenance.get("degraded"))}}))
    """)
    assert out["err"] < 1e-5 and out["devices"] == 4 and not out["degraded"]
    assert out["shm"] >= 1 and out["calls"]["shm"] == out["shm"]
    if circuit != "qft(12)":
        assert out["flips"] and max(out["flips"]) >= 10  # a device bit


def test_remap_stats_count_each_program_once():
    """qft(12) over L = 10, R = 2: two all-to-alls of two bits (each chip
    sends 3/4 of its 8 KiB shard), then a final remap of one bit with a
    ppermute; a second trace of the program (``lower()``) counts nothing
    more, and the reset clears the record."""
    out = run_sub("""
        import json
        from repro.core import generators as gen
        from repro.kernels import ops as kops
        from repro.sim.engine import engine_for

        kops.reset_kernel_counters()
        eng = engine_for(gen.qft(12), 10, 2, 0, backend="shardmap",
                         use_pallas=True, degrade=False, cache=None)
        eng.run()
        first = kops.remap_stats()
        eng.backend.lower()
        second = kops.remap_stats()
        kops.reset_kernel_counters()
        print(json.dumps({"first": first, "second": second,
                          "reset": kops.remap_stats()}))
    """)
    first = out["first"]
    assert first == out["second"]
    assert [(r["slot"], r["m"], r["a2a_bytes"], r["ppermute"])
            for r in first["remaps"]] == [
        ("init", 2, 0.75 * 8 * 2**10, False), (0, 2, 0.75 * 8 * 2**10, False),
        ("final", 1, 0.5 * 8 * 2**10, True)]
    assert first["programs"] == 1
    assert out["reset"]["programs"] == 0 and out["reset"]["remaps"] == []


def test_shardmap_program_carries_the_named_scopes():
    """qft(16) over L = 14, R = 2 holds shm groups, fused gates and XLA
    diagonals: the shard_map program names them as the pjit path does, its
    remaps ``remap`` and their collectives ``a2a``."""
    out = run_sub("""
        import json
        from repro.core import generators as gen
        from repro.sim.engine import engine_for

        eng = engine_for(gen.qft(16), 14, 2, 0, backend="shardmap",
                         use_pallas=True, degrade=False, cache=None)
        text = eng.backend.lower().as_text(debug_info=True)
        kinds = sorted({op.kind for p in eng.cc.programs for op in p.ops})
        scopes = ["shm", "fused", "diag", "remap", "a2a", "operands", "route"]
        # a scope heads an op's location inside the shard_map body
        print(json.dumps({"kinds": kinds, "found": [
                              s for s in scopes
                              if f'"{s}/' in text or f"/{s}/" in text],
                          "a2a": "remap/a2a/all_to_all" in text}))
    """)
    assert {"shm", "fused", "diag"} <= set(out["kinds"])
    assert out["found"] == ["shm", "fused", "diag", "remap", "a2a", "operands", "route"]
    assert out["a2a"]
