"""Fig. 5 analogue: weak-scaling end-to-end simulation.

On real TPUs this is a wall-clock weak-scaling run; on this CPU host we
(a) measure wall time for n = base..base+k qubits on 1..8 virtual devices
(subprocess per device count, the distributed shard_map executor), and
(b) compare the Atlas plan against a per-gate baseline (no kernelization) on
a single device — the HyQuas/cuQuantum-style comparison axis the paper uses.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import Dict, List

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SUB = r"""
import json, time, sys
import jax
from repro.core.generators import FAMILIES
from repro.core.partition import partition
from repro.sim.shardmap_executor import ShardMapExecutor

fam, n, L, R, G, reps = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]), int(sys.argv[5]), int(sys.argv[6])
c = FAMILIES[fam](n)
plan = partition(c, L, R, G, time_limit=30)
ex = ShardMapExecutor(c, plan)
out = ex.run()
out.block_until_ready()  # compile + first run
t0 = time.time()
for _ in range(reps):
    out = ex.run()
out.block_until_ready()
dt = (time.time() - t0) / reps
print(json.dumps({"time_s": dt, "stages": plan.n_stages,
                  "kernel_cost": plan.total_kernel_cost}))
"""


# single device, one kernel per gate (no fusion): the unkernelized
# comparison point
_SUB_PERGATE = r"""
import json, time, sys
import jax
from repro.core.generators import FAMILIES
from repro.sim.statevector import simulate

fam, n, reps = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
c = FAMILIES[fam](n)
fn = jax.jit(lambda: simulate(c))
fn().block_until_ready()
t0 = time.time()
for _ in range(reps):
    out = fn()
out.block_until_ready()
print(json.dumps({"time_s": (time.time() - t0) / reps}))
"""


def _child(code: str, args, devices: int = 1) -> Dict:
    """Run one measurement in a child process (the parent never imports
    JAX, so a child can own the device); a failed child fails the run."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code, *map(str, args)],
                       capture_output=True, text=True, timeout=1200, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"child {args} failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def run_cell(fam: str, n: int, L: int, R: int, G: int, reps: int = 3) -> Dict:
    return _child(_SUB, (fam, n, L, R, G, reps), devices=1 << (R + G))


def per_gate_baseline(fam: str, n: int, reps: int = 3) -> Dict:
    return _child(_SUB_PERGATE, (fam, n, reps))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", default="qft")
    ap.add_argument("--base-n", type=int, default=16)
    ap.add_argument("--max-extra", type=int, default=3)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args(argv)

    fam, L = args.family, args.base_n
    print("# weak scaling: n qubits, 2^(n-L) devices (L=%d local)" % L)
    print("family,n,devices,time_s,stages,gates_per_s")
    from repro.core.generators import FAMILIES

    rows = []
    for extra in range(args.max_extra + 1):
        n = L + extra
        R = min(extra, 2)
        G = extra - R
        res = run_cell(fam, n, L, R, G, args.reps)
        gates = FAMILIES[fam](n).n_gates
        rows.append(res)
        print(f"{fam},{n},{1 << extra},{res['time_s']:.4f},{res['stages']},"
              f"{gates / res['time_s']:.0f}")

    print("\n# kernelization speedup vs per-gate execution (single device)")
    print("family,n,atlas_time_s,pergate_time_s,speedup")
    n = L
    atlas = run_cell(fam, n, L, 0, 0, args.reps)
    pg = per_gate_baseline(fam, n, args.reps)
    print(f"{fam},{n},{atlas['time_s']:.4f},{pg['time_s']:.4f},"
          f"{pg['time_s'] / atlas['time_s']:.2f}")
    return rows


if __name__ == "__main__":
    main()
