"""Multi-pod dry-run of the quantum simulator itself (the paper's workload at
production scale): lower + compile the explicit-collective executor for a
36-qubit circuit on the 512-chip (2x16x16) bit-mesh, and derive the roofline
terms. Also validates the ILP's Eq. 2 communication model against the actual
HLO collective traffic.

State: 2^36 complex64 = 512 GiB -> 1 GiB/chip (fits v5e HBM).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import Dict

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
OUT = os.path.join(os.path.dirname(__file__), "dryrun_results")

_SUB = r"""
import json, sys, time
from repro.core.generators import FAMILIES
from repro.core.partition import partition
from repro.sim.shardmap_executor import ShardMapExecutor
from repro.launch import hlo_analysis as ha

fam, n, L, R, G = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]), int(sys.argv[5])
shm_q = int(sys.argv[6]) if len(sys.argv) > 6 else 13
from repro.core.cost_model import CostModel
c = FAMILIES[fam](n)
t0 = time.time()
plan = partition(c, L, R, G, time_limit=120, cost_model=CostModel(max_shm_qubits=shm_q))
t_part = time.time() - t0
ex = ShardMapExecutor(c, plan)
t0 = time.time()
lowered = ex.lower()
compiled = lowered.compile()
t_compile = time.time() - t0
mem = compiled.memory_analysis()
hw = ha.HardwareSpec()
rl = ha.roofline_from_hlo(compiled.as_text(), 1 << (R + G), peak=hw.fp32_flops)
# Eq. 2 traffic model: each changed local qubit ~ half the state crosses links
amps = 2 ** n
eq2_bytes_global = plan.staging_objective * amps * 8 / 2
print(json.dumps({
    "family": fam, "n": n, "L": L, "R": R, "G": G,
    "stages": plan.n_stages, "gates": c.n_gates,
    "partition_s": t_part, "compile_s": t_compile,
    "eq2_objective": plan.staging_objective,
    "eq2_pred_bytes_per_dev": eq2_bytes_global / (1 << (R + G)),
    "memory_analysis": str(mem),
    "roofline": rl.as_dict(),
}))
"""


def run_cell(fam: str, n: int, L: int, R: int, G: int, devices: int, shm_q: int = 13) -> Dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", _SUB, fam, str(n), str(L), str(R), str(G), str(shm_q)],
                       capture_output=True, text=True, timeout=3600, env=env)
    if r.returncode != 0:  # the parent stays JAX-free; a failed child fails
        raise RuntimeError(f"child {fam} n={n} failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", default="qft")
    ap.add_argument("--n", type=int, default=36)
    ap.add_argument("--multi-pod", action="store_true", default=True)
    ap.add_argument("--no-multi-pod", dest="multi_pod", action="store_false")
    ap.add_argument("--shm-qubits", type=int, default=13)
    args = ap.parse_args(argv)

    n = args.n
    # 512 chips = 9 non-local qubits (1 global/pod + 8 regional/ICI)
    R, G = (8, 1) if args.multi_pod else (8, 0)
    L = n - R - G
    devices = 1 << (R + G)
    res = run_cell(args.family, n, L, R, G, devices, args.shm_qubits)
    os.makedirs(OUT, exist_ok=True)
    tag = 'multi' if args.multi_pod else 'single'
    if args.shm_qubits != 13:
        tag += f'_shm{args.shm_qubits}'
    path = os.path.join(OUT, f"sim__{args.family}{n}__{tag}.json")
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    rl = res["roofline"]
    print(f"sim dry-run {args.family}({n}) on {devices} chips "
          f"(L/R/G={L}/{R}/{G}): {res['stages']} stages, "
          f"compile {res['compile_s']:.0f}s")
    print(f"  t_compute={rl['t_compute_s']:.4f}s t_memory={rl['t_memory_s']:.4f}s "
          f"t_collective={rl['t_collective_s']:.4f}s dominant={rl['dominant']}")
    print(f"  collective bytes/dev: {rl['coll_bytes']/1e9:.2f} GB ; "
          f"Eq.2 prediction: {res['eq2_pred_bytes_per_dev']/1e9:.2f} GB")
    return res


if __name__ == "__main__":
    main()
