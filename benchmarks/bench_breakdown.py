"""Fig. 6 analogue: communication/computation breakdown of distributed
simulation, derived from the compiled HLO roofline terms (v5e constants) at
increasing device counts (subprocess per mesh size).

Also reports the compiled pass structure: ``passes_per_stage`` is the mean
number of HBM read+write passes a stage costs (top-level ops after peephole
fusion; an shm group of g gates is ONE pass), vs ``gates_per_stage`` — the
per-gate cost a fusion-free executor would pay. The gap is the win from
compile-time op-stream fusion + the VMEM shm kernel."""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import Dict, List

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SUB = r"""
import json, sys
from repro.core.generators import FAMILIES
from repro.core.partition import partition
from repro.sim.shardmap_executor import ShardMapExecutor
from repro.launch import hlo_analysis as ha

fam, n, L, R, G = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]), int(sys.argv[5])
c = FAMILIES[fam](n)
plan = partition(c, L, R, G, time_limit=30)
ex = ShardMapExecutor(c, plan)
hlo = ex.lower().compile().as_text()
hw = ha.HardwareSpec()
rl = ha.roofline_from_hlo(hlo, 1 << (R + G), peak=hw.fp32_flops)
from repro.core.cost_model import stage_pass_us
cc = ex.cc
n_stages = max(len(cc.programs), 1)
print(json.dumps({
    "stages": plan.n_stages,
    "passes_per_stage": cc.total_passes / n_stages,
    "gates_per_stage": cc.total_gates / n_stages,
    "shm_groups": sum(p.n_shm_groups for p in cc.programs),
    "t_pass_model_s": sum(stage_pass_us(p.n_passes, L) for p in cc.programs) / 1e6,
    **rl.as_dict(),
}))
"""


def run_cell(fam, n, L, R, G) -> Dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={1 << (R + G)}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", _SUB, fam, str(n), str(L), str(R), str(G)],
                       capture_output=True, text=True, timeout=1200, env=env)
    if r.returncode != 0:  # the parent stays JAX-free; a failed child fails
        raise RuntimeError(f"child {fam} n={n} failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", default="qft")
    ap.add_argument("--L", type=int, default=16)
    args = ap.parse_args(argv)
    fam, L = args.family, args.L
    print("# comm/comp breakdown (roofline terms, v5e constants)")
    print("family,n,devices,stages,passes_per_stage,gates_per_stage,shm_groups,"
          "t_pass_model_s,t_compute_s,t_memory_s,t_collective_s,comm_frac")
    rows = []
    for extra, (R, G) in [(1, (1, 0)), (2, (2, 0)), (3, (2, 1))]:
        n = L + extra
        res = run_cell(fam, n, L, R, G)
        tc, tm, tl = res["t_compute_s"], res["t_memory_s"], res["t_collective_s"]
        frac = tl / (tl + max(tc, tm))
        print(f"{fam},{n},{1 << extra},{res['stages']},"
              f"{res['passes_per_stage']:.2f},{res['gates_per_stage']:.2f},"
              f"{res['shm_groups']},{res['t_pass_model_s']:.4g},"
              f"{tc:.4g},{tm:.4g},{tl:.4g},{frac:.3f}")
        rows.append({"family": fam, "n": n, "devices": 1 << extra, **res})
    return rows


if __name__ == "__main__":
    main()
