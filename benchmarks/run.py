"""Benchmark driver: one harness per paper table/figure.

Prints ``name,us_per_call,derived`` CSV summary lines (plus each harness's
own detailed CSV). Scaled-down defaults finish on one CPU core; pass
``--paper-scale`` for the paper's circuit sizes.

Harness -> paper artifact map:
  bench_staging    -> Fig. 9 / Fig. 12 (stage counts, ILP vs SnuQS greedy)
  bench_kernelize  -> Fig. 10 / Fig. 13 (kernelization cost + pruning sweep)
  bench_e2e        -> Fig. 5 (weak scaling, distributed executor)
  bench_offload    -> Fig. 7 / Fig. 8 (DRAM offloading vs QDAO-style)
  bench_spill      -> spill tier: capacity gain + overlap under DRAM budget
  bench_breakdown  -> Fig. 6 (comm/comp breakdown)
  bench_sampling   -> measurement subsystem (shots/marginals/expectations)
  bench_engine     -> unified engine: compile cache + batched states (serving)
  bench_param_sweep-> parameterized serving: warm rebind + fused sweeps
  bench_vqe        -> variational workloads: adjoint vs parameter-shift grads
  bench_serve      -> serving layer: structure-keyed dynamic batching under load
  bench_autotune   -> profile-guided planning: A/B plan replay + cached winners
  bench_optimize   -> pre-staging circuit optimizer: gates/stages removed
  bench_sim_dryrun -> production-scale dry-run of the simulator (512 chips)
"""

from __future__ import annotations

import argparse
import json
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper-scale", action="store_true")
    ap.add_argument(
        "--skip", default="sim_dryrun",
        help="comma list: staging,kernelize,e2e,offload,spill,breakdown,"
             "sampling,engine,param_sweep,vqe,serve,autotune,optimize,"
             "sim_dryrun",
    )
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="also write the summary as JSON (CI uploads this artifact so "
             "the perf trajectory accumulates across commits)",
    )
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    skip = set(args.skip.split(",")) if args.skip else set()

    summary = []

    def section(name):
        print(f"\n{'=' * 70}\n== {name}\n{'=' * 70}", flush=True)

    if "staging" not in skip:
        section("bench_staging (Fig. 9/12: #stages, ILP vs SnuQS-greedy)")
        from . import bench_staging

        t0 = time.time()
        rows = bench_staging.main(["--paper-scale"] if args.paper_scale else [])
        dt = time.time() - t0
        wins = sum(1 for r in rows if r["ilp_stages"] < r["greedy_stages"])
        ties = sum(1 for r in rows if r["ilp_stages"] == r["greedy_stages"])
        summary.append(("bench_staging", 1e6 * dt / max(len(rows), 1),
                        f"ilp_better_or_equal={wins + ties}/{len(rows)}"))

    if "kernelize" not in skip:
        section("bench_kernelize (Fig. 10/13: kernelization cost)")
        from . import bench_kernelize

        t0 = time.time()
        rows = bench_kernelize.main(["--paper-scale"] if args.paper_scale else [])
        dt = time.time() - t0
        import numpy as np

        rel = float(np.exp(np.mean(np.log([r["dp_cost"] / r["greedy_cost"]
                                           for r in rows]))))
        summary.append(("bench_kernelize", 1e6 * dt / max(len(rows), 1),
                        f"dp_vs_greedy_geomean={rel:.3f}"))

    if "e2e" not in skip:
        section("bench_e2e (Fig. 5: weak scaling)")
        from . import bench_e2e

        t0 = time.time()
        rows = bench_e2e.main([])
        dt = time.time() - t0
        summary.append(("bench_e2e", 1e6 * dt / max(len(rows), 1),
                        f"cells={len(rows)}"))

    if "offload" not in skip:
        section("bench_offload (Fig. 7/8: DRAM offloading vs per-gate)")
        from . import bench_offload

        t0 = time.time()
        rows = bench_offload.main([])
        dt = time.time() - t0
        ratio = rows[-1]["pergate_transfers"] / rows[-1]["atlas_transfers"]
        overlap = rows[-1]["atlas_overlap"]
        summary.append(("bench_offload", 1e6 * dt / max(len(rows), 1),
                        f"transfer_reduction={ratio:.1f}x overlap={overlap:.2f}"))

    if "spill" not in skip:
        section("bench_spill (tiered shard store: capacity + overlap)")
        from . import bench_spill

        t0 = time.time()
        rows = bench_spill.main([])
        dt = time.time() - t0
        best = max(rows, key=lambda r: r["max_n_gain"])
        overlap = min(r["spill_overlap"] for r in rows)
        summary.append(("bench_spill", 1e6 * dt / max(len(rows), 1),
                        f"max_n_gain=+{best['max_n_gain']}q "
                        f"spill_overlap>={overlap:.2f}"))

    if "breakdown" not in skip:
        section("bench_breakdown (Fig. 6: comm/comp fractions)")
        from . import bench_breakdown

        t0 = time.time()
        rows = bench_breakdown.main([])
        dt = time.time() - t0
        if rows:
            fusion = sum(r["gates_per_stage"] for r in rows) / max(
                sum(r["passes_per_stage"] for r in rows), 1e-9)
            derived = f"gates_per_pass={fusion:.1f}"
        else:
            derived = "roofline-derived"
        summary.append(("bench_breakdown", 1e6 * dt / 3, derived))

    if "sampling" not in skip:
        section("bench_sampling (measurement: shots/marginals/expectations)")
        from . import bench_sampling

        t0 = time.time()
        rows = bench_sampling.main([])
        dt = time.time() - t0
        worst = max(r["sample_s"] for r in rows)
        summary.append(("bench_sampling", 1e6 * dt / max(len(rows), 1),
                        f"worst_sample_s={worst:.3f}"))

    if "engine" not in skip:
        section("bench_engine (compile cache + batched states: serving)")
        from . import bench_engine

        t0 = time.time()
        rows = bench_engine.main([])
        dt = time.time() - t0
        cache_sp = min(r["cache_speedup"] for r in rows)
        batch_sp = max(r["batch_speedup"] for r in rows)
        summary.append(("bench_engine", 1e6 * dt / max(len(rows), 1),
                        f"cache_speedup={cache_sp:.1f}x "
                        f"batch_speedup={batch_sp:.2f}x"))

    if "param_sweep" not in skip:
        section("bench_param_sweep (parameterized serving: rebind + sweeps)")
        from . import bench_param_sweep

        t0 = time.time()
        rows = bench_param_sweep.main([])
        dt = time.time() - t0
        rebind = min(r["rebind_speedup"] for r in rows)
        sweep = max(r["sweep_speedup"] for r in rows)
        summary.append(("bench_param_sweep", 1e6 * dt / max(len(rows), 1),
                        f"rebind_speedup={rebind:.1f}x "
                        f"sweep_speedup={sweep:.2f}x"))

    if "vqe" not in skip:
        section("bench_vqe (variational: adjoint vs parameter-shift)")
        from . import bench_vqe

        t0 = time.time()
        rows = bench_vqe.main([])
        dt = time.time() - t0
        best = max(r["adjoint_speedup"] for r in rows)
        retr = sum(r["retraces"] for r in rows)
        summary.append(("bench_vqe", 1e6 * dt / max(len(rows), 1),
                        f"adjoint_speedup={best:.1f}x retraces={retr}"))

    if "serve" not in skip:
        section("bench_serve (serving: structure-keyed dynamic batching)")
        from . import bench_serve

        t0 = time.time()
        rows = bench_serve.main([])
        dt = time.time() - t0
        closed = next(r for r in rows if r["mode"] == "closed")
        opened = next(r for r in rows if r["mode"] == "open")
        n_req = sum(r["requests"] for r in rows)
        summary.append(("bench_serve", 1e6 * dt / max(n_req, 1),
                        f"batching_speedup={closed['speedup']:.2f}x "
                        f"coalesce={closed['coalesce_factor']:.1f}x "
                        f"open_p99={opened['p99_ms']:.0f}ms"))

    autotune_rows = None
    if "autotune" not in skip:
        section("bench_autotune (profile-guided plan A/B replay)")
        from . import bench_autotune

        t0 = time.time()
        autotune_rows = bench_autotune.main([])
        dt = time.time() - t0
        best = max(autotune_rows, key=lambda r: r["improvement_pct"])
        never_slower = all(r["tuned_us"] <= r["default_us"] * 1.05
                           for r in autotune_rows)
        summary.append((
            "bench_autotune", 1e6 * dt / max(len(autotune_rows), 1),
            f"best_improvement={best['improvement_pct']:.1f}%"
            f"({best['family']}:{best['chosen']}) "
            f"never_slower={never_slower}"))

    if "optimize" not in skip:
        section("bench_optimize (pre-staging optimizer: gates/stages removed)")
        from . import bench_optimize

        t0 = time.time()
        rows = bench_optimize.main([])
        dt = time.time() - t0
        red = next(r for r in rows if r["family"] == "redundant")
        never_more = all(r["gates_after"] <= r["gates_before"] for r in rows)
        summary.append((
            "bench_optimize", 1e6 * dt / max(len(rows), 1),
            f"redundant_removed={red['gates_removed']} "
            f"stages={red['stages_before']}->{red['stages_after']} "
            f"speedup={red['speedup']:.2f}x never_more_gates={never_more}"))

    if "sim_dryrun" not in skip:
        section("bench_sim_dryrun (512-chip simulator dry-run)")
        from . import bench_sim_dryrun

        t0 = time.time()
        bench_sim_dryrun.main([])
        dt = time.time() - t0
        summary.append(("bench_sim_dryrun", 1e6 * dt, "see dryrun_results/"))

    print(f"\n{'=' * 70}\n== summary CSV\n{'=' * 70}")
    print("name,us_per_call,derived")
    for name, us, derived in summary:
        print(f"{name},{us:.0f},{derived}")
    if args.json:
        payload = {"rows": [{"name": n, "us_per_call": us, "derived": d}
                            for n, us, d in summary]}
        if autotune_rows is not None:
            # per-family autotune outcome (chosen plan, speedup, candidate
            # replay times) + the calibration this process planned with
            from repro.sim.profiler import resolve_calibration

            _, calib_info = resolve_calibration()
            payload["autotune"] = {
                "calibration": calib_info,
                "families": autotune_rows,
            }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"(summary JSON written to {args.json})")


if __name__ == "__main__":
    main()
