"""End-to-end quantum circuit simulation driver (the paper's workload).

Examples (CPU):
  PYTHONPATH=src python -m repro.launch.simulate --circuit qft --n 20 --L 20
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.simulate --circuit qft --n 22 \
      --L 19 --R 2 --G 1 --executor shardmap

Measurement (shots / marginals / Pauli expectations — the result API; no
backend gathers the 2^n probability vector to one device):
  PYTHONPATH=src python -m repro.launch.simulate --circuit qft --n 20 \
      --L 17 --R 3 --executor offload --shots 1024 \
      --marginal 0,1,2 --observable "Z0 Z1 + 0.5*X2"

Unified engine (serving path: compile cache + batched initial states):
  PYTHONPATH=src python -m repro.launch.simulate --circuit qft --n 18 \
      --L 15 --R 3 --executor offload --engine --batch 4 --shots 256

Parameterized circuits (structure/parameter split — the compile cache is
structural, so rebinding angles never re-runs ILP/DP/XLA):
  PYTHONPATH=src python -m repro.launch.simulate --circuit isingparam --n 12 \
      --L 10 --R 2 --engine --bind J=0.35 --bind h=0.8 --check
  PYTHONPATH=src python -m repro.launch.simulate --circuit su2param --n 10 \
      --L 10 --sweep points.json --check
(points.json: a JSON list of {name: value} objects, or {"name": [v0, v1, ...]}
columns of equal length.)
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np
from jax.sharding import AxisType

from ..compile_cache import enable_compile_cache
from ..core.generators import FAMILIES, PARAM_FAMILIES
from ..core.partition import partition
from ..sim.statevector import fidelity, simulate


def _pjit_mesh(R: int, G: int):
    """Build the (pod, data, model) mesh when enough devices exist."""
    if R + G > 0 and len(jax.devices()) >= (1 << (R + G)):
        rd = 1 << (R // 2)
        rm = 1 << (R - R // 2)
        return jax.make_mesh((1 << G, rd, rm), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
    return None


def _print_degraded(ex):
    """Say so whenever the engine is not the one that was asked for."""
    prov = getattr(ex, "provenance", None) or {}
    if prov.get("degraded"):
        print(f"DEGRADED: asked for {prov.get('requested_backend')}"
              f"{'+pallas' if prov.get('requested_use_pallas') else ''}, "
              f"running {prov.get('backend')}"
              f"{'+pallas' if prov.get('use_pallas') else ''}")
        for fb in prov.get("fallbacks", []):
            print(f"  fallback {fb['from']} -> {fb['to']}: {fb['error']}")


def _print_storage_summary(ex):
    snap = (getattr(ex, "provenance", None) or {}).get("storage")
    if snap:
        print(f"storage: {snap['spilled_shards']}/{snap['n_shards']} shards "
              f"at rest on disk after run; {snap['spills']} spills, "
              f"{snap['spill_loads']} reloads; error bound "
              f"{snap['relative_error_bound']:.3e} "
              f"(tol {snap['error_tolerance']})")


def _parse_bind(specs):
    out = {}
    for spec in specs:
        if "=" not in spec:
            raise SystemExit(f"--bind expects name=value, got {spec!r}")
        name, _, val = spec.partition("=")
        out[name.strip()] = float(val)
    return out


def _load_sweep(path):
    """JSON sweep file -> list of {name: value} points."""
    with open(path) as f:
        d = json.load(f)
    if isinstance(d, dict) and "points" in d:
        d = d["points"]
    if isinstance(d, list):
        return [dict(p) for p in d]
    # columns form: {name: [v0, v1, ...]}
    lengths = {len(v) for v in d.values()}
    if len(lengths) != 1:
        raise SystemExit("--sweep columns must have equal length")
    P = lengths.pop()
    return [{k: float(v[p]) for k, v in d.items()} for p in range(P)]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--circuit", default="qft",
                    choices=sorted(FAMILIES) + sorted(PARAM_FAMILIES))
    ap.add_argument("--n", type=int, default=16)
    ap.add_argument("--L", type=int, default=0, help="local qubits (0: n-R-G)")
    ap.add_argument("--R", type=int, default=0)
    ap.add_argument("--G", type=int, default=0)
    ap.add_argument("--executor", default="pjit",
                    choices=["pjit", "shardmap", "offload", "dense", "pergate"])
    ap.add_argument("--staging", default="ilp", choices=["ilp", "greedy"])
    ap.add_argument("--kernelizer", default="dp", choices=["dp", "ordered", "greedy"])
    ap.add_argument("--pallas", action="store_true")
    ap.add_argument("--opt", dest="opt", action="store_true",
                    help="run the pre-staging circuit optimizer "
                         "(repro.core.optimize: cancel/merge/drop/reorder) "
                         "before planning; --check verifies against the "
                         "UN-optimized dense reference")
    ap.add_argument("--no-opt", dest="opt", action="store_false",
                    help="disable the pre-staging optimizer (default)")
    ap.set_defaults(opt=False)
    ap.add_argument("--autotune", action="store_true",
                    help="A/B-replay candidate plans first and serve the "
                         "fastest (implies --engine; winner is cached)")
    ap.add_argument("--engine", action="store_true",
                    help="route through the unified ExecutionEngine + compile "
                         "cache (repro.sim.engine.engine_for)")
    ap.add_argument("--batch", type=int, default=1,
                    help="run a batch of B basis initial states through the "
                         "engine's fused batch path (implies --engine)")
    ap.add_argument("--check", action="store_true", help="fidelity vs dense ref")
    ap.add_argument("--shots", type=int, default=0, help="sample N bitstrings")
    ap.add_argument("--seed", type=int, default=0, help="sampling PRNG seed")
    ap.add_argument("--marginal", action="append", default=[],
                    help="comma-separated qubit subset (repeatable)")
    ap.add_argument("--observable", action="append", default=[],
                    help='Pauli sum, e.g. "Z0 Z1 + 0.5*X2" (repeatable)')
    ap.add_argument("--storage", default=None, metavar="SPEC",
                    help="tiered at-rest shard store for --executor offload "
                         "(implies --engine): 'exact'|'bf16'|'int8' with "
                         "optional ':dram_kib=N', ':dir=PATH', ':tol=X' — "
                         "e.g. 'int8:dram_kib=4096'. Shards past the DRAM "
                         "budget spill to disk; see README 'Scaling past "
                         "DRAM'")
    ap.add_argument("--dram-budget-mb", type=float, default=None,
                    help="at-rest DRAM budget in MiB for --storage "
                         "(overrides any dram_kib in the spec)")
    ap.add_argument("--spill-dir", default=None,
                    help="directory for spilled shard files (default: the "
                         "system temp dir)")
    ap.add_argument("--storage-tol", type=float, default=None,
                    help="max accumulated quantization error bound before "
                         "the run is rejected (default 0.05)")
    ap.add_argument("--bind", action="append", default=[], metavar="NAME=VAL",
                    help="bind one circuit parameter (repeatable); required "
                         "for parameterized families unless --sweep is given")
    ap.add_argument("--sweep", default=None, metavar="FILE.json",
                    help="run a parameter sweep: every point reuses ONE "
                         "structural compile (implies --engine)")
    ap.add_argument("--vqe", default=None, metavar="OBSERVABLE",
                    help='minimize <H> over the circuit\'s free parameters '
                         'with Adam over adjoint-mode value_and_grad, e.g. '
                         '--vqe "Z0 Z1 + Z1 Z2 + 0.5*X0" (implies --engine)')
    ap.add_argument("--vqe-steps", type=int, default=30)
    ap.add_argument("--vqe-lr", type=float, default=0.1)
    ap.add_argument("--vqe-seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    n = args.n
    L = args.L or (n - args.R - args.G)
    circ = (FAMILIES.get(args.circuit) or PARAM_FAMILIES[args.circuit])(n)
    print(f"{args.circuit}(n={n}): {circ.n_gates} gates; L/R/G = {L}/{args.R}/{args.G}"
          + (f"; {len(circ.param_names)} free params" if not circ.is_bound else ""))

    measuring = bool(args.shots or args.marginal or args.observable)
    marginals = [tuple(int(q) for q in spec.split(",")) for spec in args.marginal]
    binds = _parse_bind(args.bind)
    storage = None
    if args.storage is not None:
        from ..sim.shard_store import StorageConfig

        if args.executor != "offload":
            ap.error("--storage requires --executor offload")
        storage = StorageConfig.parse(args.storage)
        if storage is not None:
            over = {}
            if args.dram_budget_mb is not None:
                over["dram_bytes"] = int(args.dram_budget_mb * (1 << 20))
            if args.spill_dir is not None:
                over["spill_dir"] = args.spill_dir
            if args.storage_tol is not None:
                over["error_tolerance"] = args.storage_tol
            if over:
                storage = storage.with_overrides(**over)
    use_engine = (args.engine or args.autotune or args.batch > 1
                  or args.executor == "dense" or storage is not None
                  or args.sweep is not None or args.vqe is not None)
    if use_engine and args.executor == "pergate":
        ap.error("--engine/--batch/--sweep do not support the pergate baseline")
    if not use_engine and (binds or not circ.is_bound):
        # legacy executor path: bind eagerly (the engine path binds lazily so
        # the structural compile cache stays parameter-blind)
        circ = circ.bind(binds)
        binds = {}
    # --check always cross-checks against the circuit as the user wrote it,
    # never the optimizer's rewrite of it
    ref_circ = circ

    if use_engine:
        from ..sim.engine import DEFAULT_CACHE, engine_for

        backend_kw = {"mesh": _pjit_mesh(args.R, args.G)} \
            if args.executor == "pjit" else {}
        t0 = time.time()
        if args.autotune:
            from ..core.autotune import autotune_engine

            res = autotune_engine(
                circ, L, args.R, args.G, backend=args.executor,
                use_pallas=args.pallas, backend_kw=backend_kw)
            print(f"autotune: chose '{res.chosen}' "
                  f"({res.speedup_vs_default:.2f}x vs default, "
                  f"{len(res.replay_us)} candidates, "
                  f"{res.tune_time_s:.1f}s"
                  f"{', cached' if res.cached else ''})")
        ex = engine_for(
            circ, L, args.R, args.G, backend=args.executor,
            use_pallas=args.pallas, staging_method=args.staging,
            kernelize_method=args.kernelizer, optimize=args.opt,
            backend_kw=backend_kw, storage=storage,
        )
        plan = ex.plan
        _print_degraded(ex)
        st_cfg = getattr(ex.backend, "storage", None)
        if st_cfg is not None:
            budget = ("unbounded" if st_cfg.dram_bytes is None
                      else f"{st_cfg.dram_bytes / (1 << 20):.1f} MiB")
            print(f"storage: at-rest {st_cfg.at_rest_dtype}, DRAM budget "
                  f"{budget}, tol {st_cfg.error_tolerance}")
        print(f"engine[{ex.backend.name}] ready in {time.time() - t0:.2f}s; "
              f"cache: {len(DEFAULT_CACHE)} entries, {DEFAULT_CACHE.hits} hits"
              f"/{DEFAULT_CACHE.misses} misses")
        opt_prov = getattr(ex, "provenance", {}).get("optimize")
        if opt_prov:
            print(f"optimizer: {opt_prov['gates_before']} -> "
                  f"{opt_prov['gates_after']} gates "
                  f"(-{opt_prov['gates_removed']}; "
                  f"passes: {opt_prov['pass_counts']})")
        if binds:
            t0 = time.time()
            ex.bind(binds)
            print(f"bound {len(binds)} params in {time.time() - t0:.3f}s "
                  "(tensor swap: no ILP/DP/XLA)")
        elif not circ.is_bound and args.sweep is None and args.vqe is None:
            ap.error(f"circuit has free parameters {circ.param_names}; "
                     "pass --bind NAME=VAL, --sweep FILE.json or --vqe OBS")
    else:
        if args.opt:
            from ..core.optimize import optimize_circuit

            ores = optimize_circuit(circ)
            print(f"optimizer: {ores.source.n_gates} -> "
                  f"{ores.circuit.n_gates} gates (-{ores.gates_removed}; "
                  f"passes: {ores.pass_counts()})")
            circ = ores.circuit
        t0 = time.time()
        plan = partition(circ, L, args.R, args.G,
                         staging_method=args.staging,
                         kernelize_method=args.kernelizer)
    print(f"partition: {plan.n_stages} stages, kernel cost {plan.total_kernel_cost:,.0f} us"
          f" (preprocess {plan.preprocess_time_s:.2f}s)")

    # ------------------------------------------------------------ VQE loop
    if args.vqe is not None:
        import jax.numpy as jnp

        from ..core import kernelization, staging
        from ..optim.adamw import AdamWConfig, init as adam_init, \
            update as adam_update

        names = circ.param_names
        if not names:
            ap.error("--vqe needs a parameterized circuit "
                     "(su2param/isingparam or symbolic JSON)")
        rng = np.random.default_rng(args.vqe_seed)
        theta = jnp.asarray(rng.uniform(0.0, 2 * np.pi, len(names)),
                            dtype=jnp.float32)
        cfg = AdamWConfig(lr=args.vqe_lr, weight_decay=0.0, warmup_steps=0,
                          total_steps=max(args.vqe_steps, 1), min_lr_frac=1.0,
                          moment_dtype="float32", clip_norm=10.0)
        opt = adam_init(cfg, theta)
        t0 = time.time()
        value, grads = ex.value_and_grad(args.vqe, params=np.asarray(theta))
        print(f"VQE over {len(names)} params, H = {args.vqe}; first "
              f"value+grad (incl. adjoint trace) in {time.time() - t0:.2f}s")
        solves0 = (staging.SOLVER_CALLS["ilp"], kernelization.SOLVER_CALLS["dp"])
        xla0 = ex.xla_compiles
        t0 = time.time()
        for step in range(args.vqe_steps):
            theta, opt, metrics = adam_update(
                cfg, jnp.asarray(grads, jnp.float32), opt, theta)
            value, grads = ex.value_and_grad(args.vqe, params=np.asarray(theta))
            if step % max(args.vqe_steps // 10, 1) == 0 or step == args.vqe_steps - 1:
                print(f"  step {step:4d}: <H> = {value:+.6f}  "
                      f"|grad| = {float(np.linalg.norm(grads)):.4f}")
        dt = time.time() - t0
        assert (staging.SOLVER_CALLS["ilp"],
                kernelization.SOLVER_CALLS["dp"]) == solves0, \
            "VQE iterations must not re-run ILP/DP"
        assert ex.xla_compiles == xla0, "VQE iterations must not retrace XLA"
        print(f"VQE done: <H> = {value:+.6f} after {args.vqe_steps} steps in "
              f"{dt:.2f}s ({dt / max(args.vqe_steps, 1):.3f}s/step; zero "
              "solver calls, zero retraces)")
        return {"energy": value, "theta": np.asarray(theta),
                "param_names": names}

    # ----------------------------------------------------- parameter sweep
    if args.sweep is not None:
        points = _load_sweep(args.sweep)
        P = len(points)
        t0 = time.time()
        if measuring:
            from ..sim.measure import measure_sweep

            results = measure_sweep(ex, points, shots=args.shots,
                                    seed=args.seed, marginals=marginals,
                                    observables=args.observable)
            dt = time.time() - t0
            print(f"sweep of {P} bindings simulated+measured in {dt:.3f}s "
                  f"({dt / P:.3f}s/point)")
            for p, res in enumerate(results):
                bits = []
                if args.shots:
                    bits.append("top " + ", ".join(
                        f"{s}:{c_}" for s, c_ in res.top(3)))
                bits += [f"<{k}>={v:+.4f}" for k, v in res.expectations.items()]
                print(f"  [{p}] " + "; ".join(bits))
            return results
        out = ex.run_sweep(None, points)
        out = jax.block_until_ready(out) if not isinstance(out, np.ndarray) else out
        dt = time.time() - t0
        print(f"sweep of {P} bindings in {dt:.3f}s ({dt / P:.3f}s/point, "
              f"one structural compile)")
        if args.check and n <= 24:
            for p, pt in enumerate(points):
                ref = simulate(circ.bind(pt))
                print(f"  fidelity[{p}] vs dense reference: "
                      f"{fidelity(np.asarray(out[p]), ref):.6f}")
        return out

    # --------------------------------------------------- batched serving path
    if args.batch > 1:
        B = args.batch
        psi0s = np.zeros((B, 2**n), dtype=np.complex64)
        psi0s[np.arange(B), np.arange(B) % (2**n)] = 1.0
        t0 = time.time()
        if measuring:
            from ..sim.measure import measure_batch

            results = measure_batch(ex, psi0s, shots=args.shots, seed=args.seed,
                                    marginals=marginals,
                                    observables=args.observable)
            dt = time.time() - t0
            print(f"batch of {B} simulated+measured in {dt:.3f}s "
                  f"({dt / B:.3f}s/state)")
            for b, res in enumerate(results):
                bits = []
                if args.shots:
                    bits.append("top " + ", ".join(
                        f"{s}:{c_}" for s, c_ in res.top(3)))
                bits += [f"<{k}>={v:+.4f}" for k, v in res.expectations.items()]
                print(f"  [{b}] " + "; ".join(bits))
            return results
        out = ex.run_batch(psi0s)
        out = jax.block_until_ready(out) if not isinstance(out, np.ndarray) else out
        dt = time.time() - t0
        print(f"batch of {B} simulated in {dt:.3f}s ({dt / B:.3f}s/state, "
              f"{B * circ.n_gates / dt:,.0f} gates/s)")
        _print_storage_summary(ex)
        if args.check and n <= 24:
            for b in range(B):
                f = fidelity(np.asarray(out[b]), simulate(circ, psi0=psi0s[b]))
                print(f"  fidelity[{b}] vs dense reference: {f:.6f}")
        return out

    # ------------------------------------------------------ single-state path
    t0 = time.time()
    measurer = None
    if not use_engine:
        if args.executor == "pjit":
            from ..sim.executor import StagedExecutor

            ex = StagedExecutor(circ, plan, mesh=_pjit_mesh(args.R, args.G))
        elif args.executor == "shardmap":
            from ..sim.shardmap_executor import ShardMapExecutor

            ex = ShardMapExecutor(circ, plan, use_pallas=args.pallas)
        elif args.executor == "offload":
            from ..sim.offload import OffloadedExecutor

            ex = OffloadedExecutor(circ, plan)
        else:
            from ..sim.offload import PerGateOffloadExecutor

            ex = PerGateOffloadExecutor(circ, L)
        _print_degraded(ex)
    if args.executor == "pergate":
        out = ex.run()
    else:
        out = ex.run_packed() if measuring else ex.run()
    if measuring:
        from ..sim.measure import Frame, measurer_for

        # measured runs stay distributed/packed: never gather 2^n amplitudes
        out = jax.block_until_ready(out) if not isinstance(out, np.ndarray) else out
        frame = (ex.measurement_frame if args.executor != "pergate"
                 else Frame.identity(n))
        measurer = measurer_for(out, frame)
    else:
        out = np.asarray(jax.block_until_ready(out)) if not isinstance(out, np.ndarray) else out
    dt = time.time() - t0
    print(f"simulated in {dt:.3f}s ({circ.n_gates / dt:,.0f} gates/s, "
          f"{2**n / dt / 1e6:,.1f} Mamps/s)")
    if use_engine:
        _print_storage_summary(ex)

    if measurer is not None:
        from ..sim.measure import measure_to_result

        t0 = time.time()
        res = measure_to_result(
            measurer, backend=args.executor, shots=args.shots, seed=args.seed,
            marginals=marginals,
            observables=args.observable,
        )
        print(f"measured in {time.time() - t0:.3f}s")
        if args.shots:
            top = ", ".join(f"{b}:{c}" for b, c in res.top(8))
            print(f"  top counts ({args.shots} shots): {top}")
        for qs, m in res.marginals.items():
            head = np.array2string(m[:8], precision=4, suppress_small=True)
            print(f"  marginal{qs}: {head}{' ...' if m.size > 8 else ''}")
        for name, val in res.expectations.items():
            print(f"  <{name}> = {val:+.6f}")
        if not (args.check and n <= 24):
            return res

    if args.check and n <= 24:
        if measurer is not None:
            # measured runs keep the final-stage layout; re-run with the
            # final remap applied for the logical-order fidelity check
            out = ex.run() if args.executor != "pergate" else out
            out = np.asarray(jax.block_until_ready(out)) if not isinstance(out, np.ndarray) else out
        ref = simulate(ref_circ if ref_circ.is_bound else ref_circ.bind(binds))
        print(f"fidelity vs dense reference: {fidelity(out, ref):.6f}")
    return out


if __name__ == "__main__":
    main()
