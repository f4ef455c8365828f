#!/usr/bin/env python3
"""Bring-up check of the staged simulator on the chip.

    python chip_smoke.py            # one TPU v5e: phases (a)-(d)
    python chip_smoke.py --chips 4  # four chips: the distributed phase only

One chip, at the paper's single-chip size (n = 28, a 2 GiB complex64 state):

(a) ``qft(28)`` through ``engine_for(..., backend="pjit")`` on a seeded basis
    state |x>, with and without the Pallas kernels, checked on the device
    against the closed form exp(2 pi i x rev(y) / 2^n) / 2^(n/2) (``rev`` is
    the n-bit reversal: ``qft`` has no final swaps);
(b) ``su2random(28)`` with the kernels: ||psi|| within 1e-4 of 1; then
    ``su2random(22)`` on the same path against ``simulate_np``;
(c) ``HostOffloadBackend`` at n = 28, L = 24 on ``qft`` against the same
    closed form;
(d) ``SimulationService`` in process answering 8 ``su2param(20)`` requests
    with distinct seeded bindings, each against ``simulate_np``.

Four chips (``--chips 4``): ``ShardMapBackend`` with the Pallas kernels
and ``PjitBackend`` at n = 30, R = 2, L = 28 (2 GiB per chip) on the ``qft``
closed form, computed where each shard lives, and ``su2random(24)`` with
R = 2 against ``simulate_np``.

Every engine is built with ``degrade=False`` and run with ``verify=False``:
a build failure is a failure, never a silent fallback. Every phase prints
one line (wall and compile seconds, ``memory_analysis`` of its program);
the last line is the JSON verdict. Without a TPU, or without the repository
next to it, the script exits non-zero and prints no verdict.
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import json
import multiprocessing as mp
import os
import sys
import time
import traceback

SEED = 20240817
T0 = time.perf_counter()
# qubit counts of each phase (the paper's single-chip size, and n = 30 over
# four chips with 2 GiB per chip)
SIZES = {"qft": 28, "su2random": 28, "su2random_ref": 22,
         "offload": (28, 24), "service": 20, "four": 30, "four_ref": 24}


def progress(msg: str) -> None:
    """One stderr line per step (plan, build, compile, run, check), with the
    seconds since start: a run cut at its time limit shows where it was."""
    sys.stderr.write(f"[{time.perf_counter() - T0:8.1f} s] {msg}\n")
    sys.stderr.flush()


def _fail(msg: str, code: int = 1) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    return code


# ------------------------------------------------------------ oracles


def bit_reverse(y, n: int):
    """n-bit reversal of uint32 ``y`` (traceable)."""
    import jax.numpy as jnp

    y = y.astype(jnp.uint32)
    for s, m in ((1, 0x55555555), (2, 0x33333333), (4, 0x0F0F0F0F),
                 (8, 0x00FF00FF)):
        y = ((y >> s) & m) | ((y & m) << s)
    y = (y >> 16) | (y << 16)
    return y >> (32 - n)


def qft_error(psi, x: int, n: int):
    """max |psi[y] - exp(2 pi i x rev(y) / 2^n) / 2^(n/2)| over y, computed
    where ``psi`` lives (jitted; a sharded state reduces shard by shard)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def err(psi):
        y = jax.lax.broadcasted_iota(jnp.uint32, psi.shape, psi.ndim - 1)
        if psi.ndim == 2:
            y = y + (jax.lax.broadcasted_iota(jnp.uint32, psi.shape, 0)
                     << (psi.shape[1].bit_length() - 1))
        ph = (jnp.uint32(x) * bit_reverse(y, n)) & jnp.uint32((1 << n) - 1)
        ang = ph.astype(jnp.float32) * jnp.float32(2 * 3.141592653589793 / (1 << n))
        ref = jax.lax.complex(jnp.cos(ang), jnp.sin(ang)) * jnp.float32(2.0 ** (-n / 2))
        return jnp.max(jnp.abs(psi - ref))

    return float(err(psi))


def basis_state(n: int, x: int, shape, sharding=None):
    """|x> made on the device(s) at ``shape`` (never whole on one chip)."""
    import jax
    import jax.numpy as jnp

    def make():
        return jnp.zeros((1 << n,), jnp.complex64).at[x].set(1).reshape(shape)

    if sharding is None:
        return jax.jit(make)()
    return jax.jit(make, out_shardings=sharding)()


def fidelity(a, b) -> float:
    import numpy as np

    a = np.asarray(a, dtype=np.complex128).reshape(-1)
    b = np.asarray(b, dtype=np.complex128).reshape(-1)
    return float(abs(np.vdot(a, b)))


# ------------------------------------------------------------- engines


_PLANS = {}


def build(circ, L, R=0, **kw):
    """engine_for with no fallback; refuses a degraded engine. A structure
    planned once (ILP staging + DP kernelization) is not planned again."""
    from repro.core.partition import partition
    from repro.sim.engine import engine_for

    what = (f"{len(circ.gates)} gates n={circ.n_qubits} L={L} R={R} "
            f"{kw.get('backend')}")
    key = (circ.structure_fingerprint(), L, R)
    if key not in _PLANS:
        progress(f"plan {what}")
        _PLANS[key] = partition(circ, L, R, 0)
    progress(f"build {what}")
    eng = engine_for(circ, L, R, 0, degrade=False, plan=_PLANS[key], **kw)
    if eng.provenance.get("degraded"):
        raise RuntimeError(f"degraded engine: {eng.provenance}")
    return eng


def compile_info(eng) -> dict:
    """Compile the engine's stage program once (AOT) for its seconds and
    memory analysis; the run that follows finds it in the compile cache."""
    t = time.perf_counter()
    what = f"{eng.backend.name} n={eng.n}"
    progress(f"lower {what}")
    lowered = eng.backend.lower()
    progress(f"compile {what}")
    compiled = lowered.compile()
    progress(f"compiled {what} in {time.perf_counter() - t:.1f} s")
    m = compiled.memory_analysis()
    gib = 1 << 30
    return {
        "compile_s": round(time.perf_counter() - t, 3),
        "mem_GiB": {"arg": round(m.argument_size_in_bytes / gib, 3),
                    "out": round(m.output_size_in_bytes / gib, 3),
                    "temp": round(m.temp_size_in_bytes / gib, 3),
                    "alias": round(m.alias_size_in_bytes / gib, 3)},
        "tpu_custom_call": "tpu_custom_call" in lowered.as_text(),
    }


def compile_all(engines) -> list:
    """:func:`compile_info` of several engines at once: XLA compiles on
    host threads, so independent programs overlap."""
    with cf.ThreadPoolExecutor(max_workers=len(engines)) as pool:
        return list(pool.map(compile_info, engines))


def _blocked(out):
    import jax

    return jax.block_until_ready(out)


# -------------------------------------------------------------- phases


def phase_qft(use_pallas: bool) -> dict:
    import numpy as np
    from repro.core import generators as gen

    n = SIZES["qft"]
    x = int(np.random.default_rng(SEED).integers(1 << n))
    eng = build(gen.qft(n), n, backend="pjit", use_pallas=use_pallas)
    info = compile_info(eng)
    if use_pallas and not info["tpu_custom_call"]:
        raise RuntimeError("no tpu_custom_call in the Pallas program")
    t = time.perf_counter()
    progress("run")
    out = _blocked(eng.run(basis_state(n, x, eng.backend.shape)))
    info["run_s"] = round(time.perf_counter() - t, 3)
    progress("check")
    err = qft_error(out, x, n)
    if not err <= 1e-5:
        raise AssertionError(f"qft({n}) max abs error {err} > 1e-5")
    info.update(n=n, x=x, max_abs_err=err)
    return info


def phase_su2random(ref22) -> dict:
    import jax.numpy as jnp
    import numpy as np
    from repro.core import generators as gen

    n, m = SIZES["su2random"], SIZES["su2random_ref"]
    eng = build(gen.su2random(n), n, backend="pjit", use_pallas=True)
    eng_ref = build(gen.su2random(m), m, backend="pjit", use_pallas=True)
    info, info_ref = compile_all([eng, eng_ref])
    t = time.perf_counter()
    progress("run")
    out = _blocked(eng.run())
    info["run_s"] = round(time.perf_counter() - t, 3)
    norm = float(jnp.sqrt(jnp.sum(jnp.abs(out) ** 2)))
    if not abs(norm - 1) <= 1e-4:
        raise AssertionError(f"su2random({n}) norm {norm}")
    del out, eng
    progress("run reference size")
    psi = np.asarray(eng_ref.run())
    progress("wait for the oracle")
    f = fidelity(psi, ref22.get())
    if not f >= 1 - 1e-4:
        raise AssertionError(f"su2random({m}) fidelity {f}")
    info.update(n=n, norm=norm, n_ref=m, fidelity_ref=f, ref=info_ref)
    return info


def phase_offload() -> dict:
    import jax
    import numpy as np
    from repro.core import generators as gen

    n, L = SIZES["offload"]
    x = int(np.random.default_rng(SEED + 1).integers(1 << n))
    t = time.perf_counter()
    eng = build(gen.qft(n), L, n - L, backend="offload")
    info = {"plan_s": round(time.perf_counter() - t, 3)}
    psi0 = np.zeros(1 << n, np.complex64)
    psi0[x] = 1
    t = time.perf_counter()
    progress("run")
    out = eng.run(psi0)
    info["run_s"] = round(time.perf_counter() - t, 3)
    progress("check")
    err = qft_error(jax.device_put(out.reshape(-1, 128)), x, n)
    if not err <= 1e-5:
        raise AssertionError(f"offload qft({n}) max abs error {err}")
    info.update(n=n, L=L, shards=1 << (n - L), x=x, max_abs_err=err,
                overlap_ratio=round(eng.backend.overlap_ratio, 3))
    return info


def phase_service(refs) -> dict:
    import asyncio

    import numpy as np
    from repro.core.generators import PARAM_FAMILIES
    from repro.serve.batcher import SimRequest
    from repro.serve.service import ServeConfig, SimulationService

    sym = PARAM_FAMILIES["su2param"](SIZES["service"])
    binds = refs["binds"]

    async def go():
        cfg = ServeConfig(backend="pjit", use_pallas=True, verify_norm=False,
                          degrade=False, max_batch_size=8, max_wait_ms=50.0)
        async with SimulationService(cfg) as svc:
            return await asyncio.gather(*[
                svc.submit(SimRequest(circuit=sym, params=b, return_state=True,
                                      tenant=f"t{i % 3}"))
                for i, b in enumerate(binds)])

    t = time.perf_counter()
    progress("serve")
    resps = asyncio.run(go())
    wall = time.perf_counter() - t
    progress("wait for the oracles")
    fids = [fidelity(r.state, ref.get()) for r, ref in zip(resps, refs["states"])]
    if not min(fids) >= 1 - 1e-4:
        raise AssertionError(f"service fidelities {fids}")
    if any(r.provenance for r in resps):
        raise RuntimeError(f"degraded service engine: {resps[0].provenance}")
    return {"answer_s": round(wall, 3), "requests": len(resps),
            "batch_sizes": sorted({r.batch_size for r in resps}),
            "min_fidelity": min(fids)}


def _four_chip_backends() -> dict:
    """engine_for keywords of the two distributed backends (R = 2)."""
    import jax
    from jax.sharding import AxisType

    mesh = jax.make_mesh((1, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    return {"shardmap": {}, "pjit": {"backend_kw": {"mesh": mesh}}}


def phase_four_qft() -> dict:
    """qft(30), R = 2, on both backends, shard_map with the Pallas kernels:
    the state stays spread over the four chips and the closed form is
    checked where each shard lives."""
    import numpy as np
    from repro.core import generators as gen

    n, R = SIZES["four"], 2
    x = int(np.random.default_rng(SEED + 2).integers(1 << n))
    kws = _four_chip_backends()
    kws["shardmap"] = dict(kws["shardmap"], use_pallas=True)
    engs = {b: build(gen.qft(n), n - R, R, backend=b, **kw)
            for b, kw in kws.items()}
    info = dict(zip(engs, compile_all(list(engs.values()))))
    if not info["shardmap"]["tpu_custom_call"]:
        raise RuntimeError("no tpu_custom_call in the shard_map program")
    for backend, eng in engs.items():
        shape = eng.backend.shape if backend == "pjit" else (1 << n,)
        psi0 = basis_state(n, x, shape, eng.backend.sharding)
        devs = {d.id for d in psi0.sharding.device_set}
        progress(f"run {backend} on devices {sorted(devs)}")
        t = time.perf_counter()
        out = _blocked(eng.run(psi0))
        run_s = time.perf_counter() - t
        out_devs = {d.id for d in out.sharding.device_set}
        progress(f"check {backend}")
        if len(devs) != 4 or len(out_devs) != 4:
            raise AssertionError(f"{backend}: state on devices {devs} -> {out_devs}")
        err = qft_error(out.reshape(-1, 128), x, n)
        if not err <= 1e-5:
            raise AssertionError(f"{backend} qft({n}) max abs error {err}")
        del out, psi0
        info[backend].update(run_s=round(run_s, 3), max_abs_err=err,
                             devices=sorted(out_devs))
    info.update(n=n, R=R, L=n - R, x=x)
    return info


def prepare_four_su2random():
    """Engines of su2random(24), R = 2, on both backends, compiled."""
    from repro.core import generators as gen

    m, R = SIZES["four_ref"], 2
    engs = {b: build(gen.su2random(m), m - R, R, backend=b, **kw)
            for b, kw in _four_chip_backends().items()}
    return engs, dict(zip(engs, compile_all(list(engs.values()))))


def phase_four_su2random(prepared, ref24) -> dict:
    """su2random(24), R = 2, on both backends against simulate_np.
    ``prepared``: the future of :func:`prepare_four_su2random`."""
    import numpy as np

    m, R = SIZES["four_ref"], 2
    engs, info = prepared.result()
    for backend, eng in engs.items():
        progress(f"run {backend}")
        t = time.perf_counter()
        out = _blocked(eng.run())
        info[backend]["run_s"] = round(time.perf_counter() - t, 3)
        psi = np.asarray(out)
        progress("wait for the oracle")
        f = fidelity(psi, ref24.get())
        if not f >= 1 - 1e-4:
            raise AssertionError(f"{backend} su2random({m}) fidelity {f}")
        info[backend]["fidelity"] = f
    info.update(n=m, R=R, L=m - R)
    return info


# ---------------------------------------------------------------- main


def run_phases(phases) -> bool:
    """Run each phase, printing one line for it; False if any failed."""
    ok = True
    for name, fn in phases:
        progress(f"phase {name}")
        t = time.perf_counter()
        try:
            info = fn()
            rec = {"phase": name, "ok": True}
        except Exception as e:  # a failed phase fails the run
            traceback.print_exc()
            info = {"error": f"{type(e).__name__}: {e}"[:2000]}
            rec = {"phase": name, "ok": False}
            ok = False
        rec["wall_s"] = round(time.perf_counter() - t, 3)
        rec.update((k, v) for k, v in info.items() if k not in rec)
        print(json.dumps(rec), flush=True)
    return ok


def _oracle_init(src: str) -> None:
    """Oracle worker set-up: numpy only, on the host CPU, never the chip."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["REPRO_CALIBRATION"] = "off"
    sys.path.insert(0, src)


def _oracle(name: str, n: int, params=None):
    from repro.core.generators import FAMILIES, PARAM_FAMILIES
    from repro.sim.statevector import simulate_np

    if params is None:
        return simulate_np(FAMILIES[name](n))
    return simulate_np(PARAM_FAMILIES[name](n).bind(params))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        return _fail("the repro package is not next to this script", 2)
    sys.path.insert(0, src)
    # plans come from committed code only, never a calibration file
    os.environ["REPRO_CALIBRATION"] = "off"

    import jax
    import numpy as np

    from repro.compile_cache import enable_compile_cache
    from repro.kernels import ops as kops

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return _fail(f"no TPU: JAX sees {devs[0].platform}")
    if len(devs) < args.chips:
        return _fail(f"{args.chips} chips asked for, {len(devs)} present")
    cache = enable_compile_cache()
    print(json.dumps({"phase": "setup", "devices": len(devs),
                      "kind": devs[0].device_kind, "compile_cache": cache}),
          flush=True)

    # the numpy oracles run in worker processes while the chip works, so
    # their gate loops do not hold this process's interpreter lock during
    # planning; the workers never touch the chip (JAX_PLATFORMS=cpu)
    pool = mp.get_context("spawn").Pool(3, _oracle_init, (src,))
    if args.chips == 1:
        rng = np.random.default_rng(SEED)
        from repro.core.generators import PARAM_FAMILIES

        ns = SIZES["service"]
        names = PARAM_FAMILIES["su2param"](ns).param_names
        binds = [dict(zip(names, rng.uniform(0, 2 * np.pi, len(names))))
                 for _ in range(8)]
        ref22 = pool.apply_async(_oracle, ("su2random", SIZES["su2random_ref"]))
        refs = {"binds": binds,
                "states": [pool.apply_async(_oracle, ("su2param", ns, b))
                           for b in binds]}
        phases = [
            ("a_qft28_xla", lambda: phase_qft(False)),
            ("a_qft28_pallas", lambda: phase_qft(True)),
            ("b_su2random", lambda: phase_su2random(ref22)),
            ("c_offload_qft28", phase_offload),
            ("d_service_su2param20", lambda: phase_service(refs)),
        ]
    else:
        ref24 = pool.apply_async(_oracle, ("su2random", SIZES["four_ref"]))
        # su2random(24) plans and compiles on a host thread while the
        # qft(30) phase runs: its compile is the longest step of the
        # four-chip run, and XLA compiles outside the interpreter lock
        prepared = cf.ThreadPoolExecutor(1).submit(prepare_four_su2random)
        phases = [("four_qft30", phase_four_qft),
                  ("four_su2random24",
                   lambda: phase_four_su2random(prepared, ref24))]

    kops.reset_kernel_counters()
    try:
        ok = run_phases(phases)
    finally:
        pool.terminate()
        pool.join()
    interpreted = kops.kernel_call_counts()["interpreted"]
    if interpreted:
        ok = False
        print(json.dumps({"phase": "kernels", "ok": False,
                          "interpreted_pallas_calls": interpreted}), flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
