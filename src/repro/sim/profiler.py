"""Microbenchmark profiler (paper §VII-A): measure the real kernel primitives
on the *current* device and turn them into a :class:`~repro.core.cost_model.
CostModel` calibration.

The analytic constants in :mod:`repro.core.cost_model` are hand-derived for a
TPU v5e this environment may not have. This module times the same primitives
the engine backends actually execute — the Pallas fusion matmul per k, the
shm group kernel vs member count and diagonal fraction, a raw HBM streaming
pass, the host<->device offload link, and bare dispatch overhead — and
reduces them to the cost model's 2^28-amplitude-shard reference scale so
:meth:`CostModel.from_calibration` can rebuild the model from measurement.

Calibrations persist as JSON keyed by a **device fingerprint** (platform,
device kind/count, dtype, jax version). :func:`resolve_cost_model` is the
auto-load hook used by ``repro.sim.engine.engine_for``: it returns the
calibrated model when a file with a matching fingerprint exists and the
analytic defaults otherwise, memoized per-process so every caller (the serve
warm pool, the batcher's group keys, ``engine_for``) sees one consistent
model and therefore one consistent :class:`CircuitKey`.

Environment knobs:

* ``REPRO_CALIBRATION`` — ``off``/``0``/``analytic`` forces the analytic
  defaults; any other non-empty value is an explicit calibration file path.
* ``REPRO_CALIBRATION_DIR`` — directory searched for ``calibration.json``
  (default ``~/.cache/repro-atlas``).

CLI::

    python -m repro.sim.profiler --fast --out calibration.json --verify
"""

from __future__ import annotations

import argparse
import hashlib
import json
import math
import os
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.cost_model import CostModel, DEFAULT_COST_MODEL
from .lanes import apply_shm_group, apply_unitary, lane_bits

# v2: adds the "disk" section (disk_gbps for the shard_store spill tier).
# Files written by older versions miss fields the cost model now prices, so
# resolve_calibration treats a version mismatch like a fingerprint mismatch.
CALIBRATION_VERSION = 2
CALIBRATION_FILENAME = "calibration.json"
REFERENCE_L = 28  # the cost model's reference shard: 2^28 amplitudes


# ======================================================================
# Device fingerprint
# ======================================================================


def device_fingerprint(dtype="complex64") -> Dict[str, str]:
    """Stable identity of the execution substrate a calibration is valid
    for. Two processes with equal fingerprints may share a calibration."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": getattr(devs[0], "device_kind", devs[0].platform),
        "device_count": str(len(devs)),
        "dtype": str(np.dtype(dtype)),
        "jax_version": jax.__version__,
    }


def fingerprint_digest(fp: Dict[str, str]) -> str:
    payload = tuple(sorted((str(k), str(v)) for k, v in fp.items()))
    return hashlib.sha256(repr(payload).encode()).hexdigest()[:16]


# ======================================================================
# Timing primitives
# ======================================================================


def _time_us(fn: Callable, *args, repeats: int = 5, warmup: int = 1) -> float:
    """Best-of-N wall time of ``fn(*args)`` in microseconds (the minimum is
    the standard noise-robust estimator for short kernels)."""
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(fn(*args))
    best = math.inf
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _rand_state(rng: np.random.Generator, L: int) -> jnp.ndarray:
    """A random 2^L-amplitude shard in the engines' lane-dense layout."""
    x = rng.standard_normal(1 << L) + 1j * rng.standard_normal(1 << L)
    x /= np.linalg.norm(x)
    return jnp.asarray(x.astype(np.complex64)).reshape(-1, 1 << lane_bits(L))


def _rand_unitary(rng: np.random.Generator, k: int) -> np.ndarray:
    m = rng.standard_normal((1 << k, 1 << k)) + 1j * rng.standard_normal(
        (1 << k, 1 << k))
    q, _ = np.linalg.qr(m)
    return q.astype(np.complex64)


# ======================================================================
# Microbenchmarks — each times a real engine primitive
# ======================================================================


def profile_dispatch(repeats: int = 20) -> Dict:
    """Bare kernel dispatch overhead: a jitted identity on a tiny operand.
    Maps to ``launch_us`` (scale-free)."""
    x = jnp.zeros(8, jnp.float32)
    fn = jax.jit(lambda v: v + 0.0)
    t = _time_us(fn, x, repeats=repeats, warmup=3)
    return {"launch_us": t, "raw": {"identity_us": t}}


def profile_pass(L: int, repeats: int = 5,
                 rng: Optional[np.random.Generator] = None) -> Dict:
    """One HBM read+write pass: a jitted elementwise multiply over a
    2^L-amplitude complex64 shard, scaled to the 2^28 reference. Maps to
    ``pass_us``."""
    rng = rng or np.random.default_rng(0)
    x = _rand_state(rng, L).reshape(-1)
    fn = jax.jit(lambda v: v * np.complex64(0.6 + 0.8j))
    t = _time_us(fn, x, repeats=repeats)
    scale = 2.0 ** (REFERENCE_L - L)
    return {"pass_us": t * scale, "raw": {"L": L, "elementwise_us": t}}


def profile_fusion(L: int, kmax: Optional[int] = None, repeats: int = 3,
                   rng: Optional[np.random.Generator] = None) -> Dict:
    """Fused-gate cost per k: the lane contraction the pjit/shardmap
    backends run with Pallas (``lanes.apply_unitary`` through
    ``kops.lane_matmul``), timed for k = 1..kmax on a 2^L shard, on the k
    lowest bits (*lane* gates) and on the k highest (*routed* gates: the
    targets are exchanged into the lanes and back). The model says ``t(k) ~
    launch + max(pass, mxu * 2^k)``, so the per-2^k slope of the lane gates'
    large-k tail estimates ``mxu_us_per_2k``. On a TPU a lane gate of any
    k <= 7 is the same embedded 128x128 matmul and a routed gate also pays
    its route, which that formula does not price; ``raw`` keeps both
    series."""
    from ..kernels.ops import lane_matmul

    rng = rng or np.random.default_rng(0)
    kmax = min(kmax or DEFAULT_COST_MODEL.max_fusion_qubits, L - 1)
    kmax = max(kmax, 1)
    view = _rand_state(rng, L)
    scale = 2.0 ** (REFERENCE_L - L)

    def time_gate(u, bits) -> float:
        fn = jax.jit(lambda v, m: apply_unitary(v, m, bits, L,
                                                lane_matmul=lane_matmul))
        return _time_us(fn, view, u, repeats=repeats)

    per_k: Dict[int, float] = {}
    routed: Dict[int, float] = {}
    for k in range(1, kmax + 1):
        u = jnp.asarray(_rand_unitary(rng, k))
        per_k[k] = time_gate(u, tuple(range(k)))
        routed[k] = time_gate(u, tuple(range(L - k, L)))
    # compute-bound tail: t28(k)/2^k flattens to mxu_us_per_2k
    tail = sorted(per_k)[len(per_k) // 2:]
    mxu = float(np.median([per_k[k] * scale / (1 << k) for k in tail]))
    return {
        "mxu_us_per_2k": mxu,
        "raw": {"L": L, "per_k_us": {str(k): v for k, v in per_k.items()},
                "routed_per_k_us": {str(k): v for k, v in routed.items()}},
    }


def profile_shm(L: int, repeats: int = 3,
                rng: Optional[np.random.Generator] = None) -> Dict:
    """shm group cost vs member count and diagonal fraction: the Pallas
    shared-memory kernel (``lanes.apply_shm_group``) with g member gates costs
    ``alpha + sum_g cost(g)``; the incremental cost between g=1 and g=g2
    estimates the per-gate constants (``shm_gate_us`` non-diagonal via dense
    2-qubit unitaries, ``shm_diag_gate_us`` via 1-D diagonals)."""
    rng = rng or np.random.default_rng(0)
    a = min(4, L - 1)
    window = tuple(range(a))
    view = _rand_state(rng, L)
    scale = 2.0 ** (REFERENCE_L - L)

    def time_group(gates) -> float:
        fn = jax.jit(lambda v: apply_shm_group(v, gates, window))
        return _time_us(fn, view, repeats=repeats)

    def dense_gates(g: int):
        return [((i % (a - 1), i % (a - 1) + 1),
                 jnp.asarray(_rand_unitary(rng, 2))) for i in range(g)]

    def diag_gates(g: int):
        out = []
        for i in range(g):
            d = np.exp(1j * rng.uniform(0, 2 * np.pi, 4)).astype(np.complex64)
            out.append(((i % (a - 1), i % (a - 1) + 1), jnp.asarray(d)))
        return out

    g_lo, g_hi = 1, 5
    t_dense_lo, t_dense_hi = time_group(dense_gates(g_lo)), time_group(
        dense_gates(g_hi))
    t_diag_lo, t_diag_hi = time_group(diag_gates(g_lo)), time_group(
        diag_gates(g_hi))
    span = g_hi - g_lo
    gate_us = max((t_dense_hi - t_dense_lo) * scale / span, 1e-2)
    diag_us = max((t_diag_hi - t_diag_lo) * scale / span, 1e-3)
    diag_us = min(diag_us, gate_us)  # a diagonal is never dearer than dense
    return {
        "shm_gate_us": gate_us,
        "shm_diag_gate_us": diag_us,
        "raw": {
            "L": L, "window_bits": a, "g": [g_lo, g_hi],
            "dense_us": [t_dense_lo, t_dense_hi],
            "diag_us": [t_diag_lo, t_diag_hi],
        },
    }


def profile_host_link(L: int, repeats: int = 5,
                      rng: Optional[np.random.Generator] = None) -> Dict:
    """Offload host-link bandwidth: a host->device->host round trip of one
    2^L-amplitude complex64 shard — exactly the per-shard motion of
    ``HostOffloadBackend._stream_stage``. Maps to ``host_link_gbps``
    (scale-free)."""
    rng = rng or np.random.default_rng(0)
    block = (rng.standard_normal(1 << L) +
             1j * rng.standard_normal(1 << L)).astype(np.complex64)

    def roundtrip(b):
        return np.asarray(jax.device_put(b))

    t_us = _time_us(roundtrip, block, repeats=repeats)
    nbytes = 2 * block.nbytes  # down + back
    gbps = nbytes / max(t_us, 1e-3) / 1e3  # bytes/us -> GB/s
    return {"host_link_gbps": gbps,
            "raw": {"L": L, "roundtrip_us": t_us, "bytes": nbytes}}


def profile_disk(L: int, repeats: int = 5,
                 rng: Optional[np.random.Generator] = None,
                 spill_dir: Optional[str] = None) -> Dict:
    """Spill-tier bandwidth: an fsync'd write + read round trip of one
    2^L-amplitude at-rest shard file — exactly the per-shard motion of the
    :mod:`repro.sim.shard_store` disk tier (atomic tmp+rename on the write
    side, like the store itself). Maps to ``disk_gbps`` (scale-free)."""
    import tempfile

    rng = rng or np.random.default_rng(0)
    block = (rng.standard_normal(1 << L) +
             1j * rng.standard_normal(1 << L)).astype(np.complex64)
    d = spill_dir or tempfile.gettempdir()
    path = os.path.join(d, f"repro-profile-disk-{os.getpid()}.npy")

    def roundtrip(b):
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.save(f, b)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return np.load(path)

    try:
        best = math.inf
        roundtrip(block)  # warmup (page cache, allocator)
        for _ in range(max(repeats, 1)):
            t0 = time.perf_counter()
            roundtrip(block)
            best = min(best, time.perf_counter() - t0)
    finally:
        for p in (path, path + ".tmp"):
            if os.path.exists(p):
                os.remove(p)
    t_us = best * 1e6
    nbytes = 2 * block.nbytes  # write + read
    gbps = nbytes / max(t_us, 1e-3) / 1e3  # bytes/us -> GB/s
    return {"disk_gbps": gbps,
            "raw": {"L": L, "roundtrip_us": t_us, "bytes": nbytes,
                    "dir": d}}


# ======================================================================
# Full profile run
# ======================================================================


def run_profile(fast: bool = True, L: Optional[int] = None,
                repeats: Optional[int] = None, seed: int = 0,
                dtype="complex64") -> Dict:
    """Run every microbenchmark and assemble a calibration dict (the JSON
    payload of :func:`save_calibration`). ``fast`` is the CI/test mode: tiny
    shards, few repetitions — noisy but structurally identical."""
    L = L if L is not None else (8 if fast else 14)
    repeats = repeats if repeats is not None else (2 if fast else 8)
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    sections = [
        profile_dispatch(repeats=max(repeats, 5)),
        profile_pass(L, repeats=repeats, rng=rng),
        profile_fusion(L, repeats=repeats, rng=rng),
        profile_shm(L, repeats=repeats, rng=rng),
        profile_host_link(L, repeats=repeats, rng=rng),
        profile_disk(L, repeats=repeats, rng=rng),
    ]
    measurements: Dict[str, float] = {}
    raw: Dict[str, Dict] = {}
    for name, sec in zip(
            ("dispatch", "pass", "fusion", "shm", "host_link", "disk"),
            sections):
        raw[name] = sec.pop("raw", {})
        measurements.update(sec)
    cm = CostModel.from_calibration(measurements)
    return {
        "version": CALIBRATION_VERSION,
        "fingerprint": device_fingerprint(dtype),
        "measurements": measurements,
        "cost_model": cm.to_dict(),
        "meta": {
            "fast": fast, "L": L, "repeats": repeats, "seed": seed,
            "profile_time_s": time.perf_counter() - t0,
            "raw": raw,
        },
    }


# ======================================================================
# Persistence + auto-load
# ======================================================================


def default_calibration_dir() -> str:
    return os.environ.get(
        "REPRO_CALIBRATION_DIR",
        os.path.join(os.path.expanduser("~"), ".cache", "repro-atlas"))


def default_calibration_path() -> str:
    return os.path.join(default_calibration_dir(), CALIBRATION_FILENAME)


def save_calibration(path: str, calib: Dict) -> str:
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(calib, f, indent=2, sort_keys=True)
    os.replace(tmp, path)
    return path


def load_calibration(path: str) -> Dict:
    with open(path) as f:
        calib = json.load(f)
    if not isinstance(calib, dict) or "measurements" not in calib:
        raise ValueError(f"{path}: not a calibration file")
    return calib


_RESOLVED: Dict[str, Tuple[CostModel, Dict]] = {}


def resolve_cost_model(path: Optional[str] = None, *,
                       refresh: bool = False) -> CostModel:
    """The cost model ``engine_for`` should plan with: the calibrated model
    when a calibration file with a matching device fingerprint exists, the
    analytic defaults otherwise.

    Memoized per-process (per path) so every key computation in a process —
    warm-pool admission, batcher group keys, ``engine_for`` itself — sees
    the SAME model and therefore the same :class:`CircuitKey`. Use
    ``refresh=True`` (or :func:`clear_resolved_cache`) after writing a new
    calibration mid-process."""
    cm, _ = resolve_calibration(path, refresh=refresh)
    return cm


def resolve_calibration(path: Optional[str] = None, *,
                        refresh: bool = False) -> Tuple[CostModel, Dict]:
    """:func:`resolve_cost_model` plus provenance: returns ``(model,
    info)`` where info records the source (``analytic``/``calibrated``/
    ``mismatch``/``error``), the path probed, and fingerprint digests."""
    env = os.environ.get("REPRO_CALIBRATION", "").strip()
    if env.lower() in ("off", "0", "none", "analytic"):
        return DEFAULT_COST_MODEL, {"source": "disabled", "path": None}
    if path is None:
        path = env if env else default_calibration_path()
    key = os.path.abspath(path)
    if not refresh and key in _RESOLVED:
        return _RESOLVED[key]
    info: Dict = {"path": key}
    cm = DEFAULT_COST_MODEL
    try:
        calib = load_calibration(key)
        here = fingerprint_digest(device_fingerprint())
        there = fingerprint_digest(calib.get("fingerprint", {}))
        info["fingerprint"] = there
        ver = int(calib.get("version", 0))
        if ver != CALIBRATION_VERSION:
            # a file from another schema version misses (or mis-scales)
            # fields the model now prices — fall back to analytic, loudly
            info["source"] = "version_mismatch"
            info["file_version"] = ver
            info["expected_version"] = CALIBRATION_VERSION
        elif here != there:
            info["source"] = "mismatch"
            info["local_fingerprint"] = here
        else:
            cm = CostModel.from_calibration(calib.get("measurements", {}))
            info["source"] = "calibrated"
    except FileNotFoundError:
        info["source"] = "analytic"
    except (ValueError, KeyError, OSError, json.JSONDecodeError) as e:
        info["source"] = "error"
        info["error"] = f"{type(e).__name__}: {e}"
    _RESOLVED[key] = (cm, info)
    return cm, info


def clear_resolved_cache() -> None:
    """Drop the per-process resolution memo (tests; post-recalibration)."""
    _RESOLVED.clear()


# ======================================================================
# Production observation sink
# ======================================================================

#: Bounded ring of lightweight runtime observations: every engine run (and
#: every offload stage) appends one record so production traffic keeps
#: contributing data the next calibration can sanity-check against.
OBSERVATIONS: "deque[Dict]" = deque(maxlen=4096)


def record_observation(kind: str, **data) -> None:
    OBSERVATIONS.append({"kind": kind, **data})


def observation_summary() -> Dict[str, Dict]:
    """Per-kind aggregate of the observation ring: count / total / mean /
    max wall-microseconds. Surfaced by the serve metrics snapshot."""
    agg: Dict[str, Dict] = {}
    for ob in list(OBSERVATIONS):
        a = agg.setdefault(ob["kind"], {"count": 0, "total_us": 0.0,
                                        "max_us": 0.0})
        us = float(ob.get("wall_us", 0.0))
        a["count"] += 1
        a["total_us"] += us
        a["max_us"] = max(a["max_us"], us)
    for a in agg.values():
        a["mean_us"] = a["total_us"] / max(a["count"], 1)
    return agg


def clear_observations() -> None:
    OBSERVATIONS.clear()


# ======================================================================
# Verification + CLI
# ======================================================================


def verify_calibration(calib: Dict, n_qubits: int = 6, seed: int = 0) -> bool:
    """Plan + run one circuit under the calibrated model and check the
    engine still matches the dense per-gate oracle — a wrong cost model may
    pick bad plans, it must never pick wrong ones."""
    from ..core.generators import random_circuit
    from .engine import engine_for
    from .statevector import simulate

    cm = CostModel.from_calibration(calib["measurements"])
    circ = random_circuit(n_qubits, n_gates=24, seed=seed)
    eng = engine_for(circ, L=n_qubits - 2, R=2, G=0, cost_model=cm,
                     cache=None)
    out = np.asarray(eng.run()).reshape(-1)
    ref = np.asarray(simulate(circ)).reshape(-1)
    phase = np.vdot(ref, out)
    phase = phase / abs(phase) if abs(phase) > 1e-12 else 1.0
    return bool(np.allclose(out, phase * ref, atol=1e-4))


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Profile kernel primitives and write a CostModel "
                    "calibration JSON")
    ap.add_argument("--fast", action="store_true",
                    help="tiny shards, few repetitions (CI smoke mode)")
    ap.add_argument("--L", type=int, default=None,
                    help="shard qubits for the microbenchmarks")
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=str, default=None,
                    help="output path (default: the auto-load location "
                         f"{default_calibration_path()})")
    ap.add_argument("--verify", action="store_true",
                    help="plan+run one circuit under the calibrated model "
                         "and check it against the dense oracle")
    args = ap.parse_args(argv)

    calib = run_profile(fast=args.fast, L=args.L, repeats=args.repeats,
                        seed=args.seed)
    out = args.out or default_calibration_path()
    save_calibration(out, calib)
    clear_resolved_cache()
    print(f"calibration -> {out}")
    print(f"  fingerprint {fingerprint_digest(calib['fingerprint'])} "
          f"({calib['fingerprint']['platform']} x"
          f"{calib['fingerprint']['device_count']})")
    for k in sorted(calib["measurements"]):
        print(f"  {k:<18} {calib['measurements'][k]:.4g}")
    if args.verify:
        ok = verify_calibration(calib, seed=args.seed)
        print(f"  verify: {'OK — engine matches dense oracle' if ok else 'FAILED'}")
        if not ok:
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
