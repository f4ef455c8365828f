"""Lane-dense device primitives and the chip bring-up contract, on CPU.

The primitives in :mod:`repro.sim.lanes` are checked against the oracle's
own ``apply_matrix`` on ``(2,)*L`` views; the rest pins what
``chip_smoke.py`` relies on: the closed form of ``qft`` in ``simulate_np``'s
bit order, a failed kernel build that raises instead of falling back, the
trace-time interpret choice and where the compile cache lives.
"""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import generators as gen
from repro.kernels import ops as kops
from repro.sim import faults, lanes
from repro.sim.apply import apply_matrix
from repro.sim.engine import engine_for
from repro.sim.statevector import simulate_np

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def _unitary(rng, k):
    q, _ = np.linalg.qr(rng.normal(size=(2**k, 2**k))
                        + 1j * rng.normal(size=(2**k, 2**k)))
    return q.astype(np.complex64)


def _ref(psi, u, bits, L):
    view = jnp.asarray(psi).reshape((2,) * L)
    return np.asarray(apply_matrix(view, jnp.asarray(u), list(bits))).reshape(-1)


def _state(rng, L):
    return (rng.normal(size=2**L) + 1j * rng.normal(size=2**L)).astype(np.complex64)


@pytest.mark.parametrize("L", [5, 9, 14, 16, 20])
def test_apply_unitary_and_diag_match_oracle(L):
    rng = np.random.default_rng(L)
    psi = _state(rng, L)
    l = lanes.lane_bits(L)
    x = jnp.asarray(psi).reshape(-1, 1 << l)  # the device paths' layout
    for _ in range(10):
        k = int(rng.integers(1, min(L, 7) + 1))
        bits = rng.choice(L, size=k, replace=False).tolist()  # any order
        u = _unitary(rng, k)
        out = np.asarray(lanes.apply_unitary(x, jnp.asarray(u), bits, L))
        np.testing.assert_allclose(out.reshape(-1), _ref(psi, u, bits, L),
                                   atol=1e-4)
        sb = sorted(bits)
        d = np.exp(1j * rng.uniform(0, 6, 2**k)).astype(np.complex64)
        out = np.asarray(lanes.apply_diag(x, jnp.asarray(d), sb, l))
        np.testing.assert_allclose(out.reshape(-1),
                                   _ref(psi, np.diag(d), sb, L), atol=1e-4)


@pytest.mark.parametrize("L", [9, 15])
def test_per_block_variants(L):
    """A leading tensor axis applies variant g to the g-th block of rows
    (the top bit here): the dep-batched ops of the pjit path."""
    rng = np.random.default_rng(L)
    psi = _state(rng, L)
    half = 1 << (L - 1)
    for k in (1, 2, 3):
        bits = sorted(rng.choice(L - 1, size=k, replace=False).tolist())
        ua, ub = _unitary(rng, k), _unitary(rng, k)
        out = np.asarray(lanes.apply_unitary(
            jnp.asarray(psi), jnp.stack([ua, ub]), bits, L - 1))
        ref = np.concatenate([_ref(psi[:half], ua, bits, L - 1),
                              _ref(psi[half:], ub, bits, L - 1)])
        np.testing.assert_allclose(out, ref, atol=1e-4)


@pytest.mark.parametrize("L", [6, 14, 17])
def test_permute_matches_transpose(L):
    rng = np.random.default_rng(L)
    psi = _state(rng, L)
    for _ in range(4):
        src = rng.permutation(L).tolist()
        flips = sorted(rng.choice(L, size=int(rng.integers(0, L)),
                                  replace=False).tolist())
        full = psi.reshape((2,) * L)
        for p in flips:
            full = np.flip(full, axis=L - 1 - p)
        perm = [L - 1 - src[L - 1 - i] for i in range(L)]
        ref = np.ascontiguousarray(np.transpose(full, perm)).reshape(-1)
        out = np.asarray(lanes.permute(jnp.asarray(psi), src, flips))
        np.testing.assert_allclose(out, ref, atol=1e-6)


def test_lane_route_moves_only_local_bits():
    """Steps planned for L local bits never touch the bits above them."""
    cur = list(range(20))
    for lanes_want in ([8, 9, 10, 11, 12, 13, 14], [0, 1, 15, 16, 2, 3, 4]):
        arrs = lanes.plan_lanes(cur, lanes_want, L=17)
        assert arrs[-1][:7] == lanes_want
        assert all(a[17:] == cur[17:] for a in arrs)


def test_qft_closed_form_matches_simulate_np_order():
    """chip_smoke's on-device check: simulate_np(qft(n), |x>)[y] =
    exp(2 pi i x rev(y) / 2^n) / 2^(n/2), rev = n-bit reversal."""
    n = 10
    for x in (0, 3, 613):
        psi0 = np.zeros(1 << n, np.complex128)
        psi0[x] = 1
        ref = simulate_np(gen.qft(n), psi0).astype(np.complex64)
        assert chip_smoke.qft_error(jnp.asarray(ref), x, n) < 1e-6
        assert chip_smoke.qft_error(jnp.asarray(ref).reshape(-1, 128), x, n) < 1e-6
    wrong = simulate_np(gen.qft(n), np.eye(1 << n)[5]).astype(np.complex64)
    assert chip_smoke.qft_error(jnp.asarray(wrong), 6, n) > 1e-3


def test_broken_kernel_fails_instead_of_falling_back():
    """With degrade=False a Pallas build failure raises (chip_smoke then
    exits non-zero); with the ladder on it would quietly drop the kernels."""
    c = gen.qft(6)
    with faults.inject(faults.FaultPlan.from_spec("pallas_lowering_error:rate=1.0")):
        with pytest.raises(faults.PallasLoweringError):
            chip_smoke.build(c, 6, backend="pjit", use_pallas=True)
        eng = engine_for(c, 6, backend="pjit", use_pallas=True, cache=None)
    assert eng.provenance["degraded"] and not eng.use_pallas


def test_run_phases_prints_one_line_per_phase(capsys):
    """Each phase prints one JSON line with its wall time, whatever keys its
    result holds; a phase that raises fails the run and the others go on."""
    def boom():
        raise AssertionError("bound missed")

    phases = [("p1", lambda: {"wall_s": -1.0, "n": 3}), ("p2", boom),
              ("p3", lambda: {"ok": True})]
    assert chip_smoke.run_phases(phases) is False
    lines = [json.loads(s) for s in capsys.readouterr().out.splitlines()]
    assert [(r["phase"], r["ok"]) for r in lines] == [
        ("p1", True), ("p2", False), ("p3", True)]
    assert all(r["wall_s"] >= 0 for r in lines)
    assert "bound missed" in lines[1]["error"]


def test_interpret_mode_is_chosen_at_trace_time(monkeypatch):
    assert kops.interpret_mode() is True  # CPU backend
    monkeypatch.setattr(kops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no path"):
        kops.interpret_mode()
    monkeypatch.setattr(kops.jax, "default_backend", lambda: "tpu")
    assert kops.interpret_mode() is False


_CACHE_PROBE = """
import sys, jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
salt = float(sys.argv[1])  # a program (and cache key) of this call's own
jax.jit(lambda x: jnp.sin(x) * salt)(jnp.ones(8)).block_until_ready()
"""


def _probe(env_dir, salt=3.0):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE, repr(salt)], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.strip().splitlines()[-1]


def test_compile_cache_dir(tmp_path):
    from repro.compile_cache import DEFAULT_DIR

    checkout_cache = os.path.abspath(os.path.join(ROOT, ".jax_cache"))
    assert os.path.abspath(DEFAULT_DIR) == checkout_cache
    target = str(tmp_path / "cache")
    assert _probe(target, salt=1 + np.random.default_rng().random()) == target
    written = set(os.listdir(target))
    assert written, "nothing written to $JAX_COMPILATION_CACHE_DIR"
    elsewhere = (set(os.listdir(checkout_cache))
                 if os.path.isdir(checkout_cache) else set())
    assert not written & elsewhere, "the entry landed in the checkout too"
    assert os.path.abspath(_probe(None)) == checkout_cache


def test_shm_group_with_exchanged_window_matches_oracle():
    """A window holding more than six row bits is exchanged into the lanes;
    a member whose row bit then sits below its lane bit (slots out of
    order) must still apply its matrix in the gate's own bit order."""
    L = 16
    rng = np.random.default_rng(3)
    psi = _state(rng, L)
    window = (0, 1, 2, 3, 8, 9, 10, 11, 12, 13, 14, 15)
    gates = [((3, 8), _unitary(rng, 2)), ((2, 12), _unitary(rng, 2)),
             ((8, 15), _unitary(rng, 2)), ((0,), _unitary(rng, 1))]
    out = lanes.apply_shm_group(jnp.asarray(psi).reshape(-1, 128),
                                [(b, jnp.asarray(u)) for b, u in gates], window)
    ref = psi
    for bits, u in gates:
        ref = _ref(ref, u, bits, L)
    np.testing.assert_allclose(np.asarray(out).reshape(-1), ref, atol=1e-4)
