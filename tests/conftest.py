"""Shared fixtures. NOTE: the dry-run's 512-device XLA flag is NEVER set here
— tests run with the default single CPU device (distributed tests spawn
subprocesses with their own XLA_FLAGS)."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# the suite must plan identically on every machine: never auto-load a
# developer's local device calibration (tests that exercise calibrated
# planning opt back in via monkeypatch + an explicit file)
os.environ.setdefault("REPRO_CALIBRATION", "off")

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def planned_engine(circuit, plan, backend="pjit", **kw):
    """The engine for an explicit plan, built by ``engine_for`` with the
    degradation ladder off: a backend that fails to build fails the test
    instead of falling back to another one."""
    from repro.sim.engine import engine_for

    return engine_for(circuit, plan.L, plan.R, plan.G, plan=plan,
                      backend=backend, degrade=False, **kw)


def assert_states_close(a, b, atol=1e-5, msg=""):
    """Global-phase-insensitive state-vector comparison.

    Asserts (1) both states have consistent norms and (2) the infidelity
    ``1 - |<a|b>| / (|a| |b|)`` is below ``atol`` — i.e. the states agree up
    to a global phase. Use this for every cross-backend / cross-algorithm
    state check instead of ad-hoc ``fidelity(...) > 0.9999`` or elementwise
    allclose (which a benign global phase would fail).
    """
    a = np.asarray(a, dtype=np.complex128).reshape(-1)
    b = np.asarray(b, dtype=np.complex128).reshape(-1)
    assert a.shape == b.shape, f"shape mismatch {a.shape} vs {b.shape} {msg}"
    na, nb = np.linalg.norm(a), np.linalg.norm(b)
    assert na > 1e-9 and nb > 1e-9, f"degenerate state norms ({na}, {nb}) {msg}"
    assert abs(na - nb) < 1e-3 + atol, f"norms diverge: {na} vs {nb} {msg}"
    infidelity = 1.0 - abs(np.vdot(a, b)) / (na * nb)
    assert infidelity < atol, f"infidelity {infidelity:.3e} >= {atol:.1e} {msg}"
