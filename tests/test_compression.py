"""Gradient-compression tests: quantization error bounds, error-feedback
unbiasedness, and the compressed DCN reduction inside shard_map."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean env: deterministic fallback sweep
    from _hypothesis_compat import given, settings, st

from repro.train.compression import (
    ErrorFeedback,
    compress_with_feedback,
    dequantize_int8,
    quantize_int8,
)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), scale=st.floats(1e-4, 1e3))
def test_quantize_error_bound(seed, scale):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(8, 64)) * scale, jnp.float32)
    qs = quantize_int8(x)
    deq = dequantize_int8(qs)
    absmax = np.abs(np.asarray(x)).max(axis=-1, keepdims=True)
    err = np.abs(np.asarray(deq) - np.asarray(x))
    # half-step bound: scale/2 per element
    assert (err <= absmax / 127.0 * 0.5 + 1e-9).all()


def test_error_feedback_is_unbiased_over_time():
    """With constant gradients, EF-compressed updates average to the truth."""
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)}
    ef = ErrorFeedback.init(g)
    total = jnp.zeros_like(g["w"])
    steps = 50
    for _ in range(steps):
        _, deq, ef = compress_with_feedback(g, ef)
        total = total + deq["w"]
    mean = np.asarray(total) / steps
    np.testing.assert_allclose(mean, np.asarray(g["w"]), atol=2e-3, rtol=1e-2)


@pytest.mark.slow
def test_compressed_psum_matches_mean():
    code = """
import jax, numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.experimental.shard_map import shard_map
from repro.train.compression import compressed_psum

mesh = jax.make_mesh((4,), ('pod',), axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(4, 16, 32)), jnp.float32)

fn = jax.jit(shard_map(
    lambda s: compressed_psum(s[0], 'pod')[None],
    mesh=mesh, in_specs=P('pod'), out_specs=P('pod')))
out = np.asarray(fn(x))
want = np.asarray(jnp.mean(x, axis=0))
for i in range(4):
    np.testing.assert_allclose(out[i], want, atol=2e-2, rtol=2e-2)
print('OK')
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
