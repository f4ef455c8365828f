"""The numbers that decide ``correct``: how far a state the timed path
produced lies from the reference's state for the same input."""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def _errors(out, ref):
    d = out.reshape(ref.shape) - ref
    n = ref.size.bit_length() - 1
    max_err = jnp.max(jnp.abs(d)) * jnp.float32(2.0 ** (n / 2))
    l2_err = jnp.sqrt(jnp.sum(jnp.abs(d) ** 2) / jnp.sum(jnp.abs(ref) ** 2))
    return max_err, l2_err


def errors(out, ref) -> dict:
    """``max_err``: the largest amplitude error, in units of the mean
    amplitude 2^(-n/2); ``l2_err``: ||out - ref|| / ||ref||. A NaN anywhere
    makes both NaN, which no limit admits."""
    max_err, l2_err = _errors(out, ref)
    return {"max_err": float(max_err), "l2_err": float(l2_err)}


def judge(values: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}`` for every limited number; a number
    passes when it is at most its limit (NaN never does)."""
    return {k: {"value": values[k], "limit": limits[k]} for k in limits}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
