"""Compile the main path's kernels for a described TPU v5e, no chip attached.

Mosaic refuses kernels that interpret mode runs happily (unaligned slices,
reshapes it cannot lay out, more scoped VMEM than a kernel may use), and
XLA:TPU refuses programs that do not fit the chip's memory. These tests
compile at the real size with ``interpret=False`` so such regressions show
here, without chip time. The topology is described inside a fixture: only
the worker that runs this file loads the TPU compiler.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import gates as G
from repro.kernels import ops as kops
from repro.kernels.fusion import fused_matmul
from repro.kernels.shm import shm_apply

HBM_BYTES = 16 * 10**9  # TPU v5e: 16 GB per chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_fused_matmul_compiles_at_chosen_block(one_chip):
    m, k = 1 << 21, 128
    bm = kops.fused_block_m(m, k)
    assert m % bm == 0 and bm >= 8

    def f(sre, sim, ure, uim):
        return fused_matmul(sre, sim, ure, uim, block_m=bm, interpret=False)

    state = _sds((m, k), jnp.float32, one_chip)
    gate = _sds((k, k), jnp.float32, one_chip)
    compiled = jax.jit(f).lower(state, state, gate, gate).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _shm_members(a: int):
    """A member list touching lanes, the window's row bits, diagonals and
    lane/row pairs — the shapes the shm groups of su2random/qft hold."""
    rng = np.random.default_rng(a)
    phases = np.exp(1j * rng.uniform(0, 6, 4)).astype(np.complex64)
    top = a - 1
    members = [((0,), G.H), ((2, 5), G.CX), ((3,), phases[:2])]
    if a > 7:
        members += [((6, top), G.CX), ((top,), G.gate_matrix("ry", [0.7])),
                    ((1, top), phases)]
    if a > 8:
        members.append(((top - 1, top), G.SWAP))
    return members


@pytest.mark.parametrize("a", [7, 10, 13])
def test_shm_kernel_compiles(one_chip, a):
    """One shm group over 2^28 amplitudes, window of a bits (7 lanes + a-7
    row bits), at the block ``shm_apply`` sizes against its lowered
    operands — the block the engine's groups run at."""
    rows = 1 << 21
    gates = [(bits, jnp.asarray(np.asarray(m), jnp.complex64))
             for bits, m in _shm_members(a)]

    def f(sre, sim):
        return shm_apply(sre, sim, gates, a, interpret=False)

    state = _sds((rows, 128), jnp.float32, one_chip)
    compiled = jax.jit(f).lower(state, state).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pjit_qft28_fits_hbm(one_chip, monkeypatch):
    """The whole pjit stage program of qft(28) (2 GiB state) with the Pallas
    kernels compiles for one chip and fits its memory."""
    from repro.core import generators as gen
    from repro.sim.engine import engine_for

    monkeypatch.setattr(kops, "interpret_mode", lambda: False)
    eng = engine_for(gen.qft(28), 28, backend="pjit", use_pallas=True,
                     degrade=False, cache=None)
    state = _sds(eng.backend.shape, jnp.complex64, one_chip)
    consts = {u: _sds(a.shape, a.dtype, one_chip)
              for u, a in eng.consts.items()}
    lowered = eng.backend._fns[True].lower(state, consts)
    assert "tpu_custom_call" in lowered.as_text()
    m = lowered.compile().memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert total < HBM_BYTES, m
