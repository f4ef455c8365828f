"""Unified execution layer: ONE stage-loop core, pluggable backends.

Atlas's execution model is a single pipeline — partition -> stage ->
kernelize -> compile -> execute — but it historically lived three times over
in this repo (pjit, shard_map, host-offload executors), each re-implementing
the stage loop, op dispatch, constant hoisting, inter-stage remap and the
``run``/``run_packed``/``measurement_frame`` API. This module extracts the
shared core:

* :class:`ExecutionEngine` owns the compiled program
  (:class:`repro.sim.compile.CompiledCircuit`), the op-tensor **constant
  registry** (keyed by the stable ``Op.uid`` the compiler assigns — never
  ``id(op)``), the **stage loop** (initial remap -> per-stage ops + remap ->
  optional final remap), and the public ``run`` / ``run_packed`` /
  ``run_batch`` / ``measurement_frame`` API.
* a :class:`Backend` supplies state placement plus the two primitives the
  loop composes — ``apply ops of one stage`` and ``apply one remap`` — in
  whatever substrate it owns: traced-under-jit global arrays
  (:class:`PjitBackend`), per-device views inside ``shard_map`` with explicit
  collectives (:class:`ShardMapBackend`), eager numpy shards streamed from
  host DRAM (:class:`HostOffloadBackend`), or a per-gate dense oracle that
  ignores the compiled program entirely (:class:`DenseBackend`).
* a **compile cache** (:class:`CircuitKey` -> engine LRU in
  :class:`CompileCache`, entry point :func:`engine_for`) so serving-style
  repeated traffic skips ILP staging + DP kernelization + stage compilation +
  XLA compilation after the first request.

Every caller builds its engine through :func:`engine_for`, or constructs
:class:`ExecutionEngine` directly when it holds a backend instance.

Adding a backend = subclass :class:`Backend`, implement ``prepare`` /
``execute`` (+ optionally ``execute_batch`` for a fused batch path), and
register it in :data:`BACKENDS`.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, fields as _dc_fields
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import optimize as copt
from ..core.circuit import Circuit
from ..core.cost_model import CostModel, DEFAULT_COST_MODEL
from ..core.gates import UnboundParameterError
from ..core.partition import SimulationPlan, partition
from . import faults
from .faults import (
    BackendBuildError,
    FaultError,
    IntegrityError,
    KernelizationError,
    PallasLoweringError,
    ShardTransferError,
    StagingError,
)
from .compile import (
    CompiledCircuit,
    Op,
    RemapSpec,
    StageProgram,
    bind_tensors,
    bind_tensors_sweep,
    compile_plan,
)
from . import lanes, trace
from .shard_store import ShardStore, StorageConfig


# ======================================================================
# Shared op application (global-array form; used by pjit & dense-jnp paths)
# ======================================================================


def _dep_index(op: Op, G: int, R: int, L: int) -> Optional[jnp.ndarray]:
    if not op.dep_bits:
        return None
    gdim, rdim = 1 << G, 1 << R
    g_iota = lax.broadcasted_iota(jnp.int32, (gdim, rdim), 0)
    r_iota = lax.broadcasted_iota(jnp.int32, (gdim, rdim), 1)
    idx = jnp.zeros((gdim, rdim), dtype=jnp.int32)
    for j, p in enumerate(op.dep_bits):
        if p >= L + R:
            bit = (g_iota >> (p - L - R)) & 1
        else:
            bit = (r_iota >> (p - L)) & 1
        idx = idx | (bit << j)
    return idx


#: The ``jax.named_scope`` of each stage op kind (pjit and shard_map): the
#: device trace carries it in each op's metadata (``op_name``), so device
#: time can be read per kind. Inside them, :mod:`repro.sim.lanes` opens
#: ``route``, :mod:`repro.kernels.ops` ``planar``, the making of the kernels'
#: operands ``operands``; remaps and the logical-order reshape run under
#: ``remap``, and the shard_map path's collectives under ``a2a``.
OP_SCOPES = {"shm": "shm", "fused": "fused", "diag": "diag", "scalar": "diag"}


def apply_op(
    x: jnp.ndarray, op: Op, G: int, R: int, L: int, dtype, consts=None,
    use_pallas: bool = False,
) -> jnp.ndarray:
    """Apply one op to the whole lane-dense state ``x`` (2^(G+R+L)
    amplitudes; the G+R non-local bits are the top row bits, one shard per
    row block). ``consts`` maps ``Op.uid`` -> device tensor; a dep-batched
    tensor supplies one variant per shard; ``use_pallas`` runs lane
    contractions of fused ops in the Pallas MXU kernel."""
    if op.kind == "shm":
        # non-Pallas shm group: members apply sequentially (same semantics)
        for m in op.gates:
            x = apply_op(x, m, G, R, L, dtype, consts, use_pallas)
        return x
    T = None if consts is None else consts.get(op.uid)
    if T is None:
        T = jnp.asarray(op.tensor, dtype=dtype)
    idx = _dep_index(op, G, R, L)
    if idx is None:
        w = T[0]
    else:  # [2^G, 2^R, ...] -> one variant per shard (row block)
        w = T[idx].reshape((1 << (G + R),) + T.shape[1:])
    if op.kind == "scalar":
        if idx is None:
            return x * w
        return lanes.apply_diag(x, w[:, None], (), lanes.lane_bits(L))
    if op.kind == "diag":
        return lanes.apply_diag(x, w, op.local_bits, lanes.lane_bits(L))
    return _apply_fused(x, w, op.local_bits, L, use_pallas)


def _apply_fused(x, u, bits, L: int, use_pallas: bool):
    if use_pallas:
        from ..kernels import ops as kops

        return lanes.apply_unitary(x, u, bits, L, lane_matmul=kops.lane_matmul)
    return lanes.apply_unitary(x, u, bits, L)


def apply_remap(x: jnp.ndarray, spec: RemapSpec, n: int, G: int, R: int, L: int) -> jnp.ndarray:
    """Bit permutation with flips over all n bits of the lane-dense state
    (lanes = the lowest min(7, L) bits)."""
    return lanes.permute(x, spec.src_bit_of, spec.flip_bits, lanes.lane_bits(L))


# ======================================================================
# Explicit-collective remap choreography (shard_map backend)
# ======================================================================


@dataclass
class RemapPlan:
    """Host-precomputed choreography for one inter-stage remap."""

    local_flip_axes: Tuple[int, ...]  # view axes to flip (old local pending flips)
    pre_perm: Tuple[int, ...]  # local transpose before a2a (view axes)
    a2a_axes: Tuple[str, ...]  # mesh axis names (desc bit order), may be empty
    m: int
    ppermute: Optional[Tuple[Tuple[int, int], ...]]  # full-group (src, dst) pairs
    post_flip_axes: Tuple[int, ...]  # chunk axes to flip after a2a (flipped
    # old nonlocal bits that moved into the local tier)
    post_perm: Tuple[int, ...]  # local transpose after a2a (view axes)


def _build_remap_plan(spec: RemapSpec, n: int, L: int) -> RemapPlan:
    src = spec.src_bit_of
    flips = set(spec.flip_bits)
    nonlocal_bits = list(range(L, n))

    s_out = sorted({src[p] for p in nonlocal_bits if src[p] < L}, reverse=True)
    s_in = sorted({src[p] for p in range(L) if src[p] >= L}, reverse=True)
    m = len(s_out)
    assert len(s_in) == m, "local<->nonlocal exchange must be balanced"

    # --- step A: local flips (old local bits with pending flips)
    local_flip_axes = tuple(L - 1 - s for s in sorted(flips) if s < L)

    # --- step B: pre-transpose: [S_out desc..., remaining local desc...]
    remaining = [b for b in range(L - 1, -1, -1) if b not in s_out]
    pre_order_bits = list(s_out) + remaining  # bit ids, new axis order
    pre_perm = tuple(L - 1 - b for b in pre_order_bits)

    # --- step C/D: after a2a, device bit s_in[t] holds old local bit s_out[t];
    # local chunk bit (m-1-t) holds old nonlocal bit s_in[t].
    holder = {s: s for s in nonlocal_bits if s not in s_in}
    for t in range(m):
        holder[("chunk", t)] = s_in[t]  # local chunk slot t holds old bit s_in[t]
        holder[s_in[t]] = s_out[t]  # device axis s_in[t] now holds old local bit

    # ppermute: new device bit p must hold old bit src[p]
    cur_of = {}  # old bit -> device bit currently holding it
    for s in nonlocal_bits:
        cur_of[holder[s]] = s
    perm_map = {}  # for each device bit position p: source device bit h
    flip_out = set()
    for p in nonlocal_bits:
        h = cur_of[src[p]]
        perm_map[p] = h
        if src[p] in flips and src[p] >= L:
            flip_out.add(p)
    # flips on old nonlocal bits that move INTO the local tier: apply after
    # the a2a, when the bit has become local chunk axis t (free local flip).
    post_flip_axes = tuple(t for t in range(m) if s_in[t] in flips)

    identity = all(perm_map[p] == p for p in nonlocal_bits) and not flip_out
    pairs: Optional[Tuple[Tuple[int, int], ...]] = None
    if not identity:
        nb = n - L
        pair_list = []
        for d in range(1 << nb):
            # device rank d: mesh axes desc bit order => rank bit (p-L) is bit p
            tgt = 0
            for p in nonlocal_bits:
                bit = (d >> (perm_map[p] - L)) & 1
                if p in flip_out:
                    bit ^= 1
                tgt |= bit << (p - L)
            pair_list.append((d, tgt))
        pairs = tuple(pair_list)

    # --- step E: final local transpose
    # current local axes (after a2a, viewed as (2,)*L):
    #   axes 0..m-1   <- old nonlocal bits s_in[0..m-1] (chunk bits desc)
    #   axes m..L-1   <- `remaining` old local bits (desc order)
    cur_axis_of_old_bit = {}
    for t in range(m):
        cur_axis_of_old_bit[s_in[t]] = t
    for j, b in enumerate(remaining):
        cur_axis_of_old_bit[b] = m + j
    post = []
    for i in range(L):  # new view axis i <- new local bit L-1-i
        p = L - 1 - i
        post.append(cur_axis_of_old_bit[src[p]])
    return RemapPlan(
        local_flip_axes=local_flip_axes,
        pre_perm=pre_perm,
        a2a_axes=tuple(f"b{s}" for s in s_in),
        m=m,
        ppermute=pairs,
        post_flip_axes=post_flip_axes,
        post_perm=tuple(post),
    )


def _axes_to_bits(perm: Sequence[int], L: int) -> List[int]:
    """A transpose ``perm`` of a (2,)*L view (axis i <-> bit L-1-i) as a
    ``src_bit_of`` bit permutation."""
    return [L - 1 - perm[L - 1 - p] for p in range(L)]


def _apply_remap_plan(shard, rp: RemapPlan, L: int, axis_names) -> jnp.ndarray:
    """Run one remap choreography on a per-device flat [2^L] shard (the
    plan's view axes map to bits; local transposes take the lane-dense
    route)."""
    m = rp.m
    x = lanes.permute(shard, _axes_to_bits(rp.pre_perm, L),
                      [L - 1 - ax for ax in rp.local_flip_axes])
    if m:
        # 2^m chunks of the top bits, lanes kept whole when they fit
        l = lanes.lane_bits(L)
        x = x.reshape((1 << m, -1, 1 << l) if L - m >= l else (1 << m, -1))
        with jax.named_scope("a2a"):  # tiled: dim 0 keeps its 2^m chunks
            x = lax.all_to_all(x, rp.a2a_axes, split_axis=0, concat_axis=0, tiled=True)
    if rp.ppermute is not None:
        with jax.named_scope("a2a"):
            x = lax.ppermute(x, axis_names, perm=list(rp.ppermute))
    return lanes.permute(x.reshape(shard.shape), _axes_to_bits(rp.post_perm, L),
                         [L - 1 - ax for ax in rp.post_flip_axes])


# ======================================================================
# Host-side remap + per-shard stage functions (offload backend)
# ======================================================================


def _np_remap(state: np.ndarray, spec: RemapSpec, n: int) -> np.ndarray:
    """Host bit permutation; accepts flat [2^n] or batched [B, 2^n]."""
    batched = state.ndim == 2
    lead = (state.shape[0],) if batched else ()
    off = 1 if batched else 0
    full = state.reshape(lead + (2,) * n)
    for p in spec.flip_bits:
        full = np.flip(full, axis=off + n - 1 - p)
    perm = list(range(off)) + [
        off + n - 1 - spec.src_bit_of[n - 1 - i] for i in range(n)
    ]
    full = np.transpose(full, perm)
    return np.ascontiguousarray(full).reshape(lead + (-1,))


def _op_sig(ops) -> Tuple:
    """Hashable structural signature of an op list ('shm' nests its members);
    the jitted shard function is cached per signature."""
    sig = []
    for op in ops:
        if op.kind == "shm":
            sig.append(("shm", tuple((m.kind, m.local_bits) for m in op.gates)))
        else:
            sig.append((op.kind, op.local_bits))
    return tuple(sig)


def _flat_ops(ops) -> List[Op]:
    """Ops in tensor-argument order: shm groups contribute their members."""
    flat: List[Op] = []
    for op in ops:
        flat.extend(op.gates if op.kind == "shm" else (op,))
    return flat


def _sig_arity(op_shapes: Tuple) -> int:
    return sum(len(e[1]) if e[0] == "shm" else 1 for e in op_shapes)


def _build_shard_fn(op_shapes: Tuple, L: int, batched: bool = False,
                    sweep: bool = False):
    """Jitted per-shard stage function for one op signature. With ``batched``
    the shard argument carries a leading batch axis that is vmapped over the
    shared gate tensors — one host<->device pass covers the whole batch.
    With ``sweep`` (implies batched blocks) the gate tensors carry the SAME
    leading axis — element p of the block is transformed by binding p's
    tensors (the fused parameter-sweep path)."""

    l = lanes.lane_bits(L)

    def apply_one(x, kind, local_bits, T):
        if kind == "scalar":
            return x * T
        if kind == "diag":
            return lanes.apply_diag(x, T, local_bits, l)
        return lanes.apply_unitary(x, T, local_bits, L)

    def fn(shard, *tensors):
        x = shard.reshape(-1, 1 << l)  # lane-dense (rows, lanes)
        ti = 0
        for entry in op_shapes:
            if entry[0] == "shm":
                for kind, local_bits in entry[1]:
                    x = apply_one(x, kind, local_bits, tensors[ti])
                    ti += 1
            else:
                x = apply_one(x, entry[0], entry[1], tensors[ti])
                ti += 1
        return x.reshape(-1)

    if sweep:
        fn = jax.vmap(fn, in_axes=(0,) + (0,) * _sig_arity(op_shapes))
    elif batched:
        fn = jax.vmap(fn, in_axes=(0,) + (None,) * _sig_arity(op_shapes))
    return jax.jit(fn, donate_argnums=(0,))


class JitCache:
    """Bounded LRU of compiled functions.

    Replaces a module-level ``@lru_cache(maxsize=None)`` of shard
    functions: unbounded per-process caches of jitted executables leak
    compiled programs in long-running serving processes. One instance lives on
    each backend, so dropping the engine drops its executables too.
    """

    def __init__(self, maxsize: int = 64):
        self.maxsize = maxsize
        self._d: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self, key, build: Callable):
        fn = self._d.get(key)
        if fn is None:
            self.misses += 1
            fn = build()
            self._d[key] = fn
            while len(self._d) > self.maxsize:
                self._d.popitem(last=False)
        else:
            self.hits += 1
            self._d.move_to_end(key)
        return fn

    def __len__(self) -> int:
        return len(self._d)


def _shm_operands(op: Op, select: Callable):
    """Collect the (local_bits, matrix) operand list for one shm group.

    ``select(member)`` resolves a member op to its dep-selected tensor (a
    per-device value on the shard_map path, a per-shard-batched ``[S, ...]``
    value on the pjit path). 1-D rows = diagonal member, 2-D = unitary
    member. Standalone scalar members accumulate into a product that folds
    into the first matrix so they never cost an extra pass; the product is
    returned unfolded only when the group has no matrix members.
    """
    gate_list = []
    scal = None
    for m in op.gates:
        Tsel = select(m)
        if m.kind == "scalar":
            scal = Tsel if scal is None else scal * Tsel
        else:
            gate_list.append((m.local_bits, Tsel))
    if scal is not None and gate_list:
        bits0, mat0 = gate_list[0]
        w = scal.reshape(scal.shape + (1,) * (mat0.ndim - scal.ndim))
        gate_list[0] = (bits0, mat0 * w)
        scal = None
    return gate_list, scal


# ======================================================================
# Backends
# ======================================================================


def _place_state(psi0, shape, dtype, sharding):
    """The initial state at ``shape`` on the backend's placement, never
    whole on one device first: |0..0> is built under ``jit`` with
    ``out_shardings``; a host array goes to each device as its own shard."""
    if psi0 is None:
        def zero():
            return jnp.zeros(shape, dtype).reshape(-1).at[0].set(1).reshape(shape)

        if sharding is None:
            return jax.jit(zero)()
        return jax.jit(zero, out_shardings=sharding)()
    if isinstance(psi0, jax.Array):
        x = psi0.astype(dtype).reshape(shape)
    else:
        x = np.asarray(psi0, dtype=np.dtype(dtype)).reshape(shape)
    return jax.device_put(x, sharding) if sharding is not None else jnp.asarray(x)


@partial(jax.jit, static_argnames="batch")
def _to_logical(out, batch: bool):
    """The flat logical order of a lane-dense result (a copy on a TPU,
    which tiles the last two dimensions)."""
    with jax.named_scope("remap"):
        return out.reshape(out.shape[0], -1) if batch else out.reshape(-1)


class Backend:
    """One execution substrate under the engine's stage loop.

    Contract: ``prepare`` places a flat logical [2^n] state (or a [B, 2^n]
    batch) into the backend's working form; ``execute`` runs the engine's
    :meth:`ExecutionEngine.stage_loop` over it (traced or eager);
    ``extract`` turns a final-remapped result back into flat logical order.
    ``execute_batch`` defaults to a per-element loop — override it when the
    substrate has a cheaper fused path (vmap, shared streaming pass).
    """

    name = "?"
    engine: "ExecutionEngine"

    def setup(self, engine: "ExecutionEngine") -> None:
        self.engine = engine
        # construction-failure injection point (the dense oracle is the
        # terminal rung of the degradation ladder and stays injection-free)
        if faults._ACTIVE is not None and self.name != "dense":
            faults.maybe_inject("xla_trace_error", site=f"{self.name}.setup")

    def on_rebind(self) -> None:
        """Called after the engine swaps in a new parameter binding (the
        constant registry now holds the new tensors). Backends that cache
        anything derived from tensor *values* must invalidate here; nothing
        derived from structure (jitted executables, remap plans, shardings)
        may be dropped — rebinding must not trigger recompilation."""

    def supports_fused_sweep(self) -> bool:
        """True when the backend has a fused ``execute_sweep`` path that is
        valid in its current configuration; the engine falls back to
        sequential rebinding (still zero new XLA traces) otherwise."""
        return False

    def supports_fused_grad(self) -> bool:
        """True when ``grad_sweep`` may vmap the adjoint reverse sweep over
        the binding axis on this backend (the whole batch of reverse sweeps
        is one executable). Backends whose states live outside a plain
        device array (explicit collectives, host-DRAM streaming) report
        False and the engine runs the per-point sweep sequentially — still
        one cached executable, zero retraces after the first point."""
        return False

    def prepare(self, psi0, batch: bool = False):
        raise NotImplementedError

    def execute(self, state, apply_final: bool = True):
        raise NotImplementedError

    def execute_batch(self, states, apply_final: bool = True):
        outs = [self.execute(states[b], apply_final) for b in range(len(states))]
        if isinstance(outs[0], np.ndarray):
            return np.stack(outs)
        return jnp.stack(outs)

    def extract(self, out, batch: bool = False):
        return _to_logical(out, batch)


class PjitBackend(Backend):
    """GSPMD path: whole stage loop traced under one ``jax.jit``; remaps are
    bit transposes + sharding constraints the compiler lowers to collectives.
    The state is the lane-dense ``(2^(n-l), 2^l)`` array (see
    :mod:`repro.sim.lanes`) whose rows are sharded over the mesh: shard
    ``g * 2^R + r`` is the ``(g, r)``-th block of rows. Batches vmap the
    entire loop (single-array placement only)."""

    name = "pjit"

    def __init__(self, mesh: Optional[Mesh] = None, global_axes=("pod",),
                 regional_axes=("data", "model"), donate: bool = True):
        self.mesh = mesh
        self.global_axes = global_axes
        self.regional_axes = regional_axes
        self.donate = donate

    def setup(self, engine: "ExecutionEngine") -> None:
        super().setup(engine)
        G, R = engine.G, engine.R
        if self.mesh is not None:
            mesh = self.mesh
            gsize = int(np.prod([mesh.shape[a] for a in self.global_axes])) if self.global_axes else 1
            rsize = int(np.prod([mesh.shape[a] for a in self.regional_axes])) if self.regional_axes else 1
            if gsize != (1 << G):
                raise BackendBuildError(
                    f"pjit mesh mismatch: pod devices {gsize} != 2^G={1 << G}")
            if rsize != (1 << R):
                raise BackendBuildError(
                    f"pjit mesh mismatch: ICI devices {rsize} != 2^R={1 << R}")
            axes = ((tuple(self.global_axes) if G else ())
                    + (tuple(self.regional_axes) if R else ()))
            self.sharding = NamedSharding(mesh, P(axes or None, None))
        else:
            self.sharding = None
        l = lanes.lane_bits(engine.L)
        self.shape = (1 << (engine.n - l), 1 << l)
        dargs = (0,) if self.donate else ()
        self._fns = {
            True: jax.jit(partial(self._exec, apply_final=True), donate_argnums=dargs),
            False: jax.jit(partial(self._exec, apply_final=False), donate_argnums=dargs),
        }
        self._batch_fns: Dict[bool, Callable] = {}
        self._sweep_fns: Dict[bool, Callable] = {}

    # ------------------------------------------------------------- traced
    def _wsc(self, x):
        if self.sharding is not None:
            x = lax.with_sharding_constraint(x, self.sharding)
        return x

    def _exec(self, packed, consts, apply_final: bool = True):
        # `consts` (the op-tensor registry) is an INPUT to the traced loop,
        # not a baked-in constant: one XLA executable serves every parameter
        # binding of the circuit structure.
        eng = self.engine
        eng.xla_compiles += 1  # python side effect: runs at trace time only
        x = self._wsc(packed.reshape(self.shape))
        return eng.stage_loop(
            x, lambda v, prog: self._apply_ops(v, prog, consts),
            self._remap, apply_final,
        )

    def _remap(self, x, slot, spec: RemapSpec):
        eng = self.engine
        with jax.named_scope("remap"):
            return self._wsc(apply_remap(x, spec, eng.n, eng.G, eng.R, eng.L))

    def _apply_ops(self, x, prog: StageProgram, consts):
        eng = self.engine
        # (plain fused/diag/scalar ops stay XLA einsums so GSPMD is free to
        # fuse; with use_pallas an shm group runs as ONE pallas_call per
        # shard, vmapped over the packed shard axes)
        for op in prog.ops:
            with jax.named_scope(OP_SCOPES[op.kind]):
                if eng.use_pallas and op.kind == "shm":
                    x = self._apply_shm_pallas(x, op, consts)
                else:
                    x = apply_op(x, op, eng.G, eng.R, eng.L, eng.dtype, consts,
                                 eng.use_pallas)
        return x

    def _select_batched(self, m: Op, consts):
        """[S, ...] per-shard dep-selected tensor for one shm member."""
        eng = self.engine
        G, R, L = eng.G, eng.R, eng.L
        S = 1 << (G + R)
        T = consts.get(m.uid)
        if T is None:
            T = jnp.asarray(m.tensor, dtype=eng.dtype)
        idx = _dep_index(m, G, R, L)
        if idx is not None and T.shape[0] > 1:
            return T[idx.reshape(-1)]  # [S, ...] per-shard variant
        return jnp.broadcast_to(T[0], (S,) + T.shape[1:])

    def _apply_shm_pallas(self, x, op: Op, consts):
        eng = self.engine
        S = 1 << (eng.G + eng.R)
        gate_list, scal = _shm_operands(op, lambda m: self._select_batched(m, consts))
        if not gate_list:
            return lanes.apply_diag(x, scal[:, None], (), lanes.lane_bits(eng.L))
        if S == 1:
            return lanes.apply_shm_group(
                x, [(b, m[0]) for b, m in gate_list], op.local_bits)
        bits_list = [b for b, _ in gate_list]
        xf = x.reshape((S, -1) + x.shape[1:])  # one row block per shard
        out = jax.vmap(
            lambda v, *ms: lanes.apply_shm_group(
                v, list(zip(bits_list, ms)), op.local_bits
            )
        )(xf, *[m for _, m in gate_list])
        return out.reshape(x.shape)

    # ---------------------------------------------------------------- api
    def prepare(self, psi0, batch: bool = False):
        eng = self.engine
        if batch:
            return jnp.asarray(psi0, dtype=eng.dtype).reshape((-1,) + self.shape)
        return _place_state(psi0, self.shape, eng.dtype, self.sharding)

    def execute(self, state, apply_final: bool = True):
        return self._fns[apply_final](state, self.engine.consts)

    def execute_batch(self, states, apply_final: bool = True):
        if self.sharding is not None:
            # keep each element's sharding explicit; vmapping a constrained
            # loop would need per-axis sharding rules
            return super().execute_batch(states, apply_final)
        fn = self._batch_fns.get(apply_final)
        if fn is None:
            fn = jax.jit(jax.vmap(partial(self._exec, apply_final=apply_final),
                                  in_axes=(0, None)))
            self._batch_fns[apply_final] = fn
        return fn(states, self.engine.consts)

    def supports_fused_sweep(self) -> bool:
        # vmapping the sharding-constrained loop would need per-axis
        # sharding rules (same restriction as execute_batch): with a mesh,
        # the engine falls back to sequential rebinding
        return self.sharding is None

    def supports_fused_grad(self) -> bool:
        # same restriction: the vmapped reverse sweep is a dense whole-state
        # program — valid exactly when the forward sweep may vmap too
        return self.sharding is None

    def execute_sweep(self, state, consts_b, apply_final: bool = True):
        """Fused parameter sweep: ONE state broadcast against a [P, ...]
        batch of tensor registries — the whole stage loop vmaps over the
        binding axis, so P parameter points cost one traced executable."""
        fn = self._sweep_fns.get(apply_final)
        if fn is None:
            fn = jax.jit(jax.vmap(partial(self._exec, apply_final=apply_final),
                                  in_axes=(None, 0)))
            self._sweep_fns[apply_final] = fn
        return fn(state, consts_b)

    def lower(self, psi_shape_only: bool = True):
        eng = self.engine
        shape = jax.ShapeDtypeStruct(
            self.shape, eng.dtype,
            **({"sharding": self.sharding} if self.sharding else {}),
        )
        cshapes = {u: jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for u, a in eng.consts.items()}
        return self._fns[True].lower(shape, cshapes)


class ShardMapBackend(Backend):
    """Explicit-collective path: the stage loop runs per-device inside
    ``shard_map`` over a bit-mesh; remaps execute the paper's choreography
    (local transpose + grouped all_to_all + ppermute + local transpose)."""

    name = "shardmap"

    def __init__(self, devices=None):
        self.devices = devices

    def setup(self, engine: "ExecutionEngine") -> None:
        super().setup(engine)
        n, L = engine.n, engine.L
        nb = engine.R + engine.G
        devices = self.devices if self.devices is not None else jax.devices()
        if len(devices) < (1 << nb):
            raise BackendBuildError(
                f"shard_map bit-mesh needs {1 << nb} devices, "
                f"have {len(devices)}")
        devs = np.array(devices[: 1 << nb]).reshape((2,) * nb if nb else (1,))
        self.axis_names = tuple(f"b{p}" for p in range(n - 1, L - 1, -1)) or ("b_dummy",)
        self.mesh = Mesh(devs, self.axis_names)
        self.sharding = NamedSharding(self.mesh, P(self.axis_names if nb else None))
        cc = engine.cc
        self._plans: Dict = {}
        if cc.initial_remap is not None:
            self._plans["init"] = _build_remap_plan(cc.initial_remap, n, L)
        for i, prog in enumerate(cc.programs):
            if prog.remap_after is not None:
                self._plans[i] = _build_remap_plan(prog.remap_after, n, L)
        if cc.final_remap is not None:
            self._plans["final"] = _build_remap_plan(cc.final_remap, n, L)
        self._fns: Dict[bool, Callable] = {True: self._make_fn(True)}
        # (the packed variant is built lazily on first run_packed)

    def _make_fn(self, apply_final: bool):
        nb = self.engine.R + self.engine.G
        cspecs = {u: P() for u in self.engine.consts}  # tensors replicated
        fn = shard_map(
            partial(self._device_fn, apply_final=apply_final),
            mesh=self.mesh,
            in_specs=(P(self.axis_names if nb else None), cspecs),
            out_specs=P(self.axis_names if nb else None),
            check_vma=False,
        )
        return jax.jit(fn, donate_argnums=(0,))

    # ------------------------------------------------------------- traced
    def _device_fn(self, shard, consts, apply_final: bool = True):
        self.engine.xla_compiles += 1  # trace-time side effect
        l = lanes.lane_bits(self.engine.L)
        self._remaps = _remap_record_of(self, apply_final)
        out = self.engine.stage_loop(
            shard.reshape(-1, 1 << l),  # lane-dense (rows, lanes)
            lambda v, prog: self._apply_ops(v, prog, consts),
            self._remap, apply_final,
        )
        return out.reshape(-1)

    def _remap(self, view, slot, spec: RemapSpec):
        _record_remap(self, slot)
        with jax.named_scope("remap"):
            return _apply_remap_plan(view, self._plans[slot], self.engine.L, self.axis_names)

    def _apply_ops(self, view, prog: StageProgram, consts):
        for op in prog.ops:
            with jax.named_scope(OP_SCOPES[op.kind]):
                view = self._apply_op(view, op, consts)
        return view

    def _dep_idx(self, op: Op):
        return sum(lax.axis_index(f"b{p}").astype(jnp.int32) << j
                   for j, p in enumerate(op.dep_bits))

    def _select(self, op: Op, consts):
        """Per-device tensor slice: dep-batched variant via ``lax.axis_index``."""
        T = consts.get(op.uid)
        if T is None:
            T = jnp.asarray(op.tensor, dtype=self.engine.dtype)
        if op.dep_bits and T.shape[0] > 1:
            return T[self._dep_idx(op)]
        return T[0]

    def _apply_op(self, view, op: Op, consts):
        eng = self.engine
        if op.kind == "shm":
            return self._apply_shm(view, op, consts)
        Tsel = self._select(op, consts)
        if op.kind == "scalar":
            return view * Tsel
        if op.kind == "diag":
            return lanes.apply_diag(view, Tsel, op.local_bits, lanes.lane_bits(eng.L))
        return _apply_fused(view, Tsel, op.local_bits, eng.L, eng.use_pallas)

    def _apply_shm(self, view, op: Op, consts):
        """One shm group = one memory pass. On the Pallas path the whole
        member list runs inside a single ``pallas_call``; member matrices are
        the dep-selected variants, standalone scalar members fold into the
        first matrix so they never cost an extra pass."""
        if not self.engine.use_pallas:
            for m in op.gates:
                view = self._apply_op(view, m, consts)
            return view
        with jax.named_scope("operands"):
            gate_list, scal = _shm_operands(op, lambda m: self._select(m, consts))
        if not gate_list:
            return view * scal
        return lanes.apply_shm_group(view, gate_list, op.local_bits)

    # ---------------------------------------------------------------- api
    def _fn(self, apply_final: bool):
        if apply_final not in self._fns:
            self._fns[apply_final] = self._make_fn(apply_final)
        return self._fns[apply_final]

    def prepare(self, psi0, batch: bool = False):
        eng = self.engine
        if batch:
            return jnp.asarray(psi0, dtype=eng.dtype).reshape(-1, 1 << eng.n)
        return _place_state(psi0, (1 << eng.n,), eng.dtype, self.sharding)

    def execute(self, state, apply_final: bool = True):
        return self._fn(apply_final)(state, dict(self.engine.consts))

    def execute_batch(self, states, apply_final: bool = True):
        # collectives preclude a plain vmap over the shard program; run the
        # batch through the (already compiled) per-element function instead
        fn = self._fn(apply_final)
        consts = dict(self.engine.consts)
        return jnp.stack([
            fn(jax.device_put(states[b], self.sharding), consts)
            for b in range(states.shape[0])
        ])

    def extract(self, out, batch: bool = False):
        return out  # device fn already returns flat [2^n] (or [B, 2^n])

    def lower(self):
        eng = self.engine
        shape = jax.ShapeDtypeStruct((1 << eng.n,), eng.dtype, sharding=self.sharding)
        cshapes = {u: jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for u, a in eng.consts.items()}
        return self._fns[True].lower(shape, cshapes)


class HostOffloadBackend(Backend):
    """Host-DRAM streaming path (paper §VII-C): the state lives in host
    memory as ``2^(R+G)`` shards of ``2^L`` amplitudes; each stage streams
    every shard through the device once (double-buffered), and remaps are
    host-side bit permutations. A batch streams ``[B, 2^L]`` blocks through a
    vmapped shard function — one host<->device pass covers the whole batch."""

    name = "offload"

    def __init__(self, jit_cache_size: int = 64,
                 checkpoint_dir: Optional[str] = None,
                 storage=None):
        self.jit_cache = JitCache(maxsize=jit_cache_size)
        # opt-in stage checkpointing: journal + state snapshot after every
        # completed stage so a killed long-run resumes instead of restarting
        self.checkpoint_dir = checkpoint_dir
        # opt-in tiered at-rest storage (compressed DRAM tier + disk spill):
        # when set, ``prepare`` returns a ShardStore instead of a dense host
        # array and the stage loop streams shards through it. Mutually
        # exclusive with stage checkpointing (the store IS the durable
        # representation boundary; checkpointing a store would re-gather it).
        self.storage: Optional[StorageConfig] = StorageConfig.coerce(storage)

    def setup(self, engine: "ExecutionEngine") -> None:
        super().setup(engine)
        self.stats = {
            "shard_transfers": 0,
            "host_remaps": 0,
            "tensor_uploads": 0,  # full-tensor H2D uploads (once per op)
            "tensor_slice_reuse": 0,  # per-shard slices served from device
            "overlapped_dispatches": 0,  # shard s+1 in flight while s drains
            "stage_streams": 0,  # _stream_stage invocations (one drain each)
            "memory_passes": 0,  # device HBM passes (top-level op count)
            "checkpointed_stages": 0,  # stage snapshots written (opt-in)
            "resumed_stages": 0,  # stages skipped on the last resume
            "straggler_stages": 0,  # stages flagged by the EWMA monitor
        }
        self._uploaded: set = set()  # op uids whose tensor reached the device
        self._dev_slices: Dict = {}  # (op.uid, combo) -> device slice
        self._sweep_consts: Optional[Dict[int, jnp.ndarray]] = None  # [P, ...]
        self._sweep_slices: Dict = {}  # (op.uid, combo) -> [P, ...] device slice

    def on_rebind(self) -> None:
        # per-shard tensor slices are derived from tensor VALUES: drop them
        # (the jitted shard functions are keyed by op signature only and
        # take tensors as arguments, so they survive every rebinding)
        self._dev_slices.clear()
        self._uploaded.clear()
        # sweep-mode slices are derived from a *previous* sweep's batched
        # tensor tables — equally stale after a rebind. Clearing them here
        # (not just in execute_sweep's finally) means an interrupted or
        # raced sweep can never leak per-binding slices into the next run.
        self._sweep_slices.clear()
        self._sweep_consts = None

    # ------------------------------------------------------------ tensors
    def _dep_combo(self, op: Op, shard_id: int) -> int:
        idx = 0
        for j, p in enumerate(op.dep_bits):
            bit = (shard_id >> (p - self.engine.L)) & 1
            idx |= bit << j
        return idx

    def resolve(self, op: Op, shard_id: int):
        """Device tensor slice for this shard (dep bits are known values).

        The full dep-batched tensor lives in the engine's constant registry
        (ONE upload per op); per-shard slices are device-side gathers cached
        by ``(op.uid, dep-combo)`` — no per-shard host->device re-upload.
        In sweep mode the registry carries a leading binding axis and slices
        come out ``[P, ...]``.
        """
        combo = self._dep_combo(op, shard_id) if op.dep_bits else 0
        key = (op.uid, combo)
        if self._sweep_consts is not None:
            sl = self._sweep_slices.get(key)
            if sl is None:
                sl = self._sweep_consts[op.uid][:, combo]
                self._sweep_slices[key] = sl
            else:
                self.stats["tensor_slice_reuse"] += 1
            return sl
        full = self.engine.consts[op.uid]
        if op.uid not in self._uploaded:
            self._uploaded.add(op.uid)
            self.stats["tensor_uploads"] += 1
        sl = self._dev_slices.get(key)
        if sl is None:
            sl = full[combo]
            self._dev_slices[key] = sl
        else:
            self.stats["tensor_slice_reuse"] += 1
        return sl

    def shard_fn(self, sig: Tuple, batched: bool = False, sweep: bool = False):
        eng = self.engine
        key = (sig, eng.L, str(eng.np_dtype), batched, sweep)

        def build():
            eng.xla_compiles += 1
            return _build_shard_fn(sig, eng.L, batched=batched, sweep=sweep)

        return self.jit_cache.get(key, build)

    # -------------------------------------------------------------- eager
    def _stream_stage(self, state, prog: StageProgram):
        if faults._ACTIVE is not None:
            faults.maybe_inject("slow_stage", site="offload.stage")
        # eager backend => each stage's host time is directly observable (the
        # traced backends can only time whole executables)
        with trace.span("offload_stage", self.engine.timings):
            if isinstance(state, ShardStore):
                return self._stream_stage_store(state, prog)
            return self._stream_stage_array(state, prog)

    def _stream_stage_array(self, state, prog: StageProgram):
        eng = self.engine
        L = eng.L
        batched = state.ndim == 2
        fn = self.shard_fn(_op_sig(prog.ops), batched=batched,
                           sweep=self._sweep_consts is not None)
        flat = _flat_ops(prog.ops)
        self.stats["memory_passes"] += prog.n_passes
        self.stats["stage_streams"] += 1
        n_shards = 1 << eng.n_nonlocal
        # double-buffered streaming: shard s+1 is uploaded and dispatched
        # BEFORE blocking on shard s's result, so H2D/compute/D2H overlap
        # (donated ping-pong buffers: fn donates its input shard)
        pending = None  # (shard_id, in-flight device result)
        for s in range(n_shards):
            if faults._ACTIVE is not None:
                faults.maybe_inject("shard_transfer_error",
                                    site=f"offload.shard{s}")
            lo, hi = s << L, (s + 1) << L
            tensors = [self.resolve(op, s) for op in flat]
            block = np.ascontiguousarray(state[..., lo:hi])
            out = fn(jax.device_put(block), *tensors)
            if pending is not None:
                ps, pout = pending
                state[..., ps << L:(ps + 1) << L] = np.asarray(pout)
                self.stats["overlapped_dispatches"] += 1
            pending = (s, out)
            self.stats["shard_transfers"] += 1
        if pending is not None:
            ps, pout = pending
            state[..., ps << L:(ps + 1) << L] = np.asarray(pout)
        return state

    def _stream_stage_store(self, store: ShardStore, prog: StageProgram):
        """The same double-buffered ping-pong loop over a tiered
        :class:`ShardStore`: shard s+1's disk read + dequantize runs on the
        store's prefetch worker while shard s computes on device, and shard
        s-1's result re-encodes back into the store while s+1 is in flight —
        the spill tier hides behind the same ``overlap_ratio``."""
        eng = self.engine
        batched = store.ndim == 2
        fn = self.shard_fn(_op_sig(prog.ops), batched=batched,
                           sweep=self._sweep_consts is not None)
        flat = _flat_ops(prog.ops)
        self.stats["memory_passes"] += prog.n_passes
        self.stats["stage_streams"] += 1
        n_shards = store.n_shards
        fetch = store.prefetch(0)
        pending = None  # (shard_id, in-flight device result)
        for s in range(n_shards):
            if faults._ACTIVE is not None:
                faults.maybe_inject("shard_transfer_error",
                                    site=f"offload.shard{s}")
            tensors = [self.resolve(op, s) for op in flat]
            block = fetch.result() if fetch is not None \
                else store.get_decoded(s)
            fetch = store.prefetch(s + 1) if s + 1 < n_shards else None
            out = fn(jax.device_put(block), *tensors)
            if pending is not None:
                ps, pout = pending
                store.put(ps, np.asarray(pout))
                self.stats["overlapped_dispatches"] += 1
            pending = (s, out)
            self.stats["shard_transfers"] += 1
        if pending is not None:
            ps, pout = pending
            store.put(ps, np.asarray(pout))
        return store

    def _remap(self, state, slot, spec: RemapSpec):
        self.stats["host_remaps"] += 1
        if isinstance(state, ShardStore):
            return state.remap(spec, self.engine.n)
        return _np_remap(state, spec, self.engine.n)

    # ---------------------------------------------------------------- api
    @property
    def overlap_ratio(self) -> float:
        """Fraction of *overlappable* shard dispatches issued while the
        previous shard was still in flight. Each streamed stage must drain
        its last shard, so ``shard_transfers - stage_streams`` is the
        achievable maximum; with a single shard per stage no overlap is
        possible at all and the ratio reports a vacuous 1.0 instead of a
        misleading 0.0."""
        possible = (self.stats["shard_transfers"]
                    - self.stats.get("stage_streams", 0))
        if possible <= 0:
            return 1.0
        return self.stats["overlapped_dispatches"] / possible

    def prepare(self, psi0, batch: bool = False):
        eng = self.engine
        if self.storage is not None:
            n_shards = 1 << eng.n_nonlocal
            if batch:
                arr = np.asarray(psi0, dtype=eng.np_dtype).reshape(
                    -1, 1 << eng.n)
                return ShardStore(n_shards, 1 << eng.L, (arr.shape[0],),
                                  eng.np_dtype, self.storage).fill(arr)
            state = (None if psi0 is None else
                     np.asarray(psi0, dtype=eng.np_dtype).reshape(-1))
            return ShardStore(n_shards, 1 << eng.L, (), eng.np_dtype,
                              self.storage).fill(state)
        if batch:
            arr = np.array(psi0, dtype=eng.np_dtype).reshape(-1, 1 << eng.n)
            return arr
        state = np.zeros(1 << eng.n, dtype=eng.np_dtype)
        if psi0 is None:
            state[0] = 1.0
        else:
            state[:] = np.asarray(psi0, dtype=eng.np_dtype)
        return state

    def execute(self, state, apply_final: bool = True):
        if isinstance(state, ShardStore):
            return self._execute_store(state, apply_final)
        if self.checkpoint_dir is not None and isinstance(state, np.ndarray):
            return self._execute_checkpointed(state, apply_final)
        return self.engine.stage_loop(state, self._stream_stage, self._remap, apply_final)

    def execute_batch(self, states, apply_final: bool = True):
        return self.execute(states, apply_final)  # primitives are batch-aware

    def _execute_store(self, store: ShardStore, apply_final: bool):
        """The stage loop over a tiered :class:`ShardStore`, then the
        storage contract checks: reject the run if the accumulated
        quantization error bound exceeds the configured tolerance (typed
        :class:`repro.sim.faults.StorageToleranceError` — never a silently
        less-accurate result), surface the per-run storage summary in
        ``engine.provenance["storage"]``, and gather the decoded state."""
        try:
            store = self.engine.stage_loop(store, self._stream_stage,
                                           self._remap, apply_final)
            store.check_tolerance()
            self.engine.provenance["storage"] = store.snapshot()
            return store.gather()
        finally:
            store.close()

    def storage_snapshot(self) -> Optional[Dict]:
        """The last storage-tier run summary (None when tiered storage is
        off or no run has completed) — the serving stats read this."""
        return self.engine.provenance.get("storage")

    # -------------------------------------------------- stage checkpointing
    def _run_sig(self, state: np.ndarray) -> str:
        """Identity of one run: structure + binding + initial state. A
        journal written under a different signature is ignored (never
        resumed into the wrong run)."""
        eng = self.engine
        h = hashlib.sha256()
        h.update(repr(eng.circuit.structure_fingerprint()).encode())
        h.update(repr(eng.bound_circuit.binding_signature()).encode())
        # state.shape is part of the identity: a [B, 2^L] batch and a flat
        # [B * 2^L] state serialize to the same bytes, and resuming one
        # into the other would silently mix runs
        h.update(repr((eng.n, eng.L, eng.R, eng.G, str(eng.np_dtype),
                       tuple(state.shape))).encode())
        h.update(state.tobytes())
        return h.hexdigest()

    @staticmethod
    def _save_state(path: str, state: np.ndarray) -> None:
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.save(f, state)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def _execute_checkpointed(self, state: np.ndarray, apply_final: bool):
        """The stage loop with durability: after each completed stage unit
        (ops + inter-stage remap) the host state is snapshotted (fsync'd
        tmp+rename) and the :class:`repro.train.fault_tolerance.RunJournal`
        records the stage index; per-stage wall times feed a
        :class:`StragglerMonitor`. On entry, a journal whose run signature
        matches resumes from the last completed stage. A completed run
        clears its checkpoint so stale state can never leak into a later
        run."""
        from ..train.fault_tolerance import RunJournal, StragglerMonitor

        cc = self.engine.cc
        os.makedirs(self.checkpoint_dir, exist_ok=True)
        sig = self._run_sig(state)
        jpath = os.path.join(self.checkpoint_dir, "journal.json")
        spath = os.path.join(self.checkpoint_dir, "state.npy")
        journal = RunJournal(jpath)
        rec = journal.read()
        start = 0
        if (rec.get("run_sig") == sig and rec.get("last_step", -1) >= 0
                and os.path.exists(spath)):
            state = np.load(spath).astype(self.engine.np_dtype, copy=True)
            start = int(rec["last_step"]) + 1
            journal.mark_restart()
            self.stats["resumed_stages"] = start
        monitor = StragglerMonitor()
        for i, prog in enumerate(cc.programs):
            if i < start:
                continue
            if i == 0 and cc.initial_remap is not None:
                state = self._remap(state, "init", cc.initial_remap)
            t0 = time.monotonic()
            state = self._stream_stage(state, prog)
            if prog.remap_after is not None:
                state = self._remap(state, i, prog.remap_after)
            if monitor.record(i, time.monotonic() - t0):
                self.stats["straggler_stages"] += 1
            self._save_state(spath, state)
            journal.update(i, run_sig=sig)
            self.stats["checkpointed_stages"] += 1
        if apply_final and cc.final_remap is not None:
            state = self._remap(state, "final", cc.final_remap)
        for p in (jpath, spath):  # completed: drop the checkpoint
            if os.path.exists(p):
                os.remove(p)
        return state

    def supports_fused_sweep(self) -> bool:
        return True

    def execute_sweep(self, state, consts_b, apply_final: bool = True):
        """Fused sweep: tile the initial state into a [P, 2^n] host batch and
        stream each shard-block ONCE through a shard function whose gate
        tensors carry the binding axis — one host<->device pass covers all P
        parameter points."""
        P_ = next(iter(consts_b.values())).shape[0] if consts_b else 1
        if isinstance(state, ShardStore):
            states = state.tile(P_)
            state.close()
        else:
            states = np.repeat(np.asarray(state).reshape(1, -1), P_, axis=0)
        self._sweep_consts = consts_b
        self._sweep_slices = {}
        try:
            if isinstance(states, ShardStore):
                return self._execute_store(states, apply_final)
            return self.engine.stage_loop(states, self._stream_stage,
                                          self._remap, apply_final)
        finally:
            self._sweep_consts = None
            self._sweep_slices = {}

    def extract(self, out, batch: bool = False):
        return out  # already flat [2^n] / [B, 2^n]


class DenseBackend(Backend):
    """Per-gate dense oracle behind the same engine API.

    Deliberately a *different algorithm*: it ignores the compiled stage
    programs entirely and applies the raw gate list (of the *currently bound*
    circuit) to the dense state, so an engine-vs-dense comparison
    cross-checks the whole compile + bind + execute pipeline.
    ``run_packed`` re-stores the logical state in the compiled frame's
    physical order, making it bit-comparable to the planned backends.
    """

    name = "dense"

    def prepare(self, psi0, batch: bool = False):
        eng = self.engine
        if batch:
            return np.asarray(psi0, dtype=eng.np_dtype).reshape(-1, 1 << eng.n)
        if psi0 is None:
            state = np.zeros(1 << eng.n, dtype=eng.np_dtype)
            state[0] = 1.0
            return state
        return np.asarray(psi0, dtype=eng.np_dtype).reshape(-1)

    def execute(self, state, apply_final: bool = True):
        from .statevector import simulate

        psi = np.asarray(simulate(self.engine.bound_circuit, psi0=state,
                                  dtype=self.engine.dtype))
        if not apply_final:
            frame = self.engine.measurement_frame
            idx = frame.phys_to_logical(np.arange(psi.size, dtype=np.int64))
            psi = psi[idx]
        return psi

    def extract(self, out, batch: bool = False):
        return out


BACKENDS: Dict[str, Callable[..., Backend]] = {
    "pjit": PjitBackend,
    "shardmap": ShardMapBackend,
    "offload": HostOffloadBackend,
    "dense": DenseBackend,
}


# ======================================================================
# The engine
# ======================================================================


class ExecutionEngine:
    """Backend-agnostic staged executor: one stage loop, one constant
    registry, one public API — the backend only supplies the substrate."""

    @trace.span("build")
    def __init__(
        self,
        circuit: Circuit,
        plan: SimulationPlan,
        backend: Union[str, Backend] = "pjit",
        dtype=jnp.complex64,
        use_pallas: bool = False,
        peephole: bool = True,
        compiled: Optional[CompiledCircuit] = None,
        **backend_kw,
    ):
        self.circuit = circuit  # structural reference; may carry free Params
        self.plan = plan
        # serving-path mutual exclusion: ``bind``/``run*`` mutate shared
        # engine state (the constant registry, ``bound_circuit``); concurrent
        # callers (the serve worker pool, ``engine_for`` rebinds) hold this
        # around any bind+execute sequence. Single-threaded use never blocks.
        self.lock = threading.RLock()
        self.dtype = dtype
        self.np_dtype = np.dtype(dtype)
        self.use_pallas = use_pallas
        self.peephole = peephole
        # degradation provenance: :func:`build_engine` records every ladder
        # downgrade here; the integrity guard counts its retries here too.
        # Surfaced by the serving stats / bench JSON so silent degradation
        # is impossible.
        self.provenance: Dict = {"degraded": False}
        if use_pallas and faults._ACTIVE is not None:
            faults.maybe_inject("pallas_lowering_error", site="engine.init")
        self.cc: CompiledCircuit = (
            compiled if compiled is not None
            else compile_plan(circuit, plan, dtype=self.np_dtype, peephole=peephole)
        )
        self.n, self.L, self.R, self.G = self.cc.n, self.cc.L, self.cc.R, self.cc.G
        # parameter-binding state: a symbolic circuit compiles to a reusable
        # structural program with placeholder tensors and must be bound
        # before running; a concrete circuit IS its own first binding.
        self.bound_circuit: Optional[Circuit] = (
            circuit if circuit.is_bound else None
        )
        self.bind_count = 0
        self.xla_compiles = 0  # traces of backend executables (rebinding
        # must never increment this after warmup)
        # host-time aggregates (count/total/max, seconds) of the spans of
        # every run* and offload stage, by span path (``engine.run/execute``),
        # fed only by :func:`repro.sim.trace.span`. Host time to dispatch:
        # the device may still be running when a span closes. Surfaced by
        # timing_snapshot() -> the serve stats' ``engine_timings``.
        self.timings: trace.Table = {}
        self._struct_cache: Dict = {}  # binding-independent build artifacts
        # shared by every bind_tensors pass (see compile_plan struct_cache)
        # op-tensor registry, keyed by stable ``Op.uid``: one device array per
        # tensor, passed to the jitted stage loops as an INPUT pytree (never a
        # baked-in constant) so one XLA executable serves every binding.
        # Built eagerly — inside a jit trace the dtype cast would leak tracers.
        self.consts: Dict[int, jnp.ndarray] = {}
        with trace.span("consts"):
            for prog in self.cc.programs:
                for op in prog.ops:
                    for o in (op,) + op.gates:
                        if o.tensor.size:
                            self.consts[o.uid] = jnp.asarray(o.tensor, dtype=self.dtype)
        if isinstance(backend, str):
            backend = BACKENDS[backend](**backend_kw)
        elif backend_kw:
            raise TypeError("backend_kw only apply when backend is given by name")
        self.backend = backend
        with trace.span("backend"):
            backend.setup(self)
        self.provenance["backend"] = backend.name
        self.provenance["use_pallas"] = use_pallas

    # --------------------------------------------------------- parameters
    @property
    def param_names(self) -> Tuple[str, ...]:
        return self.circuit.param_names

    def bind(self, params) -> "ExecutionEngine":
        """Bind the engine's circuit parameters (dict or flat vector ordered
        by :attr:`param_names`) and swap the materialized op tensors into the
        constant registry. Pure numpy + H2D: NO ILP/DP solves, NO new XLA
        compiles — the executables take the tensors as inputs. Returns self."""
        return self.bind_circuit(self.circuit.bind(params))

    def bind_circuit(self, bound: Circuit) -> "ExecutionEngine":
        """Install a fully-bound same-structure circuit as the current
        binding (the serving cache calls this when a request's structure hits
        but its angles differ)."""
        if bound.structure_fingerprint() != self.circuit.structure_fingerprint():
            raise ValueError("bind_circuit: circuit structure does not match "
                             "this engine's compiled structure")
        with self.lock:
            table = bind_tensors(bound, self.plan, dtype=self.np_dtype,
                                 peephole=self.peephole, expect=self.cc,
                                 struct_cache=self._struct_cache)
            self.consts = {uid: jnp.asarray(t, dtype=self.dtype)
                           for uid, t in table.items()}
            self.bound_circuit = bound
            self.bind_count += 1
            self.backend.on_rebind()
        return self

    def _require_bound(self) -> None:
        if self.bound_circuit is None:
            raise UnboundParameterError(
                f"engine has unbound parameters {self.param_names}; call "
                "bind(params) (or run_sweep) before executing"
            )

    def _sweep_points(self, params_batch) -> List[dict]:
        names = self.param_names
        if isinstance(params_batch, (list, tuple)) and params_batch and \
                isinstance(params_batch[0], dict):
            return list(params_batch)
        arr = np.asarray(params_batch, dtype=np.float64)
        if arr.ndim == 1:
            arr = arr[:, None]
        if arr.shape[1] != len(names):
            raise ValueError(
                f"params_batch has {arr.shape[1]} columns; circuit has "
                f"{len(names)} parameters {names}"
            )
        return [dict(zip(names, row)) for row in arr]

    # --------------------------------------------------------------- timing
    def timing_snapshot(self) -> Dict[str, Dict[str, float]]:
        """JSON-able copy of the engine's span aggregates (count, total,
        max and mean host seconds, by span path), which the serve stats
        embed. Host time to dispatch: the device may still be running when
        a run's span closes."""
        return trace.snapshot(self.timings)

    # ------------------------------------------------------------- shared
    @property
    def n_nonlocal(self) -> int:
        return self.R + self.G

    def stage_loop(self, x, ops_fn, remap_fn, apply_final: bool = True):
        """THE stage loop — every backend (traced or eager) runs this.

        ``ops_fn(x, prog)`` applies one stage's op list; ``remap_fn(x, slot,
        spec)`` applies one inter-stage remap, where ``slot`` is ``"init"``,
        the stage index, or ``"final"`` (backends with precomputed remap
        artifacts index them by slot; others use ``spec`` directly).
        """
        cc = self.cc
        if cc.initial_remap is not None:
            x = remap_fn(x, "init", cc.initial_remap)
        for i, prog in enumerate(cc.programs):
            x = ops_fn(x, prog)
            if prog.remap_after is not None:
                x = remap_fn(x, i, prog.remap_after)
        if apply_final and cc.final_remap is not None:
            x = remap_fn(x, "final", cc.final_remap)
        return x

    # --------------------------------------------------- integrity guard
    def dense_reference(self, bound: Optional[Circuit] = None, psi0=None,
                        apply_final: bool = True) -> np.ndarray:
        """Per-gate dense oracle state for ``bound`` (defaults to the
        current binding) — the integrity guard's one-retry path. With
        ``apply_final=False`` the result is re-stored in the compiled
        frame's physical order (comparable to ``run_packed`` output)."""
        from .statevector import simulate

        bound = self.bound_circuit if bound is None else bound
        psi = np.asarray(simulate(bound, psi0=psi0, dtype=self.dtype)).reshape(-1)
        if not apply_final:
            frame = self.measurement_frame
            idx = frame.phys_to_logical(np.arange(psi.size, dtype=np.int64))
            psi = psi[idx]
        return psi

    @staticmethod
    def _norm_ok(arr: np.ndarray, expected: float, rtol: float = 1e-2) -> bool:
        if not np.all(np.isfinite(arr)):
            return False
        return abs(float(np.linalg.norm(arr)) - expected) <= rtol * max(expected, 1e-30)

    @staticmethod
    def _expected_norm(psi0) -> float:
        if psi0 is None:
            return 1.0
        return float(np.linalg.norm(np.asarray(psi0).reshape(-1)))

    def _guard(self, out, psi0, apply_final: bool = True,
               bound: Optional[Circuit] = None):
        """Post-run ||psi|| =~ 1 check: unitary evolution preserves the
        input norm, so a NaN/denormal blowup is detectable in one cheap
        pass. On failure, retry ONCE against the dense per-gate oracle; if
        even that is poisoned, raise a typed :class:`IntegrityError`."""
        arr = np.asarray(out).reshape(-1)
        expected = self._expected_norm(psi0)
        if self._norm_ok(arr, expected):
            return out
        self.provenance["integrity_retries"] = (
            self.provenance.get("integrity_retries", 0) + 1)
        ref = self.dense_reference(bound=bound, psi0=psi0,
                                   apply_final=apply_final)
        if not self._norm_ok(ref, expected):
            raise IntegrityError(
                f"state norm {float(np.linalg.norm(arr)):.6g} != "
                f"{expected:.6g} and the dense-oracle retry is also "
                f"poisoned — numerically corrupt circuit/binding")
        self.provenance["integrity_recovered"] = (
            self.provenance.get("integrity_recovered", 0) + 1)
        return ref

    @staticmethod
    def _poison(out) -> np.ndarray:
        arr = np.array(np.asarray(out), copy=True)
        arr.reshape(-1)[0] = np.nan
        return arr

    # ---------------------------------------------------------------- api
    def run(self, psi0=None, params=None, *, verify: bool = False):
        """psi0: flat [2^n] in logical order (defaults to |0..0>). Returns
        the final flat state in logical order. ``params`` (optional) rebinds
        the circuit parameters first — a tensor swap, never a recompile.
        ``verify`` turns on the post-run norm integrity guard (NaN blowups
        become one dense-oracle retry, then a typed IntegrityError)."""
        with self.lock:
            if params is not None:
                self.bind(params)
            self._require_bound()
            if faults._ACTIVE is not None:
                faults.maybe_inject("slow_stage", site="engine.run")
            with trace.span("engine.run", self.timings):
                with trace.span("prepare", self.timings):
                    state = self.backend.prepare(psi0)
                with trace.span("execute", self.timings):
                    out = self.backend.execute(state, True)
                with trace.span("extract", self.timings):
                    out = self.backend.extract(out)
        if faults._ACTIVE is not None and faults.should_corrupt("engine.run"):
            out = self._poison(out)
        if verify:
            out = self._guard(out, psi0, apply_final=True)
        return out

    def run_packed(self, psi0=None, params=None, *, verify: bool = False):
        """Run but *skip the final inter-stage remap*: returns the state in
        the last stage's physical layout (with lazy flips still pending).
        Pair with :attr:`measurement_frame` and :mod:`repro.sim.measure` —
        sampling/marginals/expectations undo the layout on indices, which is
        far cheaper than permuting 2^n amplitudes."""
        with self.lock:
            if params is not None:
                self.bind(params)
            self._require_bound()
            if faults._ACTIVE is not None:
                faults.maybe_inject("slow_stage", site="engine.run")
            with trace.span("engine.run_packed", self.timings):
                out = self.backend.execute(self.backend.prepare(psi0), False)
        if faults._ACTIVE is not None and faults.should_corrupt("engine.run"):
            out = self._poison(out)
        if verify:
            out = self._guard(out, psi0, apply_final=False)
        return out

    def run_batch(self, psi0s, apply_final: bool = True):
        """Run a batch of initial states ``psi0s: [B, 2^n]`` through the
        shard program. Returns ``[B, 2^n]`` in logical order, or the batched
        packed layout when ``apply_final=False`` (measure each element via
        :func:`repro.sim.measure.measure_batch`)."""
        with self.lock:
            self._require_bound()
            with trace.span("engine.run_batch", self.timings):
                states = self.backend.prepare(psi0s, batch=True)
                out = self.backend.execute_batch(states, apply_final)
                out = self.backend.extract(out, batch=True) if apply_final else out
        return out

    def run_sweep(self, psi0, params_batch, apply_final: bool = True,
                  *, verify: bool = False):
        """Run ONE initial state against a batch of parameter bindings.

        ``params_batch``: a ``[P, n_params]`` array (columns ordered by
        :attr:`param_names`) or a list of ``{name: value}`` dicts. Tensor
        tables for all P points are materialized host-side (pure numpy — the
        structural plan is reused, zero ILP/DP solves) and the backend runs
        its cheapest fused path: the pjit backend vmaps the whole stage loop
        over the binding axis, the offload backend streams ``[P, 2^L]``
        blocks so one host<->device pass covers the sweep, other backends
        fall back to sequential rebinding against their already-compiled
        executables (still zero new XLA compiles). Returns ``[P, 2^n]`` in
        logical order (or the packed batch when ``apply_final=False``)."""
        points = self._sweep_points(params_batch)
        if not points:
            raise ValueError("empty params_batch")
        # the fused path parks per-sweep tensor tables on the backend
        # (``_sweep_consts``/``_sweep_slices``): without the lock two
        # concurrent sweeps interleave on that shared state and one of them
        # silently reads the other's (or the placeholder) tensors
        with trace.span("engine.run_sweep", self.timings), self.lock:
            if self.backend.supports_fused_sweep():
                if faults._ACTIVE is not None:
                    faults.maybe_inject("slow_stage", site="engine.run_sweep")
                tables_b = bind_tensors_sweep(
                    [self.circuit.bind(pt) for pt in points], self.plan,
                    dtype=self.np_dtype, peephole=self.peephole,
                    expect=self.cc, struct_cache=self._struct_cache)
                batched = {
                    uid: jnp.asarray(t, dtype=self.dtype)
                    for uid, t in tables_b.items()
                }
                state = self.backend.prepare(psi0)
                out = self.backend.execute_sweep(state, batched, apply_final)
                out = self.backend.extract(out, batch=True) if apply_final else out
            else:
                outs = []
                for pt in points:
                    self.bind(pt)
                    o = self.run(psi0) if apply_final else self.run_packed(psi0)
                    outs.append(np.asarray(o).reshape(-1) if apply_final else o)
                if apply_final or isinstance(outs[0], np.ndarray):
                    out = np.stack(outs)
                else:
                    out = jnp.stack(outs)
        if faults._ACTIVE is not None and faults.should_corrupt("engine.run_sweep"):
            out = self._poison_row(out, len(points))
        if verify:
            out = self._guard_sweep(out, psi0, points, apply_final)
        return out

    def _poison_row(self, out, n_rows: int) -> np.ndarray:
        arr = np.array(np.asarray(out), copy=True)
        plan = faults._ACTIVE
        row = plan._rng.randrange(n_rows) if plan is not None else 0
        arr.reshape(arr.shape[0], -1)[row, 0] = np.nan
        return arr

    def _guard_sweep(self, out, psi0, points, apply_final: bool):
        """Per-row norm guard for a sweep: only poisoned rows pay the
        dense-oracle retry; a row whose oracle is also poisoned raises."""
        arr = np.asarray(out)
        flat = arr.reshape(arr.shape[0], -1)
        expected = self._expected_norm(psi0)
        bad = [i for i in range(len(points))
               if not self._norm_ok(flat[i], expected)]
        if not bad:
            return out
        arr = np.array(arr, copy=True)
        self.provenance["integrity_retries"] = (
            self.provenance.get("integrity_retries", 0) + len(bad))
        for i in bad:
            ref = self.dense_reference(bound=self.circuit.bind(points[i]),
                                       psi0=psi0, apply_final=apply_final)
            if not self._norm_ok(ref, expected):
                raise IntegrityError(
                    f"sweep row {i}: norm check failed and the dense-oracle "
                    f"retry is also poisoned")
            arr.reshape(arr.shape[0], -1)[i] = ref
        self.provenance["integrity_recovered"] = (
            self.provenance.get("integrity_recovered", 0) + len(bad))
        return arr

    # ---------------------------------------------------- adjoint gradients
    def adjoint_program(self, observable):
        """The cached :class:`repro.sim.adjoint.AdjointProgram` for this
        engine's structure and ``observable`` — one jitted reverse-sweep
        executable per (structure, observable, dtype), reused by every
        binding (its traces count into :attr:`xla_compiles`)."""
        from .adjoint import AdjointProgram
        from .measure import PauliSum

        key = str(PauliSum.coerce(observable))
        progs = self.__dict__.setdefault("_adjoint_progs", {})
        prog = progs.get(key)
        if prog is None:
            def _count():
                self.xla_compiles += 1

            prog = AdjointProgram(self.circuit, observable, dtype=self.dtype,
                                  trace_counter=_count)
            progs[key] = prog
        return prog

    def value_and_grad(self, observable, params=None, psi0=None):
        """``(E, ∂E/∂θ)`` for ``E = <ψ(θ)|H|ψ(θ)>`` by adjoint
        differentiation: the backend's cached forward executable produces
        |ψ⟩, then ONE jitted reverse sweep (inverse gates as inputs, see
        :mod:`repro.sim.adjoint`) yields every parameter's gradient — 3
        state passes total, independent of P. ``params`` (optional) rebinds
        first; gradients are ordered by :attr:`param_names`. Zero ILP/DP
        solves, zero retraces after the first call per structure."""
        if params is not None:
            self.bind(params)
        self._require_bound()
        # the forward state feeds the jitted sweep directly — a jnp result
        # stays on device (no 2^n D2H+H2D round trip per VQE iteration)
        psi = self.run(psi0).reshape(-1)
        prog = self.adjoint_program(observable)
        value, grads = prog.value_and_grad(psi, self.bound_circuit)
        return float(value), np.asarray(grads, dtype=np.float64)

    def grad_sweep(self, params_batch, observable, psi0=None):
        """``value_and_grad`` over a batch of bindings: ``(values [P],
        grads [P, n_params])``. Forward states run through
        :meth:`run_sweep`'s cheapest path; when the backend reports
        ``supports_fused_grad`` the reverse sweeps vmap over the binding
        axis (one executable for the whole batch), otherwise they run
        sequentially against the same single-point executable (zero
        retraces either way)."""
        points = self._sweep_points(params_batch)
        if not points:
            raise ValueError("empty params_batch")
        prog = self.adjoint_program(observable)
        states = self.run_sweep(psi0, points).reshape(len(points), -1)
        bounds = [self.circuit.bind(pt) for pt in points]
        if self.backend.supports_fused_grad():
            inv, d = prog.stacked_tensors(bounds)
            values, grads = prog.vmapped()(states, inv, d)
            return (np.asarray(values, dtype=np.float64),
                    np.asarray(grads, dtype=np.float64))
        vals, gs = [], []
        for psi, bound in zip(states, bounds):
            v, g = prog.value_and_grad(psi, bound)
            vals.append(float(v))
            gs.append(np.asarray(g, dtype=np.float64))
        return np.asarray(vals), np.stack(gs)

    @property
    def measurement_frame(self):
        from .measure import Frame

        return Frame.from_compiled(self.cc)

    def __getattr__(self, name: str):
        # backend-specific surface (mesh, sharding, stats, lower, ...)
        if name.startswith("_"):
            raise AttributeError(name)
        backend = self.__dict__.get("backend")
        if backend is None:
            raise AttributeError(name)
        return getattr(backend, name)


# ======================================================================
# Compile cache (serving: compile once, run many)
# ======================================================================


def _canon(v):
    """Canonicalize a cache-key component into a stable, reprable value."""
    if isinstance(v, (np.floating, np.integer)):
        return v.item()
    if isinstance(v, (tuple, list)):
        return tuple(_canon(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _canon(x)) for k, x in v.items()))
    return v


def _resolve_cost_model(cm: Optional[CostModel]) -> CostModel:
    """``cost_model=None`` (the serving default) means "whatever this device
    is calibrated to": the profiler's memoized resolution — the measured
    model when a fingerprint-matching calibration file exists, the analytic
    defaults otherwise. Explicit models pass through untouched."""
    if cm is not None:
        return cm
    from . import profiler

    return profiler.resolve_cost_model()


def _placement_fingerprint(backend_kw: Optional[dict]) -> Tuple:
    """Stable fingerprint of backend placement kwargs (mesh, devices, ...):
    two requests whose placements differ must NOT share a cached engine."""
    if not backend_kw:
        return ()
    out = []
    for k in sorted(backend_kw):
        v = backend_kw[k]
        if isinstance(v, Mesh):
            v = (tuple(v.shape.items()),
                 tuple(d.id for d in np.asarray(v.devices).flat))
        elif isinstance(v, (list, tuple)) and v and hasattr(v[0], "id"):
            v = tuple(d.id for d in v)  # a device list
        elif isinstance(v, StorageConfig):
            v = v.fingerprint()  # compressed vs exact plans never collide
        else:
            v = _canon(v)
        out.append((k, v))
    return tuple(out)


@dataclass(frozen=True)
class CircuitKey:
    """Stable fingerprint of (circuit STRUCTURE, architecture split, plan/
    compile knobs): equal keys => the same structural plan and the same XLA
    executables are valid.

    Deliberately parameter-blind: the whole pipeline (ILP staging, DP
    kernelization, stage compilation, jitted stage loops with tensors as
    inputs) depends only on circuit structure, so two circuits that differ
    only in rotation angles share one cached engine — the serving path
    rebinds tensors instead of recompiling (see :func:`engine_for`)."""

    digest: str

    @staticmethod
    def make(
        circuit: Circuit,
        L: int,
        R: int = 0,
        G: int = 0,
        *,
        backend: str = "pjit",
        dtype=jnp.complex64,
        use_pallas: bool = False,
        peephole: bool = True,
        staging_method: str = "ilp",
        kernelize_method: str = "dp",
        cost_model: Optional[CostModel] = None,
        optimize=False,
        extra=(),
    ) -> "CircuitKey":
        cost_model = _resolve_cost_model(cost_model)
        cm = tuple(
            (f.name, _canon(getattr(cost_model, f.name)))
            for f in _dc_fields(cost_model)
        )
        # the optimizer's pass-list fingerprint is its own key component:
        # an optimized plan and the literal plan for the same structure must
        # NEVER collide in the compile cache (their stage programs differ)
        ofp = copt.optimize_fingerprint(optimize)
        payload = (
            circuit.structure_fingerprint(), (L, R, G), str(backend),
            str(np.dtype(dtype)), bool(use_pallas), bool(peephole),
            staging_method, kernelize_method, cm, ofp, _canon(extra),
        )
        return CircuitKey(hashlib.sha256(repr(payload).encode()).hexdigest())


class CompileCache:
    """LRU of :class:`CircuitKey` -> compiled :class:`ExecutionEngine`.

    A cached engine keeps its plan, compiled stage programs, hoisted device
    constants AND jitted executables warm, so a serving-style repeat of the
    same circuit skips ILP staging, DP kernelization, stage compilation and
    XLA compilation entirely.

    Thread-safe: every LRU mutation happens under an internal lock (the
    serving worker pool and ``engine_for`` hit one shared instance
    concurrently). With ``evict_scan > 1`` eviction is frequency-aware: the
    victim is the least-*hit* entry among the ``evict_scan`` oldest, so a
    burst of one-off structures cannot flush a hot warm-pool entry that
    merely hasn't been touched in the last few requests (the serving
    :class:`repro.serve.service.WarmPool` opts in; the default is plain
    LRU). Per-key hit counts persist across eviction/re-admission and feed
    :meth:`stats`.
    """

    def __init__(self, maxsize: int = 32, evict_scan: int = 1):
        self.maxsize = maxsize
        self.evict_scan = max(1, evict_scan)
        self._d: "OrderedDict[CircuitKey, ExecutionEngine]" = OrderedDict()
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.key_hits: Dict[str, int] = {}  # digest -> lifetime hit count

    def get(self, key: CircuitKey) -> Optional[ExecutionEngine]:
        with self._lock:
            eng = self._d.get(key)
            if eng is None:
                self.misses += 1
                return None
            self.hits += 1
            self.key_hits[key.digest] = self.key_hits.get(key.digest, 0) + 1
            self._d.move_to_end(key)
            return eng

    def peek(self, key: CircuitKey) -> Optional[ExecutionEngine]:
        """Counter-neutral lookup — the double-checked inner probe of
        ``engine_for`` (the outer :meth:`get` already recorded the event, so
        a second probe must not inflate the miss count)."""
        with self._lock:
            return self._d.get(key)

    def put(self, key: CircuitKey, engine: ExecutionEngine) -> None:
        with self._lock:
            self._d[key] = engine
            self._d.move_to_end(key)
            self.key_hits.setdefault(key.digest, 0)
            while len(self._d) > self.maxsize:
                # victim = coldest (fewest lifetime hits) of the evict_scan
                # least-recently-used entries; the just-inserted key sits at
                # the MRU end and is never scanned
                tail = list(self._d.keys())[
                    : min(self.evict_scan, len(self._d) - 1)]
                victim = min(tail, key=lambda k: self.key_hits.get(k.digest, 0))
                del self._d[victim]
                self.evictions += 1

    def clear(self) -> None:
        with self._lock:
            self._d.clear()
            self.hits = self.misses = self.evictions = 0
            self.key_hits.clear()

    def stats(self) -> Dict:
        """JSON-able counter snapshot (the serving loop and ``bench_serve``
        both read this): size, hit/miss/eviction totals and per-key hit
        counts keyed by truncated digest."""
        with self._lock:
            return {
                "size": len(self._d),
                "maxsize": self.maxsize,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "key_hits": {d[:12]: c for d, c in self.key_hits.items()},
            }

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)

    def __contains__(self, key: CircuitKey) -> bool:
        with self._lock:
            return key in self._d


DEFAULT_CACHE = CompileCache()

_BUILD_LOCKS: Dict[Tuple[int, str], threading.Lock] = {}
_BUILD_LOCKS_GUARD = threading.Lock()


def _build_lock(cache: CompileCache, key: CircuitKey) -> threading.Lock:
    """Per-(cache, key) build lock: two threads missing on the same key must
    not both pay ILP+DP+XLA — the second waits and takes the cache hit."""
    with _BUILD_LOCKS_GUARD:
        if len(_BUILD_LOCKS) > 4096:  # bounded: locks are tiny but not free
            _BUILD_LOCKS.clear()
        return _BUILD_LOCKS.setdefault((id(cache), key.digest), threading.Lock())


def circuit_key_for(
    circuit: Circuit,
    L: int,
    R: int = 0,
    G: int = 0,
    *,
    backend: str = "pjit",
    dtype=jnp.complex64,
    use_pallas: bool = False,
    peephole: bool = True,
    staging_method: str = "ilp",
    kernelize_method: str = "dp",
    cost_model: Optional[CostModel] = None,
    optimize=False,
    backend_kw: Optional[dict] = None,
    storage=None,
    _pre_optimized: bool = False,
    **plan_kw,
) -> CircuitKey:
    """The exact :class:`CircuitKey` :func:`engine_for` would use for these
    arguments — exposed so warm-pool admission policies (``repro.serve``) can
    reason about a request's cache key without building anything.

    With ``optimize`` on, the key is computed over the OPTIMIZED circuit's
    structure (plus the optimizer fingerprint): concrete circuits with the
    same literal structure but different angles can optimize to different
    structures (value-dependent identity drops), and each optimized
    structure must own its own engine. ``_pre_optimized=True`` tells this
    function that ``circuit`` already IS the optimizer output
    (:func:`engine_for` uses this to avoid optimizing twice).

    ``storage`` (a :class:`repro.sim.shard_store.StorageConfig`, spec
    string or dict) folds the at-rest storage fingerprint into the key via
    ``backend_kw`` — a compressed-tier plan and an exact plan for the same
    structure must never share a cached engine."""
    storage = StorageConfig.coerce(storage)
    if storage is not None:
        backend_kw = dict(backend_kw or {}, storage=storage)
    ocfg = copt.resolve_config(optimize)
    if ocfg is not None and not _pre_optimized:
        circuit = copt.optimize_circuit(circuit, ocfg).circuit
    return CircuitKey.make(
        circuit, L, R, G, backend=backend, dtype=dtype, use_pallas=use_pallas,
        peephole=peephole, staging_method=staging_method,
        kernelize_method=kernelize_method, cost_model=cost_model,
        optimize=ocfg,
        extra=(tuple(sorted((k, _canon(v)) for k, v in plan_kw.items())),
               _placement_fingerprint(backend_kw)),
    )


# ======================================================================
# Graceful degradation ladder
# ======================================================================

#: Backend fallback chain: construction failure walks down until the dense
#: per-gate oracle, which cannot fail to build.
BACKEND_CHAIN: Dict[str, Tuple[str, ...]] = {
    "shardmap": ("pjit", "dense"),
    "pjit": ("dense",),
    "offload": ("dense",),
    "dense": (),
}


def _record_fallback(prov: Dict, from_: str, to: str, err: Exception) -> None:
    prov["degraded"] = True
    prov.setdefault("fallbacks", []).append({
        "from": from_, "to": to,
        "error": f"{type(err).__name__}: {err}",
    })


def _plan_resilient(circuit, L, R, G, *, staging_method, kernelize_method,
                    cost_model, provenance, **plan_kw):
    """Partition with the planning rungs of the ladder: a typed
    :class:`StagingError` retries with ``stage_greedy``, a typed
    :class:`KernelizationError` retries with greedy packing. Returns
    ``(plan, staging_method, kernelize_method)`` actually used."""
    sm, km = staging_method, kernelize_method
    while True:
        try:
            plan = partition(circuit, L, R, G, staging_method=sm,
                             kernelize_method=km, cost_model=cost_model,
                             **plan_kw)
            return plan, sm, km
        except StagingError as e:
            if sm == "greedy":
                raise
            _record_fallback(provenance, f"staging:{sm}", "staging:greedy", e)
            sm = "greedy"
        except KernelizationError as e:
            if km == "greedy":
                raise
            _record_fallback(provenance, f"kernelize:{km}",
                             "kernelize:greedy", e)
            km = "greedy"


@trace.span("build")
def build_engine(
    circuit: Circuit,
    plan: SimulationPlan,
    *,
    backend: str = "pjit",
    dtype=jnp.complex64,
    use_pallas: bool = False,
    peephole: bool = True,
    backend_kw: Optional[dict] = None,
    degrade: bool = True,
    provenance: Optional[Dict] = None,
) -> ExecutionEngine:
    """Construct an :class:`ExecutionEngine`, walking the graceful-
    degradation ladder on *typed* construction failures:

    1. a transient ``compile_plan`` failure gets ONE retry (then the typed
       error propagates — persistent structural poison must not loop);
    2. a :class:`PallasLoweringError` retries the same backend with
       ``use_pallas=False``;
    3. a :class:`BackendBuildError` (mesh/device mismatch, trace failure)
       falls down :data:`BACKEND_CHAIN` to the dense per-gate oracle.

    Every downgrade lands in ``engine.provenance`` (``degraded``,
    ``fallbacks``, ``requested_backend``). With ``degrade=False`` the first
    typed error propagates unchanged."""
    prov: Dict = provenance if provenance is not None else {}
    cc = None
    compile_err: Optional[FaultError] = None
    for attempt in range(2 if degrade else 1):
        try:
            cc = compile_plan(circuit, plan, dtype=np.dtype(dtype),
                              peephole=peephole)
            if attempt:
                _record_fallback(prov, "compile", "compile(retry)", compile_err)
            break
        except FaultError as e:
            compile_err = e
    if cc is None:
        raise compile_err

    attempts: List[Tuple[str, bool, dict]] = [(backend, use_pallas,
                                               backend_kw or {})]
    if degrade:
        if use_pallas:
            attempts.append((backend, False, backend_kw or {}))
        for nb in BACKEND_CHAIN.get(backend, ()):
            # degraded rungs drop placement kwargs: a mesh built for the
            # requested backend has no meaning one rung down
            attempts.append((nb, False, {}))
    last: Optional[Exception] = None
    for bk, pl, kw in attempts:
        try:
            eng = ExecutionEngine(circuit, plan, backend=bk, dtype=dtype,
                                  use_pallas=pl, peephole=peephole,
                                  compiled=cc, **kw)
        except FaultError as e:
            last = e
            nxt = None
            for j, (b2, p2, _) in enumerate(attempts):
                if (b2, p2) == (bk, pl) and j + 1 < len(attempts):
                    nxt = attempts[j + 1]
                    break
            to = (f"{nxt[0]}{'+pallas' if nxt[1] else ''}"
                  if nxt else "<exhausted>")
            _record_fallback(prov, f"{bk}{'+pallas' if pl else ''}", to, e)
            continue
        if prov.get("degraded"):
            eng.provenance.update(prov)
            eng.provenance["requested_backend"] = backend
            eng.provenance["requested_use_pallas"] = use_pallas
        return eng
    raise last if last is not None else BackendBuildError("no backend attempts")


def engine_for(
    circuit: Circuit,
    L: int,
    R: int = 0,
    G: int = 0,
    *,
    backend: str = "pjit",
    dtype=jnp.complex64,
    use_pallas: bool = False,
    peephole: bool = True,
    staging_method: str = "ilp",
    kernelize_method: str = "dp",
    cost_model: Optional[CostModel] = None,
    optimize=False,
    cache: Optional[CompileCache] = DEFAULT_CACHE,
    plan: Optional[SimulationPlan] = None,
    backend_kw: Optional[dict] = None,
    storage=None,
    degrade: bool = True,
    **plan_kw,
) -> ExecutionEngine:
    """The serving entry point: partition + compile + build an engine, or
    return the cached engine for a structurally identical request.

    The key is **structural** — two requests whose circuits differ only in
    gate angles share one engine. On such a hit the cached engine is
    *rebound* to the request's parameters (``bind_circuit``: a host-numpy
    tensor materialization + H2D swap) — zero ILP/DP solves, zero new XLA
    compiles. Symbolic circuits are returned unbound; call ``bind``/
    ``run_sweep`` on the engine.

    ``optimize`` (bool, pass-name sequence, or
    :class:`repro.core.optimize.OptimizerConfig`) runs the pre-staging
    circuit optimizer first: planning, compilation, caching and execution
    all see the optimized circuit, and the key carries both the optimized
    structure and the pass-list fingerprint (optimized and literal plans
    never collide). Optimizing a symbolic circuit is binding-independent,
    so warm rebinds keep the zero-solve / zero-retrace contract; the
    rewrite provenance lands in ``engine.provenance["optimize"]``.

    Pass ``cache=None`` to force a fresh build; pass an explicit ``plan`` to
    bypass partitioning (such engines are NOT cached — the plan is outside
    the key; combining ``plan`` with ``optimize`` raises, the plan was made
    for the literal circuit). ``backend_kw`` (e.g. a pjit mesh) IS part of
    the key, via a placement fingerprint, so requests with different
    meshes/devices never share a cached engine.

    ``storage`` turns on the offload backend's tiered at-rest shard store
    (a :class:`repro.sim.shard_store.StorageConfig`, a spec string like
    ``"int8:dram_kib=64"``, or a dict; requires ``backend="offload"``).
    The ``REPRO_STORAGE`` env var supplies a default for offload engines
    that don't pass one (skipped when ``checkpoint_dir`` is in play — the
    store and stage checkpointing are mutually exclusive). The config
    reaches the backend via ``backend_kw`` (so it is part of the key and
    is dropped by the degradation ladder's dense fallback), and the cost
    model is re-priced for the tier the shards actually sit in:
    ``at_rest_bytes`` from the at-rest dtype, the ILP ``comm_weight``
    scaled by the spill-aware offload pass time.
    """
    storage = StorageConfig.coerce(storage)
    if storage is None and backend_kw:
        storage = StorageConfig.coerce(backend_kw.get("storage"))
    if (storage is None and backend == "offload"
            and not (backend_kw or {}).get("checkpoint_dir")):
        storage = StorageConfig.from_env()
    if storage is not None and backend != "offload":
        raise ValueError(
            f"storage= requires backend='offload' (got {backend!r}); the "
            "tiered shard store only exists under the host-offload path")
    base_cost_model = cost_model
    if storage is not None:
        backend_kw = dict(backend_kw or {}, storage=storage)
        cost_model = storage.apply_to_cost_model(
            _resolve_cost_model(cost_model), circuit.n_qubits, L)
    ocfg = copt.resolve_config(optimize)
    if plan is not None:
        if ocfg is not None:
            raise ValueError(
                "engine_for: optimize= cannot be combined with an explicit "
                "plan (the plan was computed for the literal circuit)")
        return build_engine(circuit, plan, backend=backend, dtype=dtype,
                            use_pallas=use_pallas, peephole=peephole,
                            backend_kw=backend_kw, degrade=degrade)
    source_circuit = circuit
    opt_result = None
    if ocfg is not None:
        opt_result = copt.optimize_circuit(circuit, ocfg)
        circuit = opt_result.circuit
    explicit_cm = base_cost_model is not None
    cost_model = _resolve_cost_model(cost_model)
    key = circuit_key_for(
        circuit, L, R, G, backend=backend, dtype=dtype, use_pallas=use_pallas,
        peephole=peephole, staging_method=staging_method,
        kernelize_method=kernelize_method, cost_model=cost_model,
        optimize=optimize, _pre_optimized=True,
        backend_kw=backend_kw, **plan_kw,
    )
    eng = cache.get(key) if cache is not None else None
    if eng is None:
        blk = _build_lock(cache, key) if cache is not None else threading.Lock()
        with blk:
            # double-checked: a concurrent builder may have landed it
            # (peek: the outer get already counted this request's miss)
            eng = cache.peek(key) if cache is not None else None
            if eng is None:
                prov: Dict = {}
                if degrade:
                    plan, _, _ = _plan_resilient(
                        circuit, L, R, G, staging_method=staging_method,
                        kernelize_method=kernelize_method,
                        cost_model=cost_model, provenance=prov, **plan_kw)
                else:
                    plan = partition(circuit, L, R, G,
                                     staging_method=staging_method,
                                     kernelize_method=kernelize_method,
                                     cost_model=cost_model, **plan_kw)
                eng = build_engine(circuit, plan, backend=backend,
                                   dtype=dtype, use_pallas=use_pallas,
                                   peephole=peephole, backend_kw=backend_kw,
                                   degrade=degrade, provenance=prov)
                if explicit_cm:
                    eng.provenance["calibration"] = {"source": "explicit"}
                else:
                    from . import profiler

                    eng.provenance["calibration"] = (
                        profiler.resolve_calibration()[1])
                if opt_result is not None:
                    # the engine serves the OPTIMIZED circuit; record the
                    # rewrite (and the config) so aliased hits — e.g. the
                    # autotuner installing this engine under the default
                    # key — can map literal requests through the same passes
                    eng.opt_config = ocfg
                    eng.provenance["optimize"] = dict(
                        opt_result.to_dict(),
                        passes=list(ocfg.passes),
                        source_fingerprint=(
                            source_circuit.structure_fingerprint()[:12]),
                    )
                if cache is not None:
                    cache.put(key, eng)
                return eng
    with eng.lock:
        same_structure = (eng.circuit.structure_fingerprint()
                          == circuit.structure_fingerprint())
        if not same_structure:
            # Structure mismatch on a key hit only happens through plan
            # aliasing: the autotuner may install an OPTIMIZED winner under
            # the default (literal) key. Map the request through the cached
            # engine's own optimizer config; same optimized structure =>
            # this is the engine's native circuit space and rebinding is
            # exactly as safe as for a native optimized request.
            ecfg = getattr(eng, "opt_config", None)
            if ecfg is not None:
                mapped = copt.optimize_circuit(source_circuit, ecfg).circuit
                if (mapped.structure_fingerprint()
                        == eng.circuit.structure_fingerprint()):
                    circuit = mapped
                    same_structure = True
        if same_structure:
            if circuit.is_bound and (
                eng.bound_circuit is None
                or eng.bound_circuit.binding_signature()
                != circuit.binding_signature()
            ):
                # structural hit with different angles: the dominant serving
                # pattern (same ansatz, new rotation parameters) — rebind,
                # don't recompile
                eng.bind_circuit(circuit)
            elif not circuit.is_bound and (
                eng.circuit.is_bound
                or eng.circuit.binding_signature() != circuit.binding_signature()
            ):
                # symbolic request hitting an engine whose skeleton is
                # concrete OR carries different Param names / affine
                # coefficients (the structural key is deliberately blind to
                # both): adopt the REQUESTED skeleton so the caller's
                # bind()/run_sweep names and scales resolve correctly; the
                # current binding is untouched. Adjoint programs wired to the
                # old skeleton's names/scales are stale — drop them.
                eng.circuit = circuit
                eng.__dict__.pop("_adjoint_progs", None)
    if not same_structure:
        # aliased engine in a different circuit space (e.g. the request's
        # angles optimize to a different structure than the cached winner's):
        # never rebind across structures — build fresh, un-cached
        return engine_for(
            source_circuit, L, R, G, backend=backend, dtype=dtype,
            use_pallas=use_pallas, peephole=peephole,
            staging_method=staging_method, kernelize_method=kernelize_method,
            cost_model=base_cost_model,
            optimize=optimize, cache=None, backend_kw=backend_kw,
            storage=storage, degrade=degrade, **plan_kw)
    return eng


# ======================================================================
# Trace-time record of the shard_map path's remaps (at the module's end: a
# Pallas call carries the source lines of the frames that traced it, so
# lines added above the pjit path or the stage loop change their programs)
# ======================================================================


def _remap_record_of(backend: ShardMapBackend, apply_final: bool) -> List[dict]:
    """A fresh record for one trace of ``backend``'s program, registered in
    :data:`repro.kernels.ops.REMAPS` under the program: a second trace of
    it replaces the first."""
    from ..kernels import ops as kops

    remaps = kops.REMAPS[(id(backend), apply_final)] = []
    return remaps


def _record_remap(backend: ShardMapBackend, slot) -> None:
    """Record the remap at ``slot`` of the program being traced."""
    from ..kernels import ops as kops

    eng, rp = backend.engine, backend._plans[slot]
    backend._remaps.append(kops.remap_record(slot, rp.m, eng.L, eng.dtype,
                                             rp.ppermute is not None))
