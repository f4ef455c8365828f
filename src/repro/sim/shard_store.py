"""Tiered at-rest shard store: compressed DRAM tier + disk spill tier.

Atlas's §VII-C offload path keeps the whole state resident in host DRAM as
uncompressed ``complex64`` shards, which caps the max simulable n at the
machine's DRAM. This module extends the storage hierarchy downward (the
hierarchical-partitioning-across-memory-tiers angle of the acyclic-graph
partitioning line of work):

* shards live **at rest** in one of three dtype tiers — ``exact``
  (complex64, lossless), ``bf16`` (real/imag parts as bfloat16, 2x
  smaller) or ``int8`` (per-block symmetric quantization reusing the
  :func:`repro.train.compression.quantize_int8` idiom, ~4x smaller);
* the DRAM tier has a configurable byte budget; least-recently-touched
  shards spill to a **disk tier** as atomic tmp+rename files keyed by a
  per-run tag (like the PR-7 stage checkpoints, a torn write can never be
  mistaken for a valid shard);
* every lossy encode's exact L2 roundtrip error is accumulated into a
  per-run **error bound**: all downstream stage ops and remaps are
  norm-preserving, so by the triangle inequality the final state deviates
  from the exact computation by at most the sum of per-encode errors. The
  bound is surfaced in ``engine.provenance["storage"]`` and the run is
  rejected with a typed :class:`repro.sim.faults.StorageToleranceError`
  when it exceeds the configured tolerance;
* :meth:`ShardStore.prefetch` overlaps the next shard's disk read +
  dequantize with the current shard's device compute, preserving the
  offload backend's double-buffered ``overlap_ratio``;
* :meth:`ShardStore.remap` performs the inter-stage bit permutation
  out-of-core: output shards are processed in groups that share the same
  input-shard subcube, so every input shard is decoded exactly once per
  remap and the transient working set is ``2^m + 1`` decoded shards (m =
  exchanged nonlocal bits), never the full state.

The store is deliberately engine-agnostic: it only needs the shard count,
shard length and a numpy dtype. ``HostOffloadBackend`` threads one
instance through its stage loop when ``engine_for(storage=...)`` is set.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import uuid
from collections import OrderedDict
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, replace
from typing import Dict, Optional, Tuple, Union

import numpy as np
from ml_dtypes import bfloat16 as _bf16

from . import faults

AT_REST_DTYPES = ("exact", "bf16", "int8")

#: at-rest bytes per complex amplitude for each tier (int8: 2 payload bytes
#: + per-block fp32 scales at _INT8_BLOCK granularity)
_INT8_BLOCK = 512
AT_REST_BYTES_PER_AMP = {
    "exact": 8.0,
    "bf16": 4.0,
    "int8": 2.0 + 2 * 4.0 / _INT8_BLOCK,
}

#: env knob: force a storage config on every ``engine_for(backend="offload")``
#: call that does not pass one explicitly (the CI spill smoke step sets a
#: tiny DRAM budget here so the spill path is always exercised).
STORAGE_ENV = "REPRO_STORAGE"


@dataclass(frozen=True)
class StorageConfig:
    """At-rest storage policy for the offload backend's shard state.

    ``at_rest_dtype``: ``exact`` | ``bf16`` | ``int8`` — precision of
    shards at rest (in DRAM and on disk). ``dram_bytes``: at-rest DRAM
    budget in bytes (``None`` = unbounded, disk tier never used).
    ``spill_dir``: root directory for spilled shard files (``None`` = the
    system temp dir). ``error_tolerance``: max accumulated L2 quantization
    error bound, relative to the initial state norm, before the run is
    rejected. ``prefetch``: overlap the next shard's load+dequantize with
    the current shard's device compute."""

    at_rest_dtype: str = "exact"
    dram_bytes: Optional[int] = None
    spill_dir: Optional[str] = None
    error_tolerance: float = 0.05
    prefetch: bool = True

    def __post_init__(self):
        if self.at_rest_dtype not in AT_REST_DTYPES:
            raise ValueError(
                f"at_rest_dtype={self.at_rest_dtype!r}: pick from "
                f"{AT_REST_DTYPES}")
        if self.dram_bytes is not None and self.dram_bytes < 0:
            raise ValueError("dram_bytes must be >= 0 (or None: unbounded)")

    # ------------------------------------------------------------- coercion
    @staticmethod
    def coerce(v: Union[None, str, dict, "StorageConfig"],
               ) -> Optional["StorageConfig"]:
        """``None``/``"off"`` -> None; a spec string, dict or config passes
        through. Spec string format (also the :data:`STORAGE_ENV` format)::

            exact | bf16 | int8 [:dram_kib=N] [:dir=PATH] [:tol=X]
        """
        if v is None or isinstance(v, StorageConfig):
            return v
        if isinstance(v, dict):
            return StorageConfig(**v)
        if isinstance(v, str):
            return StorageConfig.parse(v)
        raise TypeError(f"storage={v!r}: expected None, str, dict or "
                        "StorageConfig")

    @staticmethod
    def parse(text: str) -> Optional["StorageConfig"]:
        text = text.strip()
        if not text or text.lower() in ("off", "0", "none"):
            return None
        parts = text.split(":")
        kw: Dict[str, object] = {"at_rest_dtype": parts[0].strip()}
        for p in parts[1:]:
            k, _, val = p.partition("=")
            k = k.strip()
            if k == "dram_kib":
                kw["dram_bytes"] = int(float(val) * 1024)
            elif k == "dram_bytes":
                kw["dram_bytes"] = int(val)
            elif k == "dir":
                kw["spill_dir"] = val.strip()
            elif k == "tol":
                kw["error_tolerance"] = float(val)
            elif k == "prefetch":
                kw["prefetch"] = val.strip().lower() not in ("0", "false", "off")
            else:
                raise ValueError(f"unknown storage spec key {k!r} in {text!r}")
        return StorageConfig(**kw)  # type: ignore[arg-type]

    @staticmethod
    def from_env() -> Optional["StorageConfig"]:
        return StorageConfig.parse(os.environ.get(STORAGE_ENV, ""))

    # ---------------------------------------------------------------- model
    @property
    def at_rest_bytes_per_amp(self) -> float:
        return AT_REST_BYTES_PER_AMP[self.at_rest_dtype]

    def spill_fraction(self, total_amps: int) -> float:
        """Fraction of the at-rest state that does NOT fit in the DRAM
        budget — the planner's estimate of how much of every streaming pass
        crosses the disk tier."""
        if self.dram_bytes is None:
            return 0.0
        total = self.at_rest_bytes_per_amp * total_amps
        if total <= self.dram_bytes:
            return 0.0
        return 1.0 - self.dram_bytes / total

    def apply_to_cost_model(self, cm, n: int, L: int):
        """A :class:`repro.core.cost_model.CostModel` copy that prices the
        tier the shards actually sit in: ``at_rest_bytes`` reflects the
        at-rest dtype, and the ILP comm weight scales by the ratio of the
        spill-aware offload pass to the DRAM-resident one (a remap on a
        spilled run re-reads/re-writes the disk tier). Deterministic from
        (config, n, L), so it is safe inside the CircuitKey."""
        frac = self.spill_fraction(1 << n)
        cm2 = cm.with_overrides(at_rest_bytes=self.at_rest_bytes_per_amp)
        if frac <= 0.0:
            return cm2
        scale = cm2.offload_pass_us(L, frac) / max(cm2.offload_pass_us(L), 1e-9)
        return cm2.with_overrides(comm_weight=cm.comm_weight * scale)

    def fingerprint(self) -> Tuple:
        """CircuitKey component: compressed and exact plans must never
        collide in the compile cache."""
        return ("storage", self.at_rest_dtype, self.dram_bytes,
                self.spill_dir, float(self.error_tolerance), self.prefetch)

    def with_overrides(self, **kw) -> "StorageConfig":
        return replace(self, **kw)


# ======================================================================
# At-rest codecs
# ======================================================================


class Encoded:
    """One shard's at-rest representation: a tuple of contiguous numpy
    blocks (payload, and scales for int8) plus enough metadata to decode.
    Immutable after construction — a reference obtained under the store
    lock stays valid after a concurrent eviction."""

    __slots__ = ("mode", "parts", "shape", "dtype", "nbytes")

    def __init__(self, mode: str, parts: Tuple[np.ndarray, ...],
                 shape: Tuple[int, ...], dtype: np.dtype):
        self.mode = mode
        self.parts = parts
        self.shape = shape
        self.dtype = dtype
        self.nbytes = sum(int(p.nbytes) for p in parts)


def _as_float_view(arr: np.ndarray) -> np.ndarray:
    """Complex array -> interleaved real view (float32/float64 pairs)."""
    return np.ascontiguousarray(arr).view(arr.real.dtype)


def encode_shard(arr: np.ndarray, mode: str) -> Tuple[Encoded, float]:
    """Encode one decoded shard (complex, any lead dims) into its at-rest
    form. Returns ``(encoded, err)`` where ``err`` is the exact L2 norm of
    the roundtrip error ``||arr - decode(encode(arr))||_2`` (0.0 for the
    exact tier) — the quantity the store accumulates into the per-run
    error bound."""
    arr = np.ascontiguousarray(arr)
    shape = arr.shape
    dtype = arr.dtype
    if mode == "exact":
        return Encoded("exact", (arr.copy(),), shape, dtype), 0.0
    f = _as_float_view(arr).astype(np.float32, copy=False)
    if mode == "bf16":
        q = f.astype(_bf16)
        dec = q.astype(np.float32)
        err = float(np.linalg.norm((f - dec).reshape(-1)))
        return Encoded("bf16", (q,), shape, dtype), err
    if mode == "int8":
        flat = f.reshape(-1)
        block = min(_INT8_BLOCK, flat.size)
        rows = flat.reshape(-1, block)
        # symmetric per-block quantization (quantize_int8 idiom, numpy form)
        absmax = np.max(np.abs(rows), axis=-1, keepdims=True)
        scale = np.maximum(absmax / 127.0, 1e-12).astype(np.float32)
        q = np.clip(np.round(rows / scale), -127, 127).astype(np.int8)
        dec = q.astype(np.float32) * scale
        err = float(np.linalg.norm((rows - dec).reshape(-1)))
        return Encoded("int8", (q, scale), shape, dtype), err
    raise ValueError(f"unknown at-rest mode {mode!r}")


def decode_shard(enc: Encoded) -> np.ndarray:
    """Decode an at-rest shard back to its complex working form. Lossless
    from the encoded representation (all loss happens at encode time, once
    per put — spill/reload round trips are bit-stable)."""
    if enc.mode == "exact":
        return enc.parts[0].copy()
    if enc.mode == "bf16":
        f = enc.parts[0].astype(np.float32)
        return f.view(enc.dtype).reshape(enc.shape)
    if enc.mode == "int8":
        q, scale = enc.parts
        f = (q.astype(np.float32) * scale).reshape(-1)
        return f.view(enc.dtype).reshape(enc.shape)
    raise ValueError(f"unknown at-rest mode {enc.mode!r}")


# ======================================================================
# The store
# ======================================================================


class ShardStore:
    """Tiered at-rest shard container for one run.

    Shards are keyed ``0..n_shards-1`` in the *current generation*; a
    :meth:`remap` writes the permuted state under the next generation and
    swaps, so in-flight reads of old shards and writes of new ones never
    alias. The DRAM tier is an LRU ``OrderedDict`` (head = coldest) under
    a byte budget; overflow spills to atomic tmp+rename files. All tier
    bookkeeping happens under one lock; decode/dequantize runs outside it
    so a prefetch thread's dequantize overlaps the main thread's device
    wait."""

    def __init__(self, n_shards: int, shard_len: int,
                 lead_shape: Tuple[int, ...], np_dtype,
                 config: StorageConfig, run_tag: Optional[str] = None):
        self.n_shards = int(n_shards)
        self.shard_len = int(shard_len)
        self.lead_shape = tuple(lead_shape)
        self.np_dtype = np.dtype(np_dtype)
        self.config = config
        self.run_tag = run_tag or uuid.uuid4().hex[:12]
        self._lock = threading.Lock()
        self._dram: "OrderedDict[Tuple[int, int], Encoded]" = OrderedDict()
        self._disk: Dict[Tuple[int, int], str] = {}
        self._gen = 0
        self._dir: Optional[str] = None
        self._pool: Optional[ThreadPoolExecutor] = None
        self.dram_bytes = 0
        self.error_bound = 0.0  # accumulated L2 encode error (absolute)
        self.initial_norm = 1.0
        self.stats = {
            "puts": 0, "gets": 0, "spills": 0, "spill_loads": 0,
            "evictions": 0, "disk_bytes": 0, "peak_dram_bytes": 0,
            "remaps": 0, "prefetches": 0,
        }

    # ------------------------------------------------------------ lifecycle
    @property
    def total_amps(self) -> int:
        lead = 1
        for d in self.lead_shape:
            lead *= d
        return lead * self.n_shards * self.shard_len

    def _ndim(self) -> int:
        return len(self.lead_shape) + 1

    @property
    def ndim(self) -> int:
        # the offload stage loop branches on state.ndim; mirror the array
        return self._ndim()

    def _ensure_dir(self) -> str:
        if self._dir is None:
            root = self.config.spill_dir or tempfile.gettempdir()
            d = os.path.join(root, f"shardstore-{self.run_tag}")
            os.makedirs(d, exist_ok=True)
            self._dir = d
        return self._dir

    def close(self) -> None:
        """Drop everything: DRAM entries, spilled files, the prefetch
        worker. Called when the run's result has been gathered."""
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
        with self._lock:
            self._dram.clear()
            self.dram_bytes = 0
            paths = list(self._disk.values())
            self._disk.clear()
        for p in paths:
            try:
                os.remove(p)
            except OSError:
                pass
        if self._dir is not None:
            try:
                os.rmdir(self._dir)
            except OSError:
                pass
            self._dir = None

    # ------------------------------------------------------------ disk tier
    def _spill_path(self, key: Tuple[int, int]) -> str:
        return os.path.join(self._ensure_dir(),
                            f"g{key[0]}-s{key[1]}.npz")

    def _write_spill(self, key: Tuple[int, int], enc: Encoded) -> str:
        """Atomic spill write: tmp + fsync + rename, with the
        ``spill_io_error`` fault probe at the write site. A failure leaves
        no file under the final name — never a torn at-rest shard."""
        path = self._spill_path(key)
        tmp = path + ".tmp"
        if faults._ACTIVE is not None:
            faults.maybe_inject("spill_io_error",
                                site=f"spill.write.g{key[0]}s{key[1]}")
        # parts are serialized as raw bytes + a dtype/shape manifest: numpy's
        # npz format cannot round-trip ml_dtypes arrays (bf16 loads back as
        # an opaque void dtype)
        meta = {"mode": enc.mode, "shape": list(enc.shape),
                "parts": [[str(p.dtype), list(p.shape)] for p in enc.parts]}
        payload = {f"part{i}": np.frombuffer(p.tobytes(), dtype=np.uint8)
                   for i, p in enumerate(enc.parts)}
        payload["meta"] = np.frombuffer(json.dumps(meta).encode(),
                                        dtype=np.uint8)
        try:
            with open(tmp, "wb") as f:
                np.savez(f, **payload)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError as e:
            try:
                os.remove(tmp)
            except OSError:
                pass
            raise faults.SpillIOError(f"spill write failed for {path}: {e}")
        return path

    @staticmethod
    def _part_dtype(name: str):
        if name == "bfloat16":
            return np.dtype(_bf16)
        return np.dtype(name)

    def _read_spill(self, key: Tuple[int, int], path: str) -> Encoded:
        if faults._ACTIVE is not None:
            faults.maybe_inject("spill_io_error",
                                site=f"spill.read.g{key[0]}s{key[1]}")
        try:
            with np.load(path) as z:
                meta = json.loads(z["meta"].tobytes().decode())
                parts = []
                for i, (dname, pshape) in enumerate(meta["parts"]):
                    raw = z[f"part{i}"].tobytes()
                    parts.append(np.frombuffer(
                        raw, dtype=self._part_dtype(dname)
                    ).reshape(tuple(pshape)))
        except (OSError, KeyError, ValueError, json.JSONDecodeError) as e:
            raise faults.SpillIOError(f"spill read failed for {path}: {e}")
        return Encoded(meta["mode"], tuple(parts), tuple(meta["shape"]),
                       self.np_dtype)

    # ------------------------------------------------------------ LRU core
    def _evict_over_budget_locked(self) -> None:
        budget = self.config.dram_bytes
        if budget is None:
            return
        while self.dram_bytes > budget and self._dram:
            key, enc = self._dram.popitem(last=False)  # coldest
            self.dram_bytes -= enc.nbytes
            path = self._write_spill(key, enc)
            self._disk[key] = path
            self.stats["spills"] += 1
            self.stats["evictions"] += 1
            self.stats["disk_bytes"] = sum(
                os.path.getsize(p) for p in self._disk.values()
                if os.path.exists(p))

    def _put_key(self, key: Tuple[int, int], arr: np.ndarray) -> None:
        enc, err = encode_shard(arr, self.config.at_rest_dtype)
        with self._lock:
            old = self._dram.pop(key, None)
            if old is not None:
                self.dram_bytes -= old.nbytes
            stale = self._disk.pop(key, None)
            if stale is not None:
                # must happen under the lock and BEFORE eviction runs:
                # the key's spill path is deterministic, so an eviction
                # (here or from a concurrent put/get once the lock drops)
                # may rewrite this very path — deleting it later would
                # destroy the fresh spill
                try:
                    os.remove(stale)
                except OSError:
                    pass
            self._dram[key] = enc  # MRU
            self.dram_bytes += enc.nbytes
            self.error_bound += err
            self.stats["puts"] += 1
            self.stats["peak_dram_bytes"] = max(
                self.stats["peak_dram_bytes"], self.dram_bytes)
            self._evict_over_budget_locked()

    def _get_key(self, key: Tuple[int, int]) -> Encoded:
        with self._lock:
            enc = self._dram.get(key)
            if enc is not None:
                self._dram.move_to_end(key)  # touch MRU
                self.stats["gets"] += 1
                return enc
            path = self._disk.get(key)
            if path is None:
                raise KeyError(f"shard {key} not in store")
            enc = self._read_spill(key, path)
            self.stats["gets"] += 1
            self.stats["spill_loads"] += 1
            budget = self.config.dram_bytes
            if budget is None or enc.nbytes <= budget:
                # re-admit as MRU (and evict colder shards); a shard bigger
                # than the whole budget stays disk-resident — re-admitting
                # it would immediately write it straight back out
                del self._disk[key]
                # delete the consumed spill file under the lock, before
                # eviction (or any later one) can rewrite the same
                # deterministic path with a fresh spill of this key
                try:
                    os.remove(path)
                except OSError:
                    pass
                self._dram[key] = enc
                self.dram_bytes += enc.nbytes
                self.stats["peak_dram_bytes"] = max(
                    self.stats["peak_dram_bytes"], self.dram_bytes)
                self._evict_over_budget_locked()
        return enc

    def _delete_key(self, key: Tuple[int, int]) -> None:
        with self._lock:
            enc = self._dram.pop(key, None)
            if enc is not None:
                self.dram_bytes -= enc.nbytes
            path = self._disk.pop(key, None)
            if path is not None:  # under the lock: see _put_key
                try:
                    os.remove(path)
                except OSError:
                    pass

    # ------------------------------------------------------------ public API
    def put(self, shard_id: int, arr: np.ndarray) -> None:
        self._put_key((self._gen, shard_id), arr)

    def get_decoded(self, shard_id: int) -> np.ndarray:
        return decode_shard(self._get_key((self._gen, shard_id)))

    def resident_shards(self) -> Tuple[int, ...]:
        """Current-generation shard ids in the DRAM tier, coldest first
        (the LRU property tests assert against a model of this)."""
        with self._lock:
            return tuple(s for (g, s) in self._dram if g == self._gen)

    def spilled_shards(self) -> Tuple[int, ...]:
        with self._lock:
            return tuple(sorted(s for (g, s) in self._disk
                                if g == self._gen))

    def prefetch(self, shard_id: int) -> Optional[Future]:
        """Schedule shard load + dequantize on the background worker;
        returns a Future of the decoded array (None when prefetch is off —
        callers fall back to a synchronous :meth:`get_decoded`)."""
        if not self.config.prefetch:
            return None
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="shardstore-prefetch")
        self.stats["prefetches"] += 1
        return self._pool.submit(self.get_decoded, shard_id)

    # --------------------------------------------------------- bulk helpers
    def fill(self, state: Optional[np.ndarray]) -> "ShardStore":
        """Populate generation 0 from a dense array (lead dims + [2^n]) or
        the |0..0> basis state (``state=None``). Records the initial norm
        the relative error tolerance is measured against."""
        ln = self.shard_len
        sq = 0.0
        for s in range(self.n_shards):
            if state is None:
                block = np.zeros(self.lead_shape + (ln,), dtype=self.np_dtype)
                if s == 0:
                    block[..., 0] = 1.0
            else:
                block = np.ascontiguousarray(
                    state[..., s * ln:(s + 1) * ln]).astype(
                        self.np_dtype, copy=False)
            sq += float(np.sum(np.abs(block) ** 2))
            self.put(s, block)
        lead = 1
        for d in self.lead_shape:
            lead *= d
        self.initial_norm = max(np.sqrt(sq / max(lead, 1)), 1e-30)
        return self

    def tile(self, P: int) -> "ShardStore":
        """A new store whose lead axis replicates this store's state P
        times (the fused parameter-sweep layout). Carries the source
        store's accumulated error bound forward."""
        out = ShardStore(self.n_shards, self.shard_len,
                         (P,) + self.lead_shape, self.np_dtype, self.config,
                         run_tag=self.run_tag + f"-x{P}")
        for s in range(self.n_shards):
            block = self.get_decoded(s)
            out.put(s, np.repeat(block[None], P, axis=0))
        out.error_bound += self.error_bound
        out.initial_norm = self.initial_norm
        return out

    def gather(self) -> np.ndarray:
        """The full decoded state (lead dims + [2^n]) — the run's result
        extraction. At true past-DRAM scale callers should consume shards
        via :meth:`get_decoded` instead."""
        out = np.empty(self.lead_shape + (self.n_shards * self.shard_len,),
                       dtype=self.np_dtype)
        ln = self.shard_len
        for s in range(self.n_shards):
            out[..., s * ln:(s + 1) * ln] = self.get_decoded(s)
        return out

    # --------------------------------------------------------------- remap
    def remap(self, spec, n: int) -> "ShardStore":
        """Out-of-core inter-stage bit permutation (the eager analogue of
        ``_np_remap`` that never materializes the full state).

        For new bit p, ``result[x] = state[y ^ F]`` with ``bit_{src[p]}(y)
        = bit_p(x)`` and F the flip mask. An output shard (new nonlocal
        bits o) needs input shards spanning a subcube over the old
        nonlocal bits that moved INTO the local tier; output shards that
        agree on every o-bit sourced from an old nonlocal bit share that
        subcube exactly. Processing one such group at a time decodes every
        input shard exactly once per remap and bounds the transient
        working set at ``2^m`` decoded inputs + 1 output."""
        src = spec.src_bit_of
        F = 0
        for p in spec.flip_bits:
            F |= 1 << p
        ln = self.shard_len
        L = ln.bit_length() - 1
        mask = ln - 1
        # local-offset contribution to the old global index (shared by all
        # output shards: only the o-bit contribution differs)
        l = np.arange(ln, dtype=np.int64)
        lows = np.zeros(ln, dtype=np.int64)
        for i in range(L):
            lows |= ((l >> i) & 1) << src[i]
        fixed_ps = [p for p in range(L, n) if src[p] >= L]  # o-bits -> old NL
        free_ps = [p for p in range(L, n) if src[p] < L]    # o-bits -> old L
        newgen = self._gen + 1
        for fb in range(1 << len(fixed_ps)):
            group = []
            for vb in range(1 << len(free_ps)):
                o = 0
                for j, p in enumerate(fixed_ps):
                    o |= ((fb >> j) & 1) << (p - L)
                for j, p in enumerate(free_ps):
                    o |= ((vb >> j) & 1) << (p - L)
                group.append(o)
            inputs: Dict[int, np.ndarray] = {}
            for o in group:
                base_o = 0
                for p in range(L, n):
                    base_o |= ((o >> (p - L)) & 1) << src[p]
                old_global = (base_o | lows) ^ F
                old_shard = old_global >> L
                old_local = old_global & mask
                out = np.empty(self.lead_shape + (ln,), dtype=self.np_dtype)
                for sid in np.unique(old_shard):
                    if sid not in inputs:
                        inputs[int(sid)] = decode_shard(
                            self._get_key((self._gen, int(sid))))
                    sel = old_shard == sid
                    out[..., sel] = inputs[int(sid)][..., old_local[sel]]
                self._put_key((newgen, o), out)
            for sid in inputs:
                self._delete_key((self._gen, sid))
        self._gen = newgen
        self.stats["remaps"] += 1
        return self

    # ------------------------------------------------------------- snapshot
    def relative_error_bound(self) -> float:
        return self.error_bound / self.initial_norm

    def check_tolerance(self) -> None:
        """Reject the run when the accumulated quantization error bound
        exceeds the configured tolerance (typed, never a silent drop in
        accuracy)."""
        rel = self.relative_error_bound()
        if rel > self.config.error_tolerance:
            raise faults.StorageToleranceError(
                f"accumulated quantization error bound {rel:.3e} exceeds "
                f"tolerance {self.config.error_tolerance:.3e} "
                f"(at_rest_dtype={self.config.at_rest_dtype}); widen the "
                "tolerance or pick a higher-precision at-rest tier")

    def snapshot(self) -> Dict:
        """JSON-able per-run summary for provenance / serving stats."""
        with self._lock:
            resident = len(self._dram)
            spilled = len(self._disk)
        return {
            "at_rest_dtype": self.config.at_rest_dtype,
            "dram_budget_bytes": self.config.dram_bytes,
            "n_shards": self.n_shards,
            "resident_shards": resident,
            "spilled_shards": spilled,
            "dram_bytes": self.dram_bytes,
            "error_bound": self.error_bound,
            "relative_error_bound": self.relative_error_bound(),
            "error_tolerance": self.config.error_tolerance,
            **{k: v for k, v in self.stats.items()},
        }
