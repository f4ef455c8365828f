"""The algorithm's work in each Pallas call of a compiled plan, and the
least time the chip could take for it.

The count is of what the simulation needs, not of what a kernel happens to
move: a call over ``2^m`` complex64 amplitudes reads and writes each once
(``16 * 2^m`` bytes), a dense k-qubit member gate costs ``8 * 2^m * 2^k``
flops (a complex multiply-add per amplitude and matrix column) and a
diagonal or scalar member ``6 * 2^m``. Planar copies and the kernels'
128 x 128 embedding do not change it.

Which plan ops run as Pallas calls on the ``pjit`` path: every ``shm``
group, and every ``fused`` op except a shared gate on at most two row bits
(those are elementwise XLA passes); ``diag`` and ``scalar`` ops are XLA.
"""

from __future__ import annotations

import json
import os

LANE_BITS = 7
HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device missing from ``peaks.json`` is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table["devices"][device_kind]


def gate_flops(op, amps: int) -> int:
    if op.kind == "fused":
        return 8 * amps * op.tensor.shape[-1]
    if op.kind in ("diag", "scalar"):
        return 6 * amps
    raise ValueError(f"no flop count for an op of kind {op.kind!r}")


def is_pallas(op, L: int) -> bool:
    if op.kind == "shm":
        return True
    if op.kind != "fused":
        return False
    lanes = min(LANE_BITS, L)
    row_only = all(b >= lanes for b in op.local_bits) and len(op.local_bits) <= 2
    return not (row_only and not op.dep_bits)


def kernel_calls(cc) -> list:
    """One dict per Pallas call of one run of ``cc`` (a compiled plan on one
    shard of ``2^L`` amplitudes): its ``kind``, ``bytes`` and ``flops``."""
    amps = 1 << cc.L
    calls = []
    for prog in cc.programs:
        for op in prog.ops:
            if not is_pallas(op, cc.L):
                continue
            members = op.gates if op.kind == "shm" else (op,)
            calls.append({"kind": op.kind, "bytes": 16 * amps,
                          "flops": sum(gate_flops(m, amps) for m in members)})
    return calls


def least_time(call: dict, peak: dict):
    """(seconds, bound): the larger of flops over peak flops and bytes over
    peak bandwidth, and which of the two it is."""
    t_flops = call["flops"] / peak["flops_per_s"]
    t_bytes = call["bytes"] / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
