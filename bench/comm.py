"""The collectives of a run's remaps: what each chip must send, and which
ops of a device trace moved it.

The simulator's ``repro.kernels.ops.remap_stats()`` lists each traced remap
of its shard_map program (its ``m``, the shard's ``L`` and dtype, and
whether a ``ppermute`` follows); the bytes are counted here from those.
An all-to-all exchanging m bits of a shard of ``2^L`` amplitudes sends all
of its ``2^m`` chunks but its own: ``(1 - 2^-m) * itemsize * 2^L`` bytes
from each chip.

On a TPU a collective of a complex array runs as one op per real plane
(XLA splits it: two ``f32`` all-to-alls for a ``complex64`` state); the
device trace names each op by its HLO instruction (``%all_to_all.12``).
"""

from __future__ import annotations

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
_COLLECTIVE = re.compile(r"^%(all[-_]to[-_]all|collective[-_]permute|ppermute)\b")


def ici_peaks(device_kind: str) -> dict:
    """The chip's interconnect peak (``ici.json``); a device missing there
    is an error."""
    with open(os.path.join(HERE, "ici.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no interconnect peak for {device_kind!r} in ici.json")
    return table["devices"][device_kind]


def remap_stats():
    """The simulator's record of one traced program's remaps, or None
    where it keeps none, or holds more or fewer than one program."""
    try:
        from repro.kernels import ops
    except ImportError:
        return None
    stats = getattr(ops, "remap_stats", lambda: None)()
    if not stats or stats["programs"] != 1:
        return None
    return stats


def a2a_bytes(m: int, L: int, itemsize: int) -> int:
    """Bytes each chip sends in an all-to-all of ``m`` bits."""
    return itemsize * ((1 << L) - (1 << (L - m)))


def sent_bytes(stats: dict) -> int:
    """Bytes each chip sends in one run's all-to-alls."""
    return sum(a2a_bytes(r["m"], r["L"], r["itemsize"]) for r in stats["remaps"])


def device_ops(stats: dict) -> int:
    """The collective ops one run shows in a device trace: one per real
    plane of the state for each all-to-all and each ``ppermute``."""
    planes = [2 if r["dtype"].startswith("complex") else 1 for r in stats["remaps"]]
    return sum(p * ((r["m"] > 0) + bool(r["ppermute"]))
               for p, r in zip(planes, stats["remaps"]))


def collective_seconds(trace: dict, stats: dict):
    """Device seconds in the collectives over the traced window (averaged
    over the chips, as ``devtrace.reduce`` gives each op), or None unless
    the trace's ops hold exactly as many collectives as ``stats`` runs."""
    ops = [(name, s) for name, s in trace["device_ops"] if _COLLECTIVE.match(name)]
    if not ops or len(ops) != device_ops(stats):
        return None
    return sum(s for _, s in ops)
