"""The loops that traffic mixes name (``"driver"`` in ``traffic/<mix>.json``).

A driver module has ``Loop(system, traffic, seed)`` with ``warm_up()``,
which runs every shape the window will use once, and ``window(seconds)``,
which returns a :class:`Window`.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List


@dataclass
class Sample:
    """An answer kept for the check: the simulation's index in the window,
    its input basis state and its output state (on the device)."""

    index: int
    x: int
    out: Any


@dataclass
class Window:
    start: float  # host clock, seconds
    end: float  # end of the last simulation
    durations: List[float] = field(default_factory=list)
    samples: List[Sample] = field(default_factory=list)

    @property
    def count(self) -> int:
        return len(self.durations)
