"""Closed loop: one caller runs whole simulations back to back.

Each simulation starts from a basis state |x>, x drawn from the seed and
made on the device (``prepare``), runs the system's entry (``dispatch``)
and blocks until the final state is ready (``wait``); the three are host
spans in the profiler's trace. The next input is made while a simulation
runs, so that only the wait and the next dispatch lie between two
simulations on the host. A simulation's time runs from the end of the
last one (the window's start, for the first) to the end of its own. The
window runs simulations until ``seconds`` have passed since its start, and
keeps for the check the last answer and one answer drawn uniformly from
all of them by the seed (reservoir sampling, so that no more than two
answers and the next input are held at once).

Traffic parameters: ``initial_state`` (only ``"basis"``).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from . import Sample, Window


def _rng(seed: int, stream: int):
    return np.random.default_rng([stream, seed % (1 << 64)])


class Loop:
    def __init__(self, system, traffic: dict, seed: int):
        if traffic.get("initial_state") != "basis":
            raise ValueError(f"closed_loop: initial state {traffic.get('initial_state')!r}")
        self.system = system
        self.n = system.n
        self._xs = _rng(seed, 1)
        self._pick = _rng(seed, 2)

    def next_x(self) -> int:
        """The next input's basis index, drawn from the seed."""
        return int(self._xs.integers(1 << self.n))

    def warm_up(self) -> None:
        self.system.run(self.system.make_input(self.next_x())).block_until_ready()

    def window(self, seconds: float) -> Window:
        import jax

        ann = jax.profiler.TraceAnnotation
        run, make = self.system.run, self.system.make_input
        kept = out = None
        # no collection inside the window: the host's pauses there would
        # show as the device's idle time between simulations
        gc.collect()
        gc.freeze()
        gc.disable()
        try:
            w = Window(start=time.perf_counter(), end=0.0)
            deadline = w.start + seconds
            x = self.next_x()
            with ann("prepare"):
                psi0 = make(x)
            t = w.start
            while True:
                i = w.count
                out = None  # free the last answer unless it is the kept one
                with ann("dispatch"):
                    out = run(psi0)
                # the next input, made while this simulation runs
                x_next = self.next_x()
                with ann("prepare"):
                    psi0 = make(x_next)
                with ann("wait"):
                    out.block_until_ready()
                w.end = time.perf_counter()
                w.durations.append(w.end - t)
                t = w.end
                if self._pick.integers(i + 1) == 0:
                    kept = Sample(i, x, out)
                if w.end >= deadline:
                    break
                x = x_next
        finally:
            gc.enable()
            gc.unfreeze()
        w.samples = [kept] if kept.index == i else [kept, Sample(i, x, out)]
        return w
