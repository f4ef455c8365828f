"""Host spans of the simulator, on the profiler's clock.

:func:`span` times one block of host code. It opens a
``jax.profiler.TraceAnnotation`` under the span's path, so a profiler trace
shows the span on its host plane on the same clock as the device's ops, and
adds the block's host seconds to an aggregate of count, total and max. The
aggregate is the table passed as ``into`` (an engine's ``timings``) or, by
default, the process table :data:`SPANS`. Nothing is written anywhere else;
readers take a copy with :func:`snapshot`.

A span's path names its parents: a span opened while another is open on the
same thread is recorded under ``<parent path>/<name>``
(``build/peephole``). A span opened inside an open span of the same name
records nothing of its own (an engine built inside ``build`` is one build).

Times are host time. Around a dispatch to the device (``engine.run``) that is
the time to dispatch: JAX returns before the device finishes, so the device
may still be running when the span closes.

JAX's own compile phases are kept beside the spans (:func:`compile_seconds`):
a listener on its monitoring events, registered when this module is
imported, so set-up shows how much of it was tracing and compiling.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional, Tuple

import jax

Table = Dict[str, Dict[str, float]]

#: The process table: spans opened without an ``into`` table.
SPANS: Table = {}

_lock = threading.Lock()
_open = threading.local()  # .stack: [(name, path), ...] of this thread


def _add(table: Table, path: str, seconds: float) -> None:
    with _lock:
        t = table.get(path)
        if t is None:
            t = table[path] = {"count": 0, "total_s": 0.0, "max_s": 0.0}
        t["count"] += 1
        t["total_s"] += seconds
        t["max_s"] = max(t["max_s"], seconds)


@contextmanager
def span(name: str, into: Optional[Table] = None) -> Iterator[None]:
    """Time the block under ``name`` (see the module's docstring)."""
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    if stack and stack[-1][0] == name:
        yield
        return
    path = f"{stack[-1][1]}/{name}" if stack else name
    stack.append((name, path))
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(path):
            yield
    finally:
        _add(SPANS if into is None else into, path, time.perf_counter() - t0)
        stack.pop()


def snapshot(table: Optional[Table] = None) -> Table:
    """A copy of ``table`` (default: the process table), each entry with its
    mean, ``mean_s``."""
    with _lock:
        items = [(k, dict(v)) for k, v in (SPANS if table is None else table).items()]
    for _, v in items:
        v["mean_s"] = v["total_s"] / max(v["count"], 1)
    return dict(items)


#: JAX's compile events by phase: ``trace``, tracing functions to jaxprs and
#: lowering them to MLIR (Pallas kernels lower to Mosaic there), and
#: ``backend``, XLA's compile or the load of its result from the persistent
#: compilation cache (the event holds the cache's retrieval).
COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace",
    "/jax/core/compile/backend_compile_duration": "backend",
}

# each phase's events as (start, end) on ``time.perf_counter``'s clock
_compiles: Dict[str, List[Tuple[float, float]]] = {
    phase: [] for phase in set(COMPILE_PHASES.values())}


def _on_compile_event(event: str, duration: float, **kw) -> None:
    phase = COMPILE_PHASES.get(event)
    if phase is not None:
        end = time.perf_counter()  # JAX reports an event as it ends
        with _lock:
            _compiles[phase].append((end - duration, end))


jax.monitoring.register_event_duration_secs_listener(_on_compile_event)


def compile_seconds(until: Optional[float] = None) -> Dict[str, float]:
    """Host seconds of each compile phase in this process, over the events
    that ended by ``until`` (``time.perf_counter``), or all of them. A
    phase's time is the union of its events' spans: a ``jit`` traced inside
    another's trace reports a span inside the outer's."""
    out = {}
    with _lock:
        items = [(p, sorted(e for e in ev if until is None or e[1] <= until))
                 for p, ev in _compiles.items()]
    for phase, events in items:
        total, reach = 0.0, float("-inf")
        for s, e in events:
            if e > reach:
                total += e - max(s, reach)
                reach = e
        out[phase] = total
    return out
