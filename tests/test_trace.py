"""The simulator's tracing: host spans (``repro.sim.trace``), the named
scopes of stage op kinds in the compiled program, and the engine's span
table."""

import glob
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import generators as gen
from repro.sim import trace
from repro.sim.engine import OP_SCOPES, Backend, engine_for

SCOPES = ("shm", "fused", "diag", "route", "planar", "remap", "operands")


def test_span_paths_count_total_max():
    table = {}
    for _ in range(3):
        with trace.span("outer", table):
            with trace.span("inner", table):
                pass
            with trace.span("inner", table):
                pass
    assert set(table) == {"outer", "outer/inner"}
    assert table["outer"]["count"] == 3
    assert table["outer/inner"]["count"] == 6
    for t in table.values():
        assert 0 < t["max_s"] <= t["total_s"]
    # the children ran inside their parent
    assert table["outer/inner"]["total_s"] <= table["outer"]["total_s"]
    snap = trace.snapshot(table)
    assert snap["outer"]["mean_s"] == pytest.approx(table["outer"]["total_s"] / 3)
    assert "mean_s" not in table["outer"]  # a copy, the table is untouched


def test_span_into_an_engine_table_or_the_process_table():
    engine_timings = {}
    trace.SPANS.clear()
    with trace.span("plan"):
        with trace.span("step", engine_timings):
            pass
        with trace.span("step"):
            pass
    # the path comes from the thread's open spans, the table from ``into``
    assert set(engine_timings) == {"plan/step"}
    assert set(trace.snapshot()) == {"plan", "plan/step"}
    assert trace.snapshot()["plan/step"]["count"] == 1
    trace.SPANS.clear()
    assert trace.snapshot() == {}


def test_span_of_the_same_name_nested_counts_once():
    table = {}
    with trace.span("build", table):
        with trace.span("build", table):
            with trace.span("consts", table):
                pass
    assert {k: v["count"] for k, v in table.items()} == {"build": 1, "build/consts": 1}


def test_span_records_through_an_error_and_per_thread():
    table = {}
    with pytest.raises(ValueError):
        with trace.span("engine.run", table):
            raise ValueError("boom")
    assert table["engine.run"]["count"] == 1

    def worker():
        with trace.span("offload_stage", table):
            pass

    with trace.span("engine.run", table):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=30)
    assert not t.is_alive()
    # another thread's span does not nest under this thread's open one
    assert table["offload_stage"]["count"] == 1
    assert table["engine.run"]["count"] == 2


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("engine.run", {}):
            with trace.span("prepare", {}):
                jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    names = {e.name for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for line in p.lines for e in line.events}
    assert {"engine.run", "engine.run/prepare"} <= names


def test_compiled_stage_program_carries_every_named_scope():
    """qft(16) over L = 14, R = 2 holds shm groups, fused gates (routed),
    XLA diagonals and inter-stage remaps; with the Pallas kernels the
    planar split and join sit around each call, and the kernels' operands
    are built from the op tensors."""
    eng = engine_for(gen.qft(16), 14, 2, 0, backend="pjit", use_pallas=True,
                     degrade=False, cache=None)
    kinds = {op.kind for prog in eng.cc.programs for op in prog.ops}
    assert {"shm", "fused", "diag"} <= kinds and len(eng.cc.programs) > 1
    text = eng.backend.lower().compile().as_text()
    for scope in SCOPES:
        assert f"/{scope}/" in text, scope
    assert "shm_group" in text and "fused_lanes" in text
    assert set(OP_SCOPES.values()) <= set(SCOPES)


@pytest.mark.parametrize("batch", [False, True])
def test_extract_is_one_reshape_to_logical_order_under_remap(batch):
    """The pjit backend takes the base ``extract``: one jitted reshape of
    the lane-dense result to flat logical order, in the ``remap`` scope."""
    eng = engine_for(gen.qft(8), 8, 0, 0, backend="pjit", cache=None)
    assert type(eng.backend).extract is Backend.extract
    shape = ((3,) if batch else ()) + eng.backend.shape
    out = jnp.arange(int(np.prod(shape)), dtype=jnp.float32).reshape(shape)
    flat = eng.backend.extract(out, batch)
    np.testing.assert_array_equal(
        flat, np.asarray(out).reshape((3, -1) if batch else (-1,)))
    lowered = jax.jit(eng.backend.extract, static_argnums=1).lower(out, batch)
    assert "remap/reshape" in lowered.as_text(debug_info=True)


def test_engine_build_and_run_spans():
    trace.SPANS.clear()
    eng = engine_for(gen.qft(6), 4, 2, 0, backend="pjit", cache=None)
    built = trace.snapshot()
    for path in ("plan", "build", "build/compile_plan", "build/compile_plan/peephole",
                 "build/consts", "build/backend"):
        assert built[path]["count"] >= 1, path
    assert built["build/compile_plan/peephole"]["total_s"] <= built["build"]["total_s"]
    eng.run()
    snap = eng.timing_snapshot()
    assert set(snap) == {"engine.run", "engine.run/prepare", "engine.run/execute",
                         "engine.run/extract"}
    assert all(t["count"] == 1 for t in snap.values())
    child = sum(snap[f"engine.run/{c}"]["total_s"] for c in ("prepare", "execute", "extract"))
    assert child <= snap["engine.run"]["total_s"]
    # the engine's runs go to its own table, not the process table
    assert not any(k.startswith("engine.") for k in trace.snapshot())


def test_compile_seconds_by_phase_and_until():
    start = time.perf_counter()
    before = trace.compile_seconds()
    jax.jit(lambda x: jnp.sin(x) * 5 - 2)(jnp.ones(11)).block_until_ready()
    after = trace.compile_seconds()
    assert set(after) == {"trace", "backend"}
    assert all(after[p] > before[p] for p in after)
    # events that ended after ``until`` are left out
    assert trace.compile_seconds(until=start) == before


def test_compile_seconds_is_the_union_of_nested_events(monkeypatch):
    monkeypatch.setattr(trace, "_compiles", {"trace": [], "backend": []})
    # an inner trace of 1 s reported inside an outer one of 3 s, then an
    # event of its own
    trace._on_compile_event("/jax/core/compile/jaxpr_trace_duration", 1.0)
    trace._on_compile_event("/jax/core/compile/jaxpr_to_mlir_module_duration", 3.0)
    trace._on_compile_event("/jax/core/compile/backend_compile_duration", 0.5)
    trace._on_compile_event("/jax/some/other_event", 9.0)
    got = trace.compile_seconds()
    assert got["trace"] == pytest.approx(3.0, abs=0.05)
    assert got["backend"] == pytest.approx(0.5)
