"""The systems under test, by the ``engine.backend`` a configuration names
(``systems/<backend>.py``).

A system module has ``System(cfg, gates, spans)``, which builds the
simulator as the configuration states (planning inside ``spans("plan")``,
the engine's build inside ``spans("build")``), with:

* ``n``: the circuit's qubits, and ``engine``: the simulator's
  ``ExecutionEngine`` (its ``provenance`` says whether it was degraded);
* ``make_input(x)``: the basis state |x> on the device, in the engine's
  own state shape and placement;
* ``run(psi0)``: the entry the window drives, a whole simulation to the
  final state in logical order (it may return before the device is done;
  the caller blocks);
* ``kernel_calls()``: ``work.kernel_calls`` of one simulation, the
  algorithm's work in each Pallas call.
"""
