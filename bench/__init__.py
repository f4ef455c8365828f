"""The on-chip benchmark: one command, cells and metrics found by name.

See ``run.py`` for the command and ``BENCHMARK.json`` at the repository
root for the cells. Everything that belongs to one configuration, traffic
mix, reference or metric sits in a file of its own, found by its name:

* ``configs/<config>.json``   — the circuit, the engine and the limits;
* ``circuits/<family>.py``    — the gate list of a circuit family;
* ``references/<name>.py``    — a plain reference of a configuration;
* ``systems/<backend>.py``    — how the simulator is built and driven for
  a configuration's ``engine.backend``;
* ``traffic/<mix>.json``      — the parameters of a traffic mix;
* ``drivers/<driver>.py``     — the loop a traffic mix names;
* ``metrics/<metric>.py``     — one metric's reader.
"""
