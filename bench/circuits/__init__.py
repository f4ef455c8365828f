"""Gate lists of the benchmark's circuit families, kept with the benchmark.

Each module ``<family>.py`` has ``gates(n_qubits, **params)`` returning a
list of ``(name, qubits, params)``: ``qubits[0]`` is the target (the low
bit of the gate's index) and a second qubit is the control, as the
simulator's ``Circuit.add`` takes them. The simulator and the reference are
both given this list, so neither builds the workload for the other.
"""
