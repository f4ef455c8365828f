"""Plain references of the benchmark's configurations, independent of the
simulator under test: they import nothing from ``repro`` and take only the
gate list and the initial basis state. Each module ``<name>.py`` has

    state(gates, n_qubits, x, precision="highest")

returning the final state of ``gates`` applied to |x> on the device,
lane-dense ``[2^(n-7), 128]`` complex64 in logical order (index bit q is
qubit q). ``precision="high"`` computes every product with three bf16
passes, as a TPU's ``Precision.HIGH`` does: the control that the check has
to refuse.
"""
