"""The simulator on one chip through ``engine_for(..., backend="pjit")``:
one shard of ``2^L`` amplitudes (``R = G = 0``), held lane-dense as
``[2^(L-7), 128]``."""

from __future__ import annotations


class System:
    def __init__(self, cfg: dict, gates: list, spans):
        import jax
        import jax.numpy as jnp
        from repro.core.circuit import Circuit
        from repro.core.partition import partition
        from repro.sim.engine import engine_for

        e = cfg["engine"]
        n = cfg["circuit"]["n_qubits"]
        if e["R"] or e["G"]:
            raise ValueError("the pjit system holds one shard: R and G must be 0")
        circ = Circuit(n)
        for name, qubits, params in gates:
            circ.add(name, *qubits, params=params)
        with spans("plan"):
            self.plan = partition(circ, e["L"], e["R"], e["G"],
                                  staging_method=e["staging_method"],
                                  kernelize_method=e["kernelize_method"])
        with spans("build"):
            self.engine = engine_for(
                circ, e["L"], e["R"], e["G"], backend="pjit",
                dtype=getattr(jnp, e["dtype"]), use_pallas=e["use_pallas"],
                degrade=False, plan=self.plan)
        self.n = n
        rows, lanes = self.engine.backend.shape
        dtype = self.engine.dtype

        def basis(x):
            return jnp.zeros((rows, lanes), dtype).at[x // lanes, x % lanes].set(1)

        self._basis = jax.jit(basis)

    def make_input(self, x: int):
        import jax.numpy as jnp

        return self._basis(jnp.int32(x))

    def run(self, psi0):
        return self.engine.run(psi0)

    def kernel_calls(self) -> list:
        from bench import work

        return work.kernel_calls(self.engine.cc)
