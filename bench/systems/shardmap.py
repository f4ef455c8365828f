"""The simulator over ``2^(R+G)`` chips through ``engine_for(...,
backend="shardmap")``: each chip holds one shard of ``2^L`` amplitudes and
runs the stage loop inside ``shard_map``; each remap between stages is a
local transpose, a grouped all-to-all and another local transpose. The
state is flat ``[2^n]``, split over the chips of the backend's bit-mesh
(the first ``2^(R+G)`` of ``jax.devices()``, in order)."""

from __future__ import annotations


class System:
    def __init__(self, cfg: dict, gates: list, spans):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from repro.core.circuit import Circuit
        from repro.core.partition import partition
        from repro.sim.engine import engine_for

        e = cfg["engine"]
        n = cfg["circuit"]["n_qubits"]
        circ = Circuit(n)
        for name, qubits, params in gates:
            circ.add(name, *qubits, params=params)
        with spans("plan"):
            self.plan = partition(circ, e["L"], e["R"], e["G"],
                                  staging_method=e["staging_method"],
                                  kernelize_method=e["kernelize_method"])
        with spans("build"):
            self.engine = engine_for(
                circ, e["L"], e["R"], e["G"], backend="shardmap",
                dtype=getattr(jnp, e["dtype"]), use_pallas=e["use_pallas"],
                degrade=False, plan=self.plan)
        self.n = n
        dtype = self.engine.dtype

        def basis(x):
            # elementwise, so each chip makes only its own shard
            return (lax.iota(jnp.uint32, 1 << n) == x).astype(dtype)

        self._basis = jax.jit(basis, out_shardings=self.engine.backend.sharding)

    def make_input(self, x: int):
        import jax.numpy as jnp

        return self._basis(jnp.uint32(x))

    def run(self, psi0):
        return self.engine.run(psi0)

    def kernel_calls(self) -> list:
        """Per shard: every chip runs each call on its own ``2^L``
        amplitudes."""
        from bench import work

        return work.kernel_calls(self.engine.cc)
