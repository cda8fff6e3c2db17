"""Closed-loop serving of a multi-field ensemble whose members are each
domain-decomposed over a mesh, as COSMO-1E runs on a four-chip host.

``served_fields``'s loop, window and check, with the members' fields made
directly on the configuration's ``mesh`` (``[rows, cols]`` shards of each
field, ``P(None, "rows", "cols")``), so no field is ever whole on one chip,
and served by ``ForecastServer(backend="sharded-pallas",
mesh_shape=mesh)`` at its default ``max_batch``. Each member's result
keeps its sharding and is fed back as is.

Traffic parameters: ``perturbation``, ``trace_seconds`` and ``limits``.

Besides ``served_fields``' counts the window records ``chip_field_bytes``,
the fields' logical bytes each chip reads and writes (the server's
``field_bytes`` over the mesh's chips), and ``halo_bytes``, the per-chip
model bytes of the batches' halo exchanges (the server's
``stats["halo_bytes"]``; 0 where the server does not count them).

The check is ``served_fields``' comparison, one member's field at a time:
the plain reference run on the global sharded array (partitioned by XLA)
and compared on the chips. XLA partitions the reference's interior update
by gathering it, so a field of all 11 members at once reckons 14.25 GB a
chip at full size, more than fits beside the final states; one member's
field reckons 1.2 GB.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import fields, reference_fields
from bench.drivers import served_fields


class Driver(served_fields.Driver):
    def __init__(self, config, traffic, seed, timed_path=None):
        from repro.ir import hdiff_fields_program
        from repro.launch.mesh import make_mesh
        from repro.serve import ForecastServer
        from repro.serve.cache import CompileCache

        self.names = tuple(config["fields"])
        self.coeffs = dict(zip(self.names, config["coeffs"]))
        self.members = config["members"]
        self.limits = traffic["limits"]
        self.prog = hdiff_fields_program(self.names, config["coeffs"], limit=config["limit"])
        self.shape = (config["depth"], config["rows"], config["cols"])
        self.seed, self.scale = seed, traffic["perturbation"]
        mesh = tuple(config["mesh"])
        self.chips = int(np.prod(mesh))
        self.sharding = NamedSharding(make_mesh(mesh, ("rows", "cols")), P(None, "rows", "cols"))
        kwargs = {}
        if timed_path is not None:
            build = CompileCache().builder
            kwargs["cache"] = CompileCache(
                builder=lambda p, key, **kw: timed_path(build(p, key, **kw)))
        self.server = ForecastServer(backend="sharded-pallas", mesh_shape=mesh, **kwargs)
        self.states = [{} for _ in range(self.members)]
        for j, f in enumerate(self.names):
            for state, x in zip(self.states, self._initial(j)):
                state[f] = x
        self.timesteps = 0
        self.latencies: list[float] = []
        self.failed = 0
        self.programs: list[str] = []

    def _initial(self, j: int) -> list[jax.Array]:
        """``served_fields``' initial field ``j`` of each member, made on
        the mesh."""
        first = j * (self.members + 1)
        base = fields.smooth_field(self.seed, first, self.shape, self.sharding)
        return [base + self.scale * fields.smooth_field(self.seed, first + 1 + i, self.shape,
                                                        self.sharding)
                for i in range(self.members)]

    def window(self, seconds: float) -> dict:
        halo0 = self.server.stats.get("halo_bytes", 0)
        record = super().window(seconds)
        counts = record["counts"]
        counts["chip_field_bytes"] = counts["field_bytes"] // self.chips
        counts["halo_bytes"] = self.server.stats.get("halo_bytes", 0) - halo0
        return record

    def check(self) -> dict:
        ref = jax.jit(lambda x, n, f: reference_fields.forecast({f: x}, n, self.coeffs)[f],
                      static_argnums=2)
        err = 0.0
        for j, f in enumerate(self.names):
            for got, x in zip((m.pop(f) for m in self.final), self._initial(j)):
                want = ref(x, self.timesteps, f)
                e = float(served_fields._member_rel_errs(got[None], want[None])[0])
                err = max(err, e if np.isfinite(e) else float("inf"))
                del got, want
        return {"member_rel_err": (err, self.limits["member_rel_err"])}


control = served_fields.control
