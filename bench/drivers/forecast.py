"""Closed-loop forecast: one forecast continues call after call.

Each call is a ``lax.scan`` of ``steps_per_call`` hdiff steps through the
program's lowering: ``lower_pallas`` on one chip, or
``lower_sharded(inner="pallas")`` on the ``mesh`` the traffic names. After
each call the state is copied to the host, as a forecast writes its output.
The window ends at a call boundary.

Traffic parameters: ``steps_per_call``, ``mesh`` (``[rows, cols]`` shards,
or null for one chip), ``trace_seconds`` and ``limits``.

The check: a call drawn from the seed and the last call, each recomputed
from its own input (the output the previous call wrote to the host) by the
plain reference on one chip, compared by the widest gap.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, fields, reference


class Driver:
    def __init__(self, config, traffic, seed, timed_path=None):
        from repro.ir import hdiff_program, lower_pallas, lower_sharded

        self.coeff = config["coeff"]
        self.shape = (config["depth"], config["rows"], config["cols"])
        self.limits = traffic["limits"]
        self.steps = traffic["steps_per_call"]
        prog = hdiff_program(self.coeff, limit=config["limit"])
        mesh = traffic.get("mesh")
        sharding = None
        if mesh:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from repro.launch.mesh import make_mesh

            mesh = tuple(mesh)
            step = lower_sharded(prog, mesh_shape=mesh, inner="pallas")
            sharding = NamedSharding(make_mesh(mesh, ("rows", "cols")), P(None, "rows", "cols"))
        else:
            step = lower_pallas(prog)
        if timed_path is not None:
            step = timed_path(step)

        def call(x):
            out, _ = jax.lax.scan(lambda s, _: (step(s), None), x, None, length=self.steps)
            return out

        self.x = fields.smooth_field(seed, 0, self.shape, sharding)
        self.compiled = jax.jit(call).lower(self.x).compile()
        self.programs = [self.compiled.as_text()]
        self.prev_host = np.asarray(self.x)
        self.sample = compare.Sample(seed)
        self.calls = 0

    def _call(self) -> None:
        with jax.profiler.TraceAnnotation("bench.call"):
            y = self.compiled(self.x)
        with jax.profiler.TraceAnnotation("bench.output_copy"):
            out = np.asarray(y)
        self.sample.offer((self.prev_host, out))
        self.x, self.prev_host = y, out
        self.calls += 1

    def warm(self) -> None:
        self._call()

    def window(self, seconds: float) -> dict:
        calls0 = self.calls
        t0 = time.perf_counter()
        while True:
            self._call()
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        calls = self.calls - calls0
        points = int(np.prod(self.shape))
        return {
            "window_s": window_s,
            "attempted": calls,
            "failed": 0,
            "counts": {"calls": calls, "steps": calls * self.steps,
                       "grid_updates": calls * self.steps * points},
        }

    def release(self) -> None:
        self.x = self.compiled = None

    def check(self) -> dict:
        dev = jax.devices()[0]
        ref = jax.jit(lambda a: reference.forecast(a, self.steps, self.coeff))
        err = 0.0
        for x_in, out in self.sample.items():
            want = np.asarray(ref(jax.device_put(x_in, dev)))
            err = max(err, compare.rel_err(out, want))
        return {"forecast_rel_err": (err, self.limits["forecast_rel_err"])}


def control(config, traffic):
    """The control: the plain reference computed in bfloat16, put in the
    place of the program's step."""
    coeff = config["coeff"]
    return lambda step: lambda x: reference.hdiff(x.astype(jnp.bfloat16), coeff).astype(x.dtype)
