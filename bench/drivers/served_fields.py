"""Closed-loop serving of an ensemble whose members each carry several
diffused fields, as COSMO-E's ensemble runs.

Every timestep the client submits one request per member, the member's
fields as one ``{field: (depth, rows, cols)}`` mapping, to a
``ForecastServer(backend="pallas")`` at its default ``max_batch``, drains
the server batch by batch, and feeds each member's result back as its next
state (the window and the latencies are ``served``'s). The client keeps
no member's state once it is submitted, so the chip holds the ensemble
once plus the batch in flight. Each field starts from its own seeded base
plus a seeded perturbation per member (``perturbation``, relative size),
every field from its own streams.

Traffic parameters: ``perturbation``, ``trace_seconds`` and ``limits``.

The check, after ``release()``, one field at a time across all members
(so that it fits on the chip beside the final states): each member's final
field against its own reference trajectory over the same number of
timesteps, from its initial field made again from the seed, compared on
the chip.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import fields, reference_fields
from bench.drivers import served


@jax.jit
def _member_rel_errs(got, want):
    """Each member's widest gap, as a share of its reference's largest
    magnitude (``compare.rel_err`` per member, on the chip)."""
    axes = tuple(range(1, got.ndim))
    err = jnp.max(jnp.abs(got - want), axis=axes)
    scale = jnp.max(jnp.abs(want), axis=axes)
    return jnp.where(scale > 0, err / scale, err)


class Driver(served.Driver):
    def __init__(self, config, traffic, seed, timed_path=None):
        from repro.ir import hdiff_fields_program
        from repro.serve import ForecastServer
        from repro.serve.cache import CompileCache

        self.names = tuple(config["fields"])
        self.coeffs = dict(zip(self.names, config["coeffs"]))
        self.members = config["members"]
        self.limits = traffic["limits"]
        self.prog = hdiff_fields_program(self.names, config["coeffs"], limit=config["limit"])
        self.shape = (config["depth"], config["rows"], config["cols"])
        self.seed, self.scale = seed, traffic["perturbation"]
        kwargs = {}
        if timed_path is not None:
            build = CompileCache().builder
            kwargs["cache"] = CompileCache(
                builder=lambda p, key, **kw: timed_path(build(p, key, **kw)))
        self.server = ForecastServer(backend="pallas", **kwargs)
        self.states = [{} for _ in range(self.members)]
        for j, f in enumerate(self.names):
            for state, x in zip(self.states, self._initial(j)):
                state[f] = x
        self.timesteps = 0
        self.latencies: list[float] = []
        self.failed = 0
        self.programs: list[str] = []

    def _initial(self, j: int) -> list[jax.Array]:
        """Field ``j`` of each member at the start: the field's seeded base
        plus the member's seeded perturbation, each from a stream of its
        own, made one member at a time so that set-up holds little more
        than the ensemble."""
        first = j * (self.members + 1)
        base = fields.smooth_field(self.seed, first, self.shape)
        return [base + self.scale * fields.smooth_field(self.seed, first + 1 + i, self.shape)
                for i in range(self.members)]

    def _timestep(self) -> None:
        server = self.server
        states, self.states = self.states, None
        submitted = {}
        with jax.profiler.TraceAnnotation("bench.submit"):
            for i in range(len(states)):
                t = time.perf_counter()
                submitted[server.submit(self.prog, states[i])] = t
                states[i] = None  # the request holds the member's state now
        results = {}
        with jax.profiler.TraceAnnotation("bench.drain"):
            while server.step():
                t = time.perf_counter()
                for req in server.completed:
                    self.latencies.append(t - submitted[req.rid])
                    if req.failed:
                        self.failed += 1
                    results[req.rid] = req.result
                # Retired requests hold their (old) fields: let them go.
                server.completed.clear()
        self.states = [results[rid] for rid in submitted]
        self.timesteps += 1

    def warm(self) -> None:
        for _ in range(2):
            self._timestep()
        for key in self.server.cache.keys():
            marshal = self.server.cache.lookup(key).marshal
            self.programs.append(
                marshal.lower(tuple(self.states[: key.batch])).compile().as_text())

    def window(self, seconds: float) -> dict:
        bytes0 = self.server.stats.get("field_bytes", 0)
        record = super().window(seconds)
        record["counts"]["field_bytes"] = self.server.stats.get("field_bytes", 0) - bytes0
        return record

    def release(self) -> None:
        self.final = self.states
        self.states = self.server = None

    def check(self) -> dict:
        ref = jax.jit(lambda block, n: reference_fields.forecast(block, n, self.coeffs))
        err = 0.0
        for j, f in enumerate(self.names):
            want = ref({f: jnp.stack(self._initial(j))}, self.timesteps)[f]
            got = jnp.stack([m.pop(f) for m in self.final])
            errs = np.asarray(_member_rel_errs(got, want))
            del got, want
            err = max(err, float(errs.max()) if np.isfinite(errs).all() else float("inf"))
        return {"member_rel_err": (err, self.limits["member_rel_err"])}


def control(config, traffic):
    """The control: the plain reference computed in bfloat16, put in the
    place of the server's batched step."""
    coeffs = dict(zip(config["fields"], config["coeffs"]))

    def wrap(batched_step):
        def step(fields):
            return reference_fields.hdiff_fields(fields, coeffs, jnp.bfloat16)
        return step

    return wrap
