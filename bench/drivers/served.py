"""Closed-loop ensemble serving, as an ensemble driver runs it.

Every timestep the client submits one hdiff step for each of the
configuration's ``members`` to a ``ForecastServer(backend="pallas")``,
drains the server batch by batch, and feeds each member's result back as
that member's next state. Members start from one seeded base field plus a
seeded perturbation each (``perturbation``, relative size). A request's
latency runs from its submit to the end of the server step that made its
result ready, both on the benchmark's clock.

Traffic parameters: ``perturbation``, ``trace_seconds`` and ``limits``.

The check: every member's state at the end, against its own reference
trajectory over the same number of timesteps from its initial state.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, fields, reference


class Driver:
    def __init__(self, config, traffic, seed, timed_path=None):
        from repro.ir import hdiff_program
        from repro.serve import ForecastServer
        from repro.serve.cache import CompileCache

        self.coeff = config["coeff"]
        self.members = config["members"]
        self.limits = traffic["limits"]
        self.prog = hdiff_program(self.coeff, limit=config["limit"])
        shape = (config["depth"], config["rows"], config["cols"])
        kwargs = {}
        if timed_path is not None:
            build = CompileCache().builder
            kwargs["cache"] = CompileCache(
                builder=lambda p, key, **kw: timed_path(build(p, key, **kw)))
        self.server = ForecastServer(backend="pallas", **kwargs)
        init = fields.ensemble(seed, self.members, shape, traffic["perturbation"])
        self.init_host = np.asarray(init)
        self.states = [init[i] for i in range(self.members)]
        del init
        self.timesteps = 0
        self.latencies: list[float] = []
        self.failed = 0
        self.programs: list[str] = []

    def _timestep(self) -> None:
        server = self.server
        submitted = {}
        with jax.profiler.TraceAnnotation("bench.submit"):
            for s in self.states:
                t = time.perf_counter()
                submitted[server.submit(self.prog, s)] = t
        results = {}
        with jax.profiler.TraceAnnotation("bench.drain"):
            while server.step():
                t = time.perf_counter()
                for req in server.completed:
                    self.latencies.append(t - submitted[req.rid])
                    if req.failed:
                        self.failed += 1
                    results[req.rid] = req.result
                # The client takes its results; the server would keep every
                # retired request (and its device arrays) otherwise.
                server.completed.clear()
        self.states = [results[rid] for rid in submitted]
        self.timesteps += 1

    def warm(self) -> None:
        for _ in range(2):
            self._timestep()
        for key in self.server.cache.keys():
            batched = {self.prog.inputs[0]: jnp.stack(self.states[: key.batch])}
            self.programs.append(
                self.server.cache.lookup(key).fn.lower(batched).compile().as_text())

    def window(self, seconds: float) -> dict:
        steps0, batches0, members0 = (self.timesteps, self.server.stats["batches"],
                                      self.server.stats["members"])
        self.latencies, self.failed = [], 0
        t0 = time.perf_counter()
        while True:
            self._timestep()
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        members = self.server.stats["members"] - members0
        return {
            "window_s": window_s,
            "attempted": len(self.latencies),
            "failed": self.failed,
            "latencies_s": self.latencies,
            "counts": {"timesteps": self.timesteps - steps0, "members": members,
                       "batches": self.server.stats["batches"] - batches0},
        }

    def release(self) -> None:
        self.final = np.stack([np.asarray(s) for s in self.states])
        self.states = self.server = None

    def check(self) -> dict:
        ref = jax.jit(lambda a, n: reference.forecast(a, n, self.coeff))
        want = np.asarray(ref(self.init_host, self.timesteps))
        err = max(compare.rel_err(got, w) for got, w in zip(self.final, want))
        return {"member_rel_err": (err, self.limits["member_rel_err"])}


def control(config, traffic):
    """The control: the plain reference computed in bfloat16, put in the
    place of the server's batched step."""
    coeff = config["coeff"]

    def wrap(batched_step):
        def step(fields):
            (x,) = fields.values()
            return reference.hdiff(x.astype(jnp.bfloat16), coeff).astype(x.dtype)
        return step

    return wrap
